"""The device copy of the corpus: its format, its layout, its one program.

Everything that has to agree about the [capacity, dim] array the store keeps
on the device is decided here and read from here — by the store
(memory/vector_store.py: upload, two-hop search, warm-up), by the engine's
fused `qsearch` program (engine/engine.py) and, through `scan_topk`'s mesh
arm, by a corpus whose rows are sharded over chips:

- the format: rows at rest in `ROWS_DTYPE`, scored in `SCAN_DTYPE`;
- the layout: `capacity` (static shapes across growth), `is_sharded` (rows
  over the mesh's `AXIS` axis or on one device), `place` (host array → device
  under that rule) and `mesh_of` (the rule read back off a placed array);
- the program: `scan_topk`, the cosine scan of every row against one query
  and the exact top-k, traced into whatever jit calls it. "The exact top-k"
  is what `jax.lax.top_k` returns over the whole score vector, values and
  indices, ties broken by row order: `_exact_topk` selects it without
  sorting the vector where the vector is long (per-block maxes pick the k
  blocks that can hold a winner; only their rows are sorted), and
  `corpus.topk{path}` counts which form each traced program took;
- the k policy: `k_bucket` (the static k a query is served with) beside
  `warm_k_buckets` (the ks a warm-up compiles): what is routed fused is what
  was warmed.

Imports jax and the mesh's sharding helpers only (inside the functions that
touch the device, as the store does: the rules above them are plain Python):
the store and the engine call down into this module, never the other way.
"""

from __future__ import annotations

ROWS_DTYPE = "float32"   # unit rows at rest on the device
SCAN_DTYPE = "bfloat16"  # corpus and query cast to this per query (MXU)
AXIS = "data"            # the mesh axis rows shard over
K_FLOOR = 8              # smallest k bucket
TOPK_BLOCK = 1024        # scores per block of the blocked top-k
TOPK_MIN_BLOCKS_PER_K = 4  # blocked from TOPK_MIN_BLOCKS_PER_K * k blocks up


# ---------------------------------------------------------------- layout

def capacity(n: int, shard_capacity: int, mesh=None) -> int:
    """Static capacity: next multiple of shard_capacity (and of the data
    axis size when sharded) — keeps device shapes stable across growth."""
    cap = max(shard_capacity, -(-n // shard_capacity) * shard_capacity)
    if mesh is not None:
        nd = mesh.shape.get(AXIS, 1)
        cap = -(-cap // nd) * nd
    return cap


def is_sharded(mesh, cap: int) -> bool:
    """Whether a [cap, dim] corpus lives row-sharded over the mesh's data
    axis (`capacity` rounds to the axis size, so this holds whenever a
    multi-device mesh was threaded in)."""
    return (mesh is not None and mesh.shape.get(AXIS, 1) > 1
            and cap % mesh.shape[AXIS] == 0)


def place(padded, mesh=None):
    """A padded host [cap, dim] array → the device, in `ROWS_DTYPE`:
    row-sharded where `is_sharded`, on the default device otherwise."""
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(padded, ROWS_DTYPE)
    if is_sharded(mesh, rows.shape[0]):
        from symbiont_tpu.parallel.sharding import batch_sharding

        return jax.device_put(rows, batch_sharding(mesh, AXIS))
    return rows


def mesh_of(corpus):
    """The mesh a placed corpus's rows are sharded over, or None: `place`'s
    decision read back off the array, so a caller handed the array needs no
    rule (and no mesh) of its own."""
    from jax.sharding import NamedSharding

    s = getattr(corpus, "sharding", None)
    if (isinstance(s, NamedSharding) and len(s.spec) and s.spec[0] == AXIS
            and is_sharded(s.mesh, corpus.shape[0])):
        return s.mesh
    return None


# --------------------------------------------------------------- program

def _masked_scores(rows, q, n_valid, base=None):
    """Cosine of every row of one block against `q` (rows and query unit
    vectors: a dot product), `SCAN_DTYPE` on the MXU, f32 scores; rows past
    the `n_valid` stored ones score -inf. `base` is the block's first global
    row (a shard's offset; None = 0). Returns (scores, global row ids)."""
    import jax.numpy as jnp

    scores = (rows.astype(SCAN_DTYPE) @ q).astype(jnp.float32)
    ids = jnp.arange(rows.shape[0])
    if base is not None:
        ids = base + ids
    return jnp.where(ids < n_valid, scores, -jnp.inf), ids


def _exact_topk(scores, k: int, block: int = TOPK_BLOCK):
    """`jax.lax.top_k(scores, k)` of a score vector [n], element for element
    (values and indices; equal scores in position order), without sorting
    the vector where it is long: n >= TOPK_MIN_BLOCKS_PER_K * k * block, a
    static choice on the shape and k, counted in `corpus.topk{path}` once
    per traced call. The blocked form takes each block's max in one pass,
    top-ks the maxes for the k blocks that can hold a top-k row, and top-ks
    those blocks' k * block scores.

    Why no row is lost, ties included: `lax.top_k` orders by (score
    descending, position ascending). Were a top-k row e in a block b that
    was not chosen, k blocks would come before b by (max descending, block
    ascending), each holding a row that scores >= b's max >= e and, where
    equal, lies in a lower block, so at a lower position: k rows ahead of
    e. The chosen blocks are gathered in ascending order, so a candidate's
    position orders as its row does and the last top-k breaks ties as the
    whole one would."""
    import jax
    import jax.numpy as jnp

    from symbiont_tpu.utils.telemetry import metrics

    n = scores.shape[0]
    blocked = n >= TOPK_MIN_BLOCKS_PER_K * k * block
    metrics.inc("corpus.topk",
                labels={"path": "blocked" if blocked else "direct"})
    if not blocked:
        return jax.lax.top_k(scores, k)
    if n % block:
        scores = jnp.pad(scores, (0, -n % block), constant_values=-jnp.inf)
    tiles = scores.reshape(-1, block)
    _, blocks = jax.lax.top_k(tiles.max(axis=1), k)
    blocks = jnp.sort(blocks)
    vals, pos = jax.lax.top_k(tiles[blocks].reshape(-1), k)
    return vals, blocks[pos // block] * block + pos % block


def scan_topk(corpus, q, n_valid, k: int, mesh=None):
    """Exact cosine top-k of `q` [dim] over the `n_valid` stored rows of
    `corpus` [cap, dim]: (scores[k], row indices[k]), best first, as
    `jax.lax.top_k` over all cap scores orders them (`_exact_topk`: equal
    scores, common at bfloat16 spacing, come back in row order). Trace-time
    only (call inside jit), under the scopes `scan` and `topk`.

    With a `mesh` (`is_sharded`: rows over its data axis) each shard scores
    its own rows and keeps a local top-k with GLOBAL row indices, and the
    merge top-ks the [n_shards x k] candidates: only k candidates per shard
    cross the interconnect, never the score vector. The order is the
    one-device order: `lax.top_k` breaks ties by position and shards
    concatenate in global-row order, and a shard's own top-k is the same
    `_exact_topk` (pinned in tests)."""
    import jax

    q = q.astype(SCAN_DTYPE)
    if mesh is None:
        with jax.named_scope("scan"):
            scores, _ = _masked_scores(corpus, q, n_valid)
        with jax.named_scope("topk"):
            return _exact_topk(scores, k)

    from jax import shard_map

    from symbiont_tpu.parallel.sharding import P

    cap, nd = corpus.shape[0], mesh.shape[AXIS]
    if cap % nd:
        raise ValueError(f"corpus capacity {cap} not divisible by "
                         f"{AXIS}={nd}")
    rows = cap // nd

    def local(c, q, nv):
        with jax.named_scope("scan"):
            base = jax.lax.axis_index(AXIS) * rows
            scores, gidx = _masked_scores(c, q, nv, base)
        with jax.named_scope("topk"):
            s, li = _exact_topk(scores, min(k, rows))
            return s, gidx[li]

    cand_s, cand_i = shard_map(
        local, mesh=mesh,
        in_specs=(P(AXIS, None), P(None), P()),
        out_specs=(P(AXIS), P(AXIS)))(corpus, q, n_valid)
    with jax.named_scope("topk"):
        merged_s, pos = jax.lax.top_k(cand_s, k)
        return merged_s, cand_i[pos]


# -------------------------------------------------------------- k policy

def k_bucket(top_k: int, n: int, cap: int) -> int:
    """Static k bucket (next power of two ≥ k, ≤ cap) bounds executables.

    Floored at `K_FLOOR` so every interactive query with top_k ≤ 8 (the
    common range) shares ONE executable per (capacity, length-bucket) —
    without the floor, each distinct top_k minted a fresh XLA compile, which
    on a cold engine blows the fused-search probe timeout per k value. Extra
    rows cost nothing (top-8 vs top-2 is the same matmul + tiny sort) and
    surplus entries are trimmed/-inf-filtered by the caller."""
    k = K_FLOOR
    while k < min(top_k, n):
        k *= 2
    return min(k, cap)


def warm_k_buckets(warm_top_k: int, n: int, cap: int) -> list:
    """The k buckets a warm-up compiles: every one `k_bucket` can hand a
    query of top_k ≤ warm_top_k (VectorStoreConfig.warm_top_k; the gateways
    route only top_k ≤ ApiConfig.fused_search_max_top_k to the fused path,
    and config.py holds the two knobs together) — also for a store that
    holds fewer rows than k yet."""
    top_ks = [K_FLOOR]
    while top_ks[-1] < warm_top_k:
        top_ks.append(top_ks[-1] * 2)
    return sorted({k_bucket(k, max(n, k), cap) for k in top_ks})
