"""Ring attention: sequence-parallel exact attention over the mesh.

Long-context is a first-class capability here even though the reference
hard-truncates everything to one model's max length (reference:
services/preprocessing_service/src/embedding_generator.rs:93-99; SURVEY.md
§5.7). Design follows blockwise ring attention: the sequence is sharded over a
mesh axis, each device streams the K/V blocks of its peers around the ring with
`ppermute` while maintaining a numerically-stable streaming softmax
(flash-attention style running max/denominator), so attention over a sequence
of length S costs O(S/n) memory per device and the K/V transfer rides ICI.

Usage: call `ring_attention` *inside* `shard_map` with the sequence dim sharded
on `axis_name` (helper `ring_attention_sharded` wires this). Exactness is
tested against full attention on the 8-virtual-device CPU mesh
(tests/test_parallel.py).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size, pcast
from jax.sharding import Mesh, PartitionSpec as P


def ring_attention(
    q: jax.Array,  # [B, S_loc, NH, D] — local query block
    k: jax.Array,  # [B, S_loc, KVH, D] — local key block (KVH divides NH: GQA)
    v: jax.Array,  # [B, S_loc, KVH, D]
    axis_name: str,
    causal: bool = False,
) -> jax.Array:
    """Exact attention over the full (sharded) sequence; call inside shard_map.

    GQA-aware: K/V may carry fewer heads than Q (KVH | NH). The compact KVH
    blocks are what rotates over the ring — expanding to NH happens only at
    the local score computation, so grouped-query models don't pay
    NH/KVH × the necessary ICI bandwidth."""
    n_dev = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, NH, D = q.shape
    KVH = k.shape[2]
    if NH % KVH != 0:
        raise ValueError(f"query heads {NH} not divisible by KV heads {KVH}")
    rep = NH // KVH

    def expand(blk):  # [B, S, KVH, D] → [B, S, NH, D] (local, post-rotation)
        return jnp.repeat(blk, rep, axis=2) if rep > 1 else blk

    scale = 1.0 / math.sqrt(D)

    q32 = q.astype(jnp.float32)
    q_pos = idx * S + jnp.arange(S)  # global positions of local queries

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry
        # after s hops, we hold the block originally owned by (idx - s) mod n
        src = (idx - s) % n_dev
        kv_pos = src * S + jnp.arange(S)

        scores = jnp.einsum("bqhd,bkhd->bhqk", q32,
                            expand(k_blk).astype(jnp.float32)) * scale
        if causal:
            mask = q_pos[None, None, :, None] >= kv_pos[None, None, None, :]
            scores = jnp.where(mask, scores, -jnp.inf)

        blk_max = jnp.max(scores, axis=-1)  # [B, NH, S]
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked blocks (all -inf): exp(-inf - finite) = 0 is fine,
        # but new_m could stay -inf early under causal; keep it, corrections
        # below use where() to avoid NaN.
        correction = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - new_m))
        probs = jnp.exp(scores - jnp.where(jnp.isneginf(new_m), 0.0, new_m)[..., None])
        probs = jnp.where(jnp.isneginf(scores), 0.0, probs)

        l = l * correction + probs.sum(axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", probs, expand(v_blk).astype(jnp.float32))

        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, new_m, l, acc

    # pcast to 'varying': mark the fresh accumulators as device-varying over
    # the ring axis so the fori_loop carry type is stable under shard_map's
    # varying-axis tracking.
    def vary(x):
        return pcast(x, axis_name, to="varying")

    m0 = vary(jnp.full((B, NH, S), -jnp.inf, jnp.float32))
    l0 = vary(jnp.zeros((B, NH, S), jnp.float32))
    acc0 = vary(jnp.zeros((B, NH, S, D), jnp.float32))
    *_, m, l, acc = jax.lax.fori_loop(0, n_dev, step, (k, v, m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, NH, S, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, S, NH, D]


def ring_attention_sharded(
    q: jax.Array,  # [B, S, NH, D] — full sequence (host view)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
) -> jax.Array:
    """Convenience wrapper: shard the sequence dim over `axis_name` and run
    ring attention; returns the full [B, S, NH, D] result."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
