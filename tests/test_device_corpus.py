"""memory/device_corpus.py: the one scan + top-k behind every search entry
point, and the layout and k rules at their block edges.

The rule tables below are written out by hand from what the store and the
engine computed before the rules moved here (VectorStore._capacity, _sharded,
_k_static, warm_fused's loop; TpuEngine._corpus_sharded): a change to a rule
has to change a row of a table."""

from collections import Counter

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from symbiont_tpu.config import EngineConfig, VectorStoreConfig
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.memory import VectorStore, device_corpus
from symbiont_tpu.utils.telemetry import metrics

requires_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")
DIM, ROWS, K = 32, 40, 7


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


# ------------------------------------------------------- one scan + top-k

def _rows_around(q, rng):
    """ROWS unit rows whose cosines against the unit query `q` are known and
    0.02 apart (ten times what bfloat16 moves a score), in shuffled order,
    with three pairs of equal rows: a tie is broken by row order."""
    cos = 0.9 - 0.02 * rng.permutation(ROWS)
    u = rng.standard_normal((ROWS, DIM))
    u -= np.outer(u @ q, q)
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    rows = cos[:, None] * q + np.sqrt(1 - cos[:, None] ** 2) * u
    best = np.argsort(-cos)
    for a, b in ((best[0], best[1]), (best[3], best[4]), (best[5], best[6])):
        rows[max(a, b)] = rows[min(a, b)]
    return rows.astype(np.float32)


@pytest.mark.parametrize("devices", [1, pytest.param(4, marks=requires_4)])
@pytest.mark.parametrize("entry", ["two_hop", "fused"])
def test_each_entry_point_reaches_scan_topk_and_ranks_like_float32(
        monkeypatch, entry, devices):
    mesh = _mesh(devices) if devices > 1 else None
    eng = TpuEngine(EngineConfig(embedding_dim=DIM, length_buckets=[8],
                                 batch_buckets=[4], max_batch=4,
                                 dtype="float32"), mesh=mesh)
    text = "what the query asks"
    q = eng.embed_query(text).astype(np.float64)
    q /= np.linalg.norm(q)
    rows = _rows_around(q, np.random.default_rng(3))
    store = VectorStore(VectorStoreConfig(dim=DIM, data_dir="",
                                          shard_capacity=16), mesh=mesh)
    store.upsert_rows([f"r{i}" for i in range(ROWS)], rows,
                      [{} for _ in range(ROWS)])

    traced = []
    real = device_corpus.scan_topk

    def counting(corpus, query, n_valid, k, mesh=None):
        traced.append((corpus.shape, k, mesh))
        return real(corpus, query, n_valid, k, mesh)

    monkeypatch.setattr(device_corpus, "scan_topk", counting)
    hits = (store.search(q.astype(np.float32), K) if entry == "two_hop"
            else store.search_fused(eng, text, K))
    # one trace of the one program, at the store's capacity and k bucket, on
    # the mesh the rows were placed over
    assert traced == [((48, DIM), 8, mesh)]
    assert device_corpus.mesh_of(store._device_corpus) == mesh

    unit = rows / np.linalg.norm(rows, axis=1, keepdims=True)
    scores = unit @ q.astype(np.float32)
    want = np.argsort(-scores, kind="stable")[:K]
    assert [h.id for h in hits] == [f"r{i}" for i in want]
    assert [h.score for h in hits] == pytest.approx(scores[want], abs=5e-3)


@pytest.mark.parametrize("devices", [1, pytest.param(4, marks=requires_4)])
def test_place_puts_rows_where_is_sharded_says_and_mesh_of_reads_it_back(
        devices):
    mesh = _mesh(devices) if devices > 1 else None
    placed = device_corpus.place(np.zeros((16, 8), np.float32), mesh)
    assert placed.dtype == device_corpus.ROWS_DTYPE
    assert len(placed.sharding.device_set) == devices
    assert device_corpus.mesh_of(placed) == mesh
    # 18 rows do not divide over 4: not sharded, and read back as such
    odd = device_corpus.place(np.zeros((18, 8), np.float32), mesh)
    assert device_corpus.mesh_of(odd) is None


# ------------------------------------------------------- the exact top-k

BLOCK = 64  # a narrow block, so that a short vector takes the blocked form


def _topk_paths():
    """`corpus.topk{path}` as a Counter of path → traced calls so far."""
    counters = metrics.snapshot()["counters"]
    return Counter({path: int(counters.get(f'corpus.topk{{path="{path}"}}', 0))
                    for path in ("blocked", "direct")})


def _bf16_steps(rng, n):
    """Scores as the scan leaves them: float32 holding bfloat16 values, a few
    hundred distinct ones over n rows, so nearly every score is a tie."""
    import jax.numpy as jnp

    x = rng.uniform(0.55, 0.75, n).astype(np.float32)
    return np.array(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))


def _scores(case, n, k, rng):
    if case == "bf16_ties":
        return _bf16_steps(rng, n)
    if case == "tie_across_a_block_edge_at_the_kth_place":
        # k - 2 rows alone at the top, in blocks 20 and up, then FIVE equal
        # rows for the last two places: on both sides of a block edge, in a
        # later block, and in block 20 beside a top row (a block chosen
        # first, whose row must still come after those of blocks 6 and 7):
        # the two at the lowest positions are the answer
        x = _bf16_steps(rng, n)
        x[BLOCK * np.arange(20, 20 + k - 2) + 3] = 0.90625
        x[[7 * BLOCK - 1, 7 * BLOCK, 7 * BLOCK + 1, 9 * BLOCK + 5,
           20 * BLOCK + 10]] = 0.8125
        return x
    if case == "all_equal":
        return np.full(n, 0.7109375, np.float32)
    if case == "winners_in_one_block":
        x = _bf16_steps(rng, n)
        x[3 * BLOCK + 2:3 * BLOCK + 2 + 2 * k] = 0.875
        return x
    if case == "inf_tail":  # what `n_valid` leaves past the stored rows
        x = _bf16_steps(rng, n)
        x[int(0.6 * n) + 3:] = -np.inf
        return x
    if case == "fewer_than_k_finite":
        x = np.full(n, -np.inf, np.float32)
        x[:5] = [0.5, 0.75, 0.5, 0.25, 0.75]
        return x
    if case == "signed_zeros":  # lax.top_k puts +0.0 before -0.0
        x = np.where(rng.random(n) < 0.5, 0.0, -0.0).astype(np.float32)
        x[rng.choice(n, k // 2, replace=False)] = 0.5
        return x
    raise ValueError(case)


@pytest.mark.parametrize("k", [8, 16])
@pytest.mark.parametrize("case, n, block", [
    ("bf16_ties", 16 * 4 * BLOCK, BLOCK),
    ("tie_across_a_block_edge_at_the_kth_place", 16 * 4 * BLOCK, BLOCK),
    ("all_equal", 16 * 4 * BLOCK, BLOCK),
    ("winners_in_one_block", 16 * 4 * BLOCK, BLOCK),
    ("inf_tail", 16 * 4 * BLOCK, BLOCK),
    ("fewer_than_k_finite", 16 * 4 * BLOCK, BLOCK),
    ("signed_zeros", 16 * 4 * BLOCK, BLOCK),
    # a length the block does not divide: the helper pads with -inf
    ("bf16_ties", 16 * 4 * BLOCK + 37, BLOCK),
    ("inf_tail", 16 * 4 * BLOCK + 37, BLOCK),
    # the module's own block width, at the shortest vector k = 16 takes it for
    ("bf16_ties", 16 * 4 * device_corpus.TOPK_BLOCK, device_corpus.TOPK_BLOCK),
    ("inf_tail", 16 * 4 * device_corpus.TOPK_BLOCK + 1_024,
     device_corpus.TOPK_BLOCK),
])
def test_exact_topk_is_lax_top_k_element_for_element(case, n, block, k):
    import jax.numpy as jnp

    scores = jnp.asarray(_scores(case, n, k, np.random.default_rng(n + k)))
    before = _topk_paths()
    vals, idx = jax.jit(
        lambda s: device_corpus._exact_topk(s, k, block))(scores)
    assert _topk_paths() - before == {"blocked": 1}
    want_vals, want_idx = jax.lax.top_k(scores, k)
    # array_equal on the bits: -0.0 is not +0.0 here
    assert np.array_equal(np.asarray(vals).view(np.uint32),
                          np.asarray(want_vals).view(np.uint32))
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))


@pytest.mark.parametrize("devices", [1, pytest.param(4, marks=requires_4)])
@pytest.mark.parametrize("k", [8, 16])
def test_scan_topk_over_long_shards_is_the_whole_top_k(monkeypatch, devices,
                                                       k):
    """Shards long enough for the blocked form (the module's own block
    width), rows drawn from 300 distinct ones so that every score ties many
    times over: the same rows in the same order as `lax.top_k` of the one
    score vector."""
    import jax.numpy as jnp

    mesh = _mesh(devices) if devices > 1 else None
    rng = np.random.default_rng(k)
    cap = 4 * devices * k * device_corpus.TOPK_BLOCK
    distinct = rng.standard_normal((300, 8)).astype(np.float32)
    distinct /= np.linalg.norm(distinct, axis=1, keepdims=True)
    corpus = device_corpus.place(distinct[rng.integers(0, 300, cap)], mesh)
    q, n_valid = jnp.asarray(distinct[0]), cap - 1_000

    def run():
        return jax.jit(lambda c, q, n: device_corpus.scan_topk(
            c, q, n, k, mesh))(corpus, q, n_valid)

    before = _topk_paths()
    got = run()
    assert _topk_paths() - before == {"blocked": 1}
    monkeypatch.setattr(device_corpus, "_exact_topk",
                        lambda s, k: jax.lax.top_k(s, k))
    want = run()
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.isfinite(np.asarray(got[0])).all()
    assert len(set(np.asarray(got[0]))) < k  # ties inside the answer


def test_a_small_store_takes_lax_top_k_and_lowers_to_the_same_text(
        monkeypatch):
    """The 48-row store of this file: `corpus.topk{path}` reads `direct`, and
    its two-hop and fused programs lower to the text they have with
    `jax.lax.top_k` written in place of the helper."""
    import jax.numpy as jnp

    eng = TpuEngine(EngineConfig(embedding_dim=DIM, length_buckets=[8],
                                 batch_buckets=[4], max_batch=4,
                                 dtype="float32"))
    eng._time_first_call = lambda jitted, sig: jitted  # the raw jit
    corpus = device_corpus.place(np.zeros((48, DIM), np.float32))

    def texts():
        two_hop = jax.jit(lambda c, q, n: device_corpus.scan_topk(
            c, q, n, 8)).lower(corpus, jnp.zeros(DIM), 40).as_text()
        fused = eng._get_executable("qsearch", 8, 48, 8, None).lower(
            eng.params, jnp.zeros((1, 8), eng._ids_dtype),
            jnp.ones((1, 8), jnp.int32), corpus, 40).as_text()
        return two_hop, fused

    before = _topk_paths()
    with_helper = texts()
    assert _topk_paths() - before == {"direct": 2}
    monkeypatch.setattr(device_corpus, "_exact_topk",
                        lambda s, k: jax.lax.top_k(s, k))
    eng._exec_cache.clear()
    assert texts() == with_helper
    assert "top_k" in with_helper[0] and "top_k" in with_helper[1]


# ----------------------------------------------------------- layout rules

@pytest.mark.parametrize("n, block, data, cap", [
    (0, 16, 1, 16), (15, 16, 1, 16), (16, 16, 1, 16), (17, 16, 1, 32),
    (0, 16, 4, 16), (15, 16, 4, 16), (16, 16, 4, 16), (17, 16, 4, 32),
    # a block the axis does not divide is rounded up to it
    (0, 6, 4, 8), (5, 6, 4, 8), (6, 6, 4, 8), (7, 6, 4, 12),
    (1_450_000, 65_536, 1, 1_507_328), (1_450_000, 65_536, 4, 1_507_328),
])
def test_capacity_at_block_edges(n, block, data, cap):
    mesh = _mesh(data) if data > 1 else None
    assert device_corpus.capacity(n, block, mesh) == cap
    if data == 1:  # a one-device mesh is no mesh
        assert device_corpus.capacity(n, block, _mesh(1)) == cap


@pytest.mark.parametrize("data, cap, sharded", [
    (None, 16, False), (1, 16, False), (4, 16, True), (4, 32, True),
    (4, 18, False),
])
def test_is_sharded(data, cap, sharded):
    mesh = None if data is None else _mesh(data)
    assert device_corpus.is_sharded(mesh, cap) is sharded


def test_is_sharded_reads_the_data_axis_only():
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4),
                ("data", "tensor"))
    assert not device_corpus.is_sharded(mesh, 16)
    assert device_corpus.capacity(17, 6, mesh) == 18


# --------------------------------------------------------------- k policy

@pytest.mark.parametrize("top_k, n, cap, k", [
    (1, 100, 128, 8), (8, 100, 128, 8), (9, 100, 128, 16),
    (16, 100, 128, 16), (64, 100, 128, 64),
    # fewer rows than top_k: the bucket that holds the rows there are
    (64, 40, 128, 64), (64, 10, 128, 16), (16, 9, 128, 16), (9, 8, 128, 8),
    # an empty store, and k never past the capacity
    (1, 0, 16, 8), (9, 0, 16, 8), (64, 0, 16, 8),
    (64, 100, 16, 16), (9, 100, 8, 8), (1, 5, 4, 4),
    # rows at a capacity block's edges
    (16, 15, 16, 16), (16, 16, 16, 16), (16, 17, 32, 16), (64, 17, 32, 32),
])
def test_k_bucket(top_k, n, cap, k):
    assert device_corpus.k_bucket(top_k, n, cap) == k


@pytest.mark.parametrize("warm_top_k, n, cap, ks", [
    (16, 0, 64, [8, 16]), (16, 1_450_000, 1_507_328, [8, 16]),
    (8, 0, 64, [8]), (1, 0, 64, [8]), (9, 0, 64, [8, 16]),
    (64, 0, 64, [8, 16, 32, 64]), (64, 5, 16, [8, 16]),
    (16, 100, 8, [8]),
])
def test_warm_k_buckets_hold_every_bucket_a_routed_query_can_get(
        warm_top_k, n, cap, ks):
    assert device_corpus.warm_k_buckets(warm_top_k, n, cap) == ks
    # what is routed fused (top_k ≤ warm_top_k) is what was warmed, whatever
    # the store holds by then within this capacity
    for top_k in range(1, warm_top_k + 1):
        for rows in (n, cap):
            assert device_corpus.k_bucket(top_k, rows, cap) in ks
