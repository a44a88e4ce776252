"""memory/device_corpus.py: the one scan + top-k behind every search entry
point, and the layout and k rules at their block edges.

The rule tables below are written out by hand from what the store and the
engine computed before the rules moved here (VectorStore._capacity, _sharded,
_k_static, warm_fused's loop; TpuEngine._corpus_sharded): a change to a rule
has to change a row of a table."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from symbiont_tpu.config import EngineConfig, VectorStoreConfig
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.memory import VectorStore, device_corpus

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
