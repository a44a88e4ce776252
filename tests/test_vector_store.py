"""Vector store tests: ensure/upsert/search parity, durability, sharding."""

import json
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from symbiont_tpu.config import VectorStoreConfig
from symbiont_tpu.memory import VectorStore
from symbiont_tpu.utils.telemetry import metrics


def _cfg(tmp_path=None, **kw):
    kw.setdefault("dim", 8)
    kw.setdefault("shard_capacity", 16)
    return VectorStoreConfig(data_dir=str(tmp_path) if tmp_path else "", **kw)


def _unit(v):
    v = np.asarray(v, np.float32)
    return v / np.linalg.norm(v)


def test_upsert_and_search_exact_cosine_order():
    store = VectorStore(_cfg())
    store.ensure_collection()
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(20, 8)).astype(np.float32)
    store.upsert([(f"p{i}", vecs[i], {"sentence_text": f"s{i}", "sentence_order": i})
                  for i in range(20)])
    q = vecs[7]
    hits = store.search(q, top_k=5)
    assert hits[0].id == "p7"
    assert hits[0].score == pytest.approx(1.0, abs=2e-2)  # bf16 matmul
    # scores descending, exact order matches numpy cosine
    cos = (vecs @ _unit(q)) / np.linalg.norm(vecs, axis=1)
    expect = [f"p{i}" for i in np.argsort(-cos)[:5]]
    assert [h.id for h in hits] == expect
    assert hits[0].payload["sentence_text"] == "s7"


def test_top_k_larger_than_corpus():
    store = VectorStore(_cfg())
    store.upsert([("a", np.ones(8), {}), ("b", -np.ones(8), {})])
    hits = store.search(np.ones(8), top_k=10)
    assert [h.id for h in hits] == ["a", "b"]


def test_upsert_overwrites_existing_id():
    store = VectorStore(_cfg())
    store.upsert([("x", _unit(np.arange(1, 9)), {"v": 1})])
    store.upsert([("x", -_unit(np.arange(1, 9)), {"v": 2})])
    assert store.count() == 1
    hits = store.search(-np.arange(1, 9, dtype=np.float32), top_k=1)
    assert hits[0].payload["v"] == 2
    assert hits[0].score > 0.9


def test_dim_mismatch_raises():
    store = VectorStore(_cfg())
    with pytest.raises(ValueError, match="dim"):
        store.upsert([("bad", np.ones(5), {})])
    store.upsert([("ok", np.ones(8), {})])
    with pytest.raises(ValueError):
        store.ensure_collection(dim=16)  # existing data at dim 8
    with pytest.raises(ValueError, match="dim"):
        store.search(np.ones(3), top_k=1)


def test_empty_store_and_zero_k():
    store = VectorStore(_cfg())
    assert store.search(np.ones(8), top_k=3) == []
    store.upsert([("a", np.ones(8), {})])
    assert store.search(np.ones(8), top_k=0) == []


def test_growth_across_capacity_blocks():
    store = VectorStore(_cfg())  # shard_capacity 16
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(40, 8)).astype(np.float32)  # 3 blocks
    for i in range(40):
        store.upsert([(f"p{i}", vecs[i], {})])
    hits = store.search(vecs[33], top_k=1)
    assert hits[0].id == "p33"


def test_wal_durability_and_reload(tmp_path):
    store = VectorStore(_cfg(tmp_path))
    rng = np.random.default_rng(2)
    vecs = rng.normal(size=(5, 8)).astype(np.float32)
    store.upsert([(f"p{i}", vecs[i], {"i": i}) for i in range(5)])
    # simulate crash: new store instance on same dir, no compact
    store2 = VectorStore(_cfg(tmp_path))
    assert store2.count() == 5
    assert store2.search(vecs[3], top_k=1)[0].id == "p3"


def test_compact_then_reload_with_wal_tail(tmp_path):
    store = VectorStore(_cfg(tmp_path))
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(6, 8)).astype(np.float32)
    store.upsert([(f"p{i}", vecs[i], {}) for i in range(4)])
    store.compact()
    store.upsert([(f"p{i}", vecs[i], {}) for i in range(4, 6)])  # post-snapshot WAL
    store3 = VectorStore(_cfg(tmp_path))
    assert store3.count() == 6
    assert store3.search(vecs[5], top_k=1)[0].id == "p5"


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_sharded_search_matches_unsharded():
    from symbiont_tpu.parallel import build_mesh

    rng = np.random.default_rng(4)
    vecs = rng.normal(size=(64, 8)).astype(np.float32)
    points = [(f"p{i}", vecs[i], {}) for i in range(64)]
    plain = VectorStore(_cfg())
    plain.upsert(points)
    sharded = VectorStore(_cfg(), mesh=build_mesh())
    sharded.upsert(points)
    q = rng.normal(size=8).astype(np.float32)
    h1 = [h.id for h in plain.search(q, top_k=8)]
    h2 = [h.id for h in sharded.search(q, top_k=8)]
    assert h1 == h2


def test_load_counts_skipped_corrupt_wal_lines(tmp_path, caplog):
    """A pre-r5 rollback skips r5 `vector_b64` WAL records as corrupt —
    silent data loss. The count is now surfaced: one warning with the
    number, and `last_load_skipped_lines` for programmatic checks
    (flush-before-rollback requirement documented in docs/DEPLOYMENT.md)."""
    import json as _json
    import logging

    store = VectorStore(_cfg(tmp_path))
    rng = np.random.default_rng(11)
    vecs = rng.normal(size=(3, 8)).astype(np.float32)
    store.upsert([(f"p{i}", vecs[i], {"i": i}) for i in range(3)])
    assert store.last_load_skipped_lines == 0
    wal = tmp_path / f"{store.config.collection}.wal.jsonl"
    with open(wal, "a", encoding="utf-8") as f:
        f.write("{not json at all\n")
        f.write(_json.dumps({"id": "q1", "unknown_format": [1, 2]}) + "\n")
    with caplog.at_level(logging.WARNING,
                         logger="symbiont_tpu.memory.vector_store"):
        store2 = VectorStore(_cfg(tmp_path))
    assert store2.count() == 3  # intact records still load
    assert store2.last_load_skipped_lines == 2
    assert any("skipped 2" in r.getMessage() for r in caplog.records)


def test_clean_load_reports_zero_skipped(tmp_path):
    store = VectorStore(_cfg(tmp_path))
    rng = np.random.default_rng(12)
    store.upsert([("a", rng.normal(size=8).astype(np.float32), {})])
    store2 = VectorStore(_cfg(tmp_path))
    assert store2.count() == 1
    assert store2.last_load_skipped_lines == 0


# ------------------------------------------------ host rows in capacity blocks
#
# The host copy is a list of [shard_capacity, dim] blocks written in place
# (module docstring, "Host rows"). These run at shard_capacity 8 so a handful
# of rows crosses edges; `_Model` is the plain reference: ids in order of first
# appearance, the last vector and payload of each.

CAP = 8


class _Model:
    def __init__(self):
        self.rows = {}

    def upsert(self, points):
        batch = np.asarray([vec for _, vec, _ in points], np.float32)
        unit = batch / np.linalg.norm(batch, axis=1, keepdims=True)
        for (pid, _, payload), vec in zip(points, unit):
            self.rows[pid] = (vec, dict(payload))

    def assert_same(self, store):
        assert store._ids == list(self.rows)
        assert store._payloads == [p for _, p in self.rows.values()]
        got = store._vectors
        assert got.dtype == np.float32 and got.shape == (len(self.rows), 8)
        if self.rows:
            np.testing.assert_array_equal(
                got, np.stack([v for v, _ in self.rows.values()]))
        assert len(store._blocks) == -(-len(self.rows) // CAP)
        assert all(b.shape == (CAP, 8) for b in store._blocks)


def _points(rng, names):
    return [(n, rng.normal(size=8).astype(np.float32), {"n": n, "v": i})
            for i, n in enumerate(names)]


def _names(a, b):
    return [f"p{i}" for i in range(a, b)]


# batches of ids, applied in order; a name twice means an overwrite
SCENARIOS = {
    "ends_before_edge": [_names(0, 5), _names(5, 7)],
    "ends_at_edge": [_names(0, 5), _names(5, 8)],
    "ends_past_edge": [_names(0, 5), _names(5, 11)],
    "starts_at_edge": [_names(0, 8), _names(8, 16), _names(16, 17)],
    "one_flush_spans_two_edges": [_names(0, 5), _names(5, 19)],
    "first_flush_spans_edges": [_names(0, 27)],
    "overwrite_in_earlier_block": [_names(0, 20), ["p2"]],
    "overwrite_beside_crossing_append": [_names(0, 6),
                                         ["p1"] + _names(6, 12) + ["p7"]],
    "duplicate_new_id_in_one_batch": [_names(0, 7),
                                      ["a", "b", "a", "c", "b"]],
    "duplicate_existing_id_in_one_batch": [_names(0, 10), ["p9", "p1", "p9"]],
}


def _play(store, batches, model=None, seed=7):
    model, rng = model or _Model(), np.random.default_rng(seed)
    for names in batches:
        points = _points(rng, names)
        assert store.upsert(points) == len(points)
        model.upsert(points)
    return model


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_blocks_hold_what_was_upserted(scenario):
    store = VectorStore(_cfg(shard_capacity=CAP))
    moved = metrics.get("vector_store.host_bytes_moved")
    model = _play(store, SCENARIOS[scenario])
    model.assert_same(store)
    assert store.count() == len(model.rows)
    assert metrics.get("vector_store.host_bytes_moved") == moved
    assert metrics.gauge_get("vector_store.host_blocks") == len(store._blocks)
    # every row is found where it lies, whichever block that is, and ranks
    # as it does in a store that holds the same rows in ONE block
    one = VectorStore(_cfg(shard_capacity=64))
    one.upsert([(pid, v, p) for pid, (v, p) in model.rows.items()])
    for pid, (vec, payload) in list(model.rows.items())[::3]:
        hits = store.search(vec, top_k=4)
        assert hits[0].id == pid and hits[0].payload == payload
        assert [h.id for h in hits] == [h.id for h in one.search(vec, 4)]


@pytest.mark.parametrize("upsert_rows", [False, True],
                         ids=["upsert", "upsert_rows"])
def test_append_moves_only_its_own_rows(upsert_rows):
    """The mechanism itself: across three blocks of appends nothing already
    stored is copied — the counter stays put and the first block is the same
    memory — through either entry point."""
    store = VectorStore(_cfg(shard_capacity=CAP))
    rng = np.random.default_rng(5)
    moved = metrics.get("vector_store.host_bytes_moved")
    first = None
    for start in range(0, 3 * CAP, 3):  # flushes of 3: edges fall mid-flush
        pts = _points(rng, _names(start, start + 3))
        if upsert_rows:
            store.upsert_rows([p[0] for p in pts],
                              np.stack([p[1] for p in pts]),
                              [p[2] for p in pts])
        else:
            store.upsert(pts)
        if first is None:
            first = store._blocks[0]
            row0 = first[0].copy()
        assert store._blocks[0] is first
        assert np.shares_memory(store._blocks[0], first)
    assert len(store._blocks) == 3
    np.testing.assert_array_equal(first[0], row0)
    assert metrics.get("vector_store.host_bytes_moved") == moved
    assert metrics.gauge_get("vector_store.host_blocks") == 3


def test_host_bytes_moved_counts_a_copied_block():
    """The counter is read off the blocks: an append that re-made a block
    (what one growing matrix did on every call) is counted, row for row."""
    class Recopying(VectorStore):
        def _append(self, vecs):
            if self._blocks:
                self._blocks[0] = self._blocks[0].copy()
            super()._append(vecs)

    store = Recopying(_cfg(shard_capacity=CAP))
    rng = np.random.default_rng(6)
    moved = metrics.get("vector_store.host_bytes_moved")
    store.upsert(_points(rng, _names(0, 5)))  # no block before it: 0 rows
    assert metrics.get("vector_store.host_bytes_moved") == moved
    store.upsert(_points(rng, _names(5, 11)))  # block 0 held 5 rows
    assert metrics.get("vector_store.host_bytes_moved") == moved + 5 * 8 * 4
    store.upsert(_points(rng, _names(11, 12)))  # and now all 8 of its rows
    assert metrics.get("vector_store.host_bytes_moved") == moved + 13 * 8 * 4


def _text_query(text, dim=8):
    return _unit(np.random.default_rng(zlib.crc32(text.encode()))
                 .normal(size=dim))


class _ScanEngine:
    """Stands in for the engine under search_fused: scans the device corpus
    the store hands it, with the query `_text_query` draws from the text."""

    def embed_and_search(self, text, corpus, n_valid, k):
        q = jnp.asarray(_text_query(text, corpus.shape[1]))
        scores = jnp.where(jnp.arange(corpus.shape[0]) < n_valid,
                           corpus @ q, -jnp.inf)
        s, i = jax.lax.top_k(scores, k)
        return np.asarray(s), np.asarray(i)


@pytest.mark.parametrize("path", ["search", "search_fused"])
def test_hits_identical_before_and_after_a_crossing(path):
    store = VectorStore(_cfg(shard_capacity=CAP))
    store.upsert(_points(np.random.default_rng(8), _names(0, 6)))
    q = _text_query("the query")

    def hits():
        got = (store.search(q, 3) if path == "search"
               else store.search_fused(_ScanEngine(), "the query", 3))
        return [(h.id, h.payload) for h in got], [h.score for h in got]

    before, scores_before = hits()
    assert store._device_corpus.shape[0] == CAP
    # the rows that cross the edge all point away from the query
    store.upsert([(f"far{i}", -q * (i + 1), {"far": i}) for i in range(5)])
    after, scores_after = hits()
    assert store._device_corpus.shape[0] == 2 * CAP
    assert after == before and len(after) == 3
    assert scores_after == pytest.approx(scores_before, abs=1e-3)


@pytest.mark.parametrize("via", ["wal_replay", "compact_load",
                                 "compact_then_wal_tail"])
@pytest.mark.parametrize("scenario", ["one_flush_spans_two_edges",
                                      "overwrite_beside_crossing_append",
                                      "duplicate_existing_id_in_one_batch",
                                      "ends_at_edge"])
def test_round_trip_gives_the_same_store(tmp_path, scenario, via):
    store = VectorStore(_cfg(tmp_path, shard_capacity=CAP))
    model = _Model()
    if via == "compact_then_wal_tail":
        _play(store, [["early0", "early1", "p3"]], model, seed=9)
        store.compact()
    _play(store, SCENARIOS[scenario], model)
    if via == "compact_load":
        store.compact()
        # the snapshot is the format benchmark/artefacts.py writes: one
        # [n, dim] f32 array any np.load reads
        snap = np.load(tmp_path / f"{store.config.collection}.vectors.npy")
        assert snap.dtype == np.float32 and snap.shape == (store.count(), 8)
        np.testing.assert_array_equal(snap, store._vectors)
    again = VectorStore(_cfg(tmp_path, shard_capacity=CAP))
    assert again._ids == store._ids
    assert again._payloads == store._payloads
    assert again._id_to_row == store._id_to_row
    np.testing.assert_array_equal(again._vectors, store._vectors)
    assert len(again._blocks) == len(store._blocks)
    model.assert_same(again)
    # and the reloaded store appends in place, across its next edge
    tail = again._blocks[-1]
    _play(again, [_names(100, 100 + CAP + 1)], model, seed=10)
    assert again._blocks[len(store._blocks) - 1] is tail
    model.assert_same(again)


@pytest.mark.parametrize("rows", [0, 5, 8, 20])
def test_load_reads_a_plain_npy_snapshot_into_blocks(tmp_path, rows):
    """A snapshot as benchmark/artefacts.py makes one (np.save / open_memmap
    of [n, dim] f32 + meta.json), never written by this store."""
    cfg = _cfg(tmp_path, shard_capacity=CAP)
    rng = np.random.default_rng(10)
    vecs = np.stack([_unit(v) for v in rng.normal(size=(rows, 8))]
                    ).astype(np.float32) if rows else np.zeros((0, 8),
                                                               np.float32)
    np.save(tmp_path / f"{cfg.collection}.vectors.npy", vecs)
    (tmp_path / f"{cfg.collection}.meta.json").write_text(json.dumps(
        {"dim": 8, "ids": _names(0, rows),
         "payloads": [{"i": i} for i in range(rows)]}))
    store = VectorStore(cfg)
    assert store.count() == rows and len(store._blocks) == -(-rows // CAP)
    np.testing.assert_array_equal(store._vectors, vecs)
    if rows:
        assert store.search(vecs[rows - 1], 1)[0].id == f"p{rows - 1}"
    store.compact()  # and writes the same file back
    np.testing.assert_array_equal(
        np.load(tmp_path / f"{cfg.collection}.vectors.npy"), vecs)


@pytest.mark.parametrize("fault", ["rows_disagree_with_ids", "float64_rows",
                                   "truncated"])
def test_load_refuses_a_snapshot_that_is_not_the_collections(tmp_path, fault):
    cfg = _cfg(tmp_path, shard_capacity=CAP)
    vecs = np.ones((10, 8), np.float64 if fault == "float64_rows"
                   else np.float32)
    path = tmp_path / f"{cfg.collection}.vectors.npy"
    np.save(path, vecs)
    if fault == "truncated":
        path.write_bytes(path.read_bytes()[:-40])
    n_ids = 9 if fault == "rows_disagree_with_ids" else 10
    (tmp_path / f"{cfg.collection}.meta.json").write_text(json.dumps(
        {"dim": 8, "ids": _names(0, n_ids), "payloads": [{}] * n_ids}))
    with pytest.raises(ValueError, match="vectors.npy"):
        VectorStore(cfg)


def test_ensure_collection_at_another_dim_before_any_row():
    store = VectorStore(_cfg(shard_capacity=CAP))
    store.ensure_collection(dim=4)
    store.upsert([("a", np.ones(4), {})])
    assert store._vectors.shape == (1, 4)
    assert store.search(np.ones(4), 1)[0].id == "a"
