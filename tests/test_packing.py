"""Sequence packing of the `embed` dispatch: the planner (engine/bucketing.py
`plan_packed`), the packed forward against each text embedded alone through
the unpacked forward, the executable set `warmup` compiles, and that the
unpacked programs (`qsearch`, `rerank`) do not know the packing exists.

Toy widths, float32: packed and alone are the same maths on the same
numbers (a real token sees the same keys, positions and weights), so the
tolerance is summation order's, 1e-5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from symbiont_tpu.config import EngineConfig
from symbiont_tpu.engine.bucketing import (
    pack_rows,
    plan_packed,
    segments_per_row,
)
from symbiont_tpu.engine.engine import TpuEngine
from symbiont_tpu.engine.tokenizer import HashTokenizer
from symbiont_tpu.models import bert, families, mla_moe
from symbiont_tpu.utils.telemetry import metrics

BUCKETS = [32, 64, 128]
BATCHES = [1, 8, 32, 128]


# ---------------------------------------------------------------- planner

def _page_lengths(rng, n=200):
    """The ingest cells' law: lognormal(2.6, 0.7) words clipped 3-120, two
    special tokens, truncated to the top bucket."""
    words = np.clip(np.round(rng.lognormal(2.6, 0.7, n)), 3, 120)
    return [int(min(w + 2, 128)) for w in words]


PLANNER_CASES = {
    "one_sentence": ([7], 128),
    "one_long_sentence": ([128], 128),
    "all_fit_the_smallest_bucket": ([6, 9, 5, 8], 128),
    "five_short_need_the_second_bucket": ([5] * 5, 128),
    "a_sentence_of_exactly_L": ([128, 3, 128, 40, 88], 128),
    "more_than_S_short_sentences": ([3] * 40, 128),
    "more_than_S_in_one_row_of_tokens": ([4] * 17, 128),
    "more_rows_than_plan_cap": ([100] * 20, 8),
    "over_long_is_clipped": ([500, 20, 300], 128),
    **{f"page_seed_{s}": (_page_lengths(np.random.default_rng(s)), 128)
       for s in range(8)},
    **{f"uniform_seed_{s}": (list(np.random.default_rng(100 + s).integers(
        1, 129, int(np.random.default_rng(200 + s).integers(1, 700)))), 32)
       for s in range(6)},
}


@pytest.mark.parametrize("case", sorted(PLANNER_CASES))
def test_planner_places_every_sentence_once_within_its_bounds(case):
    lengths, cap = PLANNER_CASES[case]
    L, dispatches = plan_packed(lengths, BUCKETS, cap)
    assert L in BUCKETS
    S = segments_per_row(L)
    clipped = [min(int(n), BUCKETS[-1]) for n in lengths]
    rows = [row for d in dispatches for row in d]
    # every sentence in exactly one row
    assert sorted(i for row in rows for i in row) == list(range(len(lengths)))
    for row in rows:
        assert 0 < len(row) <= S
        assert sum(clipped[i] for i in row) <= L
    assert all(0 < len(d) <= cap for d in dispatches)
    assert all(len(d) == cap for d in dispatches[:-1])
    total, n = sum(clipped), len(clipped)
    if total <= L and n <= S:
        assert len(rows) == 1
        # and no smaller bucket would have held the call in one row
        assert all(total > b or n > segments_per_row(b)
                   for b in BUCKETS if b < L)
    else:
        # rows below the top bucket only ever come alone
        assert L == BUCKETS[-1]
        # first fit: of the rows that still had a sentence slot free, at
        # most one ends half empty (a later row's first sentence would
        # have fitted it), so rows <= (rows full by count) + (rows over
        # half full by tokens) + 1
        assert len(rows) >= max(-(-total // L), -(-n // S))
        assert len(rows) <= n // S + (2 * total) // L + 1


def test_planner_fills_the_cells_page_into_thirty_two_rows():
    """The ingest cells' page (benchmark/traffic/ingest_pages.json: 200
    sentences, 4,033 tokens): one [32, 128] dispatch, 1.5% padding."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmark"))
    import traffic
    from kinds import ingest

    tok = HashTokenizer(250002)
    lengths = [len(tok.encode(s, 128)) for s in ingest.page_sentences(
        traffic.load_mix("ingest_pages"), 0, 0)]
    assert (len(lengths), sum(lengths)) == (200, 4033)
    L, dispatches = plan_packed(lengths, BUCKETS, 128)
    assert L == 128 and [len(d) for d in dispatches] == [32]


@pytest.mark.parametrize("bucket,slots", [
    (8, 1), (32, 4), (64, 8), (128, 16), (256, 32), (512, 64), (1024, 64),
    (8192, 64), (32768, 64)])
def test_segments_per_row_is_an_eighth_of_short_rows_and_capped_on_long(
        bucket, slots):
    """The short buckets' values are what they were (128 -> 16 slots); from
    512 tokens on a row keeps 64: a long bucket is for longer passages."""
    assert segments_per_row(bucket) == slots


def _longdocs_lengths():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmark"))
    import traffic
    from refs.xlmr import token_count
    from kinds import ingest

    return [token_count(s, 32768) for s in ingest.page_sentences(
        traffic.load_mix("ingest_longdocs"), 0, 0)]


def test_planner_packs_a_page_of_long_passages_into_four_full_length_rows():
    """benchmark/traffic/ingest_longdocs.json: six passages of 8.4 k-32 k
    tokens, none splits, one or two to a 32,768-token row."""
    lengths = _longdocs_lengths()
    assert len(lengths) == 6
    assert all(8192 < n <= 32768 for n in lengths)
    L, dispatches = plan_packed(lengths, [32768], 1)
    rows = [row for d in dispatches for row in d]
    assert L == 32768 and [len(d) for d in dispatches] == [1] * len(rows)
    assert sorted(i for row in rows for i in row) == list(range(6))
    assert all(sum(lengths[i] for i in row) <= L for row in rows)
    assert len(rows) == 4 and sorted(len(r) for r in rows) == [1, 1, 2, 2]


def test_a_long_row_ships_a_few_bytes_and_builds_nothing_cubic():
    """What a 32,768-token row ships and what the device builds from it:
    [B, 64] lengths (256 B a row, where L // 8 slots were 16 KB and the
    pooled rows 32 MB), a [B, L] index and position, and a [B, L, 64]
    comparison (2 MB) where [B, L, L // 8] was 134 M entries."""
    L = 32768
    lengths = _longdocs_lengths()
    seqs = [[1] * n for n in lengths]
    _, dispatches = plan_packed(lengths, [L], 1)
    two = next(d[0] for d in dispatches if len(d[0]) == 2)
    ids, seg = pack_rows(seqs, [two], L, 1, pad_id=0)
    assert ids.shape == (1, L) and seg.shape == (1, 64)
    assert seg.nbytes == 256
    a, b = (lengths[i] for i in two)
    shapes = jax.eval_shape(lambda s: bert.Segments.of_lengths(s, L),
                            jnp.asarray(seg))
    assert shapes.index.shape == shapes.position.shape == (1, L)
    s = bert.Segments.of_lengths(jnp.asarray(seg), L)
    index, position = np.asarray(s.index[0]), np.asarray(s.position[0])
    assert (index[:a] == 0).all() and (index[a:a + b] == 1).all()
    assert (index[a + b:] == 64).all()
    np.testing.assert_array_equal(position[:a], np.arange(a))
    np.testing.assert_array_equal(position[a:a + b], np.arange(b))
    assert int(np.asarray(s.real).sum()) == a + b
    # the largest intermediate of of_lengths is [B, L, S] with S = 64
    jaxpr = jax.make_jaxpr(lambda x: bert.Segments.of_lengths(x, L))(
        jnp.asarray(seg))
    biggest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars)
    assert biggest == L * 64


def test_pack_rows_lays_sentences_end_to_end():
    seqs = [[1, 5, 2], [1, 6, 7, 2], [1, 2]]
    ids, seg = pack_rows(seqs, [[1, 2], [0]], 16, 4, pad_id=0)
    assert ids.shape == (4, 16) and seg.shape == (4, 2)
    np.testing.assert_array_equal(ids[:, :8], [[1, 6, 7, 2, 1, 2, 0, 0],
                                               [1, 5, 2, 0, 0, 0, 0, 0],
                                               [0] * 8, [0] * 8])
    assert not ids[:, 8:].any()
    np.testing.assert_array_equal(seg, [[4, 2], [3, 0], [0, 0], [0, 0]])
    s = bert.Segments.of_lengths(jnp.asarray(seg), 16)
    np.testing.assert_array_equal(s.index[:2, :8], [[0, 0, 0, 0, 1, 1, 2, 2],
                                                    [0, 0, 0, 2, 2, 2, 2, 2]])
    np.testing.assert_array_equal(s.position[0, :6], [0, 1, 2, 3, 0, 1])
    np.testing.assert_array_equal(s.real[:, :8], [[1] * 6 + [0] * 2,
                                                  [1] * 3 + [0] * 5,
                                                  [0] * 8, [0] * 8])
    assert not np.asarray(s.real)[:, 8:].any()
    # a sentence of no tokens takes a slot and shifts nobody
    s = bert.Segments.of_lengths(jnp.asarray([[2, 0, 3]]), 8)
    np.testing.assert_array_equal(s.index, [[0, 0, 2, 2, 2, 3, 3, 3]])
    np.testing.assert_array_equal(s.position[0, :5], [0, 1, 0, 1, 2])


# --------------------------------------------------------------- equality

MOE = {
    "model_type": "deepseek_v3", "vocab_size": 500, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
    "norm_topk_prob": True, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 800000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512,
}

TEXTS = [
    "tensor processing unit",
    "the memory bandwidth of embeddings bounds a semantic search pipeline "
    "more often than its arithmetic does",
    "graph",
    "attention masked pooling batch " * 6,
    "a row of several sentences keeps each to itself",
    "vector store",
    "w " * 40,
    "positions restart at every sentence and so do the rotary angles",
    "mean over a segment",
    "x y z",
    "the packer lays sentences end to end and never splits one " * 2,
    "short again",
]


def _family_engine(family: str, pooling: str, devices: int) -> TpuEngine:
    if family == "bert":
        # XLM-R's offset positions for the mean, classic BERT's for cls
        cfg = bert.BertConfig(
            vocab_size=1000, hidden_size=32, num_layers=2, num_heads=4,
            intermediate_size=64, max_position_embeddings=40,
            position_offset=2 if pooling == "mean" else 0, dtype="float32")
    else:
        cfg = mla_moe.MlaMoeConfig.from_hf(MOE)
    fam = families.family_of_config(cfg)
    params = fam.init_params(jax.random.key(3), cfg)
    mesh = None
    if devices > 1:
        from symbiont_tpu.parallel import build_mesh

        mesh = build_mesh([devices, 1], devices=jax.devices()[:devices])
    return TpuEngine(
        EngineConfig(length_buckets=[16, 32], batch_buckets=[1, 4, 8],
                     max_batch=8, dtype="float32",
                     data_parallel=devices > 1),
        mesh=mesh, params=params, model_cfg=cfg, pooling=pooling,
        normalize=pooling == "cls", tokenizer=HashTokenizer(cfg.vocab_size))


def _alone(eng: TpuEngine, text: str) -> np.ndarray:
    """One text through the UNPACKED forward (what `qsearch` traces)."""
    ids = np.asarray([eng.tokenizer.encode(text, 32)], np.int32)
    rows, _ = eng.family.embed(
        jax.device_get(eng.params), jnp.asarray(ids), jnp.ones_like(ids),
        eng.model_cfg, eng.pooling, eng.normalize)
    return np.asarray(rows[0])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("family,pooling", [("bert", "mean"), ("bert", "cls"),
                                            ("mla_moe", "mean")])
def test_packed_rows_equal_each_text_embedded_alone(family, pooling, devices):
    if len(jax.devices()) < devices:
        pytest.skip("needs simulated devices")
    eng = _family_engine(family, pooling, devices)
    want = np.stack([_alone(eng, t) for t in TEXTS])
    before = metrics.snapshot()["counters"]
    got = eng.embed_texts(TEXTS)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # it was packed: 12 texts of 131 tokens in rows of 32, one dispatch
    after = metrics.snapshot()["counters"]
    key = 'engine.embed.dispatches{service="engine"}'
    assert after[key] - before.get(key, 0) == 1
    # a text's row does not change with what shares its row
    for i, others in ((2, [0, 5]), (4, [9, 11, 8]), (0, [])):
        rows = eng.embed_texts([TEXTS[j] for j in others] + [TEXTS[i]])
        np.testing.assert_allclose(rows[-1], want[i], rtol=1e-5, atol=1e-5)
    # a lone short text keeps the bucket it had before packing
    assert ("embed", 16, eng._batch_bucket(1)) in eng._exec_cache


def test_padding_and_absent_slots_reach_no_row_and_no_expert():
    eng = _family_engine("mla_moe", "mean", 1)

    def assignments():
        return sum(v for k, v in metrics.snapshot()["counters"].items()
                   if k.startswith("engine.moe.assignments"))

    a0 = assignments()
    rows = eng.embed_texts(TEXTS[:3])
    real = sum(len(eng.tokenizer.encode(t, 32)) for t in TEXTS[:3])
    # every real token, top-2, in each of the 2 expert layers; padding none
    assert assignments() - a0 == real * 2 * 2
    assert np.isfinite(rows).all() and (np.abs(rows).sum(1) > 0).all()


# ------------------------------------------------------------ executables

def _words(n: int, salt: int) -> str:
    return " ".join(f"w{(salt * 31 + i) % 997}" for i in range(n))


def test_warmup_compiles_the_six_formable_shapes_and_no_more():
    eng = TpuEngine(EngineConfig(
        embedding_dim=32, length_buckets=BUCKETS, batch_buckets=BATCHES,
        max_batch=128, dtype="float32", data_parallel=False))
    eng.warmup(buckets=BUCKETS, batches=BATCHES)
    assert sorted(k[1:] for k in eng._exec_cache if k[0] == "embed") == [
        (32, 1), (64, 1), (128, 1), (128, 8), (128, 32), (128, 128)]
    compiles = eng.stats["compiles"]
    assert compiles == 6
    rng = np.random.default_rng(5)
    page = [_words(n - 2, i) for i, n in enumerate(_page_lengths(rng))]
    d0 = metrics.snapshot()["counters"].get(
        'engine.embed.dispatches{service="engine"}', 0)
    h0 = metrics.snapshot()["histograms"].get(
        'engine.pack.segments_per_row{service="engine"}', {"count": 0, "sum": 0})
    assert eng.embed_texts(page).shape == (200, 32)
    eng.embed_texts(["a single query"])
    eng.embed_texts([_words(5, i) for i in range(8)])  # share one row
    for n in (1, 9, 33, 129, 300):  # a row each: every batch bucket, cuts
        eng.embed_texts([_words(100, i) for i in range(n)])
    assert eng.stats["compiles"] == compiles
    snap = metrics.snapshot()
    assert snap["counters"][
        'engine.embed.dispatches{service="engine"}'] - d0 == 3 + (1 + 1 + 1
                                                                + 2 + 3)
    h1 = snap["histograms"]['engine.pack.segments_per_row{service="engine"}']
    assert h1["count"] - h0["count"] == 11
    # the page: 200 sentences in 32 or 33 rows
    assert h1["sum"] - h0["sum"] > 200 / 33 + 1 + 8 + 8


def test_unpacked_programs_lower_to_one_text_whatever_was_packed_before():
    """`qsearch` and `rerank` trace the model functions with no segments:
    their lowered text is the same before and after packed dispatches."""
    def engine():
        eng = TpuEngine(EngineConfig(
            embedding_dim=32, length_buckets=[8, 16], batch_buckets=[1, 4],
            max_batch=4, dtype="bfloat16", data_parallel=False,
            rerank_enabled=True))
        eng._time_first_call = lambda jitted, sig: jitted  # the raw jit
        return eng

    def texts(eng):
        ids = jnp.ones((1, 16), eng._ids_dtype)
        q = eng._get_executable("qsearch", 16, 64, 8, None).lower(
            eng.params, ids, jnp.ones((1, 16), jnp.int32),
            jnp.zeros((64, 32), jnp.float32), 5).as_text()
        r = eng._get_executable("rerank", 16, 4).lower(
            eng.cross_params, jnp.ones((4, 16), eng._ids_dtype),
            jnp.full((4,), 16, jnp.int32), jnp.full((4,), 5, jnp.int32)
        ).as_text()
        return q, r

    cold = texts(engine())
    eng = engine()
    eng.embed_texts(TEXTS)  # packed dispatches first
    assert texts(eng) == cold
    assert "symbiont.embed" not in cold[0]


def test_flash_attention_asked_for_leaves_the_embed_program_on_xla(caplog):
    import logging

    with caplog.at_level(logging.WARNING):
        eng = TpuEngine(EngineConfig(
            embedding_dim=32, length_buckets=[16], batch_buckets=[1, 4],
            max_batch=4, dtype="float32", data_parallel=False,
            attn_impl="flash"))
    assert eng.model_cfg.attn_impl == "flash"
    assert eng._embed_cfg.attn_impl == "xla"
    assert sum("packed embed program" in r.message
               for r in caplog.records) == 1
    assert eng.embed_texts(["one two", "three"]).shape == (2, 32)
    with pytest.raises(ValueError, match="per-key bias"):
        ids = jnp.ones((1, 8), jnp.int32)
        seg = bert.Segments.of_lengths(jnp.asarray([[8]]), 8)
        bert.embed_sentences(eng.params, ids, seg.real, eng.model_cfg,
                             segments=seg)
