"""models/sala.py (block-sparse InfLLM-V2 layers beside lightning linear-
attention layers) against the benchmark's plain reference
(`benchmark/refs/minicpm_sala.py`, imported by path: float32 jax.numpy at
matmul precision "highest", the recurrence token by token, selection and
attention per query over explicit masks, one passage a call, nothing of the
program in it), on seeded weights at toy widths that keep every ratio of the
published model: two periods of [sparse, linear x 3], 8 query heads over 2
kv heads, and a `sparse_config` shrunk (kernel 4, stride 2, block 8, window
16, top-6, dense up to 32 tokens) so that toy passages lie on both sides of
`dense_len` and the top-k is smaller than the blocks there are.

Tolerances, each with its reason:
- float32 program against the reference: 1e-5 relative on rows, 1e-4
  absolute on a mixer's output. Same maths in the same precision; what
  differs is summation order (chunks against a token-by-token state, an
  online softmax over key chunks against one softmax). The selected block
  SETS are compared exactly: ties go to the lower block on both sides.
- bfloat16 at rest (`f16`): 0.03 mean relative error over 8 layers of toy
  width; int8 and fp8 weights must read above what bfloat16 read on the
  same rows: int8 is the benchmark's control, the step below.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
import yardstick_sala as ys  # noqa: E402
from refs import minicpm_sala as ref  # noqa: E402

from symbiont_tpu.config import EngineConfig  # noqa: E402
from symbiont_tpu.engine.engine import TpuEngine  # noqa: E402
from symbiont_tpu.models import bert, convert, families, mla_moe, quant, sala  # noqa: E402
from symbiont_tpu.models.bert import Segments  # noqa: E402
from symbiont_tpu.models.sala import SparseConfig  # noqa: E402
from symbiont_tpu.ops.block_sparse_attention import block_sparse_attention  # noqa: E402
from symbiont_tpu.ops.linear_attention import lightning_attention  # noqa: E402
from symbiont_tpu.utils.telemetry import metrics  # noqa: E402

SPARSE_TOY = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
              "init_blocks": 1, "window_size": 16, "topk": 6, "dense_len": 32}
MODEL = {
    "model_type": "minicpm_sala", "vocab_size": 500, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "mixer_types": (["minicpm4"] + ["lightning-attn"] * 3) * 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "rope_theta": 10000, "rms_norm_eps": 1e-6, "scale_emb": 12,
    "scale_depth": 1.4, "depth_layers": 32, "max_position_embeddings": 4096,
    "qk_norm": True, "attn_use_rope": False, "lightning_use_rope": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "sparse_config": SPARSE_TOY,
}
SEED = 7
F32_TOL = 1e-5
LENS = (100, 20, 57)  # sparse, dense, sparse


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """The reference's checkpoint (assumed HF names, bfloat16) loaded
    through the program's own converter, upcast for float32 comparisons."""
    out = tmp_path_factory.mktemp("sala_toy")
    ref.write_checkpoint(MODEL, SEED, out)
    params, cfg = convert.load_sala_model(out)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    return out, params, params32, cfg32


@pytest.fixture(scope="module")
def tensors():
    return ref.common.f32(ref.common.seeded_tensors(ref.tensor_specs(MODEL),
                                                    SEED))


@pytest.fixture(scope="module")
def passages():
    rng = np.random.default_rng(0)
    return [rng.integers(3, MODEL["vocab_size"], n).astype(np.int32)
            for n in LENS]


@pytest.fixture(scope="module")
def want(passages):
    r = ref.Reference(MODEL, SEED, 4096)
    rows = np.stack(r.forward([list(p) for p in passages]))
    return rows, r.gap_share


def _packed(seqs, L, S=8):
    ids = np.zeros((1, L), np.int32)
    ids[0, :sum(map(len, seqs))] = np.concatenate(seqs)
    seg = np.zeros((1, S), np.int32)
    seg[0, :len(seqs)] = [len(s) for s in seqs]
    return jnp.asarray(ids), Segments.of_lengths(jnp.asarray(seg), L)


def _rel(got, want):
    return (np.linalg.norm(np.asarray(got, np.float32) - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-12))


def _hi(fn, *a, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*a, **kw)


# ------------------------------------------------------ the linear layers

def _recurrence(q, k, v, slopes):
    """One passage, token by token, in numpy float64: [n, H, d] each."""
    n, H, d = q.shape
    lam = np.exp(-np.asarray(slopes, np.float64))[:, None, None]
    state = np.zeros((H, d, d))
    out = np.zeros((n, H, d))
    for t in range(n):
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        out[t] = np.einsum("hd,hde->he", q[t], state)
    return out


@pytest.mark.parametrize("chunk", [8, 16, 12, 40, 64, 256])
def test_chunked_linear_attention_equals_the_recurrence(chunk):
    """Chunks that divide the row (8, 40), that do not (16, 12), one chunk
    and a chunk longer than the row; three passages and padding in a row:
    the state resets at each passage's first token."""
    rng = np.random.default_rng(chunk)
    L, H, d, lens = 40, 3, 8, (17, 9, 11)
    q, k, v = (rng.standard_normal((1, L, H, d)).astype(np.float32)
               for _ in range(3))
    slopes = np.asarray(sala.decay_slopes(H))
    index = np.full((1, L), 8, np.int32)
    at = 0
    for s, n in enumerate(lens):
        index[0, at:at + n] = s
        at += n
    got = np.asarray(lightning_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(index),
        jnp.asarray(slopes), chunk=chunk))
    at = 0
    for n in lens:
        want = _recurrence(q[0, at:at + n], k[0, at:at + n],
                           v[0, at:at + n], slopes)
        assert np.abs(got[0, at:at + n] - want).max() < 2e-5
        at += n


def test_lightning_mixer_matches_reference(checkpoint, tensors):
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(1)
    lens = (33, 50)
    x = rng.standard_normal((1, 96, 64)).astype(np.float32)
    _, segments = _packed([np.zeros(n, np.int32) for n in lens], 96)
    got = np.asarray(_hi(sala.lightning_mixer, params32["layers"][1]["mixer"],
                         jnp.asarray(x), segments, cfg))
    w, at = ref.layer_weights(tensors, MODEL, 1), 0
    for n in lens:
        want = np.asarray(_hi(ref.lightning, w, jnp.asarray(x[0, at:at + n]),
                              MODEL))
        assert np.abs(got[0, at:at + n] - want).max() < 1e-4
        at += n


# ------------------------------------------------------ the sparse layers

def _qkv(p, x, cfg):
    eps = cfg.rms_norm_eps
    return (sala._heads(x, p["q"], p["q_norm"], cfg.num_heads, eps),
            sala._heads(x, p["k"], p["k_norm"], cfg.num_kv_heads, eps),
            sala._heads(x, p["v"], None, cfg.num_kv_heads, eps))


@pytest.mark.parametrize("q_block,k_chunk", [(256, 1024), (16, 32), (24, 16),
                                             (8, 8), (64, 200)])
def test_sparse_mixer_and_its_block_sets_match_reference(
        checkpoint, tensors, q_block, k_chunk):
    """Three passages in one row, on both sides of `dense_len` (32), whatever
    the query blocks and key chunks are cut to: the output per passage, the
    selected block sets EXACTLY, and the counts of keys."""
    _, _, params32, cfg = checkpoint
    rng = np.random.default_rng(2)
    L, bs = 192, SPARSE_TOY["block_size"]
    x = rng.standard_normal((1, L, 64)).astype(np.float32)
    _, segments = _packed([np.zeros(n, np.int32) for n in LENS], L)
    p = params32["layers"][0]["mixer"]
    q, k, v = _hi(_qkv, p, jnp.asarray(x), cfg)
    ctx, counts, (sel, gap) = _hi(
        block_sparse_attention, q, k, v, segments.index, segments.position,
        segments.lengths, cfg.sparse, q_block=q_block, k_chunk=k_chunk,
        with_sets=True)
    gate = jax.nn.sigmoid(jnp.asarray(x) @ p["gate"]["kernel"])
    got = np.asarray(_hi(lambda: (ctx.reshape(gate.shape) * gate)
                         @ p["o"]["kernel"]))
    sel = np.asarray(sel)
    w, at, a_block = ref.layer_weights(tensors, MODEL, 0), 0, 0
    attended = 0
    for n in LENS:
        want, _, sets = _hi(ref.sparse, w, jnp.asarray(x[0, at:at + n]), n,
                            MODEL, with_sets=True)
        assert np.abs(got[0, at:at + n] - np.asarray(want)).max() < 1e-4
        blocks = -(-n // bs)
        mine = sel[0, :, at:at + n, a_block:a_block + blocks]  # [G, n, nb]
        assert (mine == np.moveaxis(np.asarray(sets), 1, 0)).all()
        # and nothing outside the passage's own blocks
        assert mine.sum() == sel[0, :, at:at + n].sum()
        attended += int(ys.keys_attended(n, MODEL).sum())
        at += n
        a_block += blocks
    causal = sum(n * (n + 1) // 2 for n in LENS)
    assert np.asarray(counts).tolist() == [[attended, causal]]
    assert attended < causal
    assert np.isinf(np.asarray(gap)[0, :, 100:120]).all()  # the dense one


def test_sparse_config_refuses_more_forced_blocks_than_topk():
    with pytest.raises(ValueError, match="exceed topk"):
        SparseConfig(block_size=8, kernel_size=4, kernel_stride=2,
                     window_size=64, topk=6)
    with pytest.raises(ValueError, match="multiples"):
        SparseConfig(kernel_stride=12)


# ------------------------------------------------------------ the forward

def test_packed_rows_match_reference_and_each_passage_alone(
        checkpoint, passages, want):
    """Packed = alone = the reference, to 1e-5 in float32: the state resets,
    selection and the window stay inside the passage, positions restart."""
    _, _, params32, cfg = checkpoint
    rows, gap_share = want
    ids, segments = _packed(passages, 256)
    got, counts = _hi(sala.embed_sentences, params32, ids, segments.real, cfg,
                      "mean", False, segments)
    assert got.shape == (1, 8, 64)
    assert _rel(np.asarray(got)[0, :3], rows).max() < F32_TOL
    assert (np.asarray(got)[0, 3:] == 0).all()  # slots that hold nothing
    assert np.asarray(counts).shape == (1, 2, 2)
    alone = []
    for p in passages:
        row = np.zeros((1, 128), np.int32)
        row[0, :len(p)] = p
        mask = (np.arange(128) < len(p)).astype(np.int32)[None]
        g, c = _hi(sala.embed_sentences, params32, jnp.asarray(row),
                   jnp.asarray(mask), cfg)
        alone.append(np.asarray(g)[0])
        assert int(np.asarray(c)[0, 0, 1]) == len(p) * (len(p) + 1) // 2
    assert _rel(np.stack(alone), rows).max() < F32_TOL
    assert _rel(np.stack(alone), np.asarray(got)[0, :3]).max() < F32_TOL
    assert 0.0 <= gap_share <= 1.0


def test_padding_and_row_order_never_reach_a_passage(checkpoint, passages):
    _, _, params32, cfg = checkpoint
    ids, segments = _packed(passages, 256)
    got, _ = _hi(sala.embed_sentences, params32, ids, segments.real, cfg,
                 "mean", False, segments)
    junk = np.asarray(ids).copy()
    junk[0, sum(LENS):] = 77
    ids2, segments2 = _packed(passages[::-1], 256)
    got2, _ = _hi(sala.embed_sentences, params32, jnp.asarray(junk),
                  segments.real, cfg, "mean", False, segments)
    got3, _ = _hi(sala.embed_sentences, params32, ids2, segments2.real, cfg,
                  "mean", False, segments2)
    assert _rel(np.asarray(got2)[0, :3], np.asarray(got)[0, :3]).max() < 1e-6
    assert _rel(np.asarray(got3)[0, :3][::-1],
                np.asarray(got)[0, :3]).max() < F32_TOL


@pytest.mark.parametrize("mode", ["f16", "int8", "fp8"])
def test_lower_precision_at_rest_runs_and_ranks_below_bfloat16(
        checkpoint, passages, want, mode):
    _, params, _, cfg32 = checkpoint
    cfg = dataclasses.replace(cfg32, dtype="bfloat16")
    ids, segments = _packed(passages, 256)

    def err(m):
        got, _ = sala.embed_sentences(quant.quantize_params(params, m), ids,
                                      segments.real, cfg, "mean", False,
                                      segments)
        return float(_rel(np.asarray(got, np.float32)[0, :3], want[0]).mean())

    e = err(mode)
    assert np.isfinite(e)
    if mode == "f16":
        assert e < 0.03
    else:
        assert err("f16") < e < 0.5


# ------------------------------------------------------- the family seam

@pytest.mark.parametrize("model_type,family", [
    ("minicpm_sala", "sala"), ("deepseek_v3", "mla_moe"),
    ("kimi_vl", "mla_moe"), ("xlm-roberta", "bert"), ("bert", "bert"),
    ("roberta", "bert"), ("mpnet", "bert"), ("electra", "bert"),
    (None, "bert")])
def test_family_table_picks_the_family_from_config_json(tmp_path, model_type,
                                                        family):
    import json

    hf = {} if model_type is None else {"model_type": model_type}
    if model_type == "kimi_vl":
        hf = {"model_type": "kimi_vl",
              "text_config": {"model_type": "deepseek_v3"}}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert families.family_of_checkpoint(tmp_path).name == family


@pytest.mark.parametrize("model_type", ["gpt2", "minicpm", "camembert"])
def test_a_model_type_no_family_claims_is_refused_by_name(tmp_path,
                                                          model_type):
    """No family is the fallback: a checkpoint of a type nobody claims
    (another architecture, or a BERT relative whose position offset the
    loader would get wrong) raises at the seam, before any tensor is read,
    and the engine's boot with it."""
    import json

    from symbiont_tpu.config import EngineConfig
    from symbiont_tpu.engine.engine import TpuEngine

    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": model_type, "vocab_size": 100, "hidden_size": 32}))
    with pytest.raises(ValueError, match=f"model_type '{model_type}'"):
        families.family_of_checkpoint(tmp_path)
    with pytest.raises(ValueError, match="no embedder family claims"):
        TpuEngine(EngineConfig(model_dir=str(tmp_path)))


def test_family_of_config_and_what_each_family_notes():
    assert families.family_of_config(sala.SalaConfig()) is families.SALA
    assert families.family_of_config(
        mla_moe.MlaMoeConfig()) is families.MLA_MOE
    assert families.family_of_config(bert.BertConfig()) is families.BERT
    assert families.BERT.note_aux is None
    assert {f.name for f in families.FAMILIES
            if f.note_aux} == {"mla_moe", "sala", "ouro", "ling", "mimo"}


@pytest.mark.parametrize("key,value", [
    ("attn_use_rope", True), ("lightning_use_rope", False),
    ("qk_norm", False), ("use_output_gate", False),
    ("use_output_norm", False), ("attn_use_output_gate", False),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("lightning_nkv", 2), ("mixer_types", ["minicpm4"] * 7 + ["mamba"])])
def test_from_hf_refuses_by_name_what_it_cannot_compute(key, value):
    with pytest.raises(NotImplementedError, match="sala"):
        sala.SalaConfig.from_hf({**MODEL, key: value})


def test_from_hf_reads_the_published_sizes_and_this_programs_keys():
    import json

    config = json.loads((BENCH / "configs" / "minicpm-sala-embed.json"
                         ).read_text())
    cfg = sala.SalaConfig.from_hf(config["model"])
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.lightning_heads,
            cfg.vocab_size) == (4096, 16384, 32, 2, 128, 32, 73448)
    assert cfg.mixer_types == (sala.SPARSE,) + (sala.LINEAR,) * 6 + (
        sala.SPARSE,)
    assert cfg.depth_layers == 32 and cfg.num_layers == 8
    assert cfg.sparse == SparseConfig()  # the published family's sizes
    # without this program's keys: the file's own depth, the family's sizes
    bare = {k: v for k, v in config["model"].items()
            if k not in ("sparse_config", "depth_layers")}
    assert sala.SalaConfig.from_hf(bare).depth_layers == 8


# ------------------------------------------------------------ the engine

def _snap(name):
    s = metrics.snapshot()
    return sum(v for k, v in s["counters"].items() if k.startswith(name))


def test_engine_boots_the_checkpoint_embeds_and_counts(checkpoint, want):
    """`model_dir` alone picks the family; `embed_texts` packs passages into
    rows, agrees with the reference in float32, and books the sparse
    layers' counts; the fused query runs on the same forward."""
    out, _, _, _ = checkpoint
    eng = TpuEngine(EngineConfig(
        model_dir=str(out), dtype="float32", quantize="none",
        length_buckets=[256], batch_buckets=[1, 2], max_batch=2))
    assert eng.family is families.SALA
    rng = np.random.default_rng(5)
    words = [f"w{i}" for i in range(400)]
    texts = [" ".join(rng.choice(words, n)) for n in (98, 18, 55, 150, 40)]
    before = {k: _snap(f"engine.sparse.{k}") for k in
              ("keys_attended", "keys_causal")}
    h0 = metrics.snapshot()["histograms"].get(
        'engine.sparse.kept_share{service="engine"}', {"count": 0})["count"]
    d0 = _snap("engine.embed.dispatches")
    with jax.default_matmul_precision("highest"):
        got = eng.embed_texts(texts)
    r = ref.Reference(MODEL, SEED, 256)
    assert _rel(got, r.embed(texts)).max() < F32_TOL
    lens = [ref.token_count(t, 256) for t in texts]
    after = {k: _snap(f"engine.sparse.{k}") for k in before}
    layers = 2
    assert after["keys_causal"] - before["keys_causal"] == layers * sum(
        n * (n + 1) // 2 for n in lens)
    assert after["keys_attended"] - before["keys_attended"] == layers * sum(
        int(ys.keys_attended(n, MODEL).sum()) for n in lens)
    dispatches = _snap("engine.embed.dispatches") - d0
    h1 = metrics.snapshot()["histograms"][
        'engine.sparse.kept_share{service="engine"}']["count"]
    assert h1 - h0 == dispatches * layers
    # the fused query: one passage a row through the same forward
    corpus = np.asarray(got / np.linalg.norm(got, axis=1, keepdims=True))
    corpus = jnp.asarray(np.pad(corpus, ((0, 59), (0, 0))))
    with jax.default_matmul_precision("highest"):
        scores, idx = eng.embed_and_search(texts[2], corpus, 5, 3)
    assert int(idx[0]) == 2 and abs(float(scores[0]) - 1.0) < 5e-3


def test_other_families_unpacked_programs_hold_nothing_of_this_one():
    """`segments=None` traces for bert and mla_moe are the code traced
    before the third family: none of its scopes, no loop."""
    ids = jnp.ones((1, 16), jnp.int32)
    mask = jnp.ones((1, 16), jnp.int32)
    for family, cfg in (
            (families.BERT, bert.BertConfig(
                vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                intermediate_size=64, max_position_embeddings=32)),
            (families.MLA_MOE, mla_moe.MlaMoeConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, moe_intermediate_size=16,
                n_routed_experts=4, n_shared_experts=1,
                num_experts_per_tok=2, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8))):
        params = family.init_params(jax.random.key(0), cfg)
        text = jax.jit(lambda p, i, m: family.embed(
            p, i, m, cfg, "mean", True)).lower(params, ids, mask).as_text(
                debug_info=True)
        for word in ("sparse_select", "sparse_attn", "lightning",
                     "stablehlo.while"):
            assert word not in text, (family.name, word)


def test_a_process_of_another_family_never_imports_the_kernels_package():
    """`symbiont_tpu.ops` imports pallas (over a second, paid inside every
    boot's `setup_s`): the family table and the engine load without it, and
    the sparse and linear ops are imported where a sala forward is traced."""
    import subprocess

    code = ("import sys, symbiont_tpu.models.families, "
            "symbiont_tpu.engine.engine; "
            "print(any(m.startswith('symbiont_tpu.ops') or "
            "m.startswith('jax.experimental.pallas') for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=str(BENCH.parent)).stdout
    assert out.strip() == "False", out
