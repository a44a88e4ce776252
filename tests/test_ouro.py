"""models/ouro.py (a LoopLM: one stack applied several times over the same
weights, as a scan over stacked layers inside a scan over steps) against
the benchmark's plain reference (`benchmark/refs/ouro.py`, imported by path:
float32 jax.numpy at matmul precision "highest", Python loops over steps and
layers, one chunk a row, nothing of the program in it), on seeded weights at
toy widths: hidden 64, 4 heads of 16, 4 layers, 3 steps.

Tolerances, each with its reason:
- float32 program against the reference: 2e-5 relative on rows and on each
  step's states, 1e-5 absolute on exit probabilities and 1e-3 on a row's
  exit mass (a sum over up to 120 tokens). Same maths in the same
  precision; what differs is summation order (packed rows against one
  chunk a row, XLA's loop body against eager calls).
- bfloat16 at rest and in the matmuls (`f16`, cfg.dtype bfloat16): 0.03
  mean relative error over 12 block applications of toy width (the cell's
  rehearsal at these widths reads 0.0043-0.0051); int8 and fp8 weights must read above what bfloat16 read on
  the same rows: int8 is the benchmark's control, the step below.
"""

from __future__ import annotations

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
from refs import ouro as ref  # noqa: E402

from symbiont_tpu.config import EngineConfig  # noqa: E402
from symbiont_tpu.engine.engine import TpuEngine  # noqa: E402
from symbiont_tpu.models import convert, families, ouro, quant  # noqa: E402
from symbiont_tpu.models.bert import Segments  # noqa: E402
from symbiont_tpu.utils.telemetry import metrics  # noqa: E402

MODEL = {
    "model_type": "ouro", "vocab_size": 500, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "layer_types": ["full_attention"] * 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "hidden_act": "silu",
    "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 512, "total_ut_steps": 3,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "sliding_window": None, "tie_word_embeddings": False,
}
SEED = 11
F32_TOL = 2e-5
LENS = (100, 20, 57, 7)
L = 128  # the packed rows' length


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-12))


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """The reference's checkpoint (HF names, bfloat16) loaded through the
    program's own converter, upcast for float32 comparisons."""
    out = tmp_path_factory.mktemp("ouro_toy")
    ref.write_checkpoint(MODEL, SEED, out)
    params, cfg = convert.load_ouro_model(out)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    return out, params, params32, cfg32


@pytest.fixture(scope="module")
def chunks():
    rng = np.random.default_rng(3)
    return [rng.integers(3, MODEL["vocab_size"], n).astype(np.int32)
            for n in LENS]


@pytest.fixture(scope="module")
def want(chunks):
    """The reference on each chunk alone: (rows [n, H], every step's normed
    states, every step's exit probabilities)."""
    r = ref.Reference(MODEL, SEED, 512)
    groups, batches = r.batches_of([list(c) for c in chunks], 1)
    pooled, states, p = r.forward(batches, steps=True)
    rows = np.zeros((len(chunks), MODEL["hidden_size"]), np.float32)
    by_chunk = {}
    for b, (idx,) in enumerate(groups):
        n = len(chunks[idx])
        rows[idx] = pooled[b][0]
        by_chunk[idx] = ([s[b][0, :n] for s in states],
                         [q[b][0, :n] for q in p])
    return rows, by_chunk


def _packed(chunks, rows, length=L):
    """ids [B, length] and lengths [B, S] with `rows` = lists of chunk
    indices."""
    S = 16
    ids = np.zeros((len(rows), length), np.int32)
    lengths = np.zeros((len(rows), S), np.int32)
    for r, row in enumerate(rows):
        toks = np.concatenate([chunks[i] for i in row])
        ids[r, :len(toks)] = toks
        lengths[r, :len(row)] = [len(chunks[i]) for i in row]
    return jnp.asarray(ids), jnp.asarray(lengths)


def _embed_packed(params, cfg, ids, lengths):
    seg = Segments.of_lengths(lengths, ids.shape[1])
    with jax.default_matmul_precision("highest"):
        return ouro.embed_sentences(params, ids, seg.real, cfg, "mean", False,
                                    seg)


ROWS = [[0, 3], [2, 1]]  # 100 + 7 and 57 + 20 tokens


# ------------------------------------------------- against the reference

def test_packed_rows_match_the_reference_in_float32(checkpoint, chunks, want):
    _, _, params32, cfg32 = checkpoint
    got, aux = _embed_packed(params32, cfg32, *_packed(chunks, ROWS))
    assert got.shape == (2, 16, 64) and aux.shape == (2, 4)
    rows = np.stack([got[0, 0], got[1, 1], got[1, 0], got[0, 1]])
    assert _rel(rows, want[0]).max() < F32_TOL
    assert float(jnp.abs(got[0, 2:]).max()) == 0.0  # empty slots


def test_unpacked_query_forward_matches_the_reference(checkpoint, chunks,
                                                     want):
    """`segments=None` is what the fused query traces: one chunk a row,
    right-padded."""
    _, _, params32, cfg32 = checkpoint
    ids = np.zeros((len(chunks), L), np.int32)
    mask = np.zeros((len(chunks), L), np.int32)
    for r, c in enumerate(chunks):
        ids[r, :len(c)], mask[r, :len(c)] = c, 1
    with jax.default_matmul_precision("highest"):
        got, aux = ouro.embed_sentences(params32, jnp.asarray(ids),
                                        jnp.asarray(mask), cfg32)
    assert got.shape == (4, 64)
    assert _rel(got, want[0]).max() < F32_TOL
    # packed = unpacked for the same chunks
    packed, _ = _embed_packed(params32, cfg32, *_packed(chunks, ROWS))
    rows = np.stack([packed[0, 0], packed[1, 1], packed[1, 0], packed[0, 1]])
    assert _rel(rows, got).max() < F32_TOL
    assert np.allclose(aux[:, :-1].sum(1), LENS, atol=1e-3)


def test_every_steps_exit_distribution_and_the_aux_match(checkpoint, chunks,
                                                         want):
    """`p_t` token by token against the reference's, and `aux` = per row
    each step's exit mass, then the token-steps the loop ran."""
    _, _, params32, cfg32 = checkpoint
    ids, lengths = _packed(chunks, ROWS)
    seg = Segments.of_lengths(lengths, L)
    with jax.default_matmul_precision("highest"):
        _, p = ouro.encode(params32, ids, seg, cfg32)
        _, aux = ouro.embed_sentences(params32, ids, seg.real, cfg32, "mean",
                                      False, seg)
    assert p.shape == (3, 2, L)
    mass = np.zeros((2, 3))
    for r, row in enumerate(ROWS):
        at = 0
        for i in row:
            n = len(chunks[i])
            for t in range(3):
                ref_p = want[1][i][1][t]
                assert np.abs(np.asarray(p[t, r, at:at + n]) - ref_p
                              ).max() < 1e-5
                mass[r, t] += ref_p.sum()
            at += n
    assert np.allclose(np.asarray(aux[:, :3]), mass, atol=1e-3)
    tokens = np.array([107.0, 77.0])
    assert np.allclose(np.asarray(aux[:, :3]).sum(1), tokens, atol=1e-3)
    assert np.asarray(aux[:, 3]).tolist() == (3 * tokens).tolist()
    assert 0.02 < float(p.min()) and float(p.max()) < 0.98  # the gate moves


@pytest.mark.parametrize("steps", [1, 2, 3])
def test_each_steps_state_is_the_final_state_of_a_shorter_loop(
        checkpoint, chunks, want, steps):
    """The state after step t is what a `total_ut_steps` = t model ends on:
    every step's normed state against the reference's."""
    _, _, params32, cfg32 = checkpoint
    cfg = dataclasses.replace(cfg32, total_ut_steps=steps)
    ids, lengths = _packed(chunks, ROWS)
    with jax.default_matmul_precision("highest"):
        hidden, p = ouro.encode(params32, ids, Segments.of_lengths(lengths, L),
                                cfg)
    assert p.shape[0] == steps
    assert _rel(hidden[0, :100], want[1][0][0][steps - 1]).max() < F32_TOL
    assert _rel(hidden[1, 57:77], want[1][1][0][steps - 1]).max() < F32_TOL


def test_exit_distribution_by_hand():
    lam = jnp.asarray([[0.5, 0.1], [0.5, 0.2], [0.9, 0.3]])
    p = np.asarray(ouro.exit_distribution(lam))
    assert np.allclose(p, [[0.5, 0.1], [0.25, 0.18], [0.25, 0.72]])
    assert np.allclose(np.asarray(ouro.exit_distribution(lam[:1])), 1.0)
    assert np.allclose(ref.exit_distribution(list(np.asarray(lam))), p)


def test_the_scanned_stack_equals_the_block_unrolled(checkpoint, chunks):
    _, _, params32, cfg32 = checkpoint
    ids, lengths = _packed(chunks, ROWS)
    seg = Segments.of_lengths(lengths, L)
    h = jnp.asarray(params32["wte"])[ids]
    with jax.default_matmul_precision("highest"):
        scanned = ouro.run_stack(params32["layers"], h, seg, cfg32)
        unrolled = h
        for i in range(cfg32.num_layers):
            layer = jax.tree.map(lambda a: a[i], params32["layers"])
            unrolled = ouro.block(layer, unrolled, seg, cfg32)
    assert _rel(scanned, unrolled).max() < 1e-6


# ------------------------------------------------ the kernel's route

# a shape that tiles: a head is one 128-lane column block, a row two
# 128-token blocks (the interpreter runs the kernel on the CPU)
WIDE = {**MODEL, "hidden_size": 256, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 2,
        "layer_types": ["full_attention"] * 2, "total_ut_steps": 2}
WIDE_ROWS = [[0, 3, 1], [2]]  # 100 + 7 + 20 tokens (padded to the row), 57


@pytest.fixture(scope="module")
def wide(tmp_path_factory, chunks):
    """(params, cfg) in float32 of the tileable toy model, the reference's
    rows for the four chunks."""
    out = tmp_path_factory.mktemp("ouro_wide")
    ref.write_checkpoint(WIDE, SEED, out)
    params, cfg = convert.load_ouro_model(out)
    r = ref.Reference(WIDE, SEED, 512)
    groups, batches = r.batches_of([list(c) for c in chunks], 1)
    pooled = r.forward(batches)
    rows = np.zeros((len(chunks), WIDE["hidden_size"]), np.float32)
    for b, (idx,) in enumerate(groups):
        rows[idx] = pooled[b][0]
    return (jax.tree.map(lambda a: np.asarray(a, np.float32), params),
            dataclasses.replace(cfg, dtype="float32"), rows)


def _routes(fn):
    """(what `fn` returns, the `attn.packed{path}` bumps it made by path)."""
    before = _counters("attn.packed")
    out = fn()
    return out, {k.split('"')[1]: v - before.get(k, 0)
                 for k, v in _counters("attn.packed").items()
                 if v != before.get(k, 0)}


def _wide_rows(params, cfg, chunks, length):
    (got, _), route = _routes(lambda: _embed_packed(
        params, cfg, *_packed(chunks, WIDE_ROWS, length)))
    return np.stack([got[0, 0], got[0, 2], got[1, 0], got[0, 1]]), route


def test_the_kernel_route_equals_the_einsum_route_and_the_reference(
        wide, chunks):
    """At `head_dim` 128 a 256-token row goes through the Pallas kernel
    (RoPE, chunk-and-causal mask, streaming softmax inside it) and a
    264-token row of the same chunks through the einsum form: the route
    depends on the shape alone, `attn.packed{path}` says which once per
    traced program, and both stand where the float32 program stands from
    the reference."""
    params, cfg, want = wide
    fused, route = _wide_rows(params, cfg, chunks, 256)
    assert route == {"flash_segments": 1}
    dense, route = _wide_rows(params, cfg, chunks, 264)
    assert route == {"dense": 1}
    assert _rel(fused, want).max() < F32_TOL
    assert _rel(dense, want).max() < F32_TOL
    assert _rel(fused, dense).max() < F32_TOL


@pytest.mark.parametrize("head_dim, length, path", [
    (128, 128, "flash_segments"), (128, 512, "flash_segments"),
    (256, 384, "flash_segments"), (128, 8, "dense"), (128, 192, "dense"),
    (64, 512, "dense"), (16, 128, "dense")])
def test_the_route_is_chosen_by_head_dim_and_row_length_alone(
        head_dim, length, path):
    cfg = ouro.OuroConfig(vocab_size=50, hidden_size=2 * head_dim,
                          num_layers=1, num_heads=2, head_dim=head_dim,
                          intermediate_size=64, total_ut_steps=1)
    params = jax.eval_shape(lambda: ouro.init_params(jax.random.key(0), cfg))
    _, route = _routes(lambda: jax.eval_shape(
        lambda p, i: ouro.embed_sentences(p, i, jnp.ones_like(i), cfg),
        params, jax.ShapeDtypeStruct((1, length), jnp.int32)))
    assert route == {path: 1}


# ----------------------------------------------------------- precision

@pytest.mark.parametrize("mode", ["f16", "int8", "fp8"])
def test_lower_precision_at_rest_runs_and_ranks_below_bfloat16(
        checkpoint, chunks, want, mode):
    _, params, _, cfg32 = checkpoint
    cfg = dataclasses.replace(cfg32, dtype="bfloat16")
    ids, lengths = _packed(chunks, ROWS)

    def err(m):
        got, _ = ouro.embed_sentences(
            quant.quantize_params(params, m), ids,
            Segments.of_lengths(lengths, L).real, cfg, "mean", False,
            Segments.of_lengths(lengths, L))
        rows = np.stack([got[0, 0], got[1, 1], got[1, 0], got[0, 1]])
        return float(_rel(rows, want[0]).mean())

    e = err(mode)
    if mode == "f16":
        assert e < 0.03
    else:
        assert err("f16") < e < 0.5


@pytest.mark.parametrize("mode", ["int8", "fp8", "f16"])
def test_a_stacked_quant_tensor_slices_with_its_scales_under_scan(mode):
    """One scale per stacked kernel and output channel: the scan hands its
    body the layer's `q` [in, out] and the layer's `scale` [out], and
    `quant.mm` on the slice equals the dequantized layer's product. Stacked
    norm scales stay float32 arrays (by name: a stack does not make a norm
    a kernel)."""
    rng = np.random.default_rng(0)
    tree = {"k": {"kernel": rng.standard_normal((5, 8, 6)).astype(np.float32)
                  * np.arange(1, 6, dtype=np.float32)[:, None, None]},
            "ln": {"scale": rng.standard_normal((5, 8)).astype(np.float32)},
            "b": {"bias": rng.standard_normal((5, 6)).astype(np.float32)}}
    q = quant.quantize_params(tree, mode)
    assert q["ln"]["scale"].dtype == np.float32 and not quant.is_quantized(
        q["ln"]["scale"])
    assert q["b"]["bias"].dtype == np.float32
    x = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)

    def body(_, layer):
        return None, (quant.mm(x, layer["k"]["kernel"]),
                      layer["ln"]["scale"])

    _, (got, scales) = jax.lax.scan(body, None, q)
    assert np.array_equal(scales, tree["ln"]["scale"])
    kernel = q["k"]["kernel"]
    if mode == "f16":
        assert kernel.dtype == jnp.bfloat16
        full = np.asarray(kernel, np.float32)
    else:
        assert kernel.scale.shape == (5, 6) and kernel.q.shape == (5, 8, 6)
        full = np.asarray(kernel.dequantize())
    want = np.einsum("ti,lio->lto", np.asarray(x), full)
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
    # the scales differ by layer (each stacked kernel keeps its own)
    if mode != "f16":
        assert len({float(s) for s in np.asarray(kernel.scale)[:, 0]}) == 5


# ------------------------------------------------- checkpoint and config

def test_the_references_checkpoint_loads_leaf_for_leaf(checkpoint):
    """HF names `model.layers.{i}.*` -> leaves stacked [layers, in, out] in
    the checkpoint's own bfloat16; norm scales float32; `lm_head` (here an
    extra tensor) is not read."""
    out, params, _, cfg = checkpoint
    t = ref.seeded(MODEL, SEED)
    layers = params["layers"]
    assert layers["attn"]["q"]["kernel"].dtype == ref.common.BF16
    assert layers["ln1"]["scale"].dtype == np.float32
    names = {"attn": {k: f"self_attn.{k}_proj" for k in "qkvo"},
             "mlp": {k: f"mlp.{k}_proj" for k in ("gate", "up", "down")}}
    for group, leaves in names.items():
        for leaf, name in leaves.items():
            got = layers[group][leaf]["kernel"]
            assert got.shape[0] == 4 and got.flags["C_CONTIGUOUS"]
            for i in range(4):
                assert np.array_equal(
                    got[i], np.asarray(t[f"model.layers.{i}.{name}.weight"]).T)
    for leaf, name in zip(("ln1", "ln1_post", "ln2", "ln2_post"), ref.NORMS):
        for i in range(4):
            assert np.array_equal(
                layers[leaf]["scale"][i],
                np.asarray(t[f"model.layers.{i}.{name}.weight"], np.float32))
    assert np.array_equal(params["wte"], t["model.embed_tokens.weight"])
    assert np.array_equal(params["gate"]["kernel"][:, 0],
                          t["model.early_exit_gate.weight"][0])
    assert params["gate"]["bias"].shape == (1,)
    assert set(params) == {"wte", "ln_f", "gate", "layers"}
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.num_heads,
            cfg.head_dim) == (4, 3, 4, 16)
    # a published checkpoint carries an untied head: ignored, not an error
    from safetensors.numpy import load_file

    sd = load_file(str(out / "model.safetensors"))
    sd["lm_head.weight"] = np.zeros((500, 64), ref.common.BF16)
    again = convert.convert_ouro(sd, cfg)
    assert set(again) == set(params)
    del sd["model.layers.2.input_layernorm_2.weight"]
    with pytest.raises(KeyError, match="input_layernorm_2"):
        convert.convert_ouro(sd, cfg)


@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.9), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("layer_types", ["full_attention"] * 3 + ["sliding_attention"]),
    ("layer_types", ["full_attention"] * 3), ("num_key_value_heads", 2),
    ("attention_bias", True), ("total_ut_steps", 0)])
def test_from_hf_refuses_by_name_what_it_cannot_compute(key, value):
    with pytest.raises(NotImplementedError, match="ouro"):
        ouro.OuroConfig.from_hf({**MODEL, key: value})


def test_from_hf_reads_the_published_sizes():
    config = json.loads((BENCH / "configs" / "ouro-2.6b-embed.json"
                         ).read_text())
    cfg = ouro.OuroConfig.from_hf(config["model"])
    assert cfg == ouro.OuroConfig()  # the defaults ARE the published model
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.total_ut_steps, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps) == (
        2048, 48, 16, 128, 5632, 4, 49152, 1e6, 1e-6)


@pytest.mark.parametrize("model_type,family", [
    ("ouro", "ouro"), ("minicpm_sala", "sala"), ("deepseek_v3", "mla_moe"),
    ("xlm-roberta", "bert"), ("bailing_hybrid", "ling"),
    ("mimo_v2_flash", "mimo")])
def test_family_table_has_four_rows(tmp_path, model_type, family):
    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": model_type}))
    assert families.family_of_checkpoint(tmp_path).name == family
    # four rows before the `ling` family, five with it, six with `mimo`
    assert [f.name for f in families.FAMILIES] == ["bert", "mla_moe", "sala",
                                                   "ouro", "ling", "mimo"]
    assert families.family_of_config(ouro.OuroConfig()) is families.OURO


# ------------------------------------------------------ the lowered text

def _dot_generals(layers: int, steps: int, packed: bool, head_dim: int = 16,
                  length: int = 32) -> int:
    cfg = ouro.OuroConfig(vocab_size=100, hidden_size=2 * head_dim,
                          num_layers=layers, num_heads=2, head_dim=head_dim,
                          intermediate_size=64, total_ut_steps=steps)
    params = jax.eval_shape(lambda: ouro.init_params(jax.random.key(0), cfg))
    ids = jax.ShapeDtypeStruct((2, length), jnp.int32)
    if packed:
        def fn(p, i, lengths):
            seg = Segments.of_lengths(lengths, length)
            return ouro.embed_sentences(p, i, seg.real, cfg, "mean", False,
                                        seg)
        arg = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    else:
        def fn(p, i, mask):
            return ouro.embed_sentences(p, i, mask, cfg, "mean", True)
        arg = ids
    return jax.jit(fn).lower(params, ids, arg).as_text().count("dot_general")


@pytest.mark.parametrize("packed, head_dim, length, most", [
    (True, 16, 32, 12), (False, 16, 32, 12), (True, 128, 128, 14),
    (False, 128, 128, 14)])
def test_the_lowered_program_holds_one_block_whatever_the_depth(
        packed, head_dim, length, most):
    """192 block applications are ONE block in the program text: the count
    of matmuls does not grow with layers or with steps (7 projections, 2
    attention products, the gate, the pooling), on the einsum route and on
    the kernel's (the interpreted kernel's body stands in the text once,
    with its two products for a block below the diagonal and two for the
    block on it)."""
    counts = {(n, t): _dot_generals(n, t, packed, head_dim, length)
              for n in (2, 6) for t in (1, 3)}
    assert len(set(counts.values())) == 1, counts
    assert 9 <= counts[2, 1] <= most, counts


def test_a_family_table_import_brings_no_kernels_package():
    """`symbiont_tpu.ops` imports pallas (over a second, inside every
    boot's `setup_s`): the table with its fourth row and the engine load
    without it, and so does a traced ouro forward."""
    code = ("import sys, jax, symbiont_tpu.models.families as f, "
            "symbiont_tpu.engine.engine; from symbiont_tpu.models import ouro;"
            "c = ouro.OuroConfig(vocab_size=50, hidden_size=32, num_layers=2,"
            " num_heads=2, head_dim=16, intermediate_size=64, "
            "total_ut_steps=2); "
            "p = ouro.init_params(jax.random.key(0), c); "
            "import jax.numpy as jnp; "
            "ouro.embed_sentences(p, jnp.ones((1, 8), jnp.int32), "
            "jnp.ones((1, 8), jnp.int32), c); "
            "print(any(m.startswith('symbiont_tpu.ops') or "
            "m.startswith('jax.experimental.pallas') for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=180, cwd=str(BENCH.parent))
    assert out.stdout.strip() == "False", out.stdout + out.stderr[-2000:]


# ------------------------------------------------------------ the engine

def _counters(prefix):
    s = metrics.snapshot()
    return {k: v for k, v in s["counters"].items() if k.startswith(prefix)}


def test_engine_boots_the_checkpoint_embeds_and_counts(checkpoint):
    """`model_dir` alone picks the family; `embed_texts` packs chunks into
    rows, agrees with the reference in float32 and books the loop's
    series; the fused query runs on the same forward."""
    out, _, _, _ = checkpoint
    eng = TpuEngine(EngineConfig(
        model_dir=str(out), dtype="float32", quantize="none",
        length_buckets=[128], batch_buckets=[1, 4], max_batch=8))
    assert eng.family is families.OURO
    rng = np.random.default_rng(5)
    words = [f"w{i}" for i in range(400)]
    texts = [" ".join(rng.choice(words, n)) for n in (98, 18, 55, 110, 40)]
    before = _counters("engine.loop.")
    hist = 'engine.loop.expected_exit_step{service="engine"}'
    h0 = metrics.snapshot()["histograms"].get(hist, {"count": 0})["count"]
    d0 = sum(_counters("engine.embed.dispatches").values())
    with jax.default_matmul_precision("highest"):
        got = eng.embed_texts(texts)
    r = ref.Reference(MODEL, SEED, 128)
    assert _rel(got, r.embed(texts)).max() < F32_TOL
    tokens = sum(ref.token_count(t, 128) for t in texts)
    after = _counters("engine.loop.")

    def delta(name):
        return sum(v - before.get(k, 0) for k, v in after.items()
                   if k.startswith(name))

    assert delta("engine.loop.token_steps_run") == 3 * tokens
    assert delta("engine.loop.token_steps_published") == 3 * tokens
    steps = sorted(k for k in after if k.startswith("engine.loop.exit_mass"))
    assert len(steps) == 3 and 'step="2"' in steps[2]
    assert abs(delta("engine.loop.exit_mass") - tokens) < 1e-2
    assert all(after[k] - before.get(k, 0) > 0 for k in steps)
    dispatches = sum(_counters("engine.embed.dispatches").values()) - d0
    h = metrics.snapshot()["histograms"][hist]
    assert h["count"] - h0 == dispatches
    # the fused query: one chunk a row through the same forward
    corpus = np.asarray(got / np.linalg.norm(got, axis=1, keepdims=True))
    corpus = jnp.asarray(np.pad(corpus, ((0, 59), (0, 0))))
    with jax.default_matmul_precision("highest"):
        scores, idx = eng.embed_and_search(texts[2], corpus, 5, 3)
    assert int(idx[0]) == 2 and abs(float(scores[0]) - 1.0) < 5e-3


def test_note_loop_books_the_series_from_the_aux_alone():
    before = _counters("engine.loop.")
    families.OURO.note_aux(np.asarray(
        [[3.0, 1.0, 6.0, 0.0, 40.0], [0.0, 0.0, 0.0, 0.0, 0.0]], np.float32))
    after = _counters("engine.loop.")
    got = {k.split("{")[0] + ("" if "step=" not in k else
                              k[k.index("step="):k.index("step=") + 8]):
           v - before.get(k, 0) for k, v in after.items()}
    assert got["engine.loop.token_steps_run"] == 40
    assert got["engine.loop.token_steps_published"] == 40
    assert got['engine.loop.exit_massstep="2"'] == 6.0
