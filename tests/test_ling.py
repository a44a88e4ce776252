"""models/ling.py (KDA linear-attention layers beside MLA, routed experts
with group-limited routing over the share of experts a chip holds) against
the benchmark's plain reference (`benchmark/refs/ling_flash.py`, imported by
path: float32 jax.numpy at matmul precision "highest", the recurrence token
by token, attention over explicit causal masks, one passage a call, nothing
of the program in it), on seeded weights at toy widths that keep the
mechanisms: 8 layers in the published pattern (KDA with a dense SwiGLU x 2,
KDA with experts x 3, MLA at layer 5, KDA x 2), 32 experts in 8 groups of 4
of which 8 are held (a quarter: two groups), top-4 among the best 4 groups,
one shared expert, and the seeded decay-gate law that keeps some channels'
state for a whole passage.

Tolerances, each with its reason:
- float32 program against the reference, and packed rows against each
  passage alone: 2e-5 relative on rows (read: 1e-6 to 3e-6). The same maths
  in the same precision; what differs is summation order (64-token chunks
  and a triangular solve against a token-by-token state, a convolution by
  shifts against one by slices). A bfloat16 state is ~1e-2 away and a state
  carried across a passage's start ~1: both fail it (asserted below).
- the chunked rule against the token recurrence in float64: 2e-5 relative,
  the same reason, with the gate pinned at its -5 floor as well: the factored
  decays then reach e^+-40 within a sub-chunk, the float32 guard (factored
  from a sub-chunk's start they reached e^-80, and a small q or k entry
  times that fell under float32's normal range and was flushed to zero:
  2e-4 there). Both forms: the XLA one at toy width, the Pallas kernel
  (under the interpreter) at the 128-wide heads that take it.
- the kernel against the XLA form on the same inputs: 1e-5 relative (read:
  1e-7 to 2e-6), the same float32 arithmetic in another order.
"""

from __future__ import annotations

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
from refs import ling_flash as ref  # noqa: E402

from symbiont_tpu.config import EngineConfig  # noqa: E402
from symbiont_tpu.engine.engine import TpuEngine  # noqa: E402
from symbiont_tpu.models import convert, families, ling, mla_moe  # noqa: E402
from symbiont_tpu.models.bert import Segments  # noqa: E402
from symbiont_tpu.ops import delta_rule  # noqa: E402
from symbiont_tpu.ops.delta_rule import gated_delta_rule, unit_lower_inverse  # noqa: E402
from symbiont_tpu.utils.telemetry import metrics  # noqa: E402

MODEL = {
    "model_type": "bailing_hybrid", "vocab_size": 500, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_kv_heads_for_linear_attn": 0,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 16, "num_shared_experts": 1,
    "num_experts": 32, "experts_held": 8, "num_experts_per_tok": 4,
    "n_group": 8, "topk_group": 4, "first_k_dense_replace": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "layer_group_size": 6, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16,
    "rope_theta": 6000000, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
    "use_qk_norm": True, "use_mla_nope": False, "rms_norm_eps": 1e-6,
    "gated_attention_proj_granularity_type": "head_wise",
    "max_position_embeddings": 4096, "expert_swiglu_limit_list": [0] * 42,
}
SEED = 7
TOL = 2e-5
LENS = (100, 20, 150)


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """The reference's checkpoint (assumed names, bfloat16) loaded through
    the program's own converter, upcast for float32 comparisons."""
    out = tmp_path_factory.mktemp("ling_toy")
    ref.write_checkpoint(MODEL, SEED, out)
    params, cfg = convert.load_ling_model(out)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    return out, params32, cfg32


@pytest.fixture(scope="module")
def passages():
    rng = np.random.default_rng(0)
    return [rng.integers(3, MODEL["vocab_size"], n).astype(np.int32)
            for n in LENS]


@pytest.fixture(scope="module")
def want(passages):
    return np.stack(ref.Reference(MODEL, SEED, 4096).forward(
        [list(p) for p in passages]))


def _packed(seqs, L, S=8):
    ids = np.zeros((1, L), np.int32)
    ids[0, :sum(map(len, seqs))] = np.concatenate(seqs)
    seg = np.zeros((1, S), np.int32)
    seg[0, :len(seqs)] = [len(s) for s in seqs]
    return jnp.asarray(ids), Segments.of_lengths(jnp.asarray(seg), L)


def _rel(got, want):
    return (np.linalg.norm(np.asarray(got, np.float64) - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-12))


def _embed(params, cfg, seqs, L=512):
    ids, seg = _packed(seqs, L)
    with jax.default_matmul_precision("highest"):
        rows, aux = ling.embed_sentences(params, ids, seg.real, cfg,
                                         segments=seg)
    return np.asarray(rows)[0, :len(seqs)], np.asarray(aux)


# ------------------------------------------------------------- the stack

def test_full_forward_matches_reference(checkpoint, passages, want):
    _, params, cfg = checkpoint
    got, aux = _embed(params, cfg, passages)
    assert _rel(got, want).max() < TOL
    # aux: held experts' counts by expert layer, then [routed, resets]
    counts, last = aux[:-1], aux[-1]
    assert counts.shape == (6, 8)
    assert last[0] == sum(LENS) * 4 * 6  # every real token's 4 choices
    assert last[1] == len(LENS) * 7  # passages x KDA layers
    assert 0 < counts.sum() < last[0]


def test_packed_rows_equal_each_passage_alone(checkpoint, passages):
    """The delta rule's state and the convolution's window reset at a
    passage's first token, MLA stays inside the passage: a row of three
    gives each passage what a row of its own gives it."""
    _, params, cfg = checkpoint
    packed, _ = _embed(params, cfg, passages)
    alone = np.stack([_embed(params, cfg, [p], L=256)[0][0]
                      for p in passages])
    assert _rel(packed, alone).max() < TOL


def test_the_tolerance_sees_a_missing_reset(checkpoint, passages, want,
                                            monkeypatch):
    from symbiont_tpu.ops import delta_rule

    real = delta_rule.gated_delta_rule
    monkeypatch.setattr(delta_rule, "gated_delta_rule",
                        lambda q, k, v, g, b, index: real(q, k, v, g, b,
                                                          index * 0))
    _, params, cfg = checkpoint
    got, _ = _embed(params, cfg, passages)
    err = _rel(got, want)
    assert err[0] < TOL and err[1:].min() > 100 * TOL, err


# ------------------------------------------------------------ the KDA op

def _recurrence(q, k, v, g, beta, bf16_state=False):
    """One passage, token by token, in float64: q, k, v, g [n, H, d],
    beta [n, H]."""
    n, H, d = q.shape
    state = np.zeros((H, d, v.shape[-1]))
    out = np.zeros((n, H, v.shape[-1]))
    for t in range(n):
        state = np.exp(g[t])[:, :, None] * state
        kS = np.einsum("hd,hde->he", k[t], state)
        state = state + beta[t][:, None, None] * k[t][:, :, None] * (
            v[t] - kS)[:, None, :]
        if bf16_state:
            state = np.asarray(jnp.asarray(state, jnp.bfloat16), np.float64)
        out[t] = np.einsum("hd,hde->he", q[t], state)
    return out


def _inputs(lens, L, floor, seed=0, H=2, d=16, repeat=False):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((L, H, d))) / np.sqrt(d)
    k = unit(rng.standard_normal((L, H, d)))
    if repeat:  # one key over and over: a word that recurs, or padding
        k = np.broadcast_to(k[:1], k.shape).copy()
    v = rng.standard_normal((L, H, d))
    g = (np.full((L, H, d), -5.0) if floor
         else -5.0 / (1 + np.exp(-rng.normal(-3, 3, (L, H, d)))))
    beta = 1 / (1 + np.exp(-rng.standard_normal((L, H))))
    seg = np.zeros((1, 8), np.int32)
    seg[0, :len(lens)] = lens
    index = Segments.of_lengths(jnp.asarray(seg), L).index
    return q, k, v, g, beta, index


RULE_CASES = [
    ((128, 64), 192, False, False),  # passages that fill whole chunks
    ((100, 37, 5), 160, False, False),  # that do not, and padding after them
    ((100, 37, 5), 160, True, False),  # the gate at its floor on every token
    ((256,), 256, False, True),  # one key repeated: the solve stays bounded
    # a chunk (tokens 64-127) holds the end of one passage and the start of
    # the next, the first passage's state carried into it
    ((100, 92), 192, False, False),
]


def _rule_inputs(lens, L, floor, repeat, d):
    q, k, v, g, beta, index = _inputs(lens, L, floor, d=d, repeat=repeat)
    if repeat:  # slow decay and beta near 1: entries of A near beta
        g, beta = np.full_like(g, -1e-3), np.full_like(beta, 0.95)
    return q, k, v, g, beta, index


@pytest.mark.parametrize("route", ["xla", "pallas"])
@pytest.mark.parametrize("lens, L, floor, repeat", RULE_CASES)
def test_chunked_rule_matches_the_token_recurrence(route, lens, L, floor,
                                                   repeat):
    """Both forms against the float64 recurrence: the XLA form at toy
    width, the kernel (under the Pallas interpreter here) at 128-wide heads,
    the width that takes it."""
    d = 128 if route == "pallas" else 16
    assert delta_rule.path(d, d) == ("pallas" if route == "pallas"
                                     else "chunked")
    q, k, v, g, beta, index = _rule_inputs(lens, L, floor, repeat, d)
    got = np.asarray(gated_delta_rule(
        *(jnp.asarray(a[None], jnp.float32) for a in (q, k, v, g, beta)),
        index))[0]
    assert np.isfinite(got).all()
    a = 0
    for n in lens:  # over the passage: a token's output may be near zero
        want = _recurrence(*(x[a:a + n] for x in (q, k, v, g, beta)))
        err = np.linalg.norm(got[a:a + n] - want) / np.linalg.norm(want)
        assert err < TOL, (route, n, floor, repeat, err)
        a += n


@pytest.mark.parametrize("lens, L, floor, repeat", RULE_CASES)
def test_the_kernel_is_the_xla_form(lens, L, floor, repeat):
    """The kernel and the XLA form on the same 128-wide heads: the same
    float32 arithmetic in another order."""
    args = _rule_inputs(lens, L, floor, repeat, 128)
    kernel, xla = (np.asarray(rule(
        *(jnp.asarray(a[None], jnp.float32) for a in args[:5]), args[5]))[0]
        for rule in (delta_rule._kernel_rule, delta_rule._chunked_rule))
    real = sum(lens)
    err = (np.linalg.norm(kernel[:real] - xla[:real])
           / np.linalg.norm(xla[:real]))
    assert err < 1e-5, err


def test_a_bfloat16_state_is_outside_the_tolerance():
    q, k, v, g, beta, _ = _inputs((160,), 160, False)
    exact = _recurrence(q, k, v, g, beta)
    rounded = _recurrence(q, k, v, g, beta, bf16_state=True)
    assert np.linalg.norm(rounded - exact) / np.linalg.norm(exact) > 10 * TOL


@pytest.mark.parametrize("kind", ["random", "repeated_key"])
def test_the_solve_is_the_inverse(kind):
    rng = np.random.default_rng(3)
    a = np.tril(rng.standard_normal((3, 64, 64)) * 0.1, -1)
    if kind == "repeated_key":  # beta k_i.k_j with one key: all 0.95
        a = np.tril(np.full((3, 64, 64), 0.95), -1)
    got = np.asarray(unit_lower_inverse(jnp.asarray(a, jnp.float32)))
    want = np.linalg.inv(np.eye(64) + a)
    assert np.abs(got - want).max() < 1e-5


# --------------------------------------------------------------- experts

def test_group_limited_routing_matches_numpy():
    rng = np.random.default_rng(1)
    T, E, G, topg, k = 64, 32, 8, 4, 4
    cfg = mla_moe.MlaMoeConfig(n_routed_experts=E, num_experts_per_tok=k,
                               n_group=G, topk_group=topg,
                               routed_scaling_factor=2.5)
    x = rng.standard_normal((T, 16)).astype(np.float32)
    p = {"kernel": rng.standard_normal((16, E)).astype(np.float32),
         "bias": rng.standard_normal(E).astype(np.float32) * 0.1}
    idx, w = (np.asarray(a) for a in mla_moe.route(p, jnp.asarray(x), cfg))
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ p["kernel"])))
    c = s + p["bias"]
    for t in range(T):
        groups = np.sort(c[t].reshape(G, -1), axis=1)[:, -2:].sum(1)
        kept = np.argsort(-groups, kind="stable")[:topg]
        allowed = np.isin(np.arange(E) // (E // G), kept)
        pick = np.argsort(-np.where(allowed, c[t], -np.inf),
                          kind="stable")[:k]
        assert set(idx[t]) == set(pick)
        chosen = s[t, idx[t]]
        np.testing.assert_allclose(w[t], chosen / chosen.sum() * 2.5,
                                   rtol=1e-5)


def test_four_shares_add_up_to_the_uncut_layer(checkpoint):
    """Four chips of a layer, each holding a quarter of the experts (two
    whole groups): their routed parts, with the shared expert every chip
    computes counted once, are the layer with every expert held."""
    _, params, cfg = checkpoint
    E, H, k = cfg.num_experts, cfg.hidden_size, cfg.num_experts_per_tok
    share = E // 4
    rng = np.random.default_rng(2)
    full = {"router": {"kernel": rng.standard_normal((H, E)) * 0.1,
                       "bias": rng.standard_normal(E) * 0.02},
            "experts": {n: {"kernel": rng.standard_normal(
                (E, *s)) * 0.1} for n, s in
                (("gate", (H, 16)), ("up", (H, 16)), ("down", (16, H)))},
            "shared": params["layers"][2]["moe"]["shared"]}
    full = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), full)
    h = jnp.asarray(rng.standard_normal((1, 96, H)), jnp.float32)
    mask = jnp.ones((1, 96), jnp.int32)
    ln = {"scale": jnp.ones((H,), jnp.float32)}
    uncut = dataclasses.replace(cfg, experts_held=0)
    whole = mla_moe.moe_ffn(full, h, mask, ln, uncut.mla)[0]
    parts = []
    for c in range(4):
        # chip c's experts renumbered to 0..share-1: the router's columns
        # turned by whole groups, which its group choice does not see
        turn = -c * share
        mine = {"router": {"kernel": jnp.roll(full["router"]["kernel"], turn,
                                              axis=1),
                           "bias": jnp.roll(full["router"]["bias"], turn)},
                "experts": jax.tree.map(lambda a: a[c * share:(c + 1) * share],
                                        full["experts"]),
                "shared": full["shared"]}
        parts.append(mla_moe.moe_ffn(mine, h, mask, ln, cfg.mla)[0])
    shared = mla_moe.swiglu(
        mla_moe.rmsnorm(h, ln, cfg.rms_norm_eps).reshape(-1, H),
        full["shared"]).reshape(h.shape)
    summed = sum(parts) - 3 * shared
    assert k == 4 and cfg.held == share
    np.testing.assert_allclose(np.asarray(summed), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    # and one chip's part is far from the whole: the share is not renormalised
    assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-2


@pytest.mark.parametrize("S", [96, 100])
def test_the_expert_layer_in_blocks_is_the_layer_whole(checkpoint,
                                                       monkeypatch, S):
    """A row longer than `MOE_ROWS` goes through the expert layer a block at
    a time, a row that is not a whole number of blocks padded with tokens
    that are not real: rows and counts are those of the layer taken whole."""
    _, params, cfg = checkpoint
    moe = params["layers"][2]["moe"]
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, S, cfg.hidden_size)), jnp.float32)
    mask = jnp.asarray((np.arange(S) < S - 7)[None], jnp.int32)
    ln = {"scale": jnp.ones((cfg.hidden_size,), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        whole, n_whole = mla_moe.moe_ffn(moe, h, mask, ln, cfg.mla)
        monkeypatch.setattr(mla_moe, "MOE_ROWS", 32)
        blocks, n_blocks = mla_moe.moe_ffn(moe, h, mask, ln, cfg.mla)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(n_blocks), np.asarray(n_whole))


# ------------------------------------------------------------------ MLA

def test_long_rows_attend_through_the_kernel(checkpoint):
    """A packed row over 512 tokens takes ops/flash_attention.py
    `packed_attention` (under the interpreter here): 192-wide q.k heads
    padded to 256 lanes, 128-wide... at toy widths 24 -> 128 and 16 -> 128;
    each passage's MLA equals the reference's alone."""
    _, params, cfg = checkpoint
    w_prog = params["layers"][5]["attn"]
    tensors = ref.layer_weights(MODEL, 5, SEED)
    rng = np.random.default_rng(4)
    lens = (700, 324)
    x = rng.standard_normal((1, 1024, cfg.hidden_size)).astype(np.float32)
    seg = np.zeros((1, 8), np.int32)
    seg[0, :2] = lens
    segments = Segments.of_lengths(jnp.asarray(seg), 1024)
    before = metrics.flat_snapshot().get(
        'counter.attn.packed{path="flash_segments"}', 0)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mla_moe.mla_attention(
            w_prog, jnp.asarray(x), segments.real, cfg.mla, segments))[0]
        a = 0
        for n in lens:
            want = np.asarray(ref.mla(tensors, jnp.asarray(x[0, a:a + n]),
                                      MODEL))
            assert _rel(got[a:a + n], want).max() < 1e-4
            a += n
    assert metrics.flat_snapshot().get(
        'counter.attn.packed{path="flash_segments"}', 0) == before + 1


# ------------------------------------------------------- config and seams

@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("use_mla_nope", True), ("kda_safe_gate", False),
    ("use_kda_lora", True), ("num_kv_heads_for_linear_attn", 2),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("expert_swiglu_limit_list", [0, 0, 4] + [0] * 39),
])
def test_unsupported_settings_are_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        ling.LingConfig.from_hf({**MODEL, key: value})


def test_a_limit_past_the_held_layers_is_not_refused():
    cfg = ling.LingConfig.from_hf(
        {**MODEL, "expert_swiglu_limit_list": [0] * 35 + [4] * 7})
    assert cfg.num_layers == 8 and cfg.held == 8


def test_engine_takes_the_family_from_the_checkpoint(checkpoint):
    out, _, _ = checkpoint
    assert families.family_of_checkpoint(out) is families.LING
    eng = TpuEngine(EngineConfig(model_dir=str(out), length_buckets=(256,),
                                 batch_buckets=(1,), dtype="float32"))
    assert eng.family is families.LING
    snap = metrics.flat_snapshot()
    rows = eng.embed_texts(["one two three four five.", "six seven."])
    after = metrics.flat_snapshot()

    def grew(name):
        key = "counter." + name + '{service="engine"}'
        return after.get(key, 0) - snap.get(key, 0)

    assert rows.shape == (2, MODEL["hidden_size"])
    assert np.isfinite(rows).all()
    assert grew("engine.kda.state_resets") == 2 * 7
    assert grew("engine.moe.assignments_routed") > grew(
        "engine.moe.assignments") > 0


# -------------------------------------- the programs other cells compile

def _kimi_cfg():
    return mla_moe.MlaMoeConfig(
        vocab_size=1000, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, dtype="float32")


def _kimi_packed(B, L):
    cfg = _kimi_cfg()
    params = jax.eval_shape(lambda: mla_moe.init_params(jax.random.key(0),
                                                        cfg))

    def packed(p, ids, lengths):
        seg = Segments.of_lengths(lengths, L)
        return mla_moe.embed_sentences(p, ids, seg.real, cfg, "mean", True,
                                       seg)

    return jax.jit(packed).lower(
        params, jax.ShapeDtypeStruct((B, L), jnp.int32),
        jax.ShapeDtypeStruct((B, max(1, L // 8)), jnp.int32))


def _kimi_unpacked():
    cfg = _kimi_cfg()
    params = jax.eval_shape(lambda: mla_moe.init_params(jax.random.key(0),
                                                        cfg))
    return jax.jit(lambda p, ids, mask: mla_moe.embed_sentences(
        p, ids, mask, cfg)).lower(params,
                                  jax.ShapeDtypeStruct((1, 32), jnp.int32),
                                  jax.ShapeDtypeStruct((1, 32), jnp.int32))


def _ouro_kernel(B):
    from symbiont_tpu.ops.flash_attention import packed_attention

    q = jax.ShapeDtypeStruct((B, 512, 2048), jnp.bfloat16)
    tab = jax.ShapeDtypeStruct((B, 512, 128), jnp.float32)
    return jax.jit(lambda q, k, v, i, c, s: packed_attention(
        q, k, v, i, 16, rope=(c, s), interpret=True)).lower(
            q, q, q, jax.ShapeDtypeStruct((B, 512), jnp.int32), tab, tab)


# sha256 (first 12 hex digits) of each program's lowered text on the tree
# before the `ling` family (n_group 1, every expert held, no QK-norm, no
# head gate, rows of at most 128 tokens; ouro's [B, 512] kernel call), read
# with this repository's jax 0.9.0: another jax lowers other text, and the
# digests are then taken again from that tree with the same calls
PARENT = {
    "kimi_packed_32x128": ("0.9.0", "a421192be5b4"),
    "kimi_packed_8x64": ("0.9.0", "1dcf0676f2b7"),
    "kimi_packed_1x32": ("0.9.0", "088682b14563"),
    "kimi_unpacked_1x32": ("0.9.0", "c203c4cec6f6"),
    "ouro_packed_8x512": ("0.9.0", "bbd45a2227f6"),
    "ouro_packed_1x512": ("0.9.0", "f9a9ea6a5260"),
}
LOWER = {
    "kimi_packed_32x128": lambda: _kimi_packed(32, 128),
    "kimi_packed_8x64": lambda: _kimi_packed(8, 64),
    "kimi_packed_1x32": lambda: _kimi_packed(1, 32),
    "kimi_unpacked_1x32": _kimi_unpacked,
    "ouro_packed_8x512": lambda: _ouro_kernel(8),
    "ouro_packed_1x512": lambda: _ouro_kernel(1),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_kimi_and_ouro_lower_to_the_parent_text(name):
    version, digest = PARENT[name]
    assert jax.__version__ == version, "take the digests again (see PARENT)"
    text = LOWER[name]().as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == digest
