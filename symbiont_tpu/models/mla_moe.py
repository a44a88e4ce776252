"""Latent-attention (MLA) + routed/shared-expert decoder stack, run as a
sentence encoder (DeepSeek-V3 layout: Kimi-VL-A3B's language tower,
Moonlight, DeepSeek-V2/V3 at their own sizes).

Pre-norm blocks `h = x + MLA(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`, a final
RMSNorm, then the engine's pooling over attended positions — the way
LLM-backbone embedders are deployed (causal attention as published, pooled
hidden states). Pure functions over a params pytree; every kernel is stored
[in, out] (expert kernels stacked [E, in, out]) and goes through
`quant.mm` / `quant.ragged_mm` / `quant.take`, so f32, bf16, int8 and fp8 at
rest all run.

- **MLA, expanded form** (no cache on this path): q -> heads x (nope | rope);
  one latent `c` (kv_lora_rank) and one rope key shared by all heads come out
  of `kv_a`; `kv_b RMSNorm(c)` expands to per-head (k_nope | v). RoPE pairs
  dimensions (2i, 2i+1) of the rope part, frequency theta^(-2i/d): the HF
  DeepSeek code de-interleaves ([x0, x2, ..., x1, x3, ...]) and then rotates
  halves, which is the same pairing; `_deinterleave` + layers.rope does
  exactly that. Scores over sqrt(nope + rope), causal and padding masks,
  softmax in float32.
- **Routed + shared FFN**: sigmoid scores in float32, top-k of score + bias
  (`noaux_tc`, one group), weights = chosen scores normalised x
  routed_scaling_factor. The expert layer DROPS NO TOKEN and computes no
  expert for a token that did not choose it: assignments are sorted by
  expert and each projection is one grouped matmul over the stacked
  kernels (`quant.ragged_mm`: the Pallas kernel of ops/grouped_matmul.py on
  the chip, `jax.lax.ragged_dot` elsewhere). Padding positions are sent
  to no expert (they sort past the last group): their rows never reach a
  pooled row.
- `embed_sentences` returns, beside the rows, the per-layer per-expert
  counts of REAL tokens ([expert layers, E] int32): the engine's load
  counters read them at the fetch it already makes.

What models/ling.py (Ling-3.0-flash) asks of the same pieces, each off by
default, so Kimi-VL's programs lower to the text they lowered to before:
group-limited routing (`n_group`, `topk_group`); an expert layer told which
experts this chip holds (`experts_held`: the chip's share of a layer that
several chips divide, the others' part left out); an RMSNorm over each
head's q and k before RoPE (`qk_norm`) and a sigmoid gate a head on the
context (`head_gate`); and two routes chosen by shape: a packed row over
`LONG_ROW` tokens attends through the segment-masked flash kernel, and a row
over `MOE_ROWS` tokens takes the expert layer `MOE_ROWS` tokens at a time.

Not here (ROADMAP Reach A4): the absorbed decode form, a latent KV page
format, experts inside engine/lm.py, an `expert` mesh axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant
from symbiont_tpu.models.bert import POOLERS, Segments, pool_segments
from symbiont_tpu.models.layers import rmsnorm, rope, swiglu
from symbiont_tpu.utils.telemetry import metrics

Params = Any

MODEL_TYPES = ("deepseek_v3", "kimi_vl")


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    intermediate_size: int = 11264  # dense SwiGLU width (leading layers)
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 800000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    dtype: str = "bfloat16"
    # the engine sets it on every family's config; only "xla" exists here
    attn_impl: str = "xla"
    # group-limited routing: experts in `n_group` equal groups, a token's
    # choices from its best `topk_group` groups (DeepSeek-V3 `noaux_tc`)
    n_group: int = 1
    topk_group: int = 1
    # the experts this chip holds, 0..experts_held-1 (0 = all of them): a
    # choice of another expert is another chip's part of the layer
    experts_held: int = 0
    # MLA as Ling-3.0 has it: an RMSNorm over each head's whole q and k
    # before RoPE, and the context scaled by sigmoid(W x), one gate a head
    qk_norm: bool = False
    head_gate: bool = False

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @staticmethod
    def from_hf(cfg: dict) -> "MlaMoeConfig":
        """Map an HF DeepSeek-V3-layout `config.json` (Kimi-VL nests it under
        `text_config`). What this module cannot compute is refused by name,
        never approximated."""
        cfg = cfg.get("text_config", cfg)
        unsupported = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "moe_layer_freq": (1,),
            "attention_bias": (False,), "hidden_act": ("silu",),
        }
        for key, ok in unsupported.items():
            if key in cfg and cfg[key] not in ok:
                raise NotImplementedError(
                    f"mla_moe: {key}={cfg[key]!r} is not supported (only "
                    f"{ok[0]!r})")
        return MlaMoeConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=cfg["n_routed_experts"],
            n_shared_experts=cfg.get("n_shared_experts") or 0,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            first_k_dense_replace=cfg.get("first_k_dense_replace", 0),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        )


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------


def _deinterleave(x: jax.Array) -> jax.Array:
    """[..., d] -> [x0, x2, ..., x1, x3, ...]: the HF DeepSeek rotary code's
    `view(d/2, 2).transpose` before `rotate_half`."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


LONG_ROW = 512  # packed rows longer than this attend through the kernel


def _lanes(x: jax.Array) -> jax.Array:
    """[B, S, heads, d] -> [B, S, heads * d'] with d zero-padded to whole
    128-lane columns (a zero column adds nothing to a dot product)."""
    d = x.shape[-1]
    x = jnp.pad(x, ((0, 0),) * 3 + ((0, -d % 128),))
    return x.reshape(*x.shape[:2], -1)


def mla_attention(p: Params, x: jax.Array, mask: jax.Array,
                  cfg: MlaMoeConfig,
                  segments: Optional[Segments] = None) -> jax.Array:
    """x [B, S, H] (normed), mask [B, S] (1 = attended; right-padded, so an
    attended token's position is its index) -> [B, S, H]. Packed rows
    (`segments`): a token's position is its place in its sentence, and it
    attends causally inside that sentence.

    The route is chosen by shape. A packed row longer than `LONG_ROW`
    tokens that tiles (a multiple of 128) attends through
    ops/flash_attention.py `packed_attention`, q.k heads zero-padded to 256
    lanes and the value heads at 128, so no [S, S] tensor reaches HBM (at
    32 heads and 32,768 tokens the float32 scores would be 137 GB); every
    shorter row (Kimi-VL's 3-128-token sentences) takes the einsum form.
    `attn.packed{path}` says which, once per traced program."""
    B, S, _ = x.shape
    nh, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    positions = (jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
                 if segments is None else segments.position)
    q = quant.mm(x, p["q"]["kernel"]).reshape(B, S, nh, dn + dr)
    kva = quant.mm(x, p["kv_a"]["kernel"])
    c, k_rope = kva[..., :cfg.kv_lora_rank], kva[..., cfg.kv_lora_rank:]
    kv = quant.mm(rmsnorm(c, p["kv_a_ln"], cfg.rms_norm_eps),
                  p["kv_b"]["kernel"]).reshape(B, S, nh, dn + dv)
    long = segments is not None and S > LONG_ROW and S % 128 == 0
    metrics.inc("attn.packed",
                labels={"path": "flash_segments" if long else "dense"})
    if cfg.qk_norm:
        # the shared rope key joins each head's key before the norm, so
        # after it every head has a rope key of its own: k [B, S, nh, dn + dr]
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope[:, :, None, :], (B, S, nh, dr))], axis=-1)
        q = rmsnorm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_norm_eps)
        q_rope = rope(_deinterleave(q[..., dn:]), positions, cfg.rope_theta)
        k_rope = rope(_deinterleave(k[..., dn:]), positions, cfg.rope_theta)
    else:
        k = kv  # the heads' nope keys; one rope key for all heads
        q_rope = rope(_deinterleave(q[..., dn:]), positions, cfg.rope_theta)
        k_rope = rope(_deinterleave(k_rope)[:, :, None, :], positions,
                      cfg.rope_theta)[:, :, 0, :]
    q_nope, k_nope = q[..., :dn], k[..., :dn]
    if long:
        from symbiont_tpu.ops.flash_attention import packed_attention

        if k_rope.ndim == 3:
            k_rope = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, nh, dr))
        ctx = packed_attention(
            _lanes(jnp.concatenate([q_nope, q_rope], axis=-1)),
            _lanes(jnp.concatenate([k_nope, k_rope], axis=-1)),
            _lanes(kv[..., dn:]), segments.index, nh,
            scale=1.0 / math.sqrt(dn + dr))
        ctx = ctx.reshape(B, S, nh, -1)[..., :dv]
    else:
        rope_keys = "bkhd" if k_rope.ndim == 4 else "bkd"  # a head's / shared
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                  + jnp.einsum(f"bqhd,{rope_keys}->bhqk", q_rope, k_rope))
        keep = (jnp.tril(jnp.ones((S, S), bool))[None, None]
                & ((mask[:, None, None, :] > 0) if segments is None
                   else segments.same[:, None]))
        scores = jnp.where(keep, scores.astype(jnp.float32)
                           / math.sqrt(dn + dr), -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:])
    if cfg.head_gate:
        ctx = ctx * jax.nn.sigmoid(
            quant.mm(x, p["gate"]["kernel"]))[..., None].astype(ctx.dtype)
    return quant.mm(ctx.reshape(B, S, nh * dv), p["o"]["kernel"])


# ---------------------------------------------------------------------------
# Routed + shared experts
# ---------------------------------------------------------------------------


def route(p: Params, x32: jax.Array, cfg: MlaMoeConfig):
    """x32 [T, H] float32 (normed) -> (idx [T, k] int32, weights [T, k]
    float32). Scores in float32 at full matmul precision: top-k is discrete,
    and a score off in the third digit picks another expert. With
    `n_group` > 1 a group scores the sum of its best two choice scores and
    a token chooses among the experts of its best `topk_group` groups. The
    weights are normalised over all k choices, wherever their experts
    live."""
    kernel = p["kernel"]
    if quant.is_quantized(kernel):
        kernel = kernel.dequantize()
    s = jax.nn.sigmoid(jnp.dot(x32, kernel.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    choice = s + p["bias"].astype(jnp.float32)
    if cfg.n_group > 1:
        T, E = s.shape
        per = E // cfg.n_group
        best = jax.lax.top_k(choice.reshape(T, cfg.n_group, per), 2)[0]
        _, groups = jax.lax.top_k(best.sum(-1), cfg.topk_group)
        kept = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], groups].set(True)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob and cfg.num_experts_per_tok > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def routed_experts(p: Params, x: jax.Array, idx: jax.Array, w: jax.Array,
                   real: jax.Array, cfg: MlaMoeConfig):
    """Sum over each token's chosen experts of weight x SwiGLU_e(x).

    x [T, H]; idx / w [T, k]; real [T] bool. Returns (y [T, H], counts [E]
    int32 = real tokens per expert). The T*k assignments are sorted by
    expert (padding's sort past the last expert, into no group), each
    projection is ONE grouped matmul over the stacked kernels, and the
    results are gathered back token-major: no capacity, nothing dropped.

    Holding `cfg.held` < `n_routed_experts` experts (the stacked kernels
    are experts 0..held-1), E is the held count: a choice of an expert held
    elsewhere sorts past the last group as padding does, and the sum is
    this chip's part of the layer, its weights as the router gave them."""
    T, k = idx.shape
    E = cfg.held
    here = (real[:, None] if E == cfg.n_routed_experts
            else real[:, None] & (idx < E))
    flat = jnp.where(here, idx, E).reshape(T * k)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    counts = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
    row_group = jnp.minimum(sorted_e, E - 1)
    xs = x[order // k]  # [T*k, H], rows of one expert contiguous
    ex = p["experts"]
    gate = quant.ragged_mm(xs, ex["gate"]["kernel"], counts, row_group)
    up = quant.ragged_mm(xs, ex["up"]["kernel"], counts, row_group)
    ys = quant.ragged_mm(jax.nn.silu(gate) * up, ex["down"]["kernel"],
                         counts, row_group)
    # rows past the groups (padding's) hold whatever the kernel left there
    ys = jnp.where((sorted_e < E)[:, None], ys, 0)
    inverse = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    y = jnp.einsum("tkh,tk->th", ys[inverse].reshape(T, k, -1)
                   .astype(jnp.float32), w)
    return y.astype(x.dtype), counts


MOE_ROWS = 8192  # tokens of a longer row the expert layer takes at a time


def _moe_rows(p: Params, x32: jax.Array, real: jax.Array, dtype,
              cfg: MlaMoeConfig):
    """x32 [T, H] float32 (normed) -> (y [T, H] in `dtype`, counts [E])."""
    x = x32.astype(dtype)
    with jax.named_scope("router"):
        idx, w = route(p["router"], x32, cfg)
    with jax.named_scope("experts"):
        y, counts = routed_experts(p, x, idx, w, real, cfg)
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            y = y + swiglu(x, p["shared"])
    return y, counts


def moe_ffn(p: Params, h: jax.Array, mask: jax.Array, ln: Params,
            cfg: MlaMoeConfig):
    """h [B, S, H] (the residual, not normed) -> (FFN(RMSNorm(h)), counts).
    A row longer than `MOE_ROWS` tokens goes `MOE_ROWS` tokens at a time
    (the tokens padded to whole blocks, the padding not real): over a
    32,768-token row at top-8 the gathered rows alone are 1.3 GB at a hidden
    size of 2,560."""
    B, S, H = h.shape
    real = (mask > 0).reshape(B * S)
    if S > MOE_ROWS:
        def some(xs):
            rows, real = xs
            x32 = rmsnorm(rows.astype(jnp.float32), ln, cfg.rms_norm_eps)
            return _moe_rows(p, x32, real, h.dtype, cfg)

        rows, pad = h.reshape(B * S, H), -(B * S) % MOE_ROWS
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
            real = jnp.pad(real, (0, pad))
        y, counts = jax.lax.map(some, (rows.reshape(-1, MOE_ROWS, H),
                                       real.reshape(-1, MOE_ROWS)))
        return y.reshape(-1, H)[:B * S].reshape(B, S, H), counts.sum(0)
    x32 = rmsnorm(h.astype(jnp.float32), ln, cfg.rms_norm_eps).reshape(B * S, H)
    y, counts = _moe_rows(p, x32, real, h.dtype, cfg)
    return y.reshape(B, S, H), counts


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


def encode(params: Params, input_ids: jax.Array, attention_mask: jax.Array,
           cfg: MlaMoeConfig, segments: Optional[Segments] = None):
    """-> (last hidden state after the final norm [B, S, H] in cfg.dtype,
    counts [expert layers, E] int32)."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embeddings"):
        x = quant.take(params["wte"], input_ids, dtype).astype(dtype)
    # every layer's attention has one shape, and so has every expert layer:
    # a `jax.jit` of this call's own traces and lowers each ONCE and calls
    # it per layer (the compiler inlines the calls: the program is the
    # same). A warmed bucket is traced and lowered at every boot, inside
    # `setup_s`, and the expert layers are most of that.
    attention = jax.jit(lambda p, ln, x, mask, segments: mla_attention(
        p, rmsnorm(x, ln, cfg.rms_norm_eps), mask, cfg, segments))
    experts = jax.jit(lambda p, x, mask, ln: moe_ffn(p, x, mask, ln, cfg))
    counts = []
    for layer in quant.cast_params(params["layers"], dtype):
        with jax.named_scope("mla"):
            x = x + attention(layer["attn"], layer["ln1"], x, attention_mask,
                              segments)
        if "moe" in layer:
            y, c = experts(layer["moe"], x, attention_mask, layer["ln2"])
            counts.append(c)
        else:
            with jax.named_scope("dense_ffn"):
                y = swiglu(rmsnorm(x, layer["ln2"], cfg.rms_norm_eps),
                           layer["mlp"])
        x = x + y
    x = rmsnorm(x, quant.cast_params(params["ln_f"], dtype), cfg.rms_norm_eps)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.n_routed_experts), jnp.int32))
    return x, counts


def embed_sentences(params: Params, input_ids: jax.Array,
                    attention_mask: jax.Array, cfg: MlaMoeConfig,
                    pooling: str = "mean", normalize: bool = False,
                    segments: Optional[Segments] = None):
    """Decoder stack + pooling -> ([B, H] float32 sentence embeddings, or
    [B, S, H] for packed rows: `segments`, and `attention_mask` its `real`;
    counts [expert layers, E] int32 of real tokens per expert)."""
    hidden, counts = encode(params, input_ids, attention_mask, cfg, segments)
    with jax.named_scope("pool"):
        pooled = (POOLERS[pooling](hidden, attention_mask) if segments is None
                  else pool_segments(hidden, segments, pooling))
        if normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
    return pooled, counts


# ---------------------------------------------------------------------------
# Init (random params for tests; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: MlaMoeConfig) -> Params:
    """Random N(0, 0.02) kernels, unit norm scales, small router biases;
    float32 storage."""
    keys = iter(jax.random.split(key, 4 + cfg.num_layers * 16))
    H, E = cfg.hidden_size, cfg.n_routed_experts
    nh, dn, dr, dv, r = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim,
                         cfg.kv_lora_rank)

    def dense(*shape):
        return {"kernel": jax.random.normal(next(keys), shape,
                                            jnp.float32) * 0.02}

    def ln(n: int) -> dict:
        return {"scale": jnp.ones((n,), jnp.float32)}

    def mlp(width: int, *stack) -> dict:
        return {"gate": dense(*stack, H, width), "up": dense(*stack, H, width),
                "down": dense(*stack, width, H)}

    layers = []
    for i in range(cfg.num_layers):
        layer = {"ln1": ln(H), "ln2": ln(H), "attn": {
            "q": dense(H, nh * (dn + dr)), "kv_a": dense(H, r + dr),
            "kv_a_ln": ln(r), "kv_b": dense(r, nh * (dn + dv)),
            "o": dense(nh * dv, H)}}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            moe = {"router": {**dense(H, E), "bias": jax.random.normal(
                       next(keys), (E,), jnp.float32) * 0.02},
                   "experts": mlp(cfg.moe_intermediate_size, E)}
            if cfg.n_shared_experts:
                moe["shared"] = mlp(cfg.moe_intermediate_size
                                    * cfg.n_shared_experts)
            layer["moe"] = moe
        layers.append(layer)
    return {"wte": jax.random.normal(next(keys), (cfg.vocab_size, H),
                                     jnp.float32) * 0.02,
            "ln_f": ln(H), "layers": layers}
