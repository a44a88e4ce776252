"""A looped decoder stack (Ouro's layout: a LoopLM), run as a chunk encoder.

ONE stack of `num_layers` blocks is applied `total_ut_steps` times over the
same weights; the final RMSNorm closes EVERY step and its output is what the
next step starts from:

    h = E[ids]
    for t in range(total_ut_steps):
        for layer in layers:                      # the same weights every t
            h = h + RMSNorm(Attn(RMSNorm(h)))     # sandwich: a norm before
            h = h + RMSNorm(SwiGLU(RMSNorm(h)))   # AND after each sub-layer
        h = RMSNorm(h)
        lam_t = sigmoid(w_gate . h + b_gate)      # the exit gate, per token

then the engine's pooling over a chunk's tokens (causal attention as
published, pooled hidden states; the output head is not instantiated).
Attention is full softmax attention over 16 heads (no grouped keys, no
qk-norm, no bias), RoPE on q and k over the whole head (half-split pairing).
The exit distribution `p_t = lam_t prod_{j<t}(1 - lam_j)` (the last step
takes what is left) is computed in every step; with the published
`early_exit_threshold` 1 every token runs every step and the state pooled is
the last step's. A threshold under 1 (per-token exit inside a batched
forward) is refused by `OuroConfig.from_hf`, not approximated.

**The stack is a `lax.scan` over layers STACKED leaf by leaf on a leading
axis, inside a `lax.scan` over steps**: 4 x 48 = 192 block applications are
one block in the lowered program, traced, lowered and compiled once (every
other family unrolls its layers in Python: the deepest such stack is 12).
Kernels are stored [layers, in, out] and go through `quant.mm` on the slice
a scan hands the body (a stacked `QuantTensor` slices with its scales), so
f32, bf16, int8 and fp8 at rest all run.

**The residual stream between blocks is float32** (`RESIDUAL_DTYPE`; the
sub-layers compute in `cfg.dtype`, bfloat16 on the chip: matmuls with float32
accumulation, norm statistics, softmax, gate and pooling in float32). 192
sub-layer outputs are added to a stream that is rounded at every addition
if it is bfloat16. Read on the chip at the cell's size (PERF.md section 6,
PR 34): rows stand 1.24 % from the float32 reference with a float32 stream
and 1.70 % with a bfloat16 one (worst row 1.66 % / 2.29 %), for 1.0 % of the
rate (33.50 against 33.82 chunks a second); where the seeded loop amplifies
rounding (the benchmark's first weight law) the two stood at 12.3 % and
24.0 %. It is the configuration's stated precision, not a switch.

Every row is handled as packed (`segments`, models/bert.py): positions
restart at a chunk's first token and a token attends causally inside its
own chunk. Handed only a mask (the fused query), the row is one chunk.

`embed_sentences` returns, beside the rows, `aux` `[rows, steps + 1]`
float32: per row the exit mass of each step (sum over the row's real tokens
of `p_t`; a row's steps add up to its real tokens) and, last, the
token-steps the loop ran (real tokens counted once per step run).

Not here (ROADMAP Reach A4): a KV slot per (step, layer) in the page pool
and a per-token exit decision while decoding — the generation path does not
run this family.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant
from symbiont_tpu.models.bert import Segments, pool_segments
from symbiont_tpu.models.layers import rmsnorm, rope, rope_tables, swiglu
from symbiont_tpu.utils.telemetry import metrics

Params = Any

MODEL_TYPES = ("ouro",)
RESIDUAL_DTYPE = jnp.float32  # the stream the 192 sub-layer outputs add into


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    total_ut_steps: int = 4
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    dtype: str = "bfloat16"
    # the engine sets it on every family's config; only "xla" exists here
    attn_impl: str = "xla"

    @staticmethod
    def from_hf(cfg: dict) -> "OuroConfig":
        """Map an `ouro` `config.json`. What this module cannot compute is
        refused by name, never approximated."""
        unsupported = {
            "hidden_act": ("silu",), "rope_scaling": (None,),
            "use_sliding_window": (False,), "attention_bias": (False,),
            "mlp_bias": (False,),
        }
        for key, ok in unsupported.items():
            if key in cfg and cfg[key] not in ok:
                raise NotImplementedError(
                    f"ouro: {key}={cfg[key]!r} is not supported (only "
                    f"{ok[0]!r})")
        layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
        kinds = cfg.get("layer_types") or ["full_attention"] * layers
        if len(kinds) != layers or set(kinds) != {"full_attention"}:
            raise NotImplementedError(
                f"ouro: layer_types must name {layers} layers, each "
                f"'full_attention'; got {len(kinds)} of {sorted(set(kinds))}")
        if cfg.get("num_key_value_heads", heads) != heads:
            raise NotImplementedError(
                "ouro: num_key_value_heads != num_attention_heads is not "
                "supported (the attention written has one key head a query "
                "head)")
        threshold = float(cfg.get("early_exit_threshold", 1.0))
        if threshold < 1.0:
            raise NotImplementedError(
                f"ouro: early_exit_threshold={threshold!r} is not supported "
                "(only 1: every token runs every step; per-token exit inside "
                "a batched forward is not written)")
        steps = int(cfg.get("total_ut_steps", 1))
        if steps < 1:
            raise NotImplementedError(f"ouro: total_ut_steps={steps!r}")
        return OuroConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=layers, num_heads=heads,
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            intermediate_size=cfg["intermediate_size"],
            total_ut_steps=steps,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        )


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------


def attention(p: Params, x: jax.Array, segments: Segments,
              cfg: OuroConfig) -> jax.Array:
    """x [B, L, H] (normed) -> [B, L, H]: causal softmax attention inside
    each chunk of the packed rows, RoPE counted from the chunk's start.

    Scores, mask, softmax and context are ONE Pallas kernel
    (ops/flash_attention.py `packed_attention`: the `[B, heads, L, L]`
    float32 scores never leave the chip) where the shapes tile, i.e. a head
    is whole 128-lane column blocks and a row whole 128-token blocks; the
    einsum form everywhere else (toy widths, an 8-token row). Both compute
    what the configuration states: operands in `x.dtype`, float32 scores,
    softmax and accumulation. `attn.packed{path}` says which, once per
    traced program."""
    B, L, _ = x.shape
    nh, d = cfg.num_heads, cfg.head_dim
    q, k, v = (quant.mm(x, p[n]["kernel"]).reshape(B, L, nh, d)
               for n in "qkv")
    fused = d % 128 == 0 and L % 128 == 0
    metrics.inc("attn.packed",
                labels={"path": "flash_segments" if fused else "dense"})
    if fused:
        # imported here: pallas costs every process that loads a family
        # table over a second of its boot
        from symbiont_tpu.ops.flash_attention import packed_attention

        ctx = packed_attention(
            *(t.reshape(B, L, nh * d) for t in (q, k, v)), segments.index, nh,
            rope=rope_tables(segments.position, d, cfg.rope_theta))
    else:
        q = rope(q, segments.position, cfg.rope_theta)
        k = rope(k, segments.position, cfg.rope_theta)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        keep = (jnp.tril(jnp.ones((L, L), bool))[None, None]
                & segments.same[:, None])
        scores = jnp.where(keep, scores / math.sqrt(d), -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return quant.mm(ctx.reshape(B, L, nh * d), p["o"]["kernel"])


def block(layer: Params, h: jax.Array, segments: Segments,
          cfg: OuroConfig) -> jax.Array:
    """One sandwich-norm block on the residual stream h [B, L, H]
    (`RESIDUAL_DTYPE`); `layer` is one layer's slice of the stacked tree."""
    dtype, eps = jnp.dtype(cfg.dtype), cfg.rms_norm_eps
    layer = quant.cast_params(layer, dtype)
    with jax.named_scope("loop_attn"):
        a = attention(layer["attn"], rmsnorm(h, layer["ln1"], eps).astype(dtype),
                      segments, cfg)
        h = h + rmsnorm(a.astype(h.dtype), layer["ln1_post"], eps)
    with jax.named_scope("loop_ffn"):
        m = swiglu(rmsnorm(h, layer["ln2"], eps).astype(dtype), layer["mlp"])
        h = h + rmsnorm(m.astype(h.dtype), layer["ln2_post"], eps)
    return h


def run_stack(layers: Params, h: jax.Array, segments: Segments,
              cfg: OuroConfig) -> jax.Array:
    """Every layer of the stacked tree once, in order: a scan whose body is
    ONE block, handed each layer's slice of every leaf."""
    def body(h, layer):
        return block(layer, h, segments, cfg), None

    return jax.lax.scan(body, h, layers)[0]


def step_end(params: Params, h: jax.Array, cfg: OuroConfig):
    """What closes a step: the final norm (its output is the next step's
    input and, after the last step, the state pooled) and the exit gate.
    -> (normed h, lam [B, L] float32)."""
    with jax.named_scope("loop_gate"):
        h = rmsnorm(h, params["ln_f"], cfg.rms_norm_eps)
        kernel = params["gate"]["kernel"]
        if quant.is_quantized(kernel):
            kernel = kernel.dequantize()
        logit = jnp.dot(h.astype(jnp.float32), kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)[..., 0]
        lam = jax.nn.sigmoid(logit + params["gate"]["bias"].astype(
            jnp.float32)[0])
    return h, lam


def exit_distribution(lam: jax.Array) -> jax.Array:
    """lam [T, ...] (each step's gate) -> p [T, ...], the probability of
    leaving after step t: `lam_t prod_{j<t}(1 - lam_j)`, the last step
    taking what is left, so the steps sum to 1."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


# ---------------------------------------------------------------------------
# The loop
# ---------------------------------------------------------------------------


def one_chunk(attention_mask: jax.Array) -> Segments:
    """An unpacked row (right-padded, as the fused query sends it) as a
    packed row of one chunk."""
    return Segments.of_lengths(
        attention_mask.sum(1, dtype=jnp.int32)[:, None],
        attention_mask.shape[1])


def encode(params: Params, input_ids: jax.Array, segments: Segments,
           cfg: OuroConfig):
    """-> (the last step's state after its final norm [B, L, H] float32,
    p [steps, B, L] float32 the exit distribution)."""
    with jax.named_scope("embeddings"):
        h = quant.take(params["wte"], input_ids).astype(RESIDUAL_DTYPE)

    def step(h, _):
        h, lam = step_end(params, run_stack(params["layers"], h, segments,
                                            cfg), cfg)
        return h, lam

    h, lam = jax.lax.scan(step, h, None, length=cfg.total_ut_steps)
    with jax.named_scope("loop_gate"):
        p = exit_distribution(lam)
    return h, p


def embed_sentences(params: Params, input_ids: jax.Array,
                    attention_mask: jax.Array, cfg: OuroConfig,
                    pooling: str = "mean", normalize: bool = False,
                    segments: Optional[Segments] = None):
    """Looped stack + pooling -> ([B, H] float32 chunk embeddings, or
    [B, S, H] for packed rows: `segments`, and `attention_mask` its `real`;
    aux [B, steps + 1] float32: exit mass by step, token-steps run)."""
    packed = segments is not None
    if not packed:
        segments = one_chunk(attention_mask)
    hidden, p = encode(params, input_ids, segments, cfg)
    with jax.named_scope("pool"):
        # early_exit_threshold 1: the cumulative exit probability reaches it
        # at the last step for every token, whose state is the one used
        pooled = pool_segments(hidden, segments, pooling)
        if not packed:
            pooled = pooled[:, 0]
        if normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
    with jax.named_scope("loop_gate"):
        real = segments.real.astype(jnp.float32)
        mass = (p * real).sum(-1).T  # [B, steps]
        ran = real.sum(-1, keepdims=True) * p.shape[0]
    return pooled, jnp.concatenate([mass, ran], axis=1)


# ---------------------------------------------------------------------------
# Init (random params for tests; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: OuroConfig) -> Params:
    """Random N(0, 0.02) kernels, norm scales 1 + N(0, 0.1), a small gate;
    float32 storage, the layers stacked on a leading axis."""
    keys = iter(jax.random.split(key, 16))
    H, I, n = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    wide = cfg.num_heads * cfg.head_dim

    def dense(*shape):
        return {"kernel": jax.random.normal(next(keys), shape,
                                            jnp.float32) * 0.02}

    def ln(*shape):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                       jnp.float32)}

    return {
        "wte": jax.random.normal(next(keys), (cfg.vocab_size, H),
                                 jnp.float32) * 0.02,
        "ln_f": ln(H),
        "gate": {**dense(H, 1), "bias": jnp.zeros((1,), jnp.float32)},
        "layers": {
            "ln1": ln(n, H), "ln1_post": ln(n, H),
            "ln2": ln(n, H), "ln2_post": ln(n, H),
            "attn": {"q": dense(n, H, wide), "k": dense(n, H, wide),
                     "v": dense(n, H, wide), "o": dense(n, wide, H)},
            "mlp": {"gate": dense(n, H, I), "up": dense(n, H, I),
                    "down": dense(n, I, H)}},
    }
