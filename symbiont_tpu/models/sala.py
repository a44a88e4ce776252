"""Sparse + linear attention hybrid decoder stack (MiniCPM-SALA's layout),
run as a passage encoder.

MiniCPM's muP form: `x0 = scale_emb * E[ids]`; per layer, with
`a = scale_depth / sqrt(depth_layers)` (the PUBLISHED depth under the root,
whatever slice of the stack is held),

    h = x + a * Mixer(RMSNorm(x))        y = h + a * SwiGLU(RMSNorm(h))

a final RMSNorm, then the engine's pooling over a passage's tokens (causal
mixers as published, pooled hidden states; the output head is not
instantiated). `mixer_types[i]` names layer i's mixer:

- **`lightning-attn`**: q, k, v = W x; per-head RMSNorm on q and k; RoPE on
  both (half-split pairing, positions restart per passage); per head a
  decayed outer-product state, `S_t = lam_h S_{t-1} + k_t^T v_t`,
  `o_t = q_t S_t / sqrt(d)`, `lam_h = exp(-2^(-8(h+1)/H))`; RMSNorm over the
  joined heads, times `sigmoid(W_g x)`; W_o. Computed in chunks
  (ops/linear_attention.py).
- **`minicpm4`**: grouped-query attention without positional rotation,
  per-head RMSNorm on q and k, over a per-token set of key blocks chosen
  through compressed keys (InfLLM-V2; ops/block_sparse_attention.py);
  `sigmoid(W_g x)` on the heads' output; W_o. A passage of at most
  `sparse.dense_len` tokens attends to every causal key.

Every row is handled as packed (`segments`, models/bert.py): the state
resets and positions restart at a passage's first token, selection and the
local window stay inside the passage. Handed only a mask (the fused query),
the row is one passage. Neither mixer builds anything [L, L]; the
feed-forward runs over chunks of rows so a 65,536-token dispatch does not
hold two [tokens, intermediate] activations. Every projection goes through
`quant.mm`, so f32, bf16, int8 and fp8 at rest all run.

`embed_sentences` returns, beside the rows, `[B, sparse layers, 2]` int32
per row: keys attended (mean over the KV groups) and keys a causal
attention would have read.

Not here (ROADMAP Reach A4): recurrent state in the page pool, state
snapshots for the radix cache, selection inside paged attention — the
generation path does not run this family.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant
from symbiont_tpu.models.bert import Segments, pool_segments
from symbiont_tpu.models.layers import rmsnorm, rope, swiglu

Params = Any

MODEL_TYPES = ("minicpm_sala",)
SPARSE, LINEAR = "minicpm4", "lightning-attn"
FFN_ROWS = 8192  # rows of the feed-forward computed at a time


@dataclass(frozen=True)
class SparseConfig:
    """InfLLM-V2's sizes (ops/block_sparse_attention.py says what each
    does); the defaults are the MiniCPM4 family's published values."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self) -> None:
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.kernel_size > self.block_size):
            raise ValueError("sparse_config: kernel_size and block_size must "
                             "be multiples of kernel_stride, and a kernel no "
                             "longer than a block")
        forced = self.init_blocks + self.window_size // self.block_size + 1
        if forced > self.topk:
            raise ValueError(f"sparse_config: init and window blocks "
                             f"({forced}) exceed topk ({self.topk})")


@dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_layers: int = 32
    mixer_types: Tuple[str, ...] = (SPARSE,) + (LINEAR,) * 3
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    intermediate_size: int = 16384
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    depth_layers: int = 32  # the published depth: the residual scale's root
    max_position_embeddings: int = 524288
    sparse: SparseConfig = SparseConfig()
    dtype: str = "bfloat16"
    # the engine sets it on every family's config; only "xla" exists here
    attn_impl: str = "xla"

    @staticmethod
    def from_hf(cfg: dict) -> "SalaConfig":
        """Map a `minicpm_sala` `config.json`. What this module cannot
        compute is refused by name, never approximated. `sparse_config`
        (absent from the published file: the MiniCPM4 family's values are
        the defaults) and `depth_layers` (absent: the file's own depth) are
        this program's keys."""
        unsupported = {
            "attention_bias": (False,), "hidden_act": ("silu",),
            "attn_use_rope": (False,), "lightning_use_rope": (True,),
            "qk_norm": (True,), "use_output_gate": (True,),
            "use_output_norm": (True,), "attn_use_output_gate": (True,),
            "lightning_scale": ("1/sqrt(d)",), "rope_scaling": (None,),
        }
        for key, ok in unsupported.items():
            if key in cfg and cfg[key] not in ok:
                raise NotImplementedError(
                    f"sala: {key}={cfg[key]!r} is not supported (only "
                    f"{ok[0]!r})")
        layers = cfg["num_hidden_layers"]
        mixers = tuple(cfg["mixer_types"])
        if len(mixers) != layers or set(mixers) - {SPARSE, LINEAR}:
            raise NotImplementedError(
                f"sala: mixer_types must name {layers} layers, each "
                f"{SPARSE!r} or {LINEAR!r}; got {mixers!r}")
        if cfg.get("lightning_nkv", cfg["lightning_nh"]) != cfg["lightning_nh"]:
            raise NotImplementedError(
                "sala: lightning_nkv != lightning_nh is not supported")
        return SalaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=layers, mixer_types=mixers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], lightning_heads=cfg["lightning_nh"],
            lightning_head_dim=cfg["lightning_head_dim"],
            intermediate_size=cfg["intermediate_size"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            scale_emb=float(cfg.get("scale_emb", 1.0)),
            scale_depth=float(cfg.get("scale_depth", 1.0)),
            depth_layers=cfg.get("depth_layers", layers),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            sparse=SparseConfig(**cfg.get("sparse_config", {})),
        )


def decay_slopes(heads: int) -> jax.Array:
    """-log lam_h = 2^(-8 (h + 1) / heads): the ALiBi slope law Lightning
    Attention takes its decays from."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


# ---------------------------------------------------------------------------
# Mixers
# ---------------------------------------------------------------------------


def _heads(x, kernel, norm, heads: int, eps: float):
    """`W x` as [B, L, heads, d], per-head RMSNorm where `norm` is given."""
    y = quant.mm(x, kernel["kernel"])
    y = y.reshape(*x.shape[:2], heads, -1)
    return y if norm is None else rmsnorm(y, norm, eps)


def sparse_mixer(p: Params, x: jax.Array, segments: Segments,
                 cfg: SalaConfig):
    """x [B, L, H] (normed) -> (out [B, L, H], counts [B, 2] int32)."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("proj"):
        q = _heads(x, p["q"], p["q_norm"], cfg.num_heads, eps)
        k = _heads(x, p["k"], p["k_norm"], cfg.num_kv_heads, eps)
        v = _heads(x, p["v"], None, cfg.num_kv_heads, eps)
        gate = jax.nn.sigmoid(quant.mm(x, p["gate"]["kernel"]))
    # here and not at the top: the `ops` package imports pallas, a second
    # and a half that a BERT process never needs (models/quant.py says so)
    from symbiont_tpu.ops.block_sparse_attention import block_sparse_attention

    ctx, counts = block_sparse_attention(
        q, k, v, segments.index, segments.position, segments.lengths,
        cfg.sparse)
    with jax.named_scope("proj"):
        out = quant.mm(ctx.reshape(gate.shape) * gate, p["o"]["kernel"])
    return out, counts


def lightning_mixer(p: Params, x: jax.Array, segments: Segments,
                    cfg: SalaConfig) -> jax.Array:
    """x [B, L, H] (normed) -> [B, L, H]."""
    from symbiont_tpu.ops.linear_attention import lightning_attention

    eps, nh = cfg.rms_norm_eps, cfg.lightning_heads
    with jax.named_scope("proj"):
        q = _heads(x, p["q"], p["q_norm"], nh, eps)
        k = _heads(x, p["k"], p["k_norm"], nh, eps)
        v = _heads(x, p["v"], None, nh, eps)
        gate = jax.nn.sigmoid(quant.mm(x, p["gate"]["kernel"]))
    with jax.named_scope("lightning"):
        q = rope(q, segments.position, cfg.rope_theta)
        k = rope(k, segments.position, cfg.rope_theta)
        scale = 1.0 / math.sqrt(cfg.lightning_head_dim)
        o = lightning_attention((q * scale).astype(q.dtype), k, v,
                                segments.index, decay_slopes(nh))
        o = rmsnorm(o.reshape(gate.shape), p["o_norm"], eps)
    with jax.named_scope("proj"):
        return quant.mm(o * gate, p["o"]["kernel"])


def _ffn(mlp: Params, ln: Params, h: jax.Array, cfg: SalaConfig):
    """SwiGLU(RMSNorm(h)) over h [B, L, H], `FFN_ROWS` rows at a time."""
    B, L, H = h.shape
    rows = h.reshape(B * L, H)

    def some(r):
        return swiglu(rmsnorm(r, ln, cfg.rms_norm_eps), mlp)

    if B * L <= FFN_ROWS or (B * L) % FFN_ROWS:
        return some(rows).reshape(B, L, H)
    return jax.lax.map(some, rows.reshape(-1, FFN_ROWS, H)).reshape(B, L, H)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


def one_passage(attention_mask: jax.Array) -> Segments:
    """An unpacked row (right-padded, as the fused query sends it) as a
    packed row of one passage."""
    return Segments.of_lengths(
        attention_mask.sum(1, dtype=jnp.int32)[:, None],
        attention_mask.shape[1])


def encode(params: Params, input_ids: jax.Array, segments: Segments,
           cfg: SalaConfig):
    """-> (last hidden state after the final norm [B, L, H] in cfg.dtype,
    counts [B, sparse layers, 2] int32)."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embeddings"):
        x = (quant.take(params["wte"], input_ids, dtype)
             * cfg.scale_emb).astype(dtype)
    a = cfg.scale_depth / math.sqrt(cfg.depth_layers)
    # each kind of layer has one shape: traced and lowered once, called per
    # layer (models/mla_moe.py `encode` says why)
    mixers = {
        SPARSE: jax.jit(lambda p, ln, x, seg: sparse_mixer(
            p, rmsnorm(x, ln, cfg.rms_norm_eps), seg, cfg)),
        LINEAR: jax.jit(lambda p, ln, x, seg: (lightning_mixer(
            p, rmsnorm(x, ln, cfg.rms_norm_eps), seg, cfg), None)),
    }
    ffn = jax.jit(lambda mlp, ln, h: _ffn(mlp, ln, h, cfg))
    counts = []
    for kind, layer in zip(cfg.mixer_types,
                           quant.cast_params(params["layers"], dtype)):
        y, c = mixers[kind](layer["mixer"], layer["ln1"], x, segments)
        if c is not None:
            counts.append(c)
        x = x + (a * y).astype(dtype)
        with jax.named_scope("dense_ffn"):
            x = x + (a * ffn(layer["mlp"], layer["ln2"], x)).astype(dtype)
    x = rmsnorm(x, quant.cast_params(params["ln_f"], dtype), cfg.rms_norm_eps)
    counts = (jnp.stack(counts, axis=1) if counts
              else jnp.zeros((x.shape[0], 0, 2), jnp.int32))
    return x, counts


def embed_sentences(params: Params, input_ids: jax.Array,
                    attention_mask: jax.Array, cfg: SalaConfig,
                    pooling: str = "mean", normalize: bool = False,
                    segments: Optional[Segments] = None):
    """Decoder stack + pooling -> ([B, H] float32 passage embeddings, or
    [B, S, H] for packed rows: `segments`, and `attention_mask` its `real`;
    counts [B, sparse layers, 2] int32)."""
    packed = segments is not None
    if not packed:
        segments = one_passage(attention_mask)
    hidden, counts = encode(params, input_ids, segments, cfg)
    with jax.named_scope("pool"):
        pooled = pool_segments(hidden, segments, pooling)
        if not packed:
            pooled = pooled[:, 0]
        if normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
    return pooled, counts


# ---------------------------------------------------------------------------
# Init (random params for tests; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: SalaConfig) -> Params:
    """Random N(0, 0.02) kernels, unit norm scales; float32 storage."""
    keys = iter(jax.random.split(key, 2 + cfg.num_layers * 8))
    H, I = cfg.hidden_size, cfg.intermediate_size

    def dense(n_in, n_out):
        return {"kernel": jax.random.normal(next(keys), (n_in, n_out),
                                            jnp.float32) * 0.02}

    def ln(n: int) -> dict:
        return {"scale": jnp.ones((n,), jnp.float32)}

    layers = []
    for kind in cfg.mixer_types:
        if kind == SPARSE:
            d, wide, kv = (cfg.head_dim, cfg.num_heads * cfg.head_dim,
                           cfg.num_kv_heads * cfg.head_dim)
            mixer = {"k": dense(H, kv), "v": dense(H, kv)}
        else:
            d = cfg.lightning_head_dim
            wide = cfg.lightning_heads * d
            mixer = {"k": dense(H, wide), "v": dense(H, wide),
                     "o_norm": ln(wide)}
        mixer.update({"q": dense(H, wide), "gate": dense(H, wide),
                      "o": dense(wide, H), "q_norm": ln(d), "k_norm": ln(d)})
        layers.append({"ln1": ln(H), "ln2": ln(H), "mixer": mixer,
                       "mlp": {"gate": dense(H, I), "up": dense(H, I),
                               "down": dense(I, H)}})
    return {"wte": jax.random.normal(next(keys), (cfg.vocab_size, H),
                                     jnp.float32) * 0.02,
            "ln_f": ln(H), "layers": layers}
