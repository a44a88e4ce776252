"""Hybrid sliding-window + full grouped-query attention decoder stack with
routed experts (MiMo-V2-Flash's layout, `mimo_v2_flash`), run as a passage
encoder.

Pre-norm blocks `h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`, a
final RMSNorm, then the engine's pooling over a passage's tokens (causal
attention as published, pooled hidden states; the output head and the MTP
layers are not instantiated). Layer i, `n` the RMSNorm (eps
`layernorm_epsilon`):

- **Which attention.** `hybrid_layer_pattern[i]` 0 is full attention, 1
  sliding-window attention (published: full at 0, 5, 11, ..., 47).
- **Projections, no biases.** q = W_q n(x): `num_attention_heads` heads of
  `head_dim`; k = W_k n(x): KV heads of `head_dim` (`num_key_value_heads`
  in full layers, `swa_num_key_value_heads` in window layers); v = W_v n(x):
  KV heads of `v_head_dim`.
- **Partial RoPE.** The first int(`partial_rotary_factor` x head_dim) dims
  of each q and k head turn, half-split within them (dim j with dim j +
  rot/2), frequency theta^(-2j/rot); theta is `rope_theta` in full layers
  and `swa_rope_theta` in window layers. The other dims do not turn.
- **Scores.** s_ij = q_i.k_j / sqrt(head_dim); query head h reads KV head
  h // (heads / KV heads). Full layers: j <= i in the passage. Window
  layers: i - `sliding_window` < j <= i in the passage.
- **Softmax.** Window layers: p_ij = e^s_ij / (e^sink_h + sum_j e^s_ij),
  sink_h a learned logit a head (`add_swa_attention_sink_bias`); full
  layers: no sink (`add_full_attention_sink_bias`).
- **Output.** o_i = W_o (`attention_value_scale` sum_j p_ij v_j).
- **Feed-forward.** A dense SwiGLU of `intermediate_size` where
  `moe_layer_freq[i]` is 0; else routed experts (`models/mla_moe.py`
  `moe_ffn`): sigmoid scores in float32 over all `n_routed_experts`, top-k
  of score + correction bias (`noaux_tc`, one group), weights the chosen
  scores normalised to sum 1 times `routed_scaling_factor` (null: 1), the
  experts this chip holds (`experts_held`, 0..held-1) computed and the
  others' part left to the chips that hold them, `mla_moe.MOE_ROWS`
  tokens at a time; no shared expert.

**The heads' lanes.** A q.k head is laid out in whole 128-lane columns
(`qk_lanes`: 192 -> 256): the rotary half-pairs at lanes [0, rot/2) and
[D'/2, D'/2 + rot/2), the unturned dims in the lanes after each, zero
lanes last (`lane_of`). The loader (and `init_params`) put W_q's and W_k's
columns there, so the projections emit the layout the attention kernel
reads and no q or k is re-laid; a zero column adds nothing to a dot product
and the same permutation on q and k leaves every q.k as it was. The kernel
turns a lane with the one D'/2 away (`layers.rope_tables`' convention), so
the RoPE tables (`rope_lanes`) hold the rotary angles at those lanes and
cos 1 / sin 0 everywhere else. A value head is likewise zero-padded to
whole columns (W_v's columns, W_o's rows).

Attention goes through ops/flash_attention.py `packed_attention` with its
GQA, window and sink (one Pallas kernel a layer, its grid holding only the
key blocks a window reaches) where the row is whole 128-token blocks, and
through the einsum form on the same lanes elsewhere (an unpacked query, a
toy row). `attn.packed{path}` says which, once per traced mixer:
`flash_window`, `flash_grouped` (the full layers) or `dense`.

Every row is handled as packed (`segments`, models/bert.py): positions
restart at a passage's first token and attention stays inside the passage.
Handed only a mask (the fused query), the row is one passage.

`embed_sentences` returns, beside the rows, `aux` int32 [1 + expert
layers + B, max(held, 4)]: a row [routed choices (real tokens x k x expert
layers, held or not), window layers, held, expert layers], a row per
expert layer of the real tokens each held expert took, and a row per batch
row [keys the window layers attended, causal keys one layer would see, the
full layers' kernel steps that computed, the steps under their diagonal
(`full_steps`)]; the family's `note_aux` (models/families.py) books it. The
keys attended are counted where the softmax is taken (the kernel counts the
keys its mask lets through), so a window the kernel did not apply shows
there.

Not here (ROADMAP Reach A4): pages allocated by layer kind in `kv/paged.py`
/ `kv/pool.py` and a sink in paged attention, the generation path's decode
step.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from symbiont_tpu.models import quant
from symbiont_tpu.models.bert import Segments, pool_segments
from symbiont_tpu.models.layers import rmsnorm, swiglu
from symbiont_tpu.models.mla_moe import MlaMoeConfig, moe_ffn
from symbiont_tpu.utils.telemetry import metrics

Params = Any

MODEL_TYPES = ("mimo_v2_flash",)
# the seeded sinks' law: e^3 beside the ~128 keys' sum of e^s (s ~ 0 under
# seeded weights), an eighth of each window query's mass
SINK_MEAN, SINK_STD = 3.0, 0.5


@dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 4
    swa_num_kv_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    experts_held: int = 0  # 0 = all
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    layer_pattern: tuple = ()  # 0 full, 1 window (`hybrid_layer_pattern`)
    moe_layers: tuple = ()  # 0 dense, 1 experts (`moe_layer_freq`)
    swa_sink: bool = True
    full_sink: bool = False
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: str = "bfloat16"
    # the engine sets it on every family's config; only "xla" exists here
    attn_impl: str = "xla"

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    def is_window(self, i: int) -> bool:
        return bool(self.layer_pattern[i])

    def is_moe(self, i: int) -> bool:
        return bool(self.moe_layers[i])

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    @property
    def lanes(self) -> int:
        """Lanes of one q.k head in the kernel's layout."""
        return qk_lanes(self.head_dim, self.rotary_dim)

    @property
    def v_lanes(self) -> int:
        return -(-self.v_head_dim // 128) * 128

    def kv_heads(self, i: int) -> int:
        return self.swa_num_kv_heads if self.is_window(i) else self.num_kv_heads

    def sink(self, i: int) -> bool:
        return self.swa_sink if self.is_window(i) else self.full_sink

    @property
    def moe(self) -> MlaMoeConfig:
        """The expert pieces' view of this configuration (one routing
        group, no shared expert)."""
        return MlaMoeConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            intermediate_size=self.intermediate_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.n_routed_experts, n_shared_experts=0,
            num_experts_per_tok=self.num_experts_per_tok,
            first_k_dense_replace=0,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            experts_held=self.experts_held)

    @staticmethod
    def from_hf(cfg: dict) -> "MimoConfig":
        """Map a `mimo_v2_flash` `config.json`. What this module cannot
        compute is refused by name, never approximated. `experts_held` is
        this program's key: the experts of each layer this chip holds."""
        unsupported = {
            "hidden_act": ("silu",), "attention_bias": (False,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "n_shared_experts": (None, 0),
            "rope_scaling": (None,),
        }
        for key, ok in unsupported.items():
            if key in cfg and cfg[key] not in ok:
                raise NotImplementedError(
                    f"mimo: {key}={cfg[key]!r} is not supported (only "
                    f"{ok[0]!r})")
        heads, D = cfg["num_attention_heads"], cfg["head_dim"]
        for key, same in (("swa_num_attention_heads", heads),
                          ("swa_head_dim", D),
                          ("swa_v_head_dim", cfg["v_head_dim"]),
                          ("sliding_window_size", cfg["sliding_window"]),
                          ("attention_chunk_size", cfg["sliding_window"])):
            if cfg.get(key, same) != same:
                raise NotImplementedError(
                    f"mimo: {key}={cfg[key]!r} differs from the full layers' "
                    f"{same!r}: one head shape for both kinds is written")
        n = cfg["num_hidden_layers"]
        pattern = tuple(int(v) for v in cfg["hybrid_layer_pattern"][:n])
        moe = tuple(int(v) for v in cfg["moe_layer_freq"][:n])
        if len(pattern) != n or len(moe) != n:
            raise NotImplementedError(
                f"mimo: hybrid_layer_pattern / moe_layer_freq name fewer "
                f"than the {n} layers")
        E, held = cfg["n_routed_experts"], cfg.get("experts_held", 0)
        if not 0 <= held <= E:
            raise NotImplementedError(f"mimo: experts_held={held!r} of {E}")
        if int(cfg.get("partial_rotary_factor", 1.0) * D) % 2:
            raise NotImplementedError("mimo: an odd number of rotary dims")
        return MimoConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=n, num_heads=heads,
            num_kv_heads=cfg["num_key_value_heads"],
            swa_num_kv_heads=cfg.get("swa_num_key_value_heads",
                                     cfg["num_key_value_heads"]),
            head_dim=D, v_head_dim=cfg["v_head_dim"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=E, experts_held=held,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor") or 1.0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            rope_theta=float(cfg["rope_theta"]),
            swa_rope_theta=float(cfg.get("swa_rope_theta",
                                         cfg["rope_theta"])),
            partial_rotary_factor=float(cfg.get("partial_rotary_factor",
                                                1.0)),
            sliding_window=int(cfg["sliding_window"]),
            attention_value_scale=float(cfg.get("attention_value_scale",
                                                1.0)),
            layer_pattern=pattern, moe_layers=moe,
            swa_sink=bool(cfg.get("add_swa_attention_sink_bias", False)),
            full_sink=bool(cfg.get("add_full_attention_sink_bias", False)),
            rms_norm_eps=cfg.get("layernorm_epsilon",
                                 cfg.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        )


# ---------------------------------------------------------------------------
# The heads' lanes
# ---------------------------------------------------------------------------


def _split(D: int, rot: int, lanes: int) -> int:
    """Unturned dims that fit between the first rotary half and lane
    lanes / 2."""
    return min(D - rot, lanes // 2 - rot // 2)


def qk_lanes(D: int, rot: int) -> int:
    """The fewest whole 128-lane columns that hold a head of D dims, rot of
    them turned, with each rotary half-pair lanes / 2 apart."""
    lanes = 128
    while rot // 2 > lanes // 2 or (D - rot) - _split(D, rot, lanes) > (
            lanes // 2 - rot // 2):
        lanes += 128
    return lanes


def lane_of(D: int, rot: int) -> np.ndarray:
    """[D] int: the lane of each of a head's dims (the published order: the
    rot turned dims first, then the rest) in the kernel's layout."""
    lanes = qk_lanes(D, rot)
    half, first = rot // 2, _split(D, rot, lanes)
    out = np.empty(D, np.int64)
    out[:half] = np.arange(half)
    out[half:rot] = lanes // 2 + np.arange(half)
    out[rot:rot + first] = half + np.arange(first)
    out[rot + first:] = lanes // 2 + half + np.arange(D - rot - first)
    return out


def to_lanes(w: np.ndarray, heads: int, D: int, lanes: int,
             where: np.ndarray) -> np.ndarray:
    """A kernel [in, heads * D] in the published order -> [in, heads *
    lanes], each head's columns at `where` and zeros elsewhere."""
    if lanes == D and (where == np.arange(D)).all():
        return w
    n = w.shape[0]
    w = w.reshape(n, heads, D)
    if isinstance(w, np.ndarray):
        out = np.zeros((n, heads, lanes), w.dtype)
        out[:, :, where] = w
    else:
        out = jnp.zeros((n, heads, lanes), w.dtype).at[:, :, where].set(w)
    return out.reshape(n, heads * lanes)


def rope_lanes(positions: jax.Array, cfg: MimoConfig, theta: float):
    """(cos, sin) [B, L, lanes] float32 for the kernel's turn (`x * cos +
    roll(x, lanes / 2) * sin`): the rotary angles at the half-pairs' lanes,
    sin negated at the first, cos 1 and sin 0 at every other lane."""
    rot, lanes = cfg.rotary_dim, cfg.lanes
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [B, L, half]
    B, L = positions.shape
    cos = jnp.ones((B, L, lanes), jnp.float32)
    sin = jnp.zeros((B, L, lanes), jnp.float32)
    for at, sign in ((0, -1.0), (lanes // 2, 1.0)):
        cos = cos.at[..., at:at + half].set(jnp.cos(ang))
        sin = sin.at[..., at:at + half].set(sign * jnp.sin(ang))
    return cos, sin


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _dense_attention(q, k, v, segments: Segments, tables, window: int,
                     sinks, scale: float):
    """The einsum form on the kernel's lanes: q [B, L, heads, D'], k / v
    [B, L, kv, D' / Dv'] -> ([B, L, heads, Dv'] in q.dtype, [B, L] int32
    keys each query attended)."""
    B, L, nh, D = q.shape
    group = nh // k.shape[2]
    cos, sin = (t[:, :, None] for t in tables)

    def turn(x):
        xf = x.astype(jnp.float32)
        return xf * cos + jnp.roll(xf, D // 2, axis=-1) * sin

    q = turn(q).reshape(B, L, k.shape[2], group, D)
    s = jnp.einsum("bqkgd,bpkd->bkgqp", q, turn(k)) * scale
    i = jnp.arange(L)[:, None]
    keep = (jnp.arange(L)[None, :] <= i) & segments.same
    if window:
        keep &= jnp.arange(L)[None, :] > i - window
    s = jnp.where(keep[:, None, None], s, -1e9)
    if sinks is not None:
        sk = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(
            1, k.shape[2], group, 1, 1), (*s.shape[:-1], 1))
        s = jnp.concatenate([s, sk], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :L]
    ctx = jnp.einsum("bkgqp,bpkd->bqkgd", p, v.astype(jnp.float32))
    keys = jnp.broadcast_to(keep, (B, L, L)).sum(-1, dtype=jnp.int32)
    return ctx.reshape(B, L, nh, -1).astype(v.dtype), keys


def _fits_kernel(L: int) -> bool:
    """Whether a row of L tokens takes the Pallas kernel (whole 128-token
    blocks) or the einsum form."""
    return L % 128 == 0


def full_steps(segments: Segments, cfg: MimoConfig) -> jax.Array:
    """int32 [B, 2]: the full layers' kernel grid steps that compute, and
    those a walk of every block under the diagonal would take, summed over
    the full layers and their KV heads (`flash_attention.grouped_steps` on
    the ids and padding id the layers hand the kernel); 0 where the row
    takes the einsum form."""
    B, L = segments.index.shape
    if not _fits_kernel(L):
        return jnp.zeros((B, 2), jnp.int32)
    from symbiont_tpu.ops.flash_attention import grouped_steps

    full = sum(not cfg.is_window(i) for i in range(cfg.num_layers))
    return grouped_steps(segments.index,
                         padding_id=segments.lengths.shape[1]) * (
        full * cfg.num_kv_heads)


def attention(p: Params, x: jax.Array, segments: Segments, tables,
              cfg: MimoConfig, window: bool):
    """x [B, L, H] (normed) -> ([B, L, H], keys): one window or full layer;
    keys int32 [B, L], how many keys each query attended, for a window
    layer (None for a full one)."""
    B, L, _ = x.shape
    nh, D, Dv = cfg.num_heads, cfg.lanes, cfg.v_lanes
    nkv = cfg.swa_num_kv_heads if window else cfg.num_kv_heads
    W = cfg.sliding_window if window else 0
    sinks = p.get("sink")
    q = quant.mm(x, p["q"]["kernel"])  # [B, L, heads * D'], the lanes' layout
    k = quant.mm(x, p["k"]["kernel"])
    v = quant.mm(x, p["v"]["kernel"])
    scale = 1.0 / math.sqrt(cfg.head_dim)
    fused = _fits_kernel(L)
    metrics.inc("attn.packed", labels={"path": (
        ("flash_window" if window else "flash_grouped") if fused
        else "dense")})
    if fused:
        from symbiont_tpu.ops.flash_attention import packed_attention

        out = packed_attention(q, k, v, segments.index, nh, rope=tables,
                               kv_heads=nkv, window=W, sinks=sinks,
                               scale=scale, count_keys=window,
                               padding_id=segments.lengths.shape[1])
        ctx, keys = out if window else (out, None)
    else:
        ctx, keys = _dense_attention(
            q.reshape(B, L, nh, D), k.reshape(B, L, nkv, D),
            v.reshape(B, L, nkv, Dv), segments, tables, W, sinks, scale)
        ctx = ctx.reshape(B, L, nh * Dv)
        keys = keys if window else None
    ctx = ctx * jnp.asarray(cfg.attention_value_scale, ctx.dtype)
    return quant.mm(ctx, p["o"]["kernel"]), keys


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
           cfg: MimoConfig):
    """-> (last hidden state after the final norm [B, L, H] in cfg.dtype,
    counts [expert layers, held] int32, keys [B] int32: the keys the
    window layers attended, summed over their real tokens)."""
    dtype, eps, mcfg = jnp.dtype(cfg.dtype), cfg.rms_norm_eps, cfg.moe
    mask = segments.real
    with jax.named_scope("embeddings"):
        x = quant.take(params["wte"], input_ids, dtype).astype(dtype)
    tables = {True: rope_lanes(segments.position, cfg, cfg.swa_rope_theta),
              False: rope_lanes(segments.position, cfg, cfg.rope_theta)}
    # each kind of sub-layer has one shape: traced and lowered once, called
    # per layer (models/mla_moe.py `encode` says why)
    mixers = {w: jax.jit(lambda p, ln, x, seg, tab, w=w: attention(
        p, rmsnorm(x, ln, eps), seg, tab, cfg, w)) for w in (True, False)}
    experts = jax.jit(lambda p, x, mask, ln: moe_ffn(p, x, mask, ln, mcfg))
    counts, keys = [], jnp.zeros(input_ids.shape[:1], jnp.int32)
    for i, layer in enumerate(quant.cast_params(params["layers"], dtype)):
        window = cfg.is_window(i)
        with jax.named_scope("swa" if window else "full_attn"):
            y, seen = mixers[window](layer["attn"], layer["ln1"], x,
                                     segments, tables[window])
            x = x + y
        if seen is not None:
            keys = keys + (mask * seen).sum(1, dtype=jnp.int32)
        if cfg.is_moe(i):
            y, c = experts(layer["moe"], x, mask, layer["ln2"])
            counts.append(c)
        else:
            with jax.named_scope("dense_ffn"):
                y = swiglu(rmsnorm(x, layer["ln2"], eps), layer["mlp"])
        x = x + y
    x = rmsnorm(x, quant.cast_params(params["ln_f"], dtype), eps)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.held), jnp.int32))
    return x, counts, keys


def embed_sentences(params: Params, input_ids: jax.Array,
                    attention_mask: jax.Array, cfg: MimoConfig,
                    pooling: str = "mean", normalize: bool = False,
                    segments: Optional[Segments] = None):
    """Decoder stack + pooling -> ([B, H] float32 passage embeddings, or
    [B, S, H] for packed rows: `segments`, and `attention_mask` its `real`;
    aux int32, the module's docstring says what)."""
    packed = segments is not None
    if not packed:
        segments = one_passage(attention_mask)
    hidden, counts, attended = encode(params, input_ids, segments, cfg)
    with jax.named_scope("pool"):
        pooled = pool_segments(hidden, segments, pooling)
        if not packed:
            pooled = pooled[:, 0]
        if normalize:
            pooled = pooled / jnp.maximum(
                jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
    width = max(cfg.held, 4)
    layers = counts.shape[0]
    real = segments.real
    routed = real.sum(dtype=jnp.int32) * cfg.num_experts_per_tok * layers
    windows = sum(cfg.is_window(i) for i in range(cfg.num_layers))
    last = jnp.zeros((1, width), jnp.int32).at[0, :4].set(jnp.stack([
        routed, jnp.int32(windows), jnp.int32(cfg.held), jnp.int32(layers)]))
    keys = jnp.stack([attended, (real * (segments.position + 1)).sum(
        1, dtype=jnp.int32)], axis=1)  # [B, 2]
    keys = jnp.concatenate([keys, full_steps(segments, cfg)], axis=1)
    rows = jnp.zeros((keys.shape[0], width), jnp.int32).at[:, :4].set(keys)
    counts = jnp.pad(counts, ((0, 0), (0, width - cfg.held)))
    return pooled, jnp.concatenate([last, counts, rows], axis=0)


# ---------------------------------------------------------------------------
# Init (random params for tests; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: MimoConfig) -> Params:
    """Random N(0, 0.02) kernels in the lanes' layout, unit norm scales,
    small router biases, window sinks N(3, 0.5); float32 storage, the
    experts held stacked [held, in, out]."""
    keys = iter(jax.random.split(key, 4 + cfg.num_layers * 12))
    H, nh, D, Dv = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                    cfg.v_head_dim)
    where, v_where = lane_of(D, cfg.rotary_dim), np.arange(Dv)

    def normal(*shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def dense(*shape):
        return {"kernel": normal(*shape)}

    def ln(n: int) -> dict:
        return {"scale": jnp.ones((n,), jnp.float32)}

    def mlp(width: int, *stack) -> dict:
        return {"gate": dense(*stack, H, width), "up": dense(*stack, H, width),
                "down": dense(*stack, width, H)}

    layers = []
    for i in range(cfg.num_layers):
        nkv = cfg.kv_heads(i)
        o = to_lanes(normal(H, nh * Dv), nh, Dv, cfg.v_lanes, v_where)
        attn = {"q": {"kernel": to_lanes(normal(H, nh * D), nh, D, cfg.lanes,
                                         where)},
                "k": {"kernel": to_lanes(normal(H, nkv * D), nkv, D,
                                         cfg.lanes, where)},
                "v": {"kernel": to_lanes(normal(H, nkv * Dv), nkv, Dv,
                                         cfg.v_lanes, v_where)},
                "o": {"kernel": o.T}}
        if cfg.sink(i):
            attn["sink"] = SINK_MEAN + normal(nh, std=SINK_STD)
        layer = {"ln1": ln(H), "ln2": ln(H), "attn": attn}
        if cfg.is_moe(i):
            layer["moe"] = {"router": {**dense(H, cfg.n_routed_experts),
                                       "bias": normal(cfg.n_routed_experts)},
                            "experts": mlp(cfg.moe_intermediate_size,
                                           cfg.held)}
        else:
            layer["mlp"] = mlp(cfg.intermediate_size)
        layers.append(layer)
    return {"wte": normal(cfg.vocab_size, H), "ln_f": ln(H),
            "layers": layers}
