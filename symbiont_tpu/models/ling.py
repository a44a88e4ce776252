"""Hybrid linear-attention + latent-attention decoder stack with routed
experts (Ling-3.0-flash's layout, `bailing_hybrid`), run as a passage
encoder.

Pre-norm blocks `h = x + Mixer(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`, a
final RMSNorm, then the engine's pooling over a passage's tokens (causal
mixers as published, pooled hidden states; the output head, the MTP layer and
the vision tower are not instantiated). Layer i's mixer is MLA where
`(i + 1) % layer_group_size == 0`, else KDA; layers under
`first_k_dense_replace` have a dense SwiGLU, the rest routed experts and a
shared expert.

- **KDA** (Kimi Delta Attention; `ops/delta_rule.py` computes the
  recurrence, one Pallas kernel where a head is 128 lanes wide):
  `q, k, v = SiLU(ShortConv(W x))`, the convolution depthwise and causal
  over `short_conv_kernel_size` tokens of the passage; q and k
  L2-normalised per head, q scaled by 1/sqrt(d); `beta = sigmoid(W_b x)`
  per head; `g = lower_bound * sigmoid(exp(A_h) (W_f x + b))` per key
  channel (the safe gate, `kda_lower_bound`); the gated delta rule over the
  passage; the output RMSNorm per head times `sigmoid(W_g x)`, then W_o.
- **MLA**: `models/mla_moe.py`'s `mla_attention` with an RMSNorm over each
  head's q and k before RoPE and a sigmoid gate a head on the context; long
  packed rows attend through the segment-masked flash kernel.
- **Routed + shared FFN**: `models/mla_moe.py`'s `moe_ffn`: sigmoid scores
  over all `num_experts`, group-limited top-k (`n_group`, `topk_group`),
  weights normalised over the k choices x `routed_scaling_factor`, the
  experts this chip holds (`experts_held`, 0..held-1) computed and the
  others' part left to the chips that hold them; rows longer than
  `mla_moe.MOE_ROWS` go that many tokens at a time.

Every row is handled as packed (`segments`, models/bert.py): the delta
rule's state and the convolution's window reset at a passage's first token,
positions restart there and attention stays inside the passage. Handed only
a mask (the fused query), the row is one passage.

`embed_sentences` returns, beside the rows, `aux` int32 [expert layers + 1,
held]: a row per expert layer of the real tokens each held expert took, and
a last row [routed choices (real tokens x k x expert layers, held or not),
passages started x KDA layers, 0, ...]; `note_aux` (models/families.py)
books it.

Not here (ROADMAP Reach A4): a [heads, d, d] state and a convolution tail
per sequence in the page pool, the generation path's decode step.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant
from symbiont_tpu.models.bert import Segments, pool_segments
from symbiont_tpu.models.layers import rmsnorm, swiglu
from symbiont_tpu.models.mla_moe import MlaMoeConfig, mla_attention, moe_ffn
from symbiont_tpu.utils.telemetry import metrics

Params = Any

MODEL_TYPES = ("bailing_hybrid",)
DT_BIAS_MEAN, DT_BIAS_STD = -6.0, 3.0  # seeded decay-gate bias: slow and fast channels


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_layers: int = 42
    num_heads: int = 32
    head_dim: int = 128  # KDA's q, k and v heads
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768
    num_shared_experts: int = 1
    num_experts: int = 512
    experts_held: int = 0  # 0 = all
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_group_size: int = 6
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    conv_size: int = 4
    kda_lower_bound: float = -5.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    dtype: str = "bfloat16"
    # the engine sets it on every family's config; only "xla" exists here
    attn_impl: str = "xla"

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    @property
    def mla(self) -> MlaMoeConfig:
        """The shared MLA and expert pieces' view of this configuration."""
        return MlaMoeConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            intermediate_size=self.intermediate_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.num_experts,
            n_shared_experts=self.num_shared_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            first_k_dense_replace=self.first_k_dense_replace,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            rms_norm_eps=self.rms_norm_eps,
            max_position_embeddings=self.max_position_embeddings,
            dtype=self.dtype, n_group=self.n_group,
            topk_group=self.topk_group, experts_held=self.experts_held,
            qk_norm=True, head_gate=True)

    @staticmethod
    def from_hf(cfg: dict) -> "LingConfig":
        """Map a `bailing_hybrid` `config.json` (a vision-language checkpoint
        nests it under `text_config`). What this module cannot compute is
        refused by name, never approximated. `experts_held` is this
        program's key: the experts of each layer this chip holds."""
        cfg = cfg.get("text_config", cfg)
        unsupported = {
            "hidden_act": ("silu",), "q_lora_rank": (None,),
            "rope_scaling": (None,), "use_qkv_bias": (False,),
            "use_bias": (False,), "scoring_func": ("sigmoid",),
            "score_function": ("sigmoid",), "topk_method": ("noaux_tc",),
            "use_mla_nope": (False,), "use_qk_norm": (True,),
            "gated_attention_proj_granularity_type": ("head_wise",),
            "kda_safe_gate": (True,), "no_kda_lora": (True,),
            "use_kda_lora": (False,), "linear_silu": (True,),
            "up_proj_norm": (False,), "value_norm": (False,),
            "use_nGPT": (False,), "scale_router_input": (False,),
            "group_norm_size": (1,), "rope_interleave": (True,),
            "moe_router_enable_expert_bias": (True,),
        }
        for key, ok in unsupported.items():
            if key in cfg and cfg[key] not in ok:
                raise NotImplementedError(
                    f"ling: {key}={cfg[key]!r} is not supported (only "
                    f"{ok[0]!r})")
        layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = cfg.get(key) or []
            if any(limits[:layers]):
                raise NotImplementedError(
                    f"ling: {key} is nonzero on a layer held here "
                    f"({limits[:layers]}): a clipped SwiGLU is not written")
        for key in ("num_key_value_heads", "num_kv_heads_for_linear_attn"):
            if cfg.get(key) not in (None, 0, heads):
                raise NotImplementedError(
                    f"ling: {key}={cfg[key]!r} is not supported (only "
                    f"{heads}: a key head a query head)")
        rope_dim = cfg.get("rotary_dim", cfg["qk_rope_head_dim"])
        if rope_dim != cfg["qk_rope_head_dim"]:
            raise NotImplementedError(
                f"ling: rotary_dim={rope_dim!r} is not qk_rope_head_dim")
        E, held = cfg["num_experts"], cfg.get("experts_held", 0)
        if not 0 <= held <= E or E % cfg.get("n_group", 1):
            raise NotImplementedError(
                f"ling: experts_held={held!r} of {E} in "
                f"{cfg.get('n_group', 1)} groups")
        return LingConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=layers, num_heads=heads, head_dim=cfg["head_dim"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_intermediate_size=cfg.get(
                "moe_shared_expert_intermediate_size",
                cfg["moe_intermediate_size"]),
            num_shared_experts=cfg.get("num_shared_experts") or 0,
            num_experts=E, experts_held=held,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            n_group=cfg.get("n_group", 1),
            topk_group=cfg.get("topk_group", 1),
            first_k_dense_replace=cfg.get("first_k_dense_replace", 0),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            layer_group_size=cfg["layer_group_size"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            conv_size=cfg.get("short_conv_kernel_size", 4),
            kda_lower_bound=float(cfg.get("kda_lower_bound", -5.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        )


# ---------------------------------------------------------------------------
# KDA
# ---------------------------------------------------------------------------


def _f32(w) -> jax.Array:
    return (w.dequantize() if quant.is_quantized(w) else w).astype(jnp.float32)


def short_conv(y: jax.Array, w, position: jax.Array) -> jax.Array:
    """Depthwise causal convolution over y [B, L, C] with taps w [K, C]
    (tap K - 1 is the token itself, tap K - 1 - t the token t before it, as
    a torch `Conv1d` of kernel K padded K - 1 on the left), reading no token
    of an earlier passage (`position` [B, L]: the place in the passage).
    -> [B, L, C] float32."""
    w, K, L = _f32(w), w.shape[0], y.shape[1]
    y = y.astype(jnp.float32)
    out = y * w[K - 1]
    for t in range(1, K):
        back = jnp.pad(y, ((0, 0), (t, 0), (0, 0)))[:, :L]
        out = out + jnp.where((position >= t)[..., None], back, 0.0) * w[K - 1 - t]
    return out


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def kda_mixer(p: Params, x: jax.Array, segments: Segments,
              cfg: LingConfig) -> jax.Array:
    """x [B, L, H] (normed) -> [B, L, H]."""
    from symbiont_tpu.ops import delta_rule

    B, L, _ = x.shape
    nh, d = cfg.num_heads, cfg.head_dim
    metrics.inc("kda.path", labels={"path": delta_rule.path(d, d)})

    def heads(name):
        y = short_conv(quant.mm(x, p[name]["kernel"]), p["conv"][name],
                       segments.position)
        return jax.nn.silu(y).reshape(B, L, nh, d)

    q = (_l2norm(heads("q")) / math.sqrt(d)).astype(x.dtype)
    k = _l2norm(heads("k")).astype(x.dtype)
    v = heads("v").astype(x.dtype)
    beta = jax.nn.sigmoid(quant.mm(x, p["beta"]["kernel"]).astype(jnp.float32))
    decay = p["decay"]
    z = (quant.mm(x, decay["kernel"]).astype(jnp.float32)
         + decay["bias"].astype(jnp.float32)).reshape(B, L, nh, d)
    a = jnp.exp(decay["a_log"].astype(jnp.float32))[:, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(a * z)
    with jax.named_scope("delta_rule"):
        o = delta_rule.gated_delta_rule(q, k, v, g, beta, segments.index)
    o = rmsnorm(o, p["o_norm"], cfg.rms_norm_eps).reshape(B, L, nh * d)
    gate = jax.nn.sigmoid(quant.mm(x, p["gate"]["kernel"]))
    return quant.mm(o * gate, p["o"]["kernel"])


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
           cfg: LingConfig):
    """-> (last hidden state after the final norm [B, L, H] in cfg.dtype,
    counts [expert layers, held] int32)."""
    dtype, eps, mcfg = jnp.dtype(cfg.dtype), cfg.rms_norm_eps, cfg.mla
    mask = segments.real
    with jax.named_scope("embeddings"):
        x = quant.take(params["wte"], input_ids, dtype).astype(dtype)
    # each kind of sub-layer has one shape: traced and lowered once, called
    # per layer (models/mla_moe.py `encode` says why)
    kda = jax.jit(lambda p, ln, x, seg: kda_mixer(p, rmsnorm(x, ln, eps), seg,
                                                  cfg))
    mla = jax.jit(lambda p, ln, x, seg: mla_attention(
        p, rmsnorm(x, ln, eps), seg.real, mcfg, seg))
    experts = jax.jit(lambda p, x, mask, ln: moe_ffn(p, x, mask, ln, mcfg))
    counts = []
    for i, layer in enumerate(quant.cast_params(params["layers"], dtype)):
        if cfg.is_mla(i):
            with jax.named_scope("mla"):
                x = x + mla(layer["attn"], layer["ln1"], x, segments)
        else:
            with jax.named_scope("kda"):
                x = x + kda(layer["kda"], layer["ln1"], x, segments)
        if "moe" in layer:
            y, c = experts(layer["moe"], x, mask, layer["ln2"])
            counts.append(c)
        else:
            with jax.named_scope("dense_ffn"):
                y = swiglu(rmsnorm(x, layer["ln2"], eps), layer["mlp"])
        x = x + y
    x = rmsnorm(x, quant.cast_params(params["ln_f"], dtype), eps)
    counts = (jnp.stack(counts) if counts
              else jnp.zeros((0, cfg.held), jnp.int32))
    return x, counts


def embed_sentences(params: Params, input_ids: jax.Array,
                    attention_mask: jax.Array, cfg: LingConfig,
                    pooling: str = "mean", normalize: bool = False,
                    segments: Optional[Segments] = None):
    """Decoder stack + pooling -> ([B, H] float32 passage embeddings, or
    [B, S, H] for packed rows: `segments`, and `attention_mask` its `real`;
    aux int32 [expert layers + 1, held], the module's docstring says what)."""
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
    kda_layers = sum(not cfg.is_mla(i) for i in range(cfg.num_layers))
    routed = (segments.real.sum(dtype=jnp.int32) * cfg.num_experts_per_tok
              * counts.shape[0])
    started = (segments.lengths > 0).sum(dtype=jnp.int32) * kda_layers
    last = jnp.zeros((1, cfg.held), jnp.int32).at[0, :2].set(
        jnp.stack([routed, started]))
    return pooled, jnp.concatenate([counts, last], axis=0)


# ---------------------------------------------------------------------------
# Init (random params for tests; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LingConfig) -> Params:
    """Random N(0, 0.02) kernels and convolution taps, unit norm scales,
    small router biases and A_log, the decay gate's bias N(-6, 3) (channels
    that forget in a token beside channels that keep a passage); float32
    storage, the experts held stacked [held, in, out]."""
    keys = iter(jax.random.split(key, 4 + cfg.num_layers * 24))
    H, nh, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    wide = nh * d

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
        layer = {"ln1": ln(H), "ln2": ln(H)}
        if cfg.is_mla(i):
            layer["attn"] = {
                "q": dense(H, nh * (dn + dr)), "kv_a": dense(H, r + dr),
                "kv_a_ln": ln(r), "kv_b": dense(r, nh * (dn + dv)),
                "o": dense(nh * dv, H), "q_norm": ln(dn + dr),
                "k_norm": ln(dn + dr), "gate": dense(H, nh)}
        else:
            layer["kda"] = {
                "q": dense(H, wide), "k": dense(H, wide), "v": dense(H, wide),
                "conv": {n: normal(cfg.conv_size, wide) for n in "qkv"},
                "decay": {"kernel": normal(H, wide),
                          "bias": DT_BIAS_MEAN + normal(wide, std=DT_BIAS_STD),
                          "a_log": normal(nh)},
                "beta": dense(H, nh), "gate": dense(H, wide),
                "o_norm": ln(d), "o": dense(wide, H)}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = mlp(cfg.intermediate_size)
        else:
            moe = {"router": {**dense(H, cfg.num_experts),
                              "bias": normal(cfg.num_experts)},
                   "experts": mlp(cfg.moe_intermediate_size, cfg.held)}
            if cfg.num_shared_experts:
                moe["shared"] = mlp(cfg.shared_intermediate_size
                                    * cfg.num_shared_experts)
            layer["moe"] = moe
        layers.append(layer)
    return {"wte": normal(cfg.vocab_size, H), "ln_f": ln(H), "layers": layers}
