"""BERT-family encoder, TPU-first.

Replaces the reference's one true compute core — the candle BertModel forward +
attention-masked mean pooling inside preprocessing_service (reference:
services/preprocessing_service/src/embedding_generator.rs:198-207) — with a
pure-JAX implementation designed for the MXU:

- params are a pytree of jax arrays; the forward is a pure function, so it
  jits/shards/differentiates with no adapter layer;
- compute dtype is bfloat16 by default (MXU-native) with float32 layernorm
  statistics and pooling; in float32 mode softmax and gelu are exact (erf)
  for numerical parity with the fp32 reference (golden tests in
  tests/test_bert_numerics.py), while bf16 mode keeps softmax in bf16 and
  uses tanh-gelu — both deviations sit below the bf16 matmul noise floor
  and together are worth ~+40% embedding throughput on v5e (see _act and
  attention for per-change measurements);
- static shapes only: the engine pads to length buckets (SURVEY.md §5.7) and
  this module never branches on data;
- one config covers the checkpoint layouts in BASELINE.md: classic BERT
  (MiniLM, bge, e5, ms-marco cross-encoder) and XLM-RoBERTa
  (paraphrase-multilingual-mpnet-base-v2, the reference's default model) which
  differs only in position-id offset (= pad_token_id + 1) and vocab details.

Layout convention for weights: all linear kernels are stored [in, out] so the
forward is `x @ W + b` (HF torch Linear weights are transposed on conversion —
see symbiont_tpu.models.convert).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant

# `config.json` `model_type`s this loader reads (models/families.py: a type
# no family claims is refused). The second group starts position ids past
# the padding index (`BertConfig.from_hf`).
_OFFSET_TYPES = ("roberta", "xlm-roberta", "mpnet")
MODEL_TYPES = ("bert", "electra") + _OFFSET_TYPES

Params = Any  # nested dict pytree


class Segments(NamedTuple):
    """Where the sentences of packed rows lie (engine/bucketing.py lays a
    row's sentences end to end). A forward handed one treats every sentence
    as a row of its own: positions restart, attention stays inside the
    sentence, pooling is per sentence. Handed None it traces the unpacked
    code, one sentence a row."""
    index: jax.Array  # [B, L] int32: the token's sentence in its row; S = padding
    position: jax.Array  # [B, L] int32: the token's place within its sentence
    lengths: jax.Array  # [B, S] int32: tokens of each sentence, 0 = none

    @staticmethod
    def of_lengths(lengths: jax.Array, L: int) -> "Segments":
        """From `[B, S]` sentence lengths alone (what the engine ships)."""
        lengths = lengths.astype(jnp.int32)
        t = jnp.arange(L, dtype=jnp.int32)[None, :, None]
        ended = jnp.cumsum(lengths, axis=1)[:, None, :] <= t  # [B, L, S]
        index = ended.sum(-1, dtype=jnp.int32)
        start = (ended * lengths[:, None, :]).sum(-1, dtype=jnp.int32)
        return Segments(index, t[:, :, 0] - start, lengths)

    @property
    def real(self) -> jax.Array:
        """[B, L] int32, 1 where a token belongs to a sentence."""
        return (self.index < self.lengths.shape[1]).astype(jnp.int32)

    @property
    def same(self) -> jax.Array:
        """[B, L, L] bool: query and key in one sentence (padding, which
        nothing reads, keeps itself company: no row of the softmax is
        empty; the grouped attention kernel, told padding's id, writes 0
        for a block of queries that is all padding instead)."""
        return self.index[:, :, None] == self.index[:, None, :]


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    # XLM-RoBERTa (mpnet-multilingual) offsets position ids by pad_token_id+1=2
    # and starts them past the padding index; classic BERT uses offset 0.
    # (HF: XLMRobertaEmbeddings.create_position_ids_from_input_ids.)
    position_offset: int = 0
    hidden_act: str = "gelu"
    # dtype for matmul compute; params may be stored fp32 and cast on entry.
    dtype: str = "bfloat16"
    # "xla" = einsum attention (XLA fuses); "flash" = fused pallas kernel
    # (symbiont_tpu.ops.flash_attention) — never materializes [B,NH,S,S].
    attn_impl: str = "xla"

    @staticmethod
    def from_hf(cfg: dict) -> "BertConfig":
        """Map an HF config.json dict (BertConfig/XLMRobertaConfig) to ours."""
        model_type = cfg.get("model_type", "bert")
        offset = 0
        if model_type in _OFFSET_TYPES:
            offset = cfg.get("pad_token_id", 1) + 1
        return BertConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=cfg.get("num_hidden_layers", 12),
            num_heads=cfg.get("num_attention_heads", 12),
            intermediate_size=cfg.get("intermediate_size", 4 * cfg["hidden_size"]),
            max_position_embeddings=cfg.get("max_position_embeddings", 512),
            type_vocab_size=cfg.get("type_vocab_size", 2) or 1,
            layer_norm_eps=cfg.get("layer_norm_eps", 1e-12),
            position_offset=offset,
            hidden_act=cfg.get("hidden_act", "gelu"),
        )


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    # fp32 statistics regardless of compute dtype — parity with the fp32
    # reference forward within bf16 matmul noise.
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (normed * scale + bias).astype(x.dtype)


def _act(name: str, compute_dtype=None):
    if name in ("gelu", "gelu_new", "gelu_python"):
        # exact (erf) gelu in f32 for checkpoint parity; tanh approximation
        # in bf16 mode, where its ~1e-3 relative error sits well below the
        # bf16 matmul quantization noise and the erf transcendental is the
        # single most expensive VPU op in the block (measured on v5e at
        # MiniLM geometry [1024, 64]: +26% emb/s from this switch alone).
        approx = compute_dtype == jnp.bfloat16
        return partial(jax.nn.gelu, approximate=approx)
    if name == "relu":
        return jax.nn.relu
    if name == "silu":
        return jax.nn.silu
    raise ValueError(f"unsupported activation {name!r}")


def attention(
    params: Params,
    x: jax.Array,  # [B, S, H]
    mask_bias: jax.Array,  # [B, 1, 1 or S, S] additive bias (0 or -inf-ish)
    cfg: BertConfig,
) -> jax.Array:
    B, S, H = x.shape
    nh = cfg.num_heads
    hd = H // nh

    def proj(p):
        # quant.mm: plain matmul for f32/bf16 kernels, `(x @ q) * scale`
        # for int8/fp8 QuantTensors (dequant fused — narrow HBM read)
        return (quant.mm(x, p["kernel"]) + p["bias"]).reshape(B, S, nh, hd)

    q = proj(params["query"])
    k = proj(params["key"])
    v = proj(params["value"])

    if cfg.attn_impl == "flash":
        from symbiont_tpu.ops.flash_attention import flash_attention

        ctx = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), kv_bias=mask_bias[:, 0, 0, :],
        ).transpose(0, 2, 1, 3).reshape(B, S, H)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        if x.dtype == jnp.bfloat16:
            # softmax in bf16: the f32 round-trip would materialize the
            # [B, nh, S, S] intermediate through HBM twice at double width,
            # and bf16 matmul noise already dominates the softmax rounding
            # (measured +13% emb/s on v5e at [1024, 64]). jax.nn.softmax
            # subtracts the row max, so exp stays in range; padded lanes get
            # the large negative bias and underflow to exactly 0.
            probs = jax.nn.softmax(
                scores + mask_bias.astype(scores.dtype), axis=-1)
        else:
            # fp32 softmax for exact parity with the fp32 reference forward
            scores = scores.astype(jnp.float32) + mask_bias.astype(jnp.float32)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H)
    out = quant.mm(ctx, params["out"]["kernel"]) + params["out"]["bias"]
    return out


def encoder_layer(params: Params, x: jax.Array, mask_bias: jax.Array, cfg: BertConfig) -> jax.Array:
    # Post-LN transformer block (classic BERT ordering).
    attn_out = attention(params["attention"], x, mask_bias, cfg)
    x = layer_norm(x + attn_out, params["attention"]["ln"]["scale"],
                   params["attention"]["ln"]["bias"], cfg.layer_norm_eps)
    h = quant.mm(x, params["mlp"]["in"]["kernel"]) + params["mlp"]["in"]["bias"]
    h = _act(cfg.hidden_act, x.dtype)(h)
    h = quant.mm(h, params["mlp"]["out"]["kernel"]) + params["mlp"]["out"]["bias"]
    x = layer_norm(x + h, params["mlp"]["ln"]["scale"], params["mlp"]["ln"]["bias"],
                   cfg.layer_norm_eps)
    return x


def embeddings(
    params: Params,
    input_ids: jax.Array,  # [B, S] int32
    attention_mask: jax.Array,  # [B, S] int32/bool
    cfg: BertConfig,
    token_type_ids: Optional[jax.Array] = None,
    segments: Optional[Segments] = None,
) -> jax.Array:
    B, S = input_ids.shape
    # tables stay at rest and `quant.take` casts the rows it gathered (a
    # QuantTensor's come back float32 and are summed so); the LayerNorm's
    # scale and bias go through the shared leaf-aware cast
    dtype = jnp.dtype(cfg.dtype)
    ln = quant.cast_params(params["ln"], dtype)
    tok = quant.take(params["word_embeddings"], input_ids, dtype)
    if cfg.position_offset:
        # RoBERTa-style: positions count only non-pad tokens, offset past pad id.
        mask = attention_mask.astype(jnp.int32)
        count = (jnp.cumsum(mask, axis=1) if segments is None
                 else segments.position + 1)  # restarts at every sentence
        positions = count * mask + cfg.position_offset - 1
        positions = jnp.clip(positions, 0, cfg.max_position_embeddings - 1)
    elif segments is not None:
        positions = segments.position
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    pos = quant.take(params["position_embeddings"], positions, dtype)
    if token_type_ids is None:
        token_type_ids = jnp.zeros_like(input_ids)
    typ = quant.take(params["token_type_embeddings"], token_type_ids, dtype)
    x = tok + pos + typ
    x = layer_norm(x, ln["scale"], ln["bias"], cfg.layer_norm_eps)
    return x


def bert_encode(
    params: Params,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    cfg: BertConfig,
    token_type_ids: Optional[jax.Array] = None,
    segments: Optional[Segments] = None,
) -> jax.Array:
    """Full encoder forward → last hidden state [B, S, H] in cfg.dtype."""
    if segments is not None and cfg.attn_impl == "flash":
        raise ValueError("the flash kernel takes a per-key bias: packed rows "
                         "need attn_impl='xla'")
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embeddings"):
        x = embeddings(params["embeddings"], input_ids, attention_mask, cfg,
                       token_type_ids, segments)
        x = x.astype(dtype)
    with jax.named_scope("encoder"):
        # additive mask bias: 0 for real tokens, large negative for padding
        # (packed rows: 0 inside a sentence, block-diagonal by sentence)
        attended = (attention_mask[:, None, None, :] if segments is None
                    else segments.same[:, None])
        mask_bias = (1.0 - attended.astype(jnp.float32)) * -1e9
        # shared leaf-aware cast: floating params → compute dtype,
        # QuantTensor leaves untouched (their f32 scales must not be
        # downcast); inside the scope that uses them, so a device trace
        # charges the layers' casts to `encoder`
        for layer_params in quant.cast_params(params["layers"], dtype):
            x = encoder_layer(layer_params, x, mask_bias, cfg)
    return x


def mean_pool(hidden: jax.Array, attention_mask: jax.Array) -> jax.Array:
    """Attention-masked mean pooling, fp32 accumulation.

    Exact semantics of the reference's pooling math (reference:
    services/preprocessing_service/src/embedding_generator.rs:201-207):
    sum(hidden * mask) / sum(mask), per sentence.
    """
    mask = attention_mask[..., None].astype(jnp.float32)
    summed = (hidden.astype(jnp.float32) * mask).sum(axis=1)
    counts = jnp.maximum(mask.sum(axis=1), 1.0)
    return summed / counts


def cls_pool(hidden: jax.Array, attention_mask: jax.Array) -> jax.Array:
    """CLS-token pooling (bge-style checkpoints)."""
    del attention_mask
    return hidden[:, 0, :].astype(jnp.float32)


POOLERS = {"mean": mean_pool, "cls": cls_pool}


def pool_segments(hidden: jax.Array, segments: Segments,
                  pooling: str) -> jax.Array:
    """POOLERS per sentence of packed rows: [B, L, H] → [B, S, H] float32
    (the mean over a sentence's tokens, or its first token); a slot that
    holds no sentence comes out zero. One weighted sum per slot at full
    float32 precision: each weight is 0 or 1, so it is the same sum."""
    S = segments.lengths.shape[1]
    L = hidden.shape[1]
    if pooling == "mean":
        pick = segments.index[:, None, :] == jnp.arange(S)[None, :, None]
        count = jnp.maximum(segments.lengths, 1)[..., None]
    else:
        assert pooling == "cls", pooling
        first = jnp.cumsum(segments.lengths, axis=1) - segments.lengths
        pick = ((jnp.arange(L)[None, None, :] == first[..., None])
                & (segments.lengths[..., None] > 0))
        count = 1
    summed = jnp.einsum("bsl,blh->bsh", pick.astype(jnp.float32),
                        hidden.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return summed / count


def embed_sentences(
    params: Params,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    cfg: BertConfig,
    pooling: str = "mean",
    normalize: bool = False,
    segments: Optional[Segments] = None,
) -> jax.Array:
    """Encoder forward + pooling → [B, H] float32 sentence embeddings
    ([B, S, H] for packed rows: `segments`, and `attention_mask` its `real`).

    The reference does not L2-normalize (cosine distance is computed by Qdrant,
    reference: services/vector_memory_service/src/main.rs:36), so normalize
    defaults to False; e5/bge recipes can turn it on.
    """
    hidden = bert_encode(params, input_ids, attention_mask, cfg,
                         segments=segments)
    with jax.named_scope("pool"):
        pooled = (POOLERS[pooling](hidden, attention_mask) if segments is None
                  else pool_segments(hidden, segments, pooling))
        if normalize:
            pooled = pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)
        return pooled


def cross_encoder_score(
    params: Params,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    cfg: BertConfig,
    token_type_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Cross-encoder relevance score [B] (ms-marco rerank head: pooler + linear).

    BASELINE.md config #4: ms-marco-MiniLM-L-6 rerank on top-k search hits.
    """
    hidden = bert_encode(params, input_ids, attention_mask, cfg, token_type_ids)
    # HF BertPooler: tanh(W @ h_cls + b), then classifier [H, num_labels=1].
    cls = hidden[:, 0, :]
    pooled = jnp.tanh(quant.mm(cls, params["pooler"]["kernel"])
                      + params["pooler"]["bias"])
    logits = (quant.mm(pooled, params["classifier"]["kernel"])
              + params["classifier"]["bias"])
    return logits[..., 0].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Init (random params for tests/benchmarks; real weights come from convert.py)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: BertConfig, with_pooler: bool = False) -> Params:
    """Random init with BERT's trunc-normal(0.02) scheme; fp32 storage."""
    k_iter = iter(jax.random.split(key, 6 + cfg.num_layers * 16))

    def dense(shape):
        return jax.random.truncated_normal(next(k_iter), -2, 2, shape, jnp.float32) * 0.02

    def linear(n_in, n_out):
        return {"kernel": dense((n_in, n_out)), "bias": jnp.zeros((n_out,), jnp.float32)}

    def ln():
        return {"scale": jnp.ones((cfg.hidden_size,), jnp.float32),
                "bias": jnp.zeros((cfg.hidden_size,), jnp.float32)}

    H, I = cfg.hidden_size, cfg.intermediate_size
    params: Params = {
        "embeddings": {
            "word_embeddings": dense((cfg.vocab_size, H)),
            "position_embeddings": dense((cfg.max_position_embeddings, H)),
            "token_type_embeddings": dense((cfg.type_vocab_size, H)),
            "ln": ln(),
        },
        "layers": [
            {
                "attention": {
                    "query": linear(H, H),
                    "key": linear(H, H),
                    "value": linear(H, H),
                    "out": linear(H, H),
                    "ln": ln(),
                },
                "mlp": {"in": linear(H, I), "out": linear(I, H), "ln": ln()},
            }
            for _ in range(cfg.num_layers)
        ],
    }
    if with_pooler:
        params["pooler"] = linear(H, H)
        params["classifier"] = linear(H, 1)
    return params
