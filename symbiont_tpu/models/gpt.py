"""Decoder LMs for TPU generation: GPT-2 layout and Llama/TinyLlama layout.

The reference's "text generator" is an order-1 Markov chain trained on one
hardcoded sentence (reference: services/text_generator_service/src/main.rs:13-109,
corpus at :170). BASELINE.json's north star upgrades this to a real
autoregressive LM decoded on TPU (config #5: TinyLlama-1.1B / GPT-2,
tokens/sec/chip + time-to-first-token). This module is that LM:

- pure function over a params pytree, one config for both layouts
  (GPT-2: learned positions + LN + gelu fused-qkv; Llama: RoPE + RMSNorm +
  SwiGLU + GQA);
- static-shape KV cache: prefill at a bucketed prompt length, then a
  `lax.scan` decode loop over a fixed max_new_tokens — no data-dependent
  Python control flow, one executable per (prompt_bucket, gen_bucket);
- sampling: greedy / temperature / top-k, all shape-static;
- tensor-parallel ready: attention heads and MLP hidden are the natural shard
  axes; symbiont_tpu.parallel.sharding places them on the 'tensor' mesh axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from symbiont_tpu.kv import paged as _paged
from symbiont_tpu.kv.paged import PagedKVCache
from symbiont_tpu.models import quant
# RMSNorm / RoPE / SwiGLU are shared with models/mla_moe.py; the underscore
# names are what parallel/context.py and parallel/pipeline.py import
from symbiont_tpu.models.layers import rmsnorm as _rmsnorm
from symbiont_tpu.models.layers import rope as _rope
from symbiont_tpu.models.layers import swiglu

Params = Any


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA (llama); None → num_heads
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    arch: str = "gpt2"  # "gpt2" | "llama"
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    # "flash": prefill (S>1 against an EMPTY cache — generate()/train both
    # qualify) runs the fused pallas kernel over the fresh K/V; decode steps
    # (S==1) stay on the XLA cache-read path either way.
    attn_impl: str = "xla"
    # KV-cache storage: "none" = cfg.dtype slabs (the default), "int8" =
    # per-(position, head)-scaled int8 with quantize-on-append /
    # dequant-on-attend inside the decode step (models/quant.py). Part of
    # the frozen config so the cache layout keys the compiled executables.
    kv_quant: str = "none"

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def from_hf(cfg: dict) -> "GPTConfig":
        mt = cfg.get("model_type", "gpt2")
        if mt == "gpt2":
            return GPTConfig(
                vocab_size=cfg["vocab_size"],
                hidden_size=cfg.get("n_embd", 768),
                num_layers=cfg.get("n_layer", 12),
                num_heads=cfg.get("n_head", 12),
                intermediate_size=cfg.get("n_inner") or 4 * cfg.get("n_embd", 768),
                max_position_embeddings=cfg.get("n_positions", 1024),
                layer_norm_eps=cfg.get("layer_norm_epsilon", 1e-5),
                arch="gpt2",
            )
        if mt in ("llama", "mistral"):
            return GPTConfig(
                vocab_size=cfg["vocab_size"],
                hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg.get("num_key_value_heads"),
                intermediate_size=cfg["intermediate_size"],
                max_position_embeddings=cfg.get("max_position_embeddings", 2048),
                layer_norm_eps=cfg.get("rms_norm_eps", 1e-5),
                arch="llama",
                rope_theta=cfg.get("rope_theta", 10000.0),
                tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            )
        raise ValueError(f"unsupported model_type {mt!r}")


class KVCache(NamedTuple):
    """Static-shape per-layer cache: k/v [L, B, max_len, kv_heads, head_dim]."""

    k: jax.Array
    v: jax.Array
    length: jax.Array  # [] int32 — number of valid positions


class QuantKVCache(NamedTuple):
    """int8 variant (cfg.kv_quant == "int8"): k/v slabs are int8 with one
    f32 scale per (layer, batch, position, kv_head) — quantize-on-append,
    dequant-on-attend. ~2× more session rows per HBM byte vs bf16 slabs
    (~4× vs f32) at ≤0.4% per-vector rounding; the greedy-identity gate in
    tests/test_quantization.py pins the decode-quality contract. Same field
    layout conventions as KVCache (batch at axis 1, scalar length last) so
    merge_rows and the donation-carrying decode loops treat both shapes
    uniformly."""

    k: jax.Array        # int8 [L, B, T, kv_heads, head_dim]
    v: jax.Array
    k_scale: jax.Array  # f32 [L, B, T, kv_heads]
    v_scale: jax.Array
    length: jax.Array   # [] int32


def init_cache(cfg: GPTConfig, batch: int, max_len: int, dtype):
    shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    if cfg.kv_quant == "int8":
        sshape = shape[:-1]
        return QuantKVCache(
            jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
            jnp.zeros(sshape, jnp.float32), jnp.zeros(sshape, jnp.float32),
            jnp.zeros((), jnp.int32))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def cache_bytes(cache) -> int:
    """At-rest bytes of one cache (slabs + scale planes) — feeds the
    dtype-adjusted `lm.kv_cache_bytes` gauge in engine/lm.py."""
    return sum(int(leaf.nbytes) for leaf in cache
               if hasattr(leaf, "nbytes") and getattr(leaf, "ndim", 0) > 0)


# ---------------------------------------------------------------------------
# Norms (RMSNorm / RoPE: models/layers.py)
# ---------------------------------------------------------------------------


def _ln(x, p, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return (((xf - mean) * jax.lax.rsqrt(var + eps)) * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _attn(
    layer: Params,
    x: jax.Array,  # [B, S, H]
    layer_idx: int,
    cache: KVCache,
    positions: jax.Array,  # [B, S] logical positions (RoPE / wpe)
    cfg: GPTConfig,
    kv_valid: Optional[jax.Array],  # [B, T] True where a cache slot is real
) -> tuple[jax.Array, KVCache]:
    B, S, H = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

    # Deliberately THREE projections, not a fused [H, (nh+2nkv)·hd] matmul:
    # fused qkv won an isolated microbenchmark (+23%) but LOST in the real
    # decode loop on v5e (batch-64 GPT-2: ~19.8k → ~15.6k tok/s, measured
    # with the fusion both in-body and pre-computed outside the scan) — the
    # post-matmul slicing into q/k/v interacts badly with the cache-write /
    # attention layout. Re-test on new hardware before "optimizing" this.
    q = (quant.mm(x, layer["q"]["kernel"])
         + layer["q"].get("bias", 0)).reshape(B, S, nh, hd)
    k = (quant.mm(x, layer["k"]["kernel"])
         + layer["k"].get("bias", 0)).reshape(B, S, nkv, hd)
    v = (quant.mm(x, layer["v"]["kernel"])
         + layer["v"].get("bias", 0)).reshape(B, S, nkv, hd)

    if cfg.arch == "llama":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)

    # write into the static cache at [length : length+S] with ONE
    # dynamic_update_slice on the stacked [L, B, T, h, d] array. The previous
    # slice-modify-set form (cache.k[layer_idx] → DUS → .at[layer_idx].set)
    # round-tripped a full layer slab per layer per step and XLA did not
    # always fuse it away: decode ms/step grew linearly with cache length
    # (measured on v5e, TinyLlama geometry: +2.9 ms/step from T=192 → 576).
    start = cache.length

    def _dus(slab, update, rank5=True):
        idx = (layer_idx, 0, start, 0, 0) if rank5 else (layer_idx, 0, start, 0)
        return jax.lax.dynamic_update_slice(slab, update[None], idx)

    if isinstance(cache, PagedKVCache):
        # third layout (kv/paged.py): scatter the S fresh tokens through the
        # row's page-table into the flattened pool token axis, then gather
        # the row's WHOLE cache-index space [0, T) back out — element for
        # element the [B, T, kvh, hd] tensor the dense path reads, so the
        # masks / einsums / softmax below are shared verbatim and paged
        # decode stays token-identical to dense (tests/test_kv_paged.py).
        # Rows with nothing mapped at a block (padding rows, freed rows)
        # write to and read from the scratch page; those reads are always
        # masked (causality / kv_valid / discarded padding-row outputs) and
        # land on finite values, so masked probabilities stay exactly 0.0.
        assert kv_valid is not None, "paged attention requires kv_valid"
        page = cache.page_tokens
        flat_w = _paged.flat_slot_index(
            cache.page_table, start + jnp.arange(S, dtype=jnp.int32), page)

        def _tok(pool):  # [L, n_pages, page, ...] → [L, n_pages·page, ...]
            return pool.reshape((pool.shape[0], -1) + pool.shape[3:])

        def _scat(pool, vals):
            return _tok(pool).at[layer_idx, flat_w].set(
                vals.astype(pool.dtype)).reshape(pool.shape)

        T_r = kv_valid.shape[1]
        flat_r = _paged.flat_slot_index(
            cache.page_table, jnp.arange(T_r, dtype=jnp.int32), page)
        if cache.quantized:
            k_q, k_s = quant.kv_channel_quantize(k)
            v_q, v_s = quant.kv_channel_quantize(v)
            new_cache = PagedKVCache(
                _scat(cache.k, k_q), _scat(cache.v, v_q),
                _scat(cache.k_scale, k_s), _scat(cache.v_scale, v_s),
                cache.page_table, cache.length)
            k_all = quant.kv_dequantize(
                jnp.take(_tok(new_cache.k)[layer_idx], flat_r, axis=0),
                jnp.take(_tok(new_cache.k_scale)[layer_idx], flat_r, axis=0),
                x.dtype)
            v_all = quant.kv_dequantize(
                jnp.take(_tok(new_cache.v)[layer_idx], flat_r, axis=0),
                jnp.take(_tok(new_cache.v_scale)[layer_idx], flat_r, axis=0),
                x.dtype)
        else:
            new_cache = PagedKVCache(
                _scat(cache.k, k), _scat(cache.v, v),
                cache.k_scale, cache.v_scale,
                cache.page_table, cache.length)
            k_all = jnp.take(_tok(new_cache.k)[layer_idx], flat_r, axis=0)
            v_all = jnp.take(_tok(new_cache.v)[layer_idx], flat_r, axis=0)
    elif isinstance(cache, QuantKVCache):
        # quantize-on-append: each fresh (position, head) K/V vector gets
        # its own int8 scale; dequant-on-attend reads the int8 slab + the
        # head_dim×-smaller scale plane out of HBM and upcasts in registers
        k_q, k_s = quant.kv_channel_quantize(k)
        v_q, v_s = quant.kv_channel_quantize(v)
        new_cache = QuantKVCache(
            _dus(cache.k, k_q), _dus(cache.v, v_q),
            _dus(cache.k_scale, k_s, rank5=False),
            _dus(cache.v_scale, v_s, rank5=False), cache.length)
        k_all = quant.kv_dequantize(new_cache.k[layer_idx],
                                    new_cache.k_scale[layer_idx], x.dtype)
        v_all = quant.kv_dequantize(new_cache.v[layer_idx],
                                    new_cache.v_scale[layer_idx], x.dtype)
    else:
        new_cache = KVCache(_dus(cache.k, k.astype(cache.k.dtype)),
                            _dus(cache.v, v.astype(cache.v.dtype)),
                            cache.length)
        k_all = new_cache.k[layer_idx]
        v_all = new_cache.v[layer_idx]

    if cfg.attn_impl == "flash" and S > 1:
        # Prefill-from-empty: attention over exactly the S fresh tokens (the
        # cache holds nothing older — see forward()'s docstring contract), so
        # the kernel runs on the just-projected K/V, GQA handled inside.
        from symbiont_tpu.ops.flash_attention import flash_attention

        bias = None
        if kv_valid is not None:
            bias = jnp.where(kv_valid[:, :S], 0.0, -1e9).astype(jnp.float32)
        ctx = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), kv_bias=bias, causal=True,
        ).transpose(0, 2, 1, 3).reshape(B, S, H)
        out = quant.mm(ctx, layer["o"]["kernel"]) + layer["o"].get("bias", 0)
        return out, new_cache

    T = k_all.shape[1]
    # GQA without jnp.repeat: query heads are grouped onto their kv head in
    # a 5D einsum instead of materializing K/V at full head count — at
    # TinyLlama geometry (32/4 heads) the repeat inflated per-step K/V
    # traffic 8×, and it grew linearly with cache length.
    group = nh // nkv
    q5 = q.reshape(B, S, nkv, group, hd)
    scores = jnp.einsum("bsngd,btnd->bngst", q5,
                        k_all.astype(q.dtype)) / math.sqrt(hd)
    # causality runs over CACHE indices (where K/V physically live), not
    # logical positions — they differ for padded rows; padding slots are
    # excluded via kv_valid. Shapes broadcast over [B, nkv, group, S, T].
    kv_pos = jnp.arange(T)[None, None, None, None, :]
    q_cache_pos = (start + jnp.arange(S))[None, None, None, :, None]
    valid = (kv_pos <= q_cache_pos) & (kv_pos < (start + S))
    if kv_valid is not None:
        valid = valid & kv_valid[:, None, None, None, :]
    if x.dtype == jnp.bfloat16:
        # softmax in bf16, same rationale as models/bert.py attention: the
        # f32 round-trip doubles the [B, nh, S, T] intermediate's HBM
        # traffic, and bf16 matmul noise already dominates the rounding
        scores = jnp.where(valid, scores, jnp.asarray(-1e9, scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        scores = jnp.where(valid, scores.astype(jnp.float32), -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bngst,btnd->bsngd", probs,
                     v_all.astype(x.dtype)).reshape(B, S, H)
    out = quant.mm(ctx, layer["o"]["kernel"]) + layer["o"].get("bias", 0)
    return out, new_cache


def _block(layer, x, layer_idx, cache, positions, cfg, kv_valid):
    if cfg.arch == "gpt2":
        a, cache = _attn(layer, _ln(x, layer["ln1"], cfg.layer_norm_eps),
                         layer_idx, cache, positions, cfg, kv_valid)
        x = x + a
        h = _ln(x, layer["ln2"], cfg.layer_norm_eps)
        h = quant.mm(h, layer["mlp"]["in"]["kernel"]) + layer["mlp"]["in"]["bias"]
        h = jax.nn.gelu(h, approximate=True)  # GPT-2 uses gelu_new
        h = quant.mm(h, layer["mlp"]["out"]["kernel"]) + layer["mlp"]["out"]["bias"]
        return x + h, cache
    # llama
    a, cache = _attn(layer, _rmsnorm(x, layer["ln1"], cfg.layer_norm_eps),
                     layer_idx, cache, positions, cfg, kv_valid)
    x = x + a
    h = swiglu(_rmsnorm(x, layer["ln2"], cfg.layer_norm_eps), layer["mlp"])
    return x + h, cache


def qkv_proj(layer, h: jax.Array, positions: jax.Array, cfg: GPTConfig):
    """QKV projection + RoPE, no cache — the shared front half of attention
    for the training-side forwards (parallel/context.py, parallel/pipeline.py).
    Returns (q [B,S,nh,hd], k [B,S,nkv,hd], v [B,S,nkv,hd])."""
    B, S, _ = h.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    q = (h @ layer["q"]["kernel"] + layer["q"].get("bias", 0)).reshape(B, S, nh, hd)
    k = (h @ layer["k"]["kernel"] + layer["k"].get("bias", 0)).reshape(B, S, nkv, hd)
    v = (h @ layer["v"]["kernel"] + layer["v"].get("bias", 0)).reshape(B, S, nkv, hd)
    if cfg.arch == "llama":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    return q, k, v


def block_nocache(layer, x: jax.Array, cfg: GPTConfig, attn) -> jax.Array:
    """Decoder-block scaffolding (norms, residuals, MLP) with a pluggable
    attention callable `attn(normed_hidden) -> attention output incl. o-proj`.
    ONE home for the per-arch block math on the cache-free training paths —
    _block above is its cache-threading twin for decode. Used by the
    sequence-parallel (parallel/context.py) and pipeline-parallel
    (parallel/pipeline.py) forwards so they cannot drift from each other."""
    if cfg.arch == "gpt2":
        x = x + attn(_ln(x, layer["ln1"], cfg.layer_norm_eps))
        h = _ln(x, layer["ln2"], cfg.layer_norm_eps)
        h = h @ layer["mlp"]["in"]["kernel"] + layer["mlp"]["in"]["bias"]
        h = jax.nn.gelu(h, approximate=True)  # GPT-2 uses gelu_new
        h = h @ layer["mlp"]["out"]["kernel"] + layer["mlp"]["out"]["bias"]
        return x + h
    x = x + attn(_rmsnorm(x, layer["ln1"], cfg.layer_norm_eps))
    return x + swiglu(_rmsnorm(x, layer["ln2"], cfg.layer_norm_eps),
                      layer["mlp"])


def forward(
    params: Params,
    input_ids: jax.Array,  # [B, S]
    cache: KVCache,
    positions: jax.Array,  # [B, S] absolute logical positions of these tokens
    cfg: GPTConfig,
    kv_valid: Optional[jax.Array] = None,  # [B, cache_len] mask of real slots
) -> tuple[jax.Array, KVCache]:
    """Forward over S new tokens against the cache → (logits [B, S, V], cache).

    Tokens are written at cache indices [cache.length, cache.length+S); when
    rows carry left-padding (batched generation), pass kv_valid=False on the
    padding slots so attention never reads them.

    With cfg.attn_impl == "flash", any S>1 call MUST be prefill against an
    empty cache (cache.length == 0) — the fused kernel attends over exactly
    the S fresh tokens and would silently ignore older cache entries.
    generate() and the trainer both satisfy this; chunked prefill against a
    partially-filled cache requires attn_impl == "xla"."""
    dtype = jnp.dtype(cfg.dtype)
    # leaf-aware cast (models/quant.py): floating params → compute dtype,
    # QuantTensor leaves untouched so their f32 scales survive
    params = quant.cast_params(params, dtype)
    x = quant.take(params["wte"], input_ids)
    if cfg.arch == "gpt2":
        x = x + quant.take(params["wpe"], positions)
    x = x.astype(dtype)  # quantized gathers dequantize to f32
    for i, layer in enumerate(params["layers"]):
        x, cache = _block(layer, x, i, cache, positions, cfg, kv_valid)
    if cfg.arch == "gpt2":
        x = _ln(x, params["ln_f"], cfg.layer_norm_eps)
    else:
        x = _rmsnorm(x, params["ln_f"], cfg.layer_norm_eps)
    if cfg.tie_word_embeddings:
        logits = quant.mm_tied(x, params["wte"]).astype(jnp.float32)
    else:
        logits = quant.mm(x, params["lm_head"]["kernel"]).astype(jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# Generation (static shapes; one executable per (prompt_len, max_new) pair)
# ---------------------------------------------------------------------------


def _top_k_bucket(top_k: int, vocab: int) -> int:
    """Static power-of-two bucket for the top-k cutoff. lax.top_k needs a
    static k, but compiling one executable per client-supplied value would
    mint unbounded executables (the ills bucketing exists to prevent
    everywhere else in this repo) — so the compiled cutoff width is the next
    power of two and the *exact* requested k selects the threshold
    dynamically inside it (_sample). 0 = no cutoff (top_k<=0, or >= vocab
    where the cutoff is a no-op)."""
    if top_k <= 0 or top_k >= vocab:
        return 0
    b = 8
    while b < top_k:
        b *= 2
    return min(b, vocab)


def _norm_sampling(temperature, top_k, B: int, vocab: int):
    """Normalize scalar-or-per-row sampling params to [B] device vectors plus
    the static top-k bucket wide enough for every row's cutoff."""
    t = np.broadcast_to(np.asarray(temperature, np.float32), (B,))
    k = np.broadcast_to(np.asarray(top_k, np.int32), (B,))
    cut = [int(x) for x in k if 0 < int(x) < vocab]
    bucket = _top_k_bucket(max(cut), vocab) if cut else 0
    return jnp.asarray(t), jnp.asarray(k), bucket


def _sample(logits: jax.Array, key: jax.Array, temperature, top_k,
            top_k_bucket: int) -> jax.Array:
    """temperature/top_k are TRACED per-row [B] vectors (a new sampling value
    must not recompile the decode loop, and rows of one batch may carry
    different sampling params); only top_k_bucket is static. Per row:
    temperature<=0 selects greedy; top_k<=0 (or >= vocab) disables the
    cutoff; otherwise semantics match exact top-k for any k in the bucket."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.maximum(t, 1e-6)[..., None]
    tk = jnp.asarray(top_k, jnp.int32)
    if top_k_bucket > 0:
        vals = jax.lax.top_k(scaled, top_k_bucket)[0]  # [..., bucket] desc
        idx = jnp.clip(tk, 1, top_k_bucket) - 1
        kth = jnp.take_along_axis(vals, idx[..., None], axis=-1)  # exact k-th
        cut = (tk > 0) & (tk < vocab)
        scaled = jnp.where(cut[..., None] & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(t <= 0.0, greedy, sampled)


def _align_prompt(prompt_ids: jax.Array, prompt_mask: jax.Array,
                  max_new_tokens: int):
    """Right-align prefix-aligned prompts (shared by generate and the
    streaming decoder): returns (ids_r, positions, kv_valid, prompt_len)."""
    B, P = prompt_ids.shape
    prompt_len = prompt_mask.astype(jnp.int32).sum(axis=1)  # [B]
    pad = P - prompt_len  # left-pad width per row after alignment

    j = jnp.arange(P, dtype=jnp.int32)[None, :]
    src = j - pad[:, None]
    ids_r = jnp.take_along_axis(prompt_ids, jnp.clip(src, 0, P - 1), axis=1)
    ids_r = jnp.where(src >= 0, ids_r, 0)
    positions = jnp.maximum(src, 0)

    kv_valid = jnp.concatenate(
        [j >= pad[:, None], jnp.ones((B, max_new_tokens), bool)], axis=1)
    return ids_r, positions, kv_valid, prompt_len


def _decode_step(params, cfg: GPTConfig, kv_valid, temperature, top_k,
                 top_k_bucket: int, eos_id: int):
    """The one-token decode step shared by the full scan and chunked scans."""

    def step(carry, step_key):
        cache, cur_logits, cur_pos, done = carry
        tok = _sample(cur_logits, step_key, temperature, top_k, top_k_bucket)
        tok = jnp.where(done, 0, tok)
        if eos_id >= 0:
            counted = ~done & (tok != eos_id)
            new_done = done | (tok == eos_id)
        else:
            counted = ~done
            new_done = done
        logits, new_cache = forward(params, tok[:, None], cache,
                                    cur_pos[:, None], cfg, kv_valid)
        new_cache = new_cache._replace(length=cache.length + 1)
        return (new_cache, logits[:, 0, :], cur_pos + 1, new_done), (tok, counted)

    return step


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens"))
@jax.named_scope("symbiont.prefill")  # in each device op's metadata
def prefill(params, prompt_ids, prompt_mask, cfg: GPTConfig,
            max_new_tokens: int):
    """Prompt forward against a fresh cache sized for max_new_tokens more
    tokens. Returns (cache, next_logits, kv_valid, prompt_len) — the carry a
    chunked decode loop resumes from."""
    B, P = prompt_ids.shape
    cache = init_cache(cfg, B, P + max_new_tokens, jnp.dtype(cfg.dtype))
    ids_r, positions, kv_valid, prompt_len = _align_prompt(
        prompt_ids, prompt_mask, max_new_tokens)
    logits, cache = forward(params, ids_r, cache, positions, cfg, kv_valid)
    cache = cache._replace(length=jnp.asarray(P, jnp.int32))
    return cache, logits[:, -1, :], kv_valid, prompt_len


@partial(jax.jit, static_argnames=("cfg", "top_k_bucket", "eos_id"),
         donate_argnames=("cache", "cur_logits", "cur_pos", "done"))
@jax.named_scope("symbiont.decode")  # in each device op's metadata
def _decode_chunk_jit(params, cache, cur_logits, cur_pos, done, kv_valid,
                      keys, temperature, top_k, cfg: GPTConfig,
                      top_k_bucket: int, eos_id: int):
    # The carry is DONATED: the KV cache at serving size is GBs (TinyLlama
    # b128 x 960 slots = 5.5 GB), and without donation every chunk call kept
    # input AND output caches resident and copied between them — measured
    # 385 ms/step at that shape (HBM thrash) vs ~14 ms donated. Callers
    # must treat the passed-in carry as consumed (every call site
    # reassigns).
    step = _decode_step(params, cfg, kv_valid, temperature, top_k,
                        top_k_bucket, eos_id)
    (cache, logits, pos, done), (tokens, counted) = jax.lax.scan(
        step, (cache, cur_logits, cur_pos, done), keys)
    return cache, logits, pos, done, tokens.T, counted.T


def decode_chunk(params, cache, cur_logits, cur_pos, done, kv_valid, keys,
                 cfg: GPTConfig, temperature=0.8, top_k=40,
                 eos_id: int = -1):
    """Scan `len(keys)` decode steps from a carried state; chunk length is
    static via the keys shape, so a streaming loop reuses ONE executable per
    (prompt_bucket, chunk) pair — temperature and the exact top_k are traced
    per-row vectors (only the power-of-two top_k bucket is compiled in), so
    new sampling values reuse it too. Returns (carry..., tokens [B, C],
    counted [B, C])."""
    t, k, bucket = _norm_sampling(temperature, top_k,
                                  cur_logits.shape[0], cfg.vocab_size)
    return _decode_chunk_jit(
        params, cache, cur_logits, cur_pos, done, kv_valid, keys,
        t, k, cfg, top_k_bucket=bucket, eos_id=eos_id)


def merge_rows(cache_a, logits_a, pos_a, done_a, kv_valid_a,
               cache_b, logits_b, pos_b, done_b, kv_valid_b,
               row_map, prompt_width: int):
    """Continuous batching: splice freshly-prefilled rows (state b) into an
    in-flight chunked decode (state a) at a chunk boundary. cache_a is
    DONATED (serving-size caches are GBs; the input is dead after the
    splice — every caller reassigns from the return). cache_b cannot alias
    the output (its batch dim is the admission bucket, not the session's),
    so donating it would only provoke unusable-donation warnings.

    row_map [B] int32: row_map[i] = j ≥ 0 replaces a's row i with b's row j;
    -1 keeps a's row. Both states must share the cache layout (same
    prompt_width bucket and new-token bucket, so T matches). The spliced
    rows' cache slots [prompt_width, a.length) — the steps a decoded before
    admission — are masked invalid: the row's own decode continues at cache
    slot a.length while its logical position carries on from its prompt, so
    its output is EXACTLY what a standalone decode would produce (the same
    right-alignment independence generate() guarantees across batchmates).

    Three layouts splice through here. Dense KVCache and int8 QuantKVCache
    share the field-wise jit below (scale planes ride batch axis 1 like the
    slabs). For the paged layout cache_a is a PagedKVCache and cache_b is a
    TRIPLE ``(staging, scatter_table, new_page_table)``: the dense-staged
    prefill (None when every admitted row was a full radix hit and prefill
    was skipped outright), a [bb, prompt_width/page] table mapping each
    staging row's prompt blocks to the pool pages the engine allocated for
    it (all-scratch rows for rejected / full-hit staging rows), and the
    session's rebuilt [B, n_blocks] device page table. The cache half then
    happens IN THE POOL (kv/paged.scatter_prompt, pools donated) while the
    row-state half (kv/paged.merge_row_state) applies the same row_map +
    gap-masking contract as the dense splice.

    One compiled executable per (shapes, prompt_width); the row pattern is
    traced, so which rows get replaced never recompiles."""
    if isinstance(cache_a, PagedKVCache):
        staging, scatter_table, new_page_table = cache_b
        k, v, ks, vs = cache_a.k, cache_a.v, cache_a.k_scale, cache_a.v_scale
        if staging is not None:
            k, v, ks, vs = _paged.scatter_prompt(
                k, v, ks, vs, staging, scatter_table, prompt_width)
        logits, pos, done, kvv = _paged.merge_row_state(
            logits_a, pos_a, done_a, kv_valid_a,
            logits_b, pos_b, done_b, kv_valid_b,
            row_map, cache_a.length, prompt_width)
        cache = PagedKVCache(k, v, ks, vs, new_page_table, cache_a.length)
        return cache, logits, pos, done, kvv
    return _merge_rows_jit(cache_a, logits_a, pos_a, done_a, kv_valid_a,
                           cache_b, logits_b, pos_b, done_b, kv_valid_b,
                           row_map, prompt_width=prompt_width)


@partial(jax.jit, static_argnames=("prompt_width",),
         donate_argnames=("cache_a",))
def _merge_rows_jit(cache_a, logits_a, pos_a, done_a, kv_valid_a,
                    cache_b, logits_b, pos_b, done_b, kv_valid_b,
                    row_map, prompt_width: int):
    B = logits_a.shape[0]
    T = cache_a.k.shape[2]
    sel = row_map >= 0
    j = jnp.clip(row_map, 0, logits_b.shape[0] - 1)

    def pick(a, b, batch_axis=0):
        take = jnp.take(b, j, axis=batch_axis)
        shape = [1] * a.ndim
        shape[batch_axis] = B
        return jnp.where(sel.reshape(shape), take, a)

    # the gap a decoded while b wasn't there: invalid for spliced rows forever
    t_idx = jnp.arange(T)
    gap = (t_idx >= prompt_width) & (t_idx < cache_a.length)
    kv_b = kv_valid_b & ~gap[None, :]
    # field-wise splice covers both cache layouts (KVCache and the int8
    # QuantKVCache, whose scale planes ride batch axis 1 like the slabs);
    # the scalar `length` field keeps a's value
    cache = type(cache_a)(*[
        fa if fa.ndim == 0 else pick(fa, fb, batch_axis=1)
        for fa, fb in zip(cache_a, cache_b)])
    return (cache, pick(logits_a, logits_b), pick(pos_a, pos_b),
            pick(done_a, done_b), pick(kv_valid_a, kv_b))


# ---------------------------------------------------------------------------
# Speculative decoding (docs/SPECULATIVE.md): draft k greedy tokens on a
# small model's own cache, score all k+1 positions with ONE target forward,
# emit the longest accepted prefix plus the target's correction.
#
# State contract ("spec state", vs the "plain state" decode_chunk carries):
# the cache holds every emitted token EXCEPT the last one, which rides
# host-side as `pending` [B]; `cur_pos` is pending's logical position. Each
# round writes the S = k+1 window [pending, d_1..d_k] into BOTH caches
# (drafter via its scan + one extra forward of d_k, target via the verify
# forward), so the two planes share ONE kv_valid / cur_pos / done and one
# scalar length advance of S per round. Raggedness lives ONLY in kv_valid:
# slot j of a row's window stays valid iff j <= accepted(row) — rejected
# draft slots become permanent holes the attention mask already excludes
# (the same mechanism that masks left-padding), so plain decode_chunk keeps
# working against a hole-y cache and no attention code changes at all.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("top_k_bucket", "eos_id"))
def _spec_first_jit(cur_logits, done, key, temperature, top_k,
                    top_k_bucket: int, eos_id: int):
    """plain → spec transition: sample ONE token from carried logits (exactly
    what the next plain step would emit) without forwarding it — it becomes
    `pending`. Returns (tok, counted, new_done)."""
    tok = _sample(cur_logits, key, temperature, top_k, top_k_bucket)
    tok = jnp.where(done, 0, tok)
    if eos_id >= 0:
        counted = ~done & (tok != eos_id)
        new_done = done | (tok == eos_id)
    else:
        counted = ~done
        new_done = done
    return tok, counted, new_done


def spec_first(cur_logits, done, key, cfg: GPTConfig, temperature=0.8,
               top_k=40, eos_id: int = -1):
    t, k, bucket = _norm_sampling(temperature, top_k,
                                  cur_logits.shape[0], cfg.vocab_size)
    return _spec_first_jit(cur_logits, done, key, t, k,
                           top_k_bucket=bucket, eos_id=eos_id)


@partial(jax.jit, static_argnames=("dcfg", "spec_k"),
         donate_argnames=("d_cache",))
def _draft_chunk_jit(draft_params, d_cache, pending, cur_pos, done, kv_valid,
                     dcfg: GPTConfig, spec_k: int):
    """Drafter plane: scan k GREEDY steps from `pending` on the drafter's own
    dense cache — one dispatch, same shape discipline as decode_chunk. The
    drafter always proposes greedily (a point-mass proposal), which keeps
    sampled-row acceptance a bare p_target(draft) coin flip in verify. After
    the scan, d_k itself is forwarded once more (logits discarded) so the
    drafter consumes exactly the same k+1 window slots the target's verify
    writes — slot symmetry is what lets both planes share one kv_valid."""

    def step(carry, _):
        cache, tok, pos = carry
        tok = jnp.where(done, 0, tok)
        logits, cache = forward(draft_params, tok[:, None], cache,
                                pos[:, None], dcfg, kv_valid)
        cache = cache._replace(length=cache.length + 1)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1), nxt

    (cache, tok, pos), drafts = jax.lax.scan(
        step, (d_cache, pending, cur_pos), None, length=spec_k)
    tok = jnp.where(done, 0, tok)
    _, cache = forward(draft_params, tok[:, None], cache, pos[:, None],
                       dcfg, kv_valid)
    cache = cache._replace(length=cache.length + 1)
    return cache, drafts.T  # [B, k]


def draft_chunk(draft_params, d_cache, pending, cur_pos, done, kv_valid,
                dcfg: GPTConfig, spec_k: int):
    return _draft_chunk_jit(draft_params, d_cache, pending, cur_pos, done,
                            kv_valid, dcfg=dcfg, spec_k=spec_k)


@partial(jax.jit, static_argnames=("cfg", "top_k_bucket", "eos_id"),
         donate_argnames=("cache", "cur_pos", "done", "kv_valid"))
def _verify_chunk_jit(params, cache, pending, drafts, cur_pos, done, kv_valid,
                      key_u, key_c, temperature, top_k, cfg: GPTConfig,
                      top_k_bucket: int, eos_id: int):
    B, k = drafts.shape
    S = k + 1
    # One forward scores every draft position: logits[:, j] is the target's
    # next-token distribution AFTER seq[:, :j+1], i.e. slot j scores d_{j+1}
    # (and slot k is the bonus position past the last draft).
    seq = jnp.concatenate([pending[:, None], drafts], axis=1)  # [B, S]
    seq = jnp.where(done[:, None], 0, seq)
    positions = cur_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    logits, new_cache = forward(params, seq, cache, positions, cfg, kv_valid)
    new_cache = new_cache._replace(length=cache.length + S)

    # the SAME transformed distribution _sample draws from (temperature
    # scale + exact-k top-k cutoff inside the static bucket), per row
    t = jnp.asarray(temperature, jnp.float32)
    greedy_row = t <= 0.0
    tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    scaled = logits / jnp.maximum(t, 1e-6)[:, None, None]
    tk = jnp.asarray(top_k, jnp.int32)
    if top_k_bucket > 0:
        vals = jax.lax.top_k(scaled, top_k_bucket)[0]  # [B, S, bucket] desc
        idx = jnp.clip(tk, 1, top_k_bucket) - 1
        kth = jnp.take_along_axis(
            vals, jnp.broadcast_to(idx[:, None, None], (B, S, 1)), axis=-1)
        cut = (tk > 0) & (tk < cfg.vocab_size)
        scaled = jnp.where(cut[:, None, None] & (scaled < kth),
                           -jnp.inf, scaled)

    # Acceptance. Greedy rows: longest exact-match prefix against the
    # target's own argmax — token-identical to plain decode by construction.
    # Sampled rows: the drafter's proposal is a point mass (greedy drafts),
    # so min(1, p/q) collapses to p_target(draft) — one uniform per slot.
    probs = jax.nn.softmax(scaled, axis=-1)
    p_d = jnp.take_along_axis(probs[:, :k, :], drafts[:, :, None],
                              axis=-1)[..., 0]            # [B, k]
    u = jax.random.uniform(key_u, (B, k))
    acc = jnp.where(greedy_row[:, None], drafts == tgt[:, :k], u < p_d)
    m = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)  # [B] 0..k

    # Correction token at output slot m. Sampled rows draw from the
    # rejection residual — p with the rejected draft token masked out
    # (point-mass q makes norm(max(p-q,0)) exactly that), or the untouched
    # slot-k distribution when every draft was accepted (the bonus token).
    drafts_pad = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], 1)
    scaled_m = jnp.take_along_axis(scaled, m[:, None, None], axis=1)[:, 0, :]
    d_rej = jnp.take_along_axis(drafts_pad, m[:, None], axis=1)[:, 0]
    rej_mask = jax.nn.one_hot(d_rej, cfg.vocab_size, dtype=bool)
    do_mask = (~greedy_row) & (m < k)
    scaled_m = jnp.where(do_mask[:, None] & rej_mask, -jnp.inf, scaled_m)
    sampled_c = jax.random.categorical(key_c, scaled_m, axis=-1)
    tgt_m = jnp.take_along_axis(tgt, m[:, None], axis=1)[:, 0]
    corr = jnp.where(greedy_row, tgt_m, sampled_c.astype(jnp.int32))

    # Emission: slots 0..m-1 are the accepted drafts, slot m the correction.
    # EOS bookkeeping mirrors _decode_step: the eos token itself is emitted
    # but not counted, nothing after it counts, the row goes done.
    jj = jnp.arange(S, dtype=jnp.int32)[None, :]
    out = jnp.where(jj < m[:, None], drafts_pad,
                    jnp.where(jj == m[:, None], corr[:, None], 0))
    emit = (jj <= m[:, None]) & ~done[:, None]
    out = jnp.where(emit, out, 0)
    if eos_id >= 0:
        hit = emit & (out == eos_id)
        before = jnp.cumsum(hit, axis=1) - hit  # exclusive: any eos earlier?
        counted = emit & (before == 0) & (out != eos_id)
        new_done = done | hit.any(axis=1)
    else:
        counted = emit
        new_done = done

    # Window validity + advances: rejected slots j > m become permanent
    # kv_valid holes; rows already done mark the whole window "valid" junk,
    # exactly like plain decode writing forced-0 tokens for done rows.
    m_adv = jnp.where(done, k, m)
    window = jnp.arange(S, dtype=jnp.int32)[None, :] <= m_adv[:, None]
    new_kvv = jax.lax.dynamic_update_slice(kv_valid, window, (0, cache.length))
    new_pos = cur_pos + jnp.where(done, S, m + 1)
    new_pending = jnp.where(new_done, 0, corr)
    emitted = jnp.where(done, 0, m + 1)
    return (new_cache, new_pending, new_pos, new_done, new_kvv,
            out, counted, emitted)


def verify_chunk(params, cache, pending, drafts, cur_pos, done, kv_valid,
                 key, cfg: GPTConfig, temperature=0.8, top_k=40,
                 eos_id: int = -1):
    """Score k drafts + emit in ONE target dispatch. The carry (cache,
    cur_pos, done, kv_valid) is donated like decode_chunk's — callers
    reassign from the return. Returns (cache, pending, cur_pos, done,
    kv_valid, out [B, k+1], counted [B, k+1], emitted [B]); a row's emitted
    tokens are out[i, :emitted[i]] filtered through counted (eos cut)."""
    t, tk, bucket = _norm_sampling(temperature, top_k,
                                   pending.shape[0], cfg.vocab_size)
    key_u, key_c = jax.random.split(key)
    return _verify_chunk_jit(params, cache, pending, drafts, cur_pos, done,
                             kv_valid, key_u, key_c, t, tk, cfg,
                             top_k_bucket=bucket, eos_id=eos_id)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("cache", "cur_pos"))
def _ingest_pending_jit(params, cache, pending, cur_pos, done, kv_valid,
                        cfg: GPTConfig):
    tok = jnp.where(done, 0, pending)
    logits, new_cache = forward(params, tok[:, None], cache,
                                cur_pos[:, None], cfg, kv_valid)
    new_cache = new_cache._replace(length=cache.length + 1)
    return new_cache, logits[:, 0, :], cur_pos + 1


def ingest_pending(params, cache, pending, cur_pos, done, kv_valid,
                   cfg: GPTConfig):
    """spec → plain transition: forward `pending` into the cache (one slot)
    and recover carried logits, after which decode_chunk / merge_rows apply.
    The logits are what an identically-positioned plain step would compute,
    so a greedy stream stays token-identical across the mode switch."""
    return _ingest_pending_jit(params, cache, pending, cur_pos, done,
                               kv_valid, cfg=cfg)


@partial(jax.jit, donate_argnames=("cache_a",))
def merge_cache_rows(cache_a, cache_b, row_map):
    """Drafter-side half of a continuous-batching splice: field-wise row
    pick (batch axis 1 on every slab, scalar length keeps a's) mirroring
    _merge_rows_jit, minus the logits/gap handling — gap validity for the
    drafter is governed by the SHARED kv_valid the target-side merge_rows
    already masks. cache_b rows come from a drafter prefill at the same
    prompt bucket, so slabs line up slot for slot."""
    B = cache_a.k.shape[1]
    sel = row_map >= 0
    j = jnp.clip(row_map, 0, cache_b.k.shape[1] - 1)

    def pick(a, b):
        take = jnp.take(b, j, axis=1)
        shape = [1] * a.ndim
        shape[1] = B
        return jnp.where(sel.reshape(shape), take, a)

    return type(cache_a)(*[fa if fa.ndim == 0 else pick(fa, fb)
                           for fa, fb in zip(cache_a, cache_b)])


@partial(jax.jit, static_argnames=("dcfg",), donate_argnames=("d_cache",))
def _track_chunk_jit(draft_params, d_cache, toks, start_pos, kv_valid,
                     dcfg: GPTConfig):
    B, S = toks.shape
    positions = start_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    _, cache = forward(draft_params, toks, cache=d_cache,
                       positions=positions, cfg=dcfg, kv_valid=kv_valid)
    return cache._replace(length=d_cache.length + S)


def track_chunk(draft_params, d_cache, toks, start_pos, kv_valid,
                dcfg: GPTConfig):
    """Drafter lockstep through a PLAIN interlude: teacher-force the tokens
    a plain decode chunk just wrote into the TARGET cache (decode_chunk's
    returned `toks` — exactly its written content, done-row zeros included)
    into the drafter's cache at the same slots/positions, one dispatch.
    Keeps the two planes slot-symmetric so speculation can re-enter after a
    margin fallback or a splice without a drafter re-prefill."""
    return _track_chunk_jit(draft_params, d_cache, toks, start_pos, kv_valid,
                            dcfg=dcfg)


@partial(jax.jit,
         static_argnames=("cfg", "max_new_tokens", "top_k_bucket", "eos_id"))
def _generate_jit(params, prompt_ids, prompt_mask, key, temperature, top_k,
                  cfg: GPTConfig, max_new_tokens: int, top_k_bucket: int,
                  eos_id: int):
    B = prompt_ids.shape[0]
    cache, next_logits, kv_valid, prompt_len = prefill(
        params, prompt_ids, prompt_mask, cfg, max_new_tokens)

    step = _decode_step(params, cfg, kv_valid, temperature, top_k,
                        top_k_bucket, eos_id)
    keys = jax.random.split(key, max_new_tokens)
    init = (cache, next_logits, prompt_len, jnp.zeros((B,), bool))
    _, (tokens, counted) = jax.lax.scan(step, init, keys)
    tokens = tokens.T  # [B, max_new]
    lengths = counted.T.astype(jnp.int32).sum(axis=1)
    return tokens, lengths


def generate(
    params: Params,
    prompt_ids: jax.Array,  # [B, P] left-padded with pad_id? No: right-aligned real tokens
    prompt_mask: jax.Array,  # [B, P] 1 for real prompt tokens (prefix-aligned)
    key: jax.Array,
    cfg: GPTConfig,
    max_new_tokens: int = 64,
    temperature=0.8,
    top_k=40,
    eos_id: int = -1,
) -> tuple[jax.Array, jax.Array]:
    """Prefill + scan decode. Returns (tokens [B, max_new_tokens], lengths [B]).

    Prompts arrive prefix-aligned (real tokens first, padding after); they are
    right-aligned internally so every row's last prompt token sits at cache
    index P-1 and decode steps share cache indices P, P+1, ... across the
    batch, with left-padding slots masked out of attention via kv_valid.
    Rows stop at eos_id (if ≥0); lengths counts tokens generated before eos.

    temperature and the exact top_k are traced per-row [B] vectors (scalars
    broadcast) — per-request sampling values never recompile, and rows of one
    batch may sample differently; only (shapes, cfg, top_k's power-of-two
    bucket, eos_id) key the executable.
    """
    t, k, bucket = _norm_sampling(temperature, top_k,
                                  prompt_ids.shape[0], cfg.vocab_size)
    return _generate_jit(params, prompt_ids, prompt_mask, key, t, k, cfg,
                         max_new_tokens=max_new_tokens,
                         top_k_bucket=bucket, eos_id=eos_id)


# ---------------------------------------------------------------------------
# Init (random params; real weights via convert_gpt)
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: GPTConfig) -> Params:
    keys = jax.random.split(key, 4 + cfg.num_layers)

    def dense(k, shape, scale=0.02):
        return jax.random.normal(k, shape, jnp.float32) * scale

    H, I, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nkv = cfg.kv_heads

    def make_layer(k):
        ks = jax.random.split(k, 8)
        if cfg.arch == "gpt2":
            return {
                "ln1": {"scale": jnp.ones(H), "bias": jnp.zeros(H)},
                "ln2": {"scale": jnp.ones(H), "bias": jnp.zeros(H)},
                "q": {"kernel": dense(ks[0], (H, H)), "bias": jnp.zeros(H)},
                "k": {"kernel": dense(ks[1], (H, H)), "bias": jnp.zeros(H)},
                "v": {"kernel": dense(ks[2], (H, H)), "bias": jnp.zeros(H)},
                "o": {"kernel": dense(ks[3], (H, H)), "bias": jnp.zeros(H)},
                "mlp": {
                    "in": {"kernel": dense(ks[4], (H, I)), "bias": jnp.zeros(I)},
                    "out": {"kernel": dense(ks[5], (I, H)), "bias": jnp.zeros(H)},
                },
            }
        return {
            "ln1": {"scale": jnp.ones(H)},
            "ln2": {"scale": jnp.ones(H)},
            "q": {"kernel": dense(ks[0], (H, H))},
            "k": {"kernel": dense(ks[1], (H, nkv * hd))},
            "v": {"kernel": dense(ks[2], (H, nkv * hd))},
            "o": {"kernel": dense(ks[3], (H, H))},
            "mlp": {
                "gate": {"kernel": dense(ks[4], (H, I))},
                "up": {"kernel": dense(ks[5], (H, I))},
                "down": {"kernel": dense(ks[6], (I, H))},
            },
        }

    params: Params = {
        "wte": dense(keys[0], (cfg.vocab_size, H)),
        "layers": [make_layer(k) for k in keys[4:]],
        "ln_f": ({"scale": jnp.ones(H), "bias": jnp.zeros(H)} if cfg.arch == "gpt2"
                 else {"scale": jnp.ones(H)}),
    }
    if cfg.arch == "gpt2":
        params["wpe"] = dense(keys[1], (cfg.max_position_embeddings, H))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"kernel": dense(keys[2], (H, cfg.vocab_size))}
    return params
