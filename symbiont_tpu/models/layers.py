"""Blocks that more than one model family computes the same way: RMSNorm,
rotary embedding (half-split pairing) and the SwiGLU feed-forward. Homes:
models/gpt.py's llama branch and models/mla_moe.py (ROADMAP Design 2: one
definition, imported, never copied).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from symbiont_tpu.models import quant


def rmsnorm(x, p, eps):
    """x * rsqrt(mean(x^2) + eps) * scale, statistics in float32."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (xf * scale * p["scale"]).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, S, H, D], positions: [B, S]. Pairs dimension
    i with i + D/2 (HF Llama's `rotate_half`), frequency theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(h, mlp):
    """W_down(silu(W_gate h) * W_up h) over `mlp` = {gate, up, down} kernels
    stored [in, out]; plain, bf16-at-rest or QuantTensor (quant.mm)."""
    gate = jax.nn.silu(quant.mm(h, mlp["gate"]["kernel"]))
    up = quant.mm(h, mlp["up"]["kernel"])
    return quant.mm(gate * up, mlp["down"]["kernel"])
