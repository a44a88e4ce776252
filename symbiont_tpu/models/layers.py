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


def _rope_angles(positions: jax.Array, d: int, theta: float) -> jax.Array:
    """[B, S] positions -> [B, S, D/2] float32, frequency theta^(-2i/D)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return positions[..., None].astype(jnp.float32) * freqs


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, S, H, D], positions: [B, S]. Pairs dimension
    i with i + D/2 (HF Llama's `rotate_half`), frequency theta^(-2i/D)."""
    angles = _rope_angles(positions, x.shape[-1], theta)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_tables(positions: jax.Array, d: int, theta: float
                ) -> tuple[jax.Array, jax.Array]:
    """`rope`'s rotation as (cos, sin) [B, S, D] float32 for a kernel that
    turns q and k itself (ops/flash_attention.py `packed_attention`): cos
    laid twice over and sin with its first half negated, so that
    `x * cos + rotate(x, D/2) * sin` is `rope(x)` for a whole head."""
    angles = _rope_angles(positions, d, theta)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def swiglu(h, mlp):
    """W_down(silu(W_gate h) * W_up h) over `mlp` = {gate, up, down} kernels
    stored [in, out]; plain, bf16-at-rest or QuantTensor (quant.mm)."""
    gate = jax.nn.silu(quant.mm(h, mlp["gate"]["kernel"]))
    up = quant.mm(h, mlp["up"]["kernel"])
    return quant.mm(gate * up, mlp["down"]["kernel"])
