"""Chunked causal linear attention with a per-head decay (Lightning
Attention's form) over packed rows.

Per head h, token by token inside one passage:

    S_t = lam_h * S_{t-1} + k_t^T v_t        o_t = q_t S_t

no softmax and no normaliser; `lam_h = exp(-slope_h)`. The state is zero at
a passage's first token: packed rows (`segments`) hold several passages end
to end and none reads another's state.

Computed in chunks of `chunk` tokens by one `lax.scan` that carries the
[B, H, d, d] float32 state: inside a chunk `(Q K^T * D) V` with `D_ij =
lam^(i-j)` for `i >= j` in one passage, else 0; across chunks through the
carried state, which a token reads only when its passage began before the
chunk and which a chunk hands on only when its last token's passage did.
Every exponent of `lam` is non-negative (decays are never divided out), so
nothing overflows at any chunk size. A length the chunk does not divide is
padded with tokens of no passage.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 256


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        index: jax.Array, slopes: jax.Array,
                        chunk: int = CHUNK) -> jax.Array:
    """q (already scaled), k, v [B, L, H, d]; `index` [B, L] int32, the
    token's passage in its row (models/bert.py `Segments.index`: a value no
    passage has = padding); slopes [H] float32 (-log lam). -> [B, L, H, d]
    in q's dtype."""
    B, L, H, d = q.shape
    C = min(chunk, L)
    n = -(-L // C)
    if n * C != L:
        pad = ((0, 0), (0, n * C - L))
        q, k, v = (jnp.pad(a, pad + ((0, 0), (0, 0))) for a in (q, k, v))
        index = jnp.pad(index, pad, constant_values=-2)
    dtype = q.dtype
    i = jnp.arange(C, dtype=jnp.float32)
    slopes = slopes.astype(jnp.float32)[:, None]
    gap = i[:, None] - i[None, :]
    # [H, C, C]: lam^(i-j) at and under the diagonal
    d_intra = jnp.where(gap >= 0, jnp.exp(-slopes[:, :, None] * gap), 0.0)
    # [1, C, H, 1]: from the chunk before to i; from j to the chunk's end
    d_q = jnp.exp(-slopes * (i + 1)).T[None, :, :, None]
    d_k = jnp.exp(-slopes * (C - 1 - i)).T[None, :, :, None]
    d_chunk = jnp.exp(-slopes[:, 0] * C)[None, :, None, None]  # [1, H, 1, 1]

    def chunks(a):  # [B, n*C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape(B, n, C, *a.shape[2:]), 1, 0)

    def step(carry, xs):
        state, last = carry  # [B, H, d, d] float32; [B] the passage it is of
        qc, kc, vc, ic = xs
        same = ic[:, :, None] == ic[:, None, :]
        a = jnp.einsum("bihd,bjhd->bhij", qc, kc,
                       preferred_element_type=jnp.float32)
        a = jnp.where(same[:, None], a * d_intra, 0.0).astype(dtype)
        o = jnp.einsum("bhij,bjhd->bihd", a, vc,
                       preferred_element_type=jnp.float32)
        reads = (ic == last[:, None])[:, :, None, None]
        qd = (qc.astype(jnp.float32) * d_q).astype(dtype)
        o = o + jnp.where(reads, jnp.einsum(
            "bihd,bhde->bihe", qd, state.astype(dtype),
            preferred_element_type=jnp.float32), 0.0)
        end = ic[:, -1]
        mine = (ic == end[:, None])[:, :, None, None]
        kd = jnp.where(mine, kc.astype(jnp.float32) * d_k, 0.0).astype(dtype)
        kept = jnp.where((end == last)[:, None, None, None],
                         state * d_chunk, 0.0)
        state = kept + jnp.einsum("bjhd,bjhe->bhde", kd, vc,
                                  preferred_element_type=jnp.float32)
        return (state, end), o.astype(dtype)

    init = (jnp.zeros((B, H, d, d), jnp.float32),
            jnp.full((B,), -1, index.dtype))
    _, out = jax.lax.scan(step, init, (chunks(q), chunks(k), chunks(v),
                                       chunks(index)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * C, H, d)[:, :L]
