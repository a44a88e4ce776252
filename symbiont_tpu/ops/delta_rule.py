"""Chunked gated delta rule with a decay per key channel (Kimi Delta
Attention's recurrence) over packed rows.

Per head, token by token inside one passage:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

S is [d_k, d_v] float32 and zero at a passage's first token; `alpha_t =
exp(g_t)` per key channel (`g` <= 0, bounded below: the KDA gate's lower
bound), `beta_t` one scalar per head. Packed rows (`segments`) hold several
passages end to end and none reads another's state.

Computed in chunks of `CHUNK` tokens (the WY / UT form). With `G` the
running sum of `g` from the chunk's start and `S_0` the state carried in,
the delta term of a chunk is one unit lower-triangular system:

    (I + A) W = V - (K * exp(G)) S_0,   A_ij = beta_j sum_c k_ic k_jc exp(G_ic - G_jc)  (j < i)
    O = (Q * exp(G)) S_0 + (P * beta) W,  P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)  (j <= i)
    S_C = exp(G_last) * S_0 + (beta K * exp(G_last - G))^T W

each pair (i, j) counted only inside one passage, `S_0` read only by the
passage that was running when the chunk began and handed on only by the
one running at its end. The system is solved in float32 by forward
substitution, `SUB` rows at a time (`unit_lower_inverse` says why not by a
series in powers of `A`).

**No exponent leaves what float32 holds.** `exp(G_i - G_j)` with `j <= i`
is at most 1, but a product of matrices needs it factored, `exp(G_i - G_r)
exp(G_r - G_j)`. The reference point `r` is the middle token of each
`SUB`-token sub-chunk of the rows: a row's factor, and a column's of the
row's own sub-chunk, lies in [exp(lower_bound * SUB / 2),
exp(-lower_bound * SUB / 2)] = [e^-40, e^40] at the KDA bound of -5 and SUB
16, and a column of an earlier sub-chunk gets at most 1 (where it underflows
the product it stood for was under e^-47). float32 holds e^88.7, and the chip
(like XLA's CPU backend) flushes values under 1.2e-38 ~ e^-87 to zero: a
factor of e^-80 times a small q or k entry would be flushed, which is why
the reference point sits mid sub-chunk and not at its start. A factored form
over the whole 64-token chunk would need e^160. Every other exponent
(`exp(G)`, `exp(G_last - G)`) is of a non-positive number.

Everything in here is float32 at matmul precision "highest": the state, the
solve and the decays are what the configuration states in float32.

`GROUP` chunks make one step of the `lax.scan` that carries the
[B, H, d_k, d_v] state: their intra-chunk terms are computed together, then
the state passes through them one after another. A length the step does not
divide is padded with tokens of no passage (zero q, k, v, beta and g).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64  # tokens of one triangular system
SUB = 16  # tokens a factored decay spans: e^(5 * 16 / 2) either way
GROUP = 8  # chunks of one scan step
_HI = jax.lax.Precision.HIGHEST


def _ein(spec, *xs):
    return jnp.einsum(spec, *xs, precision=_HI,
                      preferred_element_type=jnp.float32)


def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for strictly lower-triangular a [..., C, C], by forward
    substitution: each `SUB` x `SUB` diagonal block row by row (all blocks
    and all matrices of the batch at once), then the block rows in order,
    `T_r = D_r^-1 (E_r - sum_{k<r} a_rk T_k)`. The substitution is the
    delta rule's own recurrence, a contraction for unit keys and beta <= 1,
    so it stays bounded where a series in powers of `a` does not: keys that
    repeat (a word that recurs) put entries near beta all over `a`, and its
    64th power is then ~1e17 of terms that cancel."""
    C = a.shape[-1]
    m, s = C // SUB, min(SUB, C)
    eye = jnp.eye(s, dtype=a.dtype)
    blocks = a.reshape(*a.shape[:-2], m, s, m, s)
    diag = jnp.stack([blocks[..., r, :, r, :] for r in range(m)], axis=-3)
    inv = jnp.zeros_like(diag)  # [..., m, s, s]; rows below i still zero
    for i in range(s):
        inv = inv.at[..., i, :].set(
            eye[i] - _ein("...j,...jk->...k", diag[..., i, :], inv))
    rows = []
    for r in range(m):
        rhs = jnp.zeros((*a.shape[:-2], s, C), a.dtype)
        rhs = rhs.at[..., r * s:(r + 1) * s].set(eye)
        if r:
            rhs = rhs - _ein("...ij,...jk->...ik",
                             a[..., r * s:(r + 1) * s, :r * s],
                             jnp.concatenate(rows, axis=-2))
        rows.append(_ein("...ij,...jk->...ik", inv[..., r, :, :], rhs))
    return jnp.concatenate(rows, axis=-2)


def _intra(q, k, v, g, beta, idx, prev):
    """One step's chunks at once: q, k, g [B, N, C, H, dk]; v [B, N, C, H,
    dv]; beta [B, N, C, H]; idx [B, N, C] passage ids; prev [B, N] the id of
    the token before each chunk. -> what the state needs of each chunk:
    qg, wk [B, N, H, C, dk], u [B, N, H, C, dv], pm [B, N, H, C, C],
    kd [B, N, H, C, dk], gl [B, N, H, dk], keep [B, N]."""
    B, N, C, H, dk = q.shape
    m = C // SUB
    G = jnp.cumsum(g, axis=2)
    Gs = G.reshape(B, N, m, SUB, H, dk)
    ref = Gs[:, :, :, SUB // 2 - 1]  # [B, N, m, H, dk]: each sub-chunk's middle
    left = jnp.exp(Gs - ref[:, :, :, None])  # in [e^(bound SUB/2), e^(-bound SUB/2)]
    kl = (k.reshape(Gs.shape) * left)
    ql = (q.reshape(Gs.shape) * left)
    a_rows, p_rows = [], []
    for a in range(m):
        cols = (a + 1) * SUB
        # this sub-chunk's columns as the rows; an earlier one's <= 1
        right = k[:, :, :cols] * jnp.exp(ref[:, :, a, None] - G[:, :, :cols])
        pad = ((0, 0),) * 4 + ((0, C - cols),)
        a_rows.append(jnp.pad(_ein("bnihd,bnjhd->bnhij", kl[:, :, a], right),
                              pad))
        p_rows.append(jnp.pad(_ein("bnihd,bnjhd->bnhij", ql[:, :, a], right),
                              pad))
    i = jnp.arange(C)
    same = (idx[:, :, :, None] == idx[:, :, None, :])[:, :, None]  # [B,N,1,C,C]
    bcol = jnp.moveaxis(beta, 2, 3)[:, :, :, None, :]  # [B, N, H, 1, C]
    A = jnp.where(same & (i[:, None] > i[None, :]),
                  jnp.concatenate(a_rows, axis=-2) * bcol, 0.0)
    pm = jnp.where(same & (i[:, None] >= i[None, :]),
                   jnp.concatenate(p_rows, axis=-2) * bcol, 0.0)
    T = unit_lower_inverse(A)
    reads = (idx == prev[:, :, None])[:, :, :, None, None]
    gam = jnp.exp(G)  # <= 1
    wk = _ein("bnhij,bnjhd->bnhid", T, jnp.where(reads, k * gam, 0.0))
    u = _ein("bnhij,bnjhd->bnhid", T, v)
    qg = jnp.moveaxis(jnp.where(reads, q * gam, 0.0), 2, 3)
    last = idx[:, :, -1]
    mine = (idx == last[:, :, None])[:, :, :, None, None]
    kd = jnp.where(mine, beta[..., None] * k * jnp.exp(G[:, :, -1:] - G), 0.0)
    return (qg, wk, u, pm, jnp.moveaxis(kd, 2, 3), jnp.exp(G[:, :, -1]),
            last == prev)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, index: jax.Array) -> jax.Array:
    """q (already scaled), k [B, L, H, dk]; v [B, L, H, dv]; g [B, L, H, dk]
    float32 log-decays (<= 0); beta [B, L, H] float32; `index` [B, L] int32,
    the token's passage in its row (models/bert.py `Segments.index`: a value
    no passage has = padding). -> [B, L, H, dv] in q's dtype."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    dtype, chunk = q.dtype, CHUNK
    group = max(1, min(GROUP, -(-L // chunk)))  # a short row: fewer chunks a step
    span = chunk * group
    n = -(-L // span)
    if n * span != L:
        pad = ((0, 0), (0, n * span - L))
        q, k, v, g, beta = (jnp.pad(a, pad + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
        index = jnp.pad(index, pad, constant_values=-2)
    idx = index.reshape(B, n * group, chunk)
    prev = jnp.concatenate([jnp.full((B, 1), -1, idx.dtype),
                            idx[:, :-1, -1]], axis=1)  # [B, chunks]

    def steps(a):  # [B, n*span, ...] -> [n, B, group, chunk, ...]
        return jnp.moveaxis(a.reshape(B, n, group, chunk, *a.shape[2:]), 1, 0)

    def step(state, xs):
        # a step's tokens go to float32 here, not the whole row up front
        *x, idx, prev = xs
        qg, wk, u, pm, kd, gl, keep = _intra(
            *(a.astype(jnp.float32) for a in x), idx, prev)
        outs = []
        for c in range(group):
            new_v = u[:, c] - _ein("bhcd,bhde->bhce", wk[:, c], state)
            outs.append(_ein("bhcd,bhde->bhce", qg[:, c], state)
                        + _ein("bhij,bhje->bhie", pm[:, c], new_v))
            state = (jnp.where(keep[:, c, None, None, None],
                               gl[:, c, :, :, None] * state, 0.0)
                     + _ein("bhcd,bhce->bhde", kd[:, c], new_v))
        return state, jnp.stack(outs, axis=1).astype(dtype)  # [B, group, H, C, dv]

    xs = (steps(q), steps(k), steps(v), steps(g), steps(beta),
          jnp.moveaxis(idx.reshape(B, n, group, chunk), 1, 0),
          jnp.moveaxis(prev.reshape(B, n, group), 1, 0))
    _, out = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32), xs)
    # [n, B, group, H, chunk, dv] -> [B, L, H, dv]
    out = jnp.moveaxis(out, 0, 1).transpose(0, 1, 2, 4, 3, 5)
    return out.reshape(B, n * span, H, dv)[:, :L]
