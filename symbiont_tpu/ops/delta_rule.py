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

Two forms of the same arithmetic, chosen by the head width (`path`):

- **the kernel** (`_kernel_rule`, heads of a multiple of 128 lanes): ONE
  Pallas call over a grid of (row, group of `KERNEL_HEADS` heads, chunk),
  the chunk axis sequential. It reads q, k, v and g in the projections' own
  `[B, L, H * d]` layout (a head is a column block a BlockSpec picks, so
  nothing is transposed in HBM), keeps each head's float32 state in VMEM
  scratch across the chunks, and writes the output where `o_norm` reads it.
  A grid step takes every step of `_chunk` for all its heads at once, so
  their chains interleave on the chip's units (PERF.md has the sweep). On a
  `cpu` backend it runs under the Pallas interpreter (the tests).
- **the XLA form** (`_chunked_rule`, every other width: the toy widths of
  the tests): `GROUP` chunks make one step of a `lax.scan` that carries the
  [B, H, d_k, d_v] state: their intra-chunk terms are computed together,
  then the state passes through them one after another.

A length a step does not divide is padded with tokens of no passage (zero
q, k, v, beta and g).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _pad(span, q, k, v, g, beta, index):
    """The rows padded to a multiple of `span` with tokens of no passage."""
    L = q.shape[1]
    n = -(-L // span)
    if n * span == L:
        return q, k, v, g, beta, index
    pad = ((0, 0), (0, n * span - L))
    return (*(jnp.pad(a, pad + ((0, 0),) * (a.ndim - 2))
              for a in (q, k, v, g, beta)),
            jnp.pad(index, pad, constant_values=-2))


def _chunked_rule(q, k, v, g, beta, index):
    """The XLA form: `GROUP` chunks a step of a `lax.scan`."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    dtype, chunk = q.dtype, CHUNK
    group = max(1, min(GROUP, -(-L // chunk)))  # a short row: fewer chunks a step
    span = chunk * group
    n = -(-L // span)
    q, k, v, g, beta, index = _pad(span, q, k, v, g, beta, index)
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


# ---------------------------------------------------------------------------
# The kernel: one Pallas call, the state in VMEM
# ---------------------------------------------------------------------------

# Heads a grid step (timed on one TPU v5e, one [1, 32768, 32, 128] layer;
# PERF.md §6 has the table): every step of a chunk is taken for all of
# them at once, so one head's solve runs beside another's products; 16
# heads 17.5 ms a layer, 8 18.0, 4 19.7 (two chunks a step: slower); 32
# does not fit VMEM.
KERNEL_HEADS = 16


def _dot(a, b, contract=((2,), (1,))):
    """A product per head of [heads, ., .] operands, float32 at precision
    "highest"."""
    return jax.lax.dot_general(a, b, (contract, ((0,), (0,))), precision=_HI,
                               preferred_element_type=jnp.float32)


def _t(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, 1, 2)


def _mid_rows(x: jax.Array) -> jax.Array:
    """[h, C, n] -> [h, C, n]: each row replaced by its `SUB`-row
    sub-chunk's middle row (`_intra`'s reference point)."""
    h, C, n = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(x[:, a + SUB // 2 - 1:a + SUB // 2], (h, SUB, n))
         for a in range(0, C, SUB)], axis=1)


def _cumsum_rows(x: jax.Array) -> jax.Array:
    """The running sum down the rows of x [h, C, n], by doubling: log2(C)
    shifted adds, float32 on the vector unit."""
    row = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1], 1), 1)
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 1), 0.0)
        shift *= 2
    return x


def _solve(a: jax.Array) -> jax.Array:
    """`unit_lower_inverse` inside the kernel, on [h, C, C]: the same
    forward substitution a block row at a time, `T_r = D_r^-1 (E_r -
    sum_{k<r} a_rk T_k)`, the earlier block rows taken off by one product,
    then `D_r^-1` applied row by row (row i final once the rows before it
    have been taken off it). Float32; the products at precision
    "highest"."""
    h, C, _ = a.shape
    i = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    eye = jnp.broadcast_to(jnp.where(i == j, 1.0, 0.0), a.shape)
    # column c of each row's own diagonal block
    cols = [jnp.sum(jnp.where(j == (i // SUB) * SUB + c, a, 0.0), axis=2,
                    keepdims=True) for c in range(SUB - 1)]
    blocks = []
    for r in range(C // SUB):
        rows = slice(r * SUB, (r + 1) * SUB)
        x = eye[:, rows]  # [h, SUB, C]
        if r:
            x = x - _dot(a[:, rows, :r * SUB], jnp.concatenate(blocks, 1))
        for c in range(SUB - 1):
            x = x - cols[c][:, rows] * x[:, c:c + 1]
        blocks.append(x)
    return jnp.concatenate(blocks, 1)


def _chunk(q, k, v, g, beta, same, reads, mine, keep, state):
    """One chunk of `h` heads at once: `_intra` and `step`'s arithmetic in
    float32. q, k, g [h, C, dk], v [h, C, dv], beta [h, C, 1]; same [C, C]
    one passage; reads / mine [C, 1] the passage running at the chunk's
    start / end; keep whether one passage runs through it; state
    [h, dk, dv] S_0 -> (o [h, C, dv], S_C).

    The products are arranged for the matrix unit, which at precision
    "highest" streams a left operand six times, 8 rows at a time, and
    latches a right one six times: few products, their right operands
    shared. A and P come from ONE product, each sub-chunk's decayed keys
    (only up to the sub-chunk's end) stacked as the rows against the
    decayed keys and queries; the delta term is solved on `V - K_g S_0`
    (`T (V - K_g S_0)` is `step`'s `u - wk S_0`); the output's and the
    state's products share their right operand."""
    h, C, dk = q.shape
    i = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    G = _cumsum_rows(g)
    left = jnp.exp(G - _mid_rows(G))  # in [e^(bound SUB/2), e^(-bound SUB/2)]
    rights = []
    for a in range(C // SUB):
        # the columns up to sub-chunk a's end against its rows; an earlier
        # sub-chunk's column gets a factor <= 1
        end, mid = (a + 1) * SUB, a * SUB + SUB // 2 - 1
        rights.append(k[:, :end] * jnp.exp(G[:, mid:mid + 1] - G[:, :end]))
    # r[start_a + j, i]: right_a[j] . (k left)[i], then . (q left)[i - C]
    r = _dot(jnp.concatenate(rights, 1),
             jnp.concatenate([k * left, q * left], 1), ((2,), (2,)))
    column_sub = (jax.lax.broadcasted_iota(jnp.int32, (1, C, 2 * C), 2)
                  % C) // SUB
    both, start = 0.0, 0
    for a in range(C // SUB):
        end = (a + 1) * SUB
        block = r[:, start:start + end]
        if end < C:
            block = jnp.concatenate([block, jnp.zeros((h, C - end, 2 * C))], 1)
        both = both + jnp.where(column_sub == a, block, 0.0)
        start += end
    # both [h, C (j), 2 C (i)] = A^T | P^T; beta_j scales a column of A and P
    at = jnp.where(same & (i < j), both[:, :, :C] * beta, 0.0)
    pt = jnp.where(same & (i <= j), both[:, :, C:] * beta, 0.0)
    T = _solve(_t(at))
    gam = jnp.exp(G)  # <= 1
    x = _dot(jnp.concatenate([jnp.where(reads, q * gam, 0.0),
                              jnp.where(reads, k * gam, 0.0)], 1), state)
    w = _dot(T, v - x[:, C:])  # the delta term
    gl = G[:, C - 1:C]  # [h, 1, dk]
    kd = jnp.where(mine, beta * k * jnp.exp(gl - G), 0.0)
    y = _dot(jnp.concatenate([_t(pt), _t(kd)], 1), w)  # [h, C + dk, dv]
    decay = jnp.exp(_t(jnp.broadcast_to(gl, (h, 8, dk)))[:, :, :1])  # [h, dk, 1]
    return x[:, :C] + y[:, :C], jnp.where(keep, state * decay, 0.0) + y[:, C:]


def _rule_kernel(prev_ref, last_ref, ids_ref, beta_ref, q_ref, k_ref, v_ref,
                 g_ref, o_ref, state_ref):
    """One chunk of one row for a group of heads (`_chunk`), the group's
    [heads, dk, dv] state in VMEM across the grid's chunk axis.

    prev_ref / last_ref (SMEM, by chunk of the whole grid): the passage of
    the token before the chunk and of its last token; ids_ref [1, 1, 1, C]
    the chunk's passage ids; beta_ref [1, C, H]; q, k, g [1, C, heads dk];
    v and o [1, C, heads dv]."""
    b, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads, dk, dv = state_ref.shape
    C = q_ref.shape[1]

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    n = b * pl.num_programs(2) + c
    prev, last = prev_ref[n], last_ref[n]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    idr = ids_ref[0, 0]  # [1, C]
    idc = jnp.sum(jnp.where(eye, idr, 0), axis=1, keepdims=True)  # [C, 1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, beta_ref.shape[2]), 1)

    def heads_of(ref, width):  # [heads, C, width] float32
        return jnp.stack([ref[0, :, h * width:(h + 1) * width]
                          for h in range(heads)]).astype(jnp.float32)

    beta = jnp.stack([jnp.sum(jnp.where(lanes == hg * heads + h,
                                        beta_ref[0], 0.0),
                              axis=1, keepdims=True)
                      for h in range(heads)])  # [heads, C, 1]
    o, state_ref[...] = _chunk(
        heads_of(q_ref, dk), heads_of(k_ref, dk), heads_of(v_ref, dv),
        heads_of(g_ref, dk), beta, idc == idr,
        idc == prev,  # the passage running at the chunk's start
        idc == last,  # the passage running at its end
        last == prev, state_ref[...])
    for h in range(heads):
        o_ref[0, :, h * dv:(h + 1) * dv] = o[h].astype(o_ref.dtype)


def _interpret() -> bool:
    """The compiled kernel on a `tpu` backend, the Pallas interpreter on a
    `cpu` one (the tests), and nothing else (ops/flash_attention.py
    `_interpret`'s rule: the interpreter never stands in on the chip)."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"delta_rule: backend {backend!r} is neither "
                           "'tpu' (compiled kernel) nor 'cpu' (interpreter)")
    return backend == "cpu"


def _kernel_rule(q, k, v, g, beta, index):
    """`_rule_kernel` over the projections' own layout: q, k, g read as
    [B, L, H dk], v and the output as [B, L, H dv] (free reshapes), head h
    the column block h; beta [B, L, H] as it is; the passage ids by chunk,
    and each chunk's first-token predecessor and last token's passage by
    scalar prefetch."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    heads, C = math.gcd(H, KERNEL_HEADS), CHUNK
    n = -(-L // C)
    q, k, v, g, beta, index = _pad(C, q, k, v, g, beta, index)
    index = index.astype(jnp.int32)
    last = index[:, C - 1::C]  # [B, n]
    prev = jnp.concatenate([jnp.full((B, 1), -1, jnp.int32), last[:, :-1]],
                           axis=1)

    def cols(width):
        return pl.BlockSpec((1, C, heads * width),
                            lambda b, h, c, *_: (b, c, h))

    return pl.pallas_call(
        _rule_kernel,
        out_shape=jax.ShapeDtypeStruct((B, n * C, H * dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // heads, n),
            in_specs=[
                pl.BlockSpec((1, 1, 1, C), lambda b, h, c, *_: (b, c, 0, 0)),
                pl.BlockSpec((1, C, H), lambda b, h, c, *_: (b, c, 0)),
                cols(dk), cols(dk), cols(dv), cols(dk),
            ],
            out_specs=cols(dv),
            scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="delta_rule",
    )(prev.reshape(-1), last.reshape(-1), index.reshape(B, n, 1, C),
      beta.astype(jnp.float32), q.reshape(B, n * C, H * dk),
      k.reshape(B, n * C, H * dk), v.reshape(B, n * C, H * dv),
      g.astype(jnp.float32).reshape(B, n * C, H * dk)
      ).reshape(B, n * C, H, dv)[:, :L]


def path(dk: int, dv: int) -> str:
    """Which form `gated_delta_rule` takes for heads of this width: the
    kernel where both are whole 128-lane columns, else the XLA form."""
    return "pallas" if dk % 128 == 0 and dv % 128 == 0 else "chunked"


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, index: jax.Array) -> jax.Array:
    """q (already scaled), k [B, L, H, dk]; v [B, L, H, dv]; g [B, L, H, dk]
    float32 log-decays (<= 0); beta [B, L, H] float32; `index` [B, L] int32,
    the token's passage in its row (models/bert.py `Segments.index`: a value
    no passage has = padding). -> [B, L, H, dv] in q's dtype. The form is
    chosen by `path`."""
    rule = (_kernel_rule if path(q.shape[-1], v.shape[-1]) == "pallas"
            else _chunked_rule)
    return rule(q, k, v, g, beta, index)
