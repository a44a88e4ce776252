"""Flash attention, pallas-on-TPU.

Blockwise fused attention with a streaming (online) softmax: QK^T, masking,
softmax and PV happen inside one kernel, so the [B, NH, S, S] score matrix is
never materialized in HBM — the usual HBM-bandwidth win of flash attention,
plus MXU-friendly (block_q × block_k) tiles.

Design:
- grid = (batch, q_heads, q_blocks, kv_blocks). On TPU the last grid axis is
  innermost & sequential, so the running max (m), normalizer (l) and output
  accumulator live in VMEM scratch that persists across kv iterations —
  the canonical pallas accumulation pattern.
- padding masks enter as an additive f32 bias per kv position ([B, Sk],
  0 for real tokens / -1e9 for pad), exactly the encoder-side convention of
  `models/bert.py`; causal decode masking is computed from block indices with
  `broadcasted_iota`, and fully-masked causal blocks are skipped via
  `pl.when` (the flash-causal FLOP win).
- GQA: kv heads may be fewer than q heads; the kv BlockSpec index map sends q
  head h to kv head h // group, so K/V are never repeated in memory.
- numerics: compute in f32 (scores, softmax, accumulator) regardless of input
  dtype; output cast back to q.dtype. Masked-out positions use large-negative
  finite biases, never -inf, so no NaN can escape `exp`.
- autodiff: `jax.custom_vjp` whose backward is ALSO fused (two pallas
  kernels): the forward additionally emits the log-sum-exp rows, and the
  backward recomputes probability blocks from (q, k, lse) — one kernel
  accumulates dK/dV (+ the bias gradient) over q blocks, one accumulates dQ
  over kv blocks. The [B, NH, S, S] probability matrix is never
  materialized in either direction, so encoder fine-tuning at the 512
  bucket and LM training at multi-k contexts stay O(S) activation memory.
  GQA (kv heads < q heads) falls back to a dense f32 recompute backward —
  that path is prefill-only in this system; long-context LM *training*
  rides the sequence-parallel schedule (parallel/context.py).
- fallback: shapes the kernel can't tile (non-divisible or tiny S) route to
  the same dense reference implementation, so callers never need shape
  special-cases.
- packed rows (`packed_attention`, forward only; the looped embedder's
  attention, models/ouro.py): a row holds several segments laid end to end,
  the mask comes from per-token int32 segment ids and causality (keep
  (i, j) iff `id[i] == id[j]` and `j <= i`), RoPE is applied to q and k
  inside the kernel, and q, k, v and the context keep the projections' own
  `[B, L, heads * D]` layout, a head being a 128-lane column block that a
  BlockSpec picks: nothing is transposed or re-laid on either side. A value
  head may be narrower than q.k's (MLA: 192 for q.k padded to 256, 128 for
  v, models/mla_moe.py) and the caller may give the scale. The
  same arithmetic; its own kernel body (`_packed_kernel`), because what
  sets its pace on the chip is how a block is walked, not what is computed.
  Its grouped form (`_grouped_kernel`, models/mimo.py) adds GQA (a step
  takes every query head of one KV head, so a key block is fetched once
  for the group), a sliding window whose grid holds only the key blocks
  the window reaches, and a sink logit a head in the softmax's
  normaliser; without them a call lowers to the text it lowered to
  before. Each of its query blocks walks only the key blocks from the
  first whose segment ids can meet its own to the diagonal, bounds the
  wrapper computes on the device and hands over by scalar prefetch, so a
  packed row's other passage and its padding cost a grid step's overhead,
  not its work. The streaming form (a row longer than one block, MLA in
  models/mla_moe.py) uses the same bounds, and its grid walks a list of
  the block pairs inside them alone, so those pairs cost no step at all.
- every way off the compiled kernel ANNOUNCES itself (`_announce`): one log
  line and one `flash.fallback{path}` bump per traced shape — the Pallas interpreter on a CPU backend, the dense route for
  untileable shapes, the dense-recompute GQA backward. `chip_smoke.py`
  asserts all three read zero on the chip. The interpreter on a `tpu`
  backend is an error, as is any backend that is neither `tpu` nor `cpu`.

Replaces, at the bottom of the stack, the reference's candle
`BertModel::forward` attention (reference:
services/preprocessing_service/src/embedding_generator.rs:198) — which
materializes full score matrices per layer — with the TPU-native fused form.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from symbiont_tpu.utils.telemetry import metrics

log = logging.getLogger(__name__)


def _announce(path: str, q, k) -> None:
    """A way off the compiled kernel was taken for this shape: count it
    (`flash.fallback{path}`) and say so. Runs at trace time, so under jit
    that is one bump and one log line per compiled shape."""
    metrics.inc("flash.fallback", labels={"path": path})
    log.warning("flash_attention: %s for q%s k%s %s", path, tuple(q.shape),
                tuple(k.shape), q.dtype)


# Large-negative finite stand-ins for -inf: m is initialized to _ACC_NEG and
# masked scores are set to _MASK_NEG; keeping both finite (and _ACC_NEG well
# below any reachable score) means exp() underflows to exactly 0.0 instead of
# producing inf-inf NaNs.
_ACC_NEG = -1e30
_MASK_NEG = -1e9


def _dot_prec(*operands):
    """MXU precision for a kernel dot: Mosaic's DEFAULT decomposes f32 dots
    into single-pass bf16 (~1% error, observed on-chip), so f32 operands get
    Precision.HIGHEST (full f32 passes). bf16 operands MUST use the default —
    Mosaic rejects fp32 contract precision on bf16 inputs ("Bad lhs type")."""
    if all(o.dtype == jnp.float32 for o in operands):
        return jax.lax.Precision.HIGHEST
    return None


def _pick_block(s: int, pref: int) -> int:
    """Largest power-of-two block ≤ pref that divides s (0 = no tiling)."""
    b = pref
    while b >= 8:
        if b <= s and s % b == 0:
            return b
        b //= 2
    return 0


def _kernel(bias_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
            acc_scr, *, scale: float, causal: bool, block_q: int,
            block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _ACC_NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        # matmuls run in the input dtype (bf16 → native MXU multiply) with
        # f32 accumulation via preferred_element_type. precision=HIGHEST
        # matters only for f32 operands: Mosaic's default decomposes f32
        # MXU dots into single-pass bf16 (~1% error, observed on-chip);
        # HIGHEST buys full f32 passes. bf16 operands are unaffected.
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]  # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_dot_prec(q, k)) * scale
        # bias arrives pre-blocked [B, nk, 1, bk] so the BlockSpec index map
        # (not an in-kernel dynamic lane slice, which Mosaic can't tile-prove)
        # selects this kv window; [1, bk] broadcasts over q rows
        s = s + bias_ref[0, 0]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _MASK_NEG)
        m_prev = m_scr[:, :1]  # [bq, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bk]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(v))
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip blocks entirely above the diagonal
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # log-sum-exp per q row — the residual the fused backward rebuilds
        # probability blocks from (p = exp(s - lse)). Shaped [bq, 1]: the
        # trailing singleton keeps the block Mosaic-tileable (sublane dim bq
        # divisible by 8, lane dim equal to the array's).
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)


def _rotate(x, cos, sin):
    """RoPE on a [rows, D] block (half-split pairing, as `layers.rope`):
    `x1 cos - x2 sin | x2 cos + x1 sin` in float32, cast back. `cos` and
    `sin` are [rows, D] float32 as `layers.rope_tables` lays them (cos twice over,
    sin with its first half negated), so a dimension's partner is one lane
    rotation by D/2 away."""
    xf = x.astype(jnp.float32)
    turned = pltpu.roll(xf, x.shape[-1] // 2, 1)
    return (xf * cos + turned * sin).astype(x.dtype)


# How the packed kernel walks a block (my chip runs, PR 37, one [8, 512]
# application of 16 heads of 128; PERF.md §6 has the table): a whole
# [512, 512] float32 score block is 256 vector registers, every elementwise
# pass over it a round trip through VMEM, and nothing of the next product
# can start before the last pass ends (320 us); query rows 256 at a time,
# each tile reading only the keys up to its own last row, 206 us; two heads
# a grid step, whose products and passes the scheduler can interleave,
# 171 us (128 rows: 198; four heads: 178; a copy of the operands alone: 66).
_PACKED_ROWS = 256
_PACKED_HEADS = 2


def _packed_kernel(*refs, scale: float, block: int, heads: int, rotary: bool,
                   pairs: int = 0):
    """One step over packed rows, forward only: `_kernel`'s arithmetic with
    the mask from per-token segment ids and causality and, with `rotary`,
    RoPE on q and k first. Query rows go `_PACKED_ROWS` at a time, and on
    the diagonal block each tile reads only the keys up to its own last
    row: the triangle above it is neither multiplied nor exponentiated.

    A row that is ONE block (no scratch refs) is the fast form: each tile's
    softmax is whole, so its context is written straight out, `heads` heads
    a step. Longer rows stream kv blocks through `_kernel`'s running max,
    normaliser and accumulator, one head a step: step t of a row takes
    entry t of its list (`_segment_pairs`, `pairs` entries a row, the first
    ref, in scalar memory), which names the (q block, kv block) pair and
    whether the step starts the q block's state, computes, finishes it,
    writes the zeros of a block of padding or does nothing."""
    if pairs:
        pairs_ref, *refs = refs
    qid_ref, kid_ref, *refs = refs
    if rotary:
        cosq_ref, sinq_ref, cosk_ref, sink_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, *scratch = refs
    sub = min(block, _PACKED_ROWS)
    D = q_ref.shape[2] // heads
    Dv = v_ref.shape[2] // heads  # a value head may be narrower than q.k's

    def _q(r0: int, cols):
        rows = pl.ds(r0, sub)
        q = q_ref[0, rows, cols]  # [sub, D]
        return (_rotate(q, cosq_ref[0, rows, :], sinq_ref[0, rows, :])
                if rotary else q)

    def _k(cols):
        k = k_ref[0, :, cols]  # [block, D]
        return _rotate(k, cosk_ref[0], sink_ref[0]) if rotary else k

    def _keys(r0: int) -> int:
        """How many of a diagonal block's keys the tile at `r0` can see, in
        whole 128-key tiles (the scores' lane dimension)."""
        return min(block, -(-(r0 + sub) // 128) * 128)

    def _scores(q, k, r0: int, diagonal: bool):
        """[sub, keys] float32: scaled, and -1e9 where the key is in another
        segment or (on the diagonal block) after the query."""
        keys = k.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_dot_prec(q, k)) * scale
        # a query's id [sub, 1] against a key's [1, keys]: padding carries
        # an id of its own, so no row of the softmax is empty
        keep = qid_ref[0, pl.ds(r0, sub), :] == kid_ref[0, :, :keys]
        if diagonal:  # both blocks start at the same token
            keep &= (jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
                     <= r0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0))
        return jnp.where(keep, s, _MASK_NEG)

    def _context(p, v):
        return jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_prec(v))

    if not scratch:
        for h in range(heads):
            cols, vcols = pl.ds(h * D, D), pl.ds(h * Dv, Dv)
            k, v = _k(cols), v_ref[0, :, vcols]
            for r0 in range(0, block, sub):
                s = _scores(_q(r0, cols), k[:_keys(r0)], r0, True)
                p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                l = jnp.sum(p, axis=-1, keepdims=True)  # >= 1: the max's own
                o_ref[0, pl.ds(r0, sub), vcols] = (
                    _context(p, v[:_keys(r0)]) * pl.reciprocal(l)
                ).astype(o_ref.dtype)
        return

    m_scr, l_scr, acc_scr = scratch
    entry = pairs_ref[pl.program_id(0) * pairs + pl.program_id(2)]
    qi, kb = _pair_blocks(entry)

    @pl.when((entry & _INIT) != 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _ACC_NEG, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step(diagonal: bool):
        k, v = _k(slice(None)), v_ref[0]
        for r0 in range(0, block, sub):
            rows = pl.ds(r0, sub)
            keys = _keys(r0) if diagonal else block
            s = _scores(_q(r0, slice(None)), k[:keys], r0, diagonal)
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[rows, :1] + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + _context(p, v[:keys])
            m_scr[rows, :] = jnp.broadcast_to(m_new, (sub, m_scr.shape[1]))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (sub, l_scr.shape[1]))

    walked = (entry & (_ZERO | _IDLE)) == 0
    pl.when(walked & (kb < qi))(lambda: _step(False))
    pl.when(walked & (kb == qi))(lambda: _step(True))

    @pl.when((entry & _FINISH) != 0)
    def _finish():
        o_ref[0] = (acc_scr[:] * pl.reciprocal(l_scr[:, :1])
                    ).astype(o_ref.dtype)

    @pl.when((entry & _ZERO) != 0)
    def _nothing():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


# An entry of the streaming walk's list (`_segment_pairs`), one int32: the
# q block in the low bits, the kv block above it, then what the step does.
_PAIR_BITS = 12  # 4,096 blocks; scalar memory holds the list of ~720
_PAIR_MASK = (1 << _PAIR_BITS) - 1
_INIT, _FINISH, _ZERO, _IDLE = (1 << (2 * _PAIR_BITS + i) for i in range(4))


def _pair_blocks(entry):
    """(q block, kv block) of an entry; the kernel and its index maps both
    read them."""
    return entry & _PAIR_MASK, (entry >> _PAIR_BITS) & _PAIR_MASK


def _segment_pairs(ids, block: int, padding_id):
    """int32 [B, nb (nb + 1) / 2] from the ids [B, L] (nb = L / block):
    each row's list of the (q block, kv block) pairs the streaming kernel
    computes, q block by q block, each from its first kv block
    (`_grouped_bounds`) up to the diagonal. Its length is the worst case, a
    row that is one passage. A q block that is all `padding_id` takes one
    entry that writes zeros, named with the kv block of the entry before
    it (the last q block walked, whose q and kv blocks are then still
    held: nothing is fetched for it); past the last entry the list repeats
    that entry, idle."""
    B, L = ids.shape
    nb = L // block
    bounds = _grouped_bounds(ids, block, block, 0, padding_id)
    first, last = bounds[..., 0], bounds[..., 1]
    empty = first > last
    n = jnp.where(empty, 1, last - first + 1)  # entries of each q block
    start = jnp.cumsum(n, axis=1) - n
    t = jnp.arange(nb * (nb + 1) // 2, dtype=jnp.int32)
    at = jnp.minimum(t, n.sum(1, keepdims=True) - 1)  # [B, P]
    qi = (start[:, None, :] <= at[..., None]).sum(-1, dtype=jnp.int32) - 1
    j = at - jnp.take_along_axis(start, qi, 1)
    q_first, q_n, q_empty = (jnp.take_along_axis(x, qi, 1)
                             for x in (first, n, empty))
    held = jax.lax.cummax(jnp.where(empty, 0, jnp.arange(nb)), axis=1)
    kb = jnp.where(q_empty, jnp.take_along_axis(held, qi, 1), q_first + j)
    flags = (jnp.where(q_empty, _ZERO, jnp.where(j == 0, _INIT, 0)
                       | jnp.where(j == q_n - 1, _FINISH, 0)))
    flags = jnp.where(t > at, _IDLE, flags)
    return (qi | kb << _PAIR_BITS | flags).astype(jnp.int32)


def segment_steps(segment_ids: jax.Array, block: int = 512,
                  padding_id: int | None = None) -> jax.Array:
    """int32 [B, 2] for `packed_attention`'s streaming form (rows longer
    than one block) over rows with these ids [B, L]: for each head, the
    (q block, kv block) pairs its kernel computes (the entries of
    `_segment_pairs` that compute) and the pairs under the diagonal, which
    it walked before it kept a list."""
    ids = segment_ids.astype(jnp.int32)
    pairs = _segment_pairs(ids, _pick_block(ids.shape[1], block), padding_id)
    run = ((pairs & (_ZERO | _IDLE)) == 0).sum(1, dtype=jnp.int32)
    return jnp.stack([run, jnp.full_like(run, pairs.shape[1])], axis=1)


def _flash_call(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    B, NH, Sq, D = q.shape
    NKV, Sk = k.shape[1], k.shape[2]
    group = NH // NKV
    bq, bk = _pick_block(Sq, block_q), _pick_block(Sk, block_k)
    grid = (B, NH, Sq // bq, Sk // bk)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # bias pre-blocked [B, nk, 1, bk]: the block equals the array on
            # the last two dims, which TPU tiling rules always allow
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, qi, ki: (b, ki, 0, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, NH, Sq, 1), jnp.float32),  # lse
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        **kwargs,
    )(bias.reshape(B, Sk // bk, 1, bk), q, k, v)


def _packed_call(q, k, v, ids, rope, num_heads, block, interpret,
                 scale=None, padding_id=None):
    """`_packed_kernel` over packed rows in the projections' own layout: q,
    k [B, L, heads * D] and v [B, L, heads * Dv], head h the column block h
    (D and Dv multiples of the 128 lanes), the context written as v is, so
    nothing is re-laid on either side. `ids` [B, L] int32 goes in twice: a
    column [block, 1] of the queries' ids and a row [1, block] of the keys';
    `rope` = (cos, sin) [B, L, D] float32 likewise, by query rows and by key
    rows (a block whose index the step before had is not fetched again: the
    tables move once a row, not once a head). A row longer than one block
    streams (`_segment_call`)."""
    B, L, HD = q.shape
    D = HD // num_heads
    Dv = v.shape[2] // num_heads
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    if L != block:
        return _segment_call(q, k, v, ids, rope, num_heads, block, interpret,
                             scale, padding_id)
    heads = _PACKED_HEADS if num_heads % _PACKED_HEADS == 0 else 1
    kernel = functools.partial(_packed_kernel, scale=scale, block=block,
                               heads=heads, rotary=rope is not None)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    qspec = pl.BlockSpec((1, block, heads * D),
                         lambda b, h, qi, ki: (b, qi, h))
    kspec = pl.BlockSpec((1, block, heads * D),
                         lambda b, h, qi, ki: (b, ki, h))
    vspec, ospec = kspec, qspec
    if Dv != D:
        vspec = pl.BlockSpec((1, block, heads * Dv),
                             lambda b, h, qi, ki: (b, ki, h))
        ospec = pl.BlockSpec((1, block, heads * Dv),
                             lambda b, h, qi, ki: (b, qi, h))
    tables, table_specs = (), []
    if rope is not None:
        tables = (*rope, *rope)
        table_specs = 2 * [pl.BlockSpec((1, block, D),
                                        lambda b, h, qi, ki: (b, qi, 0))]
        table_specs += 2 * [pl.BlockSpec((1, block, D),
                                         lambda b, h, qi, ki: (b, ki, 0))]
    return pl.pallas_call(
        kernel,
        grid=(B, num_heads // heads, 1, 1),
        in_specs=[
            pl.BlockSpec((1, block, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block), lambda b, h, qi, ki: (b, 0, ki)),
            *table_specs, qspec, kspec, vspec,
        ],
        out_specs=ospec,
        out_shape=jax.ShapeDtypeStruct(v.shape, q.dtype),
        scratch_shapes=[],
        interpret=interpret,
        **kwargs,
    )(ids[:, :, None], ids[:, None, :], *tables, q, k, v)


def _segment_call(q, k, v, ids, rope, num_heads, block, interpret, scale,
                  padding_id):
    """`_packed_call`'s streaming form, one head a step: the grid is (B,
    heads, P), and step t of a row takes entry t of its list of the
    (q block, kv block) pairs that can compute (`_segment_pairs`, computed
    here on the device and handed over in scalar memory, P = nb (nb + 1) / 2
    entries a row, the worst case). q, its ids and tables, and the context
    go by the entry's q block, k, v, theirs by its kv block; the context of
    a q block is written once, after its last pair. An entry of padding
    asks for the q and kv blocks its predecessor held, and the idle entries
    at the list's end repeat the last one, so neither fetches anything.
    The list takes B x P x 4 bytes of the 1 MiB of scalar memory: 8,320 at
    [1, 32768] in 512-token blocks; the compiler refuses a call past ~126
    such rows."""
    B, L, HD = q.shape
    D, Dv = HD // num_heads, v.shape[2] // num_heads
    pairs = _segment_pairs(ids, block, padding_id)
    P = pairs.shape[1]

    def blocks(b, t, pairs_ref):
        """(q block fetched, kv block, context block) of entry t of row b."""
        entry = pairs_ref[b * P + t]
        qi, kb = _pair_blocks(entry)
        return jnp.where((entry & _ZERO) != 0, kb, qi), kb, qi

    def spec(width, which, head=True):
        return pl.BlockSpec((1, block, width), lambda b, h, t, s: (
            b, blocks(b, t, s)[which], h if head else 0))

    tables, table_specs = (), []
    if rope is not None:
        tables = (*rope, *rope)
        table_specs = 2 * [spec(D, 0, False)] + 2 * [spec(D, 1, False)]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    kernel = functools.partial(_packed_kernel, scale=scale, block=block,
                               heads=1, rotary=rope is not None, pairs=P)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, num_heads, P),
            in_specs=[
                spec(1, 0, False),
                pl.BlockSpec((1, 1, block), lambda b, h, t, s: (
                    b, 0, blocks(b, t, s)[1])),
                *table_specs, spec(D, 0), spec(D, 1), spec(Dv, 1),
            ],
            out_specs=spec(Dv, 2),
            scratch_shapes=[
                pltpu.VMEM((block, 128), jnp.float32),  # running max (lane-replicated)
                pltpu.VMEM((block, 128), jnp.float32),  # running normalizer
                pltpu.VMEM((block, Dv), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(v.shape, q.dtype),
        # the op's name in a profile
        name="segment_attention",
        interpret=interpret,
        **kwargs,
    )(pairs.reshape(-1), ids[:, :, None], ids[:, None, :], *tables, q, k, v)


# How the grouped kernel tiles a row: full attention takes 256 query rows
# against 512 keys a step (fewer where the row is shorter), every query head
# of a KV head in the step (at 64 query heads over 4 KV heads, 16 heads of
# 256 lanes: 2 MB of q); a window takes 128 against 128, so a 128-key
# window visits two key blocks a query block.
_GROUPED_BLOCKS = {False: (256, 512), True: (128, 128)}
_GROUPED_VMEM = 64 * 1024 * 1024  # the step's q, its rotated copy, stats


def _grouped_tiling(L: int, window: int) -> tuple[int, int, int]:
    """(bq, bk, steps) of `_grouped_call`'s grid over rows of L tokens: the
    query and key block sizes, and the key steps a query block takes (every
    key block of the row, or those a window reaches)."""
    bq, bk = (_pick_block(L, b) for b in _GROUPED_BLOCKS[bool(window)])
    return bq, bk, 1 + -(-(window - 1) // bk) if window else L // bk


def _grouped_reach(L: int, bq: int, bk: int, window: int):
    """bool [L // bq, L // bk]: the key blocks query block qi can see at all
    (at or below its diagonal block, the one that holds its last row, and
    inside the window where there is one); and int32 [L // bq] the
    diagonal block."""
    qi = jnp.arange(L // bq, dtype=jnp.int32)[:, None]
    kb = jnp.arange(L // bk, dtype=jnp.int32)[None, :]
    diagonal = ((qi + 1) * bq - 1) // bk
    reach = kb <= diagonal
    if window:
        reach &= kb >= (qi * bq - window + 1) // bk
    return reach, diagonal[:, 0]


def _grouped_bounds(ids, bq: int, bk: int, window: int, padding_id):
    """int32 [B, L // bq, 2] from the ids [B, L]: the first and last key
    block each query block walks. The last is the diagonal block. The first
    is the earliest block in reach whose range of ids meets the query
    block's: a block whose ids all lie above or all below the query block's
    shares no passage with it, whatever the order of the ids, so every
    block left out is one the mask would wipe whole. A query block that is
    all `padding_id` walks nothing (first = last + 1)."""
    B, L = ids.shape
    q = ids.reshape(B, L // bq, bq)
    k = ids.reshape(B, L // bk, bk)
    reach, last = _grouped_reach(L, bq, bk, window)
    meets = (reach & (k.min(-1)[:, None, :] <= q.max(-1)[:, :, None])
             & (k.max(-1)[:, None, :] >= q.min(-1)[:, :, None]))
    # the diagonal block holds the query block's own rows, so it always meets
    first = jnp.argmax(meets, axis=-1).astype(jnp.int32)
    last = jnp.broadcast_to(last, first.shape)
    if padding_id is not None:
        padding = (q == padding_id).all(-1)
        first = jnp.where(padding, last + 1, first)
    return jnp.stack([first, last], axis=-1)


def grouped_steps(segment_ids: jax.Array, window: int = 0,
                  padding_id: int | None = None) -> jax.Array:
    """int32 [B, 2] for `packed_attention`'s grouped form over rows with
    these ids [B, L]: for each KV head, the key steps its kernel computes
    (`_grouped_bounds`) and the steps of a walk over every block in reach
    (under the diagonal, or inside the window), which the kernel took
    before it kept bounds."""
    B, L = segment_ids.shape
    bq, bk, _ = _grouped_tiling(L, window)
    bounds = _grouped_bounds(segment_ids.astype(jnp.int32), bq, bk, window,
                             padding_id)
    run = jnp.maximum(bounds[..., 1] - bounds[..., 0] + 1, 0).sum(1)
    reach = _grouped_reach(L, bq, bk, window)[0].sum(dtype=jnp.int32)
    return jnp.stack([run, jnp.broadcast_to(reach, run.shape)], axis=1)


def _grouped_step(bounds_ref, b, qi, j, *, nq: int, bq: int, bk: int,
                  steps: int, window: int):
    """(first, last, kb): query block qi's range of key blocks, read from
    the scalar-prefetched `_grouped_bounds` of row b, and the key block of
    step j in it: first + j (full attention) or the q block's own less
    `steps - 1 - j` (a window: the blocks before it that the window
    reaches). The kernel and its index maps both walk by it."""
    at = 2 * (b * nq + qi)
    first, last = bounds_ref[at], bounds_ref[at + 1]
    kb = qi * bq // bk - (steps - 1) + j if window else first + j
    return first, last, kb


def _grouped_kernel(bounds_ref, *refs, scale: float, heads: int,
                    rotary: bool, sink: bool, count: bool, walk, bq: int,
                    bk: int, window: int, steps: int):
    """One (batch, KV head, q block, kv step) step of `_grouped_call`: the
    `heads` query heads that read this KV head, against one key block, with
    `_kernel`'s running max, normaliser and accumulator per head. `walk`
    (`_grouped_step`) gives the query block's first and last key block and
    the block of this step; a step whose block lies outside [first, last]
    is skipped, and a query block with an empty range writes zeros. With
    `sink`, head h's softmax counts e^sink_h in its
    normaliser: the running max starts at sink_h and the normaliser at 1,
    so a key block that is all masked adds nothing. With `count`, each
    query's unmasked keys over the steps taken are summed and written
    beside the output (every KV head writes the same count)."""
    qid_ref, kid_ref, *refs = refs
    if sink:
        sink_ref, *refs = refs
    if rotary:
        cosq_ref, sinq_ref, cosk_ref, sink_k_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, *refs = refs
    if count:
        n_ref, *refs = refs
    qs_scr, m_scr, l_scr, acc_scr, *refs = refs
    if count:
        n_scr, = refs
    D = k_ref.shape[2]
    Dv = v_ref.shape[2]
    qi, j = pl.program_id(2), pl.program_id(3)
    first, last, kb = walk(bounds_ref, pl.program_id(0), qi, j)
    walked = first <= last

    @pl.when((j == 0) & walked)
    def _init():
        q = q_ref[0]  # [bq, heads * D]
        for h in range(heads):
            cols = pl.ds(h * D, D)
            qh = q[:, h * D:(h + 1) * D]
            qs_scr[:, cols] = (_rotate(qh, cosq_ref[0], sinq_ref[0])
                               if rotary else qh)
            if sink:
                m_scr[h] = jnp.broadcast_to(sink_ref[0, pl.ds(h, 1), :],
                                            m_scr.shape[1:])
                l_scr[h] = jnp.ones(l_scr.shape[1:], jnp.float32)
            else:
                m_scr[h] = jnp.full(m_scr.shape[1:], _ACC_NEG, jnp.float32)
                l_scr[h] = jnp.zeros(l_scr.shape[1:], jnp.float32)
            acc_scr[h] = jnp.zeros(acc_scr.shape[1:], jnp.float32)
        if count:
            n_scr[...] = jnp.zeros(n_scr.shape, jnp.float32)

    @pl.when((kb >= first) & (kb <= last))
    def _step():
        k = k_ref[0]  # [bk, D], one KV head for every query head here
        if rotary:
            k = _rotate(k, cosk_ref[0], sink_k_ref[0])
        v = v_ref[0]
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        keep = (qid_ref[0] == kid_ref[0]) & (kpos <= qpos)
        if window:
            keep &= kpos > qpos - window
        if count:
            n_scr[...] += jnp.sum(keep.astype(jnp.float32), axis=-1,
                                  keepdims=True)
        for h in range(heads):
            q = qs_scr[:, pl.ds(h * D, D)]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=_dot_prec(q, k)) * scale
            s = jnp.where(keep, s, _MASK_NEG)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[h, :, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_dot_prec(v))
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when((j == steps - 1) & walked)
    def _finish():
        for h in range(heads):
            o_ref[0, :, pl.ds(h * Dv, Dv)] = (
                acc_scr[h] * pl.reciprocal(l_scr[h, :, :1])
            ).astype(o_ref.dtype)
        if count:
            n_ref[0] = n_scr[:, :1]

    # no step ran: without a sink the normaliser is 0, so write the zeros
    @pl.when((j == steps - 1) & jnp.logical_not(walked))
    def _nothing():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        if count:
            n_ref[...] = jnp.zeros(n_ref.shape, n_ref.dtype)


def _grouped_call(q, k, v, ids, rope, num_heads, kv_heads, window, sinks,
                  scale, interpret, count, padding_id):
    """`_grouped_kernel` over packed rows in the projections' own layout: q
    [B, L, heads * D], k [B, L, kv_heads * D], v [B, L, kv_heads * Dv]; the
    grid is (B, KV heads, q blocks, kv steps), so a step takes the whole
    group of query heads that reads one KV head and each key block is
    fetched once for the group. A window's grid holds only the key blocks
    the window reaches; full attention's holds every block. Each query
    block walks only its [first, last] (`_grouped_bounds`, computed here
    on the device and handed over in scalar memory), and a step outside it
    asks for a block of the range again, so it is not fetched. With
    `count`, also the keys each query attended, float32 [B, L, 1]."""
    B, L, _ = q.shape
    D, Dv = k.shape[2] // kv_heads, v.shape[2] // kv_heads
    heads = num_heads // kv_heads
    bq, bk, steps = _grouped_tiling(L, window)
    nq = L // bq
    bounds = _grouped_bounds(ids, bq, bk, window, padding_id)
    walk = functools.partial(_grouped_step, nq=nq, bq=bq, bk=bk,
                             steps=steps, window=window)

    def kv_block(b, qi, j, bounds_ref):
        first, last, kb = walk(bounds_ref, b, qi, j)
        return jnp.minimum(jnp.maximum(kb, first), last)

    kernel = functools.partial(
        _grouped_kernel, scale=scale, heads=heads, rotary=rope is not None,
        sink=sinks is not None, count=count, walk=walk, bq=bq, bk=bk,
        window=window, steps=steps)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM)
    extra, extra_specs = [], []
    if sinks is not None:
        # lane-replicated, one [heads, 128] block a KV head
        extra.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(kv_heads, heads, 1),
            (kv_heads, heads, 128)))
        extra_specs.append(pl.BlockSpec((1, heads, 128),
                                        lambda b, g, qi, j, _: (g, 0, 0)))
    if rope is not None:
        extra += [*rope, *rope]
        extra_specs += 2 * [pl.BlockSpec((1, bq, D),
                                         lambda b, g, qi, j, _: (b, qi, 0))]
        extra_specs += 2 * [pl.BlockSpec(
            (1, bk, D), lambda b, g, qi, j, s: (b, kv_block(b, qi, j, s), 0))]
    out_specs = [pl.BlockSpec((1, bq, heads * Dv),
                              lambda b, g, qi, j, _: (b, qi, g))]
    out_shape = [jax.ShapeDtypeStruct((B, L, num_heads * Dv), q.dtype)]
    scratch = [
        pltpu.VMEM((bq, heads * D), q.dtype),        # the rotated q
        pltpu.VMEM((heads, bq, 128), jnp.float32),   # running max
        pltpu.VMEM((heads, bq, 128), jnp.float32),   # running normaliser
        pltpu.VMEM((heads, bq, Dv), jnp.float32),    # accumulators
    ]
    if count:
        out_specs.append(pl.BlockSpec((1, bq, 1),
                                      lambda b, g, qi, j, _: (b, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, L, 1), jnp.float32))
        scratch.append(pltpu.VMEM((bq, 128), jnp.float32))  # keys attended
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, kv_heads, nq, steps),
            in_specs=[
                pl.BlockSpec((1, bq, 1), lambda b, g, qi, j, _: (b, qi, 0)),
                pl.BlockSpec((1, 1, bk), lambda b, g, qi, j, s: (
                    b, 0, kv_block(b, qi, j, s))),
                *extra_specs,
                pl.BlockSpec((1, bq, heads * D),
                             lambda b, g, qi, j, _: (b, qi, g)),
                pl.BlockSpec((1, bk, D), lambda b, g, qi, j, s: (
                    b, kv_block(b, qi, j, s), g)),
                pl.BlockSpec((1, bk, Dv), lambda b, g, qi, j, s: (
                    b, kv_block(b, qi, j, s), g)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        # the op's name in a profile: a per-layer metric reads each kernel
        name="window_attention" if window else "grouped_attention",
        interpret=interpret,
        **kwargs,
    )(bounds.reshape(-1), ids[:, :, None], ids[:, None, :], *extra, q, k, v)
    return tuple(out) if count else out[0]


def _dense_reference(q, k, v, bias, causal, scale, segment_ids=None):
    """f32 dense attention — fallback path and backward-pass recompute.
    `segment_ids` [B, S] int32 (self-attention): a query sees the keys of
    its own segment only."""
    NH, NKV = q.shape[1], k.shape[1]
    if NH != NKV:
        k = jnp.repeat(k, NH // NKV, axis=1)
        v = jnp.repeat(v, NH // NKV, axis=1)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    s = s + bias[:, None, None, :]
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]
        s = jnp.where(same[:, None], s, _MASK_NEG)
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        qpos = jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Sk)[None, :]
        s = jnp.where(qpos >= kpos, s, _MASK_NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf), (p, qf, kf, vf)


# ------------------------------------------------------------ fused backward


def _bwd_kv_kernel(bias_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dbias_ref, dk_scr, dv_scr, dbias_scr,
                   *, scale: float, causal: bool, block_q: int, block_k: int):
    """dK/dV (+ per-head dbias) for one kv block, accumulated over q blocks
    (innermost sequential axis). p is rebuilt from (q, k, lse) — no S×S
    materialization."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)
        dbias_scr[:] = jnp.zeros(dbias_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]
        g = g_ref[0, 0]  # [bq, D] — kept in input dtype for the dots
        lse = lse_ref[0, 0]    # [bq, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_dot_prec(q, k)) * scale
        s = s + bias_ref[0, 0]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _MASK_NEG)
        p = jnp.exp(s - lse)  # [bq, bk] — exact probs via the saved lse
        # dv += pᵀ g ; dp = g vᵀ ; ds = p (dp − delta) ; dk += dsᵀ q · scale
        # (f32-derived p/ds cast DOWN to the input dtype for the dots, like
        # the forward's p@v — bf16 operands keep single-pass MXU matmuls)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(g))
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_dot_prec(g, v))
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(q)) * scale
        dbias_scr[:] = dbias_scr[:] + jnp.broadcast_to(
            jnp.sum(ds, axis=0, keepdims=True), dbias_scr.shape)

    if causal:
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)
        # written at the scratch's own (8, bk) tile shape — sublane-replicated
        # rows; the host reads row 0 (keeps the store Mosaic-tileable without
        # a lane→sublane transpose in-kernel)
        dbias_ref[0, 0] = dbias_scr[:]


def _bwd_q_kernel(bias_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                  dq_ref, dq_scr, *, scale: float, causal: bool,
                  block_q: int, block_k: int):
    """dQ for one q block, accumulated over kv blocks (innermost)."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        g = g_ref[0, 0]  # input dtype — see _bwd_kv_kernel
        lse = lse_ref[0, 0]    # [bq, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_dot_prec(q, k)) * scale
        s = s + bias_ref[0, 0]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _MASK_NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=_dot_prec(g, v))
        ds = p * (dp - delta)
        # dq += ds @ k · scale — contract ds's kv dim with k's kv dim
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_dot_prec(k)) * scale

    if causal:
        @pl.when((qi + 1) * block_q > ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, bias, out, lse, g, causal, scale, bq, bk,
                     interpret):
    """Fused backward (NH == NKV): two pallas calls, O(S) memory."""
    B, NH, Sq, D = q.shape
    Sk = k.shape[2]
    # delta carries the same [B, NH, Sq, 1] layout as lse (tileable blocks)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(
        -1, keepdims=True)
    bias_blocked = bias.astype(jnp.float32).reshape(B, Sk // bk, 1, bk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    qspec_j = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, j, 0))
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0))
    kspec_j = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    rowspec_j = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, j, 0))

    dk, dv, dbias_h = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(B, NH, Sk // bk, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, i, j: (b, i, 0, 0)),
            qspec_j, kspec, kspec, qspec_j, rowspec_j, rowspec_j,
        ],
        out_specs=[kspec, kspec,
                   pl.BlockSpec((1, 1, 8, bk), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, NH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, NH, Sk, D), v.dtype),
                   jax.ShapeDtypeStruct((B, NH, 8, Sk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((8, bk), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(bias_blocked, q, k, v, g, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(B, NH, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, i, j: (b, j, 0, 0)),
            qspec, kspec_j, kspec_j, qspec, rowspec, rowspec,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, NH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(bias_blocked, q, k, v, g, lse, delta)

    return dq, dk, dv, dbias_h[:, :, 0, :].sum(axis=1).astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    if block_q == 0 or block_k == 0:
        _announce("dense_untileable", q, k)
        out, _ = _dense_reference(q, k, v, bias, causal, scale)
        return out.astype(q.dtype)
    return _flash_call(q, k, v, bias, causal, scale, block_q, block_k,
                       interpret)[0]


def _flash_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    if block_q == 0 or block_k == 0:
        _announce("dense_untileable", q, k)
        out, _ = _dense_reference(q, k, v, bias, causal, scale)
        return out.astype(q.dtype), (q, k, v, bias, None, None)
    out, lse = _flash_call(q, k, v, bias, causal, scale, block_q, block_k,
                           interpret)
    if q.shape[1] != k.shape[1]:
        # GQA routes to the dense-recompute backward, which never reads
        # out/lse — don't pin them in the autodiff residuals
        return out, (q, k, v, bias, None, None)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, bias, out, lse = res
    NH, NKV = q.shape[1], k.shape[1]
    group = NH // NKV
    if lse is not None and group == 1:
        return _flash_bwd_fused(q, k, v, bias, out, lse, g, causal, scale,
                                block_q, block_k, interpret)
    # dense f32 recompute: the fallback-shape path (announced in the
    # forward) and GQA (prefill-only in this system; long-context LM
    # training rides parallel/context.py)
    if block_q and block_k:
        _announce("dense_gqa_backward", q, k)
    _, (p, qf, kf, vf) = _dense_reference(q, k, v, bias, causal, scale)
    gf = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
    if group > 1:
        B, _, Sk, D = dk.shape
        dk = dk.reshape(B, NKV, group, Sk, D).sum(axis=2)
        dv = dv.reshape(B, NKV, group, Sk, D).sum(axis=2)
    dbias = ds.sum(axis=(1, 2))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias.astype(bias.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _interpret(interpret: bool | None, q, k) -> bool:
    """`interpret=None` resolved by the backend, and the two errors."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"flash_attention: backend {backend!r} is neither 'tpu' "
            "(compiled kernel) nor 'cpu' (interpreter)")
    if interpret is None:
        interpret = backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError(
            "flash_attention(interpret=True) on a tpu backend: the Pallas "
            "interpreter must never stand in for the compiled kernel on "
            "the chip")
    if interpret:
        _announce("interpreter", q, k)
    return interpret


def flash_attention(
    q: jax.Array,  # [B, NH, Sq, D]
    k: jax.Array,  # [B, NKV, Sk, D] — NKV divides NH (GQA)
    v: jax.Array,  # [B, NKV, Sk, D]
    kv_bias: jax.Array | None = None,  # [B, Sk] additive f32 (0 / -1e9)
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused attention → [B, NH, Sq, D] in q.dtype.

    `interpret=None` auto-selects: compiled kernel on a `tpu` backend,
    pallas interpreter on a `cpu` backend (CPU tests run the same kernel
    code path bit-for-bit; announced, see `_announce`). Any other backend
    name is an error — a TPU reached under another platform name must not
    silently run the interpreter — and so is `interpret=True` on `tpu`.
    """
    B, NH, Sq, D = q.shape
    NKV, Sk = k.shape[1], k.shape[2]
    if NH % NKV != 0:
        raise ValueError(f"q heads {NH} not a multiple of kv heads {NKV}")
    if v.shape != k.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    interpret = _interpret(interpret, q, k)
    if kv_bias is None:
        kv_bias = jnp.zeros((B, Sk), jnp.float32)
    kv_bias = kv_bias.astype(jnp.float32)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    return _flash(q, k, v, kv_bias, causal, float(scale),
                  _pick_block(Sq, block_q), _pick_block(Sk, block_k), interpret)


def packed_attention(
    q: jax.Array,  # [B, L, num_heads * D], as a projection produces it
    k: jax.Array,  # [B, L, num_heads * D]
    v: jax.Array,  # [B, L, num_heads * Dv]
    segment_ids: jax.Array,  # [B, L] int32: the token's segment in its row
    num_heads: int,
    rope: tuple[jax.Array, jax.Array] | None = None,  # `layers.rope_tables`
    block: int = 512,
    interpret: bool | None = None,
    scale: float | None = None,
    kv_heads: int | None = None,
    window: int = 0,
    sinks: jax.Array | None = None,
    count_keys: bool = False,
    padding_id: int | None = None,
):
    """Causal attention inside the segments of packed rows, forward only,
    -> [B, L, num_heads * Dv] in q.dtype: token i sees token j iff
    `segment_ids[b, i] == segment_ids[b, j]` and `j <= i`; scores scaled by
    `scale`, 1 / sqrt(D) when None (a caller whose heads are zero-padded to
    the lanes gives the scale of the width before padding). Padding carries
    an id no segment has, so it keeps itself company and no softmax row is
    empty. With `rope`, q and k are turned inside the
    kernel first (float32, cast back to their dtype, as `layers.rope` does):
    done outside, the turned q and k are two more [B, L, heads * D] arrays
    written and read per call, in a layout the kernel cannot take.

    `flash_attention`'s streaming softmax with the same arithmetic
    (operands in the input dtype, float32 scores, softmax statistics and
    accumulator) and the same `interpret` rule, over square blocks of the
    largest power of two up to `block` that divides L; it reads and writes
    the projections' own layout, a head being a D-wide (v: Dv-wide) column
    block, so D, Dv and L must be multiples of 128.

    Three things more, each off by default (`_grouped_call`, its own
    kernel; without them the call is the one above): `kv_heads` fewer than
    `num_heads` (GQA: k and v [B, L, kv_heads * D], query head h reads KV
    head h // (num_heads / kv_heads), and each key block is fetched once
    for its group of query heads), a `window` W (token i sees j only where
    i - W < j; the grid holds only the key blocks the window reaches) and
    `sinks` [num_heads] float32, a logit per head that joins each query's
    softmax normaliser and attends to nothing. L must then be a multiple
    of 128. With `count_keys` (the grouped form only) the call returns
    (out, keys): keys int32 [B, L], how many keys each query's softmax
    took, counted in the kernel from the mask it applied.

    The grouped form and the streaming one (a row longer than one block)
    walk, for each query block, only the key blocks from the first whose
    ids can meet its own to the diagonal (`grouped_steps` and
    `segment_steps` count them), which leaves every output as a walk of
    all blocks gives it; the streaming form's grid is a list of those
    pairs alone. Given the padding's id (`padding_id`; it does not make a
    call grouped), a query block that is all padding walks nothing: its
    context and its count are 0, where a walk of its own keys would give
    padding's mean value. A padding query in a block with real ones keeps
    itself company as before."""
    B, L, HD = q.shape
    D, rem = divmod(HD, num_heads)
    Dv, vrem = divmod(v.shape[-1], num_heads)
    grouped = (kv_heads is not None or window or sinks is not None
               or count_keys)
    if grouped:
        kv_heads = kv_heads or num_heads
        Dv, vrem = divmod(v.shape[-1], kv_heads)
        vrem = vrem or num_heads % kv_heads
        block = 128 if L % 128 == 0 else 0
    else:
        kv_heads = num_heads
        block = _pick_block(L, block)
    if rem or vrem or D % 128 or Dv % 128 or block < 128:
        raise ValueError(
            f"packed_attention: q{tuple(q.shape)} v{tuple(v.shape)} with "
            f"{num_heads} heads does not tile (head widths and L must be "
            "multiples of 128)")
    shapes = [t.shape for t in (k, v, *(rope or ()))]
    if (shapes != [(B, L, kv_heads * D), (B, L, kv_heads * Dv)]
            + (len(shapes) - 2) * [(B, L, D)]
            or segment_ids.shape != (B, L)
            or (sinks is not None and sinks.shape != (num_heads,))):
        raise ValueError(
            f"packed_attention: shapes q{tuple(q.shape)}, k, v and the rope "
            f"tables {shapes}, ids{tuple(segment_ids.shape)}")
    interpret = _interpret(interpret, q, k)
    if grouped:
        out = _grouped_call(
            q, k, v, segment_ids.astype(jnp.int32), rope, num_heads,
            kv_heads, int(window), sinks,
            1.0 / math.sqrt(D) if scale is None else scale, interpret,
            count_keys, padding_id)
        if count_keys:
            out, keys = out
            return out, keys[..., 0].astype(jnp.int32)
        return out
    return _packed_call(q, k, v, segment_ids.astype(jnp.int32), rope,
                        num_heads, block, interpret, scale, padding_id)

