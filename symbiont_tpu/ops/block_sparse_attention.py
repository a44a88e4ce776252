"""Blocked causal attention over a per-query set of key blocks (InfLLM-V2's
selection, as published with MiniCPM4) on packed rows.

Per passage, per query token t (position p in its passage) and KV group g
(the query heads that share one key/value head share one selection):

1. compressed keys: kernel j = mean(k[j*stride : j*stride + kernel_size]);
   only kernels whose last token is at or before t are visible;
2. r[t, j] = sum over the group's heads of softmax_j(q_h . kernel_j * scale);
3. block score = max of r over the kernels that overlap the block
   (`block_size` tokens);
4. the set = the first `init_blocks` blocks, the blocks that cover the last
   `window_size` tokens, and the best-scoring others, `topk` blocks in all
   (ties to the lower block; fewer exist: all of them);
5. softmax attention over the causal keys inside the set.

A passage of at most `dense_len` tokens attends to every causal key.

Nothing here is [L, L]. Keys and values (few heads) are first copied into
an ALIGNED layout in which every passage starts on a block boundary, so
kernels and blocks lie on one static grid whatever the passages' lengths;
queries stay where they are and carry their aligned position. Rows are
taken one after another (`lax.map`), a row's queries in blocks of
`q_block` (`lax.scan`): a block scores the row's kernels, picks its
tokens' sets as a [G, q_block, blocks] mask, and then walks the key chunks
from its first token's passage start to its last token (`fori_loop` with
traced bounds: chunks of other passages and of the future are never
touched) with the online softmax. Inside a chunk the computation is dense
and the set is a mask: on this hardware a [16 heads x 128] tile against
gathered blocks would leave seven eighths of the matrix unit idle, which
costs what the masked keys do.

`sp` is the model's sparse sizes (models/sala.py `SparseConfig`: kernel_size,
kernel_stride, block_size, init_blocks, window_size, topk, dense_len).
Returns, beside the output, per row: keys attended (mean over the groups)
and keys a causal attention reads.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 256
K_CHUNK = 1024
_NEG = -1e30


class Layout(NamedTuple):
    """The aligned key layout of packed rows and where the queries stand in
    it (all int32)."""
    src: jax.Array  # [B, La] row index an aligned slot copies
    seg: jax.Array  # [B, La] its passage; -1 = the slot holds nothing
    pos: jax.Array  # [B, La] place in its passage
    q_at: jax.Array  # [B, L] a query's aligned position
    q_len: jax.Array  # [B, L] tokens of a query's passage (0: padding)


def aligned_layout(index, position, lengths, block: int,
                   multiple: int) -> Layout:
    """`index`, `position` [B, L], `lengths` [B, S] as models/bert.py
    `Segments` has them. La = L + S * block rounded up to `multiple`."""
    B, L = index.shape
    S = lengths.shape[1]
    La = -(-(L + S * block) // multiple) * multiple
    padded = -(-lengths // block) * block
    a_end = jnp.cumsum(padded, axis=1)
    a_start = a_end - padded
    r_start = jnp.cumsum(lengths, axis=1) - lengths
    u = jnp.arange(La, dtype=jnp.int32)[None, :, None]
    seg = (a_end[:, None, :] <= u).sum(-1, dtype=jnp.int32)  # [B, La]
    slot = jnp.minimum(seg, S - 1)
    take = lambda a: jnp.take_along_axis(a, slot, axis=1)  # noqa: E731
    pos = u[:, :, 0] - take(a_start)
    real = (seg < S) & (pos < take(lengths))
    src = jnp.clip(take(r_start) + pos, 0, L - 1)
    q_slot = jnp.minimum(index, S - 1)
    q_real = index < S
    shift = jnp.take_along_axis(a_start - r_start, q_slot, axis=1)
    q_len = jnp.where(q_real, jnp.take_along_axis(lengths, q_slot, axis=1), 0)
    t = jnp.arange(L, dtype=jnp.int32)[None]
    return Layout(src, jnp.where(real, seg, -1), pos,
                  jnp.where(q_real, t + shift, 0), q_len)


def _compress(k_al, sp):
    """[La, G, d] -> [La / stride, G, d] float32 means of `kernel_size`
    tokens from each multiple of `kernel_stride`."""
    La, G, d = k_al.shape
    st, m = sp.kernel_stride, sp.kernel_size // sp.kernel_stride
    a = k_al.astype(jnp.float32).reshape(La // st, st, G, d).sum(1)
    a = jnp.pad(a, ((0, m - 1), (0, 0), (0, 0)))
    n = La // st
    return sum(a[i:i + n] for i in range(m)) / sp.kernel_size


def _select(qb, kern, kern_seg, kern_end, blk_seg, blk_start, q_seg, q_pos,
            q_len, sp, scale: float):
    """One query block's sets. qb [G, Q, hg, d]; kern [nK, G, d]; ->
    (sel [G, Q, nBlk] bool, gap [G, Q] float32: the score of the last block
    taken less the first left out, inf where nothing was left out)."""
    st, bs = sp.kernel_stride, sp.block_size
    per, tail = bs // st, sp.kernel_size // st - 1
    G, Q = qb.shape[:2]
    nBlk = blk_seg.shape[0]
    with jax.named_scope("sparse_select"):
        s = jnp.einsum("gqhd,kgd->ghqk", qb, kern.astype(qb.dtype),
                       preferred_element_type=jnp.float32) * scale
        seen = ((kern_seg[None, :] == q_seg[:, None])
                & (kern_end[None, :] <= q_pos[:, None]))[None, None]
        s = jnp.where(seen, s, _NEG)
        p = jnp.where(seen, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        r = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(1)
        r = r.reshape(G, Q, nBlk, per)  # the kernels that start in a block
        score = r.max(-1)
        if tail:  # and those of the block before that reach into it
            before = r[..., per - tail:].max(-1)
            score = jnp.maximum(score, jnp.pad(
                before, ((0, 0), (0, 0), (1, 0)))[..., :nBlk])
        valid = ((blk_seg[None, :] == q_seg[:, None])
                 & (blk_start[None, :] <= q_pos[:, None]))
        forced = valid & ((blk_start[None, :] < sp.init_blocks * bs)
                          | (blk_start[None, :] + bs - 1
                             >= q_pos[:, None] - (sp.window_size - 1)))
        score = jnp.where(valid[None], jnp.where(forced[None], jnp.inf,
                                                 score), -jnp.inf)
        k = min(sp.topk, nBlk)
        top, idx = jax.lax.top_k(score, min(k + 1, nBlk))
        took = top[..., :k] > -jnp.inf
        sel = ((idx[..., :k, None] == jnp.arange(nBlk))
               & took[..., None]).any(-2)
        dense = (q_len <= sp.dense_len)[None, :, None]
        sel = jnp.where(dense, valid[None], sel)
        if k < nBlk:
            gap = jnp.where(top[..., k] > -jnp.inf,
                            top[..., k - 1] - top[..., k], jnp.inf)
        else:
            gap = jnp.full((G, Q), jnp.inf, jnp.float32)
        gap = jnp.where(dense[..., 0], jnp.inf, gap)
    return sel, gap


def block_sparse_attention(q, k, v, index, position, lengths, sp, *,
                           q_block: int = Q_BLOCK, k_chunk: int = K_CHUNK,
                           with_sets: bool = False):
    """q [B, L, nh, d]; k, v [B, L, G, d] (nh a multiple of G); `index`,
    `position` [B, L] and `lengths` [B, S] as `Segments` has them ->
    (out [B, L, nh, d] in q's dtype, counts [B, 2] int32).

    The keyword arguments are test hooks; the model passes none. `q_block`
    and `k_chunk` shrink the tiles so that toy rows span several of each;
    `with_sets` adds (sel [B, G, L, nBlk], gap [B, G, L]): each token's set
    on the aligned grid, where a row's passages follow one another from
    block boundary to block boundary, for the tests that hold the sets to
    the reference's."""
    B, L, nh, d = q.shape
    G = k.shape[2]
    hg = nh // G
    bs, st = sp.block_size, sp.kernel_stride
    Q = min(q_block, -(-L // 8) * 8)
    nQ = -(-L // Q)
    Kc = -(-min(k_chunk, L + bs) // bs) * bs
    lay = aligned_layout(index, position, lengths, bs, Kc)
    La = lay.src.shape[1]
    nBlk, nChunk, per_chunk = La // bs, La // Kc, Kc // bs
    scale = 1.0 / math.sqrt(d)
    S = lengths.shape[1]

    def gather(a):  # [B, L, G, d] -> aligned [B, La, G, d], holes zero
        out = jnp.take_along_axis(a, lay.src[:, :, None, None], axis=1)
        return jnp.where((lay.seg >= 0)[:, :, None, None], out, 0)

    pad = nQ * Q - L
    q_seg = jnp.where(index < S, index, -2)  # padding matches no key

    def blocks(a, fill=0):  # [B, L, ...] -> [B, nQ, Q, ...]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                        constant_values=fill)
        return a.reshape(B, nQ, Q, *a.shape[2:])

    def row(xs):
        qr, k_al, v_al, seg, pos, r_seg, r_pos, r_at, r_len = xs
        kern = _compress(k_al, sp)
        kern_seg = seg[::st]
        kern_end = pos[::st] + sp.kernel_size - 1
        blk_seg, blk_start = seg[::bs], pos[::bs]

        def block(_, xs):
            qb, b_seg, b_pos, b_at, b_len = xs
            qb = qb.reshape(Q, G, hg, d).transpose(1, 0, 2, 3)
            sel, gap = _select(qb, kern, kern_seg, kern_end, blk_seg,
                               blk_start, b_seg, b_pos, b_len, sp, scale)
            real = b_len > 0
            lo = jnp.min(jnp.where(real, b_at - b_pos, La)) // Kc
            hi = jnp.max(jnp.where(real, b_at, -1)) // Kc

            def chunk(c, carry):
                m, l, acc = carry
                kc = jax.lax.dynamic_slice_in_dim(k_al, c * Kc, Kc)
                vc = jax.lax.dynamic_slice_in_dim(v_al, c * Kc, Kc)
                c_seg = jax.lax.dynamic_slice_in_dim(seg, c * Kc, Kc)
                c_pos = jax.lax.dynamic_slice_in_dim(pos, c * Kc, Kc)
                c_sel = jax.lax.dynamic_slice_in_dim(sel, c * per_chunk,
                                                     per_chunk, axis=2)
                s = jnp.einsum("gqhd,kgd->ghqk", qb, kc,
                               preferred_element_type=jnp.float32) * scale
                keep = ((c_seg[None, :] == b_seg[:, None])
                        & (c_pos[None, :] <= b_pos[:, None]))[None]
                keep = keep & jnp.repeat(c_sel, bs, axis=2)
                s = jnp.where(keep[:, None], s, _NEG)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.where(keep[:, None],
                              jnp.exp(s - m_new[..., None]), 0.0)
                fix = jnp.exp(m - m_new)
                acc = acc * fix[..., None] + jnp.einsum(
                    "ghqk,kgd->ghqd", p.astype(qb.dtype), vc,
                    preferred_element_type=jnp.float32)
                return m_new, l * fix + p.sum(-1), acc

            with jax.named_scope("sparse_attn"):
                m, l, acc = jax.lax.fori_loop(lo, hi + 1, chunk, (
                    jnp.full((G, hg, Q), _NEG, jnp.float32),
                    jnp.zeros((G, hg, Q), jnp.float32),
                    jnp.zeros((G, hg, Q, d), jnp.float32)))
                out = acc / jnp.maximum(l, 1e-30)[..., None]
                out = out.transpose(2, 0, 1, 3).reshape(Q, nh, d)
            # causal keys of each block for each query: the set's size
            span = jnp.clip(b_pos[:, None] - blk_start[None, :] + 1, 0, bs)
            attended = (jnp.where(sel, span[None], 0).sum(dtype=jnp.int32)
                        // G)
            causal = jnp.where(real, b_pos + 1, 0).sum(dtype=jnp.int32)
            extra = (sel, gap) if with_sets else ()
            return None, (out.astype(q.dtype),
                          jnp.stack([attended, causal]), *extra)

        return jax.lax.scan(block, None, (qr, r_seg, r_pos, r_at, r_len))[1]

    got = jax.lax.map(row, (
        blocks(q), gather(k), gather(v), lay.seg, lay.pos, blocks(q_seg, -2),
        blocks(position), blocks(lay.q_at), blocks(lay.q_len)))
    out = got[0].reshape(B, nQ * Q, nh, d)[:, :L]
    counts = got[1].sum(1)
    if not with_sets:
        return out, counts
    sel = jnp.moveaxis(got[2], 2, 1).reshape(B, G, nQ * Q, nBlk)[:, :, :L]
    gap = jnp.moveaxis(got[3], 2, 1).reshape(B, G, nQ * Q)[:, :, :L]
    return out, counts, (sel, gap)
