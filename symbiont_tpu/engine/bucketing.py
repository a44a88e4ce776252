"""Length buckets, padding and sequence packing — the replacement for
pad-everything-to-max.

The reference pads every sentence to the model's max_position_embeddings (514
for mpnet) regardless of true length (reference:
services/preprocessing_service/src/embedding_generator.rs:83-91), so a 6-token
sentence pays a 514-token forward. SURVEY.md §5.7 sizes that waste at ~10-80×.

Two planners live here, one per executable kind of the engine:

- **`embed` packs** (`plan_packed`): the sentences of one `embed_texts` call
  are laid end to end into rows of ONE length bucket `L`, at most
  `segments_per_row(L)` sentences a row and never a sentence split, by
  first-fit-decreasing. A dispatch is `[B, L]` token ids plus `[B, S]`
  sentence lengths; the program rebuilds per-sentence positions, a
  block-diagonal attention mask and per-sentence pooling from the lengths
  and returns `[B, S, H]`. `L` is the smallest bucket whose ONE row holds the
  whole call, else the top bucket: so rows below the top bucket only ever
  come alone, and the executable set of `embed` is |length_buckets| +
  |batch_buckets| − 1 shapes (six for three and four: (32,1) (64,1) (128,1)
  (128,8) (128,32) (128,128)), not the grid.
- **`rerank` buckets** (`plan_batches`): a (query, passage) pair is one row
  padded to the smallest bucket ≥ its length, batches grouped per bucket;
  batch sizes are bucketed too, so that executable set stays bounded at
  |length_buckets|×|batch_buckets| (the "recompile storm" guard from
  SURVEY.md §7 hard-part #2).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def choose_bucket(length: int, buckets: Sequence[int]) -> int:
    """Smallest bucket ≥ length; the largest bucket if none (caller truncates)."""
    for b in buckets:
        if length <= b:
            return b
    return buckets[-1]


def pad_to_bucket(
    seqs: Sequence[Sequence[int]], bucket: int, pad_id: int,
    dtype=np.int32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a list of token-id sequences to [n, bucket] ids + mask.

    `dtype` lets callers ship ids in the narrowest dtype the vocab allows
    (uint16 when vocab ≤ 65535) — halves h2d bytes; the device executable
    casts back to int32."""
    n = len(seqs)
    ids = np.full((n, bucket), pad_id, dtype)
    mask = np.zeros((n, bucket), np.int32)
    for i, s in enumerate(seqs):
        s = list(s[:bucket])
        ids[i, : len(s)] = s
        mask[i, : len(s)] = 1
    return ids, mask


def pad_batch_rows(
    ids: np.ndarray, mask: np.ndarray, batch_bucket: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad batch dim up to batch_bucket with all-pad rows; returns real count."""
    n = ids.shape[0]
    if n == batch_bucket:
        return ids, mask, n
    pad_rows = batch_bucket - n
    ids = np.concatenate([ids, np.tile(ids[-1:], (pad_rows, 1))], axis=0)
    mask = np.concatenate([mask, np.zeros((pad_rows, mask.shape[1]), np.int32)], axis=0)
    return ids, mask, n


def pad_ids_rows(
    seqs: Sequence[Sequence[int]], bucket: int, pad_id: int,
    dtype=np.int32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad token-id sequences to [n, bucket] ids + true lengths [n].

    The attention mask is NOT materialized on host: the device executable
    rebuilds it as `arange(bucket) < lengths[:, None]`, halving the
    host→device bytes vs shipping an explicit [n, bucket] mask.
    `dtype` further narrows the wire: uint16 ids when the vocab fits."""
    n = len(seqs)
    ids = np.full((n, bucket), pad_id, dtype)
    lengths = np.zeros((n,), np.int32)
    for i, s in enumerate(seqs):
        s = list(s[:bucket])
        ids[i, : len(s)] = s
        lengths[i] = len(s)
    return ids, lengths


def pad_batch_rows_ids(
    ids: np.ndarray, lengths: np.ndarray, batch_bucket: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Row-pad (ids, lengths) up to batch_bucket; padding rows get length 0
    (their pooled output is discarded). Returns real row count."""
    n = ids.shape[0]
    assert n <= batch_bucket, (
        f"batch of {n} rows exceeds its batch bucket {batch_bucket}")
    if n == batch_bucket:
        return ids, lengths, n
    pad_rows = batch_bucket - n
    ids = np.concatenate([ids, np.tile(ids[-1:], (pad_rows, 1))], axis=0)
    lengths = np.concatenate([lengths, np.zeros(pad_rows, np.int32)])
    return ids, lengths, n


def padding_stats(
    lengths: Sequence[int], bucket: int, batch_rows: int
) -> Tuple[int, int]:
    """(real_tokens, padded_slots) for one dispatched batch: how many of the
    `batch_rows * bucket` token slots the device will chew on carry real
    tokens vs bucket/row padding. Feeds the engine-plane padding-waste
    gauges (docs/OBSERVABILITY.md) — the quantified version of this module's
    whole reason to exist (SURVEY.md §5.7's 10-80x pad-to-max waste)."""
    real = int(sum(min(int(n), bucket) for n in lengths))
    return real, int(batch_rows) * int(bucket)


def plan_batches(
    lengths: Sequence[int],
    length_buckets: Sequence[int],
    max_batch: int,
) -> List[Tuple[int, List[int]]]:
    """Greedy plan: sort indices by length, group same-bucket runs into batches
    of ≤ max_batch. Returns [(length_bucket, [original indices]), ...]."""
    order = sorted(range(len(lengths)), key=lambda i: lengths[i])
    plans: List[Tuple[int, List[int]]] = []
    cur_bucket = None
    cur: List[int] = []
    for idx in order:
        b = choose_bucket(lengths[idx], length_buckets)
        if b != cur_bucket or len(cur) >= max_batch:
            if cur:
                plans.append((cur_bucket, cur))
            cur_bucket, cur = b, []
        cur.append(idx)
    if cur:
        plans.append((cur_bucket, cur))
    return plans


# ---------------------------------------------------------------- packing


LONG_ROW_SEGMENTS = 64


def segments_per_row(bucket: int) -> int:
    """S: the most sentences one packed row of `bucket` tokens may hold. A
    row's sentence lengths ship as `[S]` int32 (half the bytes of a
    per-token segment index) and its pooled rows come back `[S, H]`, so S
    is kept to what rows of short sentences need: a sentence of under 8
    tokens is rare enough that the cap binds on few rows. That holds up to
    rows of 512 tokens (64 slots); a longer bucket exists for longer
    passages, not for more of them, and keeps the 64: S no longer grows
    with the bucket, so a 32,768-token row ships 256 bytes of lengths,
    brings back `[64, H]` and the device's `[B, L, S]` index of
    `Segments.of_lengths` stays linear in L."""
    return max(1, min(bucket // 8, LONG_ROW_SEGMENTS))


def plan_packed(
    lengths: Sequence[int],
    length_buckets: Sequence[int],
    max_rows: int,
) -> Tuple[int, List[List[List[int]]]]:
    """Pack one call's sentences into rows: -> (L, dispatches), a dispatch
    being a list of ≤ `max_rows` rows and a row a list of original indices
    in the order their tokens are laid.

    `L` is the smallest bucket whose single row holds the whole call (its
    tokens ≤ L, its sentences ≤ `segments_per_row(L)`), else the top bucket.
    Sentences (clipped to L, as the tokenizer has already truncated them)
    go first-fit-decreasing into rows of L tokens and S sentences. Plain
    lists: at a page's 200 sentences the loop below costs a tenth of the
    same thing spelled as a numpy call per sentence, and it runs under the
    GIL the page's handlers share (PERF.md §6, PR 31)."""
    top = length_buckets[-1]
    lens = [min(int(n), top) for n in lengths]
    n, total = len(lens), sum(lens)
    L = next((b for b in length_buckets
              if total <= b and n <= segments_per_row(b)), top)
    S = segments_per_row(L)
    rows, free = [], []  # free[r]: tokens left in row r
    open_rows = []  # rows that can still take the shortest sentence
    shortest = min(lens, default=0)
    for i in sorted(range(n), key=lens.__getitem__, reverse=True):
        need = lens[i]
        for r in open_rows:
            if free[r] >= need:
                break
        else:
            r = len(rows)
            rows.append([])
            free.append(L)
            open_rows.append(r)
        rows[r].append(i)
        free[r] -= need
        if free[r] < shortest or len(rows[r]) == S:
            open_rows.remove(r)
    return L, [rows[i:i + max_rows] for i in range(0, len(rows), max_rows)]


def pack_rows(
    seqs: Sequence[Sequence[int]], rows: Sequence[Sequence[int]], bucket: int,
    batch_rows: int, pad_id: int, dtype=np.int32,
) -> Tuple[np.ndarray, np.ndarray]:
    """One packed dispatch on the host: ids `[batch_rows, bucket]` with each
    row's sentences laid end to end, and their lengths `[batch_rows, S]`
    int32 (0 = no sentence in that slot; rows past `len(rows)` are all
    padding). The device rebuilds everything else from the lengths."""
    S = segments_per_row(bucket)
    ids, seg = [], []
    for row in rows:
        parts = [seqs[i][:bucket] for i in row]
        toks = [t for part in parts for t in part]
        ids.append(toks + [pad_id] * (bucket - len(toks)))
        seg.append([len(part) for part in parts] + [0] * (S - len(row)))
    empty = batch_rows - len(rows)
    ids.extend([[pad_id] * bucket] * empty)
    seg.extend([[0] * S] * empty)
    return np.array(ids, dtype), np.array(seg, np.int32)
