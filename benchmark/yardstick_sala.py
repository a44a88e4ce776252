"""Operation and byte counts of a block-sparse + linear-attention decoder
stack (MiniCPM-SALA's layout) run as an encoder, from shapes alone. Like
`yardstick.py`, keyed by what the work IS (passages and their real lengths),
never by which executable did it, and imports nothing of the program. `m` is
the configuration's `model` block (HF keys + `sparse_config`).

Matmul FLOPs only (2 per multiply-add); norms, softmaxes, RoPE, the top-k
and the gates' sigmoids are not counted, so a share of a peak built on these
never flatters the program. The sparse layers count the keys a token's SET
holds, never the causal prefix a dense attention would read; the linear
layers count the token-by-token recurrence (one multiply-add per state
element to update it, one to read it), not the larger count of a chunked
form. Bytes are what each must move at least once at bfloat16 (float32
compressed keys): an ideal kernel keeps scores, sets and the linear state
on the chip, so none of those is counted.
"""

from __future__ import annotations

import numpy as np

SPARSE, LINEAR = "minicpm4", "lightning-attn"
ACT_BYTES = 2.0  # bfloat16 activations


def sparse_sizes(m: dict) -> dict:
    return {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
            "init_blocks": 1, "window_size": 2048, "topk": 64,
            "dense_len": 8192, **m.get("sparse_config", {})}


def keys_attended(n: int, m: dict) -> np.ndarray:
    """Keys the token at each position 0..n-1 of an n-token passage attends
    to (one kv group's; every group's set has the same size): its causal
    prefix where the passage runs dense or holds under `topk` blocks so
    far, else `topk` - 1 whole blocks and its own block up to itself."""
    sp = sparse_sizes(m)
    p = np.arange(n, dtype=np.int64)
    if n <= sp["dense_len"]:
        return p + 1
    bs, k = sp["block_size"], sp["topk"]
    return np.where(p // bs + 1 <= k, p + 1, (k - 1) * bs + p % bs + 1)


def kernels_visible(n: int, m: dict) -> np.ndarray:
    """Compressed keys each token of an n-token passage scores (0 where the
    passage runs dense: no selection is made)."""
    sp = sparse_sizes(m)
    if n <= sp["dense_len"]:
        return np.zeros(n, np.int64)
    ks, st = sp["kernel_size"], sp["kernel_stride"]
    p = np.arange(n, dtype=np.int64)
    return np.where(p >= ks - 1, (p - (ks - 1)) // st + 1, 0)


def sparse_flops(lengths, m: dict) -> float:
    """One sparse layer's selection and attention (not its projections)
    over passages of the given REAL lengths: every query head scores the
    visible kernels; every query head scores and sums the keys of its
    group's set."""
    nh, d = m["num_attention_heads"], m["head_dim"]
    return float(sum(2.0 * nh * d * kernels_visible(int(n), m).sum()
                     + 4.0 * nh * d * keys_attended(int(n), m).sum()
                     for n in lengths))


def sparse_bytes(lengths, m: dict) -> float:
    """q read and the context written once, K and V read once, the
    compressed keys (float32) written and read once."""
    nh, G, d = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    sp = sparse_sizes(m)
    n = float(np.sum(lengths))
    kernels = sum(max((int(x) - sp["kernel_size"]) // sp["kernel_stride"] + 1,
                      0) for x in lengths if x > sp["dense_len"])
    return (2.0 * n * nh * d * ACT_BYTES + 2.0 * n * G * d * ACT_BYTES
            + 2.0 * kernels * G * d * 4.0)


def lightning_flops(lengths, m: dict) -> float:
    """One linear layer's recurrence (not its projections): per token and
    head d x d multiply-adds into the state and d x d out of it."""
    nh, d = m["lightning_nh"], m["lightning_head_dim"]
    return 4.0 * nh * d * d * float(np.sum(lengths))


def lightning_bytes(lengths, m: dict) -> float:
    """q, k, v read and the output written once."""
    return (4.0 * float(np.sum(lengths)) * m["lightning_nh"]
            * m["lightning_head_dim"] * ACT_BYTES)


def mixer_params(m: dict, kind: str) -> float:
    """Matmul parameters of one mixer: q, k, v, gate, o."""
    H = m["hidden_size"]
    if kind == SPARSE:
        wide = m["num_attention_heads"] * m["head_dim"]
        kv = m["num_key_value_heads"] * m["head_dim"]
    else:
        wide = kv = m["lightning_nh"] * m["lightning_head_dim"]
    return float(3 * H * wide + 2 * H * kv)


def forward_flops(lengths, m: dict) -> float:
    """The whole stack over passages of the given REAL lengths (the
    embedding gather and the pooling are not matmuls)."""
    tokens = float(np.sum(lengths))
    total = 0.0
    for kind in m["mixer_types"]:
        total += tokens * (2.0 * mixer_params(m, kind)
                           + 6.0 * m["hidden_size"] * m["intermediate_size"])
        total += (sparse_flops(lengths, m) if kind == SPARSE
                  else lightning_flops(lengths, m))
    return total
