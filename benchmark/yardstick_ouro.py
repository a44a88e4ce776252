"""Operation and byte counts of a looped decoder stack (Ouro's layout: one
stack of sandwich-norm blocks applied `total_ut_steps` times over the same
weights) run as an encoder, from shapes alone. Like `yardstick.py`, keyed
by what the work IS (chunks and their real lengths), never by which
executable did it, and imports nothing of the program. `m` is the
configuration's `model` block (HF keys).

Matmul FLOPs only (2 per multiply-add); the four norms of a block, RoPE,
the softmax, the exit gate (2 H a token and step) and the pooling are not
counted, so a share of a peak built on these never flatters the program.
Attention counts each chunk's own causal keys (a token at position p
scores p + 1 keys and sums p + 1 values), never the padding and never keys
outside the chunk. Bytes are what a sub-layer must move at least once: its
kernels at bfloat16, read once per block application (every step reads
every layer again: 5 GB of weights do not stay on the chip between steps),
and the float32 residual stream read and written once.
"""

from __future__ import annotations

import numpy as np

WEIGHT_BYTES = 2.0  # bfloat16 at rest (the configuration's `precision`)
STREAM_BYTES = 4.0  # the float32 residual stream


def applications(m: dict) -> int:
    """Block applications of one forward: steps x layers."""
    return int(m["total_ut_steps"]) * int(m["num_hidden_layers"])


def attn_params(m: dict) -> float:
    """q, k, v, o of one block."""
    wide = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return float(2 * m["hidden_size"] * wide + 2 * m["hidden_size"] * kv)


def ffn_params(m: dict) -> float:
    """gate, up, down of one block."""
    return 3.0 * m["hidden_size"] * m["intermediate_size"]


def attn_flops(lengths, m: dict) -> float:
    """ONE application of one block's attention sub-layer over chunks of the
    given REAL lengths: the four projections per token, and causal
    attention inside each chunk."""
    n = np.asarray(lengths, np.float64)
    per_key = 4.0 * m["num_attention_heads"] * m["head_dim"]
    return float((2.0 * attn_params(m) * n + per_key * n * (n + 1) / 2).sum())


def ffn_flops(lengths, m: dict) -> float:
    """ONE application of one block's SwiGLU over the same chunks."""
    return 2.0 * ffn_params(m) * float(np.sum(lengths))


def stream_bytes(lengths, m: dict) -> float:
    """The residual stream read once and written once by a sub-layer."""
    return 2.0 * float(np.sum(lengths)) * m["hidden_size"] * STREAM_BYTES


def attn_bytes(lengths, m: dict, dispatches: float = 1.0) -> float:
    """ONE application: the kernels once per dispatch that holds the
    chunks, the stream once."""
    return (dispatches * attn_params(m) * WEIGHT_BYTES
            + stream_bytes(lengths, m))


def ffn_bytes(lengths, m: dict, dispatches: float = 1.0) -> float:
    return (dispatches * ffn_params(m) * WEIGHT_BYTES
            + stream_bytes(lengths, m))


def forward_flops(lengths, m: dict) -> float:
    """The whole loop over chunks of the given REAL lengths (the embedding
    gather, the gate and the pooling are not matmuls worth counting)."""
    return applications(m) * (attn_flops(lengths, m) + ffn_flops(lengths, m))
