"""Operation and byte counts of Ling-3.0-flash's stack (KDA layers beside
MLA, routed experts with a held share) run as an encoder, from shapes
alone. Like `yardstick.py`, keyed by what the work IS (passages and their
real lengths, the (token, held expert) pairs computed), never by which
executable did it, and imports nothing of the program. `m` is the
configuration's `model` block (HF keys + `experts_held`).

Matmul FLOPs only (2 per multiply-add); norms, the short convolution, the
gates' sigmoids and exponentials, softmax, RoPE and the sort of the
assignments are not counted, so a share of a peak built on these never
flatters the program. KDA's recurrence counts the token-by-token form (per
token and head d_k x d_v multiply-adds to decay-and-correct the state, to
write it and to read it: 6 d_k d_v FLOPs), not the larger count of a chunked
form; MLA's attention counts each passage's own causal keys, never the
padding or another passage's keys; the routed experts count the pairs the
program computed (`engine.moe.assignments`), never a choice of an expert
another chip holds.
"""

from __future__ import annotations

import numpy as np

ACT_BYTES = 2.0  # bfloat16 activations
GATE_BYTES = 4.0  # the log-decays are float32


def is_mla(m: dict, i: int) -> bool:
    return (i + 1) % m["layer_group_size"] == 0


def layer_kinds(m: dict) -> tuple:
    """(KDA layers, MLA layers, dense FFN layers, expert layers)."""
    n = m["num_hidden_layers"]
    mla = sum(is_mla(m, i) for i in range(n))
    dense = min(n, m["first_k_dense_replace"])
    return n - mla, mla, dense, n - dense


def held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def kda_params(m: dict) -> float:
    """Matmul parameters of one KDA mixer: q, k, v, the decay gate, the
    output gate, o (each H x heads*d) and beta (H x heads)."""
    H, nh = m["hidden_size"], m["num_attention_heads"]
    wide = nh * m["head_dim"]
    return float(6 * H * wide + H * nh)


def kda_rule_flops(lengths, m: dict) -> float:
    """One KDA layer's recurrence over passages of the given REAL lengths:
    6 d_k d_v a token and head."""
    d = m["head_dim"]
    return 6.0 * d * d * m["num_attention_heads"] * float(np.sum(lengths))


def kda_rule_bytes(lengths, m: dict) -> float:
    """q, k, v read and the output written once at bfloat16, the float32
    log-decays read once: the state stays on the chip."""
    per_head = 4.0 * m["head_dim"] * ACT_BYTES + m["head_dim"] * GATE_BYTES
    return per_head * m["num_attention_heads"] * float(np.sum(lengths))


def mla_params(m: dict) -> float:
    """Matmul parameters of one MLA mixer: q, kv_a, kv_b, o and the head
    gate."""
    H, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    return float(H * nh * (dn + dr) + H * (r + dr) + r * nh * (dn + dv)
                 + nh * dv * H + H * nh)


def mla_attn_flops(lengths, m: dict) -> float:
    """One MLA layer's attention over passages of the given REAL lengths:
    a token at position p scores p + 1 keys over nope + rope and sums p + 1
    values, inside its own passage."""
    n = np.asarray(lengths, np.float64)
    per_key = 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return float((per_key * n * (n + 1) / 2).sum())


def mla_flops(lengths, m: dict) -> float:
    """One MLA layer: projections per real token and the causal attention."""
    return (2.0 * mla_params(m) * float(np.sum(lengths))
            + mla_attn_flops(lengths, m))


def expert_params(m: dict) -> float:
    """One routed expert's three kernels."""
    return 3.0 * m["hidden_size"] * m["moe_intermediate_size"]


def routed_flops(assignments: float, m: dict) -> float:
    """`assignments` = (real token, held expert) pairs computed."""
    return 2.0 * expert_params(m) * float(assignments)


def ffn_flops_per_token(m: dict, layer: int) -> float:
    """The feed-forward of `layer` for one real token, the routed experts
    left out (`routed_flops` counts them from the pairs computed): the
    dense SwiGLU in the leading layers; after them the router over every
    expert and the shared expert."""
    H = m["hidden_size"]
    if layer < m["first_k_dense_replace"]:
        return 6.0 * H * m["intermediate_size"]
    shared = (6.0 * H * m["moe_shared_expert_intermediate_size"]
              * (m.get("num_shared_experts") or 0))
    return 2.0 * H * m["num_experts"] + shared


def forward_flops(lengths, m: dict) -> float:
    """The whole stack over passages of the given REAL lengths but the
    routed experts (the embedding gather and the pooling are not
    matmuls)."""
    tokens = float(np.sum(lengths))
    total = 0.0
    for i in range(m["num_hidden_layers"]):
        if is_mla(m, i):
            total += mla_flops(lengths, m)
        else:
            total += (2.0 * kda_params(m) * tokens
                      + kda_rule_flops(lengths, m))
        total += tokens * ffn_flops_per_token(m, i)
    return total
