"""Operation and byte counts of an MLA + routed/shared-expert decoder stack
(DeepSeek-V3 layout) run as an encoder, from shapes alone. Like
`yardstick.py`, keyed by what the work IS (real tokens, sentence lengths,
experts that got a token), never by which executable did it, and imports
nothing of the program. `m` is the configuration's `model` block (HF keys).

Matmul FLOPs only (2 per multiply-add); norms, softmax, RoPE and the sort of
the assignments are not counted, so a share of a peak built on these never
flatters the program.
"""

from __future__ import annotations

import numpy as np


def mla_params(m: dict) -> float:
    """Matmul parameters of one MLA block: q, kv_a, kv_b, o."""
    H, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    return float(H * nh * (dn + dr) + H * (r + dr) + r * nh * (dn + dv)
                 + nh * dv * H)


def mla_flops(lengths, m: dict) -> float:
    """One MLA block over sentences of the given REAL lengths: the four
    projections per token, and causal attention (a token at position p
    scores p + 1 keys over nope + rope and sums p + 1 values)."""
    n = np.asarray(lengths, np.float64)
    nh = m["num_attention_heads"]
    per_key = 2.0 * nh * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                          + m["v_head_dim"])
    return float((2.0 * mla_params(m) * n + per_key * n * (n + 1) / 2).sum())


def expert_params(m: dict) -> float:
    """One routed expert's three kernels."""
    return 3.0 * m["hidden_size"] * m["moe_intermediate_size"]


def routed_flops(assignments: float, m: dict) -> float:
    """`assignments` = (real token, chosen expert) pairs computed."""
    return 2.0 * expert_params(m) * float(assignments)


def ffn_flops_per_token(m: dict, layer: int) -> float:
    """The feed-forward of layer `layer` for one real token: the dense
    SwiGLU in the leading layers; after them the router, k routed experts
    and the shared experts."""
    H = m["hidden_size"]
    if layer < m["first_k_dense_replace"]:
        return 6.0 * H * m["intermediate_size"]
    shared = 6.0 * H * m["moe_intermediate_size"] * (m.get("n_shared_experts")
                                                     or 0)
    return (2.0 * H * m["n_routed_experts"]
            + m["num_experts_per_tok"] * 2.0 * expert_params(m) + shared)


def forward_flops(lengths, m: dict) -> float:
    """The whole stack over sentences of the given REAL lengths (the
    embedding gather and the pooling are not matmuls)."""
    tokens = float(np.sum(lengths))
    L = m["num_hidden_layers"]
    return (L * mla_flops(lengths, m)
            + tokens * sum(ffn_flops_per_token(m, i) for i in range(L)))
