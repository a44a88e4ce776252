"""Operation and byte counts of MiMo-V2-Flash's stack (sliding-window GQA
with a sink beside full GQA, routed experts with a held share) run as an
encoder, from shapes alone. Like `yardstick.py`, keyed by what the work IS
(passages and their real lengths, the (token, held expert) pairs computed),
never by which executable did it, and imports nothing of the program. `m`
is the configuration's `model` block (HF keys + `experts_held`).

Matmul FLOPs only (2 per multiply-add); norms, RoPE, the softmax and its
sink, the router's sigmoid and the sort of the assignments are not
counted, so a share of a peak built on these never flatters the program.
Attention counts q.k at `head_dim` (192) wide, never the lanes the program
pads a head to, and the keys each real token may see: in a window layer the
keys of its window inside its passage, in a full layer its passage's
causal keys; never the padding or another passage's keys. The routed
experts count the pairs the program computed (`engine.moe.assignments`),
never a choice of an expert another chip holds.
"""

from __future__ import annotations

import numpy as np

ACT_BYTES = 2.0  # bfloat16 activations


def is_window(m: dict, i: int) -> bool:
    return bool(m["hybrid_layer_pattern"][i])


def is_moe(m: dict, i: int) -> bool:
    return bool(m["moe_layer_freq"][i])


def layer_kinds(m: dict) -> tuple:
    """(window layers, full layers, dense FFN layers, expert layers)."""
    n = m["num_hidden_layers"]
    window = sum(is_window(m, i) for i in range(n))
    moe = sum(is_moe(m, i) for i in range(n))
    return window, n - window, n - moe, moe


def held(m: dict) -> int:
    return m.get("experts_held") or m["n_routed_experts"]


def kv_heads(m: dict, window: bool) -> int:
    return m["swa_num_key_value_heads" if window else "num_key_value_heads"]


def attn_params(m: dict, window: bool) -> float:
    """Matmul parameters of one attention mixer: q, k, v and o."""
    H, nh, D, Dv = (m["hidden_size"], m["num_attention_heads"],
                    m["head_dim"], m["v_head_dim"])
    nkv = kv_heads(m, window)
    return float(H * nh * D + H * nkv * (D + Dv) + nh * Dv * H)


def window_keys(lengths, m: dict) -> float:
    """Keys the real tokens of passages of the given lengths see in ONE
    window layer: token p of its passage sees min(p + 1, sliding_window)."""
    W = m["sliding_window"]
    n = np.asarray(lengths, np.float64)
    inside = np.minimum(n, W)
    return float((inside * (inside + 1) / 2 + (n - inside) * W).sum())


def causal_keys(lengths) -> float:
    """Keys the real tokens see in ONE full layer: token p sees p + 1."""
    n = np.asarray(lengths, np.float64)
    return float((n * (n + 1) / 2).sum())


def window_keys_kept_pct(lengths, m: dict) -> float:
    """The exact value `window_keys_kept_pct` reads for these lengths."""
    return 100.0 * window_keys(lengths, m) / causal_keys(lengths)


def attn_core_flops(keys: float, m: dict) -> float:
    """q.k over head_dim and p.v over v_head_dim for `keys` (token, key)
    pairs in every query head."""
    return (2.0 * m["num_attention_heads"] * (m["head_dim"] + m["v_head_dim"])
            * keys)


def attn_core_bytes(lengths, m: dict, window: bool) -> float:
    """q, k and v read and the context written once at bfloat16, at the
    model's own widths: the least a kernel that keeps its scores on the
    chip moves."""
    nh, D, Dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    per_token = nh * D + kv_heads(m, window) * (D + Dv) + nh * Dv
    return ACT_BYTES * per_token * float(np.sum(lengths))


def window_attn_flops(lengths, m: dict) -> float:
    """One window layer's scores and context."""
    return attn_core_flops(window_keys(lengths, m), m)


def full_attn_flops(lengths, m: dict) -> float:
    """One full layer's scores and context."""
    return attn_core_flops(causal_keys(lengths), m)


def expert_params(m: dict) -> float:
    """One routed expert's three kernels."""
    return 3.0 * m["hidden_size"] * m["moe_intermediate_size"]


def routed_flops(assignments: float, m: dict) -> float:
    """`assignments` = (real token, held expert) pairs computed."""
    return 2.0 * expert_params(m) * float(assignments)


def ffn_flops_per_token(m: dict, layer: int) -> float:
    """The feed-forward of `layer` for one real token, the routed experts
    left out (`routed_flops` counts them from the pairs computed): the
    dense SwiGLU, or the router over every expert."""
    H = m["hidden_size"]
    if not is_moe(m, layer):
        return 6.0 * H * m["intermediate_size"]
    return 2.0 * H * m["n_routed_experts"]


def forward_flops(lengths, m: dict) -> float:
    """The whole stack over passages of the given REAL lengths but the
    routed experts (the embedding gather and the pooling are not
    matmuls)."""
    tokens = float(np.sum(lengths))
    total = 0.0
    for i in range(m["num_hidden_layers"]):
        window = is_window(m, i)
        total += 2.0 * attn_params(m, window) * tokens
        total += (window_attn_flops(lengths, m) if window
                  else full_attn_flops(lengths, m))
        total += tokens * ffn_flops_per_token(m, i)
    return total
