"""Plain float32 reference for Kimi-VL-A3B's language tower (DeepSeek-V3
layout) run as a sentence encoder.

Follows the published code (HF `modeling_deepseek.py` as shipped with
moonshotai/Kimi-VL-A3B-Instruct: `DeepseekV3Attention`, `MoEGate` with
`noaux_tc`, `DeepseekV3MoE`, `DeepseekV3MLP`, `DeepseekV3RMSNorm`):

    block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm
    MLA:    q = W_q x -> heads x (nope | rope);  [c | k_rope] = W_kva x;
            [k_nope | v] = W_kvb RMSNorm(c);  RoPE on q_rope, k_rope;
            softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope)) v;  W_o
    router: s = sigmoid(W_g x) (float32); top-k of s + bias; weights
            s / sum(chosen s) * routed_scaling_factor
    FFN:    sum_chosen w_e SwiGLU_e(x) + SwiGLU_shared(x)   (layers >= first_k_dense)
            SwiGLU_dense(x)                                   (leading layers)

Straightforward `jax.numpy`, float32 under matmul precision "highest", no
kernels, no sorting, no cache. Every expert is computed as a masked sum:
expert e's SwiGLU over the block's tokens times (e's weight where the token
chose e, else 0), experts scanned one after another. The forward walks the
stack LAYER BY LAYER over all the rows compared, one layer's float32 weights
on the device at a time, so the 2.76 B float32 parameters of the benchmark's
cut never sit there together.

RoPE pairing: dimensions (2i, 2i+1) of the rope part rotate together by
angle pos * theta^(-2i/d). The HF code first permutes [x0, x1, x2, ...] ->
[x0, x2, ..., x1, x3, ...] and then applies `rotate_half`; a dot product
does not care about a permutation applied to both sides, so scores are the
same. Written here as the pairwise rotation itself.

Departures, each noted:
- the encoder head: the model publishes none. Hidden states after the final
  norm are mean-pooled over attended positions (causal attention as
  published): the configuration's `assumed`.
- positions count attended tokens from 0 (rows are right-padded, so a token's
  position is its index).
- the output head, the vision tower and its projector are not instantiated.
- tokenization is the configuration's `assumed` hash tokenizer (refs/xlmr.py
  re-implements it from its definition; imported from there).
- weights are drawn from the model block's `weights_seed` when it has one
  (the configuration's `assumed.weights`), else from the run's seed.

Imports nothing of the program.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np

from refs import common
from refs.xlmr import token_count, tokenize  # noqa: F401  (the hash tokenizer)

ARCH = "kimi_mla_moe"
HF_KEYS = ["vocab_size", "max_position_embeddings", "hidden_size",
           "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
           "num_attention_heads", "n_shared_experts", "n_routed_experts",
           "ep_size", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
           "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
           "topk_method", "n_group", "topk_group", "num_experts_per_tok",
           "moe_layer_freq", "first_k_dense_replace", "norm_topk_prob",
           "scoring_func", "seq_aux", "num_key_value_heads", "hidden_act",
           "rms_norm_eps", "rope_theta", "rope_scaling", "attention_bias",
           "tie_word_embeddings", "model_type"]
GAP = 1e-3  # a 6th-7th score gap under this is "within rounding's reach"


def _mlp_specs(prefix: str, H: int, width: int) -> list:
    # torch Linear layout: [out, in]
    return [(f"{prefix}.gate_proj.weight", (width, H), "w"),
            (f"{prefix}.up_proj.weight", (width, H), "w"),
            (f"{prefix}.down_proj.weight", (H, width), "w")]


def layer_specs(m: dict, i: int) -> list:
    H, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    p = f"model.layers.{i}"
    specs = [
        (f"{p}.input_layernorm.weight", (H,), "ln_scale"),
        (f"{p}.post_attention_layernorm.weight", (H,), "ln_scale"),
        (f"{p}.self_attn.q_proj.weight", (nh * (dn + dr), H), "w"),
        (f"{p}.self_attn.kv_a_proj_with_mqa.weight", (r + dr, H), "w"),
        (f"{p}.self_attn.kv_a_layernorm.weight", (r,), "ln_scale"),
        (f"{p}.self_attn.kv_b_proj.weight", (nh * (dn + dv), r), "w"),
        (f"{p}.self_attn.o_proj.weight", (H, nh * dv), "w"),
    ]
    if i < m["first_k_dense_replace"]:
        return specs + _mlp_specs(f"{p}.mlp", H, m["intermediate_size"])
    E, I = m["n_routed_experts"], m["moe_intermediate_size"]
    specs += [(f"{p}.mlp.gate.weight", (E, H), "w"),
              (f"{p}.mlp.gate.e_score_correction_bias", (E,), "b")]
    for e in range(E):
        specs += _mlp_specs(f"{p}.mlp.experts.{e}", H, I)
    if m.get("n_shared_experts"):
        specs += _mlp_specs(f"{p}.mlp.shared_experts", H,
                            I * m["n_shared_experts"])
    return specs


def tensor_specs(m: dict) -> list:
    specs = [("model.embed_tokens.weight",
              (m["vocab_size"], m["hidden_size"]), "w"),
             ("model.norm.weight", (m["hidden_size"],), "ln_scale")]
    for i in range(m["num_hidden_layers"]):
        specs += layer_specs(m, i)
    return specs


def weights_seed(model: dict, seed: int) -> int:
    return int(model.get("weights_seed", seed))


def write_checkpoint(model: dict, seed: int, out_dir: Path) -> None:
    """`config.json` + `model.safetensors` (HF DeepSeek-V3 names, bfloat16)
    in the hub layout the program's `model_dir` loader reads. Where the
    model block carries a `weights_seed`, the 5.5 GB of weights are written
    once per checkout under the benchmark's cache and hard-linked into
    `out_dir` (which `ensure_checkpoint` clears for every new `--seed`):
    PERF.md, section 4. No `tokenizer.json`: the program falls back to its
    hash tokenizer."""
    out_dir = Path(out_dir)
    program = Path(__file__).resolve().parents[2] / "symbiont_tpu" / "models"
    if not (program / "mla_moe.py").is_file():
        # a checkout from before the family cannot load this checkpoint:
        # say so now, not after 5.5 GB of weights are drawn and written
        raise SystemExit(f"{ARCH}: this checkout's program has no "
                         "models/mla_moe.py; the configuration cannot run")
    shape = {k: model[k] for k in HF_KEYS if k in model}
    common.write_hf_config(shape, out_dir)
    wseed = weights_seed(model, seed)
    if "weights_seed" not in model:
        common.write_safetensors(
            common.seeded_tensors(tensor_specs(model), wseed), out_dir)
        return
    store = out_dir.parent / f"weights-{wseed}"
    marker = store / "benchmark_weights.json"
    if not (marker.is_file() and json.loads(marker.read_text()) == shape):
        shutil.rmtree(store, ignore_errors=True)
        common.write_safetensors(
            common.seeded_tensors(tensor_specs(model), wseed), store)
        marker.write_text(json.dumps(shape))
    link = out_dir / "model.safetensors"
    link.unlink(missing_ok=True)
    link.hardlink_to(store / "model.safetensors")


# ------------------------------------------------------------- the maths

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_pairs(x, positions, theta: float):
    """x [..., S, d]; rotates (x[2i], x[2i+1]) by positions * theta^(-2i/d);
    returns them laid out [rotated evens..., rotated odds...] (the HF
    layout), the same for queries and keys."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv  # [S, d/2]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def mla(w: dict, x, mask, m: dict):
    """x [B, S, H] normed, mask [B, S] -> [B, S, H]."""
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    nh, dn, dr, dv, r = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    pos = jnp.arange(S)
    q = (x @ w["q_proj"].T).reshape(B, S, nh, dn + dr).transpose(0, 2, 1, 3)
    kva = x @ w["kv_a_proj_with_mqa"].T
    c, k_rope = kva[..., :r], kva[..., r:]
    kv = (rms_norm(c, w["kv_a_layernorm"], m["rms_norm_eps"])
          @ w["kv_b_proj"].T).reshape(B, S, nh, dn + dv).transpose(0, 2, 1, 3)
    q_rope = rope_pairs(q[..., dn:], pos, m["rope_theta"])  # [B, nh, S, dr]
    k_rope = rope_pairs(k_rope, pos, m["rope_theta"])  # [B, S, dr]
    scores = (q[..., :dn] @ kv[..., :dn].transpose(0, 1, 3, 2)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope)
              ) / np.sqrt(dn + dr)
    keep = (jnp.tril(jnp.ones((S, S), bool))[None, None]
            & (mask[:, None, None, :] > 0))
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e9), axis=-1)
    ctx = (probs @ kv[..., dn:]).transpose(0, 2, 1, 3).reshape(B, S, nh * dv)
    return ctx @ w["o_proj"].T


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def router(w: dict, x, m: dict):
    """x [T, H] -> (idx [T, k], weights [T, k], gap [T] = 6th - 7th of the
    scores the choice is made on)."""
    import jax
    import jax.numpy as jnp

    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["gate"].T)
    top, idx = jax.lax.top_k(s + w["gate.e_score_correction_bias"], k + 1)
    chosen = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    if m.get("norm_topk_prob", True) and k > 1:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return (idx[:, :k], chosen * m["routed_scaling_factor"],
            top[:, k - 1] - top[:, k])


def moe(w: dict, x, m: dict):
    """x [T, H] normed -> (y [T, H], gap [T]). Experts one after another,
    each over every token, weighted by the token's weight for it (0 where
    the token did not choose it): a masked sum."""
    import jax
    import jax.numpy as jnp

    idx, weights, gap = router(w, x, m)
    E = m["n_routed_experts"]
    dense_w = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].set(weights)

    def one(acc, ew):
        gate, up, down, col = ew
        return acc + swiglu(x, gate, up, down) * col[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["experts.gate_proj"], w["experts.up_proj"],
                         w["experts.down_proj"], dense_w.T))
    if "shared_experts.gate_proj" in w:
        y = y + swiglu(x, w["shared_experts.gate_proj"],
                       w["shared_experts.up_proj"],
                       w["shared_experts.down_proj"])
    return y, gap


def block(w: dict, x, mask, m: dict):
    """One decoder block on x [B, S, H] -> (x', gap [B, S] or None)."""
    eps = m["rms_norm_eps"]
    h = x + mla(w, rms_norm(x, w["input_layernorm"], eps), mask, m)
    hn = rms_norm(h, w["post_attention_layernorm"], eps)
    if "gate" in w:
        B, S, H = hn.shape
        y, gap = moe(w, hn.reshape(B * S, H), m)
        return h + y.reshape(B, S, H), gap.reshape(B, S)
    return h + swiglu(hn, w["gate_proj"], w["up_proj"], w["down_proj"]), None


def layer_weights(tensors: dict, m: dict, i: int) -> dict:
    """Layer i's float32 weights under short names, its experts stacked."""
    p = f"model.layers.{i}."
    out = {}
    for name, _, _ in layer_specs(m, i):
        short = name[len(p):].removesuffix(".weight")
        short = short.removeprefix("self_attn.").removeprefix("mlp.")
        if not short.startswith("experts."):
            out[short] = np.asarray(tensors[name], np.float32)
    if i >= m["first_k_dense_replace"]:
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[f"experts.{proj}"] = np.stack([
                np.asarray(tensors[f"{p}mlp.experts.{e}.{proj}.weight"],
                           np.float32)
                for e in range(m["n_routed_experts"])])
    return out


class Reference:
    """`embed(texts)` -> [n, H] float32 mean-pooled sentence vectors."""

    def __init__(self, model: dict, seed: int, max_len: int):
        self.m = model
        self.max_len = max_len
        self.tensors = common.seeded_tensors(tensor_specs(model),
                                             weights_seed(model, seed))
        self.gap_share = None  # tokens whose 6th-7th gap is under GAP

    def forward(self, batches: list) -> list:
        """`batches` = [(ids, mask)] -> pooled rows per batch; layer by
        layer over all batches, one layer's weights on the device at a
        time."""
        import jax
        import jax.numpy as jnp

        m, t = self.m, self.tensors
        block_fn = jax.jit(lambda w, x, mask: block(w, x, mask, m))
        near = total = 0
        with jax.default_matmul_precision("highest"):
            wte = jax.device_put(np.asarray(t["model.embed_tokens.weight"],
                                            np.float32))
            masks = [jnp.asarray(mask) for _, mask in batches]
            xs = [wte[jnp.asarray(ids)] for ids, _ in batches]
            del wte
            for i in range(m["num_hidden_layers"]):
                w = jax.device_put(layer_weights(t, m, i))
                for b, mask in enumerate(masks):
                    xs[b], gap = block_fn(w, xs[b], mask)
                    if gap is not None:
                        real = np.asarray(mask) > 0
                        near += int((np.asarray(gap)[real] < GAP).sum())
                        total += int(real.sum())
                del w
            scale = jnp.asarray(np.asarray(t["model.norm.weight"], np.float32))
            out = []
            for x, mask in zip(xs, masks):
                x = rms_norm(x, scale, m["rms_norm_eps"])
                maskf = mask.astype(jnp.float32)
                out.append(np.asarray((x * maskf[..., None]).sum(1)
                                      / maskf.sum(1, keepdims=True)))
        self.gap_share = near / total if total else 0.0
        return out

    def embed(self, texts: list, rows_per_call: int = 32) -> np.ndarray:
        enc = [tokenize(t, self.m["vocab_size"], self.max_len) for t in texts]
        out = np.zeros((len(texts), self.m["hidden_size"]), np.float32)
        # blocks of rows of like length, padded to a multiple of 32 tokens:
        # few shapes, and padding is masked, so the padded length does not
        # change a row
        order = sorted(range(len(enc)), key=lambda i: len(enc[i]))
        groups, batches = [], []
        for a in range(0, len(order), rows_per_call):
            rows = order[a:a + rows_per_call]
            S = -(-max(len(enc[i]) for i in rows) // 32) * 32
            ids = np.zeros((rows_per_call, S), np.int32)
            mask = np.zeros((rows_per_call, S), np.int32)
            mask[len(rows):, 0] = 1  # filler rows: one token, discarded
            for r, i in enumerate(rows):
                ids[r, :len(enc[i])] = enc[i]
                mask[r, :len(enc[i])] = 1
            groups.append(rows)
            batches.append((ids, mask))
        for rows, got in zip(groups, self.forward(batches)):
            out[rows] = got[:len(rows)]
        print(f"reference {ARCH}: _router_gap_under_{GAP:g}_share = "
              f"{self.gap_share:.6g} (tokens x expert layers whose 6th and "
              "7th scores lie that close)", file=sys.stderr, flush=True)
        return out
