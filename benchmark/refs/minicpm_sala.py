"""Plain float32 reference for MiniCPM-SALA run as a passage encoder.

Follows the published configuration (`openbmb/MiniCPM-SALA` `config.json`)
and, where it is silent, the families it names: MiniCPM's muP residual form,
Lightning Attention's decayed linear recurrence for `lightning-attn` layers,
MiniCPM4's InfLLM-V2 block selection for `minicpm4` layers (the
configuration's `assumed` lists each size and law taken from them):

    x0 = scale_emb * E[ids];   a = scale_depth / sqrt(depth_layers)
    h = x + a * Mixer(RMSNorm(x));   y = h + a * SwiGLU(RMSNorm(h));   RMSNorm
    lightning-attn:  q, k, v = W x; per-head RMSNorm(q), RMSNorm(k); RoPE;
        S_t = lam_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t / sqrt(d)
        out = W_o (RMSNorm(o) * sigmoid(W_g x))
    minicpm4:  q (heads), k, v (kv heads) = W x; per-head RMSNorm(q), (k);
        no rotation; per token and kv group a set of key blocks (below);
        softmax attention over the causal keys in the set
        out = W_o (ctx * sigmoid(W_g x))

Straightforward `jax.numpy`, float32 under matmul precision "highest". The
recurrence runs token by token (one `lax.scan` step a position), selection
and attention per query over explicit [queries, keys] masks, a block of
queries at a time so that 32,768 tokens fit; no kernel, no cache, no
chunking of the recurrence, no packing: ONE passage a call, whatever
`rows_per_call` says. The forward walks the stack layer by layer, one
layer's float32 weights on the device at a time.

Block selection for a passage of more than `dense_len` tokens, query t:
kernel j = mean(k[stride*j : stride*j + kernel_size]), visible when its
last token is at or before t; r[t, j] = sum over the group's heads of
softmax over the visible kernels; block b's score = max of r over the
kernels that overlap tokens [block*b, block*b + block); the set = the
first `init_blocks` blocks, the blocks that hold any of the last
`window_size` tokens, and the best others by score (stable order: ties to
the lower block), `topk` blocks in all.

Departures, each noted: the encoder head (the model publishes none: final
norm, mean over the passage's tokens); the output head is not instantiated;
a passage is padded at its END to a multiple of 1,024 tokens (every mixer is
causal, so no real token sees the padding: fewer shapes to compile); the
hash tokenizer (refs/xlmr.py re-implements it; imported from there); weights
drawn from `weights_seed` where the model block has one.

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

ARCH = "minicpm_sala"
HF_KEYS = ["attention_bias", "attn_use_rope", "head_dim", "hidden_act",
           "hidden_size", "intermediate_size", "lightning_head_dim",
           "lightning_nh", "lightning_nkv", "lightning_scale",
           "lightning_use_rope", "max_position_embeddings", "model_type",
           "mixer_types", "num_attention_heads", "num_hidden_layers",
           "num_key_value_heads", "qk_norm", "rand_init", "rms_norm_eps",
           "vocab_size", "rope_theta", "scale_emb", "scale_depth",
           "mup_denominator", "dim_model_base", "tie_word_embeddings",
           "use_output_gate", "use_output_norm", "attn_use_output_gate",
           "sparse_config", "depth_layers"]
GAP = 1e-3  # a last-taken / first-left score gap under this is "near"
SPARSE, LINEAR = "minicpm4", "lightning-attn"
PAD_TO = 1024
QUERIES = 64  # queries handled at a time in a sparse layer


def layer_specs(m: dict, i: int) -> list:
    """Tensor names: the HF MiniCPM layout, torch Linear [out, in]; the
    mixers' gates and norms as `o_gate`, `q_norm`, `k_norm`, `o_norm`
    (assumed: no checkpoint is in the repository to read them from)."""
    H, I = m["hidden_size"], m["intermediate_size"]
    p = f"model.layers.{i}"
    if m["mixer_types"][i] == SPARSE:
        d = m["head_dim"]
        wide, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
        extra = []
    else:
        d = m["lightning_head_dim"]
        wide = kv = m["lightning_nh"] * d
        extra = [(f"{p}.self_attn.o_norm.weight", (wide,), "ln_scale")]
    return [
        (f"{p}.input_layernorm.weight", (H,), "ln_scale"),
        (f"{p}.post_attention_layernorm.weight", (H,), "ln_scale"),
        (f"{p}.self_attn.q_proj.weight", (wide, H), "w"),
        (f"{p}.self_attn.k_proj.weight", (kv, H), "w"),
        (f"{p}.self_attn.v_proj.weight", (kv, H), "w"),
        (f"{p}.self_attn.o_gate.weight", (wide, H), "w"),
        (f"{p}.self_attn.o_proj.weight", (H, wide), "w"),
        (f"{p}.self_attn.q_norm.weight", (d,), "ln_scale"),
        (f"{p}.self_attn.k_norm.weight", (d,), "ln_scale"),
        *extra,
        (f"{p}.mlp.gate_proj.weight", (I, H), "w"),
        (f"{p}.mlp.up_proj.weight", (I, H), "w"),
        (f"{p}.mlp.down_proj.weight", (H, I), "w"),
    ]


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
    """`config.json` + `model.safetensors` (bfloat16) in the hub layout the
    program's `model_dir` loader reads; with a `weights_seed` the weights
    are written once per checkout and hard-linked (refs/kimi_mla_moe.py
    says why). No `tokenizer.json`: the program falls back to its hash
    tokenizer."""
    out_dir = Path(out_dir)
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

def sparse_sizes(m: dict) -> dict:
    return {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
            "init_blocks": 1, "window_size": 2048, "topk": 64,
            "dense_len": 8192, **m.get("sparse_config", {})}


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_halves(x, theta: float):
    """x [n, heads, d], position = row; pairs dimension i with i + d/2 (HF
    `rotate_half`), angle position * theta^(-2i/d)."""
    import jax.numpy as jnp

    n, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def lightning(w: dict, x, m: dict):
    """x [n, H] normed -> [n, H]: the recurrence, token by token."""
    import jax
    import jax.numpy as jnp

    nh, d, eps = m["lightning_nh"], m["lightning_head_dim"], m["rms_norm_eps"]
    n = x.shape[0]
    q = rms_norm((x @ w["q_proj"].T).reshape(n, nh, d), w["q_norm"], eps)
    k = rms_norm((x @ w["k_proj"].T).reshape(n, nh, d), w["k_norm"], eps)
    v = (x @ w["v_proj"].T).reshape(n, nh, d)
    q = rope_halves(q, m["rope_theta"]) / np.sqrt(d)
    k = rope_halves(k, m["rope_theta"])
    lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, nh + 1) / nh)))

    def step(state, qkv):
        qt, kt, vt = qkv  # [nh, d]
        state = lam[:, None, None] * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hde->he", qt, state)

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32), (q, k, v))
    o = rms_norm(o.reshape(n, nh * d), w["o_norm"], eps)
    return (o * jax.nn.sigmoid(x @ w["o_gate"].T)) @ w["o_proj"].T


def sparse_sets(q, k, n_real, t, sp: dict):
    """The key blocks of queries `t` [Q] (their q [Q, G, hg, d]) over a
    passage's keys k [n, G, d] of which `n_real` are tokens -> (chosen
    [G, Q, nBlk] bool, gap [G, Q]: score of the last block taken less the
    first left out, inf where none was)."""
    import jax
    import jax.numpy as jnp

    n, G, d = k.shape
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    nK, nBlk = max((n - ks) // st + 1, 1), -(-n // bs)
    starts = jnp.arange(nK) * st
    kern = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(k, s, ks).mean(0))(starts)
    seen = (starts[None, :] + ks - 1 <= t[:, None])[None, None]  # [1,1,Q,nK]
    s = jnp.einsum("qghd,kgd->ghqk", q, kern) / np.sqrt(d)
    p = jnp.where(seen, jnp.exp(s - jnp.where(seen, s, -jnp.inf).max(
        -1, keepdims=True, initial=-1e30)), 0.0)
    r = (p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)).sum(1)  # [G,Q,nK]
    b0 = jnp.arange(nBlk) * bs
    overlap = ((starts[None, :] + ks > b0[:, None])
               & (starts[None, :] < b0[:, None] + bs))  # [nBlk, nK]
    score = jnp.where(overlap[None, None], r[:, :, None, :], -jnp.inf).max(-1)
    score = jnp.maximum(score, 0.0)  # a block no kernel overlaps scores 0
    causal = b0[None, :] <= t[:, None]  # [Q, nBlk]
    forced = causal & ((b0[None, :] < sp["init_blocks"] * bs)
                       | (b0[None, :] + bs - 1
                          >= t[:, None] - (sp["window_size"] - 1)))
    score = jnp.where(causal[None], jnp.where(forced[None], jnp.inf, score),
                      -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    ranked = jnp.take_along_axis(score, order, axis=-1)
    topk = min(sp["topk"], nBlk)
    rank = jnp.argsort(order, axis=-1, stable=True)  # each block's place
    chosen = (rank < topk) & (score > -jnp.inf)
    if topk < nBlk:
        gap = jnp.where(ranked[..., topk] > -jnp.inf,
                        ranked[..., topk - 1] - ranked[..., topk], jnp.inf)
    else:
        gap = jnp.full(score.shape[:2], jnp.inf)
    dense = n_real <= sp["dense_len"]
    return (jnp.where(dense, causal[None], chosen),
            jnp.where(dense, jnp.inf, gap))


def sparse(w: dict, x, n_real, m: dict, with_sets: bool = False):
    """x [n, H] normed -> ([n, H], gap [n, G]); `with_sets` adds the sets
    [n, G, nBlk] (tests)."""
    import jax
    import jax.numpy as jnp

    nh, G, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, sp = m["rms_norm_eps"], sparse_sizes(m)
    n, bs = x.shape[0], sp["block_size"]
    q = rms_norm((x @ w["q_proj"].T).reshape(n, G, nh // G, d),
                 w["q_norm"], eps)
    k = rms_norm((x @ w["k_proj"].T).reshape(n, G, d), w["k_norm"], eps)
    v = (x @ w["v_proj"].T).reshape(n, G, d)
    Q = min(QUERIES, n)
    nQ = -(-n // Q)
    q = jnp.pad(q, ((0, nQ * Q - n), (0, 0), (0, 0), (0, 0)))
    key_block = jnp.arange(n) // bs

    def some(xs):
        qb, t = xs  # [Q, G, hg, d], [Q]
        chosen, gap = sparse_sets(qb, k, n_real, t, sp)
        keep = chosen[:, :, key_block] & (jnp.arange(n)[None, :]
                                          <= t[:, None])[None]  # [G, Q, n]
        s = jnp.einsum("qghd,kgd->ghqk", qb, k) / np.sqrt(d)
        s = jnp.where(keep[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("ghqk,kgd->qghd", p, v)
        return ctx, gap.T, jnp.moveaxis(chosen, 1, 0)

    ctx, gap, chosen = jax.lax.map(
        some, (q.reshape(nQ, Q, G, nh // G, d),
               jnp.arange(nQ * Q).reshape(nQ, Q)))
    ctx = ctx.reshape(nQ * Q, nh * d)[:n]
    out = (ctx * jax.nn.sigmoid(x @ w["o_gate"].T)) @ w["o_proj"].T
    gap = gap.reshape(nQ * Q, G)[:n]
    if with_sets:
        return out, gap, chosen.reshape(nQ * Q, G, -1)[:n]
    return out, gap


def block(w: dict, x, n_real, kind: str, m: dict):
    """One decoder block on a passage x [n, H] -> (x', gap or None)."""
    import jax

    eps = m["rms_norm_eps"]
    a = m["scale_depth"] / np.sqrt(m.get("depth_layers",
                                         m["num_hidden_layers"]))
    xn = rms_norm(x, w["input_layernorm"], eps)
    gap = None
    if kind == SPARSE:
        y, gap = sparse(w, xn, n_real, m)
    else:
        y = lightning(w, xn, m)
    h = x + a * y

    def ffn(rows):  # row by row the same: blocks of rows, so that it fits
        hn = rms_norm(rows, w["post_attention_layernorm"], eps)
        return (jax.nn.silu(hn @ w["gate_proj"].T) * (hn @ w["up_proj"].T)
                ) @ w["down_proj"].T

    n, H = h.shape
    y = (jax.lax.map(ffn, h.reshape(-1, PAD_TO, H)).reshape(n, H)
         if n % PAD_TO == 0 else ffn(h))
    return h + a * y, gap


def layer_weights(tensors: dict, m: dict, i: int) -> dict:
    """Layer i's float32 weights under short names."""
    p = f"model.layers.{i}."
    out = {}
    for name, _, _ in layer_specs(m, i):
        short = name[len(p):].removesuffix(".weight")
        short = short.removeprefix("self_attn.").removeprefix("mlp.")
        out[short] = np.asarray(tensors[name], np.float32)
    return out


class Reference:
    """`embed(texts)` -> [n, H] float32 mean-pooled passage vectors."""

    def __init__(self, model: dict, seed: int, max_len: int):
        self.m = model
        self.max_len = max_len
        self.tensors = common.seeded_tensors(tensor_specs(model),
                                             weights_seed(model, seed))
        self.gap_share = None  # (token, group, layer) with a gap under GAP

    def forward(self, passages: list) -> list:
        """`passages` = [ids] -> one pooled row each; layer by layer over
        all of them, one layer's weights on the device at a time."""
        import jax
        import jax.numpy as jnp

        m, t = self.m, self.tensors
        fns = {kind: jax.jit(lambda w, x, n, kind=kind: block(w, x, n, kind,
                                                              m))
               for kind in (SPARSE, LINEAR)}
        near = total = 0
        with jax.default_matmul_precision("highest"):
            wte = jax.device_put(np.asarray(t["model.embed_tokens.weight"],
                                            np.float32))
            lens = [len(ids) for ids in passages]
            xs = []
            for ids in passages:
                padded = np.zeros(-(-len(ids) // PAD_TO) * PAD_TO
                                  if len(ids) > PAD_TO else len(ids), np.int32)
                padded[:len(ids)] = ids
                xs.append(np.asarray(wte[jnp.asarray(padded)]
                                     * m["scale_emb"]))
            del wte
            for i, kind in enumerate(m["mixer_types"]):
                w = jax.device_put(layer_weights(t, m, i))
                for b, n in enumerate(lens):
                    # hidden states wait on the host: two dozen passages of
                    # float32 would fill the chip beside a layer's weights
                    x, gap = fns[kind](w, xs[b], n)
                    xs[b] = np.asarray(x)
                    if gap is not None:
                        gap = np.asarray(gap)[:n]
                        near += int((gap < GAP).sum())
                        total += gap.size
                del w
            scale = jnp.asarray(np.asarray(t["model.norm.weight"], np.float32))
            out = [np.asarray(rms_norm(jnp.asarray(x[:n]), scale,
                                       m["rms_norm_eps"]).mean(0))
                   for x, n in zip(xs, lens)]
        self.gap_share = near / total if total else 0.0
        return out

    def embed(self, texts: list, rows_per_call: int = 1) -> np.ndarray:
        del rows_per_call  # one passage a call: nothing is batched here
        enc = [tokenize(t, self.m["vocab_size"], self.max_len) for t in texts]
        out = np.stack(self.forward(enc)) if enc else np.zeros(
            (0, self.m["hidden_size"]), np.float32)
        print(f"reference {ARCH}: _selection_gap_under_{GAP:g}_share = "
              f"{self.gap_share:.6g} (tokens x kv groups x sparse layers "
              "whose last block taken and first left out score that close)",
              file=sys.stderr, flush=True)
        return out.astype(np.float32)
