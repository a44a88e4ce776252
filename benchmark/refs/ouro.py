"""Plain float32 reference for Ouro (a LoopLM: one decoder stack applied
several times over the same weights) run as a chunk encoder.

Follows the public `config.json` and the `modeling_ouro.py` beside it, as
the configuration's `assumed` records them (neither file is on this
machine):

    h = E[ids]                                         (no scaling)
    for t in 0..total_ut_steps-1:                      (the SAME weights every t)
        for i in 0..layers-1:
            h = h + RMSNorm(Attn_i(RMSNorm(h; input_layernorm));
                            input_layernorm_2)
            h = h + RMSNorm(SwiGLU_i(RMSNorm(h; post_attention_layernorm));
                            post_attention_layernorm_2)
        h = RMSNorm(h; norm)                           (at the end of EVERY step)
        lam_t = sigmoid(w_gate . h + b_gate)           (early_exit_gate, per token)
    p_t = lam_t prod_{j<t}(1 - lam_j)  for t < T-1;   p_{T-1} = prod_{j<T-1}(1 - lam_j)
    Attn:   q, k, v, o without bias; heads of head_dim; RoPE on q and k over
            the whole head, dimension i paired with i + d/2 (`rotate_half`),
            angle pos * theta^(-2i/d); softmax(q k^T / sqrt(d)), causal
    SwiGLU: W_down(silu(W_gate x) * W_up x)

Straightforward `jax.numpy`, float32 under matmul precision "highest",
Python loops over steps and layers, one chunk a row (no packing, no scan, no
stacked weights, no cache). The forward walks the stack LAYER BY LAYER over
all the rows compared, one layer's float32 weights on the device at a time
(each layer is uploaded once per step), so the 2.57 B float32 parameters
(10.3 GB) never stand there together.

Departures, each noted:
- the encoder head: the model publishes none. The state used is that of the
  first step whose cumulative exit probability reaches
  `early_exit_threshold`; at the published 1 that is the last step for every
  token. Its normed hidden states are mean-pooled over attended positions
  (causal attention as published): the configuration's `assumed`.
- positions count attended tokens from 0 (rows are right-padded, so a
  token's position is its index).
- the output head (`lm_head`, untied) is not instantiated.
- tokenization is the configuration's `assumed` hash tokenizer (refs/xlmr.py
  re-implements it from its definition; imported from there).
- weights are drawn from the model block's `weights_seed` when it has one
  (the configuration's `assumed.weights`), else from the run's seed; the
  sandwich's SECOND norms are drawn at `POST_NORM_GAIN` x the law of the
  others (below).

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

ARCH = "ouro"
HF_KEYS = ["head_dim", "hidden_act", "hidden_size", "intermediate_size",
           "layer_types", "max_position_embeddings", "max_window_layers",
           "model_type", "num_attention_heads", "num_hidden_layers",
           "num_key_value_heads", "rms_norm_eps", "rope_scaling",
           "rope_theta", "sliding_window", "tie_word_embeddings",
           "total_ut_steps", "early_exit_threshold", "use_sliding_window",
           "vocab_size"]
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")
# The second norm of the sandwich sets the size of what a sub-layer adds to
# the stream, whatever its kernels are. A step starts from a state of unit
# RMS (the final norm closes every step); at a gain of 1 its 96 sub-layer
# outputs together have ten times that, the state is overwritten within the
# step's first layers, and the seeded loop is EXPANDING: one step multiplies
# a perturbation by ~2.6, so bfloat16 rounding stood 12 % from this
# reference after four steps (0.7 % after one; PERF.md section 6, PR 34,
# has the chip's readings at both gains). At 2^-3 ~ 1 / sqrt(2 x 48) a
# step's outputs together have the RMS of the state it starts from - each
# step rewrites the state once, as a trained stack's updates are small
# beside its stream - and rounding is no longer amplified. A power of two,
# so the scaled bfloat16 values are exact.
POST_NORM_GAIN = 0.125


def layer_specs(m: dict, i: int) -> list:
    H, I = m["hidden_size"], m["intermediate_size"]
    wide = m["num_attention_heads"] * m["head_dim"]
    p = f"model.layers.{i}"
    # torch Linear layout: [out, in]
    return ([(f"{p}.{n}.weight", (H,), "ln_scale") for n in NORMS]
            + [(f"{p}.self_attn.{n}_proj.weight", (wide, H), "w")
               for n in "qkv"]
            + [(f"{p}.self_attn.o_proj.weight", (H, wide), "w"),
               (f"{p}.mlp.gate_proj.weight", (I, H), "w"),
               (f"{p}.mlp.up_proj.weight", (I, H), "w"),
               (f"{p}.mlp.down_proj.weight", (H, I), "w")])


def tensor_specs(m: dict) -> list:
    H = m["hidden_size"]
    specs = [("model.embed_tokens.weight", (m["vocab_size"], H), "w"),
             ("model.norm.weight", (H,), "ln_scale"),
             ("model.early_exit_gate.weight", (1, H), "w"),
             ("model.early_exit_gate.bias", (1,), "b")]
    for i in range(m["num_hidden_layers"]):
        specs += layer_specs(m, i)
    return specs


def weights_seed(model: dict, seed: int) -> int:
    return int(model.get("weights_seed", seed))


def seeded(model: dict, seed: int) -> dict:
    """The model's tensors by name, bfloat16: `common.seeded_tensors`' law
    (kernels 0.02 N, norm scales 1 + 0.1 N), the sandwich's second norms
    times `POST_NORM_GAIN`."""
    tensors = common.seeded_tensors(tensor_specs(model),
                                    weights_seed(model, seed))
    for name in tensors:
        if name.endswith("_2.weight"):
            tensors[name] = (np.asarray(tensors[name], np.float32)
                             * POST_NORM_GAIN).astype(common.BF16)
    return tensors


def write_checkpoint(model: dict, seed: int, out_dir: Path) -> None:
    """`config.json` + `model.safetensors` (HF names, bfloat16) in the hub
    layout the program's `model_dir` loader reads. Where the model block
    carries a `weights_seed`, the 5.1 GB of weights are written once per
    checkout under the benchmark's cache and hard-linked into `out_dir`
    (which `ensure_checkpoint` clears for every new `--seed`), as
    refs/kimi_mla_moe.py does. No `tokenizer.json`: the program falls back
    to its hash tokenizer. No `lm_head`: the role never reads it."""
    out_dir = Path(out_dir)
    program = Path(__file__).resolve().parents[2] / "symbiont_tpu" / "models"
    if not (program / "ouro.py").is_file():
        # a checkout from before the family cannot load this checkpoint:
        # say so now, not after 5.1 GB of weights are drawn and written
        raise SystemExit(f"{ARCH}: this checkout's program has no "
                         "models/ouro.py; the configuration cannot run")
    shape = {k: model[k] for k in HF_KEYS if k in model}
    common.write_hf_config(shape, out_dir)
    if "weights_seed" not in model:
        common.write_safetensors(seeded(model, seed), out_dir)
        return
    store = out_dir.parent / f"weights-{weights_seed(model, seed)}"
    marker = store / "benchmark_weights.json"
    law = {**shape, "post_norm_gain": POST_NORM_GAIN}
    if not (marker.is_file() and json.loads(marker.read_text()) == law):
        shutil.rmtree(store, ignore_errors=True)
        common.write_safetensors(seeded(model, seed), store)
        marker.write_text(json.dumps(law))
    link = out_dir / "model.safetensors"
    link.unlink(missing_ok=True)
    link.hardlink_to(store / "model.safetensors")


# ------------------------------------------------------------- the maths

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope_halves(x, theta: float):
    """x [B, heads, S, d]: dimension i rotates with i + d/2 by
    pos * theta^(-2i/d) (HF `rotate_half`)."""
    import jax.numpy as jnp

    S, d = x.shape[-2:]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # [S, d/2]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention(w: dict, x, mask, m: dict):
    """x [B, S, H] normed, mask [B, S] -> [B, S, H]."""
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    nh, d = m["num_attention_heads"], m["head_dim"]

    def heads(name):
        return (x @ w[name].T).reshape(B, S, nh, d).transpose(0, 2, 1, 3)

    q = rope_halves(heads("q_proj"), m["rope_theta"])
    k = rope_halves(heads("k_proj"), m["rope_theta"])
    scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d)
    keep = (jnp.tril(jnp.ones((S, S), bool))[None, None]
            & (mask[:, None, None, :] > 0))
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e9), axis=-1)
    ctx = (probs @ heads("v_proj")).transpose(0, 2, 1, 3).reshape(B, S, nh * d)
    return ctx @ w["o_proj"].T


def swiglu(w: dict, x):
    import jax

    return (jax.nn.silu(x @ w["gate_proj"].T) * (x @ w["up_proj"].T)
            ) @ w["down_proj"].T


def block(w: dict, x, mask, m: dict):
    """One sandwich-norm decoder block on x [B, S, H]."""
    eps = m["rms_norm_eps"]
    a = attention(w, rms_norm(x, w["input_layernorm"], eps), mask, m)
    h = x + rms_norm(a, w["input_layernorm_2"], eps)
    f = swiglu(w, rms_norm(h, w["post_attention_layernorm"], eps))
    return h + rms_norm(f, w["post_attention_layernorm_2"], eps)


def exit_distribution(lams: list) -> list:
    """[lam_0 .. lam_{T-1}] -> [p_0 .. p_{T-1}]: leave after step t with
    `lam_t prod_{j<t}(1 - lam_j)`; the last step takes what is left."""
    out, left = [], np.ones_like(lams[0])
    for lam in lams[:-1]:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return out + [left]


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
    """`embed(texts)` -> [n, H] float32 mean-pooled chunk vectors."""

    def __init__(self, model: dict, seed: int, max_len: int):
        self.m = model
        self.max_len = max_len
        self.tensors = seeded(model, seed)

    def forward(self, batches: list, steps: bool = False):
        """`batches` = [(ids, mask)] -> pooled rows per batch; step by step,
        layer by layer over all batches, one layer's weights on the device
        at a time. With `steps` (tests) also every step's normed states
        and exit probabilities: (pooled, states[t][batch] [B, S, H],
        p[t][batch] [B, S])."""
        import jax
        import jax.numpy as jnp

        m, t = self.m, self.tensors
        eps = m["rms_norm_eps"]
        block_fn = jax.jit(lambda w, x, mask: block(w, x, mask, m))
        with jax.default_matmul_precision("highest"):
            wte = jax.device_put(np.asarray(t["model.embed_tokens.weight"],
                                            np.float32))
            masks = [jnp.asarray(mask) for _, mask in batches]
            xs = [wte[jnp.asarray(ids)] for ids, _ in batches]
            del wte
            norm = jnp.asarray(np.asarray(t["model.norm.weight"], np.float32))
            w_gate = jnp.asarray(np.asarray(
                t["model.early_exit_gate.weight"], np.float32))
            b_gate = jnp.asarray(np.asarray(
                t["model.early_exit_gate.bias"], np.float32))
            states, lams = [], []
            for _ in range(m["total_ut_steps"]):
                for i in range(m["num_hidden_layers"]):
                    w = jax.device_put(layer_weights(t, m, i))
                    for b, mask in enumerate(masks):
                        xs[b] = block_fn(w, xs[b], mask)
                    del w
                xs = [rms_norm(x, norm, eps) for x in xs]
                lams.append([jax.nn.sigmoid((x @ w_gate.T)[..., 0] + b_gate[0])
                             for x in xs])
                if steps:
                    states.append([np.asarray(x) for x in xs])
            out = []
            for x, mask in zip(xs, masks):
                maskf = mask.astype(jnp.float32)
                out.append(np.asarray((x * maskf[..., None]).sum(1)
                                      / maskf.sum(1, keepdims=True)))
        if not steps:
            return out
        per_batch = [exit_distribution([np.asarray(lam[b]) for lam in lams])
                     for b in range(len(batches))]
        p = [[per_batch[b][s] for b in range(len(batches))]
             for s in range(m["total_ut_steps"])]
        return out, states, p

    def batches_of(self, enc: list, rows_per_call: int):
        """Blocks of rows of like length, padded to a multiple of 32 tokens:
        few shapes, and padding is masked, so the padded length does not
        change a row. -> (row indices per block, [(ids, mask)])."""
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
        return groups, batches

    def embed(self, texts: list, rows_per_call: int = 32) -> np.ndarray:
        enc = [tokenize(t, self.m["vocab_size"], self.max_len) for t in texts]
        out = np.zeros((len(texts), self.m["hidden_size"]), np.float32)
        # a block of rows is one float32 [rows, 16 heads, S, S] score tensor:
        # 32 rows of 512 tokens are 0.5 GB
        groups, batches = self.batches_of(enc, min(rows_per_call, 32))
        for rows, got in zip(groups, self.forward(batches)):
            out[rows] = got[:len(rows)]
        print(f"reference {ARCH}: {len(texts)} chunks, "
              f"{sum(len(e) for e in enc)} tokens, "
              f"{self.m['total_ut_steps']} steps x "
              f"{self.m['num_hidden_layers']} layers", file=sys.stderr,
              flush=True)
        return out
