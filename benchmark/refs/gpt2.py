"""Plain float32 reference for GPT-2 (Radford et al. 2019; HF `GPT2LMHeadModel`).

Token + learned position embeddings -> L pre-LN blocks (fused c_attn,
causal softmax attention, c_proj; c_fc, gelu_new (tanh), c_proj) -> ln_f ->
logits against the tied embedding. Straightforward `jax.numpy`, float32 under
matmul precision "highest", one full forward over prompt + served tokens, no
cache; layers scanned over stacked weights.

Departure, noted: the tokenizer is the configuration's `assumed` word-level
one (`w<id>` per token over all vocabulary ids, written below), because no
GPT-2 BPE file exists offline; one word per token lets the client count
tokens and read the served ids from the streamed text.

Imports nothing of the program.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from refs import common

ARCH = "gpt2"
HF_KEYS = ["model_type", "architectures", "vocab_size", "n_embd", "n_layer",
           "n_head", "n_inner", "n_positions", "n_ctx",
           "layer_norm_epsilon", "activation_function",
           "tie_word_embeddings", "bos_token_id", "eos_token_id"]
_LAYER = [("ln_1", "ln"), ("attn.c_attn", "qkv"), ("attn.c_proj", "proj"),
          ("ln_2", "ln"), ("mlp.c_fc", "up"), ("mlp.c_proj", "down")]


def word(i: int) -> str:
    return f"w{i}"


def ids_to_text(ids) -> str:
    return " ".join(word(int(i)) for i in ids)


def text_to_ids(text: str) -> list:
    return [int(t[1:]) for t in text.split()]


def write_tokenizer(vocab_size: int, out_dir: Path) -> None:
    """A `tokenizer.json` (HF `tokenizers` format) mapping `w<i>` <-> i for
    every id: whitespace split, word-level model, no special tokens (so the
    program finds no EOS and a request always runs its full budget)."""
    tok = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel",
                  "vocab": {word(i): i for i in range(vocab_size)},
                  "unk_token": word(0)},
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "tokenizer.json").write_text(json.dumps(tok))


def tensor_specs(m: dict) -> list:
    H = m["n_embd"]
    inner = m.get("n_inner") or 4 * H
    specs = [("wte.weight", (m["vocab_size"], H), "w"),
             ("wpe.weight", (m["n_positions"], H), "w"),
             ("ln_f.weight", (H,), "ln_scale"), ("ln_f.bias", (H,), "b")]
    shapes = {"qkv": (H, 3 * H), "proj": (H, H), "up": (H, inner),
              "down": (inner, H)}  # HF Conv1D layout: [in, out]
    for i in range(m["n_layer"]):
        for name, kind in _LAYER:
            p = f"h.{i}.{name}"
            if kind == "ln":
                specs += [(p + ".weight", (H,), "ln_scale"),
                          (p + ".bias", (H,), "b")]
            else:
                specs += [(p + ".weight", shapes[kind], "w"),
                          (p + ".bias", (shapes[kind][1],), "b")]
    return specs


def write_checkpoint(model: dict, seed: int, out_dir: Path) -> None:
    common.write_hf_config({k: model[k] for k in HF_KEYS if k in model},
                           out_dir)
    common.write_safetensors(
        common.seeded_tensors(tensor_specs(model), seed), out_dir)
    write_tokenizer(model["vocab_size"], out_dir)


class Reference:
    """`served_gaps(prompt_ids, served_ids)` -> per served token, how far its
    reference logit lies below the reference's best at that position."""

    def __init__(self, model: dict, seed: int):
        self.m = model
        w = common.f32(common.seeded_tensors(tensor_specs(model), seed))
        L = model["n_layer"]
        self.top = {k: w[k] for k in ("wte.weight", "wpe.weight",
                                      "ln_f.weight", "ln_f.bias")}
        self.layers = {}
        for name, _ in _LAYER:
            for part in ("weight", "bias"):
                self.layers[f"{name}.{part}"] = np.stack(
                    [w[f"h.{i}.{name}.{part}"] for i in range(L)])
        self._fn = None

    def _build(self):
        import jax
        import jax.numpy as jnp

        nh = self.m["n_head"]
        eps = self.m["layer_norm_epsilon"]

        def ln(x, scale, bias):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + eps) * scale + bias

        def block(x, lw):
            T, H = x.shape
            hd = H // nh
            h = ln(x, lw["ln_1.weight"], lw["ln_1.bias"])
            qkv = h @ lw["attn.c_attn.weight"] + lw["attn.c_attn.bias"]
            q, k, v = (t.reshape(T, nh, hd).transpose(1, 0, 2)
                       for t in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 2, 1) / np.sqrt(hd)
            causal = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(causal, scores, -1e30)
            ctx = (jax.nn.softmax(scores, axis=-1) @ v
                   ).transpose(1, 0, 2).reshape(T, H)
            x = x + ctx @ lw["attn.c_proj.weight"] + lw["attn.c_proj.bias"]
            h = ln(x, lw["ln_2.weight"], lw["ln_2.bias"])
            h = jax.nn.gelu(h @ lw["mlp.c_fc.weight"] + lw["mlp.c_fc.bias"],
                            approximate=True)  # gelu_new
            return x + h @ lw["mlp.c_proj.weight"] + lw["mlp.c_proj.bias"]

        def fwd(top, layers, ids, first, count):
            """Logits of `count` positions starting at `first` (static)."""
            T = ids.shape[0]
            x = top["wte.weight"][ids] + top["wpe.weight"][:T]
            x, _ = jax.lax.scan(lambda c, lw: (block(c, lw), None), x, layers)
            x = ln(x, top["ln_f.weight"], top["ln_f.bias"])
            x = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
            return x @ top["wte.weight"].T

        return jax.jit(fwd, static_argnums=(4,))

    def served_gaps(self, prompt_ids: list, served_ids: list) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            self._fn = self._build()
            self._dev = (jax.device_put(self.top), jax.device_put(self.layers))
        P, n = len(prompt_ids), len(served_ids)
        # causal: right padding cannot reach an earlier position, so pad the
        # sequence and the read-out count to multiples of 128/64 (few shapes)
        T = -(-(P + n) // 128) * 128
        count = -(-n // 64) * 64
        ids = np.zeros((T,), np.int32)
        ids[:P + n] = list(prompt_ids) + list(served_ids)
        first = min(P - 1, T - count)  # logits[p] predict the token at p+1
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(self._fn(*self._dev, jnp.asarray(ids),
                                         first, count))
        rows = logits[P - 1 - first:P - 1 - first + n]
        return rows.max(-1) - rows[np.arange(n), np.asarray(served_ids)]
