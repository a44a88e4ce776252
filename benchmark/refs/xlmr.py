"""Plain float32 reference for an XLM-RoBERTa sentence encoder.

Follows the published description (Conneau et al. 2019; HF
`XLMRobertaModel` + sentence-transformers mean pooling): word + learned
position + token-type embeddings -> LayerNorm -> L post-LN blocks (fused
softmax attention, exact erf GELU MLP) -> attention-masked mean pooling.
Straightforward `jax.numpy`, float32 under matmul precision "highest", no
cache, no batching tricks, layers scanned over stacked weights.

Departures, each noted:
- position ids count ATTENDED tokens (cumsum of the attention mask) + the
  padding index; HF derives the same mask from `input_ids != pad_token_id`.
  The two agree for a real XLM-R tokenizer; with the assumed hash tokenizer
  (CLS at id 1 == XLM-R's pad id) only the attention-mask form is meaningful.
- tokenization is the configuration's `assumed` hash tokenizer (no
  `tokenizer.json` exists offline), re-implemented here from its definition:
  regex word split, lower-case, blake2s -> id; CLS=1, SEP=2, PAD=0.

Imports nothing of the program.
"""

from __future__ import annotations

import hashlib
import re
from pathlib import Path

import numpy as np

from refs import common

ARCH = "xlmr"
HF_KEYS = ["model_type", "architectures", "vocab_size", "hidden_size",
           "num_hidden_layers", "num_attention_heads", "intermediate_size",
           "max_position_embeddings", "type_vocab_size", "layer_norm_eps",
           "hidden_act", "pad_token_id"]

_WORD_RE = re.compile(r"\w+|[^\w\s]", re.UNICODE)
_LAYER = [
    ("attention.self.query", "lin"), ("attention.self.key", "lin"),
    ("attention.self.value", "lin"), ("attention.output.dense", "lin"),
    ("attention.output.LayerNorm", "ln"), ("intermediate.dense", "lin_up"),
    ("output.dense", "lin_down"), ("output.LayerNorm", "ln"),
]


def tokenize(text: str, vocab_size: int, max_len: int) -> list:
    def wid(word: str) -> int:
        h = int.from_bytes(
            hashlib.blake2s(word.lower().encode()).digest()[:4], "little")
        return 3 + (h % (vocab_size - 3))

    ids = [1] + [wid(w) for w in _WORD_RE.findall(text)] + [2]
    if len(ids) > max_len:
        ids = ids[:max_len - 1] + [2]
    return ids


def token_count(text: str, max_len: int) -> int:
    """Real tokens the encoder sees for `text` (CLS + words/punct + SEP)."""
    return min(len(_WORD_RE.findall(text)) + 2, max_len)


def tensor_specs(m: dict) -> list:
    H, I = m["hidden_size"], m["intermediate_size"]
    specs = [
        ("embeddings.word_embeddings.weight", (m["vocab_size"], H), "w"),
        ("embeddings.position_embeddings.weight",
         (m["max_position_embeddings"], H), "w"),
        ("embeddings.token_type_embeddings.weight",
         (m["type_vocab_size"], H), "w"),
        ("embeddings.LayerNorm.weight", (H,), "ln_scale"),
        ("embeddings.LayerNorm.bias", (H,), "b"),
    ]
    for i in range(m["num_hidden_layers"]):
        for name, kind in _LAYER:
            p = f"encoder.layer.{i}.{name}"
            if kind == "ln":
                specs += [(p + ".weight", (H,), "ln_scale"),
                          (p + ".bias", (H,), "b")]
            else:
                n_in = I if kind == "lin_down" else H
                n_out = I if kind == "lin_up" else H
                # torch Linear layout: [out, in]
                specs += [(p + ".weight", (n_out, n_in), "w"),
                          (p + ".bias", (n_out,), "b")]
    return specs


def write_checkpoint(model: dict, seed: int, out_dir: Path) -> None:
    """`config.json` + `model.safetensors` in the hub layout the program's
    `model_dir` loader reads. No `tokenizer.json`: the program then falls
    back to its hash tokenizer, which `tokenize` above mirrors."""
    common.write_hf_config({k: model[k] for k in HF_KEYS if k in model},
                           out_dir)
    common.write_safetensors(
        common.seeded_tensors(tensor_specs(model), seed), out_dir)


class Reference:
    """`embed(texts)` -> [n, H] float32 mean-pooled sentence vectors."""

    def __init__(self, model: dict, seed: int, max_len: int):
        self.m = model
        self.max_len = max_len
        w = common.f32(common.seeded_tensors(tensor_specs(model), seed))
        L = model["num_hidden_layers"]
        self.emb = {k[len("embeddings."):]: v for k, v in w.items()
                    if k.startswith("embeddings.")}
        self.layers = {}
        for name, _ in _LAYER:
            for part in ("weight", "bias"):
                self.layers[f"{name}.{part}"] = np.stack(
                    [w[f"encoder.layer.{i}.{name}.{part}"] for i in range(L)])
        self._fn = None

    def _build(self):
        import jax
        import jax.numpy as jnp

        m = self.m
        nh = m["num_attention_heads"]
        eps = m["layer_norm_eps"]
        pad = m["pad_token_id"]

        def ln(x, scale, bias):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + eps) * scale + bias

        def lin(x, lw, name):
            return x @ lw[name + ".weight"].T + lw[name + ".bias"]

        def block(x, lw, bias):
            B, S, H = x.shape
            hd = H // nh

            def heads(t):
                return t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)

            q = heads(lin(x, lw, "attention.self.query"))
            k = heads(lin(x, lw, "attention.self.key"))
            v = heads(lin(x, lw, "attention.self.value"))
            scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(hd) + bias
            ctx = (jax.nn.softmax(scores, axis=-1) @ v
                   ).transpose(0, 2, 1, 3).reshape(B, S, H)
            x = ln(x + lin(ctx, lw, "attention.output.dense"),
                   lw["attention.output.LayerNorm.weight"],
                   lw["attention.output.LayerNorm.bias"])
            h = jax.nn.gelu(lin(x, lw, "intermediate.dense"),
                            approximate=False)
            return ln(x + lin(h, lw, "output.dense"),
                      lw["output.LayerNorm.weight"],
                      lw["output.LayerNorm.bias"])

        def fwd(emb, layers, ids, mask):
            maskf = mask.astype(jnp.float32)
            pos = jnp.cumsum(mask, axis=1) * mask + pad
            x = (emb["word_embeddings.weight"][ids]
                 + emb["position_embeddings.weight"][pos]
                 + emb["token_type_embeddings.weight"][0])
            x = ln(x, emb["LayerNorm.weight"], emb["LayerNorm.bias"])
            bias = (1.0 - maskf)[:, None, None, :] * -1e9
            x, _ = jax.lax.scan(lambda c, lw: (block(c, lw, bias), None),
                                x, layers)
            return (x * maskf[..., None]).sum(1) / maskf.sum(1, keepdims=True)

        return jax.jit(fwd)

    def embed(self, texts: list, rows_per_call: int = 32) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            self._fn = self._build()
            self._dev = (jax.device_put(self.emb), jax.device_put(self.layers))
        enc = [tokenize(t, self.m["vocab_size"], self.max_len) for t in texts]
        out = np.zeros((len(texts), self.m["hidden_size"]), np.float32)
        # blocks of rows, padded to a multiple of 64 tokens: few shapes, and
        # padding is masked, so the padded length does not change a row
        order = sorted(range(len(enc)), key=lambda i: len(enc[i]))
        for a in range(0, len(order), rows_per_call):
            rows = order[a:a + rows_per_call]
            S = -(-max(len(enc[i]) for i in rows) // 64) * 64
            ids = np.zeros((rows_per_call, S), np.int32)
            mask = np.zeros((rows_per_call, S), np.int32)
            mask[len(rows):, 0] = 1  # filler rows: one token, discarded
            for r, i in enumerate(rows):
                ids[r, :len(enc[i])] = enc[i]
                mask[r, :len(enc[i])] = 1
            with jax.default_matmul_precision("highest"):
                got = np.asarray(self._fn(*self._dev, jnp.asarray(ids),
                                          jnp.asarray(mask)))
            out[rows] = got[:len(rows)]
        return out
