"""Decode batch-scaling profiler (VERDICT r4 next-3).

Why does TinyLlama's per-step decode time triple from batch 8 to 128 when
weight reads — which every row shares — dominate the HBM traffic? This
script isolates the per-row suspects on the real chip by timing the SAME
chunked-decode loop with components ablated:

  full      : temperature=0.8, top_k=40  (lax.top_k bucket + categorical)
  no_topk   : temperature=0.8, top_k=0   (categorical only)
  greedy    : _sample monkeypatched to pure argmax (no RNG, no top_k)

and across cache sizes (NEW=128 vs 896) to expose the padded-cache-read
term (attention always reads the full [B, P+NEW] cache, valid or not).

Prints one JSON line per (geometry, batch, variant) with ms/step and the
HBM roofline context and the device it ran on. Runs on the TPU; refuses a
CPU that JAX_PLATFORMS=cpu did not ask for (symbiont_tpu/device.py).

Usage: python scripts/profile_decode.py [--quick]
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

GEOMETRIES = {
    "tinyllama_1b": dict(vocab_size=32000, hidden_size=2048, num_layers=22,
                         num_heads=32, num_kv_heads=4, intermediate_size=5632,
                         max_position_embeddings=2048, arch="llama"),
    "gpt2_124m": dict(vocab_size=50257, hidden_size=768, num_layers=12,
                      num_heads=12, intermediate_size=3072,
                      max_position_embeddings=1024, arch="gpt2"),
}


def param_bytes(params) -> int:
    import jax

    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


def time_decode(gpt_mod, params, cfg, B, P, NEW, chunk, temperature, top_k,
                steps) -> float:
    """ms per decode step over `steps` chunked steps (fresh state, warmed)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, P)), jnp.int32)
    mask = jnp.ones((B, P), jnp.int32)
    key = jax.random.key(0)

    def run(n_steps):
        cache, logits, kv_valid, plen = gpt_mod.prefill(params, ids, mask,
                                                        cfg, NEW)
        pos, done = plen, jnp.zeros((B,), bool)
        n = 0
        toks = None
        while n < n_steps:
            keys = jax.random.split(jax.random.fold_in(key, n), chunk)
            (cache, logits, pos, done, toks, _) = gpt_mod.decode_chunk(
                params, cache, logits, pos, done, kv_valid, keys, cfg,
                temperature=temperature, top_k=top_k, eos_id=-1)
            n += chunk
        # completion barrier: materializing the last chunk's tokens waits
        # for every chunk before it
        np.asarray(toks)

    run(chunk)          # compile prefill + chunk executable
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        run(steps)
        best = min(best, time.time() - t0)
    return best / steps * 1000.0


def main() -> None:
    import jax

    from symbiont_tpu.models import gpt as gpt_mod

    from symbiont_tpu.device import require_device

    quick = "--quick" in sys.argv
    info = require_device()  # a TPU, or an explicitly requested CPU
    print(f"# device: {info.count} x {info.device_kind} ({info.platform}), "
          f"jax {info.jax}", file=sys.stderr)

    for name, kw in GEOMETRIES.items():
        if quick and name != "tinyllama_1b":
            continue
        cfg = gpt_mod.GPTConfig(dtype="bfloat16", **kw)
        params = jax.device_put(gpt_mod.init_params(jax.random.key(0), cfg))
        pbytes = param_bytes(params)
        P, chunk = 64, 16
        steps = 32 if quick else 64

        orig_sample = gpt_mod._sample

        def argmax_sample(logits, key, temperature, top_k, top_k_bucket):
            import jax.numpy as jnp

            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        for NEW in (128, 896):
            for B in ((8, 128) if quick else (8, 32, 128)):
                row = {**info.report(), "geometry": name, "batch": B,
                       "prompt": P, "new": NEW, "param_bytes": pbytes}
                # KV bytes READ per step: full padded cache, both k and v
                T = P + NEW
                nkv = cfg.kv_heads
                row["kv_read_bytes_per_step"] = (
                    2 * cfg.num_layers * B * T * nkv * cfg.head_dim * 2)
                for variant, (t, k) in {
                    "full": (0.8, 40), "no_topk": (0.8, 0),
                }.items():
                    ms = time_decode(gpt_mod, params, cfg, B, P, NEW, chunk,
                                     t, k, steps)
                    row[f"ms_per_step_{variant}"] = round(ms, 3)
                # greedy-argmax: swap _sample out and drop the jit cache so
                # the ablated body actually recompiles
                gpt_mod._sample = argmax_sample
                gpt_mod._decode_chunk_jit.clear_cache()
                try:
                    ms = time_decode(gpt_mod, params, cfg, B, P, NEW, chunk,
                                     0.8, 40, steps)
                    row["ms_per_step_argmax"] = round(ms, 3)
                finally:
                    gpt_mod._sample = orig_sample
                    gpt_mod._decode_chunk_jit.clear_cache()
                row["tok_per_s_full"] = round(
                    B / row["ms_per_step_full"] * 1000, 1)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
