"""fault_run.py with the faults only a window-and-full GQA + held-expert
embedder can have (tests only): every other number of the run as it was.

    python fault_run_mimo.py ingest_longdocs_mimo <fault> [--seed N]
        [--seconds S] [--chip]

  window_off          the window layers attend to the whole causal prefix
                      of the passage (their sink and KV heads kept)
  sink_dropped        the window layers' softmax loses its sink
  full_as_window      the full layers attend through the window as well
  held_renormalised   the held experts' weights renormalised over the held
                      choices alone (the router's weights of the choices
                      another chip holds given to this chip's):
                      `experts_held_pct.ingest_mimo` does not move

The three attention faults are planted in the kernel's entry
(ops/flash_attention.py `packed_attention`); the kernel counts the keys
its mask keeps, so under `window_off` `engine.attn.window_keys` reaches
`.keys_causal` times the window layers (`window_keys_kept_pct` 100). Each
fault is planted once the stack is up: the engine's compiled `embed`
programs are dropped and its own warm-up traces them again over the broken
function, so nothing compiles in the window. Every other fault name is
fault_run.py's.
"""

from __future__ import annotations

import sys

import fault_run


def _replant(stack, fault) -> None:
    engine = stack.engine
    if fault == "held_renormalised":
        import jax.numpy as jnp

        from symbiont_tpu.models import mla_moe

        real = mla_moe.routed_experts

        def broken(p, x, idx, w, real_tok, cfg):
            here = idx < cfg.held
            kept = jnp.where(here, w, 0.0).sum(-1, keepdims=True)
            w = jnp.where(here, w * w.sum(-1, keepdims=True)
                          / jnp.maximum(kept, 1e-20), w)
            return real(p, x, idx, w, real_tok, cfg)

        mla_moe.routed_experts = broken
    else:
        import importlib

        # the package exports a function of the module's name
        fa = importlib.import_module("symbiont_tpu.ops.flash_attention")

        real = fa.packed_attention
        W = engine.model_cfg.sliding_window

        def broken(*a, window=0, sinks=None, **kw):
            if fault == "window_off":
                window = 0
            elif fault == "sink_dropped":
                sinks = None
            elif not window:  # full_as_window
                window = W
            return real(*a, window=window, sinks=sinks, **kw)

        fa.packed_attention = broken
    with engine._lock:
        engine._exec_cache.clear()
    engine.warmup(buckets=engine.config.length_buckets,
                  batches=engine.config.batch_buckets)


FAULTS = ("window_off", "sink_dropped", "full_as_window",
          "held_renormalised")

_plant = fault_run.plant
fault_run.plant = lambda fault: (
    (lambda stack: _replant(stack, fault)) if fault in FAULTS
    else _plant(fault))

if __name__ == "__main__":
    sys.exit(fault_run.main())
