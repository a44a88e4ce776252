"""fault_run.py with the faults only a KDA + held-expert embedder can have
(tests only): every other number of the run as it was.

    python fault_run_ling.py ingest_longdocs_ling <fault> [--seed N] [--chip]

  kda_no_reset        the delta rule's state is not reset at a passage:
                      every KDA layer reads each packed row as ONE passage
                      (the convolution's window still resets, the counters
                      are unchanged)
  held_renormalised   the held experts' weights renormalised over the held
                      choices alone (the router's weights of the choices
                      another chip holds given to this chip's):
                      `experts_held_pct.ingest_ling` does not move

The fault is planted once the stack is up: the engine's compiled `embed`
programs are dropped and its own warm-up traces them again over the broken
function, so nothing compiles in the window. Every other fault name is
fault_run.py's.
"""

from __future__ import annotations

import sys

import fault_run


def _replant(stack, fault) -> None:
    if fault == "kda_no_reset":
        from symbiont_tpu.ops import delta_rule as op

        real = op.gated_delta_rule

        def broken(q, k, v, g, beta, index, *a, **kw):
            return real(q, k, v, g, beta, index * 0, *a, **kw)

        op.gated_delta_rule = broken
    else:
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
    engine = stack.engine
    with engine._lock:
        engine._exec_cache.clear()
    engine.warmup(buckets=engine.config.length_buckets,
                  batches=engine.config.batch_buckets)


FAULTS = ("kda_no_reset", "held_renormalised")

_plant = fault_run.plant
fault_run.plant = lambda fault: (
    (lambda stack: _replant(stack, fault)) if fault in FAULTS
    else _plant(fault))

if __name__ == "__main__":
    sys.exit(fault_run.main())
