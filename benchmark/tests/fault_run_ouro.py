"""fault_run.py with the faults only a looped embedder can have (tests
only): the loop or its block is not the published one, every other number
of the run as it was.

    python fault_run_ouro.py ingest_chunks_ouro <fault> [--seed N] [--chip]

  loop_one_step_short   one step fewer than `total_ut_steps` is run (three
                        where four are published; the counters follow the
                        loop, so `loop_steps_run_pct.ingest_ouro` stays 100:
                        it is `correct` that has to see it)
  loop_no_norm_between  the final norm is left out BETWEEN steps: a step
                        starts from the un-normed stream, and only the state
                        pooled is normed (what a stack run once would do)
  block_no_second_norm  the second norm of the sandwich is left out: a
                        sub-layer's output is added as it is (the usual
                        pre-norm block)

The fault is planted once the stack is up: the engine's compiled `embed`
programs are dropped and its own warm-up traces them again over the broken
forward, so nothing compiles in the window. `correct` has to come out
false at the cell's own size on the chip (`--chip`; PERF.md, section 2, has
the readings) and at toy sizes (test_cell_ouro_cpu.py). Every other fault
name is fault_run.py's.
"""

from __future__ import annotations

import dataclasses
import sys

import fault_run


def _one_step_short(ouro) -> None:
    real = ouro.encode
    ouro.encode = lambda params, ids, segments, cfg: real(
        params, ids, segments,
        dataclasses.replace(cfg, total_ut_steps=cfg.total_ut_steps - 1))


def _no_norm_between(ouro) -> None:
    real_end, real_encode = ouro.step_end, ouro.encode
    ouro.step_end = lambda params, h, cfg: (h, real_end(params, h, cfg)[1])

    def encode(params, ids, segments, cfg):
        h, p = real_encode(params, ids, segments, cfg)
        return ouro.rmsnorm(h, params["ln_f"], cfg.rms_norm_eps), p

    ouro.encode = encode


def _no_second_norm(ouro) -> None:
    def block(layer, h, segments, cfg):
        import jax.numpy as jnp

        dtype, eps = jnp.dtype(cfg.dtype), cfg.rms_norm_eps
        layer = ouro.quant.cast_params(layer, dtype)
        a = ouro.attention(layer["attn"], ouro.rmsnorm(
            h, layer["ln1"], eps).astype(dtype), segments, cfg)
        h = h + a.astype(h.dtype)
        m = ouro.swiglu(ouro.rmsnorm(h, layer["ln2"], eps).astype(dtype),
                        layer["mlp"])
        return h + m.astype(h.dtype)

    ouro.block = block


FAULTS = {"loop_one_step_short": _one_step_short,
          "loop_no_norm_between": _no_norm_between,
          "block_no_second_norm": _no_second_norm}


def _replant(stack, fault) -> None:
    from symbiont_tpu.models import ouro

    FAULTS[fault](ouro)
    engine = stack.engine
    with engine._lock:
        engine._exec_cache.clear()
    engine.warmup(buckets=engine.config.length_buckets,
                  batches=engine.config.batch_buckets)


_plant = fault_run.plant
fault_run.plant = lambda fault: (
    (lambda stack: _replant(stack, fault)) if fault in FAULTS
    else _plant(fault))

if __name__ == "__main__":
    sys.exit(fault_run.main())
