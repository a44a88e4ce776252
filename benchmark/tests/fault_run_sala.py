"""fault_run.py with the faults only a block-selecting embedder can have
(tests only): the sets the sparse layers attend to are wrong, every other
number of the run as it was.

    python fault_run_sala.py ingest_longdocs_sala <fault> [--seed N] [--chip]

  select_worst      block scores read off the negated queries: of the
                    others, each token takes the WORST-scoring; init and
                    window blocks, set sizes and every counter unchanged
  select_no_window  the local window left out of the forced blocks (a
                    token's own block stays); its places go to top-k others
  select_no_others  topk cut to the forced blocks: init and window blocks only
                    (`sparse_keys_kept_pct.ingest_sala` falls with it)

The fault is planted once the stack is up: the engine's compiled `embed`
programs are dropped and its own warm-up traces them again over the broken
selection, so nothing compiles in the window. At the cell's own size on
the chip `correct` comes out false for each (PERF.md, section 2, has the
readings); at toy sizes a row hardly depends on which blocks a token read
(top-6 of ~30 blocks, 64 dimensions) and only `select_no_others` is seen.
Every other fault name is fault_run.py's.
"""

from __future__ import annotations

import dataclasses
import sys

import fault_run


def _replant(stack, broken_select) -> None:
    from symbiont_tpu.ops import block_sparse_attention as op

    op._select = broken_select(op._select)
    engine = stack.engine
    with engine._lock:
        engine._exec_cache.clear()
    engine.warmup(buckets=engine.config.length_buckets,
                  batches=engine.config.batch_buckets)


def _args(fn):
    """`_select`'s arguments by position: qb first, `sp` last but one."""
    def wrapped(real):
        def broken(qb, *rest):
            return real(*fn(qb, rest))
        return broken
    return wrapped


def _with_sp(rest, **sizes):
    *head, sp, scale = rest
    return (*head, dataclasses.replace(sp, **sizes), scale)


FAULTS = {
    "select_worst": _args(lambda qb, rest: (-qb, *rest)),
    "select_no_window": _args(lambda qb, rest: (
        qb, *_with_sp(rest, window_size=1))),
    "select_no_others": _args(lambda qb, rest: (qb, *_with_sp(
        rest, topk=rest[-2].init_blocks
        + rest[-2].window_size // rest[-2].block_size + 1))),
}

_plant = fault_run.plant
fault_run.plant = lambda fault: (
    (lambda stack: _replant(stack, FAULTS[fault])) if fault in FAULTS
    else _plant(fault))

if __name__ == "__main__":
    sys.exit(fault_run.main())
