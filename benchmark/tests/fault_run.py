"""Drive a whole run with the timed path broken underneath (tests only).

    python fault_run.py <workload> <fault> [--seed N] [--chip]

Skips the harness's look for a chip (CPU, the configuration's toy sizes),
plants ONE fault in the program once the stack is up — an answer altered
where it is produced, the fault a serving cell can have — and prints the
result line. `correct` has to come out false. `--chip` is the same at the
cell's own size on the chip (by hand, through the chip tool).

  gen_token      one token of every decode chunk replaced before it is served
  search_hit     the best hit of every fused search replaced by another row
  ingest_row     one row of every embedded batch negated before it is stored
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def plant(fault: str):
    def gen_token(stack):
        import symbiont_tpu.models.gpt as gpt_mod

        real = gpt_mod.decode_chunk

        def broken(*a, **kw):
            out = list(real(*a, **kw))
            toks = out[4]
            vocab = stack.lm.model_cfg.vocab_size
            out[4] = toks.at[:, 1].set((toks[:, 1] + 1) % vocab)
            return tuple(out)

        gpt_mod.decode_chunk = broken

    def search_hit(stack):
        real = stack.engine.embed_and_search

        def broken(text, corpus, n_valid, top_k):
            scores, idx = real(text, corpus, n_valid, top_k)
            idx = idx.copy()
            idx[0] = (idx[0] + 7) % n_valid
            return scores, idx

        stack.engine.embed_and_search = broken

    def ingest_row(stack):
        real = stack.engine.embed_texts

        def broken(texts):
            out = real(texts).copy()
            out[0] = -out[0]
            return out

        stack.engine.embed_texts = broken

    return {"gen_token": gen_token, "search_hit": search_hit,
            "ingest_row": ingest_row}[fault]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("fault")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("--benchmark-json", default=None)
    a = ap.parse_args()
    if not a.chip:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(run.artefacts.CACHE / "jax"))
    args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                      "--seconds", str(a.seconds)]
                     + ([] if a.chip else ["--rehearse-cpu"]))
    args.device = run.look_for_chip(1, rehearse_cpu=not a.chip)
    out = asyncio.run(run.run_cell(args, run.load_benchmark(a.benchmark_json),
                                   hooks={"after_boot": plant(a.fault)}))
    for name, v in out["compared"].items():
        print(f"compared {name} = {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
