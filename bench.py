"""Benchmark CLI shim: one run, ONE parsed JSON line naming its device.

The harness itself lives in `symbiont_tpu/bench/` — a tier-isolated
registry (tiers.py), a repetition engine (stats.py), a per-process resource
sampler (sampler.py), a dual-ceiling roofline accountant (roofline.py), and
a typed archive schema + regression gate (archive.py); this file is the
thin CLI the driver and docs invoke:

    python bench.py                 # full run; rc != 0 on ANY tier failure
    python bench.py --quick         # embed-policy tier only
    python bench.py --no-e2e        # skip the full-stack tier
    python bench.py --gate NEW.json BASELINE.json
    python bench.py --validate ARCHIVE.json [...]

Prints ONE JSON line to stdout (extra detail goes to stderr); the line
always carries `tier_failures`/`tier_skips`, and a thrown tier or a missing
declared primary metric exits nonzero AFTER the line is printed — the
archive carries the evidence. The run needs a TPU (symbiont_tpu/device.py):
without one it exits 3 naming the platform it found, unless JAX_PLATFORMS=cpu
was set explicitly — and then the line says `"platform": "cpu"`.

The reference publishes no numbers (BASELINE.md: "none exist"), so
vs_baseline is measured, not quoted: the same model on the same chip run the
reference's way — fixed padding to model max (514-equivalent) in serial
batches of 8 (reference: embedding_generator.rs:83-91,146) — versus this
framework's way (length-bucketed static shapes, big batches, bf16). The
ratio is the design win of SURVEY.md §5.7/§7 on identical hardware.
"""

from __future__ import annotations

import sys

# re-exports: tests and tooling import these through `bench` (the package
# modules are the single source; keep this list additions-only)
from symbiont_tpu.bench.archive import (load_archive,  # noqa: F401
                                        regression_gate, validate_file,
                                        validate_line)
from symbiont_tpu.bench.cli import main  # noqa: F401
from symbiont_tpu.bench.stats import med_min_max  # noqa: F401
from symbiont_tpu.bench.workload import (bert_fwd_flops,  # noqa: F401
                                         chip_peaks, log, make_sentences)

if __name__ == "__main__":
    sys.exit(main())
