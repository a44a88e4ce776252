"""Tier-isolated benchmark, roofline-accounting, and regression-gating
subsystem.

The bench harness is the gate on every performance claim this project makes:
one JSON line per run, naming the device it ran on (the root PERF.md says
what has and has not been measured on the chip). A monolithic harness once
lost an entire tier with rc=0 behind a swallowed `except`, crashed on a
`parsed: null` driver wrapper, and let the decode path set the very ceiling
its utilization was measured against — hence five isolated components:

- `tiers`    — a registry where each benchmark tier runs in isolation; a tier
               that throws archives a structured `tier_failures` entry and the
               run exits nonzero whenever any declared primary metric is
               absent. A swallowed tier can no longer masquerade as a clean
               run.
- `stats`    — the repetition engine: every volatile primary metric is
               measured ≥3× in-run and archived as median with `_min`/`_max`,
               so a cross-run spread claim is falsifiable from one archive.
- `sampler`  — per-process resource accounting (CPU seconds per worker role,
               bus bytes/s) sampled during the e2e waves, archiving the
               host-side decomposition.
- `roofline` — per-batch decode byte breakdowns (weights vs KV vs
               activations) and DUAL-ceiling utilization: every point is
               reported against the reference stream kernel and against the
               best OTHER observed stream separately, so no decode point can
               set its own ceiling.
- `archive`  — typed schema validation for every emitted line, a
               `parsed: null`-tolerant loader, and a noise-aware regression
               gate against a previous archive.

Tier implementations live beside them (`workload`, `compute`, `engine_plane`,
`decode`, `e2e`) and `cli.main` orchestrates; repo-root `bench.py` is a
thin CLI shim over this package.
"""

from symbiont_tpu.bench.archive import load_archive, validate_line  # noqa: F401
from symbiont_tpu.bench.stats import med_min_max  # noqa: F401
from symbiont_tpu.bench.tiers import Tier, register, run_tiers  # noqa: F401
