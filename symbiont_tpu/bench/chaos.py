"""Chaos tier: loss-under-fault gated like a perf regression.

Runs the seeded fault-injection suite (tests/test_chaos.py, marker
`chaos`) as a bench tier and archives the outcome, so a change that starts
LOSING messages under a fault class fails the bench run (and the
`--gate` comparison) exactly like a throughput regression would:

- `chaos_pass_rate` (primary): passed / collected. 1.0 means every fault
  class (handler crash, handler hang past timeout, delivery drop, store
  outage with recovery, TCP disconnect, poison-message quarantine+replay)
  proved zero loss. The regression gate treats it higher-is-better with
  the default noise floor — any failing scenario (rate <= 0.875 with the
  current 8-test suite) trips it.
- `chaos_tests_passed` / `chaos_tests_failed`: the raw counts.

A failing scenario ALSO throws, so the tier lands in `tier_failures` and
forces rc != 0 on the spot — the gate is the second line of defense for
cross-run comparisons, not the only one.

Skips (TierSkip) when pytest or the test tree is unavailable (installed
wheel without the repo checkout). `--no-chaos` skips by flag.
"""

from __future__ import annotations

import os
import pathlib
import re
import subprocess
import sys

from symbiont_tpu.bench.tiers import TierSkip, register
from symbiont_tpu.bench.workload import log

CHAOS_TIMEOUT_S = 600


@register("chaos", primary_metrics=("chaos_pass_rate",))
def tier_chaos(results: dict, ctx) -> None:
    repo = pathlib.Path(__file__).resolve().parent.parent.parent
    tests_dir = repo / "tests" / "test_chaos.py"
    if not tests_dir.exists():
        raise TierSkip("no tests/test_chaos.py next to this checkout")
    try:
        import pytest  # noqa: F401
    except ImportError:
        raise TierSkip("pytest not installed")

    env = dict(os.environ)
    # the suite needs no device — and this parent may be holding the chip
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "pytest", str(tests_dir), "-m", "chaos",
           "-q", "--no-header", "-p", "no:cacheprovider"]
    log(f"chaos: {' '.join(cmd[2:])}")
    proc = subprocess.run(cmd, cwd=str(repo), env=env,
                          capture_output=True, text=True,
                          timeout=CHAOS_TIMEOUT_S)
    tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-20:])
    if proc.returncode == 5:  # pytest: no tests collected
        raise TierSkip("chaos marker collected no tests")

    def count(word: str) -> int:
        # \b so "error" cannot double-count an "N errors" summary
        m = re.search(rf"(\d+) {word}\b", proc.stdout)
        return int(m.group(1)) if m else 0

    passed, failed = count("passed"), count("failed")
    errors = count("errors") or count("error")
    total = passed + failed + errors
    if total == 0:
        raise RuntimeError(
            f"chaos suite produced no parseable outcome (rc={proc.returncode}):\n{tail}")
    results["chaos_tests_passed"] = float(passed)
    results["chaos_tests_failed"] = float(failed + errors)
    results["chaos_pass_rate"] = passed / total
    log(f"chaos: {passed}/{total} scenarios held zero-loss "
        f"(pass rate {results['chaos_pass_rate']:.3f})")
    if failed or errors or proc.returncode != 0:
        # loud NOW, not only at the next --gate: a lost message under fault
        # is a regression of the acceptance criteria in docs/RESILIENCE.md
        raise RuntimeError(
            f"chaos suite regressed: {failed} failed, {errors} errored "
            f"(rc={proc.returncode}):\n{tail}")
