"""Repetition engine: in-run spread for every volatile primary metric.

Round-5 verdict weak #2: four of the eleven declared primary metrics had up
to ±45% cross-run spread with NO in-run repetition archived — a number with
no error bar on a drifting link is unfalsifiable. The rule this module
enforces: a primary metric is a (median, min, max) triple from ≥3 in-run
repetitions, archived as `<key>`, `<key>_min`, `<key>_max` (and optionally
`<key>_samples`), never a single sample.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Sequence

MIN_REPEATS = 3  # the floor for any primary-metric measurement


def med_min_max(samples: Sequence[float]) -> tuple:
    """(median, min, max) of a sample list. A single sample is not a
    measurement, so every headline number reports all three."""
    s = sorted(samples)
    n = len(s)
    mid = (s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2]))
    return mid, s[0], s[-1]


def repeat(fn: Callable[[], float], n: int = MIN_REPEATS) -> List[float]:
    """Collect n samples from fn (each call returns one measurement)."""
    if n < MIN_REPEATS:
        raise ValueError(f"primary metrics need >= {MIN_REPEATS} repetitions, "
                         f"got n={n}")
    return [fn() for _ in range(n)]


def time_repeats(fn: Callable[[], None], n: int = MIN_REPEATS) -> List[float]:
    """n wall-clock samples of fn() in seconds."""
    def one() -> float:
        t0 = time.time()
        fn()
        return time.time() - t0
    return repeat(one, n)


def record(results: Dict, key: str, samples: Sequence[float], digits: int = 1,
           count: bool = False) -> float:
    """Archive a sample list as `key` (median) + `key_min`/`key_max`, the
    shape the regression gate and doc renderer understand. Returns the
    median. With count=True also archives `key_samples`."""
    if len(samples) < MIN_REPEATS:
        raise ValueError(
            f"{key}: {len(samples)} sample(s) archived as a spread metric — "
            f"primary metrics need >= {MIN_REPEATS} in-run repetitions")
    med, lo, hi = med_min_max(samples)
    results[key] = round(med, digits)
    results[f"{key}_min"] = round(lo, digits)
    results[f"{key}_max"] = round(hi, digits)
    if count:
        results[f"{key}_samples"] = len(samples)
    return med


def spread_fraction(results: Dict, key: str) -> float | None:
    """Relative in-run spread (max-min)/median of an archived metric, or
    None when the archive carries no spread for it. The regression gate uses
    this as the noise floor: a delta inside the measured in-run spread is
    not a regression."""
    med, lo, hi = (results.get(key), results.get(f"{key}_min"),
                   results.get(f"{key}_max"))
    if not isinstance(med, (int, float)) or med == 0 \
            or not isinstance(lo, (int, float)) \
            or not isinstance(hi, (int, float)):
        return None
    return abs(hi - lo) / abs(med)
