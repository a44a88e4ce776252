"""Archive schema + regression gate.

Every number this project publishes flows through one JSON line per run
(`python bench.py` → stdout; a driver may archive it inside a
`{n, cmd, rc, tail, parsed}` wrapper). Two failure modes this module exists
to kill:

- a driver wrapper can carry `"parsed": null` (the driver could not parse a
  line); the loader tolerates null wrappers and the schema validator treats
  them as a first-class "no parseable line" shape;
- a malformed line (wrong-typed field, spread metric without its `_min`,
  string where a number belongs) could be archived silently; `validate_line`
  types every field so the emit path and the test suite both gate on it.

`regression_gate` compares a run against a previous archive with per-metric
noise-aware thresholds: the allowed delta per metric is the larger of a
default floor and the baseline's own archived in-run spread.
"""

from __future__ import annotations

import json
import math
import pathlib
import re
from typing import Dict, List, Optional

# line-level string fields (everything else non-listed must be numeric)
_STRING_FIELDS = {"metric", "unit", "semantic_validation",
                  # explanatory note archived alongside a null ratio when
                  # the same-run prerequisite metric is absent (bench/e2e.py
                  # bulk_ratio_fields)
                  "e2e_ingest_vs_bulk_note",
                  # host fingerprint (host_fingerprint() below): a gate
                  # failure on a DIFFERENT machine than the baseline's is
                  # usually the environment, not the code — perf_gate.sh
                  # compares these and shouts on mismatch
                  "host_cpu_model",
                  # device identity + stack versions (symbiont_tpu/device.py
                  # DeviceInfo.report()): every line names what it ran on
                  "platform", "device_kind", "jax", "jaxlib", "libtpu"}
# fields that may archive as an explicit null ("measured nothing, and here
# is why" — the paired _note says why); everything else numeric stays
# non-null so a silent None can never masquerade as a measurement
_NULLABLE_FIELDS = {"e2e_ingest_vs_bulk_x"}
_LIST_OF_STR_FIELDS = {"primary_metrics"}
# driver wrapper shape: {n, cmd, rc, tail, parsed} with parsed possibly null
_WRAPPER_FIELDS = {"n", "cmd", "rc", "tail", "parsed"}
_REQUIRED = {"metric": str, "value": (int, float), "unit": str,
             "vs_baseline": (int, float)}

# default noise floors by metric family when the baseline archives no in-run
# spread: device-bound metrics move ±1-2% run to run (measured r5: value
# spread 0.2%, ms_per_step_b128 10.87/10.88/10.88); e2e metrics ride their
# own pipeline plus a shared host core
_DEFAULT_NOISE_FLOOR = (
    # util-vs-reference-kernel divides by a denominator the project itself
    # documents drifting hour-to-hour (the same reduce-sum kernel read
    # 517–715 GB/s on this chip, ~38%): a no-change run can move the ratio
    # by that much in either direction, so only a beyond-drift collapse
    # (e.g. the unexplained 3x b128 gap appearing at b8) should gate
    (re.compile(r".*_hbm_util_vs_ref_kernel_pct"), 0.45),
    (re.compile(r"^e2e_"), 0.25),
)  # everything else: _noise_floor's 0.05 device-bound default

# lower-is-better metric families: latencies (_ms) and durations (_s) —
# but NOT rates (`*_per_s`), which are higher-is-better despite the suffix
_LOWER_BETTER = re.compile(r"(_ms|_s|_ms_per_step)(_b\d+)?$")
_RATE = re.compile(r"_per_s(_b\d+)?$")


def _lower_is_better(key: str) -> bool:
    return bool(_LOWER_BETTER.search(key)) and not _RATE.search(key)


def load_archive(path) -> dict:
    """Read an archived bench line (either the raw JSON line or the driver's
    BENCH_r{N}.json wrapper, whose `parsed` key holds the line).

    `parsed` can be null when the driver archived a run that emitted no
    parseable line (observed r5) — `d.get("parsed") or d` returns the
    wrapper itself then, so consumers see a dict either way instead of the
    fast tier dying on None (VERDICT r5 ask #1a)."""
    d = json.loads(pathlib.Path(path).read_text())
    return d.get("parsed") or d


def is_null_parsed_wrapper(d: dict) -> bool:
    """True for a driver wrapper whose run produced no parseable line."""
    return "parsed" in d and d["parsed"] is None


def host_fingerprint() -> dict:
    """The host identity every emitted line archives (`host_cpu_model` +
    `host_cpu_cores`), so a later gate failure can distinguish "the code
    regressed" from "you are gating laptop numbers against CI numbers".
    Host-only micro-tiers are pure CPU timing — a different CPU model or
    core count moves them legitimately.
    Best-effort: unknowable fields are simply absent, never fabricated."""
    import os

    out: dict = {}
    model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for ln in f:
                if ln.lower().startswith(("model name", "hardware")):
                    model = ln.split(":", 1)[-1].strip()
                    break
    except OSError:
        pass
    if not model:  # non-Linux fallback
        import platform

        model = platform.processor() or platform.machine()
    if model:
        out["host_cpu_model"] = model
    cores = os.cpu_count()
    if cores:
        out["host_cpu_cores"] = int(cores)
    return out


def _check_number(key: str, v, problems: List[str]) -> None:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        problems.append(f"{key}: expected a number, got {type(v).__name__}")
    elif isinstance(v, float) and not math.isfinite(v):
        problems.append(f"{key}: non-finite value {v!r}")


def validate_tier_failures(v, problems: List[str]) -> None:
    if not isinstance(v, list):
        problems.append(f"tier_failures: expected a list, got "
                        f"{type(v).__name__}")
        return
    for i, entry in enumerate(v):
        if not isinstance(entry, dict):
            problems.append(f"tier_failures[{i}]: expected an object")
            continue
        for req in ("tier", "exc"):
            if not isinstance(entry.get(req), str):
                problems.append(f"tier_failures[{i}].{req}: expected a string")
        tail = entry.get("traceback_tail")
        if tail is not None and not isinstance(tail, str):
            problems.append(
                f"tier_failures[{i}].traceback_tail: expected a string")


def validate_line(d: dict) -> List[str]:
    """Typed-schema check of one bench line. Returns problems (empty=valid).

    The schema is field-name driven so old archives (r1: 4 fields) and new
    ones validate under the same rules: required core fields typed exactly,
    known string/list fields typed, `tier_failures`/`tier_skips` structured,
    every other field numeric and finite, and every `<key>_min` paired with
    `<key>_max` plus the base key."""
    problems: List[str] = []
    if not isinstance(d, dict):
        return [f"line: expected an object, got {type(d).__name__}"]
    for key, typ in _REQUIRED.items():
        if key not in d:
            problems.append(f"missing required field {key!r}")
        elif isinstance(d[key], bool) or not isinstance(d[key], typ):
            problems.append(f"{key}: expected {typ}, got "
                            f"{type(d[key]).__name__}")
    for key, v in d.items():
        if key in _REQUIRED:
            continue
        if v is None and key in _NULLABLE_FIELDS:
            continue
        if key in _STRING_FIELDS:
            if not isinstance(v, str):
                problems.append(f"{key}: expected a string")
        elif key in _LIST_OF_STR_FIELDS:
            if not (isinstance(v, list)
                    and all(isinstance(x, str) for x in v)):
                problems.append(f"{key}: expected a list of strings")
        elif key == "tier_failures":
            validate_tier_failures(v, problems)
        elif key == "tier_skips":
            if not (isinstance(v, dict)
                    and all(isinstance(k, str) and isinstance(x, str)
                            for k, x in v.items())):
                problems.append(f"{key}: expected an object of "
                                "tier name -> skip reason strings")
        elif key in ("metrics_snapshot", "fleet_snapshot"):
            # internal-gauge snapshots (obs subsystem): metrics_snapshot
            # from the e2e tier, fleet_snapshot from load_multiproc (the
            # flattened per-role roll-up — obs/fleet.py rollup()); both
            # are one flat string -> finite number object
            if not isinstance(v, dict):
                problems.append(f"{key}: expected an object")
            else:
                for mk, mv in v.items():
                    if not isinstance(mk, str):
                        problems.append(f"{key}: non-string key {mk!r}")
                    else:
                        _check_number(f"{key}.{mk}", mv, problems)
        else:
            _check_number(key, v, problems)
    for key in d:
        for suffix, other in (("_min", "_max"), ("_max", "_min")):
            if key.endswith(suffix):
                base = key[:-len(suffix)]
                if base not in d or f"{base}{other}" not in d:
                    problems.append(f"{key}: spread fields must come as "
                                    f"{base} + {base}_min + {base}_max")
    return problems


def validate_wrapper(d: dict) -> List[str]:
    """Validate a driver `{n, cmd, rc, tail, parsed}` wrapper. A null
    `parsed` is a tolerated shape (the run emitted no parseable line — loud
    in `rc`/`tail`, not a crash); a non-null `parsed` must validate as a
    line."""
    problems: List[str] = []
    for key, typ in (("rc", int), ("cmd", str)):
        if key in d and not isinstance(d[key], typ):
            problems.append(f"wrapper.{key}: expected {typ.__name__}")
    if d.get("parsed") is not None:
        problems += validate_line(d["parsed"])
    return problems


def validate_file(path) -> List[str]:
    """Validate an archive file of either shape (raw line or wrapper)."""
    d = json.loads(pathlib.Path(path).read_text())
    if not isinstance(d, dict):
        return [f"{path}: expected a JSON object"]
    if _WRAPPER_FIELDS & set(d) and "parsed" in d:
        return validate_wrapper(d)
    return validate_line(d)


# ------------------------------------------------------------ regression gate

def _noise_floor(key: str) -> float:
    for pat, floor in _DEFAULT_NOISE_FLOOR:
        if pat.match(key):
            return floor
    return 0.05


def _allowed_delta(key: str, baseline: dict) -> float:
    """Per-metric noise-aware threshold: the larger of the family's default
    floor and 1.5x the baseline's own archived in-run spread."""
    from symbiont_tpu.bench.stats import spread_fraction

    floor = _noise_floor(key)
    spread = spread_fraction(baseline, key)
    return max(floor, 1.5 * spread) if spread is not None else floor


def regression_gate(current: dict, baseline: dict,
                    metrics: Optional[List[str]] = None) -> List[str]:
    """Compare a run against a baseline archive. Returns one problem string
    per regressed metric (empty = gate passes).

    Gated metrics default to the intersection of both lines'
    `primary_metrics` declarations. Direction is
    inferred from the metric name (`*_ms`/`*_ms_per_step*`/`*_s` lower is
    better, everything else higher)."""
    if metrics is None:
        metrics = [m for m in current.get("primary_metrics", [])
                   if m in baseline.get("primary_metrics", [])]
        if not metrics:
            # nothing in common (e.g. a --quick line, or a pre-declaration
            # archive): a vacuous comparison must not read as a clean pass
            return ["no gateable primary metrics are declared by both "
                    "lines — nothing was compared"]
    problems: List[str] = []
    for key in metrics:
        cur, base = current.get(key), baseline.get(key)
        if not isinstance(base, (int, float)) or base == 0:
            continue  # baseline never measured it: nothing to gate against
        if not isinstance(cur, (int, float)):
            # a gated primary the baseline HAS but the current run lost is
            # the r5 failure mode itself — silently comparing the subset
            # would report a clean pass over a vanished metric
            problems.append(f"{key}: declared primary metric present in "
                            f"baseline ({base}) but missing from the "
                            "current run")
            continue
        allowed = _allowed_delta(key, baseline)
        lower_better = _lower_is_better(key)
        delta = (cur - base) / abs(base)
        regressed = delta > allowed if lower_better else -delta > allowed
        if regressed:
            problems.append(
                f"{key}: {cur} vs baseline {base} "
                f"({delta * 100:+.1f}%, allowed ±{allowed * 100:.0f}% "
                f"[{'lower' if lower_better else 'higher'} is better])")
    return problems


def gate_files(current_path, baseline_path) -> List[str]:
    """File-level gate: schema-validate both, then regression-compare. A
    null-parsed wrapper on EITHER side fails loud — an empty
    primary_metrics intersection would otherwise compare zero metrics and
    report a clean pass."""
    problems = [f"{current_path}: {p}" for p in validate_file(current_path)]
    problems += [f"{baseline_path}: {p}" for p in validate_file(baseline_path)]
    if problems:
        return problems
    for path in (current_path, baseline_path):
        if is_null_parsed_wrapper(json.loads(pathlib.Path(path).read_text())):
            problems.append(
                f"{path}: driver wrapper has parsed: null — the run "
                "emitted no parseable line, nothing to gate against")
    if problems:
        return problems
    return regression_gate(load_archive(current_path),
                           load_archive(baseline_path))
