"""The trace reducer on a small trace recorded on the v5e
(`tests/record_trace.py`, PR 24): five calls of a jitted `fn` (four of them
inside the window annotation) and three of a
jitted `prefill` inside the harness's window annotation. The numbers the
reducer made of it on the day are kept beside it; the structural facts are
asserted outright."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce  # noqa: E402

TRACE = HERE / "recorded" / "small.xplane.pb"
EXPECTED = json.loads((HERE / "recorded" / "small.expected.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_programs_are_found_by_their_jitted_names(reduced):
    assert trace_reduce.module_seconds(reduced, r"^jit_fn$")[0] == 4
    assert trace_reduce.module_seconds(reduced, r"^jit_prefill$")[0] == 3
    assert trace_reduce.module_seconds(reduced, r"^jit_absent$") == (0, 0)


def test_busy_is_a_union_inside_the_window(reduced):
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    per_module = sum(m["seconds"] for m in reduced["modules"].values())
    # ops run inside their programs: the union cannot exceed the programs
    assert reduced["busy_s"] <= per_module * 1.001
    assert any(p.startswith("/device:TPU") for p in reduced["planes"])


def test_numbers_repeat(reduced):
    for key in ("window_s", "busy_s"):
        assert reduced[key] == pytest.approx(EXPECTED[key], rel=1e-9)
    assert reduced["modules"] == EXPECTED["modules"]
    assert reduced["device_ops"] == EXPECTED["device_ops"]


def test_breakdown_lists_are_short_and_sorted(reduced):
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10
    secs = [s for _, s in reduced["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [s for _, s in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and all(g > 0 for g in gaps)
    # the five `fn` calls were 2 ms apart: the longest gaps are those sleeps
    assert gaps[0] >= 0.002


def test_busy_merges_overlapping_intervals():
    import numpy as np

    busy, gaps = trace_reduce._busy_and_gaps(
        np.array([0, 3, 10]), np.array([5, 8, 12]), 0, 20)
    assert busy == 10 / 1e12
    assert gaps == [(2, 8, 10), (8, 12, 20)]
