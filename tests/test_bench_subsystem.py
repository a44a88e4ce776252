"""The bench subsystem (symbiont_tpu/bench/): tier isolation, repetition
stats, archive schema + gate, roofline dual ceilings, resource sampler.

The VERDICT r5 "done" bar this file encodes: a deliberately-injected tier
failure produces rc != 0 PLUS an archived `tier_failures` entry; a missing
declared primary metric alone also forces rc != 0; `load_archive` survives
the driver's `parsed: null` wrapper; and every emitted line validates
against the typed schema and names the device it ran on.
"""

import json
import os
import sys
import time
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from symbiont_tpu.bench import archive, roofline, sampler, stats, tiers  # noqa: E402
from symbiont_tpu.bench.cli import build_line  # noqa: E402

import bench  # noqa: E402


# --------------------------------------------------------------- tier registry

def _mini_registry():
    reg = {}

    def tier(name, primary=(), quick=False):
        def deco(fn):
            reg[name] = tiers.Tier(name, fn, tuple(primary), quick)
            return fn
        return deco
    return reg, tier


def test_injected_tier_failure_is_archived_and_rc_nonzero():
    """A tier that throws → structured tier_failures entry with the
    traceback tail, other tiers still run, rc != 0, and the emitted line
    both carries the entry and validates against the schema."""
    reg, tier = _mini_registry()

    @tier("ok_tier", primary=("ok_metric",))
    def ok_tier(results, ctx):
        results["ok_metric"] = 1.0

    @tier("bomb", primary=("bomb_metric",))
    def bomb(results, ctx):
        raise RuntimeError("deliberately injected")

    @tier("after_bomb")
    def after_bomb(results, ctx):
        results["after_ran"] = 1

    results = {}
    run = tiers.run_tiers(results, types.SimpleNamespace(), log=lambda *a: 0,
                          registry_override=reg)
    assert results["after_ran"] == 1, "a dead tier must not stop the others"
    assert run.rc != 0
    [fail] = [f for f in run.failures if f["tier"] == "bomb"]
    assert "RuntimeError: deliberately injected" in fail["exc"]
    assert "deliberately injected" in fail["traceback_tail"]
    # the missing-primary sweep also flags the bomb's absent metric
    run.failures.extend(
        tiers.missing_primary_metrics(results, run, registry_override=reg))
    assert any("bomb_metric" in f["exc"] for f in run.failures)
    line = build_line(results, run)
    assert any(f["tier"] == "bomb" for f in line["tier_failures"])
    assert archive.validate_line(line) == []


def test_missing_primary_metric_alone_forces_failure():
    """A tier that completes without raising but never produces a declared
    primary metric is a failure — the r5 driver's run lost e2e_gen_tok_per_s
    with rc=0 exactly this way."""
    reg, tier = _mini_registry()

    @tier("quiet_loss", primary=("vanished_metric",))
    def quiet_loss(results, ctx):
        pass  # completes "successfully", archives nothing

    results = {}
    run = tiers.run_tiers(results, types.SimpleNamespace(), log=lambda *a: 0,
                          registry_override=reg)
    assert run.rc == 0  # no exception...
    missing = tiers.missing_primary_metrics(results, run,
                                            registry_override=reg)
    assert len(missing) == 1 and "vanished_metric" in missing[0]["exc"]
    run.failures.extend(missing)
    assert run.rc != 0  # ...but the loss still forces a nonzero exit


def test_skipped_tier_primaries_are_exempt():
    reg, tier = _mini_registry()

    @tier("gated", primary=("tpu_only_metric",))
    def gated(results, ctx):
        return "not a TPU device"

    results = {}
    run = tiers.run_tiers(results, types.SimpleNamespace(), log=lambda *a: 0,
                          registry_override=reg)
    assert run.skips == {"gated": "not a TPU device"}
    assert tiers.missing_primary_metrics(results, run,
                                         registry_override=reg) == []
    assert run.rc == 0


# ------------------------------------------------------------------- archive

def test_load_archive_tolerates_null_parsed_wrapper(tmp_path):
    """Direct regression test for the r5 crash: the driver wrapper carried
    `"parsed": null` and `d.get("parsed", d)` returned None, giving
    AttributeError in every consumer (tests/test_perf_doc.py:50)."""
    p = tmp_path / "BENCH_rXX.json"
    p.write_text(json.dumps(
        {"n": 5, "cmd": "python bench.py", "rc": 0,
         "tail": "something went sideways", "parsed": None}))
    d = bench.load_archive(p)
    assert isinstance(d, dict)
    assert d.get("ts", 0) == 0  # consumers may .get() freely
    # the schema layer knows this shape explicitly
    assert archive.is_null_parsed_wrapper(json.loads(p.read_text()))
    assert archive.validate_file(p) == []


def test_load_archive_accepts_raw_line(tmp_path):
    """The driver wraps the line in {..., "parsed": {...}}; a raw line from
    `python bench.py > out.json` must load identically."""
    raw = {"metric": "m", "value": 1.5, "unit": "u", "vs_baseline": 2.0}
    p = tmp_path / "raw.json"
    p.write_text(json.dumps(raw))
    assert bench.load_archive(p) == raw
    wrapped = tmp_path / "wrapped.json"
    wrapped.write_text(json.dumps({"n": 1, "cmd": "c", "rc": 0, "tail": "",
                                   "parsed": raw}))
    assert bench.load_archive(wrapped) == raw
    assert archive.validate_file(wrapped) == []


def test_emitted_line_names_its_device_and_validates():
    """Every emitted line carries platform / device_kind / device count and
    the jax stack versions (symbiont_tpu/device.py DeviceInfo.report()) —
    a CPU run can never be read as a chip measurement — and the typed
    schema accepts exactly that shape."""
    from symbiont_tpu.device import DeviceInfo

    info = DeviceInfo("cpu", "cpu", 1, "0.9.0", "0.9.0", "0.0.34")
    run = tiers.TierRun()
    line = build_line({"mixed_corpus_emb_per_s": 10.0,
                       "mixed_corpus_emb_per_s_min": 9.0,
                       "mixed_corpus_emb_per_s_max": 11.0}, run,
                      info.report())
    assert line["platform"] == "cpu" and line["device_kind"] == "cpu"
    assert line["device_count"] == 1 and line["jax"] == "0.9.0"
    assert line["value"] == 10.0
    assert archive.validate_line(line) == []
    # the device fields are typed: a number where the kind belongs fails
    assert archive.validate_line(dict(line, device_kind=5))


def test_validate_line_catches_malformed_fields():
    good = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 2.0}
    assert archive.validate_line(good) == []
    assert archive.validate_line({}) != []
    bad_type = dict(good, rerank_pairs_per_s="fast")
    assert any("rerank_pairs_per_s" in p
               for p in archive.validate_line(bad_type))
    bad_nan = dict(good, x_ms=float("nan"))
    assert any("x_ms" in p for p in archive.validate_line(bad_nan))
    orphan_min = dict(good, y_ms_min=1.0)
    assert any("y_ms_min" in p for p in archive.validate_line(orphan_min))
    bad_failures = dict(good, tier_failures=[{"tier": "x"}])  # no exc
    assert any("tier_failures" in p
               for p in archive.validate_line(bad_failures))


def test_regression_gate_noise_aware():
    base = {"primary_metrics": ["compute_only_emb_per_s",
                                "tinyllama_1b_ms_per_step_b128",
                                "e2e_ingest_emb_per_s",
                                "mixed_corpus_emb_per_s"],
            "compute_only_emb_per_s": 36000.0,
            "tinyllama_1b_ms_per_step_b128": 10.0,
            "e2e_ingest_emb_per_s": 1500.0,
            "e2e_ingest_emb_per_s_min": 1200.0,
            "e2e_ingest_emb_per_s_max": 1800.0,
            "mixed_corpus_emb_per_s": 5000.0}
    cur = dict(base)
    # within noise: device-bound -2%, ms/step +2%
    cur["compute_only_emb_per_s"] = 35300.0
    cur["tinyllama_1b_ms_per_step_b128"] = 10.2
    assert archive.regression_gate(cur, base) == []
    # device-bound -20% → regression (higher is better)
    cur2 = dict(base, compute_only_emb_per_s=29000.0)
    assert any("compute_only_emb_per_s" in p
               for p in archive.regression_gate(cur2, base))
    # ms/step +20% → regression (lower is better)
    cur3 = dict(base, tinyllama_1b_ms_per_step_b128=12.0)
    assert any("ms_per_step" in p for p in archive.regression_gate(cur3, base))
    # e2e ingest -35%: inside 1.5x the baseline's own archived in-run
    # spread ((1800-1200)/1500 = 40% → 60% allowed) → NOT a regression
    cur4 = dict(base, e2e_ingest_emb_per_s=975.0)
    assert archive.regression_gate(cur4, base) == []
    # no metric family is exempt: the transfer-inclusive embed rate gates too
    cur5 = dict(base, mixed_corpus_emb_per_s=1000.0)
    assert any("mixed_corpus_emb_per_s" in p
               for p in archive.regression_gate(cur5, base))


# --------------------------------------------------------------------- stats

def test_stats_record_min_max_and_floor():
    results = {}
    med = stats.record(results, "e2e_gen_tok_per_s", [2000.0, 1900.0, 2100.0])
    assert med == 2000.0
    assert results["e2e_gen_tok_per_s_min"] == 1900.0
    assert results["e2e_gen_tok_per_s_max"] == 2100.0
    with pytest.raises(ValueError):
        stats.record(results, "too_few", [1.0, 2.0])
    assert stats.spread_fraction(results, "e2e_gen_tok_per_s") == \
        pytest.approx(0.1)
    assert stats.spread_fraction(results, "absent") is None


# ------------------------------------------------------------------ roofline

def test_roofline_no_point_sets_its_own_ceiling():
    """The r5 flaw, reconstructed: the fastest stream observed is a decode
    point. Against `vs_best_observed` it must be graded by the best OTHER
    stream (here the reference kernel), not by itself — so it reads >100%
    (honest overshoot) instead of exactly 100.0 (by construction)."""
    results = {
        "hbm_stream_gbps_measured": 517.3,
        "tinyllama_1b_hbm_gbps": 714.5,
        "tinyllama_1b_ms_per_step_noise_limited": 0,
        "tinyllama_1b_hbm_gbps_b128": 241.4,
        "tinyllama_1b_ms_per_step_noise_limited_b128": 0,
    }
    roofline.annotate(results)
    assert results["hbm_stream_gbps_ceiling"] == 714.5
    # b8 vs ref kernel AND vs best-other both divide by 517.3, never 714.5
    assert results["tinyllama_1b_hbm_util_vs_ref_kernel_pct"] == \
        pytest.approx(100 * 714.5 / 517.3, abs=0.1)
    assert results["tinyllama_1b_hbm_util_vs_best_observed_pct"] == \
        pytest.approx(100 * 714.5 / 517.3, abs=0.1)
    assert results["tinyllama_1b_hbm_util_vs_best_observed_pct"] != 100.0
    # b128 IS graded against the b8 point (the best other observed)
    assert results["tinyllama_1b_hbm_util_vs_best_observed_pct_b128"] == \
        pytest.approx(100 * 241.4 / 714.5, abs=0.1)


def test_roofline_noise_limited_points_never_raise_ceilings():
    results = {
        "hbm_stream_gbps_measured": 500.0,
        "gpt2_124m_hbm_gbps": 2000.0,  # wild noise-limited estimate
        "gpt2_124m_ms_per_step_noise_limited": 1,
        "tinyllama_1b_hbm_gbps_b32": 400.0,
        "tinyllama_1b_ms_per_step_noise_limited_b32": 0,
    }
    roofline.annotate(results)
    assert results["hbm_stream_gbps_ceiling"] == 500.0
    assert results["tinyllama_1b_hbm_util_vs_best_observed_pct_b32"] == \
        pytest.approx(80.0)


def test_decode_step_bytes_breakdown():
    """Weights dominate at b8 (>95%), KV grows linearly with batch, and the
    analytic parameter count matches the models' named sizes."""
    bd8 = roofline.decode_step_bytes("tinyllama_1b", 8, 64, 128)
    bd128 = roofline.decode_step_bytes("tinyllama_1b", 128, 64, 128)
    assert bd8["weight"] == bd128["weight"]  # shared by all rows
    assert bd8["weight"] / sum(bd8.values()) > 0.95
    assert bd128["kv"] == pytest.approx(16 * bd8["kv"])
    # ~1.1B params at bf16 ≈ 2.2 GB; GPT-2 124M ≈ 250 MB
    assert 2.0e9 < bd8["weight"] < 2.4e9
    gpt2 = roofline.analytic_param_bytes(roofline.GEOMETRIES["gpt2_124m"])
    assert 2.3e8 < gpt2 < 2.7e8


# ------------------------------------------------------------------- sampler

def test_resource_sampler_accounts_own_process():
    s = sampler.ResourceSampler({"me": [os.getpid()]}).start()
    # burn a little CPU and write some bytes so the deltas are nonzero
    x = 0
    t0 = time.time()
    while time.time() - t0 < 0.05:
        x += sum(i * i for i in range(1000))
    window = s.stop()
    assert window["wall_s"] >= 0.05
    assert window.get("cpu_s_me", 0) >= 0
    assert window["cpu_s_engine_host"] >= 0
    results = {}
    sampler.archive_decomposition(results, "e2e_ingest", window)
    assert "e2e_ingest_cpu_s_engine_host" in results
    assert "e2e_ingest_host_cpu_utilization" in results
    assert archive.validate_line(
        {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
         **results}) == []


def test_sampler_dead_pid_is_not_fatal():
    s = sampler.ResourceSampler({"ghost": [99999999]}).start()
    window = s.stop()
    assert "cpu_s_ghost" not in window
    assert "cpu_s_engine_host" in window


# ------------------------------------------------------------------ CLI glue

def test_cli_gate_and_validate_commands(tmp_path):
    from symbiont_tpu.bench import cli

    base = {"metric": "m", "value": 100.0, "unit": "u", "vs_baseline": 1.0,
            "primary_metrics": ["compute_only_emb_per_s"],
            "compute_only_emb_per_s": 100.0}
    cur_bad = dict(base, compute_only_emb_per_s=50.0)
    bp = tmp_path / "base.json"
    cp = tmp_path / "cur.json"
    bp.write_text(json.dumps(base))
    cp.write_text(json.dumps(cur_bad))
    assert cli.main(["--validate", str(bp), str(cp)]) == 0
    assert cli.main(["--gate", str(cp), str(bp)]) == 1  # regression
    assert cli.main(["--gate", str(bp), str(bp)]) == 0  # self-compare clean
    # a null-parsed wrapper as the CURRENT run fails the gate loudly
    np_ = tmp_path / "null.json"
    np_.write_text(json.dumps({"n": 1, "cmd": "c", "rc": 0, "tail": "",
                               "parsed": None}))
    assert cli.main(["--gate", str(np_), str(bp)]) == 1


def test_env_injected_failure_hook(monkeypatch):
    """The arms-length proof command: SYMBIONT_BENCH_INJECT_FAILURE=1
    registers a quick tier that throws, so `python bench.py --quick` under
    that env exits nonzero with an archived `injected_failure` entry."""
    from symbiont_tpu.bench import cli

    monkeypatch.setenv("SYMBIONT_BENCH_INJECT_FAILURE", "1")
    cli._maybe_register_injection()
    try:
        reg = {"injected_failure": tiers.registry()["injected_failure"]}
        assert reg["injected_failure"].quick  # fires even under --quick
        results = {}
        run = tiers.run_tiers(results, types.SimpleNamespace(), quick=True,
                              log=lambda *a: 0, registry_override=reg)
        assert run.rc != 0
        line = build_line(results, run)
        [fail] = line["tier_failures"]
        assert fail["tier"] == "injected_failure"
        assert "deliberately injected" in fail["exc"]
        assert archive.validate_line(line) == []
    finally:
        tiers._REGISTRY.pop("injected_failure", None)


def test_cli_main_end_to_end_stub_registry(monkeypatch, capsys):
    """Full `cli.main` path (the thing `python bench.py` runs) against a
    stubbed registry: a clean run prints a schema-valid line with empty
    tier_failures and exits 0; an injected bomb makes the SAME entrypoint
    exit nonzero with the failure archived in the printed line."""
    from symbiont_tpu.bench import cli
    # pre-import the real tier modules so they land in sys.modules NOW and
    # register into the ORIGINAL registry — main()'s imports then no-op and
    # only the stubs below exist in the patched registry
    from symbiont_tpu.bench import (  # noqa: F401
        chaos, compute, decode, e2e, engine_plane, load, multichip, obs,
        quant, serialization)

    monkeypatch.setattr(tiers, "_REGISTRY", {})

    @tiers.register("stub_ok", primary_metrics=("stub_metric",), quick=True)
    def stub_ok(results, ctx):
        results["stub_metric"] = 1.0

    rc = cli.main(["--quick"])
    line = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert line["tier_failures"] == []
    assert archive.validate_line(line) == []

    @tiers.register("stub_bomb", primary_metrics=("never_metric",),
                    quick=True)
    def stub_bomb(results, ctx):
        raise RuntimeError("kaboom")

    rc = cli.main(["--quick"])
    line = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any(f["tier"] == "stub_bomb" and "kaboom" in f["exc"]
               for f in line["tier_failures"])
    assert archive.validate_line(line) == []


def test_cli_only_runs_named_tier(monkeypatch, capsys):
    """`--only TIER` (scripts/multichip.sh's fast loop) runs just the named
    tier, archives every other tier under tier_skips (exempting their
    primaries) and rejects unknown names."""
    from symbiont_tpu.bench import cli
    from symbiont_tpu.bench import (  # noqa: F401
        chaos, compute, decode, e2e, engine_plane, load, multichip, obs,
        quant, serialization)

    monkeypatch.setattr(tiers, "_REGISTRY", {})

    @tiers.register("stub_a", primary_metrics=("a_metric",))
    def stub_a(results, ctx):
        results["a_metric"] = 1.0

    @tiers.register("stub_b", primary_metrics=("b_metric",))
    def stub_b(results, ctx):
        raise RuntimeError("must never run under --only stub_a")

    rc = cli.main(["--only", "stub_a"])
    line = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert line["tier_failures"] == []
    assert line["a_metric"] == 1.0
    assert "stub_b" in line["tier_skips"]
    assert "b_metric" not in line["primary_metrics"]

    assert cli.main(["--only", "no_such_tier"]) == 2
    capsys.readouterr()


def test_gate_rejects_null_parsed_on_either_side(tmp_path):
    """A null-parsed wrapper as BASELINE must fail the gate too: the empty
    primary_metrics intersection would otherwise compare zero metrics and
    report a clean pass (review finding)."""
    good = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
            "primary_metrics": ["compute_only_emb_per_s"],
            "compute_only_emb_per_s": 1.0}
    gp = tmp_path / "good.json"
    gp.write_text(json.dumps(good))
    np_ = tmp_path / "null.json"
    np_.write_text(json.dumps({"n": 1, "cmd": "c", "rc": 0, "tail": "",
                               "parsed": None}))
    assert any("parsed: null" in p
               for p in archive.gate_files(gp, np_))
    assert any("parsed: null" in p
               for p in archive.gate_files(np_, gp))


def test_validate_line_catches_orphan_max():
    good = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 2.0}
    orphan_max = dict(good, y_ms=1.0, y_ms_max=2.0)  # _min missing
    assert any("y_ms_max" in p for p in archive.validate_line(orphan_max))
    full = dict(good, y_ms=1.0, y_ms_min=0.5, y_ms_max=2.0)
    assert archive.validate_line(full) == []


def test_sampler_archives_its_own_wall():
    results = {}
    sampler.archive_decomposition(
        results, "e2e_ingest",
        {"wall_s": 10.0, "cpu_s_broker": 2.0, "cpu_s_engine_host": 3.0,
         "io_bytes_broker": 50_000_000})
    assert results["e2e_ingest_wall_s"] == 10.0
    assert results["e2e_ingest_host_cpu_utilization"] == 0.5
    assert results["e2e_ingest_bus_mb_per_s"] == 5.0


def test_gate_flags_primary_missing_from_current_run():
    """A gated primary the baseline HAS but the current run lost must be a
    gate failure, not a silent subset comparison (review finding — the r5
    vanished-metric class applied to the gate itself)."""
    base = {"primary_metrics": ["e2e_gen_tok_per_s"],
            "e2e_gen_tok_per_s": 2000.0}
    cur = {"primary_metrics": ["e2e_gen_tok_per_s"]}  # field vanished
    assert any("missing from the current run" in p
               for p in archive.regression_gate(cur, base))
    # absent from the BASELINE too → nothing to gate against, no problem
    assert archive.regression_gate(cur, {"primary_metrics":
                                         ["e2e_gen_tok_per_s"]}) == []


def test_declared_primary_metrics_single_source():
    """The archived primary_metrics list derives from the tier registry
    (plus the roofline-produced utilization primary) — the same source
    missing_primary_metrics enforces, so the two cannot drift."""
    from symbiont_tpu.bench import cli
    # the real tier modules must be registered for this check
    from symbiont_tpu.bench import (  # noqa: F401
        chaos, compute, decode, e2e, engine_plane, load, multichip, obs,
        quant, serialization)

    declared = cli.declared_primary_metrics()
    assert cli.ROOFLINE_PRIMARY in declared
    for tier in tiers.registry().values():
        for m in tier.primary_metrics:
            assert m in declared
    # the noise floor for the drifting-denominator primary is drift-sized
    assert archive._noise_floor(cli.ROOFLINE_PRIMARY) == 0.45


def test_gate_tolerates_ref_kernel_denominator_drift():
    """Two no-change runs straddling the documented 517->715 GB/s reference
    kernel drift move util_vs_ref_kernel ~28%; the gate must not call that
    a regression (review finding)."""
    base = {"primary_metrics": ["tinyllama_1b_hbm_util_vs_ref_kernel_pct"],
            "tinyllama_1b_hbm_util_vs_ref_kernel_pct": 138.0}
    cur = dict(base, tinyllama_1b_hbm_util_vs_ref_kernel_pct=100.0)  # -27.5%
    assert archive.regression_gate(cur, base) == []
    collapsed = dict(base, tinyllama_1b_hbm_util_vs_ref_kernel_pct=45.0)
    assert archive.regression_gate(collapsed, base) != []  # beyond drift


def test_gate_vacuous_comparison_is_a_failure():
    """A gate that compared ZERO metrics must say so, not print a clean
    pass — the vacuous-pass path is how a --quick line (which declares only
    what it measured) would otherwise 'pass' against a full baseline."""
    a = {"primary_metrics": [], "value": 1.0}
    b = {"primary_metrics": ["compute_only_emb_per_s"],
         "compute_only_emb_per_s": 1.0}
    assert any("nothing was compared" in p
               for p in archive.regression_gate(a, b))


def test_declared_primary_metrics_excludes_skipped_tiers():
    """A --no-e2e / CPU-only line must not declare metrics its run
    deliberately skipped, or the gate would flag the legitimate skip as a
    lost metric (review finding)."""
    from symbiont_tpu.bench import cli
    from symbiont_tpu.bench import (  # noqa: F401
        chaos, compute, decode, e2e, engine_plane, load, multichip, obs,
        quant, serialization)

    full = cli.declared_primary_metrics()
    no_e2e = cli.declared_primary_metrics(skips={"e2e": "skipped by flag"})
    assert [m for m in full if m.startswith("e2e_")]
    assert not [m for m in no_e2e if m.startswith("e2e_")]
    # skipping an ingredient tier of the roofline primary drops it too
    cpu_only = cli.declared_primary_metrics(
        skips={"stream_ceiling": "not a TPU", "compute_mfu": "not a TPU"})
    assert cli.ROOFLINE_PRIMARY not in cpu_only
    assert "mfu_compute_only_pct" not in cpu_only


def test_bulk_ratio_fields_decoupled_from_registration_order():
    """The e2e÷bulk ratio no longer rides on the engine_plane tier having
    run EARLIER IN THE SAME PROCESS (the PR 6 registration-order coupling):
    with the prerequisite absent it archives an explicit null plus a note;
    with it present, the ratio — and the null+note shape schema-validates."""
    from symbiont_tpu.bench.e2e import bulk_ratio_fields

    absent = bulk_ratio_fields({"e2e_ingest_emb_per_s": 1800.0})
    assert absent["e2e_ingest_vs_bulk_x"] is None
    assert "ingest_10k_emb_per_s absent" in absent["e2e_ingest_vs_bulk_note"]

    present = bulk_ratio_fields({"e2e_ingest_emb_per_s": 1800.0,
                                 "ingest_10k_emb_per_s": 3000.0})
    assert present == {"e2e_ingest_vs_bulk_x": 0.6}

    line = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
            **absent}
    assert archive.validate_line(line) == []
    # null remains EXPLICIT: any other field archived as null still fails
    bad = dict(line, e2e_search_p50_ms=None)
    assert archive.validate_line(bad)


def test_quant_tier_registered_with_primaries():
    from symbiont_tpu.bench import quant  # noqa: F401

    reg = tiers.registry()
    assert "quant" in reg
    assert set(reg["quant"].primary_metrics) == {
        "quant_embed_cos_int8", "quant_embed_int8_vs_bf16_x",
        "quant_decode_int8kv_vs_bf16_x"}
    assert not reg["quant"].quick  # device tier: full runs only


# ------------------------------------------------------ load-tier seed knobs

def test_load_seed_flag_parsing():
    """--chaos-seed/--load-seed parse to ints, default 0, and reject
    garbage loudly — a typo'd seed must not silently replay seed 0."""
    from symbiont_tpu.bench import cli

    assert cli.parse_seed_flag(["--load-seed", "7"], "--load-seed") == 7
    assert cli.parse_seed_flag([], "--load-seed") == 0
    with pytest.raises(ValueError):
        cli.parse_seed_flag(["--load-seed", "banana"], "--load-seed")
    with pytest.raises(ValueError):
        cli.parse_seed_flag(["--load-seed"], "--load-seed")


def test_cli_seed_flags_reach_tier_ctx(monkeypatch, capsys):
    """The seeds ride ctx into every tier (the load tier archives them as
    load_seed/chaos_seed so a red run replays bit-for-bit), and a
    malformed seed is usage (rc 2), not a traceback."""
    from symbiont_tpu.bench import cli
    from symbiont_tpu.bench import (  # noqa: F401
        chaos, compute, decode, e2e, engine_plane, load, multichip, obs,
        quant, serialization)

    monkeypatch.setattr(tiers, "_REGISTRY", {})
    seen = {}

    @tiers.register("seed_probe", primary_metrics=("probe_ok",), quick=True)
    def probe(results, ctx):
        seen["load"] = ctx.load_seed
        seen["chaos"] = ctx.chaos_seed
        results["probe_ok"] = 1.0

    rc = cli.main(["--quick", "--load-seed", "11", "--chaos-seed", "42"])
    capsys.readouterr()
    assert rc == 0 and seen == {"load": 11, "chaos": 42}
    assert cli.main(["--quick", "--load-seed", "banana"]) == 2
    capsys.readouterr()
