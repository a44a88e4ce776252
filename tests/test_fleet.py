"""Fleet telemetry plane (obs/fleet.py): cross-process metrics federation
and trace stitching over the bus.

Unit layer: exporter delta/sampling semantics, aggregator merge + role
bounds, the federated exposition (role labels), the /api/fleet roll-up's
procsup folding, per-role SLO judgment, and per-role Chrome process lanes.

Integration layer: a REAL two-process deployment — pybroker + two runner
processes (api-only gateway + perception worker; no engines anywhere) —
must return a client-carried trace as ONE stitched tree from the gateway
and expose BOTH roles in one role-labeled /metrics scrape.

C++ parity: the native heartbeat helpers (common.hpp) compile against a
stub json declaration set (GCC 10-safe — no json.hpp, no float to_chars)
and produce the byte-identical subject + payload the Python runner
publishes.
"""

import asyncio
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

import pytest

from symbiont_tpu import subjects
from symbiont_tpu.obs.fleet import (
    FleetAggregator,
    TelemetryExporter,
    subscribe_telemetry,
)
from symbiont_tpu.obs.trace_store import SpanRecord, TraceStore
from symbiont_tpu.utils.telemetry import Metrics

REPO = Path(__file__).resolve().parent.parent


class _FakeBus:
    def __init__(self):
        self.msgs = []

    async def publish(self, subject, data, headers=None):
        self.msgs.append((subject, data))


def _exporter(bus, **kw):
    defaults = dict(role="worker", publish_s=5.0,
                    registry=Metrics(), store=TraceStore(256))
    defaults.update(kw)
    return TelemetryExporter(lambda: bus, **defaults)


def _span(i, name="perception.handle", fields=None):
    return SpanRecord("t1", f"s{i}", None, name, 100.0 + i, 2.0, "ok",
                      fields=dict(fields or {}))


# ------------------------------------------------------------ exporter


def test_exporter_full_then_delta_then_quiet():
    """First publish is a FULL snapshot; later publishes carry only the
    keys that changed; the baseline only advances on successful publish."""
    async def main():
        bus = _FakeBus()
        exp = _exporter(bus)
        exp.registry.inc("a.ticks")
        exp.registry.inc("b.ticks")
        await exp.publish_once()
        first = json.loads(bus.msgs[-1][1])
        assert first["full"] is True
        assert "counter.a.ticks" in first["metrics"]
        exp.registry.inc("a.ticks")  # only a changes
        await exp.publish_once()
        second = json.loads(bus.msgs[-1][1])
        assert second["full"] is False
        assert "counter.a.ticks" in second["metrics"]
        assert "counter.b.ticks" not in second["metrics"]
        await exp.publish_once()  # nothing changed except fleet.* counters
        third = json.loads(bus.msgs[-1][1])
        assert "counter.a.ticks" not in third["metrics"]

    asyncio.run(main())


def test_exporter_span_ring_samples_and_counts_drops():
    """Backpressure is SAMPLING with a counter, never a queue: the pending
    ring keeps the newest pending_max spans, drops are counted, and one
    publish carries at most spans_max."""
    async def main():
        bus = _FakeBus()
        exp = _exporter(bus, spans_max=4, pending_max=8)
        exp.store.add_tap(exp._tap)
        for i in range(20):
            exp.store.record(_span(i))
        assert len(exp._pending) == 8
        assert exp.registry.get("fleet.spans_dropped") == 12
        await exp.publish_once()
        batch = json.loads(bus.msgs[-1][1])
        assert len(batch["spans"]) == 4
        # remaining pending spans ride the NEXT publish
        await exp.publish_once()
        assert len(json.loads(bus.msgs[-1][1])["spans"]) == 4

    asyncio.run(main())


def test_exporter_never_reexports_remote_fed_spans():
    """An aggregator+exporter process (the API role, the supervisor) feeds
    REMOTE spans into its local store — the tap must skip them or every
    span would loop through the fleet forever."""
    async def main():
        bus = _FakeBus()
        exp = _exporter(bus)
        exp.store.add_tap(exp._tap)
        exp.store.record(_span(1, fields={"role": "embed", "pid": 7}))
        exp.store.record(_span(2))
        assert len(exp._pending) == 1
        assert exp._pending[0].span_id == "s2"

    asyncio.run(main())


def test_exporter_failure_is_counted_skip_and_delta_survives():
    """A publish failure (no bus / broker gap) counts, does not queue, and
    does NOT advance the delta baseline — the changed keys arrive with the
    next successful round instead of being lost."""
    async def main():
        exp = _exporter(None)
        exp.registry.inc("a.ticks")
        assert await exp.publish_once() is False
        assert exp.registry.get("fleet.publish_failures") == 1
        bus = _FakeBus()
        exp.bus_fn = lambda: bus
        await exp.publish_once()
        assert "counter.a.ticks" in json.loads(bus.msgs[-1][1])["metrics"]

    asyncio.run(main())


# ---------------------------------------------------------- aggregator


def _spans_payload(role, spans, pid=1234):
    return json.dumps({"role": role, "pid": pid, "ts": 0.0,
                       "spans": [s.to_dict() for s in spans]}).encode()


def test_aggregator_stitches_remote_spans_with_role_pid_fields():
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=Metrics())
    agg.handle(f"{subjects.SYS_TELEMETRY_SPANS}.embed",
               _spans_payload("embed", [_span(1)], pid=77))
    [rec] = agg.store.spans_for("t1")
    assert rec.fields["role"] == "embed" and rec.fields["pid"] == 77
    # remote durations land as role-labeled histograms (watchdog food)
    [(labels, summary)] = agg.registry.histogram_summaries(
        "span.perception.handle.ms")
    assert labels == {"role": "embed"} and summary["count"] == 1


def test_aggregator_ignores_own_role_and_bounds_roles():
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=Metrics(), max_roles=2)
    agg.handle(f"{subjects.SYS_TELEMETRY_SPANS}.api",
               _spans_payload("api", [_span(1)]))
    assert len(agg.store) == 0  # own role: local ring is the fresher view
    for i in range(4):
        agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.r{i}",
                   json.dumps({"role": f"r{i}", "full": True,
                               "metrics": {"gauge.x": 1.0}}).encode())
    assert len(agg.role_snapshots()) == 2
    assert agg.registry.get("fleet.role_overflow") == 2


def test_aggregator_full_snapshot_replaces_delta_updates():
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=Metrics())

    def send(full, metrics):
        agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.w",
                   json.dumps({"role": "w", "full": full,
                               "metrics": metrics}).encode())

    send(True, {"gauge.a": 1.0, "gauge.b": 2.0})
    send(False, {"gauge.a": 5.0})
    assert agg.role_snapshots()["w"] == {"gauge.a": 5.0, "gauge.b": 2.0}
    send(True, {"gauge.a": 6.0})  # full REPLACES (b was retired remotely)
    assert agg.role_snapshots()["w"] == {"gauge.a": 6.0}


def test_rollup_folds_procsup_verdicts_into_target_roles():
    """procsup.up{role=X} gauges (exported by the supervisor under ITS
    role) fold into role X's /api/fleet entry — the broker's PING-probe
    verdict included, a role that never published telemetry included."""
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=Metrics())
    agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.procsup", json.dumps({
        "role": "procsup", "full": True, "pid": 1, "metrics": {
            'gauge.procsup.up{role="broker"}': 1.0,
            'gauge.procsup.up{role="embed"}': 0.0,
            'gauge.procsup.heartbeat_age_s{role="embed"}': 9.5,
            'counter.procsup.restarts{role="embed"}': 3.0,
            'counter.procsup.hangs{role="embed"}': 1.0,
        }}).encode())
    roles = agg.rollup()["roles"]
    assert roles["broker"]["up"] == 1.0
    embed = roles["embed"]
    assert embed["up"] == 0.0
    assert embed["heartbeat_age_s"] == 9.5
    assert embed["restarts"] == 3.0
    assert embed["hangs"] == 1.0
    # the supervisor itself appears as a telemetry role too
    assert "procsup" in roles


def test_render_fleet_exposition_role_labels():
    """Every series carries the role that produced it; a series whose OWN
    labels already name a role (procsup.up{role=broker}) keeps naming its
    TARGET — explicit labels win over the federation label."""
    reg = Metrics()
    reg.inc("bus.consumed", labels={"service": "api"})
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=reg)
    agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.embed", json.dumps({
        "role": "embed", "full": True, "metrics": {
            'counter.bus.consumed{service="preprocessing"}': 7.0,
            "gauge.batcher.queue_depth": 3.0,
            "hist.span.preprocessing.handle.ms.p99": 42.0,
        }}).encode())
    agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.procsup", json.dumps({
        "role": "procsup", "full": True, "metrics": {
            'gauge.procsup.up{role="broker"}': 1.0,
        }}).encode())
    out = agg.render_exposition()
    assert ('symbiont_bus_consumed_total{role="api",service="api"} 1'
            in out)
    assert ('symbiont_bus_consumed_total{role="embed",'
            'service="preprocessing"} 7' in out)
    # legacy dot-prefix folding applies to remote keys exactly as local
    assert 'symbiont_queue_depth{role="embed",service="batcher"} 3' in out
    # snapshot span stats are deliberately NOT merged (they federate via
    # the span path into locally-synthesized role-labeled histograms —
    # merging both would duplicate series and kill the whole scrape)
    assert ('symbiont_span_duration_ms{quantile="0.99",role="embed",'
            'service="preprocessing",span="preprocessing.handle"}'
            not in out)
    assert 'symbiont_procsup_up{role="broker"} 1' in out
    # exposition stays family-grouped (one TYPE line per family)
    assert out.count("# TYPE symbiont_bus_consumed_total counter") == 1


def test_exposition_has_no_duplicate_series_with_span_snapshots():
    """Review regression: a role's span batch feeds LOCAL role-labeled
    span histograms while its metrics snapshot carries the same hist
    stats — both merged would emit duplicate series under one label set,
    and a real Prometheus scraper rejects the WHOLE exposition on the
    first duplicate sample. The snapshot copy (span durations + slo.*)
    must be skipped in favor of the locally-synthesized series."""
    agg = FleetAggregator(local_role="api", store=TraceStore(64),
                          registry=Metrics())
    agg.handle(f"{subjects.SYS_TELEMETRY_SPANS}.embed",
               _spans_payload("embed", [_span(1)]))
    agg.handle(f"{subjects.SYS_TELEMETRY_METRICS}.embed", json.dumps({
        "role": "embed", "full": True, "metrics": {
            "hist.span.perception.handle.ms.p50": 9.0,
            "hist.span.perception.handle.ms.p99": 9.0,
            "hist.span.perception.handle.ms.count": 1.0,
            "hist.span.perception.handle.ms.min": 9.0,
            "hist.span.perception.handle.ms.max": 9.0,
            'gauge.slo.p99_ms{span="api.search"}': 9.0,
            'counter.slo.breaches{span="api.search"}': 1.0,
            "gauge.mesh.devices": 1.0,  # non-span series DO merge
        }}).encode())
    out = agg.render_exposition()
    samples = [line.split(" ")[0] for line in out.splitlines()
               if line and not line.startswith("#")]
    dupes = {s for s in samples if samples.count(s) > 1}
    assert not dupes, dupes
    # the locally-synthesized per-role span series is the one present
    assert ('symbiont_span_duration_ms_count{role="embed",'
            'service="perception",span="perception.handle"} 1' in out)
    assert 'symbiont_mesh_devices{role="embed"} 1' in out


def test_exporter_truncated_full_snapshot_rotates_not_loses():
    """Review regression: a FULL snapshot truncated at metrics_max must
    not permanently lose the stable keys past the cutoff — removal from
    the baseline makes successive deltas rotate through the remainder
    until the aggregator has every key."""
    async def main():
        bus = _FakeBus()
        exp = _exporter(bus, metrics_max=10, full_every=1000)
        agg = FleetAggregator(local_role="api", store=TraceStore(64),
                              registry=Metrics())
        for i in range(20):
            exp.registry.gauge_set(f"stable.g{i:02d}", float(i))
        for _ in range(8):  # several rounds, values never change
            await exp.publish_once()
            subject, payload = bus.msgs[-1]
            agg.handle(subject, payload)
        merged = agg.role_snapshots()["worker"]
        missing = [f"gauge.stable.g{i:02d}" for i in range(20)
                   if f"gauge.stable.g{i:02d}" not in merged]
        assert not missing, missing

    asyncio.run(main())


def test_exporter_truncation_rotates_under_continuous_churn():
    """Review regression: when EVERY key changes EVERY round (delta always
    oversized), a fixed sorted-prefix truncation would starve the
    alphabetically-late keys forever — the rotating window must cover the
    whole key space within a couple of rounds anyway."""
    async def main():
        bus = _FakeBus()
        exp = _exporter(bus, metrics_max=10, full_every=1000)
        agg = FleetAggregator(local_role="api", store=TraceStore(64),
                              registry=Metrics())
        for rnd in range(6):
            for i in range(20):  # every gauge churns every round
                exp.registry.gauge_set(f"churn.g{i:02d}", float(rnd * 100 + i))
            await exp.publish_once()
            agg.handle(*bus.msgs[-1])
        merged = agg.role_snapshots()["worker"]
        missing = [f"gauge.churn.g{i:02d}" for i in range(20)
                   if f"gauge.churn.g{i:02d}" not in merged]
        assert not missing, missing

    asyncio.run(main())


def test_exporter_repends_spans_when_publish_dies_midway():
    """Review regression: the bus dying BETWEEN the metrics and spans
    publishes of one round must re-pend the drained batch (bounded,
    counted), not silently lose up to spans_max stitched hops."""
    class _HalfDeadBus:
        def __init__(self):
            self.msgs = []

        async def publish(self, subject, data, headers=None):
            if ".spans." in subject:
                raise ConnectionError("broker died mid-round")
            self.msgs.append((subject, data))

    async def main():
        exp = _exporter(_HalfDeadBus(), spans_max=4)
        exp.store.add_tap(exp._tap)
        for i in range(3):
            exp.store.record(_span(i))
        with pytest.raises(ConnectionError):
            await exp.publish_once()
        assert len(exp._pending) == 3  # re-pended, in order
        assert [r.span_id for r in exp._pending] == ["s0", "s1", "s2"]
        good = _FakeBus()
        exp.bus_fn = lambda: good
        await exp.publish_once()
        batch = json.loads(good.msgs[-1][1])
        assert [s["span_id"] for s in batch["spans"]] == ["s0", "s1", "s2"]

    asyncio.run(main())


def test_chrome_lanes_survive_pid_one_and_cross_role_collisions():
    """Review regression: a containerized worker REALLY runs as PID 1 —
    its lane must not merge into the local pid-1 track; two roles
    claiming the same pid must not merge into one flapping lane."""
    from symbiont_tpu.obs import chrome_trace

    spans = [
        _span(1, name="api.search"),                            # local
        _span(2, name="perception.handle",
              fields={"role": "scrape", "pid": 1}),             # container
        _span(3, name="preprocessing.handle",
              fields={"role": "embed", "pid": 4242}),
        _span(4, name="vector_memory.handle",
              fields={"role": "memory", "pid": 4242}),          # collision
    ]
    doc = chrome_trace.export_spans("t1", spans)
    procs = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "M" and e["name"] == "process_name":
            assert e["pid"] not in procs, "duplicate process_name pid"
            procs[e["pid"]] = e["args"]["name"]
    assert procs[1] == "symbiont flight recorder"
    assert procs[4242] == "embed"  # first claimant keeps the real pid
    assert sorted(n for p, n in procs.items() if p > 100000) == \
        ["memory", "scrape"]


def test_flat_key_parser_edges():
    from symbiont_tpu.obs.prometheus import parse_flat_key

    assert parse_flat_key('counter.bus.consumed{service="api"}') == \
        ("counter", "bus.consumed", {"service": "api"}, None)
    assert parse_flat_key("hist.span.api.search.ms.p99") == \
        ("hist", "span.api.search.ms", {}, "p99")
    assert parse_flat_key(
        'hist.coalesce.flush_rows{service="engine"}.count') == \
        ("hist", "coalesce.flush_rows", {"service": "engine"}, "count")
    assert parse_flat_key("gauge.fleet.roles") == \
        ("gauge", "fleet.roles", {}, None)
    assert parse_flat_key("bogus") is None


def test_watchdog_judges_each_role_separately():
    """A breach in ONE role's federated span histogram alerts with that
    role in the event labels; the healthy roles stay silent."""
    from symbiont_tpu.obs.watchdog import SloWatchdog

    reg = Metrics()
    reg.observe("span.api.search.ms", 5.0)                      # local: ok
    reg.observe("span.api.search.ms", 900.0, labels={"role": "edge2"})
    wd = SloWatchdog({"api.search": 100.0}, registry=reg)
    breaches = wd.evaluate()
    assert len(breaches) == 1
    assert breaches[0]["labels"] == {"role": "edge2"}
    assert reg.get("slo.breaches",
                   labels={"span": "api.search", "role": "edge2"}) == 1
    # idle since: no re-alert off the same samples
    assert wd.evaluate() == []


def test_chrome_export_one_process_lane_per_role():
    from symbiont_tpu.obs import chrome_trace

    spans = [
        _span(1, name="api.search"),                      # local lane
        _span(2, name="preprocessing.handle",
              fields={"role": "embed", "pid": 4242}),
        _span(3, name="vector_memory.handle",
              fields={"role": "memory"}),                 # no pid: synthetic
    ]
    doc = chrome_trace.export_spans("t1", spans)
    procs = {e["args"]["name"]: e["pid"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs["symbiont flight recorder"] == 1
    assert procs["embed"] == 4242
    assert procs["memory"] > 100000  # deterministic synthetic pid
    span_pids = {e["name"]: e["pid"] for e in doc["traceEvents"]
                 if e["ph"] == "X"}
    assert span_pids == {"api.search": 1,
                         "preprocessing.handle": 4242,
                         "vector_memory.handle": procs["memory"]}


# ---------------------------------------------- two-process integration


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port, method, path, body=None, headers=None, timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json", **(headers or {})},
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            ctype = r.headers.get("Content-Type", "")
            raw = r.read()
            return r.status, (json.loads(raw or b"{}")
                              if "json" in ctype else raw.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")
    except (urllib.error.URLError, ConnectionError, OSError):
        return 0, {}


def test_two_process_trace_stitching_and_federated_exposition(tmp_path):
    """The tentpole's minimal end-to-end: pybroker + an api-only gateway
    runner + a perception runner (two OS processes, NO engines). One
    client-carried trace comes back from the gateway as a single stitched
    tree whose perception hop carries role/pid fields, /metrics shows both
    roles in one scrape, and /api/fleet lists them."""
    from symbiont_tpu.bench.load import _page_server
    from symbiont_tpu.bus.pybroker import PyBroker

    page = ("<html><body><main><p>Fleet stitch sentence one.</p>"
            "<p>Fleet stitch sentence two.</p></main></body></html>")

    async def main():
        broker = PyBroker(port=0, data_dir=str(tmp_path / "bus"))
        await broker.start()
        bus_url = f"symbus://127.0.0.1:{broker.bound_port}"
        page_srv = await _page_server({"/doc": page})
        page_port = page_srv.sockets[0].getsockname()[1]
        api_port = _free_port()
        log_path = tmp_path / "workers.log"
        stdio = open(log_path, "ab")

        def spawn(role, services, extra=None):
            env = {**os.environ,
                   "JAX_PLATFORMS": "cpu",
                   "SYMBIONT_BUS_URL": bus_url,
                   "SYMBIONT_RUNNER_SERVICES": services,
                   "SYMBIONT_RUNNER_ROLE": role,
                   "SYMBIONT_RUNNER_HEARTBEAT_S": "0.3",
                   "SYMBIONT_OBS_FLEET_PUBLISH_S": "0.2",
                   "SYMBIONT_VECTOR_STORE_DATA_DIR": str(tmp_path / "vs"),
                   "SYMBIONT_GRAPH_STORE_DATA_DIR": str(tmp_path / "gs"),
                   "SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH":
                       str(tmp_path / "markov.json"),
                   **(extra or {})}
            return subprocess.Popen(
                [sys.executable, "-m", "symbiont_tpu.runner"], env=env,
                stdout=stdio, stderr=stdio, start_new_session=True)

        procs = [
            spawn("gateway", "api",
                  {"SYMBIONT_API_HOST": "127.0.0.1",
                   "SYMBIONT_API_PORT": str(api_port),
                   "SYMBIONT_API_FUSED_SEARCH": "0"}),
            spawn("perception", "perception"),
        ]
        loop = asyncio.get_running_loop()

        def http(*a, **kw):
            return loop.run_in_executor(None, lambda: _http(*a, **kw))

        try:
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                status, _ = await http(api_port, "GET", "/readyz", timeout=2)
                if status == 200:
                    break
                await asyncio.sleep(0.25)
            else:
                raise AssertionError(
                    f"gateway never ready: {log_path.read_text()[-2000:]}")

            # ... and the perception process too, before anything is sent
            # to it: a core-subject publish to a role that has not
            # subscribed yet is lost, and beside five busy test workers the
            # second child can boot seconds after the gateway answers. A
            # role's telemetry starts once its services are up (runner.py),
            # so its entry in the gateway's roll-up is the event.
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                status, fleet = await http(api_port, "GET", "/api/fleet",
                                           timeout=2)
                if status == 200 and "perception" in fleet.get("roles", ()):
                    break
                await asyncio.sleep(0.25)
            else:
                raise AssertionError(
                    f"perception never up: {log_path.read_text()[-2000:]}")

            trace_id = "fleet-stitch-1"
            status, _ = await http(
                api_port, "POST", "/api/submit-url",
                {"url": f"http://127.0.0.1:{page_port}/doc"},
                {"X-Trace-Id": trace_id, "X-Span-Id": "stitch-root"})
            assert status == 200

            # spans federate on the 0.2s cadence: poll for a SINGLE tree
            # carrying the gateway's api.submit_url root AND the remote
            # perception.handle hop, parent-linked across the process gap
            tree = None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                status, tree = await http(api_port, "GET",
                                          f"/api/traces/{trace_id}")
                if status == 200:
                    names = set()

                    def walk(n):
                        names.add(n["name"])
                        for c in n.get("children", []):
                            walk(c)

                    for root in tree["roots"]:
                        walk(root)
                    if {"api.submit_url", "perception.handle"} <= names:
                        break
                await asyncio.sleep(0.2)
            else:
                raise AssertionError(f"trace never stitched: {tree}")
            assert len(tree["roots"]) == 1, tree
            root = tree["roots"][0]
            assert root["name"] == "api.submit_url"
            child = next(c for c in root["children"]
                         if c["name"] == "perception.handle")
            assert child["fields"]["role"] == "perception"
            assert isinstance(child["fields"]["pid"], int)
            assert child["parent_id"] == root["span_id"]

            # critical path over the stitched tree: per-hop self-times
            status, cp = await http(api_port, "GET",
                                    f"/api/traces/{trace_id}/critical_path")
            assert status == 200 and cp["chain"], cp
            assert all(isinstance(h["self_ms"], (int, float))
                       for h in cp["chain"])

            # federated exposition: both roles, one scrape
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                status, text = await http(api_port, "GET", "/metrics")
                if (status == 200 and 'role="gateway"' in text
                        and 'role="perception"' in text):
                    break
                await asyncio.sleep(0.2)
            else:
                raise AssertionError("roles never federated on /metrics")
            assert ('symbiont_published_total{role="perception",'
                    'service="perception"}' in text)

            # the roll-up lists both roles with telemetry freshness
            status, fleet = await http(api_port, "GET", "/api/fleet")
            assert status == 200 and fleet["available"], fleet
            assert {"gateway", "perception"} <= set(fleet["roles"])
        finally:
            for p in procs:
                try:
                    os.kill(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                p.wait(timeout=10)
            stdio.close()
            page_srv.close()
            await page_srv.wait_closed()
            await broker.stop()

    asyncio.run(main())


# -------------------------------------------------- C++ heartbeat parity

# Stub json DECLARATIONS only (no json.hpp): common.hpp's engine_call /
# decode_vectors are inline and never odr-used by this TU, so declarations
# satisfy the compiler and nothing needs the GCC 11 float-to_chars json
# implementation — this is what keeps the check alive on GCC 10 boxes
# where the full native tree cannot build.
CPP_HEARTBEAT_HARNESS = r"""
#include <string>
#include <vector>

namespace json {
struct Value {
  std::string dump() const;
  const Value& at(const std::string&) const;
  bool is_null() const;
  std::string as_string() const;
  double as_number() const;
  bool has(const std::string&) const;
  const std::vector<Value>& as_array() const;
};
Value parse(const std::string&);
}  // namespace json

#include "services/common.hpp"
#include <cstdio>

int main(int argc, char** argv) {
  std::string role = argc > 1 ? argv[1] : "worker";
  std::printf("%s\n", symbiont::heartbeat_subject(role).c_str());
  std::printf("%s\n", symbiont::heartbeat_payload(role).c_str());
  return 0;
}
"""


def test_cpp_heartbeat_parity_via_stub_json_harness():
    gxx = shutil.which("g++") or shutil.which("clang++")
    if gxx is None:
        pytest.skip("no C++ compiler on this host")
    with tempfile.TemporaryDirectory() as td:
        src = Path(td) / "hb.cpp"
        src.write_text(CPP_HEARTBEAT_HARNESS)
        exe = Path(td) / "hb"
        proc = subprocess.run(
            [gxx, "-std=c++17", "-O1", "-I", str(REPO / "native"),
             str(src), "-o", str(exe)],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (
            "the stub-json heartbeat TU must compile even where json.hpp "
            f"cannot (GCC 10):\n{proc.stderr[:2000]}")
        out = subprocess.run([str(exe), "text_generator"],
                             capture_output=True, text=True,
                             timeout=60).stdout.splitlines()
        subject, payload = out[0], out[1]
        assert subject == f"{subjects.SYS_HEARTBEAT}.text_generator"
        parsed = json.loads(payload)
        assert parsed["role"] == "text_generator"
        assert isinstance(parsed["pid"], int) and parsed["pid"] > 0
        # byte parity with the Python runner's heartbeat payload
        # (runner._heartbeat_payload: capacity/draining are the elastic-
        # autoscaler fields; the C++ shells always beat serving)
        assert payload == json.dumps({"role": "text_generator",
                                      "pid": parsed["pid"],
                                      "capacity": 1, "draining": False})
