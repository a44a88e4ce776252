"""Full-stack tier (VERDICT r3 item 1/2): what a user of the RUNNING stack
sees, not the in-process engine object. Boots the native broker, the C++
api_gateway, C++ perception + preprocessing (replicas on the queue group) +
vector_memory workers, and the TPU engine plane; then drives the real HTTP
surface.

Round-5 hardening (VERDICT r5 asks #1/#3/#4):
- NOTHING is swallowed: any exception propagates to the tier registry,
  which archives a structured `tier_failures` entry and forces rc != 0 —
  the driver's silent loss of the whole generation tier cannot recur;
- the ingest wave and the generation wave run 3× in-run, so their primary
  metrics carry `_min`/`_max` (the ±45% cross-run ingest spread is now
  falsifiable from one archive);
- a ResourceSampler snapshots per-process CPU seconds (broker, gateway,
  perception, preprocessing replicas, vector_memory, engine host) and
  broker bus bytes/s across the ingest waves, archiving the host-side
  decomposition;
- generated tokens are counted by the ENGINE'S OWN tokenizer, not by UTF-8
  byte length — the two were only equal because the LM happens to use
  ByteTokenizer, and that equivalence could silently break;
- the generation wave retries ONCE on shortfall with diagnostics (the class
  of timing flake that cost the driver's run the tier), then fails loud.
"""

from __future__ import annotations

import time

import numpy as np

from symbiont_tpu.bench import stats
from symbiont_tpu.bench.sampler import ResourceSampler, archive_decomposition
from symbiont_tpu.bench.tiers import register
from symbiont_tpu.bench.workload import log, make_sentences

# 360 docs per wave (was 120 through r4): at 120 the window was dominated by
# the pipeline ramp (first docs trickling through scrape→split before the
# engine sees a full backlog); 9k sentences measures the steady state the
# metric is meant to capture (measured r5: 120 docs ≈ 950 emb/s, 360 docs ≈
# 1 800 emb/s, same stack). INGEST_WAVES timed waves make the metric a
# (median, min, max) triple instead of one unfalsifiable sample.
N_DOCS, SENTS, WARM_DOCS = 360, 25, 16
INGEST_WAVES = 3
GEN_WAVES = 3


def bulk_ratio_fields(results: dict) -> dict:
    """The e2e÷bulk ingest ratio (overlap-everything target ≥ 0.6). The
    denominator comes from the engine-plane tier's SAME-RUN
    `ingest_10k_emb_per_s` — when that tier did not run in this process
    (--quick, a skip flag, or a reordered registry; the PR 6 note relied
    on import order), the ratio is archived as an explicit `null` plus a
    note instead of silently vanishing, so the archive distinguishes
    "prerequisite absent" from "field predates the metric". Pinned by
    tests/test_bench_subsystem.py."""
    if not isinstance(results.get("ingest_10k_emb_per_s"), (int, float)):
        return {
            "e2e_ingest_vs_bulk_x": None,
            "e2e_ingest_vs_bulk_note": (
                "prerequisite ingest_10k_emb_per_s absent: the engine_plane "
                "tier did not run in this process, so the same-run "
                "e2e-vs-bulk ratio cannot be formed"),
        }
    ratio = (results["e2e_ingest_emb_per_s"]
             / results["ingest_10k_emb_per_s"])
    return {"e2e_ingest_vs_bulk_x": round(ratio, 3)}


def _count_tokens(tokenizer, text: str) -> int:
    """Token count of generated text by the engine's own tokenizer (minus
    its BOS, which is framing, not generated output)."""
    ids = tokenizer.encode(text, 1 << 30)
    bos = getattr(tokenizer, "bos_id", None)
    if bos is not None and ids and ids[0] == bos:
        ids = ids[1:]
    return len(ids)


@register("e2e", primary_metrics=(
        "e2e_ingest_emb_per_s", "e2e_search_p50_ms",
        "e2e_gen_tok_per_s", "e2e_first_delta_ms"))
def tier_e2e(results: dict, ctx) -> None:
    import asyncio
    import pathlib
    import socket
    import subprocess
    import tempfile
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    REPO = pathlib.Path(__file__).resolve().parent.parent.parent
    # a native build failure is a tier FAILURE (archived, rc != 0), not a
    # silent skip: the e2e tier carries four declared primary metrics.
    # -B: native/build/ is git-ignored but survives on disk, and a
    # timestamp-driven make would let a stale binary stand in for a source
    # that no longer builds — the tier measures what git would commit
    subprocess.run(["make", "-B", "-C", str(REPO / "native")], check=True,
                   capture_output=True, timeout=900)

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    # -- synthetic corpus served over local HTTP (perception scrapes it);
    # the last WARM_DOCS are a warm-up wave through the identical path so
    # the timed windows measure steady state, not first-shape compiles.
    n_total = N_DOCS * INGEST_WAVES
    rng = np.random.default_rng(7)
    doc_sentences = [[s.capitalize() for s in make_sentences(SENTS, rng)]
                     for _ in range(n_total + WARM_DOCS)]
    pages = ["<html><body><main>"
             + "".join(f"<p>{s}.</p>" for s in sents)
             + "</main></body></html>" for sents in doc_sentences]

    class DocServer(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            i = int(self.path.rsplit("/", 1)[-1])
            body = pages[i].encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    docsrv = ThreadingHTTPServer(("127.0.0.1", 0), DocServer)
    threading.Thread(target=docsrv.serve_forever, daemon=True).start()
    doc_port = docsrv.server_address[1]

    bport, api_port = free_port(), free_port()
    broker = subprocess.Popen(
        [str(REPO / "native" / "build" / "symbus_broker"),
         "--port", str(bport), "--host", "127.0.0.1"],
        stderr=subprocess.DEVNULL)
    workers = []
    worker_roles: dict = {"broker": [broker.pid]}  # role → pids (sampler)

    def spawn(name: str, extra: dict | None = None):
        import os

        env = dict(os.environ,
                   SYMBIONT_BUS_URL=f"symbus://127.0.0.1:{bport}",
                   **(extra or {}))
        p = subprocess.Popen([str(REPO / "native" / "build" / name)], env=env,
                             stderr=subprocess.PIPE)
        workers.append(p)
        role = "gateway" if name == "api_gateway" else name
        worker_roles.setdefault(role, []).append(p.pid)
        return p

    async def wait_ready(proc, timeout=30.0):
        import os as _os

        _os.set_blocking(proc.stderr.fileno(), False)
        buf = b""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            chunk = proc.stderr.read()
            if chunk:
                buf += chunk
                if b"ready" in buf:
                    return
            await asyncio.sleep(0.05)
        raise TimeoutError(f"worker not ready: {buf!r}")

    async def drive(store, eng):
        import http.client as http_client
        import json as _json

        from symbiont_tpu.bus.tcp import TcpBus
        from symbiont_tpu.services.engine_service import EngineService

        bus = TcpBus("127.0.0.1", bport)
        await bus.connect()
        svc = EngineService(bus, engine=eng, vector_store=store)
        await svc.start()
        for _ in range(100):
            try:
                with socket.create_connection(("127.0.0.1", bport), 0.2):
                    break
            except OSError:
                await asyncio.sleep(0.05)
        # preprocessing replicas on the queue group: each is a synchronous
        # one-doc-at-a-time worker whose embed hop waits on a device call,
        # so in-flight docs — and therefore how well the engine
        # micro-batcher can aggregate — scale with replicas
        n_preproc = 8
        results["e2e_preproc_replicas"] = n_preproc
        procs = [spawn("perception")]
        procs += [spawn("preprocessing") for _ in range(n_preproc)]
        procs += [spawn("vector_memory") for _ in range(2)]
        procs += [spawn("api_gateway", {"SYMBIONT_API_PORT": str(api_port)})]
        for p in procs:
            await wait_ready(p)

        loop = asyncio.get_running_loop()

        def http(method, path, payload=None):
            conn = http_client.HTTPConnection("127.0.0.1", api_port,
                                              timeout=120)
            conn.connect()
            # the client's own Nagle delay must not pollute the measurement
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            body = _json.dumps(payload) if payload is not None else None
            conn.request(method, path, body=body)
            r = conn.getresponse()
            data = r.read().decode()
            conn.close()
            return r.status, (_json.loads(data) if data else None)

        def hx(*a):
            return loop.run_in_executor(None, lambda: http(*a))

        # warm the executables the driven paths hit (compiles must not sit
        # inside the timed region — parity with the engine-plane benches):
        # the full (length, batch) grid the micro-batcher's flush mixes can
        # produce, then a warm ingest wave through the IDENTICAL HTTP path
        # (covers the grouped-concat fetch signatures too)
        eng.warmup(buckets=[32, 64, 128], batches=[1, 8, 32, 128, 512])
        store.warm_fused(eng)
        status, body = await hx("GET", "/healthz")
        assert status == 200, (status, body)
        warm_expected = WARM_DOCS * SENTS
        for i in range(n_total, n_total + WARM_DOCS):
            status, _ = await hx("POST", "/api/submit-url",
                                 {"url": f"http://127.0.0.1:{doc_port}/doc/{i}"})
            assert status == 200
        deadline = time.time() + 120
        while time.time() < deadline and store.count() < warm_expected:
            await asyncio.sleep(0.1)
        if store.count() < warm_expected:
            log(f"e2e warm wave incomplete: {store.count()}/{warm_expected}")

        # ---- ingest through the whole pipeline (steady state), 3 timed
        # waves with per-process resource accounting across the window
        async def ingest_wave(wave: int) -> tuple:
            """(emb_per_s, landed, wall_s) for one N_DOCS-doc wave."""
            base_count = store.count()
            expected = base_count + N_DOCS * SENTS
            t0 = time.time()
            for i in range(wave * N_DOCS, (wave + 1) * N_DOCS):
                status, _ = await hx(
                    "POST", "/api/submit-url",
                    {"url": f"http://127.0.0.1:{doc_port}/doc/{i}"})
                assert status == 200
            deadline = time.time() + 300
            count = store.count()
            while time.time() < deadline:
                count = store.count()
                if count >= expected:
                    break
                await asyncio.sleep(0.1)
            dt = time.time() - t0
            landed = max(0, count - base_count)
            if landed < N_DOCS * SENTS:
                log(f"e2e ingest wave {wave}: only {landed}/"
                    f"{N_DOCS * SENTS} landed in time")
            return landed / dt, landed, dt

        sampler = ResourceSampler(worker_roles).start()
        wave_rates, total_landed, total_s = [], 0, 0.0
        for w in range(INGEST_WAVES):
            rate, landed, dt = await ingest_wave(w)
            wave_rates.append(rate)
            total_landed += landed
            total_s += dt
            log(f"e2e ingest wave {w + 1}/{INGEST_WAVES}: {landed} "
                f"sentences in {dt:.2f}s → {rate:.0f} emb/s")
        archive_decomposition(results, "e2e_ingest", sampler.stop())
        stats.record(results, "e2e_ingest_emb_per_s", wave_rates)
        results["e2e_ingest_sentences"] = total_landed
        results["e2e_ingest_s"] = round(total_s, 2)
        log(f"e2e ingest (HTTP submit-url → scrape → split → embed → "
            f"upsert, {INGEST_WAVES}×{N_DOCS} docs, {n_preproc} "
            f"preprocessing replicas): median "
            f"{results['e2e_ingest_emb_per_s']:.0f} emb/s "
            f"[{results['e2e_ingest_emb_per_s_min']:.0f}–"
            f"{results['e2e_ingest_emb_per_s_max']:.0f}]")
        # the overlap-everything target (ROADMAP item 3): e2e ingest as a
        # fraction of the same run's bulk-ingest rate — the ratio IS the
        # host-orchestration overhead. When the
        # engine-plane tier did not run in this process the field archives
        # as an explicit null + note (bulk_ratio_fields), never silently
        # dropped by registry order.
        results.update(bulk_ratio_fields(results))
        if results["e2e_ingest_vs_bulk_x"] is not None:
            log(f"e2e ingest / bulk ingest = "
                f"{results['e2e_ingest_vs_bulk_x']:.2f}× "
                f"(overlap-everything target: ≥ 0.60×)")
        else:
            log("e2e ingest / bulk ingest: prerequisite "
                "ingest_10k_emb_per_s absent — archived null + note")

        # ---- search over real HTTP (median-of-5 sweeps of 20 queries)
        for q in ["alpha beta", " ".join(["word"] * 40)]:
            status, body = await hx("POST", "/api/search/semantic",
                                    {"query_text": q, "top_k": 5})
            assert status == 200 and body["error_message"] is None, body
        p50s, p95s = [], []
        for _ in range(5):
            lat = []
            for q in make_sentences(20, rng):
                t0 = time.time()
                status, body = await hx("POST", "/api/search/semantic",
                                        {"query_text": q, "top_k": 5})
                lat.append(time.time() - t0)
                assert status == 200 and len(body["results"]) == 5, body
            ms = sorted(1000 * x for x in lat)
            p50s.append(ms[len(ms) // 2])
            p95s.append(ms[int(len(ms) * 0.95)])
        stats.record(results, "e2e_search_p50_ms", p50s)
        results["e2e_search_p95_ms"] = round(stats.med_min_max(p95s)[0], 1)
        log(f"e2e search (HTTP /api/search/semantic, median of 5 sweeps): "
            f"p50 {results['e2e_search_p50_ms']:.1f}ms "
            f"[{results['e2e_search_p50_ms_min']:.1f}–"
            f"{results['e2e_search_p50_ms_max']:.1f}], "
            f"p95 {results['e2e_search_p95_ms']:.1f}ms")

        # ---- full-stack generation: POST /api/generate-text → bus →
        # continuous-batching LM → SSE out of the C++ gateway (VERDICT r4
        # next-8; reference SSE path: api_service/src/main.rs:190-270)
        import threading
        import uuid as _uuid

        from symbiont_tpu.config import LmConfig
        from symbiont_tpu.engine.batcher import GenBatcher
        from symbiont_tpu.engine.lm import LmEngine
        from symbiont_tpu.services.text_generator import TextGeneratorService

        lm = LmEngine(LmConfig(
            enabled=True, arch="gpt2", hidden_size=768, num_layers=12,
            num_heads=12, intermediate_size=3072, max_positions=512,
            dtype="bfloat16", prompt_buckets=[64], new_token_buckets=[64],
            stream_chunk=16, gen_max_batch=16))
        gen_batcher = GenBatcher(lm)
        await gen_batcher.start()
        tg_bus = TcpBus("127.0.0.1", bport)
        await tg_bus.connect()
        tg = TextGeneratorService(tg_bus, lm_batcher=gen_batcher,
                                  lm_stream=lm.generate_stream,
                                  train_on_ingest=False)
        await tg.start()

        sse_events: list = []  # (wall-time, parsed event dict)
        sse_stop = threading.Event()

        def sse_listen():
            conn = http_client.HTTPConnection("127.0.0.1", api_port,
                                              timeout=300)
            conn.request("GET", "/api/events")
            r = conn.getresponse()
            while not sse_stop.is_set():
                line = r.readline()
                if not line:
                    break
                if line.startswith(b"data:"):
                    try:
                        sse_events.append(
                            (time.time(), _json.loads(line[5:].strip())))
                    except ValueError:
                        pass

        sse_thread = threading.Thread(target=sse_listen, daemon=True)
        sse_thread.start()
        await asyncio.sleep(0.3)  # SSE registered before the first event

        N_GEN, GEN_TOKENS = 16, 64
        prompt = "the tensor processing unit likes large matrix multiplies "

        def post_gen(stream=False):
            tid = str(_uuid.uuid4())
            body = {"task_id": tid, "prompt": prompt,
                    "max_length": GEN_TOKENS}
            if stream:
                body["stream"] = True
            status, _ = http("POST", "/api/generate-text", body)
            assert status == 200, status
            return tid

        def finals(ids):
            return {e["original_task_id"]: (t, e) for t, e in sse_events
                    if e.get("generated_text") is not None
                    and e.get("original_task_id") in ids}

        async def gen_wave(n):
            """(tokens, wall_s) for n concurrent generations; tokens are
            counted by the LM's OWN tokenizer (not UTF-8 byte length)."""
            t0 = time.time()
            ids = {await loop.run_in_executor(None, post_gen)
                   for _ in range(n)}
            deadline = time.time() + 180
            while time.time() < deadline and len(finals(ids)) < n:
                await asyncio.sleep(0.05)
            done = finals(ids)
            assert len(done) == n, (
                f"only {len(done)}/{n} generations arrived; "
                f"{len(sse_events)} SSE events total, "
                f"sse_thread alive={sse_thread.is_alive()}")
            toks = sum(_count_tokens(lm.tokenizer, e["generated_text"])
                       for _, e in done.values())
            return toks, max(t for t, _ in done.values()) - t0

        async def gen_wave_retry_once(label):
            """Retry ONCE on shortfall: the class of timing flake that lost
            the driver's r5 gen tier (cold compiles / late SSE finals under
            load). A second shortfall is a real failure and propagates to
            the registry."""
            try:
                return await gen_wave(N_GEN)
            except AssertionError as e:
                log(f"e2e gen {label} shortfall, retrying once: {e}")
                return await gen_wave(N_GEN)

        # warm: compiles session + admission shapes — the MOST flake-prone
        # wave, so it gets the retry too
        await gen_wave_retry_once("warm wave")
        gen_rates = []
        for w in range(GEN_WAVES):
            toks, dt_gen = await gen_wave_retry_once(f"wave {w + 1}")
            gen_rates.append(toks / dt_gen)
            log(f"e2e gen wave {w + 1}/{GEN_WAVES}: {toks} tokens in "
                f"{dt_gen:.2f}s → {toks / dt_gen:.0f} tok/s")
        results["e2e_gen_clients"] = N_GEN
        stats.record(results, "e2e_gen_tok_per_s", gen_rates)
        log(f"e2e generation ({N_GEN} concurrent clients, {GEN_TOKENS} new "
            f"tokens each, continuous batcher): median "
            f"{results['e2e_gen_tok_per_s']:.0f} tok/s "
            f"[{results['e2e_gen_tok_per_s_min']:.0f}–"
            f"{results['e2e_gen_tok_per_s_max']:.0f}] through the gateway")

        # streaming first-delta latency (stream=true rides the per-request
        # chunked decode; deltas ride events.text.generated.partial → SSE)
        warm_tid = post_gen(stream=True)  # warm the streaming executables
        deadline = time.time() + 120     # first compile can take tens of s
        while time.time() < deadline and not finals({warm_tid}):
            await asyncio.sleep(0.1)
        deltas = []
        for _ in range(3):
            t0 = time.time()
            tid = await loop.run_in_executor(None, post_gen, True)
            deadline = time.time() + 60
            first = None
            while time.time() < deadline and first is None:
                for t, e in sse_events:
                    if (e.get("original_task_id") == tid
                            and e.get("text_delta")):
                        first = t - t0
                        break
                await asyncio.sleep(0.01)
            assert first is not None, "no streaming delta arrived"
            deltas.append(first * 1000)
        stats.record(results, "e2e_first_delta_ms", deltas)
        log(f"e2e streaming: first SSE text delta "
            f"{results['e2e_first_delta_ms']:.0f}ms "
            f"[{results['e2e_first_delta_ms_min']:.0f}–"
            f"{results['e2e_first_delta_ms_max']:.0f}] (median of "
            f"{len(deltas)}, full HTTP→bus→decode→SSE path)")
        sse_stop.set()
        # where the time goes (obs/critical_path.py): aggregate per-hop
        # self-time shares over every trace the Python-side flight recorder
        # captured during the waves, grouped by root span name. In THIS
        # tier the HTTP/scrape hops run in C++ (span-less), so the recorded
        # roots are the engine-plane handler spans — still the accelerator
        # path the attribution is for. Archived flat as
        # `e2e_stage_<pipeline>_<hop>_pct` and exported as stage.* gauges
        # riding metrics_snapshot.
        from symbiont_tpu.obs import critical_path as _cp
        from symbiont_tpu.obs.trace_store import trace_store as _ts

        attr = _cp.aggregate_stage_attribution(_ts)
        _cp.export_stage_gauges(attr)
        for pipeline, root_candidates in (
                ("ingest", ("api.submit_url", "engine.handle")),
                ("generate", ("api.generate_text",
                              "text_generator.handle"))):
            root = next((r for r in root_candidates if r in attr), None)
            if root is None:
                log(f"e2e stage attribution: no recorded traces rooted at "
                    f"any of {root_candidates} for {pipeline}")
                continue
            agg = attr[root]
            for hop, frac in agg["stages"].items():
                results[f"e2e_stage_{pipeline}_{_cp.safe_key(hop)}_pct"] = \
                    round(100.0 * frac, 1)
            results[f"e2e_stage_{pipeline}_gap_pct"] = round(
                100.0 * agg["gap_frac"], 1)
            results[f"e2e_stage_{pipeline}_traces"] = agg["count"]
            log(f"e2e stage attribution ({pipeline}, root {root}, "
                f"{agg['count']} traces): " + ", ".join(
                    f"{hop} {100 * frac:.1f}%"
                    for hop, frac in sorted(agg["stages"].items(),
                                            key=lambda kv: -kv[1])))

        # internal-gauge snapshot INTO the archive: BENCH_*.json carried
        # only external timings before — now the engine-plane view (batcher
        # fill ratios, padding waste, compile count/seconds, decode tok/s,
        # span histograms) of the same run rides along, so a throughput
        # regression can be read against what the engine saw internally.
        # Taken before teardown: closing the batchers unregisters/kills
        # their gauges.
        from symbiont_tpu.utils.telemetry import metrics as _metrics

        # first-class overlap/coalesce fields (also inside metrics_snapshot;
        # these are the ones doc.py renders): how full the double-buffered
        # flush window ran, and how many rows each coalesced store call
        # carried on average
        overlap = _metrics.gauge_get(
            "batcher.overlap_ratio",
            labels={"service": "engine", "batcher": "embed"})
        results["e2e_batcher_overlap_ratio"] = round(float(overlap), 4)
        co = _metrics.histogram_summary("coalesce.flush_rows",
                                        labels={"service": "engine"})
        if co is not None and co["count"]:
            results["e2e_coalesce_flushes"] = co["count"]
            results["e2e_coalesce_rows_per_flush"] = round(
                co["sum"] / co["count"], 1)
        results["metrics_snapshot"] = _metrics.flat_snapshot()
        await tg.stop()
        await gen_batcher.close()
        await tg_bus.close()
        await svc.stop()
        await bus.close()

    try:
        from symbiont_tpu.config import EngineConfig, VectorStoreConfig
        from symbiont_tpu.engine.engine import TpuEngine
        from symbiont_tpu.memory.vector_store import VectorStore

        with tempfile.TemporaryDirectory() as td:
            # engine at its bulk policy: 512-row flushes, 4 in flight, so
            # per-device-call overhead is amortized over many rows
            eng = TpuEngine(EngineConfig(
                embedding_dim=384, length_buckets=[32, 64, 128],
                batch_buckets=[1, 8, 32, 128, 512], max_batch=512,
                dtype="bfloat16", data_parallel=False,
                host_prep_chunk=256, max_inflight_flushes=4))
            # capacity covers warm docs + all 3 timed waves (~27.4k points):
            # crossing a capacity block MID-RUN would invalidate the warmed
            # fused executables and send the timed searches down the 2-hop
            # fallback (observed: p50 110 ms → 365 ms)
            store = VectorStore(VectorStoreConfig(dim=384, data_dir=td,
                                                  shard_capacity=32768))
            asyncio.run(drive(store, eng))
    finally:
        # teardown always; the EXCEPTION always propagates to the registry,
        # which archives it as a tier_failures entry and forces rc != 0 —
        # the r5 harness swallowed it here and the driver's run silently
        # lost the whole generation tier (VERDICT r5 weak #1)
        for p in workers:
            p.terminate()
        broker.terminate()
        docsrv.shutdown()
