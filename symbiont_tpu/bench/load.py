"""Load tier: the multi-tenant production traffic simulator (ROADMAP item 5).

Open-loop load generation against the REAL single-process stack (runner +
inproc durable bus + HTTP/SSE surface), replaying the mixed scenarios a
million-user deployment produces — ingest bursts, search storms, streaming
generation, a fused search→generate RAG flow riding ONE trace, and the
knowledge-graph scenario (entity extraction → graph upsert → graph-augmented
search) — across N simulated tenants with per-tenant quotas, WITH a seeded
FaultPlan active (chaos ON: handler crashes + delivery drops during ingest).

Hard gates (a violation throws → tier_failures → rc != 0):
- `load_zero_loss_ingest` — EXACT point count under chaos: every accepted
  document lands exactly once (durable redelivery + deterministic ids);
- `load_fairness_jain` ≥ 0.8 — Jain index over per-tenant ADMITTED search
  throughput with one hot tenant offering ~8× everyone else: quotas clamp
  the hot tenant instead of letting it starve the rest;
- zero unbounded-queue growth — overload answered by 429/shed (counted),
  fair-queue and admission queues empty at the end;
- the shed ladder demonstrably walks its rungs on REAL SloWatchdog breach
  evaluations (low-priority generation shed → search degraded → recovery).

SLO primaries archived (regression-gated across runs, not absolute-gated on
CPU): `load_search_p99_ms`, `load_ttft_p99_ms`.

Reproducibility: `--load-seed` / `--chaos-seed` (bench/cli.py) seed the
workload mix and the FaultPlan; both are archived in the tier line so any
red run replays bit-for-bit.
"""

from __future__ import annotations

import time

import numpy as np

from symbiont_tpu.bench.tiers import register
from symbiont_tpu.bench.workload import log

# workload shape (kept modest: the tier must run on CPU in ~a minute)
N_TENANTS = 4            # equal-load tenants t0..t3
HOT_TENANT = "hot"
DOCS_PER_TENANT = 4      # ingest burst: 4 docs x (tenants+hot) = 20 docs
SENTS_PER_DOC = 4
SEARCHES_PER_TENANT = 20
HOT_SEARCHES = 150       # ~8x a normal tenant's offered load
GEN_STREAMS = 6
RAG_FLOWS = 3
GRAPH_SEARCHES = 5

VOCAB = ["alpha", "beta", "gamma", "delta", "tensor", "symbiont", "matrix",
         "vector", "graph", "stream", "decode", "ingest"]


class _StubEngine:
    """Deterministic duck-typed embed engine (same shape as the chaos
    suite's): the load tier measures the SERVING plane — admission, bus,
    store, SSE — not BERT numerics."""

    class _ModelCfg:
        hidden_size = 16

    def __init__(self):
        from symbiont_tpu.config import EngineConfig

        self.config = EngineConfig(embedding_dim=16, max_batch=16,
                                   flush_deadline_ms=2.0)
        self.model_cfg = self._ModelCfg()
        self.cross_params = None
        self.stats = {"embed_calls": 0, "compiles": 0}

    def embed_texts(self, texts):
        self.stats["embed_calls"] += 1
        import zlib

        out = np.zeros((len(texts), 16), np.float32)
        for i, t in enumerate(texts):
            # crc32, NOT hash(): str hashing is salted per interpreter
            # process, which would break the tier's bit-for-bit seed replay
            rng = np.random.default_rng(zlib.crc32(t.encode("utf-8")))
            out[i] = rng.standard_normal(16).astype(np.float32)
        return out


def jain_index(xs) -> float:
    """Jain's fairness index (Σx)² / (n·Σx²): 1.0 = perfectly equal, 1/n =
    one tenant got everything."""
    xs = [float(x) for x in xs]
    n = len(xs)
    ssq = sum(x * x for x in xs)
    if n == 0 or ssq == 0:
        return 0.0
    return (sum(xs) ** 2) / (n * ssq)


def _pct(sorted_ms, q: float) -> float:
    if not sorted_ms:
        return 0.0
    return sorted_ms[min(len(sorted_ms) - 1, int(q * len(sorted_ms)))]


def _page(rng, tenant: str, i: int, sents: int = SENTS_PER_DOC) -> str:
    # exactly `sents` period-terminated sentences per page (the splitter
    # cuts on delimiters) so the zero-loss gate is EXACT arithmetic
    lines = [f"{tenant} document {i} sentence {j} "
             + " ".join(str(rng.choice(VOCAB)) for _ in range(4))
             for j in range(sents)]
    return ("<html><body><main>"
            + "".join(f"<p>{s}.</p>" for s in lines) + "</main></body></html>")


@register("load", primary_metrics=(
        "load_search_p99_ms", "load_ttft_p99_ms",
        "load_zero_loss_ingest", "load_fairness_jain"))
def tier_load(results: dict, ctx) -> None:
    import asyncio

    load_seed = int(getattr(ctx, "load_seed", 0) or 0)
    chaos_seed = int(getattr(ctx, "chaos_seed", 0) or 0)
    results["load_seed"] = load_seed
    results["chaos_seed"] = chaos_seed
    asyncio.run(_drive(results, load_seed, chaos_seed))


async def _drive(results: dict, load_seed: int, chaos_seed: int) -> None:
    import asyncio
    import json as _json
    import tempfile
    import urllib.request

    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        AdmissionConfig,
        ApiConfig,
        GraphStoreConfig,
        LmConfig,
        ObsConfig,
        SymbiontConfig,
        TextGeneratorConfig,
        VectorStoreConfig,
    )
    from symbiont_tpu.resilience.faults import FaultPlan, FaultRule
    from symbiont_tpu.runner import SymbiontStack
    from symbiont_tpu.utils.telemetry import metrics

    rng = np.random.default_rng(load_seed)
    tenants = [f"t{i}" for i in range(N_TENANTS)]
    pages = {}
    for tenant in tenants + [HOT_TENANT]:
        for i in range(DOCS_PER_TENANT):
            pages[f"http://load/{tenant}/{i}"] = _page(rng, tenant, i)

    with tempfile.TemporaryDirectory() as td:
        cfg = SymbiontConfig(
            vector_store=VectorStoreConfig(dim=16, data_dir=f"{td}/vs",
                                           shard_capacity=256),
            graph_store=GraphStoreConfig(data_dir=f"{td}/gs"),
            text_generator=TextGeneratorConfig(markov_state_path=None),
            api=ApiConfig(host="127.0.0.1", port=0, fused_search=False,
                          sse_keepalive_s=0.5),
            lm=LmConfig(enabled=True, hidden_size=32, num_layers=1,
                        num_heads=2, intermediate_size=64, max_positions=64,
                        dtype="float32", prompt_buckets=[16, 32],
                        new_token_buckets=[16], stream_chunk=8,
                        gen_flush_deadline_ms=5.0, temperature=0.0),
            # slo_interval_s far beyond the tier's runtime: scenario 6
            # drives wd.evaluate() BY HAND, and a periodic pass landing
            # mid-tier would race it (consuming samples or adding an extra
            # escalation) — a wall-clock flake no archived seed can replay
            obs=ObsConfig(slo_p99_ms=["api.search=60000"],
                          slo_interval_s=3600.0),
            admission=AdmissionConfig(
                # search quota: normals (SEARCHES_PER_TENANT) fit the
                # burst; the hot tenant's ~8x flood is clamped to
                # burst + rate x storm-seconds
                search_rate=5.0, search_burst=float(SEARCHES_PER_TENANT),
                ingest_rate=500.0, ingest_burst=500.0,
                generate_rate=100.0, generate_burst=100.0,
                # ladder demo: no dwell, 2 clean passes to step down
                shed_hold_s=0.0, shed_recovery_passes=2,
                degraded_top_k=3),
        )
        cfg.runner.services = ("perception,preprocessing,vector_memory,"
                               "knowledge_graph,text_generator,api")
        cfg.bus.durable = True
        cfg.bus.durable_ack_wait_s = 0.3

        plan = FaultPlan(seed=chaos_seed, rules=[
            FaultRule(seam="handler", kind="error",
                      match="vector_memory:data.text.with_embeddings",
                      times=3),
            FaultRule(seam="bus.deliver", kind="drop",
                      match="data.text.with_embeddings", times=2),
            FaultRule(seam="handler", kind="error",
                      match="knowledge_graph:data.processed_text.tokenized",
                      times=1),
        ])

        bus = InprocBus()
        stack = SymbiontStack(cfg, bus=bus, engine=_StubEngine(),
                              fetcher=lambda url: pages[url])
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        # host CPU context for the whole chaos window (bench/sampler.py —
        # PR 1's resource sampler, now wired into the chaos tiers too):
        # the in-proc stack is one process, so the decomposition is the
        # driving process itself (engine_host) + wall, enough to tell "the
        # SLO numbers above ran on a saturated host core" from "idle host"
        from symbiont_tpu.bench.sampler import (
            ResourceSampler,
            archive_decomposition,
        )

        sampler = ResourceSampler({}).start()

        # the load generator gets ITS OWN thread pool: a storm of blocking
        # HTTP clients on the default executor would starve the very embed
        # calls it is waiting on (the stack shares that pool)
        from concurrent.futures import ThreadPoolExecutor

        client_pool = ThreadPoolExecutor(max_workers=48,
                                         thread_name_prefix="load-client")

        def _http(method, path, body=None, headers=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=(_json.dumps(body).encode()
                      if body is not None else None),
                headers={"Content-Type": "application/json",
                         **(headers or {})}, method=method)
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    return r.status, _json.loads(r.read() or b"{}")
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"{}")

        def http(method, path, body=None, headers=None):
            return loop.run_in_executor(
                client_pool, lambda: _http(method, path, body, headers))

        # one unfiltered SSE reader collects every generation event
        sse_events: list = []

        async def sse_reader():
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(b"GET /api/events HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            try:
                while True:
                    line = await reader.readline()
                    if not line:
                        return
                    if line.startswith(b"data: "):
                        try:
                            sse_events.append(
                                (time.monotonic(),
                                 _json.loads(line[6:].strip())))
                        except ValueError:
                            pass
            except (asyncio.CancelledError, ConnectionResetError):
                pass
            finally:
                writer.close()

        sse_task = asyncio.create_task(sse_reader())
        await asyncio.sleep(0.2)

        try:
            # ---- scenario 1: ingest burst across tenants, chaos ON -------
            expected = len(pages) * SENTS_PER_DOC
            t0 = time.monotonic()
            with plan.activate():
                for url in pages:
                    tenant = url.split("/")[3]
                    status, _ = await http(
                        "POST", "/api/submit-url", {"url": url},
                        {"X-Symbiont-Tenant": tenant})
                    assert status == 200, status
                deadline = time.monotonic() + 60
                while (time.monotonic() < deadline
                       and stack.vector_store.count() < expected):
                    await asyncio.sleep(0.05)
                # let any in-flight redelivery settle, then check EXACTLY
                await asyncio.sleep(0.5)
            landed = stack.vector_store.count()
            chaos_fired = sum(plan.fired.values())
            results["load_chaos_faults"] = chaos_fired
            results["load_ingest_docs"] = len(pages)
            results["load_ingest_expected_points"] = expected
            results["load_ingest_landed_points"] = landed
            results["load_ingest_s"] = round(time.monotonic() - t0, 2)
            results["load_zero_loss_ingest"] = float(landed == expected)
            log(f"load ingest: {len(pages)} docs / {expected} points under "
                f"chaos ({chaos_fired} faults fired) → {landed} landed in "
                f"{results['load_ingest_s']}s")
            if landed != expected:
                raise RuntimeError(
                    f"load_zero_loss_ingest violated: {landed}/{expected} "
                    f"points (chaos seed {chaos_seed})")
            if chaos_fired < 3:
                raise RuntimeError(
                    f"chaos was not ON: only {chaos_fired} faults fired")

            # ---- scenario 2: search storm, one hot tenant ----------------
            lat_ms: list = []
            admitted = {t: 0 for t in tenants + [HOT_TENANT]}
            throttled = {t: 0 for t in tenants + [HOT_TENANT]}

            async def one_search(tenant, query):
                t1 = time.monotonic()
                status, body = await http(
                    "POST", "/api/search/semantic",
                    {"query_text": query, "top_k": 3},
                    {"X-Symbiont-Tenant": tenant})
                if status == 200 and body.get("error_message") is None:
                    admitted[tenant] += 1
                    lat_ms.append((time.monotonic() - t1) * 1000.0)
                elif status == 429:
                    throttled[tenant] += 1
                else:
                    raise RuntimeError(
                        f"search failed ({tenant}): {status} {body}")

            storm = []
            for tenant in tenants:
                storm += [one_search(tenant,
                                     f"{rng.choice(VOCAB)} {rng.choice(VOCAB)}")
                          for _ in range(SEARCHES_PER_TENANT)]
            storm += [one_search(HOT_TENANT, f"{rng.choice(VOCAB)} flood")
                      for _ in range(HOT_SEARCHES)]
            t2 = time.monotonic()
            await asyncio.gather(*storm)
            storm_s = time.monotonic() - t2
            lat_ms.sort()
            n_429 = sum(throttled.values())
            results["load_search_requests"] = len(storm)
            results["load_search_ok"] = sum(admitted.values())
            results["load_throttled_429"] = n_429
            results["load_search_p50_ms"] = round(_pct(lat_ms, 0.50), 2)
            results["load_search_p99_ms"] = round(_pct(lat_ms, 0.99), 2)
            results["load_storm_s"] = round(storm_s, 2)
            fairness = jain_index(admitted.values())
            results["load_fairness_jain"] = round(fairness, 4)
            log(f"load search storm: {len(storm)} req in {storm_s:.2f}s → "
                f"{results['load_search_ok']} ok / {n_429}x 429; "
                f"p50 {results['load_search_p50_ms']}ms "
                f"p99 {results['load_search_p99_ms']}ms; admitted/tenant "
                f"{ {t: admitted[t] for t in sorted(admitted)} } → "
                f"Jain {fairness:.3f}")
            if fairness < 0.8:
                raise RuntimeError(
                    f"tenant fairness index {fairness:.3f} < 0.8 with one "
                    f"hot tenant (admitted: {admitted})")
            if n_429 == 0:
                raise RuntimeError(
                    "hot tenant was never throttled: overload is queuing, "
                    "not shedding")
            # every normal tenant kept its full quota despite the flood
            short = {t: admitted[t] for t in tenants
                     if admitted[t] < SEARCHES_PER_TENANT}
            if short:
                raise RuntimeError(
                    f"hot tenant starved normal tenants: {short}")

            # edge-deadline refusal is part of the serving contract: an
            # already-dead request is 429'd without a bus publish
            status, body = await http(
                "POST", "/api/search/semantic",
                {"query_text": "late", "top_k": 1},
                {"X-Symbiont-Tenant": "edge", "X-Symbiont-Deadline": "1"})
            assert status == 429 and body.get("reason") == "deadline", body
            results["load_deadline_429"] = 1.0

            # ---- scenario 3: streaming generation (TTFT over SSE) --------
            # mixed-length mix: prompts spanning both prompt buckets and
            # varying new-token budgets, so TTFT covers bucket mixing the
            # way real traffic does (and the paged-KV layout sees uneven
            # per-row page growth rather than one uniform shape)
            GEN_MIX = [("symbiont tensor", 6),
                       ("symbiont tensor graft compiles static shapes", 12),
                       ("symbiont tensor graft streams paged kv pages "
                        "across the decode plane under load", 16)]

            async def one_stream(i, timeout_s=90.0):
                prompt, max_len = GEN_MIX[
                    (i if isinstance(i, int) else 0) % len(GEN_MIX)]
                tid = f"load-gen-{i}"
                t3 = time.monotonic()
                status, _ = await http(
                    "POST", "/api/generate-text",
                    {"task_id": tid, "prompt": prompt,
                     "max_length": max_len, "stream": True},
                    {"X-Symbiont-Tenant": "gen"})
                assert status == 200, status
                deadline = time.monotonic() + timeout_s
                while time.monotonic() < deadline:
                    for ts, e in sse_events:
                        if (e.get("original_task_id") == tid
                                and e.get("text_delta")):
                            return (ts - t3) * 1000.0
                    await asyncio.sleep(0.01)
                raise RuntimeError(f"no streaming delta for {tid}")

            await one_stream("warm")  # compiles sit outside the timed set
            ttfts = sorted([await one_stream(i) for i in range(GEN_STREAMS)])
            results["load_gen_streams"] = GEN_STREAMS
            results["load_ttft_p50_ms"] = round(_pct(ttfts, 0.50), 1)
            results["load_ttft_p99_ms"] = round(_pct(ttfts, 0.99), 1)
            log(f"load generation: {GEN_STREAMS} SSE streams, TTFT p50 "
                f"{results['load_ttft_p50_ms']}ms p99 "
                f"{results['load_ttft_p99_ms']}ms")

            # ---- scenario 4: RAG flow (search → generate) as ONE trace ---
            rag_spans = 0
            for i in range(RAG_FLOWS):
                trace = {"X-Trace-Id": f"load-rag-{load_seed}-{i}",
                         "X-Span-Id": f"load-rag-root-{i}",
                         "X-Symbiont-Tenant": "rag"}
                status, body = await http(
                    "POST", "/api/search/semantic",
                    {"query_text": str(rng.choice(VOCAB)), "top_k": 1},
                    trace)
                assert status == 200, body
                hit = (body["results"][0]["payload"]["sentence_text"]
                       if body["results"] else "fallback context")
                status, _ = await http(
                    "POST", "/api/generate-text",
                    {"task_id": f"load-rag-gen-{i}",
                     "prompt": hit[:32], "max_length": 8}, trace)
                assert status == 200
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if any(e.get("original_task_id") == f"load-rag-gen-{i}"
                           and e.get("generated_text") is not None
                           for _, e in sse_events):
                        break
                    await asyncio.sleep(0.01)
                status, tree = await http(
                    "GET", f"/api/traces/load-rag-{load_seed}-{i}")
                assert status == 200, tree
                names = set()

                def walk(node):
                    names.add(node.get("name"))
                    for c in node.get("children", []):
                        walk(c)

                for root in tree.get("roots", []):
                    walk(root)
                if {"api.search", "api.generate_text"} <= names:
                    rag_spans += 1
            results["load_rag_flows"] = RAG_FLOWS
            results["load_rag_single_trace"] = float(rag_spans == RAG_FLOWS)
            log(f"load RAG flow: {RAG_FLOWS} search→generate flows, "
                f"{rag_spans} with both hops on ONE trace")
            if rag_spans != RAG_FLOWS:
                raise RuntimeError(
                    f"RAG flow traces incomplete: {rag_spans}/{RAG_FLOWS} "
                    "carried api.search + api.generate_text on one trace")

            # ---- scenario 5: knowledge-graph limb, end-to-end ------------
            graph_hits = 0
            for _ in range(GRAPH_SEARCHES):
                q = f"{rng.choice(VOCAB)} {rng.choice(VOCAB)}"
                status, body = await http(
                    "POST", "/api/search/graph",
                    {"query_text": q, "top_k": 3},
                    {"X-Symbiont-Tenant": "kg"})
                assert status == 200, body
                graph_hits += len(body["results"])
            results["load_graph_searches"] = GRAPH_SEARCHES
            results["load_graph_hits"] = graph_hits
            log(f"load graph scenario: {GRAPH_SEARCHES} graph-augmented "
                f"searches → {graph_hits} hits")
            if graph_hits == 0:
                raise RuntimeError(
                    "graph-augmented search returned no hits: the "
                    "knowledge-graph limb is dead again")

            # ---- scenario 6: SLO shed ladder on real watchdog passes -----
            ladder = stack.api.ladder
            wd = stack.watchdog
            # tighten the SLO so the REAL search histogram breaches it
            wd.thresholds["api.search"] = 0.001
            wd.evaluate()
            assert ladder.level == 1, ladder.level
            status, body = await http(
                "POST", "/api/generate-text",
                {"task_id": "shed-me", "prompt": "x", "max_length": 4},
                {"X-Symbiont-Tenant": "gen", "X-Symbiont-Priority": "low"})
            assert status == 429 and body["reason"] == "shed_gen_low", body
            # fresh samples so the next pass has evidence, then rung 2
            await one_search("t0", "another probe")
            wd.evaluate()
            assert ladder.level == 2, ladder.level
            status, body = await http(
                "POST", "/api/search/semantic",
                {"query_text": "degraded probe", "top_k": 10},
                {"X-Symbiont-Tenant": "t1"})
            assert status == 200 and len(body["results"]) <= 3, \
                ("degraded search did not clamp top-k", body)
            results["load_shed_generations"] = metrics.get(
                "admission.shed", labels={"reason": "shed_gen_low",
                                          "tenant": "gen"})
            results["load_degraded_searches"] = metrics.get(
                "admission.degraded", labels={"what": "search",
                                              "tenant": "t1"})
            results["load_ladder_max_level"] = float(ladder.level)
            # recovery: healthy passes step the ladder back down
            wd.thresholds["api.search"] = 60000.0
            for _ in range(2 * cfg.admission.shed_recovery_passes):
                wd.evaluate()
            results["load_ladder_recovered"] = float(ladder.level == 0)
            log(f"load shed ladder: escalated to rung 2 on real breach "
                f"passes (shed {results['load_shed_generations']:.0f} gen, "
                f"degraded {results['load_degraded_searches']:.0f} "
                f"searches), recovered={ladder.level == 0}")
            if ladder.level != 0:
                raise RuntimeError(
                    f"shed ladder did not recover: level {ladder.level}")

            # ---- no unbounded queues: everything drained, sheds counted --
            queued = stack.api.admission.fair_queue.queued()
            results["load_final_queued"] = float(queued)
            if queued != 0:
                raise RuntimeError(
                    f"fair queue not drained at end of run: {queued}")

            # host CPU decomposition over the whole simulated-traffic
            # window (load_cpu_s_engine_host / load_host_cpu_utilization)
            archive_decomposition(results, "load", sampler.stop())
        finally:
            sse_task.cancel()
            client_pool.shutdown(wait=False)
            await stack.stop()
            await bus.close()


# ---------------------------------------------------------------------------
# --multiproc: the SAME simulator against the REAL multi-process deployment
# (ROADMAP item 5 remainder #1; the process-failure plane's end-to-end
# proof). A ProcessSupervisor owns the broker (pure-Python symbus twin,
# bus/pybroker.py — wire/log-compatible with native/symbus) plus one
# `python -m symbiont_tpu.runner` process per role; a seeded kill plan
# SIGKILLs one worker and SIGSTOPs another MID-INGEST and then SIGKILLs the
# broker itself, and the hard gates still hold:
#
# - `load_mp_zero_loss_ingest` — EXACT point count across process deaths
#   (durable stream log + client reconnect/re-attach + deterministic ids);
# - `load_mp_fairness_jain` ≥ 0.8 with one ~8x hot tenant (edge admission
#   in the gateway PROCESS, engine lanes in the embed process);
# - zero final fair-queue depth (429s, not queues);
# - `load_proc_recovery_s` — worst kill→serving-again time across the
#   killed workers (supervisor liveness confirmations), the tier's new
#   primary; broker recovery archived alongside.
#
# Scale note (CPU, ~2 min): each worker is a real process importing jax and
# building a small real engine — this tier is about process failure, not
# throughput, so the corpus stays modest and generation runs the Markov
# backend (LM decode compiles would dominate the wall clock).
# ---------------------------------------------------------------------------

MP_DOCS_PER_TENANT = 3     # 3 docs x 5 tenants x 4 sentences = 60 points
MP_SEARCHES_PER_TENANT = 15
MP_HOT_SEARCHES = 110
MP_GENERATIONS = 4


@register("load_multiproc", primary_metrics=(
        "load_proc_recovery_s", "load_mp_zero_loss_ingest",
        "load_mp_fairness_jain", "load_mp_fleet_roles",
        "load_mp_trace_stitched"))
def tier_load_multiproc(results: dict, ctx) -> None:
    import asyncio

    if not getattr(ctx, "multiproc", False):
        from symbiont_tpu.bench.tiers import TierSkip

        raise TierSkip("spawns real OS processes; pass --multiproc "
                       "(scripts/multiproc.sh)")
    load_seed = int(getattr(ctx, "load_seed", 0) or 0)
    chaos_seed = int(getattr(ctx, "chaos_seed", 0) or 0)
    results["load_mp_seed"] = load_seed
    results["load_mp_chaos_seed"] = chaos_seed
    asyncio.run(_drive_multiproc(results, load_seed, chaos_seed))


async def _page_server(pages: dict):
    """Tiny HTTP server handing the perception WORKER PROCESS its pages —
    in-proc fetcher injection can't cross a process boundary, so the
    multiproc tier scrapes real HTTP like production would."""
    import asyncio

    async def handle(reader, writer):
        try:
            line = await reader.readline()
            while True:
                h = await reader.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
            path = line.split()[1].decode()
            body = pages.get(path, "").encode()
            status = "200 OK" if body else "404 Not Found"
            writer.write((f"HTTP/1.1 {status}\r\n"
                          "Content-Type: text/html\r\n"
                          f"Content-Length: {len(body)}\r\n"
                          "Connection: close\r\n\r\n").encode() + body)
            await writer.drain()
        except (ConnectionResetError, IndexError):
            pass
        finally:
            writer.close()

    return await asyncio.start_server(handle, "127.0.0.1", 0)


async def _drive_multiproc(results: dict, load_seed: int,
                           chaos_seed: int) -> None:
    import asyncio
    import json as _json
    import os
    import signal
    import socket
    import subprocess
    import tempfile
    import urllib.request

    from symbiont_tpu import subjects
    from symbiont_tpu.bus.tcp import TcpBus
    from symbiont_tpu.resilience.procsup import (
        ProcessSupervisor,
        pybroker_spec,
        runner_spec,
    )

    rng = np.random.default_rng(load_seed)
    chaos_rng = np.random.default_rng(chaos_seed)
    tenants = [f"t{i}" for i in range(N_TENANTS)]

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    pages = {}
    for tenant in tenants + [HOT_TENANT]:
        for i in range(MP_DOCS_PER_TENANT):
            pages[f"/{tenant}/{i}"] = _page(rng, tenant, i)
    page_srv = await _page_server(pages)
    page_port = page_srv.sockets[0].getsockname()[1]

    with tempfile.TemporaryDirectory() as td:
        broker_port = free_port()
        api_port = free_port()
        bus_url = f"symbus://127.0.0.1:{broker_port}"
        # worker-process config, all via env (the config layer's canonical
        # spelling — SYMBIONT_<SECTION>_<FIELD>)
        common = {
            # the bench parent holds the chip (one process per chip), so
            # these runner children are pinned to the CPU: this tier's
            # results are CPU results (docs/DEPLOYMENT.md)
            "JAX_PLATFORMS": "cpu",
            # fleet telemetry plane (obs/fleet.py): every role publishes
            # metric deltas + finished spans fast enough for the stitching
            # assertions below to converge within the tier's poll budget
            "SYMBIONT_OBS_FLEET_PUBLISH_S": "0.3",
            "SYMBIONT_BUS_DURABLE": "1",
            "SYMBIONT_BUS_DURABLE_ACK_WAIT_S": "1.0",
            "SYMBIONT_BUS_DURABLE_MAX_DELIVER": "10",
            "SYMBIONT_PARALLEL_ENABLED": "0",
            "SYMBIONT_VECTOR_STORE_DIM": "32",
            "SYMBIONT_VECTOR_STORE_DATA_DIR": f"{td}/vs",
            "SYMBIONT_VECTOR_STORE_SHARD_CAPACITY": "256",
            "SYMBIONT_GRAPH_STORE_DATA_DIR": f"{td}/gs",
            "SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH": f"{td}/markov.json",
            # tiny real engine (test_tcp_bus full-stack geometry): boots in
            # seconds on CPU, compiles two buckets on first embed
            "SYMBIONT_ENGINE_EMBEDDING_DIM": "32",
            "SYMBIONT_ENGINE_LENGTH_BUCKETS": "[16, 32]",
            "SYMBIONT_ENGINE_BATCH_BUCKETS": "[2, 8]",
            "SYMBIONT_ENGINE_MAX_BATCH": "8",
            "SYMBIONT_ENGINE_DTYPE": "float32",
            "SYMBIONT_ENGINE_DATA_PARALLEL": "0",
            "SYMBIONT_ENGINE_FLUSH_DEADLINE_MS": "2.0",
        }
        gateway_env = {
            **common,
            "SYMBIONT_API_HOST": "127.0.0.1",
            "SYMBIONT_API_PORT": str(api_port),
            "SYMBIONT_API_FUSED_SEARCH": "0",
            "SYMBIONT_API_SSE_KEEPALIVE_S": "0.5",
            # per-tenant quotas sized like the in-proc tier: normals fit,
            # the hot tenant's ~8x flood is clamped
            "SYMBIONT_ADMISSION_SEARCH_RATE": "5.0",
            "SYMBIONT_ADMISSION_SEARCH_BURST": str(
                float(MP_SEARCHES_PER_TENANT)),
            "SYMBIONT_ADMISSION_INGEST_RATE": "500.0",
            "SYMBIONT_ADMISSION_INGEST_BURST": "500.0",
            "SYMBIONT_ADMISSION_GENERATE_RATE": "100.0",
            "SYMBIONT_ADMISSION_GENERATE_BURST": "100.0",
        }

        log_path = f"{td}/workers.log"
        stdio = open(log_path, "ab")
        sup = ProcessSupervisor(bus_url=bus_url, stdio=stdio,
                                fleet_publish_s=0.3)
        sup.add_worker(pybroker_spec(broker_port, f"{td}/symbus",
                                     heartbeat_timeout_s=4.0))
        hb = dict(heartbeat_s=0.4, heartbeat_timeout_s=4.0)
        sup.add_worker(runner_spec("gateway", "api", bus_url,
                                   env=gateway_env, **hb))
        sup.add_worker(runner_spec("perception", "perception", bus_url,
                                   env=common, **hb))
        sup.add_worker(runner_spec("embed", "preprocessing", bus_url,
                                   env=common, **hb))
        sup.add_worker(runner_spec("memory", "vector_memory", bus_url,
                                   env=common, **hb))
        sup.add_worker(runner_spec("graphgen",
                                   "knowledge_graph,text_generator",
                                   bus_url, env=common, **hb))
        await sup.start()
        loop = asyncio.get_running_loop()

        from concurrent.futures import ThreadPoolExecutor

        client_pool = ThreadPoolExecutor(max_workers=32,
                                         thread_name_prefix="mp-client")

        def _http(method, path, body=None, headers=None, timeout=30):
            req = urllib.request.Request(
                f"http://127.0.0.1:{api_port}{path}",
                data=(_json.dumps(body).encode()
                      if body is not None else None),
                headers={"Content-Type": "application/json",
                         **(headers or {})}, method=method)
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return r.status, _json.loads(r.read() or b"{}")
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"{}")
            except (urllib.error.URLError, ConnectionError, OSError):
                # gateway process booting or mid-restart: status 0 lets
                # pollers keep polling instead of tearing the tier down
                return 0, {}

        def http(method, path, body=None, headers=None, timeout=30):
            return loop.run_in_executor(
                client_pool,
                lambda: _http(method, path, body, headers, timeout))

        driver_bus = None

        async def store_count() -> int:
            nonlocal driver_bus
            try:
                if driver_bus is None:
                    driver_bus = TcpBus("127.0.0.1", broker_port)
                    await driver_bus.connect()
                reply = await driver_bus.request(
                    subjects.TASKS_MEMORY_COUNT, b"{}", timeout=3.0)
                body = _json.loads(reply.data)
                return -1 if body.get("count") is None else int(body["count"])
            except (TimeoutError, ConnectionError, OSError, ValueError):
                return -1  # store process (or broker) mid-restart

        try:
            # ---- boot: gateway /readyz green + every role heartbeating --
            t_boot = time.monotonic()
            deadline = t_boot + 180
            while time.monotonic() < deadline:
                status, _ = await http("GET", "/readyz", timeout=2)
                if status == 200:
                    break
                await asyncio.sleep(0.25)
            else:
                raise RuntimeError(
                    f"gateway /readyz never went green (see {log_path})")
            for role in ("perception", "embed", "memory", "graphgen"):
                await sup.wait_role_up(role, after=t_boot - 1,
                                       timeout_s=120)
            results["load_mp_boot_s"] = round(time.monotonic() - t_boot, 2)
            log(f"multiproc deployment up in {results['load_mp_boot_s']}s "
                f"(broker + 5 worker processes)")

            # ---- phase A: first ingest wave ----------------------------
            urls = [f"http://127.0.0.1:{page_port}{path}"
                    for path in pages]
            expected = len(pages) * SENTS_PER_DOC
            half = len(urls) // 2
            t0 = time.monotonic()
            for url in urls[:half]:
                tenant = url.rsplit("/", 2)[1]
                status, _ = await http("POST", "/api/submit-url",
                                       {"url": url},
                                       {"X-Symbiont-Tenant": tenant})
                assert status == 200, status
            while (time.monotonic() < t0 + 120
                   and await store_count() < 1):
                await asyncio.sleep(0.1)

            # ---- phase B: seeded kill plan MID-INGEST ------------------
            kill_victim = str(chaos_rng.choice(["embed", "memory"]))
            stop_pool = [r for r in ("graphgen", "memory", "embed")
                         if r != kill_victim]
            stop_victim = str(chaos_rng.choice(stop_pool[:2]))
            results["load_mp_kill_victim_" + kill_victim] = 1.0
            results["load_mp_stop_victim_" + stop_victim] = 1.0
            t_kill = time.monotonic()
            os.kill(sup.pid(kill_victim), signal.SIGKILL)
            t_stop = time.monotonic()
            os.kill(sup.pid(stop_victim), signal.SIGSTOP)
            log(f"multiproc kill plan (seed {chaos_seed}): SIGKILL "
                f"{kill_victim}, SIGSTOP {stop_victim} — mid-ingest")

            # ---- phase C: second wave lands INTO the chaos -------------
            for url in urls[half:]:
                tenant = url.rsplit("/", 2)[1]
                status, _ = await http("POST", "/api/submit-url",
                                       {"url": url},
                                       {"X-Symbiont-Tenant": tenant})
                assert status == 200, status

            # ---- phase D: zero loss + recovery -------------------------
            # after = t_kill + one heartbeat period: a beat the dead
            # process published milliseconds BEFORE the SIGKILL can be
            # routed/stamped after it, and must not count as recovery
            r_kill = await sup.wait_role_up(kill_victim, after=t_kill + 1.0,
                                            timeout_s=120) - t_kill
            # the SIGSTOPped worker only recovers via the hang detector's
            # SIGKILL → restart; its liveness signal must postdate the kill
            r_stop = await sup.wait_role_up(stop_victim, after=t_stop + 4.0,
                                            timeout_s=120) - t_stop
            deadline = time.monotonic() + 180
            landed = -1
            while time.monotonic() < deadline:
                landed = await store_count()
                if landed >= expected:
                    break
                await asyncio.sleep(0.2)
            await asyncio.sleep(1.5)  # redelivery settle, then check EXACT
            landed = await store_count()
            results["load_mp_ingest_docs"] = len(pages)
            results["load_mp_expected_points"] = expected
            results["load_mp_landed_points"] = landed
            results["load_mp_zero_loss_ingest"] = float(landed == expected)
            results["load_proc_recovery_s"] = round(max(r_kill, r_stop), 2)
            results["load_mp_recovery_kill_s"] = round(r_kill, 2)
            results["load_mp_recovery_stop_s"] = round(r_stop, 2)
            log(f"multiproc ingest: {len(pages)} docs / {expected} points "
                f"across SIGKILL({kill_victim})+SIGSTOP({stop_victim}) → "
                f"{landed} landed; recovery kill {r_kill:.2f}s / "
                f"stop {r_stop:.2f}s")
            if landed != expected:
                raise RuntimeError(
                    f"load_mp_zero_loss_ingest violated: {landed}/"
                    f"{expected} points (chaos seed {chaos_seed}, "
                    f"log {log_path})")

            # ---- phase E: the broker itself dies -----------------------
            t_broker = time.monotonic()
            os.kill(sup.pid("broker"), signal.SIGKILL)
            await sup.wait_role_up("broker", after=t_broker, timeout_s=60)
            # serving again = a search round-trips through gateway →
            # preprocessing → vector_memory over the RESTARTED broker
            deadline = time.monotonic() + 60
            broker_recovered = None
            while time.monotonic() < deadline:
                status, body = await http(
                    "POST", "/api/search/semantic",
                    {"query_text": "symbiont tensor", "top_k": 2},
                    {"X-Symbiont-Tenant": "probe"}, timeout=10)
                if status == 200 and body.get("error_message") is None:
                    broker_recovered = time.monotonic() - t_broker
                    break
                await asyncio.sleep(0.5)
            if broker_recovered is None:
                raise RuntimeError(
                    "search never recovered after broker SIGKILL "
                    f"(log {log_path})")
            results["load_mp_broker_recovery_s"] = round(broker_recovered, 2)
            log(f"multiproc broker SIGKILL → stream log replayed, clients "
                f"re-attached, search serving again in "
                f"{broker_recovered:.2f}s")

            # ---- phase F: search storm, one hot tenant -----------------
            # per-process resource sampler (bench/sampler.py) over the
            # storm window: pids are re-read AFTER the kill chaos so every
            # role's restarted process is the one accounted — the chaos
            # tiers finally archive host CPU + broker bus-bytes context
            from symbiont_tpu.bench.sampler import (
                ResourceSampler,
                archive_decomposition,
            )

            roles = {}
            for role in ("broker", "gateway", "perception", "embed",
                         "memory", "graphgen"):
                pid = sup.pid(role)
                if pid is not None:
                    roles[role] = [pid]
            sampler = ResourceSampler(roles).start()
            lat_ms: list = []
            admitted = {t: 0 for t in tenants + [HOT_TENANT]}
            throttled = {t: 0 for t in tenants + [HOT_TENANT]}

            async def one_search(tenant, query):
                t1 = time.monotonic()
                status, body = await http(
                    "POST", "/api/search/semantic",
                    {"query_text": query, "top_k": 3},
                    {"X-Symbiont-Tenant": tenant}, timeout=60)
                if status == 200 and body.get("error_message") is None:
                    admitted[tenant] += 1
                    lat_ms.append((time.monotonic() - t1) * 1000.0)
                elif status == 429:
                    throttled[tenant] += 1
                else:
                    raise RuntimeError(
                        f"search failed ({tenant}): {status} {body}")

            storm = []
            for tenant in tenants:
                storm += [one_search(tenant, f"{rng.choice(VOCAB)} "
                                             f"{rng.choice(VOCAB)}")
                          for _ in range(MP_SEARCHES_PER_TENANT)]
            storm += [one_search(HOT_TENANT, f"{rng.choice(VOCAB)} flood")
                      for _ in range(MP_HOT_SEARCHES)]
            t2 = time.monotonic()
            await asyncio.gather(*storm)
            storm_s = time.monotonic() - t2
            # per-role host CPU + broker bus bytes over the storm window
            # (load_mp_storm_cpu_s_<role>, load_mp_storm_bus_mb_per_s)
            archive_decomposition(results, "load_mp_storm", sampler.stop())
            lat_ms.sort()
            n_429 = sum(throttled.values())
            fairness = jain_index(admitted.values())
            results["load_mp_search_requests"] = len(storm)
            results["load_mp_search_ok"] = sum(admitted.values())
            results["load_mp_throttled_429"] = n_429
            results["load_mp_search_p99_ms"] = round(_pct(lat_ms, 0.99), 2)
            results["load_mp_fairness_jain"] = round(fairness, 4)
            log(f"multiproc storm: {len(storm)} req in {storm_s:.2f}s → "
                f"{results['load_mp_search_ok']} ok / {n_429}x 429; "
                f"admitted {dict(sorted(admitted.items()))} → "
                f"Jain {fairness:.3f}")
            if fairness < 0.8:
                raise RuntimeError(
                    f"multiproc tenant fairness {fairness:.3f} < 0.8 "
                    f"(admitted: {admitted})")
            if n_429 == 0:
                raise RuntimeError("hot tenant was never throttled in the "
                                   "multiproc deployment")
            short = {t: admitted[t] for t in tenants
                     if admitted[t] < MP_SEARCHES_PER_TENANT}
            if short:
                raise RuntimeError(
                    f"hot tenant starved normal tenants: {short}")

            # ---- phase G: generation through the restarted worker ------
            sse_events: list = []

            async def sse_reader():
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", api_port)
                writer.write(b"GET /api/events HTTP/1.1\r\n"
                             b"Host: x\r\n\r\n")
                await writer.drain()
                try:
                    while True:
                        line = await reader.readline()
                        if not line:
                            return
                        if line.startswith(b"data: "):
                            try:
                                sse_events.append(
                                    _json.loads(line[6:].strip()))
                            except ValueError:
                                pass
                except (asyncio.CancelledError, ConnectionResetError):
                    pass
                finally:
                    writer.close()

            sse_task = asyncio.create_task(sse_reader())
            await asyncio.sleep(0.3)
            gen_ms: list = []
            for i in range(MP_GENERATIONS):
                tid = f"mp-gen-{i}"
                t3 = time.monotonic()
                status, _ = await http(
                    "POST", "/api/generate-text",
                    {"task_id": tid, "prompt": "symbiont", "max_length": 10},
                    {"X-Symbiont-Tenant": "gen"})
                assert status == 200, status
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if any(e.get("original_task_id") == tid
                           and e.get("generated_text") is not None
                           for e in sse_events):
                        gen_ms.append((time.monotonic() - t3) * 1000.0)
                        break
                    await asyncio.sleep(0.02)
                else:
                    raise RuntimeError(
                        f"no generated event for {tid} — text_generator "
                        "did not survive the kill plan")
            sse_task.cancel()
            results["load_mp_generations"] = MP_GENERATIONS
            results["load_mp_gen_p99_ms"] = round(
                _pct(sorted(gen_ms), 0.99), 1)
            log(f"multiproc generation: {MP_GENERATIONS} tasks through the "
                f"restarted worker, p99 {results['load_mp_gen_p99_ms']}ms")

            # ---- phase H: fleet telemetry — one exposition, one trace --
            # The tentpole's proof (obs/fleet.py): every supervised role
            # (the broker probe and procsup's own gauges included) must
            # appear in ONE federated /metrics exposition with a role
            # label, and a client-carried trace crossing >= 3 OS processes
            # must come back from the gateway as a single stitched tree
            # with non-null per-hop self-times.
            trace_id = f"mp-fleet-{load_seed}"
            status, body = await http(
                "POST", "/api/search/semantic",
                {"query_text": "symbiont fleet probe", "top_k": 2},
                {"X-Symbiont-Tenant": "fleet",
                 "X-Trace-Id": trace_id, "X-Span-Id": "mp-fleet-root"},
                timeout=30)
            assert status == 200, (status, body)
            # spans federate on the 0.3s publish cadence: poll the gateway
            # until the tree carries hops from the embed AND memory roles
            # alongside the gateway's own api.search span
            deadline = time.monotonic() + 45
            tree, tree_roles = None, set()
            while time.monotonic() < deadline:
                status, tree = await http("GET", f"/api/traces/{trace_id}",
                                          timeout=10)
                if status == 200:
                    tree_roles = set()

                    def note_roles(node):
                        tree_roles.add(
                            node.get("fields", {}).get("role", "gateway"))
                        for c in node.get("children", []):
                            note_roles(c)

                    for root in tree.get("roots", []):
                        note_roles(root)
                    if {"gateway", "embed", "memory"} <= tree_roles:
                        break
                await asyncio.sleep(0.3)
            results["load_mp_trace_processes"] = float(len(tree_roles))
            stitched = (tree is not None
                        and {"gateway", "embed", "memory"} <= tree_roles
                        and len(tree.get("roots", [])) == 1)
            status, cp = await http(
                "GET", f"/api/traces/{trace_id}/critical_path", timeout=10)
            hop_self_ok = (status == 200 and cp.get("chain")
                           and all(isinstance(h.get("self_ms"),
                                              (int, float))
                                   for h in cp["chain"]))
            results["load_mp_trace_stitched"] = float(
                bool(stitched and hop_self_ok))
            log(f"multiproc fleet trace: {sorted(tree_roles)} roles on one "
                f"tree (roots={len((tree or {}).get('roots', []))}), "
                f"critical path verdict: {cp.get('verdict') if status == 200 else status}")
            if not stitched:
                raise RuntimeError(
                    f"cross-process trace NOT stitched: roles {tree_roles} "
                    f"roots {len((tree or {}).get('roots', []))} "
                    f"(log {log_path})")
            if not hop_self_ok:
                raise RuntimeError(
                    f"critical path over the stitched trace lacks per-hop "
                    f"self-times: {cp}")

            # federated exposition: every role label in ONE scrape
            import re as _re

            expected_roles = {"gateway", "perception", "embed", "memory",
                              "graphgen", "procsup"}

            def _scrape() -> str:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{api_port}/metrics",
                        timeout=10) as r:
                    return r.read().decode()

            # anchor the role check to a series each role's OWN exporter
            # produces (fleet.publishes) — a bare role="..." regex would
            # also match procsup's target-role verdict labels and go green
            # with every worker exporter dead
            role_rx = _re.compile(
                r'symbiont_fleet_publishes_total\{[^}]*role="([^"]+)"')
            deadline = time.monotonic() + 30
            seen_roles: set = set()
            while time.monotonic() < deadline:
                try:
                    exposition = await loop.run_in_executor(client_pool,
                                                            _scrape)
                except OSError:
                    await asyncio.sleep(0.3)
                    continue
                seen_roles = set(role_rx.findall(exposition))
                if expected_roles <= seen_roles:
                    break
                await asyncio.sleep(0.3)
            results["load_mp_fleet_roles"] = float(len(
                expected_roles & seen_roles))
            log(f"multiproc federated /metrics: roles {sorted(seen_roles)}")
            if not expected_roles <= seen_roles:
                raise RuntimeError(
                    f"federated exposition missing roles: "
                    f"{sorted(expected_roles - seen_roles)} "
                    f"(saw {sorted(seen_roles)}; log {log_path})")

            # the /api/fleet roll-up, archived as the run's fleet snapshot
            # (per-role up / restarts / hangs / heartbeat age from procsup
            # — the broker's PING-probe verdict included — plus telemetry
            # freshness), flattened to the archive's string->number shape
            status, fleet = await http("GET", "/api/fleet", timeout=10)
            assert status == 200 and fleet.get("available"), fleet
            snap: dict = {}
            for role, e in fleet.get("roles", {}).items():
                for stat in ("up", "restarts", "hangs", "heartbeat_age_s",
                             "telemetry_age_s"):
                    v = e.get(stat)
                    if isinstance(v, (int, float)):
                        snap[f"{role}.{stat}"] = float(v)
            results["fleet_snapshot"] = snap
            if snap.get("broker.up") != 1.0:
                raise RuntimeError(
                    f"fleet roll-up lost the broker probe verdict: {snap}")
            log(f"multiproc fleet roll-up: {len(fleet['roles'])} roles, "
                f"broker up={snap.get('broker.up')}, restarts total="
                f"{sum(v for k, v in snap.items() if k.endswith('.restarts'))}")

            # ---- no unbounded queues anywhere --------------------------
            status, snap = await http("GET", "/api/metrics")
            assert status == 200
            queued = float(snap.get("gauges", {}).get("admission.queued",
                                                      0.0))
            results["load_mp_final_queued"] = queued
            if queued != 0:
                raise RuntimeError(
                    f"gateway fair queue not drained: {queued}")
            results["load_mp_worker_restarts"] = float(
                sum(sup.restarts(r) for r in
                    ("embed", "memory", "graphgen", "broker", "gateway",
                     "perception")))
        finally:
            try:
                if driver_bus is not None:
                    await driver_bus.close()
            except Exception:
                pass
            client_pool.shutdown(wait=False)
            await sup.stop()
            stdio.close()
            page_srv.close()
            await page_srv.wait_closed()


# ---------------------------------------------------------------------------
# --ramp: the load_multiproc family's TRAFFIC-RAMP phase (ROADMAP item 3's
# serving half; resilience/autoscale.py's end-to-end proof). The same
# supervised deployment — pybroker + gateway/perception/embed/memory worker
# processes, a deliberately small embed engine (~120 texts/s on CPU, so the
# ramp's backlog is real, not simulated) — under open-loop ingest that ramps
# to 4x the baseline offered rate mid-run, with the seeded kill plan STILL
# firing (SIGKILL of embed or memory mid-ramp), and the elastic autoscaler
# attached to the supervisor. Hard gates:
#
# - at least one SCALE-OUT observed (a new `embed-N` replica spawned by the
#   policy joins the durable queue group and is confirmed live), archived as
#   `load_mp_scaleout_s` (ramp start -> replica serving);
# - at least one drained SCALE-IN observed once the ramp subsides: the
#   retiring replica detaches its consumers, flushes, beats
#   `draining: true`, and exits rc 0 BEFORE the deadline (clean drain) —
#   with a submit wave landing DURING the drain, archived as
#   `load_mp_drain_loss` (expected - landed; must be exactly 0);
# - exact zero-loss ingest across the whole run (kill plan + resize);
# - Jain fairness >= 0.8 over the per-tenant search storm;
# - NO FLAP: the decision log respects the hysteresis dwell (no up-down-up
#   inside one window);
# - no rung-2 shed while capacity was addable: the gateway's SLO watchdog
#   runs live (api.search p99 budget), and the shed ladder must stay at 0 —
#   the ramp is answered with capacity, not with degraded search.
# ---------------------------------------------------------------------------

RAMP_SENTS_PER_DOC = 12
RAMP_BASE_DOCS = 6        # baseline wave, ~2 docs/s (well under capacity)
RAMP_DOCS = 72            # the 4x wave: 12 docs/s for ~6s (144 texts/s
                          # offered vs ~120/s single-replica capacity)
RAMP_DRAIN_DOCS = 10      # submitted WHILE the scale-in drain runs
RAMP_SEARCHES_PER_TENANT = 12
RAMP_HOT_SEARCHES = 90


@register("load_ramp", primary_metrics=(
        "load_mp_scaleout_s", "load_mp_drain_loss",
        "load_mp_ramp_zero_loss", "load_mp_ramp_fairness_jain"))
def tier_load_ramp(results: dict, ctx) -> None:
    import asyncio

    if not getattr(ctx, "ramp", False):
        from symbiont_tpu.bench.tiers import TierSkip

        raise TierSkip("spawns real OS processes and resizes them; pass "
                       "--ramp (scripts/multiproc.sh --ramp)")
    load_seed = int(getattr(ctx, "load_seed", 0) or 0)
    chaos_seed = int(getattr(ctx, "chaos_seed", 0) or 0)
    results["load_ramp_seed"] = load_seed
    results["load_ramp_chaos_seed"] = chaos_seed
    asyncio.run(_drive_ramp(results, load_seed, chaos_seed))


async def _drive_ramp(results: dict, load_seed: int,
                      chaos_seed: int) -> None:
    import asyncio
    import json as _json
    import os
    import signal
    import socket
    import tempfile
    import urllib.request

    from symbiont_tpu import subjects
    from symbiont_tpu.bus.tcp import TcpBus
    from symbiont_tpu.config import AutoscaleConfig
    from symbiont_tpu.resilience.autoscale import Autoscaler
    from symbiont_tpu.resilience.procsup import (
        ProcessSupervisor,
        pybroker_spec,
        runner_spec,
    )

    rng = np.random.default_rng(load_seed)
    chaos_rng = np.random.default_rng(chaos_seed)
    tenants = [f"t{i}" for i in range(N_TENANTS)]
    owners = tenants + [HOT_TENANT]

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # all pages up front, tenants round-robin; EXACT sentence arithmetic
    total_docs = RAMP_BASE_DOCS + RAMP_DOCS + RAMP_DRAIN_DOCS
    pages = {f"/ramp/{i}": _page(rng, owners[i % len(owners)], i,
                                 sents=RAMP_SENTS_PER_DOC)
             for i in range(total_docs)}
    page_srv = await _page_server(pages)
    page_port = page_srv.sockets[0].getsockname()[1]

    with tempfile.TemporaryDirectory() as td:
        broker_port = free_port()
        api_port = free_port()
        bus_url = f"symbus://127.0.0.1:{broker_port}"
        common = {
            # the bench parent holds the chip (one process per chip), so
            # these runner children are pinned to the CPU: this tier's
            # results are CPU results (docs/DEPLOYMENT.md)
            "JAX_PLATFORMS": "cpu",
            "SYMBIONT_OBS_FLEET_PUBLISH_S": "0.3",
            "SYMBIONT_BUS_DURABLE": "1",
            "SYMBIONT_BUS_DURABLE_ACK_WAIT_S": "1.5",
            "SYMBIONT_BUS_DURABLE_MAX_DELIVER": "20",
            "SYMBIONT_PARALLEL_ENABLED": "0",
            "SYMBIONT_VECTOR_STORE_DIM": "256",
            "SYMBIONT_VECTOR_STORE_DATA_DIR": f"{td}/vs",
            "SYMBIONT_VECTOR_STORE_SHARD_CAPACITY": "2048",
            "SYMBIONT_GRAPH_STORE_DATA_DIR": f"{td}/gs",
            # the ramp's capacity throttle: a REAL engine small enough to
            # boot in seconds but heavy enough (~120 texts/s embed on one
            # CPU worker) that a 144 texts/s offered ramp builds a genuine
            # batcher backlog — the exact signal the autoscaler consumes
            "SYMBIONT_ENGINE_EMBEDDING_DIM": "256",
            "SYMBIONT_ENGINE_LENGTH_BUCKETS": "[64]",
            "SYMBIONT_ENGINE_BATCH_BUCKETS": "[4]",
            "SYMBIONT_ENGINE_MAX_BATCH": "4",
            "SYMBIONT_ENGINE_DTYPE": "float32",
            "SYMBIONT_ENGINE_DATA_PARALLEL": "0",
            "SYMBIONT_ENGINE_FLUSH_DEADLINE_MS": "5.0",
        }
        gateway_env = {
            **common,
            "SYMBIONT_API_HOST": "127.0.0.1",
            "SYMBIONT_API_PORT": str(api_port),
            "SYMBIONT_API_FUSED_SEARCH": "0",
            "SYMBIONT_API_SSE_KEEPALIVE_S": "0.5",
            # the SLO watchdog runs LIVE in the gateway: rung-2 search
            # degradation is reachable in principle — the no-rung-2 gate
            # below proves the ramp was answered with capacity instead
            "SYMBIONT_OBS_SLO_P99_MS": "[\"api.search=5000\"]",
            "SYMBIONT_OBS_SLO_INTERVAL_S": "1.0",
            "SYMBIONT_ADMISSION_SEARCH_RATE": "5.0",
            "SYMBIONT_ADMISSION_SEARCH_BURST": str(
                float(RAMP_SEARCHES_PER_TENANT)),
            "SYMBIONT_ADMISSION_INGEST_RATE": "500.0",
            "SYMBIONT_ADMISSION_INGEST_BURST": "500.0",
            "SYMBIONT_ADMISSION_GENERATE_RATE": "100.0",
            "SYMBIONT_ADMISSION_GENERATE_BURST": "100.0",
        }

        log_path = f"{td}/workers.log"
        stdio = open(log_path, "ab")
        sup = ProcessSupervisor(bus_url=bus_url, stdio=stdio,
                                fleet_publish_s=0.3)
        sup.add_worker(pybroker_spec(broker_port, f"{td}/symbus",
                                     heartbeat_timeout_s=4.0))
        hb = dict(heartbeat_s=0.4, heartbeat_timeout_s=4.0)
        sup.add_worker(runner_spec("gateway", "api", bus_url,
                                   env=gateway_env, **hb))
        sup.add_worker(runner_spec("perception", "perception", bus_url,
                                   env=common, **hb))
        sup.add_worker(runner_spec("embed", "preprocessing", bus_url,
                                   env=common, **hb))
        sup.add_worker(runner_spec("memory", "vector_memory", bus_url,
                                   env=common, **hb))
        await sup.start()
        loop = asyncio.get_running_loop()

        from concurrent.futures import ThreadPoolExecutor

        client_pool = ThreadPoolExecutor(max_workers=32,
                                         thread_name_prefix="ramp-client")

        def _http(method, path, body=None, headers=None, timeout=30):
            req = urllib.request.Request(
                f"http://127.0.0.1:{api_port}{path}",
                data=(_json.dumps(body).encode()
                      if body is not None else None),
                headers={"Content-Type": "application/json",
                         **(headers or {})}, method=method)
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return r.status, _json.loads(r.read() or b"{}")
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"{}")
            except (urllib.error.URLError, ConnectionError, OSError):
                return 0, {}

        def http(method, path, body=None, headers=None, timeout=30):
            return loop.run_in_executor(
                client_pool,
                lambda: _http(method, path, body, headers, timeout))

        driver_bus = None

        async def store_count() -> int:
            nonlocal driver_bus
            try:
                if driver_bus is None:
                    driver_bus = TcpBus("127.0.0.1", broker_port)
                    await driver_bus.connect()
                reply = await driver_bus.request(
                    subjects.TASKS_MEMORY_COUNT, b"{}", timeout=3.0)
                body = _json.loads(reply.data)
                return -1 if body.get("count") is None else int(body["count"])
            except (TimeoutError, ConnectionError, OSError, ValueError):
                return -1

        doc_ids = list(pages)

        async def submit(idx: int) -> None:
            path = doc_ids[idx]
            tenant = owners[idx % len(owners)]
            status, _ = await http(
                "POST", "/api/submit-url",
                {"url": f"http://127.0.0.1:{page_port}{path}"},
                {"X-Symbiont-Tenant": tenant})
            assert status == 200, (status, path)

        autoscaler = None
        try:
            # ---- boot --------------------------------------------------
            t_boot = time.monotonic()
            deadline = t_boot + 180
            while time.monotonic() < deadline:
                status, _ = await http("GET", "/readyz", timeout=2)
                if status == 200:
                    break
                await asyncio.sleep(0.25)
            else:
                raise RuntimeError(
                    f"gateway /readyz never went green (see {log_path})")
            for role in ("perception", "embed", "memory"):
                await sup.wait_role_up(role, after=t_boot - 1, timeout_s=120)
            results["load_ramp_boot_s"] = round(time.monotonic() - t_boot, 2)
            log(f"ramp deployment up in {results['load_ramp_boot_s']}s "
                f"(broker + 4 worker processes)")

            # the supervisor's fleet aggregator is the autoscaler's signal
            # source — wait for its first federated snapshots
            deadline = time.monotonic() + 30
            while sup.fleet is None and time.monotonic() < deadline:
                await asyncio.sleep(0.1)
            if sup.fleet is None:
                raise RuntimeError("supervisor fleet aggregator never "
                                   "attached (no telemetry)")
            cfg = AutoscaleConfig(
                enabled=True, roles="embed=1:3", eval_s=0.4,
                queue_high=60.0, queue_low=15.0,
                out_dwell_s=2.0, in_dwell_s=8.0, in_clean_passes=5,
                budget_ops=8, budget_window_s=300.0, drain_deadline_s=25.0)
            autoscaler = Autoscaler(sup, cfg)
            autoscaler.start()

            # ---- baseline wave (~2 docs/s: comfortably under capacity) --
            for i in range(RAMP_BASE_DOCS):
                await submit(i)
                await asyncio.sleep(0.5)
            assert not sup.scale_events, (
                f"autoscaler scaled at BASELINE load: {sup.scale_events}")

            # ---- the 4x ramp, kill plan firing mid-run -----------------
            kill_victim = str(chaos_rng.choice(["memory", "embed"]))
            results["load_ramp_kill_" + kill_victim] = 1.0
            t_ramp = time.monotonic()
            killed = False
            probes: list = []
            for burst_start in range(RAMP_BASE_DOCS, RAMP_BASE_DOCS + RAMP_DOCS, 6):
                await asyncio.gather(*[
                    submit(i)
                    for i in range(burst_start,
                                   min(burst_start + 6,
                                       RAMP_BASE_DOCS + RAMP_DOCS))])
                if not killed and time.monotonic() - t_ramp >= 1.0:
                    killed = True
                    t_kill = time.monotonic()
                    os.kill(sup.pid(kill_victim), signal.SIGKILL)
                    log(f"ramp kill plan (seed {chaos_seed}): SIGKILL "
                        f"{kill_victim} mid-ramp")
                # interactive probes ride the ramp (BACKGROUND — a probe
                # stuck behind the killed worker must not throttle the
                # open-loop submit rate): the gateway watchdog judges
                # api.search p99 on these samples, so the ladder is live,
                # not vacuous
                probes.append(asyncio.ensure_future(http(
                    "POST", "/api/search/semantic",
                    {"query_text": f"probe {burst_start}", "top_k": 2},
                    {"X-Symbiont-Tenant": "probe"}, timeout=45)))
                await asyncio.sleep(0.5)
            ramp_s = time.monotonic() - t_ramp
            results["load_ramp_offered_docs_per_s"] = round(
                RAMP_DOCS / ramp_s, 2)
            log(f"ramp: {RAMP_DOCS} docs ({RAMP_DOCS * RAMP_SENTS_PER_DOC} "
                f"sentences) offered in {ramp_s:.1f}s "
                f"(~{RAMP_DOCS / ramp_s:.1f} docs/s, 4x the baseline)")

            # ---- gate: scale-out occurred, replica confirmed live ------
            deadline = time.monotonic() + 45
            while not any(e[2] == "out" for e in sup.scale_events) \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.2)
            outs = [e for e in sup.scale_events if e[2] == "out"]
            if not outs:
                raise RuntimeError(
                    "NO scale-out under a 4x traffic ramp: the autoscaler "
                    f"never acted (decisions: {autoscaler.decisions}, "
                    f"log {log_path})")
            ts_out, _role, _dir, new_replica = outs[0]
            t_up = await sup.wait_role_up(new_replica, after=ts_out,
                                          timeout_s=120)
            results["load_mp_scaleout_s"] = round(t_up - t_ramp, 2)
            results["load_ramp_scale_outs"] = float(len(outs))
            log(f"ramp scale-out: {new_replica} live "
                f"{results['load_mp_scaleout_s']}s after ramp start "
                f"({len(outs)} scale-out decisions)")

            # kill victim is back before the fairness storm
            await sup.wait_role_up(kill_victim, after=t_kill + 1.0,
                                   timeout_s=120)
            await asyncio.gather(*probes, return_exceptions=True)

            # the kill WINDOW may legitimately walk the shed ladder
            # (searches time out against the dead worker — PR 9's
            # degrade-don't-fail response to a FAULT, not to a capacity
            # shortfall). Wait for the ladder to step back down, then
            # baseline the shed counters: the no-rung-2 gate below covers
            # everything AFTER the fault cleared — the window where
            # capacity was genuinely addable and the autoscaler (not the
            # ladder) had to answer the ramp.
            deadline = time.monotonic() + 90
            level = -1.0  # sentinel: the pass condition must be OBSERVED
            while time.monotonic() < deadline:
                status, snap = await http("GET", "/api/metrics", timeout=10)
                if status == 200:
                    level = float(snap.get("gauges", {})
                                  .get("admission.level", 0.0))
                    if level == 0.0:
                        break
                await asyncio.sleep(0.5)
            if level != 0.0:
                raise RuntimeError(
                    "gateway never answered /api/metrics after the kill "
                    f"window (log {log_path})" if level < 0.0 else
                    f"shed ladder never recovered after the "
                    f"{kill_victim} kill window: level {level}")
            degraded_base = sum(
                v for k, v in snap.get("counters", {}).items()
                if k.startswith("admission.degraded"))
            results["load_ramp_fault_window_degraded"] = float(
                degraded_base)

            # ---- backlog fully lands (zero loss so far, exact) ---------
            expected1 = (RAMP_BASE_DOCS + RAMP_DOCS) * RAMP_SENTS_PER_DOC
            deadline = time.monotonic() + 180
            landed = -1
            while time.monotonic() < deadline:
                landed = await store_count()
                if landed >= expected1:
                    break
                await asyncio.sleep(0.3)
            log(f"ramp backlog drained: {landed}/{expected1} points landed "
                f"across the SIGKILL({kill_victim}) + resize")

            # ---- fairness storm (quotas clamp the hot tenant) ----------
            admitted = {t: 0 for t in tenants + [HOT_TENANT]}
            throttled = {t: 0 for t in tenants + [HOT_TENANT]}
            errors: list = []

            async def one_search(tenant, query):
                status, body = await http(
                    "POST", "/api/search/semantic",
                    {"query_text": query, "top_k": 3},
                    {"X-Symbiont-Tenant": tenant}, timeout=60)
                if status == 200 and body.get("error_message") is None:
                    admitted[tenant] += 1
                elif status == 429:
                    throttled[tenant] += 1
                else:
                    # the storm deliberately overlaps the scale-in: a
                    # request-reply hop is at-most-once, so a delivery
                    # racing the retiring replica's UNSUB (one broker
                    # round-trip) can still time out — bounded and
                    # counted; more than a couple means real breakage
                    errors.append((tenant, status,
                                   body.get("error_message") or body))

            storm = []
            for tenant in tenants:
                storm += [one_search(tenant, f"{rng.choice(VOCAB)} "
                                             f"{rng.choice(VOCAB)}")
                          for _ in range(RAMP_SEARCHES_PER_TENANT)]
            storm += [one_search(HOT_TENANT, f"{rng.choice(VOCAB)} flood")
                      for _ in range(RAMP_HOT_SEARCHES)]
            await asyncio.gather(*storm)
            fairness = jain_index(admitted.values())
            results["load_mp_ramp_fairness_jain"] = round(fairness, 4)
            results["load_ramp_throttled_429"] = float(
                sum(throttled.values()))
            results["load_ramp_search_errors"] = float(len(errors))
            log(f"ramp storm: {len(storm)} req -> "
                f"{sum(admitted.values())} ok / "
                f"{sum(throttled.values())}x 429 / {len(errors)} errors; "
                f"admitted {dict(sorted(admitted.items()))} -> "
                f"Jain {fairness:.3f}")
            if len(errors) > 3:
                raise RuntimeError(
                    f"{len(errors)} search failures in the ramp storm "
                    f"(first: {errors[0]}) — beyond the at-most-once "
                    "race budget")
            if fairness < 0.8:
                raise RuntimeError(
                    f"ramp tenant fairness {fairness:.3f} < 0.8 "
                    f"(admitted: {admitted})")

            # ---- gate: drained scale-in, with traffic DURING the drain -
            deadline = time.monotonic() + 60
            while not any(d == "in" for _, _, d, _ in autoscaler.decisions) \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.1)
            if not any(d == "in" for _, _, d, _ in autoscaler.decisions):
                raise RuntimeError(
                    "no scale-in after the ramp subsided (decisions: "
                    f"{autoscaler.decisions})")
            # the drain wave: submitted while the replica is retiring —
            # redelivery must route its unacked work to the survivors
            for i in range(RAMP_BASE_DOCS + RAMP_DOCS, total_docs):
                await submit(i)
            deadline = time.monotonic() + 60
            while not sup.drain_events and time.monotonic() < deadline:
                await asyncio.sleep(0.2)
            if not sup.drain_events:
                raise RuntimeError("scale-in decided but no drain "
                                   f"completed (log {log_path})")
            _ts, drained_role, clean, drain_s = sup.drain_events[0]
            results["load_ramp_drain_clean"] = float(bool(clean))
            results["load_ramp_drain_s"] = round(drain_s, 2)
            log(f"ramp scale-in: {drained_role} drained "
                f"{'CLEAN' if clean else 'by deadline SIGKILL'} in "
                f"{drain_s:.2f}s with the drain wave in flight")
            if not clean:
                raise RuntimeError(
                    f"scale-in drain was not clean: {drained_role} hit the "
                    f"deadline SIGKILL (log {log_path})")

            # ---- exact zero loss across ramp + kill + resize + drain ---
            expected_total = total_docs * RAMP_SENTS_PER_DOC
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                landed = await store_count()
                if landed >= expected_total:
                    break
                await asyncio.sleep(0.3)
            await asyncio.sleep(1.5)  # redelivery settle, then check EXACT
            landed = await store_count()
            results["load_ramp_expected_points"] = expected_total
            results["load_ramp_landed_points"] = landed
            results["load_mp_drain_loss"] = float(expected_total - landed)
            results["load_mp_ramp_zero_loss"] = float(
                landed == expected_total)
            log(f"ramp zero-loss: {landed}/{expected_total} points across "
                f"kill plan + scale-out + drained scale-in")
            if landed != expected_total:
                raise RuntimeError(
                    f"ramp zero-loss violated: {landed}/{expected_total} "
                    f"(chaos seed {chaos_seed}, log {log_path})")

            # ---- gate: no flap -----------------------------------------
            results["load_ramp_scale_decisions"] = float(
                len(autoscaler.decisions))
            dirs = [d for _, _, d, _ in autoscaler.decisions]
            compressed = [d for i, d in enumerate(dirs)
                          if i == 0 or d != dirs[i - 1]]
            if autoscaler.flaps() != 0 or compressed.count("out") > 1:
                raise RuntimeError(
                    f"autoscaler FLAPPED: decisions {autoscaler.decisions}")
            log(f"ramp hysteresis: {len(autoscaler.decisions)} decisions "
                f"({dirs}), 0 flaps")

            # ---- gate: no rung-2 shed while capacity was addable -------
            # (delta vs the post-fault baseline: the kill window's
            # degradation is PR 9's designed fault response and is
            # archived separately above)
            status, snap = await http("GET", "/api/metrics", timeout=10)
            assert status == 200, status
            level = float(snap.get("gauges", {}).get("admission.level",
                                                     0.0))
            degraded = sum(v for k, v in snap.get("counters", {}).items()
                           if k.startswith("admission.degraded"))
            new_degraded = degraded - degraded_base
            results["load_ramp_shed_level"] = level
            results["load_ramp_degraded_searches"] = float(new_degraded)
            if level >= 2 or new_degraded > 0:
                raise RuntimeError(
                    f"the ramp was answered with DEGRADED search "
                    f"(level {level}, {new_degraded} degraded serves after "
                    "the fault window closed) while capacity was still "
                    "addable — the autoscaler should have absorbed it")
            log(f"ramp SLO: shed ladder level {level:.0f}, "
                f"{new_degraded:.0f} degraded serves outside the fault "
                f"window — the ramp was answered with capacity, not "
                f"shedding")
        finally:
            try:
                if autoscaler is not None:
                    await autoscaler.stop()
            except Exception:
                pass
            try:
                if driver_bus is not None:
                    await driver_bus.close()
            except Exception:
                pass
            client_pool.shutdown(wait=False)
            await sup.stop()
            stdio.close()
            page_srv.close()
            await page_srv.wait_closed()

# ---------------------------------------------------------------------------
# --gen-chaos: the load_multiproc family's DURABLE-GENERATION phase
# (docs/RESILIENCE.md "Durable generation sessions"; resilience/genlog.py +
# services/text_generator._handle_resume end-to-end). A lean supervised
# deployment — pybroker + gateway + TWO journalled LM worker processes (a
# tiny real decoder, greedy, STREAM_CHUNK=1 so every token is a journalled
# chunk boundary) — drives three concurrent SSE token streams, then
# SIGKILLs the worker that owns a mid-flight journal tail. Hard gates:
#
# - `load_mp_gen_token_loss` must be EXACTLY 0: for every stream, the
#   SSE deltas reassembled by seq equal the final generated_text — the
#   kill lost no tokens (the journal tail re-prefilled prompt+generated
#   on the adopting replica and greedy decode continued token-identically);
# - `load_mp_gen_dupes` must be EXACTLY 0: per-stream seqs are strictly
#   contiguous with no repeats and exactly one final event — the SSE hub's
#   seq dedupe absorbed the resume's replayed chunk (exactly-once at the
#   edge, not at-least-once);
# - at least one victim-owned stream must emit events AFTER the kill
#   (proof the SIGKILL landed mid-stream and the resume plane — NOT
#   durable-bus redelivery, whose ack window is deliberately parked at
#   120s — finished it), archived as `load_mp_gen_resume_s`
#   (kill -> first adopted token at the edge);
# - every SSE data chunk arrives `id:`-stamped as `<task_id>:<seq>` (the
#   Last-Event-ID reconnect contract).
# ---------------------------------------------------------------------------

GEN_CHAOS_STREAMS = 3
GEN_CHAOS_MAX_NEW = 64


@register("load_multiproc_gen", primary_metrics=(
        "load_mp_gen_resume_s", "load_mp_gen_token_loss",
        "load_mp_gen_dupes"))
def tier_load_multiproc_gen(results: dict, ctx) -> None:
    import asyncio

    if not getattr(ctx, "gen_chaos", False):
        from symbiont_tpu.bench.tiers import TierSkip

        raise TierSkip("spawns real OS processes and SIGKILLs an LM worker "
                       "mid-stream; pass --gen-chaos "
                       "(scripts/multiproc.sh --gen-chaos)")
    load_seed = int(getattr(ctx, "load_seed", 0) or 0)
    chaos_seed = int(getattr(ctx, "chaos_seed", 0) or 0)
    results["load_mp_gen_seed"] = load_seed
    results["load_mp_gen_chaos_seed"] = chaos_seed
    asyncio.run(_drive_gen_chaos(results, load_seed, chaos_seed))


async def _drive_gen_chaos(results: dict, load_seed: int,
                           chaos_seed: int) -> None:
    import asyncio
    import json as _json
    import os
    import signal
    import socket
    import tempfile
    import urllib.request

    from symbiont_tpu.resilience.genlog import _read_tails
    from symbiont_tpu.resilience.procsup import (
        ProcessSupervisor,
        pybroker_spec,
        runner_spec,
    )
    from symbiont_tpu.utils.telemetry import metrics as _driver_metrics

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    with tempfile.TemporaryDirectory() as td:
        broker_port = free_port()
        api_port = free_port()
        bus_url = f"symbus://127.0.0.1:{broker_port}"
        genlog_dir = f"{td}/genlog"
        common = {
            # the bench parent holds the chip (one process per chip), so
            # these runner children are pinned to the CPU: this tier's
            # results are CPU results (docs/DEPLOYMENT.md)
            "JAX_PLATFORMS": "cpu",
            "SYMBIONT_OBS_FLEET_PUBLISH_S": "0.3",
            "SYMBIONT_BUS_DURABLE": "1",
            # the LONG ack window is the point: a 1s ack_wait would
            # redeliver the (multi-second, compile-included) LM stream
            # mid-flight and the re-run's un-seq'd FINAL event would break
            # the exactly-once gate. Inside this tier, recovery from the
            # kill must come from the journal resume plane alone.
            "SYMBIONT_BUS_DURABLE_ACK_WAIT_S": "120.0",
            "SYMBIONT_BUS_DURABLE_MAX_DELIVER": "3",
            "SYMBIONT_PARALLEL_ENABLED": "0",
        }
        gen_env = {
            **common,
            "SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH": f"{td}/markov.json",
            # tiny real decoder: 2 layers x 64 wide boots and compiles in
            # seconds on CPU; greedy so the adopted continuation must be
            # token-identical to the unkilled stream
            "SYMBIONT_LM_ENABLED": "1",
            "SYMBIONT_LM_ARCH": "llama",
            "SYMBIONT_LM_HIDDEN_SIZE": "64",
            "SYMBIONT_LM_NUM_LAYERS": "2",
            "SYMBIONT_LM_NUM_HEADS": "4",
            "SYMBIONT_LM_INTERMEDIATE_SIZE": "128",
            "SYMBIONT_LM_MAX_POSITIONS": "256",
            "SYMBIONT_LM_DTYPE": "float32",
            # the top bucket leaves re-prefill headroom: an adopted resume
            # enters prompt + generated-so-far (~14 + up to 64 byte tokens)
            # as its prompt, and truncating it would lose tokens
            "SYMBIONT_LM_PROMPT_BUCKETS": "[16, 64, 128]",
            "SYMBIONT_LM_NEW_TOKEN_BUCKETS": "[64]",
            "SYMBIONT_LM_TEMPERATURE": "0.0",
            # every token is a chunk boundary: 64 journalled host syncs per
            # stream = the widest possible kill window
            "SYMBIONT_LM_STREAM_CHUNK": "1",
            "SYMBIONT_GEN_JOURNAL_ENABLED": "1",
            "SYMBIONT_GEN_JOURNAL_DIR": genlog_dir,
        }
        gateway_env = {
            **common,
            "SYMBIONT_API_HOST": "127.0.0.1",
            "SYMBIONT_API_PORT": str(api_port),
            "SYMBIONT_API_SSE_KEEPALIVE_S": "0.5",
            "SYMBIONT_ADMISSION_GENERATE_RATE": "100.0",
            "SYMBIONT_ADMISSION_GENERATE_BURST": "100.0",
        }
        log_path = f"{td}/workers.log"
        stdio = open(log_path, "ab")
        sup = ProcessSupervisor(bus_url=bus_url, stdio=stdio,
                                fleet_publish_s=0.3)
        sup.add_worker(pybroker_spec(broker_port, f"{td}/symbus",
                                     heartbeat_timeout_s=4.0))
        hb = dict(heartbeat_s=0.4, heartbeat_timeout_s=4.0)
        sup.add_worker(runner_spec("gateway", "api", bus_url,
                                   env=gateway_env, **hb))
        sup.add_worker(runner_spec("gen1", "text_generator", bus_url,
                                   env=gen_env, **hb))
        sup.add_worker(runner_spec("gen2", "text_generator", bus_url,
                                   env=gen_env, **hb))
        await sup.start()
        loop = asyncio.get_running_loop()

        from concurrent.futures import ThreadPoolExecutor

        client_pool = ThreadPoolExecutor(max_workers=8,
                                         thread_name_prefix="genchaos")

        def _http(method, path, body=None, headers=None, timeout=30):
            req = urllib.request.Request(
                f"http://127.0.0.1:{api_port}{path}",
                data=(_json.dumps(body).encode()
                      if body is not None else None),
                headers={"Content-Type": "application/json",
                         **(headers or {})}, method=method)
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return r.status, _json.loads(r.read() or b"{}")
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read() or b"{}")
            except (urllib.error.URLError, ConnectionError, OSError):
                return 0, {}

        def http(method, path, body=None, headers=None, timeout=30):
            return loop.run_in_executor(
                client_pool,
                lambda: _http(method, path, body, headers, timeout))

        # (t_monotonic, sse_id_or_None, parsed_event) triples — the id line
        # is the satellite's reconnect contract, so the reader keeps it
        sse_events: list = []

        async def sse_reader():
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", api_port)
            writer.write(b"GET /api/events HTTP/1.1\r\n"
                         b"Host: x\r\n\r\n")
            await writer.drain()
            pending_id = None
            try:
                while True:
                    line = await reader.readline()
                    if not line:
                        return
                    if line.startswith(b"id: "):
                        pending_id = line[4:].strip().decode()
                    elif line.startswith(b"data: "):
                        try:
                            sse_events.append((time.monotonic(), pending_id,
                                               _json.loads(line[6:].strip())))
                        except ValueError:
                            pass
                        pending_id = None
            except (asyncio.CancelledError, ConnectionResetError):
                pass
            finally:
                writer.close()

        sse_task = None
        try:
            # ---- boot: gateway green, both LM workers heartbeating ------
            t_boot = time.monotonic()
            deadline = t_boot + 180
            while time.monotonic() < deadline:
                status, _ = await http("GET", "/readyz", timeout=2)
                if status == 200:
                    break
                await asyncio.sleep(0.25)
            else:
                raise RuntimeError(
                    f"gateway /readyz never went green (see {log_path})")
            for role in ("gen1", "gen2"):
                await sup.wait_role_up(role, after=t_boot - 1, timeout_s=120)
            results["load_mp_gen_boot_s"] = round(
                time.monotonic() - t_boot, 2)
            log(f"gen-chaos deployment up in "
                f"{results['load_mp_gen_boot_s']}s (broker + gateway + "
                f"2 journalled LM workers)")

            sse_task = asyncio.create_task(sse_reader())
            await asyncio.sleep(0.3)

            # ---- three concurrent token streams -------------------------
            tids = [f"mp-genchaos-{i}" for i in range(GEN_CHAOS_STREAMS)]
            for i, tid in enumerate(tids):
                status, _ = await http(
                    "POST", "/api/generate-text",
                    {"task_id": tid, "prompt": f"symbiont gen {i}",
                     "max_length": GEN_CHAOS_MAX_NEW, "stream": True},
                    {"X-Symbiont-Tenant": "gen"})
                assert status == 200, status

            # ---- pick the victim off the LIVE JOURNAL, then SIGKILL -----
            # wait until every stream has journalled at least one chunk
            # (first compile serializes them; after it, chunks flow) — a
            # victim-owned stream with NO tail yet would have nothing to
            # resume from and would stall out the tier on the parked
            # 120s ack window
            roles = ("gen1", "gen2")
            live: dict = {}
            deadline = time.monotonic() + 180
            tail_seq: dict = {}
            while time.monotonic() < deadline:
                live = {}
                for role in roles:
                    tails = _read_tails(
                        os.path.join(genlog_dir, f"{role}.genlog"))
                    for tid, rec in tails.items():
                        if tid in tids:
                            live[tid] = role
                            tail_seq[tid] = int(rec.get("seq") or 0)
                if len(live) == len(tids):
                    break
                await asyncio.sleep(0.005)
            else:
                raise RuntimeError(
                    f"streams never all journalled a chunk "
                    f"(live {live}; see {log_path})")
            owned = {r: [t for t, rr in live.items() if rr == r]
                     for r in roles}
            pool = [r for r in roles if owned[r]]
            victim = str(np.random.default_rng(chaos_seed).choice(pool))
            victim_tids = set(owned[victim])
            t_kill = time.monotonic()
            os.kill(sup.pid(victim), signal.SIGKILL)
            log(f"gen-chaos kill plan (seed {chaos_seed}): SIGKILL {victim} "
                f"mid-stream, owning {sorted(victim_tids)} "
                f"(journal live: {live})")

            # ---- every stream must finish exactly-once ------------------
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                finals = {tid for _, _, e in sse_events
                          if e.get("original_task_id") in tids
                          and e.get("generated_text") is not None
                          for tid in [e["original_task_id"]]}
                if finals >= set(tids):
                    break
                await asyncio.sleep(0.05)
            else:
                missing = set(tids) - finals
                raise RuntimeError(
                    f"streams never completed after the kill: {missing} "
                    f"(resume plane dead? see {log_path})")
            # a beat for trailing done-chunks racing the final event
            await asyncio.sleep(0.5)

            r_restart = await sup.wait_role_up(victim, after=t_kill + 1.0,
                                               timeout_s=120) - t_kill
            results["load_mp_gen_restart_s"] = round(r_restart, 2)

            # ---- gates --------------------------------------------------
            token_loss = 0
            dupes = 0
            chunks_total = 0
            bad_ids = 0
            for tid in tids:
                evs = [(t, sid, e) for t, sid, e in sse_events
                       if e.get("original_task_id") == tid]
                deltas = [(int(e["seq"]), e.get("text_delta") or "", t, sid)
                          for t, sid, e in evs
                          if "text_delta" in e and not e.get("done")]
                finals = [e for _, _, e in evs
                          if e.get("generated_text") is not None]
                seqs = [s for s, _, _, _ in deltas]
                # exactly-once: no repeats, no holes, exactly one final
                dupes += len(seqs) - len(set(seqs))
                dupes += max(0, len(finals) - 1)
                if sorted(set(seqs)) != list(range(len(set(seqs)))):
                    token_loss += 1  # a hole IS lost tokens
                text = "".join(d for _, d, _, _ in
                               sorted(deltas, key=lambda x: x[0]))
                if not finals or text != finals[0]["generated_text"]:
                    token_loss += 1
                bad_ids += sum(1 for s, _, _, sid in deltas
                               if sid != f"{tid}:{s}")
                chunks_total += len(deltas)
            results["load_mp_gen_streams"] = float(len(tids))
            results["load_mp_gen_chunks"] = float(chunks_total)
            results["load_mp_gen_token_loss"] = float(token_loss)
            results["load_mp_gen_dupes"] = float(dupes)
            results["load_mp_gen_victim_" + victim] = 1.0
            results["load_mp_gen_victim_tasks"] = float(len(victim_tids))

            # the kill must have landed MID-STREAM and the resume plane
            # must have finished the stream: some victim-owned task has
            # token events AFTER the kill at seqs PAST its journal tail.
            # The poll-time tail is stale within milliseconds (chunks keep
            # flowing between the read and the SIGKILL), so the TRUE tail
            # comes from the rotated orphan file — the dead worker's
            # journal frozen at the kill, exactly what the adopter
            # resumed from. Journal-before-yield means any seq beyond it
            # is adopter-produced.
            for tid, rec in _read_tails(os.path.join(
                    genlog_dir, f"{victim}.genlog.orphaned")).items():
                if tid in victim_tids:
                    tail_seq[tid] = int(rec.get("seq") or 0)
            post_kill = [t - t_kill for t, _, e in sse_events
                         if e.get("original_task_id") in victim_tids
                         and "text_delta" in e and t > t_kill
                         and int(e.get("seq") or 0)
                         > tail_seq[e["original_task_id"]]]
            if not post_kill:
                raise RuntimeError(
                    f"no victim-owned stream emitted tokens after the "
                    f"SIGKILL — the kill missed the stream window or the "
                    f"resume plane never adopted (see {log_path})")
            results["load_mp_gen_resume_s"] = round(min(post_kill), 2)

            # the supervisor's rescue runs IN THIS PROCESS: its orphan
            # counter is the direct proof recovery came from the journal
            # plane, not from a lucky bus redelivery
            orphans = float(_driver_metrics.get("gen.orphans", 0.0))
            results["load_mp_gen_orphans"] = orphans
            if orphans < 1:
                raise RuntimeError(
                    "supervisor rescued no journal tails — the kill was "
                    "absorbed some other way; the tier proved nothing")
            if token_loss:
                raise RuntimeError(
                    f"TOKENS LOST across the kill: {token_loss} stream(s) "
                    f"reassembled != final text (see {log_path})")
            if dupes:
                raise RuntimeError(
                    f"duplicate deliveries at the SSE edge: {dupes} "
                    f"(exactly-once broken; see {log_path})")
            if bad_ids:
                raise RuntimeError(
                    f"{bad_ids} SSE chunks arrived without the "
                    f"task:seq id stamp (Last-Event-ID contract broken)")
            log(f"gen-chaos: {len(tids)} streams x {GEN_CHAOS_MAX_NEW} "
                f"tokens exactly-once across a mid-stream SIGKILL of "
                f"{victim}; resume {results['load_mp_gen_resume_s']}s, "
                f"restart {results['load_mp_gen_restart_s']}s, "
                f"{chunks_total} chunks, 0 lost, 0 duped")
        finally:
            if sse_task is not None:
                sse_task.cancel()
            client_pool.shutdown(wait=False)
            await sup.stop()
            stdio.close()
