"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the README quick start once, at the full width of the models the repo
serves, through the entry points a user calls: `SymbiontStack` started the
way `python -m symbiont_tpu.runner` starts it (config from the environment,
its own `ApiService` on a loopback port, the default `inproc://` bus), driven
over real HTTP/SSE:

    ingest  POST /api/submit-url of a page of seeded mixed-length sentences
            served by a loopback http.server (the sealed machine has no
            network), until the store holds every row
    search  POST /api/search/semantic x2, query = an ingested sentence; the
            sentence itself must come back at cosine >= 0.99 — through the
            FUSED embed+top-k path, after the boot warm-up finished
    rerank  one search with "rerank": true (the cross-encoder hop)
    gen     POST /api/generate-text batched + streamed over GET /api/events,
            temperature 0, 64 new tokens; every token either decode loop
            emitted must be a greedy choice under an f32 reference (whether
            the loops are token-identical is reported: at bf16 it depends on
            batch shape); the prompt is repeated so the radix cache takes a
            hit and must reproduce the first answer exactly
    trace   POST /api/profile/device around one more generate; the artifact
            opens with jax.profiler.ProfileData and holds TPU-plane events
            inside a host annotation's window (same clock)

then asserts from the program's OWN counters that no answer came from a
host-side fallback, checks one bf16-vs-f32 numeric anchor, compiles the
Pallas flash-attention kernels forward and backward at two real shapes
and its packed-rows form (segment mask, RoPE inside) at the looped
embedder's, against the dense reference, and checks that `block_until_ready` is an
honest completion barrier.

Encoder: the default EngineConfig (mpnet-base geometry 768x12x12x3072, bf16,
5x4 bucket table, synthetic weights from a seed) with `rerank_enabled`.
LM: GPT-2 124M widths (768/12/12/3072, 1,024 positions, bf16), paged KV with
the radix cache (`--kv-layout dense` runs the default dense layout instead).

It runs in ONE process (a chip belongs to one process), starts no other,
touches neither `git` nor `native/`, and keeps every byte of state (vector
store WAL, graph store, Markov state, journal, traces, report) under the
output directory. Every failed check raises: the first failed phase ends
the run with a non-zero exit code and no result line.

    python chip_smoke.py                    # needs a TPU; fails without one
    python chip_smoke.py --mesh dp2xtp2     # on a four-chip host
    python chip_smoke.py --rehearse-cpu     # toy widths on the CPU; the
                                            # report says platform=cpu

The last line of stdout is `{"ok": true, "device": {...}}`; the line before
it is the full report (also written to <out>/report.json), which ends with
`"claim": null` — this script measures nothing it would call a metric.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import logging
import os
import shutil
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))  # runs from a bare copy: no editable install

log = logging.getLogger("chip_smoke")

# Bars, each picked after seeing the v5e's number (PERF.md, Findings PR 21)
SELF_COS_BAR = 0.99     # self-retrieval; seen 1.0 at bf16 score resolution
ANCHOR_COS_BAR = 0.999  # bf16 engine vs f32 'highest'; seen 0.99998
TIE_TOL = 0.02          # greedy token vs f32 argmax, in logits; seen 0.0019
FLASH_TOL = 0.02        # kernel vs dense, relative to max; seen 0.005
PROMPT = "the tensor processing unit accelerates matrix products and "
NEW_TOKENS = 64

# full width on the chip; toy width for the explicit CPU rehearsal
FULL = dict(
    n_sentences=200,
    env={"SYMBIONT_LM_ARCH": "gpt2", "SYMBIONT_LM_HIDDEN_SIZE": "768",
         "SYMBIONT_LM_NUM_LAYERS": "12", "SYMBIONT_LM_NUM_HEADS": "12",
         "SYMBIONT_LM_INTERMEDIATE_SIZE": "3072",
         "SYMBIONT_LM_MAX_POSITIONS": "1024"},
    # (B, q heads, kv heads, S, D, causal, padded): encoder + decoder prefill
    flash_shapes=[(8, 12, 12, 512, 64, False, True),
                  (2, 32, 4, 1024, 64, True, False)],
    # (B, heads, L, D, a row's chunks): ouro-2.6b-embed's [8, 512] program
    packed_shapes=[(8, 16, 512, 128, (200, 180, 100))],
    barrier=(2048, 200),  # matmul side, chain length
)
TOY = dict(
    n_sentences=40,
    env={"SYMBIONT_ENGINE_EMBEDDING_DIM": "64",
         "SYMBIONT_ENGINE_LENGTH_BUCKETS": "[32, 64, 128]",
         "SYMBIONT_ENGINE_BATCH_BUCKETS": "[1, 8, 32]",
         "SYMBIONT_ENGINE_MAX_BATCH": "32",
         "SYMBIONT_VECTOR_STORE_SHARD_CAPACITY": "256",
         "SYMBIONT_LM_ARCH": "gpt2", "SYMBIONT_LM_HIDDEN_SIZE": "64",
         "SYMBIONT_LM_NUM_LAYERS": "2", "SYMBIONT_LM_NUM_HEADS": "2",
         "SYMBIONT_LM_INTERMEDIATE_SIZE": "128",
         "SYMBIONT_LM_MAX_POSITIONS": "256",
         "SYMBIONT_LM_PROMPT_BUCKETS": "[16, 64]",
         "SYMBIONT_LM_NEW_TOKEN_BUCKETS": "[16, 64]"},
    flash_shapes=[(2, 2, 2, 64, 16, False, True),
                  (1, 4, 2, 64, 16, True, False)],
    packed_shapes=[(2, 2, 128, 128, (50, 40, 20))],
    barrier=(256, 50),
)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    """`assert` that survives -O: a failed check ends the run."""
    if not cond:
        raise SmokeFailure(msg)


def phase(name: str) -> None:
    log.info("=== phase: %s", name)


# ------------------------------------------------------------ instruments

class CompileWatch:
    """jax.monitoring listeners: persistent-cache hits/misses and every
    backend compile's seconds — set-up facts for the report, not metrics."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = self.written = 0
        self.compile_s: list = []
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            # jax counts a miss only for a compile it then WRITES (one that
            # took longer than jax_persistent_cache_min_compile_time_secs)
            self.written += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s.append(secs)

    def report(self) -> dict:
        sub = [s for s in self.compile_s if s < 1.0]
        return {"backend_compiles": len(self.compile_s),
                "backend_compile_s": round(sum(self.compile_s), 1),
                "backend_compiles_under_1s": len(sub),
                "backend_compile_s_under_1s": round(sum(sub), 1),
                "persistent_cache_requests": self.requests,
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses_written": self.written}


def cache_entries(cache_dir: str) -> int:
    p = Path(cache_dir)
    return sum(1 for f in p.iterdir() if f.is_file()) if p.is_dir() else 0


def serve_page(html: str) -> ThreadingHTTPServer:
    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (stdlib name)
            body = html.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="smoke-page").start()
    return srv


def http_json(method: str, port: int, path: str, body=None, timeout=120.0):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"}, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def counter(snapshot: dict, name: str) -> float:
    """Sum a counter family over its label sets in a /api/metrics snapshot."""
    return sum(v for k, v in snapshot["counters"].items()
               if k == name or k.startswith(name + "{"))


# ----------------------------------------------------------------- phases

async def drive_stack(sizes: dict, args, out: Path, report: dict) -> None:
    """§1 of the issue: boot, ingest, search, rerank, generate, trace, then
    read the program's own counters."""
    import numpy as np

    from symbiont_tpu.bench.workload import make_sentences
    from symbiont_tpu.config import load_config
    from symbiont_tpu.engine.text import clean_text, split_sentences
    from symbiont_tpu.runner import SymbiontStack
    from symbiont_tpu.services.engine_service import EngineService
    from symbiont_tpu.services.html_extract import extract_main_text

    state = out / "state"
    if state.exists():
        shutil.rmtree(state)  # a second run must not replay the first's WAL
    env = {
        "SYMBIONT_API_HOST": "127.0.0.1", "SYMBIONT_API_PORT": "0",
        "SYMBIONT_ENGINE_RERANK_ENABLED": "1",
        "SYMBIONT_LM_ENABLED": "1",
        "SYMBIONT_LM_KV_LAYOUT": args.kv_layout,
        "SYMBIONT_LM_KV_RADIX": "1",
        "SYMBIONT_VECTOR_STORE_DATA_DIR": str(state / "vector_store"),
        "SYMBIONT_GRAPH_STORE_DATA_DIR": str(state / "graph_store"),
        "SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH":
            str(state / "markov_state.json"),
        "SYMBIONT_RESILIENCE_SPILL_DIR": str(state / "resilience"),
        # the durable generation journal is ON: it is the program's own
        # record of the token ids each decode loop emitted
        "SYMBIONT_GEN_JOURNAL_ENABLED": "1",
        "SYMBIONT_GEN_JOURNAL_DIR": str(state / "genlog"),
        "SYMBIONT_OBS_XPROF_TRACE_DIR": str(state / "xprof"),
        "SYMBIONT_OBS_HBM_POSTMORTEM_DIR": str(state / "hbm_postmortem"),
        **sizes["env"],
    }
    if args.mesh:
        from symbiont_tpu.parallel.mesh import parse_mesh_spec

        env["SYMBIONT_PARALLEL_MESH_SHAPE"] = json.dumps(
            parse_mesh_spec(args.mesh))
    cfg = load_config(env=env)

    rng = np.random.default_rng(0)
    seeded = list(dict.fromkeys(
        s + "." for s in make_sentences(sizes["n_sentences"], rng)))
    html = ("<html><body><article>"
            + "".join(f"<p>{s}</p>" for s in seeded)
            + "</article></body></html>")
    # what the pipeline will store, by the repo's own extract/clean/split
    expected = split_sentences(clean_text(extract_main_text(html)))
    check(len(expected) == len(seeded),
          f"page splits into {len(expected)} sentences, seeded {len(seeded)}")
    page = serve_page(html)
    loop = asyncio.get_running_loop()

    phase("boot")
    t0 = time.monotonic()
    stack = SymbiontStack(cfg)  # as runner.main(): own bus, own ApiService
    try:
        await stack.start()
        boot_s = time.monotonic() - t0
        port = stack.api.port

        def http(method: str, path: str, body=None):
            return loop.run_in_executor(
                None, lambda: http_json(method, port, path, body))

        report["mesh"] = ({str(k): int(v)
                           for k, v in dict(stack._mesh.shape).items()}
                          if stack._mesh is not None else None)
        status, _ = await http("GET", "/readyz")
        check(status == 200, f"/readyz answered {status} after start()")

        phase("fused warm-up")
        (eng_svc,) = [s for s in stack.services
                      if isinstance(s, EngineService)]
        await asyncio.wait_for(eng_svc._warm_task, timeout=900)
        warm_wait_s = time.monotonic() - t0 - boot_s
        report["boot"] = {"stack_start_s": round(boot_s, 1),
                          "fused_warmup_wait_after_ready_s":
                              round(warm_wait_s, 1)}

        phase("ingest")
        status, body = await http(
            "POST", "/api/submit-url",
            {"url": f"http://127.0.0.1:{page.server_address[1]}/page"})
        check(status == 200, f"submit-url: {status} {body}")
        deadline = time.monotonic() + 600
        while (stack.vector_store.count() < len(expected)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.1)
        rows = stack.vector_store.count()
        check(rows == len(expected),
              f"store holds {rows} rows, page has {len(expected)} sentences")
        report["ingest"] = {"rows": rows, "sentences": len(expected)}

        phase("search")
        searches = []
        # one short and one long sentence: two query length buckets
        by_len = sorted(expected, key=len)
        for query in (by_len[len(by_len) // 4], by_len[-1]):
            status, body = await http(
                "POST", "/api/search/semantic",
                {"query_text": query, "top_k": 5})
            check(status == 200 and not body.get("error_message"),
                  f"search: {status} {body}")
            hits = [(h["payload"]["sentence_text"], h["score"])
                    for h in body["results"]]
            self_score = [s for t, s in hits if t == query]
            check(self_score and self_score[0] >= SELF_COS_BAR,
                  f"self-retrieval below {SELF_COS_BAR}: query={query!r} "
                  f"hits={hits}")
            check(all(np.isfinite(s) for _, s in hits), f"non-finite {hits}")
            searches.append({"query_words": len(query.split()),
                             "self_score": round(self_score[0], 5),
                             "self_is_top1": hits[0][0] == query,
                             "scores": [round(s, 5) for _, s in hits]})
        report["search"] = searches

        phase("rerank")
        status, body = await http(
            "POST", "/api/search/semantic",
            {"query_text": by_len[len(by_len) // 2], "top_k": 8,
             "rerank": True})
        check(status == 200 and not body.get("error_message"),
              f"rerank search: {status} {body}")
        ce = [h["score"] for h in body["results"]]
        check(len(ce) == 8 and all(np.isfinite(s) for s in ce)
              and ce == sorted(ce, reverse=True),
              f"rerank scores not 8 finite descending values: {ce}")
        report["rerank"] = {"hits": len(ce)}

        phase("generate")
        events: list = []
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /api/events HTTP/1.1\r\nHost: smoke\r\n\r\n")
        await writer.drain()

        async def read_sse() -> None:
            while True:
                line = await reader.readline()
                if not line:
                    return
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:].strip()))

        sse = asyncio.create_task(read_sse(), name="smoke-sse")
        await asyncio.sleep(0.2)

        async def generate(task_id: str, stream: bool) -> str:
            status, body = await http(
                "POST", "/api/generate-text",
                {"task_id": task_id, "prompt": PROMPT,
                 "max_length": NEW_TOKENS, "stream": stream,
                 "temperature": 0.0})
            check(status == 200, f"generate-text {task_id}: {status} {body}")
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                for e in events:
                    if (e.get("original_task_id") == task_id
                            and "generated_text" in e):
                        return e["generated_text"]
                check(not sse.done(), "SSE stream closed early")
                await asyncio.sleep(0.05)
            raise SmokeFailure(f"no final event for {task_id}")

        def lm_counters() -> tuple:
            return (stack.lm.stats["tokens_generated"],
                    counter(metrics_now(), "kv.radix_hit_tokens_total"))

        def metrics_now() -> dict:
            from symbiont_tpu.utils.telemetry import metrics

            return metrics.snapshot()

        batched = await generate("smoke-batched", stream=False)
        streamed = await generate("smoke-streamed", stream=True)
        deltas = [e for e in events
                  if e.get("original_task_id") == "smoke-streamed"
                  and "text_delta" in e]
        check(deltas and deltas[-1]["done"] is True
              and [d["seq"] for d in deltas] == list(range(len(deltas))),
              f"stream chunks malformed: {deltas}")
        check("".join(d["text_delta"] for d in deltas) == streamed,
              "stream deltas do not concatenate to the final text")
        toks_before, hits_before = lm_counters()
        repeat = await generate("smoke-repeat", stream=False)
        toks_after, hits_after = lm_counters()
        check(repeat == batched, "repeated prompt decoded differently")
        if args.kv_layout == "paged":
            check(hits_after > hits_before,
                  "repeated prompt took no radix hit "
                  f"(kv.radix_hit_tokens_total {hits_before}->{hits_after})")
        check(toks_after - toks_before == NEW_TOKENS,
              f"repeat generated {toks_after - toks_before} tokens, "
              f"wanted {NEW_TOKENS}")
        # the token ids each loop emitted, from the program's own durable
        # record (the text is lossy: random weights emit invalid UTF-8)
        journal = state / "genlog"
        prompt_ids, b_toks = journal_tokens(journal, "smoke-batched")
        _, s_toks = journal_tokens(journal, "smoke-streamed")
        _, r_toks = journal_tokens(journal, "smoke-repeat")
        check(r_toks == b_toks, "repeated prompt emitted different tokens")
        report["generate"] = {
            "kv_layout": args.kv_layout,
            "new_tokens": NEW_TOKENS, "stream_chunks": len(deltas),
            "repeat_equals_first": True, "radix_hit_tokens": hits_after,
            **decode_agreement(stack.lm, prompt_ids,
                               {"batched": b_toks, "streamed": s_toks})}

        phase("device trace")
        import jax

        async def traced_generate() -> str:
            await asyncio.sleep(0.3)  # let the capture window open first
            with jax.profiler.TraceAnnotation("chip_smoke.generate"):
                return await generate("smoke-traced", stream=True)

        (status, cap), traced = await asyncio.gather(
            http("POST", "/api/profile/device", {"duration_s": 2.0}),
            traced_generate())
        check(status == 200 and cap.get("status") == "captured",
              f"profile/device: {status} {cap}")
        check(traced == streamed, "traced generate decoded differently")
        report["trace"] = read_trace(Path(cap["artifact"]),
                                     report["device"]["platform"])
        sse.cancel()
        writer.close()

        phase("counters")
        snap = metrics_now()
        status, exes = await http("GET", "/api/engine/executables")
        check(status == 200, f"executables: {status}")
        names = [r["executable"] for r in exes["executables"]]
        decode_exes = [n for n in names if n.startswith(
            ("lm.decode_chunk", "lm.prefill"))]
        check(stack.lm.stats["tokens_generated"] > 0 and decode_exes,
              f"no LM decode on the device: stats={stack.lm.stats} "
              f"executables={names}")
        check(any(n.startswith("qsearch") for n in names)
              and any(n.startswith("rerank") for n in names)
              and any(n.startswith("embed") for n in names),
              f"encoder executables missing from the ledger: {names}")
        zero = {
            "api.fused_search_fallback":
                counter(snap, "api.fused_search_fallback"),
            "engine.fused_warmups{failed}": snap["counters"].get(
                'engine.fused_warmups{result="failed"}', 0),
            "engine.oom_total": counter(snap, "engine.oom_total"),
            "lm.degraded": counter(snap, "lm.degraded"),
            "lm.admit_hbm_rejects": counter(snap, "lm.admit_hbm_rejects"),
        }
        check(not any(zero.values()), f"fallback counters fired: {zero}")
        fused = counter(snap, "api.fused_search")
        check(fused >= 3, f"api.fused_search={fused}, wanted all 3 searches")
        check(snap["counters"].get(
            'engine.fused_warmups{result="ok"}', 0) >= 1,
            "no successful fused warm-up was counted")

        status, mem = await http("GET", "/api/memory")
        check(status == 200, f"memory: {status}")
        local = mem["local"]
        claims = {r["subsystem"]: r["bytes"] for r in local["subsystems"]}
        want_claims = ["engine.params", "lm.params",
                       "kv.page_pool" if args.kv_layout == "paged"
                       else "lm.kv_cache"]
        if report["device"]["platform"] == "tpu":
            check(local["basis"] == "memory_stats",
                  f"hbm ledger basis is {local['basis']!r}")
            check(all(claims.get(c, 0) > 0 for c in want_claims[:2])
                  and want_claims[2] in claims, f"hbm claims: {claims}")
            if args.kv_layout == "paged":
                check(claims["kv.page_pool"] > 0, f"hbm claims: {claims}")
            headroom = stack.lm.hbm_headroom_bytes()
            check(headroom is not None and headroom > 0,
                  f"lm.hbm_headroom_bytes={headroom}")
            # every device of the serving mesh holds something (a mesh
            # smaller than the host leaves the other chips untouched)
            in_mesh = {d.id for d in stack._mesh.devices.flat}
            used = {d["device"] for d in local["devices"]
                    if d["bytes_in_use"] > 0}
            check(len(local["devices"]) == report["device"]["count"]
                  and in_mesh <= used,
                  f"mesh devices {sorted(in_mesh)} without bytes in use: "
                  f"{local['devices']}")
        else:  # CPU rehearsal: no memory accounting exists to read
            headroom = stack.lm.hbm_headroom_bytes()
        check(stack.lm.can_admit(1), "can_admit(1) is false on an idle engine")
        status, traces = await http("GET", "/api/traces/recent")
        errored = [t for t in traces["traces"] if t["error_count"]]
        check(status == 200 and not errored, f"errored traces: {errored}")
        report["counters"] = {
            "lm.tokens_generated": stack.lm.stats["tokens_generated"],
            "api.fused_search": fused, **zero,
            "flash.fallback": counter(snap, "flash.fallback"),
            "engine.compiles": stack.engine.stats["compiles"],
            "engine.compile_s": round(stack.engine.stats["compile_s"], 1),
            "engine.fused_warmup_s": snap["gauges"].get(
                "engine.fused_warmup_s"),
            "ledger_executables": len(names),
            "lm_executables": sorted(n for n in names if n.startswith("lm.")),
        }
        report["memory"] = {
            "basis": local["basis"], "claims": claims,
            "unattributed_pct": local["unattributed_pct"],
            "devices": [{"device": d["device"],
                         "bytes_in_use": d["bytes_in_use"],
                         "bytes_limit": d["bytes_limit"]}
                        for d in local["devices"]],
            "lm_hbm_headroom_bytes": headroom, "can_admit_1": True}

        phase("numeric anchor")
        report["anchor"] = numeric_anchor(stack.engine, expected[:32])
    finally:
        await stack.stop()
        page.shutdown()


def journal_tokens(journal_dir: Path, task_id: str) -> tuple:
    """(prompt_ids, tokens) of a finished generation from the journal
    (resilience/genlog.py): the last snapshot per task holds every id."""
    last = None
    for path in sorted(journal_dir.glob("*.genlog")):
        for line in path.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("task_id") == task_id and rec.get("tokens"):
                last = rec
    check(last is not None, f"no journal record for {task_id}")
    check(len(last["tokens"]) == NEW_TOKENS,
          f"{task_id}: journal holds {len(last['tokens'])} tokens")
    return ([int(t) for t in last["prompt_ids"]],
            [int(t) for t in last["tokens"]])


def decode_agreement(lm, prompt_ids: list, runs: dict) -> dict:
    """Do the two decode loops emit the same tokens — and is every token
    either emitted a greedy choice of the model?

    Measured on the v5e (PERF.md, PR 21): at EQUAL batch shape the loops are
    token-identical, but the batcher's session decodes in >= 4-row batches
    and the stream in 1-row batches, and at bf16 two executables of
    different shape round differently — with random weights (logit std
    ~0.55, median top-2 margin 0.1-0.4) a top-2 gap under ~0.002 flips. So
    identity is REPORTED, and what is ASSERTED is the logit-level anchor
    that makes a flip innocent: teacher-forced through the same params in
    float32 under matmul precision 'highest', every emitted token's
    reference logit is within TIE_TOL of that position's maximum. A wrong
    token (a broken cache read, a mis-spliced page, a stale radix page)
    misses by ~1, not by 0.002."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from symbiont_tpu.models import gpt as gpt_mod

    cfg32 = dataclasses.replace(lm.model_cfg, dtype="float32",
                                attn_impl="xla", kv_quant="none")
    params32 = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        jax.device_get(lm.params))
    S = len(prompt_ids) + NEW_TOKENS

    @jax.jit
    def reference(params, ids):
        cache = gpt_mod.init_cache(cfg32, 1, S, jnp.float32)
        positions = jnp.arange(S, dtype=jnp.int32)[None]
        return gpt_mod.forward(params, ids, cache, positions, cfg32)[0][0]

    out: dict = {}
    for name, toks in runs.items():
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(reference(
                params32, jnp.asarray([prompt_ids + toks], jnp.int32)))
        # logits[p] predicts the token at p + 1
        rows = logits[len(prompt_ids) - 1:len(prompt_ids) - 1 + len(toks)]
        gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
        check(np.isfinite(rows).all(), f"{name}: reference logits not finite")
        check(gaps.max() <= TIE_TOL,
              f"{name} loop emitted a token the f32 reference scores "
              f"{gaps.max():.4f} below its argmax at position "
              f"{int(gaps.argmax())} (tolerance {TIE_TOL})")
        out[f"{name}_ref_argmax_matches"] = int((gaps == 0).sum())
        out[f"{name}_ref_gap_max"] = round(float(gaps.max()), 5)
    a, b = runs["batched"], runs["streamed"]
    first = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
    out["batched_equals_streamed"] = first is None
    out["first_divergence"] = first
    out["tie_tol"] = TIE_TOL
    for name, toks in runs.items():  # comparable across meshes and runs
        out[f"{name}_tokens_sha1"] = hashlib.sha1(
            json.dumps(toks).encode()).hexdigest()[:12]
    return out


def read_trace(artifact: Path, platform: str) -> dict:
    """Open the capture with nothing but jax: device planes, their event
    counts, and the smoke's own host annotation on the same clock."""
    import jax

    files = sorted(artifact.rglob("*.xplane.pb"))
    check(files, f"no .xplane.pb under {artifact}")
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    planes, annot, dev_spans = {}, None, []
    for plane in data.planes:
        n = 0
        for line in plane.lines:
            for ev in line.events:
                n += 1
                if ev.name == "chip_smoke.generate":
                    annot = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif plane.name.startswith("/device:TPU"):
                    dev_spans.append(ev.start_ns)
        planes[plane.name] = n
    check(annot is not None, f"host annotation missing; planes: {planes}")
    out = {"artifact": str(files[0]), "planes": planes}
    if platform == "tpu":
        tpu = {k: v for k, v in planes.items() if k.startswith("/device:TPU")}
        check(tpu and sum(tpu.values()) > 0, f"no TPU-plane events: {planes}")
        inside = sum(1 for s in dev_spans if annot[0] <= s <= annot[1])
        check(inside > 0,
              "no TPU event starts inside the host annotation's window — "
              f"clocks disagree? annotation={annot} "
              f"device range=({min(dev_spans)}, {max(dev_spans)})")
        out["tpu_events"] = sum(tpu.values())
        out["tpu_events_inside_host_annotation"] = inside
    return out


def numeric_anchor(engine, texts: list) -> dict:
    """The engine's own bf16 embeddings for one 32-row batch against the
    same params in float32 under matmul precision 'highest'."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from symbiont_tpu.engine.bucketing import pad_to_bucket
    from symbiont_tpu.models import bert as bert_mod

    got = engine.embed_texts(texts)  # the serving path, bf16
    # where one served batch's output lives: all of the mesh's 'data'
    # devices under DP, one device otherwise (the four-chip finding)
    bb = engine._batch_bucket(len(texts))
    probe = engine._warm_dispatch("embed", engine.config.length_buckets[-1],
                                  bb)  # a shape the packer forms: [bb, top]
    out_devices = len(probe.sharding.device_set)
    cfg32 = dataclasses.replace(engine.model_cfg, dtype="float32",
                                attn_impl="xla")
    max_len = engine.config.length_buckets[-1]
    enc = engine.tokenizer.encode_batch(list(texts), max_len)
    bucket = min(b for b in engine.config.length_buckets
                 if b >= max(len(e) for e in enc))
    ids, mask = pad_to_bucket(enc, bucket, engine.tokenizer.pad_id)
    params32 = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        jax.device_get(engine.params))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, i, m: bert_mod.embed_sentences(
                p, i, m, cfg32, pooling=engine.pooling,
                normalize=engine.normalize))(
            params32, jnp.asarray(ids, jnp.int32), jnp.asarray(mask)))
    check(got.shape == ref.shape == (len(texts), cfg32.hidden_size)
          and np.isfinite(got).all() and np.isfinite(ref).all(),
          f"anchor shapes/finite: {got.shape} {ref.shape}")
    cos = (got * ref).sum(-1) / (np.linalg.norm(got, axis=-1)
                                 * np.linalg.norm(ref, axis=-1))
    check(cos.min() >= ANCHOR_COS_BAR,
          f"bf16 vs f32 row cosine min {cos.min():.5f} < {ANCHOR_COS_BAR}")
    return {"rows": len(texts), "cos_min": round(float(cos.min()), 5),
            "cos_mean": round(float(cos.mean()), 5), "bar": ANCHOR_COS_BAR,
            "embed_output_devices": out_devices}


def flash_kernels(shapes: list, platform: str) -> list:
    """§7: the Pallas kernels forward AND backward against the dense
    reference. On the chip `interpret=False` is forced — compiled, never
    interpreted; the rehearsal leaves the choice to the CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from symbiont_tpu.ops.flash_attention import (
        _dense_reference,
        flash_attention,
    )

    interpret = False if platform == "tpu" else None
    out = []
    for (B, NH, NKV, S, D, causal, padded) in shapes:
        ks = jax.random.split(jax.random.key(S + NH), 4)
        q = jax.random.normal(ks[0], (B, NH, S, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, NKV, S, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, NKV, S, D), jnp.bfloat16)
        g = jax.random.normal(ks[3], (B, NH, S, D), jnp.bfloat16)
        bias = jnp.zeros((B, S), jnp.float32)
        if padded:  # rows keep 1/2 .. all of their positions
            keep = jnp.linspace(S // 2, S, B).astype(jnp.int32)
            bias = jnp.where(jnp.arange(S)[None, :] < keep[:, None],
                             0.0, -1e9).astype(jnp.float32)
        scale = 1.0 / float(np.sqrt(D))

        def flash(q, k, v):
            return flash_attention(q, k, v, kv_bias=bias, causal=causal,
                                   interpret=interpret)

        def dense(q, k, v):
            return _dense_reference(q, k, v, bias, causal, scale)[0]

        def fwd_bwd(fn):
            def loss(q, k, v):
                return (fn(q, k, v).astype(jnp.float32)
                        * g.astype(jnp.float32)).sum()
            return jax.jit(lambda q, k, v: (
                fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

        o_f, grads_f = fwd_bwd(flash)(q, k, v)
        o_d, grads_d = fwd_bwd(dense)(q, k, v)
        row = {"shape": f"B{B} h{NH}/{NKV} S{S} D{D}"
                        f"{' causal' if causal else ''}"
                        f"{' padded' if padded else ''}",
               "compiled": interpret is False}
        for name, a, b in (("out", o_f, o_d), ("dq", grads_f[0], grads_d[0]),
                           ("dk", grads_f[1], grads_d[1]),
                           ("dv", grads_f[2], grads_d[2])):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            check(np.isfinite(a).all(), f"flash {name} not finite: {row}")
            # bf16 inputs and outputs: judge the error against the
            # reference tensor's own scale
            err = float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))
            check(err <= FLASH_TOL,
                  f"flash {name} rel-to-max error {err:.4f} > {FLASH_TOL} "
                  f"at {row['shape']}")
            row[f"{name}_err"] = round(err, 5)
        out.append(row)
    return out


def packed_kernels(shapes: list, platform: str) -> list:
    """§7, the packed rows' form: `packed_attention` (q, k, v in the
    projections' layout, causal inside each of a row's chunks, RoPE from the
    chunk's start inside the kernel) against the dense reference handed the
    same mask and `layers.rope`'s operands. Compiled on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from symbiont_tpu.models.bert import Segments
    from symbiont_tpu.models.layers import rope, rope_tables
    from symbiont_tpu.ops.flash_attention import (
        _dense_reference,
        packed_attention,
    )

    interpret = False if platform == "tpu" else None
    out = []
    for (B, NH, L, D, chunks) in shapes:
        q, k, v = (jax.random.normal(key, (B, L, NH * D), jnp.bfloat16)
                   for key in jax.random.split(jax.random.key(L + NH), 3))
        # every row the same chunks, rolled: boundaries fall everywhere
        lengths = np.stack([np.roll(chunks, r) for r in range(B)])
        seg = Segments.of_lengths(jnp.asarray(lengths, jnp.int32), L)
        got = jax.jit(lambda q, k, v: packed_attention(
            q, k, v, seg.index, NH, rope=rope_tables(seg.position, D, 1e6),
            interpret=interpret))(q, k, v)

        def heads(t, turn):
            t = t.reshape(B, L, NH, D)
            return (rope(t, seg.position, 1e6) if turn else t).transpose(
                0, 2, 1, 3)

        want = _dense_reference(heads(q, True), heads(k, True),
                                heads(v, False), jnp.zeros((B, L)), True,
                                1.0 / float(np.sqrt(D)), seg.index)[0]
        a = np.asarray(got.astype(jnp.float32))
        b = np.asarray(want.transpose(0, 2, 1, 3).reshape(B, L, NH * D))
        row = {"shape": f"B{B} h{NH} L{L} D{D} chunks {list(chunks)} packed",
               "compiled": interpret is False}
        check(np.isfinite(a).all(), f"packed out not finite: {row}")
        err = float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))
        check(err <= FLASH_TOL, f"packed out rel-to-max error {err:.4f} > "
                                f"{FLASH_TOL} at {row['shape']}")
        row["out_err"] = round(err, 5)
        out.append(row)
    return out


def barrier_check(side: int, length: int) -> dict:
    """Is block_until_ready an honest completion barrier? Three walls of
    the same chain of `length` [side, side] matmuls: enqueue only,
    block_until_ready, and materializing one scalar. Set-up facts, not
    metrics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def chain(x):
        def body(c, _):
            return (c @ c) * (1.0 / c.shape[0]), None
        return jax.lax.scan(body, x, None, length=length)[0].sum()

    x = jnp.ones((side, side), jnp.bfloat16)
    float(chain(x))  # compile + warm

    def wall(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    pending = []
    enqueue = wall(lambda: pending.append(chain(x)))
    pending[0].block_until_ready()
    # the two walls sampled in turn, the least of five each: three of one
    # after three of the other let a busy neighbour (five test workers
    # beside the rehearsal) slow one kind and not the other
    blocked = materialized = float("inf")
    for _ in range(5):
        blocked = min(blocked,
                      wall(lambda: chain(x).block_until_ready()))
        materialized = min(materialized,
                           wall(lambda: np.asarray(chain(x))))
    check(blocked >= 0.8 * materialized,
          f"block_until_ready returned in {blocked * 1e3:.2f} ms but "
          f"materializing takes {materialized * 1e3:.2f} ms — not a barrier")
    return {"enqueue_ms": round(enqueue * 1e3, 3),
            "block_until_ready_ms": round(blocked * 1e3, 3),
            "materialize_ms": round(materialized * 1e3, 3),
            "honest_barrier": True}


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy widths on the CPU (sets JAX_PLATFORMS=cpu); "
                         "the report says platform=cpu")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh, e.g. dp4 or dp2xtp2 (default: the "
                         "stack's own default — all devices on 'data')")
    ap.add_argument("--kv-layout", choices=("paged", "dense"),
                    default="paged")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="state, traces and report.json land here")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the flag IS the explicit ask

    from symbiont_tpu.device import compile_cache_dir, require_device

    t_start = time.monotonic()
    info = require_device()  # raises DeviceUnavailable naming the platform
    want = "cpu" if args.rehearse_cpu else "tpu"
    if info.platform != want:
        raise SmokeFailure(
            f"chip_smoke needs platform={want!r}, jax found "
            f"platform={info.platform!r} ({info.count} x {info.device_kind})"
            + ("" if args.rehearse_cpu else
               "; --rehearse-cpu runs the toy-width CPU rehearsal"))
    if info.platform == "tpu":
        from symbiont_tpu.bench.workload import chip_peaks

        chip_peaks(info.device_kind)  # a kind the peak table lacks: error
    sizes = TOY if args.rehearse_cpu else FULL
    out = Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    cache_dir = compile_cache_dir()
    watch = CompileWatch()
    report: dict = {
        "ok": False,
        "device": {"platform": info.platform, "kind": info.device_kind,
                   "count": info.count},
        "versions": {"jax": info.jax, "jaxlib": info.jaxlib,
                     "libtpu": info.libtpu,
                     "python": sys.version.split()[0]},
        "rehearsal": args.rehearse_cpu,
        "compile_cache": {"dir": cache_dir,
                          "from_env": bool(os.environ.get(
                              "JAX_COMPILATION_CACHE_DIR")),
                          "entries_before": cache_entries(cache_dir)},
    }

    asyncio.run(drive_stack(sizes, args, out, report))
    phase("flash kernels")
    report["flash"] = (flash_kernels(sizes["flash_shapes"], info.platform)
                       + packed_kernels(sizes["packed_shapes"],
                                        info.platform))
    from symbiont_tpu.utils.telemetry import metrics

    flash_fb = {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("flash.fallback")}
    if info.platform == "tpu":
        # the GQA shape's backward is the announced dense recompute; the
        # interpreter and the untileable route must not have run at all
        check(set(flash_fb) <= {'flash.fallback{path="dense_gqa_backward"}'},
              f"flash kernels left the compiled path: {flash_fb}")
    report["flash_fallbacks"] = flash_fb
    phase("completion barrier")
    report["barrier"] = barrier_check(*sizes["barrier"])

    report["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    report["compile"] = watch.report()
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    report["ok"] = True
    report["claim"] = None
    line = json.dumps(report)
    (out / "report.json").write_text(line + "\n")
    print(line)
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
