"""docs/OBSERVABILITY.md must not drift from the metrics the code registers.

Same discipline as tests/test_perf_doc.py, pointed at the series tables: a
stub-engine runner stack is booted and driven through one ingest + one
metrics scrape, and every metric family REGISTERED at runtime must then
appear in an OBSERVABILITY.md table row (or match the explicit
dynamic-name allowlist below). A new counter merged without its doc row
fails here, mechanically — doc coverage stops being a review nicety.

The reverse direction is deliberately not enforced: the doc also tables
series this boot cannot produce (TCP bus, breakers, LM decode, devices) —
documenting more than one stub boot exercises is correct, not drift.
"""

import asyncio
import json
import re
import urllib.request
from pathlib import Path

import numpy as np

from symbiont_tpu.utils.telemetry import metrics

REPO = Path(__file__).resolve().parent.parent

# dynamic-name families: per-span / per-route series whose NAMES embed
# runtime values — documented once by convention, not one row per name
ALLOWED_DYNAMIC = (
    re.compile(r"^span\."),           # span.<name>.ms / span.<name>.errors
    re.compile(r"^api\.(GET|POST)\."),  # api.<METHOD>.<route> counters
    # engine-plane per-op request counters: engine.<op> (+ .failed), one
    # per engine.* bus subject served (services/engine_service.py)
    re.compile(r"^engine\.[a-z_]+\.[a-z_.]+$"),
)


def _documented_families(doc: str) -> set:
    """Every backticked series name in a markdown TABLE row, label part
    stripped: "`bus.dropped{subject}`" → "bus.dropped"."""
    fams = set()
    for line in doc.splitlines():
        if not line.lstrip().startswith("|"):
            continue
        for token in re.findall(r"`([^`]+)`", line):
            name = token.split("{", 1)[0].strip()
            if re.fullmatch(r"[a-zA-Z0-9_.]+", name):
                fams.add(name)
    return fams


class _StubEngine:
    class _ModelCfg:
        hidden_size = 16

    def __init__(self):
        from symbiont_tpu.config import EngineConfig

        self.config = EngineConfig(embedding_dim=16, max_batch=8,
                                   flush_deadline_ms=2.0)
        self.model_cfg = self._ModelCfg()
        self.cross_params = None
        self.stats = {"embed_calls": 0, "compiles": 0}

    def embed_texts(self, texts):
        rng = np.random.default_rng(len(texts))
        return rng.standard_normal((len(texts), 16)).astype(np.float32)


def _boot_and_collect(tmp_path) -> set:
    """Boot the stub stack, push one document through the pipeline, scrape
    /metrics once, and return every registered metric family name."""
    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.config import (
        ApiConfig,
        GraphStoreConfig,
        SymbiontConfig,
        TextGeneratorConfig,
        VectorStoreConfig,
    )
    from symbiont_tpu.runner import SymbiontStack

    page = ("<html><body><main><p>Doc drift check sentence one.</p>"
            "<p>Doc drift check sentence two!</p></main></body></html>")
    cfg = SymbiontConfig(
        vector_store=VectorStoreConfig(dim=16,
                                       data_dir=str(tmp_path / "vs"),
                                       shard_capacity=64),
        graph_store=GraphStoreConfig(data_dir=str(tmp_path / "gs")),
        text_generator=TextGeneratorConfig(markov_state_path=None),
        api=ApiConfig(host="127.0.0.1", port=0),
    )
    cfg.runner.services = ("perception,preprocessing,vector_memory,"
                           "knowledge_graph,text_generator,api")
    # a named role turns the fleet telemetry plane on (obs/fleet.py):
    # exporter + aggregator register their `fleet.*` families at start,
    # so every one of them is doc-drift-enforced on this boot too
    cfg.runner.role = "drift"

    async def scenario() -> set:
        # the registry is the process's, and this worker has run other
        # files: what THEY registered (a real engine's series, an LM's, a
        # test's own) is not what a stub boot registers, and must not
        # decide this test either way
        metrics.reset()
        stack = SymbiontStack(cfg, bus=InprocBus(), engine=_StubEngine(),
                              fetcher=lambda url: page)
        await stack.start()
        loop = asyncio.get_running_loop()
        port = stack.api.port
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/submit-url",
                data=json.dumps({"url": "http://fake/doc"}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            assert (await loop.run_in_executor(
                None, lambda: urllib.request.urlopen(req, timeout=60))
                ).status == 200
            # both sentences stored: 120 s, where an idle machine takes
            # under one (the stack shares its cores with five other workers)
            deadline = loop.time() + 120
            while (stack.vector_store.count() < 2
                   and loop.time() < deadline):
                await asyncio.sleep(0.05)
            assert stack.vector_store.count() >= 2
            # scrape once so scrape-path series (if any) register too
            await loop.run_in_executor(None, lambda: urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=60).read())
            ex = metrics.export()
            return ({n for n, _, _ in ex["counters"]}
                    | {n for n, _, _ in ex["gauges"]}
                    | {n for n, _, _ in ex["histograms"]})
        finally:
            await stack.stop()

    return asyncio.run(scenario())


def test_every_registered_family_is_documented(tmp_path):
    registered = _boot_and_collect(tmp_path)
    assert len(registered) >= 15, registered  # the boot really ran
    # PR 15 families must be IN the sweep (registered at boot / by the one
    # ingest), or the doc-drift contract silently stops covering them:
    # usage metering counters, the tail-retention gauges, and the
    # engine-timeline gauge all register on this stub boot
    for family in ("tenant.usage.tokens_in", "tenant.usage.tokens_out",
                   "tenant.usage.embed_rows", "tenant.usage.search_queries",
                   "tenant.usage.kv_row_seconds", "obs.trace_pinned_traces",
                   "obs.trace_sampled_out", "obs.trace_pin_evicted",
                   "obs.timeline_events"):
        assert family in registered, (
            f"{family} no longer registers on the stub boot — the "
            "doc-drift sweep has a blind spot")
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = _documented_families(doc)
    def covered(name: str) -> bool:
        # a family may be tabled under its registry name (dots) or its
        # rendered exposition name (process.open_fds → process_open_fds)
        for cand in (name, name.replace(".", "_")):
            if any(cand == fam or cand.startswith(fam + ".")
                   for fam in documented):
                return True
        return False

    missing = sorted(
        name for name in registered
        if not any(rx.match(name) for rx in ALLOWED_DYNAMIC)
        and not covered(name))
    assert not missing, (
        "metric families registered at runtime but absent from every "
        f"docs/OBSERVABILITY.md series table: {missing} — add a table row "
        "(or, for a name that embeds runtime values, extend "
        "ALLOWED_DYNAMIC in this test)")


def test_documented_allowlist_patterns_are_used():
    """Guard the allowlist itself: every pattern must still match at least
    one name the doc's conventions section describes — a stale pattern
    would silently exempt future families."""
    for rx, example in ((ALLOWED_DYNAMIC[0], "span.api.search.ms"),
                        (ALLOWED_DYNAMIC[1], "api.POST./api/submit-url"),
                        (ALLOWED_DYNAMIC[2], "engine.query.search")):
        assert rx.match(example), (rx.pattern, example)
    # and the op-counter pattern must NOT swallow the static engine series
    assert not ALLOWED_DYNAMIC[2].match("engine.no_reply_inbox")
    assert not ALLOWED_DYNAMIC[2].match("engine.compiles")
