"""Decode-plane flight recorder: a bounded per-step engine timeline.

ROADMAP items 2-3 (paged KV, shared-prefix radix cache, speculative
decoding, sequence packing) are about to optimize the decode/prefill path,
but the engine was observed only through coarse gauges — nothing recorded
*per-step* batch occupancy, KV rows stranded by dense max-length slabs, or
how much prefix live sessions actually share. This module is the
instrument: a process-global bounded event ring recorded by
``LmEngine.BatchSession`` / ``GenBatcher`` / ``TpuEngine._note_padding`` at
their EXISTING chunk-boundary host syncs (recording consumes only values
already materialized on host — no new device syncs, the
``jax-host-sync-in-loop`` lint inventory is unchanged), plus two
forward-looking probes:

- a host-side token-id **prefix-overlap probe** at session admit
  (``lm.prefix_share_ratio``): how much of each new prompt is a prefix of
  a recently admitted prompt — the radix-cache win of ROADMAP item 2,
  quantified before it is built;
- a **packing-opportunity estimate** from the embed flush timeline
  (``engine.packing_opportunity_pct``): the fraction of dispatched token
  slots that perfect sequence packing would reclaim — ROADMAP item 3's
  bar, read off the live padding stream.

Surfaces: ``GET /api/engine/timeline`` (JSON summary, or ``?fmt=chrome``
for Perfetto counter tracks interleaved with the flight recorder's span
lanes — ``obs/chrome_trace.export_timeline``), the ``lm.ttft_ms`` /
``lm.tpot_ms`` Prometheus histograms fed at step boundaries, and the
``decode_*`` archive fields of the bench ``decode_timeline`` tier.

Layering: imports only ``utils/telemetry`` (the registry); the engine and
batcher record into the global ``engine_timeline`` the way every handler
records into the global ``trace_store``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

from symbiont_tpu.utils.telemetry import Metrics, metrics as _global_metrics

# event kinds recorded into the ring (dicts keep the export path trivial):
#   step   — one decode chunk: wall ms, live rows vs slab capacity,
#            engine-wide KV rows live vs allocated, chunk length
#   admit  — a prefill joined the decode plane (session start or mid-flight
#            splice): row count, prefill ms, prefix-share of the new rows
#   finish — one request completed (token count, engine-side TTFT)
#   cancel — one in-flight request aborted (client vanished)
#   queue  — a batcher queue-depth sample at a flush boundary
#   flush  — one dispatched embed/rerank batch: bucket, rows, real vs
#            padded token slots (fed from TpuEngine._note_padding)
#   resume — an orphaned generation session adopted from a dead worker's
#            journal tail (resilience/genlog.py): prefix tokens
#            re-prefilled, prefill ms
#   mem    — a per-subsystem HBM ledger sample (obs/hbm.py), taken at a
#            decode chunk boundary at most every _MEM_SAMPLE_S seconds:
#            {subsystem: bytes} — the Perfetto memory counter track
STEP, ADMIT, FINISH, CANCEL, QUEUE, FLUSH, RESUME, MEM = (
    "step", "admit", "finish", "cancel", "queue", "flush", "resume", "mem")

# prompt tokens kept per registry entry for the prefix probe: overlap past
# this depth is counted as full-depth (the radix cache would share at least
# this much) — bounds the per-admit comparison cost
_PREFIX_DEPTH = 128

# minimum seconds between hbm-ledger samples on the decode path: chunk
# boundaries arrive every few ms, byte totals move per admit/finish —
# sampling each boundary would be all cost, no signal
_MEM_SAMPLE_S = 0.5


class EngineTimeline:
    """Thread-safe bounded ring of decode-plane events + windowed probes.

    ``note_*`` calls are the hot path (one per decode chunk / dispatched
    batch): they take the lock, append one dict, update O(1) running
    aggregates, and return — summary statistics are computed at read time
    over the bounded ring, never per record. ``capacity`` <= 0 disables
    recording entirely (every note becomes a cheap early return)."""

    def __init__(self, capacity: int = 2048, prompt_window: int = 64,
                 registry: Optional[Metrics] = None):
        self.registry = registry if registry is not None else _global_metrics
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._enabled = int(capacity) > 0
        # prefix probe: recent prompt token prefixes (tuples, bounded depth)
        self._prompts: deque = deque(maxlen=max(1, int(prompt_window)))
        # windowed mean for the lm.prefix_share_ratio gauge
        self._shares: deque = deque(maxlen=256)
        # packing-opportunity window over recent embed flushes
        self._flushes: deque = deque(maxlen=128)
        self._flush_real = 0
        self._flush_total = 0
        self._last_mem_t = 0.0  # last hbm-ledger sample (monotonic)

    # ------------------------------------------------------------ lifecycle

    def configure(self, capacity: int, prompt_window: int) -> None:
        """Apply ObsConfig sizing (runner, at boot). Keeps the newest
        events, like TraceStore.set_capacity."""
        with self._lock:
            self._enabled = int(capacity) > 0
            self._ring = deque(self._ring, maxlen=max(1, int(capacity)))
            self._prompts = deque(self._prompts,
                                  maxlen=max(1, int(prompt_window)))

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._prompts.clear()
            self._shares.clear()
            self._flushes.clear()
            self._flush_real = 0
            self._flush_total = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._ring.append(ev)

    # ------------------------------------------------------------ recording

    def note_decode_step(self, wall_ms: float, rows_live: int,
                         rows_capacity: int, kv_rows_live: int,
                         kv_rows_allocated: int, steps: int,
                         sessions: int = 1,
                         pages_free: Optional[int] = None,
                         pages_live: Optional[int] = None,
                         pages_total: Optional[int] = None,
                         dispatches: Optional[int] = None,
                         host_gap_ms: Optional[float] = None,
                         spec_draft_ms: Optional[float] = None,
                         spec_verify_ms: Optional[float] = None,
                         spec_proposed: Optional[int] = None,
                         spec_accepted: Optional[int] = None) -> None:
        """One decode chunk at its existing chunk-boundary host sync.
        ``pages_*`` are the paged-KV pool occupancy snapshot (host free-
        list counters, no device sync) — None on dense-layout engines.
        ``dispatches``/``host_gap_ms`` (obs/xprof.py host-gap attribution)
        are the chunk's jitted-dispatch count and the host-think wall
        between the previous chunk's device window and this one — both
        measured from host clocks already in hand, no new device syncs;
        None from recorders that predate the compute-plane profiler.
        ``spec_*`` (speculative rounds only): draft/verify wall split and
        the round's proposed/accepted draft-token counts — absent on plain
        chunks, so spec-off recorders are byte-identical."""
        if not self._enabled:
            return
        # dense engines never pass pages_*: keep their path the exact
        # single-literal dict build the decode chunk boundary always paid
        if pages_total is None:
            ev = {"kind": STEP, "t": time.time(),
                  "wall_ms": wall_ms,
                  "rows_live": int(rows_live),
                  "rows_capacity": int(rows_capacity),
                  "kv_rows_live": int(kv_rows_live),
                  "kv_rows_allocated": int(kv_rows_allocated),
                  "steps": int(steps), "sessions": int(sessions)}
        else:
            ev = {"kind": STEP, "t": time.time(), "wall_ms": wall_ms,
                  "rows_live": int(rows_live),
                  "rows_capacity": int(rows_capacity),
                  "kv_rows_live": int(kv_rows_live),
                  "kv_rows_allocated": int(kv_rows_allocated),
                  "steps": int(steps), "sessions": int(sessions),
                  "pages_free": int(pages_free or 0),
                  "pages_live": int(pages_live or 0),
                  "pages_total": int(pages_total)}
        if host_gap_ms is not None:
            ev["dispatches"] = int(dispatches or 0)
            ev["host_gap_ms"] = float(host_gap_ms)
        if spec_proposed is not None:
            # speculative round: ``steps`` is the MEAN emitted tokens per
            # live row this boundary (fractional under per-row variable
            # advance) — restore the fraction the literal dicts' int()
            # dropped so dispatches-per-EMITTED-token stays honest
            ev["steps"] = float(steps)
            ev["spec_draft_ms"] = float(spec_draft_ms or 0.0)
            ev["spec_verify_ms"] = float(spec_verify_ms or 0.0)
            ev["spec_proposed"] = int(spec_proposed)
            ev["spec_accepted"] = int(spec_accepted or 0)
        self._append(ev)
        self._maybe_note_memory()

    def _maybe_note_memory(self) -> None:
        """Sample the hbm ledger into the ring at most every
        _MEM_SAMPLE_S — the per-subsystem memory counter track in the
        Perfetto export. Rate-limited AND cached on the ledger side
        (rows(max_age_s) shares one reader pass), so the decode chunk
        boundary pays a dict copy, not a ledger walk, almost always."""
        now = time.monotonic()
        with self._lock:
            if now - self._last_mem_t < _MEM_SAMPLE_S:
                return
            self._last_mem_t = now
        try:
            from symbiont_tpu.obs.hbm import hbm_ledger

            rows = hbm_ledger.rows(max_age_s=_MEM_SAMPLE_S)
        except Exception:
            return
        if not rows:
            return
        ev = {"kind": MEM, "t": time.time()}
        for r in rows:
            if not r["overlay"]:
                ev[r["subsystem"]] = r["bytes"]
        self._append(ev)

    def note_admit(self, rows: int, prefill_ms: float,
                   prefix_share: Optional[float] = None,
                   kind: str = "start",
                   hit_tokens: Optional[int] = None,
                   prompt_tokens: Optional[int] = None) -> None:
        """``hit_tokens``/``prompt_tokens`` (paged engines only): prompt
        tokens served from radix-shared pages vs total prompt tokens in
        this admit — the pair behind ``decode_radix_hit_pct``."""
        if not self._enabled:
            return
        ev = {"kind": ADMIT, "t": time.time(), "rows": int(rows),
              "prefill_ms": prefill_ms, "admit_kind": kind}
        if prefix_share is not None:
            ev["prefix_share"] = prefix_share
        if prompt_tokens is not None:
            ev["hit_tokens"] = int(hit_tokens or 0)
            ev["prompt_tokens"] = int(prompt_tokens)
        self._append(ev)

    def note_finish(self, tokens: int,
                    ttft_ms: Optional[float] = None,
                    radix_hit: Optional[bool] = None) -> None:
        """``radix_hit`` (paged engines only): the request's FULL prompt
        was served from the radix cache, so its prefill was skipped —
        splits the TTFT population into hit vs cold."""
        if not self._enabled:
            return
        ev = {"kind": FINISH, "t": time.time(), "tokens": int(tokens)}
        if ttft_ms is not None:
            ev["ttft_ms"] = ttft_ms
        if radix_hit is not None:
            ev["radix_hit"] = bool(radix_hit)
        self._append(ev)

    def note_cancel(self) -> None:
        if not self._enabled:
            return
        self._append({"kind": CANCEL, "t": time.time()})

    def note_resume(self, tokens: int, prefill_ms: float,
                    warm_tokens: Optional[int] = None) -> None:
        """One orphaned generation session adopted on THIS engine
        (resilience/genlog.py tail replay): ``tokens`` already generated
        by the dead worker, ``prefill_ms`` spent re-prefilling the
        prompt+generated prefix, ``warm_tokens`` of that prefix still
        radix-resident here (kv/radix.py peek). Counts ``gen.resumes`` —
        the durability plane's survival counter, paired with
        ``gen.orphans`` on the supervisor side."""
        self.registry.inc("gen.resumes")
        if warm_tokens:
            self.registry.inc("gen.resume_warm_tokens", int(warm_tokens))
        if not self._enabled:
            return
        ev = {"kind": RESUME, "t": time.time(),
              "tokens": int(tokens), "prefill_ms": float(prefill_ms)}
        if warm_tokens is not None:
            ev["warm_tokens"] = int(warm_tokens)
        self._append(ev)

    def note_queue_depth(self, queue: str, depth: int) -> None:
        if not self._enabled:
            return
        self._append({"kind": QUEUE, "t": time.time(), "queue": str(queue),
                      "depth": int(depth)})

    def note_embed_flush(self, bucket: int, batch_rows: int, n_real: int,
                         real_tokens: int, total_tokens: int) -> None:
        """One dispatched embed/rerank batch (TpuEngine._note_padding).
        Also maintains the windowed packing-opportunity estimate: the
        fraction of dispatched token slots that carried padding — exactly
        the work perfect sequence packing (ROADMAP item 3) reclaims."""
        if not self._enabled:
            return
        with self._lock:
            self._ring.append({"kind": FLUSH, "t": time.time(),
                               "bucket": int(bucket),
                               "batch_rows": int(batch_rows),
                               "n_real": int(n_real),
                               "real_tokens": int(real_tokens),
                               "total_tokens": int(total_tokens)})
            if len(self._flushes) == self._flushes.maxlen:
                old_real, old_total = self._flushes[0]
                self._flush_real -= old_real
                self._flush_total -= old_total
            self._flushes.append((int(real_tokens), int(total_tokens)))
            self._flush_real += int(real_tokens)
            self._flush_total += int(total_tokens)
            total, real = self._flush_total, self._flush_real
        # gauge write OUTSIDE the timeline lock (the registry has its own)
        if total > 0:
            self.registry.gauge_set(
                "engine.packing_opportunity_pct",
                round(100.0 * (1.0 - real / total), 2),
                labels={"service": "engine"})

    # --------------------------------------------------------- prefix probe

    def prompt_prefix_share(self, token_rows: Sequence[Sequence[int]]
                            ) -> float:
        """Host-side prefix-overlap probe at session admit: for each new
        prompt, the longest common token-id prefix with any RECENTLY
        admitted prompt, as a fraction of the (depth-bounded) prompt
        length. Returns the mean share across the admitted rows and
        updates the windowed ``lm.prefix_share_ratio`` gauge — the
        shared-RAG-template number the radix cache of ROADMAP item 2 will
        convert into prefill savings. Pure host arithmetic on already-
        encoded token ids; never touches the device."""
        if not self._enabled or not token_rows:
            return 0.0
        shares = []
        with self._lock:
            registry = list(self._prompts)
            for row in token_rows:
                head = tuple(row[:_PREFIX_DEPTH])
                if not head:
                    continue
                best = 0
                for prev in registry:
                    if best >= len(head):
                        break
                    n = 0
                    for a, b in zip(head, prev):
                        if a != b:
                            break
                        n += 1
                    if n > best:
                        best = n
                shares.append(best / len(head))
                self._prompts.append(head)
                registry.append(head)
            if not shares:
                return 0.0
            for s in shares:
                self._shares.append(s)
            window = list(self._shares)
        mean_share = sum(shares) / len(shares)
        self.registry.gauge_set(
            "lm.prefix_share_ratio",
            round(sum(window) / len(window), 4),
            labels={"service": "lm"})
        return mean_share

    # -------------------------------------------------------------- summary

    def summary(self) -> dict:
        """Aggregate view over the ring: the numbers the
        ``GET /api/engine/timeline`` endpoint, ``scripts/profile_ingest.sh
        --decode`` and the bench ``decode_timeline`` tier all read. Every
        percentage is computed over the bounded window, so it is a recent
        picture, not a process-lifetime average."""
        events = self.events()
        steps = [e for e in events if e["kind"] == STEP]
        admits = [e for e in events if e["kind"] == ADMIT]
        finishes = [e for e in events if e["kind"] == FINISH]
        cancels = [e for e in events if e["kind"] == CANCEL]
        flushes = [e for e in events if e["kind"] == FLUSH]

        def pct(num: float, den: float) -> float:
            return round(100.0 * num / den, 2) if den else 0.0

        def quantile(vals: List[float], q: float) -> float:
            if not vals:
                return 0.0
            vals = sorted(vals)
            return round(vals[min(len(vals) - 1, int(q * len(vals)))], 2)

        rows_live = sum(e["rows_live"] for e in steps)
        rows_cap = sum(e["rows_capacity"] for e in steps)
        kv_alloc = sum(e["kv_rows_allocated"] for e in steps)
        kv_stranded = sum(e["kv_rows_allocated"] - e["kv_rows_live"]
                          for e in steps)
        step_ms = [e["wall_ms"] for e in steps]
        tpot_ms = [e["wall_ms"] / e["steps"] for e in steps if e["steps"]]
        ttfts = [e["ttft_ms"] for e in finishes if "ttft_ms" in e]
        ttft_hit = [e["ttft_ms"] for e in finishes
                    if "ttft_ms" in e and e.get("radix_hit")]
        ttft_cold = [e["ttft_ms"] for e in finishes
                     if "ttft_ms" in e and e.get("radix_hit") is False]
        shares = [e["prefix_share"] for e in admits if "prefix_share" in e]
        prefill_ms = sum(e["prefill_ms"] for e in admits)
        decode_ms = sum(step_ms)
        real_tok = sum(e["real_tokens"] for e in flushes)
        total_tok = sum(e["total_tokens"] for e in flushes)
        # paged-KV view: pool occupancy from step snapshots, radix hit
        # rate from the admit events' token counts
        paged_steps = [e for e in steps if "pages_total" in e]
        hit_tok = sum(e["hit_tokens"] for e in admits
                      if "prompt_tokens" in e)
        prompt_tok = sum(e["prompt_tokens"] for e in admits
                         if "prompt_tokens" in e)

        out = {
            "decode_steps": len(steps),
            "decode_occupancy_pct": pct(rows_live, rows_cap),
            "decode_kv_stranded_pct": pct(kv_stranded, kv_alloc),
            "decode_prefix_share_pct": (
                round(100.0 * sum(shares) / len(shares), 2)
                if shares else 0.0),
            "decode_admits": len(admits),
            "decode_finishes": len(finishes),
            "decode_cancels": len(cancels),
            "decode_prefill_ms_total": round(prefill_ms, 2),
            "decode_step_ms_total": round(decode_ms, 2),
            "decode_step_ms_p50": quantile(step_ms, 0.50),
            "decode_tpot_ms_p50": quantile(tpot_ms, 0.50),
            "decode_ttft_ms_p50": quantile(ttfts, 0.50),
            "decode_ttft_ms_p99": quantile(ttfts, 0.99),
            "embed_flushes": len(flushes),
            "embed_padding_pct": pct(total_tok - real_tok, total_tok),
            "packing_opportunity_pct": pct(total_tok - real_tok, total_tok),
        }
        if paged_steps or prompt_tok:
            out["decode_radix_hit_pct"] = pct(hit_tok, prompt_tok)
            out["decode_ttft_hit_ms_p50"] = quantile(ttft_hit, 0.50)
            out["decode_ttft_cold_ms_p50"] = quantile(ttft_cold, 0.50)
        if paged_steps:
            live = sum(e["pages_live"] for e in paged_steps)
            total = sum(e["pages_total"] for e in paged_steps)
            out["decode_pages_live_pct"] = pct(live, total)
        # host-gap attribution (obs/xprof.py): only steps recorded by a
        # dispatch-aware engine carry these — like the paged fields, the
        # summary keys appear only when the underlying data exists
        gap_steps = [e for e in steps if "host_gap_ms" in e]
        if gap_steps:
            disp = sum(e["dispatches"] for e in gap_steps)
            gen_tokens = sum(e["steps"] for e in gap_steps)
            gap_ms = sum(e["host_gap_ms"] for e in gap_steps)
            busy_ms = sum(e["wall_ms"] for e in gap_steps)
            out["decode_dispatches_per_token"] = (
                round(disp / gen_tokens, 4) if gen_tokens else 0.0)
            out["decode_host_gap_pct"] = pct(gap_ms, gap_ms + busy_ms)
        # speculative-decode view: only rounds recorded by a spec-enabled
        # engine carry spec_* fields — spec-off summaries are unchanged
        spec_steps = [e for e in steps if "spec_proposed" in e]
        if spec_steps:
            proposed = sum(e["spec_proposed"] for e in spec_steps)
            accepted = sum(e["spec_accepted"] for e in spec_steps)
            out["decode_spec_rounds"] = len(spec_steps)
            out["decode_spec_accept_pct"] = pct(accepted, proposed)
            out["decode_spec_draft_ms_total"] = round(
                sum(e["spec_draft_ms"] for e in spec_steps), 2)
            out["decode_spec_verify_ms_total"] = round(
                sum(e["spec_verify_ms"] for e in spec_steps), 2)
        out["dominant_stall"] = self._dominant_stall(out)
        return out

    @staticmethod
    def _dominant_stall(s: dict) -> str:
        """One-line verdict: which measured inefficiency dominates the
        recent window — the thing the next decode-plane PR should move
        first. Heuristic over the summary's own percentages (each is the
        fraction of provisioned work NOT doing useful decode/prefill)."""
        if not s["decode_steps"] and not s["embed_flushes"]:
            return "no engine traffic recorded"
        candidates = []
        if s["decode_steps"]:
            candidates.append(("row underfill (batch occupancy "
                               f"{s['decode_occupancy_pct']}%)",
                               100.0 - s["decode_occupancy_pct"]))
            candidates.append(("stranded KV rows "
                               f"({s['decode_kv_stranded_pct']}% of "
                               "allocated slabs)",
                               s["decode_kv_stranded_pct"]))
            total = s["decode_prefill_ms_total"] + s["decode_step_ms_total"]
            if total > 0:
                prefill_pct = round(
                    100.0 * s["decode_prefill_ms_total"] / total, 2)
                candidates.append(
                    (f"admission prefills ({prefill_pct}% of engine wall)",
                     prefill_pct))
            if "decode_radix_hit_pct" in s:
                # prefix overlap the radix cache did NOT convert into
                # shared pages — cold prefills of material other sessions
                # already paid for
                cold = max(0.0, s["decode_prefix_share_pct"]
                           - s["decode_radix_hit_pct"])
                candidates.append(
                    ("cold prefix prefills (prefix share "
                     f"{s['decode_prefix_share_pct']}% vs radix hits "
                     f"{s['decode_radix_hit_pct']}%)", round(cold, 2)))
            if "decode_host_gap_pct" in s:
                # per-token Python dispatch + chunk-boundary bookkeeping —
                # the ROADMAP item 5 suspect, now measured (obs/xprof.py)
                candidates.append(
                    ("host-dispatch gap ("
                     f"{s['decode_host_gap_pct']}% of chunk wall host-side, "
                     f"{s['decode_dispatches_per_token']} dispatches/token)",
                     s["decode_host_gap_pct"]))
        if s["embed_flushes"]:
            candidates.append(("embed padding (packing opportunity "
                               f"{s['packing_opportunity_pct']}%)",
                               s["packing_opportunity_pct"]))
        label, worst = max(candidates, key=lambda c: c[1])
        if worst < 10.0:
            return "none dominant (all measured waste < 10%)"
        return label


# process-global decode-plane recorder (one per process, like trace_store)
engine_timeline = EngineTimeline()
