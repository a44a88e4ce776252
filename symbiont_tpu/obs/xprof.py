"""Compute-plane profiler (ROADMAP item 5's measuring instrument).

The repo's standing claim is that the HOST, not the chip, is the ceiling
(36k compute-only emb/s vs 1.9k e2e; per-token Python dispatch on the
decode critical path) — but until now that was inferred from wall-clock
deltas. This module turns the claim into first-class series:

* **Dispatch ledger** — every jitted-executable call site in the engine
  plane (TpuEngine's executable cache, LmEngine's prefill / decode-chunk /
  merge-rows / scatter-prompt fns) reports ``note_dispatch(signature,
  wall_s)``: per-executable dispatch counts + host wall around the call,
  exported as ``xla.dispatches_total{executable}`` and served (with the
  XLA cost-model numbers below) at ``GET /api/engine/executables``.
  LightSeq (arxiv 2010.13887) reports its wins as kernel-launch counts
  and per-op device time for exactly this reason.

* **Live host-sync audit** — the ``jax-host-sync-in-loop`` lint rule
  inventories device->host sync sites statically (lint/allowlist.py);
  ``note_host_sync(site)`` counts the same sites at runtime as
  ``engine.host_syncs_total{site}``. ``known_sync_sites()`` mirrors the
  allowlist keys so tests can enforce two-direction parity: every
  allowlisted site has a live counter, and no counter fires from a site
  the lint rule doesn't know about.

* **XLA cost model** — at the engine's existing ``_time_first_call``
  compile seam, ``compile_analysis_for(jitted, args)`` captures the
  lowered computation's FLOPs / bytes-accessed estimate (None when the
  backend doesn't implement it); combined with the
  measured dispatch wall this places each executable on the PR 1
  roofline (bench/roofline.py:grade_executable).

* **On-demand device trace** — ``device_trace.capture()`` wraps
  ``jax.profiler.start_trace/stop_trace`` around a bounded window
  (ObsConfig.xprof_trace_max_s) under this module's profiler lock (the
  jax profiler is NOT reentrant), returning the artifact dir. Every
  program span open inside the window (utils/telemetry.span) is a
  ``symbiont.<name>`` host event in it, on the device ops' clock. Served
  at ``POST /api/profile/device`` and cross-linked from the Perfetto
  timeline export's otherData.

Ledger overhead rides the standing perf gate via the ``obs`` bench
tier's ``obs_dispatch_record_per_s`` primary — the hot path is one
small-lock dict update plus one metrics counter bump.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Optional

from symbiont_tpu.utils.telemetry import metrics

__all__ = [
    "DispatchLedger",
    "DeviceTraceCapture",
    "compile_analysis_for",
    "cost_analysis_of",
    "dispatch_ledger",
    "device_trace",
    "known_sync_sites",
    "memory_analysis_of",
]


def known_sync_sites() -> tuple:
    """The static host-sync inventory, as runtime counter site names.

    Single source of truth is the lint allowlist — the runtime audit can
    never drift from the static one because it IS the static one.
    """
    from symbiont_tpu.lint.allowlist import JAX_HOST_SYNC_ALLOWED

    return tuple(sorted(scope for (_file, scope) in JAX_HOST_SYNC_ALLOWED))


def cost_analysis_of(lowered) -> Optional[dict]:
    """FLOPs / bytes-accessed estimate off a ``Lowered`` (pre-compile, so
    the one real XLA compile still happens exactly once afterwards).

    Returns ``{"flops": float, "bytes_accessed": float}`` with absent
    estimates as 0.0, or None when the backend exposes no cost model —
    callers must treat None as "unknown", never as zero work. Only the
    ANALYSIS is tolerant; lowering and compiling are not (see
    ``compile_analysis_for``).
    """
    try:
        ca = lowered.cost_analysis()
    except Exception:  # backend-specific: no cost model is a legal answer
        return None
    # some backends return a per-device list, others one dict
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None

    def _num(key: str) -> float:
        try:
            v = float(ca.get(key, 0.0))
        except (TypeError, ValueError):
            return 0.0
        return v if v == v and v >= 0.0 else 0.0  # NaN / negative -> 0

    return {"flops": _num("flops"), "bytes_accessed": _num("bytes accessed")}


_MEMORY_FIELDS = (
    ("temp_bytes", "temp_size_in_bytes"),
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def memory_analysis_of(compiled) -> Optional[dict]:
    """Static HBM footprint of a compiled executable, from XLA's
    ``compiled.memory_analysis()`` (CompiledMemoryStats): temp (activation
    scratch), argument, output, and generated-code bytes. Returns None
    where the backend doesn't implement it — callers treat None as
    "unknown", never as zero bytes."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for name, attr in _MEMORY_FIELDS:
        try:
            v = float(getattr(ma, attr))
        except (AttributeError, TypeError, ValueError):
            continue
        if v == v and v >= 0.0:  # NaN / negative -> absent
            out[name] = int(v)
    return out or None


def compile_analysis_for(jitted, args) -> tuple:
    """Lower + compile ONCE, harvesting both analyses on the way.

    Returns ``(cost, memory, compiled)``: the cost model off the Lowered,
    the memory footprint off the Compiled, and the AOT Compiled object
    itself, which the caller dispatches through from then on — the first
    call costs exactly one trace and one XLA compile, same as calling the
    jitted fn directly. The analyses may be None (backend support varies).
    A failed ``lower()`` or ``compile()`` PROPAGATES: there is no second
    attempt under ``jit`` — a compile error is paid for once and reported
    where it happened (``lowered.compile()`` goes through the same
    persistent compilation cache as ``jit``, so nothing is lost by
    compiling here).
    """
    lowered = jitted.lower(*args)
    cost = cost_analysis_of(lowered)
    compiled = lowered.compile()
    return cost, memory_analysis_of(compiled), compiled


class _ExeStats:
    __slots__ = ("dispatches", "wall_s", "compiles", "flops",
                 "bytes_accessed", "temp_bytes", "argument_bytes",
                 "output_bytes", "generated_code_bytes")

    def __init__(self) -> None:
        self.dispatches = 0
        self.wall_s = 0.0
        self.compiles = 0
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.temp_bytes: Optional[int] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.generated_code_bytes: Optional[int] = None


class DispatchLedger:
    """Bounded per-executable dispatch table (LRU past max_executables).

    The hot path (``note_dispatch``) is called once per jitted dispatch
    on the decode critical path, so it does the minimum: one lock'd
    OrderedDict update + one counter bump. Everything derived (rates,
    roofline placement) happens at snapshot() time.
    """

    def __init__(self, max_executables: int = 256,
                 registry=None) -> None:
        self.registry = registry if registry is not None else metrics
        self._lock = threading.Lock()
        self._exes: "OrderedDict[str, _ExeStats]" = OrderedDict()
        self._max = max(1, int(max_executables))
        self._enabled = True

    def configure(self, enabled: bool = True,
                  max_executables: Optional[int] = None) -> None:
        with self._lock:
            self._enabled = bool(enabled)
            if max_executables is not None:
                self._max = max(1, int(max_executables))
                while len(self._exes) > self._max:
                    self._exes.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._exes.clear()

    def _entry(self, signature: str) -> _ExeStats:
        # caller holds self._lock
        st = self._exes.get(signature)
        if st is None:
            st = _ExeStats()
            self._exes[signature] = st
            while len(self._exes) > self._max:
                self._exes.popitem(last=False)
        else:
            self._exes.move_to_end(signature)
        return st

    def note_dispatch(self, signature: str, wall_s: float) -> None:
        """One jitted-executable call: count it + the host wall around it."""
        if not self._enabled:
            return
        with self._lock:
            st = self._entry(signature)
            st.dispatches += 1
            st.wall_s += wall_s
        self.registry.inc("xla.dispatches_total",
                          labels={"executable": signature})

    def note_compile(self, signature: str, cost: Optional[dict],
                     memory: Optional[dict] = None) -> None:
        """First-call compile of an executable (+ its cost-model numbers
        and, when the backend reports one, its static memory footprint)."""
        if not self._enabled:
            return
        with self._lock:
            st = self._entry(signature)
            st.compiles += 1
            if cost is not None:
                st.flops = cost.get("flops")
                st.bytes_accessed = cost.get("bytes_accessed")
            if memory is not None:
                for name, _attr in _MEMORY_FIELDS:
                    if name in memory:
                        setattr(st, name, int(memory[name]))

    def note_host_sync(self, site: str, n: int = 1) -> None:
        """n device->host syncs at an allowlisted site (live lint audit)."""
        if not self._enabled:
            return
        self.registry.inc("engine.host_syncs_total", n,
                          labels={"site": site})

    def register_zero(self) -> None:
        """Pre-register the xprof counter families at zero so /metrics
        (and the OBSERVABILITY.md doc-drift sweep) sees them before any
        traffic, and so every allowlisted sync site exports a series even
        if it never fires — absence of a site is itself a finding."""
        self.registry.inc("xla.dispatches_total", 0,
                          labels={"executable": "all"})
        for site in known_sync_sites():
            self.registry.inc("engine.host_syncs_total", 0,
                              labels={"site": site})

    def __len__(self) -> int:
        with self._lock:
            return len(self._exes)

    def snapshot(self) -> list:
        """Per-executable rows, most dispatches first. Cost fields are
        None (unknown) when the backend exposed no cost model."""
        with self._lock:
            rows = [(sig, st.dispatches, st.wall_s, st.compiles, st.flops,
                     st.bytes_accessed, st.temp_bytes, st.argument_bytes,
                     st.output_bytes, st.generated_code_bytes)
                    for sig, st in self._exes.items()]
        out = []
        for (sig, n, wall, compiles, flops, nbytes, temp, arg, outp,
             code) in rows:
            mean_us = (wall / n * 1e6) if n else 0.0
            out.append({
                "executable": sig,
                "dispatches": n,
                "compiles": compiles,
                "host_wall_ms": round(wall * 1000.0, 3),
                "mean_dispatch_us": round(mean_us, 1),
                "flops": flops,
                "bytes_accessed": nbytes,
                "temp_bytes": temp,
                "argument_bytes": arg,
                "output_bytes": outp,
                "generated_code_bytes": code,
            })
        out.sort(key=lambda r: -r["dispatches"])
        return out


_profile_lock = threading.Lock()


class DeviceTraceCapture:
    """On-demand bounded jax.profiler trace window.

    The jax profiler is process-global and non-reentrant, so captures
    take ``_profile_lock`` — a busy lock means one is already tracing and
    the request reports "busy" instead of corrupting the in-flight capture.
    """

    def __init__(self) -> None:
        self._trace_dir = "/tmp/symbiont_xprof"
        self._max_s = 30.0
        self._last_artifact: Optional[str] = None
        self._seq = 0

    def configure(self, trace_dir: Optional[str] = None,
                  max_s: Optional[float] = None) -> None:
        if trace_dir:
            self._trace_dir = str(trace_dir)
        if max_s is not None:
            self._max_s = float(max_s)

    @property
    def last_artifact(self) -> Optional[str]:
        return self._last_artifact

    def capture(self, duration_s: float = 1.0) -> dict:
        """Trace device+host activity for a bounded window; returns the
        artifact dir (TensorBoard/XProf layout) or a busy/error status."""
        try:
            dur = float(duration_s)
        except (TypeError, ValueError):
            raise ValueError("duration_s must be a number")
        if dur <= 0:
            raise ValueError("duration_s must be positive")
        dur = min(dur, self._max_s)
        if not _profile_lock.acquire(blocking=False):
            metrics.inc("profile.device_busy")
            return {"status": "busy",
                    "detail": "a profiler capture is already in flight"}
        try:
            self._seq += 1
            artifact = os.path.join(self._trace_dir,
                                    f"device_trace_{self._seq:04d}")
            os.makedirs(artifact, exist_ok=True)
            import jax

            t0 = time.perf_counter()
            jax.profiler.start_trace(artifact)
            try:
                time.sleep(dur)
            finally:
                jax.profiler.stop_trace()
            wall = time.perf_counter() - t0
        except Exception as e:  # backend without profiler support
            metrics.inc("profile.device_errors")
            return {"status": "error", "detail": str(e)}
        finally:
            _profile_lock.release()
        self._last_artifact = artifact
        metrics.inc("profile.device_captures")
        return {"status": "captured", "artifact": artifact,
                "window_s": round(wall, 3),
                "hint": "load in ui.perfetto.dev or tensorboard --logdir"}


# process-global instances, configured by the runner at boot
dispatch_ledger = DispatchLedger()
device_trace = DeviceTraceCapture()
