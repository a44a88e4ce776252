"""THE central lint allowlist — every deliberate exception, in one module,
each with a reason.

Conventions (enforced by the engine, established by the original
test_pipeline_wiring.py scans):

- an entry suppresses findings for ONE exact site (formats below) — never
  a file, never a rule;
- a STALE entry (one whose site no longer exists) is itself an error
  (``stale-allowlist``): when the code a waiver covered goes away, the
  waiver must go with it, so this file can only shrink ratchet-style;
- adding an entry requires the reason string to say WHY the site is
  exempt, not what it is — "bounded, latency-path payload" is a reason,
  "the rerank handler" is not.

Entry formats per table:
- ``(repo-relative file, dotted scope)`` — scope is the indent-stack
  qualified function path (``EngineService._rerank.op``);
- subject-constant NAME (SUBJECTS_UNPRODUCED_ALLOWED);
- canonical cycle string ``"a.B.c -> d.E.f -> a.B.c"`` (LOCK_ORDER_ALLOWED).
"""

from __future__ import annotations

# ---------------------------------------------------------------- wiring
# Served-but-uncalled endpoints we KEEP deliberately: the engine plane is a
# public RPC surface for native worker shells and external bus clients;
# engine.embed.query is the non-fused query-embedding endpoint exported in
# the generated C++ header for remote callers. Anything else showing up
# here is a dead limb — fix the wiring, don't grow this list.
SUBJECTS_UNPRODUCED_ALLOWED = {
    "ENGINE_EMBED_QUERY":
        "public RPC endpoint exported in the generated C++ header for "
        "remote callers; no in-repo caller by design",
}

# ------------------------------------------------------------- data plane
# (file, enclosing dotted scope) pairs that may keep a per-float
# conversion: bounded, latency-path payloads (top-k scores). Anything new
# showing up here is the hot path regressing to JSON float lists — route
# it through schema/frames (or ndarray.tolist()) instead.
FLOAT_LIST_ALLOWED = {
    ("symbiont_tpu/services/engine_service.py",
     "EngineService._rerank.op"):
        "bounded top-k score list on the latency path — a handful of "
        "floats is not a data plane",
}

# no current site may use asdict on a services/ message path; keep it that way
ASDICT_ALLOWED: dict = {}

# exactly one encoder may map a negotiated encoding value to a dtype name;
# every other dtype decision lives in schema/frames.py
FRAME_DTYPE_ALLOWED = {
    ("symbiont_tpu/services/engine_service.py",
     "EngineService._embed_batch.op"):
        "the ONE negotiated-encoding -> frame-dtype mapping site "
        "(engine-plane reply encoding: 'frame16' -> f16)",
}

# ------------------------------------------------------- async event loop
# (file, dotted scope of the ASYNC function). These sites hold a plain
# threading lock for a bounded O(spans_max) deque splice shared with
# producer THREADS (span taps fire from executor threads) — an
# asyncio.Lock cannot serve both sides, and an executor hop per splice
# would cost more than the splice.
ASYNC_BLOCKING_ALLOWED = {
    ("symbiont_tpu/obs/fleet.py", "TelemetryExporter.publish_once"):
        "bounded deque splice under the tap lock shared with executor-"
        "thread span producers; never held across I/O",
    ("symbiont_tpu/obs/fleet.py", "TelemetryExporter.stop"):
        "self.store here is the in-process TraceStore (flight recorder): "
        "remove_tap is an O(taps) in-memory list removal, not a store "
        "backend call",
}

# --------------------------------------------------------------- lock order
# canonical cycle strings the analysis flags but a dynamic guard makes
# safe. Empty: the codebase has no known ordering cycles — keep it that way.
LOCK_ORDER_ALLOWED: dict = {}

# ------------------------------------------------------------ jax hygiene
# executable-cache builders: jax.jit here is keyed/cached by bucket
# signature — each signature compiles once, by design.
JAX_JIT_IN_FUNCTION_ALLOWED = {
    ("symbiont_tpu/engine/engine.py", "TpuEngine._get_executable"):
        "THE executable cache: jit wrapped per (kind, length-bucket, "
        "batch-bucket) key, raced-miss-safe under _lock, LRU-bounded by "
        "executable_cache_size — each signature compiles exactly once",
    ("symbiont_tpu/models/mla_moe.py", "encode"):
        "no executable: `encode` runs inside the engine's own jit, and the "
        "two inner jits live for that one trace so that the layers of a "
        "program (one shape each) are traced and lowered once and called "
        "per layer; the compiler inlines the calls",
    ("symbiont_tpu/models/sala.py", "encode"):
        "no executable, as models/mla_moe.py `encode`: the inner jits live "
        "for the one trace of the engine's own jit, so that each kind of "
        "layer (sparse mixer, linear mixer, feed-forward: one shape each) is "
        "traced and lowered once and called per layer",
    ("symbiont_tpu/models/ling.py", "encode"):
        "no executable, as models/mla_moe.py `encode`: the inner jits live "
        "for the one trace of the engine's own jit, so that each kind of "
        "sub-layer (KDA mixer, MLA mixer, expert layer: one shape each) is "
        "traced and lowered once and called per layer",
    ("symbiont_tpu/models/mimo.py", "encode"):
        "no executable, as models/mla_moe.py `encode`: the inner jits live "
        "for the one trace of the engine's own jit, so that each kind of "
        "sub-layer (window mixer, full mixer, expert layer: one shape each) "
        "is traced and lowered once and called per layer",
}

# deliberate device→host sync points on the dispatch hot path: one bulk
# materialization per dispatched bucket/chunk — the documented idiom
# (engine/engine.py:61). This table IS the inventory of every host sync
# on the serving path; a new entry means a new sync point was added on
# purpose.
JAX_HOST_SYNC_ALLOWED = {
    ("symbiont_tpu/engine/engine.py", "TpuEngine.embed_texts"):
        "one bulk materialization per concat-fetch GROUP (not per batch); "
        "all device concats dispatch before any np.asarray so the d2h "
        "copies overlap — the loop is over already-dispatched groups",
    ("symbiont_tpu/engine/engine.py", "TpuEngine.rerank"):
        "per-bucket bulk materialization after every bucket's dispatch "
        "(_start_host_copies overlaps the d2h) — one sync per bucket, "
        "never per row",
    ("symbiont_tpu/engine/engine.py", "TpuEngine.warmup"):
        "warmup exists to FORCE the compile+execute to finish; the sync "
        "is the point, and the path never serves traffic",
    ("symbiont_tpu/engine/engine.py", "TpuEngine.warm_rerank"):
        "same as warmup: the boot warm-up of the rerank hop forces each "
        "compile+execute to finish, off the serving path",
    ("symbiont_tpu/engine/lm.py", "LmEngine._generate_stream_impl"):
        "chunk-boundary sync is the streaming contract: each decoded "
        "chunk's tokens must reach the SSE reader before the next chunk "
        "decodes (stream_chunk bounds the cadence)",
}

# rule/table registry the engine consults (allow_key -> {entry: reason})
ALLOWLISTS = {
    "subject-unproduced": SUBJECTS_UNPRODUCED_ALLOWED,
    "no-per-float-conversion": FLOAT_LIST_ALLOWED,
    "no-asdict-on-ingest": ASDICT_ALLOWED,
    "no-hardcoded-frame-dtype": FRAME_DTYPE_ALLOWED,
    "async-blocking-call": ASYNC_BLOCKING_ALLOWED,
    "lock-order": LOCK_ORDER_ALLOWED,
    "jax-jit-in-function": JAX_JIT_IN_FUNCTION_ALLOWED,
    "jax-host-sync-in-loop": JAX_HOST_SYNC_ALLOWED,
}
