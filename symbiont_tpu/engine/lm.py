"""LmEngine — autoregressive text generation on TPU (BASELINE.md config #5).

The reference's "generation" is an order-1 Markov chain trained on one
hardcoded sentence that ignores the prompt (reference:
services/text_generator_service/src/main.rs:13-109,120-123). The Markov model
is kept for parity (models/markov.py); this module is the north-star upgrade
named in SURVEY.md §2 item 7: decoder-LM generation (GPT-2 / TinyLlama
layouts) with a static-shape KV-cache decode loop.

TPU shape discipline mirrors the embed path: prompts pad to a small set of
length buckets and max_new_tokens rounds up to a bucket, so each
(prompt_bucket, new_bucket) pair is one compiled executable (the inner
`lax.scan` decode loop never retraces). Sampling params are static too —
they're part of the scan body.

Tokenization: a local HF tokenizer.json when the model dir has one; otherwise
a byte-level tokenizer (vocab 256+specials) so the full pipeline — including
decode back to text — runs with zero model assets.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import Optional, Sequence

import numpy as np

from symbiont_tpu.config import LmConfig
from symbiont_tpu.kv.pool import PagePool, kv_dtype_label
from symbiont_tpu.kv.radix import RadixCache
from symbiont_tpu.models import gpt as gpt_mod
from symbiont_tpu.models.gpt import GPTConfig, PagedKVCache
from symbiont_tpu.obs.engine_timeline import engine_timeline
from symbiont_tpu.obs.hbm import guard_oom, hbm_ledger
from symbiont_tpu.obs.usage import usage
from symbiont_tpu.obs.xprof import dispatch_ledger
from symbiont_tpu.resilience.admission import DEFAULT_TENANT
from symbiont_tpu.utils.telemetry import metrics, span

log = logging.getLogger(__name__)


class ByteTokenizer:
    """UTF-8 byte-level tokenizer: ids 0..255 = bytes, 256 = BOS/pad.

    File-free and lossless (any text round-trips), so synthetic-weight dev
    and bench runs produce decodable output without model assets."""

    vocab_size = 257
    bos_id = 256
    pad_id = 256

    def encode(self, text: str, max_len: int) -> list:
        ids = [self.bos_id] + list(text.encode("utf-8"))
        return ids[:max_len]

    def decode(self, ids) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode("utf-8", errors="replace")


class LmHFTokenizer:
    """tokenizer.json wrapper with decode (generation needs the reverse map)."""

    def __init__(self, tokenizer_file):
        from tokenizers import Tokenizer as _Tok

        self._tok = _Tok.from_file(str(tokenizer_file))
        self._tok.no_padding()
        self._tok.no_truncation()
        self.pad_id = self._tok.token_to_id("<pad>") or 0
        eos = None
        for name in ("<|endoftext|>", "</s>", "<|end_of_text|>"):
            eos = self._tok.token_to_id(name)
            if eos is not None:
                break
        self.eos_id = -1 if eos is None else eos
        self.bos_id = self.eos_id if self.eos_id >= 0 else 0

    def encode(self, text: str, max_len: int) -> list:
        return self._tok.encode(text).ids[:max_len]

    def decode(self, ids) -> str:
        return self._tok.decode([int(i) for i in ids])


def _round_up(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class IncrementalDecoder:
    """Turn growing token sequences into stable text deltas.

    `tokenizer.decode` of a prefix is NOT always a prefix of the decode of a
    longer sequence: a multi-byte UTF-8 character straddling a chunk boundary
    decodes to U+FFFD until its continuation bytes arrive. push() therefore
    holds back a trailing replacement-char run (the only unstable region of
    incremental UTF-8 decoding) and only ever emits a confirmed-stable
    prefix; flush() emits the remainder, replacement chars included if the
    model genuinely produced invalid bytes. Concatenated deltas == the full
    decode whenever decode is prefix-stable (true for byte/BPE tokenizers);
    if a tokenizer's decode rewrites earlier output (e.g. decode-time
    cleanup), flush still emits everything past the longest common prefix —
    the tail is never lost, but earlier deltas are not retracted."""

    def __init__(self, tokenizer):
        self._tok = tokenizer
        self._emitted = ""

    def _delta_to(self, text: str) -> str:
        if text.startswith(self._emitted) and len(text) > len(self._emitted):
            delta = text[len(self._emitted):]
            self._emitted = text
            return delta
        return ""

    def push(self, all_tokens) -> str:
        text = self._tok.decode(all_tokens)
        stable = text.rstrip("�")
        return self._delta_to(stable)

    def flush(self, all_tokens) -> str:
        text = self._tok.decode(all_tokens)
        if text.startswith(self._emitted):
            return self._delta_to(text)
        # non-prefix-stable decode (e.g. decode-time whitespace cleanup):
        # emit the suffix past the longest common prefix so the terminal
        # output is never silently lost
        i = 0
        for a, b in zip(self._emitted, text):
            if a != b:
                break
            i += 1
        self._emitted = text
        return text[i:]


class LmEngine:
    """Owns LM params + decode executables. Thread-safe, single device owner
    (same stance as TpuEngine — SURVEY.md §5.2's fix for the reference's
    concurrent-forward hazard).

    Tensor-parallel serving: pass a mesh with a 'tensor' axis > 1 and the
    params shard megatron-style across it (parallel/sharding.py) — decode
    then serves models larger than one chip's HBM, with GSPMD inserting the
    TP collectives into the same jitted decode the single-chip path runs
    (SURVEY.md §2: "TP optional, implemented" — now for serving, not just
    training). Requires num_heads, kv_heads, and intermediate_size divisible
    by the tensor axis."""

    def __init__(self, config: Optional[LmConfig] = None, params=None,
                 model_cfg: Optional[GPTConfig] = None, tokenizer=None,
                 mesh=None, draft_params=None, draft_model_cfg=None):
        import dataclasses

        import jax

        self.config = config or LmConfig()
        cfg = self.config

        if params is None or model_cfg is None:
            if cfg.model_dir:
                from symbiont_tpu.models.convert import load_gpt_model

                params, model_cfg = load_gpt_model(cfg.model_dir)
                log.info("loaded LM checkpoint from %s", cfg.model_dir)
            else:
                # synthetic mode: byte-level vocab, random weights — decodable
                # gibberish; throughput-true for bench, asset-free for dev
                model_cfg = GPTConfig(
                    vocab_size=ByteTokenizer.vocab_size,
                    hidden_size=cfg.hidden_size, num_layers=cfg.num_layers,
                    num_heads=cfg.num_heads,
                    intermediate_size=cfg.intermediate_size,
                    max_position_embeddings=cfg.max_positions,
                    arch=cfg.arch, dtype=cfg.dtype)
                params = gpt_mod.init_params(jax.random.key(0), model_cfg)
                log.warning("LM running with RANDOM weights (no lm model_dir)")
        if model_cfg.dtype != cfg.dtype:
            model_cfg = dataclasses.replace(model_cfg, dtype=cfg.dtype)
        attn_impl = cfg.attn_impl
        if attn_impl not in ("auto", "flash", "xla"):
            raise ValueError(f"attn_impl must be auto|flash|xla, got {attn_impl!r}")
        if attn_impl == "auto":
            # XLA everywhere, same story as the encoder engine: with the
            # bf16 softmax path, XLA beats the flash kernel at prefill too
            # (v5e, measured: gpt2 S=256 9.9 vs 15.2 ms, tinyllama-geom
            # S=256 32 vs 39 ms, tied at S=1024). Decode steps (S=1) always
            # run the XLA cache-read path regardless. 'flash' stays as the
            # memory-bound opt-in (no S² intermediates at multi-k prefill).
            attn_impl = "xla"
        if model_cfg.attn_impl != attn_impl:
            model_cfg = dataclasses.replace(model_cfg, attn_impl=attn_impl)
        if model_cfg.kv_quant != cfg.kv_quant:
            # the cache layout is part of the frozen model config so it keys
            # every compiled decode executable (models/gpt.py init_cache)
            model_cfg = dataclasses.replace(model_cfg, kv_quant=cfg.kv_quant)
        self.model_cfg = model_cfg
        self.mesh = None
        if (cfg.tensor_parallel == "on"
                and (mesh is None or mesh.shape.get("tensor", 1) <= 1)):
            # "on" promises sharded decode; booting unsharded because the
            # mesh has no usable tensor axis would be a silent multi-x
            # memory/latency regression — exactly what "on" exists to catch
            raise ValueError(
                "tensor_parallel='on' requires a mesh with a 'tensor' axis "
                f"> 1 (got {None if mesh is None else dict(mesh.shape)})")
        if (mesh is not None and mesh.shape.get("tensor", 1) > 1
                and cfg.tensor_parallel != "off"):
            tp = mesh.shape["tensor"]
            bad = [f"{name} ({val})"
                   for name, val in (("num_heads", model_cfg.num_heads),
                                     ("kv_heads", model_cfg.kv_heads),
                                     ("intermediate_size",
                                      model_cfg.intermediate_size))
                   if val % tp]
            if bad and cfg.tensor_parallel == "on":
                raise ValueError(
                    f"TP decode needs {', '.join(bad)} divisible by the "
                    f"tensor axis ({tp})")
            if bad:
                # "auto": the mesh's tensor axis may exist for the encoder or
                # training — an LM whose head counts don't divide it must
                # still boot (ADVICE r4), just without sharded decode
                log.warning(
                    "LM tensor_parallel=auto: %s not divisible by tensor "
                    "axis (%d); falling back to single-device decode",
                    ", ".join(bad), tp)
                metrics.inc("lm.degraded",
                            labels={"reason": "tp_auto_undivisible"})
            else:
                self.mesh = mesh
                log.info("LM params sharded for TP decode over tensor=%d", tp)
        self.params = self._place_params(params)

        if tokenizer is None:
            tokenizer = ByteTokenizer()
            if cfg.model_dir:
                from pathlib import Path

                f = Path(cfg.model_dir) / "tokenizer.json"
                if f.exists():
                    tokenizer = LmHFTokenizer(f)
        self.tokenizer = tokenizer
        self._key = jax.random.key(cfg.seed)
        self._lock = threading.Lock()
        # prefill shapes already compiled (session starts + admissions):
        # lets the batcher predict whether an admission prefill is ms-cheap
        # or a fresh multi-second XLA compile (GenBatcher._filter_candidates)
        self._prefill_shapes: set = set()
        self.stats = {"generate_calls": 0, "tokens_generated": 0,
                      "decode_s": 0.0}
        # generation-session durability (resilience/genlog.py): the runner
        # attaches a GenJournal when SYMBIONT_GEN_JOURNAL_ENABLED=1. The
        # engine only APPENDS chunk-boundary snapshots (at the existing
        # device→host syncs — journaling adds none); terminal mark_done is
        # owned by the service layer, AFTER the result is published, so a
        # crash in the publish window still resumes.
        self.journal = None
        # live continuous-batching sessions (BatchSession registers itself);
        # weak so a finished session vanishes from the KV gauges without an
        # explicit close hook. Own lock: sessions register from executor
        # threads while scrapes iterate from the event loop, and WeakSet is
        # not thread-safe (the engine lock is no substitute — it's held for
        # whole decode calls and a scrape must never block behind one).
        self._sessions: "weakref.WeakSet" = weakref.WeakSet()
        self._sessions_lock = threading.Lock()
        # paged KV subsystem (symbiont_tpu/kv/, docs/KV.md): one engine-
        # global device page pool + host allocator, and optionally the
        # radix prefix cache over committed prompt pages. Dense layout
        # leaves both None and every downstream branch on the old path.
        self.pool: Optional[PagePool] = None
        self.radix: Optional[RadixCache] = None
        if cfg.kv_layout == "paged":
            import jax.numpy as jnp

            n_pages = cfg.kv_pool_pages or self._auto_pool_pages()
            self.pool = PagePool(
                model_cfg.num_layers, n_pages, cfg.kv_page_tokens,
                model_cfg.kv_heads, model_cfg.head_dim,
                jnp.dtype(model_cfg.dtype),
                quantized=(model_cfg.kv_quant == "int8"),
                dtype_label=kv_dtype_label(model_cfg.dtype,
                                           model_cfg.kv_quant))
            if cfg.kv_radix:
                self.radix = RadixCache(self.pool, cfg.kv_page_tokens)
            log.info("paged KV pool: %d pages x %d tokens (%.1f MiB%s)",
                     n_pages, cfg.kv_page_tokens,
                     self.pool.device_bytes / (1 << 20),
                     ", radix on" if self.radix is not None else "")
        # speculative-decoding draft plane (docs/SPECULATIVE.md, ROADMAP
        # item 1): a small second model proposes spec_k greedy tokens per
        # round on its own dense cache and the target scores all k+1
        # positions in ONE verify_chunk dispatch. The drafter stays dense
        # and unquantized whatever the target's kv layout/quant —
        # acceptance reads only the PROPOSED token ids, so target-side
        # paging/int8 cannot break token identity (greedy spec-on ==
        # plain decode by construction; tests/test_spec_decode.py).
        self._draft = None
        self.spec_k = int(cfg.spec_k)
        self._spec_proposed = 0   # draft tokens offered to verify_chunk
        self._spec_accepted = 0   # ... of which the target accepted
        if draft_params is not None or draft_model_cfg is not None:
            if draft_params is None or draft_model_cfg is None:
                raise ValueError(
                    "draft_params and draft_model_cfg must be passed together")
            self._adopt_draft(draft_params, draft_model_cfg)
        elif cfg.spec_draft_model:
            from pathlib import Path

            if not Path(cfg.spec_draft_model).is_dir():
                # degrade, don't crash: a missing drafter only costs speed
                log.warning(
                    "spec_draft_model %r not found — speculative decoding "
                    "disabled, plain decode unaffected", cfg.spec_draft_model)
                metrics.inc("lm.degraded",
                            labels={"reason": "spec_draft_missing"})
            else:
                from symbiont_tpu.models.convert import load_gpt_model as _lg

                if cfg.model_dir:
                    # jax-free fail-fast: tokenizer fingerprint + vocab
                    # parity straight from checkpoint metadata, before any
                    # weight load (config.validate_spec_draft)
                    from symbiont_tpu.config import validate_spec_draft

                    validate_spec_draft(cfg.model_dir, cfg.spec_draft_model)
                d_params, d_cfg = _lg(cfg.spec_draft_model)
                self._adopt_draft(d_params, d_cfg)
        self._register_gauges()

    def _adopt_draft(self, d_params, d_cfg) -> None:
        """Validate + place the drafter. Vocab parity is the one hard
        compatibility requirement (token ids must mean the same thing to
        both models); attention impl follows the target's resolved choice
        so both planes trace under one policy. Plain device_put — no TP
        shard (the drafter is small by construction) and no quantization
        (its cache is a rounding error next to the target's, and its
        proposals only need to be cheap, not byte-stable across layouts)."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        if d_cfg.vocab_size != self.model_cfg.vocab_size:
            raise ValueError(
                f"spec draft vocab_size {d_cfg.vocab_size} != target "
                f"{self.model_cfg.vocab_size}: drafter and target must "
                "share a tokenizer")
        if d_cfg.attn_impl != self.model_cfg.attn_impl:
            d_cfg = dataclasses.replace(
                d_cfg, attn_impl=self.model_cfg.attn_impl)
        dtype = jnp.dtype(d_cfg.dtype)
        d_params = jax.tree.map(
            lambda a: a.astype(dtype)
            if (hasattr(a, "dtype")
                and jnp.issubdtype(a.dtype, jnp.floating)) else a, d_params)
        self._draft = (jax.device_put(d_params), d_cfg)
        log.info("speculative decoding on: drafter %d layers x %d hidden, "
                 "k=%d", d_cfg.num_layers, d_cfg.hidden_size, self.spec_k)

    def _auto_pool_pages(self) -> int:
        """kv_pool_pages=0 sizing: the dense-equivalent capacity of ONE
        max-geometry session batch (every row at the largest in-range
        (prompt, new) bucket pair), x2 for radix retention headroom, +1
        for the scratch page. Paging wins by needing far fewer of these
        pages live at once — the x2 pool still beats dense slabs because
        dense allocates that worst case PER SESSION."""
        cfg = self.config
        new_b = max(cfg.new_token_buckets)
        cap = self.model_cfg.max_position_embeddings - new_b
        usable = [b for b in cfg.prompt_buckets if b <= cap]
        T = (usable[-1] if usable else max(cap, 1)) + new_b
        rows = max(cfg.session_min_rows, cfg.gen_max_batch, 1)
        bb = 1 << (rows - 1).bit_length() if rows > 1 else 1
        blocks = -(-T // cfg.kv_page_tokens)
        return 2 * bb * blocks + 1

    def _register_gauges(self) -> None:
        """Engine-plane decode gauges (docs/OBSERVABILITY.md): KV-cache row
        occupancy across live sessions, and cumulative decode tokens/s.
        Weakref-bound so the process-global registry never pins a dead
        engine."""
        def kv_rows(active_only: bool):
            def read(lm):
                with lm._sessions_lock:
                    sessions = list(lm._sessions)
                total = 0
                for sess in sessions:
                    if sess.done():
                        continue
                    total += (sum(1 for r in sess.rows if r is not None)
                              if active_only else sess.bb)
                return total
            return read

        def tok_per_s(lm):
            # lockless read: the engine lock is held for whole decode calls,
            # and a scrape must never block seconds behind one. Two GIL-
            # atomic dict reads can straddle an update — a gauge tolerates
            # that; a frozen /metrics endpoint doesn't.
            toks, secs = lm.stats["tokens_generated"], lm.stats["decode_s"]
            return toks / secs if secs > 0 else 0.0

        def kv_bytes(lm):
            # dtype-adjusted occupancy: actual at-rest bytes of every live
            # session's cache (int8 slabs + scale planes when kv_quant is
            # on) — the companion to the row counts above, so capacity
            # planning sees bytes, not just rows. Paged layout: the pool
            # IS the resident allocation (sessions hold page tables, not
            # slabs), so report its preallocated device bytes.
            if lm.pool is not None:
                return lm.pool.device_bytes
            with lm._sessions_lock:
                sessions = list(lm._sessions)
            return sum(gpt_mod.cache_bytes(s._cache) for s in sessions
                       if not s.done())

        def kv_rows_per_gib(lm):
            # how many session rows one GiB of HBM holds at the live
            # geometry and cache dtype — the "dtype-adjusted capacity"
            # number (int8 ≈ 2× bf16's, ≈ 4× f32's). Paged layout: rows
            # per GiB of OCCUPIED page bytes (live pages only) — the
            # tentpole's density win: short/finished rows stop paying for
            # their worst-case slab.
            with lm._sessions_lock:
                sessions = [s for s in lm._sessions if not s.done()]
            if lm.pool is not None:
                rows = sum(sum(1 for r in s.rows if r is not None)
                           for s in sessions)
                occupied = (lm.pool.pages_live * lm.pool.device_bytes
                            / lm.pool.n_pages)
                return round(rows * (1 << 30) / occupied, 1) if occupied \
                    else 0.0
            total = sum(gpt_mod.cache_bytes(s._cache) for s in sessions)
            rows = sum(s.bb for s in sessions)
            return round(rows * (1 << 30) / total, 1) if total else 0.0

        def kv_stranded(lm):
            # rows allocated in dense max-length slabs but NOT live (the
            # batch-bucket padding + finished/cancelled rows a paged KV
            # layout would reclaim — ROADMAP item 2's target number).
            # Paged layout: a freed row returns its pages at the chunk
            # boundary it died on, so rows holding device memory == live
            # rows and this reads 0 by construction.
            with lm._sessions_lock:
                sessions = [s for s in lm._sessions if not s.done()]
            if lm.pool is not None:
                holding = sum(s.rows_holding_pages() for s in sessions)
                live = sum(sum(1 for r in s.rows if r is not None)
                           for s in sessions)
                return holding - live
            alloc = sum(s.bb for s in sessions)
            live = sum(sum(1 for r in s.rows if r is not None)
                       for s in sessions)
            return alloc - live

        def page_fragmentation(lm):
            # allocated-but-dead page SLOTS across live rows (left-pad
            # slots inside prompt pages + the unfilled tail of the newest
            # decode page), as a pct of every slot the live rows map.
            # Shared radix pages are counted once per mapping row — this
            # is a utilization ratio of what rows hold, not of the pool.
            if lm.pool is None:
                return 0.0
            with lm._sessions_lock:
                sessions = [s for s in lm._sessions if not s.done()]
            toks = slots = 0
            for s in sessions:
                t, sl = s.page_occupancy()
                toks += t
                slots += sl
            return round(100.0 * (1.0 - toks / slots), 2) if slots else 0.0

        labels = {"service": "lm",
                  "kv_dtype": ("int8" if self.model_cfg.kv_quant == "int8"
                               else self.model_cfg.dtype)}
        metrics.register_weakref_gauge("lm.kv_stranded_rows", self,
                                       kv_stranded, labels=labels)
        metrics.register_weakref_gauge("lm.kv_rows_active", self,
                                       kv_rows(True), labels=labels)
        metrics.register_weakref_gauge("lm.kv_rows_allocated", self,
                                       kv_rows(False), labels=labels)
        metrics.register_weakref_gauge("lm.kv_cache_bytes", self,
                                       kv_bytes, labels=labels)
        metrics.register_weakref_gauge("lm.kv_rows_per_gib", self,
                                       kv_rows_per_gib, labels=labels)
        metrics.register_weakref_gauge("lm.decode_tok_per_s", self,
                                       tok_per_s, labels=labels)
        if self.pool is not None:
            # pool-side kv.pages_free / kv.pages_live registered by the
            # PagePool itself; fragmentation needs per-session token
            # counts only the engine sees, so its reader lives here
            metrics.register_weakref_gauge("kv.page_fragmentation_pct",
                                           self, page_fragmentation,
                                           labels=labels)
        if self._draft is not None:
            def spec_accept(lm):
                # cumulative draft-acceptance rate across every spec round
                # this engine ran (stream + batch planes) — THE knob-tuning
                # signal for spec_k / drafter choice (docs/SPECULATIVE.md)
                p = lm._spec_proposed
                return round(lm._spec_accepted / p, 4) if p else 0.0

            metrics.register_weakref_gauge("lm.spec_accept_rate", self,
                                           spec_accept, labels=labels)

        # hbm attribution plane (obs/hbm.py): the LM plane's device-memory
        # owners claim their bytes in the subsystem ledger. The pool claims
        # itself (kv/pool.py), so the engine claims dense KV only — a paged
        # engine claiming pool bytes here would double count.
        from symbiont_tpu.models.quant import param_bytes

        hbm_ledger.claim("lm.params", self,
                         lambda lm: param_bytes(lm.params))
        if self._draft is not None:
            hbm_ledger.claim(
                "lm.drafter", self,
                lambda lm: (param_bytes(lm._draft[0])
                            if lm._draft is not None else 0))
        if self.pool is None:
            def dense_kv_bytes(lm):
                with lm._sessions_lock:
                    sessions = list(lm._sessions)
                return sum(gpt_mod.cache_bytes(s._cache) for s in sessions
                           if not s.done())

            hbm_ledger.claim("lm.kv_cache", self, dense_kv_bytes)
        metrics.register_weakref_gauge(
            "lm.hbm_headroom_bytes", self,
            # returning None PERMANENTLY retires the gauge — exactly right
            # on CPU (no memory accounting, ever); on TPU/GPU the reader
            # always has stats and None never fires
            lambda lm: lm.hbm_headroom_bytes(), labels=labels)

    def hbm_headroom_bytes(self) -> Optional[int]:
        """Free device bytes on the tightest local device — bytes_limit
        minus bytes_in_use off the (memoized) runtime stats. None where
        the backend reports no memory accounting (CPU): callers must skip
        the bytes forecast there, not treat it as zero headroom."""
        from symbiont_tpu.obs.device import local_device_stats

        headroom = None
        for _idx, _platform, stats in local_device_stats():
            limit, in_use = stats.get("bytes_limit"), stats.get("bytes_in_use")
            if limit is None or in_use is None:
                continue
            free = max(0, int(limit) - int(in_use))
            headroom = free if headroom is None else min(headroom, free)
        return headroom

    def _note_param_bytes(self, params, storage) -> None:
        """Dtype-labeled at-rest parameter bytes (docs/OBSERVABILITY.md) —
        the LM half of the quantization plane's byte budget."""
        from symbiont_tpu.models.quant import param_bytes

        metrics.gauge_set("lm.param_bytes", param_bytes(params),
                          labels={"service": "lm", "dtype": str(storage)})

    def _place_params(self, params):
        """ONE home for parameter placement: megatron-sharded over the mesh's
        'tensor' axis when TP serving is on, plain device_put otherwise.
        Used by __init__ and every online-fine-tune sync (update_params).

        Params are cast to the model dtype AT REST: decode already computes
        in model dtype (forward casts at trace time), so storing f32 only
        doubled HBM residency (TinyLlama: 4.1 GB vs 2.1 GB) and made every
        chunked-decode call re-convert the full parameter set (the fused
        generate hoists the convert once per call; a chunk loop pays it per
        chunk).

        LmConfig.quantize != "none" quantizes here too (once per placement,
        host-side), so online fine-tune syncs re-quantize their f32 masters
        transparently. Quantized placement composes with TP: shard_params
        places QuantTensor codes by the kernel's own PartitionSpec and the
        per-output-channel scales on the kernel's last-axis entry
        (parallel/sharding.py), so `quantize=int8` + `tensor>1` decodes
        sharded AND narrow — the PR 7 fallback (unquantized params on any
        mesh, with a warning) is gone."""
        import jax
        import jax.numpy as jnp

        mode = self.config.quantize
        dtype = jnp.dtype(self.model_cfg.dtype)
        if mode != "none":
            from symbiont_tpu.models import quant

            # cast FIRST, quantize SECOND: the other order let the model-
            # dtype sweep undo f16's bf16-at-rest whenever the compute dtype
            # was wider (f32 compute silently re-widened the weights while
            # the param_bytes gauge still said f16). Quantized rank-≥2
            # leaves now always end narrow; the trace-time entry cast
            # upcasts them on-chip, so HBM reads stay halved regardless of
            # compute dtype.
            params = quant.cast_params(params, dtype)
            params = quant.quantize_params(params, mode)
        else:
            params = jax.tree.map(
                lambda a: a.astype(dtype)
                if (hasattr(a, "dtype")
                    and jnp.issubdtype(a.dtype, jnp.floating))
                else a, params)
        storage = mode if mode != "none" else self.model_cfg.dtype
        self._note_param_bytes(params, storage)
        if self.mesh is None:
            return jax.device_put(params)
        from symbiont_tpu.parallel.sharding import (
            gpt_param_sharding,
            shard_params,
        )

        return shard_params(
            self.mesh, params,
            gpt_param_sharding(self.mesh, params, arch=self.model_cfg.arch))

    # ------------------------------------------------------------------ gen

    def _prepare_prompts(self, prompts: Sequence[str], max_new: int,
                         min_rows: int = 1, encoded=None):
        """Shared decode preamble: pick the new-token bucket, validate it
        fits, encode prompts (tail-trim to the largest usable prompt bucket,
        BOS fallback for empty), pad to a power-of-two batch bucket so the
        executable count stays log-bounded (≥ min_rows — sessions reserve
        headroom rows for mid-decode admission). `encoded` bypasses
        tokenization with pre-tokenized id lists (resume re-prefills the
        exact journaled prompt+generated prefix — resilience/genlog.py;
        the same tail-trim applies so a resumed request obeys the same
        bucket cap as a fresh one). Returns
        (prompt_ids [bb, P], prompt_mask [bb, P], new_bucket)."""
        cfg = self.config
        new_bucket = _round_up(max_new, cfg.new_token_buckets)
        # P + new_bucket must fit in max_position_embeddings, so prompt
        # buckets above that cap are unusable for this request.
        cap = self.model_cfg.max_position_embeddings - new_bucket
        if cap < 1:
            raise ValueError(
                f"max_new_tokens {max_new} (bucket {new_bucket}) leaves no "
                f"room in {self.model_cfg.max_position_embeddings} positions")
        avail = [b for b in cfg.prompt_buckets if b <= cap] or [cap]
        if encoded is None:
            encoded = [self.tokenizer.encode(p or "", 1 << 30)
                       for p in prompts]
        trimmed = []
        for ids in encoded:
            ids = list(ids)[-avail[-1]:]  # keep the tail: recent context wins
            if not ids:
                ids = [getattr(self.tokenizer, "bos_id", 0)]
            trimmed.append(ids)
        encoded = trimmed
        B = len(encoded)
        bb = 1 << (B - 1).bit_length() if B > 1 else 1
        if min_rows > 1:
            bb = max(bb, 1 << (min_rows - 1).bit_length())
        P = _round_up(max(len(e) for e in encoded), avail)
        pad = getattr(self.tokenizer, "pad_id", 0)
        bos = getattr(self.tokenizer, "bos_id", 0)
        prompt_ids = np.full((bb, P), pad, np.int32)
        prompt_mask = np.zeros((bb, P), np.int32)
        for i, ids in enumerate(encoded):
            prompt_ids[i, : len(ids)] = ids
            prompt_mask[i, : len(ids)] = 1
        for i in range(B, bb):  # padding rows: minimal one-token prompt
            prompt_ids[i, 0] = bos
            prompt_mask[i, 0] = 1
        return prompt_ids, prompt_mask, new_bucket

    def generate(self, prompt: str, max_new_tokens: int,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None) -> str:
        """Prompt → generated text (the tasks.generation.text LM backend)."""
        return self.generate_batch([prompt], [max_new_tokens],
                                   temperature=temperature, top_k=top_k)[0]

    def _norm_sampling_rows(self, value, default, bb: int, n: int, cast):
        """Scalar-or-per-request sampling param → per-row list of length bb
        (batch bucket). None → engine default (element-wise too); padding
        rows decode greedily (their output is discarded)."""
        if value is None:
            value = default
        if isinstance(value, (list, tuple, np.ndarray)):
            if len(value) != n:
                raise ValueError(
                    f"per-request sampling list length {len(value)} != {n}")
            rows = [cast(default if v is None else v) for v in value]
        else:
            rows = [cast(value)] * n
        return rows + [cast(0)] * (bb - n)

    def generate_batch(self, prompts: Sequence[str],
                       max_new_tokens: Sequence[int],
                       temperature=None, top_k=None) -> list:
        """Batched decode: B prompts through ONE (prompt_bucket, new_bucket)
        executable — concurrent generation requests share the decode loop's
        weight reads instead of serializing B single-row decodes. Rows are
        right-aligned internally by gpt.generate, so each row's output is
        independent of its batchmates (greedy decode of a batch == greedy
        decode of each row alone; asserted in tests). Per-request
        max_new_tokens trim a shared new-token bucket; temperature/top_k may
        be scalars or per-request sequences (sampling params are traced
        per-row vectors in the decode executable, so requests with different
        sampling still share one batch)."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        if len(prompts) != len(max_new_tokens):
            raise ValueError("prompts and max_new_tokens length mismatch")
        prompt_ids, prompt_mask, new_bucket = self._prepare_prompts(
            prompts, max(max_new_tokens))
        bb, n = prompt_ids.shape[0], len(prompts)
        temps = self._norm_sampling_rows(temperature, cfg.temperature,
                                         bb, n, float)
        ks = self._norm_sampling_rows(top_k, cfg.top_k, bb, n, int)
        eos_id = getattr(self.tokenizer, "eos_id", -1)
        with self._lock:
            self._key, sub = jax.random.split(self._key)
            t0 = time.perf_counter()
            # not "engine.generate": EngineService's handler span of the
            # generate op has that name, and two things in one histogram
            # are neither
            with span("lm.generate", rows=n):
                tokens, lengths = gpt_mod.generate(
                    self.params, jnp.asarray(prompt_ids),
                    jnp.asarray(prompt_mask),
                    sub, self.model_cfg, max_new_tokens=new_bucket,
                    temperature=temps, top_k=ks,
                    eos_id=int(eos_id))
                tokens = np.asarray(tokens)  # materialize → full decode done
            lengths = np.asarray(lengths)
            dt = time.perf_counter() - t0
            self.stats["generate_calls"] += 1
            self.stats["decode_s"] += dt
            out = []
            for i, want in enumerate(max_new_tokens):  # drops padding rows
                n = min(int(lengths[i]), int(want))
                self.stats["tokens_generated"] += n
                out.append(self.tokenizer.decode(tokens[i, :n]))
        return out

    def generate_stream(self, prompt: str, max_new_tokens: int,
                        temperature: Optional[float] = None,
                        top_k: Optional[int] = None,
                        tenant: Optional[str] = None,
                        task_id: Optional[str] = None,
                        stream: bool = True,
                        resume: Optional[dict] = None):
        """Thin OOM-forensics shell over ``_generate_stream_impl`` (which
        carries the real contract — see its docstring): every advance of
        the underlying generator runs under the hbm plane's guard, so a
        RESOURCE_EXHAUSTED out of any prefill/chunk dispatch dumps the
        postmortem and counts engine.oom_total{site="lm.generate_stream"}
        before propagating to the stream's consumer unchanged."""
        gen = self._generate_stream_impl(
            prompt, max_new_tokens, temperature=temperature, top_k=top_k,
            tenant=tenant, task_id=task_id, stream=stream, resume=resume)
        while True:
            try:
                with guard_oom("lm.generate_stream"):
                    item = next(gen)
            except StopIteration:
                return
            yield item

    def _generate_stream_impl(self, prompt: str, max_new_tokens: int,
                              temperature: Optional[float] = None,
                              top_k: Optional[int] = None,
                              tenant: Optional[str] = None,
                              task_id: Optional[str] = None,
                              stream: bool = True,
                              resume: Optional[dict] = None):
        """Streaming decode: yields text deltas as chunks of tokens finish
        (SURVEY.md §7 hard part #5: "streaming tokens back out through
        NATS→SSE"). Prefill + one compiled chunk-scan executable per
        (prompt_bucket, chunk) pair, re-invoked with carried device state —
        time-to-first-chunk is prefill + stream_chunk steps instead of the
        full decode. Greedy streaming concatenates to exactly generate()'s
        output in float32 (asserted in tests); under bfloat16 the chunked
        and full-scan executables may round differently, so greedy outputs
        can diverge at argmax near-ties (pronounced with random weights,
        whose logits are nearly uniform — real checkpoints have margins).

        Durability (resilience/genlog.py): with `task_id` set and a journal
        attached, every chunk appends a resume snapshot BEFORE its delta is
        yielded — a crash anywhere leaves a tail whose replay re-emits at
        most one already-delivered chunk (deduped by seq at the SSE hub),
        never loses one. `resume` is such a tail: the prompt + generated
        prefix is re-prefilled (content-relative positions make greedy
        decode continue token-identically — models/gpt.py _align_prompt),
        the journaled last chunk's delta is replayed at its original seq,
        and the PRNG chain is restored (base key + split count) so sampled
        decode continues on the same chain when the resumed chunk size
        matches. `stream` is recorded so a second crash re-resumes with the
        originating task's delivery mode."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        temperature = cfg.temperature if temperature is None else temperature
        top_k = cfg.top_k if top_k is None else top_k
        tenant = tenant or DEFAULT_TENANT
        eos_id = getattr(self.tokenizer, "eos_id", -1)
        jr = self.journal
        journaling = jr is not None and jr.enabled and bool(task_id)
        sampled = float(temperature) > 0.0

        # speculative decoding (docs/SPECULATIVE.md): with a drafter
        # attached, the loop below runs draft+verify rounds instead of
        # plain chunks while the decode-slot margin allows a worst-case
        # round PLUS a plain finish — spec can only waste SLOTS (rejected
        # draft positions become kv_valid holes), never truncate output.
        # The bucket request gets spec_k headroom so typical requests keep
        # that margin; spec-off requests are byte-identical to before.
        spec_on = self._draft is not None
        spec_cap = spec_on  # capability at stream start; spec_on may fall back
        headroom = self.spec_k if spec_on else 0

        all_tokens: list = []
        seq = 0
        chunk_start = 0
        decoder = IncrementalDecoder(self.tokenizer)
        if resume is not None:
            all_tokens = [int(t) for t in (resume.get("tokens") or [])]
            chunk_start = int(resume.get("chunk_start") or 0)
            seq = int(resume.get("seq") or 0)
            decoder._emitted = resume.get("text") or ""
            my_prompt_ids = [int(t) for t in resume["prompt_ids"]]
            # re-prefill the EXACT journaled prefix (prompt + generated so
            # far) — no re-tokenization, so byte-level/BPE boundary effects
            # can't shift the prefix the dead worker actually decoded. A
            # snapshot taken in SPEC state journalled its LAST token as the
            # un-ingested `pending` — it was NOT in the dead worker's cache,
            # so it stays out of the re-prefill too (and its would-be cache
            # slot reserves one decode slot: the +cut below).
            cut = 1 if (spec_on and resume.get("spec")
                        and all_tokens) else 0
            body = all_tokens[:len(all_tokens) - cut] if cut else all_tokens
            remaining = max(1, max_new_tokens - len(all_tokens) + cut)
            # Exact-replay slot restore: the spec/plain mode decision and the
            # plain-chunk clamp below are functions of the remaining-slot
            # margin (new_bucket - slots_used), and jax.random.split(key, n)
            # is NOT prefix-stable across n — so a sampled resume must
            # reproduce the dead worker's margin EXACTLY, not approximately.
            # The journalled margin fits a bucket (the original bucket held
            # it), so a big-enough bucket always exists.
            spec_slots = resume.get("spec_slots") if spec_on else None
            want_slots = (max(remaining, int(spec_slots))
                          if spec_slots is not None else remaining + headroom)
            prompt_ids, prompt_mask, new_bucket = self._prepare_prompts(
                [""], want_slots, encoded=[my_prompt_ids + body])
            max_new_tokens = min(max_new_tokens,
                                 len(all_tokens) + new_bucket - cut)
        else:
            cut = 0
            prompt_ids, prompt_mask, new_bucket = self._prepare_prompts(
                [prompt], max_new_tokens + headroom)
            # largest bucket caps the request (same clamp generate() applies
            # via its scan length) — the cache has new_bucket decode slots
            max_new_tokens = min(max_new_tokens, new_bucket)
            mask0 = prompt_mask[0].astype(bool)
            my_prompt_ids = [int(t) for t in prompt_ids[0][mask0]]
        # usage ledger (obs/usage.py): prefilled tokens are known exactly
        # here, host-side, before any device work
        usage.note(tenant, tokens_in=int(prompt_mask[0].sum()))
        chunk = min(cfg.stream_chunk, new_bucket)

        # Lock discipline: the engine lock is held only around device work
        # (prefill, each decode_chunk) and NEVER across a yield — a stalled
        # SSE consumer must not starve concurrent generate()/generate_batch()
        # callers waiting on the same lock. This is safe because the KV cache
        # is owned by this generator frame: decode_chunk consumes the carry
        # (cache/logits/pos/done are DONATED and reassigned each chunk;
        # params read-only), so other engine calls interleaving between
        # chunks can't observe or mutate this stream's state. The stream
        # stays consumer-paced: nothing decodes while the consumer is
        # parked between deltas.
        decode_s = 0.0
        key_base = None  # uint32 key_data the journal stores (sampled only)
        n_splits = 0     # chunk-splits consumed on that base so far
        with self._lock:
            # timers start inside the lock: decode_s counts this stream's own
            # device work, not time spent waiting on other callers
            t0 = time.perf_counter()
            self._key, sub = jax.random.split(self._key)
            if resume is not None and resume.get("key") is not None:
                # restore the dead worker's PRNG chain: its journaled base
                # key, advanced by the number of chunk-splits it consumed
                key_base = [int(x) for x in resume["key"]]
                n_splits = int(resume.get("key_splits") or 0)
                sub = jax.random.wrap_key_data(
                    jnp.asarray(np.asarray(key_base, np.uint32)))
                for _ in range(n_splits):
                    sub, _adv = jax.random.split(sub)
            elif journaling and sampled:
                # ONE key_data transfer per stream, outside the chunk loop:
                # the journal records (base, split count), never a fresh
                # device value per chunk — no host sync rides the loop
                key_base = [int(x) for x in np.asarray(
                    jax.random.key_data(sub)).reshape(-1)]
            cache, logits, kv_valid, prompt_len = gpt_mod.prefill(
                self.params, jnp.asarray(prompt_ids), jnp.asarray(prompt_mask),
                self.model_cfg, new_bucket)
            dt = time.perf_counter() - t0
            decode_s += dt
            dt_dp = 0.0
            if spec_on:
                # drafter plane: its own small DENSE cache at the same
                # (prompt, new) geometry — slot-symmetric with the target's,
                # so the two share one kv_valid/pos/done (models/gpt.py
                # spec state contract)
                t_dp = time.perf_counter()
                draft_params, dcfg = self._draft
                d_cache = gpt_mod.prefill(
                    draft_params, jnp.asarray(prompt_ids),
                    jnp.asarray(prompt_mask), dcfg, new_bucket)[0]
                dt_dp = time.perf_counter() - t_dp
                decode_s += dt_dp
        dispatch_ledger.note_dispatch(
            f"lm.prefill[P={prompt_ids.shape[1]},B={prompt_ids.shape[0]},"
            f"new={new_bucket}]", dt)
        if spec_on:
            dispatch_ledger.note_dispatch(
                f"lm.draft_prefill[P={prompt_ids.shape[1]},"
                f"B={prompt_ids.shape[0]},new={new_bucket}]", dt_dp)
        done = jnp.zeros((prompt_ids.shape[0],), bool)
        pos = prompt_len
        stop = False
        # spec state: `pending` is the last emitted token, kept OUT of both
        # caches until the next round writes it (or ingest_pending folds it
        # in on fallback). slots_used counts decode slots consumed — in spec
        # state that runs AHEAD of emitted tokens by the rejected holes.
        pending = None
        slots_used = 0
        if (resume is not None and spec_on
                and resume.get("spec_slots") is not None):
            # restore the dead worker's slot accounting so every subsequent
            # margin/clamp decision (and thus PRNG key consumption) replays
            # exactly; new_bucket >= spec_slots by the request above
            slots_used = max(0, new_bucket - int(resume["spec_slots"]))
        if spec_on and cut:
            # spec-state resume: the journalled tail's last token IS the
            # pending — restore it host→device and skip spec_first
            pending = jnp.asarray([all_tokens[-1]], jnp.int32)

        def _snapshot(text_before: str) -> dict:
            return {"task_id": task_id, "tenant": tenant, "stream": stream,
                    "prompt_ids": my_prompt_ids,
                    "max_new": int(max_new_tokens),
                    "temperature": float(temperature), "top_k": int(top_k),
                    "tokens": list(all_tokens), "chunk_start": chunk_start,
                    "text": text_before, "seq": seq,
                    "key": key_base, "key_splits": n_splits,
                    # spec state marker: tokens[-1] is the un-ingested
                    # pending (not in the cache) — a resume must reserve
                    # its slot and skip spec_first (docs/SPECULATIVE.md)
                    "spec": bool(spec_on and pending is not None
                                 and not stop),
                    # remaining-slot margin: a resume replays mode/clamp
                    # decisions from this, so sampled key chains line up
                    "spec_slots": (new_bucket - slots_used) if spec_cap
                                  else None}

        try:
            if resume is not None:
                # adopt the orphan in OUR journal before emitting anything:
                # a crash between this yield and the next chunk must leave a
                # resumable tail here, not only in the rotated-aside file
                if journaling:
                    jr.append(_snapshot(decoder._emitted))
                # warm-vs-cold attribution: how many prefix tokens were
                # still radix-resident in THIS replica (kv/radix.py peek —
                # side-effect-free; the dense resume prefill does not use
                # them yet, but the probe quantifies the paged-resume win)
                warm = 0
                if self.radix is not None:
                    ids_r, pads = _right_aligned_rows(prompt_ids,
                                                      prompt_mask)
                    warm = self.radix.peek(prompt_ids.shape[1],
                                           int(pads[0]), ids_r[0])
                engine_timeline.note_resume(
                    tokens=len(all_tokens), prefill_ms=dt * 1000.0,
                    warm_tokens=warm)
                delta = decoder.push(all_tokens)
                if delta:  # replay of the journaled last chunk, same seq
                    yield delta
                    seq += 1
            while len(all_tokens) < max_new_tokens and not stop:
                sub, use = jax.random.split(sub)
                n_splits += 1
                S = self.spec_k + 1
                if spec_on and (new_bucket - slots_used
                                < S + (max_new_tokens - len(all_tokens))
                                - (1 if pending is None else 0)):
                    # not enough decode slots for a worst-case round (one
                    # accepted token, S slots burned) PLUS a plain finish:
                    # leave speculation FOR GOOD (B=1 — the margin only
                    # shrinks) after folding pending back into the cache
                    if pending is not None:
                        with self._lock:
                            t1 = time.perf_counter()
                            cache, logits, pos = gpt_mod.ingest_pending(
                                self.params, cache, pending, pos, done,
                                kv_valid, self.model_cfg)
                            dt1 = time.perf_counter() - t1
                            decode_s += dt1
                        dispatch_ledger.note_dispatch(
                            "lm.ingest_pending[B=1]", dt1)
                        slots_used += 1
                        pending = None
                    spec_on = False
                if spec_on:
                    first = None
                    with self._lock:
                        t1 = time.perf_counter()
                        if pending is None:
                            # plain → spec: the first token comes off the
                            # carried logits — exactly what the next plain
                            # step would sample. Device refs only; the ONE
                            # host materialization for the whole round is
                            # below, at the same chunk-boundary sync plain
                            # decode already pays.
                            use, k0 = jax.random.split(use)
                            pending, c0, done = gpt_mod.spec_first(
                                logits, done, k0, self.model_cfg,
                                temperature=float(temperature),
                                top_k=int(top_k), eos_id=int(eos_id))
                            first = (pending, c0)
                        t_d = time.perf_counter()
                        d_cache, drafts = gpt_mod.draft_chunk(
                            draft_params, d_cache, pending, pos, done,
                            kv_valid, dcfg, self.spec_k)
                        t_v = time.perf_counter()
                        (cache, pending, pos, done, kv_valid, out, counted,
                         emitted) = gpt_mod.verify_chunk(
                            self.params, cache, pending, drafts, pos, done,
                            kv_valid, use, self.model_cfg,
                            temperature=float(temperature),
                            top_k=int(top_k), eos_id=int(eos_id))
                        out = np.asarray(out)[0]
                        counted = np.asarray(counted)[0]
                        n_emit = int(np.asarray(emitted)[0])
                        f_tok = f_cnt = None
                        if first is not None:
                            f_tok = int(np.asarray(first[0])[0])
                            f_cnt = bool(np.asarray(first[1])[0])
                        t_end = time.perf_counter()
                        decode_s += t_end - t1
                    dispatch_ledger.note_dispatch(
                        f"lm.draft_chunk[P={prompt_ids.shape[1]},B=1,"
                        f"k={self.spec_k}]", t_v - t_d)
                    dispatch_ledger.note_dispatch(
                        f"lm.verify_chunk[P={prompt_ids.shape[1]},B=1,"
                        f"k={self.spec_k}]", t_end - t_v)
                    if first is not None:
                        dispatch_ledger.note_dispatch(
                            "lm.spec_first[B=1]", t_d - t1)
                    # the round's out/counted/emitted materialization above
                    # is the stream's one allowlisted device->host sync
                    dispatch_ledger.note_host_sync(
                        "LmEngine._generate_stream_impl")
                    slots_used += S
                    self._spec_proposed += self.spec_k
                    self._spec_accepted += max(0, n_emit - 1)
                    chunk_start = len(all_tokens)
                    emit_pairs = [] if first is None else [(f_tok, f_cnt)]
                    emit_pairs += list(zip(out[:n_emit].tolist(),
                                           counted[:n_emit].tolist()))
                    for t, c in emit_pairs:
                        if not c:  # EOS: stream ends here, exactly as plain
                            stop = True
                            break
                        all_tokens.append(int(t))
                        if len(all_tokens) >= max_new_tokens:
                            break
                else:
                    c_n = min(chunk, new_bucket - slots_used)
                    if c_n <= 0:
                        # slot accounting exhausted — unreachable while the
                        # margin invariant holds; fuse against a wedged loop
                        break
                    keys = jax.random.split(use, c_n)
                    with self._lock:
                        t1 = time.perf_counter()
                        (cache, logits, pos, done, toks,
                         counted) = gpt_mod.decode_chunk(
                            self.params, cache, logits, pos, done, kv_valid,
                            keys, self.model_cfg,
                            temperature=float(temperature),
                            top_k=int(top_k), eos_id=int(eos_id))
                        toks = np.asarray(toks)[0]
                        counted = np.asarray(counted)[0]
                        dt1 = time.perf_counter() - t1
                        decode_s += dt1
                    dispatch_ledger.note_dispatch(
                        f"lm.decode_chunk[P={prompt_ids.shape[1]},B=1,"
                        f"chunk={c_n}]", dt1)
                    # the chunk-boundary toks/counted materialization above
                    # is the stream's one allowlisted device->host sync
                    dispatch_ledger.note_host_sync(
                        "LmEngine._generate_stream_impl")
                    slots_used += c_n
                    chunk_start = len(all_tokens)
                    for t, c in zip(toks, counted):
                        if not c:  # EOS (or post-EOS slot): stream ends here
                            stop = True
                            break
                        all_tokens.append(int(t))
                        if len(all_tokens) >= max_new_tokens:
                            break
                # journal BEFORE yield (host values already in hand): the
                # snapshot's replay re-emits this chunk at this seq, so a
                # kill in the yield window duplicates (hub-deduped), never
                # drops
                if journaling:
                    jr.append(_snapshot(decoder._emitted))
                delta = decoder.push(all_tokens)
                if delta:
                    yield delta
                    seq += 1
            final_delta = decoder.flush(all_tokens)
            if final_delta:
                yield final_delta
        finally:
            # runs on normal exit AND on generator close (client disconnect)
            usage.note(tenant, tokens_out=len(all_tokens),
                       kv_row_seconds=decode_s * prompt_ids.shape[0])
            with self._lock:
                self.stats["generate_calls"] += 1
                self.stats["tokens_generated"] += len(all_tokens)
                self.stats["decode_s"] += decode_s

    # ----------------------------------------------------- continuous batch

    def start_session(self, prompts: Sequence[str],
                      max_new_tokens: Sequence[int],
                      temperature=None, top_k=None,
                      tenants=None, task_ids=None) -> "BatchSession":
        """Open a chunked batch decode that new requests can JOIN at chunk
        boundaries (continuous batching — the GenBatcher upgrade over
        flush-window-only batching; VERDICT r3 item 3). Drive it with
        session.step(); admit newcomers with session.admit(). `tenants`
        (one per prompt; default lane otherwise) routes the usage ledger
        — obs/usage.py. `task_ids` (one per prompt) keys each row's
        durability snapshots in the generation journal."""
        return BatchSession(self, prompts, max_new_tokens, temperature,
                            top_k, tenants=tenants, task_ids=task_ids)

    def kv_rows_allocated(self) -> int:
        """Batch rows allocated across live decode sessions — the number
        the `lm.kv_rows_allocated` gauge exports, readable synchronously
        for admission decisions."""
        with self._sessions_lock:
            return sum(s.bb for s in self._sessions if not s.done())

    def kv_row_counts(self) -> tuple:
        """(live, allocated) decode rows across live sessions in ONE
        sessions-lock pass — the engine-timeline step events read both at
        every chunk boundary. Under the paged layout "allocated" counts
        rows actually HOLDING pages (freed rows return theirs at the
        chunk boundary they die on), so the stranded gap dense slabs
        carry reads zero by construction."""
        with self._sessions_lock:
            sessions = [s for s in self._sessions if not s.done()]
        if self.pool is not None:
            alloc = sum(s.rows_holding_pages() for s in sessions)
        else:
            alloc = sum(s.bb for s in sessions)
        live = sum(sum(1 for r in s.rows if r is not None)
                   for s in sessions)
        return live, alloc

    def pages_reserved(self) -> int:
        """Pages live sessions may still lazily allocate for rows already
        admitted (their worst-case remaining decode blocks). Admission
        must leave this many free+evictable pages untouched or a session
        could hit PoolExhausted mid-decode."""
        with self._sessions_lock:
            sessions = [s for s in self._sessions if not s.done()]
        return sum(s.pages_reserved() for s in sessions)

    def _pages_needed(self, n_rows: int, prompts=None,
                      max_new_tokens=None) -> int:
        """FRESH pages `n_rows` admissions will need. Without prompts:
        the worst-case block count at the largest in-range (prompt, new)
        bucket pair. With prompts (and the radix cache on): the exact
        quote — each prompt is encoded, bucketed, and radix-matched, and
        blocks already committed for its prefix cost nothing (a
        radix-hit admit needs fewer fresh pages, so admission control
        stops 429ing traffic the pool can actually serve)."""
        cfg = self.config
        page = cfg.kv_page_tokens
        if prompts is None:
            new_b = max(cfg.new_token_buckets)
            cap = self.model_cfg.max_position_embeddings - new_b
            usable = [b for b in cfg.prompt_buckets if b <= cap]
            T = (usable[-1] if usable else max(cap, 1)) + new_b
            return max(1, int(n_rows)) * (-(-T // page))
        total = 0
        wants = list(max_new_tokens) if max_new_tokens is not None else \
            [max(cfg.new_token_buckets)] * len(prompts)
        for prompt, want in zip(prompts, wants):
            new_b = _round_up(int(want), cfg.new_token_buckets)
            cap = self.model_cfg.max_position_embeddings - new_b
            avail = [b for b in cfg.prompt_buckets if b <= cap] or [cap]
            ids = self.tokenizer.encode(prompt or "", 1 << 30)[-avail[-1]:]
            if not ids:
                ids = [getattr(self.tokenizer, "bos_id", 0)]
            P = _round_up(len(ids), avail)
            blocks = -(-(P + new_b) // page)
            hit = 0
            if self.radix is not None:
                pad = P - len(ids)
                ids_r = np.zeros(P, np.int32)
                ids_r[pad:] = ids
                hit = self.radix.match(P, pad, ids_r).blocks
            total += blocks - hit
        return total

    def can_admit(self, n_rows: int = 1, max_kv_rows: int = 0,
                  prompts=None, max_new_tokens=None) -> bool:
        """Capacity-aware generation admission (resilience/admission.py):
        may `n_rows` more decode rows start without pushing allocated KV
        rows past `max_kv_rows`? The API edge consults this BEFORE
        accepting a generation stream, so overload answers 429 instead of
        growing KV caches until the device OOMs. cap <= 0 = unbounded
        (the pre-plane behavior).

        Paged layout: the binding resource is PAGES, not slab rows — the
        quote is fresh pages needed (worst-case by default; exact, radix
        hits deducted, when `prompts`/`max_new_tokens` are passed) against
        free + LRU-evictable pages minus what admitted rows may still
        lazily claim. The row cap still applies on top when set.

        On devices that report memory accounting, a BYTES forecast runs
        beside the page/row quotes (obs/hbm.py): admitting `n_rows` costs
        their KV bytes, and the dispatch that serves them needs the
        largest known lm.* executable's temp (activation scratch) bytes —
        both must fit the tightest device's headroom. The page quote
        guards the pool; this guards everything the pool doesn't see
        (activation scratch, dense slabs, other subsystems' growth). On
        CPU (headroom None) the forecast is skipped entirely, so test and
        dev behavior is byte-for-byte the old quote."""
        if self.pool is not None:
            need = self._pages_needed(max(1, int(n_rows)), prompts,
                                      max_new_tokens)
            with self.pool.lock:
                avail = (self.pool.pages_free + self.pool.pages_retained
                         - self.pages_reserved())
            if need > avail:
                return False
        headroom = self.hbm_headroom_bytes()
        if headroom is not None:
            need_bytes = self._admit_bytes_forecast(max(1, int(n_rows)))
            if need_bytes > headroom:
                metrics.inc("lm.admit_hbm_rejects")
                return False
        if max_kv_rows <= 0:
            return True
        return self.kv_rows_allocated() + max(1, int(n_rows)) <= max_kv_rows

    def _admit_bytes_forecast(self, n_rows: int) -> int:
        """Fresh HBM `n_rows` admissions may need: worst-case dense KV
        slab bytes per row (paged rows allocate from the already-resident
        pool — zero fresh bytes) plus the largest known lm.* executable
        temp footprint (the activation scratch the serving dispatch will
        ask the allocator for)."""
        from symbiont_tpu.obs.hbm import peak_temp_bytes

        kv_fresh = 0
        if self.pool is None:
            cfg = self.config
            new_b = max(cfg.new_token_buckets)
            cap = self.model_cfg.max_position_embeddings - new_b
            usable = [b for b in cfg.prompt_buckets if b <= cap]
            T = (usable[-1] if usable else max(cap, 1)) + new_b
            # [2, layers, T, kv_heads, head_dim] at cache dtype, per row
            itemsize = (1 if self.model_cfg.kv_quant == "int8"
                        else np.dtype(self.model_cfg.dtype).itemsize)
            kv_fresh = (2 * self.model_cfg.num_layers * T
                        * self.model_cfg.kv_heads * self.model_cfg.head_dim
                        * itemsize) * n_rows
        return kv_fresh + peak_temp_bytes("lm.")

    def update_params(self, params) -> None:
        """Swap in new model parameters (online fine-tune sync,
        train/online.py). Serialized on the engine lock so no decode is
        mid-flight on the old buffers; an in-progress stream picks up the new
        params at its next chunk (its KV cache entries from the old params
        remain valid context — same contract as any incremental fine-tune).
        The caller must hand over buffers it will not later donate or mutate
        (OnlineLmTrainer passes a copy)."""
        with self._lock:
            self.params = self._place_params(params)
        if self.radix is not None:
            # committed prefix pages (and their stored full-prompt logits)
            # were computed under the OLD weights — a post-swap admit must
            # not splice them into its context. Live rows keep their own
            # pages: same old-params-context contract as an in-progress
            # stream.
            self.radix.clear()

    def warmup(self, new_bucket: Optional[int] = None) -> None:
        """Pre-compile the hot (prompt, new) executable pair."""
        self.generate("warmup", new_bucket or self.config.new_token_buckets[0])


def _norm_tenants(tenants, n: int) -> list:
    """Per-row tenant list of length n (default lane where unspecified) —
    the usage ledger's routing (obs/usage.py)."""
    if tenants is None:
        return [DEFAULT_TENANT] * n
    if len(tenants) != n:
        raise ValueError(f"tenants list length {len(tenants)} != {n}")
    return [t or DEFAULT_TENANT for t in tenants]


def _real_token_rows(prompt_ids, prompt_mask, n: int) -> list:
    """The first `n` rows' REAL token ids (padding stripped) as plain int
    lists — host numpy in, host lists out; the prefix-share probe's input."""
    out = []
    for i in range(n):
        length = int(prompt_mask[i].sum())
        out.append(prompt_ids[i, :length].tolist())
    return out


def _right_aligned_rows(prompt_ids, prompt_mask) -> tuple:
    """Host mirror of gpt._align_prompt's token layout: (ids_r [bb, P]
    with 0 at left-pad slots, pads [bb]). The radix cache keys pages by
    exactly the token layout the staged prefill writes, so its match keys
    must be computed the same way."""
    bb, P = prompt_ids.shape
    ids_r = np.zeros((bb, P), np.int32)
    pads = np.empty(bb, np.int32)
    for i in range(bb):
        ln = int(prompt_mask[i].sum())
        pads[i] = P - ln
        if ln:
            ids_r[i, P - ln:] = prompt_ids[i, :ln]
    return ids_r, pads


class _SessionRow:
    __slots__ = ("tag", "want", "tokens", "tenant", "created", "first_tok",
                 "radix_hit", "task_id", "prompt_ids")

    def __init__(self, tag: int, want: int, tenant: str = DEFAULT_TENANT,
                 created: Optional[float] = None, radix_hit: bool = False,
                 task_id: Optional[str] = None, prompt_ids=None):
        self.tag = tag
        self.want = want
        self.tokens: list = []
        # durability plane (resilience/genlog.py): the originating task id
        # keys this row's journal snapshots, and the EXACT post-trim prompt
        # ids are what a resume re-prefills — rows without a task_id (bench
        # direct callers, padding) are simply not journaled
        self.task_id = task_id
        self.prompt_ids = prompt_ids
        # FULL radix hit: the row's prefill was skipped outright (its
        # whole prompt was committed pages + stored logits) — feeds the
        # hit-vs-cold TTFT split in the engine timeline
        self.radix_hit = radix_hit
        # usage ledger + engine-side TTFT (obs/engine_timeline.py): the
        # fairness-lane tenant this row bills to, when the row's PREFILL
        # started (splice passes prepare_admit's entry time — a spliced
        # row's TTFT must include its tokenize/prefill/chunk-boundary
        # wait, not start at the splice), and when its first token
        # materialized on host
        self.tenant = tenant
        self.created = time.perf_counter() if created is None else created
        self.first_tok: Optional[float] = None


class BatchSession:
    """An in-flight chunked batch decode that requests can JOIN at chunk
    boundaries (continuous batching).

    GenBatcher's flush-window batching only merged requests that arrived
    within one deadline window; everything else serialized behind the whole
    decode. A session decodes in stream_chunk-step chunks and, between
    chunks, splices newly-prefilled rows into free slots (row-padding from
    the power-of-two batch bucket, or rows that already finished) via
    gpt.merge_rows — an admitted request's output is EXACTLY what a
    standalone decode would produce (gap cache slots masked, logical
    positions carried; asserted in tests/test_lm_engine.py).

    Threading: device work runs under the engine lock; host bookkeeping is
    single-caller (GenBatcher interleaves admit()/step() sequentially).
    """

    def __init__(self, lm: LmEngine, prompts: Sequence[str],
                 max_new_tokens: Sequence[int], temperature=None,
                 top_k=None, tenants=None, task_ids=None):
        import jax
        import jax.numpy as jnp

        cfg = lm.config
        self.lm = lm
        n = len(prompts)
        if n != len(max_new_tokens):
            raise ValueError("prompts and max_new_tokens length mismatch")
        # speculative decoding (docs/SPECULATIVE.md): with a drafter on the
        # engine, ask for spec_k slots of bucket headroom — spec rounds may
        # burn up to spec_k+1 slots to emit one token (rejected drafts), and
        # the margin guard only lets rounds run while a worst-case round
        # plus a plain finish still fits. Spec-off sessions are unchanged.
        spec_headroom = lm.spec_k if lm._draft is not None else 0
        prompt_ids, prompt_mask, self.new_bucket = lm._prepare_prompts(
            prompts, max(max_new_tokens) + spec_headroom,
            min_rows=cfg.session_min_rows)
        self.bb, self.P = prompt_ids.shape
        self.chunk = max(1, min(cfg.stream_chunk, self.new_bucket))
        self._temps = lm._norm_sampling_rows(temperature, cfg.temperature,
                                             self.bb, n, float)
        self._ks = lm._norm_sampling_rows(top_k, cfg.top_k, self.bb, n, int)
        self._eos = int(getattr(lm.tokenizer, "eos_id", -1))
        self._next_tag = 0
        row_tenants = _norm_tenants(tenants, n)
        row_task_ids = list(task_ids) if task_ids else [None] * n
        self.rows: list = []
        for i, w in enumerate(max_new_tokens):
            mrow = prompt_mask[i].astype(bool)
            self.rows.append(_SessionRow(
                self._next_tag, min(int(w), self.new_bucket),
                tenant=row_tenants[i], task_id=row_task_ids[i],
                prompt_ids=[int(t) for t in prompt_ids[i][mrow]]))
            self._next_tag += 1
        self.rows += [None] * (self.bb - n)  # free slots from the row bucket
        self.steps_done = 0
        self.decode_s = 0.0
        # paged-KV bookkeeping (symbiont_tpu/kv/): the HOST page-table
        # mirror is authoritative — the device table is rebuilt from it
        # whenever it changes (`_pt_dirty`; a [bb, n_blocks] int32 H2D is
        # noise next to a decode chunk). Unmapped blocks point at the
        # scratch page. `_row_pages` holds the page ids each row has a
        # refcount on (released the moment the row finishes/cancels).
        self._paged = lm.pool is not None
        self._plen = prompt_mask.sum(axis=1).astype(np.int32)  # [bb]
        self._row_pages: list = [[] for _ in range(self.bb)]
        self._row_blocks = [0] * self.bb
        if self._paged:
            page = lm.pool.page_tokens
            self._n_blocks = -(-(self.P + self.new_bucket) // page)
            self._prompt_blocks = self.P // page
            self._pt = np.zeros((self.bb, self._n_blocks), np.int32)
            self._pt_dev = None
            self._pt_dirty = True
        # decode-plane probes, all on host data already in hand
        # (obs/engine_timeline.py): token-id prefix overlap vs recently
        # admitted prompts, and exact prompt-token billing per tenant
        share = engine_timeline.prompt_prefix_share(
            _real_token_rows(prompt_ids, prompt_mask, n))
        for i in range(n):
            usage.note(row_tenants[i],
                       tokens_in=int(prompt_mask[i].sum()))
        # radix matching + prompt-page wiring, ONE pool-lock critical
        # section: a matched page must be retained before any alloc in the
        # same admission can LRU-evict it out from under us
        matches: list = [None] * self.bb
        skip_prefill = False
        hit_tokens = 0
        if self._paged:
            ids_r_host, pads = _right_aligned_rows(prompt_ids, prompt_mask)
            self._ids_r_host, self._pads = ids_r_host, pads
            pool = lm.pool
            with pool.lock:
                for i in range(n):
                    if lm.radix is not None:
                        matches[i] = lm.radix.match(
                            self.P, int(pads[i]), ids_r_host[i])
                        for pid in matches[i].pages:
                            pool.retain(pid)
                skip_prefill = (lm.radix is not None and n > 0 and all(
                    matches[i] is not None and matches[i].logits is not None
                    for i in range(n)))
                for i in range(n):
                    shared = list(matches[i].pages) if matches[i] else []
                    hit_tokens += max(0, len(shared) * pool.page_tokens
                                      - int(pads[i]))
                    fresh_n = self._prompt_blocks - len(shared)
                    fresh = pool.alloc(fresh_n) if fresh_n else []
                    pages = shared + fresh
                    self._pt[i, :self._prompt_blocks] = pages
                    self._row_pages[i] = pages
                    self._row_blocks[i] = self._prompt_blocks
                    self._pt_dirty = True
            pool.note_hit_tokens(hit_tokens)
        with lm._lock:
            lm._key, self._sub = jax.random.split(lm._key)
            t0 = time.perf_counter()
            if skip_prefill:
                # every real row's FULL prompt is committed pages + stored
                # logits: no prefill at all — restore the row state host-
                # side and decode straight from the shared pages. TTFT
                # collapses to ~one decode chunk (the radix-hit gate).
                for i in range(n):
                    self.rows[i].radix_hit = True
                logits_np = np.zeros(
                    (self.bb, lm.model_cfg.vocab_size), np.float32)
                kvv = np.zeros((self.bb, self.P + self.new_bucket), bool)
                kvv[:, self.P:] = True
                for i in range(n):
                    logits_np[i] = matches[i].logits
                    kvv[i, int(pads[i]):self.P] = True
                self._cache = None
                self._logits = jnp.asarray(logits_np)
                self._kv_valid = jnp.asarray(kvv)
                prompt_len = jnp.asarray(self._plen)
            else:
                (staging, self._logits, self._kv_valid,
                 prompt_len) = gpt_mod.prefill(
                    lm.params, jnp.asarray(prompt_ids),
                    jnp.asarray(prompt_mask), lm.model_cfg, self.new_bucket)
                lm._prefill_shapes.add((self.bb, self.P, self.new_bucket))
                if self._paged:
                    # adopt the dense-staged prefill into the pool: scatter
                    # each real row's FRESH prompt blocks (bit-copy — what
                    # makes paged decode token-identical to dense). Radix-
                    # shared blocks stay untouched (committed page content
                    # is immutable); rows with no pages write to scratch.
                    st = np.zeros((self.bb, self._prompt_blocks), np.int32)
                    for i in range(n):
                        nsh = len(matches[i].pages) if matches[i] else 0
                        st[i, nsh:] = self._pt[i, nsh:self._prompt_blocks]
                    pool = lm.pool
                    t_sc = time.perf_counter()
                    pk, pv, pks, pvs = gpt_mod._paged.scatter_prompt(
                        pool.k, pool.v, pool.k_scale, pool.v_scale,
                        staging, jnp.asarray(st), self.P)
                    dispatch_ledger.note_dispatch(
                        f"lm.scatter_prompt[P={self.P},B={self.bb}]",
                        time.perf_counter() - t_sc)
                    pool.adopt_arrays(pk, pv, pks, pvs)
                    self._cache = None
                else:
                    self._cache = staging
            prefill_s = time.perf_counter() - t0
            self.decode_s += prefill_s
            lm.stats["sessions"] = lm.stats.get("sessions", 0) + 1
        if not skip_prefill:
            dispatch_ledger.note_dispatch(
                f"lm.prefill[P={self.P},B={self.bb},new={self.new_bucket}]",
                prefill_s)
        if self._paged and lm.radix is not None and n and not skip_prefill:
            # commit the freshly-materialized prompt blocks (and the full-
            # prompt logits) so the NEXT admit with this prefix shares
            # them. One host sync on [bb, V] logits, per session start —
            # off the per-token decode path.
            logits_host = np.asarray(self._logits)
            with lm.pool.lock:
                for i in range(n):
                    lm.radix.commit(
                        self.P, int(pads[i]), ids_r_host[i],
                        [int(p) for p in self._pt[i, :self._prompt_blocks]],
                        logits_host[i])
        # drafter plane: a dense prefill at the same (prompt, new) geometry
        # — even for radix-hit sessions (the drafter has no radix; its
        # prefill is cheap by construction). Any failure degrades to plain
        # decode: speculation is a speed feature, never a correctness
        # dependency.
        self._d_cache = None
        self._pending = None   # [bb] device array; set ⇔ spec state
        self._spec_on = lm._draft is not None
        self._spec_rounds = 0
        self._spec_ema = None  # EMA of per-round acceptance, fallback gate
        if self._spec_on:
            try:
                draft_params, dcfg = lm._draft
                with lm._lock:
                    t_dp = time.perf_counter()
                    self._d_cache = gpt_mod.prefill(
                        draft_params, jnp.asarray(prompt_ids),
                        jnp.asarray(prompt_mask), dcfg, self.new_bucket)[0]
                    dp_s = time.perf_counter() - t_dp
                    self.decode_s += dp_s
                dispatch_ledger.note_dispatch(
                    f"lm.draft_prefill[P={self.P},B={self.bb},"
                    f"new={self.new_bucket}]", dp_s)
            except Exception:
                log.warning("draft prefill failed — session decodes plain",
                            exc_info=True)
                metrics.inc("lm.degraded",
                            labels={"reason": "draft_prefill_failed"})
                self._spec_on = False
                self._d_cache = None
        engine_timeline.note_admit(
            rows=n, prefill_ms=prefill_s * 1000.0, prefix_share=share,
            kind="start",
            hit_tokens=hit_tokens if self._paged else None,
            prompt_tokens=int(self._plen[:n].sum()) if self._paged else None)
        with lm._sessions_lock:  # weak: KV-occupancy gauges see live sessions
            lm._sessions.add(self)
        self._pos = prompt_len
        self._done = jnp.zeros((self.bb,), bool)
        # host-gap attribution (obs/xprof.py): end of the last device work
        # on this session; step() reads it to split chunk-to-chunk wall
        # into device-busy vs host-think — using ONLY the chunk-boundary
        # syncs that already exist, no new device syncs
        self._last_step_end = time.perf_counter()

    # ------------------------------------------------------- paged KV state

    def rows_holding_pages(self) -> int:
        """Rows currently mapping ≥1 pool page — the paged layout's
        'allocated' row count (freed rows return pages immediately)."""
        return sum(1 for pages in self._row_pages if pages)

    def pages_reserved(self) -> int:
        """Pages this session's LIVE rows may still lazily allocate
        (worst case: every row decodes to the session cap). Admission
        control subtracts this from the pool's free+evictable total."""
        if not self._paged:
            return 0
        return sum(self._n_blocks - self._row_blocks[i]
                   for i, r in enumerate(self.rows) if r is not None)

    def page_occupancy(self) -> tuple:
        """(live_tokens, mapped_page_slots) over live rows — the
        kv.page_fragmentation_pct numerator/denominator. Shared radix
        pages count once per mapping row: this measures how well rows
        fill what they hold, not pool utilization."""
        if not self._paged:
            return 0, 0
        page = self.lm.pool.page_tokens
        toks = slots = 0
        for i, r in enumerate(self.rows):
            if r is None:
                continue
            toks += int(self._plen[i]) + len(r.tokens)
            slots += self._row_blocks[i] * page
        return toks, slots

    def _refresh_pt(self) -> None:
        if self._pt_dirty:
            import jax.numpy as jnp

            self._pt_dev = jnp.asarray(self._pt)
            self._pt_dirty = False

    def _build_cache(self):
        """PagedKVCache view for the next device call. Pool arrays are
        ENGINE-owned and donated through every chunk/splice (the engine
        re-adopts them from each call's return), so sessions never hold a
        cache across calls — each builds a fresh tuple from the pool's
        current buffers, its own device page table, and the host-tracked
        scalar length (P + steps_done, the same value the dense carry
        threads on device)."""
        import jax.numpy as jnp

        pool = self.lm.pool
        self._refresh_pt()
        return PagedKVCache(
            pool.k, pool.v, pool.k_scale, pool.v_scale, self._pt_dev,
            jnp.asarray(self.P + self.steps_done, jnp.int32))

    def _ensure_decode_blocks(self, chunk: int) -> None:
        """Lazy page growth — the tentpole's allocation model: before a
        chunk, every live row maps enough blocks to cover cache slots
        [0, P + steps_done + chunk). Pages arrive as sessions grow
        instead of as max-length slabs; rows that die early simply never
        claim their tail blocks."""
        pool = self.lm.pool
        need = min(self._n_blocks,
                   -(-(self.P + self.steps_done + chunk) // pool.page_tokens))
        with pool.lock:
            for i, r in enumerate(self.rows):
                if r is None:
                    continue
                while self._row_blocks[i] < need:
                    pid = pool.alloc(1)[0]
                    self._pt[i, self._row_blocks[i]] = pid
                    self._row_pages[i].append(pid)
                    self._row_blocks[i] += 1
                    self._pt_dirty = True

    def _release_row_pages(self, i: int) -> None:
        """Return row i's pages the moment it finishes/cancels: committed
        (radix-shared) pages drop to the LRU-retained set, private ones
        go straight back to the free list, and the row's page-table row
        points at scratch again."""
        if not self._paged or not self._row_pages[i]:
            return
        pool = self.lm.pool
        with pool.lock:
            for pid in self._row_pages[i]:
                pool.release(pid)
        self._row_pages[i] = []
        self._row_blocks[i] = 0
        self._pt[i, :] = 0
        self._pt_dirty = True

    # ------------------------------------------------------------ admission

    def capacity(self) -> int:
        return sum(1 for r in self.rows if r is None)

    def remaining_steps(self) -> int:
        return self.new_bucket - self.steps_done

    def round_slots(self) -> int:
        """Decode slots the next step() may consume — the admission
        lookahead unit. A spec round burns spec_k+1 slots (accepted or
        not); plain chunks burn `chunk`."""
        if self._spec_on:
            return max(self.chunk, self.lm.spec_k + 1)
        return self.chunk

    def done(self) -> bool:
        return all(r is None for r in self.rows) or self.remaining_steps() <= 0

    def can_admit(self, prompt: str, max_new: int,
                  lookahead_chunks: int = 0) -> bool:
        """A newcomer joins only if a row slot is free, its budget fits the
        steps this session still has, and its prompt fits the session's
        prompt bucket untrimmed (a longer prompt would lose more context
        than a standalone decode — leave it for the next session).
        `lookahead_chunks` reserves budget for chunks that will decode
        between this check and the actual splice (the prepare/splice split
        runs the newcomer's prefill concurrently with one in-flight chunk)."""
        # spec debt: splicing while a pending token is riding host-side
        # costs one ingest slot (_to_plain) before the merge can happen
        debt = 1 if self._pending is not None else 0
        if (self.capacity() == 0
                or int(max_new) > self.remaining_steps() - debt
                - lookahead_chunks * self.round_slots()):
            return False
        if len(self.lm.tokenizer.encode(prompt or "", self.P + 1)) > self.P:
            return False
        if self._paged:
            # page accounting: a radix-hit admit needs only its fresh
            # (post-fork) blocks now, but reserves the row's full span —
            # admitting must never let a later lazy decode-block alloc
            # hit PoolExhausted
            pool = self.lm.pool
            enc = self.lm.tokenizer.encode(prompt or "", self.P)
            if not enc:
                enc = [getattr(self.lm.tokenizer, "bos_id", 0)]
            ids_r = np.zeros(self.P, np.int32)
            ids_r[self.P - len(enc):] = enc
            with pool.lock:
                hit = (self.lm.radix.match(
                    self.P, self.P - len(enc), ids_r).blocks
                    if self.lm.radix is not None else 0)
                need = self._n_blocks - hit
                avail = (pool.pages_free + pool.pages_retained
                         - self.lm.pages_reserved())
            if need > avail:
                return False
        return True

    @staticmethod
    def _admission_rows(k: int) -> int:
        """Rows an admission prefill pads to (power-of-two batch bucket).
        Single source for prepare_admit AND prefill_warm — the warm/cold
        prediction is only right while they agree."""
        return 1 << (k - 1).bit_length() if k > 1 else 1

    def prefill_warm(self, k: int) -> bool:
        """Whether admitting k newcomers hits an already-compiled prefill
        shape — prepare_admit then costs milliseconds, not a fresh XLA
        compile (the batcher sizes its budget reservation by this)."""
        bb2 = self._admission_rows(k)
        return (bb2, self.P, self.new_bucket) in self.lm._prefill_shapes

    def prepare_admit(self, prompts: Sequence[str],
                      max_new_tokens: Sequence[int],
                      temperature=None, top_k=None, tenants=None,
                      task_ids=None) -> dict:
        """Phase 1 of admission: tokenize + device prefill, WITHOUT the
        engine lock — so a newcomer's prefill (which may compile a fresh
        (batch, P) shape, seconds of host time) cannot stall the in-flight
        batch's next chunk (VERDICT r4 weak #4). Lock-free is safe: params
        are immutable jax buffers read via one atomic attribute load; a
        concurrent update_params swap means the newcomer prefills on the
        old params — the same contract an in-progress stream already has.
        Returns an opaque blob for splice(); no session state is touched."""
        import jax.numpy as jnp

        cfg = self.lm.config
        t_enter = time.perf_counter()  # TTFT origin for the spliced rows
        k = len(prompts)
        bb2 = self._admission_rows(k)
        pad = getattr(self.lm.tokenizer, "pad_id", 0)
        bos = getattr(self.lm.tokenizer, "bos_id", 0)
        ids = np.full((bb2, self.P), pad, np.int32)
        mask = np.zeros((bb2, self.P), np.int32)
        for j, prompt in enumerate(prompts):
            enc = self.lm.tokenizer.encode(prompt or "", 1 << 30)[-self.P:]
            if not enc:
                enc = [bos]
            ids[j, :len(enc)] = enc
            mask[j, :len(enc)] = 1
        for j in range(k, bb2):
            ids[j, 0] = bos
            mask[j, 0] = 1
        # prefix-share probe + exact prompt-token counts BEFORE device
        # work: both read only the host arrays built above
        share = engine_timeline.prompt_prefix_share(
            _real_token_rows(ids, mask, k))
        n_tokens = [int(mask[j].sum()) for j in range(k)]
        paged_prep = None
        skip = False
        if self._paged:
            # probe-match (no retain — a rejected splice must not leak
            # refcounts): a FULL hit for every newcomer means no device
            # prefill at all; splice re-validates under the pool lock
            ids_r, pads = _right_aligned_rows(ids, mask)
            if self.lm.radix is not None:
                with self.lm.pool.lock:
                    skip = k > 0 and all(
                        self.lm.radix.match(self.P, int(pads[j]),
                                            ids_r[j]).logits is not None
                        for j in range(k))
            paged_prep = {"ids_r": ids_r, "pads": pads, "skip": skip}
        params = self.lm.params  # snapshot; immutable buffers
        t0 = time.perf_counter()
        if skip:
            cache_b = logits_b = kv_valid_b = pos_b = None
        else:
            (cache_b, logits_b, kv_valid_b, pos_b) = gpt_mod.prefill(
                params, jnp.asarray(ids), jnp.asarray(mask),
                self.lm.model_cfg, self.new_bucket)
            self.lm._prefill_shapes.add((bb2, self.P, self.new_bucket))
            dispatch_ledger.note_dispatch(
                f"lm.prefill[P={self.P},B={bb2},new={self.new_bucket}]",
                time.perf_counter() - t0)
        d_cache_b = None
        if self._spec_on and self._d_cache is not None:
            # drafter rows for the newcomers (merge_cache_rows splices them
            # at the same chunk boundary as the target merge). Runs even on
            # a full radix hit — the drafter has no radix. Same lock-free
            # contract as the target prefill above.
            draft_params, dcfg = self.lm._draft
            t_dd = time.perf_counter()
            d_cache_b = gpt_mod.prefill(
                draft_params, jnp.asarray(ids), jnp.asarray(mask),
                dcfg, self.new_bucket)[0]
            dispatch_ledger.note_dispatch(
                f"lm.draft_prefill[P={self.P},B={bb2},"
                f"new={self.new_bucket}]", time.perf_counter() - t_dd)
        return {"k": k, "bb2": bb2, "cache": cache_b, "logits": logits_b,
                "d_cache": d_cache_b,
                "kv_valid": kv_valid_b, "pos": pos_b, "paged": paged_prep,
                "max_new": [int(w) for w in max_new_tokens],
                "temps": self.lm._norm_sampling_rows(
                    temperature, cfg.temperature, bb2, k, float),
                "ks": self.lm._norm_sampling_rows(
                    top_k, cfg.top_k, bb2, k, int),
                "tenants": _norm_tenants(tenants, k),
                "task_ids": (list(task_ids) if task_ids else [None] * k),
                "prompt_row_ids": [[int(t) for t in ids[j, :n_tokens[j]]]
                                   for j in range(k)],
                "n_tokens": n_tokens,
                "prefix_share": share,
                "t_enter": t_enter,
                "prefill_s": time.perf_counter() - t0}

    def splice(self, prep: dict) -> list:
        """Phase 2: merge prepared rows into free slots at the current chunk
        boundary. Cheap under the lock — one merge_rows dispatch, no
        prefill. Returns a tag per prepared newcomer, or None where the
        request no longer fits (chunks decoded between prepare and splice
        shrank the remaining budget — truncating would break standalone
        equivalence, so the caller re-queues those for the next session).

        Paged sessions additionally wire pages here, under the pool lock:
        each taken row RE-matches the radix trie (prepare's probe is
        advisory — pages can be LRU-evicted in between), retains the
        still-shared pages, allocates fresh ones past the fork, and builds
        the scatter table that adopts the staged prefill's fresh blocks
        into the pool. A full-hit prep (no staged values at all) whose hit
        degraded is REJECTED the same way a budget miss is — there is
        nothing to materialize its pages from."""
        import contextlib

        import jax.numpy as jnp

        if prep["k"] and self._pending is not None:
            # splice merges PLAIN state (newcomer rows carry no pending
            # token): fold ours into both caches first — one slot — and let
            # the next step re-enter speculation over the merged batch
            self._to_plain()
        pg = prep.get("paged")
        pool = self.lm.pool
        free = [i for i, r in enumerate(self.rows) if r is None]
        row_map = np.full((self.bb,), -1, np.int32)
        tags: list = []
        taken = 0
        matches_by_row: dict = {}
        hit_tokens = 0
        lock = pool.lock if self._paged else contextlib.nullcontext()
        with lock:
            for j in range(prep["k"]):
                if (taken >= len(free)
                        or prep["max_new"][j] > self.remaining_steps()):
                    tags.append(None)
                    continue
                if self._paged:
                    m = (self.lm.radix.match(
                        self.P, int(pg["pads"][j]), pg["ids_r"][j])
                        if self.lm.radix is not None else None)
                    if prep["cache"] is None and (m is None
                                                  or m.logits is None):
                        tags.append(None)
                        continue
                    shared = list(m.pages) if m is not None else []
                    for pid in shared:
                        pool.retain(pid)
                    need = self._prompt_blocks - len(shared)
                    if not pool.can_alloc(need):
                        for pid in shared:
                            pool.release(pid)
                        tags.append(None)
                        continue
                i = free[taken]
                taken += 1
                row_map[i] = j
                if self._paged:
                    pages = shared + (pool.alloc(need) if need else [])
                    self._pt[i, :self._prompt_blocks] = pages
                    self._row_pages[i] = pages
                    self._row_blocks[i] = self._prompt_blocks
                    self._pt_dirty = True
                    matches_by_row[i] = (j, m, len(shared))
                    hit_tokens += max(0, len(shared) * pool.page_tokens
                                      - int(pg["pads"][j]))
                self.rows[i] = _SessionRow(
                    self._next_tag, prep["max_new"][j],
                    tenant=prep.get("tenants",
                                    [DEFAULT_TENANT] * prep["k"])[j],
                    created=prep.get("t_enter"),
                    radix_hit=(self._paged and prep["cache"] is None),
                    task_id=prep.get("task_ids",
                                     [None] * prep["k"])[j],
                    prompt_ids=prep.get("prompt_row_ids",
                                        [None] * prep["k"])[j])
                usage.note(self.rows[i].tenant,
                           tokens_in=prep.get("n_tokens",
                                              [0] * prep["k"])[j])
                tags.append(self._next_tag)
                self._next_tag += 1
                self._temps[i] = prep["temps"][j]
                self._ks[i] = prep["ks"][j]
        if self._paged:
            pool.note_hit_tokens(hit_tokens)
        if taken == 0:
            # even a fully-rejected admission paid its prefill — keep it in
            # the timing stats or wasted cold-compile work becomes invisible
            with self.lm._lock:
                self.decode_s += prep["prefill_s"]
            return tags
        with self.lm._lock:
            t0 = time.perf_counter()
            done_b = jnp.zeros((prep["bb2"],), bool)
            if self._paged:
                staging = prep["cache"]
                st = np.zeros((prep["bb2"], self._prompt_blocks), np.int32)
                for i, (j, m, nsh) in matches_by_row.items():
                    # fresh (post-fork) blocks only: committed page
                    # content is immutable, rejected rows stay on scratch
                    st[j, nsh:] = self._pt[i, nsh:self._prompt_blocks]
                if staging is None:
                    # full-hit splice: every taken row's prompt is shared
                    # pages + stored logits — restore row state host-side,
                    # nothing touches the device but the row merge
                    ln = np.zeros((prep["bb2"],
                                   self.lm.model_cfg.vocab_size), np.float32)
                    pn = np.zeros((prep["bb2"],), np.int32)
                    kn = np.zeros((prep["bb2"],
                                   self.P + self.new_bucket), bool)
                    kn[:, self.P:] = True
                    for _, (j, m, nsh) in matches_by_row.items():
                        ln[j] = m.logits
                        pn[j] = self.P - int(pg["pads"][j])
                        kn[j, int(pg["pads"][j]):self.P] = True
                    logits_b, pos_b, kv_valid_b = (jnp.asarray(ln),
                                                   jnp.asarray(pn),
                                                   jnp.asarray(kn))
                else:
                    logits_b, pos_b, kv_valid_b = (prep["logits"],
                                                   prep["pos"],
                                                   prep["kv_valid"])
                self._refresh_pt()
                cache_a = self._build_cache()
                t_mr = time.perf_counter()
                (cache, self._logits, self._pos, self._done,
                 self._kv_valid) = gpt_mod.merge_rows(
                    cache_a, self._logits, self._pos, self._done,
                    self._kv_valid,
                    (staging, jnp.asarray(st), self._pt_dev),
                    logits_b, pos_b, done_b, kv_valid_b,
                    jnp.asarray(row_map), prompt_width=self.P)
                dispatch_ledger.note_dispatch(
                    f"lm.merge_rows[P={self.P},B={self.bb}]",
                    time.perf_counter() - t_mr)
                pool.adopt_arrays(cache.k, cache.v,
                                  cache.k_scale, cache.v_scale)
                self._pt_dev = cache.page_table
            else:
                t_mr = time.perf_counter()
                (self._cache, self._logits, self._pos, self._done,
                 self._kv_valid) = gpt_mod.merge_rows(
                    self._cache, self._logits, self._pos, self._done,
                    self._kv_valid, prep["cache"], prep["logits"],
                    prep["pos"], done_b, prep["kv_valid"],
                    jnp.asarray(row_map), prompt_width=self.P)
                dispatch_ledger.note_dispatch(
                    f"lm.merge_rows[P={self.P},B={self.bb}]",
                    time.perf_counter() - t_mr)
            if self._d_cache is not None:
                if prep.get("d_cache") is not None:
                    # drafter-side row splice: same row_map, field-wise pick
                    # (gap validity rides the SHARED kv_valid merge_rows
                    # just masked — models/gpt.py merge_cache_rows)
                    t_dm = time.perf_counter()
                    self._d_cache = gpt_mod.merge_cache_rows(
                        self._d_cache, prep["d_cache"],
                        jnp.asarray(row_map))
                    dispatch_ledger.note_dispatch(
                        f"lm.draft_merge_rows[P={self.P},B={self.bb}]",
                        time.perf_counter() - t_dm)
                else:
                    # an admission prepared without drafter rows (prepared
                    # before the drafter failed, or its draft prefill was
                    # skipped): speculating over rows with no drafter
                    # content would propose garbage — decode plain instead
                    self._spec_on = False
                    self._d_cache = None
            self.decode_s += time.perf_counter() - t0 + prep["prefill_s"]
            self.lm.stats["admitted"] = (self.lm.stats.get("admitted", 0)
                                         + taken)
        if (self._paged and self.lm.radix is not None
                and prep["cache"] is not None and matches_by_row):
            # commit the taken rows' freshly-materialized blocks + full-
            # prompt logits for the next admit (same placement as the
            # session-start commit: one host sync, off the decode path)
            logits_host = np.asarray(prep["logits"])
            with pool.lock:
                for i, (j, m, nsh) in matches_by_row.items():
                    self.lm.radix.commit(
                        self.P, int(pg["pads"][j]), pg["ids_r"][j],
                        [int(p) for p in self._pt[i, :self._prompt_blocks]],
                        logits_host[j])
        engine_timeline.note_admit(
            rows=taken, prefill_ms=prep["prefill_s"] * 1000.0,
            prefix_share=prep.get("prefix_share"), kind="splice",
            hit_tokens=hit_tokens if self._paged else None,
            prompt_tokens=(sum(prep["n_tokens"][j] for (j, _, _)
                               in matches_by_row.values())
                           if self._paged else None))
        return tags

    def admit(self, prompts: Sequence[str], max_new_tokens: Sequence[int],
              temperature=None, top_k=None, tenants=None,
              task_ids=None) -> list:
        """One-shot admission (prepare + splice back-to-back, no chunks in
        between so nothing can be rejected). Caller pre-filters with
        can_admit. Returns the tags identifying each admitted request in
        step() results."""
        tags = self.splice(self.prepare_admit(
            prompts, max_new_tokens, temperature=temperature, top_k=top_k,
            tenants=tenants, task_ids=task_ids))
        assert None not in tags, "admit() beyond capacity()"
        return tags

    def cancel_tag(self, tag: int) -> bool:
        """Abort one in-flight request (SSE client vanished): its batch row
        frees IMMEDIATELY — the slot becomes admissible to newcomers at the
        next chunk boundary, the `lm.kv_rows_active` gauge stops counting
        it, and a session whose every row was cancelled reads done() (so
        `lm.kv_rows_allocated` returns to baseline too). The row's decoded
        tokens are discarded, not published. Returns False when the tag is
        not live (already finished — cancellation raced completion)."""
        for i, row in enumerate(self.rows):
            if row is not None and row.tag == tag:
                self.rows[i] = None
                # pages return to the pool IMMEDIATELY (mid-chunk cancels
                # included): private pages to the free list, radix-shared
                # ones to the evictable retained set — the kv.* gauges
                # read baseline again as soon as every row is gone
                self._release_row_pages(i)
                usage.note(row.tenant, tokens_out=len(row.tokens))
                engine_timeline.note_cancel()
                if self.lm.journal is not None and row.task_id:
                    # a cancelled row is terminal — it must never resurrect
                    # as a resume task after a later worker death
                    self.lm.journal.mark_done(row.task_id)
                with self.lm._lock:
                    self.lm.stats["cancelled"] = (
                        self.lm.stats.get("cancelled", 0) + 1)
                    # the row's share of device time is still real work done
                    self.lm.stats["tokens_generated"] += len(row.tokens)
                    # flush accumulated decode seconds like _finish does: a
                    # fully-cancelled session never reaches _finish, and
                    # tokens credited without their time would inflate the
                    # derived tok/s gauge
                    self.lm.stats["decode_s"] += self.decode_s
                    self.decode_s = 0.0
                return True
        return False

    # --------------------------------------------------------------- decode

    def step(self) -> list:
        """Decode one chunk — or one speculative draft+verify round when a
        drafter is attached and the slot margin allows it; returns
        [(tag, text), ...] for every request that finished in it (eos, its
        own budget, or the session cap). The spec/plain choice is re-made
        every chunk boundary, so a session degrades AND re-enters
        speculation as margins, splices, and drafter quality dictate.

        Runs under the OOM guard (obs/hbm.py): a RESOURCE_EXHAUSTED out of
        any step dispatch dumps the hbm postmortem (ledger + census + last
        timeline window), counts engine.oom_total{site="lm.batch_step"},
        and re-raises — the batcher's existing error path fails the
        affected requests and the engine keeps serving."""
        with guard_oom("lm.batch_step"):
            if self.done():
                return self._drain_all()
            if (self._spec_on and self._d_cache is not None
                    and self._spec_margin_ok()):
                return self._step_spec()
            if self._pending is not None:
                self._to_plain()
                if self.done():  # the ingest slot was the session's last one
                    return self._drain_all()
            return self._step_plain()

    def _spec_margin_ok(self) -> bool:
        """Slot-margin guard: a spec round may only run while the WORST
        case (one emitted token for S=spec_k+1 slots burned) still leaves
        room to finish every live row's budget with plain decode — so
        speculation can waste slots, never truncate a row."""
        S = self.lm.spec_k + 1
        r_max = max((r.want - len(r.tokens)
                     for r in self.rows if r is not None), default=0)
        return (self.remaining_steps()
                >= S + r_max - (1 if self._pending is None else 0))

    def _to_plain(self) -> None:
        """spec → plain at a chunk boundary: forward `pending` into BOTH
        caches (one slot each, one fused dispatch per plane) and recover
        carried logits, after which decode_chunk / merge_rows apply
        unchanged. Greedy output is token-identical across the mode switch
        (gpt.ingest_pending computes exactly the logits a plain step at
        that position would have carried)."""
        if self._pending is None:
            return
        lm = self.lm
        if self._paged:
            self._ensure_decode_blocks(1)
        with lm._lock:
            t0 = time.perf_counter()
            cache_in = self._build_cache() if self._paged else self._cache
            cache_out, self._logits, self._pos = gpt_mod.ingest_pending(
                lm.params, cache_in, self._pending, self._pos, self._done,
                self._kv_valid, lm.model_cfg)
            if self._paged:
                lm.pool.adopt_arrays(cache_out.k, cache_out.v,
                                     cache_out.k_scale, cache_out.v_scale)
                self._pt_dev = cache_out.page_table
            else:
                self._cache = cache_out
            if self._d_cache is not None:
                # drafter lockstep: the same token lands in the drafter's
                # matching slot so speculation can re-enter later
                draft_params, dcfg = lm._draft
                self._d_cache = gpt_mod.track_chunk(
                    draft_params, self._d_cache, self._pending[:, None],
                    self._pos - 1, self._kv_valid, dcfg)
            dt = time.perf_counter() - t0
            self.decode_s += dt
            self._last_step_end = time.perf_counter()
        dispatch_ledger.note_dispatch(f"lm.ingest_pending[B={self.bb}]", dt)
        self._pending = None
        self.steps_done += 1

    def _step_spec(self) -> list:
        """One speculative round: the drafter proposes spec_k greedy tokens
        (its own chunk-scan dispatch), the target scores all k+1 window
        positions in ONE verify_chunk dispatch, and each row advances by
        its own accepted count — the per-row variable advance every piece
        of chunk-boundary bookkeeping below is keyed on. Rejected draft
        slots become kv_valid holes (never rewritten); drafter divergence
        and page-pool pressure both degrade to plain decode, never error."""
        import jax

        lm = self.lm
        S = lm.spec_k + 1
        if self._paged:
            try:
                self._ensure_decode_blocks(S)
            except Exception:
                # spec-window page pressure (PoolExhausted): degrade FOR
                # GOOD — speculation must never turn pool pressure into a
                # caller-visible error
                log.warning("page alloc for spec window failed — session "
                            "falls back to plain decode", exc_info=True)
                metrics.inc("lm.degraded",
                            labels={"reason": "spec_page_pressure"})
                self._spec_on = False
                return self.step()
        draft_params, dcfg = lm._draft
        first_t = first_c = None
        with lm._lock:
            t0 = time.perf_counter()
            host_gap_s = max(0.0, t0 - self._last_step_end)
            self._sub, use = jax.random.split(self._sub)
            if self._pending is None:
                # plain → spec: the first token comes off the carried
                # logits — exactly what the next plain step would sample
                use, k0 = jax.random.split(use)
                self._pending, c0, self._done = gpt_mod.spec_first(
                    self._logits, self._done, k0, lm.model_cfg,
                    temperature=self._temps, top_k=self._ks,
                    eos_id=self._eos)
                first = (self._pending, c0)
            else:
                first = None
            t_d = time.perf_counter()
            self._d_cache, drafts = gpt_mod.draft_chunk(
                draft_params, self._d_cache, self._pending, self._pos,
                self._done, self._kv_valid, dcfg, lm.spec_k)
            # the draft/verify ms split the timeline archives: one device
            # wait (no host transfer), at a boundary that syncs anyway
            jax.block_until_ready(drafts)
            t_v = time.perf_counter()
            cache_in = self._build_cache() if self._paged else self._cache
            (cache_out, self._pending, self._pos, self._done,
             self._kv_valid, out, counted, emitted) = gpt_mod.verify_chunk(
                lm.params, cache_in, self._pending, drafts, self._pos,
                self._done, self._kv_valid, use, lm.model_cfg,
                temperature=self._temps, top_k=self._ks, eos_id=self._eos)
            if self._paged:
                lm.pool.adopt_arrays(cache_out.k, cache_out.v,
                                     cache_out.k_scale, cache_out.v_scale)
                self._pt_dev = cache_out.page_table
            else:
                self._cache = cache_out
            out = np.asarray(out)
            counted = np.asarray(counted)
            em = np.asarray(emitted)
            if first is not None:
                first_t = np.asarray(first[0])
                first_c = np.asarray(first[1])
            t_end = time.perf_counter()
            step_s = t_end - t0
            draft_s = t_v - t_d
            verify_s = t_end - t_v
            self.decode_s += step_s
            self._last_step_end = time.perf_counter()
        dispatch_ledger.note_dispatch(
            f"lm.draft_chunk[P={self.P},B={self.bb},k={lm.spec_k}]", draft_s)
        dispatch_ledger.note_dispatch(
            f"lm.verify_chunk[P={self.P},B={self.bb},k={lm.spec_k}]",
            verify_s)
        if first_t is not None:
            dispatch_ledger.note_dispatch(
                f"lm.spec_first[B={self.bb}]", t_d - t0)
        self.steps_done += S
        live_rows = [r for r in self.rows if r is not None]
        live_idx = [i for i, r in enumerate(self.rows) if r is not None]
        n_live = max(1, len(live_rows))
        proposed = lm.spec_k * len(live_rows)
        accepted = sum(max(0, int(em[i]) - 1) for i in live_idx)
        emitted_total = (sum(int(em[i]) for i in live_idx)
                         + (len(live_rows) if first_t is not None else 0))
        lm._spec_proposed += proposed
        lm._spec_accepted += accepted
        kv_live, kv_alloc = lm.kv_row_counts()
        pool = lm.pool
        engine_timeline.note_decode_step(
            wall_ms=step_s * 1000.0, rows_live=len(live_rows),
            rows_capacity=self.bb, kv_rows_live=kv_live,
            kv_rows_allocated=kv_alloc,
            steps=emitted_total / n_live,
            pages_free=pool.pages_free if self._paged else None,
            pages_live=pool.pages_live if self._paged else None,
            pages_total=pool.n_pages - 1 if self._paged else None,
            dispatches=2 + (1 if first_t is not None else 0),
            host_gap_ms=host_gap_s * 1000.0,
            spec_draft_ms=draft_s * 1000.0,
            spec_verify_ms=verify_s * 1000.0,
            spec_proposed=proposed, spec_accepted=accepted)
        mean_emitted = emitted_total / n_live
        if mean_emitted > 0:
            metrics.observe("lm.tpot_ms", step_s * 1000.0 / mean_emitted,
                            labels={"service": "lm"})
        by_tenant: dict = {}
        for row in live_rows:
            by_tenant[row.tenant] = by_tenant.get(row.tenant, 0) + 1
        for tenant, n_rows in by_tenant.items():
            usage.note(tenant, kv_row_seconds=step_s * n_rows)
        # drafter-divergence fallback: an EMA of per-round acceptance that
        # stays near zero means rounds burn S slots to emit ~1 token —
        # strictly worse than plain decode. Off for good, this session.
        rate = accepted / proposed if proposed else 0.0
        self._spec_rounds += 1
        self._spec_ema = (rate if self._spec_ema is None
                          else 0.5 * self._spec_ema + 0.5 * rate)
        if self._spec_rounds >= 3 and self._spec_ema < 0.1:
            log.info("spec accept EMA %.2f after %d rounds — session "
                     "falls back to plain decode", self._spec_ema,
                     self._spec_rounds)
            self._spec_on = False

        def pairs(i):
            if first_t is not None:
                yield first_t[i], first_c[i]
            for j in range(int(em[i])):
                yield out[i, j], counted[i, j]

        return self._emit_and_finish(pairs)

    def _step_plain(self) -> list:
        """Plain chunk decode (the spec-off path, byte-identical to the
        pre-spec engine); with a live drafter the chunk's tokens are also
        teacher-forced into the drafter cache (ONE extra small dispatch)
        so speculation can re-enter at a later boundary."""
        import jax

        chunk = min(self.chunk, self.remaining_steps())
        if self._paged:
            # lazy page growth happens at the chunk boundary, off the
            # engine lock (host-only free-list work)
            self._ensure_decode_blocks(chunk)
        with self.lm._lock:
            t0 = time.perf_counter()
            # host-think since the previous chunk's device window closed:
            # splice/admission/bookkeeping + batcher scheduling. Measured
            # from values already on host — no new device syncs.
            host_gap_s = max(0.0, t0 - self._last_step_end)
            self._sub, use = jax.random.split(self._sub)
            keys = jax.random.split(use, chunk)
            cache_in = self._build_cache() if self._paged else self._cache
            (cache_out, self._logits, self._pos, self._done, toks,
             counted) = gpt_mod.decode_chunk(
                self.lm.params, cache_in, self._logits, self._pos,
                self._done, self._kv_valid, keys, self.lm.model_cfg,
                temperature=self._temps, top_k=self._ks, eos_id=self._eos)
            if self._paged:
                # pool buffers were donated through the chunk — hand the
                # returned arrays back to the engine-global pool
                self.lm.pool.adopt_arrays(cache_out.k, cache_out.v,
                                          cache_out.k_scale,
                                          cache_out.v_scale)
                self._pt_dev = cache_out.page_table
            else:
                self._cache = cache_out
            if self._spec_on and self._d_cache is not None:
                # drafter lockstep: teacher-force the chunk's tokens into
                # the drafter cache (decode_chunk's returned toks are
                # exactly what it wrote — done-row zeros included), so
                # speculation can re-enter at a later boundary. pos was
                # donated through decode_chunk; start = new pos - chunk.
                draft_params, dcfg = self.lm._draft
                self._d_cache = gpt_mod.track_chunk(
                    draft_params, self._d_cache, toks,
                    self._pos - chunk, self._kv_valid, dcfg)
            toks = np.asarray(toks)
            counted = np.asarray(counted)
            step_s = time.perf_counter() - t0
            self.decode_s += step_s
            self._last_step_end = time.perf_counter()
        dispatch_ledger.note_dispatch(
            f"lm.decode_chunk[P={self.P},B={self.bb},chunk={chunk}]", step_s)
        self.steps_done += chunk
        # decode-plane flight recorder (obs/engine_timeline.py), recorded
        # at this EXISTING chunk-boundary host sync — everything below is
        # host bookkeeping on already-materialized values. Occupancy /
        # per-tenant KV-row-seconds are measured over the rows that were
        # live DURING the chunk (before this chunk's finishes free them).
        live_rows = [r for r in self.rows if r is not None]
        kv_live, kv_alloc = self.lm.kv_row_counts()
        pool = self.lm.pool
        engine_timeline.note_decode_step(
            wall_ms=step_s * 1000.0, rows_live=len(live_rows),
            rows_capacity=self.bb, kv_rows_live=kv_live,
            kv_rows_allocated=kv_alloc, steps=chunk,
            pages_free=pool.pages_free if self._paged else None,
            pages_live=pool.pages_live if self._paged else None,
            pages_total=pool.n_pages - 1 if self._paged else None,
            dispatches=1, host_gap_ms=host_gap_s * 1000.0)
        if chunk:
            metrics.observe("lm.tpot_ms", step_s * 1000.0 / chunk,
                            labels={"service": "lm"})
        by_tenant: dict = {}
        for row in live_rows:
            by_tenant[row.tenant] = by_tenant.get(row.tenant, 0) + 1
        for tenant, n_rows in by_tenant.items():
            usage.note(tenant, kv_row_seconds=step_s * n_rows)
        return self._emit_and_finish(lambda i: zip(toks[i], counted[i]))

    def _emit_and_finish(self, pairs) -> list:
        """Per-row chunk-boundary bookkeeping shared by the plain and spec
        paths — journal snapshot, TTFT, finish detection — over host
        values already materialized (`pairs(i)` iterates row i's
        (token, counted) run for this boundary; under speculation rows
        yield DIFFERENT run lengths, which is the per-row variable
        advance)."""
        now = time.perf_counter()
        finished = []
        jr = self.lm.journal
        journaling = jr is not None and jr.enabled
        for i, row in enumerate(self.rows):
            if row is None:
                continue
            hit_eos = False
            had_tokens = bool(row.tokens)
            for t, c in pairs(i):
                if not c:  # EOS (or a post-EOS slot)
                    hit_eos = True
                    break
                row.tokens.append(int(t))
                if len(row.tokens) >= row.want:
                    break
            if journaling and row.task_id and row.prompt_ids is not None:
                # durability snapshot at this EXISTING chunk-boundary host
                # sync (toks/counted are already np arrays above — no new
                # device syncs). Batch rows carry no stream seq and no PRNG
                # key: a session's sample chain is shared across its rows,
                # so a different replica cannot restore it per-row — greedy
                # resume is token-identical, sampled resume continues on a
                # fresh chain (docs/RESILIENCE.md).
                jr.append({"task_id": row.task_id, "tenant": row.tenant,
                           "stream": False, "prompt_ids": row.prompt_ids,
                           "max_new": int(row.want),
                           # _temps/_ks are host lists (normalized by
                           # _norm_sampling_rows) — no device value here
                           "temperature": self._temps[i],
                           "top_k": self._ks[i],
                           "tokens": list(row.tokens),
                           "chunk_start": len(row.tokens), "text": "",
                           "seq": 0, "key": None, "key_splits": 0,
                           # mid-spec snapshots: tokens[-1] is the pending
                           # token (emitted but not yet in-cache) — resume
                           # re-ingests it before continuing
                           "spec": self._pending is not None})
            if not had_tokens and row.tokens and row.first_tok is None:
                # engine-side TTFT: row creation (its prefill started) →
                # its first token materialized on host
                row.first_tok = now
                metrics.observe("lm.ttft_ms",
                                (now - row.created) * 1000.0,
                                labels={"service": "lm"})
            if hit_eos or len(row.tokens) >= row.want:
                finished.append(self._finish(i))
        if self.remaining_steps() <= 0:
            finished += self._drain_all()
        return finished

    def _finish(self, i: int):
        row = self.rows[i]
        self.rows[i] = None
        self._release_row_pages(i)
        usage.note(row.tenant, tokens_out=len(row.tokens))
        engine_timeline.note_finish(
            tokens=len(row.tokens),
            ttft_ms=((row.first_tok - row.created) * 1000.0
                     if row.first_tok is not None else None),
            radix_hit=row.radix_hit if self._paged else None)
        with self.lm._lock:
            self.lm.stats["generate_calls"] += 1
            self.lm.stats["tokens_generated"] += len(row.tokens)
            self.lm.stats["decode_s"] += self.decode_s
            self.decode_s = 0.0
        return (row.tag, self.lm.tokenizer.decode(row.tokens))

    def _drain_all(self) -> list:
        return [self._finish(i) for i, r in enumerate(self.rows)
                if r is not None]
