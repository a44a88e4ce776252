"""TpuEngine — single owner of the device mesh; embed / rerank / generate.

Replaces the reference's EmbeddingGenerator (reference:
services/preprocessing_service/src/embedding_generator.rs:134-223) and its
serial batch-8, pad-to-max loop with:

- static shapes from length and batch buckets (engine/bucketing.py): an
  `embed` call's sentences are PACKED end to end into full rows of one
  length bucket (rows below the top bucket only come alone: |lengths| +
  |batches| − 1 executables), a `rerank` pair is a row padded to its
  bucket (the length × batch grid) — a bounded executable cache either
  way, no recompile storms;
- data-parallel batches over the mesh 'data' axis (params replicated,
  batch dim sharded) — the DP row of SURVEY.md §2's parallelism table;
- a single-owner design: services talk to the engine, never to the device,
  removing the reference's concurrent-forward contention hazard (§5.2).

The engine is synchronous at this layer; the async micro-batching facade for
the interactive query path lives in engine/batcher.py.

Concurrency contract ("single owner" made precise): single-owner means this
process — one TpuEngine instance owns the device; no other code touches it.
The engine's entry points (embed_texts / embed_and_search / rerank / warmup)
ARE safe to call from multiple threads concurrently: JAX dispatch is
thread-safe and the XLA runtime serializes device execution per stream, so
interleaved calls only interleave host-side dispatch, never device state.
Two internal locks keep the bookkeeping consistent under that concurrency:
_lock guards the executable cache, _stats_lock guards counters (asserted by
a concurrent stress test). Deliberately NOT serialized: a bulk embed_texts
must not block an interactive rerank/fused query behind its whole batch —
that's the two-queue-policies design of SURVEY.md §7 hard part 4. (LmEngine
is different: its decode loop carries KV-cache state across a long scan, so
it DOES hold its lock for the whole generate call.)
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional, Sequence

import numpy as np

from symbiont_tpu.config import EngineConfig
from symbiont_tpu.engine.bucketing import (
    choose_bucket,
    pack_rows,
    pad_batch_rows_ids,
    pad_ids_rows,
    pad_to_bucket,
    padding_stats,
    plan_batches,
    plan_packed,
    segments_per_row,
)
from symbiont_tpu.engine.tokenizer import Tokenizer, load_tokenizer
from symbiont_tpu.memory import device_corpus
from symbiont_tpu.models import bert as bert_mod
from symbiont_tpu.models import families
from symbiont_tpu.models.bert import BertConfig
from symbiont_tpu.obs.hbm import guard_oom, hbm_ledger
from symbiont_tpu.obs.xprof import compile_analysis_for, dispatch_ledger
from symbiont_tpu.utils.telemetry import carry_context, metrics, span

log = logging.getLogger(__name__)


def _start_host_copies(arrays) -> None:
    """Kick off device→host copies for every pending result before any is
    materialized, so the copies overlap instead of each np.asarray waiting
    for its own. No-op on backends without copy_to_host_async."""
    for a in arrays:
        try:
            a.copy_to_host_async()
        except AttributeError:
            return


class TpuEngine:
    # max batches fused into one d2h fetch (see _concat in __init__); bounds
    # the transient concat buffer to ~CONCAT_FETCH_MAX × max_batch rows and
    # the concat-executable variety to small operand tuples
    CONCAT_FETCH_MAX = 16

    def __init__(
        self,
        config: Optional[EngineConfig] = None,
        mesh=None,
        params=None,
        model_cfg=None,  # the family's config (BertConfig, MlaMoeConfig)
        tokenizer: Optional[Tokenizer] = None,
        pooling: str = "mean",
        normalize: bool = False,
        cross_params=None,
        cross_cfg: Optional[BertConfig] = None,
    ):
        import jax

        self.config = config or EngineConfig()
        self.mesh = mesh
        self.pooling = pooling
        self.normalize = normalize

        if params is None or model_cfg is None:
            if self.config.model_dir:
                # the one model-family seam: the checkpoint's own config.json
                # names its family (models/families.py)
                family = families.family_of_checkpoint(self.config.model_dir)
                params, model_cfg = family.load(self.config.model_dir)
                if self.config.quantize == "none":
                    # the mode's meaning is float32 at rest, whatever dtype
                    # the checkpoint holds (a no-op for the BERT loader)
                    params = jax.tree.map(
                        lambda a: np.asarray(a, np.float32), params)
                log.info("loaded %s checkpoint from %s", family.name,
                         self.config.model_dir)
            else:
                # synthetic mode: random weights at the configured dim — full
                # pipeline runs with zero model assets (dev / bench / tests).
                # Depth follows the BASELINE.md checkpoint that dim implies
                # (384→MiniLM-L6, 768→mpnet-base L12, 1024→e5-large L24) so
                # synthetic throughput/MFU numbers are honest for the real
                # model's FLOPs, not a shallower stand-in.
                d = self.config.embedding_dim
                layers = {384: 6, 768: 12, 1024: 24}.get(d, 6 if d <= 512 else 12)
                model_cfg = BertConfig(
                    vocab_size=30000, hidden_size=d,
                    num_layers=layers, num_heads=max(1, d // 64),
                    intermediate_size=4 * d, max_position_embeddings=512,
                    dtype=self.config.dtype)
                params = bert_mod.init_params(jax.random.key(0), model_cfg)
                log.warning("engine running with RANDOM weights (no model_dir)")
        if cross_params is None and (self.config.cross_model_dir
                                     or self.config.rerank_enabled):
            if self.config.cross_model_dir:
                from symbiont_tpu.models.convert import load_bert_model

                cross_params, cross_cfg = load_bert_model(
                    self.config.cross_model_dir, with_pooler=True)
                log.info("loaded cross-encoder from %s",
                         self.config.cross_model_dir)
            elif not isinstance(model_cfg, BertConfig):
                raise ValueError(
                    "rerank_enabled with a non-BERT embedder needs "
                    "cross_model_dir: the synthetic cross-encoder borrows "
                    "the embedder's geometry, and rerank is a BERT head")
            else:
                # synthetic cross-encoder: embedder geometry + pooler head —
                # the rerank path runs end-to-end with zero model assets
                cross_cfg = model_cfg
                cross_params = bert_mod.init_params(
                    jax.random.key(1), cross_cfg, with_pooler=True)
                log.warning(
                    "cross-encoder running with RANDOM weights (rerank_enabled "
                    "without cross_model_dir)")
        import dataclasses

        if model_cfg.dtype != self.config.dtype:
            model_cfg = dataclasses.replace(model_cfg, dtype=self.config.dtype)
        attn_impl = self.config.attn_impl
        if attn_impl not in ("auto", "flash", "xla"):
            raise ValueError(
                f"attn_impl must be auto|flash|xla, got {attn_impl!r}")
        # 'auto' resolves to XLA attention for EVERY encoder bucket: with the
        # bf16 softmax path in models/bert.py, XLA's fused attention now
        # beats the pallas flash kernel at all bucket lengths on v5e
        # (measured compute-only: +36% at S=256, +9% at S=512 — flash won
        # these buckets only back when the softmax round-tripped f32).
        # attn_impl='flash' remains an explicit opt-in for memory-bound
        # cases (no S² intermediates; fused backward for training).
        if attn_impl == "auto":
            attn_impl = "xla"
        if model_cfg.attn_impl != attn_impl:
            model_cfg = dataclasses.replace(model_cfg, attn_impl=attn_impl)
        if cross_cfg is not None and cross_cfg.dtype != self.config.dtype:
            cross_cfg = dataclasses.replace(cross_cfg, dtype=self.config.dtype)
        if cross_cfg is not None and cross_cfg.attn_impl != attn_impl:
            cross_cfg = dataclasses.replace(cross_cfg, attn_impl=attn_impl)
        if self.config.quantize != "none":
            # ONCE on host, before device placement: rank-≥2 params become
            # bf16 / per-channel int8 / fp8 at rest (models/quant.py), and
            # the dequant is fused into the jitted forward — XLA reads the
            # narrow representation out of HBM. Parity bars:
            # docs/QUANTIZATION.md, gated in tests/test_quantization.py.
            from symbiont_tpu.models import quant

            params = quant.quantize_params(params, self.config.quantize)
            if cross_params is not None:
                cross_params = quant.quantize_params(cross_params,
                                                     self.config.quantize)
            log.info("engine params quantized: %s", self.config.quantize)
        self.model_cfg = model_cfg
        # the `embed` program's rows are packed (block-diagonal attention by
        # sentence); the flash kernel takes a per-key bias and cannot
        # express that, so an explicit attn_impl='flash' holds for rerank
        # and the fused query and the embed program runs XLA attention
        self._embed_cfg = model_cfg
        if attn_impl == "flash":
            self._embed_cfg = dataclasses.replace(model_cfg, attn_impl="xla")
            log.warning("attn_impl='flash': the packed embed program runs "
                        "XLA attention (the kernel has no block-diagonal "
                        "mask); rerank and the fused query keep the kernel")
        self.family = families.family_of_config(model_cfg)
        self.tokenizer = tokenizer or load_tokenizer(self.config.model_dir,
                                                     model_cfg.vocab_size)
        self.cross_params = cross_params
        self.cross_cfg = cross_cfg

        self._lock = threading.Lock()  # guards the executable cache
        self._stats_lock = threading.Lock()  # guards the counters below
        self._exec_cache: OrderedDict = OrderedDict()
        # narrowest id dtype the vocab allows: uint16 halves h2d bytes for
        # every BERT-family vocab ≤ 65535 (MiniLM/bge/e5: 30522; NOT
        # multilingual-mpnet's XLM-R 250002); executables cast back to int32
        self._ids_dtype = (np.uint16 if model_cfg.vocab_size <= 65535
                           else np.int32)
        self._prep_pool = None  # lazy 1-thread pool for the ingest pipeline
        # fused result fetch: batch outputs concatenate on device and come
        # back in ONE d2h copy per group instead of one per batch (whether
        # this still pays on a locally attached chip is not measured).
        # Grouped at most CONCAT_FETCH_MAX operands per concat: arity (and
        # therefore the jit retrace variety AND the transient duplicate of
        # the group's outputs on device) stays bounded no matter the corpus
        # size.
        import jax as _jax
        import jax.numpy as _jnp

        self._concat = _jax.jit(lambda *xs: _jnp.concatenate(xs, axis=0))

        self._data_parallel = False
        if mesh is not None and self.config.data_parallel:
            if mesh.shape.get("data", 1) > 1:
                self._data_parallel = True
        if self._data_parallel:
            from symbiont_tpu.parallel.sharding import batch_sharding, replicate

            self.params = replicate(mesh, params)
            self._batch_sharding = batch_sharding(mesh)
            self._n_data = mesh.shape["data"]
            if cross_params is not None:
                self.cross_params = replicate(mesh, cross_params)
        else:
            self.params = jax.device_put(params)
            self._batch_sharding = None
            self._n_data = 1
            if cross_params is not None:
                self.cross_params = jax.device_put(cross_params)

        # stats (SURVEY.md §5.5: the reference has none). Mutate via _bump
        # only — bare `stats[k] += 1` is a read-modify-write that loses
        # increments under concurrent entry points. compile_s is first-call
        # wall time of each executable (lower + compile + one dispatch): an
        # approximation, but compiles are seconds and dispatches are not.
        self.stats = {"embed_calls": 0, "sentences_embedded": 0,
                      "rerank_calls": 0, "qsearch_calls": 0, "compiles": 0,
                      "compile_s": 0.0}
        self._register_gauges()
        # dtype-labeled at-rest parameter bytes (docs/OBSERVABILITY.md):
        # the quantization plane's byte budget, readable off /metrics
        from symbiont_tpu.models.quant import param_bytes

        storage = (self.config.quantize if self.config.quantize != "none"
                   else "f32")
        metrics.gauge_set("engine.param_bytes", param_bytes(self.params),
                          labels={"service": "engine", "dtype": storage})
        # hbm attribution plane (obs/hbm.py): the embed/cross params claim
        # their device bytes in the subsystem ledger — weakref-bound, so a
        # dead engine retires the claim like its gauges
        def _engine_param_bytes(eng):
            b = param_bytes(eng.params)
            if eng.cross_params is not None:
                b += param_bytes(eng.cross_params)
            return b

        hbm_ledger.claim("engine.params", self, _engine_param_bytes)

    def _register_gauges(self) -> None:
        """Engine-plane gauges (docs/OBSERVABILITY.md): compile count and
        seconds under a service label. Weakref-bound so the process-global
        registry never pins a dead engine (tests churn through dozens)."""
        def stat(key):
            def read(eng):
                with eng._stats_lock:
                    return eng.stats[key]
            return read

        labels = {"service": "engine"}
        metrics.register_weakref_gauge("engine.compiles", self,
                                       stat("compiles"), labels=labels)
        metrics.register_weakref_gauge("engine.compile_s", self,
                                       stat("compile_s"), labels=labels)
        metrics.register_weakref_gauge("engine.sentences_embedded", self,
                                       stat("sentences_embedded"),
                                       labels=labels)

    def _bump(self, **counts) -> None:
        with self._stats_lock:
            for k, v in counts.items():
                self.stats[k] += v

    # ------------------------------------------------------------------ jit

    def _attn_cfg(self, cfg, L: int):
        """attn_impl='auto' → XLA at every bucket (see __init__: with bf16
        softmax, XLA wins all measured encoder lengths on v5e). The per-
        bucket hook stays so a future chip/length where the kernel wins can
        re-split the policy without touching call sites."""
        del L
        return cfg

    def _get_executable(self, kind: str, L: int, *shape) -> Callable:
        """The compiled program of one (kind, length, *shape): `shape` is
        the batch bucket B of an `embed` or `rerank` program and (capacity,
        k, mesh) of a `qsearch` program — the corpus's rows, the static k
        and the mesh those rows are sharded over (None: one device), as the
        caller read them off the corpus it was handed. Every
        jitted function keeps the Python name `fn` (the benchmark's
        rooflines find the XLA module `jit_fn`, and the decorator below
        keeps it); a program and its phases are named by `jax.named_scope`
        instead, which lands in each device op's metadata (`tf_op` in a
        profiler trace) and changes nothing that is compiled."""
        import jax

        key = (kind, L, *shape)
        with self._lock:
            if key in self._exec_cache:
                self._exec_cache.move_to_end(key)
                return self._exec_cache[key]
        # what a dispatch is booked under (obs/xprof.py): kind[L=,B=]
        sig_b = shape[0]

        if kind == "embed":
            import jax.numpy as jnp

            cfg, pooling, normalize = (self._attn_cfg(self._embed_cfg, L),
                                       self.pooling, self.normalize)
            d2h_bf16 = self.config.dtype == "bfloat16"
            embed = self.family.embed

            @jax.named_scope("symbiont.embed")
            def fn(params, ids, seg_lengths):
                # packed rows (engine/bucketing.py): ids [B, L] hold each
                # row's sentences end to end, seg_lengths [B, S] their token
                # counts; positions, the block-diagonal mask and per-sentence
                # pooling are rebuilt on device from the lengths (a quarter
                # of an explicit mask's h2d bytes). ids may arrive uint16
                # (see _ids_dtype); bf16 engines also ship the [B, S, H]
                # rows back as bf16 (half the d2h bytes), cast to f32 on host
                ids = ids.astype(jnp.int32)
                segments = bert_mod.Segments.of_lengths(seg_lengths,
                                                        ids.shape[1])
                # aux: None, or what the family's forward counted on the
                # device (`Family.note_aux` books it), fetched with the rows
                emb, aux = embed(params, ids, segments.real, cfg, pooling,
                                 normalize, segments)
                return (emb.astype(jnp.bfloat16) if d2h_bf16 else emb), aux
        elif kind == "qsearch":
            # fused interactive query: encoder forward + pool + normalize +
            # cosine scores against the device-resident corpus + top-k, ONE
            # compiled program — the whole search hop is a single device
            # dispatch (the split embed→search path pays ≥2). The scan, the
            # top-k and what they do with a row-sharded corpus are
            # memory/device_corpus.scan_topk's.
            import jax.numpy as jnp

            cfg, pooling = self._attn_cfg(self.model_cfg, L), self.pooling
            embed = self.family.embed
            cap, k, mesh = shape
            sig_b = (cap, k)

            @jax.named_scope("symbiont.qsearch")
            def fn(params, ids, mask, corpus, n_valid):
                ids = ids.astype(jnp.int32)
                emb, _ = embed(params, ids, mask, cfg, pooling, True)
                return device_corpus.scan_topk(corpus, emb[0], n_valid, k,
                                               mesh)
        elif kind == "rerank":
            import jax.numpy as jnp

            ccfg = self._attn_cfg(self.cross_cfg, L)

            @jax.named_scope("symbiont.rerank")
            def fn(params, ids, lengths, len_a):
                # mask and token-type ids rebuilt on device from two [B]
                # length vectors (vs two [B, L] matrices over the wire)
                ids = ids.astype(jnp.int32)
                pos = jnp.arange(ids.shape[1])
                mask = (pos < lengths[:, None]).astype(jnp.int32)
                types = ((pos >= len_a[:, None]) & (pos < lengths[:, None])
                         ).astype(jnp.int32)
                return bert_mod.cross_encoder_score(params, ids, mask, ccfg,
                                                    types)
        else:
            raise ValueError(kind)

        jitted = self._time_first_call(jax.jit(fn),
                                       f"{kind}[L={L},B={sig_b}]")
        with self._lock:
            # two threads can race the cold-miss check above; the loser
            # discards its wrapper and reuses the winner's, so one shape
            # never compiles (or counts) twice
            if key in self._exec_cache:
                self._exec_cache.move_to_end(key)
                return self._exec_cache[key]
            self._exec_cache[key] = jitted
            while len(self._exec_cache) > self.config.executable_cache_size:
                self._exec_cache.popitem(last=False)
        self._bump(compiles=1)
        return jitted

    def _time_first_call(self, jitted: Callable, sig: str) -> Callable:
        """Wrap one cache key's jitted fn: the first call lowers + compiles
        it AOT (obs/xprof.compile_analysis_for) and every call dispatches
        through that ONE ``Compiled`` object.

        - The first call's wall time is accounted as compile seconds (XLA
          compiles synchronously inside it) and lands on the flight-
          recorder timeline (trace id "engine-compiles", obs/device.py): a
          recompile storm is a row of spans in the Perfetto export, not
          just a counter that rose. The XLA cost model AND the static
          memory footprint (temp/argument/output bytes) come off that one
          real compile.
        - Two threads can race a cold executable (see the cache-miss note
          in _get_executable): the loser WAITS on the wrapper's lock for
          the winner's compile instead of compiling the same program a
          second time.
        - There is no way back to ``jit``: every call per cache key shares
          exact shapes, dtypes and shardings, so the Compiled is always
          call-valid, and a failed lower/compile raises where it happened —
          it is never retried under jit and swallowed (a failed first call
          leaves the key cold; the next call compiles again and raises
          again).
        - EVERY call reports its host wall to the per-executable dispatch
          ledger (obs/xprof.py) and runs under the OOM guard: a
          RESOURCE_EXHAUSTED escaping XLA is recorded to the hbm forensics
          plane (postmortem + engine.oom_total{site}) and re-raised."""
        compiled = None  # the AOT Compiled, set once under compile_lock
        compile_lock = threading.Lock()

        def first_call(*args):
            nonlocal compiled
            # the one real XLA compile happens INSIDE compile_analysis_for
            # (lowered.compile()), so compile_s timing starts before it
            t0 = time.perf_counter()
            start_s = time.time()
            cost, mem, exe = compile_analysis_for(jitted, args)
            with guard_oom(f"engine.{sig}"):
                out = exe(*args)
            compiled = exe
            dt = time.perf_counter() - t0
            self._bump(compile_s=dt)
            dispatch_ledger.note_compile(sig, cost, memory=mem)
            dispatch_ledger.note_dispatch(sig, dt)
            from symbiont_tpu.obs.device import record_compile_event

            record_compile_event(
                "engine.compile", dt, start_s=start_s, signature=sig)
            return out

        def wrapper(*args):
            if compiled is None:
                with compile_lock:
                    if compiled is None:
                        return first_call(*args)
            t0 = time.perf_counter()
            with guard_oom(f"engine.{sig}"):
                out = compiled(*args)
            dispatch_ledger.note_dispatch(sig, time.perf_counter() - t0)
            return out

        return wrapper

    def _note_padding(self, true_lengths, bucket: int, batch_rows: int,
                      n_real: int) -> None:
        """Real and padding token counters + the batch fill-ratio gauge for
        one dispatched batch (engine/bucketing.py quantified live):
        `true_lengths` of every sequence in it (a packed row holds several),
        `n_real` of its `batch_rows` rows holding any."""
        real, total = padding_stats(true_lengths, bucket, batch_rows)
        # decode-plane flight recorder, embed side (obs/engine_timeline.py):
        # the per-flush bucket-occupancy/padding timeline behind the
        # packing-opportunity estimate — host ints already in hand
        from symbiont_tpu.obs.engine_timeline import engine_timeline

        engine_timeline.note_embed_flush(bucket, batch_rows, n_real,
                                         real_tokens=real,
                                         total_tokens=total)
        labels = {"service": "engine"}
        metrics.inc("engine.tokens_real", real, labels=labels)
        metrics.inc("engine.tokens_padding", total - real, labels=labels)
        metrics.gauge_set("engine.batch_fill_ratio",
                          round(n_real / batch_rows, 4) if batch_rows else 0.0,
                          labels=labels)
        if self._n_data > 1 and batch_rows:
            # DP accounting (docs/SCALING.md): rows shard contiguously over
            # the 'data' axis, real rows first, so the trailing replicas
            # carry the padding. Per-replica padding waste names WHICH
            # replicas burn cycles on pad rows, and the balance gauge
            # (min real rows ÷ max real rows) reads 1.0 when every replica
            # does equal useful work.
            per = batch_rows // self._n_data
            real_rows = [min(max(n_real - r * per, 0), per)
                         for r in range(self._n_data)]
            for r, rr in enumerate(real_rows):
                metrics.gauge_set(
                    "batcher.padding_waste",
                    round(1.0 - rr / per, 4) if per else 0.0,
                    labels={"service": "engine", "replica": str(r)})
            mx = max(real_rows)
            metrics.gauge_set("engine.dp_shard_balance",
                              round(min(real_rows) / mx, 4) if mx else 0.0,
                              labels=labels)
            metrics.gauge_set("engine.dp_replicas", self._n_data,
                              labels=labels)

    def _device_batch(self, *arrays: np.ndarray):
        """Move batch-dim-0 arrays to the device (sharded over 'data' when
        data-parallel)."""
        import jax.numpy as jnp

        if self._batch_sharding is not None:
            import jax

            return tuple(jax.device_put(jnp.asarray(a), self._batch_sharding)
                         for a in arrays)
        return tuple(jnp.asarray(a) for a in arrays)

    @property
    def _plan_cap(self) -> int:
        """Rows per planned batch: max_batch clamped to the LARGEST batch
        bucket. A plan chunk bigger than every bucket has no executable
        shape to run in — found by the engine-restart chaos test, where a
        redelivery surge flushed max_batch-sized work through buckets
        smaller than it. Clamping (rather than rounding shapes up) keeps
        the executable set inside the buckets — warmup coverage and the
        recompile-storm bound stay intact; a surge simply splits into
        top-bucket batches."""
        return min(self.config.max_batch, self.config.batch_buckets[-1])

    def _batch_bucket(self, n: int) -> int:
        b = choose_bucket(n, self.config.batch_buckets)
        if self._n_data > 1:
            # batch must divide over the data axis
            b = max(b, self._n_data)
            b = ((b + self._n_data - 1) // self._n_data) * self._n_data
        return b

    @staticmethod
    def _note_stages(name: str, t0: float, t_dispatched: float) -> None:
        """The two stages of one engine call, stamped at a call and a fetch
        that exist: `<name>.host_ms` (entry -> the last dispatch returned:
        tokenize, pad, h2d, dispatch) and `<name>.device_wait_ms` (from
        there -> every result on the host)."""
        metrics.observe(f"{name}.host_ms", (t_dispatched - t0) * 1e3)
        metrics.observe(f"{name}.device_wait_ms",
                        (time.perf_counter() - t_dispatched) * 1e3)

    # ---------------------------------------------------------------- embed

    def _prep_executor(self):
        """The 1-thread pool that tokenizes the NEXT ingest chunk while the
        main thread pads/dispatches the current one."""
        with self._lock:
            if self._prep_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._prep_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="engine-prep")
        return self._prep_pool

    def _dispatch_embed(self, encoded, offset: int, buckets, pending) -> None:
        """Plan + pack + dispatch one tokenized chunk; device calls are async,
        so this returns as soon as the last batch is enqueued. `offset` maps
        chunk-local indices back to the caller's rows. A pending entry says
        where each sentence's row lies in the dispatch's [B, S, H] result."""
        with span("engine.embed.pack", cpu=True):
            lengths = [len(e) for e in encoded]
            L, dispatches = plan_packed(lengths, buckets, self._plan_cap)
        labels = {"service": "engine"}
        for rows in dispatches:
            with span("engine.embed.pack", cpu=True):
                bb = self._batch_bucket(len(rows))
                ids, seg = pack_rows(encoded, rows, L, bb,
                                     self.tokenizer.pad_id,
                                     dtype=self._ids_dtype)
                sent = [i for row in rows for i in row]
                self._note_padding([lengths[i] for i in sent], L, bb,
                                   len(rows))
                # (row, slot) of each sentence in the [B, S, H] result
                at = ([r for r, row in enumerate(rows) for _ in row],
                      [s for row in rows for s in range(len(row))])
                sent = [offset + i for i in sent]
            metrics.inc("engine.embed.dispatches", labels=labels)
            metrics.observe("engine.pack.segments_per_row",
                            len(sent) / len(rows), labels=labels)
            with span("engine.embed.dispatch", cpu=True):
                fn = self._get_executable("embed", L, bb)
                ids_d, seg_d = self._device_batch(ids, seg)
                res = fn(self.params, ids_d, seg_d)
            pending.append((sent, at, *res))

    def embed_texts(self, texts: Sequence[str]) -> np.ndarray:
        """Texts → [n, hidden] float32 embeddings. Parity surface of the
        reference's generate_sentence_embeddings (embedding_generator.rs:134).

        Pipelined in three overlapping stages: a prep thread tokenizes chunk
        N+1 while this thread packs/dispatches chunk N (host_prep_chunk texts
        per chunk); jax dispatch is async, so device compute and h↔d
        transfers of successive batches overlap too; all results then
        materialize at once (serializing np.asarray per batch would pay a
        full device round-trip per batch)."""
        if len(texts) == 0:
            return np.zeros((0, self.model_cfg.hidden_size), np.float32)
        with span("engine.embed", rows=len(texts)):
            t0 = time.perf_counter()
            max_len = min(self.config.length_buckets[-1],
                          self.model_cfg.max_position_embeddings)
            buckets = [b for b in self.config.length_buckets
                       if b <= self.model_cfg.max_position_embeddings]
            out = np.zeros((len(texts), self.model_cfg.hidden_size),
                           np.float32)
            chunk = self.config.host_prep_chunk
            pending = []

            def tokenize(part):
                with span("engine.embed.tokenize", cpu=True, rows=len(part)):
                    return self.tokenizer.encode_batch(part, max_len)

            if 0 < chunk < len(texts):
                texts = list(texts)
                pool = self._prep_executor()
                # on the prep thread the span still parents to this call's
                prep = carry_context(tokenize)
                fut = pool.submit(prep, texts[:chunk])
                for start in range(0, len(texts), chunk):
                    encoded = fut.result()
                    nxt = start + chunk
                    if nxt < len(texts):
                        # prefetch BEFORE dispatching this chunk: tokenize of
                        # chunk N+1 runs while the device chews on chunk N
                        fut = pool.submit(prep, texts[nxt:nxt + chunk])
                    # a bulk call's chunks all pack into top-bucket rows
                    # (only its last could fit a shorter one): one [., S, H]
                    # result shape for the grouped fetch below
                    self._dispatch_embed(encoded, start, buckets[-1:],
                                         pending)
            else:
                self._dispatch_embed(tokenize(list(texts)), 0, buckets,
                                     pending)
            # every batch is dispatched: what is left is waiting for the
            # device and fetching (no stamp adds a sync of its own)
            t_dispatched = time.perf_counter()
            if len(pending) > 1 and self._batch_sharding is None:
                # grouped single-copy fetch (see _concat in __init__); the
                # DP-sharded path keeps per-batch fetches — its outputs live
                # sharded across the mesh and gather independently. All
                # group concats dispatch before any materializes, so the
                # d2h copies still overlap.
                fetches = []
                for i in range(0, len(pending), self.CONCAT_FETCH_MAX):
                    grp = pending[i:i + self.CONCAT_FETCH_MAX]
                    res = (grp[0][2] if len(grp) == 1
                           else self._concat(*[b[2] for b in grp]))
                    fetches.append((grp, res))
                _start_host_copies(res for _, res in fetches)
                for grp, res in fetches:
                    allv = np.asarray(res)
                    off = 0
                    for sent, at, res_dev, aux in grp:
                        out[sent] = allv[off:off + res_dev.shape[0]][at]
                        off += res_dev.shape[0]
                        if aux is not None:  # computed with the rows above
                            self.family.note_aux(np.asarray(aux))
                dispatch_ledger.note_host_sync("TpuEngine.embed_texts",
                                               len(fetches))
            else:
                _start_host_copies(b[2] for b in pending)
                for sent, at, res_dev, aux in pending:
                    out[sent] = np.asarray(res_dev)[at]
                    if aux is not None:
                        self.family.note_aux(np.asarray(aux))
                dispatch_ledger.note_host_sync("TpuEngine.embed_texts",
                                               len(pending))
            self._note_stages("engine.embed", t0, t_dispatched)
        self._bump(embed_calls=1, sentences_embedded=len(texts))
        return out

    def embed_query(self, text: str) -> np.ndarray:
        """Single query embedding (the tasks.embedding.for_query path)."""
        return self.embed_texts([text])[0]

    def embed_and_search(self, text: str, corpus_dev, n_valid: int,
                         top_k: int):
        """Fused interactive query (the latency half of SURVEY.md §7 hard
        part 4): tokenize on host, then ONE device program does the BERT
        forward, pooling, normalization, cosine scores against the
        device-resident corpus, and top-k. Returns (scores[k], idx[k]) as
        numpy. corpus_dev is the store's device copy ([cap, D] unit rows, as
        memory/device_corpus.place left it: its capacity and whether its
        rows are sharded over a mesh are read off the array)."""
        import jax.numpy as jnp

        with span("engine.qsearch", top_k=top_k):
            t0 = time.perf_counter()
            max_len = min(self.config.length_buckets[-1],
                          self.model_cfg.max_position_embeddings)
            with span("engine.qsearch.tokenize", cpu=True):
                encoded = self.tokenizer.encode(text, max_len)
                buckets = [b for b in self.config.length_buckets
                           if b <= self.model_cfg.max_position_embeddings]
                bucket = choose_bucket(len(encoded), buckets)
                ids, mask = pad_to_bucket([encoded], bucket,
                                          self.tokenizer.pad_id,
                                          dtype=self._ids_dtype)
            with span("engine.qsearch.dispatch", cpu=True):
                fn = self._get_executable(
                    "qsearch", bucket, corpus_dev.shape[0], top_k,
                    device_corpus.mesh_of(corpus_dev))
                scores, idx = fn(self.params, jnp.asarray(ids),
                                 jnp.asarray(mask), corpus_dev, n_valid)
            t_dispatched = time.perf_counter()
            _start_host_copies((scores, idx))  # both d2h copies in flight
            self._bump(qsearch_calls=1)
            out = np.asarray(scores), np.asarray(idx)
            self._note_stages("engine.qsearch", t0, t_dispatched)
            return out

    # --------------------------------------------------------------- rerank

    def rerank(self, query: str, passages: Sequence[str]) -> np.ndarray:
        """Cross-encoder scores for (query, passage) pairs — BASELINE.md #4."""
        if self.cross_params is None or self.cross_cfg is None:
            raise RuntimeError("no cross-encoder model loaded")
        if len(passages) == 0:
            return np.zeros((0,), np.float32)
        max_len = min(self.config.length_buckets[-1],
                      self.cross_cfg.max_position_embeddings)
        pairs = [self.tokenizer.encode_pair(query, p, max_len) for p in passages]
        lengths = [len(ids) for ids, _ in pairs]
        # segment-A width per pair (types are a contiguous 0-run then 1-run);
        # the executable rebuilds mask AND token-type ids from two [B]
        # vectors instead of shipping two [B, L] matrices
        a_widths = [sum(1 for t in types if t == 0) for _, types in pairs]
        buckets = [b for b in self.config.length_buckets
                   if b <= self.cross_cfg.max_position_embeddings]
        out = np.zeros((len(passages),), np.float32)

        pending = []
        # not "engine.rerank": EngineService's handler span of the rerank op
        # has that name, and two things in one histogram are neither
        with span("engine.rerank.forward", rows=len(passages)):
            for bucket, indices in plan_batches(lengths, buckets,
                                                self._plan_cap):
                ids, lens = pad_ids_rows([pairs[i][0] for i in indices],
                                         bucket, self.tokenizer.pad_id,
                                         dtype=self._ids_dtype)
                len_a = np.asarray([min(a_widths[i], bucket) for i in indices],
                                   np.int32)
                bb = self._batch_bucket(len(indices))
                ids, lens, n_real = pad_batch_rows_ids(ids, lens, bb)
                self._note_padding([lengths[i] for i in indices], bucket, bb,
                                   n_real)
                if len_a.shape[0] < bb:
                    len_a = np.concatenate(
                        [len_a, np.zeros(bb - n_real, np.int32)])
                fn = self._get_executable("rerank", bucket, bb)
                ids_d, lens_d, len_a_d = self._device_batch(ids, lens, len_a)
                pending.append((indices, n_real,
                                fn(self.cross_params, ids_d, lens_d, len_a_d)))
            _start_host_copies(batch for _, _, batch in pending)
            for indices, n_real, res_dev in pending:
                out[indices] = np.asarray(res_dev)[:n_real]
            dispatch_ledger.note_host_sync("TpuEngine.rerank", len(pending))
        self._bump(rerank_calls=1)
        return out

    # ---------------------------------------------------------------- warm

    def _warm_dispatch(self, kind: str, L: int, bb: int):
        """Compile (kind, L, bb) by dispatching one dummy batch through it;
        returns the device result for the caller to materialize. ids ride in
        the runtime wire dtype: a warm-up at int32 would compile a signature
        the uint16 runtime path never hits."""
        fn = self._get_executable(kind, L, bb)
        if kind == "embed":
            seg = np.zeros((bb, segments_per_row(L)), np.int32)
            seg[:, 0] = L  # one sentence fills each row
            return fn(self.params, *self._device_batch(
                np.ones((bb, L), self._ids_dtype), seg))[0]
        ids_d, lens_d, len_a_d = self._device_batch(
            np.ones((bb, L), self._ids_dtype), np.full((bb,), L, np.int32),
            np.full((bb,), L // 2, np.int32))
        return fn(self.cross_params, ids_d, lens_d, len_a_d)

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               batches: Optional[Sequence[int]] = None) -> None:
        """Pre-compile the hot (bucket, batch) executables so first queries
        don't pay a cold XLA compile: of the pairs asked for, the `embed`
        programs the packer can form (a row below the top length bucket
        only ever comes alone, engine/bucketing.py `plan_packed`), and every
        pair's rerank executable when a cross-encoder is loaded."""
        top = max(b for b in self.config.length_buckets
                  if b <= self.model_cfg.max_position_embeddings)
        for L in buckets or self.config.length_buckets[:2]:
            for B in batches or self.config.batch_buckets[:2]:
                bb = self._batch_bucket(B)
                if L >= top or bb == self._batch_bucket(1):
                    np.asarray(self._warm_dispatch("embed", L, bb))
                    dispatch_ledger.note_host_sync("TpuEngine.warmup")
                if self.cross_params is not None:
                    np.asarray(self._warm_dispatch("rerank", L, bb))
                    dispatch_ledger.note_host_sync("TpuEngine.warmup")

    def warm_rerank(self, max_rows: int = 8) -> None:
        """Boot warm-up of the cross-encoder hop (EngineService runs it in
        the background after the fused-search warm-up). The rerank hop has
        the tightest caller timeout (request_timeout_rerank_s, 10 s) and one
        request's (query, passage) pairs spread over several length buckets,
        each its own executable — on the v5e one cold compile alone is ~10 s,
        so an unwarmed first rerank after boot answered 503 (seen on the
        chip). Covers every length bucket x the batch buckets a request of
        up to `max_rows` hits can land in; larger requests compile on first
        use."""
        if self.cross_params is None:
            return
        buckets = [b for b in self.config.length_buckets
                   if b <= self.cross_cfg.max_position_embeddings]
        for bb in sorted({self._batch_bucket(n) for n in (1, max_rows)}):
            for L in buckets:
                np.asarray(self._warm_dispatch("rerank", L, bb))
                dispatch_ledger.note_host_sync("TpuEngine.warm_rerank")
