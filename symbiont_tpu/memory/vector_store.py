"""TPU-native vector store: brute-force exact cosine top-k on the MXU.

Design rationale: at the corpus scales the reference system handles (sentences
of scraped documents), exact search as one [N, D] x [D] matmul on a TPU chip
beats an ANN index round-trip — no gRPC hop, no graph traversal, exact
results, and the matmul rides the MXU at bf16. Rows shard over the mesh 'data'
axis for corpora beyond one chip's HBM (capacity blocks keep shapes static).

API parity with the reference's Qdrant adapter:
- ensure_collection (dim + cosine at startup):
  reference vector_memory_service/src/main.rs:24-119
- upsert(points with uuid ids + QdrantPointPayload-shaped payloads), ack after
  durable: main.rs:121-228 (wait=true at :196)
- search(query, top_k) → hits with id, score, payload: main.rs:230-456

Host rows: the corpus is held on the host as a list of row blocks of
`shard_capacity` rows each ([shard_capacity, dim] f32, unit rows; the unit the
device copy's capacity is rounded to). A block is allocated when the one before
it fills and is written in place from then on: an append of n rows moves
n x dim x 4 bytes, whether or not it crosses a block edge, and never copies a
row already stored (`vector_store.host_bytes_moved` counts such copies and
reads 0; `vector_store.host_blocks` is the number of blocks). Row r lives at
`divmod(r, shard_capacity)`. The device copy is assembled from the blocks, one
copy into the padded upload (_sync_device).

Durability: append-only JSONL WAL + optional compacted .npy snapshot (one
[n, dim] f32 array, written block by block); load() reads the snapshot into
the blocks and replays the WAL tail (SURVEY.md §5.4: DB-as-truth stance kept,
now inside the framework).
"""

from __future__ import annotations

import json
import logging
import os
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from symbiont_tpu.config import VectorStoreConfig
from symbiont_tpu.memory import device_corpus
from symbiont_tpu.utils.telemetry import metrics, span

log = logging.getLogger(__name__)


@dataclass
class SearchHit:
    id: str
    score: float
    payload: dict


class VectorStore:
    supports_fused = True  # corpus is device-resident → fused embed+top-k

    def __init__(self, config: Optional[VectorStoreConfig] = None, mesh=None):
        self.config = config or VectorStoreConfig()
        self.mesh = mesh
        self.dim = self.config.dim
        self._lock = threading.RLock()
        self._ids: List[str] = []
        self._id_to_row: Dict[str, int] = {}
        self._payloads: List[dict] = []
        # L2-normalized rows, [shard_capacity, dim] f32 each, filled in order
        self._blocks: List[np.ndarray] = []
        self._device_corpus = None  # padded [capacity_blocks, D] on device
        self._device_rows = 0  # rows valid in the device copy
        self._dirty = True
        self._search_fns: dict = {}
        self._warmed_capacity = None  # capacity warm_fused last compiled for
        self._wal_file = None
        self.last_load_skipped_lines = 0  # corrupt WAL lines on last load()
        # hbm attribution plane (obs/hbm.py): the device-resident corpus
        # claims its padded bytes — .nbytes is host metadata, no sync
        from symbiont_tpu.obs.hbm import hbm_ledger

        hbm_ledger.claim(
            "memory.corpus", self,
            lambda vs: (0 if vs._device_corpus is None
                        else int(vs._device_corpus.nbytes)))
        if self.config.data_dir:
            Path(self.config.data_dir).mkdir(parents=True, exist_ok=True)
            self.load()

    # ------------------------------------------------------------ lifecycle

    def ensure_collection(self, dim: Optional[int] = None) -> None:
        """Validate/establish the collection config (reference: main.rs:24-119).

        Like Qdrant's ensure path this is idempotent; a dim mismatch with
        existing data is an error rather than silent re-create."""
        dim = dim or self.config.dim
        with self._lock:
            if len(self._ids) and dim != self.dim:
                raise ValueError(
                    f"collection '{self.config.collection}' already has dim "
                    f"{self.dim}, requested {dim}")
            if dim != self.dim:  # no rows yet: blocks of the old width go
                self._blocks = []
            self.dim = dim

    def count(self) -> int:
        with self._lock:
            return len(self._ids)

    # ----------------------------------------------------------- host rows

    def _stored(self):
        """The stored rows in order, one view per block (the last block's
        may be short)."""
        left = len(self._ids)
        for block in self._blocks:
            yield block[:min(left, len(block))]
            left -= len(block)

    def _gather(self, out: np.ndarray) -> np.ndarray:
        """Copy the stored rows into the head of `out`: the one host copy an
        upload, or a tool's whole-matrix read, makes."""
        at = 0
        for rows in self._stored():
            out[at:at + len(rows)] = rows
            at += len(rows)
        return out

    @property
    def _vectors(self) -> np.ndarray:
        """The stored rows as one [n, dim] f32 array, assembled on every
        read: for tests and tools, never on a served path."""
        return self._gather(np.empty((len(self._ids), self.dim), np.float32))

    def _append(self, vecs: np.ndarray) -> None:
        """Write `vecs` after the last stored row, in place: into the tail
        block's free rows, then into new blocks as each fills (one call may
        cross several edges). Only the new rows' bytes move."""
        cap = self.config.shard_capacity
        at, done = len(self._ids), 0
        while done < len(vecs):
            b, off = divmod(at + done, cap)
            if b == len(self._blocks):
                self._blocks.append(np.empty((cap, self.dim), np.float32))
            take = min(cap - off, len(vecs) - done)
            self._blocks[b][off:off + take] = vecs[done:done + take]
            done += take

    # -------------------------------------------------------------- upsert

    def upsert(self, points: Sequence[Tuple[str, Sequence[float], dict]]) -> int:
        """Insert or overwrite points; ack only after the WAL write+flush
        (the reference's wait=true durability, main.rs:196). Returns count.

        New ids are written in place after the last stored row and an
        existing id over its own row (module docstring, "Host rows"): the
        call costs its own rows — normalise, n x dim x 4 bytes into the
        blocks, the WAL lines and their fsync — whatever the corpus holds.

        Normalization is one vectorized pass over the whole batch — the
        per-point numpy calls (asarray + norm per row) were ~1 s of CPU per
        3k-point ingest wave on the one-core host (measured r5)."""
        if not points:
            return 0
        with self._lock:
            try:
                batch = np.asarray([vec for _, vec, _ in points], np.float32)
            except (ValueError, TypeError):
                batch = None  # ragged input: report the offending row below
            if batch is None or batch.ndim != 2 or batch.shape[1] != self.dim:
                for _, vec, _ in points:
                    v = np.asarray(vec, np.float32)
                    if v.shape != (self.dim,):
                        raise ValueError(
                            f"vector dim {v.shape} != collection dim {self.dim}")
                raise ValueError(f"vectors must be [n, {self.dim}]")
            return self._ingest_locked([p[0] for p in points], batch,
                                       [p[2] for p in points])

    def upsert_rows(self, ids: Sequence[str], rows,
                    payloads: Optional[Sequence[dict]] = None) -> int:
        """Tensor-frame fast path: ingest an already-packed [n, dim] float
        block (typically a read-only `np.frombuffer` view straight off the
        bus — schema/frames) without ever materializing per-float Python
        objects. Same semantics, WAL durability and cost as upsert(): the
        rows go into the host blocks in place, nothing already stored moves.

        Non-f32 rows (the half-width f16 wire form, or bf16 engine output)
        are upcast to f32 here — the store's in-memory matrix, WAL, and
        search math stay f32 regardless of what dtype rode the bus."""
        ids = list(ids)
        if not ids:
            return 0
        rows = np.asarray(rows, np.float32)  # upcasts f16/f64 views in C
        if rows.ndim != 2 or rows.shape[0] != len(ids):
            raise ValueError(
                f"rows shape {rows.shape} does not match {len(ids)} ids")
        if rows.shape[1] != self.dim:
            raise ValueError(
                f"vector dim ({rows.shape[1]},) != collection dim {self.dim}")
        payloads = ([{}] * len(ids) if payloads is None else list(payloads))
        if len(payloads) != len(ids):
            # zip would silently truncate and drop points
            raise ValueError(f"{len(payloads)} payloads for {len(ids)} ids")
        with self._lock:
            return self._ingest_locked(ids, rows, payloads)

    def _ingest_locked(self, ids: List[str], batch: np.ndarray,
                       payloads: List[dict]) -> int:
        """Shared ingest tail (caller holds the lock, batch is validated
        [n, dim] f32 — possibly a read-only view; the WAL records the RAW
        vectors, normalization happens on the in-memory copy only)."""
        with span("store.ingest_rows", cpu=True, rows=len(ids)):
            norms = np.linalg.norm(batch, axis=1, keepdims=True)
            normed = np.divide(batch, norms, out=batch.astype(np.float32,
                                                              copy=True),
                               where=norms > 0)
            cap = self.config.shard_capacity
            n_before, held = len(self._ids), list(self._blocks)
            rows = []
            new_pos: Dict[str, int] = {}  # ids first seen in THIS call — a
            # duplicate id within one batch (e.g. WAL replay of an update)
            # must overwrite, not append twice
            for j, (pid, payload) in enumerate(zip(ids, payloads)):
                if pid in self._id_to_row:
                    r = self._id_to_row[pid]
                    b, off = divmod(r, cap)
                    self._blocks[b][off] = normed[j]
                    self._payloads[r] = dict(payload)
                    self._dirty = True
                elif pid in new_pos:
                    rows[new_pos[pid]] = (pid, j, dict(payload))
                else:
                    new_pos[pid] = len(rows)
                    rows.append((pid, j, dict(payload)))
            if rows:
                self._append(normed[[j for _, j, _ in rows]])
                for i, (pid, _, payload) in enumerate(rows):
                    self._ids.append(pid)
                    self._id_to_row[pid] = n_before + i
                    self._payloads.append(payload)
                self._dirty = True
            # read off the blocks, not assumed: one that is another array
            # after the call has had the rows it held copied
            metrics.inc("vector_store.host_bytes_moved", self.dim * 4 * sum(
                min(cap, n_before - i * cap) for i, block in enumerate(held)
                if self._blocks[i] is not block))
            metrics.gauge_set("vector_store.host_blocks", len(self._blocks))
        self._wal_append(zip(ids, batch, payloads))
        return len(ids)

    # -------------------------------------------------------------- search

    def _sync_device(self) -> None:
        n = len(self._ids)
        if self._device_corpus is not None and not self._dirty and self._device_rows == n:
            return
        cap = device_corpus.capacity(n, self.config.shard_capacity, self.mesh)
        self._device_corpus = device_corpus.place(
            self._gather(np.zeros((cap, self.dim), np.float32)), self.mesh)
        self._device_rows = n
        self._dirty = False

    def _get_search_fn(self, cap: int, k: int):
        import jax

        key = (cap, k)
        if key not in self._search_fns:
            mesh = (self.mesh if device_corpus.is_sharded(self.mesh, cap)
                    else None)

            def fn(corpus, query, n_valid):
                return device_corpus.scan_topk(corpus, query, n_valid, k, mesh)

            self._search_fns[key] = jax.jit(fn)
        return self._search_fns[key]

    def _hits_from(self, scores, idx, top_k: int) -> List[SearchHit]:
        hits = []
        for s, i in zip(np.asarray(scores)[:top_k], np.asarray(idx)[:top_k]):
            if not np.isfinite(s):
                continue
            hits.append(SearchHit(id=self._ids[i], score=float(s),
                                  payload=dict(self._payloads[i])))
        return hits

    def search(self, query: Sequence[float], top_k: int) -> List[SearchHit]:
        """Exact cosine top-k (reference search handler: main.rs:230-456).

        The device call (and any first-shape XLA compile, 20-40s on TPU) runs
        OUTSIDE the store lock: rows only ever append (upsert overwrites in
        place), so a snapshot of (corpus, n) taken under the lock stays valid,
        and concurrent ingest/search callers never stall behind a compile."""
        import jax.numpy as jnp

        with self._lock:
            n = len(self._ids)
            if n == 0 or top_k <= 0:
                return []
            self._sync_device()
            corpus = self._device_corpus
            cap = corpus.shape[0]
            q = np.asarray(query, np.float32)
            if q.shape != (self.dim,):
                raise ValueError(f"query dim {q.shape} != collection dim {self.dim}")
            fn = self._get_search_fn(
                cap, device_corpus.k_bucket(top_k, n, cap))
        qn = float(np.linalg.norm(q))
        q = q / qn if qn > 0 else q
        scores, idx = fn(corpus, jnp.asarray(q), n)
        with self._lock:
            return self._hits_from(scores, idx, top_k)

    def search_fused(self, engine, text: str, top_k: int) -> List[SearchHit]:
        """Interactive-query fast path: hand the device-resident corpus to the
        engine's fused embed+top-k executable (one device round-trip instead
        of embed then search). Same results as search(embed_query(text)) —
        asserted in tests — with the same static-k bucketing."""
        # the thread-side whole of one fused query: lock, device sync, the
        # engine's call (its own span inside), hits assembly
        with span("store.search_fused", top_k=top_k):
            with self._lock:
                n = len(self._ids)
                if n == 0 or top_k <= 0:
                    return []
                self._sync_device()
                corpus = self._device_corpus
                k = device_corpus.k_bucket(top_k, n, corpus.shape[0])
            # device call (and any first-shape compile) outside the lock —
            # see search() for why the snapshot stays valid
            scores, idx = engine.embed_and_search(text, corpus, n, k)
            with self._lock:
                return self._hits_from(scores, idx, top_k)

    def warm_fused(self, engine,
                   word_counts: Optional[Sequence[int]] = None) -> None:
        """Pre-compile the fused embed+top-k executables for the store's
        CURRENT capacity across EVERY query length bucket of the engine —
        including an empty store (capacity is the first block, which the first
        shard_capacity upserts keep). Without this, the first fused query per
        (length-bucket, capacity) pays the full XLA compile inside the
        gateway's short probe timeout — on the v5e that is ~16 s against a
        5 s probe: the gateway negative-caches the fused subject, the 2-hop
        path compiles its own cold executable, and the client gets a 503
        (seen on the chip when only three of the five buckets were warmed).
        `word_counts` defaults to one text per bucket: one word more than
        the previous bucket holds. Warms every k bucket up to
        config.warm_top_k (device_corpus.warm_k_buckets: 8 and 16 by
        default) and records the warmed capacity so callers can re-warm when
        upserts cross a capacity block (fused_warm_stale)."""
        if word_counts is None:
            buckets = [b for b in engine.config.length_buckets
                       if b <= engine.model_cfg.max_position_embeddings]
            word_counts = [prev + 1 for prev in [0] + buckets[:-1]]
        with self._lock:
            self._sync_device()
            corpus = self._device_corpus
            n = len(self._ids)
            ks = device_corpus.warm_k_buckets(self.config.warm_top_k, n,
                                              corpus.shape[0])
        for k in ks:
            for wc in word_counts:
                engine.embed_and_search("warm " * wc, corpus, n, k)
        with self._lock:
            self._warmed_capacity = corpus.shape[0]

    def fused_warm_stale(self) -> bool:
        """True when upserts have crossed a capacity block since the last
        warm_fused — the next fused query would pay a fresh XLA compile, so
        the owner should re-run warm_fused in the background."""
        with self._lock:
            return (self._warmed_capacity is not None
                    and device_corpus.capacity(
                        len(self._ids), self.config.shard_capacity,
                        self.mesh) != self._warmed_capacity)

    # --------------------------------------------------------- persistence

    def _wal_path(self) -> Optional[Path]:
        if not self.config.data_dir:
            return None
        return Path(self.config.data_dir) / f"{self.config.collection}.wal.jsonl"

    def _wal_append(self, points) -> None:
        path = self._wal_path()
        if path is None:
            return
        if self._wal_file is None:
            self._wal_file = open(path, "a", encoding="utf-8")
        # vectors ride as base64 f32 (internal durability format, not wire
        # schema): json-serializing 384 floats per point was the single
        # hottest CPU term of a bulk-ingest wave (measured r5). load()
        # accepts both this and the pre-r5 "vector" float-list records.
        import base64

        with span("store.wal_encode", cpu=True):
            lines = []
            for pid, vec, payload in points:
                rec = {"id": pid,
                       "vector_b64": base64.b64encode(
                           np.asarray(vec, np.float32).tobytes()
                       ).decode("ascii"),
                       "payload": payload}
                lines.append(json.dumps(rec, ensure_ascii=False))
            data = "\n".join(lines) + "\n"
        with span("store.wal_sync", cpu=True):
            self._wal_file.write(data)
            self._wal_file.flush()
            os.fsync(self._wal_file.fileno())

    def compact(self) -> None:
        """Snapshot vectors+payloads, truncate the WAL. The vectors file is
        what `np.save` of the [n, dim] f32 matrix writes, written block by
        block: the header, then each block's stored rows."""
        if not self.config.data_dir:
            return
        with self._lock:
            root = Path(self.config.data_dir)
            with open(root / f"{self.config.collection}.vectors.npy",
                      "wb") as f:
                np.lib.format.write_array_header_1_0(f, {
                    "descr": np.lib.format.dtype_to_descr(
                        np.dtype(np.float32)),
                    "fortran_order": False,
                    "shape": (len(self._ids), self.dim)})
                for rows in self._stored():
                    rows.tofile(f)
            meta = {"dim": self.dim, "ids": self._ids, "payloads": self._payloads}
            tmp = root / f"{self.config.collection}.meta.json.tmp"
            tmp.write_text(json.dumps(meta, ensure_ascii=False))
            tmp.replace(root / f"{self.config.collection}.meta.json")
            if self._wal_file is not None:
                self._wal_file.close()
                self._wal_file = None
            wal = self._wal_path()
            if wal and wal.exists():
                wal.unlink()

    def _read_snapshot(self, path: Path) -> None:
        """Read the snapshot's rows straight into fresh blocks: one pass over
        the bytes, no whole matrix in between."""
        n, cap = len(self._ids), self.config.shard_capacity
        with open(path, "rb") as f:
            major, _ = np.lib.format.read_magic(f)
            shape, fortran, dtype = (
                np.lib.format.read_array_header_1_0 if major == 1
                else np.lib.format.read_array_header_2_0)(f)
            if shape != (n, self.dim) or fortran or dtype != np.float32:
                raise ValueError(
                    f"{path}: holds {shape} {dtype}, the collection's "
                    f"meta.json {n} ids at dim {self.dim} (float32 rows)")
            self._blocks = []
            for start in range(0, n, cap):
                block = np.empty((cap, self.dim), np.float32)
                rows = block[:min(cap, n - start)]
                if f.readinto(rows) != rows.nbytes:
                    raise ValueError(f"{path}: truncated at row {start}")
                self._blocks.append(block)

    def load(self) -> None:
        root = Path(self.config.data_dir)
        meta_p = root / f"{self.config.collection}.meta.json"
        with self._lock:
            if meta_p.exists():
                meta = json.loads(meta_p.read_text())
                self.dim = meta["dim"]
                self._ids = list(meta["ids"])
                self._payloads = list(meta["payloads"])
                self._id_to_row = {pid: i for i, pid in enumerate(self._ids)}
                self._read_snapshot(
                    root / f"{self.config.collection}.vectors.npy")
            wal = self._wal_path()
            skipped = 0
            if wal and wal.exists():
                replay: List[Tuple[str, list, dict]] = []
                with open(wal, encoding="utf-8") as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rec = json.loads(line)
                            if "vector_b64" in rec:
                                import base64

                                vec = np.frombuffer(
                                    base64.b64decode(rec["vector_b64"]),
                                    dtype=np.float32)
                            else:  # pre-r5 float-list records
                                vec = rec["vector"]
                            replay.append((rec["id"], vec, rec["payload"]))
                        except (json.JSONDecodeError, KeyError, ValueError):
                            skipped += 1
                if skipped:
                    # a rollback to a pre-r5 build re-writes this WAL with
                    # float-list records; anything the OLD code cannot parse
                    # (e.g. the r5 vector_b64 format) is not "a corrupt
                    # line", it is DATA LOSS — make the count visible so the
                    # operator knows how many points vanished (compact()
                    # BEFORE rolling back, see docs/DEPLOYMENT.md)
                    log.warning(
                        "%s: skipped %d corrupt/unreadable WAL line(s) — "
                        "these points are NOT loaded; if this follows a "
                        "version rollback, the WAL format changed and the "
                        "skipped records are lost unless re-ingested "
                        "(run compact() before rolling back)",
                        wal, skipped)
                if replay:
                    # replay through upsert minus re-logging
                    wal_file, self._wal_file = self._wal_file, None
                    data_dir, self.config.data_dir = self.config.data_dir, ""
                    try:
                        self.upsert(replay)
                    finally:
                        self.config.data_dir = data_dir
                        self._wal_file = wal_file
            self.last_load_skipped_lines = skipped
            self._dirty = True
            metrics.gauge_set("vector_store.host_blocks", len(self._blocks))
