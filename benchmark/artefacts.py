"""Seeded artefacts the program loads through its own loaders.

- the checkpoint (`config.json` + `model.safetensors` [+ `tokenizer.json`])
  in the hub layout `model_dir` reads, drawn from `--seed` by the
  configuration's plain-reference module (`refs/<architecture>.py`);
- the corpus snapshot in the store's durability format
  (`<collection>.vectors.npy` + `<collection>.meta.json`), drawn from the
  configuration's own `corpus.seed`: 4.6 GB per `--seed` would breach the
  rule that a run writes little, so it is written once per checkout and
  `--seed` draws the queries and pages instead (PERF.md, Cells).

All of it lives under `benchmark/.cache/` (git-ignored, fixed path); only the
current seed's checkpoint is kept. Imports neither jax nor the program.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".cache"


def architecture(config: dict):
    """The configuration's plain reference, found by name."""
    return importlib.import_module(f"refs.{config['architecture']}")


def ensure_checkpoint(config: dict, model: dict, seed: int) -> Path:
    out = CACHE / config["name"] / "model"
    marker = out / "benchmark_seed.json"
    want = {"seed": int(seed), "model": model}
    if marker.is_file() and json.loads(marker.read_text()) == want:
        return out
    if out.exists():
        shutil.rmtree(out)
    architecture(config).write_checkpoint(model, seed, out)
    _flush(out / "model.safetensors")
    marker.write_text(json.dumps(want))
    return out


def _flush(path: Path) -> None:
    """Push a freshly written artefact to disk NOW, in set-up: gigabytes of
    dirty pages left to the kernel are written back half a minute later,
    inside the measured window."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def corpus_row_id(i: int) -> str:
    return f"c{i}"


def corpus_payload(i: int) -> dict:
    return {"original_document_id": "corpus", "source_url": "seed://corpus",
            "sentence_text": f"corpus row {i}", "sentence_order": i,
            "model_name": "seeded", "processed_at_ms": 0}


def corpus_rows(spec: dict, dim: int, lo: int, hi: int) -> np.ndarray:
    """Rows [lo, hi) of the seeded corpus: unit-norm float32 Gaussian rows,
    drawn in blocks of 65,536 rows, each from its own child generator, so any
    slice can be re-made without the rest (the reference does)."""
    block = 65536
    out = np.empty((hi - lo, dim), np.float32)
    for b in range(lo // block, -(-hi // block)):
        rng = np.random.default_rng([int(spec["seed"]), b])
        n = min(block, spec["rows"] - b * block)
        x = rng.standard_normal((n, dim), dtype=np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        a, z = max(lo, b * block), min(hi, b * block + n)
        out[a - lo:z - lo] = x[a - b * block:z - b * block]
    return out


def ensure_corpus(config: dict, spec: dict, dim: int, collection: str) -> Path:
    """The store's `data_dir`, holding a snapshot of `spec["rows"]` rows."""
    out = CACHE / config["name"] / f"corpus-{spec['rows']}-{spec['seed']}-{dim}"
    done = out / "benchmark_done.json"
    for wal in out.glob("*.wal.jsonl"):  # a run's WAL must not replay
        wal.unlink()
    if done.is_file():
        return out
    if out.exists():
        shutil.rmtree(out)
    out.mkdir(parents=True)
    rows = spec["rows"]
    vec = np.lib.format.open_memmap(out / f"{collection}.vectors.npy",
                                    mode="w+", dtype=np.float32,
                                    shape=(rows, dim))
    block = 65536

    def fill(b: int) -> None:
        lo, hi = b * block, min(rows, (b + 1) * block)
        vec[lo:hi] = corpus_rows(spec, dim, lo, hi)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(-(-rows // block))))
    vec.flush()
    del vec
    meta = {"dim": dim, "ids": [corpus_row_id(i) for i in range(rows)],
            "payloads": [corpus_payload(i) for i in range(rows)]}
    (out / f"{collection}.meta.json").write_text(json.dumps(meta))
    _flush(out / f"{collection}.vectors.npy")
    _flush(out / f"{collection}.meta.json")
    done.write_text(json.dumps(spec))
    return out
