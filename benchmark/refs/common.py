"""What the plain references share: seeded tensors and checkpoint writing.

Weights are drawn on the host, tensor by tensor from generators spawned off
one seed (so a tensor's values do not depend on the order of the others),
rounded to bfloat16 (the type the checkpoint file holds; the program upcasts
to float32 on load), and handed out as float32. The reference calls the same
function with the same seed: it never reads a file or an array the program
made.
"""

from __future__ import annotations

import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16  # numpy converts it to float32 20x faster than float16
BLOCK_ELEMENTS = 1 << 22  # 4 M values per draw: threads share big tensors
WORKERS = 12


def seeded_tensors(specs: list, seed: int) -> dict:
    """`specs` = [(name, shape, kind)], kind in {"w", "b", "ln_scale"}:
    weights N(0, 0.02), biases N(0, 0.02), norm scales 1 + N(0, 0.1).
    Returns {name: float32 array holding bfloat16-representable values}."""
    children = np.random.SeedSequence(int(seed)).spawn(len(specs))
    out = {name: np.empty(shape, BF16) for name, shape, _ in specs}
    jobs = []
    for (name, shape, kind), ss in zip(specs, children):
        # a large tensor is drawn in blocks of rows, each from its own
        # child generator, so the threads share the work of one tensor
        rows = shape[0]
        per = max(1, BLOCK_ELEMENTS // max(1, int(np.prod(shape[1:]))))
        blocks = [(a, min(rows, a + per)) for a in range(0, rows, per)]
        for (a, b), child in zip(blocks, ss.spawn(len(blocks))):
            jobs.append((name, kind, a, b, child))

    def draw(job):
        name, kind, a, b, child = job
        rng = np.random.default_rng(child)
        x = rng.standard_normal(out[name][a:b].shape, dtype=np.float32)
        x *= np.float32(0.1 if kind == "ln_scale" else 0.02)
        if kind == "ln_scale":
            x += np.float32(1.0)
        out[name][a:b] = x

    with ThreadPoolExecutor(max_workers=WORKERS) as pool:
        list(pool.map(draw, jobs))
    return out


def write_safetensors(tensors: dict, out_dir: Path) -> None:
    from safetensors.numpy import save_file

    out_dir.mkdir(parents=True, exist_ok=True)
    save_file({k: np.ascontiguousarray(v) for k, v in tensors.items()},
              str(out_dir / "model.safetensors"), metadata={"format": "pt"})


def write_hf_config(hf_config: dict, out_dir: Path) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "config.json").write_text(json.dumps(hf_config, indent=1))


def f32(tensors: dict) -> dict:
    return {k: np.asarray(v, np.float32) for k, v in tensors.items()}
