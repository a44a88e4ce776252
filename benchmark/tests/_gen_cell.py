"""BENCHMARK.json with the generation cell's entries merged in (the cell is
not listed; `gen_cell.json` holds its entries as PR 24 ran them), written to
a path the tests hand to `run.py --benchmark-json`."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def merged() -> dict:
    bench = json.loads((HERE.parent.parent / "BENCHMARK.json").read_text())
    extra = json.loads((HERE / "gen_cell.json").read_text())
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[group] = bench[group] + extra[group]
    return bench


def write(path: Path) -> Path:
    path.write_text(json.dumps(merged()))
    return path
