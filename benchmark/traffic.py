"""The one general traffic generator.

A traffic mix is a data file `benchmark/traffic/<mix>.json` of parameters;
this module turns (mix, seed, seconds) into the plan the client process
runs: the mix's sizes, its arrivals and their order, with the request bodies
made by the mix's kind (`benchmark/kinds/<kind>.py`, one file per API
surface, found by name). A later PR adds a mix, or a kind, by adding a file,
never by editing this one.

Every seed gets the SAME multiset of sizes and of arrival gaps (the
stratified quantiles of the mix's distributions), so every run offers the
same work; `--seed` draws the content (words, token ids, the weights too)
and the ORDER. A mix that carries `schedule_seed` fixes the order as well,
the same in every run. A mix that carries `order_block` too fixes the order
only inside blocks of that many requests, and `--seed` draws the order of the
blocks: in an open loop the ORDER of gaps decides which requests collide, so a
free order makes a tail differ from seed to seed by far more than two runs of
one seed do (PERF.md, section 2); whole blocks moved about keep every seed's
collisions the same but for the requests at a block's edge.

Mix keys read here:
  kind          which file of `kinds/` drives the API
  loop          "open" (Poisson arrivals at `rate_per_s`, each request timed
                from when it was due) or "closed" (the kind paces itself)
  rate_per_s    offered rate of an open loop
  schedule_seed optional, see above
  order_block   optional, with `schedule_seed`: requests to a block
Imports neither jax nor the program.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

import numpy as np

import yardstick

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix file {path}")
    mix = json.loads(path.read_text())
    if not (HERE / "kinds" / f"{mix.get('kind')}.py").is_file():
        raise ValueError(f"{path}: no kinds/{mix.get('kind')}.py")
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open|closed")
    if "order_block" in mix and "schedule_seed" not in mix:
        raise ValueError(f"{path}: order_block needs a schedule_seed")
    return mix


def load_kind(name: str):
    return importlib.import_module(f"kinds.{name}")


def rng(seed: int, stream: int):
    return np.random.default_rng([int(seed), stream])


class BlockOrder:
    """`permutation(values)` as a generator has it: the mix's fixed shuffle
    of the values, cut into blocks of `block`, the blocks in the order the
    run's seed draws. Every stream of one run (sizes, gaps) of one length
    gets the same order of blocks, so a block keeps its pairs."""

    def __init__(self, fixed, seed: int, block: int):
        self.fixed, self.seed, self.block = fixed, seed, int(block)

    def permutation(self, values) -> np.ndarray:
        values = self.fixed.permutation(values)
        starts = np.arange(0, len(values), self.block)
        return np.concatenate(
            [values[a:a + self.block]
             for a in rng(self.seed, 7_000_000).permutation(starts)])


def order_rng(mix: dict, seed: int, stream: int):
    """The generator that orders a mix's sizes and gaps: the mix's own
    `schedule_seed` where it has one (inside blocks only, where it has
    `order_block` too), else the run's seed."""
    if "order_block" in mix:
        return BlockOrder(rng(mix["schedule_seed"], stream), seed,
                          mix["order_block"])
    if "schedule_seed" in mix:
        return rng(mix["schedule_seed"], stream)
    return rng(seed, 7_000_000 + stream)


def sentence(n_words: int, rng_) -> str:
    return " ".join(rng_.choice(yardstick.WORDS, size=int(n_words)))


def lengths(n: int, dist: dict, rng_) -> np.ndarray:
    return yardstick.stratified_lognormal(
        n, dist["median"], dist["sigma"], dist["min"], dist["max"], rng_)


def build_plan(mix: dict, seed: int, seconds: float, model: dict) -> dict:
    """The client's plan: {"kind", "loop", "seed", "seconds", "mix"} plus
    what the kind's `requests` adds ("warmup" and "window" bodies) and, for
    an open loop, "due" (seconds after the window opens, one per request)."""
    plan = {"kind": mix["kind"], "loop": mix["loop"], "seed": int(seed),
            "seconds": float(seconds), "mix": mix}
    n = 0
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        plan["due"] = yardstick.stratified_poisson_arrivals(
            n, mix["rate_per_s"], order_rng(mix, seed, 3)).tolist()
    plan.update(load_kind(mix["kind"]).requests(mix, seed, n, model))
    return plan
