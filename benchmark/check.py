"""The comparison that decides `correct`.

Run once the window has closed, `memory_peak_bytes` has been read and the
program's state is freed. It compares what the timed path itself produced,
at the timed sizes, with the configuration's plain reference (`refs/`); how
is the mix's kind's to say (`kinds/<kind>.py: check`), since what a reply
IS differs by API surface. Every number compared has its own limit; exact
comparisons have the limit 0. Limits that are not 0 come from the
configuration file (`limits`, by kind), set from readings on the chip
(PERF.md, End-to-end metrics and limits). A number whose name starts with
`_` is reported beside them and not compared.
"""

from __future__ import annotations

import numpy as np

import traffic


def number(value, limit) -> dict:
    return {"value": float(value), "limit": float(limit),
            "ok": bool(np.isfinite(value) and value <= limit)}


def sample(n_total: int, n: int, must: int, seed: int) -> list:
    """`n` indices out of range(n_total) drawn from the seed, `must` among
    them."""
    rng = np.random.default_rng([int(seed), 77])
    pick = set(rng.choice(n_total, size=min(n, n_total), replace=False).tolist())
    pick.add(int(must))
    return sorted(pick)


def compare(ctx: dict) -> dict:
    """`ctx` is run.py's (arch, model, config, mix, plan, client, seed,
    data_dir, collection) with the limits of this mix's kind."""
    kind = ctx["mix"]["kind"]
    seed = ctx["seed"]
    return traffic.load_kind(kind).check({
        **ctx, "limits": ctx["config"]["limits"][kind], "number": number,
        "sample": lambda n_total, n, must: sample(n_total, n, must, seed)})
