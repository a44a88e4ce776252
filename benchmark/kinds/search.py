"""Traffic kind `search`: `POST /api/search/semantic`, open loop.

A kind is one file holding what belongs to one API surface: the request
bodies of a plan (`requests`), the client process's driver (`drive`, no jax),
the count of attempts and failures, and the comparison that decides
`correct` (`check`). A mix names its kind; the harness finds this file by
that name. Mix keys read here: `top_k`, `rerank`, `query_words` (a clipped
lognormal of words per query), `warmup_requests`, `check_queries`.
"""

from __future__ import annotations

import asyncio
import time
from pathlib import Path

import numpy as np

import traffic

LATENCY_FIELD = "latency_ms"  # what the sweep watches for a growing backlog
PATH = "/api/search/semantic"


# -------------------------------------------------------------------- plan

def _requests(mix: dict, seed: int, n: int, stream: int) -> list:
    rng = traffic.rng(seed, stream)
    lens = traffic.lengths(n, mix["query_words"],
                           traffic.order_rng(mix, seed, stream))
    return [{"query_text": traffic.sentence(k, rng), "top_k": mix["top_k"],
             "rerank": bool(mix.get("rerank", False))} for k in lens]


def requests(mix: dict, seed: int, n: int, model: dict) -> dict:
    return {"warmup": _requests(mix, seed, int(mix.get("warmup_requests", 8)), 1),
            "window": _requests(mix, seed, n, 2)}


# ------------------------------------------------- the client process

async def drive(plan: dict, port: int, io) -> dict:
    for body in plan["warmup"]:
        status, reply = await io.http(port, "POST", PATH, body)
        if status != 200:
            raise RuntimeError(f"warm-up search answered {status}: {reply}")
    io.say("READY")
    if await io.wait_go() != "GO":
        return {"aborted": True}
    records = []

    async def one(i: int, body: dict, due_at: float) -> None:
        sent = time.monotonic()
        status, reply = await io.http(port, "POST", PATH, body)
        done = time.monotonic()
        ok = (status == 200 and isinstance(reply, dict)
              and not reply.get("error_message"))
        records.append({
            "i": i, "late_ms": (sent - due_at) * 1e3,
            "latency_ms": (done - due_at) * 1e3, "status": status, "ok": ok,
            "hits": ([[h["qdrant_point_id"], h["score"]]
                      for h in reply["results"]] if ok else None)})

    t0, tasks = await io.open_loop(plan, one)
    await asyncio.wait(tasks, timeout=plan["seconds"] + io.AFTER_CLOSE_S)
    return {"t0": t0, "records": sorted(records, key=lambda r: r["i"]),
            "attempted": len(tasks)}


def attempted_failed(client: dict) -> tuple:
    return client["attempted"], client["attempted"] - sum(
        1 for r in client["records"] if r["ok"])


# ------------------------------------------------------------------- check

def bf16_step(x: np.ndarray) -> np.ndarray:
    """Distance between neighbouring bfloat16 values at |x| (8 significant
    bits: 2**(exponent - 7))."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def check(ctx: dict) -> dict:
    """A seeded sample of the replies the client received (the longest query
    in it): each returned id's score against the reference's score for that
    id, and how far each returned hit lies below the reference's k-th best
    over the whole corpus.

    The configuration states bfloat16 scores, so every returned score carries
    a rounding error of up to half a bfloat16 step whatever the encoder did;
    `score_excess_mse` takes that known share (step**2 / 12, a uniform
    rounding error's variance) off the mean squared error, and what is left
    is the encoder's and the scan's own error: the number that tells int8
    weights from bfloat16 ones (PERF.md, section 2)."""
    number, limits = ctx["number"], ctx["limits"]
    client, mix, plan = ctx["client"], ctx["mix"], ctx["plan"]
    recs = client["records"]
    answered = [r for r in recs if r["status"] != 0]
    ok = [r for r in recs if r["ok"]]
    k = mix["top_k"]
    out = {"unanswered": number(client["attempted"] - len(answered), 0),
           "hits_short": number(sum(1 for r in ok if len(r["hits"]) != k), 0)}
    if not ok:
        for name in limits:
            out[name] = number(float("inf"), limits[name])
        return out
    texts = [plan["window"][r["i"]]["query_text"] for r in ok]
    longest = max(range(len(ok)), key=lambda i: len(texts[i]))
    pick = ctx["sample"](len(ok), mix["check_queries"], longest)
    ref = ctx["arch"].Reference(ctx["model"], ctx["seed"],
                                ctx["config"]["max_tokens"])
    q = ref.embed([texts[i] for i in pick])
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    corpus = np.load(Path(ctx["data_dir"]) / f"{ctx['collection']}.vectors.npy",
                     mmap_mode="r")  # the benchmark's own seeded file
    scores = np.empty((len(pick), corpus.shape[0]), np.float32)
    step = 131072
    for a in range(0, corpus.shape[0], step):
        scores[:, a:a + step] = q @ np.asarray(corpus[a:a + step]).T
    kth = np.partition(scores, -k, axis=1)[:, -k]
    unknown = 0
    got, want, rgaps = [], [], []
    for row, i in enumerate(pick):
        for pid, score in ok[i]["hits"]:
            if not (pid.startswith("c") and pid[1:].isdigit()
                    and int(pid[1:]) < corpus.shape[0]):
                unknown += 1
                continue
            got.append(score)
            want.append(float(scores[row, int(pid[1:])]))
            rgaps.append(max(0.0, float(kth[row]) - want[-1]))
    out["unknown_ids"] = number(unknown, 0)
    if not got:
        for name in limits:
            out[name] = number(float("inf"), limits[name])
        return out
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = got - want
    excess = (err ** 2).mean() - (bf16_step(got) ** 2).mean() / 12.0
    out["score_err_max"] = number(np.abs(err).max(), limits["score_err_max"])
    out["score_excess_mse"] = number(excess, limits["score_excess_mse"])
    out["rank_gap_max"] = number(max(rgaps), limits["rank_gap_max"])
    out["_score_err_mean"] = float(np.abs(err).mean())
    out["_rank_gap_mean"] = float(np.mean(rgaps))
    out["_queries_compared"] = len(pick)
    return out
