"""Traffic kind `generate`: `POST /api/generate-text` with `stream: true`,
deltas read from one `GET /api/events` connection, open loop. See
kinds/search.py for what a kind file holds.

No cell of `BENCHMARK.json` uses this kind yet: PR 24 proved it on the chip
(gpt2-large, 24 seeds) and took its one cell out again, because streamed
requests never touch the page pool the configuration reserves (PERF.md,
Open questions). It is kept, with its toy-size tests, for the generation
cell that comes next.

Mix keys read here: `prompt_tokens`, `output_tokens` (clipped lognormals),
`temperature`, `warmup_requests`, `warmup_prompt_tokens` x
`warmup_output_tokens` (one warm-up request at the top of each bucket pair
the window can hit), `check_requests`.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

import traffic

LATENCY_FIELD = "ttft_ms"
HOST = "127.0.0.1"
PATH = "/api/generate-text"


# -------------------------------------------------------------------- plan

def _body(task_id: str, ids, max_length: int, mix: dict) -> dict:
    return {"task_id": task_id, "prompt": " ".join(f"w{t}" for t in ids),
            "max_length": int(max_length), "stream": True,
            "temperature": float(mix.get("temperature", 0.0))}


def _requests(mix: dict, seed: int, n: int, stream: int, tag: str,
              vocab_size: int) -> list:
    rng = traffic.rng(seed, stream)
    order = traffic.order_rng(mix, seed, stream)
    prompts = traffic.lengths(n, mix["prompt_tokens"], order)
    outputs = traffic.lengths(n, mix["output_tokens"], order)
    # ids 1..V-1: 0 is the assumed tokenizer's pad/unk id
    return [_body(f"{tag}-{seed}-{i}", rng.integers(1, vocab_size, size=int(p)),
                  o, mix) for i, (p, o) in enumerate(zip(prompts, outputs))]


def requests(mix: dict, seed: int, n: int, model: dict) -> dict:
    vocab = model["vocab_size"]
    warm = _requests(mix, seed, int(mix.get("warmup_requests", 8)), 1, "warm",
                     vocab)
    for p in mix.get("warmup_prompt_tokens", []):
        for o in mix.get("warmup_output_tokens", []):
            rng = traffic.rng(seed, 3000 + p * 7 + o)
            warm.append(_body(f"warmb-{seed}-{p}-{o}",
                              rng.integers(1, vocab, size=int(p)), o, mix))
    return {"warmup": warm,
            "window": _requests(mix, seed, n, 2, "req", vocab)}


# ------------------------------------------------- the client process

class SseReader:
    """One `GET /api/events` connection; every event stamped on arrival."""

    def __init__(self, port: int):
        self.port = port
        self.by_task: dict = {}
        self.closed = False

    async def start(self) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            HOST, self.port, limit=1 << 22)
        self.writer.write(b"GET /api/events HTTP/1.1\r\nHost: bench\r\n\r\n")
        await self.writer.drain()
        self.task = asyncio.create_task(self._read())
        await asyncio.sleep(0.2)

    async def _read(self) -> None:
        while True:
            line = await self.reader.readline()
            if not line:
                self.closed = True
                return
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            try:
                ev = json.loads(line[6:].strip())
            except ValueError:
                continue
            tid = ev.get("original_task_id")
            if tid is None:
                continue
            rec = self.by_task.setdefault(
                tid, {"deltas": [], "done_at": None, "final_at": None})
            if "text_delta" in ev:
                if ev["text_delta"]:
                    rec["deltas"].append([now, ev["text_delta"]])
                if ev.get("done"):
                    rec["done_at"] = now
            elif "generated_text" in ev:
                rec["final_at"] = now
                rec["final_text"] = ev["generated_text"]

    def finished(self, tid: str) -> bool:
        rec = self.by_task.get(tid)
        return bool(rec and rec["done_at"] is not None)

    async def stop(self) -> None:
        self.task.cancel()
        self.writer.close()


async def drive(plan: dict, port: int, io) -> dict:
    sse = SseReader(port)
    await sse.start()

    async def until_finished(ids: list, deadline: float) -> None:
        while (time.monotonic() < deadline and not sse.closed
               and not all(sse.finished(t) for t in ids)):
            await asyncio.sleep(0.02)

    # warm-up: a few at a time, so admissions and joins are warm too
    warm = plan["warmup"]
    for a in range(0, len(warm), 4):
        group = warm[a:a + 4]
        for body in group:
            status, reply = await io.http(port, "POST", PATH, body)
            if status != 200:
                raise RuntimeError(f"warm-up generate answered {status}: "
                                   f"{reply}")
        ids = [b["task_id"] for b in group]
        await until_finished(ids, time.monotonic() + 1100)
        if not all(sse.finished(t) for t in ids):
            raise RuntimeError(f"warm-up generations did not finish: {ids}")
    io.say("READY")
    if await io.wait_go() != "GO":
        return {"aborted": True}
    sends = {}

    async def one(i: int, body: dict, due_at: float) -> None:
        sent = time.monotonic()
        status, reply = await io.http(port, "POST", PATH, body)
        sends[i] = {"late_ms": (sent - due_at) * 1e3, "status": status,
                    "due_at": due_at}

    t0, tasks = await io.open_loop(plan, one)
    await asyncio.wait(tasks, timeout=io.AFTER_CLOSE_S)
    ids = [b["task_id"] for b in plan["window"]]
    await until_finished(ids, t0 + plan["seconds"] + io.AFTER_CLOSE_S)
    await sse.stop()
    records = []
    for i, body in enumerate(plan["window"]):
        s = sends.get(i, {"late_ms": None, "status": 0, "due_at": None})
        ev = sse.by_task.get(body["task_id"],
                             {"deltas": [], "done_at": None})
        deltas = ev["deltas"]
        text = "".join(d[1] for d in deltas)
        rec = {"i": i, "late_ms": s["late_ms"], "status": s["status"],
               "ok": s["status"] == 200 and ev["done_at"] is not None,
               "asked": body["max_length"],
               "prompt_tokens": len(body["prompt"].split()),
               "served_text": text}
        if deltas and s["due_at"] is not None:
            rec["ttft_ms"] = (deltas[0][0] - s["due_at"]) * 1e3
            first_n = len(deltas[0][1].split())
            rest = len(text.split()) - first_n
            if rest > 0:
                rec["tpot_ms"] = (deltas[-1][0] - deltas[0][0]) * 1e3 / rest
            rec["done_ms"] = ((ev["done_at"] or deltas[-1][0])
                              - s["due_at"]) * 1e3
        records.append(rec)
    return {"t0": t0, "records": records, "attempted": len(tasks),
            "sse_closed_early": sse.closed}


def attempted_failed(client: dict) -> tuple:
    return client["attempted"], client["attempted"] - sum(
        1 for r in client["records"] if r["ok"])


# ------------------------------------------------------------------- check

def check(ctx: dict) -> dict:
    """A seeded sample of finished requests (the longest in it): how far
    each served token's reference logit lies below the reference's best at
    its position (greedy decoding)."""
    number, limits, arch = ctx["number"], ctx["limits"], ctx["arch"]
    recs, plan = ctx["client"]["records"], ctx["plan"]
    done = [r for r in recs if r["ok"]]
    sent_ok = [r for r in recs if r["status"] == 200]
    served = {r["i"]: arch.text_to_ids(r["served_text"]) for r in done}
    out = {"unfinished": number(len(sent_ok) - len(done), 0),
           "tokens_short": number(
               sum(1 for r in done if len(served[r["i"]]) != r["asked"]), 0)}
    if not done:
        for name in limits:
            out[name] = number(float("inf"), limits[name])
        return out
    longest = max(range(len(done)), key=lambda i: (
        done[i]["prompt_tokens"] + len(served[done[i]["i"]])))
    pick = ctx["sample"](len(done), ctx["mix"]["check_requests"], longest)
    ref = arch.Reference(ctx["model"], ctx["seed"])
    gaps = []
    for j in pick:
        r = done[j]
        toks = served[r["i"]]
        if toks:
            prompt = arch.text_to_ids(plan["window"][r["i"]]["prompt"])
            gaps.append(ref.served_gaps(prompt, toks))
    gaps = np.concatenate(gaps) if gaps else np.array([np.inf])
    out["logit_gap_max"] = number(gaps.max(), limits["logit_gap_max"])
    out["logit_gap_mean"] = number(gaps.mean(), limits["logit_gap_mean"])
    out["_argmax_miss_share"] = float((gaps > 0).mean())
    out["_logit_gap_p99"] = float(np.percentile(gaps, 99))
    out["_tokens_compared"] = int(gaps.size)
    return out
