"""Traffic kind `ingest`: `POST /api/submit-url` against a loopback page
server, closed loop on the store's row count (`outstanding` pages always in
flight). See kinds/search.py for what a kind file holds. Besides the parts
every kind has, this one brings the window itself (`window`) and a wait for
late rows (`settle`), both run in the server process beside the stack.

Mix keys read here: `outstanding`, `sentences_per_page`, `sentence_words`,
`warmup_pages`, `check_rows`.
"""

from __future__ import annotations

import asyncio
import base64
import json
import time
from pathlib import Path

import numpy as np

import traffic

HOST = "127.0.0.1"


# -------------------------------------------------------------------- plan

def page_sentences(mix: dict, seed: int, index: int) -> list:
    """Sentences of page `index` (>= 0 window pages, < 0 warm-up): the same
    lengths on every page, seeded words. Each ends in a full stop and carries
    its page and position, so no two rows of a run share a text and a split
    can be checked sentence by sentence."""
    rng = traffic.rng(seed, 1000 + index)
    lens = traffic.lengths(mix["sentences_per_page"], mix["sentence_words"],
                           traffic.order_rng(mix, seed, 1000))
    tag = f"p{index}".replace("-", "m")
    return [f"{traffic.sentence(max(n - 2, 1), rng)} {tag} s{j}."
            for j, n in enumerate(lens)]


def page_html(sentences: list) -> str:
    return ("<html><body><article>"
            + "".join(f"<p>{s}</p>" for s in sentences)
            + "</article></body></html>")


def requests(mix: dict, seed: int, n: int, model: dict) -> dict:
    return {}  # pages are made on demand by page_sentences


# ------------------------------------------------- the client process

async def _serve_pages(mix: dict, seed: int):
    async def handle(reader, writer):
        try:
            line = await reader.readline()
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            path = line.split(b" ")[1].decode()
            index = int(path.rsplit("/", 1)[1])
            body = page_html(page_sentences(mix, seed, index)).encode()
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/html; "
                         b"charset=utf-8\r\nConnection: close\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            await writer.drain()
        except (OSError, IndexError, ValueError):
            pass
        finally:
            writer.close()

    return await asyncio.start_server(handle, HOST, 0)


async def drive(plan: dict, port: int, io) -> dict:
    mix, seed = plan["mix"], plan["seed"]
    per_page = mix["sentences_per_page"]
    server = await _serve_pages(mix, seed)
    page_port = server.sockets[0].getsockname()[1]
    failed = []

    async def rows_landed() -> int:
        status, snap = await io.http(port, "GET", "/api/metrics", timeout=30)
        if status != 200:
            return -1
        return int(snap["counters"].get("vector_memory.points_upserted", 0))

    async def submit(index: int) -> None:
        status, reply = await io.http(
            port, "POST", "/api/submit-url",
            {"url": f"http://{HOST}:{page_port}/page/{index}"})
        if status != 200:
            failed.append([index, status])

    base = await rows_landed()
    n_warm = int(mix.get("warmup_pages", 2))
    await asyncio.gather(*[submit(-1 - k) for k in range(n_warm)])
    deadline = time.monotonic() + 1100
    while (await rows_landed() - base < n_warm * per_page
           and time.monotonic() < deadline):
        await asyncio.sleep(0.1)
    if failed or await rows_landed() - base < n_warm * per_page:
        raise RuntimeError(f"warm-up pages did not land (failed={failed})")
    base = await rows_landed()
    io.say("READY")
    if await io.wait_go() != "GO":
        return {"aborted": True}
    stop = asyncio.create_task(io.wait_go())
    t0 = time.monotonic()
    submitted = 0
    while not stop.done():
        landed_pages = (await rows_landed() - base) // per_page
        while submitted - landed_pages < mix["outstanding"]:
            await submit(submitted)
            submitted += 1
        await asyncio.sleep(0.05)
    # the page server must outlive the fetches still in flight
    await asyncio.sleep(2.0)
    server.close()
    return {"t0": t0, "t_stop": time.monotonic(), "submitted": submitted,
            "failed": failed, "attempted": submitted, "rows_base": base}


def attempted_failed(client: dict) -> tuple:
    return client["attempted"], len(client["failed"])


# ------------------------------------ the server process, beside the stack

def _next_count_change(store, seen: int, timeout_s: float) -> tuple:
    """(time, count) at the store's next change of row count after `seen`
    (`count()` waits on the store's own lock, so it returns as a flush
    lands); the time of giving up where nothing changes."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        n = store.count()
        if n != seen:
            return time.monotonic(), n
        time.sleep(0.005)
    return time.monotonic(), store.count()


async def window(stack, tell, seconds: float) -> dict:
    """The store takes rows in flushes of hundreds: a window cut at arbitrary
    instants would gain or lose a whole flush at each end. So the window runs
    from one flush's landing to another's: it opens at the first change of
    the row count after GO and closes at the first change after `seconds`
    more. The rate is still all the rows over all the time between the two."""
    loop = asyncio.get_running_loop()
    store = stack.vector_store
    seen = store.count()
    await tell("GO")
    t0, rows0 = await loop.run_in_executor(
        None, _next_count_change, store, seen, 120.0)
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t1, rows1 = await loop.run_in_executor(
        None, _next_count_change, store, store.count(), 60.0)
    await tell("STOP")
    return {"t0": t0, "t1": t1, "rows0": rows0, "rows1": rows1}


async def settle(client: dict, mix: dict, counter_now) -> None:
    """Every page submitted is due: wait for its rows (late is late, not
    wrong), a minute past the close at most."""
    due = ((client["submitted"] - len(client["failed"]))
           * mix["sentences_per_page"])
    deadline = time.monotonic() + 60
    while (counter_now("vector_memory.points_upserted") - client["rows_base"]
           < due and time.monotonic() < deadline):
        await asyncio.sleep(0.2)


# ------------------------------------------------------------------- check

def _read_wal(data_dir: Path) -> list:
    rows = []
    for wal in sorted(Path(data_dir).glob("*.wal.jsonl")):
        with open(wal, encoding="utf-8") as f:
            for line in f:
                if line.strip():
                    rows.append(json.loads(line))
    return rows


def check(ctx: dict) -> dict:
    """Rows as they landed in the store's write-ahead log (the store's own
    durable record): every row of every page submitted is there, once, with
    the sentence the page held (perception's extract + preprocessing's
    split), and every row's vector (a seeded sample with the longest
    sentence in it, beyond the mix's `check_rows`) matches the reference
    encoder."""
    number, limits = ctx["number"], ctx["limits"]
    client, mix, seed = ctx["client"], ctx["mix"], ctx["seed"]
    pages = ([-1 - k for k in range(int(mix.get("warmup_pages", 2)))]
             + list(range(client["submitted"])))
    failed = {p for p, _ in client["failed"]}
    landed = {}
    for rec in _read_wal(ctx["data_dir"]):
        pl = rec["payload"]
        landed.setdefault(
            (int(pl["source_url"].rsplit("/", 1)[1]), pl["sentence_order"]),
            []).append(rec)
    missing = mismatch = 0
    candidates = []
    for p in pages:
        if p in failed:
            continue
        for j, sent in enumerate(page_sentences(mix, seed, p)):
            got = landed.get((p, j), [])
            if len(got) != 1:
                missing += 1
            elif got[0]["payload"]["sentence_text"] != sent:
                mismatch += 1
            else:
                candidates.append((sent, got[0]))
    out = {"rows_missing": number(missing, 0),
           "text_mismatch": number(mismatch, 0)}
    if not candidates:
        for name in limits:
            out[name] = number(float("inf"), limits[name])
        return out
    longest = max(range(len(candidates)),
                  key=lambda i: len(candidates[i][0]))
    pick = ctx["sample"](len(candidates), mix["check_rows"], longest)
    ref = ctx["arch"].Reference(ctx["model"], seed,
                                ctx["config"]["max_tokens"])
    want = ref.embed([candidates[i][0] for i in pick], rows_per_call=128)
    got = np.stack([np.frombuffer(base64.b64decode(
        candidates[i][1]["vector_b64"]), np.float32) for i in pick])
    err = (np.linalg.norm(got - want, axis=1)
           / np.maximum(np.linalg.norm(want, axis=1), 1e-12))
    out["embed_rel_err_max"] = number(err.max(), limits["embed_rel_err_max"])
    out["embed_rel_err_mean"] = number(err.mean(),
                                       limits["embed_rel_err_mean"])
    out["_rows_compared"] = len(pick)
    return out
