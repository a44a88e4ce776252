"""The load generator: a child process that never imports jax or the program.

    python client.py <plan.json> <out.json> <api_port>

It runs the plan `traffic.build_plan` made, with the driver of the plan's
kind (`kinds/<kind>.py: drive`): warm-up, prints `READY`, waits for `GO` on
stdin, runs the window, writes its record to <out.json> and exits. The
server process (which holds the chip) times nothing the client sees: every
latency here is on this process's own clock, from the moment a request was
DUE, so a stalled server shows as latency, and a starved generator as
`late_ms`.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

HOST = "127.0.0.1"
AFTER_CLOSE_S = 60.0  # an answer that comes late is late, not wrong


async def http(port: int, method: str, path: str, body=None,
               timeout: float = 120.0):
    """One request on its own connection; (status, parsed json or None)."""
    async def go():
        reader, writer = await asyncio.open_connection(HOST, port)
        try:
            data = json.dumps(body).encode() if body is not None else b""
            writer.write(
                f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
                f"Connection: close\r\nContent-Type: application/json\r\n"
                f"Content-Length: {len(data)}\r\n\r\n".encode() + data)
            await writer.drain()
            raw = await reader.read(-1)
        finally:
            writer.close()
        head, _, payload = raw.partition(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1]) if head else 0
        try:
            return status, json.loads(payload) if payload else None
        except ValueError:
            return status, None

    try:
        return await asyncio.wait_for(go(), timeout)
    except (OSError, asyncio.TimeoutError, IndexError, ValueError) as e:
        return 0, {"client_error": repr(e)}


async def wait_go() -> str:
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    return line.strip()


def say(word: str) -> None:
    sys.stdout.write(word + "\n")
    sys.stdout.flush()


async def open_loop(plan: dict, one) -> tuple:
    """Start `one(i, body, due_at)` for each request of the window when it
    is due, whatever became of those before it; (t0, the tasks)."""
    t0 = time.monotonic() + 0.05
    tasks = []
    for i, (body, due) in enumerate(zip(plan["window"], plan["due"])):
        delay = t0 + due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(i, body, t0 + due)))
    return t0, tasks


async def main(plan_path: str, out_path: str, port: int) -> None:
    import traffic  # no jax, no program

    plan = json.loads(Path(plan_path).read_text())
    out = await traffic.load_kind(plan["kind"]).drive(
        plan, port, sys.modules[__name__])
    Path(out_path).write_text(json.dumps(out))
    say("DONE")


if __name__ == "__main__":
    asyncio.run(main(sys.argv[1], sys.argv[2], int(sys.argv[3])))
