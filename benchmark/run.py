"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one new process. It finds the cell in `BENCHMARK.json`, its
configuration in the file the entry names, its traffic mix in
`benchmark/traffic/<traffic>.json`, the mix's kind (the API surface: plan,
client driver, check) in `benchmark/kinds/<kind>.py`, each end-to-end
metric's reader in `benchmark/end_to_end/<metric>.py` and each per-layer
metric's in `benchmark/layer_metrics/<metric>.py` — all by name; adding a
cell, a mix, a kind, a configuration or a metric adds files and entries and
edits none.

Set-up (counted in `setup_s`, process start -> window start): seeded
checkpoint and corpus snapshot (artefacts.py), the stack booted the way
`symbiont_tpu.runner.main()` boots it (config from an environment mapping,
its own `ApiService` on a loopback port, in-proc bus), `/readyz`, the
program's own warm-ups, then the cell's warm-up traffic from the client
process. The window is driven over HTTP/SSE by `client.py`, a child that
never imports jax (this process holds the chip). Afterwards: counters,
`memory_peak_bytes`, the stack stopped and freed, then the comparison that
decides `correct` (check.py) against the plain reference (refs/).

The last line of stdout is the result object; the numbers compared, each
beside its limit, are the last lines of stderr and the result's last key.
No chip (or fewer than the cell asks for): exit 3 and no result line.
`--rehearse-cpu` runs the configuration's `toy` sizes on the CPU (platform
named `cpu`; for tests, never a measurement).
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import importlib.util
import json
import logging
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))  # a bare checkout: no editable install

import artefacts  # noqa: E402
import check  # noqa: E402
import traffic  # noqa: E402
import yardstick  # noqa: E402


class NoChip(RuntimeError):
    pass


# ------------------------------------------------------------- discovery

def load_benchmark(path=None) -> dict:
    return json.loads(Path(path or ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    return cell, config, traffic.load_mix(cell["traffic"])


def metrics_of(bench: dict, group: str, cell: str, reported: set) -> list:
    """The metrics of `group` this cell reports: those that list it, and
    those with no `workloads` key whose `moves` metric the cell reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def load_reader(folder: str, name: str):
    """`read(ctx)` of metric `name`: `<folder>/<name>.py` (`end_to_end` or
    `layer_metrics`), or, where the quantity is the same in every family of
    cells, the file named without the metric's last dotted part
    (`device_idle_pct.py` reads `device_idle_pct.ingest` and `.search`)."""
    folder = HERE / folder
    path = folder / f"{name}.py"
    if not path.is_file() and "." in name:
        path = folder / f"{name.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader in {folder}")
    if str(folder) not in sys.path:
        sys.path.insert(0, str(folder))  # a folder's readers share _common.py
    spec = importlib.util.spec_from_file_location(
        f"{folder.name}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def toy_view(config: dict) -> dict:
    """The configuration at its `toy` sizes (CPU rehearsal and tests)."""
    toy = config["toy"]
    merged = ("env", "corpus", "limits", "control")
    out = {**config, **{k: v for k, v in toy.items() if k not in merged}}
    for key in merged:
        if key in config:
            out[key] = {**config[key], **toy.get(key, {})}
    return out


# ------------------------------------------------------------- the device

def look_for_chip(chips: int, rehearse_cpu: bool):
    from symbiont_tpu.device import DeviceUnavailable, require_device

    try:
        info = require_device()
    except DeviceUnavailable as e:
        raise NoChip(str(e)) from e
    want = "cpu" if rehearse_cpu else "tpu"
    if info.platform != want or (not rehearse_cpu and info.count < chips):
        raise NoChip(f"need {chips} x {want}; jax found {info.count} x "
                     f"{info.device_kind} ({info.platform})")
    peaks = None if rehearse_cpu else yardstick.chip_peaks(info.device_kind)
    return info, peaks


class CompileCount:
    """Backend compiles, by jax's own monitoring event (a program loaded
    from the persistent cache is not one)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ------------------------------------------------------------- the stack

def stack_env(config: dict, model_dir, data_dir, state: Path,
              extra: dict) -> dict:
    subst = {"model_dir": str(model_dir), "data_dir": str(data_dir),
             "state": str(state)}
    env = {"SYMBIONT_API_HOST": "127.0.0.1", "SYMBIONT_API_PORT": "0"}
    for k, v in {**config["env"], **extra}.items():
        env[k] = str(v).format(**subst) if isinstance(v, str) else json.dumps(v)
    return env


def snapshot() -> dict:
    from symbiont_tpu.utils.telemetry import metrics

    return metrics.snapshot()


def counter(snap: dict, name: str) -> float:
    return sum(v for k, v in snap["counters"].items()
               if k == name or k.startswith(name + "{"))


async def wait_ready(port: int, want_fused_warm: bool) -> None:
    import client

    deadline = time.monotonic() + 1100
    while time.monotonic() < deadline:
        status, _ = await client.http(port, "GET", "/readyz", timeout=10)
        if status == 200:
            break
        await asyncio.sleep(0.2)
    else:
        raise RuntimeError("/readyz never answered 200")
    while want_fused_warm:
        snap = snapshot()
        if counter(snap, "engine.fused_warmups") >= 1:
            failed = snap["counters"].get(
                'engine.fused_warmups{result="failed"}', 0)
            if failed:
                raise RuntimeError("the program's fused warm-up failed")
            return
        if time.monotonic() > deadline:
            raise RuntimeError("fused warm-up did not settle")
        await asyncio.sleep(0.2)


class Client:
    """The child process and the three words it exchanges with us."""

    def __init__(self, plan: dict, state: Path, port: int):
        self.plan_path = state / "plan.json"
        self.out_path = state / "client.json"
        self.plan_path.write_text(json.dumps(plan))
        self.port = port
        self.proc = None

    async def start(self) -> None:
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "TPU_", "XLA_"))}
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, str(HERE / "client.py"), str(self.plan_path),
            str(self.out_path), str(self.port),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=env)

    async def expect(self, word: str, timeout: float) -> None:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if line.decode().strip() != word:
            await self.kill()
            raise RuntimeError(f"client said {line!r}, expected {word}")

    async def tell(self, word: str) -> None:
        self.proc.stdin.write((word + "\n").encode())
        await self.proc.stdin.drain()

    async def result(self, timeout: float) -> dict:
        await self.expect("DONE", timeout)
        await asyncio.wait_for(self.proc.wait(), 30)
        return json.loads(self.out_path.read_text())

    async def kill(self) -> None:
        if self.proc is not None and self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()


class Tracer:
    """A bounded profiler window inside the measured window, in a thread
    (start/stop block), bracketed by a host annotation the reducer finds."""

    def __init__(self, out_dir: Path, delay_s: float, length_s: float):
        self.out_dir, self.delay_s, self.length_s = out_dir, delay_s, length_s
        self.future = None

    def _run(self) -> None:
        import jax

        time.sleep(self.delay_s)
        jax.profiler.start_trace(str(self.out_dir))
        try:
            self.snap0 = snapshot()
            with jax.profiler.TraceAnnotation("benchmark.window"):
                time.sleep(self.length_s)
            self.snap1 = snapshot()
        finally:
            jax.profiler.stop_trace()

    def start(self) -> None:
        self.future = asyncio.get_running_loop().run_in_executor(
            None, self._run)

    async def reduced(self) -> dict:
        import trace_reduce

        await self.future
        red = trace_reduce.reduce(trace_reduce.find_xplane(self.out_dir))
        red["snap0"], red["snap1"] = self.snap0, self.snap1
        return red


# ----------------------------------------------------------------- a run

async def run_cell(args, bench: dict, *, hooks=None) -> dict:
    """Everything after the look for a chip. `hooks` (tests only) may carry
    `after_boot(stack)`, called once the stack is up, to break the timed
    path underneath a run."""
    cell, config, mix = find_cell(bench, args.workload)
    if args.rehearse_cpu:
        config = toy_view(config)
        mix = {**mix, **mix.get("toy", {})}
    info, peaks = args.device
    compiles = CompileCount()
    arch = artefacts.architecture(config)
    model = config["model"]
    state = artefacts.CACHE / "state" / args.workload
    if state.exists():
        shutil.rmtree(state)
    state.mkdir(parents=True)

    model_dir = artefacts.ensure_checkpoint(config, model, args.seed)
    data_dir = state / "vector_store"
    collection = config["env"].get("SYMBIONT_VECTOR_STORE_COLLECTION",
                                   "symbiont_document_embeddings")
    if "corpus" in config:
        data_dir = artefacts.ensure_corpus(
            config, config["corpus"], config["corpus"]["dim"], collection)
    t_art = time.monotonic() - T_START

    from symbiont_tpu.config import load_config
    from symbiont_tpu.runner import SymbiontStack

    if args.control == "cell":
        args.control = config["control"][mix["kind"]]
    extra = dict(config["controls"][args.control]) if args.control else {}
    cfg = load_config(env=stack_env(config, model_dir, data_dir, state,
                                    extra))
    stack = SymbiontStack(cfg)  # as runner.main(): own bus, own ApiService
    client = None
    try:
        await stack.start()
        port = stack.api.port
        await wait_ready(port, bool(config.get("wait_fused_warmup")))
        if mix.get("engine_warmup"):
            # the program's own warm-up entry, for every (length, batch)
            # bucket the role is configured with: a live micro-batcher can
            # form any of them
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: stack.engine.warmup(
                    buckets=cfg.engine.length_buckets,
                    batches=cfg.engine.batch_buckets))
        if hooks and hooks.get("after_boot"):
            hooks["after_boot"](stack)
        t_boot = time.monotonic() - T_START
        kind = traffic.load_kind(mix["kind"])
        plan = traffic.build_plan(mix, args.seed, args.seconds, model)
        client = Client(plan, state, port)
        await client.start()
        await client.expect("READY", 1100)

        if args.sweep:
            return await sweep(args, bench, cell, mix, model, state, port)
        # ------------------------------------------------ the window
        compiles_before = compiles.compiles
        snap0 = snapshot()
        tracer = None
        if args.trace:
            length = min(float(mix.get("trace_seconds", 4.0)),
                         max(args.seconds * 0.5, 0.5))
            tracer = Tracer(state / "trace", args.seconds * 0.25, length)
            tracer.start()
        if hasattr(kind, "window"):
            # a kind that paces itself opens and closes its own window
            window = await kind.window(stack, client.tell, args.seconds)
        else:
            t0 = time.monotonic()
            await client.tell("GO")
            window = {"t0": t0, "t1": t0 + args.seconds}
        setup_s = window["t0"] - T_START
        result = await client.result(args.seconds + 150)
        snap1 = snapshot()
        compiled_in_window = compiles.compiles - compiles_before
        if hasattr(kind, "settle"):
            await kind.settle(result, mix,
                              lambda name: counter(snapshot(), name))
        reduced = await tracer.reduced() if tracer else None
        peak = memory_peak_bytes()
    finally:
        if client is not None:
            await client.kill()
        await stack.stop()
    # free the program's state before the reference touches the device
    del stack
    gc.collect()
    import jax

    jax.clear_caches()

    # ------------------------------------------------- metrics and check
    ctx = {
        **window, "cell": cell, "config": config, "model": model, "mix": mix,
        "plan": plan, "client": result, "seed": args.seed,
        "window_s": window["t1"] - window["t0"], "seconds": args.seconds,
        "snap0": snap0, "snap1": snap1, "trace": reduced, "peaks": peaks,
        "setup_s": setup_s, "arch": arch, "yardstick": yardstick,
        "data_dir": data_dir, "collection": collection,
        "setup_parts": {"artefacts_s": t_art, "boot_and_warm_s": t_boot - t_art,
                        "client_warmup_s": setup_s - t_boot},
    }
    e2e = end_to_end(ctx, bench)
    numbers = check.compare(ctx)
    numbers["compiles_in_window"] = check.number(compiled_in_window, 0)
    attempted, failed = kind.attempted_failed(result)
    device = {"platform": info.platform, "kind": info.device_kind,
              "count": info.count, "memory_peak_bytes": peak}
    reported = set(e2e)
    if args.trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        values = {}
        for m in metrics_of(bench, "per_layer", cell["name"], reported):
            v = load_reader("layer_metrics", m["name"])(ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        values = {k: {"value": float(v), "unit": units[k]}
                  for k, v in e2e.items() if k in units}
    compared = {k: v for k, v in numbers.items() if not k.startswith("_")}
    out = {"correct": all(v["ok"] for v in compared.values()),
           "attempted": attempted, "failed": failed, "metrics": values,
           "device": device}
    if args.trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["setup_parts"] = ctx["setup_parts"]
    out["compared_sizes"] = {k: v for k, v in numbers.items()
                             if k.startswith("_")}
    out["control"] = args.control
    out["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in compared.items()}
    return out


async def sweep(args, bench: dict, cell: dict, mix: dict, model: dict,
                state: Path, port: int) -> dict:
    """One window at each offered rate, in this process (set-up paid once).
    The knee is the highest rate at which the backlog does not grow: the
    last quarter of the window waits no longer than the first, and the
    window drains soon after it closes."""
    rows = []
    key = traffic.load_kind(mix["kind"]).LATENCY_FIELD
    for k, rate in enumerate(float(r) for r in args.sweep.split(",")):
        m = {**mix, "rate_per_s": rate, "warmup_requests": 0,
             "warmup_prompt_tokens": [], "warmup_output_tokens": []}
        plan = traffic.build_plan(m, args.seed + k, args.seconds, model)
        c = Client(plan, state, port)
        await c.start()
        try:
            await c.expect("READY", 300)
            await c.tell("GO")
            res = await c.result(args.seconds + 200)
        finally:
            await c.kill()
        ctx = {"cell": cell, "mix": m, "client": res, "setup_s": 0.0,
               "yardstick": yardstick}
        recs = [r for r in res["records"] if r["ok"]]
        vals = [r[key] for r in recs if key in r]
        q = max(1, len(vals) // 4)
        done = [r.get("done_ms", r.get("latency_ms", 0.0)) for r in recs]
        due_ms = [d * 1e3 for d in plan["due"]]
        drain = max((due_ms[r["i"]] + d for r, d in zip(recs, done)),
                    default=0.0) / 1e3 - args.seconds
        row = {"rate": rate, "attempted": res["attempted"], "ok": len(recs),
               **{k2: v for k2, v in end_to_end(ctx, bench).items()
                  if k2 != "setup_s"},
               "first_quarter_mean_ms": sum(vals[:q]) / q if vals else None,
               "last_quarter_mean_ms": sum(vals[-q:]) / q if vals else None,
               "drain_s": drain,
               "late_p95_ms": yardstick.percentile(
                   [r["late_ms"] for r in res["records"]
                    if r.get("late_ms") is not None] or [0.0], 95)}
        print("sweep " + json.dumps(row), file=sys.stderr, flush=True)
        rows.append(row)
    return {"sweep": rows}


def end_to_end(ctx: dict, bench: dict) -> dict:
    """The cell's end-to-end metrics, each by its own reader, over ALL the
    work and ALL the time of the window, on the benchmark's own clocks.
    `setup_s` is the harness's own: process start -> window start."""
    out = {"setup_s": ctx["setup_s"]}
    for m in metrics_of(bench, "end_to_end", ctx["cell"]["name"], set()):
        if m["name"] != "setup_s":
            v = load_reader("end_to_end", m["name"])(ctx)
            if v is not None:
                out[m["name"]] = v
    return out


# ------------------------------------------------------------------- main

def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU; platform named cpu")
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (open-loop mixes): "
                         "one window of --seconds at each, in this one "
                         "process, to find the knee; prints a table, no "
                         "result line")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json than the checkout's (tests "
                         "of a cell that is not listed yet)")
    ap.add_argument("--control", default="",
                    help="switch on one of the configuration's lower-"
                         "precision paths (`controls`, by name; `cell` = "
                         "the one the configuration names as this kind of "
                         "cell's control): `correct` must come out false")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    bench = load_benchmark(args.benchmark_json)
    cell, _, _ = find_cell(bench, args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the flag IS the explicit ask
    # the compile cache sits at ONE fixed path inside the checkout unless
    # the environment names another; the program takes what it is given
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(artefacts.CACHE / "jax"))
    try:
        args.device = look_for_chip(cell["chips"], args.rehearse_cpu)
    except NoChip as e:
        print(f"benchmark: no chip: {e}", file=sys.stderr)
        return 3
    out = asyncio.run(run_cell(args, bench))
    if args.sweep:
        return 0
    for name, v in out["compared"].items():
        print(f"compared {name} = {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    print(f"correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
