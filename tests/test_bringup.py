"""Bring-up contracts (ISSUE 21): the program runs on a TPU or on a CPU that
was asked for — never on a CPU it fell back to — and says which.

- `chip_smoke.py` without a chip exits non-zero naming the platform it
  found and prints no result; its `--rehearse-cpu` mode passes at toy width
  and reports `platform=cpu`.
- `symbiont_tpu.device.require_device` refuses a CPU that was not asked for
  and accepts `JAX_PLATFORMS=cpu`; the runner and the bench CLI sit on it.
- The compile cache resolves to `JAX_COMPILATION_CACHE_DIR` when set and to
  the one fixed in-checkout path when not, identically across processes; a
  second process gets cache hits through both the jit and the AOT seam.
- The peak table is exact-keyed and errors on an unknown `device_kind`.
- The supervisors stay jax-free (a parent that touches jax holds the chip).
- The ways off the compiled path announce themselves or raise.
"""

import asyncio
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from symbiont_tpu import device as device_mod
from symbiont_tpu.device import DeviceUnavailable, require_device
from symbiont_tpu.utils.telemetry import metrics

REPO = Path(__file__).resolve().parent.parent


def _env(**overrides) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(overrides)
    return env


@pytest.fixture()
def fresh_policy():
    """require_device() memoizes per process; tests that flip the
    environment need it re-evaluated, and restored afterwards."""
    require_device.cache_clear()
    yield
    require_device.cache_clear()


# ------------------------------------------------------------ chip_smoke.py

@pytest.mark.parametrize("platforms", [None, "cpu"])
def test_chip_smoke_without_a_chip_fails_naming_the_platform(platforms):
    """No accelerator: non-zero exit, NO result on stdout, and the message
    names the platform jax found — whether the CPU was a silent fallback
    (JAX_PLATFORMS unset) or asked for (the smoke still needs the chip;
    only --rehearse-cpu may run there)."""
    env = _env() if platforms is None else _env(JAX_PLATFORMS=platforms)
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform='cpu'" in proc.stderr, proc.stderr[-2000:]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it must fail, not pass
    vacuously."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    env = _env(JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-cpu"], env=env,
        capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_rehearsal_passes_at_toy_width(tmp_path):
    """The explicit CPU rehearsal drives the same phases at toy width, says
    platform=cpu, claims nothing, keeps its state under --out and its
    compile cache where JAX_COMPILATION_CACHE_DIR points."""
    cache = tmp_path / "cache"
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse-cpu",
         "--out", str(out)],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": last["device"]["count"]}}
    report = json.loads(lines[-2])
    assert report["rehearsal"] is True and report["ok"] is True
    assert list(report)[-1] == "claim" and report["claim"] is None
    assert report["device"]["platform"] == "cpu"
    assert report["compile_cache"] == {
        **report["compile_cache"], "dir": str(cache), "from_env": True}
    assert report["ingest"]["rows"] == report["ingest"]["sentences"] > 0
    gen = report["generate"]
    assert gen["repeat_equals_first"] is True and gen["radix_hit_tokens"] > 0
    assert max(gen["batched_ref_gap_max"],
               gen["streamed_ref_gap_max"]) <= gen["tie_tol"]
    assert report["counters"]["api.fused_search"] >= 3
    assert report["counters"]["api.fused_search_fallback"] == 0
    assert json.loads((out / "report.json").read_text()) == report
    # state under --out; nothing of the run under the working directory
    assert (out / "state" / "vector_store").is_dir()
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache", "out"]


# ------------------------------------------------------------ device policy

def test_device_policy_refuses_a_cpu_nobody_asked_for(monkeypatch,
                                                      fresh_policy):
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(DeviceUnavailable, match="platform='cpu'"):
        require_device()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = require_device()
    assert info.platform == "cpu" and info.count >= 1
    assert info.report()["device_kind"] == info.device_kind
    assert require_device() is info  # resolved once per process


def test_runner_refuses_to_build_an_engine_on_a_fallback_cpu(
        monkeypatch, fresh_policy, tmp_path):
    """`python -m symbiont_tpu.runner` with no chip and no explicit
    JAX_PLATFORMS=cpu must not serve from the host: start() raises where it
    is about to build the real engine, and stop() still cleans up."""
    from symbiont_tpu.config import load_config
    from symbiont_tpu.runner import SymbiontStack

    monkeypatch.delenv("JAX_PLATFORMS")
    cfg = load_config(env={
        "SYMBIONT_API_PORT": "0",
        "SYMBIONT_VECTOR_STORE_DATA_DIR": str(tmp_path / "vs"),
        "SYMBIONT_GRAPH_STORE_DATA_DIR": str(tmp_path / "gs"),
        "SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH": str(tmp_path / "m")})

    async def scenario():
        stack = SymbiontStack(cfg)
        try:
            with pytest.raises(DeviceUnavailable):
                await stack.start()
            assert stack.engine is None and stack.lm is None
        finally:
            await stack.stop()

    asyncio.run(scenario())


def test_bench_cli_refuses_a_fallback_cpu(monkeypatch, fresh_policy, capsys):
    """`python bench.py --quick` without a chip exits 3 naming the platform
    instead of measuring the CPU under device metric names."""
    from symbiont_tpu.bench import cli

    monkeypatch.delenv("JAX_PLATFORMS")
    assert cli.main(["--quick"]) == 3
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "platform='cpu'" in captured.err


# ------------------------------------------------------------ compile cache

def test_cache_dir_env_wins_else_fixed_in_checkout_path(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device_mod.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert device_mod.compile_cache_dir() == str(REPO / ".jax_cache")
    # one assignment of the jax option in the whole tree, and it uses the
    # fixed path — never a temporary name, a pid or a time
    hits = [p for p in (REPO / "symbiont_tpu").rglob("*.py")
            if "jax_compilation_cache_dir" in p.read_text()]
    assert [p.name for p in hits] == ["device.py"]
    assert (REPO / ".jax_cache").name + "/" in (
        REPO / ".gitignore").read_text().split()


_CACHE_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
from symbiont_tpu.device import compile_cache_dir, require_device
out = {{"dir": compile_cache_dir()}}
if {compile}:
    from collections import Counter
    require_device()
    import jax, jax.numpy as jnp
    from jax import monitoring
    ev = Counter()
    monitoring.register_event_listener(lambda e, **kw: ev.update([e]))
    x = jnp.ones((32, 32))
    jax.jit(lambda x: jnp.sin(x) @ x.T)(x).block_until_ready()
    after_jit = ev["/jax/compilation_cache/cache_hits"]
    jax.jit(lambda x: jnp.cos(x) @ x.T).lower(x).compile()(x)
    out["jit_hits"] = after_jit
    out["aot_hits"] = ev["/jax/compilation_cache/cache_hits"] - after_jit
    out["configured"] = jax.config.jax_compilation_cache_dir
print(json.dumps(out))
"""


def _probe(env: dict, compile_: bool) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c",
         _CACHE_PROBE.format(repo=str(REPO), compile=compile_)],
        env=env, capture_output=True, text=True, timeout=300, cwd="/")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_path_identical_across_processes_when_env_unset():
    a = _probe(_env(JAX_PLATFORMS="cpu"), compile_=False)
    b = _probe(_env(JAX_PLATFORMS="cpu"), compile_=False)
    assert a == b == {"dir": str(REPO / ".jax_cache")}


def test_second_process_hits_the_cache_through_jit_and_aot(tmp_path):
    """The directory is part of the key: two processes resolving the same
    directory share entries, and the AOT seam (`lowered.compile()`, the
    engine's dispatch path) hits the same cache `jit` does."""
    env = _env(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               # CPU toy compiles are sub-second; jax only writes entries
               # slower than this threshold (default 1 s)
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    first = _probe(env, compile_=True)
    assert first["configured"] == str(tmp_path / "cc")  # env: code set none
    assert first["jit_hits"] == first["aot_hits"] == 0
    entries = sorted(p.name for p in (tmp_path / "cc").iterdir())
    assert entries
    second = _probe(env, compile_=True)
    assert second["jit_hits"] >= 1 and second["aot_hits"] >= 1
    assert sorted(p.name for p in (tmp_path / "cc").iterdir()) == entries


# --------------------------------------------------------------- peak table

def test_peak_table_is_exact_keyed_and_errors_on_unknown_kinds():
    from symbiont_tpu.bench.workload import CHIP_PEAKS, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    for kind in ("TPU v5", "TPU v5p", "tpu v5 lite", "TPU v5 lite pod",
                 "cpu", ""):
        assert kind not in CHIP_PEAKS
        with pytest.raises(ValueError, match="not in bench/workload"):
            chip_peaks(kind)


# ------------------------------------------------------ one process per chip

def test_supervisor_parents_stay_jax_free():
    """procsup / autoscale / config / deploy are imported by parents whose
    CHILDREN own the chip; importing them must not pull jax in."""
    code = ("import sys; sys.path.insert(0, %r); "
            "import symbiont_tpu.resilience.procsup, "
            "symbiont_tpu.resilience.autoscale, symbiont_tpu.config, "
            "symbiont_tpu.deploy; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith(('jax.', 'jaxlib'))]; "
            "assert not bad, bad" % str(REPO))
    proc = subprocess.run([sys.executable, "-c", code], env=_env(),
                          capture_output=True, text=True, timeout=120,
                          cwd="/")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_mesh_smaller_than_the_host_takes_the_first_devices():
    import jax

    from symbiont_tpu.parallel.mesh import build_mesh

    devs = jax.devices()
    assert len(devs) >= 4  # conftest: 8 virtual CPU devices
    one = build_mesh([1, 1])
    assert list(one.devices.flat) == devs[:1]
    four = build_mesh([2, 2])
    assert list(four.devices.flat) == devs[:4]
    assert dict(build_mesh().shape) == {"data": len(devs), "tensor": 1}
    with pytest.raises(ValueError, match="only %d present" % len(devs)):
        build_mesh([len(devs) + 1, 1])


# --------------------------------------------------- fallbacks loud or gone

def _qkv(nh=2, nkv=2, s=16, d=8):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(ks[0], (1, nh, s, d), jnp.float32),
            jax.random.normal(ks[1], (1, nkv, s, d), jnp.float32),
            jax.random.normal(ks[2], (1, nkv, s, d), jnp.float32))


def test_flash_interpreter_on_a_tpu_backend_is_an_error(monkeypatch):
    import jax

    from symbiont_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True.*tpu"):
        flash_attention(q, k, v, interpret=True)
    # a TPU reached under any other platform name must not silently get
    # the interpreter either
    monkeypatch.setattr(jax, "default_backend", lambda: "rocm")
    with pytest.raises(RuntimeError, match="neither 'tpu'"):
        flash_attention(q, k, v)


def _flash_counts() -> dict:
    return {k.split('path="')[1].rstrip('"}'): v
            for k, v in metrics.snapshot()["counters"].items()
            if k.startswith("flash.fallback")}


def test_flash_ways_off_the_kernel_announce_themselves(caplog):
    import jax

    from symbiont_tpu.ops.flash_attention import flash_attention

    before = _flash_counts()

    def delta(path):
        return _flash_counts().get(path, 0) - before.get(path, 0)

    with caplog.at_level("WARNING", logger="symbiont_tpu.ops.flash_attention"):
        q, k, v = _qkv()
        flash_attention(q, k, v)  # CPU backend: the interpreter, announced
        assert delta("interpreter") == 1
        q7, k7, v7 = _qkv(s=7)  # untileable: dense route
        flash_attention(q7, k7, v7)
        assert delta("dense_untileable") == 1
        qg, kg, vg = _qkv(nh=4, nkv=2)  # GQA backward: dense recompute
        jax.grad(lambda q: flash_attention(q, kg, vg).sum())(qg)
        assert delta("dense_gqa_backward") == 1
    said = " ".join(r.getMessage() for r in caplog.records)
    for path in ("interpreter", "dense_untileable", "dense_gqa_backward"):
        assert path in said


def test_engine_compiles_a_raced_cold_executable_once(monkeypatch):
    """Two threads hitting one cold (kind, L, B): the loser waits for the
    winner's AOT compile and dispatches through the same Compiled — no
    second compile under jit, and no way back to jit afterwards."""
    from symbiont_tpu.config import EngineConfig
    from symbiont_tpu.engine import engine as engine_mod

    eng = engine_mod.TpuEngine(EngineConfig(
        embedding_dim=32, length_buckets=[16], batch_buckets=[4],
        max_batch=4, dtype="float32", data_parallel=False))
    calls = []
    gate = threading.Event()
    real = engine_mod.compile_analysis_for

    def slow_compile(jitted, args):
        calls.append(threading.current_thread().name)
        gate.wait(5)  # hold the compile open until both threads are in
        return real(jitted, args)

    monkeypatch.setattr(engine_mod, "compile_analysis_for", slow_compile)
    outs = {}

    def run(name):
        outs[name] = eng.embed_texts(["one short sentence"])

    threads = [threading.Thread(target=run, args=(n,), name=n)
               for n in ("a", "b")]
    for t in threads:
        t.start()
    threading.Timer(0.5, gate.set).start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert len(calls) == 1
    np.testing.assert_array_equal(outs["a"], outs["b"])
    assert eng.stats["compiles"] == 1


def test_engine_compile_error_propagates_and_is_not_retried_under_jit(
        monkeypatch):
    from symbiont_tpu.config import EngineConfig
    from symbiont_tpu.engine import engine as engine_mod

    eng = engine_mod.TpuEngine(EngineConfig(
        embedding_dim=32, length_buckets=[16], batch_buckets=[4],
        max_batch=4, dtype="float32", data_parallel=False))
    calls = []

    def broken(jitted, args):
        calls.append(1)
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(engine_mod, "compile_analysis_for", broken)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        eng.embed_texts(["x"])
    assert calls == [1]  # paid once, reported once — no quiet jit re-run


def test_failed_fused_warmup_is_counted_not_only_logged():
    """A warm-up that throws keeps the process serving (2-hop path), so the
    COUNTER is what a smoke or a scraper can see."""
    from symbiont_tpu.bus.inproc import InprocBus
    from symbiont_tpu.services.engine_service import EngineService

    class _Store:
        supports_fused = True

        def __init__(self, fail):
            self.fail = fail

        def warm_fused(self, engine):
            if self.fail:
                raise RuntimeError("compile blew up")

        def fused_warm_stale(self):
            return False

    def count(result):
        return metrics.snapshot()["counters"].get(
            'engine.fused_warmups{result="%s"}' % result, 0)

    class _Engine:
        def warm_rerank(self):
            pass

    async def scenario(fail):
        svc = EngineService(InprocBus(), engine=_Engine(), batcher=object(),
                            vector_store=_Store(fail), coalesce=False)
        svc._spawn_fused_warm()
        await svc._warm_task
        return svc._warm_failed

    failed0, ok0 = count("failed"), count("ok")
    assert asyncio.run(scenario(fail=True)) is True
    assert (count("failed"), count("ok")) == (failed0 + 1, ok0)
    assert asyncio.run(scenario(fail=False)) is False
    assert (count("failed"), count("ok")) == (failed0 + 1, ok0 + 1)
