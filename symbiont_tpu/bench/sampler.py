"""Per-process resource sampler for the full-stack tier.

A claim like "the e2e-ingest floor is one shared host core running every
byte of 15 processes" is unfalsifiable without a measurement behind it.
This sampler snapshots `/proc/<pid>/stat`
(utime+stime) and `/proc/<pid>/io` (rchar+wchar — syscall-level bytes, which
on socket-only workers like the broker is bus traffic) around a measured
window, so the archive carries the decomposition: CPU seconds per worker
role (broker, gateway, perception, preprocessing replicas, vector_memory,
and the Python engine-host process itself) plus broker bytes/s. If the host
core is saturated the archive shows it; if not, the next lever is exposed.

Linux-only by construction (/proc); on anything else `stop()` returns {} and
the e2e tier archives no decomposition rather than failing.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Iterable, List, Optional

_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
_HAS_PROC = os.path.exists("/proc/self/stat")


def _proc_cpu_s(pid: int) -> Optional[float]:
    """utime+stime of one pid in seconds, None when gone/unsupported."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            stat = f.read().decode("ascii", "replace")
        # field 2 (comm) may contain spaces/parens: split after the last ')'
        fields = stat.rsplit(")", 1)[1].split()
        utime, stime = int(fields[11]), int(fields[12])
        return (utime + stime) / _CLK_TCK
    except (OSError, IndexError, ValueError):
        return None


def _proc_io_bytes(pid: int) -> Optional[int]:
    """rchar+wchar of one pid (all read/write syscalls incl. sockets)."""
    try:
        with open(f"/proc/{pid}/io", "rb") as f:
            vals = dict(line.split(b":") for line in f.read().splitlines())
        return int(vals[b"rchar"]) + int(vals[b"wchar"])
    except (OSError, KeyError, ValueError):
        return None


class ResourceSampler:
    """Snapshot-based accounting over a measured window.

    `roles` maps a role name ("broker", "preprocessing", ...) to its pids;
    replicas under one role are summed. The driving Python process (engine
    host thread, bus clients, vector store) is always accounted under
    "engine_host" via os.times() — children are separate processes, so this
    is exactly the host-side engine-plane cost."""

    def __init__(self, roles: Dict[str, Iterable[int]]):
        self.roles = {name: list(pids) for name, pids in roles.items()}
        self._t0: Optional[float] = None
        self._cpu0: Dict[str, float] = {}
        self._io0: Dict[str, int] = {}
        self._self0 = 0.0

    def _snapshot_cpu(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, pids in self.roles.items():
            vals = [v for v in (_proc_cpu_s(p) for p in pids)
                    if v is not None]
            if vals:
                out[name] = sum(vals)
        return out

    def _snapshot_io(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for name, pids in self.roles.items():
            vals = [v for v in (_proc_io_bytes(p) for p in pids)
                    if v is not None]
            if vals:
                out[name] = sum(vals)
        return out

    def start(self) -> "ResourceSampler":
        self._t0 = time.time()
        self._cpu0 = self._snapshot_cpu()
        self._io0 = self._snapshot_io()
        t = os.times()
        self._self0 = t.user + t.system
        return self

    def stop(self) -> Dict[str, float]:
        """Deltas over the window: `cpu_s_<role>` seconds per role,
        `cpu_s_engine_host` for the driving process, `io_bytes_<role>`
        syscall bytes per role, and `wall_s`. Empty dict off-Linux."""
        if self._t0 is None:
            raise RuntimeError("stop() before start()")
        if not _HAS_PROC:
            # non-Linux: return nothing rather than an engine-host-only
            # "decomposition" that claims to account for every worker
            # while silently excluding all of them (dead pids on Linux are
            # different: their roles are simply absent from the window)
            return {}
        wall = time.time() - self._t0
        out: Dict[str, float] = {}
        cpu1 = self._snapshot_cpu()
        for name, v0 in self._cpu0.items():
            if name in cpu1:
                out[f"cpu_s_{name}"] = round(cpu1[name] - v0, 2)
        io1 = self._snapshot_io()
        for name, v0 in self._io0.items():
            if name in io1:
                out[f"io_bytes_{name}"] = io1[name] - v0
        t = os.times()
        out["cpu_s_engine_host"] = round(t.user + t.system - self._self0, 2)
        out["wall_s"] = round(wall, 2)
        return out


def archive_decomposition(results: dict, prefix: str,
                          window: Dict[str, float]) -> None:
    """Flatten a sampler window into archive fields: `<prefix>_cpu_s_<role>`,
    `<prefix>_bus_mb_per_s` (broker syscall bytes over the wall — every bus
    frame crosses the broker twice, in and out), `<prefix>_host_cpu_total_s`
    and `<prefix>_host_cpu_utilization` (total CPU over wall: ~1.0 means the
    one shared host core IS the wall, the floor claim measured)."""
    if not window:
        return
    wall = window.get("wall_s", 0.0)
    # the utilization denominator must itself be archived, or the doc would
    # quote a different wall next to the ratio computed over this one
    results[f"{prefix}_wall_s"] = wall
    total_cpu = 0.0
    for key, v in window.items():
        if key.startswith("cpu_s_"):
            results[f"{prefix}_{key}"] = v
            total_cpu += v
    broker_bytes = window.get("io_bytes_broker")
    if broker_bytes is not None and wall > 0:
        results[f"{prefix}_bus_mb_per_s"] = round(broker_bytes / wall / 1e6, 2)
    results[f"{prefix}_host_cpu_total_s"] = round(total_cpu, 2)
    if wall > 0:
        results[f"{prefix}_host_cpu_utilization"] = round(total_cpu / wall, 3)
