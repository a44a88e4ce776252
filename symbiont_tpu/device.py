"""One device policy per process: a TPU, or a CPU that was asked for.

Every entry point that is about to put real work on a device —
`SymbiontStack.start` before it builds an engine, the bench CLI,
`scripts/profile_decode.py`, `chip_smoke.py` — calls `require_device()`
first. It resolves the JAX backend once and refuses to carry on when the
platform is anything but `tpu`, unless `JAX_PLATFORMS=cpu` was set
explicitly (tests and the CPU recipes in `scripts/*.sh` do). Without this,
a process that cannot reach the chip (none attached, or another process
holds it) gets `[CpuDevice(id=0)]` back from `jax.devices()` with no error
and serves or measures from the host.

The same call places JAX's persistent compilation cache. Where
`JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and no directory is
set here. Where it is unset, the cache goes to ONE fixed directory inside
the checkout (`<repo>/.jax_cache`, git-ignored). The directory is part of
the cache key, so it must never be a temporary name, a pid or a time. On a
TPU the write threshold is lowered to zero so every compile is cached.

This module imports jax lazily and is imported only by device-owning
processes: `symbiont_tpu/__init__.py`, `config`, `deploy` and the
`resilience` supervisors stay jax-free (a parent that touches jax holds
the chip its children need).
"""

from __future__ import annotations

import functools
import importlib.metadata
import logging
import os
from pathlib import Path
from typing import NamedTuple

log = logging.getLogger(__name__)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


class DeviceUnavailable(RuntimeError):
    """The process resolved to a platform it was not asked to run on."""


class DeviceInfo(NamedTuple):
    platform: str
    device_kind: str
    count: int
    jax: str
    jaxlib: str
    libtpu: str

    def report(self) -> dict:
        """The fields every benchmark/smoke result line carries."""
        return {"platform": self.platform, "device_kind": self.device_kind,
                "device_count": self.count, "jax": self.jax,
                "jaxlib": self.jaxlib, "libtpu": self.libtpu}


def compile_cache_dir() -> str:
    """Where this process keeps its persistent compile cache."""
    return os.environ.get(CACHE_ENV) or str(REPO_CACHE_DIR)


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


@functools.lru_cache(maxsize=1)
def require_device() -> DeviceInfo:
    """Resolve the device (once per process), place the compile cache, log
    what was found. Raises DeviceUnavailable naming the platform when it is
    not a TPU and the CPU was not explicitly requested."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    try:
        devices = jax.devices()
    except RuntimeError as e:  # e.g. the chip is held by another process
        raise DeviceUnavailable(
            f"jax could not initialise a backend with "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}: {e}") from e
    dev = devices[0]
    cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform != "tpu" and not (dev.platform == "cpu" and cpu_asked):
        raise DeviceUnavailable(
            f"no TPU: jax resolved platform={dev.platform!r} "
            f"({len(devices)} x {dev.device_kind}) with "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. Either no "
            "chip is attached or another process holds it (one process per "
            "chip). Set JAX_PLATFORMS=cpu explicitly to run on the CPU.")
    if (dev.platform == "tpu"
            and "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ):
        # jax only writes compiles slower than 1 s by default. On the v5e a
        # stack boot makes ~80 faster ones (~15 s together, my chip run,
        # PR 21), and a compile hovering around the threshold is written on
        # one boot and not the next — so on the chip everything is cached,
        # and a warm boot writes nothing new. CPU runs keep jax's default.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info = DeviceInfo(dev.platform, dev.device_kind, len(devices),
                      jax.__version__, _version("jaxlib"), _version("libtpu"))
    log.info("device: %d x %s (%s); jax %s jaxlib %s libtpu %s; "
             "compile cache %s", info.count, info.device_kind, info.platform,
             info.jax, info.jaxlib, info.libtpu, compile_cache_dir())
    return info
