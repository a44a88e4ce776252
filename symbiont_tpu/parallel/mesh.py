"""Mesh construction over whatever devices the runtime exposes.

Axes:
- "data"   — batch sharding (DP); embedding throughput scales on this axis.
- "tensor" — parameter sharding (TP) for decoder LMs too big for one chip.

PP/SP are deliberately *pluggable, not default*: the mesh helper accepts
arbitrary extra axes so a pipeline or sequence axis can be added without
touching call sites (SURVEY.md §2: PP "design mesh axes so PP can be added").
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def local_device_count() -> int:
    return len(jax.devices())


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> int:
    """Multi-host bring-up: one engine process per host in a TPU slice.

    Thin wrapper over `jax.distributed.initialize` — on TPU pods the runtime
    discovers coordinator/process topology itself, so all arguments are
    optional (pass them explicitly only for non-TPU backends or tests). After
    this, `jax.devices()` spans the whole slice and `build_mesh` meshes over
    it; XLA collectives ride ICI within a host block and DCN between hosts.
    Env override: SYMBIONT_COORDINATOR / SYMBIONT_NUM_PROCESSES /
    SYMBIONT_PROCESS_ID. Returns the global device count.

    Safe to call when already initialized (a second call is a no-op)."""
    import os

    coordinator = coordinator or os.environ.get("SYMBIONT_COORDINATOR")
    if num_processes is None and "SYMBIONT_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["SYMBIONT_NUM_PROCESSES"])
    if process_id is None and "SYMBIONT_PROCESS_ID" in os.environ:
        process_id = int(os.environ["SYMBIONT_PROCESS_ID"])
    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise
    return len(jax.devices())


def build_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = ("data", "tensor"),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh.

    shape=None → all devices on the first axis, 1 on the rest (pure DP, the
    right default for the embedding models: MiniLM..e5-large all fit a single
    v5e chip's HBM; TP is for LMs). An explicit shape smaller than the host
    takes the FIRST prod(shape) devices — a one-chip stack (`[1, 1]`) can be
    asked for on a four-chip host without hiding chips; a shape larger than
    the host is an error.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shape is None:
        shape = [n] + [1] * (len(axis_names) - 1)
    shape = list(shape)
    want = int(np.prod(shape))
    if want > n:
        raise ValueError(f"mesh shape {shape} needs {want} devices, "
                         f"only {n} present")
    dev_array = np.asarray(devices[:want]).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


def parse_mesh_spec(spec: str) -> list:
    """`"dp4xtp2"` → [4, 2] (also plain `"4x2"`, or `"8"` for pure DP).

    The human-facing mesh shorthand of the bench CLI's `--mesh` knob and
    docs/SCALING.md: `dp<N>` is the 'data' axis, `tp<N>` the 'tensor' axis,
    in that order. Kept here (not in bench/) so deployment tooling can share
    the exact same parse."""
    import re

    s = spec.strip().lower()
    m = re.fullmatch(r"dp(\d+)(?:xtp(\d+))?", s)
    if m:
        return [int(m.group(1)), int(m.group(2) or 1)]
    m = re.fullmatch(r"tp(\d+)", s)
    if m:
        return [1, int(m.group(1))]
    m = re.fullmatch(r"(\d+)(?:x(\d+))?", s)
    if m:
        return [int(m.group(1)), int(m.group(2) or 1)]
    raise ValueError(
        f"mesh spec {spec!r} not understood: use dpNxtpM, dpN, tpM, NxM or N")


def mesh_from_config(parallel_cfg) -> Mesh:
    """THE production mesh constructor (ROADMAP item 1): build the serving
    mesh purely from `ParallelConfig` — `mesh_shape` unset means all local
    devices on the 'data' axis, 1 on the rest. The runner calls this once at
    stack start and threads the result through TpuEngine, LmEngine, and the
    vector store; no caller ever hands a mesh in by hand to go multi-chip."""
    return build_mesh(parallel_cfg.mesh_shape,
                      tuple(parallel_cfg.axis_names))
