"""Test harness setup.

Multi-chip testing without a real pod: force the JAX CPU backend with 8 virtual
devices (SURVEY.md §4 item 4) so sharding/collective tests exercise a real
8-device mesh. Must run before the first `import jax` anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

# Two test tiers (VERDICT r3 item 7): `pytest -m "not slow"` is the fast
# tier (<2 min on CPU — logic, schema, stores, bus, numerics goldens);
# the slow tier adds compile-heavy JAX modules, multi-process/native
# integration, and e2e pipelines. Whole modules are marked here so the
# split can't silently rot as tests are added to existing files.
SLOW_MODULES = {
    "test_e2e_pipeline",     # full-stack async pipelines, many engines
    "test_multihost",        # spawns real OS processes for collectives
    "test_parallel",         # ring/Ulysses/GPipe: many XLA compiles
    "test_native_services",  # builds C++ tree, spawns broker + workers
    "test_engine",           # dozens of (bucket, batch) executables
    "test_lm_engine",        # decode-loop compiles per geometry
    "test_train",            # train-step compiles + checkpoint I/O
    "test_online_train",     # fine-tune passes on device
    "test_qdrant_backend",   # includes a full-stack pipeline run
    "test_ops_flash",        # pallas kernel compiles fwd+bwd
    "test_gpt_numerics",     # transformers goldens + decode compiles
    "test_engine_service",   # engine-plane request-reply over real engines
    "test_tcp_bus",          # broker build + socket timing waits
    "test_durable_streams",  # broker build + redelivery ack_wait sleeps
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = item.nodeid.split("::", 1)[0].rsplit("/", 1)[-1]
        if module.removesuffix(".py") in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def tmp_data_dir(tmp_path):
    return tmp_path


# Native build selection shared by the broker/worker test modules.
# SYMBIONT_NATIVE_BUILD=build-tsan SYMBIONT_NATIVE_MAKE_TARGET=tsan runs them
# against ThreadSanitizer builds (see native/Makefile).
from pathlib import Path as _Path  # noqa: E402

_REPO = _Path(__file__).resolve().parent.parent
NATIVE_MAKE_TARGET = os.environ.get("SYMBIONT_NATIVE_MAKE_TARGET", "all")


def native_bin(name: str) -> str:
    build = os.environ.get("SYMBIONT_NATIVE_BUILD", "build")
    return str(_REPO / "native" / build / name)
