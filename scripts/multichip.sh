#!/usr/bin/env bash
# Multi-chip serving plane: parity tests + the `multichip` bench tier on 8
# SIMULATED host devices (docs/SCALING.md). A CPU PLUMBING CHECK, not a chip
# measurement: it sets JAX_PLATFORMS=cpu itself, XLA splits the host CPU
# into 8 virtual devices, and the sharded code paths (DP embed over 'data',
# per-shard top-k + global merge, TP decode collectives) execute with the
# same program structure as on real chips. The emitted line says
# "platform": "cpu". On real chips the same paths were first met by
# `python chip_smoke.py --mesh ...` on the four-chip v5e host (root PERF.md).
#
#   scripts/multichip.sh                # parity suite + multichip tier
#   scripts/multichip.sh --tests-only   # just the tier-1 parity suite
#   scripts/multichip.sh --mesh dp4xtp2 # tier at a specific mesh shape
#
# NOTE on the numbers: simulated devices share the same cores, so the
# mc_scale_efficiency_* values are bounded by ~1/n here and only prove the
# plumbing — they are CPU numbers and are never quoted as device metrics.
# The parity gates (identical search results, token-identical decode) are
# hard everywhere.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu  # simulated devices exist on the CPU backend only
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

mesh_args=()
tests_only=0
for arg in "$@"; do
  case "$arg" in
    --tests-only) tests_only=1 ;;
    --mesh) mesh_args+=(--mesh) ;;
    *) [[ ${#mesh_args[@]} -eq 1 ]] && mesh_args+=("$arg") ;;
  esac
done

echo "== multichip parity suite (platform=$JAX_PLATFORMS, 8 simulated devices) ==" >&2
python -m pytest tests/test_multichip_serving.py -q

if [[ "$tests_only" -eq 1 ]]; then
  exit 0
fi

echo "== multichip bench tier (platform=$JAX_PLATFORMS: a CPU plumbing check) ==" >&2
exec python bench.py --only multichip "${mesh_args[@]}"
