#!/usr/bin/env bash
# Contract linter: the static-analysis pass over the repo's own invariants
# (docs/LINTING.md) — subject wiring, event-loop blocking calls, lock
# ordering, JAX recompile hygiene, C++ wire-contract parity, knob/doc
# drift. Device-free and fast (~2s); run it pre-merge.
#
#   scripts/lint.sh                       # the whole pass (CI entrypoint)
#   scripts/lint.sh --rules cpp-parity    # one rule family
#   scripts/lint.sh --list                # rule catalog
#   scripts/lint.sh --tests               # + the pytest proof suite (-m lint)
#
# Exit codes: 0 clean, 1 findings (incl. stale allowlist entries), 2 usage.
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--tests" ]]; then
    python -m symbiont_tpu.lint
    # the proof suite: every rule fires on seeded fixtures, the allowlist
    # ratchet trips, the repo stays clean (tests/test_lint.py + the
    # pipeline-wiring shim)
    exec python -m pytest tests/ -m lint -q -p no:cacheprovider
fi
exec python -m symbiont_tpu.lint "$@"
