#!/usr/bin/env bash
# What does a second runner do while the first holds the chip?
#
# A chip belongs to one process. This probe starts the stock runner, waits
# for /readyz, then starts a SECOND runner on another port and reports how it
# ended. With the device policy (symbiont_tpu/device.py) the second must exit
# non-zero naming the reason — it must never come up serving from the CPU.
# The answer recorded in docs/DEPLOYMENT.md ("One process per chip") came
# from this script on the v5e.
#
# Usage (on the machine with the chip): scripts/one_process_per_chip.sh [OUT]
# Prints one JSON line: {"second_rc": N, "second_served": bool, "reason": ...}
set -u
cd "$(dirname "$0")/.."
OUT="${1:-chiprun_out/one_process_per_chip}"
mkdir -p "$OUT"

run() {  # run <tag> <port>: the stock runner, state under $OUT/<tag>
  SYMBIONT_API_PORT="$2" \
  SYMBIONT_VECTOR_STORE_DATA_DIR="$OUT/$1/vs" \
  SYMBIONT_GRAPH_STORE_DATA_DIR="$OUT/$1/gs" \
  SYMBIONT_TEXT_GENERATOR_MARKOV_STATE_PATH="$OUT/$1/markov.json" \
  exec python -m symbiont_tpu.runner
}

ready() {  # ready <port>: 0 when /readyz answers 200
  python - "$1" <<'PY'
import sys, urllib.request
try:
    sys.exit(0 if urllib.request.urlopen(
        f"http://127.0.0.1:{sys.argv[1]}/readyz", timeout=2).status == 200
        else 1)
except Exception:
    sys.exit(1)
PY
}

( run first 18081 ) > "$OUT/first.log" 2>&1 &
FIRST=$!
trap 'kill "$FIRST" 2>/dev/null; wait "$FIRST" 2>/dev/null' EXIT
for _ in $(seq 1 120); do
  ready 18081 && break
  kill -0 "$FIRST" 2>/dev/null || { echo "first runner died" >&2; tail -5 "$OUT/first.log" >&2; exit 1; }
  sleep 1
done
ready 18081 || { echo "first runner never became ready" >&2; exit 1; }

( run second 18082 ) > "$OUT/second.log" 2>&1 &
SECOND=$!
SERVED=false
for _ in $(seq 1 90); do
  kill -0 "$SECOND" 2>/dev/null || break
  if ready 18082; then SERVED=true; break; fi
  sleep 1
done
if kill -0 "$SECOND" 2>/dev/null; then
  kill "$SECOND"; wait "$SECOND"; RC="still-running"
else
  wait "$SECOND"; RC=$?
fi
python - "$RC" "$SERVED" "$OUT/second.log" <<'PY'
import json, sys
rc, served, log = sys.argv[1:]
lines = [ln.strip() for ln in open(log) if ln.strip()]
print(json.dumps({"second_rc": int(rc) if rc.lstrip("-").isdigit() else rc,
                  "second_served": served == "true",
                  "reason": lines[-1][-600:] if lines else ""}))
PY
