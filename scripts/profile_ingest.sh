#!/usr/bin/env bash
# Localize a host-overlap regression in ONE command (ROADMAP item 3, the
# overlap-everything ingest rework): where does e2e ingest time actually go?
#
#   scripts/profile_ingest.sh                  # run the bench e2e tier
#       (full stack: native broker + C++ workers + engine plane), then
#       print the archived "where the time goes" ingest stage shares, the
#       e2e÷bulk ratio vs the ≥0.6 target, and the overlap/coalesce stats.
#
#   scripts/profile_ingest.sh localhost:8080   # against a RUNNING stack:
#       pick the slowest recent ingest trace from GET /api/traces and print
#       its critical path — per-hop self-times, the dominant-hop verdict,
#       and gap_ms (untraced time: bus queueing / scheduling / span-less
#       native hops). A growing gap_ms is host overlap regressing.
#
#   scripts/profile_ingest.sh --decode [host:port]   # against a RUNNING
#       stack (default localhost:8080): print the newest engine-timeline
#       summary (GET /api/engine/timeline, obs/engine_timeline.py) the way
#       the ingest mode prints hop self-times — decode batch occupancy,
#       stranded KV rows, prefix share, TTFT/TPOT, embed packing
#       opportunity, and the dominant-stall verdict.
#
#   scripts/profile_ingest.sh --memory [host:port]   # against a RUNNING
#       stack (default localhost:8080): print the HBM attribution plane
#       (GET /api/memory + /api/memory/census, obs/hbm.py) — per-subsystem
#       byte ledger, per-device bytes-in-use/limit/headroom, the
#       unattributed residual, the last OOM verdict, and the top
#       live-array census groups.
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${1:-}" = "--decode" ]; then
  python3 - "${2:-localhost:8080}" <<'EOF'
import json
import sys
import urllib.request

api = sys.argv[1]
with urllib.request.urlopen(f"http://{api}/api/engine/timeline",
                            timeout=10) as r:
    s = json.load(r)["summary"]
if not s["decode_steps"] and not s["embed_flushes"]:
    sys.exit("no engine timeline recorded yet — drive some embed/decode "
             "traffic first")
print(f"engine timeline window: {s['decode_steps']} decode steps, "
      f"{s['decode_admits']} admits, {s['decode_finishes']} finishes, "
      f"{s['decode_cancels']} cancels, {s['embed_flushes']} embed flushes")
rows = [
    ("decode batch occupancy", f"{s['decode_occupancy_pct']}%"),
    ("stranded KV rows", f"{s['decode_kv_stranded_pct']}% of allocated"),
    ("prompt prefix share", f"{s['decode_prefix_share_pct']}%"),
    ("TTFT p50 / p99", f"{s['decode_ttft_ms_p50']} / "
                       f"{s['decode_ttft_ms_p99']} ms"),
    ("TPOT p50", f"{s['decode_tpot_ms_p50']} ms/token"),
    ("prefill vs decode wall", f"{s['decode_prefill_ms_total']} / "
                               f"{s['decode_step_ms_total']} ms"),
    ("embed packing opportunity", f"{s['packing_opportunity_pct']}%"),
]
for name, val in rows:
    print("  " + name.ljust(28) + val)
# paged-KV + radix rows appear only when the engine runs kv_layout=paged
# (summary fields) and the kv.* gauges are registered — guard every key
if s.get("decode_radix_hit_pct") is not None:
    paged = [
        ("radix prompt-token hits", f"{s['decode_radix_hit_pct']}%"),
        ("TTFT radix-hit / cold", f"{s.get('decode_ttft_hit_ms_p50', '-')} "
                                  f"/ {s.get('decode_ttft_cold_ms_p50', '-')}"
                                  " ms"),
        ("KV pages live", f"{s.get('decode_pages_live_pct', '-')}% of pool"),
    ]
    try:
        with urllib.request.urlopen(f"http://{api}/api/metrics",
                                    timeout=10) as r:
            g = json.load(r).get("gauges", {})
        def kv(name):
            for k, v in g.items():
                if k == name or k.startswith(name + "{"):
                    return v
            return None
    except Exception:
        def kv(name):
            return None
    free, live, frag = (kv("kv.pages_free"), kv("kv.pages_live"),
                        kv("kv.page_fragmentation_pct"))
    if free is not None or live is not None:
        paged.append(("page pool free / live",
                      f"{'-' if free is None else int(free)} / "
                      f"{'-' if live is None else int(live)} pages"))
    if frag is not None:
        paged.append(("page fragmentation", f"{frag}%"))
    for name, val in paged:
        print("  " + name.ljust(28) + val)
# compute-plane dispatch rows (obs/xprof.py host-gap attribution) appear
# only once decode steps carry dispatch counts — guard like the paged rows
if s.get("decode_host_gap_pct") is not None:
    print("  " + "dispatches per token".ljust(28)
          + f"{s['decode_dispatches_per_token']}")
    print("  " + "host gap (chunk wall)".ljust(28)
          + f"{s['decode_host_gap_pct']}% host-side between dispatches")
# speculative-decode rows (engine/lm.py draft plane) appear only when the
# window recorded spec rounds — spec-off deployments print unchanged
if s.get("decode_spec_accept_pct") is not None:
    print("  " + "spec accept rate".ljust(28)
          + f"{s['decode_spec_accept_pct']}% over "
            f"{s.get('decode_spec_rounds', 0)} rounds")
    print("  " + "spec draft / verify wall".ljust(28)
          + f"{s.get('decode_spec_draft_ms_total', 0)} / "
            f"{s.get('decode_spec_verify_ms_total', 0)} ms")
print("dominant stall:", s["dominant_stall"])
print(f"(Perfetto view: curl http://{api}"
      "'/api/engine/timeline?fmt=chrome' > tl.json, open in "
      "ui.perfetto.dev)")
EOF
  exit 0
fi

if [ "${1:-}" = "--memory" ]; then
  python3 - "${2:-localhost:8080}" <<'EOF'
import json
import sys
import urllib.request

api = sys.argv[1]
with urllib.request.urlopen(f"http://{api}/api/memory", timeout=10) as r:
    mem = json.load(r)
local = mem.get("local") or {}
rows = local.get("subsystems") or []


def gib(n):
    return f"{n / (1 << 30):8.3f} GiB" if n is not None else "       -    "


print(f"hbm attribution (basis: {local.get('basis')})")
if not rows:
    print("  no subsystem claims yet — is an engine plane up on this role?")
for row in rows:
    mark = "  (overlay: inside another claim)" if row["overlay"] else ""
    print("  " + row["subsystem"].ljust(24) + gib(row["bytes"]) + mark)
print("  " + "-" * 44)
print("  " + "attributed".ljust(24) + gib(local.get("attributed_bytes")))
print("  " + "unattributed".ljust(24) + gib(local.get("unattributed_bytes"))
      + f"  ({local.get('unattributed_pct')}% of "
        f"{gib(local.get('bytes_in_use')).strip()} in use)")
for d in local.get("devices") or []:
    limit, use = d.get("bytes_limit"), d["bytes_in_use"]
    head = (limit - use) if limit else None
    print(f"  device {d['device']} ({d['platform']}): "
          f"{gib(use).strip()} in use / {gib(limit).strip()} limit"
          + (f", {gib(head).strip()} headroom" if head is not None else ""))
oom = mem.get("last_oom")
if oom:
    print(f"LAST OOM: site={oom['site']} postmortem={oom.get('postmortem')}")
    print(f"  {oom.get('error', '')[:120]}")
for role, entry in (mem.get("roles") or {}).items():
    subs = entry.get("subsystems") or {}
    if subs:
        total = sum(v for v in subs.values())
        print(f"  role {role}: {len(subs)} subsystem claims, "
              f"{gib(total).strip()} attributed")
with urllib.request.urlopen(f"http://{api}/api/memory/census?top=8",
                            timeout=10) as r:
    cen = json.load(r)["census"]
if cen.get("available"):
    print(f"live-array census: {cen['arrays']} arrays, "
          f"{gib(cen['bytes_total']).strip()} total")
    for g in cen["groups"][:8]:
        shape = "x".join(str(d) for d in g["shape"]) or "scalar"
        print(f"  {g['dtype']:<10} {shape:<22} x{g['count']:<5} "
              + gib(g["bytes"]).strip())
else:
    print("live-array census unavailable:", cen.get("detail"))
EOF
  exit 0
fi

if [ $# -ge 1 ]; then
  python3 - "$1" <<'EOF'
import json
import sys
import urllib.request

api = sys.argv[1]
with urllib.request.urlopen(f"http://{api}/api/traces/recent",
                            timeout=10) as r:
    traces = json.load(r)["traces"]
ingest_roots = ("api.submit_url", "perception.handle", "preprocessing.handle",
                "vector_memory.handle", "engine.handle")
picks = [t for t in traces if t.get("root") in ingest_roots] or traces
if not picks:
    sys.exit("no traces recorded yet — drive some ingest first")
tid = picks[0]["trace_id"]
with urllib.request.urlopen(f"http://{api}/api/traces/{tid}/critical_path",
                            timeout=10) as r:
    cp = json.load(r)
print(f"trace {tid} (root {picks[0].get('root')}, e2e {cp.get('e2e_ms')} ms)")
for hop in cp.get("chain", []):
    print("  " + hop["name"].ljust(40)
          + f" self {hop['self_ms']:>9} ms  ({hop['share_of_e2e_pct']}%)")
print("  " + "<untraced gap>".ljust(40)
      + f" self {cp['gap_ms']:>9} ms  ({cp.get('gap_pct')}%)")
print("verdict:", cp.get("verdict"))
EOF
  exit 0
fi

# no host given: run the bench (e2e tier included) and read its archived
# attribution + overlap fields off the one JSON line it prints on stdout
LINE_FILE="$(mktemp)"
trap 'rm -f "${LINE_FILE}"' EXIT
python bench.py --no-chaos | tee "${LINE_FILE}"
python3 - "${LINE_FILE}" <<'EOF'
import json, sys
line = [l for l in open(sys.argv[1]) if l.strip().startswith("{")][-1]
r = json.loads(line)
stages = sorted(((k, v) for k, v in r.items()
                 if k.startswith("e2e_stage_ingest_") and k.endswith("_pct")),
                key=lambda kv: -kv[1])
print()
print("== where the ingest time goes (critical-path self-time shares) ==")
if not stages:
    print("no e2e_stage_ingest_* fields archived — did the e2e tier run?")
for k, v in stages:
    hop = k[len("e2e_stage_ingest_"):-len("_pct")]
    marker = "  <- dominant" if (k, v) == stages[0] else ""
    if hop == "gap":
        marker = "  (untraced: bus queueing / span-less native hops)"
    print(f"  {hop:<32} {v:>6.1f}%{marker}")
ratio = r.get("e2e_ingest_vs_bulk_x")
if ratio is not None:
    verdict = "OK" if ratio >= 0.6 else "REGRESSION (target >= 0.6)"
    print(f"e2e ingest / bulk ingest: {ratio}x  [{verdict}]")
ov = r.get("e2e_batcher_overlap_ratio")
if ov is not None:
    print(f"embed flush window overlap ratio: {ov}")
rows = r.get("e2e_coalesce_rows_per_flush")
if rows is not None:
    print(f"coalesced upsert: {rows} rows/flush over "
          f"{r.get('e2e_coalesce_flushes')} flushes")
EOF
