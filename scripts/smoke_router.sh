#!/usr/bin/env bash
# Disaggregated-serving smoke: the 1-router / 2-decode-pool cocktail on
# the CPU mesh, end to end through the real CLIs.
#
#   scripts/smoke_router.sh
#
# What it proves (exit 0 = all of it):
#   1. `examples/serve_load.py --topology 1x2` runs the seeded
#      CI trace through the router (sequence-sharded prefill pool +
#      2 paged decode replicas, KV handoff as pool pages) AND through
#      its single-process twin on the byte-identical serialized trace.
#   2. The router/prefill logs schema-validate and actually carry the
#      disaggregation events (router.route placements, prefill.handoff
#      page transfers).
#   3. Goodput computed over the MERGED per-member logs passes the
#      committed SLO_BASELINE.json gate (`obs slo check` with labeled
#      replica=path sources) — the same gate the single-process smoke
#      answers to.
#   4. Every submitted request is accounted exactly once across the
#      merged logs, and the routed topology's goodput is at least the
#      twin's on the same trace (2x the capacity never does worse).
#   5. `obs critpath` reconstructs every request's causal phase chain
#      from the merged logs with the phases PARTITIONING its e2e
#      latency (sum == total_seconds to 1e-6 in virtual time — the
#      command exits non-zero on any partition failure).
#   6. `obs trace export` emits Chrome-trace/Perfetto JSON that
#      revalidates (required keys on every event, per-track ts
#      monotone) and actually carries the phase slices.
set -u -o pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

dir="$(mktemp -d /tmp/ddp_router_smoke.XXXXXX)"
row="$dir/row.json"
trap 'rm -rf "$dir"' EXIT

echo "== smoke_router: serve-load --topology 1x2 (logs in $dir) =="
python examples/serve_load.py --topology 1x2 \
    --event-log "$dir" --file "$row" || exit 1

echo '== smoke_router: member logs schema-validate + carry the routing events =='
python -m distributed_dot_product_tpu.obs validate "$dir/router.jsonl" \
    --require router.route || exit 1
python -m distributed_dot_product_tpu.obs validate "$dir/prefill.jsonl" \
    --require prefill.handoff || exit 1

echo '== smoke_router: goodput gate over the MERGED replica logs =='
python -m distributed_dot_product_tpu.obs slo check \
    router="$dir/router.jsonl" prefill="$dir/prefill.jsonl" \
    r0="$dir/r0.jsonl" r1="$dir/r1.jsonl" \
    --against SLO_BASELINE.json || exit 1

echo '== smoke_router: exactly-once accounting + twin comparison =='
python - "$row" <<'PY' || exit 1
import json
import sys

rec = json.load(open(sys.argv[1]))[-1]
assert rec['topology'] == '1x2', rec
assert sum(rec['counts'].values()) == rec['requests'], (
    f"classification classes {rec['counts']} do not partition the "
    f"{rec['requests']} submitted requests")
assert rec['goodput_pct'] >= rec['twin_goodput_pct'], (
    f"routed topology goodput {rec['goodput_pct']:.1f}% fell below its "
    f"single-process twin's {rec['twin_goodput_pct']:.1f}% on the same "
    f"trace")
assert set(rec['routed']) == {'r0', 'r1'}, rec['routed']
assert rec['handoffs'] >= 1, 'no prefill->decode KV handoff happened'
print(f"router smoke OK: goodput {rec['goodput_pct']:.1f}% "
      f"(twin {rec['twin_goodput_pct']:.1f}%), routed {rec['routed']}, "
      f"{rec['handoffs']} handoffs / {rec['handoff_pages']} pages, "
      f"{rec['prefix_hits']} prefix hits")
PY

echo '== smoke_router: critpath phase partition over the merged logs =='
# Exits non-zero when any completed request's phases fail to sum to
# its e2e — the partition-by-construction gate.
python -m distributed_dot_product_tpu.obs critpath \
    router="$dir/router.jsonl" prefill="$dir/prefill.jsonl" \
    r0="$dir/r0.jsonl" r1="$dir/r1.jsonl" || exit 1
python -m distributed_dot_product_tpu.obs critpath \
    router="$dir/router.jsonl" prefill="$dir/prefill.jsonl" \
    r0="$dir/r0.jsonl" r1="$dir/r1.jsonl" --json \
    > "$dir/critpath.json" || exit 1
python - "$dir/critpath.json" <<'PY' || exit 1
import json
import sys

prof = json.load(open(sys.argv[1]))
assert prof['requests'] > 0, 'critpath reconstructed zero requests'
assert prof['complete'] > 0, 'no request carried an e2e anchor'
assert not prof['partition_failures'], prof['partition_failures']
assert prof['phases'].get('decode', 0) > 0, (
    'no decode time attributed on a run that committed tokens')
assert prof.get('dispatch', {}).get('total', {}).get('ticks', 0) > 0, (
    'no serve.dispatch records — the dispatch-floor accounting is off')
print(f"critpath OK: {prof['requests']} requests, phases partition "
      f"e2e exactly, {prof['dispatch']['total']['ticks']} dispatch "
      f"ticks accounted")
PY

echo '== smoke_router: Perfetto/Chrome-trace export + schema check =='
python -m distributed_dot_product_tpu.obs trace export \
    router="$dir/router.jsonl" prefill="$dir/prefill.jsonl" \
    r0="$dir/r0.jsonl" r1="$dir/r1.jsonl" \
    -o "$dir/trace.json" || exit 1
python - "$dir/trace.json" <<'PY' || exit 1
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace['traceEvents']
assert events, 'empty trace'
last = {}
for ev in events:
    for key in ('name', 'ph', 'ts', 'pid', 'tid'):
        assert key in ev, f'missing {key!r}: {ev}'
    if ev['ph'] == 'M':
        continue
    track = (ev['pid'], ev['tid'])
    assert ev['ts'] >= last.get(track, 0), (
        f"non-monotone ts on track {track}: {ev}")
    last[track] = ev['ts']
slices = [e for e in events if e['ph'] == 'X']
assert slices, 'no phase slices in the exported trace'
assert any(e['ph'] == 'i' for e in events), (
    'no instant markers (handoffs at minimum) in the exported trace')
print(f"trace OK: {len(events)} events, {len(slices)} phase slices, "
      f"{len(last)} tracks, ts monotone per track")
PY

echo 'smoke_router OK'
