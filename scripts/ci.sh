#!/usr/bin/env bash
# CI gate: generic hygiene (ruff) → domain static analysis (graphlint)
# → tier-1 tests. Each stage fails the build on its own; later stages
# still run so one CI pass reports everything (exit is the OR).
#
#   scripts/ci.sh            # full gate
#   SKIP_TESTS=1 scripts/ci.sh   # lint-only (fast pre-push check)
#
# Two-tier lint story (README "Static analysis"): ruff owns generic
# python hygiene; graphlint owns the jaxpr/domain contracts (fp32
# accumulation, KV-cache aliasing/donation, collective mesh axes,
# retrace budgets, AST hazard patterns). The TPU container image does
# not ship ruff — that stage is skipped with a notice there (the
# pyproject [tool.ruff] config makes any box that HAS ruff enforce the
# same rules).
set -u -o pipefail
cd "$(dirname "$0")/.."

rc=0

echo '=== [1/11] ruff (generic hygiene) ==='
if command -v ruff >/dev/null 2>&1; then
    ruff check . || rc=1
elif python -c 'import ruff' >/dev/null 2>&1; then
    python -m ruff check . || rc=1
else
    echo 'ruff not installed in this image — skipping (graphlint still runs)'
fi

echo '=== [2/11] graphlint + servelint + flowlint (jaxpr/domain/serving contracts) ==='
# Full pass: jaxpr rules over every registered entrypoint (incl. the
# bf16 serving-dtype and int8-weight twins — the owned dense retired
# the flax-Dense f32-accum waivers, so zero allowed records remain)
# + the AST families (host-pull/traced-bool/clock/
# silent-except) + servelint (protolint event-schema call sites,
# conclint guarded-by/thread discipline, determlint tick-path
# determinism) + flowlint (interprocedural typed-failure flow: typed
# escapes at the serving roots with propagation chains, handler
# totality, RejectReason liveness, shard-stride ownership; pragma
# waivers stay visible and the gate keeps them at zero). Fast
# pre-commit twin:
#   python -m distributed_dot_product_tpu.analysis --changed-only origin/main
JAX_PLATFORMS=cpu python -m distributed_dot_product_tpu.analysis || rc=1

echo '=== [3/11] tier-1 tests ==='
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping pytest stage'
else
    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider || rc=1
fi

echo '=== [4/11] smoke serve + event-log schema validation ==='
# Drives the real serving process through the fault cocktail and then
# schema-validates + timeline-reconstructs its JSONL event log (the
# obs validate CLI runs inside smoke_serve.sh over the run's log).
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping smoke-serve stage'
else
    scripts/smoke_serve.sh 12 4 || rc=1
fi

echo '=== [5/11] spec-decode bit-identity smoke (DDP_TPU_SPEC=ngram) ==='
# Speculative decoding's exactness guarantee, proven on a real burst
# through the ENV knob a deployment would flip: the same traffic served
# with the n-gram proposer (verify-k steps) and without (plain n=1
# steps) must produce token-for-token identical streams and statuses.
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping spec-smoke stage'
else
    JAX_PLATFORMS=cpu DDP_TPU_SPEC=ngram python - <<'PY' || rc=1
import numpy as np

from distributed_dot_product_tpu.serve import (
    KernelEngine, Scheduler, ServeConfig,
)
from distributed_dot_product_tpu.utils.tracing import MetricsRegistry


def burst(spec):
    """spec=None resolves the DDP_TPU_SPEC env knob; 'off' overrides
    it — so the spec run exercises the deployment path and the
    baseline run the explicit opt-out."""
    eng = KernelEngine(slots=2, t_max=128, vocab=32, seed=4,
                       decode_impl='xla')
    sched = Scheduler(
        eng, ServeConfig(queue_limit=16, max_new_tokens=24,
                         watchdog=False, spec=spec, spec_k=4),
        registry=MetricsRegistry())
    rng = np.random.RandomState(11)
    # Mixed traffic: cyclic prompts (speculation's win case) and
    # random ones (its miss case) in one batch.
    for i in range(6):
        if i % 2:
            p = [(j % 3) + 1 for j in range(8)]
        else:
            p = [int(x) for x in rng.randint(1, 32, size=6)]
        sched.submit(p, request_id=f'r{i}')
    results = sched.run_until_idle()
    sched.close()
    steps = sched.registry.snapshot()['counters']['serve.decode_steps']
    return results, steps


spec, spec_steps = burst(None)        # DDP_TPU_SPEC=ngram applies
base, base_steps = burst('off')
assert set(spec) == set(base)
for rid in base:
    assert spec[rid].status == base[rid].status, rid
    assert spec[rid].tokens == base[rid].tokens, (
        f'{rid}: spec stream diverged from non-spec — the greedy '
        f'verify exactness guarantee is broken')
assert spec_steps < base_steps, (
    f'spec burst took {spec_steps} dispatches vs {base_steps} non-spec'
    ' — the verify-k path never amortized a step')
print(f'spec smoke OK: {len(base)} streams bit-identical, '
      f'{spec_steps} vs {base_steps} decode dispatches')
PY
fi

echo '=== [6/11] serve-load smoke + SLO goodput gate ==='
# A seeded open-loop trace (virtual clock — minutes of simulated
# traffic in seconds of wall time, CPU-deterministic) drives the
# scheduler, then the goodput report computed FROM THE EVENT LOG ALONE
# is gated against the committed SLO_BASELINE.json (generous
# tolerances; every violation names the metric and tenant). Goodput
# here is virtual-time: a behaviour check, never a speed. The load
# driver's constants and flag DEFAULTS are the smoke config — on an
# intentional serving/load change refresh the baseline in the same
# diff:
#   python examples/serve_load.py --event-log /tmp/slo.jsonl
#   python -m distributed_dot_product_tpu.obs slo report /tmp/slo.jsonl \
#       --spec SLO_BASELINE.json --baseline-out SLO_BASELINE.json
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping serve-load stage'
else
    slo_log="$(mktemp -u /tmp/ddp_slo_smoke.XXXXXX).jsonl"
    slo_row="$(mktemp /tmp/ddp_slo_row.XXXXXX.json)"
    rm -f "$slo_row"    # the driver appends into a fresh JSON file
    { JAX_PLATFORMS=cpu python examples/serve_load.py \
          --event-log "$slo_log" --file "$slo_row" \
      && JAX_PLATFORMS=cpu python -m distributed_dot_product_tpu.obs \
          slo check "$slo_log" --against SLO_BASELINE.json; } || rc=1
    rm -f "$slo_log" "$slo_row"
fi

echo '=== [7/11] disaggregated-serving smoke (router + 2 decode pools) ==='
# The 1-router/2-pool cocktail on the CPU mesh: the seeded trace through
# the disaggregated topology AND its single-process twin, member logs
# schema-validated (--require router.route / prefill.handoff), goodput
# over the MERGED replica logs gated against SLO_BASELINE.json, and the
# exactly-once / topology-beats-twin invariants asserted from the row.
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping router-smoke stage'
else
    scripts/smoke_router.sh || rc=1
fi

echo '=== [8/11] closed-loop control smoke (static vs controlled under a ramp) ==='
# The control-plane acceptance row: the SAME seeded ramp trace (rate
# climbing to 10x across the trace — deterministic overload) through a
# 1-decode-replica topology twice. STATIC must breach the committed
# per-tenant SLO floors (the trace is sized to break one replica);
# CONTROLLED (the closed-loop controller autoscaling decode replicas
# and actuating admission watermarks) must hold every tenant within
# SLO_BASELINE.json tolerance. The controlled run's control history is
# then validated as closed-vocabulary events from the log alone
# (obs validate --require control.scale).
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping control-smoke stage'
else
    ctl_rows="$(mktemp /tmp/ddp_ctl_rows.XXXXXX.json)"
    ctl_static="$(mktemp -d /tmp/ddp_ctl_static.XXXXXX)"
    ctl_logs="$(mktemp -d /tmp/ddp_ctl_logs.XXXXXX)"
    rm -f "$ctl_rows"    # the driver appends into a fresh JSON file
    { JAX_PLATFORMS=cpu python examples/serve_load.py \
          --topology 0x1 --arrival ramp --load-rate 300 \
          --ramp-factor 10 --load-requests 64 \
          --event-log "$ctl_static" --file "$ctl_rows" \
      && JAX_PLATFORMS=cpu python examples/serve_load.py \
          --topology 0x1 --arrival ramp --load-rate 300 \
          --ramp-factor 10 --load-requests 64 --control \
          --event-log "$ctl_logs" --file "$ctl_rows" \
      && JAX_PLATFORMS=cpu python -m distributed_dot_product_tpu.obs \
          validate "$ctl_logs/router.jsonl" \
          --require control.adjust,control.scale \
      && python - "$ctl_rows" <<'PY'; } || rc=1
import json
import sys

rows = json.load(open(sys.argv[1]))
static, controlled = rows[-2], rows[-1]
assert not static['control'] and controlled['control']
base = json.load(open('SLO_BASELINE.json'))
tol = base['tolerances']['tenant_goodput_abs']
floors = {t: gp - tol for t, gp in base['per_tenant'].items()}
breached = [t for t, gp in static['per_tenant'].items()
            if gp < floors[t]]
assert breached, (
    f"the ramp trace no longer breaks the static config "
    f"({static['per_tenant']} vs floors {floors}) — re-size the ramp "
    f"so the control win stays measurable")
held = {t: gp for t, gp in controlled['per_tenant'].items()}
bad = [t for t, gp in held.items() if gp < floors[t]]
assert not bad, (
    f'controlled run breaches the per-tenant SLO floors for {bad}: '
    f'{held} vs floors {floors} — the closed loop stopped holding '
    f'goodput under the ramp')
ups = [a for a in controlled['control_actions']
       if a['action'] == 'scale' and a['direction'] == 'up']
assert ups, 'controlled run never scaled up — the ramp was not acted on'
print(f"control smoke OK: static {static['per_tenant']} (breached "
      f"{breached}) vs controlled {held} within floors {floors}; "
      f"{len(ups)} scale-up(s), {controlled['replicas_final']} "
      f"replicas final")
PY
    rm -rf "$ctl_rows" "$ctl_static" "$ctl_logs"
fi

echo '=== [9/11] replica-failure-domain smoke (seeded crash + recovery) ==='
# The robustness acceptance row: the seeded CI trace with decode
# replica r1 killed at a fixed virtual tick. Probes declare the loss,
# every in-flight stream re-dispatches to the survivor bit-identical
# to the crash-free twin, goodput with recovery strictly beats the
# max_recoveries=0 twin of the same crash, the victim's torn log still
# validates, and `obs doctor` classifies the auto-dumped flight bundle
# as replica_loss NAMING the dead replica.
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping chaos-smoke stage'
else
    scripts/smoke_chaos.sh || rc=1
fi

echo '=== [10/11] data-integrity smoke (seeded bit flip + detect/heal) ==='
# The KV-page-integrity acceptance row: the seeded CI trace with one
# exponent bit flipped in a live KV page of r0 at a fixed virtual
# tick. The scrub detects the flip before any poisoned token is
# delivered, the victim heals bit-identical to the crash-free twin, a
# checksums-off twin of the same flip delivers a SILENTLY wrong
# stream, and `obs doctor` classifies the auto-dumped flight bundle
# as kv_corruption NAMING the dirty replica.
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping corrupt-smoke stage'
else
    scripts/smoke_corrupt.sh || rc=1
fi

echo '=== [11/11] long-context smoke (128k stream on the sharded KV mesh) ==='
# The cluster-scale long-context acceptance row: a 128k-token stream
# prefilled into a kv_shards=8 paged engine (each mesh member owns a
# contiguous page range, per-shard flash partials psum/pmax-merged)
# decodes token-for-token identical to the single-pool reference on
# the 8-dev CPU mesh — XLA path at full length, fused kernel path on a
# shorter sharded stream — and capacity_tokens scales linearly in
# kv_shards on a fixed per-shard pool (≥3.5x line).
if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo 'SKIP_TESTS=1 — skipping longctx-smoke stage'
else
    scripts/smoke_longctx.sh || rc=1
fi

exit $rc
