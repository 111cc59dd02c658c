# -*- coding: utf-8 -*-
"""
SLO accounting unit + gate tests (obs/slo.py):

- the classifier's six-way partition (met / missed_ttft / missed_token
  / missed_e2e / rejected / incomplete) with per-tenant overrides;
- check_baseline tolerances, violations naming metric AND tenant,
  slo.violation events landing in the active log;
- the committed SLO_BASELINE.json gate end to end through the CLI —
  the seeded CI smoke passes clean (rc 0) and a seeded regression
  fixture (the same trace on 50x slower virtual ticks) fails (rc 1)
  naming the metric and tenant.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_dot_product_tpu import obs
from distributed_dot_product_tpu.obs import slo as obs_slo
from distributed_dot_product_tpu.obs.slo import (
    CLASSES, SloSpec, check_baseline, classify, goodput, make_baseline,
)
from distributed_dot_product_tpu.obs.timeline import Timeline

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tl(status='completed', ttft=0.01, gaps=(), total=0.1,
        tenant='t0', complete=True):
    return Timeline(request_id='r', events=[], status=status,
                    complete=complete, ttft=ttft,
                    token_gaps=list(gaps), total_seconds=total,
                    tenant=tenant)


def test_classifier_partition():
    spec = SloSpec(ttft=0.1, per_token=0.05, e2e=1.0)
    assert classify(_tl(), spec) == 'met'
    assert classify(_tl(ttft=0.2), spec) == 'missed_ttft'
    assert classify(_tl(ttft=None), spec) == 'missed_ttft'
    assert classify(_tl(gaps=[0.01, 0.2]), spec) == 'missed_token'
    assert classify(_tl(total=2.0), spec) == 'missed_e2e'
    assert classify(_tl(status='rejected'), spec) == 'rejected'
    # Any non-completed terminal — and a truncated lifecycle — is
    # 'incomplete': the stream was not delivered.
    assert classify(_tl(status='evicted'), spec) == 'incomplete'
    assert classify(_tl(status='failed_nan'), spec) == 'incomplete'
    assert classify(_tl(complete=False), spec) == 'incomplete'
    # Classification order: a rejected/incomplete request never counts
    # as a latency miss, a TTFT miss wins over a token miss.
    assert classify(_tl(status='rejected', ttft=9.0), spec) \
        == 'rejected'
    assert classify(_tl(ttft=0.2, gaps=[0.2]), spec) == 'missed_ttft'
    # Disabled checks never miss.
    assert classify(_tl(ttft=9.9, gaps=[9.9], total=9.9),
                    SloSpec()) == 'met'


def test_per_tenant_overrides():
    spec = SloSpec(ttft=0.1, tenants={'batch': {'ttft': 10.0}})
    assert classify(_tl(ttft=0.5, tenant='batch'), spec) == 'met'
    assert classify(_tl(ttft=0.5, tenant='t0'), spec) == 'missed_ttft'
    # Unset override keys inherit the global contract.
    spec = SloSpec(ttft=0.1, per_token=0.05,
                   tenants={'batch': {'ttft': 10.0}})
    assert classify(_tl(ttft=0.5, gaps=[0.2], tenant='batch'),
                    spec) == 'missed_token'


def _records(recs):
    for i, r in enumerate(recs):
        r.setdefault('seq', i)
        r.setdefault('ts', float(i))
        r.setdefault('schema', obs.SCHEMA_VERSION)
    return recs


def test_goodput_over_records_partitions_and_groups_by_tenant():
    recs = _records([
        # a: met (tenant t0)
        {'event': 'serve.admit', 'request_id': 'a', 'slot': 0,
         'tenant': 't0', 'queue_wait': 0.01},
        {'event': 'serve.decode', 'request_id': 'a', 'slot': 0,
         'token_index': 0, 'ttft': 0.02},
        {'event': 'serve.retire', 'request_id': 'a',
         'status': 'completed', 'total_seconds': 0.05, 'tenant': 't0'},
        # b: missed_ttft (tenant t1)
        {'event': 'serve.admit', 'request_id': 'b', 'slot': 1,
         'tenant': 't1', 'queue_wait': 0.2},
        {'event': 'serve.decode', 'request_id': 'b', 'slot': 1,
         'token_index': 0, 'ttft': 0.9},
        {'event': 'serve.retire', 'request_id': 'b',
         'status': 'completed', 'total_seconds': 1.0, 'tenant': 't1'},
        # c: rejected at submit (tenant t1)
        {'event': 'serve.reject', 'request_id': 'c',
         'reason': 'queue_full', 'tenant': 't1'},
    ])
    report = goodput(recs, SloSpec(ttft=0.1))
    assert report.requests == 3
    assert report.counts['met'] == 1
    assert report.counts['missed_ttft'] == 1
    assert report.counts['rejected'] == 1
    assert sum(report.counts.values()) == 3
    assert report.by_request == {'a': 'met', 'b': 'missed_ttft',
                                 'c': 'rejected'}
    assert report.per_tenant['t0']['goodput_pct'] == 100.0
    assert report.per_tenant['t1']['goodput_pct'] == 0.0
    assert sum(tb['requests'] for tb in report.per_tenant.values()) == 3
    assert report.percentiles['ttft']['count'] == 2
    assert report.goodput_pct == pytest.approx(100.0 / 3)


def _report(goodput_pct=90.0, per_tenant=None, requests=10):
    per_tenant = per_tenant or {'t0': 95.0, 't1': 80.0}
    return obs_slo.SloReport(
        spec=SloSpec(ttft=0.1).to_dict(), requests=requests,
        counts={c: 0 for c in CLASSES}, goodput_pct=goodput_pct,
        per_tenant={t: {'requests': 5, 'goodput_pct': g,
                        'counts': {c: 0 for c in CLASSES}}
                    for t, g in per_tenant.items()},
        percentiles={}, statuses={}, by_request={})


def test_check_baseline_gate_names_metric_and_tenant():
    base = make_baseline(_report())
    assert base['schema'] == obs_slo.SLO_BASELINE_SCHEMA
    # Clean: identical report passes.
    assert check_baseline(_report(), base, emit_events=False) == []
    # Within tolerance passes; past it fails naming the metric.
    ok = _report(goodput_pct=82.0)          # -8 pts, tol 10
    assert check_baseline(ok, base, emit_events=False) == []
    bad = _report(goodput_pct=60.0,
                  per_tenant={'t0': 95.0, 't1': 30.0})
    v = check_baseline(bad, base, emit_events=False)
    assert any('goodput_pct' in s and 'tenant' not in s for s in v)
    assert any('tenant t1' in s and 'goodput_pct' in s for s in v)
    assert not any('tenant t0' in s for s in v)
    # Request-count drift is a config error, named as such.
    v = check_baseline(_report(requests=7), base, emit_events=False)
    assert any('requests' in s for s in v)
    # Tenant coverage both directions.
    v = check_baseline(_report(per_tenant={'t0': 95.0}), base,
                       emit_events=False)
    assert any('tenant t1' in s and 'coverage' in s for s in v)
    v = check_baseline(
        _report(per_tenant={'t0': 95.0, 't1': 80.0, 'tX': 1.0}),
        base, emit_events=False)
    assert any('tenant tX' in s and 'coverage' in s for s in v)
    # Unknown baseline schema demands a refresh.
    v = check_baseline(_report(), {'schema': 99}, emit_events=False)
    assert v and 'schema' in v[0]


def test_check_baseline_emits_slo_violation_events(tmp_path):
    log = obs.EventLog(tmp_path / 'gate.jsonl')
    base = make_baseline(_report())
    with obs.activate(log):
        check_baseline(_report(goodput_pct=10.0,
                               per_tenant={'t0': 10.0, 't1': 10.0}),
                       base)
    log.close()
    recs = [r for r in obs.read_events(log.path)
            if r['event'] == 'slo.violation']
    assert recs, 'no slo.violation events landed in the active log'
    metrics = {(r['metric'], r.get('tenant')) for r in recs}
    assert ('goodput_pct', None) in metrics
    assert ('goodput_pct', 't0') in metrics
    _, errors = obs.validate_file(log.path)
    assert errors == []


def test_goodput_merges_multi_replica_logs(tmp_path):
    """A disaggregated request — admit+prefill in the prefill pool's
    log, decode+retire in the decode pool's — classifies from the
    merged pair."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    pre = obs.EventLog(tmp_path / 'prefill.jsonl', clock=clock)
    pre.emit('serve.admit', request_id='x', slot=0, tenant='t0',
             queue_wait=0.01)
    pre.emit('serve.prefill', request_id='x', slot=0, pos=4)
    pre.close()
    dec = obs.EventLog(tmp_path / 'decode.jsonl', clock=clock)
    dec.emit('serve.decode', request_id='x', slot=2, token_index=0,
             ttft=0.03)
    dec.emit('serve.retire', request_id='x', status='completed',
             total_seconds=0.05, tenant='t0')
    dec.close()
    report = goodput([('prefill', pre.path), ('decode', dec.path)],
                     SloSpec(ttft=0.1))
    assert report.requests == 1
    assert report.by_request['x'] == 'met'
    assert report.per_tenant['t0']['requests'] == 1


def test_committed_slo_baseline_gate_cli(tmp_path):
    """Tier-1 acceptance: the CI stage end to end, subprocess for
    subprocess — the seeded serve-load smoke (examples/serve_load.py,
    bare) must pass `slo check` against the COMMITTED
    SLO_BASELINE.json; the regression fixture — the same seeded trace
    on 50x slower ticks — must exit 1 naming the metric and at least
    one tenant."""
    env = {**os.environ, 'JAX_PLATFORMS': 'cpu'}

    def smoke(tag, *extra):
        log = tmp_path / f'{tag}.jsonl'
        rows = tmp_path / f'{tag}_rows.json'
        r = subprocess.run(
            [sys.executable, 'examples/serve_load.py',
             '--event-log', str(log), '--file', str(rows), *extra],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=300)
        assert r.returncode == 0, r.stderr + r.stdout
        return log

    def check(log):
        return subprocess.run(
            [sys.executable, '-m', 'distributed_dot_product_tpu.obs',
             'slo', 'check', str(log), '--against',
             'SLO_BASELINE.json', '--json'],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)

    clean = check(smoke('clean'))
    assert clean.returncode == 0, clean.stdout + clean.stderr

    regress = check(smoke('regress', '--load-tick', '0.1'))
    assert regress.returncode == 1, (
        'the 50x-slower-tick regression fixture passed the SLO gate')
    payload = json.loads(regress.stdout)
    assert any('goodput_pct' in v for v in payload['violations'])
    assert any('tenant t' in v for v in payload['violations'])


def test_committed_baseline_shape():
    """The committed baseline's own contract: schema, a parseable
    embedded spec, the two smoke tenants, a sane goodput."""
    with open(os.path.join(REPO, 'SLO_BASELINE.json'),
              encoding='utf-8') as f:
        base = json.load(f)
    assert base['schema'] == obs_slo.SLO_BASELINE_SCHEMA
    spec = SloSpec.from_dict(base['spec'])
    assert spec.ttft is not None and spec.per_token is not None
    assert set(base['per_tenant']) == {'t0', 't1'}
    assert 0.0 < base['goodput_pct'] <= 100.0
