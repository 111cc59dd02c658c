# -*- coding: utf-8 -*-
"""
Load/SLO observatory acceptance (tier-1) + loadgen unit tests.

The acceptance scenario (ISSUE 9): a seeded open-loop loadgen run over
the scheduler WITH FAULTS INJECTED yields a goodput report computed
from the event log ALONE in which

- every submitted request is classified exactly once
  (met + missed_* + rejected + incomplete == submitted),
- per-tenant counts sum to the total,
- the same seed reproduces the identical report,
- and /metrics exposes nonzero tenant-labeled TTFT histograms for at
  least two tenants.

Everything runs in virtual time: the scheduler, the event log and the
trace share one injectable clock, so minutes of simulated traffic cost
milliseconds and the report is bit-reproducible.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from distributed_dot_product_tpu import obs
from distributed_dot_product_tpu.obs import slo as obs_slo
from distributed_dot_product_tpu.obs.exporter import (
    MetricsServer, render_prometheus,
)
from distributed_dot_product_tpu.serve import (
    KernelEngine, LoadGenConfig, ServeConfig, TenantSpec, VirtualClock,
    default_tenants, generate_trace, run_load,
)
from distributed_dot_product_tpu.utils.faults import (
    ServeFaultInjector, ServeFaultPlan,
)
from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

pytestmark = pytest.mark.obs

SPEC = obs_slo.SloSpec(ttft=0.25, per_token=0.05)


# -- trace generation ---------------------------------------------------

def test_trace_is_seeded_and_replayable():
    cfg = LoadGenConfig(seed=11, rate=300.0, requests=40)
    a, b = generate_trace(cfg), generate_trace(cfg)
    assert [x.at for x in a] == [x.at for x in b]
    assert [x.request_id for x in a] == [x.request_id for x in b]
    assert [x.tenant for x in a] == [x.tenant for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # A different seed is a different trace.
    c = generate_trace(LoadGenConfig(seed=12, rate=300.0, requests=40))
    assert [x.at for x in a] != [x.at for x in c]


def test_trace_respects_tenant_shapes_and_shares():
    tenants = [TenantSpec('small', share=3.0, prompt_lo=1, prompt_hi=4,
                          new_lo=2, new_hi=4),
               TenantSpec('big', share=1.0, prompt_lo=8, prompt_hi=16,
                          new_lo=8, new_hi=16)]
    cfg = LoadGenConfig(seed=0, rate=100.0, requests=200,
                        tenants=tenants)
    trace = generate_trace(cfg)
    by_tenant = {'small': [], 'big': []}
    for a in trace:
        by_tenant[a.tenant].append(a)
        spec = tenants[0] if a.tenant == 'small' else tenants[1]
        assert spec.prompt_lo <= len(a.prompt) <= spec.prompt_hi
        assert spec.new_lo <= a.max_new_tokens <= spec.new_hi
    # 3:1 shares: the split lands near 150/50 (seeded, not flaky).
    assert len(by_tenant['small']) > 2 * len(by_tenant['big'])
    # Heavy tail: the bulk of draws sits in the lower half of the range.
    lens = sorted(len(a.prompt) for a in by_tenant['big'])
    assert lens[len(lens) // 2] <= (8 + 16) // 2


def test_bursty_arrivals_cluster_but_keep_the_mean_rate():
    rate = 200.0
    po = generate_trace(LoadGenConfig(seed=5, rate=rate, requests=400))
    # burst_dwell small enough that 400 arrivals cross MANY ON/OFF
    # cycles — the long-run rate only converges over whole cycles.
    bu = generate_trace(LoadGenConfig(seed=5, rate=rate, requests=400,
                                      arrival='bursty',
                                      burst_factor=8.0,
                                      burst_dwell_s=0.02))
    span_po = po[-1].at - po[0].at
    span_bu = bu[-1].at - bu[0].at
    # Long-run offered rate stays ~rate for both processes...
    assert 400 / span_bu == pytest.approx(rate, rel=0.5)
    assert 400 / span_po == pytest.approx(rate, rel=0.3)
    # ...but the bursty one clusters: its median inter-arrival gap is
    # far below Poisson's (arrivals ride ON windows at rate*factor).
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    gaps = lambda tr: [b.at - a.at  # noqa: E731
                       for a, b in zip(tr, tr[1:])]
    assert med(gaps(bu)) < 0.5 * med(gaps(po))


def test_config_validation():
    with pytest.raises(ValueError, match='rate'):
        generate_trace(LoadGenConfig(rate=0.0))
    with pytest.raises(ValueError, match='arrival'):
        generate_trace(LoadGenConfig(arrival='fractal'))
    with pytest.raises(ValueError, match='burst_factor'):
        generate_trace(LoadGenConfig(arrival='bursty',
                                     burst_factor=0.5))
    with pytest.raises(ValueError, match='TenantSpec'):
        generate_trace(LoadGenConfig(tenants=[]))


# -- the acceptance scenario -------------------------------------------

def _engine():
    return KernelEngine(slots=3, t_max=64, vocab=32, heads=2,
                        head_dim=4, prefill_chunk=4, seed=5,
                        decode_impl='xla')


def _cfg(seed=9):
    return LoadGenConfig(seed=seed, rate=500.0, requests=30,
                         tenants=default_tenants(2), vocab=32,
                         tick_seconds=0.002)


def _run_faulted(tmp_path, tag):
    """One seeded loadgen run with the NaN fault armed, fully virtual
    (scheduler + event log share the clock)."""
    clock = VirtualClock()
    log = obs.EventLog(tmp_path / f'{tag}.jsonl', clock=clock)
    registry = MetricsRegistry()
    injector = ServeFaultInjector(
        ServeFaultPlan(nan_at_step=4, nan_slot=1))
    res = run_load(
        _cfg(), engine=_engine(),
        serve_config=ServeConfig(queue_limit=6, max_new_tokens=24,
                                 watchdog=False,
                                 evict_before_reject=False),
        registry=registry, event_log=log, clock=clock,
        fault_injector=injector)
    log.close()
    return res, log.path, registry


def test_goodput_acceptance_under_faults(tmp_path, devices):
    res, log_path, registry = _run_faulted(tmp_path, 'a')

    # The log itself is schema-clean.
    _, errors = obs.validate_file(log_path)
    assert errors == [], errors

    report = obs_slo.goodput(log_path, SPEC)

    # Every submitted request classified EXACTLY once, from the log
    # alone: the classes partition the submitted set.
    assert res.accounted
    assert report.requests == len(res.submitted)
    assert sum(report.counts.values()) == report.requests
    assert set(report.by_request) == {rid for rid, _ in res.submitted}

    # Per-tenant counts sum back to the aggregate, class by class.
    assert len(report.per_tenant) >= 2
    for cls in obs_slo.CLASSES:
        assert sum(tb['counts'][cls]
                   for tb in report.per_tenant.values()) \
            == report.counts[cls], cls
    assert sum(tb['requests'] for tb in report.per_tenant.values()) \
        == report.requests

    # The armed fault actually fired and is visible in the SAME log.
    records = obs.read_events(log_path)
    assert any(r['event'] == 'serve.quarantine' for r in records)

    # Same seed -> byte-identical report (fresh engine, fresh log,
    # fresh injector).
    res2, log2, _ = _run_faulted(tmp_path, 'b')
    report2 = obs_slo.goodput(log2, SPEC)
    assert report.to_dict() == report2.to_dict()

    # /metrics exposes nonzero tenant-labeled TTFT histograms for both
    # tenants (live per-tenant goodput for an external Prometheus).
    with MetricsServer(registry) as srv:
        with urllib.request.urlopen(srv.url + '/metrics',
                                    timeout=5) as resp:
            text = resp.read().decode()
    assert render_prometheus(registry) == text
    for tenant in ('t0', 't1'):
        line = next((ln for ln in text.splitlines()
                     if ln.startswith('ddp_serve_ttft_seconds_sum'
                                      f'{{tenant="{tenant}"}}')), None)
        assert line is not None, f'no tenant-labeled TTFT for {tenant}'
        assert float(line.split()[-1]) > 0, line
    # Tenant-labeled queue-wait and admit counters ride along.
    assert 'ddp_serve_queue_wait_seconds_sum{tenant="t0"}' in text
    assert 'ddp_serve_admitted_total{tenant="t0"}' in text


def test_open_loop_overload_sheds_typed_and_accounts(tmp_path, devices):
    """Overload (rate far past service capacity, tiny queue): the
    ladder sheds with typed rejects; the report still partitions the
    submitted set and the rejected class is tenant-attributed."""
    clock = VirtualClock()
    log = obs.EventLog(tmp_path / 'overload.jsonl', clock=clock)
    cfg = LoadGenConfig(seed=3, rate=5000.0, requests=40,
                        tenants=default_tenants(2), vocab=32)
    res = run_load(
        cfg, engine=_engine(),
        serve_config=ServeConfig(queue_limit=4, max_new_tokens=24,
                                 watchdog=False,
                                 evict_before_reject=False),
        registry=MetricsRegistry(), event_log=log, clock=clock)
    log.close()
    assert res.rejected_at_submit, 'overload never shed anything'
    report = obs_slo.goodput(log.path, SPEC)
    assert report.requests == len(res.submitted)
    assert sum(report.counts.values()) == report.requests
    assert report.counts['rejected'] >= len(res.rejected_at_submit)
    rej_by_tenant = sum(tb['counts']['rejected']
                       for tb in report.per_tenant.values())
    assert rej_by_tenant == report.counts['rejected']


def test_virtual_time_latencies_are_exact(tmp_path, devices):
    """The whole point of the virtual clock: latency observations are
    tick arithmetic, not wall noise. A lone request admitted into an
    idle scheduler sees queue_wait == 0 and ttft == one tick per
    prefill chunk + one decode tick."""
    clock = VirtualClock()
    log = obs.EventLog(tmp_path / 'exact.jsonl', clock=clock)
    cfg = LoadGenConfig(seed=0, rate=10.0, requests=1,
                        tenants=[TenantSpec('only', prompt_lo=5,
                                            prompt_hi=5, new_lo=4,
                                            new_hi=4)],
                        vocab=32, tick_seconds=0.01)
    run_load(cfg, engine=_engine(),
             serve_config=ServeConfig(queue_limit=4,
                                      max_new_tokens=8,
                                      watchdog=False),
             registry=MetricsRegistry(), event_log=log, clock=clock)
    log.close()
    (tl,) = obs.reconstruct(log.path).values()
    assert tl.complete and tl.status == 'completed'
    assert tl.queue_wait == 0.0
    # One scheduler tick runs admit -> prefill chunk -> decode with
    # `now` read at tick start and the clock advancing AFTER the tick:
    # an idle scheduler admits, prefills the 4-wide chunk and emits
    # the first token inside the arrival tick, so virtual TTFT is
    # exactly 0 — waiting costs ticks, in-tick work does not.
    assert tl.ttft == 0.0
    assert all(g == pytest.approx(0.01) for g in tl.token_gaps)
    assert len(tl.token_gaps) == 3          # 4 tokens, 3 gaps


# -- trace serialization (save_trace / load_trace) ----------------------

def test_trace_save_load_round_trip_exact(tmp_path):
    """A serialized trace reloads to the last bit — every float, id,
    prompt token and budget — so the identical request stream can
    drive a router topology and its single-process twin byte for
    byte."""
    from distributed_dot_product_tpu.serve import load_trace, save_trace

    cfg = LoadGenConfig(
        seed=11, rate=700.0, requests=32, arrival='bursty',
        tenants=[TenantSpec('t0', share=1.0, deadline_s=0.4),
                 TenantSpec('t1', share=2.0)])
    trace = generate_trace(cfg)
    path = tmp_path / 'trace.json'
    save_trace(path, trace, note='round-trip test')
    loaded = load_trace(path)
    assert len(loaded) == len(trace)
    for a, b in zip(trace, loaded):
        assert b.at == a.at                      # exact, not approx
        assert b.request_id == a.request_id
        assert b.tenant == a.tenant
        assert b.prompt.dtype == np.int32
        assert (b.prompt == a.prompt).all()
        assert b.max_new_tokens == a.max_new_tokens
        assert b.deadline_s == a.deadline_s
    # Serialization is deterministic: same trace, same bytes.
    path2 = tmp_path / 'trace2.json'
    save_trace(path2, loaded, note='round-trip test')
    assert path.read_bytes() == path2.read_bytes()


def test_trace_load_rejects_bad_schema_and_malformed(tmp_path):
    import json

    from distributed_dot_product_tpu.serve import load_trace, save_trace

    p = tmp_path / 'bad_schema.json'
    p.write_text('{"schema": 999, "arrivals": []}')
    with pytest.raises(ValueError, match='schema'):
        load_trace(p)
    trace = generate_trace(LoadGenConfig(seed=1, requests=2))
    good = tmp_path / 'good.json'
    save_trace(good, trace)
    payload = json.loads(good.read_text())
    del payload['arrivals'][1]['prompt']
    mangled = tmp_path / 'mangled.json'
    mangled.write_text(json.dumps(payload))
    with pytest.raises(ValueError, match='arrival 1'):
        load_trace(mangled)


def test_saved_trace_drives_identical_run(tmp_path, devices):
    """Generated and reloaded traces produce the SAME results dict
    through a scheduler — the twin-comparison precondition."""
    from distributed_dot_product_tpu.serve import (
        Scheduler, load_trace, run_trace, save_trace,
    )

    cfg = LoadGenConfig(seed=5, rate=400.0, requests=16,
                        tick_seconds=0.002)
    trace = generate_trace(cfg)
    path = tmp_path / 'trace.json'
    save_trace(path, trace)

    def run(tr):
        clock = VirtualClock()
        sched = Scheduler(
            KernelEngine(slots=2, t_max=64, decode_impl='xla'),
            ServeConfig(watchdog=False, queue_limit=8,
                        max_new_tokens=24),
            clock=clock, registry=MetricsRegistry(),
            fault_injector=False)
        try:
            res = run_trace(sched, tr, clock,
                            tick_seconds=cfg.tick_seconds)
        finally:
            sched.close()
        return {rid: (r.status, tuple(r.tokens))
                for rid, r in res.results.items()}

    assert run(trace) == run(load_trace(path))


@pytest.mark.slow
def test_serve_load_topology_cli(tmp_path):
    """``examples/serve_load.py --topology 1x2`` as a process: the
    trace goes through the router AND the single-process twin, the
    per-member logs merge, and the row records both goodputs plus the
    routing telemetry. The per-member JSONL logs must exist and the
    placements must cover every decode replica."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs, out = tmp_path / 'topo', tmp_path / 'row.json'
    # An inherited fault plan or path selector would change the run.
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')
           and not k.startswith('DDP_TPU_')}
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, 'examples', 'serve_load.py'),
         '--topology', '1x2', '--load-requests', '24',
         '--event-log', str(logs), '--file', str(out)],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    with open(out) as f:
        (rec,) = json.load(f)
    assert rec['topology'] == '1x2' and rec['clock'] == 'virtual'
    assert rec['requests'] == 24
    assert set(rec['routed']) == {'r0', 'r1'}
    assert sum(rec['routed'].values()) + rec['counts']['rejected'] >= 24
    assert rec['handoffs'] >= 1          # the long-prompt tail offloads
    # 2x the capacity on the same trace: the topology never does worse.
    assert rec['goodput_pct'] >= rec['twin_goodput_pct']
    for name in ('router', 'prefill', 'r0', 'r1', 'twin'):
        assert (logs / f'{name}.jsonl').exists(), name
    assert (logs / 'trace.json').exists()
