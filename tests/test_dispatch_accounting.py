# -*- coding: utf-8 -*-
"""
Dispatch-floor accounting (serve/engine.py program_seconds odometer +
serve/scheduler.py per-tick split): every decode tick stamps REAL tick
wall time vs device-program time into a `serve.dispatch` event and the
`serve.dispatch_overhead_seconds` / `serve.device_seconds` histograms,
each committed token carries its tick's `device_seconds`, the split
surfaces in /metrics exposition and the load driver's row helper — and
none of it touches the virtual timeline (the phase partition stays
exact with the accounting on, which is always).
"""

import importlib.util
import os

import numpy as np
import pytest

from distributed_dot_product_tpu.obs.critpath import (
    attribute, dispatch_floor,
)
from distributed_dot_product_tpu.obs.events import (
    EventLog, read_events, validate_file,
)
from distributed_dot_product_tpu.obs.exporter import render_prometheus
from distributed_dot_product_tpu.serve import (
    KernelEngine, Scheduler, ServeConfig, VirtualClock,
)
from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

pytestmark = pytest.mark.obs

VOCAB = 16


def _run(tmp_path, *, spec=None, max_new=5, n=3):
    clock = VirtualClock()
    log = EventLog(tmp_path / 'serve.jsonl', clock=clock)
    registry = MetricsRegistry()
    cfg_kw = dict(queue_limit=8, max_new_tokens=max_new,
                  watchdog=False)
    if spec:
        cfg_kw.update(spec=spec, spec_k=3)
    sched = Scheduler(
        KernelEngine(slots=2, t_max=32, vocab=VOCAB, heads=2,
                     head_dim=4, prefill_chunk=4, seed=5,
                     decode_impl='xla'),
        ServeConfig(**cfg_kw), clock=clock, registry=registry,
        fault_injector=False, event_log=log,
        on_tick=lambda s: clock.advance(0.01))
    for i in range(n):
        sched.submit(np.asarray([i + 1], np.int32),
                     request_id=f'r{i}')
    results = sched.run_until_idle()
    sched.close()
    log.close()
    return log.path, registry, results


def test_every_decode_tick_stamps_the_split(tmp_path, devices):
    path, registry, results = _run(tmp_path)
    records, errors = validate_file(path)
    assert errors == [], errors

    disp = [r for r in records if r['event'] == 'serve.dispatch']
    assert disp, 'no serve.dispatch records on a decode run'
    for r in disp:
        # REAL seconds: the program slice is timed inside the tick
        # window, so tick wall time bounds it.
        assert 0.0 <= r['device_seconds'] <= r['tick_seconds'] + 1e-9
        assert r['overhead'] == pytest.approx(
            max(0.0, r['tick_seconds'] - r['device_seconds']))
        assert r['tokens'] >= 0
        assert 'request_id' not in r     # per-tick, not per-stream
    # Tick token counts fold to the run's committed total.
    total_tokens = sum(len(res.tokens) for res in results.values())
    assert sum(r['tokens'] for r in disp) == total_tokens


def test_tokens_carry_their_ticks_device_seconds(tmp_path, devices):
    path, _, _ = _run(tmp_path)
    records = read_events(path)
    decodes = [r for r in records if r['event'] == 'serve.decode']
    assert decodes
    stamped = [r for r in decodes if r.get('device_seconds')
               is not None]
    assert stamped, 'no serve.decode carries the device stamp'
    for r in stamped:
        assert r['device_seconds'] >= 0.0
    # All tokens committed by one tick share that tick's stamp.
    disp = {r['step']: r for r in records
            if r['event'] == 'serve.dispatch'}
    assert disp


def test_histograms_and_metrics_exposition(tmp_path, devices):
    path, registry, _ = _run(tmp_path)
    h_over = registry.peek('histogram',
                           'serve.dispatch_overhead_seconds')
    h_dev = registry.peek('histogram', 'serve.device_seconds')
    assert h_over is not None and h_over.total_count > 0
    assert h_dev is not None and h_dev.total_count == \
        h_over.total_count
    n_disp = sum(1 for r in read_events(path)
                 if r['event'] == 'serve.dispatch')
    assert h_over.total_count == n_disp

    text = render_prometheus(registry)
    assert 'dispatch_overhead_seconds' in text
    assert 'device_seconds' in text


def test_spec_ticks_account_too(tmp_path, devices):
    """Speculative decoding runs its device work through verify_step —
    the odometer must cover that path as well."""
    path, registry, results = _run(tmp_path, spec='ngram', max_new=8)
    assert any(len(r.tokens) for r in results.values())
    records = read_events(path)
    assert any(r['event'] == 'spec.verify' for r in records)
    disp = [r for r in records if r['event'] == 'serve.dispatch']
    assert disp
    assert any(r['device_seconds'] > 0 for r in disp), (
        'spec verify steps never moved the program odometer')


def test_accounting_never_touches_the_virtual_partition(tmp_path,
                                                        devices):
    """The REAL-seconds stamps are payload only: with the accounting
    on (it cannot be turned off), every request's virtual-time phase
    partition still closes exactly."""
    path, _, results = _run(tmp_path)
    chains = attribute(path)
    assert set(chains) == set(results)
    for c in chains.values():
        assert not c.partial and c.ok, (c.request_id, c.errors)
    floor = dispatch_floor(path)
    assert floor['total']['ticks'] > 0
    assert floor['total']['overhead_per_token'] is not None


def test_load_driver_row_helper_reads_the_registry(tmp_path, devices):
    """examples/serve_load.py's `_dispatch_split` turns the two
    histograms into the load driver's row columns."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        'serve_load_for_test',
        os.path.join(repo, 'examples', 'serve_load.py'))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    _, registry, results = _run(tmp_path)
    n_tok = sum(len(r.tokens) for r in results.values())
    row = bench._dispatch_split(registry, n_tok)
    assert row['dispatch_ticks'] > 0
    assert row['dispatch_overhead_s'] >= 0.0
    assert row['dispatch_overhead_ms_per_token'] == pytest.approx(
        row['dispatch_overhead_s'] / n_tok * 1e3)
    assert 0.0 <= row['dispatch_overhead_pct'] <= 100.0
    assert row['dispatch_overhead_p99_ms'] >= 0.0
    # An idle registry yields no columns rather than zeros.
    assert bench._dispatch_split(MetricsRegistry(), 0) == {}
