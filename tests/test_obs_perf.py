# -*- coding: utf-8 -*-
"""
Perf observatory (obs/perf.py): compiled-program cost/roofline
accounting over the analysis registry, the committed-baseline gate,
the seeded-regression negative path, and the report rendering.
"""

import json
import os
import subprocess
import sys

import pytest

from distributed_dot_product_tpu.obs import events as obs_events
from distributed_dot_product_tpu.obs import perf
from distributed_dot_product_tpu.obs.events import EventLog, activate

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, 'PERF_BASELINE.json')


def _fixtures_module():
    """tests/ is not a package: `tests.perf_fixtures` resolves as a
    PEP-420 namespace package when the repo root is on sys.path —
    fall back to inserting it (same dance as test_graphlint)."""
    try:
        from tests import perf_fixtures
    except ImportError:
        sys.path.insert(0, REPO)
        from tests import perf_fixtures
    return perf_fixtures


@pytest.fixture(scope='module')
def full_snapshot(devices):
    """ONE compile pass over the whole registry, shared by the
    acceptance tests below (it is the expensive part — the same cost
    class as the graphlint clean-tree gate)."""
    return perf.snapshot()


@pytest.fixture(scope='module')
def fixture_snapshots(devices):
    fx = _fixtures_module()
    return (perf.snapshot(fx.clean()), perf.snapshot(fx.regressed()))


# -- snapshot coverage (tier-1 acceptance) ------------------------------

def test_every_entrypoint_in_snapshot_with_nonzero_cost(full_snapshot):
    """Every registered entrypoint appears with nonzero compiler-counted
    flops AND bytes and a roofline classification — the registry and
    the cost snapshot cannot drift apart."""
    from distributed_dot_product_tpu.analysis.registry import (
        default_entrypoints,
    )
    entries = full_snapshot['entries']
    assert set(entries) == set(default_entrypoints())
    for name, e in entries.items():
        assert 'error' not in e, f'{name}: {e.get("error")}'
        assert e['flops'] > 0, name
        assert e['bytes_accessed'] > 0, name
        assert e['roofline'] in ('compute-bound', 'bandwidth-bound'), name
        assert e['compile_seconds'] > 0, name
        assert e['peak_bytes'] > 0, name


def test_snapshot_schema_and_retrace_totals(full_snapshot):
    assert full_snapshot['schema'] == perf.PERF_SCHEMA_VERSION
    assert full_snapshot['n_devices'] >= 8
    # The engine/decode builders run under watch_traces — the snapshot
    # must have recorded the traces its own compiles incurred.
    rt = full_snapshot['retrace_totals']
    assert any(v > 0 for v in rt.values()), rt
    # The hermetic gate names its target chip: the published v5e
    # peaks, whatever device compiled the programs.
    peaks = full_snapshot['peaks']
    assert peaks == perf.peaks_for(perf.TARGET_DEVICE_KIND).as_dict()
    assert (peaks['flops_per_s'], peaks['bytes_per_s']) == (197e12, 819e9)
    assert peaks['ridge_flops_per_byte'] == pytest.approx(
        peaks['flops_per_s'] / peaks['bytes_per_s'])


def test_committed_baseline_gate_passes(full_snapshot):
    """THE gate scripts/ci.sh stage [5/5] runs: the current tree against
    the committed PERF_BASELINE.json must be violation-free. On an
    intentional program change, refresh with
    `python -m distributed_dot_product_tpu.obs.perf snapshot -o
    PERF_BASELINE.json`."""
    with open(BASELINE) as f:
        baseline = json.load(f)
    violations = perf.check_snapshots(full_snapshot, baseline)
    assert violations == [], '\n'.join(violations)


# -- the regression gate ------------------------------------------------

def test_seeded_upcast_regression_is_caught(fixture_snapshots):
    """An f32 cache upcast persisted into the stored buffer: argument
    bytes double and the compiler-counted bytes/peak blow through the
    tolerances — check must flag the entry by name."""
    clean_snap, bad_snap = fixture_snapshots
    ce = clean_snap['entries']['fx.cache_step']
    be = bad_snap['entries']['fx.cache_step']
    assert be['argument_bytes'] > 1.9 * ce['argument_bytes']
    assert be['peak_bytes'] > 1.25 * ce['peak_bytes']
    violations = perf.check_snapshots(bad_snap, clean_snap)
    assert violations, 'seeded regression not detected'
    assert any('fx.cache_step' in v
               and ('argument_bytes' in v or 'peak_bytes' in v)
               for v in violations), violations
    # The clean tree against its own baseline stays green.
    assert perf.check_snapshots(clean_snap, clean_snap) == []


def test_check_emits_perf_regression_events(fixture_snapshots, tmp_path):
    clean_snap, bad_snap = fixture_snapshots
    log_path = tmp_path / 'perf_events.jsonl'
    with activate(EventLog(log_path)) as log:
        perf.check_snapshots(bad_snap, clean_snap)
        log.flush()
    records = obs_events.read_events(str(log_path))
    regs = [r for r in records if r['event'] == 'perf.regression']
    assert regs and regs[0]['entry'] == 'fx.cache_step'
    # The extended schema validates offline like every other event.
    _, errors = obs_events.validate_file(str(log_path))
    assert errors == []


def test_check_compile_time_tolerance():
    def snap(compile_s):
        return {'schema': 1, 'entries': {'e': {
            'flops': 100.0, 'bytes_accessed': 100.0,
            'argument_bytes': 100, 'peak_bytes': 100,
            'compile_seconds': compile_s}}, 'retrace_totals': {}}
    base, ok, slow = snap(1.0), snap(9.0), snap(40.0)
    tol = perf.Tolerances(compile_factor=10.0, compile_slack_s=5.0)
    assert perf.check_snapshots(ok, base, tol=tol,
                                emit_events=False) == []
    v = perf.check_snapshots(slow, base, tol=tol, emit_events=False)
    assert v and 'compile_seconds' in v[0]


def test_check_coverage_and_retrace_gates():
    entry = {'flops': 1.0, 'bytes_accessed': 1.0, 'argument_bytes': 1,
             'peak_bytes': 1, 'compile_seconds': 0.1}
    base = {'schema': 1, 'entries': {'a': dict(entry)},
            'retrace_totals': {'engine.decode': 1}}
    # Missing entry.
    cur = {'schema': 1, 'entries': {}, 'retrace_totals': {}}
    v = perf.check_snapshots(cur, base, emit_events=False)
    assert any('a' in s and 'coverage' in s for s in v)
    # New unbaselined entry.
    cur = {'schema': 1, 'entries': {'a': dict(entry), 'b': dict(entry)},
           'retrace_totals': {'engine.decode': 1}}
    v = perf.check_snapshots(cur, base, emit_events=False)
    assert any(s.startswith('b: coverage') for s in v)
    # Retrace storm during snapshot.
    cur = {'schema': 1, 'entries': {'a': dict(entry)},
           'retrace_totals': {'engine.decode': 5}}
    v = perf.check_snapshots(cur, base, emit_events=False)
    assert any('retrace_total' in s for s in v)
    # Storm under a NEW watcher name (not in the baseline) is gated
    # against an implicit baseline of 0, not silently skipped.
    cur = {'schema': 1, 'entries': {'a': dict(entry)},
           'retrace_totals': {'engine.decode': 1, 'models.new_step': 7}}
    v = perf.check_snapshots(cur, base, emit_events=False)
    assert any('models.new_step' in s and 'retrace_total' in s
               for s in v), v
    # ...but a current-only name with zero traces (a counter merely
    # alive during the snapshot) stays green.
    cur = {'schema': 1, 'entries': {'a': dict(entry)},
           'retrace_totals': {'engine.decode': 1, 'models.idle': 0}}
    assert perf.check_snapshots(cur, base, emit_events=False) == []
    # Schema drift refuses to compare.
    v = perf.check_snapshots({'schema': 99}, base, emit_events=False)
    assert v and 'schema' in v[0]


def test_snapshot_retrace_delta_ignores_prior_history(devices):
    """Traces incurred (and counters retired) BEFORE a snapshot must
    not charge its retrace delta — otherwise any in-process use after
    prior engine churn fails the gate with a phantom storm."""
    import gc

    from distributed_dot_product_tpu.analysis import retrace
    w = retrace.watch_traces(lambda x: x, 'unit.prior_history',
                             budget=10)
    w(1)
    w(2)
    del w
    gc.collect()
    assert retrace.total('unit.prior_history') == 2   # folded, retired
    fx = _fixtures_module()
    snap = perf.snapshot(fx.clean())
    assert snap['retrace_totals'].get('unit.prior_history', 0) == 0


# -- report + program model --------------------------------------------

def test_report_renders_roofline_table(fixture_snapshots):
    clean_snap, _ = fixture_snapshots
    text = perf.render_report(clean_snap)
    assert 'fx.cache_step' in text
    assert 'bandwidth' in text          # tiny-q cache read: HBM-bound
    assert 'ridge' in text


def test_program_model_measured_columns(devices):
    import jax
    import jax.numpy as jnp
    compiled = jax.jit(
        lambda a, b: a @ b).lower(jnp.ones((64, 64)),
                                  jnp.ones((64, 64))).compile()
    # A roofline against the LIVE device looks its kind up; the CPU
    # mesh has no published peaks, so it must raise, never default.
    with pytest.raises(ValueError, match='no published peaks'):
        perf.program_model(compiled, measured_seconds=1e-3)
    m = perf.program_model(compiled, measured_seconds=1e-3,
                           peaks=perf.peaks_for(perf.TARGET_DEVICE_KIND))
    assert m['flops'] > 0 and m['bytes_accessed'] > 0
    assert m['measured_gflops_per_s'] == pytest.approx(
        m['flops'] / 1e-3 / 1e9)
    assert 0 < m['fraction_of_roofline']
    assert m['roofline'] in ('compute-bound', 'bandwidth-bound')


# -- CLI ----------------------------------------------------------------

def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, '-m', 'distributed_dot_product_tpu.obs.perf',
         *args], capture_output=True, text=True, cwd=REPO, env=env,
        timeout=540)


def test_cli_snapshot_check_report_on_fixture(tmp_path):
    """End-to-end through the CLI surface on the one-entry fixture
    registry: snapshot a clean baseline, check the regressed tree
    against it (exit 1, entry named), check clean-vs-clean (exit 0),
    render the report from the file (no devices touched)."""
    base = tmp_path / 'base.json'
    res = _cli('--registry', 'tests.perf_fixtures:clean',
               'snapshot', '-o', str(base))
    assert res.returncode == 0, res.stdout + res.stderr
    snap = json.loads(base.read_text())
    assert snap['entries']['fx.cache_step']['flops'] > 0

    res = _cli('--registry', 'tests.perf_fixtures:regressed',
               'check', '--against', str(base))
    assert res.returncode == 1, res.stdout + res.stderr
    assert 'fx.cache_step' in res.stdout
    assert 'argument_bytes' in res.stdout or 'peak_bytes' in res.stdout

    res = _cli('--registry', 'tests.perf_fixtures:clean',
               'check', '--against', str(base))
    assert res.returncode == 0, res.stdout + res.stderr
    assert 'OK' in res.stdout

    res = _cli('report', str(base))
    assert res.returncode == 0, res.stdout + res.stderr
    assert 'fx.cache_step' in res.stdout
