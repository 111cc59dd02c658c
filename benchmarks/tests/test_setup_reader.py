"""The set-up reader (``reducers/build_ledger.py``) on a hand-made ledger
and phase list: a record lies in the phase that holds its start, only
phases that are part of ``setup_s`` count, the five time metrics add up
to the counted phases; every new metric has its file and lists all ten
cells; a package that predates the ledger gives no number."""

import json
import os
import types

import pytest

from benchmarks import harness, loader

METRICS = ('setup.trace_s', 'setup.kernel_trace_s', 'setup.lower_s',
           'setup.backend_compile_s', 'setup.cache_misses',
           'setup.execute_s')
TIMES = tuple(m for m in METRICS if m != 'setup.cache_misses')


def rec(kind, start, seconds, self_seconds=None, stage=None):
    return types.SimpleNamespace(
        kind=kind, stage=stage or kind, start=start, seconds=seconds,
        self_seconds=seconds if self_seconds is None else self_seconds)


# [name, seconds, counted, ended at]: runtime_init 0-8 (not counted),
# init 10-14, lower 14-20, compile 20-23, prefill 23-53, reference
# 53-60 (not counted), warm 60-61; the window and what follows it lie
# in no phase.
PHASES = [['runtime_init', 8.0, False, 8.0], ['init', 4.0, True, 14.0],
          ['lower', 6.0, True, 20.0], ['compile', 3.0, True, 23.0],
          ['prefill', 30.0, True, 53.0], ['reference', 7.0, False, 60.0],
          ['warm', 1.0, True, 61.0]]
COUNTED = 4.0 + 6.0 + 3.0 + 30.0 + 1.0

RECORDS = [
    rec('compile', 1.0, 2.0),                   # runtime_init: left out
    rec('trace', 9.0, 0.5),                     # between phases: left out
    rec('trace', 10.0, 1.0), rec('lower', 11.0, 0.5),
    rec('cache_miss', 11.6, 0.0), rec('compile', 11.5, 1.5),
    # the step's trace, 4 s, holds an inner jit's 0.5 s and a kernel's
    # body of 1 s (0.8 s of it the inner jit Pallas traces it as): its
    # own Python is 2.5 s
    rec('trace', 14.5, 0.5), rec('trace', 15.6, 0.8, stage='build'),
    rec('build', 15.5, 1.0, self_seconds=0.2),
    rec('trace', 14.0, 4.0, self_seconds=2.5),
    rec('lower', 18.0, 2.0),
    # a compile that hit: 0.9 s of it the cache's read
    rec('cache_hit', 20.95, 0.0), rec('cache_read', 20.0, 0.9),
    rec('compile', 20.0, 1.0, self_seconds=0.1),
    rec('cache_miss', 22.9, 0.0), rec('compile', 21.0, 2.0),
    rec('trace', 55.0, 1.0), rec('compile', 56.0, 3.0),   # reference
    rec('cache_miss', 58.0, 0.0),
    rec('trace', 60.2, 0.1),                    # warm: a late retrace
    rec('compile', 70.0, 5.0),                  # the window: no phase
]


@pytest.fixture
def reducer():
    return loader.Cell('mpt-7b.decode-12k').reducer('build_ledger')


def metric(name):
    return loader.read_json(loader.find('layer_metrics', f'{name}.json'))


def test_records_outside_the_counted_phases_are_left_out(reducer):
    got = {m: reducer.reduce(RECORDS, PHASES, metric(m)) for m in METRICS}
    assert got['setup.trace_s'] == pytest.approx(1.0 + 0.5 + 2.5 + 0.1)
    assert got['setup.kernel_trace_s'] == pytest.approx(1.0)
    assert got['setup.lower_s'] == pytest.approx(0.5 + 2.0)
    # XLA and Mosaic, or the cache's read where it hit
    assert got['setup.backend_compile_s'] == pytest.approx(
        1.5 + 0.1 + 0.9 + 2.0)
    assert got['setup.cache_misses'] == 2       # the reference's is not
    built = 3.0 + 6.0 + 3.0 + 0.1
    assert got['setup.execute_s'] == pytest.approx(COUNTED - built)


def test_the_five_time_metrics_add_up_to_the_counted_phases(reducer):
    five = sum(reducer.reduce(RECORDS, PHASES, metric(m)) for m in TIMES)
    assert five == pytest.approx(COUNTED)
    assert five == pytest.approx(sum(
        s for _, s, counted, _ in PHASES if counted))


def test_execute_is_the_time_in_which_nothing_is_open(reducer):
    # a record that runs over its phase's end counts to the end alone
    recs = [rec('compile', 12.0, 4.0)]
    assert reducer.reduce(recs, PHASES[:3], metric(
        'setup.execute_s')) == pytest.approx(10.0 - 4.0)
    # no counted phase yet: nothing to say
    assert reducer.reduce(recs, PHASES[:1], metric(
        'setup.execute_s')) is None


def test_read_takes_the_programs_ledger_and_the_harness_phases(
        reducer, monkeypatch, capsys):
    from distributed_dot_product_tpu.utils import build_ledger
    ledger = build_ledger.BuildLedger()
    monkeypatch.setattr(build_ledger, '_LEDGER', ledger)
    ledger.note('trace', 'step_fn', 14.0, 4.0)
    ledger.note('cache_miss', None, 21.5, 0.0)
    ledger.note('compile', 'step_fn', 21.0, 2.0)
    ledger.note('compile', 'reference_fn', 56.0, 3.0)   # its own phase
    ledger.note('compile', 'late_fn', 70.0, 5.0)        # the window
    monkeypatch.setattr(harness, 'PHASES', PHASES)
    got = {m: reducer.read(None, metric(m)) for m in METRICS}
    assert got == {
        'setup.trace_s': 4.0, 'setup.kernel_trace_s': 0,
        'setup.lower_s': 0, 'setup.backend_compile_s': 2.0,
        'setup.cache_misses': 1,
        'setup.execute_s': pytest.approx(COUNTED - 6.0)}
    # ONE metric's file asks for the ledger's account of the run
    said = [json.loads(line) for line in capsys.readouterr().out.split('\n')
            if line.startswith('{"build_ledger"')]
    assert len(said) == 1
    account = said[0]['build_ledger']
    assert account['records'] == 5 and account['dropped'] == 0
    # a compile behind set-up in no phase (the window's, which
    # `window_compiles` cannot name) is named; the reference's lies in
    # its own (uncounted) phase
    assert account['built_after_setup_in_no_phase'] == [
        ['late_fn', 'compile', 5.0]]
    assert account['by_phase']['lower'] == {'trace': 4.0}
    assert account['by_phase']['compile'] == {'compile': 2.0, 'misses': 1}
    assert ['step_fn', 6.0] in [c[:2] for c in account['costliest']]


def test_only_a_package_without_the_module_gives_no_number(
        reducer, monkeypatch):
    """A parent commit's package has no ``utils/build_ledger``, and the
    driver runs it under this benchmark: the reader returns nothing and
    raises nothing there. Anything else that stops the import raises."""
    import importlib

    def missing(name):
        def fail(module):
            raise ModuleNotFoundError(f'No module named {name!r}',
                                      name=name)
        return fail

    monkeypatch.setattr(harness, 'PHASES', PHASES)
    monkeypatch.setattr(importlib, 'import_module', missing(reducer.LEDGER))
    assert [reducer.read(None, metric(m)) for m in METRICS] == 6 * [None]
    # the module is there and what IT imports is not: no silence
    monkeypatch.setattr(importlib, 'import_module', missing('jax.monitoring'))
    with pytest.raises(ModuleNotFoundError):
        reducer.read(None, metric('setup.trace_s'))


def test_every_new_metric_has_its_file_and_lists_all_ten_cells():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    cells = [w['name'] for w in bench['workloads']]
    assert len(cells) == 10
    entries = {m['name']: m for m in bench['per_layer']}
    assert [m['name'] for m in bench['per_layer']][-6:] == list(METRICS)
    for name in METRICS:
        entry = entries[name]
        assert entry['workloads'] == cells
        assert (entry['layer'], entry['moves'], entry['source'],
                entry['better']) == ('set-up', 'setup_s',
                                     'program_counter', 'lower')
        assert entry['unit'] == ('programs' if name == 'setup.cache_misses'
                                 else 's')
        path = loader.find('layer_metrics', f'{name}.json')
        assert os.path.isfile(path)
        assert metric(name)['reducer'] == 'build_ledger'
        assert metric(name)['layer'] == 'set-up'
    # every cell reports the six, and setup_s, which they move
    for cell in cells:
        mine = [m['name'] for m in loader.Cell(cell).per_layer()]
        assert set(METRICS) <= set(mine)
