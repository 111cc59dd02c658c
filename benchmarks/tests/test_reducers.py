"""Each reducer on a small recorded trace: two steps of the first
traced run of ``starcoder2-3b.train-16k`` on the chip (PR 23), and a
hand-made one with collectives."""

import os

import pytest

from benchmarks import loader, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))


class FakeRun:
    def __init__(self, trace, observed, device=None):
        self.trace, self.observed = trace, observed
        self.patterns = tr.patterns()
        self.peaks = loader.peaks_for('TPU v5 lite')
        self.device = device or {}


@pytest.fixture(scope='module')
def recorded():
    raw = loader.read_json(HERE, 'recorded_trace.json')
    return {'devices': {k: tr.with_self_time(v)
                        for k, v in raw['devices'].items()},
            'host': raw['host']}, raw['steps']


def read(name, run, **metric):
    return loader.load_module('reducers', name).read(run, metric)


def test_classes_partition_the_step(recorded):
    trace, steps = recorded
    run = FakeRun(trace, {'steps': steps})
    kernel = read('class_ms_per_step', run, op_class='kernel')
    xla = read('class_ms_per_step', run, op_class='xla')
    # The Steps line of that trace gave 751.13 ms a step, with no idle
    # time: self times of the classes add up to it.
    assert kernel == pytest.approx(181.93, abs=0.01)
    assert xla == pytest.approx(569.91, abs=0.01)
    assert read('collective_ms_per_step', run) is None
    assert read('exposed_collective', run) is None
    assert read('idle', run) == pytest.approx(0.0013, abs=0.0005)


def test_kernel_roofline(recorded):
    trace, steps = recorded
    need = {'flops': 10823695073280, 'bytes': 3271557120}
    run = FakeRun(trace, {'steps': steps, 'flash_per_step': need})
    share = read('kernel_roofline', run, needs='flash_per_step')
    assert share == pytest.approx(100 * (need['flops'] / 197e12) / 0.18193,
                                  rel=1e-3)
    assert read('kernel_roofline', FakeRun(trace, {'steps': steps}),
                needs='decode_per_step') is None


def test_mfu_and_peak_hbm():
    run = FakeRun({'devices': {}, 'host': []},
                  {'model_flops_per_token': 4e9, 'tokens_per_s': 19700.0,
                   'chips': 1}, {'memory_peak_bytes': 3 * 2 ** 30})
    assert read('mfu', run) == pytest.approx(40.0)
    assert read('peak_hbm', run) == pytest.approx(3.0)
    assert read('mfu', FakeRun({'devices': {}, 'host': []}, {})) is None
    assert read('idle', FakeRun({'devices': {}, 'host': []}, {})) is None


def test_exposed_collective_time_and_idle_gaps():
    ops = tr.with_self_time([
        ['%while.1 while', 0, 1000],
        ['%fusion.1 fusion', 0, 400],
        ['%attn.1 custom-call', 700, 300],
        ['%all-reduce.2 all-reduce', 1500, 100],    # after a 500 gap
    ])
    trace = {'devices': {'/device:TPU:0': ops,
                         '/device:TPU:1': tr.with_self_time(
                             [['%fusion.1 fusion', 0, 100]])},
             # in flight 300..600: 100 behind the fusion, 200 bare
             'async': {'/device:TPU:0': [
                 ['%all-gather-start.1 all-gather-start', 300, 300]]},
             'host': [['bench.fetch_loss', 900, 700],
                      ['bench.outer', 0, 2000]]}
    run = FakeRun(trace, {'steps': 1})
    pats = run.patterns
    assert tr.busiest(trace, pats) == '/device:TPU:0'
    assert read('collective_ms_per_step', run) == pytest.approx(400e-6)
    # 300 of the 400 are uncovered by a compute leaf
    assert read('exposed_collective', run) == pytest.approx(75.0)
    assert read('class_ms_per_step', run,
                op_class='kernel') == pytest.approx(300e-6)
    # the while's own time is what its children leave: 1000 - 400 - 300
    assert read('class_ms_per_step', run,
                op_class='xla') == pytest.approx(700e-6)
    assert read('idle', run) == pytest.approx(100 * 500 / 1600)
    gaps = tr.breakdown(trace, pats)['idle_gaps']
    assert gaps == [['bench.fetch_loss', pytest.approx(500e-9)]]
    assert tr.busy_seconds(trace)['/device:TPU:0'] == pytest.approx(1.1e-6)


def test_short_name():
    text = ('%attn.38 = (bf16[24,16384,128]{2,1,0:T(8,128)(2,1)}, '
            'bf16[24,16384,128]{2,1,0:T(8,128)(2,1)}) custom-call(s32[1]{0}'
            ' %x), custom_call_target="tpu_custom_call"')
    assert tr.short_name(text) == '%attn.38 custom-call'
    assert tr.short_name('%while.15 = (s32[]{:T(128)}, f32[5,3072]{1,0}) '
                         'while(%tuple.3), body=%b') == '%while.15 while'
    assert tr.short_name('1') == '1'
    assert tr.op_class('%attn.38 custom-call', tr.patterns()) == 'kernel'
    assert tr.op_class('%all-gather-start.2 all-gather-start',
                       tr.patterns()) == 'collective'
    assert tr.op_class('%fusion.3 fusion', tr.patterns()) == 'xla'
