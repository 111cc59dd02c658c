"""``tools/trace_prefill.py``'s reduction on a hand-made trace: the
device operations inside the prefill programs' executions alone, joined
to the cell's own patterns by (program, instruction), a call's
milliseconds by scope, the idle share a session."""

import re

import pytest

from benchmarks import loader, scopes
from benchmarks.tools import trace_prefill as tp
from test_scopes import instruction, message, program

CELL = 'ling-3.0-flash.decode-32k'


def device_plane(lines):
    """An XPlane ``/device:TPU:0`` from ``{line: [(event name, start
    ns, duration ns), ...]}``."""
    names = sorted({n for events in lines.values() for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    metas = [message((1, i), (2, message((1, i), (2, n))))
             for n, i in ids.items()]
    xlines = [message((1, k + 1), (2, line), (3, 0), *[
        (4, message((1, ids[n]), (2, s * 1000), (3, d * 1000)))
        for n, s, d in events])
        for k, (line, events) in enumerate(lines.items())]
    return message((2, '/device:TPU:0'), *[(3, x) for x in xlines],
                   *[(4, m) for m in metas])


def xspace(lines, programs):
    stat = message((1, 7), (2, message((1, 7), (2, scopes.HLO_STAT))))
    metas = [message((1, i + 1), (2, message(
        (1, i + 1), (2, name), (5, message((1, 7), (6, proto))))))
        for i, (name, proto) in enumerate(programs.items())]
    return message((1, device_plane(lines)),
                   (1, message((2, scopes.METADATA_PLANE), (5, stat),
                               *[(4, m) for m in metas])))


def text(name, opcode='fusion'):
    return f'%{name} = f32[8]{{0}} {opcode}()'


@pytest.fixture
def trace(tmp_path):
    """Two sessions of two prefill calls, an insert after each session;
    the insert program numbers a fusion as the prefill program does and
    names it otherwise."""
    prefill = program([
        instruction('fusion.1', 'fusion', 10, 'jit(prefill_fn)/lm.mlp/dot'),
        instruction('delta.2', 'fusion', 11,
                    'jit(prefill_fn)/lm.stack_carry/ops.delta_scan/mul'),
        instruction('flash_fwd.3', 'custom-call', 12,
                    'jit(prefill_fn)/lm.attn_proj/ops.flash_fwd/pallas'),
        instruction('copy.4', 'copy', 13)])
    insert = program([instruction('fusion.1', 'fusion', 10,
                                  'jit(insert_fn)/lm.head/dot')])
    mods, ops = [], []
    for call, at in enumerate((0, 1000, 3000, 4000)):
        mods.append(('jit_prefill_fn(11)', at, 900))
        ops += [(text('fusion.1'), at, 400),
                (text('delta.2'), at + 400, 300),
                (text('flash_fwd.3', 'custom-call'), at + 700, 100),
                (text('copy.4', 'copy'), at + 800, 50)]
        if call % 2:
            mods.append(('jit_insert_fn(12)', at + 1000, 500))
            ops.append((text('fusion.1'), at + 1000, 500))
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(xspace(
        {tp.MODULE_LINE: mods, 'XLA Ops': ops},
        {'jit_prefill_fn(11)': prefill, 'jit_insert_fn(12)': insert}))
    return str(path)


def test_prefill_by_scope_on_a_hand_made_trace(trace):
    got = tp.reduce(trace, loader.Cell(CELL), re.compile('prefill'), 2)
    assert got['programs'] == ['prefill_fn']
    assert (got['calls'], got['calls_a_session']) == (4, 2)
    assert got['module_ms_a_call'] == pytest.approx(900e-6)
    assert got['device_ms_a_call'] == pytest.approx(850e-6)
    # the insert's `fusion.1` is another program's: neither its time nor
    # its name (lm.head) reaches the prefill's rows
    assert got['by_scope_ms_a_call'] == {
        'lm.mlp': pytest.approx(400e-6),
        'ops.delta_scan': pytest.approx(300e-6),
        'ops.flash_fwd': pytest.approx(100e-6),
        'unattributed': pytest.approx(50e-6)}
    assert list(got['by_scope_ms_a_call']) == [
        'lm.mlp', 'ops.delta_scan', 'ops.flash_fwd', 'unattributed']
    # a session: first call's start to second call's end, 1 900 ns of
    # which 2 x 850 are busy
    assert got['idle_pct_by_session'] == [
        pytest.approx(100 * (1 - 1700 / 1900))] * 2
    assert got['top_ops_ms_a_call'][0] == [
        '%fusion.1 fusion', 'lm.mlp', pytest.approx(400e-6)]


def test_no_prefill_program_no_reading(trace):
    assert tp.reduce(trace, loader.Cell(CELL),
                     re.compile('no_such_program'), 2) is None


def test_names_and_patterns():
    assert tp.program_of('jit_prefill_fn(1234567)') == 'prefill_fn'
    assert tp.program_of('jit_step_fn') == 'step_fn'
    # the cell's own patterns: the file of its readers that knows most
    classes = [c for c, _ in tp.cell_patterns(loader.Cell(CELL))['classes']]
    assert {'ops.delta_scan', 'ops.mla_decode', 'lm.moe_route'} <= set(
        classes)
    assert len(classes) > len(scopes.patterns()['classes'])
    assert tp.inside([['a', 5, 1, 1], ['b', 15, 1, 1], ['c', 25, 1, 1]],
                     [[4, 10], [20, 30]]) == [['a', 5, 1, 1],
                                              ['c', 25, 1, 1]]
