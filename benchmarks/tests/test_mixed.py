"""The mixed-stack cell's files on the CPU: the driver against the plain
reference at the tiny preset (its own root, ``tiny_mixed``), sound and
broken; the reducer ``mixed_scopes`` on a hand-made trace; the
needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_mixed, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_mixed')
CELL = 'tiny-command-a.decode'
REAL = 'command-a-plus.decode-64k'


def cell_run(capsys, **kwargs):
    cell = loader.Cell(CELL, root=ROOT)
    line = run.run_cell(cell, 4_000_000_007, 0.3, False, jax.devices(),
                        **kwargs)
    out = [json.loads(x) for x in capsys.readouterr().out.splitlines()
           if x.startswith('{')]
    rows = {r['compared']: r for r in out if 'compared' in r}
    return line, rows, out


def test_sound_run_is_correct(capsys):
    line, rows, out = cell_run(capsys)
    assert line['correct'] is True and line['failed'] == 0
    assert set(line['metrics']) == {'decode_tokens_per_s',
                                    'decode_gap_ms_p95', 'setup_s'}
    # float32 on both sides: the reference agrees to rounding
    assert rows['served_logit_gap']['value'] < 1e-4
    assert rows['expert_pick_difference_share']['value'] == 0.0
    assert rows['router_pick_regret']['value'] < 1e-6
    # both modes (the CPU takes the XLA step: no limit in the tiny root)
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:layer', 'xla:ring']
    assert said['cache']['ring_gib'] > 0 and said['cache']['full_gib'] > 0
    # the request compared is never the window's first
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    json.dumps(line)


def test_the_window_serves_min_requests(capsys):
    """``min_requests`` holds the window open past ``--seconds``."""
    cell = loader.Cell(CELL, root=ROOT)
    cell.traffic = dict(cell.traffic, min_requests=5)
    run.run_cell(cell, 4_000_000_007, 0.0, False, jax.devices())
    out = [json.loads(x) for x in capsys.readouterr().out.splitlines()
           if x.startswith('{')]
    window, = [o for o in out if 'requests' in o]
    assert window['requests'] == 5 and window['gaps'] == 5 * 5


def test_float8_reference_is_not_correct(capsys):
    line, rows, _ = cell_run(capsys, operand_dtype=jnp.float8_e4m3fn)
    assert line['correct'] is False
    assert not (rows['served_logit_gap']['ok']
                and rows['expert_pick_difference_share']['ok']
                and rows['router_pick_regret']['ok'])


def altered_token(step):
    def broken(params, tok, caches, stats):
        caches, nxt, ok, stats = step(params, tok, caches, stats)
        return caches, (nxt + 1) % 64, ok, stats
    return broken


def test_broken_timed_path_is_not_correct(capsys):
    line, rows, _ = cell_run(capsys, step_wrapper=altered_token)
    assert line['correct'] is False
    assert not rows['served_logit_gap']['ok']


def test_a_ring_that_loses_rows_is_not_correct(capsys, monkeypatch):
    """A ring of the window's size alone: a request's rows recycle rows
    that the next request's window still holds, the reset restores
    nothing, and the request compared follows a reset. The driver
    refuses such a ring outright; with that check off, the comparison
    sees what it lost."""
    cell = loader.Cell(CELL, root=ROOT)
    cell.config = dict(cell.config, serving={'ring_capacity': 8})
    with pytest.raises(ValueError, match='would lose rows'):
        run.run_cell(cell, 4_000_000_007, 0.3, False, jax.devices())
    capsys.readouterr()
    driver = cell.driver()
    monkeypatch.setattr(driver, 'check_ring_room', lambda *a: None)
    monkeypatch.setattr(cell, 'driver', lambda: driver)
    line = run.run_cell(cell, 4_000_000_007, 0.3, False, jax.devices())
    rows = {r['compared']: r for r in (
        json.loads(x) for x in capsys.readouterr().out.splitlines()
        if x.startswith('{"compared"'))}
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-3


def test_counters_say_what_the_step_routed():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 11)
    server.load()
    server.request()
    stats, = server.stats_read
    t, cfg = cell.traffic, cell.config
    layers, k = cfg['num_hidden_layers'], cfg['num_experts_per_tok']
    lo, hi = cfg['experts_held']
    assert int(stats['step']) == t['new_tokens']
    assert stats['expert_tokens'].shape == (
        layers, cfg['published']['num_experts'])
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']                  # (steps, layers, sessions, k)
    held = sum(len({e for e in np.unique(picks[i, l]) if lo <= e < hi})
               for i in range(len(picks)) for l in range(layers))
    assert int(stats['active']) == held     # over the experts HELD
    routing = driver.routing_readings(cfg, server.stats_read, t['sessions'])
    assert routing['active_experts_per_step'] == held / t['new_tokens']
    assert routing['load_max_over_mean'] >= 1.0
    assert server.cache_gib == flops_mixed.cache_gib(server.caches.layers)


# -- the reducer on a hand-made trace ----------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
ATTN = '/block_{}.decode/attn.decode/lm.attn_proj'
OPS = {   # instruction: (op_name, self ns)
    'flash_decode_ring.1': (
        STEP + ATTN.format(0) + '/ops.flash_decode/ops.flash_decode_ring/'
        'flash_decode_ring/pallas_call', 1000),
    'flash_decode.1': (STEP + ATTN.format(3) + '/ops.flash_decode/'
                       'flash_decode/pallas_call', 4000),
    'fusion.1': (STEP + ATTN.format(0) + '/keys/dot_general', 700),
    'ragged-dot-none.1': ('ragged-dot-none', 3000),
    'fusion.2': (STEP + '/block_0.decode/moe/lm.moe_route/top_k', 300),
    'fusion.3': (STEP + '/block_0.decode/moe/lm.mlp/shared/gate/'
                 'dot_general', 500),
}


def opcode(name):
    if 'dot-' in name or name.startswith('flash'):
        return 'custom-call'
    return name.split('.')[0]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(xspace(program([
        instruction(name, opcode(name), i + 10, op_name)
        for i, (name, (op_name, _)) in enumerate(OPS.items())])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    at, rows = 0, []
    for name, (_, ns) in OPS.items():
        rows.append([f'%{name} {opcode(name)}', at, ns, ns])
        at += ns

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': rows}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {
            'steps': 2,
            'full_decode_per_step': {'bytes': 819e9 * 1e-6, 'flops': 1.0},
            'ring_decode_per_step': {'bytes': 819e9 * 0.2e-6, 'flops': 1.0},
            'cache': {'full_gib': 3.0, 'ring_gib': 0.7},
            'moe': {'active_experts_per_step': 10.0,
                    'expert_bytes': 819e9 * 0.1e-6,
                    'load_max_over_mean': 1.5}}
    return Run


def read(run, name):
    metric = loader.read_json(loader.HERE, 'layer_metrics', f'{name}.json')
    return loader.load_module('reducers', metric['reducer']).read(run,
                                                                  metric)


def test_mixed_scope_metrics_on_a_hand_made_trace(traced):
    # ns of the window over 2 steps, in ms: the two modes apart
    assert read(traced, 'kernel.ring_decode_ms_per_step') == (
        pytest.approx(0.5e-3))
    assert read(traced, 'kernel.full_decode_ms_per_step') == (
        pytest.approx(2e-3))
    # needed 1 us a step over 2; 0.2 us over 0.5
    assert read(traced, 'kernel.full_decode_roofline') == pytest.approx(50.0)
    assert read(traced, 'kernel.ring_decode_roofline') == pytest.approx(40.0)
    assert read(traced, 'cache.full_gib.decode') == 3.0
    assert read(traced, 'cache.ring_gib.decode') == 0.7
    # The accepted readers take the ring mode for the decode kernel, not
    # for the projections its scope is opened under.
    assert read(traced, 'model.attn_proj_ms_per_step.decode') == (
        pytest.approx(0.35e-3))
    assert read(traced, 'model.moe_experts_ms_per_step.decode') == (
        pytest.approx(1.5e-3))
    assert read(traced, 'model.moe_route_ms_per_step.decode') == (
        pytest.approx(0.15e-3))
    assert read(traced, 'model.mlp_ms_per_step.decode') == (
        pytest.approx(0.25e-3))
    assert read(traced, 'model.stack_rest_ms_per_step.decode') == 0.0
    assert read(traced, 'moe.expert_stream_roofline') == pytest.approx(
        100 * 1.0 / 1.5)


def test_a_program_without_a_ring_mode_gives_no_number(traced, tmp_path,
                                                       monkeypatch):
    """As a parent commit's: the new readers return nothing and raise
    nothing."""
    path = tmp_path / 'slab.xplane.pb'
    path.write_bytes(xspace(program([
        instruction('flash_decode.1', 'custom-call', 10,
                    STEP + ATTN.format(0) + '/ops.flash_decode/'
                    'flash_decode/pallas_call')])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    for name in ('kernel.full_decode_ms_per_step',
                 'kernel.full_decode_roofline',
                 'kernel.ring_decode_ms_per_step',
                 'kernel.ring_decode_roofline'):
        assert read(traced, name) is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert read(traced, 'kernel.ring_decode_ms_per_step') is None
    traced.observed = {'steps': 2}
    assert read(traced, 'cache.ring_gib.decode') is None


def test_the_patterns_put_the_ring_mode_in_front():
    from distributed_dot_product_tpu.obs.spans import DEVICE_SCOPES
    reducer = loader.load_module('reducers', 'mixed_scopes')
    classes = [c for c, _ in reducer.patterns()['classes']]
    assert classes[0] == reducer.RING and classes[-1] == scopes.UNATTRIBUTED
    assert sorted(classes[:-1]) == sorted(DEVICE_SCOPES)
    latent = loader.read_json(loader.HERE, 'scope_patterns_latent.json')
    assert reducer.patterns()['classes'][1:] == latent['classes']
    ring = OPS['flash_decode_ring.1'][0]
    assert scopes.classify(ring, reducer.patterns())[0] == reducer.RING
    assert scopes.classify(ring, scopes.patterns())[0] == 'ops.flash_decode'
    assert scopes.classify(OPS['flash_decode.1'][0],
                           reducer.patterns())[0] == 'ops.flash_decode'


def test_every_new_metric_has_its_file_and_the_cell():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    mine = [m for m in bench['per_layer'] if REAL in m.get('workloads', [])]
    new = [m['name'] for m in mine if m['workloads'] == [REAL]]
    assert sorted(new) == [
        'cache.full_gib.decode', 'cache.ring_gib.decode',
        'kernel.full_decode_ms_per_step', 'kernel.full_decode_roofline',
        'kernel.ring_decode_ms_per_step', 'kernel.ring_decode_roofline']
    assert len(mine) == 20
    for name in new:
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        assert metric['reducer'] == 'mixed_scopes'
    cell = loader.Cell(REAL)
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert cell.kind == 'decode_mixed' and cell.chips == 1
    # ISSUE 30's traffic, letter for letter; ``min_requests`` is how long
    # the window measures it, not what is offered
    assert cell.traffic == {
        'kind': 'decode_mixed', 'sessions': 12, 'context': 65536,
        't_max': 66560, 'prefill_chunk': 4096, 'new_tokens': 256,
        'check_samples': 1, 'trace_requests': 1, 'tokens_in_flight': 4,
        'min_requests': 12}
    assert set(cell.limits) == {
        'served_logit_gap', 'expert_pick_difference_share',
        'router_pick_regret', 'decode_impl_is_kernel'}


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    assert flops_mixed.layer_counts(cfg) == (3, 1)
    rows = 65536 + 128 + 1
    full = flops_mixed.full_decode_step(cfg, batch=12, context=65664)
    # 8 KV heads x (K + V) x 128 x 2 B a row, read once for 16 query heads
    assert full['bytes'] == 1 * 12 * 8 * 256 * 2 * (rows + 1)
    # per query head and row: 128 multiply-adds of score, 128 of context
    assert full['flops'] == 1 * 12 * 128 * 2 * 256 * rows
    ring = flops_mixed.ring_decode_step(cfg, batch=12, context=65664)
    assert ring['bytes'] == 3 * 12 * 8 * 256 * 2 * (4096 + 1)
    assert ring['flops'] == 3 * 12 * 128 * 2 * 256 * 4096
    # before the window is full a ring step needs what is there
    early = flops_mixed.ring_decode_step(cfg, batch=1, context=99)
    assert early['bytes'] == 3 * 8 * 256 * 2 * 101
    assert flops_mixed.expert_bytes(cfg) == 3 * 4096 * 4096 * 2
    assert flops_mixed.expected_distinct_held(cfg, 12) == pytest.approx(
        16 * (1 - (120 / 128) ** 12))


def test_shape_table_counts_the_share():
    """ISSUE 30's arithmetic: 4.73 B parameters, 9.47 GB."""
    cell = loader.Cell(REAL)
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d, w = 4096, 4096
    attn = d * 16384 + 2 * d * 1024 + 16384 * d
    layer = attn + 4 * 3 * d * w + d * 128 + 16 * 3 * d * w + d
    assert count == 4 * layer + 32768 * d + d
    assert 4.72e9 < count < 4.74e9
    routers = 4 * d * 128                       # float32
    assert 9.46e9 < 2 * count + 2 * routers < 9.48e9


def test_the_router_draw_follows_the_configurations_init():
    """``init.router_columns`` is read: ``unit_norm`` gives every
    expert's column norm 1, and a configuration without the key keeps
    the plain fan-in draw."""
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()

    def norms(config):
        tree = driver.make(config, 4_000_000_007, jnp.bfloat16)
        router = tree['params']['stack']['block_0']['moe']['router']
        return np.linalg.norm(np.asarray(router, np.float32), axis=0)

    assert cell.config['init']['router_columns'] == 'unit_norm'
    np.testing.assert_allclose(norms(cell.config), 1.0, atol=1e-6)
    plain = dict(cell.config, init={
        k: v for k, v in cell.config['init'].items()
        if k != 'router_columns'})
    assert np.ptp(norms(plain)) > 0.01
