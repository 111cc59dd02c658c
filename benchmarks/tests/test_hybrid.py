"""The hybrid-stack cell's files on the CPU: the driver against the plain
reference at the tiny preset (its own root, ``tiny_hybrid``), sound and
broken — a restore that restores nothing among the broken; the reducer
``hybrid_scopes`` on a hand-made trace; the needed-work functions
against hand counts. (The reference's literal recurrence against a
64-token hand computation is tier-1's, ``tests/test_hybrid_stack.py``.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_hybrid, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_hybrid')
CELL = 'tiny-nemotron.decode'
REAL = 'nemotron-3-super.decode-32k'


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
    assert rows['nonfinite_state_resets']['value'] == 0
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:layer']     # the one slab
    assert said['cache']['state_gib'] > 0 and said['cache']['full_gib'] > 0
    # the request compared follows a restore
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    assert {'snapshot', 'prefill'} <= {o.get('setup_part') for o in out}
    json.dumps(line)


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


def test_a_reset_that_restores_nothing_is_not_correct(capsys, monkeypatch):
    """The lengths set back and the states left where the last request
    took them — what a slab or a ring needs and a recurrent state does
    not survive: the request compared follows a reset, and the
    comparison sees it."""
    from distributed_dot_product_tpu.models import decode
    monkeypatch.setattr(decode, 'restore_states',
                        lambda caches, snapshot: caches)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-3


def test_a_nonfinite_state_is_seen(capsys):
    def poisoned(step):
        def broken(params, tok, caches, stats):
            caches, nxt, ok, stats = step(params, tok, caches, stats)
            caches[1] = caches[1]._replace(
                state=caches[1].state.at[0, 0, 0, 0].set(jnp.inf))
            return caches, nxt, ok, stats
        return broken
    line, rows, _ = cell_run(capsys, step_wrapper=poisoned)
    assert line['correct'] is False
    assert rows['nonfinite_state_resets']['value'] > 0


def test_counters_say_what_the_step_routed():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 11)
    server.load()
    server.request()
    stats, = server.stats_read
    t, cfg = cell.traffic, cell.config
    layers, k = len(driver.expert_layers(cfg)), cfg['num_experts_per_tok']
    lo, hi = cfg['experts_held']
    assert layers == 5 and int(stats['step']) == t['new_tokens']
    assert stats['expert_tokens'].shape == (
        layers, cfg['published']['n_routed_experts'])
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']                  # (steps, layers, sessions, k)
    held = sum(len({e for e in np.unique(picks[i, l]) if lo <= e < hi})
               for i in range(len(picks)) for l in range(layers))
    assert int(stats['active']) == held     # over the experts HELD
    routing = driver.routing_readings(cfg, server.stats_read, t['sessions'])
    assert routing['active_experts_per_step'] == held / t['new_tokens']
    assert routing['expert_bytes'] == 2 * 16 * 12 * 2
    assert server.cache_gib == flops_hybrid.cache_gib(server.caches.layers)
    # the context picks kept are the sampled session's alone
    assert server.context_picks.shape == (layers, t['context'], k)
    assert server.sampled == driver.sampled_session(11, t['sessions'])


def test_the_sampled_session_is_sample_requests_first_draw():
    from benchmarks.drivers.decode import sample_requests
    driver = loader.Cell(CELL, root=ROOT).driver()
    for seed in (0, 7, 4_000_000_007, 2 ** 33 + 5):
        (r, s), = sample_requests(seed, [None] * 3, 48, 1)
        assert (r, s) == (2, driver.sampled_session(seed, 48))


# -- the reducer on a hand-made trace ----------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'fusion.1': (STEP + '/block_1.decode/ssm.decode/ops.ssm_step/mul', 2000),
    'fusion.2': (STEP + '/block_1.decode/ssm.decode/lm.ssm_proj/in_proj/'
                 'dot_general', 1200),
    'fusion.3': (STEP + '/block_0.decode/moe/lm.moe_latent/latent_down/'
                 'dot_general', 400),
    'ragged-dot-none.1': ('ragged-dot-none', 3000),
    'fusion.4': (STEP + '/block_0.decode/moe/lm.moe_route/top_k', 300),
    'fusion.5': (STEP + '/block_0.decode/moe/lm.mlp/shared/up/'
                 'dot_general', 500),
    'flash_decode.1': (STEP + '/block_10.decode/attn.decode/lm.attn_proj/'
                       'ops.flash_decode/flash_decode/pallas_call', 4000),
    'fusion.6': (STEP + '/block_10.decode/ln1/mul', 100),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 6000),
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
            'steps': 2, 'requests': 3,
            'full_decode_per_step': {'bytes': 819e9 * 1e-6, 'flops': 1.0},
            'ssm_step_per_step': {'bytes': 819e9 * 0.8e-6, 'flops': 1.0},
            'cache': {'full_gib': 1.5, 'state_gib': 0.9},
            'moe': {'active_experts_per_step': 10.0,
                    'expert_bytes': 819e9 * 0.1e-6,
                    'load_max_over_mean': 1.5}}
    return Run


def read(run, name):
    metric = loader.read_json(loader.HERE, 'layer_metrics', f'{name}.json')
    return loader.load_module('reducers', metric['reducer']).read(run,
                                                                  metric)


def test_hybrid_scope_metrics_on_a_hand_made_trace(traced):
    # ns of the window over 2 steps, in ms
    assert read(traced, 'kernel.ssm_step_ms_per_step') == (
        pytest.approx(1e-3))
    # needed 0.8 us a step over 1 us
    assert read(traced, 'kernel.ssm_step_roofline') == pytest.approx(80.0)
    assert read(traced, 'kernel.attn_decode_ms_per_step') == (
        pytest.approx(2e-3))
    assert read(traced, 'kernel.attn_decode_roofline') == (
        pytest.approx(50.0))
    assert read(traced, 'model.ssm_proj_ms_per_step.decode') == (
        pytest.approx(0.6e-3))
    assert read(traced, 'model.moe_latent_ms_per_step.decode') == (
        pytest.approx(0.2e-3))
    assert read(traced, 'model.stack_rest_ms_per_step.hybrid') == (
        pytest.approx(0.05e-3))
    assert read(traced, 'cache.state_gib.decode') == 0.9
    # a restore runs once a request: 6000 ns over 3 requests
    assert read(traced, 'cache.state_restore_ms_per_request') == (
        pytest.approx(2e-3))
    # The accepted readers, whose cell list this cell joins, read what
    # they read elsewhere; under THEIR patterns the new scopes are the
    # stack's, which is why this cell has a stack_rest of its own.
    assert read(traced, 'model.moe_experts_ms_per_step.decode') == (
        pytest.approx(1.5e-3))
    assert read(traced, 'model.moe_route_ms_per_step.decode') == (
        pytest.approx(0.15e-3))
    assert read(traced, 'model.mlp_ms_per_step.decode') == (
        pytest.approx(0.25e-3))
    assert read(traced, 'model.attn_proj_ms_per_step.decode') == 0.0
    assert read(traced, 'model.unscoped_ms_per_step.decode') == (
        pytest.approx(3e-3))           # the restore: no scope they know
    assert read(traced, 'cache.full_gib.decode') == 1.5
    assert read(traced, 'moe.expert_stream_roofline') == pytest.approx(
        100 * 1.0 / 1.5)
    assert read(traced, 'model.stack_rest_ms_per_step.decode') == (
        pytest.approx((2000 + 1200 + 400 + 100) / 2 * 1e-6))


def test_a_program_without_the_new_scopes_gives_no_number(traced, tmp_path,
                                                          monkeypatch):
    """As a parent commit's: the new readers return nothing and raise
    nothing."""
    path = tmp_path / 'slab.xplane.pb'
    path.write_bytes(xspace(program([
        instruction('flash_decode.1', 'custom-call', 10,
                    OPS['flash_decode.1'][0])])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    new = new_metrics()
    for name in new:
        if not name.startswith('cache.state_gib'):
            assert read(traced, name) is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert read(traced, 'kernel.ssm_step_ms_per_step') is None
    traced.observed = {'steps': 2}
    assert read(traced, 'cache.state_gib.decode') is None


def test_the_patterns_put_the_new_scopes_in_front():
    from distributed_dot_product_tpu.obs.spans import DEVICE_SCOPES
    reducer = loader.load_module('reducers', 'hybrid_scopes')
    classes = [c for c, _ in reducer.patterns()['classes']]
    assert sorted(classes[:5]) == sorted(reducer.NEW_SCOPES)
    assert classes[-1] == scopes.UNATTRIBUTED
    assert sorted(classes[:-1]) == sorted(DEVICE_SCOPES)
    mixed = loader.read_json(loader.HERE, 'scope_patterns_mixed.json')
    assert reducer.patterns()['classes'][5:] == mixed['classes']
    for name, (op_name, _) in OPS.items():
        want = next((s for s in reducer.NEW_SCOPES
                     if f'/{s}/' in op_name), None)
        if want:
            assert scopes.classify(op_name, reducer.patterns())[0] == want


def new_metrics():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    return sorted(m['name'] for m in bench['per_layer']
                  if m.get('workloads') == [REAL])


def test_every_new_metric_has_its_file_and_the_cell():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    assert new_metrics() == [
        'cache.state_gib.decode', 'cache.state_restore_ms_per_request',
        'kernel.attn_decode_ms_per_step', 'kernel.attn_decode_roofline',
        'kernel.ssm_step_ms_per_step', 'kernel.ssm_step_roofline',
        'model.moe_latent_ms_per_step.decode',
        'model.ssm_proj_ms_per_step.decode',
        'model.stack_rest_ms_per_step.hybrid']
    for name in new_metrics():
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        assert metric['reducer'] == 'hybrid_scopes'
    mine = [m for m in bench['per_layer'] if REAL in m.get('workloads', [])]
    assert len(mine) == 23
    assert all(m['moves'] == 'decode_tokens_per_s' for m in mine)
    cell = loader.Cell(REAL)
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert cell.kind == 'decode_hybrid' and cell.chips == 1
    # ISSUE 32's traffic, letter for letter
    assert cell.traffic == {
        'kind': 'decode_hybrid', 'sessions': 48, 'context': 32768,
        't_max': 33792, 'prefill_chunk': 4096, 'new_tokens': 256,
        'check_samples': 1, 'trace_requests': 1, 'tokens_in_flight': 4,
        'min_requests': 12}
    assert set(cell.limits) == {
        'served_logit_gap', 'expert_pick_difference_share',
        'router_pick_regret', 'decode_impl_is_kernel'}
    assert all(v is not None for v in cell.limits.values())


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    assert flops_hybrid.layer_counts(cfg) == {'M': 5, 'E': 5, '*': 1}
    assert flops_hybrid.conv_channels(cfg) == 8192 + 2 * 8 * 128 == 10240
    # a session's state (128 heads x 64 x 128, float32) and window
    assert flops_hybrid.state_bytes(cfg) == (
        128 * 64 * 128 * 4 + 3 * 10240 * 2) == 4255744
    step = flops_hybrid.ssm_step(cfg, batch=48)
    assert step['bytes'] == 5 * 48 * 2 * 4255744        # read + written
    assert step['flops'] == 5 * 48 * 5 * 128 * 64 * 128
    rows = 32768 + 128 + 1
    attn = flops_hybrid.attn_decode_step(cfg, batch=48, context=32896)
    # 2 KV heads x (K + V) x 128 x 2 B a row, read once for 16 query heads
    assert attn['bytes'] == 1 * 48 * 2 * 256 * 2 * (rows + 1)
    assert attn['flops'] == 1 * 48 * 32 * 2 * 256 * rows
    # two matrices in the latent, where flops_latent counts three at
    # the stream's width
    assert flops_hybrid.expert_bytes(cfg) == 2 * 1024 * 2688 * 2
    assert flops_hybrid.expected_distinct_held(cfg, 48) == pytest.approx(
        128 * (1 - (490 / 512) ** 48))
    assert 112 < flops_hybrid.expected_distinct_held(cfg, 48) < 113


def test_shape_table_counts_the_share():
    """ISSUE 32's arithmetic: 4.648 B parameters, 9.30 GB."""
    cell = loader.Cell(REAL)
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d = 4096
    mamba = d * 18560 + 8192 * d + 4 * 10240 + 10240 + 3 * 128 + 8192 + d
    expert = (d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376
              + 128 * 2 * 1024 * 2688 + d)
    attn = 2 * d * 4096 + 2 * d * 256 + d
    assert abs(mamba - 109.64e6) < 0.01e6 and abs(attn - 35.66e6) < 0.01e6
    assert count == 5 * mamba + 5 * expert + attn + 2 * 32768 * d + d
    assert 4.647e9 < count < 4.649e9
    assert 9.29e9 < 2 * count < 9.31e9


def test_the_recurrences_draws_follow_the_configurations_init():
    cell = loader.Cell(CELL, root=ROOT)
    tree = cell.driver().make(cell.config, 4_000_000_007, jnp.bfloat16)
    ssm = tree['params']['stack']['block_1']['ssm']
    assert ssm['A_log'].dtype == ssm['dt_bias'].dtype == jnp.float32
    decay = np.exp(np.asarray(ssm['A_log']))
    assert np.all((decay >= 1.0) & (decay <= 16.0))
    steps = np.log1p(np.exp(np.asarray(ssm['dt_bias'], np.float64)))
    assert np.all((steps >= 0.99e-3) & (steps <= 0.101))
    np.testing.assert_array_equal(ssm['D'], 1.0)
    router = tree['params']['stack']['block_0']['moe']['router']
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(router, np.float32), axis=0), 1.0,
        atol=1e-6)
