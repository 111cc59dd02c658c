"""The ``solar_open2`` cell's files on the CPU: the driver against the
plain reference at the tiny preset (its own root, ``tiny_solar``), sound
and broken — a restore that restores nothing, a state kept in bfloat16
and a delta step off the form its counter names among the broken; the
reference's recurrence against a hand computation of two tokens of one
head; the new reader and the accepted ones on a hand-made trace of this
stack's names; the needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_solar, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_solar')
CELL = 'tiny-solar.decode'
REAL = 'solar-open2-250b.decode-4k'


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
    assert rows['recurrent_state_gap']['value'] < 5e-5
    assert rows['nonfinite_state_resets']['value'] == 0
    assert rows['expert_routes_off_the_rule']['value'] == 0
    # off the TPU the step's form is XLA's, and the row says so (the
    # tiny preset sets no limit on it)
    assert rows['delta_steps_off_the_kernel']['value'] == 3
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:layer']     # the one slab
    assert len(said['expert_routes']) == 4
    assert said['delta_forms'] == 3 * [
        {'form': 'xla', 'tile': None, 'chunk': 8}]
    assert said['cache']['state_gib'] > 0 and said['cache']['full_gib'] > 0
    # the request compared follows a restore
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    assert {'init', 'snapshot', 'prefill'} <= {
        o.get('setup_part') for o in out}
    json.dumps(line)


def test_the_kernels_form_of_the_step_is_correct_and_counted(
        capsys, monkeypatch):
    """The same cell with every delta mixer told ``'pallas'`` (the
    interpreter here): the same numbers, and the counter names it."""
    driver = loader.Cell(CELL, root=ROOT).driver()
    build = driver.build_lm

    def kernel_step(config, **kw):
        model = build(config, **kw)
        kinds = {**model.layer_kinds}
        kinds['kda'] = {**kinds['kda'], 'ssm_kwargs': {
            **kinds['kda']['ssm_kwargs'], 'step_impl': 'pallas'}}
        return model.clone(layer_kinds=kinds)
    monkeypatch.setattr(loader.Cell, 'driver', lambda self: driver)
    monkeypatch.setattr(driver, 'build_lm', kernel_step)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is True
    assert rows['delta_steps_off_the_kernel']['value'] == 0
    assert rows['recurrent_state_gap']['value'] < 5e-5


def test_float8_reference_is_not_correct(capsys):
    line, rows, _ = cell_run(capsys, operand_dtype=jnp.float8_e4m3fn)
    assert line['correct'] is False
    assert not (rows['served_logit_gap']['ok']
                and rows['expert_pick_difference_share']['ok']
                and rows['router_pick_regret']['ok'])
    assert not rows['recurrent_state_gap']['ok']


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
    """The lengths set back and all three states left where the last
    request took them: the request compared follows a reset, and the
    comparison sees it."""
    from distributed_dot_product_tpu.models import decode
    monkeypatch.setattr(decode, 'restore_states',
                        lambda caches, snapshot: caches)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-3
    assert rows['recurrent_state_gap']['value'] > 1e-2


def test_a_state_kept_in_bfloat16_is_not_correct(capsys, monkeypatch):
    cell = loader.Cell(CELL, root=ROOT)
    cell.config['precision']['state'] = 'bfloat16'
    monkeypatch.setattr(loader, 'Cell', lambda *a, **k: cell)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert not rows['recurrent_state_gap']['ok']
    assert rows['recurrent_state_gap']['value'] > 1e-3


def test_a_bfloat16_control_rounds_the_states_too():
    """The control at bfloat16 (an explicit ``reduce_precision``: a
    convert pair can be compiled away) moves the logits and, more, the
    states."""
    cell = loader.Cell(CELL, root=ROOT)
    ref, driver = cell.reference(), cell.driver()
    ref.ROW_BLOCK = 8
    params = driver.make(cell.config, 7, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, size=56).astype(np.int32))
    sound = ref.logits_at(cell.config, params, tokens, 56)
    lower = ref.logits_at(cell.config, params, tokens, 56, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(sound[0] - lower[0]))) > 1e-3
    from benchmarks.drivers.decode_granite import state_gap
    assert state_gap(lower[3], sound[3]) > 0.01
    assert sound[3].shape == (3, 4, 8, 8)
    from benchmarks.reference import common
    with common.operands_in(jnp.bfloat16):
        np.testing.assert_array_equal(
            jax.jit(ref.lowp)(jnp.asarray([0.999, 1 / 3], jnp.float32)),
            [1.0, 0.333984375])


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
    assert layers == 4 and int(stats['step']) == t['new_tokens']
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
    assert routing['expert_bytes'] == 3 * 32 * 12 * 2
    assert server.cache_gib == flops_solar.cache_gib(server.caches.layers)
    assert server.context_picks.shape == (layers, t['context'], k)
    assert server.delta_steps_off_the_kernel() == 3
    server.delta_forms = server.delta_forms[:1]    # a layer not traced
    assert server.delta_steps_off_the_kernel() == 3


def test_level_routers_takes_the_common_offset_out_of_every_router():
    """Every router's columns orthogonal to the mean of its input as
    the PLAIN reference reads it over the seeded probe tokens, layer
    after layer, at unit norm; an expert's mean logit over those tokens
    is then zero where the draw's was not; the other leaves are the
    same objects, the weights a function of the seed alone; without
    ``router_level_tokens``, the draw as it was."""
    from benchmarks.drivers import decode
    from benchmarks.reference import solar_open2 as ref
    cell = loader.Cell(CELL, root=ROOT)
    driver, cfg = cell.driver(), cell.config
    n = cfg['init']['router_level_tokens']
    drawn = driver.make(cfg, 4_000_000_007, jnp.float32)
    level = driver.level_routers(cfg, drawn, 4_000_000_007)
    again = driver.level_routers(cfg, drawn, 4_000_000_007)

    def mean_logits(params):
        """(layers, experts): every router's mean logit over the probe
        tokens, the stream the plain reference's."""
        p = params['params']
        x = jnp.asarray(p['embed']['embedding'])[decode.seeded_tokens(
            4_000_000_007, 2, (n,), cfg['vocab_size'])]
        out = []
        with jax.default_matmul_precision('highest'):
            for i, kind in enumerate(ref.kinds(cfg)):
                lp = p['stack'][f'block_{i}']
                x = (ref.delta_branch(cfg, lp, x)[0] if kind == 'kda'
                     else ref.attention_branch(cfg, lp, x))
                out.append(jnp.mean(
                    ref.norm(cfg, lp['ln2'], x), 0) @ lp['moe']['router'])
                x = ref.experts_branch(cfg, lp, x)[0]
        return np.stack(out)
    assert np.abs(mean_logits(drawn)).max(axis=1).min() > 0.05
    assert np.abs(mean_logits(level)).max() < 1e-5
    for i in range(cfg['num_hidden_layers']):
        was = drawn['params']['stack'][f'block_{i}']
        now = level['params']['stack'][f'block_{i}']
        np.testing.assert_allclose(np.linalg.norm(
            np.asarray(now['moe']['router']), axis=0), 1.0, atol=1e-6)
        np.testing.assert_array_equal(
            now['moe']['router'],
            again['params']['stack'][f'block_{i}']['moe']['router'])
        assert now['moe']['w_up'] is was['moe']['w_up']
        assert now['ln2'] is was['ln2']
    assert level['params']['embed'] is drawn['params']['embed']
    plain = {**cfg, 'init': {k: v for k, v in cfg['init'].items()
                             if k != 'router_level_tokens'}}
    assert driver.level_routers(plain, drawn, 5) is drawn


# -- the reference against a hand computation ----------------------------------

def test_reference_recurrence_is_two_tokens_of_one_head_by_hand():
    """``S' = Diag(α) S``, ``S = S' + β k (v − S'ᵀ k)ᵀ``, ``o = Sᵀ q``
    written out for ``d_k = d_v = 2`` from a zero state: token 1 writes
    ``β₁ k₁ v₁ᵀ``; token 2 decays it a key CHANNEL, takes what it holds
    for ``k₂`` away from ``v₂`` and writes the rest, ``β₂ = 1.5``."""
    ref = loader.load_module('reference', 'solar_open2')
    k = np.array([[[1.0, 0.0]], [[0.6, 0.8]]])          # (2, 1, 2) unit
    q = np.array([[[0.5, 0.5]], [[1.0, -1.0]]])
    v = np.array([[[2.0, -1.0]], [[0.5, 3.0]]])
    g = np.log(np.array([[[0.5, 0.25]], [[0.5, 0.25]]]))
    beta = np.array([[0.8], [1.5]])
    s1 = 0.8 * np.outer(k[0, 0], v[0, 0])               # from zero
    o1 = s1.T @ q[0, 0]
    decayed = np.array([[0.5], [0.25]]) * s1
    held = decayed.T @ k[1, 0]
    s2 = decayed + 1.5 * np.outer(k[1, 0], v[1, 0] - held)
    o2 = s2.T @ q[1, 0]
    # the numbers themselves, not only the formula twice
    np.testing.assert_allclose(s1, [[1.6, -0.8], [0.0, 0.0]])
    np.testing.assert_allclose(held, [0.48, -0.24])
    np.testing.assert_allclose(
        s2, [[0.8 + 0.9 * 0.02, -0.4 + 0.9 * 3.24],
             [1.2 * 0.02, 1.2 * 3.24]])
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    with jax.default_matmul_precision('highest'):
        o, s = ref.recurrence(f(q), f(k), f(v), f(g), f(beta),
                              jnp.zeros((1, 2, 2)))
        # a row that is not live leaves the state alone
        _, kept = ref.recurrence(f(q), f(k), f(v), f(g), f(beta),
                                 jnp.zeros((1, 2, 2)),
                                 jnp.asarray([True, False]))
    np.testing.assert_allclose(o[:, 0], [o1, o2], atol=1e-6)
    np.testing.assert_allclose(s[0], s2, atol=1e-6)
    np.testing.assert_allclose(kept[0], s1, atol=1e-6)


def test_reference_router_is_sigmoid_with_a_bias_that_only_chooses():
    ref = loader.load_module('reference', 'solar_open2')
    cfg = {'num_experts_per_tok': 2, 'norm_topk_prob': True,
           'routed_scaling_factor': 1}
    logits = np.array([[2.0, 1.0, 0.0, -1.0]])
    bias = np.array([0.0, 0.0, 0.6, 0.0])       # lifts expert 2 over 1
    mp = {'router': jnp.eye(4), 'router_bias': jnp.asarray(bias)}
    gates, own, regret = ref.route(cfg, mp, jnp.asarray(logits))
    s = 1 / (1 + np.exp(-logits[0]))
    assert sorted(np.asarray(own[0]).tolist()) == [0, 2]
    want = np.zeros(4)
    want[[0, 2]] = s[[0, 2]] / (s[0] + s[2])    # the bias is not in a gate
    np.testing.assert_allclose(gates[0], want, atol=1e-6)
    assert float(regret[0]) == 0.0
    # forced onto experts 0 and 1: the regret is how far the worse of
    # them lies under the second best BIASED score (expert 0's)
    _, _, regret = ref.route(cfg, mp, jnp.asarray(logits),
                             jnp.asarray([[0, 1]]))
    assert s[2] + 0.6 > s[0] > s[1]
    np.testing.assert_allclose(regret[0], s[0] - s[1], atol=1e-6)


def test_reference_attention_gates_the_heads_output():
    """Two rows, one head: ``softmax(q·k / sqrt(d)) v ⊙ sigmoid(u Wz)``."""
    ref = loader.load_module('reference', 'solar_open2')
    cfg = {'num_attention_heads': 1, 'num_key_value_heads': 1,
           'head_dim': 4, 'use_gqa_gate': True}
    eye = jnp.eye(4, dtype=jnp.float32)
    ap = {name: {'kernel': eye}
          for name in ('keys', 'queries', 'values', 'composition')}
    ap['gate'] = {'kernel': 2 * eye}
    u = jnp.asarray([[1.0, 0, 0, 0], [1.0, 2.0, 0, 0]])
    with jax.default_matmul_precision('highest'):
        keys, values = ref.keys_values(cfg, ap, u)
        out = ref.attend(cfg, ap, u, jnp.arange(2), keys, values,
                         jnp.arange(2))
    w = np.exp(0.5 * np.array([1.0, 5.0]))
    w = w / w.sum()
    gate = 1 / (1 + np.exp(-2 * np.asarray(u)))
    np.testing.assert_allclose(out[0], u[0] * gate[0], atol=1e-6)
    np.testing.assert_allclose(
        out[1], (w[0] * u[0] + w[1] * u[1]) * gate[1], atol=1e-6)


# -- the readers on this stack's names -------------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'delta_step.1': (STEP + '/block_1.decode/delta.decode/ops.delta_step/'
                     'delta_step/pallas_call', 3600),
    'fusion.1': (STEP + '/block_1.decode/delta.decode/ops.delta_step/'
                 'transpose', 400),
    'fusion.2': (STEP + '/block_1.decode/delta.decode/lm.delta_proj/'
                 'in_proj/dot_general', 1200),
    'moe_hit_experts.1': (STEP + '/block_0.decode/moe/lm.moe_experts/'
                          'moe_hit_experts/pallas_call', 3000),
    'fusion.4': (STEP + '/block_0.decode/moe/lm.moe_route/top_k', 300),
    'fusion.5': (STEP + '/block_0.decode/moe/lm.mlp/shared/up/'
                 'dot_general', 500),
    'flash_decode.1': (STEP + '/block_0.decode/attn.decode/lm.attn_proj/'
                       'ops.flash_decode/flash_decode/pallas_call', 2000),
    'fusion.8': (STEP + '/block_0.decode/attn.decode/lm.attn_proj/gate/'
                 'dot_general', 160),
    'fusion.6': (STEP + '/block_0.decode/add', 100),
    'fusion.9': ('jit(step_fn)/argmax', 40),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 6000),
}


def opcode(name):
    return 'custom-call' if name[:5] in ('moe_h', 'flash', 'delta') else (
        name.split('.')[0])


def test_the_cells_metrics_on_a_hand_made_trace(tmp_path, monkeypatch):
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(xspace(program([
        instruction(name, opcode(name), i + 10, op_name)
        for i, (name, (op_name, _)) in enumerate(OPS.items())])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    at, rows = 0, []
    for name, (_, ns) in OPS.items():
        rows.append([f'%{name} {opcode(name)}', at, ns, ns])
        at += ns
    cfg = loader.Cell(REAL).config

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': rows}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {
            'steps': 2, 'requests': 3,
            'full_decode_per_step': {'bytes': 819e9 * 0.9e-6, 'flops': 1.0},
            'delta_step_per_step': {'bytes': 819e9 * 1.6e-6, 'flops': 1.0},
            'cache': {'full_gib': 2.5, 'state_gib': 1.55},
            'moe': {'active_experts_per_step': 154.0,
                    'expert_bytes': flops_solar.expert_bytes(cfg),
                    'load_max_over_mean': 1.2}}

    def read(name):
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        return loader.load_module('reducers', metric['reducer']).read(
            Run, metric)
    # the new reader: kernel + the transposition of its operands
    assert read('kernel.delta_step_ms_per_step') == pytest.approx(2e-3)
    assert read('kernel.delta_step_roofline') == pytest.approx(80.0)
    assert read('model.delta_proj_ms_per_step.decode') == pytest.approx(
        0.6e-3)
    assert read('model.stack_rest_ms_per_step.delta') == pytest.approx(
        0.05e-3)
    assert read('model.unscoped_ms_per_step.delta') == pytest.approx(
        0.02e-3)
    # the accepted readers read this program the same
    assert read('kernel.attn_decode_ms_per_step') == pytest.approx(1e-3)
    assert read('kernel.attn_decode_roofline') == pytest.approx(90.0)
    assert read('model.attn_proj_ms_per_step.decode') == pytest.approx(
        0.08e-3)
    assert read('model.moe_experts_ms_per_step.decode') == pytest.approx(
        1.5e-3)
    assert read('model.moe_route_ms_per_step.decode') == pytest.approx(
        0.15e-3)
    assert read('model.mlp_ms_per_step.decode') == pytest.approx(0.25e-3)
    assert read('cache.state_gib.decode') == 1.55
    assert read('cache.full_gib.decode') == 2.5
    assert read('cache.state_restore_ms_per_request') == pytest.approx(
        2e-3)
    assert read('moe.active_experts_per_step') == 154.0
    assert read('moe.expert_stream_roofline') == pytest.approx(
        100 * 154 * 3 * 4096 * 1280 * 2 / 819e9 / 1.5e-6)
    # an accepted reader that does not know the new scopes takes them
    # for the stack's: why the cell does not join it
    hybrid = loader.read_json(loader.HERE, 'layer_metrics',
                              'model.stack_rest_ms_per_step.hybrid.json')
    assert loader.load_module('reducers', hybrid['reducer']).read(
        Run, hybrid) == pytest.approx((3600 + 400 + 1200 + 100) / 2e6)


def test_the_new_reader_finds_nothing_in_a_parents_program(tmp_path,
                                                           monkeypatch):
    """A program that opens none of the new scopes: every new metric is
    absent, nothing raises."""
    ops = {k: v for k, v in OPS.items() if 'delta' not in v[0]}
    path = tmp_path / 'parent.xplane.pb'
    path.write_bytes(xspace(program([
        instruction(name, opcode(name), i + 10, op_name)
        for i, (name, (op_name, _)) in enumerate(ops.items())])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': [
            [f'%{name} {opcode(name)}', i * 100, 100, 100]
            for i, name in enumerate(ops)]}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {'steps': 2, 'requests': 1}
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    new = [m['name'] for m in bench['per_layer']
           if m['name'].endswith('.delta') or 'delta_' in m['name']]
    assert len(new) == 5
    for name in new:
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        assert metric['reducer'] == 'delta_scopes'
        assert loader.load_module('reducers', 'delta_scopes').read(
            Run, metric) is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert loader.load_module('reducers', 'delta_scopes').read(
        Run, metric) is None


def test_the_cell_joins_the_accepted_metrics():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    mine = [m for m in bench['per_layer'] if REAL in m.get('workloads', [])]
    new = ['kernel.delta_step_ms_per_step', 'kernel.delta_step_roofline',
           'model.delta_proj_ms_per_step.decode',
           'model.stack_rest_ms_per_step.delta',
           'model.unscoped_ms_per_step.delta']
    assert sorted(m['name'] for m in mine) == sorted(new + [
        'model.xla_ms_per_step.decode', 'model.mlp_ms_per_step.decode',
        'model.attn_proj_ms_per_step.decode',
        'model.head_ms_per_step.decode', 'model.other_ms_per_step.decode',
        'model.moe_route_ms_per_step.decode',
        'model.moe_experts_ms_per_step.decode',
        'kernel.attn_decode_ms_per_step', 'kernel.attn_decode_roofline',
        'moe.expert_stream_roofline', 'moe.active_experts_per_step',
        'moe.load_max_over_mean', 'cache.state_gib.decode',
        'cache.full_gib.decode', 'cache.state_restore_ms_per_request',
        'device.idle_pct.decode', 'device.peak_hbm_gib.decode'])
    assert all(REAL in m['workloads']
               and m['moves'] == 'decode_tokens_per_s' for m in mine)
    assert all(m['workloads'] == [REAL] for m in mine if m['name'] in new)
    # the new entries stand at the end of their lists
    assert [m['name'] for m in bench['per_layer'][-5:]] == new
    assert bench['workloads'][-1]['name'] == REAL
    assert bench['configs'][-1]['name'] == 'solar-open2-250b-serve'
    cell = loader.Cell(REAL)
    assert len(bench['workloads']) >= 8 and len(bench['configs']) >= 8
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 0
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert cell.kind == 'decode_solar' and cell.chips == 1
    # ISSUE 39's traffic, letter for letter
    assert cell.traffic == {
        'kind': 'decode_solar', 'sessions': 128, 'context': 4096,
        't_max': 5120, 'prefill_chunk': 4096, 'new_tokens': 256,
        'check_samples': 1, 'trace_requests': 1, 'tokens_in_flight': 4,
        'min_requests': 12}
    assert set(cell.limits) == {
        'served_logit_gap', 'expert_pick_difference_share',
        'router_pick_regret', 'recurrent_state_gap',
        'decode_impl_is_kernel', 'expert_routes_off_the_rule',
        'delta_steps_off_the_kernel'}
    assert all(v is not None for v in cell.limits.values())
    patterns = loader.read_json(loader.HERE, 'scope_patterns_delta.json')
    assert [c[0] for c in patterns['classes'][:3]] == [
        'ops.delta_step', 'ops.delta_scan', 'lm.delta_proj']
    assert patterns['classes'][3:] == loader.read_json(
        loader.HERE, 'scope_patterns_hybrid.json')['classes']


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    assert flops_solar.layer_kinds(cfg) == ['gqa', 'kda', 'kda', 'kda']
    assert flops_solar.delta_sizes(cfg) == (64, 128, 4)
    assert flops_solar.conv_channels(cfg) == 3 * 8192 == 24576
    # a session's state (64 heads x 128 x 128, float32) and windows
    assert flops_solar.state_bytes(cfg) == (
        64 * 128 * 128 * 4 + 3 * 24576 * 2) == 4341760
    step = flops_solar.delta_step(cfg, batch=128)
    assert step['bytes'] == 3 * 128 * 2 * 4341760       # read + written
    assert step['flops'] == 3 * 128 * 7 * 64 * 128 * 128
    assert step['bytes'] / 819e9 > step['flops'] / 197e12   # bytes bind
    rows = 4096 + 128 + 1
    attn = flops_solar.attn_decode_step(cfg, batch=128, context=4224)
    # 8 KV heads x (K + V) x 128 x 2 B a row, read once for 8 query heads
    assert attn['bytes'] == 1 * 128 * 8 * 2 * 128 * 2 * (rows + 1)
    assert attn['flops'] == 1 * 128 * 64 * 2 * 256 * rows
    assert flops_solar.expert_bytes(cfg) == 3 * 4096 * 1280 * 2
    assert flops_solar.experts_held(cfg) == 40
    # P(a held expert unhit) = (312/320)^128 = 0.039: ~38.4 of 40 a layer
    assert 40 - flops_solar.expected_distinct_held(cfg, 128) == (
        pytest.approx(40 * (312 / 320) ** 128))
    assert 38.3 < flops_solar.expected_distinct_held(cfg, 128) < 38.5


def test_shape_table_counts_the_share():
    """ISSUE 39's arithmetic: 3.308 B parameters, 6.62 GB."""
    cell = loader.Cell(REAL)
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d = 4096
    kda = (3 * d * 8192 + 8192 * d + 2 * (d * 128 + 128 * 8192) + d * 64
           + 4 * 24576 + 8192 + 64 + 128)
    gqa = d * 16384 + 2 * d * 1024 + 8192 * d
    rest = d * 320 + 320 + 3 * d * 1280 + 40 * 3 * d * 1280 + 2 * d
    assert abs(kda - 137.7e6) < 0.05e6 and abs(gqa - 109.1e6) < 0.05e6
    assert count == 3 * (kda + rest) + gqa + rest + 2 * 24576 * d + d
    assert 3.307e9 < count < 3.309e9
    assert 6.61e9 < 2 * count < 6.63e9


def test_the_draws_follow_the_configurations_init():
    cell = loader.Cell(CELL, root=ROOT)
    tree = cell.driver().make(cell.config, 4_000_000_007, jnp.bfloat16)
    block = tree['params']['stack']['block_1']
    delta = block['delta']
    assert delta['A_log'].dtype == delta['dt_bias'].dtype == jnp.float32
    assert delta['in_proj']['kernel'].dtype == jnp.bfloat16
    assert delta['conv_kernel'].shape == (4, 96)
    decay = np.exp(np.asarray(delta['A_log']))
    assert np.all((decay >= 1.0) & (decay <= 16.0))
    steps = np.log1p(np.exp(np.asarray(delta['dt_bias'])))
    assert np.all((steps > 0.0009) & (steps < 0.11))
    router = block['moe']['router']
    assert router.dtype == block['moe']['router_bias'].dtype == jnp.float32
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(router), axis=0), 1.0, atol=1e-6)
    assert tree['params']['lm_head_kernel'].shape == (32, 64)
    # a score q.k / sqrt(head_dim) at the stated standard deviation
    attn = cell.driver().make(cell.config, 5, jnp.float32)['params'][
        'stack']['block_0']['attn']
    h = np.random.default_rng(0).normal(size=(4096, 32))
    q = (h @ np.asarray(attn['keys']['kernel'])).reshape(-1, 4, 8)
    k = (h @ np.asarray(attn['queries']['kernel'])).reshape(-1, 2, 8)
    scores = np.einsum('nhd,nhd->nh', q[:, ::2], k) / np.sqrt(8)
    assert 2.0 < scores.std() < 4.5
