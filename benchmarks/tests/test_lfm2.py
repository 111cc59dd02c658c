"""The ``lfm2_moe`` cell's files on the CPU: the driver against the plain
reference at the tiny preset (its own root, ``tiny_lfm2``), sound and
broken — both controls, a reset that restores nothing, a conv step off
its traced form, a route off the rule among the broken; the reference's
convolution, head norms and router against hand computations; the new
reader and the accepted ones on a hand-made trace of this stack's
names; the needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_lfm2, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_lfm2')
CELL = 'tiny-lfm2.decode'
REAL = 'lfm2-8b-a1b.decode-4k'


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
    assert rows['kv_cache_gap']['value'] < 5e-5
    assert rows['nonfinite_state_resets']['value'] == 0
    assert rows['expert_routes_off_the_rule']['value'] == 0
    assert rows['conv_steps_off_the_form']['value'] == 0
    # off the TPU the step's form is XLA's, and the row says so (the
    # tiny preset sets no limit on it)
    assert rows['decode_impl_is_kernel']['value'] == 1
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:packed']      # both slabs
    assert len(said['kernel_steps']) == 2
    assert len(said['expert_routes']) == 4
    assert said['conv_forms'] == 3 * [
        {'form': 'shift', 'taps': 3, 'channels': 128}]
    assert said['cache']['state_gib'] == 3 * 3 * 2 * 128 * 4 / 2 ** 30
    assert said['cache']['full_gib'] == 2 * 3 * 64 * 128 * 4 / 2 ** 30
    # the request compared follows a restore
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    assert {'init', 'snapshot', 'prefill'} <= {
        o.get('setup_part') for o in out}
    json.dumps(line)


@pytest.mark.parametrize('control', [
    {'operand_dtype': jnp.float8_e4m3fn}, {'operand_dtype': jnp.bfloat16},
    {'kv_dtype': jnp.float8_e4m3fn}], ids=['float8', 'bfloat16', 'kv-float8'])
def test_a_lower_precision_reference_is_not_correct(capsys, control):
    """Every matmul operand and the windows rounded, or the keys and
    values alone: the rows the caches hold read both, the windows' gap
    the first (at the cell's size a float8 K/V control read inside every
    limit but the rows': chip, PR 51); the served token may well stay
    the reference's best, so the logit gap need not."""
    line, rows, _ = cell_run(capsys, **control)
    assert line['correct'] is False
    assert not rows['kv_cache_gap']['ok']
    assert rows['kv_cache_gap']['value'] > 1e-3
    if 'operand_dtype' in control:
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
    """The lengths set back and all three windows left where the last
    request took them: the request compared follows a reset, and the
    comparison sees it."""
    from distributed_dot_product_tpu.models import decode
    monkeypatch.setattr(decode, 'restore_states',
                        lambda caches, snapshot: caches)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-3


def test_a_conv_step_off_its_form_and_a_route_off_the_rule_are_counted():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 11)
    server.load()
    assert server.conv_steps_off_the_form() == 0
    assert server.routes_off_the_rule() == 0
    forms, routes = server.conv_forms, server.expert_routes
    server.conv_forms = forms[:2]                  # a layer not traced
    assert server.conv_steps_off_the_form() == 1
    server.conv_forms = forms[:2] + [{**forms[2], 'taps': 4}]
    assert server.conv_steps_off_the_form() == 1
    server.expert_routes = routes[:3] + [{**routes[3], 'bound_by': 'caller'}]
    assert server.routes_off_the_rule() == 1
    server.expert_routes = routes[:3] + [{**routes[3], 'route': 'sorted'}]
    assert server.routes_off_the_rule() == 1
    server.expert_routes = routes[:2]
    assert server.routes_off_the_rule() == 2


def test_counters_say_what_the_step_routed():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 11)
    server.load()
    server.request()
    stats, = server.stats_read
    t, cfg = cell.traffic, cell.config
    layers, k = len(driver.expert_layers(cfg)), cfg['num_experts_per_tok']
    assert layers == 4 and int(stats['step']) == t['new_tokens']
    assert stats['expert_tokens'].shape == (layers, cfg['num_experts'])
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']                  # (steps, layers, sessions, k)
    hit = sum(len(np.unique(picks[i, l]))
              for i in range(len(picks)) for l in range(layers))
    assert int(stats['active']) == hit
    routing = driver.routing_readings(cfg, server.stats_read)
    assert routing['active_experts_per_step'] == hit / t['new_tokens']
    assert routing['expert_bytes'] == 3 * 128 * 16 * 2
    assert server.cache_gib == flops_lfm2.cache_gib(server.caches.layers)
    assert server.context_picks.shape == (layers, t['context'], k)


def test_a_program_without_this_stacks_fields_fails_in_build_lm(
        monkeypatch):
    """A parent commit's program — no ``models/shortconv`` — fails in
    ``build_lm``, at once, before a weight is drawn."""
    import builtins
    cell = loader.Cell(CELL, root=ROOT)
    real = builtins.__import__

    def no_shortconv(name, *args, **kwargs):
        if name.endswith('models.shortconv'):
            raise ImportError(name)
        return real(name, *args, **kwargs)
    monkeypatch.setattr(builtins, '__import__', no_shortconv)
    with pytest.raises(ImportError):
        cell.driver().build_lm(cell.config)


def test_level_routers_takes_the_common_offset_out_of_every_router():
    from benchmarks.drivers import decode
    cell = loader.Cell(CELL, root=ROOT)
    driver, cfg, ref = cell.driver(), cell.config, cell.reference()
    n = cfg['init']['router_level_tokens']
    drawn = driver.make(cfg, 4_000_000_007, jnp.float32)
    level = driver.level_routers(cfg, drawn, 4_000_000_007)

    def mean_logits(params):
        p = params['params']
        x = jnp.asarray(p['embed']['embedding'])[decode.seeded_tokens(
            4_000_000_007, 2, (n,), cfg['vocab_size'])]
        out = []
        with jax.default_matmul_precision('highest'):
            for i, kind in enumerate(ref.kinds(cfg)):
                lp = p['stack'][f'block_{i}']
                x = ref.mixer_branch(cfg, kind, lp, x)[0]
                if i not in ref.expert_layers(cfg):
                    x = ref.mlp_branch(cfg, lp, x)
                    continue
                out.append(jnp.mean(
                    ref.norm(cfg, lp['ln2'], x), 0) @ lp['moe']['router'])
                x = ref.experts_branch(cfg, lp, x)[0]
        return np.stack(out)
    assert np.abs(mean_logits(drawn)).max() > 0.01
    assert np.abs(mean_logits(level)).max() < 1e-5
    assert level['params']['embed'] is drawn['params']['embed']
    plain = {**cfg, 'init': {k: v for k, v in cfg['init'].items()
                             if k != 'router_level_tokens'}}
    assert driver.level_routers(plain, drawn, 5) is drawn


# -- the reference against hand computations ------------------------------------

def test_reference_convolution_is_three_rows_by_hand():
    """``v_t = f_0 w_{t-2} + f_1 w_{t-1} + f_2 w_t`` with ``w = B ⊙ x̃``,
    then ``C ⊙ v``: two channels, three rows, identity projections, from
    a window of zeros and from a window carried in."""
    ref = loader.load_module('reference', 'lfm2')
    cfg = {'conv_L_cache': 3}
    cp = {'in_proj': {'kernel': jnp.eye(6)},
          'conv_kernel': jnp.asarray([[1.0, 0.5], [2.0, -1.0], [3.0, 0.25]]),
          'out_proj': {'kernel': jnp.eye(2)}}
    # columns: B | C | x~
    u = jnp.asarray([[2.0, 1.0, 1.0, 1.0, 3.0, -1.0],
                     [1.0, 2.0, 0.5, 2.0, -2.0, 4.0],
                     [0.5, 1.0, 2.0, -1.0, 2.0, 2.0]])
    w = np.array([[6.0, -1.0], [-2.0, 8.0], [1.0, 2.0]])
    with jax.default_matmul_precision('highest'):
        out, seen = ref.conv_block(cfg, cp, u, jnp.zeros((2, 2)))
        carried, _ = ref.conv_block(cfg, cp, u[2:], seen[2:4])
    v = np.array([[3 * 6.0, 0.25 * -1.0],
                  [2 * 6.0 + 3 * -2.0, -1 * -1.0 + 0.25 * 8.0],
                  [1 * 6.0 + 2 * -2.0 + 3 * 1.0,
                   0.5 * -1.0 - 1 * 8.0 + 0.25 * 2.0]])
    c = np.array([[1.0, 1.0], [0.5, 2.0], [2.0, -1.0]])
    np.testing.assert_allclose(seen[2:], w, atol=1e-6)
    np.testing.assert_allclose(out, c * v, atol=1e-6)
    np.testing.assert_allclose(carried[0], (c * v)[2], atol=1e-6)


def test_reference_router_is_sigmoid_with_a_bias_that_only_chooses():
    ref = loader.load_module('reference', 'lfm2')
    cfg = {'num_experts_per_tok': 2, 'norm_topk_prob': True,
           'routed_scaling_factor': 1, 'use_expert_bias': True}
    logits = np.array([[2.0, 1.0, 0.0, -1.0]])
    bias = np.array([0.0, 0.0, 0.6, 0.0])       # lifts expert 2 over 1
    mp = {'router': jnp.eye(4), 'router_bias': jnp.asarray(bias)}
    gates, own, regret = ref.route(cfg, mp, jnp.asarray(logits))
    s = 1 / (1 + np.exp(-logits[0]))
    assert sorted(np.asarray(own[0]).tolist()) == [0, 2]
    want = np.zeros(4)
    # the bias is in no gate; the source's 1e-6 is in the denominator
    want[[0, 2]] = s[[0, 2]] / (s[0] + s[2] + 1e-6)
    np.testing.assert_allclose(gates[0], want, atol=1e-7)
    assert float(regret[0]) == 0.0
    _, _, regret = ref.route(cfg, mp, jnp.asarray(logits),
                             jnp.asarray([[0, 1]]))
    np.testing.assert_allclose(regret[0], s[0] - s[1], atol=1e-6)
    # without the bias the pick is the scores' own
    _, own, _ = ref.route({**cfg, 'use_expert_bias': False}, mp,
                          jnp.asarray(logits))
    assert sorted(np.asarray(own[0]).tolist()) == [0, 1]


def test_reference_attention_norms_each_head_before_it_rotates():
    """Two rows, one head of 4: q and k through the per-head RMSNorm
    (its scale 2 on k), THEN the rotation, then ``softmax(q·k / 2) v``."""
    ref = loader.load_module('reference', 'lfm2')
    cfg = {'num_attention_heads': 1, 'num_key_value_heads': 1,
           'hidden_size': 4, 'norm_eps': 0.0, 'rope_theta': 100.0}
    eye = jnp.eye(4, dtype=jnp.float32)
    ap = {name: {'kernel': eye}
          for name in ('keys', 'queries', 'values', 'composition')}
    ap['keys_norm'] = jnp.ones((4,))
    ap['queries_norm'] = 2.0 * jnp.ones((4,))
    u = jnp.asarray([[3.0, 0, 0, 4.0], [0.0, 2.0, 0, 0]])
    pos = jnp.arange(2)
    with jax.default_matmul_precision('highest'):
        keys, values = ref.keys_values(cfg, ap, u, pos)
        out = ref.attend(cfg, ap, u, pos, keys, values, pos)

    def rot(x, p):
        ang = p * 100.0 ** (-np.arange(0, 4, 2) / 4)
        x1, x2 = x[:2], x[2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x1 * np.sin(ang) + x2 * np.cos(ang)])
    unit = [np.asarray(r) / np.sqrt(np.mean(np.square(r))) for r in u]
    q = [rot(unit[i], i) for i in range(2)]
    k = [rot(2 * unit[i], i) for i in range(2)]
    np.testing.assert_allclose(keys[0], np.stack(k), atol=1e-6)
    np.testing.assert_allclose(out[0], u[0], atol=1e-6)
    w = np.exp(0.5 * np.array([q[1] @ k[0], q[1] @ k[1]]))
    w = w / w.sum()
    np.testing.assert_allclose(out[1], w[0] * u[0] + w[1] * u[1],
                               atol=1e-6)


# -- the readers on this stack's names -------------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'fusion.1': (STEP + '/block_1.decode/conv.decode/lm.conv_proj/'
                 'in_proj/dot_general', 900),
    'fusion.2': (STEP + '/block_1.decode/conv.decode/lm.conv_proj/'
                 'concatenate', 100),
    'moe_hit_experts.1': (STEP + '/block_1.decode/moe/lm.moe_experts/'
                          'moe_hit_experts/pallas_call', 3000),
    'sparse_pick.1': (STEP + '/block_1.decode/moe/lm.moe_route/'
                      'sparse_pick/pallas_call', 300),
    'fusion.5': (STEP + '/block_0.decode/lm.mlp/mlp/up/dot_general', 500),
    'flash_decode.1': (STEP + '/block_4.decode/attn.decode/lm.attn_proj/'
                       'ops.flash_decode/flash_decode/pallas_call', 2000),
    'fusion.8': (STEP + '/block_4.decode/attn.decode/lm.attn_proj/keys/'
                 'dot_general', 160),
    'fusion.6': (STEP + '/block_0.decode/add', 120),
    'fusion.9': ('jit(step_fn)/argmax', 40),
    'fusion.10': ('jit(step_fn)/TransformerLM.decode/lm.head/dot_general',
                  700),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 600),
}


def opcode(name):
    return 'custom-call' if name[:5] in ('moe_h', 'flash', 'spars') else (
        name.split('.')[0])


def hand_run(tmp_path, monkeypatch, ops):
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(xspace(program([
        instruction(name, opcode(name), i + 10, op_name)
        for i, (name, (op_name, _)) in enumerate(ops.items())])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    at, rows = 0, []
    for name, (_, ns) in ops.items():
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
            'cache': {'full_gib': 5.0, 'state_gib': 0.0137},
            'moe': {'active_experts_per_step': 256.0,
                    'expert_bytes': flops_lfm2.expert_bytes(cfg),
                    'load_max_over_mean': 1.2}}

    def read(name):
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        return loader.load_module('reducers', metric['reducer']).read(
            Run, metric)
    return read, Run


def test_the_cells_metrics_on_a_hand_made_trace(tmp_path, monkeypatch):
    read, hand = hand_run(tmp_path, monkeypatch, OPS)
    # the new reader
    assert read('model.conv_proj_ms_per_step.decode') == pytest.approx(
        0.5e-3)
    assert read('model.stack_rest_ms_per_step.lfm2') == pytest.approx(
        0.06e-3)
    assert read('model.unscoped_ms_per_step.lfm2') == pytest.approx(
        0.02e-3)
    assert read('model.head_ms_per_step.lfm2') == pytest.approx(0.35e-3)
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
    assert read('cache.state_gib.decode') == 0.0137
    assert read('cache.full_gib.decode') == 5.0
    assert read('cache.state_restore_ms_per_request') == pytest.approx(
        0.2e-3)
    assert read('moe.active_experts_per_step') == 256.0
    assert read('moe.expert_stream_roofline') == pytest.approx(
        100 * 256 * 3 * 2048 * 1792 * 2 / 819e9 / 1.5e-6)
    # an accepted reader that does not know the new scope takes it for
    # the stack's: why the cell does not join it
    hybrid = loader.read_json(loader.HERE, 'layer_metrics',
                              'model.stack_rest_ms_per_step.hybrid.json')
    assert loader.load_module('reducers', hybrid['reducer']).read(
        hand, hybrid) == pytest.approx((900 + 100 + 120) / 2e6)


def test_the_new_reader_finds_nothing_in_a_parents_program(tmp_path,
                                                           monkeypatch):
    """A program that does not open ``lm.conv_proj``: every new metric
    is absent, nothing raises."""
    ops = {k: v for k, v in OPS.items() if 'conv_proj' not in v[0]}
    read, _ = hand_run(tmp_path, monkeypatch, ops)
    for name in ('model.conv_proj_ms_per_step.decode',
                 'model.stack_rest_ms_per_step.lfm2',
                 'model.unscoped_ms_per_step.lfm2',
                 'model.head_ms_per_step.lfm2'):
        assert read(name) is None
    assert read('kernel.attn_decode_ms_per_step') == pytest.approx(1e-3)


def test_the_loader_finds_every_new_file_and_the_cell_joins():
    cell = loader.Cell(REAL)
    assert cell.kind == 'decode_lfm2' and cell.chips == 1
    assert cell.driver().__name__.endswith('decode_lfm2')
    assert cell.reference().__name__.endswith('lfm2')
    names = {m['name'] for m in cell.per_layer()}
    new = {'model.conv_proj_ms_per_step.decode',
           'model.stack_rest_ms_per_step.lfm2',
           'model.unscoped_ms_per_step.lfm2', 'model.head_ms_per_step.lfm2'}
    joined = {'kernel.attn_decode_ms_per_step', 'kernel.attn_decode_roofline',
              'cache.full_gib.decode', 'cache.state_gib.decode',
              'cache.state_restore_ms_per_request',
              'model.moe_experts_ms_per_step.decode',
              'moe.expert_stream_roofline',
              'model.moe_route_ms_per_step.decode',
              'moe.active_experts_per_step', 'moe.load_max_over_mean',
              'model.mlp_ms_per_step.decode',
              'model.attn_proj_ms_per_step.decode', 'device.idle_pct.decode',
              'device.peak_hbm_gib.decode', 'setup.trace_s',
              'setup.kernel_trace_s', 'setup.lower_s',
              'setup.backend_compile_s', 'setup.cache_misses',
              'setup.execute_s'}
    assert new | joined <= names
    for m in cell.per_layer():
        if m['name'] in new:
            assert m['reducer'] == 'lfm2_scopes' and m['workloads'] == [REAL]
    assert {m['name'] for m in cell.end_to_end()} == {
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s'}
    assert set(cell.limits) >= {
        'decode_impl_is_kernel', 'expert_routes_off_the_rule',
        'conv_steps_off_the_form'}
    assert cell.traffic['sessions'] == 256
    pats = loader.read_json(loader.HERE, 'scope_patterns_lfm2.json')
    assert pats['classes'][0][0] == 'lm.conv_proj'
    sparse = loader.read_json(loader.HERE, 'scope_patterns_sparse.json')
    assert pats['classes'][1:] == sparse['classes']


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under its key, but the three
    keys of ``reduced``; the cut is one leading dense layer and two
    whole periods."""
    cell = loader.Cell(REAL)
    cfg, entry = cell.config, cell.config_entry
    published = {
        'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
        'intermediate_size': 7168, 'max_position_embeddings': 128000,
        'model_type': 'lfm2_moe', 'moe_intermediate_size': 1792,
        'norm_eps': 1e-05, 'norm_topk_prob': True,
        'num_attention_heads': 32, 'num_experts': 32,
        'num_experts_per_tok': 4, 'num_key_value_heads': 8,
        'rope_theta': 1000000, 'routed_scaling_factor': 1,
        'use_expert_bias': True, 'vocab_size': 65536}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert sorted(entry['reduced']) == sorted(cfg['reduced']) == [
        'layer_types', 'num_dense_layers', 'num_hidden_layers']
    assert entry['source'] == cfg['source']
    assert cfg['published']['num_hidden_layers'] == 24
    assert [i for i, kind in enumerate(cfg['published']['layer_types'])
            if kind == 'full_attention'] == [2, 6, 10, 14, 18, 21]
    kept = cfg['published_layers_kept']
    assert kept == [1, 3, 4, 5, 6, 7, 8, 9, 10]
    assert cfg['layer_types'] == [cfg['published']['layer_types'][i]
                                  for i in kept]
    assert (cfg['num_hidden_layers'], cfg['num_dense_layers']) == (9, 1)
    assert cfg['tie_embedding'] is True
    assert flops_lfm2.parameters(cfg) == 3_135_848_960
    full = {**cfg, **cfg['published']}
    assert round(flops_lfm2.parameters(full) / 1e9, 2) == 8.34


def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    need = flops_lfm2.attn_decode_step(cfg, 256, 4224)
    assert need['bytes'] == 2 * 256 * 8 * 2 * 64 * 2 * (4225 + 1)
    assert need['flops'] == 2 * 256 * 32 * 4 * 64 * 4225
    assert flops_lfm2.expert_bytes(cfg) == 3 * 2048 * 1792 * 2
    assert flops_lfm2.window_bytes(cfg) == 2 * 2048 * 2
    conv = flops_lfm2.conv_step(cfg, 256)
    assert conv['bytes'] == 7 * ((4 * 2048 * 2048 + 3 * 2048) * 2
                                 + 256 * 2 * 8192)
    assert flops_lfm2.layer_kinds(cfg).count('attn') == 2
    assert flops_lfm2.expert_layers(cfg) == list(range(1, 9))


def test_shape_table_counts_the_share():
    cell = loader.Cell(REAL)
    driver, cfg = cell.driver(), cell.config
    table = driver.shapes(cfg)
    assert sum(int(np.prod(shape)) for shape, _ in table.values()) == (
        flops_lfm2.parameters(cfg))
    assert ('lm_head_kernel',) not in table              # the tied head
    assert table[('stack', 'block_0', 'mlp', 'gate', 'kernel')][0] == (
        2048, 7168)
    assert ('stack', 'block_0', 'moe', 'router') not in table
    assert table[('stack', 'block_1', 'moe', 'w_up')] == (
        (32, 2048, 1792), 2048)
    assert table[('stack', 'block_4', 'attn', 'queries', 'kernel')][0] == (
        2048, 512)
    assert table[('stack', 'block_1', 'conv', 'conv_kernel')] == (
        (3, 2048), 3)


def test_the_draws_follow_the_configurations_init():
    cell = loader.Cell(CELL, root=ROOT)
    driver, cfg = cell.driver(), cell.config
    params = driver.make(cfg, 3, jnp.float32)['params']
    block = params['stack']['block_2']
    for name in ('keys_norm', 'queries_norm'):
        scale = np.asarray(block['attn'][name])
        assert abs(scale.mean() - cfg['init']['qk_norm_scale']) < 0.05
    np.testing.assert_allclose(np.linalg.norm(
        np.asarray(block['moe']['router']), axis=0), 1.0, atol=1e-6)
    taps = np.asarray(params['stack']['block_1']['conv']['conv_kernel'])
    assert abs(taps.std() - 3 ** -0.5) < 0.08
    assert np.abs(np.asarray(block['moe']['router_bias'])).max() < 0.05
