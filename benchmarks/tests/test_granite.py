"""The ``granitemoehybrid`` cell's files on the CPU: the driver against
the plain reference at the tiny preset (its own root, ``tiny_granite``),
sound and broken — a restore that restores nothing and a layer off the
hit list among the broken; the reference's router and one layer against
a hand computation in numpy; the accepted readers on a hand-made trace of
this stack's names; the needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_granite, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_granite')
CELL = 'tiny-granite.decode'
REAL = 'granite-4.0-h-small.decode-4k'


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
    assert rows['recurrent_state_gap']['value'] < 2e-5
    assert rows['nonfinite_state_resets']['value'] == 0
    assert rows['expert_routes_off_the_rule']['value'] == 0
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:layer']     # the one slab
    assert len(said['expert_routes']) == 10
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
    """The lengths set back and all nine states left where the last
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
    """The same program with its states stored in bfloat16: the logits
    may still pass, the states themselves do not."""
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
    states: the decay of a slow head rounds to another number."""
    cell = loader.Cell(CELL, root=ROOT)
    ref, driver = cell.reference(), cell.driver()
    ref.ROW_BLOCK = 8
    params = driver.make(cell.config, 7, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, size=56).astype(np.int32))
    sound = ref.logits_at(cell.config, params, tokens, 56)
    lower = ref.logits_at(cell.config, params, tokens, 56,
                                     jnp.bfloat16)
    assert float(jnp.max(jnp.abs(sound[0] - lower[0]))) > 1e-3
    assert driver.state_gap(lower[3], sound[3]) > 0.05
    from benchmarks.reference import common
    with common.operands_in(jnp.bfloat16):
        np.testing.assert_array_equal(
            jax.jit(ref.lowp)(jnp.asarray([0.999, 1 / 3], jnp.float32)),
            [1.0, 0.333984375])


def test_state_gap_is_the_worst_heads_relative_distance():
    driver = loader.Cell(CELL, root=ROOT).driver()
    ref = np.ones((2, 3, 4, 5))
    got = ref.copy()
    got[1, 2] *= 1.1                    # one head of one layer, 10 % off
    got[0, 0, 0, 0] += 0.01
    assert driver.state_gap(got, ref) == pytest.approx(0.1)
    assert driver.state_gap(ref, ref) == 0.0


def test_a_layer_off_the_hit_list_is_not_correct(capsys, monkeypatch):
    """A caller's bound that sends the step's rows down the sorted
    route: the same numbers, and not the program the cell times."""
    driver = loader.Cell(CELL, root=ROOT).driver()
    build = driver.build_lm

    def sorted_route(config, **kw):
        model = build(config, **kw)
        experts = {**model.block_kwargs['ffn_kwargs'], 'dense_tokens': 0}
        return model.clone(block_kwargs={**model.block_kwargs,
                                         'ffn_kwargs': experts})
    monkeypatch.setattr(loader.Cell, 'driver', lambda self: driver)
    monkeypatch.setattr(driver, 'build_lm', sorted_route)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['expert_routes_off_the_rule']['value'] == 10
    assert rows['served_logit_gap']['ok']


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
    assert layers == 10 and int(stats['step']) == t['new_tokens']
    assert stats['expert_tokens'].shape == (
        layers, cfg['published']['num_local_experts'])
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']                  # (steps, layers, sessions, k)
    held = sum(len({e for e in np.unique(picks[i, l]) if lo <= e < hi})
               for i in range(len(picks)) for l in range(layers))
    assert int(stats['active']) == held     # over the experts HELD
    routing = driver.routing_readings(cfg, server.stats_read, t['sessions'])
    assert routing['active_experts_per_step'] == held / t['new_tokens']
    assert routing['expert_bytes'] == 3 * 32 * 12 * 2
    assert server.cache_gib == flops_granite.cache_gib(server.caches.layers)
    assert server.context_picks.shape == (layers, t['context'], k)
    assert server.sampled == driver.sampled_session(11, t['sessions'])


# -- the reference against a hand computation ----------------------------------

def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_reference_layer_is_the_hand_computation():
    """One expert branch in numpy, float64: the top-3 of the raw logits,
    their softmax, the picked experts HELD here (experts 2-5 of 8) and
    the shared MLP, scaled by the residual multiplier."""
    ref = loader.load_module('reference', 'granitemoehybrid')
    ref.ROW_BLOCK = 8
    rng = np.random.default_rng(5)
    d, w, s, e, k = 8, 6, 10, 8, 3
    cfg = {'num_experts_per_tok': k, 'experts_held': [2, 6],
           'published': {'num_local_experts': e}, 'rms_norm_eps': 1e-5,
           'residual_multiplier': 0.22}
    x = rng.normal(size=(8, d))
    scale = 1 + 0.1 * rng.normal(size=d)
    router = rng.normal(size=(d, e))
    wg, wu = rng.normal(size=(2, 4, d, w)) / np.sqrt(d)
    wd = rng.normal(size=(4, w, d)) / np.sqrt(w)
    sg, su = rng.normal(size=(2, d, s)) / np.sqrt(d)
    sd = rng.normal(size=(s, d)) / np.sqrt(s)
    v = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * scale
    logits = v @ router
    want = np.zeros_like(x)
    for n in range(8):
        top = np.argsort(-logits[n])[:k]
        g = np.exp(logits[n, top] - logits[n, top].max())
        g = g / g.sum()
        for gate, i in zip(g, top):
            if 2 <= i < 6:
                j = i - 2
                want[n] += gate * ((_silu(v[n] @ wg[j]) * (v[n] @ wu[j]))
                                   @ wd[j])
        want[n] += (_silu(v[n] @ sg) * (v[n] @ su)) @ sd
    want = x + 0.22 * want
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    lp = {'ln2': {'scale': f(scale)},
          'moe': {'router': f(router), 'w_gate': f(wg), 'w_up': f(wu),
                  'w_down': f(wd),
                  'shared': {'gate': {'kernel': f(sg)},
                             'up': {'kernel': f(su)},
                             'down': {'kernel': f(sd)}}}}
    with jax.default_matmul_precision('highest'):
        got, picks, regret = ref.experts_branch(cfg, lp, f(x))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.sort(picks, -1),
                                  np.sort(np.argsort(-logits)[:, :k], -1))
    assert not np.any(regret)
    # a forced pick of the worst expert: its regret is the logits' gap
    worst = np.argsort(logits)[:, :k].astype(np.int32)
    with jax.default_matmul_precision('highest'):
        _, own, regret = ref.experts_branch(cfg, lp, f(x), jnp.asarray(worst))
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(picks, -1))
    np.testing.assert_allclose(
        regret, np.sort(logits)[:, -k] - np.sort(logits)[:, 0], atol=1e-5)


def test_reference_attention_scales_by_the_multiplier():
    """Two rows, one head: ``softmax(q.k x attention_multiplier)``, not
    ``head_dim^-1/2``."""
    ref = loader.load_module('reference', 'granitemoehybrid')
    cfg = {'num_attention_heads': 1, 'num_key_value_heads': 1,
           'attention_multiplier': 0.3}
    eye = jnp.eye(4, dtype=jnp.float32)
    ap = {name: {'kernel': eye}
          for name in ('keys', 'queries', 'values', 'composition')}
    u = jnp.asarray([[1.0, 0, 0, 0], [1.0, 2.0, 0, 0]])
    with jax.default_matmul_precision('highest'):
        keys, values = ref.keys_values(cfg, ap, u)
        out = ref.attend(cfg, ap, u, jnp.arange(2), keys, values,
                         jnp.arange(2))
    w = np.exp(0.3 * np.array([1.0, 5.0]))
    w = w / w.sum()
    np.testing.assert_allclose(out[0], u[0], atol=1e-6)     # causal
    np.testing.assert_allclose(out[1], w[0] * u[0] + w[1] * u[1],
                               atol=1e-6)


# -- the accepted readers on this stack's names --------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'fusion.1': (STEP + '/block_0.decode/ssm.decode/ops.ssm_step/mul', 4000),
    'fusion.2': (STEP + '/block_0.decode/ssm.decode/lm.ssm_proj/in_proj/'
                 'dot_general', 1200),
    'moe_hit_experts.1': (STEP + '/block_0.decode/moe/lm.moe_experts/'
                          'moe_hit_experts/pallas_call', 3000),
    'fusion.4': (STEP + '/block_0.decode/moe/lm.moe_route/top_k', 300),
    'fusion.5': (STEP + '/block_0.decode/moe/lm.mlp/shared/up/'
                 'dot_general', 500),
    'flash_decode.1': (STEP + '/block_5.decode/attn.decode/lm.attn_proj/'
                       'ops.flash_decode/flash_decode/pallas_call', 2000),
    'fusion.6': (STEP + '/block_5.decode/mul', 100),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 6000),
}


def opcode(name):
    return 'custom-call' if name[:5] in ('moe_h', 'flash') else (
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
            'ssm_step_per_step': {'bytes': 819e9 * 1.6e-6, 'flops': 1.0},
            'cache': {'full_gib': 1.5, 'state_gib': 2.8},
            'moe': {'active_experts_per_step': 180.0,
                    'expert_bytes': flops_granite.expert_bytes(cfg),
                    'load_max_over_mean': 1.2}}

    def read(name):
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        return loader.load_module('reducers', metric['reducer']).read(
            Run, metric)
    assert read('kernel.ssm_step_ms_per_step') == pytest.approx(2e-3)
    assert read('kernel.ssm_step_roofline') == pytest.approx(80.0)
    assert read('kernel.attn_decode_ms_per_step') == pytest.approx(1e-3)
    assert read('kernel.attn_decode_roofline') == pytest.approx(90.0)
    assert read('model.ssm_proj_ms_per_step.decode') == pytest.approx(
        0.6e-3)
    assert read('model.stack_rest_ms_per_step.hybrid') == pytest.approx(
        0.05e-3)
    assert read('model.moe_experts_ms_per_step.decode') == pytest.approx(
        1.5e-3)
    assert read('model.moe_route_ms_per_step.decode') == pytest.approx(
        0.15e-3)
    assert read('model.mlp_ms_per_step.decode') == pytest.approx(0.25e-3)
    assert read('cache.state_gib.decode') == 2.8
    assert read('cache.full_gib.decode') == 1.5
    assert read('cache.state_restore_ms_per_request') == pytest.approx(
        2e-3)
    assert read('moe.active_experts_per_step') == 180.0
    # 180 experts x 18.87 MB a step over 1.5 us: far past the peak here,
    # the arithmetic alone
    assert read('moe.expert_stream_roofline') == pytest.approx(
        100 * 180 * 3 * 4096 * 768 * 2 / 819e9 / 1.5e-6)


def test_the_cell_joins_the_accepted_metrics():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    mine = [m for m in bench['per_layer'] if REAL in m.get('workloads', [])]
    assert sorted(m['name'] for m in mine) == sorted([
        'model.xla_ms_per_step.decode', 'model.mlp_ms_per_step.decode',
        'model.attn_proj_ms_per_step.decode',
        'model.head_ms_per_step.decode', 'model.other_ms_per_step.decode',
        'model.moe_route_ms_per_step.decode',
        'model.moe_experts_ms_per_step.decode',
        'model.unscoped_ms_per_step.decode',
        'model.ssm_proj_ms_per_step.decode',
        'model.stack_rest_ms_per_step.hybrid',
        'kernel.ssm_step_ms_per_step', 'kernel.ssm_step_roofline',
        'kernel.attn_decode_ms_per_step', 'kernel.attn_decode_roofline',
        'moe.expert_stream_roofline', 'moe.active_experts_per_step',
        'moe.load_max_over_mean', 'cache.state_gib.decode',
        'cache.full_gib.decode', 'cache.state_restore_ms_per_request',
        'device.idle_pct.decode', 'device.peak_hbm_gib.decode'])
    assert all(m['workloads'][-1] == REAL
               and m['moves'] == 'decode_tokens_per_s' for m in mine)
    cell = loader.Cell(REAL)
    assert len(bench['workloads']) == 7 and len(bench['configs']) == 7
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert cell.kind == 'decode_granite' and cell.chips == 1
    # ISSUE 36's traffic, letter for letter
    assert cell.traffic == {
        'kind': 'decode_granite', 'sessions': 80, 'context': 4096,
        't_max': 5120, 'prefill_chunk': 4096, 'new_tokens': 256,
        'check_samples': 1, 'trace_requests': 1, 'tokens_in_flight': 4,
        'min_requests': 12}
    assert set(cell.limits) == {
        'served_logit_gap', 'expert_pick_difference_share',
        'router_pick_regret', 'recurrent_state_gap',
        'decode_impl_is_kernel', 'expert_routes_off_the_rule'}
    assert all(v is not None for v in cell.limits.values())


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    kinds = flops_granite.layer_kinds(cfg)
    assert kinds.count('mamba') == 9 and kinds.index('attention') == 5
    assert flops_granite.conv_channels(cfg) == 8192 + 2 * 128 == 8448
    assert flops_granite.head_dim(cfg) == 128
    # a session's state (128 heads x 64 x 128, float32) and window
    assert flops_granite.state_bytes(cfg) == (
        128 * 64 * 128 * 4 + 3 * 8448 * 2) == 4244992
    step = flops_granite.ssm_step(cfg, batch=80)
    assert step['bytes'] == 9 * 80 * 2 * 4244992        # read + written
    assert step['flops'] == 9 * 80 * 5 * 128 * 64 * 128
    rows = 4096 + 128 + 1
    attn = flops_granite.attn_decode_step(cfg, batch=80, context=4224)
    # 8 KV heads x (K + V) x 128 x 2 B a row, read once for 4 query heads
    assert attn['bytes'] == 1 * 80 * 8 * 2 * 128 * 2 * (rows + 1)
    assert attn['flops'] == 1 * 80 * 32 * 2 * 256 * rows
    assert flops_granite.expert_bytes(cfg) == 3 * 4096 * 768 * 2
    assert flops_granite.experts_held(cfg) == 18
    # every held expert is hit: P(unhit) = (62/72)^80
    assert 18 - flops_granite.expected_distinct_held(cfg, 80) == (
        pytest.approx(18 * (62 / 72) ** 80))
    assert flops_granite.expected_distinct_held(cfg, 80) > 17.9998


def test_shape_table_counts_the_share():
    """ISSUE 36's arithmetic: 2.956 B parameters, 5.91 GB."""
    cell = loader.Cell(REAL)
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d = 4096
    mamba = d * 16768 + 8192 * d + 4 * 8448 + 8448 + 3 * 128 + 8192
    attn = 2 * d * d + 2 * d * 1024
    rest = d * 72 + 3 * d * 1536 + 18 * 3 * d * 768 + 2 * d
    assert abs(mamba - 102.3e6) < 0.05e6 and abs(attn - 41.9e6) < 0.05e6
    assert count == 9 * (mamba + rest) + attn + rest + 25088 * d + d
    assert 2.955e9 < count < 2.957e9
    assert 5.90e9 < 2 * count < 5.92e9


def test_the_draws_follow_the_configurations_init():
    cell = loader.Cell(CELL, root=ROOT)
    tree = cell.driver().make(cell.config, 4_000_000_007, jnp.bfloat16)
    block = tree['params']['stack']['block_0']
    ssm = block['ssm']
    assert ssm['A_log'].dtype == ssm['dt_bias'].dtype == jnp.float32
    assert ssm['in_proj']['kernel'].dtype == jnp.bfloat16
    decay = np.exp(np.asarray(ssm['A_log']))
    assert np.all((decay >= 1.0) & (decay <= 16.0))
    np.testing.assert_array_equal(ssm['D'], 1.0)
    router = block['moe']['router']
    assert router.dtype == jnp.float32
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(router), axis=0), 1.0, atol=1e-6)
    # a score q.k x attention_multiplier at the stated standard deviation
    attn = cell.driver().make(cell.config, 5, jnp.float32)['params'][
        'stack']['block_5']['attn']
    h = np.random.default_rng(0).normal(size=(4096, 32))
    q = (h @ np.asarray(attn['keys']['kernel'])).reshape(-1, 4, 8)
    k = (h @ np.asarray(attn['queries']['kernel'])).reshape(-1, 2, 8)
    scores = np.einsum('nhd,nhd->nh', q[:, ::2], k) * (
        cell.config['attention_multiplier'])
    assert 2.0 < scores.std() < 4.5
