"""The ``bailing_hybrid`` cell's files on the CPU: the driver against the
plain reference at the tiny preset (its own root, ``tiny_ling``), sound
and broken — a router without groups, a restore that restores nothing,
a latent length that is not set back and a lower-precision reference
among the broken; the reference's group-limited rule and its gate by
hand; the new readers and the accepted ones on a hand-made trace of this
stack's names; the needed-work functions against hand counts; the loader
finding every new file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_ling, loader, run, scopes, trace as tr
from test_scopes import instruction, message, program

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_ling')
CELL = 'tiny-ling.decode'
REAL = 'ling-3.0-flash.decode-32k'


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
    # off the TPU both steps' forms are XLA's, and the rows say so (the
    # tiny preset sets no limit on them)
    assert rows['delta_steps_off_the_kernel']['value'] == 3
    assert rows['decode_impl_is_kernel']['value'] == 1
    said, = [o for o in out if 'decode_impl' in o]
    assert said['decode_impl'] == ['xla:latent']    # one layer's buffer
    assert len(said['expert_routes']) == 3          # D has no experts
    assert said['delta_forms'] == 3 * [
        {'form': 'xla', 'tile': None, 'chunk': 8}]
    assert said['cache']['state_gib'] > 0
    assert said['cache']['latent_gib'] == 3 * 64 * 128 * 4 / 2 ** 30
    window, = [o for o in out if 'group_rows_per_step' in o]
    # two of four groups kept a token: half the 3 rows, give or take
    assert 0.5 < window['group_rows_per_step'] < 2.5
    assert window['expected_group_rows_per_step'] == 1.5
    # the request compared follows a restore
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    assert {'init', 'snapshot', 'prefill'} <= {
        o.get('setup_part') for o in out}
    json.dumps(line)


def test_the_kernels_forms_of_the_step_are_correct_and_counted(
        capsys, monkeypatch):
    """The same cell with every delta mixer told ``'pallas'`` and the
    latent layer ``'kernel'`` (the interpreter here): the same numbers,
    and the counters name them."""
    driver = loader.Cell(CELL, root=ROOT).driver()
    build = driver.build_lm

    def kernel_step(config, **kw):
        model = build(config, decode_impl='kernel', **kw)
        block = {**model.block_kwargs, 'ssm_kwargs': {
            **model.block_kwargs['ssm_kwargs'], 'step_impl': 'pallas'}}
        return model.clone(block_kwargs=block)
    monkeypatch.setattr(loader.Cell, 'driver', lambda self: driver)
    monkeypatch.setattr(driver, 'build_lm', kernel_step)
    # 128 rows a K split: the tiny t_max has one
    cell = loader.Cell(CELL, root=ROOT)
    monkeypatch.setattr(loader.Cell, '__init__', lambda self, *a, **k: (
        self.__dict__.update(cell.__dict__),
        self.__dict__.update(traffic={**cell.traffic, 't_max': 128}))[0])
    line, rows, out = cell_run(capsys)
    assert line['correct'] is True
    assert rows['delta_steps_off_the_kernel']['value'] == 0
    assert rows['decode_impl_is_kernel']['value'] == 0
    assert rows['recurrent_state_gap']['value'] < 5e-5
    assert rows['served_logit_gap']['value'] < 1e-4


@pytest.mark.parametrize('dtype', [jnp.float8_e4m3fn, jnp.bfloat16])
def test_a_lower_precision_reference_is_not_correct(capsys, dtype):
    line, rows, _ = cell_run(capsys, operand_dtype=dtype)
    assert line['correct'] is False
    assert not (rows['served_logit_gap']['ok']
                and rows['expert_pick_difference_share']['ok']
                and rows['router_pick_regret']['ok'])
    assert not rows['recurrent_state_gap']['ok']


def test_a_router_without_groups_is_not_correct(capsys):
    """The control that routes by the plain top-k of all experts: the
    logits agree (the reference follows the picks it is fed) and the
    reference's own group-limited rule refuses the picks."""
    line, rows, out = cell_run(capsys, plain_routing=True)
    assert line['correct'] is False
    assert rows['served_logit_gap']['ok']
    assert rows['expert_pick_difference_share']['value'] > 0.2
    assert rows['router_pick_regret']['value'] > 0.05
    window, = [o for o in out if 'group_rows_per_step' in o]
    assert window['group_rows_per_step'] == 3.0      # every row, no groups


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
    """The latent lengths set back and all three states left where the
    last request took them: the request compared follows a reset, and
    the comparison sees it."""
    from distributed_dot_product_tpu.models import decode
    monkeypatch.setattr(decode, 'restore_states',
                        lambda caches, snapshot: caches)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-3
    assert rows['recurrent_state_gap']['value'] > 1e-2


def test_a_latent_length_that_is_not_set_back_is_not_correct(
        capsys, monkeypatch):
    """The states restored and the latent cache left at the last
    request's end: the next request attends the last one's rows."""
    driver = loader.Cell(CELL, root=ROOT).driver()
    programs = driver.make_programs

    def keep_lengths(model, config):
        from distributed_dot_product_tpu.models.decode import (
            restore_states,
        )
        made = list(programs(model, config))
        made[4] = jax.jit(lambda c, s, length: restore_states(c, s),
                          donate_argnums=(0,))
        return tuple(made)
    monkeypatch.setattr(loader.Cell, 'driver', lambda self: driver)
    monkeypatch.setattr(driver, 'make_programs', keep_lengths)
    line, rows, _ = cell_run(capsys)
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
    k = cfg['num_experts_per_tok']
    lo, hi = cfg['experts_held']
    layers = len(flops_ling.expert_layers(cfg))
    assert layers == 3 and int(stats['step']) == t['new_tokens']
    assert stats['expert_tokens'].shape == (
        layers, cfg['published']['num_experts'])
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']          # (steps, expert layers, sessions, k)
    held = sum(len({e for e in np.unique(picks[i, l]) if lo <= e < hi})
               for i in range(len(picks)) for l in range(layers))
    assert int(stats['active']) == held     # over the experts HELD
    # a row that picked a held expert kept the held group; the counter
    # also has the rows that kept it and picked elsewhere
    reached = int(np.sum(np.any((picks >= lo) & (picks < hi), axis=-1)))
    assert reached <= int(stats['group_rows']) <= picks[..., 0].size
    # no pick outside two groups a token
    assert np.all([len(set(p // 4)) <= 2 for p in picks.reshape(-1, k)])
    routing = driver.routing_readings(cfg, server.stats_read, t['sessions'])
    assert routing['active_experts_per_step'] == held / t['new_tokens']
    assert routing['group_rows_per_step'] == int(stats['group_rows']) / (
        t['new_tokens'] * layers)
    assert routing['expert_bytes'] == 3 * 32 * 12 * 2
    assert server.cache_gib == flops_ling.cache_gib(server.caches.layers)
    assert server.context_picks.shape == (layers, t['context'], k)
    assert server.delta_steps_off_the_kernel() == 3
    assert server.routes_off_the_rule() == 0
    server.expert_routes = server.expert_routes[:1]   # layers not traced
    assert server.routes_off_the_rule() == 2


def test_a_program_without_this_stacks_fields_fails_in_build_lm(
        monkeypatch):
    """What the parent commit does on the new cell: ``build_lm`` raises
    before a weight is drawn."""
    from distributed_dot_product_tpu.models import moe
    cell = loader.Cell(CELL, root=ROOT)

    class Old(moe.SparseExperts.__base__):
        n_experts: int = 1

    monkeypatch.setattr(moe, 'SparseExperts', Old)
    with pytest.raises(ValueError, match='n_group'):
        cell.driver().build_lm(cell.config)


# -- the reference by hand -------------------------------------------------------

def test_reference_routes_inside_the_kept_groups_and_judges_by_them():
    """Eight experts in four groups of two, top-2 of the two best
    groups; scores from an identity router. Group scores are the sums of
    a group's two (biased) scores: token 0 keeps groups 3 and 1 — expert
    0, the best single expert of group 0, is NOT picked, where plain
    top-2 would take it — and the bias moves the choice but not the
    gates."""
    from benchmarks.reference import ling3 as ref
    cfg = {'n_group': 4, 'topk_group': 2, 'num_experts_per_tok': 2,
           'norm_topk_prob': True, 'routed_scaling_factor': 2.5,
           'published': {'num_experts': 8}}
    logits = np.array([[2.0, -4.0, 0.5, 0.4, -1.0, -1.0, 1.0, 0.9]])
    bias = np.array([0, 0, 0, 0, 0, 0, 0, 0.5])
    mp = {'router': jnp.eye(8), 'router_bias': jnp.asarray(bias)}
    s = 1 / (1 + np.exp(-logits[0]))
    gates, own, regret = ref.route(cfg, mp, jnp.asarray(logits))
    # groups: 0 -> s0 + s1 = 0.899, 1 -> 1.221, 2 -> 0.538, 3 -> 1.942
    assert sorted(np.asarray(own[0]).tolist()) == [6, 7]
    assert float(regret[0]) == 0.0
    want = np.zeros(8)
    want[[6, 7]] = s[[6, 7]] / s[[6, 7]].sum() * 2.5     # unbiased scores
    np.testing.assert_allclose(gates[0], want, atol=1e-6)
    # fed the plain top-2 (expert 0 of a dropped group): the gates are
    # the fed picks', the own picks stand, and the regret is the
    # group's distance from the second kept group
    gates, own, regret = ref.route(cfg, mp, jnp.asarray(logits),
                                   forced=jnp.asarray([[7, 0]]))
    assert sorted(np.asarray(own[0]).tolist()) == [6, 7]
    assert np.count_nonzero(np.asarray(gates[0])) == 2
    group = [s[0] + s[1], s[2] + s[3], s[4] + s[5], s[6] + s[7] + 0.5]
    assert float(regret[0]) == pytest.approx(group[1] - group[0], abs=1e-6)
    # fed picks inside the kept groups but not the best there: the
    # regret is the pick's distance from the 2nd best over those groups
    _, _, regret = ref.route(cfg, mp, jnp.asarray(logits),
                             forced=jnp.asarray([[7, 3]]))
    assert float(regret[0]) == pytest.approx(s[6] - s[3], abs=1e-6)


def test_reference_gates_each_heads_context_before_the_output():
    """One head of width 4 (nope 2 + rope 2), identity projections, a
    gate of its own: ``out = sigmoid(u W_g) · softmax(...) v``, row 0
    attending itself alone."""
    from benchmarks.reference import ling3 as ref
    cfg = {'num_attention_heads': 1, 'qk_nope_head_dim': 2,
           'qk_rope_head_dim': 2, 'kv_lora_rank': 2, 'v_head_dim': 2,
           'rms_norm_eps': 1e-6, 'rope_theta': 10000.0}
    ap = {'q': {'kernel': jnp.eye(4)},
          'kv_a': {'kernel': jnp.eye(4)},
          'kv_norm': {'scale': jnp.ones(2)},
          'kv_b': jnp.concatenate([jnp.eye(2), jnp.eye(2)], -1)[:, None],
          'gate': {'kernel': jnp.asarray([[3.0], [0], [0], [0]])},
          'out': {'kernel': jnp.eye(2)}}
    u = jnp.asarray([[1.0, 2.0, 0.5, 0.0], [0.5, 0.0, 0.0, 1.0]])
    with jax.default_matmul_precision('highest'):
        rows = ref.latent_rows(cfg, ap, u, jnp.arange(2))
        keys, values = ref.expand(cfg, ap, rows)
        out = ref.attend(cfg, ap, u, jnp.arange(2), keys, values,
                         jnp.arange(2))
    c0 = np.array([1.0, 2.0]) / np.sqrt(2.5 + 1e-6)     # RMSNorm of c_kv
    np.testing.assert_allclose(values[0, 0], c0, atol=1e-6)
    gate = 1 / (1 + np.exp(-3.0 * np.asarray(u[:, 0])))
    np.testing.assert_allclose(out[0], gate[0] * c0, atol=1e-6)
    # row 1 is gated by ITS input, after the softmax-weighted sum
    s = (np.asarray(keys[0]) @ np.concatenate([
        np.asarray(u[1, :2]), np.asarray(ref.rotate(
            cfg, u[1:2, 2:], jnp.asarray([1])))[0]])) / 2.0
    w = np.exp(s - s.max())
    w = w / w.sum()
    np.testing.assert_allclose(
        out[1], gate[1] * (w @ np.asarray(values[0])), atol=1e-6)


# -- the readers on this stack's names -------------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'delta_step.1': (STEP + '/block_1.decode/delta.decode/ops.delta_step/'
                     'delta_step/pallas_call', 3600),
    'fusion.1': (STEP + '/block_1.decode/delta.decode/ops.delta_step/'
                 'transpose', 400),
    'fusion.2': (STEP + '/block_1.decode/delta.decode/lm.delta_proj/'
                 'in_proj/dot_general', 1200),
    'moe_hit_experts.1': (STEP + '/block_1.decode/moe/lm.moe_experts/'
                          'moe_hit_experts/pallas_call', 3000),
    'fusion.4': (STEP + '/block_1.decode/moe/lm.moe_route/top_k', 300),
    'fusion.5': (STEP + '/block_1.decode/moe/lm.mlp/shared/up/'
                 'dot_general', 500),
    'mla_decode.1': (STEP + '/block_6.decode/attn.decode/ops.mla_decode/'
                     'mla_decode/pallas_call', 5000),
    'fusion.8': (STEP + '/block_6.decode/attn.decode/lm.attn_proj/gate/'
                 'dot_general', 160),
    'fusion.6': (STEP + '/block_0.decode/add', 100),
    'fusion.9': ('jit(step_fn)/argmax', 40),
    # one name, two programs, two classes: the head's matmul with the
    # driver's finite check fused in, and the finite-check program's own
    'is-finite_reduce_fusion': (
        'jit(step_fn)/TransformerLM.decode/lm.head/dot_general', 700),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 6000),
}
FINITE = {'is-finite_reduce_fusion': 'jit(finite_fn)/is_finite'}


def opcode(name):
    return 'custom-call' if name[:5] in ('moe_h', 'mla_d', 'delta') else (
        name.split('.')[0])


def named_xspace(**programs):
    """``test_scopes.xspace`` with the programs' own names (the reader
    tells the step program by its name)."""
    stat = message((1, 7), (2, message((1, 7), (2, scopes.HLO_STAT))))
    metas = [message((1, i), (2, message(
        (1, i), (2, name), (5, message((1, 7), (6, proto))))))
        for i, (name, proto) in enumerate(programs.items())]
    return message((1, message((2, '/device:TPU:0'))),
                   (1, message((2, scopes.METADATA_PLANE), (5, stat),
                               *[(4, m) for m in metas])))


def hand_trace(tmp_path, ops, **more):
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(named_xspace(**more, jit_step_fn=program([
        instruction(name, opcode(name), i + 10, op_name)
        for i, (name, (op_name, _)) in enumerate(ops.items())])))
    at, rows = 0, []
    for name, (_, ns) in ops.items():
        rows.append([f'%{name} {opcode(name)}', at, ns, ns])
        at += ns
    return str(path), rows


def reader(run_class):
    def read(name):
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        return loader.load_module('reducers', metric['reducer']).read(
            run_class, metric)
    return read


def test_the_cells_metrics_on_a_hand_made_trace(tmp_path, monkeypatch):
    finite = program([instruction(name, 'fusion', 10, op_name)
                      for name, op_name in FINITE.items()])
    path, rows = hand_trace(tmp_path, OPS, jit_finite_fn=finite)
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: path)
    cfg = loader.Cell(REAL).config

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': rows}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {
            'steps': 2, 'requests': 3,
            'mla_decode_per_step': {'bytes': 819e9 * 2.0e-6, 'flops': 1.0},
            'delta_step_per_step': {'bytes': 819e9 * 1.6e-6, 'flops': 1.0},
            'cache': {'latent_gib': 3.87, 'state_gib': 1.16},
            'moe': {'active_experts_per_step': 299.0,
                    'expert_bytes': flops_ling.expert_bytes(cfg),
                    'load_max_over_mean': 1.2,
                    'group_rows_per_step': 48.5}}
    read = reader(Run)
    # the two kernels' accepted readers, each under its own patterns,
    # read ONE program
    assert read('kernel.mla_decode_ms_per_step') == pytest.approx(2.5e-3)
    assert read('kernel.mla_decode_roofline') == pytest.approx(80.0)
    assert read('kernel.delta_step_ms_per_step') == pytest.approx(2e-3)
    assert read('kernel.delta_step_roofline') == pytest.approx(80.0)
    assert read('model.delta_proj_ms_per_step.decode') == pytest.approx(
        0.6e-3)
    assert read('model.moe_experts_ms_per_step.decode') == pytest.approx(
        1.5e-3)
    assert read('model.moe_route_ms_per_step.decode') == pytest.approx(
        0.15e-3)
    assert read('model.mlp_ms_per_step.decode') == pytest.approx(0.25e-3)
    assert read('cache.state_gib.decode') == 1.16
    assert read('cache.state_restore_ms_per_request') == pytest.approx(
        2e-3)
    assert read('moe.active_experts_per_step') == 299.0
    assert read('moe.expert_stream_roofline') == pytest.approx(
        100 * 299 * 3 * 2560 * 768 * 2 / 819e9 / 1.5e-6)
    # the two new counters
    assert read('cache.latent_gib.decode') == 3.87
    assert read('moe.group_rows_per_step') == 48.5
    # this cell's own reader lets the step program's class stand where
    # two programs share a name: the head is read, nothing is unscoped
    # but the argmax
    assert read('model.head_ms_per_step.ling') == pytest.approx(0.35e-3)
    assert read('model.unscoped_ms_per_step.ling') == pytest.approx(
        0.02e-3)
    # the attention projections and the stack's rest share no name with
    # another program: the accepted readers read them
    assert read('model.attn_proj_ms_per_step.decode') == pytest.approx(
        0.08e-3)
    assert read('model.stack_rest_ms_per_step.delta') == pytest.approx(
        0.05e-3)
    # ... where the accepted readers file the shared name as
    # unattributed (PERF.md section 7 (vv)): why the cell reports its
    # head, and what else hangs on a name, through the reader above
    assert read('model.head_ms_per_step.decode') == 0.0
    assert read('model.unscoped_ms_per_step.delta') == pytest.approx(
        (40 + 700) / 2e6)
    # an accepted reader that does not know the delta scopes takes them
    # for the stack's: why the cell does not join it
    assert read('model.stack_rest_ms_per_step.decode') == pytest.approx(
        (3600 + 400 + 1200 + 100) / 2e6)


def test_the_new_reader_finds_nothing_in_a_parents_program(tmp_path,
                                                           monkeypatch):
    """A program that lacks one of this stack's scopes (the Solar
    cell's has no latent kernel, the Xing4 cell's no delta rule): every
    ``.ling`` metric is absent, nothing raises."""
    ops = {k: v for k, v in OPS.items() if 'mla_decode' not in v[0]}
    path, rows = hand_trace(tmp_path, ops)
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: path)

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': rows}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {'steps': 2, 'requests': 1}
    read = reader(Run)
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    new = [m['name'] for m in bench['per_layer']
           if m['name'].endswith('.ling')]
    assert new == ['model.head_ms_per_step.ling',
                   'model.unscoped_ms_per_step.ling']
    for name in new:
        assert read(name) is None
    # the two counters are absent where the program counted nothing
    assert read('cache.latent_gib.decode') is None
    assert read('moe.group_rows_per_step') is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert read(new[0]) is None


def test_the_loader_finds_every_new_file_and_the_cell_joins():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    cell = loader.Cell(REAL)
    assert cell.kind == 'decode_ling' and cell.chips == 1
    assert cell.config_entry['name'] == 'ling-3.0-flash-serve'
    assert hasattr(cell.driver(), 'run')
    assert hasattr(cell.reference(), 'logits_at')
    mine = {m['name']: m for m in bench['per_layer']
            if REAL in m.get('workloads', [])}
    new = {'cache.latent_gib.decode', 'moe.group_rows_per_step'} | {
        name for name in mine if name.endswith('.ling')}
    assert {'kernel.mla_decode_ms_per_step', 'kernel.mla_decode_roofline',
            'kernel.delta_step_ms_per_step', 'kernel.delta_step_roofline',
            'moe.expert_stream_roofline', 'moe.active_experts_per_step',
            'cache.state_gib.decode', 'cache.state_restore_ms_per_request',
            'model.xla_ms_per_step.decode', 'device.idle_pct.decode',
            'device.peak_hbm_gib.decode', 'model.mlp_ms_per_step.decode',
            'model.moe_route_ms_per_step.decode',
            'model.moe_experts_ms_per_step.decode',
            'model.delta_proj_ms_per_step.decode',
            'model.attn_proj_ms_per_step.decode',
            'model.stack_rest_ms_per_step.delta'} | new <= set(mine)
    # the two accepted readers that misfile this program's head (a name
    # shared with the finite-check program) are not joined
    assert not {'model.head_ms_per_step.decode',
                'model.unscoped_ms_per_step.delta',
                'cache.full_gib.decode'} & set(mine)
    assert all(m['moves'] == 'decode_tokens_per_s' for m in mine.values())
    assert all(mine[name]['workloads'] == [REAL] for name in new)
    for m in cell.per_layer():
        assert hasattr(cell.reducer(m['reducer']), 'read'), m['name']
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 0
    # ISSUE 46's traffic, letter for letter
    assert cell.traffic == {
        'kind': 'decode_ling', 'sessions': 96, 'context': 32768,
        't_max': 33792, 'prefill_chunk': 4096, 'new_tokens': 256,
        'check_samples': 1, 'trace_requests': 1, 'tokens_in_flight': 4,
        'min_requests': 12}
    assert set(cell.limits) == {
        'served_logit_gap', 'expert_pick_difference_share',
        'router_pick_regret', 'recurrent_state_gap',
        'decode_impl_is_kernel', 'expert_routes_off_the_rule',
        'delta_steps_off_the_kernel'}
    assert all(v is not None for v in cell.limits.values())


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` under its own key, but
    for the keys ``reduced`` names; no width among those."""
    cfg = loader.Cell(REAL).config
    assert cfg['reduced'] == [
        'num_hidden_layers', 'first_k_dense_replace', 'num_experts',
        'vocab_size', 'num_nextn_predict_layers',
        'expert_swiglu_limit_list', 'share_expert_swiglu_limit_list']
    widths = {'hidden_size': 2560, 'num_attention_heads': 32,
              'head_dim': 128, 'kv_lora_rank': 512, 'q_lora_rank': None,
              'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
              'v_head_dim': 128, 'intermediate_size': 6144,
              'moe_intermediate_size': 768,
              'moe_shared_expert_intermediate_size': 768,
              'num_experts_per_tok': 8, 'n_group': 8, 'topk_group': 4,
              'routed_scaling_factor': 2.5, 'short_conv_kernel_size': 4,
              'kda_lower_bound': -5, 'rope_theta': 6000000,
              'layer_group_size': 6}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg['published'] == {
        'num_hidden_layers': 42, 'first_k_dense_replace': 2,
        'num_experts': 512, 'vocab_size': 157184,
        'num_nextn_predict_layers': 1,
        'expert_swiglu_limit_list': 35 * [0] + 7 * [4],
        'share_expert_swiglu_limit_list': 34 * [0] + 6 * [5] + 2 * [7]}
    assert cfg['layers_held'] == [1, 6, 7, 8, 9, 10, 11]
    assert cfg['experts_held'] == [0, 64]
    assert flops_ling.group_size(cfg) == 64          # one whole group
    assert cfg['vocab_size'] * 8 == 157184


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell(REAL).config
    assert flops_ling.layer_kinds(cfg) == list('DKKKKKA')
    assert flops_ling.expert_layers(cfg) == [1, 2, 3, 4, 5, 6]
    assert flops_ling.delta_layers(cfg) == [0, 1, 2, 3, 4, 5]
    assert flops_ling.delta_sizes(cfg) == (32, 128, 4)
    assert flops_ling.conv_channels(cfg) == 3 * 4096 == 12288
    # a session's state (32 heads x 128 x 128, float32) and windows
    assert flops_ling.state_bytes(cfg) == (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 2170880
    step = flops_ling.delta_step(cfg, batch=96)
    assert step['bytes'] == 6 * 96 * 2 * 2170880         # read + written
    assert step['flops'] == 6 * 96 * 7 * 32 * 128 * 128
    assert step['bytes'] / 819e9 > step['flops'] / 197e12   # bytes bind
    rows = 32768 + 128 + 1
    mla = flops_ling.mla_decode_step(cfg, batch=96, context=32896)
    # ONE layer: a 576-value row read once for all 32 heads, one written
    assert mla['bytes'] == 1 * 96 * 576 * 2 * (rows + 1)
    assert mla['flops'] == 1 * 96 * 32 * 2 * rows * (576 + 512)
    assert mla['bytes'] / 819e9 > mla['flops'] / 197e12     # bytes bind
    assert 59 < mla['flops'] / mla['bytes'] < 61            # ~60 FLOP/B
    assert flops_ling.expert_bytes(cfg) == 3 * 2560 * 768 * 2
    assert flops_ling.experts_held(cfg) == 64
    assert flops_ling.expected_group_rows(cfg, 96) == 48.0
    # P(a held expert unhit) = (504/512)^96 = 0.221: ~49.8 of 64 a layer
    assert 64 - flops_ling.expected_distinct_held(cfg, 96) == (
        pytest.approx(64 * (504 / 512) ** 96))
    assert 49.7 < flops_ling.expected_distinct_held(cfg, 96) < 49.9
    with pytest.raises(ValueError, match='layers_held'):
        flops_ling.layer_kinds({**cfg, 'layers_held': [1, 6]})


def test_shape_table_counts_the_share():
    """ISSUE 46's arithmetic: 2.87 B parameters, 5.73 GB."""
    cell = loader.Cell(REAL)
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d = 2560
    kda = (d * (5 * 4096 + 32) + 4096 * d + 4 * 12288 + 4096 + 32 + 128)
    mla = d * 6144 + d * 576 + 512 + 512 * 32 * 256 + d * 32 + 4096 * d
    rest = d * 512 + 512 + 3 * d * 768 + 64 * 3 * d * 768 + 2 * d
    dense = 3 * d * 6144 + 2 * d
    assert abs(kda - 63.05e6) < 0.05e6 and abs(mla - 32.0e6) < 0.05e6
    assert count == (kda + dense) + 5 * (kda + rest) + (mla + rest) + (
        2 * 19648 * d + d)
    assert 2.86e9 < count < 2.87e9
    assert 5.72e9 < 2 * count < 5.74e9


def test_the_draws_follow_the_configurations_init():
    cell = loader.Cell(CELL, root=ROOT)
    tree = cell.driver().make(cell.config, 4_000_000_007, jnp.bfloat16)
    block = tree['params']['stack']['block_1']
    delta = block['delta']
    assert delta['A_log'].dtype == delta['dt_bias'].dtype == jnp.float32
    assert delta['in_proj']['kernel'].dtype == jnp.bfloat16
    assert delta['in_proj']['kernel'].shape == (32, 5 * 32 + 4)
    assert 'decay_up' not in delta and 'gate_up' not in delta
    decay = np.exp(np.asarray(delta['A_log']))
    assert np.all((decay >= 1.0) & (decay <= 2.0))
    steps = np.log1p(np.exp(np.asarray(delta['dt_bias'])))
    assert np.all((steps > 0.0009) & (steps < 0.11))
    router = block['moe']['router']
    assert router.dtype == block['moe']['router_bias'].dtype == jnp.float32
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(router), axis=0), 1.0, atol=1e-6)
    assert tree['params']['stack']['block_0']['mlp']['gate'][
        'kernel'].shape == (32, 48)
    attn = cell.driver().make(cell.config, 5, jnp.float32)['params'][
        'stack']['block_2']['attn']
    assert set(attn) == {'q', 'kv_a', 'kv_norm', 'kv_b', 'gate', 'out'}
    # W_q alone is drawn wider, by the whole score deviation
    assert 2.0 < np.asarray(attn['q']['kernel']).std() * np.sqrt(32) < 4.0
    assert 0.7 < np.asarray(attn['kv_a']['kernel']).std() * np.sqrt(
        32) < 1.3
