"""The ``minicpm_sala`` cell's files on the CPU: the loader and the
configuration's keys; the driver against the plain reference at the tiny
preset (its own root, ``tiny_sala``), sound and broken — a float8
reference, a reference that attends every row, an altered token, a reset
that restores nothing, a state kept in bfloat16 among the broken; the
reference's selection and recurrence against hand computations; the new
reader and the accepted ones on a hand-made trace of this stack's names,
the step program's names standing where two programs clash; the
needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_sala, loader, run, scopes, trace as tr
from test_scopes import instruction, message, program

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_sala')
CELL = 'tiny-sala.decode'
REAL = 'minicpm-sala.decode-64k'


def cell_run(capsys, **kwargs):
    cell = loader.Cell(CELL, root=ROOT)
    line = run.run_cell(cell, 4_300_000_007, 0.3, False, jax.devices(),
                        **kwargs)
    out = [json.loads(x) for x in capsys.readouterr().out.splitlines()
           if x.startswith('{')]
    rows = {r['compared']: r for r in out if 'compared' in r}
    return line, rows, out


# -- the files ------------------------------------------------------------------

def test_the_configuration_keeps_the_published_keys():
    cell = loader.Cell(REAL)
    c = cell.config
    assert cell.kind == 'decode_sala' and cell.chips == 1
    assert cell.config_entry['source'] == c['source'] == (
        'https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json')
    assert cell.config_entry['reduced'] == c['reduced'] == [
        'num_hidden_layers', 'mixer_types']
    # the cut: one whole period, published layers 9-12; no width touched
    assert c['num_hidden_layers'] == 4
    assert c['mixer_types'] == c['published']['mixer_types'][9:13] == [
        'minicpm4'] + 3 * ['lightning-attn']
    assert c['published']['num_hidden_layers'] == 32 == len(
        c['published']['mixer_types'])
    assert c['published']['mixer_types'].count('minicpm4') == 8
    assert (c['hidden_size'], c['intermediate_size'], c['vocab_size'],
            c['num_attention_heads'], c['num_key_value_heads'],
            c['head_dim'], c['lightning_nh'], c['lightning_head_dim']) == (
                4096, 16384, 73448, 32, 2, 128, 32, 128)
    assert (c['scale_emb'], c['scale_depth'], c['dim_model_base']) == (
        12, 1.4, 256)
    for key in ('assumed', 'deployment', 'precision', 'init', 'published',
                'reduced_why', 'sparse_config'):
        assert c[key]
    assert c['precision'] == {'params': 'bfloat16', 'compute': 'bfloat16',
                              'state': 'float32'}
    t = cell.traffic
    assert (t['sessions'], t['context'], t['t_max'], t['new_tokens']) == (
        64, 65536, 66560, 256)
    # ISSUE 43's loop, as the six accepted decode cells run theirs
    assert t['tokens_in_flight'] == 4 and t['min_requests'] == 12
    # every step of every request is above dense_len
    assert t['context'] > c['sparse_config']['dense_len']
    # the parameter arithmetic closes on the name, and on the cut
    shapes = cell.driver().shapes(c)
    held = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert 1.711e9 < held < 1.712e9
    layer = {'block_0': 0, 'block_1': 0}         # a sparse, a Lightning
    for path, (shape, _) in shapes.items():
        if len(path) > 1 and path[1] in layer:
            layer[path[1]] += int(np.prod(shape))
    assert 253.7e6 < layer['block_0'] < 253.8e6
    assert 285.2e6 < layer['block_1'] < 285.3e6
    whole = 8 * layer['block_0'] + 24 * layer['block_1'] + 2 * 73448 * 4096
    assert 9.47e9 < whole < 9.49e9


def test_the_cell_joins_the_accepted_metrics():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    mine = {m['name']: m for m in bench['per_layer']
            if REAL in m.get('workloads', [])}
    new = {m for m in mine if mine[m]['workloads'] == [REAL]}
    assert new == {
        'kernel.sparse_decode_ms_per_step', 'kernel.sparse_decode_roofline',
        'kernel.sparse_select_ms_per_step', 'kernel.sparse_select_roofline',
        'kernel.lightning_step_ms_per_step',
        'kernel.lightning_step_roofline',
        'model.lightning_proj_ms_per_step.decode',
        'model.stack_rest_ms_per_step.sparse',
        'model.unscoped_ms_per_step.sparse', 'attn.picked_rows_share',
        'cache.pooled_gib.decode', 'model.head_ms_per_step.sparse',
        'model.attn_proj_ms_per_step.sparse'}
    # by scope the accepted head and projection readers lose this
    # process's clashing names (PERF.md section 7 (vv)): not joined
    assert not {'model.head_ms_per_step.decode',
                'model.attn_proj_ms_per_step.decode'} & set(mine)
    assert {'model.xla_ms_per_step.decode', 'device.idle_pct.decode',
            'device.peak_hbm_gib.decode', 'model.mlp_ms_per_step.decode',
            'cache.full_gib.decode', 'cache.state_gib.decode',
            'cache.state_restore_ms_per_request'} <= set(mine) - new
    assert all(m['moves'] == 'decode_tokens_per_s' for m in mine.values())
    for name in new:
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        assert metric['reducer'] == 'sparse_scopes'
        assert metric['layer'] == mine[name]['layer']
    ends = {m['name'] for m in bench['end_to_end']
            if REAL in m.get('workloads', [REAL])}
    assert ends == {'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s'}
    assert sum(w['chips'] == 4 for w in bench['workloads']) == 0
    # the patterns put the new scopes in front, the selection's before
    # the prefill's that it is also opened inside
    classes = [c for c, _ in loader.read_json(
        loader.HERE, 'scope_patterns_sparse.json')['classes']]
    new_scopes = list(loader.load_module(
        'reducers', 'sparse_scopes').NEW_SCOPES)
    assert set(classes[:6]) == set(new_scopes)
    assert classes.index('ops.sparse_select') < classes.index(
        'ops.sparse_prefill')
    from distributed_dot_product_tpu.obs.spans import DEVICE_SCOPES
    assert set(new_scopes) <= set(DEVICE_SCOPES)


# -- the driver -----------------------------------------------------------------

def test_sound_run_is_correct(capsys):
    line, rows, out = cell_run(capsys)
    assert line['correct'] is True and line['failed'] == 0
    assert {'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s'} <= set(
        line['metrics'])
    # float32 on both sides: the reference agrees to rounding, and picks
    # the program's blocks
    assert rows['served_logit_gap']['value'] < 1e-4
    assert rows['block_pick_difference_share']['value'] == 0.0
    assert rows['block_pick_regret']['value'] < 1e-6
    assert rows['recurrent_state_gap']['value'] < 5e-5
    assert rows['nonfinite_state_resets']['value'] == 0
    # off the TPU the step's form is XLA's, and the row says so (the
    # tiny preset sets no limit on it)
    assert rows['sparse_steps_off_the_kernel']['value'] == 1
    said, = [o for o in out if 'sparse_forms' in o]
    assert said['sparse_forms'] == [
        {'impl': 'xla', 'picks': 4, 'topk': 4, 'group': 4}]
    assert all(said['cache'][k] > 0
               for k in ('full_gib', 'pooled_gib', 'state_gib'))
    # the request compared follows a restore; the picks hold fewer rows
    # than are valid
    sampled, = [o for o in out if 'sampled_request' in o]
    assert sampled['sampled_request'] >= 1
    window, = [o for o in out if 'picked_rows_share' in o]
    assert 0.2 < window['picked_rows_share'] < 0.4      # 4 of ~12 blocks
    assert {'init', 'snapshot', 'prefill'} <= {
        o.get('setup_part') for o in out}
    json.dumps(line)


def test_the_kernels_form_of_the_step_is_correct_and_counted(capsys,
                                                             monkeypatch):
    """The same cell with the sparse layer told ``'kernel'`` (the
    interpreter here): the same numbers, and the counter names it."""
    driver = loader.Cell(CELL, root=ROOT).driver()
    build = driver.build_lm
    monkeypatch.setattr(loader.Cell, 'driver', lambda self: driver)
    monkeypatch.setattr(driver, 'build_lm', lambda config, **kw: build(
        config, decode_impl='kernel', **kw))
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is True
    assert rows['sparse_steps_off_the_kernel']['value'] == 0
    assert rows['block_pick_difference_share']['value'] == 0.0


def test_float8_reference_is_not_correct(capsys):
    line, rows, _ = cell_run(capsys, operand_dtype=jnp.float8_e4m3fn)
    assert line['correct'] is False
    assert not rows['served_logit_gap']['ok']
    assert not rows['block_pick_regret']['ok']
    assert not rows['recurrent_state_gap']['ok']


def test_a_reference_that_attends_every_row_is_not_correct(capsys):
    """Dense in place of picked: the picks are judged sound (they are
    the reference's own) and the logits are not."""
    line, rows, _ = cell_run(capsys, dense_reference=True)
    assert line['correct'] is False
    assert rows['served_logit_gap']['value'] > 1e-2
    assert rows['block_pick_difference_share']['value'] == 0.0


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
    from distributed_dot_product_tpu.models import decode
    monkeypatch.setattr(decode, 'restore_states',
                        lambda caches, snapshot: caches)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert rows['recurrent_state_gap']['value'] > 1e-2


def test_a_state_kept_in_bfloat16_is_not_correct(capsys, monkeypatch):
    cell = loader.Cell(CELL, root=ROOT)
    cell.config['precision']['state'] = 'bfloat16'
    monkeypatch.setattr(loader, 'Cell', lambda *a, **k: cell)
    line, rows, _ = cell_run(capsys)
    assert line['correct'] is False
    assert not rows['recurrent_state_gap']['ok']
    assert rows['recurrent_state_gap']['value'] > 1e-3


def test_the_step_counts_the_rows_its_picks_hold():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 5)
    server.load()
    server.request()
    stats, t, c = server.stats_read[0], cell.traffic, cell.config
    assert stats['picks'].shape == (t['new_tokens'], 1, t['sessions'], 2, 4)
    assert int(stats['step']) == t['new_tokens']
    assert int(stats['steps_off_topk']) == 0
    # by hand: three full blocks and the token's own as far as the token
    want = sum(3 * 16 + (t['context'] + i) % 16 + 1
               for i in range(t['new_tokens'])) * t['sessions'] * 2
    assert float(stats['rows_read']) == want
    assert float(stats['rows_valid']) == sum(
        t['context'] + i + 1
        for i in range(t['new_tokens'])) * t['sessions'] * 2
    # every pick list ends on the token's own block and begins on block 0
    own = (t['context'] + np.arange(t['new_tokens'])) // 16
    assert np.all(stats['picks'][..., -1] == own[:, None, None, None])
    assert np.all(stats['picks'][..., 0] == 0)
    assert server.context_picks.shape == (1, 2, t['context'], 4)
    assert server.cache_gib == flops_sala.cache_gib(server.caches.layers)
    assert server.sparse_steps_off_the_kernel() == 1       # XLA's form


# -- the reference --------------------------------------------------------------

def tiny_config():
    return loader.Cell(CELL, root=ROOT).config


def test_reference_recurrence_by_hand():
    """Two tokens of one head: ``S = λ S + v kᵀ``, ``o = S q``."""
    ref = loader.load_module('reference', 'minicpm_sala')
    q = jnp.asarray([[[1.0, 0.0]], [[0.5, 2.0]]])
    k = jnp.asarray([[[2.0, 1.0]], [[1.0, -1.0]]])
    v = jnp.asarray([[[3.0, 4.0]], [[-1.0, 2.0]]])
    o, state = ref.recurrence(q, k, v, jnp.zeros((1, 2, 2)),
                              jnp.ones((2,), bool))
    lam = np.exp(-2.0 ** -8.0)                      # one head: head 0 of 1
    s1 = np.outer([3.0, 4.0], [2.0, 1.0])
    s2 = lam * s1 + np.outer([-1.0, 2.0], [1.0, -1.0])
    np.testing.assert_allclose(o[0, 0], s1 @ [1.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(o[1, 0], s2 @ [0.5, 2.0], atol=1e-6)
    np.testing.assert_allclose(state[0], s2, atol=1e-6)
    # a row that is not live leaves the state alone
    _, kept = ref.recurrence(q, k, v, jnp.zeros((1, 2, 2)),
                             jnp.asarray([True, False]))
    np.testing.assert_allclose(kept[0], s1, atol=1e-6)


def test_reference_block_scores_by_hand():
    """One KV head, two query heads, 100 keys at the tiny sizes: pooled
    means, the softmax's group sum, the max over the rows that overlap a
    block, the forced blocks."""
    ref = loader.load_module('reference', 'minicpm_sala')
    cfg = {**tiny_config(), 'num_key_value_heads': 1,
           'num_attention_heads': 2}
    rng = np.random.default_rng(0)
    keys = rng.normal(size=(1, 112, 16)).astype(np.float32)
    q = rng.normal(size=(2, 1, 16)).astype(np.float32) * 2
    with jax.default_matmul_precision('highest'):
        pooled = ref.pooled_keys(cfg, jnp.asarray(keys))
        got = ref.block_scores(cfg, jnp.asarray(q), pooled,
                               jnp.asarray([99]), 7)
    assert pooled.shape == (1, 27, 16)
    np.testing.assert_allclose(pooled[0, 5], keys[0, 20:28].mean(0),
                               atol=1e-6)
    rows = (100 - 8) // 4 + 1
    s = q[:, 0] @ np.asarray(pooled[0, :rows]).T / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    s = (p / p.sum(-1, keepdims=True)).sum(0)
    own = 99 // 16                                            # block 6
    for b in range(7):
        js = [j for j in range(rows) if j * 4 < (b + 1) * 16
              and j * 4 + 8 > b * 16]
        want = max(s[j] for j in js)
        if b == 0 or b > own - 2:
            want = np.inf
        np.testing.assert_allclose(got[0, 0, b], want, rtol=1e-5)


# -- the needed work ------------------------------------------------------------

def test_needed_work_by_hand():
    cfg = loader.Cell(REAL).config
    assert flops_sala.layer_kinds(cfg) == ['minicpm4'] + 3 * [
        'lightning-attn']
    mid = 65536 + 128
    # 63 whole blocks and the token's own as far as the token (row 0 of
    # block 1026: 65664 = 1026 x 64)
    assert flops_sala.picked_rows(cfg, mid) == 63 * 64 + 1
    assert flops_sala.picked_rows(cfg, 8191) == 8192        # dense_len
    assert flops_sala.picked_rows(cfg, 8192) == 63 * 64 + 1
    assert flops_sala.picked_rows(cfg, 100) == 101
    assert flops_sala.pooled_rows(cfg, mid) == (65665 - 32) // 16 + 1
    assert flops_sala.pooled_rows(cfg, 30) == 0
    need = flops_sala.sparse_decode_step(cfg, 64, mid)
    assert need['bytes'] == 1 * 64 * 2 * (4033 + 1) * 512
    assert need['flops'] == 64 * 32 * 4 * 128 * 4033
    need = flops_sala.sparse_select_step(cfg, 64, mid)
    assert need['bytes'] == 64 * 2 * 4103 * 256
    need = flops_sala.lightning_step(cfg, 64)
    assert need['bytes'] == 3 * 64 * 2 * 32 * 128 * 128 * 4
    assert need['flops'] == 3 * 64 * 5 * 32 * 128 * 128
    assert flops_sala.picked_rows_share(cfg, mid) == pytest.approx(
        4033 / 65665)


def test_cache_gib_counts_the_three_kinds():
    from distributed_dot_product_tpu.models.decode import (
        StateCache, init_sparse_cache,
    )
    caches = [init_sparse_cache(2, 2, 1024, 128, 16),
              StateCache(jnp.zeros((2, 4, 8, 8), jnp.float32),
                         jnp.zeros((2, 0, 32), jnp.bfloat16))]
    got = flops_sala.cache_gib(caches)
    assert got == {'full_gib': 2 * 2 * 2 * 1024 * 128 * 2 / 2 ** 30,
                   'pooled_gib': 2 * 2 * 64 * 128 * 2 / 2 ** 30,
                   'state_gib': 2 * 4 * 8 * 8 * 4 / 2 ** 30}


# -- the readers on this stack's names -------------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
OPS = {   # instruction: (op_name, self ns)
    'sparse_decode.1': (STEP + '/block_0.decode/attn.decode/'
                        'ops.sparse_decode/sparse_decode/pallas_call', 1200),
    'sort.1': (STEP + '/block_0.decode/attn.decode/ops.sparse_select/top_k',
               700),
    'fusion.1': (STEP + '/block_0.decode/attn.decode/ops.sparse_select/'
                 'dot_general', 100),
    'fusion.2': (STEP + '/block_0.decode/attn.decode/lm.attn_proj/gate/'
                 'dot_general', 160),
    'fusion.3': (STEP + '/block_1.decode/lightning.decode/'
                 'ops.lightning_step/mul', 2400),
    'fusion.4': (STEP + '/block_1.decode/lightning.decode/'
                 'lm.lightning_proj/in_proj/dot_general', 1000),
    'fusion.5': (STEP + '/block_1.decode/lm.mlp/mlp/up/dot_general', 500),
    'fusion.6': (STEP + '/block_0.decode/add', 100),
    'fusion.8': ('jit(step_fn)/TransformerLM.decode/lm.head/dot_general',
                 900),
    'fusion.9': ('jit(step_fn)/argmax', 40),
    'fusion.7': ('jit(restore_fn)/lm.state_restore/dynamic_update_slice',
                 6000),
}
# Another program of the process numbers its fusions alike.
CLASH = {'fusion.8': 'jit(prefill_fn)/lm.stack_carry/add',
         'fusion.3': 'jit(prefill_fn)/lm.attn_proj/dot_general'}


def opcode(name):
    return 'custom-call' if name.startswith('sparse_decode') else (
        name.split('.')[0])


def named_xspace(**programs):
    """``test_scopes.xspace`` with the programs' names given."""
    stat = message((1, 7), (2, message((1, 7), (2, scopes.HLO_STAT))))
    metas = [message((1, i), (2, message(
        (1, i), (2, name), (5, message((1, 7), (6, proto))))))
        for i, (name, proto) in enumerate(programs.items())]
    return message((1, message((2, '/device:TPU:0'))),
                   (1, message((2, scopes.METADATA_PLANE), (5, stat),
                               *[(4, m) for m in metas])))


def hand_trace(tmp_path, monkeypatch, ops, name='jit_step_fn', clash=None):
    programs = {name: program([
        instruction(n, opcode(n), i + 10, op_name)
        for i, (n, (op_name, _)) in enumerate(ops.items())])}
    if clash:
        programs['jit_prefill_fn'] = program([
            instruction(n, 'fusion', i + 10, op_name)
            for i, (n, op_name) in enumerate(clash.items())])
    path = tmp_path / 'hand.xplane.pb'
    path.write_bytes(named_xspace(**programs))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    at, rows = 0, []
    for n, (_, ns) in ops.items():
        rows.append([f'%{n} {opcode(n)}', at, ns, ns])
        at += ns

    class Run:
        cell, patterns = None, tr.patterns()
        trace = {'devices': {'/device:TPU:0': rows}, 'host': []}
        peaks = {'flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
        observed = {
            'steps': 2, 'requests': 3,
            'sparse_decode_per_step': {'bytes': 819e9 * 0.3e-6,
                                       'flops': 1.0},
            'sparse_select_per_step': {'bytes': 819e9 * 0.1e-6,
                                       'flops': 1.0},
            'lightning_step_per_step': {'bytes': 819e9 * 0.96e-6,
                                        'flops': 1.0},
            'attn': {'picked_rows_share': 0.0614},
            'cache': {'full_gib': 4.06, 'pooled_gib': 0.127,
                      'state_gib': 0.375}}
    return Run


def read(run_class, name):
    metric = loader.read_json(loader.HERE, 'layer_metrics', f'{name}.json')
    return loader.load_module('reducers', metric['reducer']).read(
        run_class, metric)


@pytest.mark.parametrize('clash', [None, CLASH], ids=['alone', 'clash'])
def test_the_cells_metrics_on_a_hand_made_trace(tmp_path, monkeypatch,
                                                clash):
    """With and without a second program whose instructions carry the
    step's names under other scopes: the new reader reads the step's."""
    Run = hand_trace(tmp_path, monkeypatch, OPS, clash=clash)
    assert read(Run, 'kernel.sparse_decode_ms_per_step') == pytest.approx(
        0.6e-3)
    assert read(Run, 'kernel.sparse_decode_roofline') == pytest.approx(50.0)
    assert read(Run, 'kernel.sparse_select_ms_per_step') == pytest.approx(
        0.4e-3)
    assert read(Run, 'kernel.sparse_select_roofline') == pytest.approx(25.0)
    assert read(Run, 'kernel.lightning_step_ms_per_step') == pytest.approx(
        1.2e-3)
    assert read(Run, 'kernel.lightning_step_roofline') == pytest.approx(80.0)
    assert read(Run, 'model.lightning_proj_ms_per_step.decode') == (
        pytest.approx(0.5e-3))
    assert read(Run, 'model.stack_rest_ms_per_step.sparse') == (
        pytest.approx(0.05e-3))
    assert read(Run, 'model.unscoped_ms_per_step.sparse') == pytest.approx(
        0.02e-3)
    assert read(Run, 'model.head_ms_per_step.sparse') == pytest.approx(
        0.45e-3)
    assert read(Run, 'model.attn_proj_ms_per_step.sparse') == (
        pytest.approx(0.08e-3))
    assert read(Run, 'attn.picked_rows_share') == 0.0614
    assert read(Run, 'cache.pooled_gib.decode') == 0.127
    # the accepted counters read this program's observed groups
    assert read(Run, 'cache.full_gib.decode') == 4.06
    assert read(Run, 'cache.state_gib.decode') == 0.375
    assert read(Run, 'cache.state_restore_ms_per_request') == (
        pytest.approx(2e-3))
    if clash is None:
        # the accepted readers by scope read this program the same: the
        # new scopes are SIBLINGS of lm.attn_proj, which stays the
        # projections alone
        assert read(Run, 'model.attn_proj_ms_per_step.decode') == (
            pytest.approx(0.08e-3))
        assert read(Run, 'model.mlp_ms_per_step.decode') == pytest.approx(
            0.25e-3)
        assert read(Run, 'model.head_ms_per_step.decode') == pytest.approx(
            0.45e-3)
        # one that does not know the new scopes takes them for the
        # stack's: why the cell has a stack_rest of its own
        hybrid = loader.read_json(
            loader.HERE, 'layer_metrics',
            'model.stack_rest_ms_per_step.hybrid.json')
        assert loader.load_module('reducers', hybrid['reducer']).read(
            Run, hybrid) == pytest.approx(
                (1200 + 700 + 100 + 2400 + 1000 + 100) / 2e6)
    else:
        # scopes.instruction_map files a clashing name as unattributed:
        # the accepted head reader loses the head to it
        assert read(Run, 'model.head_ms_per_step.decode') == 0.0


def test_the_new_reader_finds_nothing_in_a_parents_program(tmp_path,
                                                           monkeypatch):
    """A program that opens none of the new scopes: every new metric by
    scope is absent, nothing raises."""
    ops = {k: v for k, v in OPS.items()
           if 'sparse' not in v[0] and 'lightning' not in v[0]}
    Run = hand_trace(tmp_path, monkeypatch, ops)
    Run.observed = {'steps': 2, 'requests': 1}
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    new = [m['name'] for m in bench['per_layer']
           if m.get('workloads') == [REAL]]
    assert len(new) == 13
    for name in new:
        assert read(Run, name) is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert read(Run, 'kernel.sparse_decode_ms_per_step') is None
