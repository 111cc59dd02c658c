"""The latent-attention / sparse-expert cell's files on the CPU: the
driver against the plain reference at the tiny preset (its own root,
``tiny_latent``), sound and broken; the reducer ``latent_scopes`` on a
hand-made trace; the needed-work functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_latent, loader, run, scopes, trace as tr
from test_scopes import instruction, program, xspace

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    'tiny_latent')
CELL = 'tiny-xing4.decode'


def cell_run(capsys, **kwargs):
    cell = loader.Cell(CELL, root=ROOT)
    line = run.run_cell(cell, 4_000_000_007, 0.3, False, jax.devices(),
                        **kwargs)
    rows = {r['compared']: r for r in (
        json.loads(x) for x in capsys.readouterr().out.splitlines()
        if x.startswith('{"compared"'))}
    return line, rows


def test_sound_run_is_correct(capsys):
    line, rows = cell_run(capsys)
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0
    assert set(line['metrics']) == {'decode_tokens_per_s',
                                    'decode_gap_ms_p95', 'setup_s'}
    assert rows['served_logit_gap']['value'] < 0.15
    assert 0.0 <= rows['expert_pick_difference_share']['value'] < 0.15
    assert 0.0 <= rows['router_pick_regret']['value'] < 0.05
    json.dumps(line)


def test_float8_reference_is_not_correct(capsys):
    line, rows = cell_run(capsys, operand_dtype=jnp.float8_e4m3fn)
    assert line['correct'] is False
    # by one of the limits (which request the window ends on, and so
    # which is sampled, varies from run to run)
    assert not (rows['served_logit_gap']['ok']
                and rows['expert_pick_difference_share']['ok']
                and rows['router_pick_regret']['ok'])


def altered_token(step):
    def broken(params, tok, caches, stats):
        caches, nxt, ok, stats = step(params, tok, caches, stats)
        return caches, (nxt + 1) % 128, ok, stats
    return broken


def test_broken_timed_path_is_not_correct(capsys):
    line, rows = cell_run(capsys, step_wrapper=altered_token)
    assert line['correct'] is False
    assert not rows['served_logit_gap']['ok']


class WrongPick:
    """``jax.lax`` as ``models/moe.py`` sees it, with a ``top_k`` that
    swaps every eighth token's last pick for its worst-ranked expert:
    a router that decides wrongly on an eighth of the tokens, the gates
    still the picked scores'."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def top_k(ranked, k):
        values, picked = jax.lax.top_k(ranked, k)
        worst = jnp.argmin(ranked, axis=-1).astype(picked.dtype)
        swap = (jnp.arange(ranked.shape[0]) % 8 == 0)
        return values, picked.at[:, -1].set(
            jnp.where(swap, worst, picked[:, -1]))


def test_a_router_that_decides_wrongly_is_not_correct(capsys,
                                                      monkeypatch):
    """The reference follows the program's picks, so the logits agree
    and the share of picks that differ stays under ITS limit at the
    cell's size (an eighth on top of the near-ties); the regret, by the
    reference's own router scores, does not."""
    from distributed_dot_product_tpu.models import moe
    monkeypatch.setattr(moe, 'lax', WrongPick())
    line, rows = cell_run(capsys)
    assert rows['served_logit_gap']['ok']
    assert rows['expert_pick_difference_share']['value'] < 0.25
    assert rows['router_pick_regret']['value'] > 0.3
    assert not rows['router_pick_regret']['ok']
    assert line['correct'] is False


def test_counters_say_what_the_step_routed():
    cell = loader.Cell(CELL, root=ROOT)
    driver = cell.driver()
    server = driver.Server(cell, 11)
    server.load()
    server.request()
    stats, = server.stats_read
    t, cfg = cell.traffic, cell.config
    layers, k = flops_latent.expert_layers(cfg), cfg['num_experts_per_tok']
    assert int(stats['step']) == t['new_tokens']
    # every token of every session picks k experts in every expert layer
    assert stats['expert_tokens'].sum(axis=1).tolist() == [
        t['new_tokens'] * t['sessions'] * k] * layers
    picks = stats['picks']                  # (steps, layers, sessions, k)
    counted = np.stack([np.bincount(picks[:, l].ravel(),
                                    minlength=cfg['n_routed_experts'])
                        for l in range(layers)])
    assert np.array_equal(counted, stats['expert_tokens'])
    distinct = sum(len(np.unique(picks[i, l])) for i in range(len(picks))
                   for l in range(layers))
    assert int(stats['active']) == distinct
    routing = driver.routing_readings(cfg, server.stats_read, t['sessions'])
    assert routing['active_experts_per_step'] == distinct / t['new_tokens']
    assert routing['load_max_over_mean'] >= 1.0


# -- the reducer on a hand-made trace ----------------------------------------

STEP = 'jit(step_fn)/TransformerLM.decode/stack.decode/lm.stack_carry'
BLOCK = STEP + '/while/body/closed_call/layers.decode/block.decode'
OPS = {   # instruction: (op_name, self ns)
    'mla_decode.1': (BLOCK + '/attn.decode/ops.mla_decode/mla_decode/'
                     'pallas_call', 4000),
    'fusion.1': (BLOCK + '/attn.decode/lm.attn_proj/q_b/dot_general', 700),
    'ragged-dot-none.1': ('ragged-dot-none', 3000),
    'ragged-dot-metadata.1': ('ragged-dot-metadata', 10),
    'fusion.2': (BLOCK + '/block._mlp/moe/lm.moe_experts/mul', 90),
    'fusion.3': (BLOCK + '/block._mlp/moe/lm.moe_route/top_k', 300),
    'fusion.4': (BLOCK + '/block._mlp/moe/lm.mlp/shared/gate/dot_general',
                 500),
    'fusion.5': (BLOCK + '/hc_attn/lm.hc/dot_general', 200),
    'fusion.6': (BLOCK + '/ln1/mul', 50),
    'fusion.7': ('jit(step_fn)/TransformerLM.decode/lm.head/dot_general',
                 600),
    'convert.1': ('', 40),
}


def opcode(name):
    if 'dot-' in name or name.startswith('mla'):
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
            'mla_decode_per_step': {'bytes': 819e9 * 1e-6, 'flops': 1.0},
            'moe': {'active_experts_per_step': 10.0,
                    'expert_bytes': 819e9 * 0.1e-6,
                    'load_max_over_mean': 1.5}}
    return Run


def read(run, name):
    metric = loader.read_json(loader.HERE, 'layer_metrics', f'{name}.json')
    return loader.load_module('reducers', metric['reducer']).read(run,
                                                                  metric)


def test_latent_scope_metrics_on_a_hand_made_trace(traced):
    ms = {name: read(traced, name) for name in (
        'kernel.mla_decode_ms_per_step',
        'model.moe_experts_ms_per_step.decode',
        'model.moe_route_ms_per_step.decode', 'model.hc_ms_per_step.decode',
        'model.stack_rest_ms_per_step.decode',
        'model.unscoped_ms_per_step.decode')}
    # ns of the window over 2 steps, in ms: XLA's grouped-matmul kernels
    # count as the experts' by their own name
    assert ms == pytest.approx({
        'kernel.mla_decode_ms_per_step': 2e-3,
        'model.moe_experts_ms_per_step.decode': 1.55e-3,
        'model.moe_route_ms_per_step.decode': 0.15e-3,
        'model.hc_ms_per_step.decode': 0.1e-3,
        'model.stack_rest_ms_per_step.decode': 0.025e-3,
        'model.unscoped_ms_per_step.decode': 0.02e-3})
    # with the accepted scopes' rows the parts are the whole step
    rest = (700 + 500 + 600) / 2 * 1e-6
    assert sum(ms.values()) + rest == pytest.approx(
        sum(ns for _, ns in OPS.values()) / 2 * 1e-6)
    # needed 1 us a step over 2 us a step; 10 experts x 0.1 us over 1.55
    assert read(traced, 'kernel.mla_decode_roofline') == pytest.approx(50.0)
    assert read(traced, 'moe.expert_stream_roofline') == pytest.approx(
        100 * 1.0 / 1.55)
    assert read(traced, 'moe.active_experts_per_step') == 10.0
    assert read(traced, 'moe.load_max_over_mean') == 1.5


def test_a_program_without_these_scopes_gives_no_number(traced, tmp_path,
                                                        monkeypatch):
    path = tmp_path / 'dense.xplane.pb'
    path.write_bytes(xspace(program([
        instruction('fusion.1', 'fusion', 10,
                    'jit(step_fn)/lm.stack_carry/lm.mlp/dot_general')])))
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: str(path))
    assert read(traced, 'model.hc_ms_per_step.decode') is None
    assert read(traced, 'kernel.mla_decode_roofline') is None
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: None)
    assert read(traced, 'model.moe_route_ms_per_step.decode') is None


def test_the_patterns_cover_the_programs_vocabulary():
    from distributed_dot_product_tpu.obs.spans import DEVICE_SCOPES
    reducer = loader.load_module('reducers', 'latent_scopes')
    classes = [c for c, _ in reducer.patterns()['classes']]
    assert classes[-1] == scopes.UNATTRIBUTED
    assert sorted(classes[:-1]) == sorted(DEVICE_SCOPES)
    assert set(reducer.NEW_SCOPES) < set(classes)
    # the accepted rows follow the new ones, in their order
    accepted = [c for c, _ in scopes.patterns()['classes']]
    assert classes[len(reducer.NEW_SCOPES):] == accepted
    assert scopes.classify('ragged-dot-none', reducer.patterns()) == (
        'lm.moe_experts', 'none')


def test_every_new_metric_has_its_file_and_the_cell():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    mine = [m for m in bench['per_layer']
            if 'xing4-29b-a4b.decode-32k' in m.get('workloads', [])]
    new = [m['name'] for m in mine
           if m['workloads'] == ['xing4-29b-a4b.decode-32k']]
    assert len(new) == 10 and len(mine) == 17
    for name in new:
        metric = loader.read_json(loader.HERE, 'layer_metrics',
                                  f'{name}.json')
        assert metric['reducer'] == 'latent_scopes'
    cell = loader.Cell('xing4-29b-a4b.decode-32k')
    assert [m['name'] for m in cell.end_to_end()] == [
        'decode_tokens_per_s', 'decode_gap_ms_p95', 'setup_s']
    assert cell.kind == 'decode_latent'


# -- needed work -----------------------------------------------------------------

def test_needed_work_against_hand_counts():
    cfg = loader.Cell('xing4-29b-a4b.decode-32k').config
    need = flops_latent.mla_decode_step(cfg, batch=16, context=32895)
    rows = 32896
    # a 576-value bfloat16 row a token, read once for all 32 heads
    assert need['bytes'] == 6 * 16 * 1152 * (rows + 1)
    # per head and row: 576 multiply-adds of score, 512 of context
    assert need['flops'] == 6 * 16 * 32 * 2 * (576 + 512) * rows
    assert need['flops'] / need['bytes'] == pytest.approx(60.4, abs=0.1)
    assert flops_latent.expert_bytes(cfg) == 3 * 3584 * 1024 * 2
    assert flops_latent.expert_layers(cfg) == 5
    assert flops_latent.expected_distinct_experts(cfg, 16) == (
        pytest.approx(64 * (1 - (60 / 64) ** 16)))


def test_shape_table_counts_the_published_parameters():
    cell = loader.Cell('xing4-29b-a4b.decode-32k')
    table = cell.driver().shapes(cell.config)
    count = sum(int(np.prod(shape)) for shape, _ in table.values())
    d, e = 3584, 64
    attn = (d * 768 + 768 + 768 * 32 * 192 + d * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * d)
    hc = 2 * (4 * d * 24 + 24 + 3)
    block = attn + hc + 2 * d
    dense = block + 3 * d * 9216
    sparse = block + 3 * d * 1024 + d * e + e + e * 3 * d * 1024
    assert count == dense + 5 * sparse + 2 * 131072 * d + d
    assert 9.58e9 < 2 * count < 9.62e9          # bfloat16 bytes
