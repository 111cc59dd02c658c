"""The scope reader: patterns, the protobuf wire reader, how an
instruction comes by its ``op_name``, and the new metrics on two
fragments cut (``tools/cut_trace.py``) from this PR's traced chip runs
(``recorded_scopes.json`` names them)."""

import gzip
import os

import jax
import pytest

from benchmarks import loader, scopes, trace as tr
from distributed_dot_product_tpu.obs.spans import DEVICE_SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = loader.read_json(HERE, 'recorded_scopes.json')
STACK = 'jit(step)/transpose(jvp(TransformerLM.nll_sum))/stack/lm.stack_carry'
BLOCK = STACK + '/while/body/closed_call/checkpoint/layers.layer/block'


# -- patterns ------------------------------------------------------------

@pytest.mark.parametrize('op_name, cls, pas', [
    # innermost scope first, whatever is open around it
    (BLOCK + '/attn/lm.attn_proj/attn._attend/ops.flash_bwd_dq/'
     'flash_bwd_dq/pallas_call', 'ops.flash_bwd_dq', 'backward'),
    (BLOCK + '/attn/lm.attn_proj/attn._attend/keys/dot_general',
     'lm.attn_proj', 'backward'),
    (BLOCK.replace('checkpoint', 'checkpoint/rematted_computation')
     + '/attn/lm.attn_proj/attn._attend/ops.flash_fwd/flash_fwd/'
     'pallas_call', 'ops.flash_fwd', 'recompute'),
    (BLOCK + '/ln1/reduce_sum', 'lm.stack_carry', 'backward'),
    (STACK + '/while/body/dynamic_update_slice', 'lm.stack_carry',
     'backward'),
    ('jit(step)/jvp(TransformerLM.nll_sum)/stack/lm.stack_carry/while/body/'
     'closed_call/layers.layer/block/block._mlp/lm.mlp/mlp_in/dot_general',
     'lm.mlp', 'forward'),
    # lm.head is no prefix match of lm.head_loss
    ('jit(step)/jvp(TransformerLM.nll_sum)/lm.head_loss/while/body/'
     'closed_call/...cd,vd->...cv/dot_general', 'lm.head_loss', 'forward'),
    ('jit(step_fn)/TransformerLM.decode/TransformerLM._head/lm.head/ln_f/'
     'mul', 'lm.head', 'none'),
    # the first scope inside a transformed function stands in brackets
    ('jit(step)/shard_map/jvp(train.grad_sync)/psum', 'train.grad_sync',
     'optimizer'),
    ('jit(step)/train.optimizer/add', 'train.optimizer', 'optimizer'),
    ('jit(step)/jvp(TransformerLM.nll_sum)/mul', 'unattributed', 'forward'),
    ("params['params']['embed']['embedding']", 'unattributed', 'none'),
    ('', 'unattributed', 'none'),
])
def test_first_match_wins(op_name, cls, pas):
    assert scopes.classify(op_name, scopes.patterns()) == (cls, pas)


def test_every_class_is_a_device_scope():
    classes = [c for c, _ in scopes.patterns()['classes']]
    assert classes[-1] == scopes.UNATTRIBUTED
    assert sorted(classes[:-1]) == sorted(DEVICE_SCOPES)


def metric_files():
    names = [m['name'] for m in loader.read_json(loader.ROOT,
                                                 'BENCHMARK.json')['per_layer']]
    files = {n: loader.read_json(loader.HERE, 'layer_metrics', f'{n}.json')
             for n in names}
    return {n: f for n, f in files.items()
            if f['reducer'] == 'scope_ms_per_step'}


SCOPE_METRICS = {
    'train': ['kernel.flash_fwd_ms_per_step',
              'kernel.flash_recompute_ms_per_step',
              'kernel.flash_bwd_ms_per_step', 'train.optimizer_ms_per_step',
              'model.mlp_ms_per_step.train',
              'model.attn_proj_ms_per_step.train',
              'model.head_loss_ms_per_step',
              'model.stack_carry_ms_per_step.train',
              'model.other_ms_per_step.train',
              'model.unattributed_ms_per_step.train'],
    'decode': ['model.cache_carry_ms_per_step.decode',
               'model.mlp_ms_per_step.decode',
               'model.attn_proj_ms_per_step.decode',
               'model.head_ms_per_step.decode',
               'model.other_ms_per_step.decode',
               'model.unattributed_ms_per_step.decode'],
}
PASS_METRICS = ['train.forward_ms_per_step', 'train.recompute_ms_per_step',
                'train.backward_ms_per_step']


@pytest.mark.parametrize('kind', ['train', 'decode'])
def test_a_cells_scope_metrics_share_out_every_class_once(kind):
    files = metric_files()
    listed = [(c, p) for n in SCOPE_METRICS[kind] for c in files[n]['scopes']
              for p in files[n].get('passes', ['any'])]
    if kind == 'decode':      # kernel.decode_ms_per_step reads the kernel
        listed.append(('ops.flash_decode', 'any'))
    assert len(set(listed)) == len(listed)
    assert {c for c, _ in listed} == {c for c, _ in
                                      scopes.patterns()['classes']}
    assert set(files) == (set(SCOPE_METRICS['train'])
                          | set(SCOPE_METRICS['decode']) | set(PASS_METRICS))


# -- how an instruction comes by its op_name ------------------------------

def message(*pairs):
    """A serialized protobuf message from (field number, int | bytes |
    str) pairs."""
    out = bytearray()

    def varint(n):
        while True:
            out.append((n & 0x7f) | (0x80 if n > 0x7f else 0))
            n >>= 7
            if not n:
                return

    for number, value in pairs:
        if isinstance(value, int):
            varint(number << 3)
            varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            varint(number << 3 | 2)
            varint(len(value))
            out += value
    return bytes(out)


def instruction(name, opcode, ident, op_name='', operands=(), calls=()):
    return message((1, name), (2, opcode), (7, message((2, op_name))),
                   (35, ident), *[(36, i) for i in operands],
                   *[(38, i) for i in calls])


def program(entry_instructions, **others):
    """An HloProto: the entry computation (id 1) and ``others`` as
    ``name=(id, root id, instructions)``."""
    comps = [message((1, 'main'), (5, 1), (6, 0),
                     *[(2, i) for i in entry_instructions])]
    comps += [message((1, name), (5, ident), (6, root),
                      *[(2, i) for i in body])
              for name, (ident, root, body) in others.items()]
    return message((1, message((6, 1), *[(3, c) for c in comps])))


SCAN = 'jit(f)/lm.stack_carry/while'


@pytest.fixture(scope='module')
def synthetic():
    return program(
        [instruction('p', 'parameter', 10),
         instruction('convert.1', 'convert', 11, operands=[10]),
         instruction('while.1', 'while', 12, SCAN, operands=[11],
                     calls=[2]),
         instruction('gte.1', 'get-tuple-element', 13, operands=[12]),
         instruction('copy.1', 'copy', 14, operands=[13])],
        body=(2, 23, [
            instruction('arg', 'parameter', 20),
            instruction('copy.2', 'copy', 21, operands=[20]),
            instruction('fusion.1', 'fusion', 22, operands=[21], calls=[3]),
            instruction('fusion.2', 'fusion', 23, operands=[22], calls=[4]),
            instruction('fusion.3', 'fusion', 24,
                        SCAN + '/body/lm.mlp/mlp_in/dot_general',
                        operands=[22], calls=[3])]),
        fused_named_root=(3, 31, [
            instruction('mul.9', 'multiply', 30, SCAN + '/body/lm.mlp/mul'),
            instruction('add.9', 'add', 31,
                        SCAN + '/body/lm.attn_proj/add')]),
        fused_bitcast_root=(4, 42, [
            instruction('dot.9', 'dot', 40,
                        SCAN + '/body/lm.mlp/mlp_out/dot_general'),
            instruction('neg.9', 'negate', 41),
            instruction('bitcast.9', 'bitcast', 42)]))


@pytest.mark.parametrize('name, op_name, how', [
    ('while.1', SCAN, 'own'),
    ('fusion.3', SCAN + '/body/lm.mlp/mlp_in/dot_general', 'own'),
    # a fusion takes its root's scope, not another fused instruction's
    ('fusion.1', SCAN + '/body/lm.attn_proj/add', 'fused'),
    # ... and a nameless root leaves it to the named one nearest before
    ('fusion.2', SCAN + '/body/lm.mlp/mlp_out/dot_general', 'fused'),
    # what XLA put in takes its producer's: the scan's result, copied
    ('gte.1', SCAN, 'operand'),
    ('copy.1', SCAN, 'operand'),
    # ... or its caller's: the scan body's parameter, and a copy of it
    ('arg', SCAN, 'caller'),
    ('copy.2', SCAN, 'operand'),
    ('convert.1', '', 'none'),
])
def test_where_an_op_name_comes_from(synthetic, name, op_name, how):
    assert scopes.op_names(synthetic)[name] == (op_name, how)


def test_fused_instructions_are_no_events(synthetic):
    assert 'add.9' not in scopes.op_names(synthetic)


def xspace(*programs):
    stat = message((1, 7), (2, message((1, 7), (2, scopes.HLO_STAT))))
    metas = [message((1, i), (2, message(
        (1, i), (2, f'jit_f({i})'), (5, message((1, 7), (6, proto))))))
        for i, proto in enumerate(programs)]
    return message((1, message((2, '/device:TPU:0'))),
                   (1, message((2, scopes.METADATA_PLANE), (5, stat),
                               *[(4, m) for m in metas])))


def test_a_name_two_programs_disagree_on_is_unattributed(tmp_path):
    other = program([instruction('while.1', 'while', 12,
                                 'jit(g)/lm.head/while'),
                     instruction('gte.1', 'get-tuple-element', 13,
                                 SCAN + '/x')])
    path = tmp_path / 'two.xplane.pb'
    path.write_bytes(xspace(
        program([instruction('while.1', 'while', 12, SCAN),
                 instruction('gte.1', 'get-tuple-element', 13,
                             operands=[12])]), other))
    mapping = scopes.instruction_map(str(path))
    assert mapping['while.1'][:2] == ('unattributed', 'none')
    assert mapping['gte.1'][:2] == ('lm.stack_carry', 'none')


# -- the recorded fragments -------------------------------------------------

@pytest.fixture(scope='module', params=['train', 'decode'])
def fragment(request, tmp_path_factory):
    """(kind, path, a run as the reducers see it)."""
    entry = RECORDED[request.param]
    path = tmp_path_factory.mktemp(request.param) / 'cut.xplane.pb'
    with gzip.open(os.path.join(HERE, entry['file'])) as f:
        path.write_bytes(f.read())

    class Run:
        patterns = tr.patterns()
        trace = tr.load_xplane(str(path), patterns)
        observed = {'steps': entry['steps']}
        cell = None

    return request.param, str(path), Run


def read(name, run, path, monkeypatch, **metric):
    monkeypatch.setattr(scopes, 'xplane_for', lambda cell: path)
    return loader.load_module('reducers', name).read(run, metric)


def test_wire_reader_agrees_with_the_generated_classes(fragment):
    hlo_pb2 = pytest.importorskip('tensorflow.compiler.xla.service.hlo_pb2')
    xplane_pb2 = pytest.importorskip(
        'tensorflow.tsl.profiler.protobuf.xplane_pb2')
    _, path, _ = fragment
    with open(path, 'rb') as f:
        data = f.read()
    space = xplane_pb2.XSpace.FromString(data)
    plane = next(p for p in space.planes if p.name == scopes.METADATA_PLANE)
    want = []
    for meta in plane.event_metadata.values():
        module = hlo_pb2.HloProto.FromString(
            meta.stats[0].bytes_value).hlo_module
        want.append((meta.name, module.entry_computation_id, [
            (c.id, c.name, c.root_id,
             [(i.name, i.opcode, i.metadata.op_name, i.id,
               list(i.operand_ids), list(i.called_computation_ids))
              for i in c.instructions]) for c in module.computations]))
    got = []
    for program_name, proto in scopes.hlo_protos(data):
        comps, entry = scopes.computations(proto)
        got.append((program_name, entry, [
            (ident, c['name'], c['root_id'],
             [(i['name'], i['opcode'], i['op_name'], i['id'], i['operands'],
               i['calls']) for i in c['instructions']])
            for ident, c in comps.items()]))
    assert got == want and got[0][2]


def test_scope_metrics_partition_the_step(fragment, monkeypatch):
    kind, path, run = fragment
    files = metric_files()
    classes = {c: read('class_ms_per_step', run, path, monkeypatch,
                       op_class=c) for c in ('xla', 'kernel')}
    by_metric = {n: read('scope_ms_per_step', run, path, monkeypatch,
                         **files[n])
                 for n in SCOPE_METRICS[kind] + (
                     PASS_METRICS if kind == 'train' else [])}
    total = sum(by_metric[n] for n in SCOPE_METRICS[kind])
    if kind == 'decode':
        total += classes['kernel']
    assert total == pytest.approx(classes['xla'] + classes['kernel'],
                                  rel=1e-3)
    want = RECORDED[kind]['ms_per_step']
    assert {n: round(v, 2) for n, v in by_metric.items()} == want
    step = classes['xla'] + classes['kernel']
    unattributed = by_metric[f'model.unattributed_ms_per_step.{kind}']
    assert unattributed < 0.05 * step
    if kind == 'train':
        flash = sum(by_metric[f'kernel.flash_{k}_ms_per_step']
                    for k in ('fwd', 'recompute', 'bwd'))
        assert flash == pytest.approx(classes['kernel'], rel=1e-3)
        passes = (sum(by_metric[n] for n in PASS_METRICS)
                  + by_metric['train.optimizer_ms_per_step'])
        assert 0 <= step - passes < 0.02 * step


def test_unattributed_is_what_no_class_took(fragment):
    _, path, run = fragment
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    by_scope = scopes.seconds_by_scope(ops, scopes.instruction_map(path),
                                       run.patterns)
    named = sum(sec for (cls, _), sec in by_scope.items()
                if cls != scopes.UNATTRIBUTED)
    loose = sum(sec for (cls, _), sec in by_scope.items()
                if cls == scopes.UNATTRIBUTED)
    assert named + loose == pytest.approx(sum(own for *_, own in ops) / 1e9)
    assert named > 20 * loose


def test_no_trace_file_no_number(monkeypatch):
    class Cell:
        name = 'no-such-cell'

    class Run:
        cell, patterns = Cell, tr.patterns()
        trace = {'devices': {'/device:TPU:0': []}, 'host': []}
        observed = {'steps': 2}

    assert scopes.xplane_for(Cell) is None
    assert loader.load_module('reducers', 'scope_ms_per_step').read(
        Run, {'scopes': ['lm.mlp']}) is None


# -- four chips, on virtual devices ----------------------------------------

def hlo_proto_of(compiled):
    module = compiled.runtime_executable().hlo_modules()[0]
    return message((1, module.as_serialized_hlo_module_proto()))


def test_every_collective_of_the_four_device_step_has_a_class(tiny_root):
    """``tiny-mpt.train4`` as ``test_run.py`` rehearses it: the step
    compiled for four virtual devices has all-gathers (and their
    reduce-scatters backward) and all-reduces, each under a scope."""
    cell = loader.Cell('tiny-mpt.train4', root=tiny_root)
    trainer = cell.driver().Trainer(cell, 4_000_000_007)
    trainer.init_state()
    compiled = trainer._jit_step.lower(
        trainer.params, trainer.opt_state, trainer.batches[0]).compile()
    assert len(jax.devices()) >= 4
    proto = hlo_proto_of(compiled)
    names, pats = scopes.op_names(proto), scopes.patterns()
    found = {}
    for comp in scopes.computations(proto)[0].values():
        for ins in comp['instructions']:
            if (ins['name'] in names
                    and tr.op_class(ins['opcode'], tr.patterns())
                    == 'collective'):
                found[ins['name']] = scopes.classify(names[ins['name']][0],
                                                     pats)
    assert {cls for cls, _ in found.values()} == {'lm.attn_gather',
                                                  'train.grad_sync'}
    assert {pas for cls, pas in found.values()
            if cls == 'lm.attn_gather'} == {'forward', 'recompute',
                                            'backward'}
