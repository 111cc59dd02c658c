# -*- coding: utf-8 -*-
"""The ``granitemoehybrid`` block (Granite 4.0-H Small): TWO branches a
layer — a Mamba-2 or an attention mixer, then small gated experts beside
a shared MLP — each residual scaled, the router's gates the softmax of
the picked logits, the embedding, the softmax and the logits scaled,
under a tied head. All against the plain reference
``benchmarks/reference/granitemoehybrid.py`` at tiny widths, float32,
seeded weights; every multiplier differs from its neutral value, so
that dropping ONE fails."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loader  # noqa: E402
from distributed_dot_product_tpu.models.attention import (  # noqa: E402
    DistributedDotProductAttn, apply_seq_parallel, decode_seq_parallel,
)
from distributed_dot_product_tpu.models.decode import (  # noqa: E402
    StateCache, insert_session, restore_states, snapshot_states,
)
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts, expert_route_traces,
)
from distributed_dot_product_tpu.models.ssm import (  # noqa: E402
    chunked_scan, state_step,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: E402
    TransformerBlock,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh  # noqa: E402

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_granite')
CELL = loader.Cell('tiny-granite.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
REF.ROW_BLOCK = 8
TOL = 2e-5          # float32 on both sides; logits are O(1)
STATES = 5 * ['StateCache'] + ['DecodeCache'] + 4 * ['StateCache']


# -- (a) one block, both mixers, against the literal equations ------------

def _block_params(kind, seed=5):
    """One layer's seeded tree out of the driver's table."""
    i = DRIVER.layer_kinds(CFG).index(kind)
    return DRIVER.make(CFG, seed, jnp.float32)['params']['stack'][
        f'block_{i}']


def _block(kind, **over):
    model = DRIVER.build_lm(CFG, distributed=False, softmax_impl='full')
    attn, block = model.attn_kwargs, {**model.block_kwargs,
                                      **model.layer_kinds[kind]}
    attn = {**attn, 'causal': True}
    if 'softmax_scale' in over:
        attn['softmax_scale'] = over.pop('softmax_scale')
    return TransformerBlock(dim=CFG['hidden_size'],
                            num_heads=CFG['num_attention_heads'],
                            attn_kwargs=attn, **{**block, **over})


def _reference_block(kind, lp, x):
    with jax.default_matmul_precision('highest'):
        out = []
        for row in x:
            h = (REF.ssm_branch(CFG, lp, row)[0] if kind == 'mamba'
                 else REF.attention_branch(CFG, lp, row))
            out.append(REF.experts_branch(CFG, lp, h)[0])
    return np.stack(out)


@pytest.mark.parametrize('kind', ['mamba', 'attention'])
def test_two_branch_block_is_the_literal_equations(kind):
    """``h = x + r · mixer(RMSNorm_1(x))``, ``y = h + r · (experts +
    shared)(RMSNorm_2(h))``: the tree holds both norms, the mixer and the
    experts, and the whole-sequence call is the reference's."""
    lp = _block_params(kind)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    block = _block(kind)
    assert set(lp) == {'ln1', 'ln2', 'moe',
                       'ssm' if kind == 'mamba' else 'attn'}
    want = _reference_block(kind, lp, x)
    np.testing.assert_allclose(block.apply({'params': lp}, x), want,
                               atol=TOL)


@pytest.mark.parametrize('kind, dropped', [
    ('mamba', {'residual_scale': 1.0}),
    ('attention', {'residual_scale': 1.0}),
    ('attention', {'softmax_scale': None})],
    ids=['mamba-residual', 'attention-residual', 'attention-softmax'])
def test_a_dropped_block_multiplier_shows(kind, dropped):
    lp = _block_params(kind)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    want = _reference_block(kind, lp, x)
    got = _block(kind, **dropped).apply({'params': lp}, x)
    assert np.max(np.abs(got - want)) > 1000 * TOL


def test_fields_that_mean_nothing_together_are_refused():
    x = jnp.zeros((1, 4, 16))
    for kw in (dict(router_bias=True), dict(router_bias=False, scaling=2.5),
               dict(router_bias=False, norm_topk=False)):
        with pytest.raises(ValueError, match='softmax_picked'):
            SparseExperts(n_experts=4, top_k=2, hidden=8,
                          score='softmax_picked', **kw).init(
                              jax.random.key(0), x)
    with pytest.raises(ValueError, match='score'):
        SparseExperts(n_experts=4, top_k=2, hidden=8,
                      score='softmax').init(jax.random.key(0), x)
    with pytest.raises(ValueError, match='residual_scale'):
        TransformerBlock(dim=16, num_heads=2, residual='hyper',
                         residual_scale=0.5).init(jax.random.key(0), x)


# -- (a') ONE softmax scale for every attention route ----------------------

SCALE = 0.07            # neither 1 nor 8 ** -0.5


@pytest.fixture(scope='module')
def scaled_attention():
    """A causal GQA module with ``softmax_scale`` set, 32 rows of 2
    sessions, and ``softmax(q·k · scale) v`` by hand in numpy."""
    module = DistributedDotProductAttn(
        key_dim=32, num_heads=4, num_kv_heads=2, causal=True,
        distributed=False, softmax_scale=SCALE)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 32, 32)),
                    jnp.float32)
    params = module.init(jax.random.key(0), x, x, x)
    w = {k: np.asarray(v['kernel'], np.float64)
         for k, v in params['params'].items()}
    xs = np.asarray(x, np.float64)
    q = (xs @ w['keys']).reshape(2, 32, 4, 8)
    k = np.repeat((xs @ w['queries']).reshape(2, 32, 2, 8), 2, axis=2)
    v = np.repeat((xs @ w['values']).reshape(2, 32, 2, 8), 2, axis=2)
    s = np.einsum('bqhd,bkhd->bhqk', q, k) * SCALE
    s = np.where(np.tril(np.ones((32, 32), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum('bhqk,bkhd->bqhd', p, v).reshape(2, 32, 32) @ (
        w['composition'])
    return module, params, x, want


@pytest.mark.parametrize('route', [
    'full', 'flash', 'online', 'full-sharded', 'flash-sharded', 'ring',
    'ulysses', 'prefill-decode', 'decode-sharded'])
def test_every_attention_route_reads_the_one_softmax_scale(
        scaled_attention, route):
    module, params, x, want = scaled_attention
    impl = {'ring': 'online', 'prefill-decode': 'flash',
            'decode-sharded': 'flash'}.get(route, route.split('-')[0])
    module = module.clone(softmax_impl=impl)
    if route in ('full', 'flash', 'online'):
        got = module.apply(params, x, x, x)
    elif route == 'prefill-decode':
        cache = module.make_decode_cache(2, 64)
        cache, head = module.apply(params, x[:, :20], x[:, :20], x[:, :20],
                                   cache, method='prefill')
        got = [head]
        for i in range(20, 32):
            cache, out = module.apply(params, x[:, i:i + 1], x[:, i:i + 1],
                                      x[:, i:i + 1], cache, method='decode')
            got.append(out)
        got = jnp.concatenate(got, axis=1)
    elif route == 'decode-sharded':
        mesh = seq_mesh(4)
        cache = module.make_decode_cache(2, 64)
        got = []
        for i in range(32):
            cache, out = decode_seq_parallel(
                module, params, mesh, x[:, i:i + 1], x[:, i:i + 1],
                x[:, i:i + 1], cache)
            got.append(out)
        got = jnp.concatenate(got, axis=1)
    else:
        # (Ulysses splits the 2 KV heads over the mesh: 2 wide)
        got = apply_seq_parallel(module.clone(distributed=True), params,
                                 seq_mesh(2 if route == 'ulysses' else 4),
                                 x, x, x)
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the default scale, head_dim ** -0.5, is another number
    assert abs(SCALE - 8 ** -0.5) > 0.2


# -- (b) the router: the softmax of the picked logits ---------------------

def test_gates_are_the_softmax_of_the_picked_logits():
    """72-wide in the cell, 8-wide here: the pick is the top-3 of the raw
    logits, the gates their softmax (sum one), against the reference's
    ``route``; the layer's result is those gates on the experts."""
    lp = _block_params('mamba')['moe']
    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    k, wide = CFG['num_experts_per_tok'], 8
    with jax.default_matmul_precision('highest'):
        gates, own, regret = REF.route(CFG, lp, v)
        logits = np.asarray(v @ lp['router'])
    assert gates.shape == (24, wide) and not np.any(regret)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    top = np.sort(logits, -1)[:, -k:]
    soft = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(
        np.sort(np.asarray(gates), -1)[:, -k:],
        soft / soft.sum(-1, keepdims=True), atol=1e-6)
    assert np.count_nonzero(gates) == 24 * k
    layer = SparseExperts(
        n_experts=wide, top_k=k, hidden=CFG['intermediate_size'],
        shared_hidden=CFG['shared_intermediate_size'], router_bias=False,
        score='softmax_picked', experts_held=(0, 4))
    (_, counts), sown = layer.apply({'params': lp}, v,
                                    mutable=['counters'])
    np.testing.assert_array_equal(
        np.sort(sown['counters']['expert_picks'], -1), np.sort(own, -1))
    assert int(counts.sum()) == 24 * k


# -- (c) the shares of a layer add up to the layer ------------------------

@pytest.mark.parametrize('dense_tokens', [0, None], ids=['sorted', 'hit-list'])
def test_four_shares_of_a_layer_add_up_to_the_uncut_layer(dense_tokens):
    """16 gated experts over 4 holders of 4, top-6 by the softmax of the
    picked logits, the shared MLP counted once (holder 0 adds it): the
    parts add up to the reference's whole layer, through the sorted
    grouped matmuls and through the hit-list kernel."""
    dim, hidden, shared, n_exp, k = 16, 10, 20, 16, 6
    cfg = {'num_experts_per_tok': k,
           'published': {'num_local_experts': n_exp}}
    rng = np.random.default_rng(1)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    whole = {'router': draw(dim, n_exp),
             'w_gate': draw(n_exp, dim, hidden),
             'w_up': draw(n_exp, dim, hidden),
             'w_down': draw(n_exp, hidden, dim),
             'shared': {'gate': {'kernel': draw(dim, shared)},
                        'up': {'kernel': draw(dim, shared)},
                        'down': {'kernel': draw(shared, dim)}}}
    x = jnp.asarray(rng.normal(size=(24, dim)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want, picks, _ = REF.expert_layer(cfg, whole, x)
    total = 0
    for share in range(4):
        lo, hi = 4 * share, 4 * share + 4
        layer = SparseExperts(
            n_experts=n_exp, top_k=k, hidden=hidden, shared_hidden=shared,
            router_bias=False, score='softmax_picked',
            experts_held=(lo, hi), add_shared=share == 0,
            dense_tokens=dense_tokens)
        mine = {**whole, **{name: whole[name][lo:hi]
                            for name in ('w_gate', 'w_up', 'w_down')}}
        if share:
            del mine['shared']
        with expert_route_traces() as routes:
            (y, counts), sown = layer.apply({'params': mine}, x,
                                            mutable=['counters'])
        assert routes[0]['route'] == (
            'sorted' if dense_tokens == 0 else 'hit_list')
        np.testing.assert_array_equal(
            np.sort(sown['counters']['expert_picks'], -1),
            np.sort(picks, -1))
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL)


# -- (d) the one-period LM through nine states and a slab -----------------

@pytest.fixture(scope='module')
def tokens():
    return np.random.default_rng(0).integers(
        0, CFG['vocab_size'], size=(3, 56)).astype(np.int32)


@pytest.fixture(scope='module')
def served(tokens):
    """Weights, the reference's logits of session 0, and a 3-session
    batch prefilled together in chunks of 13, 20 and 7 tokens."""
    params = DRIVER.make(CFG, 7, jnp.float32)
    want, picks, _, _ = REF.logits_at(CFG, params, jnp.asarray(tokens[0]),
                                      56)
    assert picks.shape == (10, 56, CFG['num_experts_per_tok'])
    model = DRIVER.build_lm(CFG)
    caches = model.make_decode_caches(3, 64)
    assert [type(c).__name__ for c in caches] == STATES
    logits = []
    for i, n in ((0, 13), (13, 20), (33, 7)):
        caches, out = model.apply(params, tokens[:, i:i + n], caches,
                                  method='prefill')
        logits.append(out)
    return model, params, np.asarray(want), caches, np.concatenate(
        logits, axis=1)


def _serve(model, params, caches, tokens, n):
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'))
    out = []
    for i in range(40, 40 + n):
        caches, logits = step(params, tokens[:, i:i + 1], caches)
        out.append(logits)
    return caches, np.concatenate(out, axis=1)


def test_full_forward_matches_the_reference(tokens, served):
    _, params, want, _, _ = served
    model = DRIVER.build_lm(CFG, distributed=False)
    np.testing.assert_allclose(model.apply(params, tokens[:1])[0], want,
                               atol=TOL)


@pytest.mark.parametrize('field, neutral', [
    ('embed_scale', 1.0), ('logit_scale', 1.0)])
def test_a_dropped_lm_multiplier_shows(tokens, served, field, neutral):
    _, params, want, _, _ = served
    model = DRIVER.build_lm(CFG, distributed=False).clone(
        **{field: neutral})
    got = model.apply(params, tokens[:1])[0]
    assert np.max(np.abs(got - want)) > 1000 * TOL


def test_prefill_and_decode_match_the_reference(tokens, served):
    model, params, want, caches, prefilled = served
    _, first = _serve(model, params, caches, tokens, 16)
    np.testing.assert_allclose(
        np.concatenate([prefilled[0], first[0]]), want, atol=TOL)


def test_a_state_kept_in_bfloat16_shows(tokens, served):
    """The comparison is tight enough that a recurrent state stored
    below float32 fails it, everything else as it was."""
    _, params, want, _, _ = served
    lower = {**CFG, 'precision': {**CFG['precision'], 'state': 'bfloat16'}}
    model = DRIVER.build_lm(lower)
    caches = model.make_decode_caches(3, 64)
    assert caches[0].state.dtype == jnp.bfloat16
    caches, head = model.apply(params, tokens[:, :40], caches,
                               method='prefill')
    _, rest = _serve(model, params, caches, tokens, 16)
    got = np.concatenate([head[0], rest[0]])
    assert np.max(np.abs(got[:40] - want[:40])) < TOL    # one chunk: float32
    assert np.max(np.abs(got[40:] - want[40:])) > 10 * TOL


def test_a_request_after_restore_reads_what_the_first_did(tokens, served):
    """The snapshot of all nine states at the prompt's end, 16 tokens,
    the states put back and the slab's length set back: the same logits
    bit for bit; with the length alone set back they differ."""
    model, params, _, caches, _ = served
    taken = snapshot_states(caches)
    assert [type(s).__name__ for s in taken] == [
        name if name == 'StateCache' else 'NoneType' for name in STATES]
    after, first = _serve(model, params, caches, tokens, 16)

    def rewind(layers):
        return [c._replace(length=jnp.asarray(40, jnp.int32))
                if hasattr(c, 'length') else c for c in layers]
    restore = jax.jit(lambda c, s: rewind(restore_states(c, s)),
                      donate_argnums=(0,))
    _, stale = _serve(model, params, rewind(after), tokens, 16)
    assert np.max(np.abs(stale - first)) > 100 * TOL
    _, again = _serve(model, params, restore(after, taken), tokens, 16)
    np.testing.assert_array_equal(again, first)
    assert all(not s.state.is_deleted() for s in taken if s is not None)


def test_sessions_prefilled_alone_and_inserted_equal_the_batch(tokens,
                                                               served):
    model, params, _, together, _ = served
    batch = model.make_decode_caches(3, 64)
    for s in range(3):
        one = model.make_decode_caches(1, 64)
        for i, n in ((0, 13), (13, 20), (33, 7)):
            one, _ = model.apply(params, tokens[s:s + 1, i:i + n], one,
                                 method='prefill')
        batch = [insert_session(c, s, o) for c, o in zip(batch, one)]
    for got, want in zip(batch, together):
        assert type(got) is type(want)
        if isinstance(want, StateCache):
            np.testing.assert_allclose(got.state, want.state, atol=TOL)
            np.testing.assert_allclose(got.conv, want.conv, atol=TOL)
        else:
            assert int(got.length) == int(want.length) == 40
            np.testing.assert_allclose(got.k, want.k, atol=TOL)
            np.testing.assert_allclose(got.v, want.v, atol=TOL)


def test_the_drivers_shape_table_is_the_models_tree():
    model = DRIVER.build_lm(CFG)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))['params']
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {path: shape for path, (shape, _) in
                    DRIVER.shapes(CFG).items()}


# -- (e) ONE B / C group for every head -----------------------------------

@pytest.mark.parametrize('chunk, length', [(256, 300), (256, 256), (8, 21)],
                         ids=['splits-256', 'whole-256', 'splits-8'])
def test_one_group_scan_and_step_are_the_token_by_token_recurrence(
        chunk, length):
    """``groups=1``: B and C shared by all heads, at the cell's chunk of
    256 (a sequence that splits it and one that fills it) — the chunked
    form, then six single steps, against the reference's literal scan."""
    heads, p, n = 8, 4, 8
    cfg = {'mamba_n_heads': heads, 'mamba_d_head': p, 'mamba_n_groups': 1,
           'mamba_d_state': n}
    rng = np.random.default_rng(3)
    t = length + 6
    x = jnp.asarray(rng.normal(size=(t, heads, p)), jnp.float32)
    b, c = (jnp.asarray(u, jnp.float32)
            for u in rng.normal(size=(2, t, 1, n)))
    dt = jnp.asarray(np.exp(rng.uniform(-5, -1, size=(t, heads))),
                     jnp.float32)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, heads))
    sp = {'A_log': a_log, 'D': jnp.zeros((heads,))}
    with jax.default_matmul_precision('highest'):
        want, last = REF.recurrence(cfg, sp, x, b, c, dt,
                                    jnp.zeros((heads, p, n)))
    log_a = -dt * jnp.exp(a_log)
    y, state = chunked_scan(x[None, :length], dt[None, :length],
                            log_a[None, :length], b[None, :length],
                            c[None, :length],
                            jnp.zeros((1, heads, p, n)), chunk)
    got = [y[0]]
    for i in range(length, t):
        y, state = state_step(x[None, i], dt[None, i], log_a[None, i],
                              b[None, i], c[None, i], state)
        got.append(y)
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-4)
    np.testing.assert_allclose(state[0], last, atol=1e-4)


# -- (f) the configuration file against the catalog's row -----------------

CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def test_the_configuration_file_states_its_cut():
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           'granite-4.0-h-small-serve.json')) as f:
        cfg = json.load(f)
    assert cfg['reduced'] == ['num_hidden_layers', 'layer_types',
                              'num_local_experts', 'vocab_size']
    assert set(cfg['reduced_why']) == set(cfg['published']) == set(
        cfg['reduced'])
    period = cfg['layer_types']
    assert period == 5 * ['mamba'] + ['attention'] + 4 * ['mamba']
    assert cfg['published']['layer_types'] == 4 * period
    widths = dict(
        hidden_size=4096, intermediate_size=768,
        shared_intermediate_size=1536, num_attention_heads=32,
        num_key_value_heads=8, num_experts_per_tok=10, mamba_n_heads=128,
        mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
        mamba_d_conv=4, mamba_chunk_size=256, mamba_expand=2,
        attention_multiplier=0.0078125, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg['published']['num_local_experts'] == 72
    assert cfg['experts_held'] == [0, cfg['num_local_experts']] == [0, 18]
    assert cfg['vocab_size'] * 4 == cfg['published']['vocab_size']
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        entry, = [c for c in json.load(f)['configs']
                  if c['name'] == 'granite-4.0-h-small-serve']
    assert entry['reduced'] == cfg['reduced']
    assert entry['source'] == cfg['source']
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
    for row in rows:
        if row['source_url'] == cfg['source']:
            differ = {k for k, v in row['config'].items()
                      if k not in cfg or cfg[k] != v}
            assert differ == set(cfg['reduced'])
            assert {k: cfg['published'][k] for k in differ} == {
                k: row['config'][k] for k in differ}
    # The arithmetic of the cut: 2.956 B parameters.
    count = sum(int(np.prod(shape))
                for shape, _ in DRIVER.shapes(cfg).values())
    assert abs(count - 2.956e9) < 1e6
