# -*- coding: utf-8 -*-
"""The ``solar_open2`` stack (Solar Open 2): a gated delta-rule (KDA)
mixer — a decay a key channel, a rank-one correction of the state by
what it holds for the key, ``β`` in (0, 2) — or a gated NoPE GQA mixer,
then gated experts beside a shared one, in every layer, under an untied
head. The recurrence's two forms against the token-by-token rule, then
the block, the one-period LM and its caches against the plain reference
``benchmarks/reference/solar_open2.py`` at tiny widths, float32, seeded
weights; every gate differs from its neutral value, so that dropping
ONE fails."""

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
from distributed_dot_product_tpu.models.delta import (  # noqa: E402
    GatedDeltaMixer, chunked_delta, delta_step, delta_step_traces,
)
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts, expert_route_traces,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: E402
    TransformerBlock,
)
from distributed_dot_product_tpu.ops import pallas_delta  # noqa: E402
from distributed_dot_product_tpu.ops.pallas_delta import (  # noqa: E402
    heads_tile,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh  # noqa: E402

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_solar')
CELL = loader.Cell('tiny-solar.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
REF.ROW_BLOCK = 8
TOL = 5e-5          # float32 on both sides; logits are O(1), up to 3
STATES = ['DecodeCache'] + 3 * ['StateCache']


# -- (a) the recurrence: the step and the chunked form --------------------

def _literal(q, k, v, log_a, beta, state):
    """The rule as the issue writes it, one token and one head at a
    time, in float64 numpy: ``S' = Diag(α) S``, ``S = S' + β k (v −
    S'ᵀ k)ᵀ``, ``o = Sᵀ q``. ``(T, H, d)`` operands, ``state (H, d_k,
    d_v)``."""
    q, k, v, log_a, beta, state = (np.asarray(x, np.float64) for x in (
        q, k, v, log_a, beta, state))
    state, out = state.copy(), np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            decayed = np.exp(log_a[t, h])[:, None] * state[h]
            held = decayed.T @ k[t, h]
            state[h] = decayed + beta[t, h] * np.outer(
                k[t, h], v[t, h] - held)
            out[t, h] = state[h].T @ q[t, h]
    return out, state


def _operands(t, heads=3, d_k=8, d_v=6, rate=0.3, seed=0):
    """Unit keys, β on both sides of 1 (up to 1.9: a negative
    eigenvalue), a decay of its own in every key channel, a non-zero
    state."""
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, t, heads, d_k))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(t, heads, d_v))
    log_a = -rng.uniform(0.01, rate, size=(t, heads, d_k))
    beta = rng.uniform(0.1, 1.9, size=(t, heads))
    state = rng.normal(size=(heads, d_k, d_v))
    return [jnp.asarray(x, jnp.float32)
            for x in (q, k, v, log_a, beta, state)]


@pytest.mark.parametrize('form', ['xla', 'pallas', 'pallas-two-blocks'])
def test_the_step_is_the_literal_rule(form, monkeypatch):
    """Six tokens, one step each, in both forms (the kernel under the
    interpreter: all sixteen heads a grid step, and eight of them where
    a step's state block holds no more)."""
    q, k, v, log_a, beta, state = _operands(6, heads=16)
    assert float(beta.max()) > 1.5
    want, last = _literal(q, k, v, log_a, beta, state)
    if form == 'pallas-two-blocks':
        monkeypatch.setattr(pallas_delta, '_STATE_BLOCK_BYTES',
                            8 * 8 * 6 * 4)
        assert heads_tile(16, 8, 6) == 8
    s, got = state[None], []
    for t in range(6):
        o, s = delta_step(q[None, t], k[None, t], v[None, t],
                          log_a[None, t], beta[None, t], s,
                          impl=form.split('-')[0])
        got.append(o[0])
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)
    np.testing.assert_allclose(s[0], last, atol=TOL)


@pytest.mark.parametrize('dropped', ['decay', 'channel-decay', 'beta',
                                     'beta-scale', 'held'])
def test_a_dropped_part_of_the_step_shows(dropped):
    """No decay, ONE decay a head in place of one a key channel, β = 1,
    β without its factor 2, no correction by what the state holds: each
    is another result."""
    q, k, v, log_a, beta, state = _operands(6)
    want, _ = _literal(q, k, v, log_a, beta, state)
    if dropped == 'decay':
        log_a = jnp.zeros_like(log_a)
    elif dropped == 'channel-decay':
        log_a = jnp.broadcast_to(log_a.mean(-1, keepdims=True), log_a.shape)
    elif dropped == 'beta':
        beta = jnp.ones_like(beta)
    elif dropped == 'beta-scale':
        beta = beta / 2
    if dropped == 'held':         # plain linear attention: S += β k vᵀ
        got, s = [], np.asarray(state, np.float64)
        for t in range(6):
            s = (np.exp(np.asarray(log_a[t]))[:, :, None] * s
                 + np.asarray(beta[t])[:, None, None] * np.einsum(
                     'hk,hv->hkv', k[t], v[t]))
            got.append(np.einsum('hkv,hk->hv', s, q[t]))
        got = np.stack(got)
    else:
        got, _ = _literal(q, k, v, log_a, beta, state)
    assert np.max(np.abs(got - want)) > 1000 * TOL


def test_the_kernel_takes_whole_sublane_tiles_of_heads():
    assert heads_tile(64, 128, 128) == 16      # 1 MiB of state a step
    assert heads_tile(64, 64, 64) == 64
    assert heads_tile(4, 8, 8) == 4            # all of them
    assert heads_tile(24, 128, 128) == 8 and heads_tile(12, 128, 128) == 12
    with pytest.raises(ValueError, match='float32'):
        q, k, v, log_a, beta, state = _operands(1)
        pallas_delta.delta_step(q, k, v, log_a, beta[0][None],
                                state[None].astype(jnp.bfloat16))


@pytest.mark.parametrize('chunk, length, rate', [
    (64, 150, 0.3), (64, 64, 0.3), (8, 21, 0.3), (64, 100, 6.0)],
    ids=['splits-64', 'whole-64', 'splits-8', 'strong-decay'])
def test_the_chunked_form_is_the_token_by_token_rule(chunk, length, rate):
    """The chunked form from a NON-ZERO state over a length that splits
    a chunk (and one that fills it), then six single steps, against the
    literal rule. ``strong-decay``: the cumulative log-decay passes −88
    inside one chunk, where ``exp`` of it underflows float32 and its
    reciprocal overflows."""
    t = length + 6
    q, k, v, log_a, beta, state = _operands(t, rate=rate)
    want, last = _literal(q, k, v, log_a, beta, state)
    o, s = jax.jit(chunked_delta, static_argnums=6)(
        q[None, :length], k[None, :length], v[None, :length],
        log_a[None, :length], beta[None, :length], state[None], chunk)
    got = [o[0]]
    for i in range(length, t):
        o, s = delta_step(q[None, i], k[None, i], v[None, i],
                          log_a[None, i], beta[None, i], s, impl='xla')
        got.append(o)
    np.testing.assert_allclose(np.concatenate(got), want, atol=5e-5)
    np.testing.assert_allclose(s[0], last, atol=5e-5)


def test_a_quotient_by_the_cumulative_decay_fails_where_differences_hold():
    """The same chunk written with ``k / exp(cum)`` — the form the
    scalar-decay recurrences use — is not finite at the strong decay
    that :func:`chunked_delta`'s differences pass (the case above)."""
    q, k, v, log_a, beta, state = _operands(64, rate=6.0)
    cum = jnp.cumsum(log_a, axis=0)
    assert float(cum.min()) < -88.0
    quotient = jnp.einsum('thc,ihc->hti', k * jnp.exp(cum),
                          k / jnp.exp(cum))
    assert not bool(jnp.all(jnp.isfinite(quotient)))
    o, s = chunked_delta(q[None], k[None], v[None], log_a[None],
                         beta[None], state[None], 64)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))


# -- (b) the mixer: three entries over one set of parameters --------------

def test_the_mixers_three_entries_agree_and_carry_state_and_window():
    """``__call__`` over 29 tokens = ``prefill`` of 13 and 10 (a chunk of
    8 splits both) then six ``decode`` steps, through a cache of
    ``(B, H, d, d)`` float32 and one ``(B, 3, 3 H d)`` window; the step's
    counter names its form."""
    mixer = GatedDeltaMixer(dim=32, heads=4, head_dim=8, chunk=8)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 29, 32)),
                    jnp.float32)
    params = mixer.init(jax.random.key(0), x)
    # non-neutral recurrence parameters, as the driver's init draws them
    rng = np.random.default_rng(3)
    params['params']['A_log'] = jnp.log(jnp.asarray(
        rng.uniform(1, 16, size=4), jnp.float32))
    params['params']['dt_bias'] = jnp.asarray(
        rng.uniform(-4, 0, size=32), jnp.float32)
    want = mixer.apply(params, x)
    cache = mixer.make_cache(2)
    assert cache.state.shape == (2, 4, 8, 8) and cache.conv.shape == (
        2, 3, 96) and cache.state.dtype == jnp.float32
    got = []
    for lo, hi in ((0, 13), (13, 23)):
        cache, out = mixer.apply(params, x[:, lo:hi], cache,
                                 method='prefill')
        got.append(out)
    with delta_step_traces() as forms:
        for i in range(23, 29):
            cache, out = mixer.apply(params, x[:, i:i + 1], cache,
                                     method='decode')
            got.append(out)
    assert forms == 6 * [{'form': 'xla', 'tile': None, 'chunk': 8}]
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, atol=TOL)
    # the kernel's form of the step, through the interpreter
    kernel = mixer.clone(step_impl='pallas')
    with delta_step_traces() as forms:
        _, again = kernel.apply(params, x[:, 28:29], StateCache(
            *jax.tree.map(jnp.copy, tuple(cache))), method='decode')
    assert forms == [{'form': 'pallas', 'tile': 4, 'chunk': 8}]
    with pytest.raises(ValueError, match='step_impl'):
        mixer.clone(step_impl='mosaic').init(jax.random.key(0), x)


# -- (c) the output gate on softmax attention, at every entry -------------

@pytest.fixture(scope='module')
def gated_attention():
    """A causal NoPE GQA module with its output gate, 32 rows of 2
    sessions, and ``(softmax(q·k / sqrt(d)) v ⊙ sigmoid(x Wz)) Wo`` by
    hand in numpy — with the gate and without."""
    module = DistributedDotProductAttn(
        key_dim=32, num_heads=4, num_kv_heads=2, causal=True,
        distributed=False, out_gate=True)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 32, 32)),
                    jnp.float32)
    params = module.init(jax.random.key(0), x, x, x)
    assert set(params['params']) == {'keys', 'queries', 'values', 'gate',
                                     'composition'}
    w = {k: np.asarray(v['kernel'], np.float64)
         for k, v in params['params'].items()}
    xs = np.asarray(x, np.float64)
    q = (xs @ w['keys']).reshape(2, 32, 4, 8)
    k = np.repeat((xs @ w['queries']).reshape(2, 32, 2, 8), 2, axis=2)
    v = np.repeat((xs @ w['values']).reshape(2, 32, 2, 8), 2, axis=2)
    s = np.einsum('bqhd,bkhd->bhqk', q, k) * 8 ** -0.5
    s = np.where(np.tril(np.ones((32, 32), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    heads = np.einsum('bhqk,bkhd->bqhd', p, v).reshape(2, 32, 32)
    gate = 1.0 / (1.0 + np.exp(-(xs @ w['gate'])))
    return (module, params, x, (heads * gate) @ w['composition'],
            heads @ w['composition'])


@pytest.mark.parametrize('gate', [True, False], ids=['gated', 'gate-off'])
@pytest.mark.parametrize('route', [
    'full', 'flash', 'online', 'full-sharded', 'flash-sharded', 'ring',
    'ulysses', 'prefill-decode', 'decode-sharded'])
def test_every_attention_route_reads_the_output_gate(gated_attention,
                                                     route, gate):
    """Gate off: no ``gate`` parameter, and the numbers of the module
    without the field."""
    module, params, x, gated, plain = gated_attention
    want = gated if gate else plain
    if not gate:
        params = {'params': {k: v for k, v in params['params'].items()
                             if k != 'gate'}}
    impl = {'ring': 'online', 'prefill-decode': 'flash',
            'decode-sharded': 'flash'}.get(route, route.split('-')[0])
    module = module.clone(softmax_impl=impl, out_gate=gate)
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
    assert np.max(np.abs(gated - plain)) > 0.05     # the gate is no 1


# -- (d) one block, both mixers, against the reference --------------------

def _block_params(kind, seed=5):
    """One layer's seeded tree out of the driver's table."""
    i = DRIVER.layer_kinds(CFG).index(kind)
    return DRIVER.make(CFG, seed, jnp.float32)['params']['stack'][
        f'block_{i}']


def _block(kind, **over):
    model = DRIVER.build_lm(CFG, distributed=False, softmax_impl='full',
                            **over.pop('attn', {}))
    block = {**model.block_kwargs, **model.layer_kinds[kind]}
    if 'ssm' in over:
        block['ssm_kwargs'] = {**block['ssm_kwargs'], **over.pop('ssm')}
    return TransformerBlock(dim=CFG['hidden_size'],
                            num_heads=CFG['num_attention_heads'],
                            attn_kwargs={**model.attn_kwargs,
                                         'causal': True}, **block)


def _reference_block(kind, lp, x):
    with jax.default_matmul_precision('highest'):
        out = []
        for row in x:
            h = (REF.delta_branch(CFG, lp, row)[0] if kind == 'kda'
                 else REF.attention_branch(CFG, lp, row))
            out.append(REF.experts_branch(CFG, lp, h)[0])
    return np.stack(out)


@pytest.mark.parametrize('kind', ['kda', 'gqa'])
def test_two_branch_block_is_the_reference(kind):
    """``h = x + mixer(RMSNorm_1(x))``, ``y = h + (experts +
    shared)(RMSNorm_2(h))``: the tree holds both norms, the mixer and
    the experts, and the whole-sequence call is the reference's."""
    lp = _block_params(kind)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    assert set(lp) == {'ln1', 'ln2', 'moe',
                       'delta' if kind == 'kda' else 'attn'}
    if kind == 'kda':
        assert set(lp['delta']) == {
            'in_proj', 'conv_kernel', 'decay_up', 'gate_up', 'dt_bias',
            'A_log', 'norm_scale', 'out_proj'}
    want = _reference_block(kind, lp, x)
    np.testing.assert_allclose(_block(kind).apply({'params': lp}, x), want,
                               atol=TOL)


@pytest.mark.parametrize('kind, dropped', [
    ('kda', {'ssm': {'beta_scale': 1.0}}),
    ('gqa', {'attn': {'out_gate': False}})],
    ids=['beta-without-its-2', 'gqa-gate'])
def test_a_dropped_gate_of_a_block_shows(kind, dropped):
    lp = _block_params(kind)
    if kind == 'gqa':
        lp = {**lp, 'attn': {k: v for k, v in lp['attn'].items()
                             if k != 'gate'}}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    want = _reference_block(kind, _block_params(kind), x)
    got = _block(kind, **dropped).apply({'params': lp}, x)
    assert np.max(np.abs(got - want)) > 1000 * TOL


def test_the_reference_is_a_hand_computation_of_the_rule():
    """``reference/solar_open2.recurrence`` on the literal rule (its own
    scan against this file's numpy loop)."""
    q, k, v, log_a, beta, state = _operands(7)
    want, last = _literal(q, k, v, log_a, beta, state)
    with jax.default_matmul_precision('highest'):
        got, s = REF.recurrence(q, k, v, log_a, beta, state)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(s, last, atol=TOL)


# -- (e) the shares of a 320-way layer add up to the layer ----------------

@pytest.mark.parametrize('dense_tokens', [0, None], ids=['sorted', 'hit-list'])
def test_eight_shares_of_a_layer_add_up_to_the_uncut_layer(dense_tokens):
    """320 gated experts over 8 holders of 40, sigmoid top-8 with a
    correction bias and normalised gates, the shared expert counted once
    (holder 0 adds it): the parts add up to the reference's whole layer,
    through the sorted grouped matmuls and through the hit-list
    kernel."""
    dim, hidden, n_exp, k = 16, 10, 320, 8
    cfg = {'num_experts_per_tok': k, 'norm_topk_prob': True,
           'routed_scaling_factor': 1,
           'published': {'n_routed_experts': n_exp}}
    rng = np.random.default_rng(1)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    whole = {'router': draw(dim, n_exp),
             'router_bias': jnp.asarray(rng.normal(size=n_exp) * 0.1,
                                        jnp.float32),
             'w_gate': draw(n_exp, dim, hidden),
             'w_up': draw(n_exp, dim, hidden),
             'w_down': draw(n_exp, hidden, dim),
             'shared': {'gate': {'kernel': draw(dim, hidden)},
                        'up': {'kernel': draw(dim, hidden)},
                        'down': {'kernel': draw(hidden, dim)}}}
    x = jnp.asarray(rng.normal(size=(24, dim)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want, picks, _ = REF.expert_layer(cfg, whole, x)
    total = 0
    for share in range(8):
        lo, hi = 40 * share, 40 * share + 40
        layer = SparseExperts(
            n_experts=n_exp, top_k=k, hidden=hidden,
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


# -- (f) the one-period LM through three states and a slab ----------------

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
    assert picks.shape == (4, 56, CFG['num_experts_per_tok'])
    model = DRIVER.build_lm(CFG)
    assert model.layer_pattern == ('gqa', 'kda', 'kda', 'kda')
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
    assert caches[1].state.dtype == jnp.bfloat16
    caches, head = model.apply(params, tokens[:, :40], caches,
                               method='prefill')
    _, rest = _serve(model, params, caches, tokens, 16)
    got = np.concatenate([head[0], rest[0]])
    assert np.max(np.abs(got[:40] - want[:40])) < TOL    # one call: float32
    assert np.max(np.abs(got[40:] - want[40:])) > 10 * TOL


def test_a_request_after_restore_reads_what_the_first_did(tokens, served):
    """The snapshot of all three states and windows at the prompt's end,
    16 tokens, the states put back and the slab's length set back: the
    same logits bit for bit; with the length alone set back they
    differ."""
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
    restored = restore(after, taken)
    for got, want in zip(restored, taken):
        if want is not None:
            np.testing.assert_array_equal(got.state, want.state)
            np.testing.assert_array_equal(got.conv, want.conv)
    _, again = _serve(model, params, restored, tokens, 16)
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


def test_a_scanned_stack_refuses_a_recurrent_mixer():
    from distributed_dot_product_tpu.models.transformer import (
        TransformerStack,
    )
    stack = TransformerStack(
        dim=16, num_heads=2, scan_layers=True,
        block_kwargs={'mixer': 'delta',
                      'ssm_kwargs': {'heads': 2, 'head_dim': 8}})
    with pytest.raises(ValueError, match='unrolled'):
        stack.init(jax.random.key(0), *3 * [jnp.zeros((1, 4, 16))])
    with pytest.raises(ValueError, match="'delta'"):
        TransformerBlock(dim=16, num_heads=2, mixer='kda').init(
            jax.random.key(0), jnp.zeros((1, 4, 16)))


# -- (g) the configuration file against the catalog's row -----------------

CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


def test_the_configuration_file_states_its_cut():
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           'solar-open2-250b-serve.json')) as f:
        cfg = json.load(f)
    assert cfg['reduced'] == ['num_hidden_layers', 'gqa_layers',
                              'n_routed_experts', 'vocab_size']
    assert set(cfg['reduced_why']) == set(cfg['published']) == set(
        cfg['reduced'])
    assert DRIVER.layer_kinds(cfg) == ['gqa', 'kda', 'kda', 'kda']
    assert cfg['published']['gqa_layers'] == list(range(0, 48, 4))
    widths = dict(
        hidden_size=4096, moe_intermediate_size=1280,
        intermediate_size=10240, num_attention_heads=64, head_dim=128,
        num_key_value_heads=8, num_experts_per_tok=8, n_shared_experts=1,
        routed_scaling_factor=1, rms_norm_eps=1e-5,
        linear_attn_config={'short_conv_kernel_size': 4, 'head_dim': 128,
                            'num_heads': 64, 'num_kv_heads': None})
    assert {k: cfg[k] for k in widths} == widths
    assert cfg['published']['n_routed_experts'] == 320
    assert cfg['experts_held'] == [0, cfg['n_routed_experts']] == [0, 40]
    assert cfg['vocab_size'] * 8 == cfg['published']['vocab_size']
    assert {'assumed', 'departures', 'deployment', 'precision'} <= set(cfg)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entry, = [c for c in bench['configs']
              if c['name'] == 'solar-open2-250b-serve']
    assert entry['reduced'] == cfg['reduced']
    assert entry['source'] == cfg['source']
    cell, = [w for w in bench['workloads']
             if w['config'] == 'solar-open2-250b-serve']
    assert (cell['name'], cell['traffic'], cell['chips']) == (
        'solar-open2-250b.decode-4k', 'decode-4k-x128', 1)
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
    # The arithmetic of the cut: 3.308 B parameters.
    count = sum(int(np.prod(shape))
                for shape, _ in DRIVER.shapes(cfg).values())
    assert abs(count - 3.3084e9) < 1e6
