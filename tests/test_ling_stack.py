# -*- coding: utf-8 -*-
"""The ``bailing_hybrid`` stack (Ling 3.0 flash): gated delta-rule (KDA)
layers with FULL-RANK gates and a BOUNDED decay beside ONE latent
(MLA) layer with no query rank and a head-wise output gate, in one
stack — a ``LatentCache`` beside ``StateCache``s — and experts under
GROUP-LIMITED routing held as one routing group. Each new switch against
a literal rule, then the one-period LM and its caches against the plain
reference ``benchmarks/reference/ling3.py`` at tiny widths, float32,
seeded weights; every gate differs from its neutral value, so that
dropping ONE fails."""

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
from distributed_dot_product_tpu.models.decode import (  # noqa: E402
    LatentCache, StateCache, decode_impl_traces, insert_session,
    restore_states, snapshot_states,
)
from distributed_dot_product_tpu.models.delta import (  # noqa: E402
    GatedDeltaMixer,
)
from distributed_dot_product_tpu.models.latent import (  # noqa: E402
    LatentAttention, init_latent_cache,
)
from distributed_dot_product_tpu.models import moe as moe_model  # noqa: E402
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts, expert_route_traces,
)

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_ling')
CELL = loader.Cell('tiny-ling.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
REF.ROW_BLOCK = 8
# float32 on both sides; logits are O(1), up to 3: what is left is the
# order of float32 sums (the chunked form against token by token, the
# absorbed form against the expanded one).
TOL = 5e-5
KINDS = ['D', 'K', 'A', 'K']
CACHES = ['StateCache', 'StateCache', 'LatentCache', 'StateCache']


# -- (a) the latent mixer's two switches --------------------------------------

LATENT = dict(dim=32, num_heads=4, q_rank=None, kv_rank=16, nope_dim=8,
              rope_dim=4, v_dim=8, rope_theta=6e6, out_gate='head')


def _latent(seed=0, **over):
    layer = LatentAttention(**{**LATENT, **over})
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(2, 12, 32)),
                    jnp.float32)
    return layer, x, layer.init(jax.random.key(seed), x)


def test_no_query_rank_is_one_matrix_and_the_gate_a_head_wide_one():
    _, _, params = _latent()
    assert {k: jax.tree.map(jnp.shape, v)
            for k, v in params['params'].items()} == {
        'q': {'kernel': (32, 48)}, 'gate': {'kernel': (32, 4)},
        'kv_a': {'kernel': (32, 20)}, 'kv_norm': {'scale': (16,)},
        'kv_b': (16, 4, 16), 'out': {'kernel': (32, 32)}}
    with pytest.raises(ValueError, match='out_gate'):
        _latent(out_gate='channel')


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
@pytest.mark.parametrize('cache', ['one-layer', 'stacked'])
def test_the_absorbed_form_is_the_expanded_form(cache, impl):
    """Prefill of 7 rows then 5 decode steps — the gate applied AFTER
    ``W_kvb``'s V half in the absorbed form — against the whole-sequence
    expanded form, over one layer's buffer and over layer 1 of a stacked
    one (whose other layers keep their bits)."""
    layer, x, params = _latent(decode_impl=impl)
    want = layer.apply(params, x)
    layers, at = (None, None) if cache == 'one-layer' else (3, 1)
    held = init_latent_cache(layers, 2, 128, 20, jnp.float32)
    # time-minor, nothing padded: 16 + 4 values a token, a column each
    assert held.rows.shape == ((2, 20, 128) if layers is None
                               else (3, 2, 20, 128))
    assert held.t_max == 128
    with decode_impl_traces() as traces:
        held, out = layer.apply(params, x[:, :7], held, at,
                                method='prefill')
        got = [out]
        for t in range(7, 12):
            held, out = layer.apply(params, x[:, t:t + 1], held, at,
                                    method='decode')
            got.append(out)
    assert {(t['resolved'], t['cache']) for t in traces} == {
        (impl, 'latent' if layers is None else 'stacked')}
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=TOL)
    if layers is None:
        np.testing.assert_array_equal(held.length, [12, 12])
    else:
        np.testing.assert_array_equal(held.length,
                                      [[0, 0], [12, 12], [0, 0]])
        assert not np.any(np.asarray(held.rows[0]))
        assert not np.any(np.asarray(held.rows[2]))


# Columns a session holds before the step (its token lands there) on a
# buffer of two 1536-column splits (what the kernel's own rule takes of
# 3072 columns): inside the first split; the last column of a split; 1 /
# 128 / 129 / 256 / 257 / 512 / 513 columns into the last split once the
# token is in (the first piece's first column, a lane tile's last column
# and the next one's first — the append on a tile's edge —, a piece's
# last column and the next one's first, the same for a sub-block of 512);
# the buffer's last column.
_ROUTES = {'first-split': 300, 'split-end': 1535, 'into-1': 1536,
           'into-128': 1536 + 127, 'into-129': 1536 + 128,
           'into-256': 1536 + 255, 'into-257': 1536 + 256,
           'into-512': 1536 + 511, 'into-513': 1536 + 512,
           'buffer-end': 3071}


@pytest.mark.parametrize('route', sorted(_ROUTES))
@pytest.mark.parametrize('cache', ['one-layer', 'stacked'])
def test_the_latent_kernel_is_the_xla_form_on_every_route(cache, route):
    """One token step of the layer through the kernel (interpret mode)
    against the XLA form of ``LatentAttention.decode`` on the SAME
    time-minor buffer — random columns everywhere, so every length is a
    rewound one — with the session under test at a length that takes
    each route of the kernel, a session inside its first split, and a
    session whose last split is one piece: outputs to tolerance, the
    buffers and lengths bit for bit (the other layers' and every other
    column untouched). Then a
    slot that appends nothing: the kernel itself at the same operands,
    the buffer returned as it came."""
    from distributed_dot_product_tpu.ops.pallas_decode import flash_decode
    layers, at = (None, None) if cache == 'one-layer' else (3, 1)
    lead = () if layers is None else (layers,)
    lens = jnp.asarray([_ROUTES[route], 300, 1536 + 76], jnp.int32)
    rng = np.random.default_rng(3)
    held = LatentCache(
        rows=jnp.asarray(rng.normal(size=(*lead, 3, 20, 3072)),
                         jnp.float32),
        length=jnp.broadcast_to(lens, (*lead, 3)))
    x = jnp.asarray(rng.normal(size=(3, 1, 32)), jnp.float32)
    got = {}
    for impl in ('xla', 'kernel'):
        layer, _, params = _latent(decode_impl=impl)
        got[impl] = layer.apply(params, x, held, at, method='decode')
    np.testing.assert_allclose(got['kernel'][1], got['xla'][1], atol=TOL)
    np.testing.assert_array_equal(got['kernel'][0].rows,
                                  got['xla'][0].rows)
    np.testing.assert_array_equal(got['kernel'][0].length,
                                  got['xla'][0].length)
    assert np.sum(np.asarray(got['kernel'][0].rows != held.rows)) == 3 * 20
    # nothing appended (append_at −1): nothing written
    rows5 = held.rows[..., None, :, :]
    tile = jnp.ones((3, 1, 20, 128), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 4, 1, 20)), jnp.float32)
    _, same, *_ = flash_decode(
        q, tile, None, rows5, None, lens - 1, jnp.full((3,), -1),
        layer=at, latent_v=16, interpret=True)
    np.testing.assert_array_equal(same, rows5)


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
@pytest.mark.parametrize('cache', ['one-layer', 'stacked'])
def test_a_session_prefilled_alone_in_odd_chunks_decodes_in_its_slot(
        cache, impl):
    """A session prefilled ALONE into a one-session cache in chunks of
    5, 1, 130 and 7 tokens — starts 0, 5, 6, 136: no lane-tile multiple
    behind the first, one chunk across a tile's edge — then put in slot
    1 of a batch of three (``insert_session``) whose other slots hold
    another session, and decoded for four steps: the whole-sequence
    expanded form's outputs, token for token, for both."""
    layer, _, params = _latent(decode_impl=impl)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 147, 32)), jnp.float32)
    want = layer.apply(params, x)
    layers, at = (None, None) if cache == 'one-layer' else (3, 1)

    def alone(i):
        one = init_latent_cache(layers, 1, 256, 20, jnp.float32)
        start = 0
        for n in (5, 1, 130, 7):
            one, out = layer.apply(params, x[i:i + 1, start:start + n],
                                   one, at, method='prefill')
            np.testing.assert_allclose(
                out, want[i:i + 1, start:start + n], atol=TOL)
            start += n
        return one
    batch = init_latent_cache(layers, 3, 256, 20, jnp.float32)
    put = jax.jit(insert_session)
    for slot, i in enumerate((0, 1, 0)):
        batch = put(batch, slot, alone(i))
    for t in range(143, 147):
        step = x[jnp.asarray([0, 1, 0]), t:t + 1]
        batch, out = layer.apply(params, step, batch, at, method='decode')
        np.testing.assert_allclose(out[:, 0], want[jnp.asarray([0, 1, 0]), t],
                                   atol=TOL)
    np.testing.assert_array_equal(
        batch.length[at] if layers else batch.length, [147, 147, 147])


@pytest.mark.parametrize('dropped', ['gate', 'gate-before-values'])
def test_a_dropped_gate_shows(dropped):
    """No gate, and the gate on the latent context BEFORE ``W_kvb``'s V
    half mixes the heads... which it cannot: a head's gate is a scalar,
    so before or after the V half is the same product — what differs is
    a gate shared by the heads."""
    layer, x, params = _latent()
    want = layer.apply(params, x)
    p = jax.tree.map(lambda a: a, params)
    kernel = p['params']['gate']['kernel']
    if dropped == 'gate':
        # sigmoid(0) = 1/2 for every head: a constant, not the gate
        p['params']['gate'] = {'kernel': jnp.zeros_like(kernel)}
    else:
        p['params']['gate'] = {'kernel': jnp.broadcast_to(
            kernel[:, :1], kernel.shape)}
    assert np.max(np.abs(layer.apply(p, x) - want)) > 100 * TOL


# -- (b) the delta mixer's two switches ---------------------------------------

DELTA = dict(dim=32, heads=4, head_dim=8, chunk=4, beta_scale=1.0,
             gate_rank=None, decay='bounded', decay_lower_bound=-5.0,
             norm_eps=1e-6)


def _delta(seed=1, **over):
    mixer = GatedDeltaMixer(**{**DELTA, **over})
    h = jnp.asarray(np.random.default_rng(seed).normal(size=(2, 11, 32)),
                    jnp.float32)
    params = mixer.init(jax.random.key(seed), h)
    # A_log and dt_bias away from zero: the decay is no constant
    rng = np.random.default_rng(seed + 1)
    params['params']['A_log'] = jnp.asarray(rng.uniform(0, 0.7, 4),
                                            jnp.float32)
    params['params']['dt_bias'] = jnp.asarray(rng.uniform(-4, -1, 32),
                                              jnp.float32)
    return mixer, h, params


def test_full_rank_gates_sit_in_the_input_projection():
    _, _, params = _delta()
    assert jax.tree.map(jnp.shape, params['params']) == {
        'in_proj': {'kernel': (32, 3 * 32 + 2 * 32 + 4)},
        'out_proj': {'kernel': (32, 32)}, 'conv_kernel': (4, 96),
        'dt_bias': (32,), 'A_log': (4,), 'norm_scale': (8,)}
    _, _, low = _delta(gate_rank='head_dim')
    assert low['params']['in_proj']['kernel'].shape == (32, 96 + 16 + 4)
    assert low['params']['decay_up']['kernel'].shape == (8, 32)
    _, _, ranked = _delta(gate_rank=5)
    assert ranked['params']['gate_up']['kernel'].shape == (5, 32)
    with pytest.raises(ValueError, match='decay'):
        _delta(decay='clipped')


def _literal_mixer(p, h, lower):
    """The mixer as ISSUE 46 writes it, one token and one head at a
    time, float64 numpy: full-rank gates, ``g = lower · sigmoid(exp(A) ·
    (f + dt_bias))``, ``β = sigmoid(b)``."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p['params'])
    h = np.asarray(h, np.float64)
    heads, d, inner = 4, 8, 32

    def sigmoid(x):
        return 1.0 / (1.0 + np.exp(-x))
    out = np.zeros_like(h)
    for b in range(h.shape[0]):
        u = h[b] @ p['in_proj']['kernel']
        qkv, f, z, beta = np.split(u, [3 * inner, 4 * inner, 5 * inner], -1)
        seen = np.concatenate([np.zeros((3, 3 * inner)), qkv])
        conv = sum(p['conv_kernel'][j] * seen[j:j + len(qkv)]
                   for j in range(4))
        conv = conv * sigmoid(conv)                            # SiLU
        q, k, v = (x.reshape(-1, heads, d) for x in np.split(conv, 3, -1))
        q = q / np.sqrt((q ** 2).sum(-1, keepdims=True) + 1e-6) / np.sqrt(d)
        k = k / np.sqrt((k ** 2).sum(-1, keepdims=True) + 1e-6)
        g = lower * sigmoid(np.exp(p['A_log'])[:, None] * (
            f + p['dt_bias']).reshape(-1, heads, d))
        assert np.all(g > lower) and np.all(g < 0)
        state = np.zeros((heads, d, d))
        o = np.zeros((len(qkv), heads, d))
        for t in range(len(qkv)):
            for a in range(heads):
                decayed = np.exp(g[t, a])[:, None] * state[a]
                state[a] = decayed + sigmoid(beta[t, a]) * np.outer(
                    k[t, a], v[t, a] - decayed.T @ k[t, a])
                o[t, a] = state[a].T @ q[t, a]
        o = o / np.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-6)
        o = (o * p['norm_scale']).reshape(-1, inner) * sigmoid(z)
        out[b] = o @ p['out_proj']['kernel']
    return out


@pytest.mark.parametrize('form', ['chunked', 'steps-xla', 'steps-pallas'])
def test_the_bounded_full_rank_mixer_is_the_literal_rule(form):
    """The whole sequence in chunks of 4 (11 tokens: a ragged last
    chunk), and a prefill of 5 then six single steps in both forms of
    the step, against the token-by-token rule."""
    mixer, h, params = _delta(step_impl=form.split('-')[-1]
                              if form != 'chunked' else None)
    want = _literal_mixer(params, h, -5.0)
    if form == 'chunked':
        got = mixer.apply(params, h)
    else:
        cache = mixer.make_cache(2, jnp.float32)
        cache, out = mixer.apply(params, h[:, :5], cache, method='prefill')
        got = [out]
        for t in range(5, 11):
            cache, out = mixer.apply(params, h[:, t:t + 1], cache,
                                     method='decode')
            got.append(out)
        got = jnp.concatenate(got, 1)
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize('other', [dict(decay='softplus'),
                                   dict(decay_lower_bound=-1.0),
                                   dict(beta_scale=2.0)])
def test_another_decay_or_rate_is_another_result(other):
    mixer, h, params = _delta()
    want = mixer.apply(params, h)
    got = GatedDeltaMixer(**{**DELTA, **other}).apply(params, h)
    assert np.max(np.abs(got - want)) > 100 * TOL


# -- (c) group-limited routing ------------------------------------------------

def _numpy_route(scores, bias, k, n_group, topk_group):
    """The rule, plainly: groups of consecutive experts, a group's score
    the sum of its two best biased scores, the best ``topk_group``
    groups, the top-k of the biased scores inside them; a tie goes to
    the lower index at both choices. Returns the picks ``(n, k)`` and
    the kept groups ``(n, topk_group)``."""
    biased = scores + bias
    n, e = biased.shape
    size = e // n_group
    picks, groups = [], []
    for row in biased:
        part = row.reshape(n_group, size)
        score = np.sort(part, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind='stable')[:topk_group]
        masked = np.full(e, -np.inf)
        for g in kept:
            masked[g * size:(g + 1) * size] = row[g * size:(g + 1) * size]
        picks.append(np.argsort(-masked, kind='stable')[:k])
        groups.append(kept)
    return np.asarray(picks), np.asarray(groups)


GROUPED = dict(n_experts=16, top_k=3, hidden=12, scaling=2.5, n_group=4,
               topk_group=2)


def _grouped_layer(held=None, tokens=24, seed=3, **over):
    kw = {**GROUPED, 'experts_held': held, **over}
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(tokens, 32)),
                    jnp.float32)
    params = SparseExperts(**{**kw, 'experts_held': None}).init(
        jax.random.key(seed), x)
    params['params']['router_bias'] = jnp.asarray(
        np.random.default_rng(seed + 1).normal(size=16) * 0.05, jnp.float32)
    return kw, x, params


def _share(params, lo, hi):
    """``params`` of the whole layer cut to the experts ``[lo, hi)``."""
    held = jax.tree.map(lambda a: a, params)
    for name in ('w_gate', 'w_up', 'w_down'):
        held['params'][name] = params['params'][name][lo:hi]
    return held


def _route_inputs(params, x):
    logits = np.asarray(x, np.float64) @ np.asarray(
        params['params']['router'], np.float64)
    return (1 / (1 + np.exp(-logits)),
            np.asarray(params['params']['router_bias'], np.float64))


@pytest.mark.parametrize('route', ['hit_list', 'sorted'])
def test_group_limited_picks_are_the_plain_rules(route):
    """Both routes pick as the NumPy rule does, gate by the UNBIASED
    scores of the picks, and count the rows whose kept groups include
    the held one; the picks of plain top-k differ."""
    kw, x, params = _grouped_layer(
        held=(4, 8), dense_tokens=None if route == 'hit_list' else 0)
    held = _share(params, 4, 8)
    (y, counts), sown = SparseExperts(**kw).apply(held, x,
                                                  mutable=['counters'])
    scores, bias = _route_inputs(params, x)
    want, groups = _numpy_route(scores, bias, 3, 4, 2)
    got = np.asarray(sown['counters']['expert_picks'])
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    assert int(sown['counters']['group_rows']) == int(
        np.sum(np.any(groups == 1, axis=-1)))
    assert 0 < int(sown['counters']['group_rows']) < len(x)
    # a pick never leaves its token's kept groups
    assert all(set(p // 4) <= set(g) for p, g in zip(got, groups))
    plain, _ = _numpy_route(scores, bias, 3, 1, 1)
    assert np.any(np.sort(plain, -1) != np.sort(want, -1))
    # the routed part, by hand: the picks in the held group, gated by
    # their unbiased scores over the sum of all three picks' x 2.5
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params['params'])
    xs = np.asarray(x, np.float64)
    total = np.zeros_like(xs)
    for t, picked in enumerate(want):
        gates = scores[t, picked] / scores[t, picked].sum() * 2.5
        for e, g in zip(picked, gates):
            if 4 <= e < 8:
                a = xs[t] @ p['w_gate'][e]
                total[t] += g * ((a / (1 + np.exp(-a)) * (
                    xs[t] @ p['w_up'][e])) @ p['w_down'][e])
    routed, _ = SparseExperts(**{**kw, 'add_shared': False}).apply(held, x)
    np.testing.assert_allclose(routed, total, atol=TOL)


def test_ties_go_to_the_lower_index_and_a_token_may_pick_nothing_held():
    """A router that scores every expert alike but for its bias: groups
    0 and 1 tie for first, experts tie inside them — lower indices win,
    as the NumPy rule's stable sort has it — and a layer that holds
    group 3 gets no row: its routed part is exactly zero and the counter
    says 0."""
    kw, x, params = _grouped_layer(held=(12, 16), tokens=6)
    params['params']['router'] = jnp.zeros_like(params['params']['router'])
    bias = np.zeros(16, np.float32)
    bias[[0, 1, 4, 5]] = 0.25            # two groups tie, two experts each
    params['params']['router_bias'] = jnp.asarray(bias)
    held = _share(params, 12, 16)
    (y, _), sown = SparseExperts(**{**kw, 'add_shared': False}).apply(
        held, x, mutable=['counters'])
    want, _ = _numpy_route(np.full((6, 16), 0.5), bias.astype(np.float64),
                           3, 4, 2)
    np.testing.assert_array_equal(want, 6 * [[0, 1, 4]])
    np.testing.assert_array_equal(
        np.sort(sown['counters']['expert_picks'], -1), want)
    assert int(sown['counters']['group_rows']) == 0
    assert not np.any(np.asarray(y))


def _levels(seed, rows, width, levels):
    """Logits that take few values, so that experts (and groups' sums)
    tie all over the row."""
    return np.random.default_rng(seed).integers(
        -levels, levels + 1, size=(rows, width)).astype(np.float32) / 2


def _select_case(name):
    """``(layer fields, router logits (rows, n_experts), bias | None)``
    of a hit-list call: the six expert cells' widths, rows and k, and
    the inputs on which a selection by compares could leave
    ``lax.top_k``'s."""
    rng = np.random.default_rng(sum(map(ord, name)))

    def normal(rows, width, scale=1.0):
        return (rng.normal(size=(rows, width)) * scale).astype(np.float32)

    def biased(width):
        return (rng.normal(size=width) * 0.05).astype(np.float32)
    ling = dict(n_experts=512, top_k=8, n_group=8, topk_group=4,
                scaling=2.5)
    if name == 'ties_across_the_kth_place':
        return dict(n_experts=64, top_k=6), _levels(1, 12, 64, 2), None
    if name == 'two_groups_tie_for_the_last_kept_place':
        # groups 1, 2, 5, 6 hold the same two best; one of them is cut
        logits = normal(12, 64, 0.1)
        for g in (1, 2, 5, 6):
            logits[:, 8 * g + 3], logits[:, 8 * g + 6] = 2.0, 1.5
        logits[:, 0] = logits[:, 1] = 3.0
        return (dict(n_experts=64, top_k=8, n_group=8, topk_group=4),
                logits, np.zeros(64, np.float32))
    if name == 'a_groups_two_best_are_equal':
        logits = normal(12, 128)
        for g in range(4):
            logits[:, 32 * g + 5] = logits[:, 32 * g + 20] = 2.0 + g % 2
        return (dict(n_experts=128, top_k=8, n_group=4, topk_group=2),
                logits, np.zeros(128, np.float32))
    if name == 'every_group_and_expert_ties':
        return (dict(n_experts=64, top_k=6, n_group=4, topk_group=2),
                np.zeros((12, 64), np.float32), np.zeros(64, np.float32))
    if name == 'saturated_sigmoids':
        # float32 sigmoid is exactly 1.0 from ~17 up: dozens an expert row
        return ling, normal(96, 512, 20.0), np.zeros(512, np.float32)
    if name == 'signed_zeros':
        zeros = rng.choice(np.asarray([-0.0, 0.0, -1.0, 1.0], np.float32),
                           size=(12, 72))
        return (dict(n_experts=72, top_k=10, score='softmax_picked',
                     router_bias=False), zeros, None)
    if name == 'xing4_16_rows_of_64':
        return (dict(n_experts=64, top_k=6, scaling=2.0),
                normal(16, 64), biased(64))
    if name == 'one_row_of_64':
        return dict(n_experts=64, top_k=6), normal(1, 64), biased(64)
    if name == 'granite_80_rows_of_72_softmax_picked':
        return (dict(n_experts=72, top_k=10, score='softmax_picked',
                     router_bias=False, experts_held=(18, 36)),
                normal(80, 72, 3.0), None)
    if name == 'command_a_12_rows_of_128':
        return (dict(n_experts=128, top_k=8, router_bias=False,
                     experts_held=(16, 32)), normal(12, 128), None)
    if name == 'solar_128_rows_of_320':
        return (dict(n_experts=320, top_k=8, experts_held=(40, 80)),
                normal(128, 320), biased(320))
    if name == 'nemotron_top_22_of_512':
        return (dict(n_experts=512, top_k=22, norm_topk=False,
                     experts_held=(128, 160)), normal(12, 512),
                biased(512))
    if name == 'ling_96_rows_of_512_grouped':
        return ({**ling, 'experts_held': (0, 64)}, normal(96, 512),
                biased(512))
    if name == 'ling_holding_group_5':
        return ({**ling, 'experts_held': (320, 384)}, _levels(2, 96, 512, 3),
                biased(512))
    if name == 'four_groups_at_the_rules_bound':
        return (dict(n_experts=128, top_k=10, n_group=4, topk_group=2,
                     experts_held=(32, 64)), normal(128, 128), biased(128))
    raise KeyError(name)


@pytest.mark.parametrize('name', [
    'ties_across_the_kth_place', 'two_groups_tie_for_the_last_kept_place',
    'a_groups_two_best_are_equal', 'every_group_and_expert_ties',
    'saturated_sigmoids', 'signed_zeros', 'xing4_16_rows_of_64',
    'one_row_of_64', 'granite_80_rows_of_72_softmax_picked',
    'command_a_12_rows_of_128', 'solar_128_rows_of_320',
    'nemotron_top_22_of_512', 'ling_96_rows_of_512_grouped',
    'ling_holding_group_5', 'four_groups_at_the_rules_bound'])
def test_the_threshold_selection_picks_what_top_k_picks(name, monkeypatch):
    """The hit-list route's choices as a TPU makes them (``select_form``
    steered here: the program asks the backend; ``sparse_pick``
    interpreted) against ``lax.top_k``'s: the picks the same SETS entry
    for entry — ascending, where ``top_k``'s come by score —, the same
    counts, ``group_rows`` and result, the gate table zero off the picks.
    First the selection alone on the case's logits as they are (signed
    zeros reach it), then the layer, whose router is made to give each
    row its logits."""
    fields, logits, bias = _select_case(name)
    fields = {'hidden': 8, **fields}
    layer = SparseExperts(**fields)
    rows, width = logits.shape
    k, lo, hi = (fields['top_k'],
                 *(fields.get('experts_held') or (0, width)))
    raw = scores = jnp.asarray(logits)
    if fields.get('score') != 'softmax_picked':
        scores = jax.nn.sigmoid(raw)
    choice = kept = scores if bias is None else scores + bias
    want_rows = None
    if fields.get('n_group', 1) > 1:
        kept, want_rows = layer._kept_groups(choice, lo, hi)
    want = np.sort(jax.lax.top_k(kept, k)[1], -1)
    picked, table, counts, group_rows = layer._threshold_route(
        scores, choice, lo, hi)
    np.testing.assert_array_equal(picked, want)
    np.testing.assert_array_equal(counts, np.bincount(want.reshape(-1),
                                                      minlength=width))
    assert (group_rows is None) == (want_rows is None)
    if want_rows is not None:
        assert int(group_rows) == int(want_rows)
    on = np.zeros((rows, width), bool)
    np.put_along_axis(on, want, True, axis=-1)
    assert np.all((np.asarray(table) != 0) == on)

    dim = max(rows, 8)
    x = jnp.eye(rows, dim, dtype=jnp.float32)
    params = SparseExperts(**{**fields, 'experts_held': None}).init(
        jax.random.key(0), x)
    params['params']['router'] = jnp.zeros((dim, width)).at[:rows].set(raw)
    if bias is not None:
        params['params']['router_bias'] = jnp.asarray(bias)
    params = _share(params, lo, hi)
    assert moe_model.select_form() == 'sort'
    (y, tokens), sown = layer.apply(params, x, mutable=['counters'])
    monkeypatch.setattr(moe_model, 'select_form', lambda: 'threshold')
    with expert_route_traces() as traces:
        (got_y, got_tokens), got = layer.apply(params, x,
                                               mutable=['counters'])
    assert [(t['route'], t['select']) for t in traces] == [
        ('hit_list', 'threshold')]
    np.testing.assert_array_equal(
        got['counters']['expert_picks'],
        np.sort(sown['counters']['expert_picks'], -1))
    np.testing.assert_array_equal(got_tokens, tokens)
    if want_rows is not None:
        assert int(got['counters']['group_rows']) == int(
            sown['counters']['group_rows'])
    np.testing.assert_allclose(got_y, y, atol=TOL)


def test_the_group_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the parts all four one-group shares give, the
    shared expert counted once, add up to the uncut layer; the rows the
    shares count add up to ``tokens x topk_group``."""
    kw, x, params = _grouped_layer()
    want, counts = SparseExperts(**kw).apply(params, x)
    total, rows = 0.0, 0
    for g in range(4):
        lo, hi = 4 * g, 4 * g + 4
        (y, part), sown = SparseExperts(
            **{**kw, 'experts_held': (lo, hi), 'add_shared': g == 0}
        ).apply(_share(params, lo, hi), x, mutable=['counters'])
        np.testing.assert_array_equal(part, counts)
        total, rows = total + y, rows + int(sown['counters']['group_rows'])
    np.testing.assert_allclose(total, want, atol=TOL)
    assert rows == len(x) * 2
    assert int(counts.sum()) == len(x) * 3


@pytest.mark.parametrize('bad', [dict(n_group=3), dict(topk_group=5),
                                 dict(n_group=16, topk_group=4),
                                 dict(top_k=9),
                                 dict(score='softmax_picked', scaling=1.0,
                                      router_bias=False)])
def test_groups_that_do_not_divide_the_experts_are_refused(bad):
    kw, x, _ = _grouped_layer()
    with pytest.raises(ValueError, match='group'):
        SparseExperts(**{**kw, **bad}).init(jax.random.key(0), x)


# -- (d) the caches of the mixed stack ----------------------------------------

def test_insert_session_puts_a_latent_session_in_its_slot():
    """One layer's buffer and the stacked one: the rows AND the
    session's own length go in; the other sessions keep theirs."""
    for layers in (None, 2):
        batch = init_latent_cache(layers, 3, 16, 20, jnp.float32)
        lead = () if layers is None else (layers,)
        batch = batch._replace(
            rows=batch.rows + 1.0,
            length=jnp.full((*lead, 3), 5, jnp.int32))
        one = LatentCache(rows=jnp.full((*lead, 1, 20, 16), 7.0),
                          length=jnp.full((*lead, 1), 9, jnp.int32))
        out = jax.jit(insert_session)(batch, 1, one)
        np.testing.assert_array_equal(out.rows[..., 1, :, :], one.rows[
            ..., 0, :, :])
        np.testing.assert_array_equal(out.rows[..., 0, :, :],
                                      batch.rows[..., 0, :, :])
        np.testing.assert_array_equal(
            out.length, jnp.full((*lead, 3), 5).at[..., 1].set(9))


def test_snapshot_and_restore_pass_a_latent_cache_through():
    """Over a list that holds a ``LatentCache`` beside ``StateCache``s:
    the snapshot holds None for it, the restore returns it as it is (its
    length rewinds it)."""
    state = StateCache(state=jnp.ones((2, 4, 8, 8)),
                       conv=jnp.ones((2, 3, 96)))
    latent = init_latent_cache(None, 2, 16, 20, jnp.float32)
    caches = [state, latent, state]
    taken = snapshot_states(caches)
    assert [type(t).__name__ for t in taken] == [
        'StateCache', 'NoneType', 'StateCache']
    moved = [StateCache(state=state.state * 3, conv=state.conv * 3),
             latent._replace(length=latent.length + 4), state]
    back = restore_states(moved, taken)
    np.testing.assert_array_equal(back[0].state, state.state)
    np.testing.assert_array_equal(back[1].length, [4, 4])
    assert back[1].rows is moved[1].rows


# -- (e) the stack against the plain reference --------------------------------

@pytest.fixture(scope='module')
def tokens():
    return np.random.default_rng(11).integers(
        0, CFG['vocab_size'], size=(2, 24)).astype(np.int32)


@pytest.fixture(scope='module')
def served(tokens):
    """The model, its seeded and levelled weights, the reference's
    logits of both sequences, and the caches after a prefill of 16
    tokens in chunks of 8 (the chunked delta rule continuing its state,
    the MLA layer's chunk attending the latent rows so far)."""
    model = DRIVER.build_lm(CFG)
    params = DRIVER.level_routers(CFG, DRIVER.make(CFG, 7, jnp.float32), 7)
    want = np.stack([np.asarray(REF.logits_at(
        CFG, params, jnp.asarray(seq), 24)[0]) for seq in tokens])
    caches = model.make_decode_caches(2, 32)
    assert [type(c).__name__ for c in caches] == CACHES
    assert DRIVER.layer_kinds(CFG) == KINDS
    out = []
    for i in (0, 8):
        caches, logits = model.apply(params, jnp.asarray(tokens[:, i:i + 8]),
                                     caches, method='prefill')
        out.append(np.asarray(logits))
    return model, params, want, caches, np.concatenate(out, 1)


def _serve(model, params, caches, tokens, start):
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'),
                   donate_argnums=(2,))
    out = []
    for t in range(start, tokens.shape[1]):
        caches, logits = step(params, jnp.asarray(tokens[:, t:t + 1]),
                              caches)
        out.append(np.asarray(logits))
    return caches, np.concatenate(out, 1)


def test_the_whole_forward_is_the_reference(tokens, served):
    model, params, want, _, _ = served
    np.testing.assert_allclose(model.apply(params, jnp.asarray(tokens)),
                               want, atol=TOL)


def test_prefill_then_decode_through_the_mixed_caches_is_the_reference(
        tokens, served):
    model, params, want, caches, prefilled = served
    caches = jax.tree.map(jnp.copy, caches)
    after, rest = _serve(model, params, caches, tokens, 16)
    np.testing.assert_allclose(np.concatenate([prefilled, rest], 1), want,
                               atol=TOL)
    np.testing.assert_array_equal(after[2].length, [24, 24])


def test_a_bfloat16_reference_is_outside_the_tolerance(tokens, served):
    """The same comparison with the reference's matmul operands and the
    recurrence's rounded to bfloat16 fails it fifty times over: the
    tolerance would catch a lower precision."""
    model, params, want, _, _ = served
    low = np.asarray(REF.logits_at(CFG, params, jnp.asarray(tokens[0]), 24,
                                   jnp.bfloat16)[0])
    assert np.max(np.abs(low - want[0])) > 50 * TOL


def test_the_references_states_are_the_programs(tokens, served):
    """The three KDA layers' states after 16 tokens of sequence 0, the
    reference's token by token against the chunked prefill's."""
    model, params, _, caches, _ = served
    states = np.asarray(REF.logits_at(
        CFG, params, jnp.asarray(tokens[0, :16]), 8)[3])
    got = np.stack([np.asarray(c.state[0]) for c in caches
                    if isinstance(c, StateCache)])
    np.testing.assert_allclose(got, states, atol=TOL)


def test_a_request_after_restore_reads_what_the_first_did(tokens, served):
    """The snapshot at the prompt's end, 8 tokens, the three states put
    back and the latent lengths set back: the same logits bit for bit;
    with the lengths alone set back they differ, and with the states
    alone restored (the latent length left where the request took it)
    too."""
    model, params, _, caches, _ = served
    caches = jax.tree.map(jnp.copy, caches)
    taken = snapshot_states(caches)
    assert [type(s).__name__ for s in taken] == [
        'StateCache', 'StateCache', 'NoneType', 'StateCache']
    after, first = _serve(model, params, caches, tokens, 16)

    def rewind(layers):
        return [c._replace(length=jnp.full_like(c.length, 16))
                if hasattr(c, 'length') else c for c in layers]
    lengths_only = rewind(jax.tree.map(jnp.copy, after))
    _, stale = _serve(model, params, lengths_only, tokens, 16)
    assert np.max(np.abs(stale - first)) > 100 * TOL
    states_only = restore_states(jax.tree.map(jnp.copy, after), taken)
    _, longer = _serve(model, params, states_only, tokens, 16)
    assert np.max(np.abs(longer - first)) > 100 * TOL
    restore = jax.jit(lambda c, s: rewind(restore_states(c, s)),
                      donate_argnums=(0,))
    _, again = _serve(model, params, restore(after, taken), tokens, 16)
    np.testing.assert_array_equal(again, first)
    assert all(not s.state.is_deleted() for s in taken if s is not None)


def test_a_session_prefilled_alone_serves_as_in_the_batch(tokens, served):
    """Each session prefilled alone in chunks of 8 and put in its slot
    (the latent rows with the session's own length): the batch decodes
    as the one prefilled together."""
    model, params, _, caches, _ = served
    batch = model.make_decode_caches(2, 32)
    for s in range(2):
        one = model.make_decode_caches(1, 32)
        for i in (0, 8):
            one, _ = model.apply(params, jnp.asarray(
                tokens[s:s + 1, i:i + 8]), one, method='prefill')
        batch = [insert_session(c, s, o) for c, o in zip(batch, one)]
    _, want = _serve(model, params, jax.tree.map(jnp.copy, caches), tokens,
                     16)
    _, got = _serve(model, params, batch, tokens, 16)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_nonzero_swiglu_limit_is_refused():
    """The clamp's form is not published: program and reference raise
    instead of guessing it."""
    cfg = {**CFG, 'expert_swiglu_limit_list': [0, 0, 4, 0]}
    with pytest.raises(ValueError, match='swiglu'):
        DRIVER.build_lm(cfg)
    with pytest.raises(ValueError, match='swiglu'):
        REF.kinds(cfg)
    with pytest.raises(ValueError, match='swiglu'):
        REF.kinds({**CFG, 'share_expert_swiglu_limit_list': [5, 0, 0, 0]})


def test_the_all_latent_stack_keeps_its_one_stacked_cache():
    """A stack whose EVERY layer is latent (the Xing4 cell's) still
    carries one layer-stacked ``LatentCache``; a latent layer among
    other kinds gets one layer's."""
    from distributed_dot_product_tpu import TransformerLM
    attn = {k: v for k, v in LATENT.items() if k not in ('dim', 'num_heads')}
    lm = TransformerLM(vocab_size=16, dim=32, num_heads=4, n_layers=3,
                       scan_layers=False, attn_kwargs=attn,
                       block_kwargs={'mixer': 'latent', 'ffn': 'gated',
                                     'ffn_kwargs': {'hidden': 16}},
                       dense_prefix=1,
                       prefix_kwargs={'ffn_kwargs': {'hidden': 24}})
    cache = lm.make_decode_caches(2, 128)
    assert isinstance(cache, LatentCache)
    assert cache.rows.shape == (3, 2, 20, 128)
    mixed = TransformerLM(
        vocab_size=16, dim=32, num_heads=4, n_layers=2, scan_layers=False,
        block_kwargs={'mixer': 'delta', 'ffn': 'gated',
                      'ffn_kwargs': {'hidden': 16},
                      'ssm_kwargs': {'heads': 4, 'head_dim': 8}},
        layer_kinds={'K': {}, 'A': {'mixer': 'latent',
                                    'attn_kwargs': attn}},
        layer_pattern=('K', 'A'))
    caches = mixed.make_decode_caches(2, 128)
    assert [type(c).__name__ for c in caches] == ['StateCache',
                                                  'LatentCache']
    assert caches[1].rows.shape == (2, 20, 128)
