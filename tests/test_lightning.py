# -*- coding: utf-8 -*-
"""The Lightning linear-attention mixer (``models/lightning.py``): its
three entry points over one set of parameters against the recurrence
written token by token in float32 — the rotation continued across chunks
and steps, a restore that rewinds the position with the state — and the
plumbing that hands a recurrent mixer the stack's position."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.models.decode import (
    StateCache, insert_session, restore_states, snapshot_states,
)
from distributed_dot_product_tpu.models.lightning import (
    LightningMixer, lightning_log_decay,
)
from distributed_dot_product_tpu.models.transformer import (
    RECURRENT, TransformerStack,
)

HEADS, HEAD_DIM, DIM = 4, 8, 24


def mixer(**kw):
    return LightningMixer(dim=DIM, heads=HEADS, head_dim=HEAD_DIM, chunk=8,
                          **kw)


@pytest.fixture(scope='module')
def setup():
    m = mixer()
    h = jax.random.normal(jax.random.key(1), (2, 40, DIM))
    params = m.init(jax.random.key(0), h[:, :8])
    p = params['params']
    keys = jax.random.split(jax.random.key(2), 3)
    p['q_norm'] = 1.0 + 0.2 * jax.random.normal(keys[0], (HEAD_DIM,))
    p['k_norm'] = 1.0 + 0.2 * jax.random.normal(keys[1], (HEAD_DIM,))
    p['norm_scale'] = 1.0 + 0.2 * jax.random.normal(
        keys[2], (HEADS * HEAD_DIM,))
    return m, params, h


def by_hand(params, h, use_rope=True, start=0):
    """The docstring of ``models/lightning.py`` for one session ``h (T,
    dim)``, one token at a time: outputs and the final state (value
    channel, then key channel)."""
    p = params['params']
    h = np.asarray(h, np.float64)
    t = len(h)
    inner = HEADS * HEAD_DIM
    q, k, v, g = np.split(h @ np.asarray(p['in_proj']['kernel'],
                                         np.float64), 4, -1)

    def normed(x, scale):
        x = x.reshape(t, HEADS, HEAD_DIM)
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * (
            np.asarray(scale, np.float64))

    def rope(x):
        inv = 10000.0 ** (-np.arange(0, HEAD_DIM, 2) / HEAD_DIM)
        ang = (start + np.arange(t))[:, None, None] * inv
        x1, x2 = x[..., :HEAD_DIM // 2], x[..., HEAD_DIM // 2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x1 * np.sin(ang) + x2 * np.cos(ang)], -1)
    q, k = normed(q, p['q_norm']), normed(k, p['k_norm'])
    if use_rope:
        q, k = rope(q), rope(k)
    v = v.reshape(t, HEADS, HEAD_DIM)
    lam = np.exp(-2.0 ** (-8.0 * (np.arange(HEADS) + 1) / HEADS))
    state = np.zeros((HEADS, HEAD_DIM, HEAD_DIM))
    outs = []
    for i in range(t):
        state = lam[:, None, None] * state + (
            v[i][:, :, None] * k[i][:, None, :])
        o = (state * q[i][:, None, :]).sum(-1) / math.sqrt(HEAD_DIM)
        o = o.reshape(inner)
        o = o / np.sqrt((o ** 2).mean() + 1e-6) * np.asarray(
            p['norm_scale'], np.float64)
        o = o / (1 + np.exp(-g[i]))
        outs.append(o @ np.asarray(p['out_proj']['kernel'], np.float64))
    return np.stack(outs), state


def test_the_decay_is_lightning_attention_2s_slope_a_head():
    got = np.asarray(lightning_log_decay(32))
    assert got.shape == (32,) and np.all(got < 0)
    np.testing.assert_allclose(got[0], -2.0 ** -0.25, rtol=1e-6)
    np.testing.assert_allclose(got[-1], -2.0 ** -8, rtol=1e-6)


def test_the_whole_sequence_is_the_recurrence(setup):
    m, params, h = setup
    got = m.apply(params, h)
    for b in range(2):
        np.testing.assert_allclose(got[b], by_hand(params, h[b])[0],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('chunks', [(40,), (8, 8, 8, 8, 8), (5, 19, 16),
                                    (13, 27)],
                         ids=['one', 'whole', 'ragged', 'two'])
def test_prefill_in_chunks_continues_state_and_rotation(setup, chunks):
    """Chunks of any lengths, each told where it starts: the outputs of
    the whole sequence, and the recurrence's final state."""
    m, params, h = setup
    cache = m.make_cache(2)
    assert cache.conv.shape == (2, 0, HEADS * HEAD_DIM)     # no window
    outs, start = [], 0
    for c in chunks:
        cache, o = m.apply(params, h[:, start:start + c], cache,
                           position=jnp.int32(start), method='prefill')
        outs.append(o)
        start += c
    want, state = by_hand(params, h[1])
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[1], want,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(cache.state[1], state, atol=2e-5, rtol=2e-5)


def test_decode_steps_follow_a_prefilled_prompt(setup):
    m, params, h = setup
    cache = m.make_cache(2)
    cache, first = m.apply(params, h[:, :24], cache, position=jnp.int32(0),
                           method='prefill')
    step = jax.jit(lambda c, x, at: m.apply(params, x, c, position=at,
                                            method='decode'))
    outs = [first]
    for t in range(24, 40):
        cache, o = step(cache, h[:, t:t + 1], jnp.int32(t))
        outs.append(o)
    want, state = by_hand(params, h[0])
    np.testing.assert_allclose(jnp.concatenate(outs, 1)[0], want,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(cache.state[0], state, atol=2e-5, rtol=2e-5)


def test_a_position_that_is_off_shows(setup):
    """The rotation is by the position given: the same chunk from
    another position is another result, and a mixer that rotates
    refuses to guess one."""
    m, params, h = setup
    cache = m.make_cache(2)
    at0 = m.apply(params, h[:, :8], cache, position=jnp.int32(0),
                  method='prefill')[1]
    at5 = m.apply(params, h[:, :8], cache, position=jnp.int32(5),
                  method='prefill')[1]
    # a first chunk's scores are relative: the same outputs
    np.testing.assert_allclose(at0, at5, atol=2e-5)
    cache = m.apply(params, h[:, :8], cache, position=jnp.int32(0),
                    method='prefill')[0]
    right = m.apply(params, h[:, 8:16], cache, position=jnp.int32(8),
                    method='prefill')[1]
    wrong = m.apply(params, h[:, 8:16], cache, position=jnp.int32(9),
                    method='prefill')[1]
    assert float(jnp.max(jnp.abs(right - wrong))) > 1e-3
    with pytest.raises(ValueError, match='position'):
        m.apply(params, h[:, :8], cache, method='prefill')
    plain = mixer(use_rope=False)
    got = plain.apply(params, h[:, :8], m.make_cache(2), method='prefill')[1]
    np.testing.assert_allclose(
        got[0], by_hand(params, h[0, :8], use_rope=False)[0], atol=2e-5)


def stack():
    return TransformerStack(
        dim=DIM, num_heads=2, n_layers=2, scan_layers=False,
        attn_kwargs=dict(causal=True, softmax_impl='flash',
                         distributed=False, use_rope=False),
        block_kwargs=dict(norm='rmsnorm', ffn='none'),
        layer_kinds={
            'a': dict(mixer='attention'),
            'l': dict(mixer='lightning', ssm_kwargs=dict(
                heads=HEADS, head_dim=HEAD_DIM, chunk=8))},
        layer_pattern=('a', 'l'))


def test_the_stack_hands_the_mixer_its_attention_layers_length():
    """Through ``TransformerStack``: the Lightning layer is told the
    slab's length, chunk after chunk and step after step, so prefill +
    decode is the whole-sequence forward."""
    assert RECURRENT['lightning'] is LightningMixer
    s = stack()
    x = jax.random.normal(jax.random.key(3), (2, 32, DIM))
    params = s.init(jax.random.key(0), x, x, x)
    want = s.apply(params, x, x, x)
    caches = s.make_decode_caches(2, 48)
    assert isinstance(caches[1], StateCache)
    caches, a = s.apply(params, x[:, :10], caches, method='prefill')
    caches, b = s.apply(params, x[:, 10:24], caches, method='prefill')
    outs = [a, b]
    for t in range(24, 32):
        caches, o = s.apply(params, x[:, t:t + 1], caches, method='decode')
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=3e-5,
                               rtol=3e-5)


def test_a_restore_rewinds_the_position_with_the_state():
    """A served request, the slab's length set back and the state
    restored from the prompt's snapshot, then the same tokens again: the
    same outputs bit for bit — the position is the slab's length, not a
    count the state cache keeps."""
    s = stack()
    x = jax.random.normal(jax.random.key(4), (2, 32, DIM))
    params = s.init(jax.random.key(0), x, x, x)
    caches = s.make_decode_caches(2, 48)
    caches, _ = s.apply(params, x[:, :20], caches, method='prefill')
    taken = snapshot_states(caches)
    step = jax.jit(lambda c, xt: s.apply(params, xt, c, method='decode'))

    def serve(caches):
        outs = []
        for t in range(20, 32):
            caches, o = step(caches, x[:, t:t + 1])
            outs.append(o)
        return caches, jnp.stack(outs)
    caches, first = serve(caches)
    assert int(caches[0].length) == 32
    caches = [c._replace(length=jnp.int32(20)) if hasattr(c, 'length')
              else c for c in restore_states(caches, taken)]
    caches, second = serve(caches)
    np.testing.assert_array_equal(first, second)
    # the state alone restored, the length left where the request ended:
    # the rotation runs on and the outputs differ
    kept = restore_states(caches, taken)
    assert float(jnp.max(jnp.abs(serve(kept)[1] - first))) > 1e-3


def test_a_state_cache_without_a_window_inserts_snapshots_and_restores():
    m = mixer()
    batch, one = m.make_cache(3), m.make_cache(1)
    one = one._replace(state=one.state + 2.0)
    out = insert_session(batch, 2, one)
    np.testing.assert_array_equal(out.state[2], 2.0)
    np.testing.assert_array_equal(out.state[:2], 0.0)
    assert out.conv.shape == batch.conv.shape
    taken = snapshot_states([None, out])
    moved = out._replace(state=out.state + 1.0)
    back = jax.jit(restore_states)([None, moved], taken)
    np.testing.assert_array_equal(back[1].state, out.state)
    assert back[0] is None and back[1].conv.shape == out.conv.shape
