# -*- coding: utf-8 -*-
"""
ALiBi (additive linear position bias) tests: the in-kernel
``slope·(pos_k − pos_q)`` bias against a dense jnp oracle, composed with
the shard offset, explicit positions, windows and GQA. No reference
analog.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.ops.pallas_attention import (
    flash_attention,
)

B, H, D = 2, 4, 16

pytestmark = pytest.mark.slow


def _qkv(t, key=0, h=H):
    ks = jax.random.split(jax.random.key(key), 3)
    return tuple(jax.random.normal(kk, (B, h, t, D)) for kk in ks)


def _slopes(h=H):
    # The classic geometric ALiBi slopes 2^(-8i/h).
    return 2.0 ** (-8.0 * (jnp.arange(h) + 1) / h)


def _oracle(q, k, v, slopes, t, causal=True, offset=0, window=None):
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum('bhtd,bhod->bhto', q * scale, k)
    rows = offset + jnp.arange(q.shape[-2])[:, None]
    cols = jnp.arange(t)[None, :]
    s = s + slopes[None, :, None, None] * (cols - rows)
    if causal:
        s = jnp.where(rows < cols, -jnp.inf, s)
    if window is not None:
        s = jnp.where(rows - cols >= window, -jnp.inf, s)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhto,bhod->bhtd', a, v)


@pytest.mark.parametrize('t', [64, 100])
def test_alibi_matches_dense_oracle(t):
    q, k, v = _qkv(t)
    sl = _slopes()
    out = flash_attention(q, k, v, causal=True, alibi_slopes=sl)
    ref = _oracle(q, k, v, sl, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_alibi_with_shard_offset():
    t, off = 64, 128
    q, k, v = _qkv(t, key=1)
    kf = jnp.concatenate([k, k, k], axis=-2)
    vf = jnp.concatenate([v, v, v], axis=-2)
    sl = _slopes()
    out = flash_attention(q, kf, vf, causal=True, causal_offset=off,
                          alibi_slopes=sl)
    ref = _oracle(q, kf, vf, sl, 3 * t, offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_alibi_gradients():
    t = 64
    q, k, v = _qkv(t, key=2)
    sl = _slopes()

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                alibi_slopes=sl) ** 2).sum()

    def f_ref(q, k, v):
        return (_oracle(q, k, v, sl, t) ** 2).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=1e-4)


def test_alibi_with_positions_layout():
    """Shuffled rows with explicit positions: bias follows GLOBAL
    positions, not buffer order."""
    t = 64
    q, k, v = _qkv(t, key=3)
    sl = _slopes()
    perm = jax.random.permutation(jax.random.key(9), t)
    pos = jnp.arange(t, dtype=jnp.int32)
    out_p = flash_attention(
        q[..., perm, :], k[..., perm, :], v[..., perm, :],
        positions=(pos[perm], pos[perm]), alibi_slopes=sl)
    ref = _oracle(q, k, v, sl, t)
    np.testing.assert_allclose(np.asarray(out_p[..., jnp.argsort(perm), :]),
                               np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_alibi_with_window_and_gqa():
    t, window = 64, 13
    q, k, v = _qkv(t, key=4)
    sl = _slopes()
    kg, vg = k[:, ::2], v[:, ::2]     # 2 kv heads
    out = flash_attention(q, kg, vg, causal=True, window=window,
                          alibi_slopes=sl)
    ref = _oracle(q, jnp.repeat(kg, 2, axis=1), jnp.repeat(vg, 2, axis=1),
                  sl, t, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_alibi_bounded_mode_falls_back_exact():
    t = 64
    q, k, v = _qkv(t, key=5)
    sl = _slopes()
    out_b = flash_attention(q, k, v, causal=True, alibi_slopes=sl,
                            softmax_mode='bounded')
    out_e = flash_attention(q, k, v, causal=True, alibi_slopes=sl)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_e),
                               atol=1e-6)


def test_alibi_requires_positions_or_causal():
    q, k, v = _qkv(16)
    with pytest.raises(ValueError, match='alibi'):
        flash_attention(q, k, v, alibi_slopes=_slopes())


def _iota_alibi_bias(slope, qi, ki, bq, bk, off_ref, pos):
    """The whole bias an element, as every block rebuilt it before the
    vector form: rows and columns from iotas, an int32 difference, a
    convert, a multiply — and no row constant anywhere."""
    rows = (off_ref[0, 0] + qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    cols = (off_ref[0, 1] + ki * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    return slope * (cols - rows).astype(jnp.float32)


@pytest.mark.parametrize('t,offset,window,blocks', [
    (64, 0, None, (16, 16)), (100, 0, None, (16, 32)),
    (64, 128, None, (32, 16)), (64, 0, 13, (16, 16)),
    (100, 37, 24, (8, 32))])
def test_alibi_vector_form_is_the_iota_form(monkeypatch, t, offset, window,
                                            blocks):
    """The bias as a ``(1, bk)`` vector a block with the row's constant
    carried in the logsumexp's domain (``_alibi_bias``,
    ``_alibi_row_shift``) against ``slope · float(cols − rows)`` rebuilt
    from iotas an element, to this file's tolerance: output and all three
    gradients, several blocks a side (forward and backward blocks of
    different shapes), row offsets, windows, ragged lengths — and the
    dense oracle beside."""
    import distributed_dot_product_tpu.ops.pallas_attention as pa
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: blocks)
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: blocks[::-1])
    q, k, v = _qkv(t, key=6)
    kf = jnp.concatenate([k] * (1 + -(-offset // t)), axis=-2)
    vf = jnp.concatenate([v] * (1 + -(-offset // t)), axis=-2)
    sl = _slopes()

    def run():
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   causal_offset=offset, window=window,
                                   alibi_slopes=sl)
        out, vjp = jax.vjp(f, q, kf, vf)
        return (out, *vjp(jnp.ones_like(out)))

    got = run()
    monkeypatch.setattr(pa, '_alibi_bias', _iota_alibi_bias)
    monkeypatch.setattr(pa, '_alibi_row_shift',
                            lambda slope, bq: jnp.zeros((bq, 1)))
    want = run()
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    ref = _oracle(q, kf, vf, sl, kf.shape[-2], offset=offset,
                  window=window)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
