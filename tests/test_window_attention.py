# -*- coding: utf-8 -*-
"""
Sliding-window (local) attention tests.

Oracle pattern per SURVEY §4: the window is densified into a boolean mask
(``i − j >= window`` masked, on global positions) and fed to the unfused
jnp math / the windowless kernel — the windowed kernel must match both,
forward and gradients, including when the window does not align with the
kernel block sizes and when it composes with user masks, segment ids and
explicit-position layouts. No reference analog (its module materializes
every (T/N, T) score row, reference module.py:66-67).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.ops.pallas_attention import (
    _reference_math, flash_attention,
)

B, H, D = 2, 3, 16

pytestmark = pytest.mark.slow  # Pallas-interpreter-heavy


def _qkv(t, key=0):
    k1, k2, k3 = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(k1, (B, H, t, D), jnp.float32)
    k = jax.random.normal(k2, (B, H, t, D), jnp.float32)
    v = jax.random.normal(k3, (B, H, t, D), jnp.float32)
    return q, k, v


def _window_mask(t, window, offset=0):
    """Dense equivalent: global row i attends cols (i − window, i]."""
    rows = offset + jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    return rows - cols >= window


@pytest.mark.parametrize('t,window', [(64, 16), (100, 7), (64, 1),
                                      (64, 200)])
def test_window_matches_densified_mask(t, window):
    q, k, v = _qkv(t)
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = _reference_math(q, k, v, _window_mask(t, window),
                          1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_window_with_causal_offset():
    """Sequence-sharded case: query rows are global rows offset..offset+t."""
    t, window, off = 64, 10, 128
    q, k, v = _qkv(t, key=3)
    kf = jnp.concatenate([k, k, k], axis=-2)   # gathered keys, Tk = 3t
    vf = jnp.concatenate([v, v, v], axis=-2)
    out = flash_attention(q, kf, vf, causal=True, causal_offset=off,
                          window=window)
    rows = off + jnp.arange(t)[:, None]
    cols = jnp.arange(3 * t)[None, :]
    dense = (rows < cols) | (rows - cols >= window)
    ref = _reference_math(q, kf, vf, dense, 1.0 / np.sqrt(D), False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_window_gradients_match_densified(t=100, window=13):
    q, k, v = _qkv(t, key=1)

    def f_win(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                window=window) ** 2).sum()

    def f_dense(q, k, v):
        return (_reference_math(q, k, v, _window_mask(t, window),
                                1.0 / np.sqrt(D), True) ** 2).sum()

    g_win = jax.grad(f_win, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for gw, gr in zip(g_win, g_ref):
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gr),
                                   atol=2e-4, rtol=1e-4)


def test_window_with_positions_shuffled_layout():
    """window over EXPLICIT positions: a zigzag-style permuted row layout
    must behave as if rows were in natural order."""
    t, window = 64, 9
    q, k, v = _qkv(t, key=2)
    perm = jax.random.permutation(jax.random.key(11), t)
    pos = jnp.arange(t, dtype=jnp.int32)
    qp, kp, vp = q[..., perm, :], k[..., perm, :], v[..., perm, :]
    out_p = flash_attention(qp, kp, vp, positions=(pos[perm], pos[perm]),
                            window=window)
    out_n = flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_p[..., jnp.argsort(perm), :]),
                               np.asarray(out_n), atol=1e-5, rtol=1e-5)


def test_window_composes_with_mask_and_segments():
    t, window = 64, 12
    q, k, v = _qkv(t, key=4)
    user = jax.random.bernoulli(jax.random.key(5), 0.2, (B, H, t, t))
    seg = (jnp.arange(t, dtype=jnp.int32) * 4 // t)
    out = flash_attention(q, k, v, user, causal=True, window=window,
                          segment_ids=seg)
    dense = (user | _window_mask(t, window)
             | (seg[:, None] != seg[None, :]))
    ref = _reference_math(q, k, v, dense, 1.0 / np.sqrt(D), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_window_bounded_mode_matches_exact():
    t, window = 64, 8
    q, k, v = _qkv(t, key=6)
    out_b = flash_attention(q, k, v, causal=True, window=window,
                            softmax_mode='bounded')
    out_e = flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_e),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize('t,window,off', [(64, 16, 0), (48, 5, 32),
                                          (64, 200, 0)])
def test_banded_grid_matches_full_grid(monkeypatch, t, window, off):
    """The TPU-only banded grid (scalar-prefetch index maps select each Q
    block's K band; ~window/bk blocks per row instead of Tk/bk) must be
    bit-identical to the full-grid window path, forward and backward —
    forced under the Mosaic interpreter on tiny shapes, like the mask
    redirect."""
    import distributed_dot_product_tpu.ops.pallas_attention as pa

    q, k, v = _qkv(t, key=8)
    kf = jnp.concatenate([k, k], axis=-2)
    vf = jnp.concatenate([v, v], axis=-2)

    def run(q):
        def f(q):
            return (flash_attention(q, kf, vf, causal=True,
                                    causal_offset=off,
                                    window=window) ** 2).sum()
        return jax.value_and_grad(f)(q)

    ref_out, ref_g = run(q)
    monkeypatch.setattr(pa, '_BAND_ON_INTERPRET', True)
    band_out, band_g = run(q)
    np.testing.assert_allclose(np.asarray(band_out), np.asarray(ref_out),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(band_g), np.asarray(ref_g),
                               atol=1e-5, rtol=1e-5)


def test_banded_grid_with_segments(monkeypatch):
    """Banded grid composes with segment ids (their kv-side vector spec is
    the one aux input that needs the band's index translation)."""
    import distributed_dot_product_tpu.ops.pallas_attention as pa

    t, window = 64, 10
    q, k, v = _qkv(t, key=9)
    seg = (jnp.arange(t, dtype=jnp.int32) * 3 // t)
    ref = flash_attention(q, k, v, causal=True, window=window,
                          segment_ids=seg)
    monkeypatch.setattr(pa, '_BAND_ON_INTERPRET', True)
    got = flash_attention(q, k, v, causal=True, window=window,
                          segment_ids=seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_window_validation():
    q, k, v = _qkv(16)
    with pytest.raises(ValueError, match='causal semantics'):
        flash_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match='positive int'):
        flash_attention(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match='positive int'):
        flash_attention(q, k, v, causal=True, window=2.5)


# --- module-level: every softmax path agrees with the local oracle -------

from distributed_dot_product_tpu.models.attention import (  # noqa: E402
    DistributedDotProductAttn, apply_seq_parallel,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh  # noqa: E402

WORLD, LEN = 4, 8
T = WORLD * LEN
DIM = 16


@pytest.fixture(scope='module')
def mesh():
    return seq_mesh(WORLD)


def _module_inputs():
    kk, kq, kv = jax.random.split(jax.random.key(20), 3)
    k = jax.random.normal(kk, (2, T, DIM), jnp.float32)
    q = jax.random.normal(kq, (2, T, DIM), jnp.float32)
    v = jax.random.normal(kv, (2, T, DIM), jnp.float32)
    return k, q, v


@pytest.mark.parametrize('impl', ['full', 'flash', 'online', 'ulysses'])
def test_module_window_matches_local_oracle(mesh, impl):
    """Distributed window attention == the distributed=False oracle, for
    every softmax path. The oracle runs the 'full' path (windows densified
    into the mask), so kernels and densification cross-check each other."""
    kwargs = dict(key_dim=DIM, num_heads=4, causal=True, window=11)
    dist = DistributedDotProductAttn(distributed=True, softmax_impl=impl,
                                     **kwargs)
    local = DistributedDotProductAttn(distributed=False, **kwargs)
    k, q, v = _module_inputs()
    params = local.init(jax.random.key(1), k, q, v, None)
    out = apply_seq_parallel(dist, params, mesh, k, q, v, None)
    ref = local.apply(params, k, q, v, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_module_window_gradients(mesh):
    kwargs = dict(key_dim=DIM, num_heads=4, causal=True, window=7)
    dist = DistributedDotProductAttn(distributed=True, softmax_impl='flash',
                                     **kwargs)
    local = DistributedDotProductAttn(distributed=False, **kwargs)
    k, q, v = _module_inputs()
    params = local.init(jax.random.key(2), k, q, v, None)

    def ld(p):
        return jnp.sum(apply_seq_parallel(dist, p, mesh, k, q, v, None) ** 2)

    def ll(p):
        return jnp.sum(local.apply(p, k, q, v, None) ** 2)

    for got, want in zip(jax.tree.leaves(jax.grad(ld)(params)),
                         jax.tree.leaves(jax.grad(ll)(params))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def test_ring_window_zigzag_layout(mesh):
    """window composes with the zigzag causal ring layout (positions-based
    masking path)."""
    from distributed_dot_product_tpu.models.ring_attention import (
        ring_attention, local_attention_reference, zigzag_indices,
    )
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    window = 9
    kq, kk, kv = jax.random.split(jax.random.key(30), 3)
    q = jax.random.normal(kq, (2, T, DIM), jnp.float32)
    k = jax.random.normal(kk, (2, T, DIM), jnp.float32)
    v = jax.random.normal(kv, (2, T, DIM), jnp.float32)
    idx = zigzag_indices(T, WORLD)
    inv = jnp.argsort(idx)

    def run(qz, kz, vz):
        return ring_attention(qz, kz, vz, causal=True, layout='zigzag',
                              window=window)

    out_z = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(None, 'seq', None),) * 3,
        out_specs=P(None, 'seq', None), check_vma=False,
    ))(q[:, idx], k[:, idx], v[:, idx])[:, inv]
    ref = local_attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_z), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_module_window_requires_causal():
    with pytest.raises(ValueError, match='causal'):
        DistributedDotProductAttn(key_dim=DIM, window=4).init(
            jax.random.key(0), *([jnp.zeros((1, 8, DIM))] * 3), None)


@pytest.mark.parametrize('band', [False, True], ids=['full', 'band'])
@pytest.mark.parametrize('t,window,off', [(64, 16, 0), (64, 40, 0),
                                          (48, 5, 32), (64, 21, 0),
                                          (100, 40, 0), (64, 48, 16)])
def test_window_by_kind_is_the_whole_mask_form(monkeypatch, band, t, window,
                                               off):
    """Blocks inside the band (under the diagonal, above the window's
    edge) take the branch without the causal and window selects; output
    and gradients are bit for bit the whole-mask form's on the full and
    the banded grid, for windows that are and are not block multiples, a
    row offset and a ragged length."""
    import distributed_dot_product_tpu.ops.pallas_attention as pa
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: (16, 16))
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: (16, 16))
    monkeypatch.setattr(pa, '_BAND_ON_INTERPRET', band)
    q, k, v = _qkv(t, key=11)
    kf = jnp.concatenate([k, k], axis=-2)
    vf = jnp.concatenate([v, v], axis=-2)

    def run():
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, causal_offset=off,
                                   window=window)
        out, vjp = jax.vjp(f, q, kf, vf)
        return (out, *vjp(jnp.ones_like(out)))

    with pa.flash_block_traces() as traces:
        got = run()
    assert {tr['grid'] for tr in traces} == {'band' if band else 'full'}
    assert all(0 <= tr['interior_blocks'] < tr['run_blocks']
               for tr in traces)
    # interior: 16 (qi − ki) + 15 < window for some qi > ki
    assert any(tr['interior_blocks'] for tr in traces) == (window > 31)
    monkeypatch.setattr(pa, '_block_interior', lambda *a, **k: None)
    want = run()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
