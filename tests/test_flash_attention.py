# -*- coding: utf-8 -*-
"""
Tests for the fused flash-attention Pallas kernel.

Oracle pattern per SURVEY §4: the unfused jnp math
(``_reference_math``, identical semantics to
``local_attention_reference``) on the same arrays. On the CPU test mesh the
kernel runs in Pallas interpreter mode — the same code path that compiles
on TPU. Covers what the reference never tests (SURVEY §4): non-trivial
masks, fully-masked rows, batch > 1, and sizes that don't divide the block
shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_dot_product_tpu.models.attention import (
    DistributedDotProductAttn,
)
import distributed_dot_product_tpu.ops.pallas_attention as pa
from distributed_dot_product_tpu.ops.pallas_attention import (
    _reference_math, flash_attention,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh

B, H, D = 2, 3, 16


# Pallas-interpreter / lax.scan-heavy cases are slow; the fused-backward
# cases at the end of the file are tiny and run in tier-1.
slow = pytest.mark.slow


def _qkv(t, key=0, d_v=D):
    k1, k2, k3 = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(k1, (B, H, t, D), jnp.float32)
    k = jax.random.normal(k2, (B, H, t, D), jnp.float32)
    v = jax.random.normal(k3, (B, H, t, d_v), jnp.float32)
    return q, k, v


def _mask(t, p=0.3):
    m = jax.random.bernoulli(jax.random.key(7), p, (B, H, t, t))
    return m.at[..., 0].set(False)  # keep every row attendable


@slow
@pytest.mark.parametrize('t', [64, 100])   # 100: blocks don't divide T
@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('masked', [False, True])
def test_matches_unfused_math(t, causal, masked):
    q, k, v = _qkv(t)
    m = _mask(t) if masked else None
    out = flash_attention(q, k, v, m, causal=causal)
    ref = _reference_math(q, k, v, m, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_rectangular_and_dv():
    """Tq != Tk and d_v != d (the general shape contract)."""
    q, _, _ = _qkv(48)
    _, k, v = _qkv(80, key=1, d_v=24)
    out = flash_attention(q, k, v)
    ref = _reference_math(q, k, v, None, 1.0 / np.sqrt(D), False)
    assert out.shape == (B, H, 48, 24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_fully_masked_rows_zero_not_nan():
    q, k, v = _qkv(32)
    m = _mask(32).at[:, :, 5, :].set(True)   # row 5 fully masked
    out = flash_attention(q, k, v, m)
    assert np.isfinite(np.asarray(out)).all()
    assert (np.asarray(out)[:, :, 5] == 0).all()
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, m) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


@slow
@pytest.mark.parametrize('t', [64, 100])   # 100: blocks don't divide T
@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('masked', [False, True])
def test_gradients_match_unfused(t, causal, masked):
    q, k, v = _qkv(t)
    m = _mask(t) if masked else None

    def f_fused(q, k, v):
        return jnp.sum(flash_attention(q, k, v, m, causal=causal) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference_math(q, k, v, m, 1.0 / np.sqrt(D),
                                       causal) ** 2)

    g1 = jax.grad(f_fused, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@slow
def test_gradients_rectangular_and_dv():
    """Backward with Tq != Tk and d_v != d (exercises both bwd kernels on
    non-square grids)."""
    q, _, _ = _qkv(48)
    _, k, v = _qkv(80, key=1, d_v=24)

    def f_fused(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_reference_math(q, k, v, None, 1.0 / np.sqrt(D),
                                       False) ** 2)

    g1 = jax.grad(f_fused, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@slow
def test_gradient_dtype_matches_primal():
    """custom_vjp contract: cotangent dtypes equal primal dtypes (bf16)."""
    q, k, v = _qkv(32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32) ** 2), (0, 1, 2))(
            q, k, v)
    assert all(x.dtype == jnp.bfloat16 for x in g)


@slow
@pytest.mark.parametrize('causal', [False, True])
def test_bounded_softmax_mode_matches_exact(causal):
    """softmax_mode='bounded' (norm-bound shift, no running max) must agree
    with 'exact' to fp32 softmax tolerance, forward and gradients, including
    masks and fully-masked rows."""
    t = 100
    q, k, v = _qkv(t)
    m = _mask(t).at[:, :, 5, :].set(True)   # row 5 fully masked

    out_b = flash_attention(q, k, v, m, causal=causal,
                            softmax_mode='bounded')
    out_e = flash_attention(q, k, v, m, causal=causal)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_e),
                               atol=1e-5, rtol=1e-5)
    gb = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, m, causal=causal, softmax_mode='bounded') ** 2),
        (0, 1, 2))(q, k, v)
    ge = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, m, causal=causal) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(gb, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@slow
def test_bounded_mode_safe_on_adversarial_norms():
    """Huge-norm near-orthogonal q/k make the Cauchy-Schwarz bound exceed
    fp32's exponent range; 'bounded' must auto-fall back to the exact
    kernel instead of silently underflowing every weight to zero."""
    t, d = 32, 64
    q = jnp.zeros((1, t, d)).at[:, :, 0].set(35.0)
    k = jnp.zeros((1, t, d)).at[:, :, 1].set(35.0)   # all scores exactly 0
    v = jax.random.normal(jax.random.key(0), (1, t, d), jnp.float32)
    out_b = flash_attention(q, k, v, softmax_mode='bounded')
    out_e = flash_attention(q, k, v)
    assert not np.allclose(np.asarray(out_b), 0.0)   # the failure mode
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_e),
                               atol=1e-6, rtol=1e-6)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, softmax_mode='bounded') ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


@slow
@pytest.mark.parametrize('mode', ['exact', 'bounded'])
def test_row_masked_only_by_causal_union_is_zero(mode):
    """A row whose attendable keys are emptied only by the UNION of the
    user mask and causality (neither alone) must behave like a
    fully-masked row — 0 output, zero/finite grads — identically in both
    softmax modes and in the oracle."""
    t, row = 16, 5
    q, k, v = _qkv(t)
    m = jnp.zeros((B, H, t, t), dtype=bool)
    m = m.at[:, :, row, :row + 1].set(True)   # user mask kills j<=row only
    out = flash_attention(q, k, v, m, causal=True, softmax_mode=mode)
    ref = _reference_math(q, k, v, m, 1.0 / np.sqrt(D), True)
    assert (np.asarray(out)[:, :, row] == 0).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    g = jax.grad(lambda v: jnp.sum(flash_attention(
        q, k, v, m, causal=True, softmax_mode=mode) ** 2))(v)
    gr = jax.grad(lambda v: jnp.sum(_reference_math(
        q, k, v, m, 1.0 / np.sqrt(D), True) ** 2))(v)
    assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               atol=1e-5, rtol=1e-5)


@slow
def test_bad_softmax_mode_rejected():
    q, k, v = _qkv(32)
    with pytest.raises(ValueError, match='softmax_mode'):
        flash_attention(q, k, v, softmax_mode='fast')


@slow
@pytest.mark.tpu
def test_tpu_hardware_compile_path():
    """Mosaic (real-TPU) compile coverage the interpreter can't give:
    off-block-size T and bf16, forward + gradient, both softmax modes.
    Skipped off-TPU; on TPU f32 matmuls default to bf16 compute, hence the
    loose tolerance vs the fp32 oracle."""
    import jax
    if jax.default_backend() != 'tpu':
        pytest.skip('requires a real TPU backend')
    t = 777   # pads to non-trivial block multiple
    q, k, v = _qkv(t)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    m = _mask(t)
    ref = _reference_math(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), m, 1.0 / np.sqrt(D), False)
    for mode in ('exact', 'bounded'):
        out = flash_attention(q, k, v, m, softmax_mode=mode,
                              interpret=False)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=2e-2, rtol=2e-2)
        g = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, m, softmax_mode=mode,
            interpret=False).astype(jnp.float32) ** 2))(q)
        assert np.isfinite(np.asarray(g, np.float32)).all()


@slow
def test_mask_with_extra_leading_dims_rejected():
    """A mask may broadcast over q/k/v leading dims but not ADD dims —
    output batch shape comes solely from q/k/v."""
    q, k, v = (x[0, 0] for x in _qkv(32))   # (T, d)
    m = jnp.zeros((B, 32, 32), dtype=bool)
    with pytest.raises(ValueError, match='may not add batch dims'):
        flash_attention(q, k, v, m)


@slow
def test_module_flash_impl_matches_local_oracle(devices):
    """DistributedDotProductAttn(softmax_impl='flash') inside shard_map ==
    the distributed=False local oracle (the reference test_gradient.py
    pattern), through projections, multi-head split and mask broadcast."""
    mesh = seq_mesh(4)
    t, dim, heads = 32, 16, 4
    kw = dict(key_dim=dim, num_heads=heads, offset=2)
    dist = DistributedDotProductAttn(softmax_impl='flash', **kw)
    local = DistributedDotProductAttn(distributed=False, **kw)

    x = jax.random.normal(jax.random.key(0), (B, t, dim))
    m = jax.random.bernoulli(jax.random.key(1), 0.3, (B, t, t))
    m = m.at[..., 0].set(False)
    params = local.init(jax.random.key(2), x, x, x, m)

    expected = local.apply(params, x, x, x, m)

    spec = P(None, 'seq', None)
    got = jax.shard_map(
        lambda p, k, q, v, mm: dist.apply(p, k, q, v, mm),
        mesh=mesh, in_specs=(P(), spec, spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(params, x, x, x, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


# -- the fused backward against the split one ----------------------------
# One K-major kernel gives dq, dk and dv where the dq accumulator of a
# batch-head fits its VMEM budget; past it, and for a pass asked alone,
# the dq and dk/dv kernels run. Blocks are shrunk to 16 x 16 so that the
# walks (trapezoid, banded, plain grid) have several blocks at tiny T.

FB, FH, FT, FD = 1, 2, 64, 16


def _fused_inputs(tq=FT, tk=FT, hkv=None):
    ks = jax.random.split(jax.random.key(29), 4)
    q = jax.random.normal(ks[0], (FB, FH, tq, FD))
    k = jax.random.normal(ks[1], (FB, hkv or FH, tk, FD))
    v = jax.random.normal(ks[2], (FB, hkv or FH, tk, FD))
    g = jax.random.normal(ks[3], (FB, FH, tq, FD))
    return q, k, v, g


def _backward(monkeypatch, budget, *, tq=FT, tk=FT, hkv=None, hooks=(),
              grad_dtype=None, **kw):
    """(trace records, (dq, dk, dv)) of one backward under a dq budget of
    ``budget`` bytes (0: always split)."""
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: (16, 16))
    monkeypatch.setattr(pa, '_FUSED_DQ_BYTES', budget)
    for hook in hooks:
        monkeypatch.setattr(pa, hook, True)
    q, k, v, g = _fused_inputs(tq, tk, hkv)
    with pa.flash_bwd_traces() as traces:
        if grad_dtype is None:
            _, vjp = jax.vjp(
                lambda q, k, v: flash_attention(q, k, v, **kw), q, k, v)
            grads = vjp(g)
        else:   # the ring path's call: float32 partials
            scale = 1.0 / np.sqrt(FD)
            causal = kw.pop('causal', False)
            out, lse = pa._flash_fwd_impl(q, k, v, None, 0, scale, causal,
                                          True, save_lse=True, **kw)
            grads = pa._flash_bwd_impl(q, k, v, None, 0, out, lse, g,
                                       scale, causal, True,
                                       grad_dtype=grad_dtype, **kw)
    return traces, grads


_SEG = (jnp.arange(FT) // 20, jnp.arange(FT) // 20)
_POS = (jnp.arange(FT)[::-1], jnp.arange(FT)[::-1])
FUSED_CASES = {
    'causal_trapezoid': dict(causal=True, hooks=['_TRAP_ON_INTERPRET']),
    'causal_full_grid': dict(causal=True),
    'window_banded': dict(causal=True, window=24,
                          hooks=['_BAND_ON_INTERPRET']),
    'gqa_window_banded': dict(causal=True, window=24, hkv=1,
                              hooks=['_BAND_ON_INTERPRET']),
    'gqa_trapezoid': dict(causal=True, hkv=1,
                          hooks=['_TRAP_ON_INTERPRET']),
    'alibi_trapezoid': dict(causal=True,
                            alibi_slopes=jnp.asarray([0.5, 0.25]),
                            hooks=['_TRAP_ON_INTERPRET']),
    'segments_trapezoid': dict(causal=True, segment_ids=_SEG,
                               hooks=['_TRAP_ON_INTERPRET']),
    'segments_full_grid': dict(segment_ids=_SEG),
    'ragged_q': dict(tq=56, tk=72),         # 3.5 x 4.5 blocks
    'ragged_causal': dict(causal=True, tq=72, tk=72),
    'row_offset_trapezoid': dict(causal=True, causal_offset=32,
                                 hooks=['_TRAP_ON_INTERPRET']),
    'positions': dict(positions=_POS),
    'dense_mask': dict(mask=True),
    'dropout': dict(causal=True, dropout_rate=0.25, dropout_seed=3),
    'int8': dict(causal=True, qk_quant='int8'),
    'grad_float32': dict(causal=True, grad_dtype=jnp.float32),
    'grad_float32_window': dict(causal=True, window=24,
                                grad_dtype=jnp.float32),
}


def _case_kwargs(case):
    kw = dict(FUSED_CASES[case])
    if kw.pop('mask', False):
        m = jax.random.bernoulli(jax.random.key(7), 0.3,
                                 (FB, FH, FT, FT))
        kw['mask'] = m.at[..., 0].set(False)
    return kw


@pytest.mark.parametrize('case', sorted(FUSED_CASES))
def test_fused_backward_matches_split(monkeypatch, case):
    """Same inputs, both forms: dq, dk and dv agree to the tolerance this
    file holds against ``_reference_math``, in the dtype asked."""
    tf, fused = _backward(monkeypatch, 1 << 20, **_case_kwargs(case))
    ts, split = _backward(monkeypatch, 0, **_case_kwargs(case))
    assert [t['form'] for t in tf] == ['fused'], tf
    assert tf[0]['reason'] is None and tf[0]['vmem_limit_bytes']
    assert [t['form'] for t in ts] == ['split'], ts
    assert 'budget' in ts[0]['reason']
    for a, b in zip(fused, split):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_fused_backward_matches_unfused_math(monkeypatch, causal):
    """The fused form against the plain jnp oracle, several blocks a
    side."""
    kw = dict(causal=causal,
              hooks=['_TRAP_ON_INTERPRET'] if causal else [])
    traces, fused = _backward(monkeypatch, 1 << 20, **kw)
    assert [t['form'] for t in traces] == ['fused']
    q, k, v, g = _fused_inputs()
    _, vjp = jax.vjp(lambda q, k, v: _reference_math(
        q, k, v, None, 1.0 / np.sqrt(FD), causal), q, k, v)
    for a, b in zip(fused, vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_fused_backward_fully_masked_row_gives_zero(monkeypatch):
    """A row with no attendable key: zero dq, finite everything, and the
    other rows' gradients as the split form gives them."""
    m = jax.random.bernoulli(jax.random.key(7), 0.3, (FB, FH, FT, FT))
    m = m.at[..., 0].set(False).at[:, :, 21, :].set(True)
    tf, fused = _backward(monkeypatch, 1 << 20, mask=m)
    _, split = _backward(monkeypatch, 0, mask=m)
    assert [t['form'] for t in tf] == ['fused']
    assert (np.asarray(fused[0])[:, :, 21] == 0).all()
    for a, b in zip(fused, split):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_backward_past_the_dq_budget_splits_and_says_so(monkeypatch):
    """The choice is a function of (Tq, d): the largest shape inside the
    budget is fused, the first past it takes the two kernels and the
    counter gives the reason."""
    budget = 80 * 128 * 4        # 5 blocks of 16 rows, 128 lanes, float32
    inside, _ = _backward(monkeypatch, budget, causal=True, tq=80, tk=80)
    past, grads = _backward(monkeypatch, budget, causal=True, tq=81, tk=81)
    assert [(t['form'], t['dq_bytes']) for t in inside] == [
        ('fused', budget)]
    assert [(t['form'], t['dq_bytes']) for t in past] == [
        ('split', 96 * 128 * 4)]
    assert 'past the' in past[0]['reason'] and str(budget) in \
        past[0]['reason']
    assert past[0]['vmem_limit_bytes'] is None
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


@pytest.mark.parametrize('only', ['dq', 'dkv'])
def test_backward_pass_asked_alone_is_split(monkeypatch, only):
    """``only='dq'`` / ``'dkv'`` (the beyond-cap chunking's calls) keep
    their own kernel whatever the budget."""
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: (16, 16))
    q, k, v, g = _fused_inputs()
    scale = 1.0 / np.sqrt(FD)
    out, lse = pa._flash_fwd_impl(q, k, v, None, 0, scale, True, True,
                                  save_lse=True)
    with pa.flash_bwd_traces() as traces:
        grads = pa._flash_bwd_impl(q, k, v, None, 0, out, lse, g, scale,
                                   True, True, only=only)
    assert [(t['form'], t['only']) for t in traces] == [('split', only)]
    assert only in traces[0]['reason']
    got = [x is not None for x in grads]
    assert got == ([True, False, False] if only == 'dq'
                   else [False, True, True])
