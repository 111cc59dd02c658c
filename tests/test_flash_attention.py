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


# -- a block's position arithmetic by the block's kind -------------------
# A block whose every (row, column) pair is attendable by place is
# *interior* (``_block_interior``) and goes through a branch of the body
# without the causal / window / padding selects; a select that selects
# nothing is the identity, so every result is the whole-mask form's bit
# for bit. ALiBi's bias is a ``(1, bk)`` vector a block with the row's
# constant carried in the logsumexp's domain (``_alibi_bias``): the
# results of the form rebuilt from iotas to this file's tolerance.

def _brute_interior(causal, off_r, off_c, qi, ki, bq, bk, kv_len, window):
    rows = off_r + qi * bq + np.arange(bq)[:, None]
    cols_local = ki * bk + np.arange(bk)[None, :]
    cols = off_c + cols_local
    ok = np.broadcast_to(cols_local < kv_len, (bq, bk))
    if causal:
        ok = ok & (rows >= cols)
        if window is not None:
            ok = ok & (rows - cols < window)
    return bool(ok.all()), bool(ok.any())


@pytest.mark.parametrize('window', [None, 1, 7, 16, 24, 40])
@pytest.mark.parametrize('offsets', [(0, 0), (32, 0), (5, 0), (0, 24),
                                     (0, 40), (-19, 3)])
@pytest.mark.parametrize('blocks', [(16, 16), (8, 32), (32, 8)])
def test_interior_predicate_is_the_brute_force_all(blocks, offsets, window):
    """``_block_interior`` (and ``_causal_run`` beside it) against
    ``all()`` / ``any()`` over the block's (row, column) pairs: row and
    column offsets (negative ``rel`` too), ``bq != bk``, windows that are
    and are not multiples of the block, a ragged ``kv_len``."""
    bq, bk = blocks
    off = np.asarray([offsets])
    for kv_len in (64, 53):
        nqb, nkb = 64 // bq, -(-kv_len // bk)
        for qi in range(nqb):
            for ki in range(nkb):
                every, some = _brute_interior(True, *offsets, qi, ki, bq,
                                              bk, kv_len, window)
                inside = pa._block_interior(True, off, qi, ki, bq, bk,
                                            kv_len, window)
                run = pa._causal_run(True, off, qi, ki, bq, bk, window)
                assert bool(inside) == every, (qi, ki, kv_len)
                # run may keep a block the padding alone empties; it
                # never skips one with an attendable pair
                assert bool(run) or not some, (qi, ki, kv_len)
                assert bool(run) or not bool(inside)
    # not causal: only the padding has a place
    for ki in range(4):
        inside = pa._block_interior(False, off, 0, ki, bq, 16, 53, None)
        assert bool(inside) == ((ki + 1) * 16 <= 53)


def test_calls_without_a_kind_keep_one_branch():
    """Data masks (dense mask, segment ids, positions) and calls with
    nothing positional have no kinds: one branch, the whole body."""
    off = np.asarray([[0, 0]])
    assert pa._block_interior(True, off, 1, 0, 16, 16, 64, None,
                              data_masks=True) is None
    assert pa._block_interior(False, off, 1, 0, 16, 16, 64, None) is None
    assert pa._block_interior(False, off, 1, 0, 16, 16, 53, None) is not None


def _by_kind(monkeypatch, whole, *, blocks=(16, 32), bwd_blocks=(32, 16),
             budget=1 << 20, hooks=(), tq=FT, tk=FT, hkv=None,
             iota_alibi=False, **kw):
    """(out, lse, dq, dk, dv) with the bodies entered by kind, or
    (``whole``) with every block through the whole-mask branch."""
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: blocks)
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: bwd_blocks)
    monkeypatch.setattr(pa, '_FUSED_DQ_BYTES', budget)
    for hook in hooks:
        monkeypatch.setattr(pa, hook, True)
    if whole:
        monkeypatch.setattr(pa, '_block_interior', lambda *a, **k: None)
    if iota_alibi:
        monkeypatch.setattr(pa, '_alibi_bias', _iota_alibi_bias)
        monkeypatch.setattr(pa, '_alibi_row_shift',
                            lambda slope, bq: jnp.zeros((bq, 1)))
    q, k, v, g = _fused_inputs(tq, tk, hkv)
    scale = 1.0 / np.sqrt(FD)
    causal = kw.pop('causal', True)
    off = kw.pop('causal_offset', 0)
    out, lse = pa._flash_fwd_impl(q, k, v, None, off, scale, causal, True,
                                  save_lse=True, **kw)
    grads = pa._flash_bwd_impl(q, k, v, None, off, out, lse, g, scale,
                               causal, True, **kw)
    return (out, lse, *grads)


def _iota_alibi_bias(slope, qi, ki, bq, bk, off_ref, pos):
    """The whole bias an element, as every block rebuilt it before the
    vector form: rows and columns from iotas, an int32 difference, a
    convert, a multiply — and no row constant anywhere."""
    rows = (off_ref[0, 0] + qi * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    cols = (off_ref[0, 1] + ki * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
    return slope * (cols - rows).astype(jnp.float32)


_SLOPES = jnp.asarray([0.5, 0.0625])
KIND_CASES = {
    'causal_full': dict(),
    'causal_full_split_bwd': dict(budget=0),
    'causal_trap': dict(hooks=['_TRAP_ON_INTERPRET']),
    'causal_trap_split_bwd': dict(hooks=['_TRAP_ON_INTERPRET'], budget=0),
    'causal_square_blocks': dict(blocks=(16, 16), bwd_blocks=(16, 16),
                                 hooks=['_TRAP_ON_INTERPRET']),
    'window_full': dict(window=24),
    'window_full_split_bwd': dict(window=24, budget=0),
    'window_band': dict(window=24, hooks=['_BAND_ON_INTERPRET']),
    'window_band_split_bwd': dict(window=24, budget=0,
                                  hooks=['_BAND_ON_INTERPRET']),
    'window_not_a_block_multiple': dict(window=21,
                                        hooks=['_BAND_ON_INTERPRET']),
    'window_one': dict(window=1),
    'gqa_window_band': dict(window=24, hkv=1,
                            hooks=['_BAND_ON_INTERPRET']),
    'row_offset_trap': dict(causal_offset=32, tk=96,
                            hooks=['_TRAP_ON_INTERPRET']),
    'kv_offset_full': dict(kv_offset=16),
    'ragged_causal': dict(tq=72, tk=72),
    'ragged_window_band': dict(tq=72, tk=72, window=24,
                               hooks=['_BAND_ON_INTERPRET']),
    'ragged_not_causal': dict(causal=False, tq=56, tk=72),
    'alibi_trap': dict(alibi=_SLOPES, hooks=['_TRAP_ON_INTERPRET']),
    'alibi_window_band': dict(alibi=_SLOPES, window=24,
                              hooks=['_BAND_ON_INTERPRET']),
    'dropout_trap': dict(dropout_rate=0.25, dropout_seed=3,
                         hooks=['_TRAP_ON_INTERPRET']),
    'int8_full': dict(qk_quant='int8'),
}


@pytest.mark.parametrize('case', sorted(KIND_CASES))
def test_by_kind_is_the_whole_mask_form_bit_for_bit(monkeypatch, case):
    """Output, logsumexp and all three gradients, on the full, banded and
    trapezoid grids, fused and split backward. (int8 scores: to the last
    bit but one — with no select behind the two dequantization multiplies
    XLA's CPU backend, which runs the interpreter, contracts the second
    into the subtraction after it.)"""
    got = _by_kind(monkeypatch, False, **KIND_CASES[case])
    want = _by_kind(monkeypatch, True, **KIND_CASES[case])
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        if case.startswith('int8'):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('case', ['alibi_trap', 'alibi_window_band',
                                  'alibi_full', 'alibi_row_offset',
                                  'alibi_split_bwd', 'alibi_ragged'])
def test_alibi_vector_form_is_the_iota_form(monkeypatch, case):
    """A ``(1, bk)`` vector a block, the row's constant taken out of the
    logsumexp at the forward's end and put back by every backward block,
    gives what ``s + slope · float(cols − rows)`` rebuilt from iotas an
    element gives: output, logsumexp (the TRUE one: the row's constant
    must not show in it), dq, dk, dv, to this file's tolerance."""
    kw = dict(KIND_CASES.get(case, {}), alibi=_SLOPES)
    kw.update({'alibi_row_offset': dict(causal_offset=32, tk=96),
               'alibi_split_bwd': dict(budget=0),
               'alibi_ragged': dict(tq=72, tk=72)}.get(case, {}))
    got = _by_kind(monkeypatch, False, **kw)
    want = _by_kind(monkeypatch, True, iota_alibi=True, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize('window', [None, 24])
def test_by_kind_under_a_traced_offset_in_shard_map(monkeypatch, devices,
                                                    window):
    """Sequence-sharded callers: each shard's row offset is
    ``axis_index · T/N``, a traced scalar, so the kind is decided at run
    time like ``run``. Same bits as the whole-mask form, forward and
    backward."""
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: (16, 16))
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: (16, 16))
    mesh = seq_mesh(2)
    q, k, v, g = _fused_inputs()

    def local(q, k, v, g):
        off = jax.lax.axis_index('seq') * q.shape[-2]
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, causal_offset=off, window=window,
                alibi_slopes=_SLOPES), q, k, v)
        dq, dk, dv = vjp(g)
        return out, dq, jax.lax.psum(dk, 'seq'), jax.lax.psum(dv, 'seq')

    rows, rep = P(None, None, 'seq', None), P()

    def run():
        with pa.flash_block_traces() as traces:
            res = jax.shard_map(local, mesh=mesh,
                                in_specs=(rows, rep, rep, rows),
                                out_specs=(rows, rows, rep, rep),
                                check_vma=False)(q, k, v, g)
        return traces, res

    traces, got = run()
    assert traces and all(t['run_blocks'] is None
                          and t['interior_blocks'] is None
                          and t['alibi'] == 'vector' for t in traces)
    monkeypatch.setattr(pa, '_block_interior', lambda *a, **k: None)
    _, want = run()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _cell_call(heads, kv_heads, **kw):
    """Trace (nothing runs) the forward and backward of one layer of a
    training cell at T 16384, d 128, as the chip would take it."""
    q = jax.ShapeDtypeStruct((1, heads, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, kv_heads, 16384, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False, **kw),
                       dtype=jnp.float32)

    with pa.flash_block_traces() as traces:
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    return traces


@pytest.mark.parametrize('cell', ['mpt-7b.train-16k',
                                  'starcoder2-3b.train-16k'])
def test_block_counter_at_the_training_cells_shapes(cell):
    """``flash_block_traces()``: 120 of MPT's 136 run blocks a head are
    interior (trapezoid grid, ALiBi in the vector form), 42 of StarCoder2's 70
    (banded grid), forward and fused backward alike."""
    if cell.startswith('mpt'):
        traces = _cell_call(32, 32, alibi_slopes=jnp.ones((32,)))
        want = dict(grid='trap', run_blocks=136, interior_blocks=120,
                    alibi='vector')
    else:
        traces = _cell_call(24, 2, window=4096)
        want = dict(grid='band', run_blocks=70, interior_blocks=42,
                    alibi=None)
    assert traces == [dict(want, kernel='flash_fwd'),
                      dict(want, kernel='flash_bwd_fused')]


@pytest.mark.parametrize('case', ['segments', 'positions', 'dense_mask',
                                  'not_causal', 'ragged_not_causal',
                                  'split_backward'])
def test_block_counter_says_what_has_no_kind(monkeypatch, case):
    """Calls whose masks are data count no interior block (their body is
    whole) and no run blocks (data decides); a call with nothing
    positional runs every block through one branch."""
    kw = dict({'segments': dict(causal=True, segment_ids=_SEG),
               'positions': dict(positions=_POS, alibi_slopes=_SLOPES),
               'dense_mask': dict(causal=True, mask=True),
               'not_causal': dict(),
               'ragged_not_causal': dict(tq=56, tk=72),
               'split_backward': dict(causal=True)}[case])
    if kw.pop('mask', False):
        kw['mask'] = jnp.zeros((FB, FH, FT, FT), bool)
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: (16, 16))
    with pa.flash_block_traces() as traces:
        _backward(monkeypatch, 0 if case == 'split_backward' else 1 << 20,
                  **kw)
    kernels = [t['kernel'] for t in traces]
    if case == 'split_backward':
        assert kernels == ['flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv']
        assert {(t['run_blocks'], t['interior_blocks'])
                for t in traces} == {(10, 6)}
        return
    assert kernels == ['flash_fwd', 'flash_bwd_fused']
    for t in traces:
        assert t['grid'] == 'full'
        if case in ('segments', 'positions', 'dense_mask'):
            assert (t['run_blocks'], t['interior_blocks']) == (None, 0)
        elif case == 'not_causal':
            assert (t['run_blocks'], t['interior_blocks']) == (16, 0)
        else:       # the ragged last K block alone is a boundary block
            assert (t['run_blocks'], t['interior_blocks']) == (20, 16)
        assert t['alibi'] == ('positions' if case == 'positions' else None)
