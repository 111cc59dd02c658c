# -*- coding: utf-8 -*-
"""
Trapezoid causal pair-grid parity (ops/pallas_attention.py
``_trap_tables``/``_wrap_specs_pairs``): the flattened grid must be
bitwise identical to the full grid with in-kernel skipping, in both
directions, across the feature compositions it claims to support.

The pair grid needs the Mosaic interpreter off-TPU (scalar-prefetch index
maps), so these tests force it via the ``_TRAP_ON_INTERPRET`` hook and
keep shapes tiny. The pair grid on the chip: ``tests/test_tpu_hardware.py``
(parity) and the ``*.train-16k`` benchmark cells (speed, ``PERF.md``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_dot_product_tpu.ops.pallas_attention as pa

pytestmark = pytest.mark.slow

B, H, T, D = 1, 2, 64, 16


def _qkvg(key=0):
    ks = jax.random.split(jax.random.key(key), 4)
    return [jax.random.normal(k, (B, H, T, D)) for k in ks]


def _run(trap, monkeypatch, *, seg=None, drop=0.0, hkv=None, off=0,
         alibi=None):
    monkeypatch.setattr(pa, '_TRAP_ON_INTERPRET', trap)
    q, k, v, g = _qkvg()
    if hkv is not None:
        k, v = k[:, :hkv], v[:, :hkv]

    def f(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, causal_offset=off, segment_ids=seg,
            alibi_slopes=alibi, dropout_rate=drop,
            dropout_seed=3 if drop else None)

    out, vjp = jax.vjp(f, q, k, v)
    return (out, *vjp(g))


CASES = {
    'plain': {},
    'segments': {'seg': (jnp.arange(T) // 20, jnp.arange(T) // 20)},
    'dropout': {'drop': 0.25},
    'gqa': {'hkv': 1},
    'row_offset': {'off': 32},
    'alibi': {'alibi': jnp.asarray([0.5, 0.25])},
}


@pytest.mark.parametrize('case', sorted(CASES))
def test_trapezoid_matches_full_grid(monkeypatch, case):
    a = _run(True, monkeypatch, **CASES[case])
    b = _run(False, monkeypatch, **CASES[case])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_trap_tables_cover_exactly_the_triangle():
    """Every causally-relevant (Q block, K block) pair appears exactly
    once, in Q-major order with K ascending from 0 — the ordering the
    kernels' init/finalize conditions assume."""
    for rel, nqb, nkb, bq, bk in [(0, 7, 7, 8, 8), (16, 4, 6, 8, 8),
                                  (-8, 5, 5, 8, 8), (0, 3, 9, 16, 8)]:
        qtab, ktab, ext = (np.asarray(t)
                           for t in pa._trap_tables(rel, nqb, nkb, bq, bk))
        assert len(qtab) == len(ktab) == ext.sum()
        for qi in range(nqb):
            ks = ktab[qtab == qi]
            # contiguous run 0..ext-1; ext covers every K block with any
            # visible column (clamped to >= 1 so the output block writes)
            want = min(nkb, max(1, -(-(rel + (qi + 1) * bq) // bk)))
            assert list(ks) == list(range(want)), (rel, qi, ks)


def test_trap_tables_t_cover_exactly_the_triangle():
    for rel, nqb, nkb, bq, bk in [(0, 7, 7, 8, 8), (16, 4, 6, 8, 8),
                                  (0, 3, 9, 16, 8)]:
        qtab, ktab, qlo = (np.asarray(t) for t in
                           pa._trap_tables_t(rel, nqb, nkb, bq, bk))
        for kj in range(nkb):
            qs = qtab[ktab == kj]
            assert list(qs) == list(range(qlo[kj], nqb)), (rel, kj, qs)
            # first visible Q block: its last row reaches this K block
            lo = qlo[kj]
            if lo not in (0, nqb - 1):
                assert rel + (lo + 1) * bq - 1 >= kj * bk
                assert rel + lo * bq - 1 < kj * bk


def test_trap_eligibility_gates():
    """Traced offsets, windows, masks, positions and 'bounded' must all
    fall back to the full grid (the pair count would be dynamic, or the
    config has its own grid)."""
    assert pa._trap_eligible(True, None, None, None, 0, 0, 'exact', False)
    ok = pa._trap_eligible
    assert not ok(False, None, None, None, 0, 0, 'exact', False)
    assert not ok(True, 8, None, None, 0, 0, 'exact', False)   # window
    assert not ok(True, None, 'm', None, 0, 0, 'exact', False)  # mask
    assert not ok(True, None, None, 'p', 0, 0, 'exact', False)  # positions
    assert not ok(True, None, None, None, jnp.int32(0), 0, 'exact', False)
    assert not ok(True, None, None, None, 0, 0, 'bounded', False)
    assert not ok(True, None, None, None, 0, 0, 'exact', True)  # interp


def test_trap_with_kv_offset_static():
    """Static kv_offset (a caller whose K slab is a slice of a longer
    sequence) composes with the trapezoid."""
    q, k, v, g = _qkvg(1)
    half = T // 2

    def run(trap):
        import distributed_dot_product_tpu.ops.pallas_attention as m
        old = m._TRAP_ON_INTERPRET
        m._TRAP_ON_INTERPRET = trap
        try:
            out = pa.flash_attention(q, k[..., :half, :], v[..., :half, :],
                                     causal=True, causal_offset=16,
                                     kv_offset=8)
        finally:
            m._TRAP_ON_INTERPRET = old
        return out

    np.testing.assert_array_equal(np.asarray(run(True)),
                                  np.asarray(run(False)))


def test_chunked_trapezoid_matches_full_grid(monkeypatch):
    """Beyond-cap sequences split into Q-row chunks that each take the
    trapezoid (fwd: rows concat; bwd: dk/dv partials sum in fp32) — a
    tiny forced cap must still be bitwise identical to the full grid,
    with dropout and segments composed."""
    monkeypatch.setattr(pa, '_TRAP_ON_INTERPRET', True)
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v, g = (jax.random.normal(kk, (B, H, 96, D)) for kk in ks)
    seg = (jnp.arange(96) // 40, jnp.arange(96) // 40)

    def run(cap, trap):
        monkeypatch.setattr(pa, '_TRAP_MAX_PAIRS', cap)
        monkeypatch.setattr(pa, '_TRAP_ON_INTERPRET', trap)
        f = lambda q, k, v: pa.flash_attention(  # noqa: E731
            q, k, v, causal=True, segment_ids=seg, dropout_rate=0.25,
            dropout_seed=3)
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(g))

    a = run(8, True)            # forced chunking
    b = run(10 ** 9, False)     # plain full grid
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_chunk_bounds_cover_rows_exactly():
    import distributed_dot_product_tpu.ops.pallas_attention as m
    orig = m._TRAP_MAX_PAIRS
    try:
        m._TRAP_MAX_PAIRS = 10
        bounds = m._trap_chunk_bounds(0, 512, 512, 8, 8)
        assert bounds[0][0] == 0 and bounds[-1][1] == 512
        for (a0, a1), (b0, b1) in zip(bounds, bounds[1:]):
            assert a1 == b0 and a0 < a1
    finally:
        m._TRAP_MAX_PAIRS = orig


@pytest.mark.parametrize('case', sorted(CASES))
def test_trapezoid_by_kind_is_the_whole_mask_form(monkeypatch, case):
    """Several blocks a side on the pair grid: blocks under the diagonal
    go through the branch without the causal select
    (``_block_interior``), and output and gradients are bit for bit
    those of every block through the whole-mask branch. (Segment ids are
    data: that case has one branch either way.)"""
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: (16, 16))
    monkeypatch.setattr(pa, '_bwd_block_sizes', lambda *a, **k: (16, 16))
    with pa.flash_block_traces() as traces:
        a = _run(True, monkeypatch, **CASES[case])
    assert {t['grid'] for t in traces} == {'trap'}
    off = CASES[case].get('off', 0)
    blocks = [(i, j) for i in range(4) for j in range(4)]
    want = (sum(off + 16 * i + 15 >= 16 * j for i, j in blocks),
            sum(off + 16 * i >= 16 * j + 15 for i, j in blocks))
    if case == 'segments':      # data decides what runs; no kinds
        want = (None, 0)
    assert {(t['run_blocks'], t['interior_blocks'])
            for t in traces} == {want}
    monkeypatch.setattr(pa, '_block_interior', lambda *a, **k: None)
    b = _run(True, monkeypatch, **CASES[case])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
