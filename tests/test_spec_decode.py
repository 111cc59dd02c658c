# -*- coding: utf-8 -*-
"""
Verify-k decode + acceptance-prefix rollback (models/decode.py,
ops/pallas_decode.py) — the kernel half of speculative decoding.

The contracts that make draft-verify decoding EXACT, each pinned here:

- a verify-k step (``decode_step`` with ``q (B, H, k, d)`` + per-slot
  ``counts``) is BIT-IDENTICAL per query row to running k sequential
  single-token steps *on the same impl* — that per-impl identity is
  what makes a speculative stream token-for-token the non-speculative
  stream, whatever the proposer guessed. It is exact for the programs
  the chip runs (``tests/test_tpu_hardware.py`` asserts the kernel's
  there); on XLA:CPU, and so in the Pallas interpreter, an M=1 dot and
  an M=k dot may round one float32 ulp apart, and those cases compare
  to 1e-6;
- the kernel and XLA verify-k formulations agree to the suite's float
  tolerance (exp2- vs exp-softmax rounding, same as the n=1 parity
  tests) while each stays bitwise-consistent with itself;
- acceptance-prefix rollback (``rollback_slots`` /
  ``paged_rollback_slots`` + ``PagePool.truncate``) leaves the cache
  bit-identical to having appended ONLY the accepted tokens — no
  residue from rejected proposals for any later read.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.models.decode import (
    PagePool, decode_step, init_cache, init_paged_cache,
    init_slot_cache, paged_rollback_slots, rollback_slots,
)

B, D, T = 2, 8, 32
K = 3                     # verify width (proposals per step)
PRE = [5, 9]              # staggered pre-fill per slot


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


def _prefill(cache, impl, steps=max(PRE), key=100, **kw):
    """Advance each slot to its PRE fill through n=1 steps of ``impl``
    (the per-impl oracle must build its prefix on the same impl)."""
    interp = True if impl == 'kernel' else None
    for i in range(steps):
        mask = jnp.asarray([i < p for p in PRE])
        h_kv = cache.k.shape[1]
        h = 2 * h_kv
        cache, _ = decode_step(
            _rand(key + 3 * i, (B, h, 1, D)), cache,
            _rand(key + 3 * i + 1, (B, h_kv, 1, D)),
            _rand(key + 3 * i + 2, (B, h_kv, 1, D)),
            slot_mask=mask, impl=impl, interpret=interp, **kw)
    return cache


def _sequential(cache, impl, q, kn, vn, counts, **kw):
    """The oracle: per slot, ``counts[i]`` single-token steps on the
    same impl. Returns (cache, outs (B, H, K, D) with don't-care rows
    left zero)."""
    interp = True if impl == 'kernel' else None
    outs = np.zeros(q.shape, np.float32)
    for j in range(K):
        mask = jnp.asarray([j < int(counts[i]) for i in range(B)])
        cache, o = decode_step(
            q[:, :, j:j + 1], cache, kn[:, :, j:j + 1],
            vn[:, :, j:j + 1], slot_mask=mask, impl=impl,
            interpret=interp, **kw)
        outs[:, :, j] = np.asarray(o, np.float32)[:, :, 0]
    return cache, outs


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
@pytest.mark.parametrize('h,h_kv,kw', [
    (2, 2, {}),                                            # MHA
    (4, 2, {}),                                            # GQA
    (4, 2, {'window': 8}),                                 # sliding
    (4, 2, {'alibi_slopes': tuple(2.0 ** -(i + 1)         # ALiBi
                                  for i in range(4))}),
])
def test_verify_k_matches_sequential_bitwise(impl, h, h_kv, kw):
    """One verify-k step == counts[i] sequential n=1 steps on the same
    impl, mixed counts across the batch: the cache BITWISE, the outputs
    bitwise where XLA:CPU takes one dot path for both and to one
    float32 ulp (1e-6) where it does not."""
    kw = dict(kw)
    if 'alibi_slopes' in kw:
        kw['alibi_slopes'] = jnp.asarray(kw['alibi_slopes'])
    cache0 = _prefill(init_slot_cache(B, h_kv, T, D, dtype=jnp.float32),
                      impl, **kw)
    q = _rand(0, (B, h, K, D))
    kn = _rand(1, (B, h_kv, K, D))
    vn = _rand(2, (B, h_kv, K, D))
    counts = jnp.asarray([K, K - 1], jnp.int32)
    ref_cache, ref_out = _sequential(cache0, impl, q, kn, vn, counts,
                                     **kw)
    interp = True if impl == 'kernel' else None
    cv, ov = decode_step(q, cache0, kn, vn, counts=counts, impl=impl,
                         interpret=interp, **kw)
    ov = np.asarray(ov, np.float32)
    for i in range(B):
        c = int(counts[i])
        if impl == 'kernel' or h == h_kv:
            # XLA:CPU lowers an M=1 dot as gemv and an M=k dot as gemm:
            # two accumulation orders, one float32 ulp apart. That
            # reaches the 'xla' impl at group 1 (GQA folds group·n rows
            # into M, so both shapes take the gemm path and stay
            # bitwise) and every 'kernel' case here: off the chip the
            # Pallas INTERPRETER evaluates the kernel's block dots with
            # the same XLA:CPU (max abs 1.2e-7). The kernel's program is
            # bitwise: tests/test_tpu_hardware.py::
            # test_verify_k_kernel_matches_sequential_bitwise_on_chip
            # asserts it where that program runs (chip, PR 28: MHA and
            # GQA, bfloat16 and float32, all equal).
            np.testing.assert_allclose(ov[i, :, :c], ref_out[i, :, :c],
                                       atol=1e-6, rtol=1e-6)
        else:
            np.testing.assert_array_equal(ov[i, :, :c],
                                          ref_out[i, :, :c])
    np.testing.assert_array_equal(np.asarray(cv.k),
                                  np.asarray(ref_cache.k))
    np.testing.assert_array_equal(np.asarray(cv.v),
                                  np.asarray(ref_cache.v))
    np.testing.assert_array_equal(np.asarray(cv.length),
                                  np.asarray(ref_cache.length))


def test_verify_k_kernel_vs_xla_tolerance():
    """Across impls the two verify-k formulations agree to the n=1
    parity tolerance (exp2 vs exp rounding — bit-identity is a
    per-impl guarantee, same as the engine's)."""
    h, h_kv = 4, 2
    cache0 = _prefill(init_slot_cache(B, h_kv, T, D,
                                      dtype=jnp.float32), 'xla')
    q = _rand(0, (B, h, K, D))
    kn = _rand(1, (B, h_kv, K, D))
    vn = _rand(2, (B, h_kv, K, D))
    counts = jnp.asarray([K, 1], jnp.int32)
    cx, ox = decode_step(q, cache0, kn, vn, counts=counts, impl='xla')
    ck, ok = decode_step(q, cache0, kn, vn, counts=counts,
                         impl='kernel')
    for i in range(B):
        c = int(counts[i])
        np.testing.assert_allclose(
            np.asarray(ok)[i, :, :c], np.asarray(ox)[i, :, :c],
            atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ck.k), np.asarray(cx.k),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ck.length),
                                  np.asarray(cx.length))


def test_verify_k_zero_count_slot_frozen():
    """counts[i] = 0 freezes the slot exactly like slot_mask=False: no
    append, length unchanged, buffers bit-identical."""
    h_kv = 2
    cache0 = _prefill(init_slot_cache(B, h_kv, T, D,
                                      dtype=jnp.float32), 'xla')
    q = _rand(0, (B, 4, K, D))
    kn = _rand(1, (B, h_kv, K, D))
    vn = _rand(2, (B, h_kv, K, D))
    counts = jnp.asarray([2, 0], jnp.int32)
    cv, _ = decode_step(q, cache0, kn, vn, counts=counts, impl='xla')
    assert [int(x) for x in cv.length] == [PRE[0] + 2, PRE[1]]
    np.testing.assert_array_equal(np.asarray(cv.k)[1],
                                  np.asarray(cache0.k)[1])


def test_verify_k_overflow_contract():
    """Concrete per-slot overflow raises eagerly naming the slot and
    the row count; traced overflow writes nothing while the length
    still advances (the append contract, verify-k width)."""
    cache = init_slot_cache(2, 2, 8, D, dtype=jnp.float32)
    cache = cache._replace(length=jnp.asarray([7, 1], jnp.int32))
    q = jnp.ones((2, 2, K, D))
    one = jnp.ones((2, 2, K, D))
    with pytest.raises(ValueError, match=r'slot 0.*3 new'):
        decode_step(q, cache, one, one, impl='xla')
    out_c, _ = jax.jit(
        lambda c, q, k, v: decode_step(q, c, k, v, impl='kernel',
                                       interpret=True)
    )(cache, q, one, one)
    assert [int(x) for x in out_c.length] == [10, 4]
    assert float(jnp.abs(out_c.k[0]).sum()) == 0.0       # wrote nothing
    assert float(jnp.abs(out_c.k[1]).sum()) > 0.0        # in-bounds did


# -- acceptance-prefix rollback ----------------------------------------

def test_rollback_bit_identical_to_accepted_only():
    """Append K proposals per slot, roll back to the accepted prefix:
    the cache must be BIT-IDENTICAL to having appended only the
    accepted rows (buffers, lengths — no rejected-row residue)."""
    h_kv = 2
    cache0 = _prefill(init_slot_cache(B, h_kv, T, D,
                                      dtype=jnp.float32), 'xla')
    q = _rand(0, (B, 4, K, D))
    kn = _rand(1, (B, h_kv, K, D))
    vn = _rand(2, (B, h_kv, K, D))
    accepted = [1, 2]
    ca, _ = decode_step(q, cache0, kn, vn, impl='xla')
    target = jnp.asarray(np.asarray(cache0.length) + accepted,
                         jnp.int32)
    cr = rollback_slots(ca, target)
    ref, _ = _sequential(cache0, 'xla', q, kn, vn,
                         jnp.asarray(accepted, jnp.int32))
    np.testing.assert_array_equal(np.asarray(cr.k), np.asarray(ref.k))
    np.testing.assert_array_equal(np.asarray(cr.v), np.asarray(ref.v))
    np.testing.assert_array_equal(np.asarray(cr.length),
                                  np.asarray(ref.length))
    # The surgical span path (the serving hot path: O(B·span·d)
    # scatter, not a full-cache rewrite) is bit-identical to the
    # full-mask path.
    cs = rollback_slots(ca, target, span=K)
    np.testing.assert_array_equal(np.asarray(cs.k), np.asarray(ref.k))
    np.testing.assert_array_equal(np.asarray(cs.v), np.asarray(ref.v))
    np.testing.assert_array_equal(np.asarray(cs.length),
                                  np.asarray(ref.length))


def test_rollback_sentinel_leaves_slots_untouched():
    """min(current, target): a past-fill sentinel rolls nothing back,
    so ONE batched call serves a few slots without disturbing the
    rest."""
    h_kv = 2
    cache = _prefill(init_slot_cache(B, h_kv, T, D,
                                     dtype=jnp.float32), 'xla')
    big = np.iinfo(np.int32).max
    cr = rollback_slots(cache, jnp.asarray([3, big], jnp.int32))
    assert [int(x) for x in cr.length] == [3, PRE[1]]
    np.testing.assert_array_equal(np.asarray(cr.k)[1],
                                  np.asarray(cache.k)[1])
    assert float(jnp.abs(np.asarray(cr.k)[0, :, 3:]).sum()) == 0.0


def test_rollback_int8_mirror():
    """Mirror-carrying caches roll the k_q/k_scale rows back with the
    K rows — a later int8 step must not dequantize rejected residue."""
    cache0 = init_cache(B, 2, T, D, dtype=jnp.float32, qk_quant='int8')
    kn = _rand(1, (B, 2, K, D))
    vn = _rand(2, (B, 2, K, D))
    q = _rand(0, (B, 4, K, D))
    ca, _ = decode_step(q, cache0, kn, vn, impl='xla',
                        qk_quant='int8')
    cr = rollback_slots(ca, jnp.asarray(1, jnp.int32))
    ref, _ = decode_step(q[:, :, :1], cache0, kn[:, :, :1],
                         vn[:, :, :1], impl='xla', qk_quant='int8')
    np.testing.assert_array_equal(np.asarray(cr.k_q),
                                  np.asarray(ref.k_q))
    np.testing.assert_array_equal(np.asarray(cr.k_scale),
                                  np.asarray(ref.k_scale))
    assert int(cr.length) == 1


def test_rollback_paged_raises():
    cache = init_paged_cache(B, 2, T, D, pages=4, page_size=8)
    with pytest.raises(ValueError, match='paged_rollback_slots'):
        rollback_slots(cache, jnp.asarray([0, 0], jnp.int32))


# -- paged verify + rollback -------------------------------------------

def _paged_setup(ps=8, pages=10):
    cache = init_paged_cache(B, 2, T, D, pages=pages, page_size=ps,
                             dtype=jnp.float32)
    pool = PagePool(pages, ps, B, T // ps)
    for i in range(max(PRE)):
        mask = np.array([i < p for p in PRE])
        for s in np.nonzero(mask)[0]:
            st, src, dst = pool.prepare_append(int(s))
            assert st in ('ok', 'alloc')
        cache = cache._replace(page_table=jnp.asarray(pool.table))
        cache, _ = decode_step(
            _rand(200 + 3 * i, (B, 4, 1, D)), cache,
            _rand(201 + 3 * i, (B, 2, 1, D)),
            _rand(202 + 3 * i, (B, 2, 1, D)),
            slot_mask=jnp.asarray(mask), impl='xla')
        pool.lengths[mask] += 1
    return cache, pool


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
def test_paged_verify_k_matches_sequential(impl):
    """Paged verify-k == sequential paged steps, bitwise per impl —
    the page-table BlockSpec redirect changes DMA, not math."""
    cache, pool = _paged_setup()
    for s in range(B):
        ok, copies = pool.reserve_rows(s, K)
        assert ok and not copies
    cache = cache._replace(page_table=jnp.asarray(pool.table))
    q = _rand(0, (B, 4, K, D))
    kn = _rand(1, (B, 2, K, D))
    vn = _rand(2, (B, 2, K, D))
    counts = jnp.asarray([K, 2], jnp.int32)
    ref_cache, ref_out = _sequential(cache, impl, q, kn, vn, counts)
    interp = True if impl == 'kernel' else None
    cv, ov = decode_step(q, cache, kn, vn, counts=counts, impl=impl,
                         interpret=interp)
    ov = np.asarray(ov, np.float32)
    for i in range(B):
        c = int(counts[i])
        np.testing.assert_array_equal(ov[i, :, :c], ref_out[i, :, :c])
    # Live pages only: the reserved SINK row (last pool page) parks
    # idle grid rows' mandatory block flushes — its bits are don't-care
    # garbage by contract and legitimately differ between schedules.
    pages = cv.pages
    np.testing.assert_array_equal(np.asarray(cv.k_pool)[:pages],
                                  np.asarray(ref_cache.k_pool)[:pages])
    np.testing.assert_array_equal(np.asarray(cv.v_pool)[:pages],
                                  np.asarray(ref_cache.v_pool)[:pages])


def test_paged_rollback_bit_identical_and_returns_pages():
    """Paged rollback: the pool is bit-identical to having appended
    only the accepted rows, and PagePool.truncate releases exactly the
    now-empty tail pages (refcounts back on the free list)."""
    cache, pool = _paged_setup(ps=4)
    for s in range(B):
        ok, _ = pool.reserve_rows(s, K)
        assert ok
    cache = cache._replace(page_table=jnp.asarray(pool.table))
    q = _rand(0, (B, 4, K, D))
    kn = _rand(1, (B, 2, K, D))
    vn = _rand(2, (B, 2, K, D))
    accepted = [0, 2]
    ca, _ = decode_step(q, cache, kn, vn, impl='xla')
    pool.lengths[:] += K
    pre = np.array(PRE)
    target = jnp.asarray(pre + accepted, jnp.int32)
    cr = paged_rollback_slots(ca, target, span=K)
    # Reference: only the accepted rows ever appended (fresh pool walk
    # over the same page tables — reserve_rows already mapped them).
    ref, _ = _sequential(cache, 'xla', q, kn, vn,
                         jnp.asarray(accepted, jnp.int32))
    np.testing.assert_array_equal(np.asarray(cr.k_pool),
                                  np.asarray(ref.k_pool))
    np.testing.assert_array_equal(np.asarray(cr.v_pool),
                                  np.asarray(ref.v_pool))
    np.testing.assert_array_equal(np.asarray(cr.length),
                                  np.asarray(ref.length))
    # Host side: truncate returns exactly the now-empty tail pages.
    free_before = pool.free_pages
    used_before = [pool.slot_pages(s) for s in range(B)]
    for s, tgt in enumerate(np.asarray(pre) + accepted):
        freed = pool.truncate(s, int(tgt))
        want = used_before[s] - pool.pages_for_rows(int(tgt))
        assert len(freed) == want
        assert pool.lengths[s] == tgt
    assert pool.free_pages >= free_before
    # A no-op truncate (target >= fill) frees nothing.
    assert pool.truncate(0, T) == []


def test_paged_truncate_returns_boundary_pages():
    """A rollback that retreats across a page boundary RETURNS the
    opened tail page: refcount to zero, back on the free list, the
    slot's table entry cleared."""
    ps = 4
    pool = PagePool(6, ps, 1, T // ps)
    ok, _ = pool.reserve_rows(0, 2 * ps)      # two full pages
    assert ok
    pool.lengths[0] = 2 * ps
    ok, _ = pool.reserve_rows(0, 3)           # verify-k opens page 3
    assert ok and pool.slot_pages(0) == 3
    pool.lengths[0] = 2 * ps + 3              # the verify appended
    free_before = pool.free_pages
    opened = int(pool.table[0, 2])
    freed = pool.truncate(0, 2 * ps)          # reject every proposal
    assert freed == [opened]
    assert pool.free_pages == free_before + 1
    assert pool.slot_pages(0) == 2
    assert int(pool.table[0, 2]) == -1
    assert pool.lengths[0] == 2 * ps
