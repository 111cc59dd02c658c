# -*- coding: utf-8 -*-
"""
Fused Pallas decode kernel (ops/pallas_decode.py) — parity and alias
safety. The oracle is the existing XLA formulation (``append_kv*`` +
``decode_attention``), pinned bit-for-tolerance across batch, heads,
GQA, int8, per-slot lengths, window and ALiBi; the alias tests pin the
in-place contract — ONE cache block written per step, every other bit
untouched, and nothing stale after an eviction. On the CPU mesh the
kernel runs under the Pallas interpreter (the same code path the TPU
compiles), exactly like the training-kernel suites.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.models.decode import (
    append_kv, append_kv_slots, decode_kernel_eligible, decode_step,
    init_cache, init_slot_cache, reset_slot,
)
from distributed_dot_product_tpu.ops.pallas_decode import decode_block_k

B, D, T = 3, 8, 16
LENS = [5, 9, 0]        # staggered slot fills, incl. an empty slot


def _operands(h, h_kv, key=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(ks[0], (B, h, 1, D), dtype)
    kn = jax.random.normal(ks[1], (B, h_kv, 1, D), dtype)
    vn = jax.random.normal(ks[2], (B, h_kv, 1, D), dtype)
    kf = jax.random.normal(ks[3], (B, h_kv, T, D), dtype)
    vf = jax.random.normal(ks[4], (B, h_kv, T, D), dtype)
    return q, kn, vn, kf, vf


def _filled(h_kv, kf, vf, lens=LENS, dtype=jnp.float32):
    cache = init_slot_cache(B, h_kv, T, D, dtype=dtype)
    return append_kv_slots(cache, kf, vf,
                           counts=jnp.asarray(lens, jnp.int32))


def _both(q, cache_fn, kn, vn, **kw):
    cx, ox = decode_step(q, cache_fn(), kn, vn, impl='xla', **kw)
    ck, ok = decode_step(q, cache_fn(), kn, vn, impl='kernel', **kw)
    return (cx, ox), (ck, ok)


def _assert_cache_match(ck, cx):
    np.testing.assert_array_equal(np.asarray(ck.length),
                                  np.asarray(cx.length))
    for name in ('k', 'v', 'k_q', 'k_scale'):
        a, b = getattr(ck, name), getattr(cx, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, err_msg=name)


@pytest.mark.parametrize('h,h_kv', [(2, 2), (4, 2), (4, 1)])
@pytest.mark.parametrize('kw', [{}, {'window': 4}])
def test_kernel_matches_xla_per_slot(h, h_kv, kw):
    """Per-slot staggered lengths (incl. an empty slot), MHA/GQA/MQA,
    with and without a sliding window."""
    q, kn, vn, kf, vf = _operands(h, h_kv)
    (cx, ox), (ck, ok) = _both(q, lambda: _filled(h_kv, kf, vf),
                               kn, vn, **kw)
    _assert_cache_match(ck, cx)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=1e-5, rtol=1e-5)


def test_kernel_matches_xla_alibi():
    h = 4
    q, kn, vn, kf, vf = _operands(h, 2, key=1)
    slopes = jnp.asarray([2.0 ** -(i + 1) for i in range(h)])
    (cx, ox), (ck, ok) = _both(q, lambda: _filled(2, kf, vf), kn, vn,
                               alibi_slopes=slopes)
    _assert_cache_match(ck, cx)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=1e-5, rtol=1e-5)


def test_kernel_matches_xla_slot_mask():
    """Frozen slots append nothing and attend their un-advanced prefix;
    the kernel and XLA steps agree on buffers, lengths AND outputs."""
    q, kn, vn, kf, vf = _operands(2, 2, key=2)
    mask = jnp.asarray([True, False, True])
    (cx, ox), (ck, ok) = _both(q, lambda: _filled(2, kf, vf), kn, vn,
                               slot_mask=mask)
    _assert_cache_match(ck, cx)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=1e-5, rtol=1e-5)


def test_kernel_matches_xla_scalar_cache_bf16():
    """Scalar-length cache (one clock for the whole batch), bf16
    buffers — the greedy-generation configuration."""
    q, kn, vn, kf, vf = _operands(2, 2, key=3, dtype=jnp.bfloat16)

    def cache_fn():
        c = init_cache(B, 2, T, D, dtype=jnp.bfloat16)
        return append_kv(c, kf[:, :, :6], vf[:, :, :6])

    (cx, ox), (ck, ok) = _both(q, cache_fn, kn, vn)
    _assert_cache_match(ck, cx)
    np.testing.assert_allclose(np.asarray(ok, dtype=np.float32),
                               np.asarray(ox, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)
    assert int(ck.length) == 7


def test_kernel_matches_xla_int8_mirror():
    """int8-trained decode through the append-time K mirror: the kernel
    dequantizes in-place-streamed int8 blocks and must reproduce the
    XLA mirror path's logits — and maintain the mirror bit-identically
    (rows quantize once, at append)."""
    q, kn, vn, kf, vf = _operands(4, 2, key=4)

    def cache_fn():
        c = init_cache(B, 2, T, D, dtype=jnp.float32, qk_quant='int8')
        return append_kv(c, kf[:, :, :9], vf[:, :, :9])

    (cx, ox), (ck, ok) = _both(q, cache_fn, kn, vn, qk_quant='int8')
    np.testing.assert_array_equal(np.asarray(ck.k_q),
                                  np.asarray(cx.k_q))
    np.testing.assert_allclose(np.asarray(ck.k_scale),
                               np.asarray(cx.k_scale), atol=1e-7)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=1e-5, rtol=1e-5)


def test_kernel_first_token_empty_cache():
    """Length-0 slots appending their first row attend exactly that row
    — out = v_new per head group, no NaN from the empty prefix."""
    q, kn, vn, _, _ = _operands(4, 2, key=5)
    cache = init_slot_cache(B, 2, T, D, dtype=jnp.float32)
    ck, ok = decode_step(q, cache, kn, vn, impl='kernel')
    want = jnp.repeat(vn, 2, axis=1)        # softmax over one column
    np.testing.assert_allclose(np.asarray(ok), np.asarray(want),
                               atol=1e-6)
    assert [int(x) for x in ck.length] == [1, 1, 1]


def test_kernel_alias_in_place_and_surgical():
    """The in-place append contract: exactly one row changes per slot —
    every other bit of every buffer is IDENTICAL before/after."""
    q, kn, vn, kf, vf = _operands(2, 2, key=6)
    before = _filled(2, kf, vf)
    ck, _ = decode_step(q, before, kn, vn, impl='kernel')
    bk, bv = np.asarray(before.k), np.asarray(before.v)
    ak, av = np.asarray(ck.k), np.asarray(ck.v)
    for i, ln in enumerate(LENS):
        np.testing.assert_array_equal(ak[i, :, :ln], bk[i, :, :ln])
        np.testing.assert_array_equal(ak[i, :, ln + 1:],
                                      bk[i, :, ln + 1:])
        np.testing.assert_array_equal(ak[i, :, ln],
                                      np.asarray(kn)[i, :, 0])
        np.testing.assert_array_equal(av[i, :, ln],
                                      np.asarray(vn)[i, :, 0])
    # Several KV heads a grid step on a layer-stacked buffer: of the
    # WHOLE stack only layer 1's append rows change their bits.
    from distributed_dot_product_tpu.ops.pallas_decode import (
        decode_geometry, flash_decode,
    )
    assert decode_geometry(T, 2, D, D, 1, jnp.float32,
                           jnp.float32).heads == 2
    stack_k = jnp.stack([before.k, before.k + 1.0, before.k + 2.0])
    stack_v = jnp.stack([before.v, before.v - 1.0, before.v - 2.0])
    ap = jnp.asarray([5, 9, -1], jnp.int32)      # slot 2 appends nothing
    _, sk, sv, _, _ = flash_decode(q, kn, vn, stack_k, stack_v,
                                   jnp.asarray(LENS, jnp.int32), ap,
                                   layer=jnp.int32(1))
    for got, old, new in ((sk, stack_k, kn), (sv, stack_v, vn)):
        want = np.array(old)
        for i, col in enumerate(np.asarray(ap)):
            if col >= 0:
                want[1, i, :, col] = np.asarray(new)[i, :, 0]
        np.testing.assert_array_equal(np.asarray(got), want)


def test_kernel_not_stale_after_eviction():
    """Evict a filled slot (reset_slot), serve a fresh sequence through
    fused steps: the attention must see ONLY the new rows (a stale
    block would poison the new stream bit-visibly)."""
    q, kn, vn, kf, vf = _operands(2, 2, key=7)
    cache = _filled(2, kf, vf, lens=[12, 3, 7])
    cache = reset_slot(cache, 0)
    only0 = jnp.asarray([True, False, False])
    # Two fused steps land rows 0 and 1 of the fresh sequence.
    cache, _ = decode_step(q, cache, kn, vn, slot_mask=only0,
                           impl='kernel')
    cache, out = decode_step(q, cache, kn + 1.0, vn + 1.0,
                             slot_mask=only0, impl='kernel')
    # Oracle: the same two rows alone in a fresh single-slot cache.
    solo = init_slot_cache(1, 2, T, D, dtype=jnp.float32)
    solo, _ = decode_step(q[:1], solo, kn[:1], vn[:1], impl='xla')
    solo, want = decode_step(q[:1], solo, kn[:1] + 1.0, vn[:1] + 1.0,
                             impl='xla')
    np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert [int(x) for x in cache.length] == [2, 3, 7]
    # The evicted slot's tail is still zero — nothing stale survived.
    assert float(jnp.abs(cache.k[0, :, 2:]).sum()) == 0.0


def test_kernel_overflow_contract():
    """Traced overflow: the fused step writes NOTHING for a full slot
    while its length still advances (append_kv_slots' contract);
    concrete overflow raises eagerly naming the slot."""
    cache = init_slot_cache(2, 2, 4, D, dtype=jnp.float32)
    cache = cache._replace(length=jnp.asarray([4, 1], jnp.int32))
    q = jnp.ones((2, 2, 1, D))
    one = jnp.ones((2, 2, 1, D))
    with pytest.raises(ValueError, match='slot 0'):
        decode_step(q, cache, one, one, impl='kernel')
    out_c, _ = jax.jit(
        lambda c, q, k, v: decode_step(q, c, k, v, impl='kernel')
    )(cache, q, one, one)
    assert [int(x) for x in out_c.length] == [5, 2]
    assert float(jnp.abs(out_c.k[0]).sum()) == 0.0       # wrote nothing
    assert float(jnp.abs(out_c.k[1]).sum()) > 0.0        # in-bounds did


def test_kernel_eligibility_and_fallback():
    """The kernel covers the serving hot path; everything else resolves
    to the XLA step under 'auto' and refuses under 'kernel'."""
    assert decode_block_k(16) == 16
    assert decode_block_k(131072) == 1024
    assert decode_block_k(3 * 1024) == 1024
    assert decode_block_k(1027) is None              # prime > cap
    cache = init_slot_cache(B, 2, T, D, dtype=jnp.float32)
    assert decode_kernel_eligible(cache)
    # Verify-k: n up to the K split is kernel-native; wider calls and
    # quantized verify-k fall back to the XLA formulation.
    assert decode_kernel_eligible(cache, n=2)
    assert decode_kernel_eligible(cache, n=decode_block_k(T))
    assert not decode_kernel_eligible(cache, n=decode_block_k(T) + 1)
    assert not decode_kernel_eligible(cache, n=0)
    assert not decode_kernel_eligible(cache, segment_ids=jnp.zeros(
        (B, T), jnp.int32))
    assert not decode_kernel_eligible(cache, qk_quant='int8')  # no mirror
    mirror = init_cache(B, 2, T, D, dtype=jnp.float32, qk_quant='int8')
    assert decode_kernel_eligible(mirror, qk_quant='int8')
    assert not decode_kernel_eligible(mirror, n=2, qk_quant='int8')
    q, kn, vn, kf, vf = _operands(2, 2, key=8)
    seg = jnp.zeros((B, T), jnp.int32)
    seg_q = jnp.zeros((B, 1), jnp.int32)
    with pytest.raises(ValueError, match='fused kernel'):
        decode_step(q, _filled(2, kf, vf), kn, vn, impl='kernel',
                    segment_ids=seg, seg_q=seg_q)
    # auto + segments: falls back, matches the explicit XLA step.
    ca, oa = decode_step(q, _filled(2, kf, vf), kn, vn, impl='auto',
                         segment_ids=seg, seg_q=seg_q)
    cx, ox = decode_step(q, _filled(2, kf, vf), kn, vn, impl='xla',
                         segment_ids=seg, seg_q=seg_q)
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(ox))


def test_module_decode_kernel_matches_xla():
    """Module surface: projections + GQA + RoPE + fused kernel step ==
    the XLA step, token by token (decode_impl is the only delta)."""
    from distributed_dot_product_tpu import DistributedDotProductAttn
    dim = 32
    kw = dict(key_dim=dim, num_heads=4, num_kv_heads=2, causal=True,
              use_rope=True, distributed=False)
    mx = DistributedDotProductAttn(decode_impl='xla', **kw)
    mk = DistributedDotProductAttn(decode_impl='kernel', **kw)
    x = jax.random.normal(jax.random.key(0), (2, 8, dim), jnp.float32)
    params = mx.init(jax.random.key(1), x, x, x, None)
    cx = mx.make_decode_cache(2, 8)
    ck = mk.make_decode_cache(2, 8)
    for t in range(4):
        xt = x[:, t:t + 1]
        cx, ox = mx.apply(params, xt, xt, xt, cx, method='decode')
        ck, ok = mk.apply(params, xt, xt, xt, ck, method='decode')
        np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f't={t}')
    np.testing.assert_allclose(np.asarray(ck.k), np.asarray(cx.k),
                               atol=1e-6)


def test_engine_kernel_path_streams():
    """KernelEngine on the fused kernel path: same slot lifecycle and
    (to greedy-argmax stability at these magnitudes) the same token
    streams as the XLA path."""
    from distributed_dot_product_tpu.serve import KernelEngine

    def drive(impl):
        eng = KernelEngine(slots=3, t_max=32, vocab=16, heads=2,
                           head_dim=4, prefill_chunk=4, seed=5,
                           decode_impl=impl)
        eng.prefill(0, [1, 2, 3])
        eng.prefill(1, [4, 5])
        toks = np.array([3, 5, 0], np.int32)
        act = np.array([True, True, False])
        stream = []
        for _ in range(6):
            toks, fin = eng.step(toks, act)
            assert fin.all()
            stream.append(toks.copy())
        return eng.lengths(), stream

    lens_x, stream_x = drive('xla')
    lens_k, stream_k = drive('kernel')
    np.testing.assert_array_equal(lens_k, lens_x)
    for a, b in zip(stream_x, stream_k):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Stacked addressing: flash_decode(layer=l) on a layer-stacked buffer
# ---------------------------------------------------------------------------

_LAYERS = 3
_STACKED_CASES = {
    'mha': (2, 2, {}),
    'gqa-window-alibi': (4, 2, {'window': 5,
                                'alibi_slopes': (0.5, 0.25, 0.125, 0.0625)}),
    'int8-mirror': (4, 2, {'qk_quant': 'int8'}),
}


def _stacked_buffers(h_kv, quant):
    """``_LAYERS`` layers of distinct cache contents, stacked; with
    ``quant`` the K mirror too (as ``append_kv`` keeps it)."""
    layers = []
    for l in range(_LAYERS):
        _, _, _, kf, vf = _operands(2, h_kv, key=20 + l)
        cache = init_cache(B, h_kv, T, D, dtype=jnp.float32,
                           qk_quant='int8' if quant else None)
        layers.append(append_kv(cache, kf, vf))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


@pytest.mark.parametrize('layer', range(_LAYERS))
@pytest.mark.parametrize('case', sorted(_STACKED_CASES))
def test_flash_decode_layer_addresses_stack_in_place(case, layer):
    """``flash_decode(layer=l)`` on the stacked buffers is
    ``flash_decode`` on layer l's, bit for bit — output, appended
    buffers, mirror — and every other layer keeps its bits. The layer
    index is traced (it is a scan's counter in the model)."""
    from distributed_dot_product_tpu.ops.pallas_decode import flash_decode
    h, h_kv, kw = _STACKED_CASES[case]
    quant = kw.get('qk_quant') == 'int8'
    q, kn, vn, _, _ = _operands(h, h_kv, key=7)
    stack = _stacked_buffers(h_kv, quant)
    vt = jnp.asarray([5, 9, 0], jnp.int32)
    ap = jnp.asarray([5, 9, -1], jnp.int32)      # slot 2 appends nothing

    def mirror(c):
        return dict(k_q=c.k_q, k_scale=c.k_scale) if quant else {}

    one = jax.tree.map(lambda x: x[layer], stack)
    want = jax.jit(lambda: flash_decode(
        q, kn, vn, one.k, one.v, vt, ap, **mirror(one), **kw))()
    got = jax.jit(lambda l: flash_decode(
        q, kn, vn, stack.k, stack.v, vt, ap, layer=l, **mirror(stack),
        **kw))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    before = (stack.k, stack.v, stack.k_q, stack.k_scale)
    for new, new_one, old in zip(got[1:], want[1:], before):
        assert (new is None) == (new_one is None)
        if new is None:
            continue
        assert new.shape == old.shape
        np.testing.assert_array_equal(np.asarray(new[layer]),
                                      np.asarray(new_one))
        others = [l for l in range(_LAYERS) if l != layer]
        np.testing.assert_array_equal(np.asarray(new)[others],
                                      np.asarray(old)[others])


def test_flash_decode_layer_argument_checks():
    """``layer`` goes with a stacked slab buffer only: not with a page
    table, and a stacked buffer not without it."""
    from distributed_dot_product_tpu.models.decode import init_paged_cache
    from distributed_dot_product_tpu.ops.pallas_decode import flash_decode
    q, kn, vn, kf, vf = _operands(2, 2, key=8)
    vt = ap = jnp.zeros((B,), jnp.int32)
    paged = init_paged_cache(B, 2, T, D, pages=6, page_size=8,
                             dtype=jnp.float32)
    with pytest.raises(ValueError, match='layer'):
        flash_decode(q, kn, vn, paged.k_pool, paged.v_pool, vt, ap,
                     page_table=paged.page_table, layer=0)
    with pytest.raises(ValueError, match='layer'):
        flash_decode(q, kn, vn, kf[None], vf[None], vt, ap)
    with pytest.raises(ValueError, match='layer'):
        flash_decode(q, kn, vn, kf, vf, vt, ap, layer=0)


# ---------------------------------------------------------------------------
# The grid step's geometry: several KV heads a step, the new rows
# substituted in the one split that holds them, a sublane-tile write-back
# ---------------------------------------------------------------------------

_GT, _GBK = 64, 16                  # four K splits of two f32 tiles each
# The append row in the first block, straddling blocks 1 -> 2 (n = 3),
# in the last block's second tile; slot 3 is frozen (append_at = -1).
_GLENS = (3, 31, 60, 40)
_GFROZEN = 3
_GEOMETRIES = [(4, 4, 1), (4, 4, 2), (4, 4, 4),      # MHA
               (4, 2, 1), (4, 2, 2),                 # GQA 4:2
               (4, 1, 1)]                            # 4:1


def _geometry_case(h, h_kv, n, stacked, key=30):
    ks = jax.random.split(jax.random.key(key), 6)
    b = len(_GLENS)
    q = jax.random.normal(ks[0], (b, h, n, D), jnp.float32)
    kn = jax.random.normal(ks[1], (b, h_kv, n, D), jnp.float32)
    vn = jax.random.normal(ks[2], (b, h_kv, n, D), jnp.float32)
    layers = []
    for l in range(2 if stacked else 1):
        kf = jax.random.normal(ks[3 + l], (b, h_kv, _GT, D), jnp.float32)
        vf = jax.random.normal(ks[5], (b, h_kv, _GT, D), jnp.float32) + l
        layers.append(append_kv_slots(
            init_slot_cache(b, h_kv, _GT, D, dtype=jnp.float32), kf, vf,
            counts=jnp.asarray(_GLENS, jnp.int32)))
    cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *layers) if stacked
             else layers[0])
    return q, kn, vn, cache


@pytest.mark.parametrize('stacked', [False, True], ids=['layer', 'stack'])
@pytest.mark.parametrize('n', [1, 3])
@pytest.mark.parametrize('alibi', [False, True], ids=['plain', 'alibi'])
@pytest.mark.parametrize('window', [None, 20], ids=['full', 'window'])
@pytest.mark.parametrize('h,h_kv,hb', _GEOMETRIES)
def test_kernel_matches_xla_at_every_geometry(monkeypatch, h, h_kv, hb,
                                              window, alibi, n, stacked):
    """The kernel against the XLA formulation at every grid step the
    geometry function can return for these shapes (``hb`` KV heads a
    step: the stream budget is lowered until it returns that ``hb``),
    over four K splits, with the append in the first block, across a
    block boundary (n = 3) and in the last block, one slot frozen
    (``append_at = -1``), on one layer's buffers and on ``layer=`` of a
    stack (the other layer keeps its bits)."""
    from distributed_dot_product_tpu.ops import pallas_decode
    one_head = pallas_decode.decode_geometry(
        _GT, 1, D, D, n * h // h_kv, jnp.float32, jnp.float32, n=n,
        block_k=_GBK).bytes
    monkeypatch.setattr(pallas_decode, '_STEP_STREAM_BYTES',
                        hb * one_head)
    geom = pallas_decode.decode_geometry(
        _GT, h_kv, D, D, n * h // h_kv, jnp.float32, jnp.float32, n=n,
        block_k=_GBK)
    assert (geom.heads, geom.block_k) == (hb, _GBK)
    assert geom.write_rows == (8 if n == 1 else _GBK)
    q, kn, vn, cache = _geometry_case(h, h_kv, n, stacked)
    layer = 1 if stacked else None
    kw = dict(window=window)
    if alibi:
        kw['alibi_slopes'] = tuple(2.0 ** -(i + 1) for i in range(h))
    mask = jnp.arange(len(_GLENS)) != _GFROZEN
    cx, ox = decode_step(q, cache, kn, vn, slot_mask=mask, impl='xla',
                         layer=layer, **kw)
    lens = jnp.asarray(_GLENS, jnp.int32)
    vt = jnp.where(mask, lens, lens - n)
    ap = jnp.where(mask, lens, -1)
    ok, nk, nv, _, _ = pallas_decode.flash_decode(
        q, kn, vn, cache.k, cache.v, vt, ap, block_k=_GBK,
        layer=None if layer is None else jnp.int32(layer), **kw)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=2e-5, rtol=2e-5)
    # The appended buffers bit for bit (the XLA step's are a scatter of
    # the same rows), every other layer included.
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(cx.k))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(cx.v))


# ---------------------------------------------------------------------------
# The tail: where no more than ``geom.tail`` rows are filled of the split
# that holds a slot's last column, those rows alone are moved and scored
# ---------------------------------------------------------------------------

_TT, _TBK, _TSUB = 3072, 1024, 256      # three real splits, the tail's rows
# Rows a slot holds before the step (its new row lands at that column):
# around a split's start, around the tail's last row, the buffer's end.
# (and, for the latent buffer's lane tiles, the append in a tile's last
# column and in the next one's first: 128 and 129 columns into the split)
_TAIL_FILLS = {'k.bk-1': 2 * _TBK - 1, 'k.bk': 2 * _TBK,
               'k.bk+1': 2 * _TBK + 1, '+lane-1': 2 * _TBK + 127,
               '+lane': 2 * _TBK + 128, '+sub-1': 2 * _TBK + _TSUB - 1,
               '+sub': 2 * _TBK + _TSUB, '+sub+1': 2 * _TBK + _TSUB + 1,
               't_max-1': _TT - 1}


def _tail_case(kind, fill, key=50, d=128):
    """Three slots: the fill under test; a slot inside its first split
    (never a tail); a FROZEN slot 76 rows into its second split (a tail
    that appends nothing). ``latent`` / ``latent-one``: one buffer of
    one shared head, layer-stacked / one layer's; every column past a
    length holds what a longer session left there (a rewound length)."""
    ks = jax.random.split(jax.random.key(key), 5)
    h_kv, h = (1, 4) if kind.startswith('latent') else (2, 4)
    lens = jnp.asarray([fill, 300, _TBK + 76], jnp.int32)
    layers = 2 if kind in ('stack', 'latent') else 1
    q = jax.random.normal(ks[0], (3, h, 1, d), jnp.float32)
    kn = jax.random.normal(ks[1], (3, h_kv, 1, d), jnp.float32)
    vn = jax.random.normal(ks[2], (3, h_kv, 1, d), jnp.float32)
    k = jax.random.normal(ks[3], (layers, 3, h_kv, _TT, d), jnp.float32)
    v = jax.random.normal(ks[4], (layers, 3, h_kv, _TT, d), jnp.float32)
    return q, kn, vn, k, v, lens


def _tail_geometry(kind, d=128):
    from distributed_dot_product_tpu.ops import pallas_decode as pd
    f32 = jnp.float32
    if kind.startswith('latent'):
        # (its own rule would take one split of 1536 here: the tests hold
        # it to the slab's three splits, so the same fills are its edges —
        # a split's start, a piece's last column, the buffer's end)
        return pd.latent_geometry(_TT, d, d // 2, 4, f32, block_k=_TBK)
    return pd.decode_geometry(_TT, 2, d, d, 2, f32, f32)


def _time_minor(held, new):
    """The latent kernel's operands from row-major ones: the buffer
    ``(…, 1, t, d)`` time-minor, the new rows ``(B, 1, 1, d)`` as lane
    tiles of 128 identical columns."""
    b, d = new.shape[0], new.shape[-1]
    return (jnp.swapaxes(held, -1, -2),
            jnp.broadcast_to(new.reshape(b, 1, d, 1), (b, 1, d, 128)))


@pytest.mark.parametrize('fill', sorted(_TAIL_FILLS))
@pytest.mark.parametrize('kind', ['slab', 'stack', 'latent', 'latent-one'])
def test_kernel_tail_matches_xla_at_the_edges(kind, fill):
    """The kernel on caches of three real 1024-row splits against the
    XLA formulation (the latent buffer, time-minor, layer 1 of a stack
    or one layer's: a plain softmax over its columns), the slot under
    test one row either side of every edge of the rule: the split's
    start, a lane tile's last column and the next one's first, the
    tail's last row, the buffer's end. Outputs to tolerance; the aliased
    caches bit for bit — the appended row in place, every other row and
    layer untouched, the frozen slot's too (with a tail nothing is
    copied through for it)."""
    from distributed_dot_product_tpu.models.decode import DecodeCache
    from distributed_dot_product_tpu.ops import pallas_decode as pd
    geom = _tail_geometry(kind)
    assert (geom.block_k, geom.tail) == (_TBK, _TSUB)
    q, kn, vn, k, v, lens = _tail_case(kind, _TAIL_FILLS[fill])
    mask = jnp.asarray([True, True, False])
    vt = jnp.where(mask, lens, lens - 1)
    ap = jnp.where(mask, lens, -1)
    if kind.startswith('latent'):
        dv = q.shape[-1] // 2
        stacked = kind == 'latent'
        cache, tile = _time_minor(k if stacked else k[0], kn)
        out, rows, none, *_ = pd.flash_decode(
            q, tile, None, cache, None, vt, ap,
            layer=jnp.int32(1) if stacked else None, latent_v=dv,
            scale=0.3, block_k=_TBK)
        assert none is None
        want_rows = np.array(k)
        at = 1 if stacked else 0
        for i in range(2):
            want_rows[at, i, 0, int(lens[i])] = np.asarray(kn[i, 0, 0])
        assert np.array_equal(
            np.asarray(rows), np.swapaxes(
                want_rows if stacked else want_rows[0], -1, -2))
        held = want_rows[at, :, 0]
        s = np.einsum('bhd,btd->bht', np.asarray(q[:, :, 0]), held) * 0.3
        s = np.where(np.arange(_TT) <= np.asarray(vt)[:, None, None],
                     s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum('bht,btd->bhd', p / p.sum(-1, keepdims=True),
                         held[..., :dv])
        np.testing.assert_allclose(np.asarray(out[:, :, 0]), want,
                                   atol=2e-5, rtol=2e-5)
        return
    stacked = kind == 'stack'
    cache = DecodeCache(k=k if stacked else k[0], v=v if stacked else v[0],
                        length=jnp.stack([lens, lens]) if stacked else lens)
    layer = 1 if stacked else None
    cx, ox = decode_step(q, cache, kn, vn, slot_mask=mask, impl='xla',
                         layer=layer)
    ok, nk, nv, _, _ = pd.flash_decode(
        q, kn, vn, cache.k, cache.v, vt, ap,
        layer=None if layer is None else jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(ok), np.asarray(ox),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(cx.k))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(cx.v))


@pytest.mark.parametrize('interpreter', ['plain', 'tpu-nan'])
@pytest.mark.parametrize('kind', ['slab', 'latent-one'])
def test_rows_the_tail_did_not_move_reach_no_product(kind, interpreter):
    """Every cache row behind what the kernel moves is NaN — behind the
    tail's 256 rows of the last split for the slots that take the tail,
    behind the last split for the one that does not — and the result is
    finite and the clean cache's to tolerance: masking a score is not
    enough (0 · NaN is NaN), the rows must stay out of both products.
    (The parent moved the whole last split and fails this.) Once more
    under the TPU interpreter with uninitialised VMEM as NaN: what the
    tail's buffer holds where no copy landed is not read either. The
    latent kernel the same, in columns of its time-minor buffer: of the
    split that holds a session's last column it moves the 256-column
    pieces that hold a valid one (the lane tile it reads and writes back
    for the append lies inside them)."""
    from jax.experimental.pallas import tpu as pltpu
    from distributed_dot_product_tpu.ops import pallas_decode as pd
    assert _tail_geometry(kind).tail == _TSUB
    q, kn, vn, k, v, lens = _tail_case(kind, 2 * _TBK + 40)
    k, v = k[0], v[0]
    # Rows moved: slot 0 two splits + the tail; slot 1 its one split
    # whole; slot 2 one split + the tail.
    moved = jnp.asarray([2 * _TBK + _TSUB, _TBK, _TBK + _TSUB])
    behind = jnp.arange(_TT)[None, None, :, None] >= moved[:, None, None,
                                                           None]
    interp = (pltpu.InterpretParams(uninitialized_memory='nan')
              if interpreter == 'tpu-nan' else None)
    if kind == 'latent-one':
        dv = q.shape[-1] // 2
        clean, tile = _time_minor(k, kn)
        dirty, _ = _time_minor(jnp.where(behind, jnp.nan, k), kn)
        want, *_ = pd.flash_decode(q, tile, None, clean, None, lens, lens,
                                   latent_v=dv, block_k=_TBK)
        got, rows, *_ = pd.flash_decode(q, tile, None, dirty, None, lens,
                                        lens, latent_v=dv, block_k=_TBK,
                                        interpret=interp)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        assert np.isnan(np.asarray(rows)).sum() == np.isnan(
            np.asarray(dirty)).sum()
        return
    want, *_ = pd.flash_decode(q, kn, vn, k, v, lens, lens)
    got, nk, nv, _, _ = pd.flash_decode(
        q, kn, vn, jnp.where(behind, jnp.nan, k),
        jnp.where(behind, jnp.nan, v), lens, lens, interpret=interp)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # … and the poisoned rows are where they were: nothing was written
    # but the appended rows.
    assert np.isnan(np.asarray(nk)).sum() == np.isnan(
        np.asarray(jnp.where(behind, jnp.nan, k))).sum()


def test_decode_geometry_of_the_cells_and_its_budget():
    """The geometry function alone: the two decode cells' shapes, the
    divisor rule, the stream budget over head dims (Mosaic's verdict on
    the VMEM plan is ``test_tpu_compile``'s), the XLA fallback."""
    from distributed_dot_product_tpu.ops import pallas_decode as pd
    bf16 = jnp.bfloat16
    # mpt-7b.decode-12k: 32 KV heads of 128, one query row each.
    cell = pd.decode_geometry(16384, 32, 128, 128, 1, bf16, bf16)
    assert cell.heads >= 4 and cell.block_k == 1024
    assert cell.write_rows == 16
    assert cell.bytes == cell.heads * 1024 * 128 * 2 * 2
    # xing4-29b-a4b.decode-32k and ling-3.0-flash.decode-32k: the latent
    # buffer's own rule — a token a COLUMN of 576 values, nothing padded
    # (until PR 47 a row of 640: 1 310 720 B a step), 32 query rows; the
    # append's write-back the 128-lane tile that holds the column.
    # The split is LONG: the longest of 2048 / 1536 / 1024 / 512 that
    # divides t_max (33792 = 22 x 1536) within the VMEM plan; the split
    # that holds a session's last column moves in pieces of 256.
    latent = pd.latent_geometry(33792, 576, 512, 32, bf16)
    assert latent == (1, 1536, 128, 1536 * 576 * 2, 256)
    assert latent.bytes == 1769472 == 1536 * 1152
    assert pd.latent_geometry(32768, 576, 512, 32, bf16).block_k == 2048
    assert pd.latent_geometry(32768, 576, 512, 128, bf16).block_k == 2048
    # float32 rows of 128 heads: the plan has room for 1536 columns
    assert pd.latent_geometry(32768 * 3, 576, 512, 128,
                              jnp.float32).block_k == 1536
    assert pd.latent_geometry(66560, 576, 512, 32, bf16).block_k == 1024
    # a split of whole lane tiles, or the one split the buffer is
    assert pd.latent_geometry(33792, 576, 512, 32, bf16, block_k=64) is None
    assert pd.latent_geometry(192, 576, 512, 32, bf16) == (
        1, 192, 192, 192 * 576 * 2, None)
    assert pd.latent_geometry(2048, 576, 512, 32, bf16) == (
        1, 2048, 128, 2048 * 576 * 2, None)
    assert pd.latent_geometry(1027 * 4, 576, 512, 32, bf16) is None
    for h_kv in (1, 2, 3, 8, 12, 32):
        for d in (64, 96, 128, 256):
            for rows in (1, 12):
                g = pd.decode_geometry(32768, h_kv, d, d, rows, bf16, bf16)
                assert h_kv % g.heads == 0
                assert g.bytes <= pd._STEP_STREAM_BYTES
    # StarCoder2's 2 KV heads cap the step at 2; verify-k, paged pools
    # and the int8 mirror keep the whole split as their write-back.
    assert pd.decode_geometry(16384, 2, 128, 128, 12, bf16, bf16).heads == 2
    assert pd.decode_geometry(16384, 8, 128, 128, 4, bf16, bf16,
                              n=4).write_rows == 1024
    assert pd.decode_geometry(32768, 8, 96, 96, 1, bf16, bf16,
                              page_size=256).write_rows == 256
    assert pd.decode_geometry(32768, 8, 96, 96, 1, bf16, bf16,
                              quantized=True).write_rows == 1024
    # No usable split: the caller takes the XLA path, as before.
    assert pd.decode_geometry(1027, 8, 128, 128, 1, bf16, bf16) is None
    assert pd.decode_block_k(1027) is None
    # The tail: 256 rows for both cells' calls; 128 where the plan has
    # room for no more; none for a cache of one split, a head dim that
    # is not whole lane tiles, the ring, and the three modes above.
    assert cell.tail == 256
    assert pd.decode_geometry(16384, 8, 256, 256, 1, bf16, bf16).tail == 128
    assert pd.decode_geometry(1024, 8, 128, 128, 1, bf16, bf16).tail is None
    assert pd.decode_geometry(32768, 8, 96, 96, 1, bf16, bf16).tail is None
    assert pd.decode_geometry(5120, 8, 128, 128, 16, bf16, bf16,
                              ring=True).tail is None
    for kw in (dict(n=4), dict(page_size=256), dict(quantized=True)):
        assert pd.decode_geometry(32768, 8, 128, 128, 4, bf16, bf16,
                                  **kw).tail is None


def test_decode_impl_traces_carry_the_step():
    """A kernel-resolved trace says what grid step the kernel takes —
    the geometry function's answer for the call's shapes; an XLA one
    carries None, and so does a bare probe of the resolution, which
    has no queries to size a step from."""
    from distributed_dot_product_tpu.models.decode import (
        _resolve_decode_impl, decode_impl_traces,
    )
    from distributed_dot_product_tpu.ops.pallas_decode import (
        decode_geometry,
    )
    q, kn, vn, kf, vf = _operands(4, 2, key=9)
    with decode_impl_traces() as traces:
        decode_step(q, _filled(2, kf, vf), kn, vn, impl='kernel')
        decode_step(q, _filled(2, kf, vf), kn, vn, impl='xla')
        _resolve_decode_impl('kernel', _filled(2, kf, vf), 1, None, None)
    geom = decode_geometry(T, 2, D, D, 2, jnp.float32, jnp.float32)
    assert [t['resolved'] for t in traces] == ['kernel', 'xla', 'kernel']
    assert traces[0]['step'] == geom.step() == {
        'heads': 2, 'block_k': T, 'bytes': geom.bytes, 'heads_a_pass': 1}
    assert traces[0]['step']['heads'] == 2
    assert traces[1]['step'] is None and traces[2]['step'] is None
    # The tail's rows ride beside the step, in a key of their own: one
    # split holds this whole cache, so it is always moved whole.
    assert [t['tail'] for t in traces] == [None, None, None]
    wide = jnp.zeros((B, 4, 1, 128), jnp.float32)
    big = init_slot_cache(B, 2, 4096, 128, dtype=jnp.float32)
    with decode_impl_traces() as traces:
        decode_step(wide, big, wide[:, :2], wide[:, :2], impl='kernel')
    assert traces[0]['tail'] == decode_geometry(
        4096, 2, 128, 128, 2, jnp.float32, jnp.float32).tail == 256
    assert sorted(traces[0]['step']) == [
        'block_k', 'bytes', 'heads', 'heads_a_pass']
