# -*- coding: utf-8 -*-
"""``flash_decode``'s packed mode scores two KV heads a pass wherever a
grid step holds an even number of them (``ops/pallas_decode.py``, the
pair pass): heads A and B of a step as ``[k_A | k_B]`` and ``[v_A |
v_B]``, their queries block-diagonal in one tile. Every case is held to
the packed XLA oracle (``packed_append`` + ``decode_attention``, which
``decode_step(impl='xla')`` is) and to the single-head packed body — the
parent's, which an odd head count still takes — on the same operands:
what the slab holds afterwards bit for bit, the context to the order of
float32 sums inside a pass. The Pallas interpreter, small splits."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.models.decode import (
    PackedCache, decode_impl_traces, decode_step, init_packed_cache,
    packed_append,
)
from distributed_dot_product_tpu.ops import pallas_decode
from distributed_dot_product_tpu.ops.pallas_decode import (
    DecodeGeometry, PairedGeometry, decode_geometry, flash_decode,
    flash_decode_geometry,
)

D, T_MAX, BLOCK_K = 64, 1024, 256          # four splits, a tail of 128 rows
# (KV heads, query heads a KV head): the cell's 8 x 4, a pair alone, MHA
# pairs, and ONE head — which has nobody to pair with and keeps the
# single-head body.
HEADS = {'8x4': (8, 4), '2x4': (2, 4), '2x1': (2, 1), '1x4': (1, 4)}
# n new rows at ``fill``, with a window and ALiBi slopes or without. A
# single row lands 200 rows into a split (moved whole), 5 rows into one
# (the tail), on its first row, and past ``t_max`` (attended, not
# appended); a verify-3 step lands inside a split, across a boundary and
# past ``t_max``.
CASES = {
    'whole-split': dict(n=1, fill=456),
    'tail': dict(n=1, fill=261),
    'boundary': dict(n=1, fill=512),
    'past-t_max': dict(n=1, fill=1024),
    'window': dict(n=1, fill=700, window=300),
    'alibi': dict(n=1, fill=456, alibi=True),
    'window-alibi-tail': dict(n=1, fill=773, window=400, alibi=True),
    'verify3': dict(n=3, fill=456),
    'verify3-straddle': dict(n=3, fill=510),
    'verify3-window-alibi': dict(n=3, fill=766, window=300, alibi=True),
    'verify3-past-t_max': dict(n=3, fill=1022),
}


def _rows(seed, *shape, dtype):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _kernel(q, cache, k_new, v_new, *, window, slopes):
    """``models.decode._packed_step``'s kernel call, at this file's
    small split: ``(context, slab, step record)``."""
    b, n = q.shape[0], q.shape[2]
    wide = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    new = jnp.concatenate([k_new, v_new], axis=-1)
    at = jnp.broadcast_to(cache.length, (b,))
    out, kv, _, _, _ = flash_decode(
        wide, new, None, cache.kv, None, at,
        jnp.where(at + n <= cache.t_max, at, -1), scale=D ** -0.5,
        window=window, alibi_slopes=slopes, interpret=True,
        block_k=BLOCK_K)
    return out, kv, flash_decode_geometry(wide, cache.kv,
                                          block_k=BLOCK_K).step()


# bfloat16 (the slab's type: the pairing goes through 32-bit words) at
# every head shape; float32 (no detour, sums to compare closely) at one.
_CALLS = [(heads, case, 'bfloat16') for heads in sorted(HEADS)
          for case in sorted(CASES)]
_CALLS += [('2x4', case, 'float32') for case in sorted(CASES)]


@pytest.mark.parametrize('heads,case,dtype', _CALLS,
                         ids=['-'.join(call) for call in _CALLS])
def test_the_pair_pass_is_the_oracle_and_the_single_head_body(
        heads, case, dtype, monkeypatch):
    dtype = jnp.dtype(dtype)
    h_kv, group = HEADS[heads]
    case = CASES[case]
    n, fill = case['n'], case['fill']
    b, h = 2, h_kv * group
    slopes = (tuple(2.0 ** (-8.0 * (i + 1) / h) for i in range(h))
              if case.get('alibi') else None)
    window = case.get('window')
    cache = packed_append(
        init_packed_cache(b, h_kv, T_MAX, D, dtype),
        _rows(1, b, h_kv, fill, D, dtype=dtype),
        _rows(2, b, h_kv, fill, D, dtype=dtype))
    q = _rows(3, b, h, n, D, dtype=dtype)
    k_new = _rows(4, b, h_kv, n, D, dtype=dtype)
    v_new = _rows(5, b, h_kv, n, D, dtype=dtype)

    got, kv, step = _kernel(q, cache, k_new, v_new, window=window,
                            slopes=slopes)
    assert step['heads_a_pass'] == (1 if h_kv == 1 else 2)
    assert step['heads'] == h_kv
    oracle, want = decode_step(q, cache, k_new, v_new, impl='xla',
                               window=window, alibi_slopes=slopes)
    np.testing.assert_array_equal(kv, oracle.kv)
    f32 = lambda x: np.asarray(x, np.float32)         # noqa: E731
    np.testing.assert_allclose(
        f32(got), f32(want), atol=2e-5 if dtype == jnp.float32 else 2e-2)

    # The parent's body on the same operands: the geometry's KIND is all
    # that selects the pair pass.
    monkeypatch.setattr(pallas_decode, 'PairedGeometry', DecodeGeometry)
    single, kv_single, step = _kernel(q, cache, k_new, v_new,
                                      window=window, slopes=slopes)
    assert step['heads_a_pass'] == 1
    np.testing.assert_array_equal(kv, kv_single)
    # The same products in the same split order; float32 sums inside a
    # pass may differ in order (bfloat16: a rounding of the result).
    np.testing.assert_allclose(
        f32(got), f32(single),
        atol=2e-6 if dtype == jnp.float32 else 8e-3)


def test_a_verify_step_is_its_single_token_steps_on_the_pair_pass():
    """Three rows in one verify-3 step against the same rows one step at
    a time, across a split boundary: the pair pass keeps the kernel's
    promise that a column's score enters the softmax with its tile
    whether the row arrived in this step or an earlier one."""
    h_kv, group, b, fill = 2, 4, 2, 510
    dtype = jnp.float32
    cache = packed_append(
        init_packed_cache(b, h_kv, T_MAX, D, dtype),
        _rows(1, b, h_kv, fill, D, dtype=dtype),
        _rows(2, b, h_kv, fill, D, dtype=dtype))
    q = _rows(3, b, h_kv * group, 3, D, dtype=dtype)
    k_new = _rows(4, b, h_kv, 3, D, dtype=dtype)
    v_new = _rows(5, b, h_kv, 3, D, dtype=dtype)
    got, kv, step = _kernel(q, cache, k_new, v_new, window=None,
                            slopes=None)
    assert step['heads_a_pass'] == 2
    for j in range(3):
        one, kv_one, _ = _kernel(
            q[:, :, j:j + 1], cache, k_new[:, :, j:j + 1],
            v_new[:, :, j:j + 1], window=None, slopes=None)
        cache = PackedCache(kv=kv_one, length=cache.length + 1)
        np.testing.assert_allclose(got[:, :, j:j + 1], one, atol=2e-6)
    np.testing.assert_array_equal(kv, cache.kv)


# (t_max, KV heads, packed row width, query rows a head, dtype) -> the
# plan's tuple — the parent's, whichever body scores it — and the heads
# a pass.
_PLANS = {
    'lfm2-cell': ((5120, 8, 128, 4, jnp.bfloat16),
                  (8, 1024, 16, 2097152, 256), 2),
    'two-heads': ((5120, 2, 128, 4, jnp.bfloat16),
                  (2, 1024, 16, 524288, 256), 2),
    'one-head': ((5120, 1, 128, 4, jnp.bfloat16),
                 (1, 1024, 16, 262144, 256), 1),
    'three-heads': ((5120, 3, 128, 4, jnp.bfloat16),
                    (3, 1024, 16, 786432, 256), 1),
    'sixteen-heads': ((16384, 16, 128, 1, jnp.bfloat16),
                      (8, 1024, 16, 2097152, 256), 2),
    'd128-pairs': ((8192, 2, 256, 4, jnp.bfloat16),
                   (2, 1024, 16, 1048576, 256), 2),
    # float32 rows are twice the bytes: with the pair's two block-sized
    # temporaries counted, the plan has room for the 128-row tail only.
    'float32-8-heads': ((2048, 8, 128, 1, jnp.float32),
                        (8, 1024, 8, 4194304, 128), 2),
}


@pytest.mark.parametrize('call', sorted(_PLANS))
def test_the_packed_plan_and_its_heads_a_pass(call):
    (t_max, h_kv, w, rows, dtype), want, heads_a_pass = _PLANS[call]
    geom = decode_geometry(t_max, h_kv, w, w, rows, dtype, dtype,
                           packed=True)
    assert geom == want
    assert isinstance(geom, PairedGeometry) == (heads_a_pass == 2)
    assert geom.step() == {'heads': want[0], 'block_k': want[1],
                           'bytes': want[3], 'heads_a_pass': heads_a_pass}
    # the same call unpacked never pairs
    padded = decode_geometry(t_max, h_kv, w // 2, w // 2, rows, dtype,
                             dtype)
    assert padded.step()['heads_a_pass'] == 1
    assert not isinstance(padded, PairedGeometry)


def test_the_step_record_says_heads_a_pass():
    """``decode_impl_traces()`` reports the pair pass beside the impl:
    the cache says ``packed`` as before, the step ``heads_a_pass: 2``."""
    b, h, h_kv = 1, 8, 2
    cache = init_packed_cache(b, h_kv, 2048, D, jnp.bfloat16)
    x = jnp.zeros((b, h, 1, D), jnp.bfloat16)
    new = jnp.zeros((b, h_kv, 1, D), jnp.bfloat16)
    with decode_impl_traces() as traces:
        decode_step(x, cache, new, new, impl='kernel')
        decode_step(x[:, :4], init_packed_cache(b, 1, 2048, D), new[:, :1],
                    new[:, :1], impl='kernel')
    assert [(t['resolved'], t['cache'], t['step']['heads'],
             t['step']['heads_a_pass']) for t in traces] == [
        ('kernel', 'packed', 2, 2), ('kernel', 'packed', 1, 1)]


# The single-head packed body is the parent's text (PR 51's, traced
# there): a packed call of three KV heads — no pair to form — lowers to
# the program it was before the pair pass, and so does the same call of
# two heads once the geometry's kind is taken away. The pair pass is a
# body of its own, not a branch inside the one every slab cell shares
# (whose own pin is ``tests/test_mixed_stack.py``'s MPT-shaped call).
PARENT_PACKED_JAXPR = {
    3: 'bcdb7a6dd4b4fb42f001ebf0508ddba1e34a343fb5a4e85944ffd2fee6414ee1',
    2: 'd4fd48829ff5ad53d3db5685723c0c26b9cb9f7249dd6e05cea34172c6eb86b2',
}


@pytest.mark.parametrize('h_kv', sorted(PARENT_PACKED_JAXPR))
def test_the_single_head_packed_body_is_the_program_it_was(
        h_kv, monkeypatch):
    import hashlib

    import jax
    monkeypatch.setattr(pallas_decode, 'PairedGeometry', DecodeGeometry)
    b, group, t = 2, 2, 4096

    def call(q, new, kv, at):
        return flash_decode(q, new, None, kv, None, at, at, scale=0.125,
                            interpret=True)[:2]

    def sh(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(call)(
        sh(b, h_kv * group, 1, 2 * D), sh(b, h_kv, 1, 2 * D),
        sh(b, h_kv, t, 2 * D), jax.ShapeDtypeStruct((b,), jnp.int32))
    assert hashlib.sha256(
        str(jaxpr).encode()).hexdigest() == PARENT_PACKED_JAXPR[h_kv]
