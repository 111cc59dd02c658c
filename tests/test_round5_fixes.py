# -*- coding: utf-8 -*-
"""
Round-5 advisor-finding regressions:

1. ``make_train_step`` must REFUSE to run a dropout-enabled module
   without an explicit ``dropout_seed`` (a silent constant seed would
   reuse one dropout mask every step).
2. ``flash_softmax_mode='bounded'`` combined with dropout/ALiBi/int8
   canonicalizes to the exact kernel BEFORE the beyond-cap chunk
   eligibility check, so long causal sequences still take the chunked
   trapezoid grid.
3. ``prefill`` supports packed segments (parity with ``decode``).
4. ``append_kv`` under jit: an overflowing append leaves the buffers
   unchanged (no silent last-slot corruption) while ``length`` advances
   past ``t_max`` as a detectable flag.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_dot_product_tpu.ops.pallas_attention as pa
from distributed_dot_product_tpu import DistributedDotProductAttn
from distributed_dot_product_tpu.models.decode import append_kv, init_cache
from distributed_dot_product_tpu.parallel.mesh import seq_mesh
from distributed_dot_product_tpu.train import make_train_step


# ---------------------------------------------------------------------------
# 1. dropout-enabled modules require an explicit seed
# ---------------------------------------------------------------------------

def _dropout_step():
    mesh = seq_mesh(8)
    dim, heads, t, b = 32, 4, 16, 2
    model = DistributedDotProductAttn(
        key_dim=dim, num_heads=heads, softmax_impl='flash',
        dropout_rate=0.1)
    x = jax.random.normal(jax.random.key(0), (b, t, dim), jnp.float32)
    target = jax.random.normal(jax.random.key(1), (b, t, dim), jnp.float32)
    params = model.init(jax.random.key(2), x, x, x, None)
    optimizer = optax.adam(1e-2)
    step = make_train_step(model, optimizer, mesh, donate=False)
    return step, params, optimizer.init(params), (x, x, x, None, target)


def test_train_step_requires_seed_with_dropout():
    step, params, opt_state, batch = _dropout_step()
    with pytest.raises(ValueError, match='dropout_seed'):
        step(params, opt_state, batch)
    # With the seed, the same step runs.
    _, _, loss = step(params, opt_state, batch, dropout_seed=0)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# 2. bounded + dropout canonicalizes before beyond-cap chunking
# ---------------------------------------------------------------------------

def test_bounded_with_dropout_still_chunks_beyond_cap(monkeypatch):
    """'bounded' with dropout always resolves to the exact kernel — the
    resolution must happen before the chunk-eligibility check, or long
    causal sequences silently run the slow full grid (ADVICE round 4)."""
    monkeypatch.setattr(pa, '_TRAP_ON_INTERPRET', True)
    monkeypatch.setattr(pa, '_TRAP_MAX_PAIRS', 8)
    # Tiny blocks so T=96 spans several Q blocks (at natural block sizes
    # one block covers it and no chunking can trigger at test scale).
    monkeypatch.setattr(pa, '_block_sizes', lambda *a, **k: (16, 16))
    seen = []
    orig = pa._trap_chunk_bounds

    def spy(*args, **kw):
        bounds = orig(*args, **kw)
        seen.append(bounds)
        return bounds

    monkeypatch.setattr(pa, '_trap_chunk_bounds', spy)
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 96, 16)) for kk in ks)
    out_b = pa.flash_attention(q, k, v, causal=True,
                               softmax_mode='bounded',
                               dropout_rate=0.25, dropout_seed=3)
    assert any(len(b) > 1 for b in seen), (
        'bounded+dropout forward never took the beyond-cap chunking path')
    out_e = pa.flash_attention(q, k, v, causal=True, softmax_mode='exact',
                               dropout_rate=0.25, dropout_seed=3)
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_e))


# ---------------------------------------------------------------------------
# 3. prefill packed segments == causal forward with segment_ids
# ---------------------------------------------------------------------------

def test_prefill_segments_matches_causal_forward():
    b, t, dim = 2, 48, 32
    model = DistributedDotProductAttn(
        key_dim=dim, num_heads=2, causal=True, distributed=False,
        softmax_impl='flash')
    x = jax.random.normal(jax.random.key(0), (b, t, dim), jnp.float32)
    seg = jnp.broadcast_to((jnp.arange(t) // 20)[None], (b, t)
                           ).astype(jnp.int32)
    params = model.init(jax.random.key(1), x, x, x, None)
    want = model.apply(params, x, x, x, None, segment_ids=seg)

    cache = model.make_decode_cache(b, t)
    cache, got = model.apply(params, x, x, x, cache, seg, seg,
                             method='prefill')
    assert int(cache.length) == t
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_prefill_segments_requires_seg_cache():
    b, t, dim = 1, 8, 16
    model = DistributedDotProductAttn(key_dim=dim, causal=True,
                                      distributed=False)
    x = jnp.ones((b, t, dim), jnp.float32)
    params = model.init(jax.random.key(0), x, x, x, None)
    cache = model.make_decode_cache(b, t)
    with pytest.raises(ValueError, match='seg_cache'):
        model.apply(params, x, x, x, cache,
                    jnp.zeros((b, t), jnp.int32), method='prefill')


# ---------------------------------------------------------------------------
# 4. jitted append_kv overflow: buffers intact, length flags it
# ---------------------------------------------------------------------------

def test_append_kv_jit_overflow_no_corruption():
    b, hkv, t_max, d = 1, 1, 4, 8
    cache = init_cache(b, hkv, t_max, d, dtype=jnp.float32,
                       qk_quant='int8')
    step = jax.jit(append_kv)
    for i in range(6):   # two past the cap
        kv = jnp.full((b, hkv, 1, d), float(i + 1), jnp.float32)
        cache = step(cache, kv, kv)
    # length advanced past t_max: the detectable overflow flag.
    assert int(cache.length) == 6 > t_max
    # Buffers hold exactly the first t_max appends — the overflowing
    # writes were dropped, nothing clamped onto the last slot.
    want = np.arange(1, t_max + 1, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(cache.k[0, 0, :, 0]), want)
    np.testing.assert_array_equal(np.asarray(cache.v[0, 0, :, 0]), want)
    # The int8 mirror followed the same guard.
    np.testing.assert_array_equal(
        np.asarray(cache.k_q[0, 0, :, 0]), np.full(t_max, 127, np.int8))
    np.testing.assert_allclose(
        np.asarray(cache.k_scale[0, 0, :, 0]), want / 127.0, rtol=1e-6)
