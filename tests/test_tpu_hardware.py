# -*- coding: utf-8 -*-
"""
Real-TPU hardware parity suite (``DDP_TPU_TESTS_ON_TPU=1 pytest -m tpu``).

The reference runs its whole test suite on the accelerator when present
(cpu/cuda device fixture, reference test_gradient.py:64-70). The CPU-mesh
suite here covers the same *code* (shard_map plumbing, Pallas interpreter),
but the real backend differs materially — Mosaic kernel compilation, bf16
MXU matmul defaults, ICI collectives — so this module re-runs the core
parity assertions on the actual chip: the three L2 kernels (bitwise, under
``default_matmul_precision('highest')`` — TPU's default bf16 3-pass would
round the integer oracle), their VJPs, flash fwd+bwd with every mask form
(dense + block-skip redirect, segments, positions), ring attention (both
layouts), the 'full' module path and one full train step.

Single-chip W=1 meshes: the shard_map/collective plumbing compiles and
executes for real, degenerate but on-device (multi-chip execution is
covered by the CPU mesh + the driver dryrun; this suite is about the
hardware backend).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu

_ON_TPU = (os.environ.get('DDP_TPU_TESTS_ON_TPU')
           and jax.default_backend() == 'tpu')


@pytest.fixture(autouse=True)
def _require_tpu():
    if not _ON_TPU:
        pytest.skip('requires DDP_TPU_TESTS_ON_TPU=1 and a real TPU backend')


def _ints(*shape, lo=-3, hi=4, seed=0):
    """Integer-valued f32: bitwise-comparable when matmul precision is
    forced to 'highest' (partial sums stay far below 2^24)."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(lo, hi, size=shape).astype(np.float32))


T, D = 64, 32


# --- L2 kernels: bitwise parity + VJPs -----------------------------------

@pytest.mark.parametrize('offset', [8, None])
def test_matmul_nt_bitwise(offset):
    from distributed_dot_product_tpu.ops.functions import (
        distributed_matmul_nt_global,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    left, right = _ints(2, T, D), _ints(2, T, D, seed=1)
    with jax.default_matmul_precision('highest'):
        got = distributed_matmul_nt_global(left, right, offset=offset,
                                           mesh=seq_mesh(1))
        want = jnp.matmul(left, jnp.swapaxes(right, -1, -2))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_tn_bitwise():
    from distributed_dot_product_tpu.ops.functions import (
        distributed_matmul_tn_global,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    left, right = _ints(2, T, T), _ints(2, T, D, seed=1)
    with jax.default_matmul_precision('highest'):
        got = distributed_matmul_tn_global(left, right, mesh=seq_mesh(1))
        want = jnp.matmul(jnp.swapaxes(left, -1, -2), right)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul_all_bitwise():
    from distributed_dot_product_tpu.ops.functions import (
        distributed_matmul_all_global,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    left, right = _ints(2, T, T), _ints(2, T, D, seed=1)
    with jax.default_matmul_precision('highest'):
        got = distributed_matmul_all_global(left, right, offset=8,
                                            mesh=seq_mesh(1))
        want = jnp.matmul(left, right)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_op_grads_match_full_autodiff():
    """The custom VJPs (reference ops.py pairings, fixed) on the chip."""
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.ops.ops import matmul_nt
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    left, right = _ints(1, T, D), _ints(1, T, D, seed=1)
    cot = _ints(1, T, T, seed=2)
    mesh = seq_mesh(1)

    def dist(left, right):
        return jax.shard_map(
            lambda l, r: matmul_nt(l, r, 8), mesh=mesh,
            in_specs=(P(None, 'seq', None),) * 2,
            out_specs=P(None, 'seq', None), check_vma=False)(left, right)

    with jax.default_matmul_precision('highest'):
        g_dist = jax.grad(lambda l, r: jnp.sum(dist(l, r) * cot),
                          argnums=(0, 1))(left, right)
        g_full = jax.grad(
            lambda l, r: jnp.sum(
                jnp.matmul(l, jnp.swapaxes(r, -1, -2)) * cot),
            argnums=(0, 1))(left, right)
    for got, want in zip(g_dist, g_full):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- flash kernels: every mask form on Mosaic ----------------------------

def _qkv(t=512, d=64, dtype=jnp.bfloat16, heads=4):
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(kk, (1, heads, t, d), dtype) for kk in ks)


def _oracle(q, k, v, mask, causal=False):
    from distributed_dot_product_tpu.ops.pallas_attention import (
        _reference_math,
    )
    return _reference_math(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), mask,
                           1.0 / np.sqrt(q.shape[-1]), causal)


def _close(got, want, atol=2.5e-2):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=atol, rtol=atol)


def test_flash_dense_mask_redirect_fwd_bwd():
    """Dense mask through the scalar-prefetch DMA redirect (TPU-only
    path): block-diagonal mask = skipped, redirected AND mixed tiles."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t = 1024
    q, k, v = _qkv(t)
    blk = jnp.arange(t) // 256
    mask = (blk[:, None] != blk[None, :])[None, None]
    mask = mask.at[:, :, :300, :].set(False)
    _close(flash_attention(q, k, v, mask), _oracle(q, k, v, mask))
    g = jax.grad(lambda v_: jnp.sum(flash_attention(q, k, v_, mask)
                                    .astype(jnp.float32) ** 2))(v)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_segments_fwd_bwd():
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t = 768
    q, k, v = _qkv(t)
    seg = (jnp.arange(t, dtype=jnp.int32) * 3 // t)[None]
    dense = (seg[0][:, None] != seg[0][None, :])[None, None]
    _close(flash_attention(q, k, v, segment_ids=seg),
           _oracle(q, k, v, jnp.broadcast_to(dense, (1, 1, t, t))))
    g = jax.grad(lambda v_: jnp.sum(flash_attention(
        q, k, v_, segment_ids=seg).astype(jnp.float32) ** 2))(v)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_positions_fwd_bwd():
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t = 512
    q, k, v = _qkv(t)
    pos = jax.random.permutation(jax.random.key(3), t)[None].astype(
        jnp.int32)
    dense = (pos[0][:, None] < pos[0][None, :])[None, None]
    _close(flash_attention(q, k, v, positions=pos),
           _oracle(q, k, v, jnp.broadcast_to(dense, (1, 1, t, t))))
    g = jax.grad(lambda q_: jnp.sum(flash_attention(
        q_, k, v, positions=pos).astype(jnp.float32) ** 2))(q)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_causal_offset_traced():
    """Sequence-sharded causal: the traced scalar offset input."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t = 512
    q, k, v = _qkv(t)
    half = q[:, :, t // 2:]
    rows = t // 2 + jnp.arange(t // 2)
    dense = (rows[:, None] < jnp.arange(t)[None, :])[None, None]
    got = jax.jit(lambda off: flash_attention(
        half, k, v, causal=True, causal_offset=off))(t // 2)
    _close(got, _oracle(half, k, v,
                        jnp.broadcast_to(dense, (1, 1, t // 2, t))))


# --- ring attention on the chip ------------------------------------------

def test_ring_attention_w1_fwd_grad():
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.models.ring_attention import (
        ring_attention,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    q, k, v = _qkv(512)
    mesh = seq_mesh(1)
    spec = P(None, None, 'seq', None)
    ring = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    _close(ring(q, k, v), _oracle(q, k, v, None, causal=True))
    g = jax.grad(lambda v_: jnp.sum(ring(q, k, v_)
                                    .astype(jnp.float32) ** 2))(v)
    assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_ring_zigzag_w1():
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.models.ring_attention import (
        ring_attention, zigzag_indices,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    t = 512
    q, k, v = _qkv(t)
    idx = zigzag_indices(t, 1)
    inv = jnp.argsort(idx)
    mesh = seq_mesh(1)
    spec = P(None, None, 'seq', None)
    ring = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, causal=True,
                                       layout='zigzag'),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    got = ring(q[..., idx, :], k[..., idx, :], v[..., idx, :])[..., inv, :]
    _close(got, _oracle(q, k, v, None, causal=True))


# --- module + train step -------------------------------------------------

def test_module_full_path_matches_oracle():
    """The reference-parity 'full' softmax path (chunked allgather nt/all
    kernels through the module) on the chip."""
    from distributed_dot_product_tpu.models.attention import (
        DistributedDotProductAttn, apply_seq_parallel,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    dim, t = 64, 256
    model = DistributedDotProductAttn(key_dim=dim, num_heads=4, offset=32)
    oracle = DistributedDotProductAttn(key_dim=dim, num_heads=4,
                                       distributed=False)
    x = jax.random.normal(jax.random.key(1), (2, t, dim), jnp.float32)
    m = jnp.zeros((2, t, t), dtype=bool)
    params = oracle.init(jax.random.key(2), x, x, x, m)
    got = apply_seq_parallel(model, params, seq_mesh(1), x, x, x, m)
    want = oracle.apply(params, x, x, x, m)
    _close(got, want)


def test_train_step_updates_params():
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_train_step
    dim, t = 64, 512
    mesh = seq_mesh(1)
    model = DistributedDotProductAttn(key_dim=dim, num_heads=4,
                                      softmax_impl='flash', causal=True,
                                      dtype=jnp.bfloat16)
    x = jax.device_put(
        jax.random.normal(jax.random.key(1), (1, t, dim), jnp.bfloat16),
        NamedSharding(mesh, P(None, 'seq', None)))
    x0 = jnp.zeros((1, 16, dim), jnp.bfloat16)
    params = model.init(jax.random.key(0), x0, x0, x0, None)
    opt = optax.adam(1e-3)
    step = make_train_step(model, opt, mesh, donate=False)
    new_params, _, loss = step(params, opt.init(params),
                               (x, x, x, None, x))
    assert np.isfinite(float(loss))
    changed = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert changed, 'adam update did not change the parameters'


def test_ulysses_w1_matches_flash():
    from distributed_dot_product_tpu.models.ulysses_attention import (
        ulysses_attention,
    )
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from jax.sharding import PartitionSpec as P
    q, k, v = _qkv(256)
    mesh = seq_mesh(1)
    spec = P(None, None, 'seq', None)
    uly = jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    _close(uly(q, k, v), flash_attention(q, k, v, causal=True),
           atol=1e-2)


def test_flash_window_banded_fwd_bwd():
    """Sliding-window attention on the real chip: the banded grid (active
    on TPU by default — scalar-prefetch index maps, Mosaic-compiled) must
    match the densified-mask oracle, forward and gradients."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        _reference_math, flash_attention,
    )
    t, window = 192, 40
    k1, k2, k3 = jax.random.split(jax.random.key(17), 3)
    q = jax.random.normal(k1, (2, t, D), jnp.float32)
    k = jax.random.normal(k2, (2, t, D), jnp.float32)
    v = jax.random.normal(k3, (2, t, D), jnp.float32)
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    dense = rows - cols >= window

    def f_win(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                window=window) ** 2).sum()

    def f_ref(q, k, v):
        return (_reference_math(q, k, v, dense, 1.0 / np.sqrt(D),
                                True).astype(jnp.float32) ** 2).sum()

    l_w, g_w = jax.value_and_grad(f_win, argnums=(0, 1, 2))(q, k, v)
    l_r, g_r = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(l_w), float(l_r), rtol=2e-2)
    for gw, gr in zip(g_w, g_r):
        # 5e-2: TPU f32 matmul defaults to 3-pass bf16 and the oracle's
        # op order differs; CPU parity for the same path is 1e-5
        # (tests/test_window_attention.py).
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gr),
                                   atol=5e-2, rtol=2e-2)


def test_flash_gqa_fwd_bwd():
    """Grouped-query attention on the real chip: Mosaic-compiled grouped
    K/V index maps + group-summed dk/dv match the repeated-kv oracle."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t, hq, hkv = 128, 4, 2
    k1, k2, k3 = jax.random.split(jax.random.key(23), 3)
    q = jax.random.normal(k1, (hq, t, D), jnp.float32)
    k = jax.random.normal(k2, (hkv, t, D), jnp.float32)
    v = jax.random.normal(k3, (hkv, t, D), jnp.float32)
    rep = lambda x: jnp.repeat(x, hq // hkv, axis=0)  # noqa: E731

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def f_rep(q, kr, vr):
        return (flash_attention(q, kr, vr, causal=True) ** 2).sum()

    l, (dq, dk, dv) = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    lr, (dqr, dkr, dvr) = jax.value_and_grad(
        f_rep, argnums=(0, 1, 2))(q, rep(k), rep(v))
    np.testing.assert_allclose(float(l), float(lr), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dqr), atol=2e-2,
                               rtol=2e-2)
    for got, r in ((dk, dkr), (dv, dvr)):
        want = r.reshape(hkv, hq // hkv, t, D).sum(1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2)


def test_flash_alibi_and_rope_fwd_bwd():
    """ALiBi slopes (in-kernel SMEM table, Mosaic-compiled) + RoPE'd
    inputs on the real chip vs the dense jnp oracle."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from distributed_dot_product_tpu.ops.rope import rope
    t, h = 128, 4
    ks = jax.random.split(jax.random.key(29), 3)
    q, k, v = (jax.random.normal(kk, (h, t, D), jnp.float32) for kk in ks)
    q, k = rope(q), rope(k)
    sl = 2.0 ** (-2.0 * (jnp.arange(h) + 1))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                alibi_slopes=sl) ** 2).sum()

    def f_ref(q, k, v):
        scale = 1.0 / np.sqrt(D)
        s = jnp.einsum('htd,hod->hto', q * scale, k)
        rows = jnp.arange(t)[:, None]
        cols = jnp.arange(t)[None, :]
        s = s + sl[:, None, None] * (cols - rows)
        s = jnp.where(rows < cols, -jnp.inf, s)
        a = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum('hto,hod->htd', a, v) ** 2).sum()

    l, g = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    lr, gr = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(l), float(lr), rtol=1e-2)
    for got, want in zip(g, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-2, rtol=2e-2)


def test_flash_qk_quant_int8_fwd_bwd():
    """int8-quantized QK^T on the real chip: the Mosaic int8 MXU dot +
    in-kernel dequant must match the dense quantized-math oracle."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t, h = 128, 4
    ks = jax.random.split(jax.random.key(31), 3)
    q, k, v = (jax.random.normal(kk, (h, t, D), jnp.float32) for kk in ks)

    def dense(q, k, v):
        scale = 1.0 / np.sqrt(D)
        sq = jnp.maximum(jnp.abs(q).max(-1, keepdims=True) / 127.0, 1e-20)
        sk = jnp.maximum(jnp.abs(k).max(-1, keepdims=True) / 127.0, 1e-20)
        s = jnp.einsum('htd,hod->hto', jnp.round(q / sq) * sq,
                       jnp.round(k / sk) * sk) * scale
        rows = jnp.arange(t)[:, None]
        s = jnp.where(rows < jnp.arange(t)[None, :], -jnp.inf, s)
        return jnp.einsum('hto,hod->htd', jax.nn.softmax(s, -1), v)

    out = flash_attention(q, k, v, causal=True, qk_quant='int8')
    with jax.default_matmul_precision('highest'):
        ref = dense(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    g = jax.grad(lambda v_: (flash_attention(
        q, k, v_, causal=True, qk_quant='int8') ** 2).sum())(v)
    assert bool(jnp.isfinite(g).all())


def test_flash_dropout_prng_path():
    """In-kernel PRNG dropout on the real chip: deterministic per seed,
    seed-sensitive, keep-rate within statistical bounds, expectation
    close to the exact output, and finite grads."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    t, h, rate = 256, 4, 0.3
    ks = jax.random.split(jax.random.key(37), 3)
    q, k, v = (jax.random.normal(kk, (h, t, D), jnp.float32) for kk in ks)
    kw = dict(dropout_rate=rate)
    a = flash_attention(q, k, v, dropout_seed=1, **kw)
    b = flash_attention(q, k, v, dropout_seed=1, **kw)
    c = flash_attention(q, k, v, dropout_seed=2, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))

    # Keep-rate: recover the dropped-weight matrix by feeding v = I.
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32), (h, t, t))
    w = flash_attention(q, k, eye, dropout_seed=3, **kw)
    kept = float((np.asarray(w) != 0).mean())
    assert abs(kept - (1 - rate)) < 0.02, kept

    # The mask is a pure element-coordinate hash — replicate it in
    # numpy and demand EXACT agreement with the Mosaic-compiled kernel
    # (softmax weights are strictly positive non-causal, so w != 0
    # recovers the complete mask).
    u = np.uint32
    rows = np.arange(t, dtype=np.uint32)[None, :, None]
    cols = np.arange(t, dtype=np.uint32)[None, None, :]
    bidx = np.arange(h, dtype=np.uint32)[:, None, None]
    with np.errstate(over='ignore'):
        x = (rows * u(2654435761) ^ cols * u(2246822519)
             ^ (u(3) + bidx * u(668265263)))
        x ^= x >> u(16)
        x = (x * u(2246822507)).astype(np.uint32)
        x ^= x >> u(13)
        x = (x * u(3266489909)).astype(np.uint32)
        x ^= x >> u(16)
    want_keep = x >= u(int(rate * 2.0 ** 32))
    np.testing.assert_array_equal(np.asarray(w) != 0, want_keep)

    exact = flash_attention(q, k, v)
    mean = jnp.stack([flash_attention(q, k, v, dropout_seed=s, **kw)
                      for s in range(48)]).mean(0)
    # Loose: the max-deviation TAIL over h·t·D elements shrinks only as
    # 1/√seeds; the keep-rate assertion above pins the distribution.
    np.testing.assert_allclose(np.asarray(mean), np.asarray(exact),
                               atol=0.2)
    g = jax.grad(lambda q_: (flash_attention(
        q_, k, v, dropout_seed=1, **kw) ** 2).sum())(q)
    assert bool(jnp.isfinite(g).all())


# --- round-4 surface: trapezoid grid, module GQA/RoPE, ring features ----

def test_trapezoid_causal_matches_full_grid_on_chip():
    """Static-offset causal takes the trapezoid pair grid on the real
    Mosaic backend; a traced offset keeps the full grid. Same math, so
    fwd AND both gradients must agree bitwise (identical kernels, only
    the grid walk differs)."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    ks = jax.random.split(jax.random.key(5), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 4, 1024, 64), jnp.bfloat16)
                  for kk in ks)

    def run(off):
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, causal_offset=off,
            segment_ids=(jnp.arange(1024) // 300, jnp.arange(1024) // 300))
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(g))

    trap = run(0)                      # static -> trapezoid
    full = jax.jit(run)(jnp.int32(0))  # traced -> full grid
    for a, b in zip(trap, full):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize('form', ['trapezoid_alibi', 'banded_gqa'])
def test_fused_backward_matches_split_bitwise_on_chip(monkeypatch, form):
    """The fused backward walks K blocks in ascending order, so each dq
    row block sums its terms in the dq kernel's order: dq, dk and dv
    equal the split form's bit for bit on the chip's own program, in
    both training cells' mask forms, several 1024-row blocks a side."""
    import distributed_dot_product_tpu.ops.pallas_attention as pa
    h, h_kv, kw = 4, 4, dict(alibi_slopes=jnp.asarray(
        [2.0 ** (-2.0 * (i + 1)) for i in range(4)], jnp.float32))
    if form == 'banded_gqa':
        h, h_kv, kw = 4, 2, dict(window=2048)
    ks = jax.random.split(jax.random.key(29), 4)
    t, d = 4096 + 300, 128         # a ragged last block
    q, g = (jax.random.normal(kk, (1, h, t, d), jnp.bfloat16)
            for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, h_kv, t, d), jnp.bfloat16)
            for kk in ks[2:])

    def run(budget):
        monkeypatch.setattr(pa, '_FUSED_DQ_BYTES', budget)
        with pa.flash_bwd_traces() as traces:
            _, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
                q, k, v, causal=True, **kw), q, k, v)
            grads = vjp(g)
        return [tr['form'] for tr in traces], grads

    forms_f, fused = run(16 << 20)
    forms_s, split = run(0)
    assert (forms_f, forms_s) == (['fused'], ['split'])
    for a, b in zip(fused, split):
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all())
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_module_gqa_rope_fwd_bwd_on_chip():
    """The round-4 module surface on real hardware: num_kv_heads + RoPE
    through apply_seq_parallel (W=1 mesh) vs the distributed=False
    oracle, forward and parameter gradients."""
    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.models.attention import (
        apply_seq_parallel,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    mesh = seq_mesh(1)
    dim, t = 64, 512
    x = jax.random.normal(jax.random.key(2), (1, t, dim), jnp.float32)

    def mk(dist):
        return DistributedDotProductAttn(
            key_dim=dim, num_heads=8, num_kv_heads=2, causal=True,
            use_rope=True, softmax_impl='flash', distributed=dist)

    m = mk(True)
    params = m.init(jax.random.key(0), x[:, :16], x[:, :16], x[:, :16],
                    None)

    def loss_d(p):
        return jnp.sum(apply_seq_parallel(m, p, mesh, x, x, x, None) ** 2)

    def loss_l(p):
        return jnp.sum(mk(False).apply(p, x, x, x, None) ** 2)

    ld, gd = jax.value_and_grad(loss_d)(params)
    ll, gl = jax.value_and_grad(loss_l)(params)
    np.testing.assert_allclose(float(ld), float(ll), rtol=1e-3)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gl)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0.1,
                                   atol=2e-2)


def test_ring_dropout_segments_matches_flash_on_chip():
    """Ring path carrying dropout + packed segments on the real chip:
    with one seed the global-coordinate hash must reproduce the flash
    path's mask exactly (W=1: one fold, but the Mosaic-compiled kernels
    and the kv_offset plumbing are the real thing)."""
    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.models.attention import (
        apply_seq_parallel,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    mesh = seq_mesh(1)
    dim, t = 64, 512
    x = jax.random.normal(jax.random.key(3), (1, t, dim), jnp.float32)
    seg = (jnp.arange(t)[None] // 150).astype(jnp.int32)

    def mk(impl):
        return DistributedDotProductAttn(
            key_dim=dim, num_heads=4, causal=True, softmax_impl=impl,
            dropout_rate=0.3)

    mo, mf = mk('online'), mk('flash')
    params = mo.init(jax.random.key(0), x[:, :16], x[:, :16], x[:, :16],
                     None)
    oo = apply_seq_parallel(mo, params, mesh, x, x, x, None,
                            segment_ids=seg, dropout_seed=7)
    of = apply_seq_parallel(mf, params, mesh, x, x, x, None,
                            segment_ids=seg, dropout_seed=7)
    np.testing.assert_allclose(np.asarray(oo), np.asarray(of), atol=1e-5)
    od = apply_seq_parallel(mo, params, mesh, x, x, x, None,
                            segment_ids=seg, deterministic=True)
    assert not np.allclose(np.asarray(oo), np.asarray(od))


# --- round-5 surfaces on the chip ----------------------------------------

def test_ring_int8_matches_flash_int8_on_chip():
    """Per-fold int8 quantization through the Mosaic int8 MXU path must
    equal the single-device int8 flash kernel (W=1 ring)."""
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.models.ring_attention import (
        ring_attention,
    )
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    ks = jax.random.split(jax.random.key(11), 3)
    q, k, v = (jax.random.normal(kk, (1, 4, 512, 64), jnp.float32)
               for kk in ks)
    spec = P(None, None, 'seq', None)
    ring = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, causal=True,
                                       qk_quant='int8'),
        mesh=seq_mesh(1), in_specs=(spec,) * 3, out_specs=spec,
        check_vma=False)
    want = flash_attention(q, k, v, causal=True, qk_quant='int8')
    np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                               np.asarray(want), atol=2e-2)


def test_zigzag_dense_mask_on_chip():
    """Zigzag + dense mask: per-fold column gather composed with the
    positions kernels, Mosaic-compiled."""
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.models.ring_attention import (
        local_attention_reference, ring_attention, zigzag_indices,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    t = 512
    ks = jax.random.split(jax.random.key(12), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, t, 32), jnp.float32)
               for kk in ks)
    m = jax.random.bernoulli(jax.random.key(13), 0.3, (1, 1, t, t))
    m = m.at[..., 0].set(False)
    idx = zigzag_indices(t, 1)
    inv = jnp.argsort(idx)
    spec = P(None, None, 'seq', None)
    ring = jax.shard_map(
        lambda a, b, c, d: ring_attention(a, b, c, d, causal=True,
                                          layout='zigzag'),
        mesh=seq_mesh(1), in_specs=(spec,) * 4, out_specs=spec,
        check_vma=False)
    got = ring(q[..., idx, :], k[..., idx, :], v[..., idx, :],
               m[..., idx, :])[..., inv, :]
    want = local_attention_reference(q, k, v, m, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2)


def test_scanned_lm_trains_and_generates_on_chip():
    """The capstone on hardware: a scanned+remat'd TransformerLM's
    sharded train step improves the loss, and greedy generation through
    the layer-stacked KV caches runs."""
    import optax

    from distributed_dot_product_tpu import TransformerLM, greedy_generate
    from distributed_dot_product_tpu.models.lm import lm_targets
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_lm_train_step
    vocab, t = 64, 256
    lm = TransformerLM(vocab_size=vocab, dim=64, num_heads=4, n_layers=3,
                       scan_layers=True, remat=True)
    toks = jax.random.randint(jax.random.key(0), (1, t), 0, vocab,
                              dtype=jnp.int32)
    tgts = lm_targets(toks)
    params = lm.init(jax.random.key(1), toks[:, :16])
    opt = optax.adam(1e-2)
    step = make_lm_train_step(lm, opt, seq_mesh(1), donate=False,
                              loss_chunk=64)
    ost = opt.init(params)
    losses = []
    for _ in range(3):
        params, ost, loss = step(params, ost, (toks, tgts))
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    out = greedy_generate(lm, params, toks[:, :16], steps=4, t_max=64)
    assert out.shape == (1, 4)


def test_sharded_decode_matches_local_on_chip():
    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.models.attention import (
        decode_seq_parallel,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    mesh = seq_mesh(1)
    m = DistributedDotProductAttn(key_dim=64, num_heads=4,
                                  num_kv_heads=2, causal=True,
                                  use_rope=True)
    x = jax.random.normal(jax.random.key(2), (2, 6, 64), jnp.float32)
    p = m.init(jax.random.key(3), x, x, x, None)
    sc = m.make_decode_cache(2, 16)
    lc = m.make_decode_cache(2, 16)
    for t in range(4):
        xt = x[:, t:t + 1]
        sc, so = decode_seq_parallel(m, p, mesh, xt, xt, xt, sc)
        lc, lo = m.apply(p, xt, xt, xt, lc, method='decode')
        np.testing.assert_allclose(np.asarray(so), np.asarray(lo),
                                   atol=2e-2)
    assert int(sc.length) == 4


def test_fused_decode_kernel_compiles_on_chip():
    """The fused Pallas decode step (ops/pallas_decode.py) through the
    Mosaic compiler: parity with the XLA step across GQA/window/int8,
    and the aliased in-place append under a donated jit — the config
    the serving engine runs."""
    from distributed_dot_product_tpu.models.decode import (
        append_kv_slots, decode_step, init_cache, init_slot_cache,
    )
    from distributed_dot_product_tpu.models.decode import append_kv
    b, h, hkv, d, t_max = 4, 8, 2, 64, 512
    ks = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.bfloat16)
    kn = jax.random.normal(ks[1], (b, hkv, 1, d), jnp.bfloat16)
    vn = jax.random.normal(ks[2], (b, hkv, 1, d), jnp.bfloat16)
    kf = jax.random.normal(ks[3], (b, hkv, t_max, d), jnp.bfloat16)
    vf = jax.random.normal(ks[4], (b, hkv, t_max, d), jnp.bfloat16)
    lens = [300, 511, 0, 17]

    def filled():
        c = init_slot_cache(b, hkv, t_max, d, dtype=jnp.bfloat16)
        return append_kv_slots(c, kf, vf,
                               counts=jnp.asarray(lens, jnp.int32))

    for kw in ({}, {'window': 64}):
        cx, ox = decode_step(q, filled(), kn, vn, impl='xla', **kw)
        ck, ok = decode_step(q, filled(), kn, vn, impl='kernel', **kw)
        np.testing.assert_allclose(np.asarray(ok, dtype=np.float32),
                                   np.asarray(ox, dtype=np.float32),
                                   atol=3e-2, rtol=3e-2,
                                   err_msg=str(kw))
        np.testing.assert_array_equal(np.asarray(ck.length),
                                      np.asarray(cx.length))

    # int8 mirror: dequantize-in-kernel vs the XLA s8 einsum.
    ci = init_cache(b, hkv, t_max, d, dtype=jnp.bfloat16,
                    qk_quant='int8')
    ci = append_kv(ci, kf[:, :, :300], vf[:, :, :300])
    cx8, ox8 = decode_step(q, ci, kn, vn, qk_quant='int8', impl='xla')
    ck8, ok8 = decode_step(q, ci, kn, vn, qk_quant='int8',
                           impl='kernel')
    np.testing.assert_array_equal(np.asarray(ck8.k_q),
                                  np.asarray(cx8.k_q))
    np.testing.assert_allclose(np.asarray(ok8, dtype=np.float32),
                               np.asarray(ox8, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)

    # Donated + aliased = the cache buffer must not move between steps
    # (the whole point: no scan-carry or donated-copy round trip).
    step = jax.jit(
        lambda c, q, k, v: decode_step(q, c, k, v, impl='kernel'),
        donate_argnums=(0,))
    c0 = filled()
    c1, _ = step(c0, q, kn, vn)
    ptr0 = c1.k.unsafe_buffer_pointer()
    c2, _ = step(c1, q, kn, vn)
    assert c2.k.unsafe_buffer_pointer() == ptr0, \
        'aliased decode cache was copied between donated steps'


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bf16', 'f32'])
@pytest.mark.parametrize('h,h_kv', [(4, 4), (8, 2)], ids=['mha', 'gqa'])
def test_verify_k_kernel_matches_sequential_bitwise_on_chip(h, h_kv, dtype):
    """README "Speculative decoding" on the chip's own program: one
    verify-k step of the fused kernel equals ``counts[i]`` sequential
    single-token steps of the same kernel BIT for bit, outputs and
    cache (the CPU suite's ``[*-kernel]`` cases run the Pallas
    interpreter, whose XLA:CPU dots round differently at M=1)."""
    from distributed_dot_product_tpu.models.decode import (
        append_kv_slots, decode_step, init_slot_cache,
    )
    b, d, t_max, k = 2, 128, 1024, 3
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (b, h, k, d), dtype)
    kn = jax.random.normal(ks[1], (b, h_kv, k, d), dtype)
    vn = jax.random.normal(ks[2], (b, h_kv, k, d), dtype)
    kf = jax.random.normal(ks[3], (b, h_kv, t_max, d), dtype)
    vf = jax.random.normal(ks[4], (b, h_kv, t_max, d), dtype)
    # Staggered fills; slot 1's three rows straddle a 512-row K block.
    fills = jnp.asarray([300, 511], jnp.int32)
    counts = jnp.asarray([k, k - 1], jnp.int32)

    def filled():
        return append_kv_slots(init_slot_cache(b, h_kv, t_max, d,
                                               dtype=dtype),
                               kf, vf, counts=fills)

    seq, outs = filled(), []
    for j in range(k):
        seq, o = decode_step(q[:, :, j:j + 1], seq, kn[:, :, j:j + 1],
                             vn[:, :, j:j + 1], slot_mask=j < counts,
                             impl='kernel')
        outs.append(np.asarray(o, np.float32)[:, :, 0])
    cv, ov = decode_step(q, filled(), kn, vn, counts=counts,
                         impl='kernel')
    ov = np.asarray(ov, np.float32)
    for i in range(b):
        for j in range(int(counts[i])):
            np.testing.assert_array_equal(ov[i, :, j], outs[j][i],
                                          err_msg=f'slot {i} row {j}')
    for got, want in ((cv.k, seq.k), (cv.v, seq.v),
                      (cv.length, seq.length)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize('h_kv', [8, 2, 1])
@pytest.mark.parametrize('fill', [500, 2648, 4224])
def test_packed_kernel_matches_its_xla_step_on_chip(h_kv, fill):
    """``flash_decode``'s packed mode under Mosaic — the pair pass (8 and
    2 KV heads) and the single-head body (1) — against the packed XLA
    step, head by head: a fill of one split, a row landing deep in a
    split and the cell's (four whole splits and the tail). Every piece
    of the pair pass read right under the interpreter AND on XLA:CPU
    while its first caller-side pick of the context (a stack of two
    lane-offset slices) read wrong on XLA:TPU and head B of every pair
    with it (chip, PR 52): what the chip computes is checked on the
    chip."""
    from distributed_dot_product_tpu.models.decode import (
        PackedCache, decode_impl_traces, decode_step,
    )
    b, group, d, t_max = 2, 4, 64, 5120
    ks = jax.random.split(jax.random.key(11), 4)
    cache = PackedCache(
        kv=jax.random.normal(ks[0], (b, h_kv, t_max, 2 * d), jnp.bfloat16),
        length=jnp.asarray(fill, jnp.int32))
    q = jax.random.normal(ks[1], (b, h_kv * group, 1, d), jnp.bfloat16)
    kn = jax.random.normal(ks[2], (b, h_kv, 1, d), jnp.bfloat16)
    vn = jax.random.normal(ks[3], (b, h_kv, 1, d), jnp.bfloat16)
    with decode_impl_traces() as traces:
        got_cache, got = decode_step(q, cache, kn, vn, impl='kernel')
    want_cache, want = decode_step(q, cache, kn, vn, impl='xla')
    assert traces[0]['step']['heads_a_pass'] == (1 if h_kv == 1 else 2)
    np.testing.assert_array_equal(np.asarray(got_cache.kv, np.float32),
                                  np.asarray(want_cache.kv, np.float32))
    err = np.abs(np.asarray(got, np.float32)
                 - np.asarray(want, np.float32)).max(axis=(0, 2, 3))
    # bfloat16 contexts of ~0.1: a rounding, in every head
    assert err.max() < 4e-3, err.reshape(h_kv, group).max(1)
