# -*- coding: utf-8 -*-
"""
The jaxpr linter's example programs: every public computation of the
package at *example abstract shapes and meshes*, so that
analysis/jaxpr_rules.py can trace it without running it.

The examples live WITH the lint, which imports the kernels, the model,
the serving engine and the train step — never the reverse: no module
below ``analysis/`` knows it is linted. :func:`entrypoint` registers a
builder under its name in :data:`ENTRYPOINTS`, in the order of this
file; a name registered twice is an error, since the registry is the
namespace the gate test (tests/test_graphlint.py) and the CLI report
against. A new public entrypoint adds its example here.

Builders are lazy (constructing flax params or meshes costs real work)
and run on whatever devices are visible; mesh-using entries need >= 2
devices (the CLI forces an 8-device CPU platform — see
analysis/__main__.py — and the test suite already runs on one).

Precision convention for examples: every projection matmul is the
OWNED dense (models/dense.py — explicit ``preferred_element_type``
accumulation), so module-level entries register at the serving dtype
(bf16, plus int8-weight twins) right alongside the raw-op entries
(flash kernels, decode steps, the LM head einsum) — the
fp32/i32-accumulation contract is enforced end to end with zero
waivers (the flax ``linen.Dense`` debt that used to force f32
registration is retired).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from distributed_dot_product_tpu.analysis.registry import TraceSpec
from distributed_dot_product_tpu.models.attention import (
    DistributedDotProductAttn, apply_seq_parallel, make_decode_step,
)
from distributed_dot_product_tpu.models.decode import (
    DecodeCache, PagedDecodeCache, decode_step, init_cache,
    init_paged_cache, init_sharded_paged_cache, init_slot_cache,
)
from distributed_dot_product_tpu.models.lm import TransformerLM
from distributed_dot_product_tpu.obs.spans import span
from distributed_dot_product_tpu.ops.ops import matmul_all, matmul_nt
from distributed_dot_product_tpu.ops.pallas_attention import (
    flash_attention,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh
from distributed_dot_product_tpu.serve.engine import KernelEngine
from distributed_dot_product_tpu.train import make_lm_train_step
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

__all__ = ['ENTRYPOINTS', 'entrypoint']

ENTRYPOINTS = {}        # name -> builder of its TraceSpec, in this order


def entrypoint(name, builder=None):
    """Register ``builder`` (a no-argument callable returning the
    :class:`TraceSpec`) under ``name``; without one, a decorator."""
    if builder is None:
        return partial(entrypoint, name)
    if name in ENTRYPOINTS:
        raise ValueError(f'duplicate entrypoint registration: {name!r}')
    ENTRYPOINTS[name] = builder
    return builder


# -- ops/functions.py ---------------------------------------------------
# The distributed matmuls — forward AND the custom-vjp backward, whose
# kernels are defined in terms of the other two ops — under a real
# 2-device mesh, so the collective-axis rule sees the all_gather /
# ppermute / psum_scatter traffic of both comm impls.

def _matmul_grad(impl):
    mesh = seq_mesh(2)

    def body(a, b):
        scores = matmul_nt(a, b, 2, impl=impl)     # (B, T/N, T)
        return matmul_all(scores, b, 2, impl=impl)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, SEQ_AXIS, None), P(None, SEQ_AXIS, None)),
        out_specs=P(None, SEQ_AXIS, None), check_vma=False)

    def loss(a, b):
        return jnp.sum(sharded(a, b).astype(jnp.float32))

    a = jax.ShapeDtypeStruct((1, 8, 4), jnp.float32)
    return TraceSpec(name=f'ops.matmul_grad_{impl}',
                     fn=jax.grad(loss, argnums=(0, 1)),
                     args=(a, a), mesh_axes=(SEQ_AXIS,))


entrypoint('ops.matmul_grad_allgather', partial(_matmul_grad, 'allgather'))
entrypoint('ops.matmul_grad_ring', partial(_matmul_grad, 'ring'))


# -- ops/pallas_attention.py --------------------------------------------
# The fused flash kernels at bf16 — THE paths whose fp32-accumulation
# contract the f32-accum rule encodes (every in-kernel dot_general must
# carry preferred_element_type=f32, int8 scoring i32). The linter
# descends into the pallas_call jaxprs, so a regression inside a kernel
# body is caught even though the kernel is one opaque primitive to XLA.

def _flash_qkv():
    q = jax.ShapeDtypeStruct((1, 2, 16, 8), jnp.bfloat16)
    return q, q, q


@entrypoint('ops.flash_fwd_bf16')
def _flash_fwd_bf16():
    return TraceSpec(name='ops.flash_fwd_bf16',
                     fn=partial(flash_attention, causal=True),
                     args=_flash_qkv())


@entrypoint('ops.flash_bwd_bf16')
def _flash_bwd_bf16():
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    return TraceSpec(name='ops.flash_bwd_bf16',
                     fn=jax.grad(loss, argnums=(0, 1, 2)),
                     args=_flash_qkv())


@entrypoint('ops.flash_fwd_int8')
def _flash_fwd_int8():
    return TraceSpec(name='ops.flash_fwd_int8',
                     fn=partial(flash_attention, causal=True,
                                qk_quant='int8'),
                     args=_flash_qkv())


# -- models/attention.py ------------------------------------------------
# The module-level attention surfaces on a real 2-device mesh — forward
# and backward through every softmax_impl's comm pattern (all_gather,
# ring ppermute, ulysses all_to_all) for the collective-axis rule, and
# the full sequence-sharded decode step (make_decode_step) for the
# donation + cache-alias rules on the exact callable a serving loop
# holds. The *_bf16 twins trace the module-level surfaces at SERVING
# dtype, so the aliasing/donation/upcast/f32-accum contracts are
# enforced on the program a bf16 deployment actually runs — the
# owned-dense projections accumulate in fp32, so these trace with ZERO
# waivers. The _wq8 twin traces the int8-WEIGHT serving program
# (s8×s8→s32 projection dots + in-kernel dequant).

def _attn_module(softmax_impl, **kw):
    return DistributedDotProductAttn(
        key_dim=8, num_heads=2, causal=True, offset=2,
        softmax_impl=softmax_impl, **kw)


def _attn_fwd(name, softmax_impl, dtype=jnp.float32, **kw):
    mesh = seq_mesh(2)
    module = _attn_module(softmax_impl, dtype=dtype, **kw)
    x = jnp.zeros((1, 16, 8), dtype)
    params = module.init(jax.random.key(0), x, x, x, None)

    def fn(p, k, q, v):
        return apply_seq_parallel(module, p, mesh, k, q, v, None)

    return TraceSpec(name=name, fn=fn, args=(params, x, x, x),
                     mesh_axes=(SEQ_AXIS,))


def _attn_bwd(name, softmax_impl, **kw):
    base = _attn_fwd(name, softmax_impl, **kw)

    def loss(p, k, q, v):
        return jnp.sum(base.fn(p, k, q, v))

    return base.replace(fn=jax.grad(loss, argnums=(0, 1)))


def _seq_parallel_step(name, dtype=jnp.float32):
    mesh = seq_mesh(2)
    module = _attn_module('flash', dtype=dtype)
    x = jnp.zeros((1, 16, 8), dtype)
    params = module.init(jax.random.key(0), x, x, x, None)
    cache = module.make_decode_cache(1, 64)     # global t_max
    step = make_decode_step(module, mesh)       # jitted + donating
    tok = jnp.zeros((1, 1, 8), dtype)
    return TraceSpec(
        name=name, fn=step,
        args=(params, tok, tok, tok, cache),
        mesh_axes=(SEQ_AXIS,), prejitted=True,
        cache_in=lambda a: [a[4].k, a[4].v],
        cache_out=lambda o: [o[0].k, o[0].v],
        expect_donation=True, min_donated=2)


entrypoint('attention.fwd_flash',
           partial(_attn_fwd, 'attention.fwd_flash', 'flash'))
entrypoint('attention.fwd_flash_bf16',
           partial(_attn_fwd, 'attention.fwd_flash_bf16', 'flash',
                   dtype=jnp.bfloat16))
entrypoint('attention.fwd_flash_wq8',
           partial(_attn_fwd, 'attention.fwd_flash_wq8', 'flash',
                   dtype=jnp.bfloat16, weight_quant='int8'))
entrypoint('attention.bwd_full',
           partial(_attn_bwd, 'attention.bwd_full', 'full'))
entrypoint('attention.fwd_ring',
           partial(_attn_fwd, 'attention.fwd_ring', 'online'))
entrypoint('attention.fwd_ulysses',
           partial(_attn_fwd, 'attention.fwd_ulysses', 'ulysses'))
entrypoint('decode.seq_parallel_step',
           partial(_seq_parallel_step, 'decode.seq_parallel_step'))
entrypoint('decode.seq_parallel_step_bf16',
           partial(_seq_parallel_step, 'decode.seq_parallel_step_bf16',
                   dtype=jnp.bfloat16))


# -- models/decode.py ---------------------------------------------------
# The decode steps at the shapes where the contracts bite — bf16 caches
# (cache-upcast/f32-accum), the int8 mirror through the fused kernel
# (int32 accumulation + pallas input_output_aliases), and the
# sequence-sharded slab (collective axes + aliasing across the
# shard_map boundary).

def _cache_io(*names):
    """``cache_in`` / ``cache_out`` of a step ``(q, cache, k, v) ->
    (cache, out)`` over the cache fields ``names``."""
    return dict(
        cache_in=lambda a: [getattr(a[1], n) for n in names],
        cache_out=lambda o: [getattr(o[0], n) for n in names],
        expect_donation=True, donate_argnums=(1,),
        min_donated=len(names))


@entrypoint('decode.step_xla_slots')
def _step_xla_slots():
    b, h, t, d = 2, 2, 32, 8
    cache = init_slot_cache(b, h, t, d, dtype=jnp.bfloat16)
    new = jnp.zeros((b, h, 1, d), jnp.bfloat16)
    return TraceSpec(
        name='decode.step_xla_slots',
        fn=partial(decode_step, impl='xla'),
        args=(new, cache, new, new), **_cache_io('k', 'v'))


@entrypoint('decode.step_kernel_int8')
def _step_kernel_int8():
    b, h, t, d = 1, 2, 64, 8
    cache = init_cache(b, h, t, d, dtype=jnp.bfloat16, qk_quant='int8')
    new = jnp.zeros((b, h, 1, d), jnp.bfloat16)
    return TraceSpec(
        name='decode.step_kernel_int8',
        fn=partial(decode_step, impl='kernel', qk_quant='int8',
                   interpret=True),
        args=(new, cache, new, new),
        **_cache_io('k', 'v', 'k_q', 'k_scale'))


@entrypoint('decode.step_sharded')
def _step_sharded():
    mesh = seq_mesh(2)
    b, h, t, d = 1, 2, 64, 8          # t is the GLOBAL capacity
    cache = init_cache(b, h, t, d, dtype=jnp.bfloat16)
    new = jnp.zeros((b, h, 1, d), jnp.bfloat16)
    spec4 = P(None, None, SEQ_AXIS, None)
    cache_spec = DecodeCache(k=spec4, v=spec4, length=P(),
                             k_q=None, k_scale=None)
    step = jax.shard_map(
        partial(decode_step, impl='xla', axis_name=SEQ_AXIS),
        mesh=mesh, in_specs=(P(), cache_spec, P(), P()),
        out_specs=(cache_spec, P()), check_vma=False)
    return TraceSpec(
        name='decode.step_sharded', fn=step,
        args=(new, cache, new, new), mesh_axes=(SEQ_AXIS,),
        **_cache_io('k', 'v'))


def _paged_args(qk_quant=None):
    b, h, d = 2, 2, 8
    cache = init_paged_cache(b, h, 32, d, pages=6, page_size=8,
                             dtype=jnp.bfloat16, qk_quant=qk_quant)
    # A realistic mid-serve table: slot 0 holds two pages (fill 10),
    # slot 1 one page (fill 3); pool page 3 stays free.
    cache = cache._replace(
        page_table=jnp.array([[0, 1, -1, -1], [2, -1, -1, -1]],
                             jnp.int32),
        length=jnp.array([10, 3], jnp.int32))
    new = jnp.zeros((b, h, 1, d), jnp.bfloat16)
    return cache, new


@entrypoint('decode.step_paged_xla')
def _step_paged_xla():
    cache, new = _paged_args()
    return TraceSpec(
        name='decode.step_paged_xla',
        fn=partial(decode_step, impl='xla'),
        args=(new, cache, new, new), **_cache_io('k_pool', 'v_pool'))


@entrypoint('decode.step_paged_kernel')
def _step_paged_kernel():
    cache, new = _paged_args()
    return TraceSpec(
        name='decode.step_paged_kernel',
        fn=partial(decode_step, impl='kernel', interpret=True),
        args=(new, cache, new, new), **_cache_io('k_pool', 'v_pool'))


@entrypoint('decode.step_paged_kernel_int8')
def _step_paged_kernel_int8():
    # Quantized decode ON the page pool through the fused kernel — the
    # mirror POOLS must alias in place alongside the bf16 pools (4
    # aliased pairs), and every int8 dot must request its i32
    # accumulator.
    cache, new = _paged_args(qk_quant='int8')
    return TraceSpec(
        name='decode.step_paged_kernel_int8',
        fn=partial(decode_step, impl='kernel', qk_quant='int8',
                   interpret=True),
        args=(new, cache, new, new),
        **_cache_io('k_pool', 'v_pool', 'k_q_pool', 'k_scale_pool'))


def _sharded_paged_args():
    # Two shards over a pps=4 table (each owns 2 ordinals); a
    # mid-serve fill: slot 0 holds 10 rows (ordinals 0-1, both
    # shard 0's), slot 1 holds 3 (ordinal 0 → shard 0's page 2).
    b, h, d = 2, 2, 8
    cache = init_sharded_paged_cache(2, b, h, 32, d,
                                     pages_per_shard=3, page_size=8,
                                     dtype=jnp.bfloat16)
    pt = np.full((2, b, 4), -1, np.int32)
    pt[0, 0, 0] = 0
    pt[0, 0, 1] = 1
    pt[0, 1, 0] = 2
    cache = cache._replace(page_table=jnp.asarray(pt),
                           length=jnp.array([10, 3], jnp.int32))
    new = jnp.zeros((b, h, 1, d), jnp.bfloat16)
    return cache, new


def _step_paged_sharded(impl):
    mesh = seq_mesh(2)
    cache, new = _sharded_paged_args()
    cache_spec = PagedDecodeCache(
        k_pool=P(SEQ_AXIS), v_pool=P(SEQ_AXIS),
        page_table=P(SEQ_AXIS), length=P(),
        k_q_pool=None, k_scale_pool=None)

    def body(qq, cc, kk, vv):
        # Each member squeezes its (1, slots, pps) table block into
        # the local view and runs the paged ring-decode step; the
        # merged output is replicated by the psum/pmax rule.
        local = cc._replace(page_table=cc.page_table[0])
        out_cache, out = decode_step(
            qq, local, kk, vv, impl=impl, axis_name=SEQ_AXIS,
            **({'interpret': True} if impl == 'kernel' else {}))
        return (out_cache._replace(
            page_table=out_cache.page_table[None]), out)

    step = jax.shard_map(
        body, mesh=mesh, in_specs=(P(), cache_spec, P(), P()),
        out_specs=(cache_spec, P()), check_vma=False)
    suffix = '_kernel' if impl == 'kernel' else ''
    return TraceSpec(
        name=f'decode.step_paged_sharded{suffix}', fn=step,
        args=(new, cache, new, new), mesh_axes=(SEQ_AXIS,),
        **_cache_io('k_pool', 'v_pool'))


# The paged ring-decode step (XLA formulation): the stacked sharded
# cache through shard_map — collective-axis and cache-alias rules must
# hold across the flash merge. And the same program on the fused kernel
# path: per-shard Pallas partials + the cross-shard pmax/psum merge,
# cache aliased in place per shard.
entrypoint('decode.step_paged_sharded', partial(_step_paged_sharded, 'xla'))
entrypoint('decode.step_paged_sharded_kernel',
           partial(_step_paged_sharded, 'kernel'))


@entrypoint('decode.step_verify_slab')
def _step_verify_slab():
    b, h, t, d, k = 2, 2, 32, 8, 3
    cache = init_slot_cache(b, h, t, d, dtype=jnp.bfloat16)
    cache = cache._replace(length=jnp.array([5, 9], jnp.int32))
    q = jnp.zeros((b, h, k, d), jnp.bfloat16)
    counts = jnp.array([3, 1], jnp.int32)   # mixed spec/non-spec
    return TraceSpec(
        name='decode.step_verify_slab',
        fn=partial(decode_step, impl='kernel', interpret=True,
                   counts=counts),
        args=(q, cache, q, q), **_cache_io('k', 'v'))


@entrypoint('decode.step_verify_paged')
def _step_verify_paged():
    cache, _ = _paged_args()
    q = jnp.zeros((2, 2, 3, 8), jnp.bfloat16)
    counts = jnp.array([3, 2], jnp.int32)
    return TraceSpec(
        name='decode.step_verify_paged',
        fn=partial(decode_step, impl='kernel', interpret=True,
                   counts=counts),
        args=(q, cache, q, q), **_cache_io('k_pool', 'v_pool'))


# -- models/lm.py -------------------------------------------------------
# The LM head at bf16 — its einsum's explicit fp32 accumulation IS the
# PR-3 contract the f32-accum rule encodes — and the chunked token-mean
# loss (nll_sum) whose scan must keep its logsumexp math in f32,
# registered at f32 AND at the bf16 serving dtype. The loss entries
# trace the loss un-differentiated AND its gradient: a rule reads the
# primal through ``head_loss``'s ``custom_vjp`` call but not its forward
# rule (a callable, traced only under differentiation), and that rule
# holds the loss's other two matmuls. The projections are the owned
# dense (models/dense.py), so the bf16 entry traces with zero f32-accum
# waivers.

@entrypoint('lm.head_bf16')
def _lm_head_bf16():
    model = TransformerLM(
        vocab_size=32, dim=16, num_heads=2, n_layers=1,
        dtype=jnp.bfloat16,
        attn_kwargs={'distributed': False})
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    x = jax.ShapeDtypeStruct((1, 8, 16), jnp.bfloat16)

    def fn(p, h):
        return model.apply(p, h, method='_head')

    return TraceSpec(name='lm.head_bf16', fn=fn, args=(params, x))


def _lm_loss(name, dtype=None):
    kw = {} if dtype is None else {'dtype': dtype}
    model = TransformerLM(
        vocab_size=32, dim=16, num_heads=2, n_layers=1,
        attn_kwargs={'distributed': False}, **kw)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    targets = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def fn(p, tok, tgt):
        def nll(p):
            return model.apply(p, tok, tgt, chunk=4, method='nll_sum')

        return nll(p), jax.grad(nll, has_aux=True)(p)[0]

    return TraceSpec(name=name, fn=fn,
                     args=(params, jax.ShapeDtypeStruct(
                         (1, 16), jnp.int32), targets))


entrypoint('lm.loss_f32', partial(_lm_loss, 'lm.loss_f32'))
# The full LM loss at SERVING dtype: the chunked-logsumexp f32 math, the
# head contract AND the owned-dense projection accumulation are all
# enforced on the bf16 program — no waivers (the gate asserts zero
# waived records stay that way).
entrypoint('lm.loss_bf16',
           partial(_lm_loss, 'lm.loss_bf16', dtype=jnp.bfloat16))


# -- serve/engine.py ----------------------------------------------------
# The serving engine's batched decode step — the program the
# continuous-batching scheduler drives per tick — checked for real
# cache donation/aliasing and surgical per-slot writes on the exact
# jitted callable the engine holds.

def _engine_decode(name, **kw):
    eng = KernelEngine(slots=2, decode_impl='xla', **kw)
    paged, sharded = eng.cache_mode == 'paged', eng.kv_shards > 1
    if paged:
        assert eng.prepare_step(np.ones(2, bool)).all()
    if sharded:
        eng._sync_page_table()
    tokens = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    poison = jnp.zeros((2,), bool)
    k, v = ('k_pool', 'v_pool') if paged else ('k', 'v')
    return TraceSpec(
        name=name, fn=eng._decode,
        args=(eng.cache, tokens, active, poison), prejitted=True,
        mesh_axes=(SEQ_AXIS,) if sharded else (),
        cache_in=lambda a: [getattr(a[0], k), getattr(a[0], v)],
        cache_out=lambda o: [getattr(o[0], k), getattr(o[0], v)],
        expect_donation=True, min_donated=2)


entrypoint('serve.engine_decode',
           partial(_engine_decode, 'serve.engine_decode', t_max=16))
entrypoint('serve.engine_decode_paged',
           partial(_engine_decode, 'serve.engine_decode_paged', t_max=16,
                   cache_mode='paged', page_size=8, pages=3))
# The int8-WEIGHT serving program: same decode step, weights stored int8
# — the s8×s8→s32 projection dots must request their i32 accumulator and
# the cache contracts must survive the precision change.
entrypoint('serve.engine_decode_wq8',
           partial(_engine_decode, 'serve.engine_decode_wq8', t_max=16,
                   weight_quant='int8'))
# The cluster-scale long-context serving program: the SAME engine decode
# body shard_mapped over the seq mesh with the page table split 2 ways —
# cache aliasing must survive the shard_map boundary (donation of the
# stacked sharded pools) and the flash-partials merge must keep its
# collectives on the declared mesh axis.
entrypoint('serve.engine_decode_kv_sharded',
           partial(_engine_decode, 'serve.engine_decode_kv_sharded',
                   t_max=32, cache_mode='paged', page_size=8, pages=3,
                   kv_shards=2))


# -- train.py -----------------------------------------------------------
# The full sharded LM train step — forward, chunked loss, cross-shard
# gradient psum, optax update — as ONE traced program on a real 2-device
# mesh, plus the donation check on the jitted step (params and optimizer
# state are donated by default; losing that doubles peak parameter
# memory per step).

@entrypoint('train.lm_step')
def _train_lm_step():
    mesh = seq_mesh(2)
    model = TransformerLM(vocab_size=32, dim=16, num_heads=2, n_layers=1)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    optimizer = optax.sgd(1e-2)
    opt_state = optimizer.init(params)
    step = make_lm_train_step(model, optimizer, mesh, loss_chunk=8)
    targets = jnp.zeros((1, 16), jnp.int32)
    return TraceSpec(name='train.lm_step', fn=step,
                     args=(params, opt_state, (tokens, targets)),
                     mesh_axes=(SEQ_AXIS,), prejitted=True,
                     expect_donation=True, min_donated=1)


# -- obs/spans.py -------------------------------------------------------
# The serving engine's decode program traced THROUGH a host-side span —
# the supported composition — must keep the cache-alias / precision
# contracts unchanged. A span that leaked ops or constants into the
# traced program (the clock-in-jit hazard the AST rule rejects in jitted
# bodies) would surface here as a rule violation or a jaxpr diff against
# the engine's own entry.

@entrypoint('obs.spanned_decode')
def _spanned_decode():
    eng = KernelEngine(slots=2, t_max=16, decode_impl='xla')
    tokens = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    poison = jnp.zeros((2,), bool)

    def dispatch(cache, tokens, active, poison):
        # The span wraps the dispatch from the HOST side; the traced
        # body below it must come out identical to the unspanned
        # engine entry (serve.engine_decode).
        with span('obs.decode_dispatch'):
            return eng._decode_impl(cache, tokens, active, poison)

    return TraceSpec(
        name='obs.spanned_decode', fn=dispatch,
        args=(eng.cache, tokens, active, poison),
        cache_in=lambda a: [a[0].k, a[0].v],
        cache_out=lambda o: [o[0].k, o[0].v])
