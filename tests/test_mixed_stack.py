# -*- coding: utf-8 -*-
"""Window and full attention layers in one stack (the ``cohere2_moe``
block: Command A+): a ring cache beside a growing one, the decode
kernel's ring mode against its XLA oracle and the slab, the share of an
expert layer against the whole layer, the parallel block, layer kinds —
all against the plain reference ``benchmarks/reference/command_a_plus.py``
at tiny widths, float32, seeded weights."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loader  # noqa: E402
from distributed_dot_product_tpu import TransformerLM  # noqa: E402
from distributed_dot_product_tpu.models.decode import (  # noqa: E402
    RingCache, decode_attention, decode_impl_traces, decode_step,
    init_cache, init_ring_cache, insert_session, ring_positions,
)
from distributed_dot_product_tpu.models.lm import (  # noqa: E402
    greedy_generate,
)
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: E402
    TransformerBlock, TransformerStack,
)
from distributed_dot_product_tpu.ops.pallas_decode import (  # noqa: E402
    decode_geometry, flash_decode,
)

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_mixed')
CELL = loader.Cell('tiny-command-a.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
TOL = 5e-6          # float32 on both sides; logits are O(0.5)


# -- (a) the LM through ring and full caches against the reference ------

@pytest.fixture(scope='module')
def tokens():
    return np.random.default_rng(0).integers(
        0, CFG['vocab_size'], size=(1, 80)).astype(np.int32)


@pytest.fixture(scope='module')
def reference_logits(tokens):
    params = DRIVER.make(CFG, 7, jnp.float32)
    REF.ROW_BLOCK = 8
    logits, _, _, judged = REF.logits_at(CFG, params,
                                         jnp.asarray(tokens[0]), 80)
    assert bool(np.all(judged))
    return params, np.asarray(logits)


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
def test_lm_prefill_and_decode_match_the_reference(tokens,
                                                   reference_logits, impl):
    """window 8 < chunk 12 < context 48; the ring (16 columns) wraps in
    prefill and again in decode; a second request after the lengths are
    set back reads what the first did (8 tokens: capacity 16 >= window 8
    + 8), and one of 24 tokens loses rows of the window, which the
    comparison sees."""
    params, want = reference_logits
    model = DRIVER.build_lm(CFG, decode_impl=impl)
    caches = model.make_decode_caches(1, 96)
    assert [type(c).__name__ for c in caches] == [
        'RingCache', 'RingCache', 'RingCache', 'DecodeCache']
    assert [c.k.shape[2] for c in caches] == [16, 16, 16, 96]
    context, chunk = 48, 12
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'))
    got = []
    for i in range(0, context, chunk):
        caches, logits = prefill(params, tokens[:, i:i + chunk], caches)
        got.append(logits)

    def serve(caches, n):
        out = []
        for i in range(context, context + n):
            caches, logits = step(params, tokens[:, i:i + 1], caches)
            out.append(logits)
        return caches, np.concatenate(out, axis=1)[0]

    after, first = serve(caches, 8)
    got = np.concatenate([np.concatenate(got, axis=1)[0], first])
    np.testing.assert_allclose(got, want[:context + 8], atol=TOL)

    def reset(caches):
        return [c._replace(length=jnp.asarray(context, jnp.int32))
                for c in caches]
    after, again = serve(reset(after), 8)
    np.testing.assert_allclose(again, want[context:context + 8], atol=TOL)
    # 24 tokens overwrite rows inside the next request's window.
    after, long = serve(reset(after), 24)
    np.testing.assert_allclose(long, want[context:context + 24], atol=TOL)
    _, lost = serve(reset(after), 8)
    assert np.max(np.abs(lost - want[context:context + 8])) > 100 * TOL


def test_insert_session_puts_a_prefilled_session_in_its_slot(tokens):
    model = DRIVER.build_lm(CFG)
    params = DRIVER.make(CFG, 7, jnp.float32)
    one = model.make_decode_caches(1, 96)
    one, _ = model.apply(params, tokens[:, :24], one, method='prefill')
    batch = model.make_decode_caches(3, 96)
    batch = [insert_session(c, 1, o) for c, o in zip(batch, one)]
    for c, o in zip(batch, one):
        assert type(c) is type(o) and int(c.length) == 24
        np.testing.assert_array_equal(c.k[1], o.k[0])
        np.testing.assert_array_equal(c.v[1], o.v[0])
        assert not np.any(np.asarray(c.k[0])) and not np.any(
            np.asarray(c.k[2]))


# -- (b) the ring mode of the kernel and of decode_attention -------------

def _steps(h, h_kv, window, capacity, t_max, steps, seed=0):
    """``steps`` decode steps on a slab with ``window=`` (XLA), a ring
    through the kernel and a ring through XLA: outputs a step, and the
    two rings."""
    b, d = 2, 16
    key = jax.random.PRNGKey(seed)
    slab = init_cache(b, h_kv, t_max, d, dtype=jnp.float32)
    ring_k = init_ring_cache(b, h_kv, capacity, d, dtype=jnp.float32)
    ring_x = init_ring_cache(b, h_kv, capacity, d, dtype=jnp.float32)
    outs = []
    for _ in range(steps):
        key, kq, kk, kv = jax.random.split(key, 4)
        q = jax.random.normal(kq, (b, h, 1, d))
        kn = jax.random.normal(kk, (b, h_kv, 1, d))
        vn = jax.random.normal(kv, (b, h_kv, 1, d))
        slab, want = decode_step(q, slab, kn, vn, window=window,
                                 impl='xla')
        ring_k, by_kernel = decode_step(q, ring_k, kn, vn, window=window,
                                        impl='kernel')
        ring_x, by_xla = decode_step(q, ring_x, kn, vn, window=window,
                                     impl='xla')
        outs.append((want, by_kernel, by_xla))
    return outs, ring_k, ring_x


@pytest.mark.parametrize('h,h_kv', [(4, 4), (32, 2)],
                         ids=['mha', 'group16'])
def test_ring_step_matches_the_slab_with_a_window(h, h_kv):
    """Before the ring is full (steps 0-7), while it fills (8-15) and
    after it wraps (16-39), for MHA and a group of 16."""
    outs, ring_k, ring_x = _steps(h, h_kv, window=8, capacity=16,
                                  t_max=64, steps=40)
    for want, by_kernel, by_xla in outs:
        np.testing.assert_allclose(by_kernel, want, atol=2e-6)
        np.testing.assert_allclose(by_xla, want, atol=2e-6)
    np.testing.assert_array_equal(ring_k.k, ring_x.k)
    np.testing.assert_array_equal(ring_k.v, ring_x.v)
    assert int(ring_k.length) == int(ring_x.length) == 40


@pytest.mark.parametrize('length', [3, 20, 31, 32, 33, 50, 77])
def test_ring_kernel_over_several_splits(length):
    """A 32-column ring in 8-row K splits, window 20: the valid interval
    lies inside the ring, wraps over its end, covers whole splits and
    leaves whole splits out (which are then neither scored nor
    streamed); the oracle is ``decode_attention`` on the same ring."""
    b, h, h_kv, d, cap, window = 2, 4, 2, 16, 32, 20
    rng = np.random.default_rng(length)
    ring = RingCache(
        k=jnp.asarray(rng.normal(size=(b, h_kv, cap, d)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(b, h_kv, cap, d)), jnp.float32),
        length=jnp.asarray(length, jnp.int32))
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, h_kv, 1, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, h_kv, 1, d)), jnp.float32)
    after, want = decode_step(q, ring, kn, vn, window=window, impl='xla')
    col = jnp.full((b,), length % cap, jnp.int32)
    span = jnp.full((b,), min(length + 1, window), jnp.int32)
    out, new_k, new_v, _, _ = flash_decode(
        q, kn, vn, ring.k, ring.v, col, col, ring_span=span, block_k=8,
        interpret=True)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_array_equal(new_k, after.k)
    np.testing.assert_array_equal(new_v, after.v)


def test_ring_positions():
    assert ring_positions(jnp.asarray(0), 4).tolist() == [-4, -3, -2, -1]
    assert ring_positions(jnp.asarray(3), 4).tolist() == [0, 1, 2, -1]
    assert ring_positions(jnp.asarray(6), 4).tolist() == [4, 5, 2, 3]


def test_ring_mode_refuses_what_it_does_not_cover():
    ring = init_ring_cache(1, 1, 16, 16, dtype=jnp.float32)
    x = jnp.zeros((1, 1, 1, 16))
    with pytest.raises(ValueError, match='window'):
        decode_step(x, ring, x, x, window=32, impl='xla')
    with pytest.raises(ValueError, match='RingCache step takes'):
        decode_step(x, ring, x, x, window=8, alibi_slopes=(1.0,))
    two = jnp.zeros((1, 1, 2, 16))
    with pytest.raises(ValueError, match='single-token'):
        decode_step(two, ring, two, two, window=8, impl='kernel')
    at = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match='ring_span'):
        flash_decode(x, x, x, ring.k, ring.v, at, at, ring_span=at,
                     window=8, interpret=True)
    with pytest.raises(ValueError, match='window'):
        decode_attention(x, ring)


def test_ring_step_reports_its_mode():
    ring = init_ring_cache(1, 1, 16, 16, dtype=jnp.float32)
    x = jnp.zeros((1, 1, 1, 16))
    with decode_impl_traces() as traces:
        decode_step(x, ring, x, x, window=8, impl='kernel')
        decode_step(x, ring, x, x, window=8)
    assert [(t['resolved'], t['cache']) for t in traces] == [
        ('kernel', 'ring'), ('xla', 'ring')]
    assert traces[0]['step'] == {'heads': 1, 'block_k': 16,
                                 'bytes': 16384,   # lanes pad d to 128
                                 'heads_a_pass': 1}


def test_the_cells_geometry():
    """One kernel body, tile parameters from the call's shapes: both
    cache geometries of ``command-a-plus.decode-64k`` (8 KV heads, a
    group of 16, d 128, bfloat16) take MPT's grid step, a whole slot's
    KV heads x 1024 rows, and write back one 16-row tile. The slabs
    move a last split of which no more than 256 rows are filled as
    those rows; the ring's splits are moved whole (its newest split is
    that case, its oldest the mirror image: no second mechanism)."""
    bf16 = jnp.bfloat16
    for t_max in (5120, 66560):
        geom = decode_geometry(t_max, 8, 128, 128, 16, bf16, bf16)
        assert geom == (8, 1024, 16, 4 << 20, 256)
    assert decode_geometry(5120, 8, 128, 128, 16, bf16, bf16,
                           ring=True) == (8, 1024, 16, 4 << 20, None)
    assert decode_geometry(16384, 32, 128, 128, 1, bf16,
                           bf16) == (8, 1024, 16, 4 << 20, 256)


# ``decode_geometry`` of every accepted call — and of the shapes at its
# rules' corners — as the commit before the packed mode returned it (PR
# 51's parent, read there): ``(heads, block_k, write_rows, bytes, tail)``.
# The packed mode is one more argument at its default.
_PARENT_GEOMETRY = {
    'mpt-stacked': ((12544, 32, 128, 128, 1, {}),
                    (16, 256, 16, 2097152, 128)),
    'command-a-full': ((66560, 8, 128, 128, 16, {}),
                       (8, 1024, 16, 4194304, 256)),
    'command-a-ring': ((5120, 8, 128, 128, 16, {'ring': True}),
                       (8, 1024, 16, 4194304, None)),
    'nemotron': ((33792, 2, 128, 128, 16, {}), (2, 1024, 16, 1048576, 256)),
    'granite': ((5120, 8, 128, 128, 4, {}), (8, 1024, 16, 4194304, 256)),
    'solar': ((5120, 8, 128, 128, 8, {}), (8, 1024, 16, 4194304, 256)),
    'sala-slab': ((66560, 2, 128, 128, 16, {}),
                  (2, 1024, 16, 1048576, 256)),
    'padded-d64': ((5120, 8, 64, 64, 4, {}), (8, 1024, 16, 4194304, None)),
    'int8-mirror': ((16384, 32, 128, 128, 1, {'quantized': True}),
                    (2, 1024, 1024, 794624, None)),
    'paged-256': ((16384, 8, 128, 128, 4, {'page_size': 256}),
                  (8, 256, 256, 1048576, None)),
    'verify-4': ((8192, 8, 128, 128, 16, {'n': 4}),
                 (4, 1024, 1024, 2097152, None)),
}


@pytest.mark.parametrize('call', sorted(_PARENT_GEOMETRY))
def test_every_accepted_calls_geometry_is_the_parents(call):
    (t_max, h_kv, d, dv, rows, more), want = _PARENT_GEOMETRY[call]
    bf16 = jnp.bfloat16
    assert decode_geometry(t_max, h_kv, d, dv, rows, bf16, bf16,
                           **more) == want
    # … and the packed form of the 64-wide call beside its padded one:
    # half the bytes a step, and the tail a padded row cannot have.
    if call == 'padded-d64':
        assert decode_geometry(t_max, h_kv, 2 * d, 2 * d, rows, bf16, bf16,
                               packed=True) == (8, 1024, 16, 2097152, 256)


# The slab kernel's program for an MPT-shaped call (MHA, ALiBi, d 128,
# bfloat16, two K splits): the ring mode is Python-level branches only,
# so this does not move with it. Pinned as PR 45 traced it — the call
# takes the tail (its own copies of 256 rows, results in HBM), so the
# program is by necessity another text than the commit before the ring
# mode traced ('ee87a3aa…').
MPT_SHAPED_JAXPR = (
    '9e2c08ced3d467f45f1cba0092e1771c30b538b410c5aa8c6f18781466b13204')


def test_mpt_shaped_slab_call_is_the_program_it_was():
    b, h, d, t = 2, 4, 128, 2048
    slopes = tuple(2.0 ** (-8.0 * (i + 1) / h) for i in range(h))

    def call(q, kn, vn, ck, cv, vt):
        return flash_decode(q, kn, vn, ck, cv, vt, vt,
                            alibi_slopes=slopes, interpret=True)[:3]

    def sh(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(call)(
        sh(b, h, 1, d), sh(b, h, 1, d), sh(b, h, 1, d), sh(b, h, t, d),
        sh(b, h, t, d), jax.ShapeDtypeStruct((b,), jnp.int32))
    assert hashlib.sha256(
        str(jaxpr).encode()).hexdigest() == MPT_SHAPED_JAXPR


# -- (c) the shares of an expert layer add up to the layer ---------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """128 experts over 8 holders of 16, top-8, four shared experts
    averaged and counted once (holder 0 adds them): the parts add up to
    the reference's whole layer."""
    dim, hidden, n_exp, k, n_shared = 16, 8, 128, 8, 4
    cfg = {'num_experts_per_tok': k, 'norm_topk_prob': True,
           'num_shared_experts': n_shared, 'intermediate_size': hidden,
           'published': {'num_experts': n_exp}}
    rng = np.random.default_rng(1)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    whole = {'router': draw(dim, n_exp), 'w_gate': draw(n_exp, dim, hidden),
             'w_up': draw(n_exp, dim, hidden),
             'w_down': draw(n_exp, hidden, dim),
             'shared': {'gate': {'kernel': draw(dim, n_shared * hidden)},
                        'up': {'kernel': draw(dim, n_shared * hidden)},
                        'down': {'kernel': draw(n_shared * hidden, dim)}}}
    x = jnp.asarray(rng.normal(size=(24, dim)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want, picks, _ = REF.expert_layer(cfg, whole, x)
    total = 0
    for share in range(8):
        lo, hi = 16 * share, 16 * share + 16
        layer = SparseExperts(
            n_experts=n_exp, top_k=k, hidden=hidden, n_shared=n_shared,
            shared_combine='mean', router_bias=False,
            experts_held=(lo, hi), add_shared=share == 0)
        mine = {'router': whole['router'],
                **{w: whole[w][lo:hi] for w in ('w_gate', 'w_up',
                                                'w_down')}}
        if share == 0:
            mine['shared'] = whole['shared']
        assert jax.tree.map(jnp.shape, layer.init(
            jax.random.key(0), x)['params']) == jax.tree.map(
                jnp.shape, mine)                  # no router_bias leaf
        part, counts = layer.apply({'params': mine}, x)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert np.array_equal(np.asarray(counts), np.bincount(
        np.asarray(picks).ravel(), minlength=n_exp))


def test_shared_experts_sum_or_mean():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(5, 16)),
                    jnp.float32)
    kw = dict(n_experts=4, top_k=2, hidden=8, n_shared=4)
    params = SparseExperts(**kw).init(jax.random.key(1), x)
    routed = SparseExperts(**kw, add_shared=False).apply(
        {'params': {k: v for k, v in params['params'].items()
                    if k != 'shared'}}, x)[0]
    summed = SparseExperts(**kw).apply(params, x)[0]
    mean = SparseExperts(**kw, shared_combine='mean').apply(params, x)[0]
    np.testing.assert_allclose(mean - routed, (summed - routed) / 4,
                               atol=1e-6)
    with pytest.raises(ValueError, match='shared_combine'):
        SparseExperts(**kw, shared_combine='max').init(
            jax.random.key(1), x)


# -- (d) the parallel block, layer kinds, the dense prefix as one --------

def test_parallel_block_is_one_norm_and_two_branches():
    block = TransformerBlock(
        dim=16, num_heads=2, parallel=True, norm='layernorm_nobias',
        norm_eps=1e-5, ffn='gated', ffn_kwargs={'hidden': 24},
        attn_kwargs=dict(distributed=False, causal=True))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 6, 16)),
                    jnp.float32)
    params = block.init(jax.random.key(0), x)
    assert sorted(params['params']) == ['attn', 'ln1', 'mlp']
    assert sorted(params['params']['ln1']) == ['scale']
    p = jax.tree.map(
        lambda v: v + 0.1 * jnp.arange(v.size).reshape(v.shape) / v.size,
        params)
    h = REF.layer_norm(x, 1e-5, p['params']['ln1']['scale'])
    attn = block.apply(p, h, method=lambda m, h: m.attn(h, h, h, None))
    mlp = block.apply(p, h, method=lambda m, h: m.mlp(h))
    np.testing.assert_allclose(block.apply(p, x), x + attn + mlp,
                               atol=1e-6)
    with pytest.raises(ValueError, match="residual='add'"):
        TransformerBlock(dim=16, num_heads=2, parallel=True,
                         residual='hyper').init(jax.random.key(0), x)


def test_a_period_of_layer_kinds():
    kinds = {'local': {'attn_kwargs': {'window': 4, 'ring_cache': 8}},
             'global': {'ffn': 'gated', 'ffn_kwargs': {'hidden': 24}}}
    stack = TransformerStack(
        dim=16, num_heads=2, n_layers=4,
        attn_kwargs=dict(distributed=False, causal=True,
                         softmax_impl='flash'),
        layer_kinds=kinds, layer_pattern=('local', 'global'))
    x = jnp.zeros((1, 8, 16))
    params = stack.init(jax.random.key(0), x, x, x)['params']
    assert ['mlp' in params[f'block_{i}'] for i in range(4)] == [
        False, True, False, True]
    caches = stack.make_decode_caches(2, 32)
    assert [(type(c).__name__, c.k.shape[2]) for c in caches] == [
        ('RingCache', 8), ('DecodeCache', 32)] * 2
    # t_max no larger than the ring: the slab is the smaller one.
    assert all(type(c).__name__ == 'DecodeCache'
               for c in stack.make_decode_caches(2, 8))
    for bad in (dict(layer_pattern=('local', 'nope')),
                dict(layer_pattern=('local', 'global', 'local'))):
        with pytest.raises(ValueError, match='layer_pattern'):
            TransformerStack(dim=16, num_heads=2, n_layers=4,
                             layer_kinds=kinds, **bad).init(
                                 jax.random.key(0), x, x, x)
    with pytest.raises(ValueError, match='scan_layers=False'):
        TransformerStack(dim=16, num_heads=2, n_layers=4, scan_layers=True,
                         layer_kinds=kinds,
                         layer_pattern=('local', 'global')).init(
                             jax.random.key(0), x, x, x)


# What the commit before layer kinds generated at xing4's tiny preset
# (weights of seed 5, the prompt of seed 3, 12 greedy tokens).
XING4_TINY_TOKENS = [[51, 103, 87, 67, 73, 33, 51, 30, 56, 18, 8, 94],
                     [94, 16, 94, 16, 94, 23, 16, 94, 23, 18, 127, 15]]


def test_dense_prefix_is_a_case_of_layer_kinds():
    """``dense_prefix`` / ``prefix_kwargs`` say a two-kind pattern: the
    same parameter tree and the same tokens as the pattern written out,
    which are the tokens xing4's tiny preset gave before."""
    latent_root = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_latent')
    cell = loader.Cell('tiny-xing4.decode', root=latent_root)
    driver, cfg = cell.driver(), cell.config
    short = driver.build_lm(cfg)
    assert short.dense_prefix == 1
    fields = {f: getattr(short, f) for f in (
        'vocab_size', 'dim', 'num_heads', 'n_layers', 'dtype',
        'scan_layers', 'tie_embeddings', 'attn_kwargs', 'block_kwargs')}
    written = TransformerLM(
        **fields,
        layer_kinds={'dense': short.prefix_kwargs, 'sparse': {}},
        layer_pattern=('dense',) + ('sparse',) * (short.n_layers - 1))
    params = driver.make(cfg, 5, jnp.float32)
    shapes = [jax.tree.map(jnp.shape, jax.eval_shape(
        lambda m=m: m.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32)))['params'])
        for m in (short, written)]
    assert shapes[0] == shapes[1] == jax.tree.map(jnp.shape,
                                                  params['params'])
    prompt = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg['vocab_size'], size=(2, 8)), jnp.int32)
    for model in (short, written):
        out = greedy_generate(model, params, prompt, steps=12, t_max=32)
        assert np.asarray(out).tolist() == XING4_TINY_TOKENS
    with pytest.raises(ValueError, match='dense_prefix'):
        TransformerLM(**fields, dense_prefix=1, layer_kinds={'a': {}},
                      layer_pattern=('a',)).init(
                          jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_logit_scale_scales_the_logits():
    tok = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    kw = dict(vocab_size=16, dim=16, num_heads=2, n_layers=1,
              attn_kwargs=dict(distributed=False))
    params = TransformerLM(**kw).init(jax.random.key(0), tok)
    plain = TransformerLM(**kw).apply(params, tok)
    np.testing.assert_allclose(
        TransformerLM(**kw, logit_scale=0.25).apply(params, tok),
        0.25 * plain, rtol=1e-6)


def test_rotary_pair_layouts_give_the_same_scores():
    """Interleaved pairs are the half-split pairs on permuted features:
    q·k is the same once both sides are permuted alike."""
    from distributed_dot_product_tpu.ops.rope import rope, rope_interleaved
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    pos = 100 + jnp.arange(6)
    inv = 50000.0 ** (-jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    inter = jnp.einsum('hqd,hkd->hqk', rope_interleaved(q, pos, inv),
                       rope_interleaved(k, pos, inv))
    half = jnp.einsum('hqd,hkd->hqk',
                      rope(q[..., perm], pos, base=50000.0),
                      rope(k[..., perm], pos, base=50000.0))
    np.testing.assert_allclose(inter, half, atol=2e-5)
    np.testing.assert_allclose(rope_interleaved(q, pos, inv),
                               REF.rotate(q, pos, 50000.0), atol=1e-6)


def test_the_drivers_shape_table_is_the_models_tree():
    model = DRIVER.build_lm(CFG)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))['params']
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {path: shape for path, (shape, _) in
                    DRIVER.shapes(CFG).items()}


def test_the_configuration_file_states_its_cut():
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           'command-a-plus-serve.json')) as f:
        cfg = json.load(f)
    assert cfg['reduced'] == ['num_hidden_layers', 'num_experts',
                              'vocab_size']
    assert cfg['published'] == {'num_hidden_layers': 32,
                                'num_experts': 128, 'vocab_size': 262144}
    assert (cfg['hidden_size'], cfg['num_attention_heads'],
            cfg['num_key_value_heads'], cfg['head_dim'],
            cfg['intermediate_size'], cfg['num_experts_per_tok'],
            cfg['sliding_window'], cfg['rope_theta']) == (
                4096, 128, 8, 128, 4096, 8, 4096, 50000)
    assert cfg['experts_held'] == [0, cfg['num_experts']] == [0, 16]
    assert cfg['layer_types'][:4] == ['sliding_attention'] * 3 + [
        'full_attention']
    assert cfg['serving']['ring_capacity'] >= cfg['sliding_window'] + 256
