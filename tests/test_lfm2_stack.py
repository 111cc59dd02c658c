# -*- coding: utf-8 -*-
"""The ``lfm2_moe`` stack (LFM2-8B-A1B): gated short-convolution layers
(a window of two rows, no state) beside GQA layers at 64-wide heads on a
PACKED slab (keys and values side by side in one unpadded row), a dense
leading layer and experts with no shared one. The mixer against the
plain reference ``benchmarks/reference/lfm2.py`` (whole sequence,
chunked prefill that continues a window, decode, snapshot / restore,
``insert_session``), the packed slab against the padded one and against
XLA decode at d = 64, the cut stack's prefill-then-decode through its
caches against the reference's full forward pass, and the route rule at
every accepted cell's expert shape. Tiny widths, float32, seeded
weights; both gates of the mixer differ from one, so dropping ONE
fails."""

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
from distributed_dot_product_tpu.models.attention import (  # noqa: E402
    DistributedDotProductAttn,
)
from distributed_dot_product_tpu.models.decode import (  # noqa: E402
    DecodeCache, PackedCache, StateCache, append_kv, decode_impl_traces,
    decode_step, init_cache, init_packed_cache, insert_session,
    packed_append, packed_views, restore_states, snapshot_states,
)
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts, expert_route_traces,
)
from distributed_dot_product_tpu.models.shortconv import (  # noqa: E402
    ShortConvMixer, conv_step_traces,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: E402
    RECURRENT,
)
from distributed_dot_product_tpu.ops.pallas_decode import (  # noqa: E402
    decode_geometry,
)
from distributed_dot_product_tpu.ops.pallas_experts import (  # noqa: E402
    HIT_LIST_ROWS, hit_list_rows,
)

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_lfm2')
CELL = loader.Cell('tiny-lfm2.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
REF.ROW_BLOCK = 8
# float32 on both sides; what is left is the order of float32 sums.
TOL = 2e-5


# -- (a) the mixer ------------------------------------------------------------

DIM, TAPS = 32, 3


def _mixer(seed=0):
    layer = ShortConvMixer(dim=DIM, taps=TAPS)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(2, 24, DIM)),
                    jnp.float32)
    return layer, x, layer.init(jax.random.key(seed), x)


def _reference_mixer(params, x, window=None, **more):
    """``reference/lfm2.conv_block`` a session: the output and the rows
    of ``u`` it saw (the window first)."""
    cfg = {'conv_L_cache': TAPS}
    window = jnp.zeros((TAPS - 1, DIM)) if window is None else window
    with jax.default_matmul_precision('highest'):
        return REF.conv_block(cfg, params['params'], x, window, **more)


def test_the_mixer_is_one_of_the_recurrent_kinds_and_its_state_is_empty():
    assert RECURRENT['conv'] is ShortConvMixer
    layer, _, params = _mixer()
    assert jax.tree.map(jnp.shape, params['params']) == {
        'in_proj': {'kernel': (DIM, 3 * DIM)}, 'conv_kernel': (TAPS, DIM),
        'out_proj': {'kernel': (DIM, DIM)}}
    cache = layer.make_cache(5, jnp.bfloat16)
    assert isinstance(cache, StateCache)
    assert cache.state.shape == (5, 0, 0, 0) and cache.state.size == 0
    assert cache.conv.shape == (5, TAPS - 1, DIM)
    assert cache.conv.dtype == jnp.bfloat16


def test_the_whole_sequence_is_the_references():
    layer, x, params = _mixer()
    got = layer.apply(params, x)
    for s in range(2):
        want, _ = _reference_mixer(params, x[s])
        np.testing.assert_allclose(got[s], want, atol=TOL)


@pytest.mark.parametrize('dropped', ['B', 'C', 'taps'])
def test_a_dropped_gate_or_a_flat_filter_is_seen(dropped):
    """The comparison above has teeth: the same mixer with one gate at
    one, or the filter reading the newest row alone, is far from the
    reference."""
    layer, x, params = _mixer()
    p = params['params']
    want, _ = _reference_mixer(params, x[0])
    b, c, xt = jnp.split(x[0] @ p['in_proj']['kernel'], 3, -1)
    u = xt if dropped == 'B' else b * xt
    rows = jnp.concatenate([jnp.zeros((TAPS - 1, DIM)), u])
    w = p['conv_kernel']
    v = (w[-1] * u if dropped == 'taps' else
         sum(w[j] * rows[j:j + 24] for j in range(TAPS)))
    broken = ((v if dropped == 'C' else c * v)) @ p['out_proj']['kernel']
    assert float(jnp.max(jnp.abs(broken - want))) > 100 * TOL


@pytest.mark.parametrize('chunks', [(24,), (7, 17), (1, 2, 21), (5, 5, 14)])
def test_chunked_prefill_continues_the_window(chunks):
    """Chunks of any length — shorter than the window among them —
    carried through the cache give the whole sequence's rows, and the
    window after them is the reference's last two rows of ``u``."""
    layer, x, params = _mixer(1)
    want = layer.apply(params, x)
    cache, got, at = layer.make_cache(2, jnp.float32), [], 0
    for n in chunks:
        cache, out = layer.apply(params, x[:, at:at + n], cache,
                                 method='prefill', position=at)
        got.append(out)
        at += n
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=TOL)
    for s in range(2):
        _, seen = _reference_mixer(params, x[s])
        np.testing.assert_allclose(cache.conv[s], seen[-(TAPS - 1):],
                                   atol=TOL)


def test_decode_shifts_the_window_and_says_its_form():
    layer, x, params = _mixer(2)
    want = layer.apply(params, x)
    cache, first = layer.apply(params, x[:, :9], layer.make_cache(
        2, jnp.float32), method='prefill')
    got = [first]
    with conv_step_traces() as forms:
        for t in range(9, 24):
            cache, out = layer.apply(params, x[:, t:t + 1], cache,
                                     method='decode')
            got.append(out)
    assert forms == 15 * [{'form': 'shift', 'taps': TAPS, 'channels': DIM}]
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=TOL)
    _, seen = _reference_mixer(params, x[0])
    np.testing.assert_allclose(cache.conv[0], seen[-(TAPS - 1):], atol=TOL)


def test_snapshot_restore_and_insert_session_carry_the_window():
    """A request served from a snapshot, the windows put back, and the
    same request again: the same rows. A session prefilled alone and put
    in its slot is the batch's row."""
    layer, x, params = _mixer(3)
    batch, _ = layer.apply(params, x[:, :10], layer.make_cache(
        2, jnp.float32), method='prefill')
    one, _ = layer.apply(params, x[1:, :10], layer.make_cache(
        1, jnp.float32), method='prefill')
    put = insert_session(layer.make_cache(2, jnp.float32), 1, one)
    np.testing.assert_array_equal(put.conv[1], batch.conv[1])
    assert not np.any(np.asarray(put.conv[0]))
    assert put.state.shape == (2, 0, 0, 0)

    taken = snapshot_states([batch, None])

    def serve(cache):
        out = []
        for t in range(10, 16):
            cache, y = layer.apply(params, x[:, t:t + 1], cache,
                                   method='decode')
            out.append(y)
        return cache, jnp.concatenate(out, 1)
    moved, first = serve(batch)
    assert float(jnp.max(jnp.abs(moved.conv - batch.conv))) > 1e-3
    restored, = [c for c in restore_states([moved, None], taken)
                 if c is not None]
    np.testing.assert_array_equal(restored.conv, batch.conv)
    _, again = serve(restored)
    np.testing.assert_array_equal(first, again)


# -- (b) the packed slab ------------------------------------------------------

B, H, HKV, D = 2, 8, 2, 64


def _rows(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       dtype)


def test_a_packed_row_is_one_lane_tile_and_the_plan_counts_it_once():
    cache = init_packed_cache(B, HKV, 2048, D)
    assert cache.kv.shape == (B, HKV, 2048, 128)
    assert (cache.t_max, cache.head_dim) == (2048, D)
    bf16 = jnp.bfloat16
    packed = decode_geometry(5120, 8, 128, 128, 4, bf16, bf16, packed=True)
    padded = decode_geometry(5120, 8, 64, 64, 4, bf16, bf16)
    # 2 x 64 x 2 B a token a KV head; the padded pair streams 512 and
    # gets no tail (a 64-wide row is no whole lane tile)
    assert packed.bytes // (packed.heads * packed.block_k) == 256
    assert padded.bytes // (padded.heads * padded.block_k) == 512
    assert (packed.heads, packed.tail, padded.tail) == (8, 256, None)
    with pytest.raises(ValueError, match='kv_packed'):
        DistributedDotProductAttn(
            key_dim=96, num_heads=2, kv_packed=True).make_decode_cache(1, 64)


@pytest.mark.parametrize('fill', [5, 300, 1023, 1030, 1279, 1500])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
def test_the_packed_step_is_the_padded_step(fill, dtype):
    """Three tokens appended and attended on a packed slab of two
    splits, kernel and XLA, against the padded ``DecodeCache`` at the
    same rows: the append, the output, and — in bfloat16, where a row is
    whole lane tiles — the TAIL (``fill`` 1030 and 1279: 6 and 255 rows
    into the last split; 1500: past it)."""
    k0, v0 = _rows(1, B, HKV, fill, D, dtype=dtype), _rows(
        2, B, HKV, fill, D, dtype=dtype)
    packed = packed_append(init_packed_cache(B, HKV, 2048, D, dtype), k0, v0)
    padded = append_kv(init_cache(B, HKV, 2048, D, dtype=dtype), k0, v0)
    kernel = packed
    tol = TOL if dtype == jnp.float32 else 2e-2
    for i in range(3):
        q = _rows(10 + i, B, H, 1, D, dtype=dtype)
        kn = _rows(20 + i, B, HKV, 1, D, dtype=dtype)
        vn = _rows(30 + i, B, HKV, 1, D, dtype=dtype)
        with decode_impl_traces() as traces:
            kernel, got = decode_step(q, kernel, kn, vn, impl='kernel')
        packed, xla = decode_step(q, packed, kn, vn, impl='xla')
        padded, want = decode_step(q, padded, kn, vn, impl='xla')
        trace, = traces
        assert (trace['resolved'], trace['cache']) == ('kernel', 'packed')
        assert trace['token_bytes'] == 128 * jnp.dtype(dtype).itemsize
        assert trace['tail'] == 256
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
        np.testing.assert_allclose(np.asarray(xla, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
    np.testing.assert_array_equal(kernel.kv, packed.kv)
    k, v = packed_views(kernel)
    np.testing.assert_array_equal(k, padded.k)
    np.testing.assert_array_equal(v, padded.v)
    assert int(kernel.length) == fill + 3


def test_a_length_set_back_rewinds_the_packed_slab():
    """A request's rows left behind a length set back are never read:
    the next request's first step is the first request's."""
    k0, v0 = _rows(1, B, HKV, 1100, D), _rows(2, B, HKV, 1100, D)
    cache = packed_append(init_packed_cache(B, HKV, 2048, D, jnp.float32),
                          k0, v0)
    q, kn, vn = (_rows(3, B, H, 1, D), _rows(4, B, HKV, 1, D),
                 _rows(5, B, HKV, 1, D))
    served, first = decode_step(q, cache, kn, vn, impl='kernel')
    for i in range(4):
        served, _ = decode_step(_rows(40 + i, B, H, 1, D), served,
                                _rows(50 + i, B, HKV, 1, D),
                                _rows(60 + i, B, HKV, 1, D), impl='kernel')
    back = served._replace(length=cache.length)
    _, again = decode_step(q, back, kn, vn, impl='kernel')
    np.testing.assert_array_equal(first, again)


def test_insert_session_and_the_step_refuse_what_they_do_not_cover():
    one = packed_append(init_packed_cache(1, HKV, 256, D, jnp.float32),
                        _rows(1, 1, HKV, 40, D), _rows(2, 1, HKV, 40, D))
    batch = insert_session(init_packed_cache(3, HKV, 256, D, jnp.float32),
                           2, one)
    assert isinstance(batch, PackedCache) and int(batch.length) == 40
    np.testing.assert_array_equal(batch.kv[2], one.kv[0])
    assert not np.any(np.asarray(batch.kv[:2]))
    q = _rows(3, 3, H, 1, D)
    with pytest.raises(ValueError, match='PackedCache'):
        decode_step(q, batch, q[:, :HKV], q[:, :HKV], qk_quant='int8')
    with pytest.raises(ValueError, match='packed cache'):
        decode_step(_rows(3, 3, H, 1, 32), batch, q[:, :HKV], q[:, :HKV])


def test_the_module_prefills_and_decodes_on_the_packed_cache():
    """The attention module with ``kv_packed`` — per-head norms, RoPE,
    GQA at 64-wide heads — prefilled in two chunks and decoded, against
    its own causal forward and against the same module on the padded
    cache."""
    kw = dict(key_dim=128, num_heads=2, num_kv_heads=1, causal=True,
              softmax_impl='flash', use_rope=True, rope_base=1e6,
              qk_norm=True, qk_norm_eps=1e-5, distributed=False)
    x = _rows(7, 2, 40, 128)
    packed = DistributedDotProductAttn(**kw, kv_packed=True)
    padded = DistributedDotProductAttn(**kw)
    params = padded.init(jax.random.key(0), x, x, x)
    want = padded.apply(params, x, x, x)

    def run(module):
        cache = module.make_decode_cache(2, 256, dtype=jnp.float32)
        got = []
        for lo, hi in ((0, 16), (16, 33)):
            cache, out = module.apply(params, x[:, lo:hi], x[:, lo:hi],
                                      x[:, lo:hi], cache, method='prefill')
            got.append(out)
        for t in range(33, 40):
            cache, out = module.apply(params, x[:, t:t + 1], x[:, t:t + 1],
                                      x[:, t:t + 1], cache, method='decode')
            got.append(out)
        return cache, jnp.concatenate(got, 1)
    cache, got = run(packed)
    assert isinstance(cache, PackedCache) and int(cache.length) == 40
    other, same = run(padded)
    assert isinstance(other, DecodeCache)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(got, same, atol=TOL)
    np.testing.assert_allclose(packed_views(cache)[0], other.k, atol=TOL)


# -- (c) the cut stack against the reference's full forward pass -----------------

def _stack(seed=11):
    model = DRIVER.build_lm(CFG)
    params = DRIVER.level_routers(
        CFG, DRIVER.make(CFG, seed, jnp.float32), seed)
    return model, params


def _served(model, params, tokens, chunks, t_max=64):
    """``tokens (T,)`` through one session's caches: prefill in
    ``chunks``, the rest one token a step; logits of every position and
    the expert picks, ``(expert layers, T, k)``."""
    caches = model.make_decode_caches(1, t_max)
    logits, picks, at = [], [], 0

    def call(method, lo, hi, caches):
        (caches, out), sown = model.apply(
            params, jnp.asarray(tokens[None, lo:hi]), caches, method=method,
            mutable=['counters'])
        picks.append(DRIVER.sown_counters(CFG, sown)['expert_picks'])
        logits.append(out[0])
        return caches
    for n in chunks:
        caches = call('prefill', at, at + n, caches)
        at += n
    for t in range(at, len(tokens)):
        caches = call('decode', t, t + 1, caches)
    return (caches, jnp.concatenate(logits), jnp.concatenate(picks, 1))


def test_the_stack_is_the_configurations_and_its_caches_by_kind():
    model, params = _stack()
    kinds = DRIVER.layer_kinds(CFG)
    assert kinds == ['conv', 'conv', 'attn', 'conv', 'attn']
    caches = model.make_decode_caches(3, 64)
    assert [type(c).__name__ for c in caches] == [
        'StateCache', 'StateCache', 'PackedCache', 'StateCache',
        'PackedCache']
    blocks = params['params']['stack']
    assert set(blocks['block_0']) == {'ln1', 'conv', 'ln2', 'mlp'}
    assert set(blocks['block_1']) == {'ln1', 'conv', 'ln2', 'moe'}
    assert set(blocks['block_2']) == {'ln1', 'attn', 'ln2', 'moe'}
    assert 'shared' not in blocks['block_1']['moe']
    assert 'lm_head_kernel' not in params['params']      # the tied head
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert jax.tree.map(jnp.shape, shapes['params']) == jax.tree.map(
        jnp.shape, params['params'])


@pytest.mark.parametrize('chunks', [(24,), (9, 15)])
def test_prefill_then_decode_is_the_references_full_forward(chunks):
    """40 tokens: 24 prefilled (in one chunk, in two), 16 decoded, every
    position's logits against the reference's full forward pass over
    the same tokens, fed the program's picks and judging them by its
    own scores; the windows after the last token against the
    reference's last two rows of ``u``."""
    model, params = _stack()
    tokens = np.random.default_rng(5).integers(0, CFG['vocab_size'], 40,
                                               dtype=np.int32)
    caches, logits, picks = _served(model, params, tokens, chunks)
    want, own, regret, windows, rows = REF.logits_at(
        CFG, params, jnp.asarray(tokens), 40, forced_picks=picks)
    np.testing.assert_allclose(logits, want, atol=5 * TOL)
    np.testing.assert_array_equal(np.sort(own, -1), np.sort(picks, -1))
    assert float(jnp.max(regret)) < 1e-6
    served = jnp.stack([c.conv[0] for c in caches if hasattr(c, 'conv')])
    assert windows.shape == served.shape == (3, 2, CFG['hidden_size'])
    np.testing.assert_allclose(served, windows, atol=TOL)
    assert [int(c.length) for c in caches if hasattr(c, 'length')] == [
        40, 40]
    # … and what the packed slabs hold against the reference's keys
    # (normed, rotated) and values, side by side
    held = jnp.stack([c.kv[0, :, :40] for c in caches if hasattr(c, 'kv')])
    assert rows.shape == held.shape == (2, 1, 40, 128)
    np.testing.assert_allclose(held, rows, atol=TOL)
    assert DRIVER.rows_gap(held, rows) < 1e-5
    assert DRIVER.rows_gap(held, REF.logits_at(
        CFG, params, jnp.asarray(tokens), 40, forced_picks=picks,
        kv_dtype=jnp.float8_e4m3fn)[4]) > 1e-2


def test_the_comparison_fails_a_rounded_window_and_a_rounded_cache():
    """The same comparison with the REFERENCE's operands and windows
    rounded to bfloat16, and with its keys and values alone rounded to
    float8: both far outside what float32 leaves."""
    model, params = _stack()
    tokens = np.random.default_rng(5).integers(0, CFG['vocab_size'], 40,
                                               dtype=np.int32)
    _, logits, picks = _served(model, params, tokens, (24,))
    for kwargs in ({'operand_dtype': jnp.bfloat16},
                   {'kv_dtype': jnp.float8_e4m3fn}):
        want = REF.logits_at(CFG, params, jnp.asarray(tokens), 40,
                             forced_picks=picks, **kwargs)[0]
        assert float(jnp.max(jnp.abs(logits - want))) > 100 * TOL


def test_valid_reads_the_window_behind_the_last_real_row():
    """Padding behind the sequence's end leaves the reference's windows
    where the last real token put them."""
    _, params = _stack()
    tokens = np.random.default_rng(6).integers(0, CFG['vocab_size'], 40,
                                               dtype=np.int32)
    short = REF.logits_at(CFG, params, jnp.asarray(tokens[:24]), 8)[3]
    for valid in (17, 24, 25, 33):
        padded = REF.logits_at(CFG, params, jnp.asarray(tokens), 8,
                               valid=valid)[3]
        whole = REF.logits_at(CFG, params, jnp.asarray(tokens[:valid + (
            -valid) % 8]), 8, valid=valid)[3]
        np.testing.assert_allclose(padded, whole, atol=TOL)
    np.testing.assert_allclose(
        REF.logits_at(CFG, params, jnp.asarray(tokens), 8, valid=24)[3],
        short, atol=TOL)


# -- (d) the route rule -------------------------------------------------------

def _expert_shapes():
    """``{cell: (wide, hidden, held, router width, k)}`` of every
    accepted expert cell's routed experts and this one's, from the
    configurations."""
    def cfg(name):
        with open(os.path.join(ROOT, 'benchmarks', 'configs',
                               f'{name}.json')) as f:
            return json.load(f)
    out = {}
    c = cfg('xing4-29b-a4b-serve')
    out['xing4'] = (c['hidden_size'], c['moe_intermediate_size'], 64, 64,
                    c['num_experts_per_tok'])
    c = cfg('command-a-plus-serve')
    lo, hi = c['experts_held']
    out['command-a'] = (c['hidden_size'], c['intermediate_size'], hi - lo,
                        c['published']['num_experts'],
                        c['num_experts_per_tok'])
    c = cfg('nemotron-3-super-serve')
    lo, hi = c['experts_held']
    out['nemotron'] = (c['moe_latent_size'], c['moe_intermediate_size'],
                       hi - lo, c['published']['n_routed_experts'],
                       c['num_experts_per_tok'])
    c = cfg('granite-4.0-h-small-serve')
    lo, hi = c['experts_held']
    out['granite'] = (c['hidden_size'], c['intermediate_size'], hi - lo,
                      c['published']['num_local_experts'],
                      c['num_experts_per_tok'])
    c = cfg('solar-open2-250b-serve')
    lo, hi = c['experts_held']
    out['solar'] = (c['hidden_size'], c['moe_intermediate_size'], hi - lo,
                    c['published']['n_routed_experts'],
                    c['num_experts_per_tok'])
    c = cfg('ling-3.0-flash-serve')
    lo, hi = c['experts_held']
    out['ling'] = (c['hidden_size'], c['moe_intermediate_size'], hi - lo,
                   c['published']['num_experts'], c['num_experts_per_tok'])
    c = cfg('lfm2-8b-a1b-serve')
    out['lfm2'] = (c['hidden_size'], c['moe_intermediate_size'],
                   c['num_experts'], c['num_experts'],
                   c['num_experts_per_tok'])
    return out


SHAPES = _expert_shapes()
# The rows of the largest decode call each accepted cell makes a step.
DECODE_ROWS = {'xing4': 16, 'command-a': 12, 'nemotron': 48, 'granite': 80,
               'solar': 128, 'ling': 96, 'lfm2': 256}


@pytest.mark.parametrize('rows', [127, 128, 129, 256, 4096])
@pytest.mark.parametrize('cell', sorted(SHAPES))
def test_the_rule_routes_every_cells_expert_shape(cell, rows):
    """The rule at each cell's published expert shape, traced (nothing
    runs): every call of at most 128 rows the kernel — every accepted
    decode call among them —, a 4096-row prefill chunk the sorted route,
    and between them the rows' resident blocks decide: 256 rows of a
    stream 2048 or narrower the kernel (this cell's decode call), of a
    wider one the sorted route."""
    wide, hidden, held, width, k = SHAPES[cell]
    bound = hit_list_rows(wide)
    assert bound == (2 * HIT_LIST_ROWS if wide <= 2048 else HIT_LIST_ROWS)
    assert DECODE_ROWS[cell] <= bound
    layer = SparseExperts(n_experts=width, top_k=k, hidden=hidden,
                          n_shared=0, experts_held=(0, held),
                          dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((rows, wide), jnp.bfloat16)
    with expert_route_traces() as traces:
        jax.eval_shape(lambda x: layer.init_with_output(
            jax.random.key(0), x)[0][0], x)
    trace, = traces
    want = 'hit_list' if rows <= bound else 'sorted'
    assert (trace['route'], trace['n'], trace['bound'],
            trace['bound_by']) == (want, rows, bound, 'rule')
    if rows <= 128:
        assert want == 'hit_list'
    if rows == 4096:
        assert want == 'sorted'
    if cell == 'lfm2' and rows == 256:
        assert want == 'hit_list'
