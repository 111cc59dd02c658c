# -*- coding: utf-8 -*-
"""Learned block-sparse attention (``models/sparse.py``,
``ops/pallas_sparse.py``), each piece against the token-by-token
equations in float32 at small sizes: the pooled-key cache (prefill in
chunks = decode token by token; a length set back needs no restore), the
selection (forced blocks, ties, fewer blocks than ``topk``, the dense /
sparse switch at ``dense_len``), the kernel ``sparse_decode`` under the
interpreter against a gathered softmax, and the attention module's three
entry points against one another."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.models.attention import (
    DistributedDotProductAttn,
)
from distributed_dot_product_tpu.models.decode import (
    SparseCache, init_sparse_cache, insert_session, sparse_decode_traces,
)
from distributed_dot_product_tpu.models.sparse import (
    SparseSpec, block_scores, pick_blocks, pool_rows, pooled_after_chunk,
    pooled_after_step, sparse_attention, sparse_select, sparse_step,
)
from distributed_dot_product_tpu.models import sparse as sparse_model
from distributed_dot_product_tpu.ops.pallas_sparse import (
    picks_group, sorted_picks, sparse_decode, sparse_decode_reference,
    threshold_picks,
)

SPEC = SparseSpec(kernel=8, stride=4, block=16, init_blocks=1, window=32,
                  topk=4, dense_len=64)
D = 16


def normal(seed, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, dtype)


# -- the equations, literally ---------------------------------------------------

def pooled_by_hand(keys, spec, n):
    """``K̄_j`` for every complete ``j``: ``keys (n, d)`` numpy."""
    rows = max((n - spec.kernel) // spec.stride + 1, 0)
    return np.stack([keys[j * spec.stride:j * spec.stride + spec.kernel]
                     .mean(0) for j in range(rows)]) if rows else (
                         np.zeros((0, keys.shape[-1])))


def picks_by_hand(q, keys, spec, n):
    """The pick set of one KV head's query group ``q (heads, d)`` at
    ``n`` keys so far (``keys (n, d)``): the docstring of
    ``models/sparse.py`` with Python loops."""
    own = (n - 1) // spec.block
    if n <= spec.dense_len:
        return list(range(own + 1))
    pooled = pooled_by_hand(keys, spec, n)
    s = q @ pooled.T / math.sqrt(q.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    s = (p / p.sum(-1, keepdims=True)).sum(0)
    scores = []
    for b in range(own + 1):
        js = [j for j in range(len(s))
              if j * spec.stride < (b + 1) * spec.block
              and j * spec.stride + spec.kernel > b * spec.block]
        score = max([s[j] for j in js], default=-1.0)
        if b < spec.init_blocks or b > own - spec.window // spec.block:
            score = np.inf
        scores.append(score)
    order = sorted(range(own + 1), key=lambda b: (-scores[b], b))
    return sorted(order[:spec.topk])


def attend_by_hand(q, keys, values, picks, spec, n):
    rows = [r for b in picks for r in range(b * spec.block,
                                            (b + 1) * spec.block) if r < n]
    s = q @ keys[rows].T / math.sqrt(q.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ values[rows]


# -- the pooled-key cache -------------------------------------------------------

def test_pool_rows_is_the_mean_of_a_window_every_stride():
    rows = normal(0, 2, 3, 28, D)
    pooled = pool_rows(rows, SPEC)
    assert pooled.shape == (2, 3, 6, D)
    for j in range(6):
        np.testing.assert_allclose(
            pooled[:, :, j], rows[:, :, 4 * j:4 * j + 8].mean(2),
            rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize('chunks', [(96,), (32, 32, 32), (40, 8, 48),
                                    (7, 9, 50, 30)],
                         ids=['whole', 'strides', 'ragged', 'odd'])
def test_prefill_in_chunks_writes_the_rows_decode_writes(chunks):
    """Pooled rows after a prompt written in chunks of any lengths, and
    after the same rows written token by token: the complete rows agree
    with the hand-made means to rounding."""
    t_max, n = 128, sum(chunks)
    keys = normal(1, 2, 2, t_max, D)
    slab = jnp.zeros_like(keys)
    by_chunk = jnp.zeros((2, 2, t_max // 4, D))
    start = 0
    for c in chunks:
        slab = slab.at[:, :, start:start + c].set(
            keys[:, :, start:start + c])
        by_chunk = pooled_after_chunk(slab, by_chunk, jnp.int32(start), c,
                                      SPEC)
        start += c
    slab = jnp.zeros_like(keys)
    by_step = jnp.zeros_like(by_chunk)
    step = jax.jit(lambda s, p, k, i: pooled_after_step(s, k, p, i, SPEC))
    for i in range(n):
        new = keys[:, :, i:i + 1]
        by_step = step(slab, by_step, new, jnp.int32(i))
        slab = slab.at[:, :, i:i + 1].set(new)
    rows = (n - 8) // 4 + 1
    want = np.stack([np.asarray(keys[:, :, 4 * j:4 * j + 8]).mean(2)
                     for j in range(rows)], axis=2)
    np.testing.assert_allclose(by_chunk[:, :, :rows], want, atol=1e-6)
    np.testing.assert_allclose(by_step[:, :, :rows], want, atol=1e-6)


def test_a_chunk_that_ends_at_t_max_writes_no_row_out_of_place():
    t_max = 64
    keys = normal(2, 1, 1, t_max, D)
    pooled = pooled_after_chunk(keys, jnp.full((1, 1, 16, D), 7.0),
                                jnp.int32(32), 32, SPEC)
    rows = (64 - 8) // 4 + 1                                # 15 complete
    for j in range(7, rows):
        np.testing.assert_allclose(
            pooled[0, 0, j], keys[0, 0, 4 * j:4 * j + 8].mean(0), atol=1e-6)
    np.testing.assert_array_equal(pooled[0, 0, :7], 7.0)    # left alone


# -- the selection --------------------------------------------------------------

@pytest.mark.parametrize('n', [1, 15, 16, 17, 63, 64, 65, 80, 97, 128, 191,
                               192])
def test_selection_is_the_equations(n):
    """Picks and count at ``n`` keys so far, below, at and above
    ``dense_len``, against the loops: all blocks below, the forced
    blocks (the first, the last two up to the token's own) and the best
    of the rest above."""
    t_max, heads, kv = 192, 4, 2
    keys = normal(3, 1, kv, t_max, D)
    q = normal(4, 1, heads, 1, D) * 3.0
    pooled = jnp.zeros((1, kv, t_max // 4, D))
    rows = max((n - 8) // 4 + 1, 0)
    if rows:
        pooled = pooled.at[:, :, :rows].set(pool_rows(
            keys[:, :, :4 * rows + 4], SPEC))
    picks, count = sparse_select(q, pooled, jnp.asarray([n]), SPEC,
                                 1 / math.sqrt(D), t_max // 16)
    assert picks.shape == (1, kv, 1, SPEC.picks) and SPEC.picks == 4
    for g in range(kv):
        want = picks_by_hand(np.asarray(q[0, 2 * g:2 * g + 2, 0]),
                             np.asarray(keys[0, g, :n]), SPEC, n)
        c = int(count[0])
        assert c == len(want)
        assert np.asarray(picks[0, g, 0, :c]).tolist() == want
        assert want[-1] == (n - 1) // 16       # the token's own block
        # past the count: a repeat, a block of the cache all the same
        assert np.all(np.asarray(picks[0, g, 0, c:]) <= (192 // 16) - 1)


def test_ties_go_to_the_lower_block_and_forced_blocks_come_first():
    keys = jnp.asarray([130])
    scores = jnp.full((1, 1, 1, 12), 0.25)
    own = (130 - 1) // 16                                       # block 8
    b = jnp.arange(12)
    forced = (b < 1) | (b > own - 2)
    scores = jnp.where(forced, jnp.inf, scores)
    scores = jnp.where(b <= own, scores, -jnp.inf)
    picks, count = pick_blocks(scores, keys, SPEC)
    # block 0, the local two (7, 8), and of the all-equal rest the lowest
    assert np.asarray(picks[0, 0, 0]).tolist() == [0, 1, 7, 8]
    assert int(count[0]) == 4


def _forced(scores, own, local):
    """``block_scores``' last two lines: block 0 and the ``local`` blocks
    up to ``own`` at ``+inf``, ``-inf`` past ``own``."""
    b = jnp.arange(scores.shape[-1])
    scores = jnp.where((b < 1) | (b > own - local), jnp.inf, scores)
    return jnp.where(b <= own, scores, -jnp.inf)


def _pick_case(name):
    """``(scores (…, n) float32, k)`` of one case of the threshold pick."""
    coarse = jnp.round(normal(11, 8, 300) * 2) / 2      # ~12 levels of 300
    return {
        'one_row': lambda: (normal(12, 1, 1, 1, 200), 16),
        'step_128_rows': lambda: (jnp.abs(normal(13, 64, 2, 1, 1040)), 64),
        'chunk_1024_rows': lambda: (
            jnp.abs(normal(14, 1, 2, 512, 1040)), 64),
        'whole_lane_tiles': lambda: (normal(15, 3, 256), 128),
        'ties_across_the_kth_place': lambda: (coarse, 37),
        'every_score_equal': lambda: (jnp.full((2, 140), 0.25), 9),
        'signed_zeros': lambda: (
            jnp.asarray([[0.0, -0.0, 0.0, -0.0, -1.0, 0.0, -0.0, 1.0]]), 4),
        'forced_blocks_and_none_past_the_own': lambda: (
            _forced(jnp.abs(normal(16, 4, 2, 1, 1040)) * 0.004, 1027, 32),
            64),
        'forced_blocks_tied_rest': lambda: (
            _forced(jnp.full((1, 1, 1, 12), 0.25), 8, 2), 4),
        'more_picks_than_live_blocks': lambda: (
            _forced(jnp.abs(normal(17, 2, 40)), 5, 2), 8),
        'incomplete_rows': lambda: (
            _forced(jnp.where(jnp.arange(40) % 3 > 0, -1.0,
                              jnp.abs(normal(18, 5, 40))), 30, 2), 16),
        'every_block_picked': lambda: (normal(19, 3, 40), 40),
        'picks_not_a_whole_group': lambda: (normal(20, 2, 2, 1, 24), 6),
    }[name]()


@pytest.mark.parametrize('name', [
    'one_row', 'step_128_rows', 'chunk_1024_rows', 'whole_lane_tiles',
    'ties_across_the_kth_place', 'every_score_equal', 'signed_zeros',
    'forced_blocks_and_none_past_the_own', 'forced_blocks_tied_rest',
    'more_picks_than_live_blocks', 'incomplete_rows', 'every_block_picked',
    'picks_not_a_whole_group'])
def test_the_threshold_pick_is_the_sorted_pick_entry_for_entry(name):
    """``threshold_picks`` (the Pallas program, interpreted) against
    ``lax.top_k`` and a sort of its picks: 1, 128 and 1024 rows, blocks
    a whole number of lane tiles and not, ties that straddle the k-th
    place (to the lower block), ``+0.0`` above ``-0.0`` as XLA's order
    has them, the forced ``+inf`` blocks with ``-inf`` past the token's
    own (and fewer live blocks than picks: the ``-inf`` ones by number),
    the ``-1.0`` of incomplete pooled rows, ``k`` = every block, and a
    ``k`` that is no multiple of the kernel's copy group."""
    scores, k = _pick_case(name)
    want = sorted_picks(scores, k)
    assert want.shape == scores.shape[:-1] + (k,)
    np.testing.assert_array_equal(
        threshold_picks(scores, k, interpret=True), want)


def test_threshold_picks_refuses_what_it_does_not_cover():
    for scores, k in ((normal(21, 2, 40).astype(jnp.bfloat16), 4),
                      (normal(21, 2, 40), 41), (normal(21, 2, 40), 0)):
        with pytest.raises(ValueError, match='float32 scores'):
            threshold_picks(scores, k, interpret=True)


@pytest.mark.parametrize('n', [60, 128, 130, 192])
def test_both_routes_of_the_selection_give_one_pick_list(n, monkeypatch):
    """``pick_blocks`` on its TPU route (steered here: the program asks
    the backend) against its route off the TPU, below and above
    ``dense_len``, under a ``SparseSpec`` whose ``topk`` 6 is no whole
    copy group of its 8-entry pick list: picks, padding and count
    alike."""
    spec = SparseSpec(kernel=8, stride=4, block=16, window=32, topk=6,
                      dense_len=128)
    assert spec.picks == 8 and picks_group(spec.picks, spec.block) == 8
    scores = block_scores(normal(22, 2, 4, 3, D) * 3.0,
                          normal(23, 2, 2, 48, D),
                          jnp.asarray([n - 2, n - 1, n]), spec, 0.25, 12)
    keys = jnp.asarray([n - 2, n - 1, n])
    assert sparse_model.pick_form() == 'sort'
    want = pick_blocks(scores, keys, spec)
    monkeypatch.setattr(sparse_model, 'pick_form', lambda: 'threshold')
    got = pick_blocks(scores, keys, spec)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_a_cache_of_fewer_blocks_than_topk_picks_them_all():
    spec = SparseSpec(kernel=8, stride=4, block=16, window=16, topk=4,
                      dense_len=64)
    scores = block_scores(normal(5, 1, 2, 1, D), jnp.zeros((1, 1, 12, D)),
                          jnp.asarray([48]), spec, 0.25, 3)
    picks, count = pick_blocks(scores, jnp.asarray([48]), spec)
    assert np.asarray(picks[0, 0, 0]).tolist() == [0, 1, 2, 2]
    assert int(count[0]) == 3


def test_spec_refuses_sizes_that_do_not_fit_together():
    for bad in (dict(kernel=6), dict(window=24), dict(topk=2, window=32),
                dict(dense_len=32)):
        with pytest.raises(ValueError, match='whole strides'):
            SparseSpec(**{**dict(kernel=8, stride=4, block=16, window=32,
                                 topk=4, dense_len=64), **bad})
    assert SparseSpec().picks == 128 and SPEC.picks == 4


# -- the kernel -----------------------------------------------------------------

def kernel_case(dtype, length, picks, count, heads=8, kv=2, block=16,
                t_max=256, n_picks=8):
    args = [normal(i, *shape).astype(dtype) for i, shape in enumerate((
        (2, heads, 1, 128), (2, kv, 1, 128), (2, kv, 1, 128),
        (2, kv, t_max, 128), (2, kv, t_max, 128)))]
    picks = jnp.broadcast_to(jnp.asarray(picks, jnp.int32),
                             (2, kv, n_picks))
    return args, picks, count, length, block


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('length,picks,count', [
    (200, [0, 3, 5, 6, 9, 10, 11, 12], 8),   # one run among loose picks
    (127, [0, 1, 2, 3, 4, 5, 6, 7], 8),      # adjacent: one copy a group
    (37, [0, 1, 2, 2, 2, 2, 2, 2], 3),       # a short list, repeats behind
    (96, [0, 1, 3, 4, 5, 6, 6, 6], 7),       # the token opens a block
    (115, [0, 2, 3, 4, 5, 6, 7, 7], 7),      # a repeat spans the gap
    (0, [0, 0, 0, 0, 0, 0, 0, 0], 1)],       # the first token
    ids=['runs', 'adjacent', 'short', 'opens', 'gapped', 'first'])
def test_sparse_decode_is_the_gathered_softmax(dtype, length, picks, count):
    """The kernel under the interpreter against the plain form: the
    output, and both buffers with the token's row written at
    ``length`` and nothing else touched."""
    args, picks, count, length, block = kernel_case(dtype, length, picks,
                                                    count)
    want = sparse_decode_reference(*args, picks, count, length, block=block)
    got = sparse_decode(*args, picks, count, length, block=block,
                        interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
        atol=tol, rtol=tol)
    for new, old, mine in ((got[1], args[3], args[1]),
                           (got[2], args[4], args[2])):
        np.testing.assert_array_equal(new[:, :, length], mine[:, :, 0])
        np.testing.assert_array_equal(
            np.delete(np.asarray(new, np.float32), length, axis=2),
            np.delete(np.asarray(old, np.float32), length, axis=2))


def test_sparse_decode_reads_no_row_past_the_token():
    """Rows of the token's own block behind it (an abandoned request's)
    and picks past the count change nothing."""
    args, picks, count, length, block = kernel_case(
        jnp.float32, 100, [0, 2, 5, 6, 1, 1, 1, 1], 4)
    out = sparse_decode(*args, picks, count, length, block=block,
                        interpret=True)[0]
    dirty = [a for a in args]
    for i in (3, 4):
        dirty[i] = dirty[i].at[:, :, 101:112].set(1e4).at[
            :, :, 16:32].set(-1e4)         # behind the token; block 1
    again = sparse_decode(*dirty, picks, count, length, block=block,
                          interpret=True)[0]
    np.testing.assert_array_equal(out, again)


def test_sparse_decode_refuses_what_it_does_not_cover():
    args, picks, count, length, _ = kernel_case(jnp.float32, 5, [0] * 8, 1)
    with pytest.raises(ValueError, match='whole blocks'):
        sparse_decode(*args, picks, count, length, block=24, interpret=True)
    assert picks_group(128, 64) == 16 and picks_group(4, 16) == 4
    assert picks_group(6, 16) == 2


# -- the module -----------------------------------------------------------------

def module(**kw):
    return DistributedDotProductAttn(**{**dict(
        key_dim=64, num_heads=4, num_kv_heads=2, causal=True,
        softmax_impl='flash', distributed=False, qk_norm=True,
        out_gate=True, use_rope=False, sparse=dict(
            kernel=8, stride=4, block=16, window=32, topk=4,
            dense_len=64)), **kw})


@pytest.fixture(scope='module')
def prompt():
    x = normal(7, 2, 160, 64)
    m = module()
    params = m.init(jax.random.key(0), x[:, :16], x[:, :16], x[:, :16])
    # per-head scales off 1, so that the norm's scale is in the numbers
    p = jax.tree.map(lambda v: v, params)
    p['params']['keys_norm'] = 1.5 + 0.1 * normal(8, 16)
    p['params']['queries_norm'] = 1.2 + 0.1 * normal(9, 16)
    return m, p, x


def by_hand(m, p, x):
    """The module's forward over ``x (T, 64)`` of one session, row by
    row from the equations."""
    pp = p['params']
    def heads(w, n):
        return (x @ np.asarray(w['kernel'])).reshape(len(x), n, 16)
    def norm(v, scale):
        return v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-6) * (
            np.asarray(scale))
    q = norm(heads(pp['keys'], 4), pp['keys_norm'])
    k = norm(heads(pp['queries'], 2), pp['queries_norm'])
    v = heads(pp['values'], 2)
    gate = 1 / (1 + np.exp(-(x @ np.asarray(pp['gate']['kernel']))))
    out = np.zeros((len(x), 4, 16))
    picked = []
    for t in range(len(x)):
        for g in range(2):
            picks = picks_by_hand(q[t, 2 * g:2 * g + 2], k[:t + 1, g],
                                  m._sparse if hasattr(m, '_sparse')
                                  else SPEC, t + 1)
            picked.append(picks)
            out[t, 2 * g:2 * g + 2] = attend_by_hand(
                q[t, 2 * g:2 * g + 2], k[:t + 1, g], v[:t + 1, g], picks,
                SPEC, t + 1)
    return (out.reshape(len(x), 64) * gate) @ np.asarray(
        pp['composition']['kernel']), picked


def test_the_forward_is_the_equations_row_by_row(prompt):
    m, p, x = prompt
    got, sown = m.apply(p, x, x, x, mutable=['counters'])
    want, picked = by_hand(m, p, np.asarray(x[1], np.float64))
    np.testing.assert_allclose(got[1], want, atol=2e-5, rtol=2e-5)
    picks = np.asarray(sown['counters']['sparse_picks'])
    count = np.asarray(sown['counters']['sparse_count'])
    assert picks.shape == (2, 2, 160, 4) and count.shape == (160,)
    assert count[63] == 4 and count[64] == 4 and count[15] == 1
    for t in (70, 100, 159):                    # above dense_len
        for g in range(2):
            assert picks[1, g, t].tolist() == picked[2 * t + g]
    # sparse means sparse: above dense_len not every block is read
    assert len(set(picks[1, 0, 159].tolist())) == 4 < 10


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
@pytest.mark.parametrize('chunks', [(96,), (32, 40, 24)],
                         ids=['one', 'ragged'])
def test_prefill_then_decode_is_the_forward(prompt, impl, chunks):
    """The three entry points over one set of parameters: a prompt
    prefilled in chunks (the dense / sparse switch falls inside one),
    then token by token through the step — the kernel under the
    interpreter or the gathered softmax — against ``__call__``."""
    m, p, x = prompt
    m = module(decode_impl=impl)
    want = m.apply(p, x, x, x)
    cache = m.make_decode_cache(2, 192)
    assert isinstance(cache, SparseCache)
    assert cache.pooled.shape == (2, 2, 48, 16)
    outs, start = [], 0
    for c in chunks:
        xc = x[:, start:start + c]
        cache, o = m.apply(p, xc, xc, xc, cache, method='prefill')
        outs.append(o)
        start += c
    step = jax.jit(lambda c, xt: m.apply(p, xt, xt, xt, c,
                                         method='decode'))
    with sparse_decode_traces() as forms:
        for t in range(start, 160):
            cache, o = step(cache, x[:, t:t + 1])
            outs.append(o)
    assert forms == [{'impl': impl, 'picks': 4, 'topk': 4, 'group': 4,
                      'select': 'sort'}]
    assert int(cache.length) == 160
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=3e-5,
                               rtol=3e-5)


def test_a_length_set_back_needs_no_restore_of_the_pooled_rows(prompt):
    """Decode 48 tokens past the prompt, set the length back to the
    prompt's end, decode the same tokens again: the same pooled rows,
    the same picks, the same outputs, bit for bit — though the pooled
    rows past the prompt still hold the first pass's values when the
    second begins."""
    m, p, x = prompt
    cache = m.make_decode_cache(2, 192)
    cache, _ = m.apply(p, x[:, :100], x[:, :100], x[:, :100], cache,
                       method='prefill')
    step = jax.jit(lambda c, xt: m.apply(
        p, xt, xt, xt, c, method='decode', mutable=['counters']))

    def serve(cache, rows):
        outs, picks = [], []
        for t in range(100, 148):
            (cache, o), sown = step(cache, rows[:, t:t + 1])
            outs.append(o)
            picks.append(sown['counters']['sparse_picks'])
        return cache, jnp.stack(outs), jnp.stack(picks)
    other = x.at[:, 100:].set(normal(11, 2, 60, 64))
    cache, _, _ = serve(cache, other)            # an abandoned request
    stale = cache.pooled
    cache, first, picks1 = serve(cache._replace(length=jnp.int32(100)), x)
    assert not np.array_equal(stale[:, :, 24:35], cache.pooled[:, :, 24:35])
    pooled1 = cache.pooled
    cache, second, picks2 = serve(cache._replace(length=jnp.int32(100)), x)
    np.testing.assert_array_equal(first, second)
    np.testing.assert_array_equal(picks1, picks2)
    np.testing.assert_array_equal(pooled1[:, :, :36], cache.pooled[:, :, :36])


def test_insert_session_moves_the_pooled_rows_with_the_slab():
    batch = init_sparse_cache(3, 2, 64, 16, 4, dtype=jnp.float32)
    one = init_sparse_cache(1, 2, 64, 16, 4, dtype=jnp.float32)
    one = one._replace(k=one.k + 1, v=one.v + 2, pooled=one.pooled + 3,
                       length=jnp.int32(40))
    out = insert_session(batch, 1, one)
    assert isinstance(out, SparseCache) and int(out.length) == 40
    for buf, value in ((out.k, 1), (out.v, 2), (out.pooled, 3)):
        np.testing.assert_array_equal(buf[1], value)
        np.testing.assert_array_equal(buf[0], 0)
        np.testing.assert_array_equal(buf[2], 0)


def test_the_module_refuses_what_the_sparse_route_does_not_cover(prompt):
    m, p, x = prompt
    for kw, match in ((dict(window=32), 'window'),
                      (dict(alibi_slopes=(1.0,) * 4), 'alibi_slopes'),
                      (dict(softmax_impl='full'), 'softmax_impl')):
        bad = module(**kw)
        with pytest.raises(ValueError, match=match):
            bad.init(jax.random.key(0), x[:, :16], x[:, :16], x[:, :16])
    with pytest.raises(ValueError, match='whole blocks'):
        m.make_decode_cache(1, 100)
    cache = m.make_decode_cache(2, 64)
    with pytest.raises(ValueError, match='one token'):
        m.apply(p, x[:, :2], x[:, :2], x[:, :2], cache, method='decode')
    with pytest.raises(ValueError, match='scalar'):
        sparse_step(normal(0, 2, 4, 1, 16), cache._replace(
            length=jnp.zeros((2,), jnp.int32)), normal(1, 2, 2, 1, 16),
            normal(2, 2, 2, 1, 16), SPEC)


def test_qk_norm_alone_is_a_per_head_rms_norm_at_every_entry():
    """``qk_norm`` on a plain (dense) attention layer: ``__call__`` and
    prefill + decode agree, and the scales reach the scores."""
    m = DistributedDotProductAttn(
        key_dim=32, num_heads=2, causal=True, softmax_impl='flash',
        distributed=False, qk_norm=True, use_rope=True)
    x = normal(12, 1, 24, 32)
    params = m.init(jax.random.key(1), x, x, x)
    assert params['params']['keys_norm'].shape == (16,)
    want = m.apply(params, x, x, x)
    cache = m.make_decode_cache(1, 32)
    cache, a = m.apply(params, x[:, :16], x[:, :16], x[:, :16], cache,
                       method='prefill')
    outs = [a]
    for t in range(16, 24):
        cache, o = m.apply(params, x[:, t:t + 1], x[:, t:t + 1],
                           x[:, t:t + 1], cache, method='decode')
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5)
    wide = jax.tree.map(lambda v: v, params)
    wide['params']['keys_norm'] = params['params']['keys_norm'] * 3
    assert not np.allclose(m.apply(wide, x, x, x), want, atol=1e-3)
    off = DistributedDotProductAttn(
        key_dim=32, num_heads=2, causal=True, softmax_impl='flash',
        distributed=False)
    assert 'keys_norm' not in off.init(jax.random.key(1), x, x, x)['params']
