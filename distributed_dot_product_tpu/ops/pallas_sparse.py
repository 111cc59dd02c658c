# -*- coding: utf-8 -*-
"""
The decode step of a block-sparse attention layer: one token attends the
cache rows of the BLOCKS a selection picked for it (``models/sparse.py``:
pooled-key scores, forced blocks, top-k), not every row of its prefix.

With ``block`` rows a block, ``picks (B, H_kv, P)`` the picked blocks of
each (session, KV head) in ASCENDING order, ``count`` of them valid and
the last valid one the token's own block (the selection forces it):

    K', V' = cache with the token's row written at ``length``
    rows   = {block · p + r : p in picks[:count], r < block} ∩ [0, length]
    out    = softmax(q K'[rows]ᵀ · scale) V'[rows]       a KV head's group

ONE Pallas program a step (``name='sparse_decode'``), grid ``(sessions,
KV heads)``:

- the K/V buffers stay in HBM (``memory_space=pl.ANY``) and are ALIASED
  to the outputs (``input_output_aliases``), as ``flash_decode``'s are:
  the token's row is appended in place, by writing back the one block
  that holds it;
- the picks ride as a scalar-prefetch operand; a program reads its own
  row of them and moves the picked blocks with its OWN DMAs, ``group``
  picks a wait: all of a program's copies are started before the first
  is waited on, then each group is scored as one ``(rows of the KV
  head's query group) x (group · block)`` product under an online
  softmax. A grid step costs ~0.46 µs whatever it moves (chip, PR 27)
  and a picked block is 16 KB of K: a block a grid step would be
  bookkeeping alone;
- a group whose picks are ADJACENT blocks (always the local window's;
  every group below ``dense_len``, where the picks are all blocks)
  moves as ONE copy of ``group · block`` rows;
- only the token's own block has rows past the token (stale rows of an
  abandoned request) and only the last group has picks past ``count``:
  both are masked by position, every group alike.

Off the TPU the kernel runs under the Pallas interpreter, as the other
kernels do. :func:`sparse_decode_reference` is the same step as a
gathered softmax in plain ``jax.numpy``: the tests' oracle, and what a
layer runs where it is told ``'xla'``.

The pick list itself — the ``k`` best of a row of block scores, in
ascending block order — is :func:`threshold_picks`: ONE Pallas program
(``name='sparse_pick'``) for all the rows of a call, 128 rows a grid
step, rows on sublanes and blocks on lanes, with no sort in it:

- a float32 score goes to its order-preserving int32 image (the bits,
  with the magnitude flipped where the sign is set: XLA's total order,
  ``-inf < -1.0 < -0.0 < +0.0 < +inf``); the row's ``k``-th largest
  image ``thr`` is built a bit a round from the top, a round one compare
  and one count along the row;
- the picks are the blocks above ``thr`` and, of those equal to it, the
  lowest-numbered ``k - #above`` — ``lax.top_k``'s tie rule, so the SET
  is :func:`sorted_picks`' on every input;
- with ``c_b`` the picks at or below block ``b`` (two running counts,
  each a 128-lane tile's product with a triangle of ones), the ``j``-th
  pick is ``#{b : c_b <= j}``: ascending as it is made;
- asked for the MASK too (``mask=True``: a sparse-expert layer's router,
  ``models/moe.py``), the same program writes ``(rows, n)`` true at the
  picks — above ``thr``, or equal to it among the first ``k - #above``.

:func:`sorted_picks` is ``lax.top_k`` and a sort of its indices — two
full sorts a row as XLA lowers them for a TPU: the tests' oracle, and
what a layer runs off the TPU.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.ops.kernel_call import kernel_call

__all__ = ['sparse_decode', 'sparse_decode_reference', 'picks_group',
           'threshold_picks', 'sorted_picks', 'order_image']

_NEG_BIG = -1e30
_LANES = 128
_PICK_ROWS = 128        # rows of scores a grid step of ``sparse_pick``,
_PICK_CELLS = 1 << 18   # and the most scores: 1 MiB an array in VMEM
_INT_MIN = -2 ** 31


def picks_group(picks, block):
    """Picks scored together (and moved as one copy where adjacent): the
    most, of 16 / 8 / 4 / 2 / 1, that divide ``picks`` and keep a group
    within 1024 rows."""
    return max(g for g in (16, 8, 4, 2, 1)
               if picks % g == 0 and g * block <= 1024)


def sparse_decode_reference(q, k_new, v_new, k_cache, v_cache, picks, count,
                            length, *, block, scale=None):
    """The step of the module docstring in plain ``jax.numpy``: the same
    operands and results as :func:`sparse_decode`."""
    bsz, heads, _, d = q.shape
    kv = k_cache.shape[1]
    n_picks = picks.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    zero = jnp.zeros((), jnp.int32)
    at = (zero, zero, jnp.asarray(length, jnp.int32), zero)
    k_cache = lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), at)
    v_cache = lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), at)
    rows = (picks[..., None] * block + jnp.arange(block)).reshape(
        bsz, kv, n_picks * block)
    live = (jnp.repeat(jnp.arange(n_picks) < count, block)
            & (rows <= length))
    k = jnp.take_along_axis(k_cache, rows[..., None], axis=2)
    v = jnp.take_along_axis(v_cache, rows[..., None], axis=2)
    qg = q.reshape(bsz, kv, heads // kv, d)
    s = jnp.einsum('bgqd,bgsd->bgqs', qg, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(live[:, :, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum('bgqs,bgsd->bgqd', p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out.reshape(bsz, heads, 1, -1).astype(q.dtype), k_cache,
            v_cache)


def _kernel(picks_ref, meta_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm,
            o_ref, ko_hbm, vo_hbm, kbuf, vbuf, sems, *, block, group,
            scale):
    b, g = pl.program_id(0), pl.program_id(1)
    count, length = meta_ref[0], meta_ref[1]
    rows = group * block
    n_groups = (count + group - 1) // group

    def pick(i):
        return picks_ref[b, g, i]

    def adjacent(c):
        # Sorted distinct picks span group - 1 only as one run; past
        # ``count`` the list repeats its last pick, so a gapped last
        # group could span as much.
        return jnp.logical_and(
            (c + 1) * group <= count,
            pick(c * group + group - 1) - pick(c * group) == group - 1)

    def copies(c, hbm, buf, sem, whole):
        """Group ``c``'s copies of one buffer: the whole group as one
        copy, or a copy a pick."""
        if whole:
            start = pl.multiple_of(pick(c * group) * block, block)
            return [pltpu.make_async_copy(
                hbm.at[b, g, pl.ds(start, rows), :],
                buf.at[pl.ds(pl.multiple_of(c * rows, rows), rows), :],
                sem)]
        out = []
        for s in range(group):
            start = pl.multiple_of(pick(c * group + s) * block, block)
            out.append(pltpu.make_async_copy(
                hbm.at[b, g, pl.ds(start, block), :],
                buf.at[pl.ds(pl.multiple_of(c * rows + s * block, block),
                             block), :], sem))
        return out

    def each(c, what):
        """``what`` (start / wait) on every copy of group ``c``."""
        run = adjacent(c)
        for whole in (True, False):
            @pl.when(run if whole else jnp.logical_not(run))
            def _():
                for hbm, buf, sem in ((k_hbm, kbuf, sems.at[0, c]),
                                      (v_hbm, vbuf, sems.at[1, c])):
                    for copy in copies(c, hbm, buf, sem, whole):
                        getattr(copy, what)()

    # Every copy of the program is in flight before the first wait.
    def start(c, carry):
        each(c, 'start')
        return carry
    lax.fori_loop(0, n_groups, start, 0)

    q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    heads, d_v = q.shape[0], vbuf.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    # (past t_max the token's row is not written: own stays a block of
    # the cache and no row of it is the token's)
    own = jnp.minimum(length // block, k_hbm.shape[2] // block - 1)

    def body(c, carry):
        m, den, acc = carry
        each(c, 'wait')
        at = pl.multiple_of(c * rows, rows)

        # The token's row, in the one block that holds it: laid into the
        # group as it sits in VMEM, and the block written back in place.
        for s in range(group):
            @pl.when(jnp.logical_and(c * group + s < count,
                                     pick(c * group + s) == own))
            def _():
                sub = pl.ds(pl.multiple_of(at + s * block, block), block)
                row = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
                mine = row == length - own * block
                for buf, new, out, sem in (
                        (kbuf, kn_ref, ko_hbm, sems.at[0, c]),
                        (vbuf, vn_ref, vo_hbm, sems.at[1, c])):
                    buf[sub, :] = jnp.where(
                        mine, new[0, 0, 0:1, :].astype(buf.dtype),
                        buf[sub, :])
                    back = pltpu.make_async_copy(
                        buf.at[sub, :],
                        out.at[b, g, pl.ds(pl.multiple_of(own * block,
                                                          block), block),
                               :], sem)
                    back.start()
                    back.wait()

        # Position of every column of the group; past the count, or past
        # the token, a column scores nothing.
        pos = jnp.full((1, rows), length + 1, jnp.int32)
        for s in range(group):
            i = c * group + s
            base = jnp.where(i < count, pick(i) * block - s * block,
                             length + 1)
            pos = jnp.where(jnp.logical_and(lane >= s * block,
                                            lane < (s + 1) * block),
                            base + lane, pos)
        k = kbuf[pl.ds(at, rows), :]
        v = vbuf[pl.ds(at, rows), :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(pos <= length, s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(pos <= length, jnp.exp(s - m_new), 0.0)
        den = alpha * den + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, den, acc

    m, den, acc = lax.fori_loop(0, n_groups, body, (
        jnp.full((heads, 1), _NEG_BIG, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, d_v), jnp.float32)))
    o_ref[0, 0] = (acc / den).astype(o_ref.dtype)


def sparse_decode(q, k_new, v_new, k_cache, v_cache, picks, count, length,
                  *, block, scale=None, interpret=None):
    """One token's step over the picked blocks (module docstring).

    ``q (B, H, 1, d)``; ``k_new (B, H_kv, 1, d)`` / ``v_new (B, H_kv, 1,
    d_v)`` the token's row; ``k_cache`` / ``v_cache (B, H_kv, t_max,
    d·)`` (donated by the caller's jit: written in place); ``picks (B,
    H_kv, P) int32`` ascending block numbers, every entry a block of the
    cache; ``count () int32`` how many are valid (the last of them the
    token's own block ``length // block``); ``length () int32`` the rows
    before the token. Returns ``(out (B, H, 1, d_v), k_cache,
    v_cache)``."""
    bsz, heads, n, d = q.shape
    kv, t_max = k_cache.shape[1], k_cache.shape[2]
    d_v = v_cache.shape[-1]
    n_picks = picks.shape[-1]
    if n != 1 or heads % kv or t_max % block:
        raise ValueError(f'sparse_decode takes one token, query heads a '
                         f'multiple of the KV heads and whole blocks: got '
                         f'{n} rows, {heads} / {kv} heads, t_max {t_max} '
                         f'at block {block}')
    per = heads // kv
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    group = picks_group(n_picks, block)
    # Rows ride padded to their sublane tile (Mosaic refuses a dot
    # against a short operand, and a one-row block of a packed type).
    tile = 32 // jnp.dtype(q.dtype).itemsize
    rows_q = -(-per // tile) * tile
    qg = q.reshape(bsz, kv, per, d)
    if rows_q != per:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_q - per), (0, 0)))
    new_tile = 32 // jnp.dtype(k_cache.dtype).itemsize

    def padded(new, buf):
        return jnp.pad(new.astype(buf.dtype),
                       ((0, 0), (0, 0), (0, new_tile - 1), (0, 0)))

    meta = jnp.stack([jnp.asarray(count, jnp.int32).reshape(()),
                      jnp.asarray(length, jnp.int32).reshape(())])

    def mine(i, j, picks, meta):
        return (i, j, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, k_cache, v_cache = kernel_call(
        functools.partial(_kernel, block=block, group=group, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, kv),
            in_specs=[pl.BlockSpec((1, 1, rows_q, d), mine),
                      pl.BlockSpec((1, 1, new_tile, d), mine),
                      pl.BlockSpec((1, 1, new_tile, d_v), mine),
                      hbm, hbm],
            out_specs=[pl.BlockSpec((1, 1, rows_q, d_v), mine), hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((n_picks * block, d), k_cache.dtype),
                pltpu.VMEM((n_picks * block, d_v), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, n_picks // group))]),
        out_shape=[jax.ShapeDtypeStruct((bsz, kv, rows_q, d_v), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
        name='sparse_decode')(
            picks.astype(jnp.int32), meta, qg, padded(k_new, k_cache),
            padded(v_new, v_cache), k_cache, v_cache)
    return (out[:, :, :per].reshape(bsz, heads, 1, d_v), k_cache, v_cache)


def sorted_picks(scores, k):
    """The ``k`` best entries of each row of ``scores (…, n)``, ties to
    the lower index, as ascending indices ``(…, k) int32``:
    ``lax.top_k`` and a sort of what it picked."""
    return jnp.sort(lax.top_k(scores, k)[1].astype(jnp.int32), axis=-1)


def order_image(scores):
    """The order-preserving int32 image of float32 ``scores``: the bits,
    with the magnitude flipped where the sign is set, so that integer
    compares give XLA's total order (``-inf < -1.0 < -0.0 < +0.0 <
    +inf``), the one ``lax.top_k`` ranks by."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _pick_kernel(s_ref, o_ref, m_ref=None, *, k, n):
    rows, width = s_ref.shape
    key = order_image(s_ref[...])
    # (a column past the row — the block overhangs the array — is below
    # every score, and a higher index than any that ties with it)
    col = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    key = jnp.where(col < n, key, _INT_MIN)

    # thr, the k-th largest image, as its offset from INT_MIN: the
    # largest t with #{key >= t} >= k, a bit a round.
    def bit(i, t):
        up = t | lax.shift_left(jnp.int32(1), 31 - i)
        at_least = jnp.sum(jnp.where(key >= (up ^ _INT_MIN), 1.0, 0.0),
                           axis=-1, keepdims=True)
        return jnp.where(at_least >= k, up, t)
    thr = lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)
                        ) ^ _INT_MIN

    # Running counts along the row, a lane tile at a time: the tile
    # against a triangle of ones, and against ones for the tiles after.
    i = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    j = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    upto = (i <= j).astype(jnp.bfloat16)
    ones = jnp.ones((_LANES, _LANES), jnp.bfloat16)

    def running(mask):
        """Per lane tile, how many of ``mask`` lie at or below each
        column; and the row's total in every lane."""
        before, tiles = jnp.zeros((rows, _LANES), jnp.float32), []
        for t in range(width // _LANES):
            m = mask[:, t * _LANES:(t + 1) * _LANES].astype(jnp.bfloat16)
            tiles.append(before + jnp.dot(
                m, upto, preferred_element_type=jnp.float32))
            before = before + jnp.dot(
                m, ones, preferred_element_type=jnp.float32)
        return tiles, before
    above, n_above = running(jnp.where(key > thr, 1.0, 0.0))
    equal, _ = running(jnp.where(key == thr, 1.0, 0.0))
    picked = jnp.concatenate(
        [a + jnp.minimum(e, k - n_above) for a, e in zip(above, equal)],
        axis=-1)                                        # c_b, (rows, width)
    if m_ref is not None:
        # The picks as a mask: what lies above thr and, of what ties
        # with it, the first ``k - #above`` along the row.
        tied = jnp.concatenate([e <= k - n_above for e in equal], axis=-1)
        m_ref[...] = ((key > thr) | ((key == thr) & tied)).astype(
            jnp.int32)

    slot = lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def place(j, out):
        below = jnp.sum(jnp.where(picked <= j.astype(jnp.float32), 1.0,
                                  0.0), axis=-1, keepdims=True)
        return jnp.where(slot == j, below.astype(jnp.int32), out)
    o_ref[...] = lax.fori_loop(0, k, place,
                               jnp.zeros(o_ref.shape, jnp.int32))


def threshold_picks(scores, k, *, mask=False, interpret=None):
    """:func:`sorted_picks` with no sort (module docstring): ``scores
    (…, n) float32`` to ``(…, k) int32``, ``k <= n``. Exact: the same
    entries on every input. With ``mask`` also the picks as ``(…, n)
    bool``, true at a row's ``k`` picked entries, from the same
    program."""
    *lead, n = scores.shape
    if scores.dtype != jnp.float32 or not 0 < k <= n:
        raise ValueError(f'threshold_picks takes float32 scores and 0 < k '
                         f'<= n: got {scores.dtype}, k {k} of {n}')
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    rows = math.prod(lead)
    width, slots = (-(-x // _LANES) * _LANES for x in (n, k))
    tile = min(_PICK_ROWS, -(-rows // 8) * 8,
               max(8, _PICK_CELLS // width // 8 * 8))
    out_specs = pl.BlockSpec((tile, slots), lambda r: (r, 0))
    out_shape = jax.ShapeDtypeStruct((rows, slots), jnp.int32)
    if mask:
        out_specs = [out_specs,
                     pl.BlockSpec((tile, width), lambda r: (r, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((rows, width), jnp.int32)]
    out = kernel_call(
        functools.partial(_pick_kernel, k=k, n=n),
        grid=(pl.cdiv(rows, tile),),
        in_specs=[pl.BlockSpec((tile, width), lambda r: (r, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',)),
        interpret=interpret,
        name='sparse_pick')(scores.reshape(rows, n))
    if not mask:
        return out[:, :k].reshape(*lead, k)
    picks, picked = out
    return (picks[:, :k].reshape(*lead, k),
            (picked[:, :n] != 0).reshape(*lead, n))
