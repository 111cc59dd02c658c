# -*- coding: utf-8 -*-
"""
Learned block-sparse attention (MiniCPM4's InfLLM-v2 form, the
``minicpm4`` layers of ``minicpm_sala``): a token attends the rows of
the BLOCKS it picks for itself, a pick set a KV head. With ``n`` the
keys so far (the token's own included), ``g`` a KV head and its query
heads, and :class:`SparseSpec`'s sizes (``kernel`` 32, ``stride`` 16,
``block`` 64, ``init_blocks`` 1, ``window`` 2048, ``topk`` 64,
``dense_len`` 8192 as published):

    n <= dense_len:  every block up to the token's own (plain causal)
    else  pooled   K̄_j = mean(k_i, i in [stride·j, stride·j + kernel)),
                         every j with stride·j + kernel <= n
          scores   p_head = softmax_j(q_head · K̄_j · scale)
                   s_j = Σ_{head in g} p_head,j
          blocks   B_b = max(s_j : pooled row j overlaps block b)
                         (kernel 32 / stride 16 / block 64: j in
                         [4 b − 1, 4 b + 3])
          forced   the first ``init_blocks`` blocks and the last
                   ``window / block`` up to the token's own: B_b = +inf
          P = top-``topk``(B_b, b <= (n − 1) // block)
    out = softmax(q kᵀ · scale over the rows of P, causal) v

Three pieces, each tested apart (``tests/test_sparse_attention.py``):

- the POOLED-KEY cache, ``SparseCache.pooled (B, H_kv, t_max // stride,
  d)`` beside the slab: :func:`pooled_after_chunk` writes the rows a
  prefilled chunk completes, :func:`pooled_after_step` the row a decode
  step completes — both from the slab's rows. Only rows with ``stride·j
  + kernel <= n`` are ever scored and a step rewrites row ``(n −
  kernel) // stride``, so after a session's length is set BACK the rows
  past it are rewritten before they are read: nothing is restored;
- the SELECTION, :func:`sparse_select`: float32 scores, the picks in
  ascending order ``(…, P) int32`` with ``P = max(topk, dense_len /
  block)`` and the count of them that are valid (below ``dense_len``
  the picks are simply all blocks; above it ``topk`` of them, the rest
  a repeat of the last). On a TPU the ``topk`` come from an exact
  threshold over the row's block scores, every row of the call in one
  Pallas program (``ops/pallas_sparse.threshold_picks``); elsewhere
  from ``lax.top_k`` and a sort, the same entries (:func:`pick_form`);
- the ATTENTION over the picks: one token through
  ``ops/pallas_sparse.sparse_decode`` (:func:`sparse_step`), a chunk of
  a prompt through the flash forward under a block mask
  (:func:`sparse_attention`: every row of the chunk has its own picks).
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.ops.pallas_attention import (
    flash_attention,
)
from distributed_dot_product_tpu.ops.pallas_sparse import (
    picks_group, sorted_picks, sparse_decode, sparse_decode_reference,
    threshold_picks,
)
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['SparseSpec', 'pool_rows', 'pooled_after_chunk',
           'pooled_after_step', 'block_scores', 'pick_form', 'pick_blocks',
           'sparse_select', 'sparse_attention', 'sparse_step']


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selection's sizes (module docstring), in cache rows."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if (self.kernel % self.stride or self.block % self.stride
                or self.window % self.block
                or self.topk * self.block > self.dense_len
                or self.topk < self.init_blocks + self.window // self.block):
            raise ValueError(
                f'{self}: kernel and block are whole strides, the window '
                f'whole blocks, the forced blocks fit inside topk, and '
                f'topk blocks exist where the dense route ends')

    @property
    def picks(self):
        """Entries of a pick list: every block below ``dense_len``, or
        ``topk``."""
        return max(self.topk, -(-self.dense_len // self.block))

    def pooled_rows(self, n):
        """Pooled rows complete once ``n`` keys are written."""
        return jnp.maximum((n - self.kernel) // self.stride + 1, 0)


def pool_rows(rows, spec):
    """Means of ``kernel`` rows every ``stride``: ``rows (…, m · stride +
    kernel − stride, d)`` to ``(…, m, d)`` in ``rows``' type, summed in
    float32 a stride at a time (a chunk's and a step's rows go through
    the same sums)."""
    per = spec.kernel // spec.stride
    strides = rows.shape[-2] // spec.stride
    sums = jnp.sum(rows.astype(jnp.float32).reshape(
        *rows.shape[:-2], strides, spec.stride, rows.shape[-1]), axis=-2)
    m = strides - per + 1
    total = sums[..., :m, :]
    for i in range(1, per):
        total = total + sums[..., i:i + m, :]
    return (total / spec.kernel).astype(rows.dtype)


def pooled_after_chunk(k_cache, pooled, start, n, spec):
    """``pooled`` with the rows written that the chunk ``[start, start +
    n)`` completes, from the slab ``k_cache`` AFTER the chunk's append
    (a chunk's first pooled row reads the rows before it). Rows the
    chunk does not complete may be written too: they are rewritten when
    they are."""
    m = -(-n // spec.stride) + 1
    first = spec.pooled_rows(start)
    at = first * spec.stride + jnp.arange(
        m * spec.stride + spec.kernel - spec.stride)
    rows = jnp.take(k_cache, jnp.minimum(at, k_cache.shape[2] - 1), axis=2)
    return pooled.at[:, :, first + jnp.arange(m)].set(
        pool_rows(rows, spec), mode='drop')


def pooled_after_step(k_cache, k_new, pooled, length, spec):
    """``pooled`` with row ``(n − kernel) // stride`` written (``n =
    length + 1``), from the slab BEFORE the step's append and the
    token's own row ``k_new (B, H_kv, 1, d)``: the row the token
    completes where ``n`` is a whole stride, else a row written
    before, to the same bits."""
    j = jnp.maximum((length + 1 - spec.kernel) // spec.stride, 0)
    rows = lax.dynamic_slice_in_dim(k_cache, j * spec.stride, spec.kernel,
                                    axis=2)
    own = (j * spec.stride + jnp.arange(spec.kernel)) == length
    rows = jnp.where(own[:, None], k_new.astype(rows.dtype), rows)
    return lax.dynamic_update_slice_in_dim(
        pooled, pool_rows(rows, spec), j, axis=2)


def block_scores(q, pooled, keys, spec, scale, n_blocks):
    """``B_b`` of the module docstring for every row of ``q (B, H, T,
    d)``: ``(B, H_kv, T, n_blocks)`` float32, ``+inf`` at a row's forced
    blocks and ``-inf`` past its own. ``pooled (B, H_kv, J, d)``;
    ``keys (T,) int32`` the keys so far at each row, its own
    included."""
    bsz, heads, t, d = q.shape
    kv, j_max = pooled.shape[1], pooled.shape[2]
    s = jnp.einsum('bgptd,bgjd->bgptj', q.reshape(bsz, kv, heads // kv,
                                                  t, d), pooled,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(j_max) < spec.pooled_rows(keys)[:, None])  # (T, J)
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    # A pooled row that is not complete scores below every one that is.
    s = jnp.where(valid, jnp.sum(p, axis=2), -1.0)         # (B, G, T, J)
    per = spec.block // spec.stride
    reach = spec.kernel // spec.stride - 1
    # as many pooled rows as begin in the cache's blocks
    s = jnp.pad(s[..., :n_blocks * per], ((0, 0),) * 3 + (
        (0, max(n_blocks * per - j_max, 0)),), constant_values=-1.0)
    s = s.reshape(bsz, kv, t, n_blocks, per)
    best = jnp.max(s, axis=-1)
    if reach:
        # The rows that begin in the block before and reach into this.
        tail = jnp.max(s[..., per - reach:], axis=-1)
        best = jnp.maximum(best, jnp.pad(
            tail[..., :-1], ((0, 0),) * 3 + ((1, 0),),
            constant_values=-1.0))
    b = jnp.arange(n_blocks)
    own = ((keys - 1) // spec.block)[:, None]               # (T, 1)
    forced = (b < spec.init_blocks) | (b > own - spec.window // spec.block)
    best = jnp.where(forced, jnp.inf, best)
    return jnp.where(b <= own, best, -jnp.inf)


def pick_form():
    """How :func:`pick_blocks` finds a row's best blocks: ``'threshold'``
    (``ops/pallas_sparse.threshold_picks``, one Pallas program for all
    rows and no sort) on a TPU, ``'sort'`` (``lax.top_k`` and a sort of
    its picks: the oracle) elsewhere. The same entries either way."""
    return 'threshold' if jax.default_backend() == 'tpu' else 'sort'


def pick_blocks(scores, keys, spec):
    """The picks of :func:`block_scores`' ``scores (B, H_kv, T,
    n_blocks)``: ``(picks (B, H_kv, T, P) int32, count (T,) int32)``,
    the valid picks ascending at the front, the token's own block the
    last of them, every entry a block of the cache."""
    n_blocks = scores.shape[-1]
    k = min(spec.topk, n_blocks)
    picked = (threshold_picks if pick_form() == 'threshold'
              else sorted_picks)(scores, k)
    picked = jnp.pad(picked, ((0, 0),) * 3 + ((0, spec.picks - k),),
                     mode='edge')
    every = jnp.minimum(jnp.arange(spec.picks, dtype=jnp.int32),
                        n_blocks - 1)
    dense = (keys <= spec.dense_len)[:, None]
    count = jnp.where(dense[:, 0], (keys - 1) // spec.block + 1, k)
    return jnp.where(dense, every, picked), count.astype(jnp.int32)


def sparse_select(q, pooled, keys, spec, scale, n_blocks):
    """:func:`block_scores` then :func:`pick_blocks`."""
    return pick_blocks(
        block_scores(q, pooled, keys, spec, scale, n_blocks), keys, spec)


def _allowed(picks, count, n_blocks):
    """``(B, H_kv, T, n_blocks) bool``: the blocks each row picked."""
    live = jnp.arange(picks.shape[-1]) < count[:, None]          # (T, P)
    hit = picks[..., None] == jnp.arange(n_blocks)
    return jnp.any(hit & live[..., None], axis=-2)


def sparse_attention(q, k, v, pooled, start, spec, scale=None, rows=512):
    """Causal attention of the rows ``q (B, H, T, d)`` at positions
    ``start + arange(T)`` over ``k`` / ``v (B, H_kv, S, d·)`` (``S``
    whole blocks; rows past a query's own are never read), each row
    over the blocks it picks by ``pooled (B, H_kv, J, d)`` — which must
    hold every pooled row complete before the last query. The
    selection runs ``rows`` queries at a time; the attention is the
    flash forward under the picks' block mask, a KV head at a time.
    Returns ``(out (B, H, T, d_v), picks (B, H_kv, T, P), count
    (T,))``."""
    bsz, heads, t, d = q.shape
    kv, s_len = k.shape[1], k.shape[2]
    if s_len % spec.block:
        raise ValueError(f'{s_len} cache rows are not whole blocks of '
                         f'{spec.block}')
    n_blocks = s_len // spec.block
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    rows = min(rows, t)
    pad = (-t) % rows
    keys = start + 1 + jnp.arange(t + pad)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q

    def some(args):
        q_c, keys_c = args
        picks, count = sparse_select(q_c, pooled, keys_c, spec, scale,
                                     n_blocks)
        return picks, count, _allowed(picks, count, n_blocks)

    with device_scope('ops.sparse_select'):
        picks, count, allowed = lax.map(some, (
            jnp.moveaxis(qp.reshape(bsz, heads, -1, rows, d), 2, 0),
            keys.reshape(-1, rows)))
    # (chunks, B, G, rows, ·) -> (B, G, T, ·)
    picks, allowed = (
        jnp.moveaxis(x, 0, 2).reshape(bsz, kv, t + pad, -1)[:, :, :t]
        for x in (picks, allowed))
    count = count.reshape(-1)[:t]
    masked = jnp.repeat(~allowed, spec.block, axis=-1)
    per = heads // kv
    out = jnp.concatenate([
        flash_attention(q[:, g * per:(g + 1) * per], k[:, g:g + 1],
                        v[:, g:g + 1], masked[:, g:g + 1], causal=True,
                        causal_offset=start, scale=scale)
        for g in range(kv)], axis=1)
    return out, picks, count


def sparse_step(q, cache, k_new, v_new, spec, scale=None, impl=None,
                interpret=None):
    """One token: the pooled row it completes, its picks, and
    ``sparse_decode`` over them with the token's row appended in place.
    ``q (B, H, 1, d)``, ``k_new`` / ``v_new (B, H_kv, 1, d·)``,
    ``cache`` a :class:`~distributed_dot_product_tpu.models.decode.
    SparseCache` with a scalar length. ``impl``: ``'kernel'`` |
    ``'xla'`` (:func:`sparse_decode_reference`) | None (the kernel on a
    TPU, XLA elsewhere). Returns ``(cache, out (B, H, 1, d_v), picks
    (B, H_kv, P), count ())``."""
    from distributed_dot_product_tpu.models.decode import (
        note_sparse_decode,
    )
    if impl not in (None, 'auto', 'kernel', 'xla'):
        raise ValueError(f"decode impl must be None/'auto'/'kernel'/"
                         f"'xla', got {impl!r}")
    if impl in (None, 'auto'):
        impl = 'kernel' if jax.default_backend() == 'tpu' else 'xla'
    if cache.length.ndim:
        raise ValueError('the sparse step shares one clock: a scalar '
                         'length')
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    length = cache.length
    with device_scope('ops.sparse_select'):
        pooled = pooled_after_step(cache.k, k_new, cache.pooled, length,
                                   spec)
        picks, count = sparse_select(
            q, pooled, (length + 1)[None], spec, scale,
            cache.t_max // spec.block)
        picks, count = picks[:, :, 0], count[0]
    note_sparse_decode({
        'impl': impl, 'picks': spec.picks, 'topk': spec.topk,
        'group': picks_group(spec.picks, spec.block),
        'select': pick_form()})
    with device_scope('ops.sparse_decode'):
        step = (functools.partial(sparse_decode, interpret=interpret)
                if impl == 'kernel' else sparse_decode_reference)
        out, k, v = step(q, k_new, v_new, cache.k, cache.v, picks, count,
                         length, block=spec.block, scale=scale)
    return (cache._replace(k=k, v=v, length=length + 1, pooled=pooled),
            out, picks, count)

