# -*- coding: utf-8 -*-
"""
Incremental decoding (KV-cache) attention — the inference companion to
the training stack.

No reference analog (the reference is a training-side library; its module
recomputes full (T/N, T) scores every call, reference module.py:60-69).
Autoregressive inference wants the standard KV-cache pattern instead:
keep the projected k/v of all past positions in a pair of device buffers,
append one position per step, and attend a single query row against the
prefix — O(T·d) work per token with no O(T²) anything.

TPU-first choices:

- The cache is a **static-shape** ``(B, H_kv, T_max, d)`` buffer pair plus
  a scalar length; every step is the same compiled program
  (``lax.dynamic_update_slice`` append + masked attention over the full
  buffer) — no dynamic shapes, no retraces, XLA keeps it on-device.
- A decode step is bandwidth-bound (one query row): it runs as a plain
  masked ``einsum`` softmax — at Tq=1 a Pallas kernel buys nothing over
  XLA's fused reduction, and the einsum path is backend-portable. The
  in-kernel features that matter at decode time (GQA via grouped heads,
  ALiBi, sliding window, RoPE positions) are applied directly.
- GQA: the cache holds ``H_kv`` heads; the query's ``H`` heads attend
  their group's cached head — cache memory is the whole point of GQA at
  inference, so the grouped layout is native here too.

Usage::

    cache = init_cache(batch, kv_heads, t_max, head_dim)
    for t in range(steps):
        cache = append_kv(cache, k_t, v_t)        # (B, H_kv, 1, d) each
        out = decode_attention(q_t, cache, ...)   # (B, H, 1, d_v)

Prefill: ``append_kv`` accepts any chunk length, so the prompt can be
appended in one call (with outputs computed by
:func:`~distributed_dot_product_tpu.ops.pallas_attention.flash_attention`
over the prompt — the training kernels ARE the prefill kernels).

Performance note: jit your step with the cache DONATED
(``jax.jit(step, donate_argnums=(<cache arg>,))``) so the append's
``dynamic_update_slice`` writes in place — without donation every token
copies the whole K/V buffer pair first.
"""

import math
import zlib
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['DecodeCache', 'init_cache', 'append_kv', 'append_kv_sharded',
           'decode_attention', 'init_slot_cache', 'append_kv_slots',
           'reset_slot', 'slots_all_finite', 'decode_step',
           'decode_kernel_eligible', 'decode_impl_traces',
           'rollback_slots', 'RingCache', 'init_ring_cache',
           'ring_append', 'ring_window', 'insert_session',
           'StateCache', 'snapshot_states', 'restore_states',
           'SparseCache', 'init_sparse_cache', 'sparse_decode_traces',
           'LatentCache', 'PackedCache', 'init_packed_cache',
           'packed_append', 'packed_views',
           'PagedDecodeCache', 'PagePool', 'PageChecksums',
           'ShardedPageTable', 'init_sharded_paged_cache',
           'init_paged_cache', 'paged_gather', 'paged_gather_mirror',
           'paged_append_kv_slots',
           'paged_append_rows', 'paged_reset_slot',
           'paged_rollback_slots', 'paged_copy_attach',
           'paged_transfer_pages']


class DecodeCache(NamedTuple):
    """Static-shape KV cache: ``k``/``v`` are ``(B, H_kv, T_max, d·)``
    buffers, ``length`` the number of valid positions (traced scalar).
    ``k_q``/``k_scale``: optional int8 mirror of ``k`` with per-row
    scales, maintained at append time for ``qk_quant='int8'`` models —
    rows are append-only and the quantization is per-row, so quantizing
    once on append is bit-identical to re-quantizing the buffer each
    step, and the decode step then streams the int8 mirror (half the
    bf16 K bytes) instead of re-reading + re-reducing the full cache."""
    k: jax.Array
    v: jax.Array
    length: jax.Array
    k_q: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None

    @property
    def t_max(self):
        return self.k.shape[-2]


def init_cache(batch, kv_heads, t_max, head_dim, v_head_dim=None,
               dtype=jnp.bfloat16, qk_quant=None):
    """Zero cache for ``t_max`` positions (the compile-time ceiling; pick
    the serving context limit). ``qk_quant='int8'`` allocates the
    quantized K mirror for int8-trained models."""
    v_head_dim = v_head_dim or head_dim
    if qk_quant not in (None, 'int8'):
        raise ValueError(f"qk_quant must be None or 'int8', "
                         f'got {qk_quant!r}')
    quant = qk_quant == 'int8'
    return DecodeCache(
        k=jnp.zeros((batch, kv_heads, t_max, head_dim), dtype),
        v=jnp.zeros((batch, kv_heads, t_max, v_head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
        k_q=(jnp.zeros((batch, kv_heads, t_max, head_dim), jnp.int8)
             if quant else None),
        k_scale=(jnp.zeros((batch, kv_heads, t_max, 1), jnp.float32)
                 if quant else None))


class RingCache(NamedTuple):
    """A sliding-window layer's RECYCLED cache: ``k``/``v`` are
    ``(B, H_kv, capacity, d·)`` and position ``p`` lives in column
    ``p mod capacity``, so the buffers hold the newest ``capacity``
    positions whatever the context's length; ``length`` (traced scalar)
    counts the positions appended so far. With ``capacity >= window``
    every row a query may attend is held. Rows are never cleared: a
    column holds the newest position ``<= length − 1`` that maps to it
    (:func:`ring_positions`), so after ``length`` is set BACK (a serving
    loop's reset between requests) the rows that the abandoned positions
    overwrote are taken for older ones — sound exactly while those rows
    lay outside every later query's window, i.e. while ``capacity >=
    window + (positions abandoned)``; :func:`decode_step`'s kernel mode
    and :func:`decode_attention` both mask by the valid interval, which
    never reaches them."""
    k: jax.Array
    v: jax.Array
    length: jax.Array

    @property
    def capacity(self):
        return self.k.shape[-2]

    t_max = capacity


def init_ring_cache(batch, kv_heads, capacity, head_dim, v_head_dim=None,
                    dtype=jnp.bfloat16):
    """Zero ring cache of ``capacity`` columns (at least the window of
    the layer it serves; a multiple of the decode kernel's K split keeps
    the kernel eligible)."""
    return RingCache(
        k=jnp.zeros((batch, kv_heads, capacity, head_dim), dtype),
        v=jnp.zeros((batch, kv_heads, capacity, v_head_dim or head_dim),
                    dtype),
        length=jnp.zeros((), jnp.int32))


def ring_positions(length, capacity):
    """The position each column of a ring holds once ``length``
    positions were appended: the newest ``p <= length − 1`` with
    ``p mod capacity`` the column; negative where none was written."""
    last = length - 1
    return last - jnp.mod(last - jnp.arange(capacity), capacity)


def ring_append(cache: RingCache, k_new, v_new) -> RingCache:
    """Append ``n`` rows at positions ``length … length + n − 1``: each
    to its column, the last ``capacity`` of them where ``n`` is more
    (the ring keeps no others)."""
    n, cap = k_new.shape[-2], cache.capacity
    keep = min(n, cap)
    start = cache.length + (n - keep)

    def write(buf, new):
        new = new[..., n - keep:, :].astype(buf.dtype)
        if keep == 1:
            zero = jnp.zeros((), jnp.int32)
            return lax.dynamic_update_slice(
                buf, new, (zero, zero, jnp.mod(start, cap), zero))
        cols = jnp.mod(start + jnp.arange(keep), cap)
        return buf.at[:, :, cols].set(new, unique_indices=True)
    return RingCache(k=write(cache.k, k_new), v=write(cache.v, v_new),
                     length=cache.length + n)


def ring_window(cache: RingCache, k_new, v_new, window):
    """What a chunk of ``n`` new rows attends, laid out as a slab:
    ``(k, v, offset)`` with ``k``/``v`` ``(B, H_kv, window + n, d·)``
    holding positions ``base … base + window + n − 1`` in order
    (``base = max(length − window, 0)``): the ring's previous rows, then
    the chunk at row ``offset = length − base``. Rows past the chunk are
    whatever the ring held there; they lie in the chunk's future, where
    causal masking never looks. Take it BEFORE :func:`ring_append`, which
    may recycle rows the chunk's first queries still see."""
    n, cap = k_new.shape[-2], cache.capacity
    if window > cap:
        raise ValueError(f'window {window} exceeds the ring\'s capacity '
                         f'{cap}: rows a query attends would be recycled')
    base = jnp.maximum(cache.length - window, 0)
    cols = jnp.mod(base + jnp.arange(window + n), cap)
    offset = cache.length - base

    def lay(buf, new):
        return lax.dynamic_update_slice_in_dim(
            jnp.take(buf, cols, axis=2), new.astype(buf.dtype), offset,
            axis=2)
    return lay(cache.k, k_new), lay(cache.v, v_new), offset


class SparseCache(NamedTuple):
    """A block-sparse attention layer's cache (``models/sparse.py``): the
    slab ``k`` / ``v (B, H_kv, t_max, d·)`` with its scalar ``length``,
    and beside it the POOLED keys ``(B, H_kv, t_max // stride, d)`` that
    a token's block selection scores — a third cache that grows, one row
    every ``stride`` tokens. A pooled row is written at the step that
    completes it, from the slab's rows, and no row is scored before it
    is complete: ``length`` set back rewinds both."""
    k: jax.Array
    v: jax.Array
    length: jax.Array
    pooled: jax.Array

    @property
    def t_max(self):
        return self.k.shape[-2]


def init_sparse_cache(batch, kv_heads, t_max, head_dim, stride,
                      v_head_dim=None, dtype=jnp.bfloat16):
    """Zero :class:`SparseCache` for ``t_max`` positions."""
    return SparseCache(
        k=jnp.zeros((batch, kv_heads, t_max, head_dim), dtype),
        v=jnp.zeros((batch, kv_heads, t_max, v_head_dim or head_dim),
                    dtype),
        length=jnp.zeros((), jnp.int32),
        pooled=jnp.zeros((batch, kv_heads, t_max // stride, head_dim),
                         dtype))


class StateCache(NamedTuple):
    """A recurrent layer's cache, of FIXED size: ``state (B, heads,
    head_dim, N)`` (float32 unless the mixer says otherwise) is what the
    layer remembers of every position so far, ``conv (B, K − 1,
    channels)`` the convolution's last inputs. Either may be EMPTY: a
    Lightning layer has no convolution (``conv`` of no rows), a gated
    short convolution no recurrence (``state (B, 0, 0, 0)``, of no
    elements: its window is all it remembers). A step OVERWRITES both,
    so nothing of it rewinds by a length: a serving loop that sets a
    request back to its prompt's end puts both back from a copy taken
    there (:func:`snapshot_states` / :func:`restore_states`)."""
    state: jax.Array
    conv: jax.Array


class PackedCache(NamedTuple):
    """A slab cache for heads NARROWER than a lane tile: ``kv (B, H_kv,
    t_max, 2·d)`` holds a token's key (as scored: normed and rotated) in
    lanes ``[0, d)`` and its value in lanes ``[d, 2·d)`` of ONE row;
    ``length`` is the scalar clock of a :class:`DecodeCache`. At ``d =
    64`` a row is one 128-lane tile with nothing padded — ``2 · 64``
    values a token a KV head in HBM, where a ``DecodeCache``'s two
    ``(…, t_max, 64)`` buffers are each tiled to 128 lanes and a decode
    step streams twice the bytes. A step is ``decode_step``'s
    (``flash_decode``'s packed mode: the query zero-extended over the
    value lanes, a resident block in both products, the output the value
    lanes — two KV heads a pass where a grid step holds an even number
    of them); a length set back rewinds it."""
    kv: jax.Array
    length: jax.Array

    @property
    def t_max(self):
        return self.kv.shape[-2]

    @property
    def head_dim(self):
        return self.kv.shape[-1] // 2


def init_packed_cache(batch, kv_heads, t_max, head_dim,
                      dtype=jnp.bfloat16):
    """Zero :class:`PackedCache` for ``t_max`` positions of ``kv_heads``
    heads ``head_dim`` wide (keys and values alike)."""
    return PackedCache(
        kv=jnp.zeros((batch, kv_heads, t_max, 2 * head_dim), dtype),
        length=jnp.zeros((), jnp.int32))


def packed_append(cache: PackedCache, k_new, v_new) -> PackedCache:
    """:func:`append_kv` on a :class:`PackedCache`: ``k_new`` / ``v_new
    (B, H_kv, n, d)`` go to the two halves of rows ``length … length + n
    − 1``. Past ``t_max`` nothing is written while the length still
    advances (the traced guard of :func:`append_kv`)."""
    new = jnp.concatenate([k_new, v_new], axis=-1).astype(cache.kv.dtype)
    n = new.shape[-2]
    if n > cache.t_max:
        raise ValueError(f'appending {n} positions to a t_max='
                         f'{cache.t_max} cache')
    zero = jnp.zeros((), jnp.int32)
    idx = (zero, zero, cache.length, zero)
    cur = lax.dynamic_slice(cache.kv, idx, new.shape)
    kv = lax.dynamic_update_slice(
        cache.kv, jnp.where(cache.length + n > cache.t_max, cur, new), idx)
    return PackedCache(kv=kv, length=cache.length + n)


def packed_views(cache: PackedCache):
    """``(k, v)``, each ``(B, H_kv, t_max, d)``: the two halves as
    buffers of their own (copies: what prefill's flash forward and the
    XLA step read; the decode kernel reads the packed rows)."""
    d = cache.head_dim
    return cache.kv[..., :d], cache.kv[..., d:]


class LatentCache(NamedTuple):
    """A latent-attention (MLA) layer's cache (``models/latent.py``):
    ``rows (B, row_dim, t_max)``, TIME-MINOR — column ``t`` of session
    ``b`` is its token ``t``'s compressed row, ``row_dim`` values with
    no padding (576 is no multiple of the 128-lane tile: stored a token
    a row it is either padded to 640, a ninth of what a decode step
    streams, or laid out time-minor by the chip anyway) —, ``length
    (B,) int32`` the tokens held of each session — ONE layer's, beside
    the other layers' caches of a mixed stack. A stack of latent layers
    alone keeps one layer-stacked buffer ``(L, B, row_dim, t_max)`` with
    lengths ``(L, B)`` (a layer advances its own, as the slab caches'
    layers do). Columns past a length are never read: a length set back
    rewinds it."""
    rows: jax.Array
    length: jax.Array

    @property
    def t_max(self):
        return self.rows.shape[-1]


def snapshot_states(caches):
    """A copy of every :class:`StateCache` in the per-layer list
    ``caches`` (None at the layers of other kinds — a cache that grows,
    a :class:`LatentCache` among them, rewinds by its length): what a
    prefix cache of a recurrent model holds at the prompt's end."""
    with device_scope('lm.state_restore'):
        return [jax.tree.map(jnp.copy, c) if isinstance(c, StateCache)
                else None for c in caches]


def restore_states(caches, snapshot):
    """``caches`` with every recurrent layer's state and window put back
    from ``snapshot`` (:func:`snapshot_states`'s; it is left intact);
    caches that grow are returned as they are — their length rewinds
    them. Donate ``caches``: one device copy a state, in place. Each
    buffer is written over in two halves: under ``jit`` two in-place
    updates of the donated buffer that carry this scope's name (a
    whole-buffer update XLA reduces to its operand, and the copy it then
    inserts is nameless; through a temporary, too, where the program
    also reads the old state); eagerly the result shares nothing with
    the snapshot."""
    def over(old, new):
        axis = int(np.argmax(old.shape))
        half = old.shape[axis] // 2
        for lo, hi in ((0, half), (half, old.shape[axis])):
            old = lax.dynamic_update_slice_in_dim(
                old, lax.slice_in_dim(new, lo, hi, axis=axis), lo, axis)
        return old

    with device_scope('lm.state_restore'):
        return [c if s is None else jax.tree.map(over, c, s)
                for c, s in zip(caches, snapshot)]


def insert_session(cache, session, one):
    """``cache`` (a :class:`DecodeCache`, :class:`PackedCache`,
    :class:`RingCache`, :class:`SparseCache`, :class:`LatentCache` or
    :class:`StateCache` of a serving batch; None, a layer without a
    mixer, passes through) with
    session ``session`` replaced by the
    single session ``one`` holds — a prompt prefilled alone, then put in
    its slot. The batch shares one clock, so every session put in must
    be of ``one``'s length, which becomes the batch's (a state has
    none; a latent cache keeps a length a session, and the session's is
    put in with its rows). Donate ``cache``: the update is in place."""
    if cache is None:
        return None
    if isinstance(cache, LatentCache):
        # one layer's (B, w, t_max) or the stacked (L, B, w, t_max)
        zero = jnp.zeros((), jnp.int32)
        lead = (zero,) * (cache.rows.ndim - 3)
        session = jnp.asarray(session, jnp.int32)
        return LatentCache(
            rows=lax.dynamic_update_slice(
                cache.rows, one.rows, (*lead, session, zero, zero)),
            length=lax.dynamic_update_slice(cache.length, one.length,
                                            (*lead, session)))
    if isinstance(cache, StateCache):
        return StateCache(*(
            lax.dynamic_update_index_in_dim(buf, new[0], session, 0)
            for buf, new in zip(cache, one)))
    if getattr(cache, 'k_q', None) is not None:
        raise ValueError('insert_session moves k and v alone: a cache '
                         'with an int8 mirror is not covered')
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(session, jnp.int32), zero, zero, zero)
    if isinstance(cache, PackedCache):
        return PackedCache(
            kv=lax.dynamic_update_slice(cache.kv, one.kv, at),
            length=one.length)
    more = {}
    if isinstance(cache, SparseCache):
        more['pooled'] = lax.dynamic_update_slice(cache.pooled,
                                                  one.pooled, at)
    return cache._replace(
        k=lax.dynamic_update_slice(cache.k, one.k, at),
        v=lax.dynamic_update_slice(cache.v, one.v, at),
        length=one.length, **more)


def append_kv(cache: DecodeCache, k_new, v_new) -> DecodeCache:
    """Append ``k_new``/``v_new`` ``(B, H_kv, n, d·)`` at the cache head.
    ``n`` is static per call site (1 for decode, the prompt length for
    prefill); the write is a ``dynamic_update_slice`` at the traced
    length, so one compiled program serves every step.

    The caller owns the ``t_max`` budget: appending past it raises when
    the length is concrete (the usual serving loop, where the cache
    crosses the host between jitted steps). Under ``jit`` the length is
    traced and cannot raise, so the write carries a traced guard
    instead: an overflowing append leaves the buffers UNCHANGED (the
    write-back trick below — ``dynamic_update_slice`` alone would clamp
    onto the last slot and silently corrupt the newest entries) while
    ``length`` still advances, so after a jitted generation loop
    ``cache.length > cache.t_max`` detectably flags the overflow. Bound
    your loop by ``t_max`` regardless; the guard turns a miscounted
    loop's silent corruption into a checkable condition."""
    n = k_new.shape[-2]
    if n > cache.t_max:
        raise ValueError(f'appending {n} positions to a t_max='
                         f'{cache.t_max} cache')
    try:
        length = int(cache.length)
    except (jax.errors.ConcretizationTypeError, TypeError):
        length = None  # traced (inside jit): the traced guard applies
    if length is not None and length + n > cache.t_max:
        raise ValueError(
            f'KV-cache overflow: length {length} + {n} new positions '
            f'exceeds t_max {cache.t_max} — grow the cache or stop the '
            f'generation loop')
    idx = (jnp.zeros((), jnp.int32),) * 2 + (cache.length,
                                             jnp.zeros((), jnp.int32))
    overflow = cache.length + n > cache.t_max

    def guarded_write(buf, new):
        # Overflow → write the slice's CURRENT contents back (a no-op
        # write at the clamped index: buffers stay intact); in-bounds →
        # the normal append. One extra n-row read per append — noise
        # against the full-buffer stream the attention step does anyway.
        cur = lax.dynamic_slice(buf, idx, new.shape)
        return lax.dynamic_update_slice(
            buf, jnp.where(overflow, cur, new), idx)
    k_q = k_scale = None
    if cache.k_q is not None:
        # Maintain the int8 mirror with the SAME per-row rule as the
        # training kernels (ops.pallas_attention._quantize_rows) — rows
        # never change after append, so this is exact.
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        b, h_kv, _, d = cache.k.shape
        # Quantize the CACHE-dtype value (what the raw buffer stores),
        # not the caller's dtype — the mirror's exactness contract is
        # "identical to re-quantizing the buffer", which a higher-
        # precision k_new would silently break.
        ki, sk = _quantize_rows(k_new.astype(cache.k.dtype), b * h_kv,
                                n, d)
        k_q = guarded_write(cache.k_q, ki.reshape(b, h_kv, n, d))
        k_scale = guarded_write(cache.k_scale,
                                sk.reshape(b, h_kv, n, 1))
    return DecodeCache(
        k=guarded_write(cache.k, k_new.astype(cache.k.dtype)),
        v=guarded_write(cache.v, v_new.astype(cache.v.dtype)),
        length=cache.length + n, k_q=k_q, k_scale=k_scale)


def append_kv_sharded(cache: DecodeCache, k_new, v_new, *,
                      axis_name=SEQ_AXIS):
    """Sequence-sharded :func:`append_kv` (inside a ``shard_map``): the
    cache buffers hold this shard's ``(B, H_kv, t_max/N, d·)`` slab of
    a global ``N·t_local`` buffer — serving memory scales PAST one
    chip's HBM — while ``cache.length`` stays the GLOBAL length
    (replicated; RoPE positions and the causal mask read it).

    Decode (``n == 1``): the write is an in-place single-row
    ``dynamic_update_slice`` on the OWNING shard and the write-back
    no-op everywhere else — per-token cost is unchanged from the local
    path. Prefill (``n > 1``): the chunk may straddle shard boundaries,
    so each shard rebuilds its slab through a masked gather — O(t_local)
    traffic, the same order as the prefill attention that follows.
    Appends past the global capacity write nowhere while ``length``
    still advances (the :func:`append_kv` overflow contract)."""
    n = k_new.shape[-2]
    tl = cache.t_max                       # local slab length
    lo = lax.axis_index(axis_name) * tl
    p = cache.length
    b, h_kv, _, d = cache.k.shape

    k_q_new = k_scale_new = None
    if cache.k_q is not None:
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        ki, sk = _quantize_rows(k_new.astype(cache.k.dtype), b * h_kv,
                                n, d)
        k_q_new = ki.reshape(b, h_kv, n, d)
        k_scale_new = sk.reshape(b, h_kv, n, 1)

    # Whole-append overflow drop, matching append_kv's contract exactly:
    # an append that would cross the GLOBAL capacity writes NOTHING
    # anywhere (not even its in-capacity prefix — the local path drops
    # the whole chunk, and sharded parity means doing the same).
    ok = p + n <= lax.psum(1, axis_name) * tl
    if n == 1:
        local = jnp.clip(p - lo, 0, tl - 1)
        owns = jnp.logical_and(jnp.logical_and(p >= lo, p < lo + tl), ok)
        idx = (jnp.zeros((), jnp.int32),) * 2 + (local,
                                                 jnp.zeros((), jnp.int32))

        def write(buf, new):
            cur = lax.dynamic_slice(buf, idx, new.shape)
            return lax.dynamic_update_slice(
                buf, jnp.where(owns, new.astype(buf.dtype), cur), idx)
    else:
        g = lo + jnp.arange(tl)                       # global slab rows
        src = jnp.clip(g - p, 0, n - 1)
        hit = jnp.logical_and(jnp.logical_and(g >= p, g < p + n),
                              ok)[:, None]

        def write(buf, new):
            vals = jnp.take(new.astype(buf.dtype), src, axis=-2)
            return jnp.where(hit, vals, buf)

    k_q = k_scale = None
    if cache.k_q is not None:
        k_q = write(cache.k_q, k_q_new)
        k_scale = write(cache.k_scale, k_scale_new)
    return DecodeCache(k=write(cache.k, k_new), v=write(cache.v, v_new),
                       length=cache.length + n, k_q=k_q, k_scale=k_scale)


def init_slot_cache(slots, kv_heads, t_max, head_dim, v_head_dim=None,
                    dtype=jnp.bfloat16):
    """Serving cache with PER-SLOT lengths: identical buffers to
    :func:`init_cache` but ``length`` is a ``(slots,)`` vector — each
    batch row is an independent decode slot holding its own sequence.
    This is the continuous-batching substrate: slots fill, decode and
    free on their own clocks (:func:`append_kv_slots`,
    :func:`reset_slot`) with no whole-batch reallocation, and
    :func:`decode_attention` masks each row against its own length.

    The int8 K mirror is a chained-decode throughput optimization that
    the serving scheduler doesn't drive yet, so ``qk_quant`` is not a
    parameter here (a mirror-less cache still accepts
    ``decode_attention(..., qk_quant='int8')`` via on-the-fly
    quantization)."""
    base = init_cache(slots, kv_heads, t_max, head_dim,
                      v_head_dim=v_head_dim, dtype=dtype)
    return base._replace(length=jnp.zeros((slots,), jnp.int32))


def _concrete_lengths(length):
    """Host ints when the length vector is concrete, else None (traced)."""
    try:
        return [int(x) for x in length]
    except (jax.errors.ConcretizationTypeError, TypeError):
        return None


def append_kv_slots(cache: DecodeCache, k_new, v_new, *, slot_mask=None,
                    counts=None) -> DecodeCache:
    """Per-slot append onto a slot cache (``length`` a ``(B,)`` vector):
    each slot's rows land at ITS length, in one compiled program.

    ``k_new``/``v_new`` are ``(B, H_kv, n, d·)``; ``counts (B,) int32``
    takes the first ``counts[i]`` of the ``n`` rows for slot ``i``
    (padded prefill chunks keep one compiled shape; default: all ``n``);
    ``slot_mask (B,) bool`` freezes unselected slots entirely (buffers
    AND length — a decode step only appends for live slots).

    The write is a masked gather over the ``t_max`` axis — O(t_max)
    traffic, the same order as the attention step that follows, and the
    only way distinct per-row offsets fit one ``jit``. Overflow matches
    :func:`append_kv`'s contract per slot: concrete lengths raise
    eagerly naming the slot; traced lengths write NOTHING for the
    overflowing slot while its length still advances (detectable as
    ``cache.length[i] > cache.t_max``)."""
    if isinstance(cache, PagedDecodeCache):
        # Same surface, paged substrate: rows scatter into pool pages
        # through the slot's page-table row instead of its dense strip.
        return paged_append_kv_slots(cache, k_new, v_new,
                                     slot_mask=slot_mask, counts=counts)
    if cache.length.ndim != 1:
        raise ValueError(
            'append_kv_slots needs a per-slot cache (init_slot_cache); '
            'this cache has a scalar length — use append_kv')
    b, _, _, _ = cache.k.shape
    n = k_new.shape[-2]
    if n > cache.t_max:
        raise ValueError(f'appending {n} positions to a t_max='
                         f'{cache.t_max} cache')
    counts = (jnp.full((b,), n, jnp.int32) if counts is None
              else jnp.asarray(counts, jnp.int32))
    active = (jnp.ones((b,), bool) if slot_mask is None
              else jnp.asarray(slot_mask, bool))
    eff = jnp.where(active, jnp.clip(counts, 0, n), 0)     # rows per slot

    host_len = _concrete_lengths(cache.length)
    host_eff = _concrete_lengths(eff)
    if host_len is not None and host_eff is not None:
        for i, (cur, add) in enumerate(zip(host_len, host_eff)):
            if cur + add > cache.t_max:
                raise ValueError(
                    f'KV-cache overflow on slot {i}: length {cur} + '
                    f'{add} new positions exceeds t_max {cache.t_max} '
                    f'— evict the slot (reset_slot) or stop its '
                    f'generation loop')

    ok = cache.length + eff <= cache.t_max                 # (B,)
    g = jnp.arange(cache.t_max)[None, :]                   # (1, t_max)
    lo = cache.length[:, None]                             # (B, 1)
    hit = jnp.logical_and(
        jnp.logical_and(g >= lo, g < lo + eff[:, None]),
        ok[:, None])                                       # (B, t_max)
    src = jnp.clip(g - lo, 0, n - 1)                       # (B, t_max)

    def write(buf, new):
        vals = jnp.take_along_axis(new.astype(buf.dtype),
                                   src[:, None, :, None], axis=-2)
        return jnp.where(hit[:, None, :, None], vals, buf)

    k_q = k_scale = None
    if cache.k_q is not None:
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        bb, h_kv, _, d = cache.k.shape
        ki, sk = _quantize_rows(k_new.astype(cache.k.dtype), bb * h_kv,
                                n, d)
        k_q = write(cache.k_q, ki.reshape(bb, h_kv, n, d))
        k_scale = write(cache.k_scale, sk.reshape(bb, h_kv, n, 1))
    return DecodeCache(k=write(cache.k, k_new), v=write(cache.v, v_new),
                       length=cache.length + eff, k_q=k_q,
                       k_scale=k_scale)


def reset_slot(cache: DecodeCache, slot) -> DecodeCache:
    """Evict one sequence: zero slot ``slot``'s buffers and length. The
    slot immediately serves a fresh sequence; every OTHER slot's bits
    are untouched (tested bit-identical) and nothing reallocates —
    that's the whole point of the per-slot length vector. ``slot`` may
    be traced (one compiled program resets any slot)."""
    if cache.length.ndim != 1:
        raise ValueError(
            'reset_slot needs a per-slot cache (init_slot_cache); a '
            'scalar-length cache is reset by init_cache — its batch '
            'rows share one sequence clock')
    if isinstance(cache, PagedDecodeCache):
        raise ValueError(
            'reset_slot on a paged cache needs the freed-page list — '
            'use paged_reset_slot with PagePool.release()\'s result')
    sel = jnp.arange(cache.k.shape[0]) == slot             # (B,)

    def clear(buf):
        return jnp.where(sel[:, None, None, None],
                         jnp.zeros_like(buf), buf)

    return cache._replace(
        k=clear(cache.k), v=clear(cache.v),
        length=jnp.where(sel, 0, cache.length),
        k_q=None if cache.k_q is None else clear(cache.k_q),
        k_scale=None if cache.k_scale is None else clear(cache.k_scale))


def slots_all_finite(x):
    """Per-slot all-finite predicate: ``(B, ...)`` → ``(B,) bool``. The
    serving layer's quarantine test — the train loop's all-finite guard
    (train.py ``guard=True``) at slot granularity, so ONE poisoned
    sequence is evicted instead of failing the whole batch."""
    return jnp.all(jnp.isfinite(x.reshape(x.shape[0], -1)), axis=-1)


def rollback_slots(cache: DecodeCache, lengths, span=None):
    """Acceptance-prefix rollback (speculative decoding): truncate each
    slot's length to ``lengths`` AND zero every row at or past it, so
    the cache is BIT-IDENTICAL to having appended only the accepted
    tokens — the rejected proposals' k/v (and int8-mirror rows) leave
    no residue for a later query row, padded verify row, or recycled
    position to read. ``lengths`` broadcasts against ``cache.length``
    (a ``(B,)`` vector for slot caches, a scalar for scalar-clock
    caches — including a layer-stacked generation cache, where both
    carry a leading layer axis); a slot whose target is at or past its
    current fill is untouched (``min(current, target)`` semantics, so
    one batched call can roll back a FEW slots with a don't-touch
    sentinel for the rest).

    ``span`` (static, per-slot caches only): the most rows any slot
    rolls back — a verify-k step rejects at most k proposals, so the
    serving engine passes its verify width. With a span the zeroing is
    a SURGICAL scatter over the ``span`` rows at each slot's new length
    (O(B·span·d) traffic — the verify hot path must not rewrite the
    whole cache to drop k rows); rows past a slot's old fill were
    already zero, so over-zeroing the span is harmless and the result
    is bit-identical to the full-mask path. Without a span the mask
    covers the whole ``t_max`` axis (the general form scalar-clock and
    layer-stacked generation caches use). Paged caches route through
    :func:`paged_rollback_slots` — the pool needs the host allocator's
    page release."""
    if isinstance(cache, PagedDecodeCache):
        raise ValueError(
            'rollback_slots on a paged cache needs the bounded span '
            'and the host page release — use paged_rollback_slots '
            "with PagePool.truncate()'s bookkeeping")
    new_len = jnp.minimum(cache.length,
                          jnp.asarray(lengths, cache.length.dtype))
    if span is not None:
        if cache.length.ndim != 1:
            raise ValueError('span needs a per-slot cache '
                             '(init_slot_cache); scalar-clock caches '
                             'take the full-mask path (span=None)')
        b = cache.length.shape[0]
        pos = new_len[:, None] + jnp.arange(span)[None, :]  # (B, span)
        bi = jnp.arange(b)[:, None]

        def trunc(buf):
            zero = jnp.zeros((buf.shape[1], buf.shape[-1]), buf.dtype)
            return buf.at[bi, :, pos, :].set(zero, mode='drop')

        return cache._replace(
            k=trunc(cache.k), v=trunc(cache.v), length=new_len,
            k_q=None if cache.k_q is None else trunc(cache.k_q),
            k_scale=(None if cache.k_scale is None
                     else trunc(cache.k_scale)))

    keep = (jnp.arange(cache.t_max) < new_len[..., None])

    def trunc(buf):
        # keep is length-shaped + (t_max,); pad singleton axes between
        # the length dims and the time axis so it broadcasts against
        # scalar (B, H, T, d·), per-slot (B, H, T, d·) and layer-
        # stacked (L, B, H, T, d·) buffers alike.
        extra = buf.ndim - new_len.ndim - 2
        k = keep.reshape(keep.shape[:-1] + (1,) * extra
                         + (cache.t_max, 1))
        return jnp.where(k, buf, jnp.zeros((), buf.dtype))

    return cache._replace(
        k=trunc(cache.k), v=trunc(cache.v), length=new_len,
        k_q=None if cache.k_q is None else trunc(cache.k_q),
        k_scale=(None if cache.k_scale is None
                 else trunc(cache.k_scale)))


# -- paged KV cache -----------------------------------------------------
#
# The slab cache above reserves a dense t_max-length strip per slot, so
# concurrency per chip is bounded by WORST-CASE context length. The
# paged cache breaks that bound: one global pool of fixed-size pages,
# indexed per slot by a page table — a slot holds exactly the pages its
# actual fill needs, pages can be SHARED between slots (refcounted — a
# registered system-prompt prefix occupies its pages once no matter how
# many sequences ride it), and forking a sequence for parallel sampling
# is a refcount bump plus one partial-page copy (copy-on-write). The
# slab path stays as the reference implementation; the paged step must
# match it bit-identically (tests/test_paged_decode.py pins it).
#
# Split of responsibilities: the DEVICE side (PagedDecodeCache + the
# paged_* ops below) only ever reads/writes pool pages named by the
# page table — appends are drop-mode scatters, so a -1 (unallocated)
# table entry writes nothing. The HOST side (PagePool) owns the policy:
# free list, refcounts, copy-on-write, prefix attach, fork. The serving
# engine mirrors the page table to the device whenever the host mutates
# it (a (slots, pages_per_slot) int32 array — bytes, not buffers).


class PagedDecodeCache(NamedTuple):
    """Paged serving cache: ``k_pool``/``v_pool`` are global
    ``(pages + 1, H_kv, page_size, d·)`` pools; ``page_table`` is the
    ``(slots, pages_per_slot) int32`` map from each slot's logical page
    ordinal to its pool page (−1 = unallocated); ``length`` the per-slot
    fill, exactly as :func:`init_slot_cache`. Logical positions work
    like the slab cache's: position ``p`` of slot ``i`` lives at row
    ``p % page_size`` of pool page ``page_table[i, p // page_size]``.

    The LAST pool row (index :attr:`pages`) is the reserved SINK page —
    never allocated, never attended. The fused kernel redirects the
    write-back of slots with nothing to append (and the stream of
    unallocated table entries) there, so no grid row ever touches a
    page another slot owns: Pallas flushes every output block whether
    or not the kernel wrote it, and without the sink an idle slot's
    copy-through could race another slot's in-flight append on real
    TPU (grid rows have no cross-row write ordering).

    ``k_q_pool``/``k_scale_pool``: the optional int8 K mirror ON THE
    PAGE POOL — ``(pages + 1, H_kv, page_size, d) int8`` and
    ``(pages + 1, H_kv, page_size, 1) f32`` pools maintained by every
    paged write exactly like the slab cache's ``k_q``/``k_scale``
    (rows quantize once at append with the training kernels' per-row
    rule, so the mirror is bit-identical to re-quantizing the pool).
    With the mirror, quantized decode rides the fused kernel at paged
    concurrency: the kernel streams the 1-byte mirror pages through
    the same page-table BlockSpec redirect as the bf16 pool."""
    k_pool: jax.Array
    v_pool: jax.Array
    page_table: jax.Array
    length: jax.Array
    k_q_pool: Optional[jax.Array] = None
    k_scale_pool: Optional[jax.Array] = None

    @property
    def page_size(self):
        return self.k_pool.shape[-2]

    @property
    def pages(self):
        """Allocatable pages (the sink row is not one of them)."""
        return self.k_pool.shape[0] - 1

    @property
    def pages_per_slot(self):
        return self.page_table.shape[1]

    @property
    def slots(self):
        return self.page_table.shape[0]

    @property
    def t_max(self):
        """Per-slot logical capacity (the page table's reach)."""
        return self.page_table.shape[1] * self.k_pool.shape[-2]


def init_paged_cache(slots, kv_heads, t_max, head_dim, *, pages,
                     page_size, v_head_dim=None, dtype=jnp.bfloat16,
                     qk_quant=None):
    """Zero paged cache: a ``pages``-page pool whose page size must
    divide the per-slot capacity ``t_max``. The pool is sized by the
    MEMORY budget, not ``slots × t_max`` — that decoupling is the whole
    point (``pages << slots · t_max/page_size`` serves more concurrent
    sequences than a slab of the same bytes whenever actual fill is
    below worst case). ``qk_quant='int8'`` allocates the int8 K-mirror
    pools for int8-trained models — quantized decode then rides the
    fused kernel on the page pool (see :class:`PagedDecodeCache`)."""
    v_head_dim = v_head_dim or head_dim
    if page_size < 1 or t_max % page_size:
        raise ValueError(f'page_size {page_size} must divide t_max '
                         f'{t_max}')
    if pages < 1:
        raise ValueError(f'need pages >= 1, got {pages}')
    if qk_quant not in (None, 'int8'):
        raise ValueError(f"qk_quant must be None or 'int8', "
                         f'got {qk_quant!r}')
    quant = qk_quant == 'int8'
    # +1: the reserved write-sink row (see PagedDecodeCache).
    return PagedDecodeCache(
        k_pool=jnp.zeros((pages + 1, kv_heads, page_size, head_dim),
                         dtype),
        v_pool=jnp.zeros((pages + 1, kv_heads, page_size, v_head_dim),
                         dtype),
        page_table=jnp.full((slots, t_max // page_size), -1, jnp.int32),
        length=jnp.zeros((slots,), jnp.int32),
        k_q_pool=(jnp.zeros((pages + 1, kv_heads, page_size, head_dim),
                            jnp.int8) if quant else None),
        k_scale_pool=(jnp.zeros((pages + 1, kv_heads, page_size, 1),
                                jnp.float32) if quant else None))


def paged_gather(cache: PagedDecodeCache):
    """Materialize the slab view ``(slots, H_kv, t_max, d·)`` of a paged
    cache — the portable XLA decode path attends against this (the same
    masked math as the slab cache, so outputs are bit-identical), and
    tests compare paged and slab contents through it. Unallocated table
    entries redirect to the reserved SINK row (last pool page): on the
    XLA path nothing ever writes it, so those columns read the slab's
    literal zeros — and a slot never gathers another slot's live pages
    even when its host-tracked length runs ahead of its allocation.
    (Columns past ``length`` are masked regardless, so kernel-path
    flush garbage parked on the sink still contributes exactly 0.)"""
    return _gather_pools(cache, cache.k_pool, cache.v_pool)


def _gather_pools(cache: PagedDecodeCache, *pools):
    """THE page-table gather (shared by the data and mirror slab
    views, so the sink-redirect/clip semantics cannot drift between
    them): each ``(pages + 1, H_kv, page_size, d·)`` pool →
    ``(slots, H_kv, t_max, d·)``, unallocated entries reading the
    reserved sink row."""
    pt = jnp.where(cache.page_table >= 0, cache.page_table,
                   cache.pages).reshape(-1)                # (B·np,)
    b, npg = cache.page_table.shape
    ps = cache.page_size

    def g(pool):
        h_kv, d = pool.shape[1], pool.shape[-1]
        x = jnp.take(pool, pt, axis=0, mode='clip')  # (B·np, H, ps, d)
        x = x.reshape(b, npg, h_kv, ps, d)
        return jnp.moveaxis(x, 2, 1).reshape(b, h_kv, npg * ps, d)

    return tuple(g(pool) for pool in pools)


def paged_gather_mirror(cache: PagedDecodeCache):
    """Slab view of the int8 K mirror — ``(k_q (B, H_kv, t_max, d),
    k_scale (B, H_kv, t_max, 1))`` gathered through the page table
    exactly like :func:`paged_gather`; the portable XLA quantized
    decode attends against it (unallocated entries read the sink
    page's zeros — zero scale, masked columns anyway)."""
    if cache.k_q_pool is None:
        raise ValueError('this paged cache carries no int8 K mirror — '
                         "allocate it with init_paged_cache("
                         "qk_quant='int8')")
    return _gather_pools(cache, cache.k_q_pool, cache.k_scale_pool)


def _paged_scatter_indices(cache: PagedDecodeCache, start, count, n):
    """Drop-mode scatter targets for ``n`` candidate rows per slot at
    logical positions ``start..`` — THE page/row index computation
    every per-slot paged writer shares (appends and the mirror fixup),
    so the two writers provably target the same pool rows. ``start
    (B,)`` is row 0's logical position (−1 = slot writes nothing);
    ``count (B,)`` how many of the ``n`` rows are real. Returns
    ``(pg, rw) (B, n)`` with every invalid row (past its count, no
    start, past the table reach, unallocated page) redirected ONE PAST
    the pool end so ``.at[...].set(mode='drop')`` discards it (−1
    would WRAP to the last pool page and corrupt it)."""
    b, npg = cache.page_table.shape
    ps = cache.page_size
    pos = start[:, None] + jnp.arange(n)[None, :]          # (B, n)
    pi = pos // ps
    valid = jnp.logical_and(jnp.arange(n)[None, :] < count[:, None],
                            start[:, None] >= 0)
    pg = jnp.take_along_axis(cache.page_table,
                             jnp.clip(pi, 0, npg - 1), axis=1)
    pg = jnp.where(jnp.logical_and(valid,
                                   jnp.logical_and(pi < npg, pg >= 0)),
                   pg, cache.pages + 1)                    # (B, n)
    rw = pos % ps
    return pg, rw


def paged_append_kv_slots(cache: PagedDecodeCache, k_new, v_new, *,
                          slot_mask=None, counts=None):
    """:func:`append_kv_slots` over the paged pool: each slot's rows
    scatter into the pool pages its table names, at its own length.
    Same contract — ``counts``/``slot_mask`` semantics, eager overflow
    raise on concrete lengths naming the slot, traced overflow writes
    nothing while the length advances — plus the paged guard: a row
    whose page-table entry is unallocated (−1) is DROPPED, never
    written anywhere (the host allocator must have reserved pages
    first; :class:`PagePool` is that allocator)."""
    b = cache.page_table.shape[0]
    t_max = cache.t_max
    n = k_new.shape[-2]
    if n > t_max:
        raise ValueError(f'appending {n} positions to a t_max='
                         f'{t_max} cache')
    counts = (jnp.full((b,), n, jnp.int32) if counts is None
              else jnp.asarray(counts, jnp.int32))
    active = (jnp.ones((b,), bool) if slot_mask is None
              else jnp.asarray(slot_mask, bool))
    eff = jnp.where(active, jnp.clip(counts, 0, n), 0)

    host_len = _concrete_lengths(cache.length)
    host_eff = _concrete_lengths(eff)
    if host_len is not None and host_eff is not None:
        for i, (cur, add) in enumerate(zip(host_len, host_eff)):
            if cur + add > t_max:
                raise ValueError(
                    f'KV-cache overflow on slot {i}: length {cur} + '
                    f'{add} new positions exceeds t_max {t_max} '
                    f'— evict the slot (reset_slot) or stop its '
                    f'generation loop')

    ok = cache.length + eff <= t_max                       # (B,)
    pg, rw = _paged_scatter_indices(cache, cache.length,
                                    jnp.where(ok, eff, 0), n)

    def write(pool, new):
        vals = jnp.moveaxis(new.astype(pool.dtype), 2, 1)  # (B, n, H, d)
        return pool.at[pg, :, rw, :].set(vals, mode='drop')

    k_q_pool, k_scale_pool = cache.k_q_pool, cache.k_scale_pool
    if cache.k_q_pool is not None:
        # Maintain the pool mirror — the ONE quantize-and-scatter body
        # (also the kernel path's post-hoc fixup), so the append rule
        # and the fixup rule are provably the same computation.
        k_q_pool, k_scale_pool = _paged_mirror_fixup(
            cache, k_new, cache.length, jnp.where(ok, eff, 0))
    return cache._replace(k_pool=write(cache.k_pool, k_new),
                          v_pool=write(cache.v_pool, v_new),
                          length=cache.length + eff,
                          k_q_pool=k_q_pool,
                          k_scale_pool=k_scale_pool)


def paged_append_rows(cache: PagedDecodeCache, k_rows, v_rows, page_row,
                      start, count):
    """Single-SEQUENCE scatter used by prefix registration: ``count`` of
    the ``k_rows``/``v_rows (H_kv, C, d·)`` rows land at logical
    positions ``start..`` through the ``(pages_per_slot,) int32``
    ``page_row`` vector (−1-padded), with no slot or length involved —
    a registered prefix lives in registry-owned pages, not a slot."""
    npg = cache.pages_per_slot
    ps = cache.page_size
    c = k_rows.shape[-2]
    pos = start + jnp.arange(c)
    pi = pos // ps
    pg = jnp.take(page_row, jnp.clip(pi, 0, npg - 1))
    pg = jnp.where(jnp.logical_and(jnp.arange(c) < count,
                                   jnp.logical_and(pi < npg, pg >= 0)),
                   pg, cache.pages + 1)   # past the sink row: dropped
    rw = pos % ps

    def write(pool, rows):
        vals = jnp.moveaxis(rows.astype(pool.dtype), 1, 0)  # (C, H, d)
        return pool.at[pg, :, rw, :].set(vals, mode='drop')

    k_q_pool, k_scale_pool = cache.k_q_pool, cache.k_scale_pool
    if cache.k_q_pool is not None:
        # Registered prefixes carry mirror rows too — a quantized slot
        # riding a shared prefix must stream identical int8 pages to a
        # slot that prefilled the same tokens itself.
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        h_kv, d = cache.k_pool.shape[1], cache.k_pool.shape[-1]
        ki, sk = _quantize_rows(k_rows.astype(cache.k_pool.dtype),
                                h_kv, c, d)
        k_q_pool = write(k_q_pool, ki.reshape(h_kv, c, d))
        k_scale_pool = write(k_scale_pool, sk.reshape(h_kv, c, 1))
    return cache._replace(k_pool=write(cache.k_pool, k_rows),
                          v_pool=write(cache.v_pool, v_rows),
                          k_q_pool=k_q_pool,
                          k_scale_pool=k_scale_pool)


def paged_reset_slot(cache: PagedDecodeCache, slot, freed_pages):
    """Evict one sequence from a paged cache: zero the pool pages in
    ``freed_pages`` (a ``(pages_per_slot,) int32`` vector, −1-padded —
    the pages whose refcount the host allocator just dropped to zero;
    still-shared pages are NOT listed and keep their bits), clear the
    slot's page-table row and zero its length. Zeroing freed pages is
    what keeps a recycled page's unfilled tail benign: the masked
    attention multiplies it by exactly 0, and a NaN left behind by a
    poisoned sequence would otherwise leak into its next owner's
    output (0 · NaN = NaN)."""
    idx = jnp.asarray(freed_pages, jnp.int32)
    idx = jnp.where(idx >= 0, idx, cache.pages + 1)  # −1 pads: dropped

    def clear(pool):
        return pool.at[idx].set(jnp.zeros((), pool.dtype), mode='drop')

    sel = jnp.arange(cache.slots) == slot
    return PagedDecodeCache(
        k_pool=clear(cache.k_pool), v_pool=clear(cache.v_pool),
        page_table=jnp.where(sel[:, None], -1, cache.page_table),
        length=jnp.where(sel, 0, cache.length),
        k_q_pool=(None if cache.k_q_pool is None
                  else clear(cache.k_q_pool)),
        k_scale_pool=(None if cache.k_scale_pool is None
                      else clear(cache.k_scale_pool)))


def paged_rollback_slots(cache: PagedDecodeCache, lengths, span):
    """Acceptance-prefix rollback over the paged pool: truncate each
    slot's length to ``lengths`` (``min(current, target)`` — a
    don't-touch slot passes a sentinel past its fill) and zero the
    rejected rows, which live at logical positions ``lengths ..
    lengths + span − 1`` of each rolled-back slot. ``span`` is STATIC
    (one compiled program): the most rows any slot rolls back — a
    verify-k step rejects at most k proposals, so the serving engine
    compiles with ``span = k``. Rows are zeroed through the slot's
    page table with the same drop-mode scatter as the appends
    (unallocated / out-of-range rows write nowhere); rows past a
    slot's CURRENT fill are already zero, so over-zeroing the span is
    harmless — and the pages touched were written this step, hence
    private (shared prefix/fork pages are always full pages below the
    fill). The HOST side releases now-empty tail pages separately
    (:meth:`PagePool.truncate`); the caller zeroes freed pages through
    the reset program as usual."""
    b, npg = cache.page_table.shape
    ps = cache.page_size
    new_len = jnp.minimum(cache.length,
                          jnp.asarray(lengths, cache.length.dtype))
    pos = new_len[:, None] + jnp.arange(span)[None, :]     # (B, span)
    pi = pos // ps
    pg = jnp.take_along_axis(cache.page_table,
                             jnp.clip(pi, 0, npg - 1), axis=1)
    pg = jnp.where(jnp.logical_and(pi < npg, pg >= 0),
                   pg, cache.pages + 1)     # past the sink: dropped
    rw = pos % ps

    def clear(pool):
        zero = jnp.zeros((pool.shape[1], pool.shape[-1]), pool.dtype)
        return pool.at[pg, :, rw, :].set(zero, mode='drop')

    return cache._replace(k_pool=clear(cache.k_pool),
                          v_pool=clear(cache.v_pool),
                          length=new_len,
                          k_q_pool=(None if cache.k_q_pool is None
                                    else clear(cache.k_q_pool)),
                          k_scale_pool=(None if cache.k_scale_pool is
                                        None
                                        else clear(cache.k_scale_pool)))


def paged_copy_attach(cache: PagedDecodeCache, src_page, dst_page, slot,
                      length_val):
    """The copy-on-write / attach primitive, one compiled program for
    all three uses: copy pool page ``src_page`` → ``dst_page`` (both
    scalars; −1 = no copy) and set ``length[slot] = length_val``
    (``slot = −1`` = no length change). CoW passes pages only; prefix
    attach and fork pass the partial tail-page copy plus the inherited
    length. The page table is host-owned; the caller re-mirrors it."""
    dst = jnp.where(dst_page >= 0, dst_page, cache.pages + 1)[None]

    def copy(pool):
        val = jnp.take(pool, jnp.maximum(src_page, 0)[None], axis=0)
        return pool.at[dst].set(val, mode='drop')

    sel = jnp.arange(cache.slots) == slot
    return cache._replace(
        k_pool=copy(cache.k_pool), v_pool=copy(cache.v_pool),
        length=jnp.where(sel, jnp.asarray(length_val, jnp.int32),
                         cache.length),
        k_q_pool=(None if cache.k_q_pool is None
                  else copy(cache.k_q_pool)),
        k_scale_pool=(None if cache.k_scale_pool is None
                      else copy(cache.k_scale_pool)))


def paged_transfer_pages(cache: PagedDecodeCache, src_k_pool, src_v_pool,
                         src_pages, dst_pages):
    """Cross-CACHE page transfer — the prefill→decode KV handoff unit
    of disaggregated serving (serve/replica.py): copy the pool pages
    named by ``src_pages`` out of ANOTHER paged cache's
    ``src_k_pool``/``src_v_pool`` into THIS cache's ``dst_pages``.
    Both vectors are ``−1``-padded to a fixed width (one compiled
    program per pool-shape pair, not per prefix length); a padded
    entry copies nothing — the write drops past the sink row like
    every other masked paged write. The page geometry (page size, KV
    heads, head dim) must match; the page COUNT of the two pools may
    differ (a prefill pool is sized for one prompt in flight, a decode
    pool for its whole batch). Page tables and host refcounts are
    untouched: the caller (``KernelEngine.adopt_prefix``) owns the
    allocator bookkeeping on both sides."""
    src = jnp.asarray(src_pages, jnp.int32)
    dst = jnp.asarray(dst_pages, jnp.int32)
    ok = jnp.logical_and(src >= 0, dst >= 0)
    dsti = jnp.where(ok, dst, cache.pages + 1)   # pads: dropped
    srci = jnp.maximum(src, 0)

    def put(pool, src_pool):
        rows = jnp.take(src_pool, srci, axis=0).astype(pool.dtype)
        return pool.at[dsti].set(rows, mode='drop')

    new_k = put(cache.k_pool, src_k_pool)
    k_q_pool, k_scale_pool = cache.k_q_pool, cache.k_scale_pool
    if cache.k_q_pool is not None:
        # Rebuild the mirror rows of the copied pages from the adopted
        # K itself: the per-row rule is deterministic over the
        # cache-dtype bits, so every FILLED row's mirror is bit-
        # identical to the source's (unfilled tail rows get the eps
        # scale instead of the init zero — both score exactly nothing
        # under the mask) — and it works whether or not the SOURCE
        # pool (a prefill pool may be unquantized) carries one.
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        h_kv, ps = cache.k_pool.shape[1], cache.page_size
        d = cache.k_pool.shape[-1]
        w = dst.shape[0]
        pages_k = jnp.take(new_k, jnp.minimum(dsti, cache.pages),
                           axis=0)                 # (W, H, ps, d)
        ki, sk = _quantize_rows(pages_k.reshape(w * h_kv, ps, d),
                                w * h_kv, ps, d)
        k_q_pool = k_q_pool.at[dsti].set(
            ki.reshape(w, h_kv, ps, d), mode='drop')
        k_scale_pool = k_scale_pool.at[dsti].set(
            sk.reshape(w, h_kv, ps, 1), mode='drop')
    return cache._replace(k_pool=new_k,
                          v_pool=put(cache.v_pool, src_v_pool),
                          k_q_pool=k_q_pool,
                          k_scale_pool=k_scale_pool)


class PagePool:
    """Host-side page allocator for a :class:`PagedDecodeCache`: free
    list, per-page refcounts, per-slot page-table mirror and length
    mirror. Pure numpy bookkeeping — deterministic (LIFO free list),
    no device work; the owner performs the device-side copies/zeroing
    its return values call for and re-mirrors :attr:`table` to the
    device when :attr:`dirty` is set.

    Sharing model: a page's refcount counts the page-table rows (plus
    registered prefixes) naming it. Pages are only ever WRITTEN at
    refcount 1 — :meth:`prepare_append` returns the copy-on-write pair
    when a slot's append page is shared, and :meth:`fork` /
    :meth:`attach` share full pages read-only while copying the partial
    tail page the branch will append into."""

    def __init__(self, pages, page_size, slots, pages_per_slot):
        self.pages = pages
        self.page_size = page_size
        self.slots = slots
        self.pages_per_slot = pages_per_slot
        self.refcount = np.zeros(pages, np.int32)
        self._free = list(range(pages - 1, -1, -1))   # pop() → 0, 1, …
        self.table = np.full((slots, pages_per_slot), -1, np.int32)
        self.counts = np.zeros(slots, np.int32)       # pages per slot
        self.lengths = np.zeros(slots, np.int64)      # fill per slot
        self.dirty = False          # table changed since last mirror
        self.quarantined = set()    # pages withdrawn from circulation

    # -- introspection --------------------------------------------------
    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        return self.pages - len(self._free)

    @property
    def shared_pages(self):
        """Pages referenced more than once — the prefix-sharing/fork
        win, and the acceptance gauge ('the prefix's pages occupied
        exactly once')."""
        return int(np.sum(self.refcount > 1))

    def slot_pages(self, slot):
        return int(self.counts[slot])

    def pages_for_rows(self, rows):
        """Pages a fresh sequence of ``rows`` tokens needs."""
        return -(-rows // self.page_size)

    # -- allocation -----------------------------------------------------
    def alloc(self):
        """One free page at refcount 1, or None (exhausted). Freshly
        allocated pages are always zero: init starts them zero and
        :meth:`_unref` only frees a page after the owner zeroes it."""
        if not self._free:
            return None
        page = self._free.pop()
        self.refcount[page] = 1
        return page

    def _unref(self, page):
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            # A quarantined page never re-enters the free list: the
            # owner still zeroes it (True), but it stays withdrawn.
            if page not in self.quarantined:
                self._free.append(page)
            return True
        return False

    def quarantine(self, pages):
        """Withdraw ``pages`` from circulation permanently (corruption
        verdict): free pages leave the free list now, referenced pages
        are withheld by :meth:`_unref` when their last reference drops.
        Returns the pages newly quarantined (idempotent)."""
        fresh = []
        for page in pages:
            page = int(page)
            if page in self.quarantined:
                continue
            self.quarantined.add(page)
            if self.refcount[page] == 0:
                # Delete by INDEX, never list.remove: .remove raises an
                # untyped ValueError when the element is missing (the
                # PR 17 deque.remove bug class; flowlint typed-escape
                # flags it even behind a membership guard).
                idx = next(
                    (i for i, f in enumerate(self._free) if f == page), None
                )
                if idx is not None:
                    self._free.pop(idx)
            fresh.append(page)
        return fresh

    def alloc_block(self, n):
        """Allocate ``n`` fresh pages as one unit (prefix
        registration). Returns the page list, or None with NOTHING
        changed when the pool cannot supply all of them (partial
        allocations roll back — never-written pages go straight back
        on the free list, still zero)."""
        pages = []
        for _ in range(n):
            p = self.alloc()
            if p is None:
                for q in reversed(pages):
                    self.refcount[q] = 0
                    self._free.append(q)
                return None
            pages.append(p)
        return pages

    def release_pages(self, pages):
        """Drop one reference from each page. Returns the pages that
        hit refcount 0 — back on the free list, and owed a device zero
        by the caller before any reuse (the :meth:`alloc` invariant)."""
        return [p for p in pages if self._unref(p)]

    def prepare_append(self, slot):
        """Make the next append position of ``slot`` writable. Returns
        ``(status, src, dst)``: ``('ok', -1, -1)`` nothing to do;
        ``('alloc', -1, page)`` a fresh (zero) page was mapped;
        ``('cow', src, dst)`` the append page was shared — the caller
        must device-copy ``src → dst`` (copy-on-write: the FIRST
        divergent append after a fork/attach pays one page copy);
        ``('full', -1, -1)`` the slot is at ``t_max`` — no page can
        ever cover the position, the device write drops (the slab
        engine's frozen-write contract), and allocating would not
        help; ``('exhausted', -1, -1)`` the pool is out of pages and
        nothing changed."""
        pos = int(self.lengths[slot])
        pi = pos // self.page_size
        if pi >= self.pages_per_slot:
            return ('full', -1, -1)
        if pi >= self.counts[slot]:
            page = self.alloc()
            if page is None:
                return ('exhausted', -1, -1)
            self.table[slot, pi] = page
            self.counts[slot] = pi + 1
            self.dirty = True
            return ('alloc', -1, page)
        page = int(self.table[slot, pi])
        if self.refcount[page] > 1:
            fresh = self.alloc()
            if fresh is None:
                return ('exhausted', -1, -1)
            self.refcount[page] -= 1        # > 1 before: never frees
            self.table[slot, pi] = fresh
            self.dirty = True
            return ('cow', page, fresh)
        return ('ok', -1, -1)

    def reserve_rows(self, slot, rows):
        """Reserve every page covering logical rows ``[length, length +
        rows)`` of ``slot`` (admission-time: a prompt's prefill must
        never fail mid-chunk). Returns ``(ok, copies)`` — ``copies``
        is the list of ``(src, dst)`` device copies the caller owes
        (at most one: the shared tail page). On exhaustion nothing is
        changed (partial allocations are rolled back)."""
        start = int(self.lengths[slot])
        end = start + rows
        if end > self.pages_per_slot * self.page_size:
            return False, []
        counts0 = int(self.counts[slot])
        undo = []                   # (pi, previous_entry, was_cow)
        copies = []
        for pi in range(start // self.page_size,
                        -(-end // self.page_size)):
            if pi >= self.counts[slot]:
                page = self.alloc()
                if page is None:
                    self._undo_reserve(slot, undo, counts0)
                    return False, []
                undo.append((pi, -1, False))
                self.table[slot, pi] = page
                self.counts[slot] = pi + 1
                self.dirty = True
            else:
                page = int(self.table[slot, pi])
                if self.refcount[page] > 1:
                    dup = self.alloc()
                    if dup is None:
                        self._undo_reserve(slot, undo, counts0)
                        return False, []
                    undo.append((pi, page, True))
                    self.refcount[page] -= 1
                    self.table[slot, pi] = dup
                    copies.append((page, dup))
                    self.dirty = True
        return True, copies

    def _undo_reserve(self, slot, undo, counts0):
        """Roll a partial :meth:`reserve_rows` back: on exhaustion the
        pool and the slot's row look exactly as they did before the
        call (a shed admission must not leak pages or CoW remaps)."""
        for pi, prev, was_cow in reversed(undo):
            page = int(self.table[slot, pi])
            self.refcount[page] = 0
            self._free.append(page)
            self.table[slot, pi] = prev
            if was_cow:
                self.refcount[prev] += 1
        self.counts[slot] = counts0

    def release(self, slot):
        """Drop every page reference ``slot`` holds; returns the pages
        whose refcount reached zero (the caller zeroes them on device
        BEFORE they can be re-allocated) and clears the slot's row and
        length."""
        freed = []
        for pi in range(int(self.counts[slot])):
            page = int(self.table[slot, pi])
            if page >= 0 and self._unref(page):
                freed.append(page)
        self.table[slot, :] = -1
        self.counts[slot] = 0
        self.lengths[slot] = 0
        self.dirty = True
        return freed

    def truncate(self, slot, new_length):
        """Acceptance-prefix rollback, host side: shrink ``slot``'s fill
        to ``new_length`` and release the tail pages no kept row lives
        in (refcount−−; the returned list is the pages that hit 0 — the
        caller zeroes them on device before reuse, the :meth:`alloc`
        invariant, via the same reset program as eviction). The kept
        partial tail page stays mapped; the device-side
        :func:`paged_rollback_slots` zeroes its rejected rows. A
        ``new_length`` at or past the current fill is a no-op."""
        if new_length >= int(self.lengths[slot]):
            return []
        keep = self.pages_for_rows(int(new_length))
        freed = []
        for pi in range(keep, int(self.counts[slot])):
            page = int(self.table[slot, pi])
            if page >= 0:
                if self._unref(page):
                    freed.append(page)
                self.table[slot, pi] = -1
                self.dirty = True
        self.counts[slot] = min(int(self.counts[slot]), keep)
        self.lengths[slot] = new_length
        return freed

    # -- sharing --------------------------------------------------------
    def attach(self, slot, pages, length):
        """Point an EMPTY slot at a registered prefix: share the full
        pages read-only (refcount++), and if ``length`` ends mid-page
        allocate a private tail page the caller must device-copy the
        prefix's tail into. Returns ``(ok, tail_src, tail_dst)`` with
        −1s when no tail copy is needed; on exhaustion nothing is
        changed."""
        if self.counts[slot] or self.lengths[slot]:
            # Pool-state invariant, not an argument check: the serving
            # stack attaches only onto a just-reset slot, so a non-empty
            # one means the bookkeeping broke — RuntimeError, the typed
            # internal-state shape (flowlint typed-escape: this raise is
            # reachable from Scheduler.submit via start_with_prefix).
            raise RuntimeError(f'attach needs an empty slot, slot '
                               f'{slot} holds {self.counts[slot]} '
                               f'pages')
        full = length // self.page_size
        rem = length % self.page_size
        tail_src = tail_dst = -1
        if rem:
            tail_dst = self.alloc()
            if tail_dst is None:
                return False, -1, -1
            tail_src = int(pages[full])
        for i in range(full):
            self.table[slot, i] = pages[i]
            self.refcount[pages[i]] += 1
        if rem:
            self.table[slot, full] = tail_dst
        self.counts[slot] = full + (1 if rem else 0)
        self.lengths[slot] = length
        self.dirty = True
        return True, tail_src, tail_dst

    def fork(self, src, dst):
        """Copy-on-write fork ``src → dst`` (an empty slot): full pages
        shared (refcount++), the partial tail page — the only page the
        branches will write divergently — copied. Returns ``(ok,
        tail_src, tail_dst)`` exactly like :meth:`attach`."""
        length = int(self.lengths[src])
        pages = [int(self.table[src, i])
                 for i in range(int(self.counts[src]))]
        return self.attach(dst, pages, length)


class PageChecksums:
    """Host-side per-page integrity table for a
    :class:`PagedDecodeCache`: CRC32 over a page's K and V rows (plus
    the int8 K-mirror rows when the cache carries one), recorded at
    TRANSFER boundaries only — registry fills, prefill→decode slab
    handoff, ``adopt_prefix``, recovery replay. Pure numpy/zlib over
    host copies of the device pages; nothing here ever enters a
    compiled program, so graphlint/determlint/perf baselines are
    untouched by construction.

    Coverage is deliberately registry-only: a slot's PRIVATE append
    pages mutate every decode step and could only be covered by
    per-step digests — exactly the cost the "verify at transfer, never
    per step" contract forbids. Registered prefix pages are immutable
    once filled (CoW guarantees divergent appends land on fresh
    pages), so a digest recorded at fill time stays valid for the
    page's whole tracked life.

    The digest is a ``(kv_crc, mirror_crc)`` pair; ``mirror_crc`` is 0
    for mirror-less caches. Cross-cache comparison (handoff source vs
    destination) must compare ``kv_crc`` alone:
    :func:`paged_transfer_pages` re-quantizes the destination mirror
    from the adopted K and seeds unfilled tail rows with the eps
    scale, so mirror bytes legitimately differ across caches."""

    def __init__(self):
        self._crc = {}              # page -> (kv_crc, mirror_crc)

    def __contains__(self, page):
        return int(page) in self._crc

    def __len__(self):
        return len(self._crc)

    def pages(self):
        """Tracked pages, sorted (deterministic iteration order)."""
        return sorted(self._crc)

    @staticmethod
    def digest(cache, page):
        """Compute ``page``'s ``(kv_crc, mirror_crc)`` from the live
        cache buffers. One host transfer per pool slice; called only
        at transfer boundaries."""
        page = int(page)
        crc = zlib.crc32(np.asarray(cache.k_pool[page]).tobytes())
        crc = zlib.crc32(np.asarray(cache.v_pool[page]).tobytes(), crc)
        mirror = 0
        if cache.k_q_pool is not None:
            mirror = zlib.crc32(
                np.asarray(cache.k_q_pool[page]).tobytes())
            mirror = zlib.crc32(
                np.asarray(cache.k_scale_pool[page]).tobytes(), mirror)
        return crc, mirror

    def record(self, cache, pages):
        """(Re)digest ``pages`` from ``cache`` and remember the result
        — the page's content is declared canonical as of now."""
        for page in pages:
            self._crc[int(page)] = self.digest(cache, page)

    def record_at(self, cache, page, row=None):
        """Record ``page``'s digest computed from pool row ``row``
        (default: the page itself). The sequence-sharded engines key
        their per-shard tables by SHARD-LOCAL page id while the page's
        bytes live at its stacked pool row — this is the one seam
        where the two id spaces meet."""
        self._crc[int(page)] = self.digest(
            cache, page if row is None else row)

    def get(self, page):
        return self._crc.get(int(page))

    def drop(self, pages):
        """Forget digests for pages leaving the tracked set (prefix
        unregistration / pool zeroing)."""
        for page in pages:
            self._crc.pop(int(page), None)

    def verify(self, cache, pages=None):
        """Re-digest ``pages`` (default: every tracked page) against
        the recorded values. Returns the sorted list of mismatching
        pages — empty means clean. Unrecorded pages are skipped, not
        failures (private append pages are out of coverage)."""
        if pages is None:
            pages = self.pages()
        bad = []
        for page in pages:
            page = int(page)
            want = self._crc.get(page)
            if want is not None and self.digest(cache, page) != want:
                bad.append(page)
        return sorted(bad)


class ShardedPageTable:
    """Host-side allocator for a SEQUENCE-SHARDED paged cache: one
    stream's page table split across the mesh's ``seq`` axis so its KV
    capacity sums over ``n_shards`` pools instead of capping at one
    chip's HBM (ROADMAP "cluster-scale long context"). Each mesh member
    owns a CONTIGUOUS run of the logical page ordinals —
    ``ordinals_per_shard = ceil(pages_per_slot / n_shards)``, shard
    ``s`` owning ``[s·ops, min((s+1)·ops, pages_per_slot))`` — matching
    the contiguously sequence-sharded prefill pool, so a long prompt's
    handoff is shard-local by construction.

    Composition, not reimplementation: ``n_shards`` ordinary
    :class:`PagePool` instances (one per mesh member, each sized
    ``pages_per_shard``) SHARING one canonical ``lengths`` vector (the
    fill is a global property; every shard advances it identically).
    Each sub-pool's ``table`` keeps the FULL logical width with ``−1``
    at every ordinal another shard owns — exactly the local view
    :func:`decode_step`'s paged ring-decode step wants (position math
    stays global; non-owned appends drop through the ``−1``; non-owned
    columns are masked/run-gated and the flash ``(num, m, l)`` merge
    reassembles exact full attention). A sub-pool's ``counts[slot]`` is
    the global high-watermark ordinal + 1 as seen by that shard — safe
    for :meth:`PagePool.prepare_append`'s routing because fill advances
    ordinal-sequentially and every mapped ordinal below a shard's
    watermark inside its owned range holds a real page.

    Methods that touch more than one sub-pool (:meth:`reserve_rows`
    with its cross-shard rollback, :meth:`release`, :meth:`truncate`,
    :meth:`attach`) are implemented here; single-ordinal operations
    route to the owning sub-pool. Returned page ids are LOCAL to their
    shard — every (page, shard) crossing is explicit in the signatures,
    so the engine cannot confuse a shard-local id for a global one."""

    def __init__(self, n_shards, pages_per_shard, page_size, slots,
                 pages_per_slot):
        if n_shards < 2:
            raise ValueError(f'need n_shards >= 2 (a single shard is a '
                             f'plain PagePool), got {n_shards}')
        self.n_shards = n_shards
        self.pages_per_shard = pages_per_shard
        self.page_size = page_size
        self.slots = slots
        self.pages_per_slot = pages_per_slot
        self.ordinals_per_shard = -(-pages_per_slot // n_shards)
        self.shards = [PagePool(pages_per_shard, page_size, slots,
                                pages_per_slot)
                       for _ in range(n_shards)]
        # ONE canonical fill vector: rebind every sub-pool's lengths to
        # the same array object so `pool.lengths[slot] += 1` through
        # any alias (including the engine's) advances all shards.
        self.lengths = self.shards[0].lengths
        for p in self.shards[1:]:
            p.lengths = self.lengths

    # -- geometry -------------------------------------------------------
    def owner(self, ordinal):
        """Mesh member owning logical page ``ordinal``."""
        return min(ordinal // self.ordinals_per_shard,
                   self.n_shards - 1)

    def owned_range(self, shard):
        """``(lo, hi)``: the contiguous ordinal run shard ``shard``
        owns (the last shard absorbs the ceil-split remainder)."""
        lo = min(self.pages_per_slot, shard * self.ordinals_per_shard)
        hi = (self.pages_per_slot if shard == self.n_shards - 1
              else min(self.pages_per_slot,
                       (shard + 1) * self.ordinals_per_shard))
        return lo, hi

    def owner_vector(self):
        """``(pages_per_slot,) int32``: ordinal → owning shard."""
        return np.asarray([self.owner(o)
                           for o in range(self.pages_per_slot)],
                          np.int32)

    # The stacked-pool row layout: each shard contributes
    # ``pages_per_shard`` allocatable rows PLUS its own sink row, so a
    # shard's block in the stacked device pool is
    # ``pages_per_shard + 1`` rows wide. These three helpers are the
    # ONLY place that stride may appear — host code elsewhere goes
    # through them (flowlint's shard-ownership rule enforces it).
    def gpage(self, shard, page):
        """Shard-local page id → GLOBAL stacked-pool row id."""
        return shard * (self.pages_per_shard + 1) + page

    def gsplit(self, gpage):
        """GLOBAL stacked-pool row id → ``(shard, local page)``."""
        stride = self.pages_per_shard + 1
        return int(gpage) // stride, int(gpage) % stride

    def page_shard(self, gpage):
        """Mesh member owning GLOBAL stacked-pool row id ``gpage``."""
        return int(gpage) // (self.pages_per_shard + 1)

    # -- aggregate introspection ---------------------------------------
    @property
    def pages(self):
        """Allocatable pages summed across shards — the capacity the
        tentpole scales linearly with mesh size."""
        return self.n_shards * self.pages_per_shard

    @property
    def free_pages(self):
        return sum(p.free_pages for p in self.shards)

    @property
    def free_pages_by_shard(self):
        return [p.free_pages for p in self.shards]

    @property
    def used_pages(self):
        return sum(p.used_pages for p in self.shards)

    @property
    def shared_pages(self):
        return sum(p.shared_pages for p in self.shards)

    @property
    def quarantined(self):
        """Withdrawn pages as ``(shard, local_page)`` pairs — local ids
        only mean something next to their shard."""
        return {(s, page) for s, p in enumerate(self.shards)
                for page in p.quarantined}

    @property
    def dirty(self):
        return any(p.dirty for p in self.shards)

    @dirty.setter
    def dirty(self, value):
        for p in self.shards:
            p.dirty = bool(value)

    def pages_for_rows(self, rows):
        return -(-rows // self.page_size)

    def slot_pages(self, slot):
        """Pages actually mapped for ``slot`` across all shards."""
        return sum(int(np.sum(p.table[slot] >= 0)) for p in self.shards)

    def covered_rows(self, slot):
        """Longest ``[0, r)`` row prefix of ``slot`` whose pages are
        all mapped (chunked prefill's no-fail-mid-prompt check)."""
        o = 0
        while (o < self.pages_per_slot
               and int(self.shards[self.owner(o)].table[slot, o]) >= 0):
            o += 1
        return o * self.page_size

    def local_tables(self):
        """``(n_shards, slots, pages_per_slot) int32`` stacked local
        views — the device mirror the sharded decode program reads
        (axis 0 sharded over the ``seq`` mesh axis)."""
        return np.stack([p.table for p in self.shards]).astype(np.int32)

    # -- allocation -----------------------------------------------------
    def prepare_append(self, slot):
        """:meth:`PagePool.prepare_append` routed to the shard owning
        the slot's next append ordinal. Returns ``(status, shard, src,
        dst)`` — ``shard`` names the pool the status is about (−1 for
        'full'), so exhaustion reports can say WHICH shard's range is
        out of pages while the others still have headroom."""
        pos = int(self.lengths[slot])
        pi = pos // self.page_size
        if pi >= self.pages_per_slot:
            return ('full', -1, -1, -1)
        s = self.owner(pi)
        st, src, dst = self.shards[s].prepare_append(slot)
        return (st, s, src, dst)

    def reserve_rows(self, slot, rows):
        """Cross-shard :meth:`PagePool.reserve_rows`: reserve every
        page covering rows ``[length, length + rows)`` wherever they
        are owned. Returns ``(ok, copies)`` with ``copies`` a list of
        ``(shard, src, dst)`` device copies owed. On ANY shard's
        exhaustion nothing is changed anywhere — the rollback spans
        shards (a shed admission must not leak pages into pool A
        because pool B was full)."""
        start = int(self.lengths[slot])
        end = start + rows
        if end > self.pages_per_slot * self.page_size:
            return False, []
        counts0 = [int(p.counts[slot]) for p in self.shards]
        undo = []                     # (shard, pi, prev, was_cow)
        copies = []
        for pi in range(start // self.page_size,
                        -(-end // self.page_size)):
            s = self.owner(pi)
            pool = self.shards[s]
            if pi >= int(pool.counts[slot]) \
                    or int(pool.table[slot, pi]) < 0:
                page = pool.alloc()
                if page is None:
                    self._undo_reserve(slot, undo, counts0)
                    return False, []
                undo.append((s, pi, -1, False))
                pool.table[slot, pi] = page
                pool.counts[slot] = max(int(pool.counts[slot]), pi + 1)
                pool.dirty = True
            else:
                page = int(pool.table[slot, pi])
                if pool.refcount[page] > 1:
                    dup = pool.alloc()
                    if dup is None:
                        self._undo_reserve(slot, undo, counts0)
                        return False, []
                    undo.append((s, pi, page, True))
                    pool.refcount[page] -= 1
                    pool.table[slot, pi] = dup
                    copies.append((s, page, dup))
                    pool.dirty = True
        return True, copies

    def _undo_reserve(self, slot, undo, counts0):
        for s, pi, prev, was_cow in reversed(undo):
            pool = self.shards[s]
            page = int(pool.table[slot, pi])
            pool.refcount[page] = 0
            pool._free.append(page)
            pool.table[slot, pi] = prev
            if was_cow:
                pool.refcount[prev] += 1
        for s, c in enumerate(counts0):
            self.shards[s].counts[slot] = c

    def release(self, slot):
        """Evict ``slot`` everywhere. Returns ``{shard: [pages]}`` of
        LOCAL pages that hit refcount 0 — the caller zeroes each
        shard's list in that shard's pool (the alloc invariant, per
        shard)."""
        freed = {}
        for s, pool in enumerate(self.shards):
            for pi in range(int(pool.counts[slot])):
                page = int(pool.table[slot, pi])
                if page >= 0 and pool._unref(page):
                    freed.setdefault(s, []).append(page)
            pool.table[slot, :] = -1
            pool.counts[slot] = 0
            pool.dirty = True
        self.lengths[slot] = 0
        return freed

    def truncate(self, slot, new_length):
        """Cross-shard :meth:`PagePool.truncate` — NOT a per-shard
        delegation: the shared ``lengths`` vector would make the first
        sub-pool's early-out hide every other shard's tail pages.
        Returns ``{shard: [freed local pages]}``."""
        if new_length >= int(self.lengths[slot]):
            return {}
        keep = self.pages_for_rows(int(new_length))
        freed = {}
        for s, pool in enumerate(self.shards):
            for pi in range(keep, int(pool.counts[slot])):
                page = int(pool.table[slot, pi])
                if page >= 0:
                    if pool._unref(page):
                        freed.setdefault(s, []).append(page)
                    pool.table[slot, pi] = -1
                    pool.dirty = True
            pool.counts[slot] = min(int(pool.counts[slot]), keep)
        self.lengths[slot] = new_length
        return freed

    # -- sharing --------------------------------------------------------
    def attach(self, slot, ordinal_pages, length):
        """Point an EMPTY slot at registry pages laid out by ordinal:
        ``ordinal_pages (pages_per_slot,) int`` holds, at each ordinal
        the prefix covers, the LOCAL page id in the OWNING shard's pool
        (−1 elsewhere). Full pages are shared read-only (refcount++ on
        their shard); a partial tail page gets a private copy on the
        tail ordinal's owner. Returns ``(ok, tail_shard, tail_src,
        tail_dst)`` — −1s when the prefix ends on a page boundary; on
        tail-page exhaustion nothing is changed."""
        if self.lengths[slot] or any(int(p.counts[slot])
                                     for p in self.shards):
            # Same internal-state shape as PagePool.attach above.
            raise RuntimeError(f'attach needs an empty slot, slot '
                               f'{slot} is in use')
        full = length // self.page_size
        rem = length % self.page_size
        tail_shard = tail_src = tail_dst = -1
        if rem:
            tail_shard = self.owner(full)
            tail_dst = self.shards[tail_shard].alloc()
            if tail_dst is None:
                return False, -1, -1, -1
            tail_src = int(ordinal_pages[full])
        for o in range(full):
            s = self.owner(o)
            pool = self.shards[s]
            pg = int(ordinal_pages[o])
            pool.table[slot, o] = pg
            pool.refcount[pg] += 1
            pool.counts[slot] = o + 1
            pool.dirty = True
        if rem:
            pool = self.shards[tail_shard]
            pool.table[slot, full] = tail_dst
            pool.counts[slot] = full + 1
            pool.dirty = True
        self.lengths[slot] = length
        return True, tail_shard, tail_src, tail_dst

    def release_pages_on(self, shard, pages):
        """Per-shard :meth:`PagePool.release_pages` (registry release);
        returns the LOCAL pages owed a zero in that shard's pool."""
        return self.shards[shard].release_pages(pages)

    def quarantine(self, shard, pages):
        """Withdraw LOCAL ``pages`` of ``shard`` from circulation;
        returns the pages newly quarantined on that shard."""
        return self.shards[shard].quarantine(pages)


def init_sharded_paged_cache(n_shards, slots, kv_heads, t_max, head_dim,
                             *, pages_per_shard, page_size,
                             v_head_dim=None, dtype=jnp.bfloat16):
    """Zero STACKED sharded paged cache — the device twin of
    :class:`ShardedPageTable`. Pools stack the per-shard
    ``(pages_per_shard + 1, H_kv, page_size, d·)`` local pools (each
    with its OWN sink row) along axis 0, page tables stack the local
    views along a leading ``(n_shards,)`` axis, and the fill vector is
    replicated. Shard everything but ``length`` over the ``seq`` mesh
    axis (``P(SEQ_AXIS)`` on axis 0) and each ``shard_map`` member sees
    a perfectly ordinary local :class:`PagedDecodeCache` — the whole
    point of the layout: the local decode step, append drop semantics
    and sink-redirect contracts apply verbatim per shard. Shard ``s``'s
    local page ``p`` lives at stacked row ``s·(pages_per_shard+1)+p``
    (the engine's host-side transfer/zero bookkeeping uses this)."""
    v_head_dim = v_head_dim or head_dim
    if page_size < 1 or t_max % page_size:
        raise ValueError(f'page_size {page_size} must divide t_max '
                         f'{t_max}')
    if n_shards < 2 or pages_per_shard < 1:
        raise ValueError(f'need n_shards >= 2 and pages_per_shard >= 1, '
                         f'got {n_shards}/{pages_per_shard}')
    rows = n_shards * (pages_per_shard + 1)
    return PagedDecodeCache(
        k_pool=jnp.zeros((rows, kv_heads, page_size, head_dim), dtype),
        v_pool=jnp.zeros((rows, kv_heads, page_size, v_head_dim),
                         dtype),
        page_table=jnp.full((n_shards, slots, t_max // page_size), -1,
                            jnp.int32),
        length=jnp.zeros((slots,), jnp.int32))


def _paged_mirror_fixup(cache: PagedDecodeCache, k_new, ap, nvec):
    """Quantize this step's appended rows into the mirror pools — THE
    mirror-maintenance body: :func:`paged_append_kv_slots` calls it on
    every mirror-carrying append, and :func:`decode_step`'s kernel
    path calls it post hoc when a non-int8 step left the mirror to
    XLA (one definition, so the append rule and the fixup rule cannot
    diverge). Per-row quantization of the CACHE-dtype value, scattered
    through the page table with the usual drop-mode indices. ``ap
    (B,)`` is each slot's first append column (−1 = none), ``nvec
    (B,)`` the rows it appended; returns the updated
    ``(k_q_pool, k_scale_pool)``."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        _quantize_rows,
    )
    b = cache.page_table.shape[0]
    h_kv, d = cache.k_pool.shape[1], cache.k_pool.shape[-1]
    n = k_new.shape[-2]
    ki, sk = _quantize_rows(k_new.astype(cache.k_pool.dtype), b * h_kv,
                            n, d)
    pg, rw = _paged_scatter_indices(cache, ap, nvec, n)

    def write(pool, new):
        vals = jnp.moveaxis(new.astype(pool.dtype), 2, 1)
        return pool.at[pg, :, rw, :].set(vals, mode='drop')

    return (write(cache.k_q_pool, ki.reshape(b, h_kv, n, d)),
            write(cache.k_scale_pool, sk.reshape(b, h_kv, n, 1)))


def decode_kernel_eligible(cache, n=1, segment_ids=None, qk_quant=None,
                           explain=False, n_shards=1, shard=None):
    """Can :func:`decode_step` take the fused Pallas kernel for this
    call? The kernel covers the serving hot path — ``1 <= n <= K split``
    new rows per slot per step (n = 1 classic decode; n > 1 the fused
    VERIFY-k step of speculative decoding, whose rows then span at most
    two cache blocks), causal/window/ALiBi/GQA masking, and the int8
    mirror at n = 1 on BOTH layouts (the slab's ``k_q``/``k_scale``
    buffers and the page pool's ``k_q_pool``/``k_scale_pool`` —
    quantized decode rides the kernel at paged concurrency) — and
    leaves the long tail (packed segments, quantized verify-k,
    mirror-less int8, K splits that don't divide ``t_max``, verify
    widths past the split) to the XLA formulation. Paged caches are
    otherwise kernel-native (the page size IS the K split, so
    ``n <= page_size``), with the page size capped by the same VMEM
    budget the slab split honors (an oversized page would
    double-buffer a K+V stream past it).

    ``explain=True`` returns ``(eligible, reason)`` — ``reason`` is
    ``None`` when eligible, else a string naming the exact gap (the
    string ``impl='kernel'``'s ValueError and ``impl='auto'``'s
    fallback decision rest on), so a silent XLA fallback is one probe
    away from an explanation.

    MESH GEOMETRY: ``n_shards > 1`` describes a sequence-sharded step
    (``cache`` is then ONE shard's local view — a shard of the sharded
    page table, or one slab of the slab-sharded cache) and ``shard``
    optionally names which mesh member is being probed. With
    ``explain=True`` every verdict then carries the geometry — shard
    count and the member's owned page-ordinal/column range — so an
    eligible sharded probe returns ``(True, '<geometry>')`` rather
    than ``(True, None)``, and an ineligible one explains the gap PER
    SHARD (``'<geometry> — <reason>'``). Kernel-specific sharded
    restriction: the flash-decoding merge carries one query row per
    shard, so ``n != 1`` is ineligible under sharding."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        _BLOCK_K_CAP,
        decode_block_k,
    )

    geom = None
    if n_shards > 1:
        if isinstance(cache, PagedDecodeCache):
            pps = cache.pages_per_slot
            local = -(-pps // n_shards)
            if shard is None:
                own = (f'each of the {n_shards} shards owns a '
                       f'contiguous run of {local} of the {pps} '
                       f'logical page ordinals')
            else:
                lo = shard * local
                hi = min(pps, lo + local)
                own = (f'shard {shard}/{n_shards} owns logical page '
                       f'ordinals [{lo}, {hi}) of {pps}')
            geom = f'sequence-sharded page table: {own}'
        else:
            t_loc = cache.t_max
            if shard is None:
                own = (f'each of the {n_shards} shards owns a '
                       f'{t_loc}-column slab')
            else:
                own = (f'shard {shard}/{n_shards} owns columns '
                       f'[{shard * t_loc}, {(shard + 1) * t_loc})')
            geom = f'sequence-sharded slab: {own}'

    def verdict(reason):
        ok = reason is None
        if geom is not None:
            reason = geom if ok else f'{geom} — {reason}'
        return (ok, reason) if explain else ok

    if n_shards > 1 and n != 1:
        return verdict(f'the sharded kernel step is single-token (its '
                       f'flash-decoding merge carries one query row '
                       f'per shard), got n={n} — the XLA formulation '
                       f'covers sharded verify-k')
    if n < 1:
        return verdict(f'needs at least one query row (n={n})')
    if segment_ids is not None:
        return verdict('packed segment_ids are masked by the XLA '
                       'formulation only')
    if qk_quant == 'int8' and n != 1:
        return verdict(f'quantized verify-k (n={n} > 1) is XLA-only — '
                       'the kernel appends the int8 mirror '
                       'single-token')
    if isinstance(cache, PagedDecodeCache):
        if qk_quant == 'int8' and cache.k_q_pool is None:
            return verdict(
                'this paged cache carries no int8 K mirror — allocate '
                "the mirror pools with init_paged_cache("
                "qk_quant='int8') so quantized decode can ride the "
                'kernel on the page pool')
        if cache.page_size > _BLOCK_K_CAP:
            return verdict(
                f'page_size {cache.page_size} exceeds the K-split '
                f'VMEM cap {_BLOCK_K_CAP} — the page is the K split '
                f'and an oversized page double-buffers past the '
                f'budget')
        if n > cache.page_size:
            return verdict(
                f'verify-k width {n} exceeds the page size '
                f'{cache.page_size} — k rows must span at most two '
                f'pages')
        return verdict(None)
    if isinstance(cache, RingCache):
        if n != 1 or qk_quant is not None:
            return verdict(f'the ring kernel step is single-token and '
                           f'unquantized, got n={n}, qk_quant='
                           f'{qk_quant!r}')
    elif qk_quant == 'int8' and cache.k_q is None:
        return verdict('this slab cache carries no int8 K mirror — '
                       "allocate it with init_cache(qk_quant='int8')")
    bk = decode_block_k(cache.t_max)
    if bk is None:
        return verdict(f'no usable K split divides t_max='
                       f'{cache.t_max} (serving caches are powers of '
                       f'two)')
    if n > bk:
        return verdict(f'verify-k width {n} exceeds the K split {bk} '
                       f'— k rows must span at most two blocks')
    return verdict(None)


def _axis_env_size(axis_name):
    """Static size of ``axis_name`` when tracing inside its shard_map
    (a host int, no traced value involved); 2 — "sharded, count
    unknown" — when the axis is unbound (a direct host-side probe
    outside any mesh: every sharded gate keys on ``n_shards > 1``, not
    the count)."""
    if axis_name is None:
        return 1
    try:
        return lax.axis_size(axis_name)
    except NameError:       # unbound axis: probed outside the mesh
        return 2


def _take_layer(x, layer):
    """Layer ``layer`` of a layer-stacked array (``layer=None``: ``x``
    is one layer's already)."""
    if layer is None:
        return x
    return lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)


def _put_layer(stacked, x, layer):
    """``stacked`` with layer ``layer`` replaced by ``x``
    (``layer=None``: ``x`` itself)."""
    if layer is None:
        return x
    return lax.dynamic_update_index_in_dim(stacked, x, layer, 0)


_IMPL_TRACES = TraceSinks()


def decode_impl_traces():
    """Collect what :func:`decode_step` resolves ``impl`` to while the
    block runs: one dict ``{'requested', 'resolved', 'reason',
    'cache', 'step', 'tail', 'token_bytes'}`` per TRACE (= per compiled
    step; ``reason``
    names why ``'auto'`` fell back to ``'xla'``, else None; ``cache`` is
    ``'stacked'`` where the step addressed a layer-stacked buffer by
    ``layer`` — a scanned stack's in-place loop — and ``'layer'`` where
    it was handed one layer's buffers, so a return to slicing the stack
    per layer shows here, ``'ring'`` for a window layer's
    :class:`RingCache`, whose kernel step is the ring mode, and
    ``'packed'`` for a :class:`PackedCache`, keys and values in one
    unpadded row; ``step`` is
    the kernel's grid step,
    ``{'heads', 'block_k', 'bytes', 'heads_a_pass'}`` — KV heads and
    cache rows of one step, the cache bytes it streams and the heads one
    pass of the body scores (2 where the packed mode's pair pass runs, 1
    elsewhere), as ``ops.pallas_decode.decode_geometry`` chose them from
    the call's shapes — or None off the kernel; ``tail`` is the rows the
    kernel
    moves of the split that holds a slot's last valid column where no
    more than those are filled of it, by the same function — None where
    that split is always moved whole, and off the kernel; ``token_bytes``
    the bytes of keys and values one token of one KV head costs the
    step's stream — lane padding counted, so 512 for two bfloat16
    buffers of 64-wide heads and 256 for the packed slab — None off the
    kernel). ``'auto'``
    takes the XLA formulation off-TPU and wherever the kernel does not
    cover the call, so a smoke or benchmark run wraps the compile of its
    step in this and asserts the path the program holds instead of
    trusting it::

        with decode_impl_traces() as traces:
            step.lower(*args).compile()
        assert {t['resolved'] for t in traces} == {'kernel'}
    """
    return _IMPL_TRACES.open()


_SPARSE_TRACES = TraceSinks()


def sparse_decode_traces():
    """Collect the form of every block-sparse layer's decode step
    (``models/sparse.sparse_step``) while the block runs: one dict
    ``{'impl', 'picks', 'topk', 'group', 'select'}`` per TRACE: ``impl``
    ``'kernel'`` (the Pallas program ``sparse_decode``) or ``'xla'`` (the
    gathered softmax), ``picks`` the entries of a pick list, ``topk`` how
    many a step above ``dense_len`` reads, ``group`` the picks scored at
    a time, ``select`` ``'threshold'`` (``sparse_pick``) or ``'sort'``::

        with sparse_decode_traces() as traces:
            step.lower(*args).compile()
        assert [t['impl'] for t in traces] == ['kernel']
    """
    return _SPARSE_TRACES.open()


def note_sparse_decode(trace):
    _SPARSE_TRACES.note(trace)


def _kernel_geometry(q, cache, qk_quant):
    """The grid step the fused kernel takes for this call and its tail —
    the trace fields ``'step'`` and ``'tail'`` — asked of the kernel's own
    ``ops.pallas_decode.flash_decode_geometry`` with the operands
    :func:`decode_step` hands ``flash_decode``."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode_geometry,
    )
    if isinstance(cache, PagedDecodeCache):
        geom = flash_decode_geometry(
            q, cache.k_pool, cache.v_pool, page_table=cache.page_table,
            qk_quant=qk_quant)
    elif isinstance(cache, PackedCache):
        geom = flash_decode_geometry(q, cache.kv)
    else:
        geom = flash_decode_geometry(q, cache.k, cache.v,
                                     qk_quant=qk_quant,
                                     ring=isinstance(cache, RingCache))
    return geom


def _resolve_decode_impl(impl, cache, n, segment_ids, qk_quant,
                         axis_name=None, stacked=False, q=None):
    # Thread the mesh geometry into EVERY eligibility probe so the
    # explain string names every gate this resolver actually tests —
    # before this, a forced-kernel sharded verify-k passed the
    # (unsharded) probe here and only blew up at the late kernel-path
    # check, with no geometry in the error.
    n_shards = _axis_env_size(axis_name)
    if impl not in (None, 'auto', 'kernel', 'xla'):
        raise ValueError(f"decode impl must be None/'auto'/'kernel'/"
                         f"'xla', got {impl!r}")
    resolved, reason = impl, None
    if impl != 'xla':
        ok, why = decode_kernel_eligible(cache, n, segment_ids, qk_quant,
                                         explain=True, n_shards=n_shards)
        if impl == 'kernel':
            if not ok:
                raise ValueError(
                    f'decode_step: the fused kernel does not cover this '
                    f"call — {why} — use impl='auto' to fall back to "
                    f'the XLA formulation')
        elif not ok:
            # Sharded verify-k, packed segments, … : 'auto' must fall
            # back rather than resolve to a path that raises.
            resolved, reason = 'xla', why
        elif jax.default_backend() != 'tpu':
            # Mirror the flash-kernel gating: the kernel is the TPU
            # path; elsewhere it would run interpreted (covered by
            # tests that force impl='kernel').
            resolved = 'xla'
            reason = f'backend is {jax.default_backend()}, not tpu'
        else:
            resolved = 'kernel'
    # The step is reported where the caller says what it scores with:
    # decode_step does; a bare probe of the resolution has no queries.
    geom = None
    if resolved == 'kernel' and q is not None and _IMPL_TRACES:
        geom = _kernel_geometry(q, cache, qk_quant)
    kind = ('ring' if isinstance(cache, RingCache)
            else 'packed' if isinstance(cache, PackedCache)
            else 'stacked' if stacked else 'layer')
    record_decode_impl(impl, resolved, reason, kind, geom)
    return resolved


def record_decode_impl(requested, resolved, reason, cache, geom=None):
    """Tell the open :func:`decode_impl_traces` blocks what one traced
    decode step resolved to (this module's, and the latent cache's in
    ``models/latent.py``); ``geom`` the kernel's ``DecodeGeometry``."""
    _IMPL_TRACES.note({'requested': requested or 'auto',
                       'resolved': resolved, 'reason': reason,
                       'cache': cache,
                       'step': geom.step() if geom else None,
                       'tail': geom.tail if geom else None,
                       'token_bytes': (geom.bytes // (
                           geom.heads * geom.block_k) if geom else None)})


def decode_step(q, cache: DecodeCache, k_new, v_new, *, slot_mask=None,
                counts=None, scale=None, window=None, alibi_slopes=None,
                segment_ids=None, seg_q=None, qk_quant=None,
                axis_name=None, impl=None, interpret=None, layer=None):
    """One fused decode step: append ``k_new``/``v_new`` to the cache
    AND attend ``q`` against the result — ``append_kv*`` +
    :func:`decode_attention` as ONE call, so the kernel path
    (``impl='kernel'``, or ``'auto'`` on TPU) runs it as a single
    Pallas program with the cache appended IN PLACE via
    ``input_output_aliases`` (no scan-carry or donated-copy round trip
    of the buffers; see ``ops/pallas_decode.py``). ``impl='xla'`` (and
    ``'auto'`` off-TPU, or
    whenever the kernel doesn't cover the call —
    :func:`decode_kernel_eligible`) computes the identical math through
    the existing portable ops.

    ``q (B, H, n, d)``: n = 1 is the classic per-token step; n > 1 is
    a VERIFY-k step (speculative decoding's fused verify): the n new
    rows land at consecutive positions and query row ``j`` attends the
    prefix plus appended rows ``<= j`` — bit-identical per row to n
    sequential single-token steps. The kernel covers
    ``n <= the K split`` (:func:`decode_kernel_eligible`); wider calls
    take the XLA formulation.

    Per-slot caches (:func:`init_slot_cache`) take ``slot_mask``
    exactly as :func:`append_kv_slots` does (masked slots append
    nothing and their queries attend their un-advanced prefix) and —
    verify-k — ``counts (B,) int32``: per slot, how many of the n rows
    are REAL (a mixed spec/non-spec batch rides one program; a slot
    with ``counts[i] = c`` appends rows ``0..c-1`` and its query rows
    ``>= c`` produce don't-care outputs the caller discards — they
    attend at their nominal positions over never-written (zero)
    columns). ``axis_name`` runs the sequence-sharded step (inside a
    ``shard_map``): a SLAB cache is sharded on its ``t_max`` axis
    (scalar global length), while a PAGED cache runs the paged
    ring-decode step — each shard holds a local pool plus the LOCAL
    view of the sequence-sharded page table (logical width intact,
    −1 at every ordinal another shard owns; see
    :class:`ShardedPageTable`), scores only its own pages, drops
    non-owned appends through the table's −1, and the shards merge by
    the flash-decoding pmax/psum rule on both impls (kernel partials
    or masked XLA partials; n == 1 only on the kernel). Overflow
    follows the append contracts: concrete lengths raise eagerly,
    traced lengths write nothing while the length still advances.

    ``layer`` (int32 scalar, may be traced): ``cache`` is a
    LAYER-STACKED :class:`DecodeCache` (every field with a leading
    layer axis, as ``TransformerStack.make_decode_caches`` builds for
    a scanned stack) and the step is layer ``layer``'s: the kernel
    path addresses that layer of the stacked buffers in place
    (``flash_decode(layer=)``), so a layer loop that CARRIES the stack
    moves no cache bytes beyond the appended block; the XLA
    formulation takes the layer out and puts it back
    (``dynamic_index_in_dim`` / ``dynamic_update_index_in_dim``). The
    returned cache is the stack with layer ``layer`` updated.
    Returns ``(cache, out (B, H, n, d_v))``.
    """
    n = q.shape[-2]
    if isinstance(cache, RingCache):
        given = dict(slot_mask=slot_mask, counts=counts,
                     alibi_slopes=alibi_slopes, segment_ids=segment_ids,
                     seg_q=seg_q, qk_quant=qk_quant, axis_name=axis_name,
                     layer=layer)
        extra = sorted(k for k, v in given.items() if v is not None)
        if extra:
            raise ValueError(f'decode_step: a RingCache step takes scale, '
                             f'window and impl alone, got {extra}')
        return _ring_step(q, cache, k_new, v_new, scale=scale,
                          window=window, impl=impl, interpret=interpret)
    if isinstance(cache, PackedCache):
        given = dict(slot_mask=slot_mask, counts=counts,
                     segment_ids=segment_ids, seg_q=seg_q,
                     qk_quant=qk_quant, axis_name=axis_name, layer=layer)
        extra = sorted(k for k, v in given.items() if v is not None)
        if extra:
            raise ValueError(f'decode_step: a PackedCache step takes '
                             f'scale, window, alibi_slopes and impl '
                             f'alone, got {extra}')
        return _packed_step(q, cache, k_new, v_new, scale=scale,
                            window=window, alibi_slopes=alibi_slopes,
                            impl=impl, interpret=interpret)
    paged = isinstance(cache, PagedDecodeCache)
    stack = None
    if layer is not None:
        if paged:
            raise ValueError('decode_step: layer addresses a '
                             'layer-stacked DecodeCache; paged caches '
                             'have no layer axis')
        # The layer's own clock from here on; the buffers stay stacked
        # until a path needs one layer's.
        stack = cache
        cache = cache._replace(length=_take_layer(cache.length, layer))
    impl = _resolve_decode_impl(impl, cache, n, segment_ids, qk_quant,
                                axis_name=axis_name,
                                stacked=stack is not None, q=q)
    per_slot = cache.length.ndim == 1
    if per_slot and axis_name is not None and not paged:
        raise ValueError(
            'per-slot lengths (init_slot_cache) are a local serving '
            'construct; sequence-sharded decode uses the scalar global '
            'length (or the sequence-sharded PAGE TABLE — a paged '
            'cache whose table holds only this shard\'s ordinals)')
    if slot_mask is not None and not per_slot:
        raise ValueError('slot_mask needs a per-slot cache '
                         '(init_slot_cache); scalar-length caches share '
                         'one sequence clock')
    if counts is not None and not per_slot:
        raise ValueError('counts needs a per-slot cache '
                         '(init_slot_cache); scalar-length caches '
                         'append all n rows — slice k_new/v_new '
                         'instead')
    if counts is not None and axis_name is not None:
        raise ValueError('per-slot counts are a local serving '
                         'construct; the sharded step appends whole '
                         'rows')

    if impl == 'xla':
        if stack is not None:
            cache = jax.tree.map(lambda x: _take_layer(x, layer), stack)
        before = cache.length
        if axis_name is not None and not paged:
            cache = append_kv_sharded(cache, k_new, v_new,
                                      axis_name=axis_name)
        elif per_slot:
            # Sharded page table included: the LOCAL table holds −1 at
            # every ordinal another shard owns, so the drop-mode
            # scatter discards non-owned appends for free — only the
            # owning shard's pool takes the row, all shards advance
            # the (replicated) lengths identically.
            cache = append_kv_slots(cache, k_new, v_new,
                                    slot_mask=slot_mask, counts=counts)
        else:
            cache = append_kv(cache, k_new, v_new)
        attend = cache
        col_valid = col_offset = None
        if paged:
            # Reference formulation: attend against the gathered slab
            # view — the IDENTICAL masked math as the slab path, so the
            # paged step matches it bit for bit (the contract the tests
            # pin). The gather is O(t_max) traffic, the same order as
            # the attention read itself; the kernel path avoids it.
            # Quantized decode gathers the mirror pools the same way,
            # so the int8 scoring streams the pool's append-time int8
            # rows — identical to the slab mirror's.
            gk, gv = paged_gather(cache)
            gkq = gks = None
            if qk_quant == 'int8' and cache.k_q_pool is not None:
                gkq, gks = paged_gather_mirror(cache)
            attend = DecodeCache(k=gk, v=gv, length=cache.length,
                                 k_q=gkq, k_scale=gks)
            if axis_name is not None:
                # Sequence-sharded page table: the gathered local view
                # keeps the table's LOGICAL width, so its columns sit
                # at GLOBAL positions already (no column offset) — but
                # ordinals owned by OTHER shards gathered the sink
                # page and lie BELOW the causal fill, where the
                # position mask alone would admit them; mask them out
                # explicitly and let the flash-decoding pmax/psum
                # merge reassemble exact full attention.
                col_offset = 0
                col_valid = jnp.repeat(cache.page_table >= 0,
                                       cache.page_size, axis=1)
        if per_slot and counts is not None:
            # Verify-k masking base: query row j of slot i sits at
            # position before[i] + j whatever the slot's REAL count —
            # decode_attention's pos_q = length − n + j convention
            # needs length = before + n per active slot (the tracked
            # length advanced only by the real count; padded rows then
            # attend never-written zero columns — don't-care outputs).
            active = (jnp.ones(before.shape, bool) if slot_mask is None
                      else jnp.asarray(slot_mask, bool))
            attend = attend._replace(
                length=jnp.where(active, before + n, before))
        out = decode_attention(
            q, attend, scale=scale, window=window,
            alibi_slopes=alibi_slopes, segment_ids=segment_ids,
            seg_q=seg_q, qk_quant=qk_quant, axis_name=axis_name,
            col_valid=col_valid, col_offset=col_offset)
        if stack is not None:
            cache = jax.tree.map(lambda s, x: _put_layer(s, x, layer),
                                 stack, cache)
        return cache, out

    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode,
    )
    b = q.shape[0]
    t_max = cache.t_max
    nn = None
    if axis_name is not None and n != 1:
        raise ValueError(
            'the sharded kernel step is single-token (its '
            'flash-decoding merge carries one row per shard) — '
            "use impl='xla' for sharded verify-k")
    if axis_name is not None and not paged:
        # Sharded slab: the append lands on the owning shard only; the
        # masking bound is the query's GLOBAL position localized to
        # this slab (negative = slab wholly in the future).
        p = cache.length
        col_off = lax.axis_index(axis_name) * t_max
        ok = p + 1 <= lax.psum(1, axis_name) * t_max
        owner = jnp.logical_and(
            jnp.logical_and(p >= col_off, p < col_off + t_max), ok)
        vt = jnp.broadcast_to(p - col_off, (b,))
        ap = jnp.broadcast_to(jnp.where(owner, p - col_off, -1), (b,))
        new_length = cache.length + 1
    else:
        # Local per-slot/scalar step — REUSED VERBATIM by the sharded
        # PAGE TABLE: positions are logical-global on every shard (the
        # local table keeps the logical width), so vt/ap need no
        # localization. A non-owning shard's ap still names the append
        # position, but its local table holds −1 at that ordinal, so
        # the kernel's run-gate skips scoring the append block and the
        # write-back parks on the sink — only the owner's pool takes
        # the row, and the flash merge below reassembles the rest.
        lengths = (cache.length if per_slot
                   else jnp.broadcast_to(cache.length, (b,)))
        active = (jnp.ones((b,), bool) if slot_mask is None
                  else jnp.asarray(slot_mask, bool))
        eff = (jnp.full((b,), n, jnp.int32) if counts is None
               else jnp.clip(jnp.asarray(counts, jnp.int32), 0, n))
        eff = jnp.where(active, eff, 0)
        # Eager overflow raise when the lengths are concrete — same
        # contract (and message shape) as the append ops.
        host_len = _concrete_lengths(lengths)
        host_eff = _concrete_lengths(eff)
        if host_len is not None and host_eff is not None:
            for i, (cur, add) in enumerate(zip(host_len, host_eff)):
                if add and cur + add > t_max:
                    where = f' on slot {i}' if per_slot else ''
                    raise ValueError(
                        f'KV-cache overflow{where}: length {cur} + '
                        f'{add} new position(s) exceeds t_max {t_max} '
                        f'— evict the slot (reset_slot) or stop the '
                        f'generation loop')
        fits = lengths + eff <= t_max
        writes = jnp.logical_and(jnp.logical_and(active, fits), eff > 0)
        ap = jnp.where(writes, lengths, -1)
        nn = jnp.where(writes, eff, 0)
        # Active queries' row 0 sits AT the first appended position
        # (row j at position + j); frozen slots' queries attend their
        # un-advanced prefix (decode_attention's semantics after a
        # slot-masked append). An overflowing append writes nothing
        # but the queries still mask at their advanced positions —
        # matching the traced-guard contract bit for bit.
        vt = jnp.where(active, lengths, lengths - n)
        new_length = (cache.length + eff if per_slot
                      else cache.length + n)

    if paged:
        # Same fused program, page-table-redirected DMA: the BlockSpec
        # index maps read the prefetched page-table row, aliasing still
        # writes only the append page(s) (ops/pallas_decode.py). With
        # qk_quant='int8' the mirror POOLS ride along: scoring streams
        # the 1-byte mirror pages through the same redirect, and the
        # append maintains them in place — quantized decode at paged
        # concurrency (eligibility guarantees the pools exist here).
        quant_kernel = qk_quant == 'int8'
        out, new_k, new_v, new_kq, new_ks = flash_decode(
            q, k_new, v_new, cache.k_pool, cache.v_pool, vt, ap,
            n_new=nn, page_table=cache.page_table,
            k_q=cache.k_q_pool if quant_kernel else None,
            k_scale=cache.k_scale_pool if quant_kernel else None,
            qk_quant=qk_quant, scale=scale,
            window=window, alibi_slopes=alibi_slopes,
            interpret=interpret, partials=axis_name is not None)
        if cache.k_q_pool is not None and new_kq is None:
            # Non-int8 step on a mirror-carrying pool: keep the mirror
            # exact by quantizing the appended rows the append-op way
            # (rare path — mirrors exist for int8 decoding). Sharded,
            # the non-owner's scatter drops through the local table's
            # −1 exactly like the data append.
            new_kq, new_ks = _paged_mirror_fixup(cache, k_new, ap, nn)
        elif cache.k_q_pool is None:
            new_kq = new_ks = None
        cache = PagedDecodeCache(k_pool=new_k, v_pool=new_v,
                                 page_table=cache.page_table,
                                 length=new_length,
                                 k_q_pool=new_kq,
                                 k_scale_pool=new_ks)
        if axis_name is not None:
            # Paged ring-decode merge: each shard scored only the
            # pages it owns; the (num, m, l) partials combine by the
            # flash-decoding rule into exact full attention.
            out = _flash_merge(out, axis_name, cache.v_pool.dtype)
        return cache, out

    res = flash_decode(
        q, k_new, v_new, cache.k, cache.v, vt, ap, n_new=nn, layer=layer,
        k_q=cache.k_q if qk_quant == 'int8' else None,
        k_scale=cache.k_scale if qk_quant == 'int8' else None,
        scale=scale, window=window, alibi_slopes=alibi_slopes,
        qk_quant=qk_quant, interpret=interpret,
        partials=axis_name is not None)
    out, new_k, new_v, new_kq, new_ks = res
    if cache.k_q is not None and new_kq is None:
        # A non-int8 step on a mirror-carrying cache still has to keep
        # the mirror exact — quantize the appended row(s) the append-op
        # way (rare path: mirrors exist for int8 decoding).
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        bb, h_kv, _, d = cache.k.shape[-4:]
        ki8, ks = _quantize_rows(k_new.astype(cache.k.dtype), bb * h_kv,
                                 n, d)
        nvec = nn if nn is not None else jnp.where(ap >= 0, n, 0)
        g = jnp.arange(t_max)[None, :]
        hit = jnp.logical_and(
            jnp.logical_and(g >= ap[:, None], ap[:, None] >= 0),
            g < ap[:, None] + nvec[:, None])[:, None, :, None]
        src = jnp.clip(g - ap[:, None], 0, n - 1)[:, None, :, None]
        new_kq = _put_layer(cache.k_q, jnp.where(
            hit, jnp.take_along_axis(ki8.reshape(bb, h_kv, n, d),
                                     src, axis=-2),
            _take_layer(cache.k_q, layer)), layer)
        new_ks = _put_layer(cache.k_scale, jnp.where(
            hit, jnp.take_along_axis(ks.reshape(bb, h_kv, n, 1),
                                     src, axis=-2),
            _take_layer(cache.k_scale, layer)), layer)
    elif cache.k_q is not None:
        pass                                    # kernel maintained it
    else:
        new_kq = new_ks = None
    if stack is not None:
        new_length = _put_layer(stack.length, new_length, layer)
    cache = DecodeCache(k=new_k, v=new_v, length=new_length,
                        k_q=new_kq, k_scale=new_ks)
    if axis_name is None:
        return cache, out
    return cache, _flash_merge(out, axis_name, cache.v.dtype)


def _ring_step(q, cache: RingCache, k_new, v_new, *, scale, window, impl,
               interpret):
    """:func:`decode_step` on a window layer's :class:`RingCache`: the
    new row goes to column ``length mod capacity`` and the query attends
    the ``min(length + 1, window)`` rows ending there. The kernel
    (``flash_decode(ring_span=)``) covers the single-token step on a
    capacity its K split divides (:func:`decode_kernel_eligible`); the
    XLA formulation (:func:`ring_append` + :func:`decode_attention`)
    everything, and is its oracle."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode,
    )
    cap = cache.capacity
    if window is None or window > cap:
        raise ValueError(f'a RingCache step needs its layer\'s window '
                         f'(<= capacity {cap}), got {window!r}')
    impl = _resolve_decode_impl(impl, cache, q.shape[-2], None, None, q=q)
    if impl == 'xla':
        cache = ring_append(cache, k_new, v_new)
        return cache, decode_attention(q, cache, scale=scale,
                                       window=window)
    b = q.shape[0]
    col = jnp.broadcast_to(jnp.mod(cache.length, cap), (b,))
    span = jnp.broadcast_to(jnp.minimum(cache.length + 1, window), (b,))
    out, new_k, new_v, _, _ = flash_decode(
        q, k_new, v_new, cache.k, cache.v, col, col, ring_span=span,
        scale=scale, interpret=interpret)
    return RingCache(k=new_k, v=new_v, length=cache.length + 1), out


def _packed_step(q, cache: PackedCache, k_new, v_new, *, scale, window,
                 alibi_slopes, impl, interpret):
    """:func:`decode_step` on a :class:`PackedCache`: the new rows go
    to both halves of rows ``length …`` and the queries attend the
    prefix and themselves. The kernel (``flash_decode``'s packed mode)
    streams the one buffer; the XLA formulation (:func:`packed_append`,
    then :func:`decode_attention` over the two halves) is its oracle and
    what ``'auto'`` takes off the TPU."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode,
    )
    b, _, n, d = q.shape
    if d != cache.head_dim:
        raise ValueError(f'queries {d} wide against a packed cache of '
                         f'{cache.head_dim}-wide heads')
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    wide = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    impl = _resolve_decode_impl(impl, cache, n, None, None, q=wide)
    if impl == 'xla':
        cache = packed_append(cache, k_new, v_new)
        k, v = packed_views(cache)
        return cache, decode_attention(
            q, DecodeCache(k=k, v=v, length=cache.length), scale=scale,
            window=window, alibi_slopes=alibi_slopes)
    at = jnp.broadcast_to(cache.length, (b,))
    out, kv, _, _, _ = flash_decode(
        wide, jnp.concatenate([k_new, v_new], axis=-1), None, cache.kv,
        None, at, jnp.where(at + n <= cache.t_max, at, -1), scale=scale,
        window=window, alibi_slopes=alibi_slopes, interpret=interpret)
    return PackedCache(kv=kv, length=cache.length + n), out


def _flash_merge(partials, axis_name, out_dtype):
    """Flash-decoding cross-shard merge of the kernel's un-normalized
    ``(num, m, l)`` triple (base-2 running max/denominator): shift
    every shard's partials by the global ``pmax`` row max, then
    numerator/denominator are plain ``psum``s — the slab-sharded and
    page-table-sharded decode steps share this one definition."""
    num, m, l = partials
    m_g = lax.pmax(m, axis_name)
    corr = jnp.exp2(m - m_g)
    num = lax.psum(num * corr, axis_name)
    den = lax.psum(l * corr, axis_name)
    return (num / jnp.where(den == 0.0, 1.0, den)).astype(out_dtype)


def decode_attention(q, cache: DecodeCache, *, scale=None, window=None,
                     alibi_slopes=None, segment_ids=None, seg_q=None,
                     qk_quant=None, axis_name=None, col_valid=None,
                     col_offset=None):
    """One masked-softmax attention step of ``q (B, H, n, d)`` against the
    cache prefix; returns ``(B, H, n, d_v)``.

    ``n`` is usually 1 (token-by-token) but any static ``n`` works (the
    queries are assumed to be the LAST ``n`` appended positions, i.e.
    call :func:`append_kv` with their k/v first — standard causal
    decode ordering; rows see themselves and everything before).

    ``window``: sliding-window lookback cap over absolute positions —
    matches the training kernels' semantics, so a model trained with
    ``window=N`` decodes identically. ``alibi_slopes (H,)``: the same
    relative-distance bias as training. ``segment_ids``: optional
    ``(B, T_max)`` cached-side ids with ``seg_q (B, n)`` for the query
    rows (packed multi-turn serving); pairs in different segments don't
    attend. ``qk_quant='int8'`` reproduces the training kernels'
    quantized scoring exactly (see the inline comment). Fully-masked
    rows return 0, matching the training kernels.

    ``axis_name``: sequence-sharded serving (inside a ``shard_map``
    with the cache slab-sharded on the ``t_max`` axis — see
    :func:`append_kv_sharded`): each shard scores q against ITS slab,
    and the softmax merges across shards by the flash-decoding rule
    (global row max via ``pmax``, then one ``psum`` each for the
    numerator and denominator — exactly the training kernels' LSE
    combine, so the merged result equals the unsharded one). ``q`` is
    replicated; ``segment_ids`` (when used) is the slab's local shard;
    ``cache.length`` is global.

    A :class:`RingCache` (a window layer's recycled buffers; needs
    ``window <= capacity``, scalar length, no ``axis_name``) is masked by
    the POSITION each column holds (:func:`ring_positions`) in place of
    the column's index: the same causal and window comparisons, plus
    "was ever written". This is the oracle of the kernel's ring mode.

    ``col_offset``: explicit global position of this buffer's column 0
    (default: ``axis_index · t_max`` when sharded, else 0). The
    sequence-SHARDED PAGED view passes 0 — a shard's gathered slab
    keeps the table's LOGICAL width, so its columns already sit at
    global positions — together with ``col_valid (B, t_local) bool``:
    ordinals owned by OTHER shards gathered the sink page and lie
    BELOW the causal fill, where the position mask alone would admit
    them, so they are masked out explicitly. ``col_offset`` also lifts
    the per-slot × sharded restriction (the sharded page table is
    per-slot by construction; slab sharding stays scalar-length).
    """
    b, h, n, d = q.shape
    h_kv = cache.k.shape[1]
    if h % h_kv:
        raise ValueError(f'query heads {h} must be a multiple of cache '
                         f'kv heads {h_kv}')
    group = h // h_kv
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    t_max = cache.t_max

    qg = q.reshape(b, h_kv, group * n, d)
    if qk_quant == 'int8':
        # Reproduce the training kernels' quantized scoring: both sides
        # per-row symmetrically quantized with the SAME rule as the
        # fused kernel, so a model trained with int8 QK^T decodes to its
        # training-time logits. The dot runs s8×s8→s32 (exact) with the
        # per-row scales applied to the s32 scores, so the cached side
        # streams int8 — half the bf16 K bytes. Measured honesty
        # (RESULTS "decode", chained, kv2/131K): 0.32 ms/step vs a
        # bf16-trained model's 0.21 — XLA's s8 dot lowering doesn't
        # cash the byte saving in at 4-row operands (an earlier
        # formulation that dequantized to fp32 BEFORE the dot was 0.49:
        # never widen the streamed operand). For int8-trained models
        # this is still the best available path — strictly less work
        # than re-quantizing the bf16 buffer each step. The mirror
        # comes from the cache when it carries one (init_cache
        # (qk_quant=) — rows quantize once at append); a mirror-less
        # cache quantizes on the fly (exact but re-reads the full K
        # buffer).
        from distributed_dot_product_tpu.ops.pallas_attention import (
            _quantize_rows,
        )
        qi, sq = _quantize_rows(qg, b * h_kv, group * n, d)
        qi = qi.reshape(qg.shape)
        sq = sq.reshape(b, h_kv, group * n, 1)
        if getattr(cache, 'k_q', None) is not None:
            ki, sk = cache.k_q, cache.k_scale
        else:
            ki, sk = _quantize_rows(cache.k, b * h_kv, t_max, d)
            ki = ki.reshape(cache.k.shape)
            sk = sk.reshape(b, h_kv, t_max, 1)
        s = jnp.einsum('bhqd,bhtd->bhqt', qi, ki,
                       preferred_element_type=jnp.int32
                       ).astype(jnp.float32)
        s = s * (sq * scale) * jnp.swapaxes(sk, -1, -2)
    elif qk_quant is not None:
        raise ValueError(f"qk_quant must be None or 'int8', "
                         f'got {qk_quant!r}')
    else:
        # Stream K at its storage dtype with an f32 ACCUMULATOR
        # (preferred_element_type) instead of upcasting the buffer:
        # `cache.k.astype(f32)` would materialize a full-size f32 copy
        # of the cache every step — twice the bytes of the attention
        # read itself. bf16→f32 conversion is exact per element, so the
        # scores match the upcast-first formulation bit for bit on
        # backends that widen inside the dot. lax.dot_general (not
        # jnp.einsum) because einsum's dtype promotion would sneak the
        # same full-buffer convert back in when q and cache dtypes
        # differ. Enforced by graphlint's cache-upcast/f32-accum rules.
        s = lax.dot_general(
            qg, cache.k, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale
    s = s.reshape(b, h_kv, group, n, t_max)

    # Query row i (0-based within the n new rows) sits at absolute
    # position length - n + i; it attends positions <= its own. A
    # PER-SLOT cache (init_slot_cache: length is a (B,) vector) gives
    # every batch row its own clock — each slot masks against its own
    # length, which is what lets continuous batching pack sequences of
    # different ages into one compiled step. Sharded, this slab's
    # columns sit at global offset shard·t_local.
    per_slot = cache.length.ndim == 1
    if per_slot and axis_name is not None and col_offset is None:
        raise ValueError(
            'per-slot lengths (init_slot_cache) are a local serving '
            'construct; sequence-sharded decode uses the scalar global '
            'length — the sharded PAGED view passes col_offset=0')
    if col_offset is not None:
        col_off = col_offset
    else:
        col_off = (0 if axis_name is None
                   else lax.axis_index(axis_name) * t_max)
    lengths = cache.length[:, None] if per_slot else cache.length
    pos_q = lengths - n + jnp.arange(n)       # (B, n) per-slot else (n,)
    ring = isinstance(cache, RingCache)
    if ring:
        if (per_slot or axis_name is not None or window is None
                or window > t_max):
            raise ValueError(
                'a RingCache is attended with its layer\'s window (<= '
                'capacity), a scalar length and no axis_name')
        pos_k = ring_positions(cache.length, t_max)
    else:
        pos_k = col_off + jnp.arange(t_max)                 # (t_local,)
    rel = pos_k - pos_q[..., None]            # ([B,] n, t_max)
    allowed = rel <= 0
    if ring:
        allowed = jnp.logical_and(allowed, pos_k >= 0)
    if window is not None:
        allowed = jnp.logical_and(allowed, -rel < window)
    if not per_slot:
        allowed, rel = allowed[None], rel[None]   # (1, n, t_max)
    if col_valid is not None:
        # Columns this buffer does not actually hold (a sharded page
        # table's other-shard ordinals): masked regardless of position.
        allowed = jnp.logical_and(
            allowed, jnp.asarray(col_valid, bool)[:, None, :])
    if segment_ids is not None:
        if seg_q is None:
            raise ValueError('segment_ids needs seg_q (the query rows\' '
                             'ids)')
        same = (segment_ids[:, None, :] == seg_q[..., None])  # (B, n, Tm)
        allowed = jnp.logical_and(allowed, same)
    allowed = allowed[:, None, None]          # (B|1, 1, 1, n, Tm)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(
            h_kv, group, 1, 1)
        s = s + slopes * rel[:, None, None].astype(jnp.float32)
    s = jnp.where(allowed, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    if axis_name is not None:
        # Flash-decoding merge: shift every shard's weights by the
        # GLOBAL row max, then the numerator/denominator sums are plain
        # psums (a shard whose slab is entirely masked/unfilled
        # contributes exp(-inf − m) = 0).
        m = lax.pmax(m, axis_name)
    m_safe = jnp.maximum(m, jnp.float32(-1e30))             # empty rows
    p = jnp.exp(s - m_safe)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    # Context dots: f32 weights against the V buffer AT ITS STORAGE
    # DTYPE, f32 accumulation (mixed-dtype dot_general — see the score
    # dot above). The former p.astype(v.dtype) rounding and the
    # cache.v.astype(f32) full-buffer upcast are both gone: weights
    # stay f32 (more accurate) and the cache is never re-materialized.
    if axis_name is None:
        p = p / jnp.where(denom == 0.0, 1.0, denom)
        out = lax.dot_general(
            p, cache.v, (((4,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32).astype(cache.v.dtype)
        return out.reshape(b, h, n, cache.v.shape[-1])
    num = lax.dot_general(
        p, cache.v, (((4,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)
    num = lax.psum(num, axis_name)
    denom = lax.psum(denom, axis_name)        # (…, n, 1): broadcasts
    out = num / jnp.where(denom == 0.0, 1.0, denom)
    return out.reshape(b, h, n, cache.v.shape[-1]).astype(cache.v.dtype)
