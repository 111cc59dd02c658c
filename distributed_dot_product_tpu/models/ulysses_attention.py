# -*- coding: utf-8 -*-
"""
Ulysses (head all-to-all) sequence parallelism — the framework's third
sequence-parallel attention strategy.

The reference has exactly one strategy: chunked-allgather sequence
parallelism over the time axis (SURVEY §2.2; its "Ulysses" row reads
"No. Heads stay local; no all-to-all anywhere", reference module.py:47-58).
This module adds the DeepSpeed-Ulysses layout as a first-class TPU path:

- inputs arrive sequence-sharded ``(..., H, T/N, d)`` like every other op
  in this framework;
- ONE ``lax.all_to_all`` per operand re-shards heads↔time:
  each device ends up with the FULL sequence for ``H/N`` heads
  ``(..., H/N, T, d)``;
- attention for those heads runs entirely locally — here through the fused
  Pallas flash kernel (:func:`..ops.pallas_attention.flash_attention`), so
  there is no (T, T) score materialization either;
- a mirror ``all_to_all`` restores the ``(..., H, T/N, d_v)`` layout.

Communication per device is O(T·d·H/N) — a factor H/N less than the
allgather path's O(T·d·H) — and it rides ICI as a single fused collective
per tensor instead of a chunk loop. The trade: head parallelism caps the
mesh width (``H % N == 0`` required), where ring/allgather scale with T
alone. Ring wins when N > H or when masks must stay sharded; Ulysses wins
when heads are plentiful (communication volume, and the local flash kernel
sees the full sequence, so its online softmax never crosses devices).

Masking: an optional boolean ``mask (..., T/N, T)`` (True = masked,
reference README.md:67 convention) is all-gathered to the full ``(T, T)``
per device — O(T²) bytes, unavoidable because every device now owns whole
rows of the attention matrix. Prefer ``causal=True`` (handled inside the
kernel with block skipping, no materialized mask) for triangular masking.
"""

import math

import jax
from jax import lax
import jax.numpy as jnp

from distributed_dot_product_tpu.models.remat import note_named
from distributed_dot_product_tpu.ops.pallas_attention import (
    FLASH_QKV_NAME, flash_attention,
)
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

__all__ = ['ulysses_attention']


def ulysses_attention(q, k, v, mask=None, *, axis_name=SEQ_AXIS,
                      causal=False, scale=None, softmax_mode='exact',
                      segment_ids=None, window=None, alibi_slopes=None,
                      qk_quant=None, dropout_rate=0.0, dropout_seed=None):
    """Sequence-parallel attention via head↔time all-to-all re-sharding.

    ``q, k, v``: local shards ``(..., H, T/N, d)`` (``v`` may differ in its
    feature dim). Requires ``H % N == 0`` for mesh width ``N``. Grouped
    K/V heads (GQA) are accepted with the extra constraint
    ``H_kv % N == 0`` — the kv heads ride their own all_to_all, so they
    must split over the mesh too (use the ring path when they can't). ``mask``:
    optional boolean ``(..., T/N, T)`` broadcastable over the leading dims
    — NOTE it is gathered to full ``(T, T)`` per device (see module
    docstring). ``segment_ids``: optional non-negative int ``(..., T/N)``
    local shard (NO head axis) — the packed-sequence mask form; gathered
    to ``(..., T)`` (O(T), unlike the dense mask's O(T²)) and applied
    inside the kernel. Returns ``(..., H, T/N, d_v)``.

    Must run inside a ``shard_map`` over ``axis_name`` (use
    :func:`~distributed_dot_product_tpu.models.attention.apply_seq_parallel`
    with ``softmax_impl='ulysses'`` for global arrays). Differentiable —
    ``all_to_all`` is its own transpose, so the backward is the mirrored
    communication pattern automatically.
    """
    world = lax.psum(1, axis_name)
    if q.ndim < 3:
        raise ValueError(
            f'ulysses_attention needs (..., H, T/N, d) inputs with an '
            f'explicit head axis; got {q.ndim}-D')
    heads = q.shape[-3]
    if heads % world:
        raise ValueError(
            f'ulysses_attention requires heads ({heads}) divisible by the '
            f'mesh width ({world}); use softmax_impl="online" (ring) when '
            f'N > H')
    if k.shape[-3] != heads and k.shape[-3] % world:
        # GQA: the kv heads ride their own all_to_all, so they must split
        # over the mesh too (the flash kernel then sees Hq/N : Hkv/N —
        # the same group ratio).
        raise ValueError(
            f'ulysses_attention GQA requires kv heads ({k.shape[-3]}) '
            f'divisible by the mesh width ({world})')
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    h_ax = q.ndim - 3   # head axis index
    t_ax = q.ndim - 2   # time axis index

    def scatter_heads(x):
        # (..., H, T/N, d) -> (..., H/N, T, d): split heads, concat time.
        return lax.all_to_all(x, axis_name, split_axis=h_ax,
                              concat_axis=t_ax, tiled=True)

    def gather_heads(x):
        # (..., H/N, T, d_v) -> (..., H, T/N, d_v): the exact inverse.
        return lax.all_to_all(x, axis_name, split_axis=t_ax,
                              concat_axis=h_ax, tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)

    full_mask = None
    if mask is not None:
        # Every device owns whole attention rows now — it needs all T of
        # them. The mask must carry an EXPLICIT size-1 head axis aligned
        # with q's (same convention as ring_attention): after the gather it
        # is (..., 1, T, T) and broadcasts against the (..., H/N, T, T)
        # scores on the correct axis. Rank checking is strict because a
        # rank-mismatched mask would silently broadcast its batch dim
        # against the head axis. Per-head masks are not supported (they
        # would need their own head scatter; reference masks are
        # head-broadcast, reference module.py:52-58).
        if mask.ndim != q.ndim:
            raise ValueError(
                f'mask must have the same rank as q with a size-1 head '
                f'axis at position -3 (insert one with mask[..., None, :, :]'
                f'); got mask.ndim={mask.ndim}, q.ndim={q.ndim}')
        if mask.shape[-3] != 1:
            raise ValueError(
                f'ulysses_attention supports head-broadcast masks only '
                f'(head axis of size 1, got {mask.shape[-3]}); per-head '
                f'masks would need their own head scatter')
        full_mask = lax.all_gather(mask, axis_name, axis=mask.ndim - 2,
                                   tiled=True)

    seg_pair = None
    if segment_ids is not None:
        # Both sides of every locally-owned attention row span the full
        # sequence after the head scatter; one O(T) gather serves q and kv
        # (size-1 head axis inserted to broadcast against (..., H/N, T)).
        seg_full = lax.all_gather(segment_ids.astype(jnp.int32), axis_name,
                                  axis=segment_ids.ndim - 1, tiled=True)
        seg_full = seg_full[..., None, :]
        seg_pair = (seg_full, seg_full)

    # After the head scatter every device owns whole rows at global
    # positions, so causal/window need no offset plumbing.
    slopes_local = None
    if alibi_slopes is not None:
        # Per-head slopes follow their heads through the scatter: device
        # i holds the contiguous head chunk [i·H/N, (i+1)·H/N).
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        slopes_local = lax.dynamic_slice_in_dim(
            slopes, lax.axis_index(axis_name) * (heads // world),
            heads // world, axis=-1)
    seed_local = None
    if dropout_rate and dropout_seed is not None:
        # Distinct per-device seeds: the flat batch indices repeat across
        # devices after the head scatter (each holds batch×H/N rows), so
        # a shared seed would repeat masks head-group-to-head-group.
        # (A missing seed passes None through so flash_attention raises
        # its actionable error instead of an opaque asarray failure.)
        seed_local = (jnp.asarray(dropout_seed, jnp.int32)
                      + lax.axis_index(axis_name) * jnp.int32(40503))
    # qk_quant threads straight through: after the head scatter the flash
    # kernel runs locally over the full sequence, so the per-row int8
    # quantization is computed on exactly the rows a single-device kernel
    # would see.
    note_named(FLASH_QKV_NAME, qh, kh, vh)
    out = flash_attention(qh, kh, vh, full_mask, causal=causal, scale=scale,
                          softmax_mode=softmax_mode, segment_ids=seg_pair,
                          window=window, alibi_slopes=slopes_local,
                          qk_quant=qk_quant, dropout_rate=dropout_rate,
                          dropout_seed=seed_local)
    return gather_heads(out)
