# -*- coding: utf-8 -*-
"""
Distributed sequence-matmul kernels (functional layer, no custom gradients).

TPU-native rebuild of the reference L2 layer (reference
multiplication/functions.py): three distributed matrix products over a
sequence axis ``T`` sharded ``T/N`` per device —

- ``distributed_matmul_nt``:  ``A·Bᵀ``  (reference functions.py:44-99)
- ``distributed_matmul_tn``:  ``Aᵀ·B``  (reference functions.py:102-148)
- ``distributed_matmul_all``: ``A·B``   (reference functions.py:160-212)

All three are plain functions meant to run **inside a shard_map body** over
a 1-D mesh axis (default ``'seq'``): every array argument is the *local
shard* ``(*, T/N, ·)``, exactly the reference's per-process view. Use the
``*_global`` wrappers (or your own ``shard_map``) to apply them to global
arrays on a mesh.

Communication mapping (reference → here):

- chunked ``hvd.allgather`` loops (reference functions.py:89-97, 202-210)
  → a ``lax.scan`` whose body all-gathers one ``offset``-sized slab and
  feeds one large MXU matmul. ``offset`` keeps its meaning: gathered-operand
  memory is O(W·offset·d) instead of O(T·d) (reference functions.py:64-68);
  smaller offset = less live memory, more (smaller) collectives.
- the reference's per-block ``hvd.allreduce_async(Sum)`` + keep-own-block in
  ``tn`` (reference functions.py:140-147) is exactly a reduce-scatter
  → one ``lax.psum_scatter``.
- the MPI barrier opening every kernel (reference functions.py:77) has no
  analog: one compiled XLA program cannot misorder its collectives.
- ``impl='ring'`` gives a ``lax.ppermute`` systolic-ring variant of nt/all
  (neighbour exchange over the ICI torus instead of all-gather) — a pattern
  the reference doesn't have; it keeps peak gathered memory at one shard
  regardless of ``offset`` and overlaps compute with ICI transfers.

Shape contracts (identical to the reference; W = mesh-axis size):

===========  =======================  =======================  ==================
kernel       left                     right                    out
===========  =======================  =======================  ==================
nt           ``(*, T/N, D)``          ``(*, T/N, D)``          ``(*, T/N, T)``
tn           ``(*, T/N, T)``          ``(*, T/N, D)``          ``(*, T/N, D)``
all          ``(*, T/N, T)``          ``(*, T/N, D)``          ``(*, T/N, D)``
===========  =======================  =======================  ==================

Global column order of ``nt`` matches the reference's interleave (reference
functions.py:98): global column ``w·(T/N) + j`` is local row ``j`` of shard
``w`` — i.e. plain global order.

The reference also defines a dead ``distributed_matmul_block`` with a typo
(reference functions.py:151-157, SURVEY §2.1); deliberately not carried
forward.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_dot_product_tpu.utils.build_ledger import build_span
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

__all__ = [
    'distributed_matmul_nt', 'distributed_matmul_tn',
    'distributed_matmul_all',
    'distributed_matmul_nt_global', 'distributed_matmul_tn_global',
    'distributed_matmul_all_global',
]


def _axis_size(axis_name):
    # Static Python int inside shard_map (mesh axis sizes are compile-time).
    return lax.psum(1, axis_name)


def _check_offset(offset):
    if offset is not None and int(offset) < 1:
        raise ValueError(
            f'offset must be a positive chunk size or None (full gather), '
            f'got {offset}')


def _pad_to_multiple(x, multiple, axis):
    """Zero-pad ``x`` along ``axis`` up to the next multiple. Returns
    (padded, padded_size). Lifts the reference's hard requirement that
    ``offset`` divide ``T/N`` (reference functions.py:66) — the pad columns
    are sliced off after the chunk loop."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis % x.ndim] = (0, target - size)
    return jnp.pad(x, pad), target


@build_span('ops.nt')
def distributed_matmul_nt(left, right, offset=32, axis_name=SEQ_AXIS,
                          impl='allgather', precision=None):
    """``A·Bᵀ`` over sequence-sharded operands (reference functions.py:44-99).

    ``left``/``right``: local shards ``(*, T/N, D)``; returns ``(*, T/N, T)``
    — each shard holds its row-block of the global ``(T, T)`` product, with
    columns in global order.

    ``offset``: rows of ``right`` gathered per step (memory/time knob,
    reference functions.py:64-68). ``None`` gathers everything in one step.
    ``impl``: ``'allgather'`` (chunked, honors ``offset``) or ``'ring'``
    (ppermute neighbour ring; ``offset`` ignored — peak gathered memory is
    always exactly one shard).
    """
    if impl == 'ring':
        return _matmul_nt_ring(left, right, axis_name, precision)
    _check_offset(offset)
    W = _axis_size(axis_name)
    Tn = right.shape[-2]
    offset = Tn if offset is None else min(int(offset), Tn)

    if offset >= Tn:
        # Single step: tiled all-gather puts rows in global order already.
        gathered = lax.all_gather(right, axis_name, axis=right.ndim - 2,
                                  tiled=True)  # (*, T, D)
        return jnp.matmul(left, jnp.swapaxes(gathered, -1, -2),
                          precision=precision)

    r, Tp = _pad_to_multiple(right, offset, axis=-2)
    nchunks = Tp // offset

    def body(c, _):
        chunk = lax.dynamic_slice_in_dim(r, c * offset, offset, axis=-2)
        g = lax.all_gather(chunk, axis_name)        # (W, *, offset, D)
        # (*, T/N, W, offset): one fused MXU contraction per step.
        part = jnp.einsum('...td,w...od->...two', left, g,
                          precision=precision)
        return c + 1, part

    _, ys = lax.scan(body, 0, None, length=nchunks)
    # ys: (nchunks, *, T/N, W, offset) -> (*, T/N, W, nchunks, offset)
    out = jnp.moveaxis(ys, 0, -2)
    out = out.reshape(*out.shape[:-3], W, Tp)
    if Tp != Tn:
        out = out[..., :Tn]  # drop pad columns inside each shard's block
    # (*, T/N, W, T/N) -> (*, T/N, T): global column = w*(T/N) + j, the same
    # interleave as the reference's unsqueeze/transpose/reshape
    # (reference functions.py:98).
    return out.reshape(*left.shape[:-1], W * Tn)


def _matmul_nt_ring(left, right, axis_name, precision):
    """Systolic-ring ``A·Bᵀ``: rotate ``right`` shards around the mesh ring
    with ``lax.ppermute``; at step ``s`` the resident buffer is shard
    ``(rank+s) mod W``, producing that owner's column block. ICI-friendly:
    W-1 neighbour exchanges, no radix-W all-gather; gathered memory = one
    shard."""
    W = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    Tn = right.shape[-2]
    out_shape = (*left.shape[:-1], W * Tn)
    perm = [(i, (i - 1) % W) for i in range(W)]

    def compute(s, buf, out):
        owner = (idx + s) % W
        block = jnp.einsum('...td,...od->...to', left, buf,
                           precision=precision)  # (*, T/N, T/N)
        return lax.dynamic_update_slice_in_dim(
            out, block.astype(out.dtype), owner * Tn, axis=-1)

    def body(s, carry):
        buf, out = carry
        out = compute(s, buf, out)
        return lax.ppermute(buf, axis_name, perm), out

    dtype = jnp.result_type(left.dtype, right.dtype)
    # W-1 rotated steps; the last resident block needs no trailing permute.
    buf, out = lax.fori_loop(
        0, W - 1, body, (right, jnp.zeros(out_shape, dtype)))
    return compute(W - 1, buf, out)


@build_span('ops.tn')
def distributed_matmul_tn(left, right, axis_name=SEQ_AXIS, precision=None):
    """``Aᵀ·B`` over sequence-sharded operands (reference
    functions.py:102-148).

    ``left``: ``(*, T/N, C)`` with ``C = W·(C/W)``; ``right``:
    ``(*, T/N, D)``. Returns ``(*, C/W, D)`` — shard ``w`` keeps rows
    ``[w·C/W, (w+1)·C/W)`` of the global ``AᵀB``.

    The reference expressed this as W named async allreduces where each rank
    keeps only its own block (reference functions.py:140-147) — that is
    reduce-scatter by construction, so here it is a single
    ``lax.psum_scatter`` riding ICI. No ``offset`` knob, same as the
    reference (functions.py:103).
    """
    W = _axis_size(axis_name)
    C = left.shape[-1]
    if C % W:
        raise ValueError(
            f'distributed_matmul_tn: left last dim {C} must be divisible by '
            f'the mesh axis size {W}')
    blocks = left.reshape(*left.shape[:-1], W, C // W)  # (*, T/N, W, C/W)
    # Local partial of every output block: (W, *, C/W, D).
    contrib = jnp.einsum('...twc,...td->w...cd', blocks, right,
                         precision=precision)
    return lax.psum_scatter(contrib, axis_name, scatter_dimension=0,
                            tiled=False)


@build_span('ops.all')
def distributed_matmul_all(left, right, offset=32, axis_name=SEQ_AXIS,
                           impl='allgather', precision=None):
    """``A·B`` over sequence-sharded operands (reference
    functions.py:160-212).

    ``left``: ``(*, T/N, T)`` (e.g. attention rows), ``right``:
    ``(*, T/N, D)`` (e.g. values). Returns ``(*, T/N, D)``.

    ``offset``: feature *columns* of ``right`` gathered per step — the same
    D-chunking as the reference (functions.py:202-210); gathered memory is
    O(T·offset). ``impl='ring'`` rotates whole ``right`` shards instead
    (gathered memory O(T/N·D), W-1 neighbour hops).
    """
    if impl == 'ring':
        return _matmul_all_ring(left, right, axis_name, precision)
    _check_offset(offset)
    W = _axis_size(axis_name)
    Tn, D = right.shape[-2], right.shape[-1]
    offset = D if offset is None else min(int(offset), D)
    concat_axis = right.ndim - 2

    if offset >= D:
        gathered = lax.all_gather(right, axis_name, axis=concat_axis,
                                  tiled=True)  # (*, T, D)
        return jnp.matmul(left, gathered, precision=precision)

    r, Dp = _pad_to_multiple(right, offset, axis=-1)
    nchunks = Dp // offset

    def body(c, _):
        chunk = lax.dynamic_slice_in_dim(r, c * offset, offset, axis=-1)
        g = lax.all_gather(chunk, axis_name, axis=concat_axis,
                           tiled=True)  # (*, T, offset) in global row order
        part = jnp.matmul(left, g, precision=precision)  # (*, T/N, offset)
        return c + 1, part

    _, ys = lax.scan(body, 0, None, length=nchunks)
    # (nchunks, *, T/N, offset) -> (*, T/N, nchunks*offset)
    out = jnp.moveaxis(ys, 0, -2)
    out = out.reshape(*out.shape[:-2], Dp)
    return out[..., :D] if Dp != D else out


def _matmul_all_ring(left, right, axis_name, precision):
    """Ring ``A·B``: rotate ``right`` shards; at step ``s`` multiply the
    resident shard (owner ``(rank+s) mod W``) against the matching column
    block of ``left`` and accumulate."""
    W = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    Tn = right.shape[-2]
    perm = [(i, (i - 1) % W) for i in range(W)]
    acc_dtype = jnp.result_type(left.dtype, right.dtype)

    def compute(s, buf, acc):
        owner = (idx + s) % W
        block = lax.dynamic_slice_in_dim(left, owner * Tn, Tn, axis=-1)
        return acc + jnp.matmul(block, buf, precision=precision)

    def body(s, carry):
        buf, acc = carry
        acc = compute(s, buf, acc)
        return lax.ppermute(buf, axis_name, perm), acc

    out_shape = (*left.shape[:-1], right.shape[-1])
    # W-1 rotated steps; the last resident block needs no trailing permute.
    buf, acc = lax.fori_loop(
        0, W - 1, body, (right, jnp.zeros(out_shape, acc_dtype)))
    return compute(W - 1, buf, acc)


# ---------------------------------------------------------------------------
# Global-array wrappers: apply the shard-local kernels to global arrays on a
# mesh. The reference has no analog (its processes only ever see shards);
# these are the convenient entry points for single-program users.
# ---------------------------------------------------------------------------

def _seq_specs(ndims, mesh_axis):
    return tuple(
        P(*([None] * (nd - 2) + [mesh_axis, None])) for nd in ndims)


def _shard_mapped(fn, mesh, ndims_in, ndim_out, mesh_axis=SEQ_AXIS):
    in_specs = _seq_specs(ndims_in, mesh_axis)
    (out_spec,) = _seq_specs([ndim_out], mesh_axis)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)


def distributed_matmul_nt_global(left, right, offset=32, mesh=None,
                                 mesh_axis=SEQ_AXIS, **kw):
    """``A·Bᵀ`` on *global* arrays ``(*, T, D)`` sharded over ``mesh``."""
    fn = partial(distributed_matmul_nt, offset=offset, axis_name=mesh_axis,
                 **kw)
    return _shard_mapped(fn, mesh, (left.ndim, right.ndim), left.ndim,
                         mesh_axis)(left, right)


def distributed_matmul_tn_global(left, right, mesh=None,
                                 mesh_axis=SEQ_AXIS, **kw):
    """``Aᵀ·B`` on *global* arrays sharded over ``mesh``."""
    fn = partial(distributed_matmul_tn, axis_name=mesh_axis, **kw)
    return _shard_mapped(fn, mesh, (left.ndim, right.ndim), left.ndim,
                         mesh_axis)(left, right)


def distributed_matmul_all_global(left, right, offset=32, mesh=None,
                                  mesh_axis=SEQ_AXIS, **kw):
    """``A·B`` on *global* arrays sharded over ``mesh``."""
    fn = partial(distributed_matmul_all, offset=offset, axis_name=mesh_axis,
                 **kw)
    return _shard_mapped(fn, mesh, (left.ndim, right.ndim), left.ndim,
                         mesh_axis)(left, right)
