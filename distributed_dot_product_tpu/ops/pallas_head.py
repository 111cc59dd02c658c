# -*- coding: utf-8 -*-
"""
The gradient half of the language-model head: ONE Pallas program a chunk
that builds each ``dlogits`` tile once, in VMEM, and feeds it to both of
the head's gradient products.

``models.lm.head_loss`` takes its gradient in the forward pass: a chunk's
float32 logits give the loss and, while they are live, ``dlogits =
logit_scale · (softmax − onehot)``, ``dx = dlogits · table`` and ``dW +=
dlogitsᵀ · x``. XLA keeps no ``(chunk, vocab)`` tile: it fuses the
elementwise expression into the operand of EACH product, the second time
transposed (``exp``, compare and select twice over 206 M elements a
chunk), and ran ``dW`` at 65 % of the MXU's peak (chip, PR 33). This
kernel is the fused flash backward's shape (``ops/pallas_attention.py``,
``flash_bwd_fused``) turned to the head:

- grid ``(row groups, vocabulary blocks)``, the vocabulary innermost; a
  grid step takes the group's ``(group, tile)`` float32 logits, builds
  ``dl = exp(logits − lse) − (target == column)`` once, rounds it to the
  compute type as the MXU would, and uses it twice;
- ``dW[j] = dW_in[j] + dlᵀ · x`` contracts over the group's rows, so the
  block is read once and written once a group (the accumulator is
  aliased in and out: no second ``(vocab, d)`` float32 array lives);
- ``dx += dl · table[j]`` lands in the group's float32 ``(group, d)``
  output block, which stays in VMEM across the vocabulary walk — what
  ``_FUSED_DQ_BYTES`` budgets for a batch-head's ``dq``;
- the group's ``x`` rides whole, in the compute type, fetched once a
  group;
- a vocabulary that is no multiple of the tile pads its last block INSIDE
  the kernel (columns past the vocabulary give ``dl = 0`` and the table
  rows there read as zero); the table is never padded in HBM.

Ignored rows (``target < 0``) reach the kernel with a logsumexp of
``+inf``: ``exp(logits − inf) = 0`` and −1 matches no column, so their
``dl`` is zero without a select of its own.

:func:`head_tiles` is the one rule that says, from a call's shapes and
types, whether the kernel takes it and with which tiles; ``None`` keeps
``models/lm.py``'s XLA body. Off the TPU the kernel runs under the
Pallas interpreter, as the other kernels do.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.ops.kernel_call import kernel_call

__all__ = ['head_grad', 'head_tiles']

# The group's float32 dx stays in VMEM across the vocabulary walk: the
# budget the fused flash backward gives a batch-head's dq
# (``ops/pallas_attention._FUSED_DQ_BYTES``), 1024 rows at width 4096.
_DX_BYTES = 16 * 1024 * 1024
# The most rows of a group. dW is read and written once a group, so a
# group's two products (4 x group x d x vocab FLOPs) have to outlast
# 10 x vocab x d bytes of dW and table: 512 rows are bound by the HBM
# (66 % / 65 % of the MXU's peak at the two training cells' shapes),
# 1024 by the MXU (95 %); 2048, dx single-buffered, lost to 1024 at
# both (71-77 % and 75-94 %; chip, PR 41). The group's (group, tile)
# float32 logits and the temporaries of dl grow with it.
_ROW_GROUP = 1024
# Rows and vocabulary columns come in MXU tiles.
_TILE = 128
# The widest vocabulary block, and what dW's (tile, d) float32 blocks
# may take of VMEM, in and out and both double-buffered: 512 rows at
# width 4096 (512 read 1 % / 2.5 % faster than 256 at the cells' shapes).
_VOCAB_TILE = 512
_DW_BYTES = 32 * 1024 * 1024
# The fewest vocabulary blocks of a walk: dW is aliased in and out, and a
# group's write of block j has to have landed before the next group's
# read of it is issued, one grid step ahead of its use.
_MIN_BLOCKS = 4
# What a grid step needs beside its streamed blocks: the (group, tile)
# float32 temporaries of dl and the products' results on their way to
# the accumulators.
_VMEM_BASE = 24 * 1024 * 1024


def head_tiles(rows, dim, vocab, compute_dtype):
    """``(tiles, why)`` for one chunk of the head's gradient: ``tiles``
    is ``{'row_group', 'vocab_tile', 'row_tile'}`` when the kernel takes
    the call — ``rows`` rows (batch × chunk) of width ``dim`` against a
    ``(vocab, dim)`` table, the products' operands in ``compute_dtype``
    — and ``None`` with the reason in ``why`` when the XLA body keeps
    it. The group is the most rows up to :data:`_ROW_GROUP`, a
    power-of-two count of 128-row tiles that divides ``rows``, whose
    float32 ``(group, dim)`` dx fits :data:`_DX_BYTES`; the vocabulary
    tile the most 128-column tiles up to :data:`_VOCAB_TILE` whose dW
    blocks fit :data:`_DW_BYTES` and that leave the walk
    :data:`_MIN_BLOCKS` blocks. A grid step takes the whole group
    (``row_tile`` is the group)."""
    if jnp.dtype(compute_dtype) != jnp.bfloat16:
        return None, (f'compute type {jnp.dtype(compute_dtype).name}: the '
                      'kernel rounds dlogits to bfloat16')
    if dim % _TILE:
        return None, f'width {dim} is no multiple of {_TILE}'
    if vocab < _MIN_BLOCKS * _TILE:
        return None, (f'vocabulary {vocab} is under {_MIN_BLOCKS} tiles of '
                      f'{_TILE}')
    if rows < _TILE or rows % _TILE:
        return None, f'{rows} rows are no multiple of the row tile {_TILE}'
    if _TILE * dim * 4 > min(_DX_BYTES, _DW_BYTES // 4):
        return None, (f'one {_TILE}-row tile of float32 at width {dim} is '
                      'past the VMEM plan')
    group = vocab_tile = _TILE
    while (2 * group <= _ROW_GROUP and rows % (2 * group) == 0
           and 2 * group * dim * 4 <= _DX_BYTES):
        group *= 2
    while (2 * vocab_tile <= min(_VOCAB_TILE, vocab // _MIN_BLOCKS)
           and 4 * 2 * vocab_tile * dim * 4 <= _DW_BYTES):
        vocab_tile *= 2
    return {'row_group': group, 'vocab_tile': vocab_tile,
            'row_tile': group}, None


def _vmem_limit(group, tile, dim):
    """The call's scoped-VMEM limit: its blocks, double-buffered — x in
    the compute type and dx in float32 a group, dW in and out and the
    table a vocabulary block, the logits — and :data:`_VMEM_BASE`."""
    blocks = (group * dim * (2 + 4) + tile * dim * (4 + 4 + 2)
              + group * tile * 4 + 2 * group * _TILE * 4)
    return _VMEM_BASE + 2 * blocks


def _kernel(lg_ref, lse_ref, tgt_ref, x_ref, tab_ref, dw_ref, dx_ref,
            dwo_ref, *, vocab, logit_scale):
    j = pl.program_id(1)
    group, tile = lg_ref.shape

    @pl.when(j == 0)
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    col = j * tile + jax.lax.broadcasted_iota(jnp.int32, (group, tile), 1)
    p = jnp.exp(lg_ref[...] - lse_ref[...])
    dl = jnp.where(tgt_ref[...] == col, p - 1.0, p)
    if logit_scale != 1.0:
        dl = dl * logit_scale
    tab = tab_ref[...]
    if vocab % tile:
        # The last block hangs over the vocabulary: what lies there is
        # whatever the buffers held.
        dl = jnp.where(col < vocab, dl, 0.0)
        row = j * tile + jax.lax.broadcasted_iota(jnp.int32, tab.shape, 0)
        tab = jnp.where(row < vocab, tab, jnp.zeros_like(tab))
    dl = dl.astype(tab.dtype)
    dwo_ref[...] = dw_ref[...] + jax.lax.dot_general(
        dl, x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (tile, d)
    dx_ref[...] += jnp.dot(dl, tab,
                           preferred_element_type=jnp.float32)  # (group, d)


def head_grad(logits, lse, targets, x, table, dw, *, logit_scale, tiles,
              interpret=None):
    """One chunk's ``(dx, dW + its share)`` at a unit cotangent of the
    summed loss.

    ``logits (rows, vocab)`` float32, already scaled by ``logit_scale``;
    ``lse (rows,)`` float32 their logsumexp; ``targets (rows,)`` int32
    (``< 0``: ignored); ``x (rows, d)`` and ``table (vocab, d)`` in the
    compute type; ``dw (vocab, d)`` float32, the accumulator so far,
    aliased to the second result. ``tiles`` from :func:`head_tiles`.
    Returns ``dx (rows, d)`` and ``dW (vocab, d)``, both float32."""
    rows, vocab = logits.shape
    dim = x.shape[-1]
    group, tile = tiles['row_group'], tiles['vocab_tile']
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    lse = jnp.where(targets >= 0, lse, jnp.inf).reshape(rows, 1)
    targets = targets.reshape(rows, 1)

    def by_group(g, j):
        return (g, 0)

    def by_block(g, j):
        return (j, 0)

    return kernel_call(
        functools.partial(_kernel, vocab=vocab, logit_scale=logit_scale),
        grid=(rows // group, pl.cdiv(vocab, tile)),
        in_specs=[pl.BlockSpec((group, tile), lambda g, j: (g, j)),
                  pl.BlockSpec((group, 1), by_group),
                  pl.BlockSpec((group, 1), by_group),
                  pl.BlockSpec((group, dim), by_group),
                  pl.BlockSpec((tile, dim), by_block),
                  pl.BlockSpec((tile, dim), by_block)],
        out_specs=[pl.BlockSpec((group, dim), by_group),
                   pl.BlockSpec((tile, dim), by_block)],
        out_shape=[jax.ShapeDtypeStruct((rows, dim), jnp.float32),
                   jax.ShapeDtypeStruct((vocab, dim), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(group, tile, dim)),
        interpret=interpret,
        name='head_grad')(logits, lse, targets, x, table, dw)
