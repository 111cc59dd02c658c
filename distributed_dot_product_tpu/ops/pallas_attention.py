# -*- coding: utf-8 -*-
"""
Fused flash-attention Pallas TPU kernels (the hot-op fusion layer).

The reference computes attention as four separate eager ops — scores matmul,
mask fill, softmax, context matmul (reference module.py:60-69) — each
reading/writing the full ``(*, T/N, T)`` score tensor through device memory.
XLA fuses the elementwise pieces; these kernels fuse the *whole* chain in
VMEM with an online softmax, so score blocks never touch HBM: traffic drops
from O(T²) to O(T·d) and live score memory from O(Tq·Tk) to
O(BLOCK_Q·BLOCK_K) — in BOTH directions. The backward is the standard
flash recompute strategy: score blocks are re-derived from q/k and the
saved row logsumexp, so training memory is O(T·d) too, not O(T²). It is
ONE Pallas kernel (each block pair's scores built once, five matmuls)
wherever one batch-head's dq fits VMEM, and a dq pass plus a dk/dv pass
(seven) elsewhere.

No reference analog (SURVEY §7 step 6 names this as the post-parity
performance pass). Layout, per the TPU Pallas playbook:

- forward grid = (batch·heads, Tq/BLOCK_Q, Tk/BLOCK_K) with the K sweep
  innermost — TPU grids run sequentially, so the running
  ``(max, denom, numerator)`` accumulators live in VMEM scratch across K
  steps; only one ``(BLOCK, d)`` tile of K/V is resident at a time (Pallas
  double-buffers the HBM→VMEM streams), so sequence length is bounded by
  HBM, not VMEM;
- the backward walks K-major (Q innermost) with dk/dv accumulators in
  VMEM scratch; a pair recomputes ``p = exp(s − lse)`` from the residuals
  ``(q, k, lse)`` once and contracts with the standard flash-backward
  algebra ``ds = p · (dp − Δ)``, ``Δ = rowsum(dO ⊙ O)`` into all three
  gradients: dq of the whole batch-head stays in a float32 VMEM
  accumulator across the walk (``flash_bwd_fused``; ``_bwd_form`` picks it
  by ``(Tq, d)`` against ``_FUSED_DQ_BYTES`` and states the call's
  ``vmem_limit_bytes``). Past that budget, and for a pass asked alone, the
  dk/dv kernel runs without dq and a dq kernel sweeps K innermost with its
  own accumulator, each rebuilding ``s`` and ``dp``;
  :func:`flash_bwd_traces` reports which form a trace took;
- all matmuls hit the MXU with fp32 accumulation
  (``preferred_element_type``) whatever the input dtype; block shapes are
  lane(128)/sublane aligned;
- causal programs whose whole K block lies in the masked future skip the
  matmuls entirely (``pl.when``) — ~2× for causal attention, forward and
  backward; sliding-window programs additionally skip blocks wholly past
  the window (compute linear in T);
- a computed block's position arithmetic goes by the block's KIND, told
  once a block from its place in the grid (``_block_interior``): blocks
  wholly under the diagonal and inside the window — most of a causal
  call's — take a branch of the body without the causal / window /
  padding compares, and ALiBi's bias is one ``(1, bk)`` vector a block,
  its row constant carried in the logsumexp's domain (``_alibi_bias``);
  :func:`flash_block_traces` counts a trace's blocks by kind;
- masked logits are ``-inf`` (safe: every shift is clamped finite, see
  ``_apply_masks``), so fully-masked rows return 0 with zero gradients
  in-kernel, matching
  :mod:`distributed_dot_product_tpu.models.ring_attention` semantics (the
  reference NaNs on fully-masked rows, SURVEY §4).

On non-TPU backends (the 8-virtual-device CPU test mesh) the kernels run in
Pallas interpreter mode, so the identical code paths are covered by the
regular test suite.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
# pltpu is importable (pure Python) even off-TPU; the interpreter emulates
# VMEM scratch on CPU.
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.ops.kernel_call import kernel_call
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['flash_attention', 'flash_bwd_traces', 'flash_block_traces',
           'FLASH_RESIDUAL_NAMES', 'FLASH_QKV_NAME']

# ``jax.ad_checkpoint.checkpoint_name`` tags of the two residuals the
# differentiated forward computes itself: the output ``(*batch, Tq, d_v)``
# and the row logsumexp ``(*batch, Tq)`` float32. Each costs O(T²·d) to
# rebuild and O(T) to hold, so a ``jax.checkpoint`` around attention keeps
# them with ``save_only_these_names(*FLASH_RESIDUAL_NAMES)`` and the
# forward kernel is not run a second time (``TransformerStack``'s default).
FLASH_RESIDUAL_NAMES = ('flash_out', 'flash_lse')

# Tag of q, k and v as the differentiated forward takes them (rotated,
# split by head, gathered): the backward kernel's other operands. A
# checkpoint that keeps it rebuilds neither the projections nor the
# rotation in front of them (``models.remat.LAYER_MATMUL_NAMES``).
FLASH_QKV_NAME = 'flash_qkv'

_NEG_BIG = -0.7 * 3.4e38  # large-finite fp32; keeps exp()/VJP NaN-free


def _block_sizes(tq, tk, dtype, d_total=128, has_mask=False):
    """Measured on v5e (T=16K, d=64, bf16): 1024×1024 blocks hit
    ~76 TFLOP/s vs ~38 at 512×512; 2048×2048 exceeds VMEM. Halve the Q
    block when the head dims are large — or when a mask is present
    (Mosaic widens bool blocks to s32 in VMEM, so a (1024, 1024) mask
    block alone is 4 MB of the ~16 MB scoped budget)."""
    sub = 16 if dtype == jnp.bfloat16 else 8
    cap_q = 1024 if d_total <= 256 and not has_mask else 512
    bq = min(cap_q, max(sub, -(-tq // sub) * sub))
    bk = min(1024, max(128 if tk >= 128 else sub,
                       -(-tk // sub) * sub))
    return bq, bk


def _bwd_block_sizes(tq, tk, dtype, d_total=128, has_mask=False):
    """The backward keeps more tiles live per program (q, k, v, dO, plus
    the p/dp/ds score blocks and the dk/dv accumulators). Measured on v5e
    (T=16K, d=64, bf16): 1024×1024 runs the fwd+bwd chain 17% faster than
    512×512 and still fits VMEM; halve when the head dims are large or a
    (s32-widened) mask block joins the working set. One pair of sizes
    serves the fused kernel and the two split ones; the fused kernel's dq
    accumulator is not a tile of these sizes but the whole batch-head's
    (``_bwd_form``), under a ``vmem_limit_bytes`` of its own."""
    sub = 16 if dtype == jnp.bfloat16 else 8
    cap_q = 1024 if d_total <= 256 and not has_mask else 256
    cap_k = 1024 if d_total <= 256 and not has_mask else 512
    bq = min(cap_q, max(sub, -(-tq // sub) * sub))
    bk = min(cap_k, max(128 if tk >= 128 else sub,
                        -(-tk // sub) * sub))
    return bq, bk


def _pad_dim(x, axis, mult):
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


def _apply_masks(s, qi, ki, bq, bk, causal, kv_len, mask_ref, off_ref,
                 seg=None, pos=None, mask_live=None, window=None,
                 alibi=None, interior=False):
    """Shared logit masking: user mask block, segment ids, causal future,
    Tk padding — and the ALiBi bias.

    ``interior`` (static) is the block's KIND, decided once a block by
    :func:`_block_interior` from the block's place in the grid: on an
    interior block every row sees every column, so the causal, window and
    ``kv_len`` selects would select nothing and are left out — the same
    bits, none of the work. What the body then does an ELEMENT of the
    ``(bq, bk)`` score block, beside the softmax's own operations:

    - interior block, no ALiBi: nothing;
    - interior block, ALiBi: ONE add, of the block's ``(1, bk)`` bias
      vector (``_alibi_bias``; the row's constant never touches the
      block);
    - boundary block (the diagonal, the window's edge, a ragged last K
      block): two iotas and two adds of the block origin, then a compare
      and a select for the causal future, a subtract, a compare and a
      select for the window, an iota, a compare and a select for the
      padding, and ALiBi's add as above;
    - a call that carries a dense mask, segment ids or explicit
      positions (data, not place): every block is a boundary block and
      the mask / segment / position selects come on top.

    The mask arrives as int8 (1 = masked): Mosaic widens bool kernel
    operands to s32 — a full-size O(4·Tq·Tk) HBM copy — but takes int8
    blocks natively. ``off_ref`` ((1, 2) int32) holds the GLOBAL indices
    of query row 0 AND key column 0 — sequence-sharded callers pass their
    shard offsets so the causal triangle is over global positions with no
    materialized mask (ring folds report the rotating block's column
    offset too, which also keys the dropout hash to true global
    coordinates). ``seg``/``pos`` carry (1, B, 1)/(1, 1, B) int32
    per-position vector blocks (plus their SMEM skip tables, unused here):
    ``seg`` masks pairs in different segments (the packed-sequence mask
    form, O(T) not O(T²) HBM traffic); ``pos`` masks pairs where the query
    GLOBAL position precedes the key's — causal over arbitrary row
    layouts (zigzag/striped sharding).

    Masked logits are ``-inf``, NOT the large-finite ``_NEG_BIG``: every
    kernel shifts ``s`` by a value clamped ≥ ``_NEG_BIG`` (the running-max
    scratch is INITIALIZED to ``_NEG_BIG``, the bounded kernel's shift and
    the backward's lse are finite by construction), so ``exp2(s − shift)``
    is exactly 0 for masked entries and never NaN. That makes fully-masked
    rows yield 0 output / 0 gradients *inside* the kernel — which is also
    what makes whole-block skipping exact: a skipped block contributes
    nothing, the same as folding its all-zero weights.
    """
    assert not interior or (mask_ref is None and seg is None
                            and pos is None), 'data masks have no kind'
    if mask_ref is not None:
        masked = mask_ref[0] != 0
        if mask_live is not None:
            # Scalar-prefetch redirection aliases non-mixed tiles onto
            # block (0, 0): their resident mask content is arbitrary and
            # must not be applied (``mask_live`` = this tile is mixed).
            masked = jnp.logical_and(masked, mask_live)
        s = jnp.where(masked, -jnp.inf, s)
    if alibi is not None:
        s = s + _alibi_bias(alibi, qi, ki, bq, bk, off_ref, pos)
    if seg is not None:
        s = jnp.where(seg[0][0] != seg[1][0], -jnp.inf, s)
    if pos is not None:
        s = jnp.where(pos[0][0] < pos[1][0], -jnp.inf, s)
        if window is not None:
            # Sliding window over explicit positions: a pair whose key
            # lies ≥ window positions in the query's past is masked.
            s = jnp.where(pos[0][0] - pos[1][0] >= window, -jnp.inf, s)
    if interior:
        return s
    if causal:
        rows = (off_ref[0, 0] + qi * bq
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        cols = (off_ref[0, 1] + ki * bk
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
        s = jnp.where(rows < cols, -jnp.inf, s)
        if window is not None:
            s = jnp.where(rows - cols >= window, -jnp.inf, s)
    if kv_len % bk:
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols >= kv_len, -jnp.inf, s)
    return s


def _alibi_bias(slope, qi, ki, bq, bk, off_ref, pos):
    """ALiBi's addend to a score block, the ONE expression the forward and
    every backward body build it by (the backward's ``exp2(s − lse)`` is
    the forward's ``p`` only then). The wrapper pre-folds log2e into the
    slope, so the bias is in the kernels' log2 logit units.

    With explicit positions (arbitrary layouts) the distance is data:
    ``slope · (pos_k − pos_q)``, a full ``(bq, bk)`` block. Else it is
    place, and ``slope · (col − row)`` splits into a column's part and a
    row's: the addend is the ``(1, bk)`` vector ``slope · (col − r_mid)``
    (``r_mid``: the block's middle row — the split is about it so that
    both parts stay small where the weights are, near the diagonal), ONE
    add an element. The row's part ``slope · (row − r_mid)``, which a
    block's scores are then too HIGH by, is constant along the softmax's
    axis and the same in every K block of a row: it changes neither the
    weights nor the output, only the logsumexp, and
    :func:`_alibi_row_shift` gives it to the two places that hold one —
    the forward's finalize takes it out once a Q block, a backward block
    adds it to its ``(bq, 1)`` logsumexp from a scratch filled once a
    batch-head. Not the last bits of ``s + slope · float(cols − rows)``:
    a dominant score now rounds at its magnitude plus ≤
    ``slope · bq / 2``."""
    if pos is not None:
        return slope * (pos[1][0] - pos[0][0]).astype(jnp.float32)
    origin = (off_ref[0, 1] + ki * bk) - (off_ref[0, 0] + qi * bq + bq // 2)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + origin
    return slope * col.astype(jnp.float32)


def _alibi_row_shift(slope, bq):
    """What ``_alibi_bias`` (by place) leaves a block's scores too high
    by, a row: ``slope · (row − r_mid)`` as ``(bq, 1)``, the same in every
    block of a batch-head. (A ``(bq, 1)`` array is one value a vector
    register row: cheap once a Q block or a batch-head, a tenth of a
    block's element operation if rebuilt every block.)"""
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) - bq // 2
    return slope * row.astype(jnp.float32)


def _fill_row_shift(shift_s, slope, bq, grid_axes):
    """A backward kernel's row-shift scratch, written by the batch-head's
    first program (TPU grids run in order on one core; the slope is the
    batch-head's)."""
    first = pl.program_id(1) == 0
    for axis in range(2, grid_axes):
        first = first & (pl.program_id(axis) == 0)

    @pl.when(first)
    def _():
        shift_s[:] = _alibi_row_shift(slope, bq)


def _shift_scratch(flags, bq):
    """The LAST scratch of a backward kernel whose ALiBi bias goes by
    place (slopes given, no explicit positions): the row shift."""
    _, _, has_pos, has_alibi, _ = flags
    if has_alibi and not has_pos:
        return [pltpu.VMEM((bq, 1), jnp.float32)]
    return []


def _causal_run(causal, off_ref, qi, ki, bq, bk, window=None):
    """Block-skip predicate: does this (Q block, K block) pair contain any
    un-masked causal entry? With a traced row offset this is a dynamic
    scalar — ``pl.when`` still skips the matmuls at run time. ``window``
    additionally skips blocks wholly ≥ window positions in the past (the
    oldest pair is newest-query − oldest-key = block row 0 vs the K
    block's LAST column): compute becomes O(Tq·window), not O(Tq·Tk).
    Row/column global offsets both come from ``off_ref`` (see
    ``_apply_masks``). Plain comparisons and ``&``: the counter
    (:func:`flash_block_traces`) evaluates it over whole index arrays."""
    if not causal:
        return True
    rel = off_ref[0, 0] - off_ref[0, 1]
    run = rel + (qi + 1) * bq - 1 >= ki * bk
    if window is not None:
        run = run & (rel + qi * bq - (ki * bk + bk - 1) < window)
    return run


def _block_interior(causal, off_ref, qi, ki, bq, bk, kv_len, window=None,
                    data_masks=False):
    """A block's KIND, from the scalars ``_causal_run`` reads: *interior*
    when every (row, column) pair of the block is attendable by place —
    its smallest row is ≥ its largest column, its largest row less its
    smallest column is < ``window``, and it holds no padded column. The
    bodies are entered through one ``pl.when`` branch a kind
    (:func:`_enter_by_kind`) and the interior branch leaves the causal,
    window and ``kv_len`` selects out (``_apply_masks``). A dynamic scalar
    like ``run``, so a traced offset (sequence-sharded callers) works.

    Returns None — one branch, the whole body — where there is no kind to
    tell: a call whose masks are data (``data_masks``: a dense mask,
    segment ids, explicit positions), or one with nothing positional at
    all (not causal, no padded column)."""
    if data_masks or not (causal or kv_len % bk):
        return None
    inside = True
    if kv_len % bk:
        inside = (ki + 1) * bk <= kv_len
    if causal:
        rel = off_ref[0, 0] - off_ref[0, 1]
        inside = inside & (rel + qi * bq >= ki * bk + bk - 1)
        if window is not None:
            inside = inside & (rel + (qi + 1) * bq - 1 - ki * bk < window)
    return inside


def _enter_by_kind(run, interior, body):
    """Enter ``body(interior)`` for a block that runs: through two
    ``pl.when`` branches, one a kind, where the call has kinds, and
    through one (the whole body) where it has none."""
    if interior is None:
        pl.when(run)(functools.partial(body, False))
    else:
        pl.when(run & interior)(functools.partial(body, True))
        pl.when(run & ~interior)(functools.partial(body, False))


def _row_has_valid(mask, causal, tq, tk, row_offset=0, window=None):
    """(..., Tq, 1) bool: does row i have ANY attendable key, counting the
    causal (and sliding-window) restriction too? Rows without one output 0
    with zero gradients (in every softmax path — the kernels' semantics
    must not depend on WHICH mask made the row empty). ``row_offset`` is
    the global index of row 0 (sequence-sharded callers pass their shard
    offset)."""
    valid = ~mask
    if causal:
        rows = row_offset + jnp.arange(tq)
        cols = jnp.arange(tk)
        allowed = rows[:, None] >= cols[None, :]
        if window is not None:
            allowed = jnp.logical_and(
                allowed, rows[:, None] - cols[None, :] < window)
        valid = jnp.logical_and(valid, allowed)
    return jnp.any(valid, axis=-1, keepdims=True)


def _bcast_lead(kind, shape_lead, batch, ndim_trailing):
    """Validate that an auxiliary input's leading dims broadcast against the
    q/k/v batch dims; returns them left-padded with 1s to ``len(batch)``."""
    if len(shape_lead) > len(batch):
        # More leading dims than q/k/v: the output batch shape comes solely
        # from q/k/v, so NumPy-style broadcasting cannot apply — reject
        # instead of silently indexing only [0].
        raise ValueError(
            f'{kind} has {len(shape_lead)} leading dims but q/k/v have '
            f'{len(batch)}; {kind} may not add batch dims')
    lead = (1,) * (len(batch) - len(shape_lead)) + tuple(shape_lead)
    for db, dm in zip(batch, lead):
        if dm not in (1, db):
            raise ValueError(
                f'{kind} leading dims {tuple(shape_lead)} do not broadcast '
                f'against q/k/v leading dims {tuple(batch)}')
    return lead


def _batch_index_fn(batch, lead):
    """Flat-batch-index map (folded into a BlockSpec) from the q/k/v flat
    batch index to the flat index of an aux input whose size-1 lead axes
    are broadcast (stride 0)."""
    strides = []
    stride = 1
    for db, dm in zip(reversed(batch), reversed(lead)):
        strides.append(0 if dm == 1 else stride)
        stride *= dm

    strides.reverse()

    def index(b):
        out = 0
        rem = b
        for db, st in zip(reversed(batch), reversed(strides)):
            out = out + (rem % db) * st
            rem = rem // db
        return out

    return index


def _mask_setup(mask, batch, tq, tk, tq_p, tk_p):
    """Validate mask broadcasting and flatten it WITHOUT materializing the
    broadcast: returns the padded flat mask, a flat-batch-index map that
    skips size-1 mask axes, and the mask's (broadcast-padded) lead dims.

    Padding rows/cols are set True (masked) so padded K columns never
    contribute and padded Q rows recompute as fully-masked (their
    cotangents are zero-padded anyway).
    """
    if mask.shape[-2:] != (tq, tk):
        raise ValueError(
            f'mask trailing dims {mask.shape[-2:]} must equal '
            f'(Tq, Tk) = {(tq, tk)}')
    mlead = _bcast_lead('mask', mask.shape[:-2], batch, 2)
    nm = int(math.prod(mlead)) if mlead else 1
    # int8, not bool: see _apply_masks. Padding rows/cols are masked (1).
    maskf = jnp.pad(mask.reshape(nm, tq, tk).astype(jnp.int8),
                    ((0, 0), (0, tq_p - tq), (0, tk_p - tk)),
                    constant_values=1)
    return maskf, _batch_index_fn(batch, mlead), mlead


def _vec_setup(kind, pair, batch, tq, tk, tq_p, tk_p, pad_q, pad_k):
    """Prepare a per-position int vector pair for the kernels (segment ids
    or global positions): ``(vec_q, vec_kv)`` with trailing shapes
    ``(Tq,)`` / ``(Tk,)`` (leading dims broadcastable against q/k/v like a
    mask's). Returns the padded flat column/row vectors ``(nq, Tq_p, 1)``
    / ``(nk, 1, Tk_p)``, their batch-index maps, and their lead dims.
    ``pad_q``/``pad_k`` are the padding sentinels (chosen per use so
    padded positions always end up masked)."""
    vec_q, vec_k = pair
    if vec_q.shape[-1] != tq or vec_k.shape[-1] != tk:
        raise ValueError(
            f'{kind} trailing dims ({vec_q.shape[-1]}, '
            f'{vec_k.shape[-1]}) must equal (Tq, Tk) = {(tq, tk)}')
    qlead = _bcast_lead(f'{kind}[0]', vec_q.shape[:-1], batch, 1)
    klead = _bcast_lead(f'{kind}[1]', vec_k.shape[:-1], batch, 1)
    nq = int(math.prod(qlead)) if qlead else 1
    nk = int(math.prod(klead)) if klead else 1
    vqf = jnp.pad(vec_q.astype(jnp.int32).reshape(nq, tq, 1),
                  ((0, 0), (0, tq_p - tq), (0, 0)), constant_values=pad_q)
    vkf = jnp.pad(vec_k.astype(jnp.int32).reshape(nk, 1, tk),
                  ((0, 0), (0, 0), (0, tk_p - tk)), constant_values=pad_k)
    return (vqf, _batch_index_fn(batch, qlead), qlead,
            vkf, _batch_index_fn(batch, klead), klead)


def _seg_setup(segment_ids, batch, tq, tk, tq_p, tk_p):
    """Segment-id pair: ids must be non-negative — Q padding uses sentinel
    −1 and K padding −2, so padded positions never match anything (and
    padded K columns stay masked even without the ``kv_len % bk``
    guard)."""
    return _vec_setup('segment_ids', segment_ids, batch, tq, tk, tq_p,
                      tk_p, -1, -2)


def _pos_setup(positions, batch, tq, tk, tq_p, tk_p):
    """Explicit-global-position pair for causal masking over ARBITRARY row
    layouts (zigzag/striped sequence sharding): entry (i, j) is masked
    when ``pos_q[i] < pos_kv[j]``. Positions must be non-negative; Q pads
    with −1 (< every real position ⇒ padded rows fully masked) and K pads
    with a huge sentinel (> every real position ⇒ padded columns
    masked)."""
    return _vec_setup('positions', positions, batch, tq, tk, tq_p, tk_p,
                      -1, 2 ** 30)




_LOG2E = math.log2(math.e)
_LN2 = math.log(2.0)
# softmax_mode='bounded' safety threshold: with worst-case
# bound − true_rowmax ≤ 100 log2 units, the max softmax weight is
# ≥ 2^-100 — above TPU's flush-to-zero line (2^-126) with ≥26 log2 units
# left for the tail, i.e. only weights < 2^-26 relative are lost.
_BOUNDED_SAFE_GAP = 100.0


# Dense block-skip summaries above this size stay un-streamed (the skip is
# dropped, not the mask): SMEM is ~a MiB per core and the summary competes
# with nothing else we place there.
_RUNSUM_SMEM_CAP = 512 * 1024

# Test hook: force the scalar-prefetch mask redirect under the (slow)
# Mosaic interpreter so the CPU suite can cover the TPU-only path on tiny
# shapes.
_REDIRECT_ON_INTERPRET = False

# Test hook: likewise for the banded window grid (scalar-prefetch index
# maps need the Mosaic interpreter off-TPU; the full-grid window path with
# in-kernel skipping is the off-TPU default and is numerically identical).
_BAND_ON_INTERPRET = False

# Test hook: likewise for the trapezoid causal grid.
_TRAP_ON_INTERPRET = False

# Trapezoid pair-table budget: 2 int32 tables of npairs entries ride SMEM
# via scalar prefetch; past this many pairs fall back to the full grid
# with in-kernel skipping (same 512 KiB SMEM thinking as _RUNSUM_SMEM_CAP).
_TRAP_MAX_PAIRS = 64 * 1024


def _trap_tables(rel, nqb, nkb, bq, bk):
    """Flattened causal-trapezoid pair tables (STATIC offsets only).

    Plain causal attention runs a full (nqb, nkb) grid where nearly half
    the programs are skipped by ``pl.when`` — but a skipped program still
    pays its block DMA and grid sequencing (which is why windows got
    a banded grid). The trapezoid grid removes it for causal: the K axis
    flattens into ONE grid axis of exactly the valid (Q block, K block)
    pairs, ordered Q-major with K ascending, and scalar-prefetched SMEM
    tables map each program to its actual block indices. Out-of-triangle
    blocks then cost nothing at all — no DMA, no sequencing.

    Returns ``(qtab, ktab, ext)``: per-pair Q/K block indices and the
    per-Q-block K extent (the kernels derive accumulator init/finalize
    from ``ki == 0`` / ``ki == ext[qi] − 1``). ``rel`` is the static
    row−column global offset. Rows whose extent would be 0 (entirely in
    the future — negative ``rel``) keep one fully-masked pair so their
    output block is still written (as 0).
    """
    qi = np.arange(nqb)
    ext = np.clip((rel + (qi + 1) * bq + bk - 1) // bk, 1, nkb)
    qtab = np.repeat(qi, ext)
    ktab = np.concatenate([np.arange(e) for e in ext])
    return (jnp.asarray(qtab, jnp.int32), jnp.asarray(ktab, jnp.int32),
            jnp.asarray(ext, jnp.int32))


def _trap_tables_t(rel, nqb, nkb, bq, bk):
    """Transposed trapezoid tables for the dk/dv pass (K-major, Q
    ascending from each K block's first causally-visible Q block).
    Returns ``(qtab, ktab, qlo)`` — init fires at ``qi == qlo[kj]``,
    finalize at ``qi == nqb − 1`` (the bottom row block sees every K
    block). K blocks beyond every row keep one fully-masked pair so
    their dk/dv blocks are still written (as 0)."""
    kj = np.arange(nkb)
    qlo = np.clip((kj * bk - rel + bq) // bq - 1, 0, nqb - 1)
    counts = nqb - qlo
    ktab = np.repeat(kj, counts)
    qtab = np.concatenate([np.arange(lo, nqb) for lo in qlo])
    return (jnp.asarray(qtab, jnp.int32), jnp.asarray(ktab, jnp.int32),
            jnp.asarray(qlo, jnp.int32))


def _trap_chunk_bounds(rel, tq, tk, bq, bk):
    """Q-row chunk boundaries such that each chunk's causal pair table
    fits ``_TRAP_MAX_PAIRS``: beyond-cap sequences (T≈512K at block 1024)
    split into a few row chunks, each of which the trapezoid grid then
    covers — the kernels never see the full grid. Greedy accumulation of
    per-Q-block extents; returns [(row0, row1), ...] (block-aligned,
    one entry = no chunking needed)."""
    nqb = -(-tq // bq)
    nkb = -(-tk // bk)
    ext = np.clip((rel + (np.arange(nqb) + 1) * bq + bk - 1) // bk,
                  1, nkb)
    return _greedy_bounds(ext, bq, tq)


def _greedy_bounds(counts, blk, total):
    bounds = []
    start = 0
    acc = 0
    for i, e in enumerate(counts):
        if acc + e > _TRAP_MAX_PAIRS and i > start:
            bounds.append((start * blk, min(i * blk, total)))
            start, acc = i, 0
        acc += int(e)
    bounds.append((start * blk, total))
    return bounds


def _trap_chunk_bounds_t(rel, tq, tk, bq, bk):
    """K-block chunk boundaries for the dk/dv pass (each K chunk's
    transposed pair table fits the cap); chunks emit DISJOINT dk/dv
    slices, so beyond-cap backward chunking needs no partial sums."""
    nqb = -(-tq // bq)
    nkb = -(-tk // bk)
    qlo = np.clip((np.arange(nkb) * bk - rel + bq) // bq - 1, 0, nqb - 1)
    return _greedy_bounds(nqb - qlo, bk, tk)


def _static_offsets(causal_offset, kv_offset):
    """The (row, column) offsets as a pair of ints where both are known at
    trace time, else None (a traced offset: sequence-sharded callers)."""
    if all(isinstance(o, (int, np.integer))
           for o in (causal_offset, kv_offset)):
        return int(causal_offset), int(kv_offset)
    return None


def _trap_eligible(causal, window, mask, positions, causal_offset,
                   kv_offset, mode, interpret):
    """The trapezoid grid applies to plain causal attention with STATIC
    offsets: a traced offset (sequence-sharded SPMD — every shard runs
    one program, but their triangles differ) would make the pair count
    dynamic, which a grid size cannot be. Windows have their own banded
    grid; dense masks keep the full grid (their skip tables are indexed
    by absolute blocks); 'bounded' keeps the full grid (its case is the
    forward-only pass)."""
    static = _static_offsets(causal_offset, kv_offset) is not None
    return (causal and window is None and mask is None and positions is None
            and static and mode == 'exact'
            and ((not interpret) or _TRAP_ON_INTERPRET))


def _wrap_specs_pairs(specs, transposed=False):
    """Re-aim 3-axis index maps at the pair grid: program p's block
    indices come from the prefetched tables (``rs[0]``/``rs[1]`` = the
    Q/K tables). SMEM whole-array specs (block_shape None) pass through.
    ``transposed``: inner maps have the (b, kj, qi) signature of the
    dk/dv grid."""
    def wrap(spec):
        if spec.block_shape is None:
            return spec
        f = spec.index_map
        if transposed:
            g = lambda b, p, *rs, f=f: f(b, rs[1][p], rs[0][p], *rs)  # noqa: E731,E501
        else:
            g = lambda b, p, *rs, f=f: f(b, rs[0][p], rs[1][p], *rs)  # noqa: E731,E501
        return pl.BlockSpec(spec.block_shape, g)
    return [wrap(s) for s in specs]


def _mask_streams_per_tile(nb, tq, tk, dtype, d_total, allow_redirect,
                           bwd=False):
    """Will the dense mask stream for (almost) every tile? Only when the
    block-skip summary cannot ride SMEM (or the redirect is off) — block
    sizing must then keep the halved blocks that fit the streamed mask in
    VMEM. With the redirect live, the resident mask block is a single
    aliased tile and full-size blocks win (measured on v5e, T=16K d=96
    bf16 fwd+bwd: 44.7 ms at 256×512 vs 31.2 ms at 1024×1024)."""
    if not allow_redirect:
        return True
    f = _bwd_block_sizes if bwd else _block_sizes
    bq, bk = f(tq, tk, dtype, d_total=d_total, has_mask=False)
    return nb * (-(-tq // bq)) * (-(-tk // bk)) * 4 > _RUNSUM_SMEM_CAP


def _band_size(b_outer, b_inner, window, n_inner):
    """Number of inner-axis blocks a sliding-window band can touch per
    outer block: the band spans ``b_outer + window − 1`` positions, so at
    most ``ceil((b_outer + window − 2)/b_inner) + 1`` blocks."""
    return min(n_inner, (b_outer + window - 2) // b_inner + 2)


def _band_lo(raw, n_inner, band):
    """Clamp a band's first inner block so ``[lo, lo + band)`` stays in
    range; edge blocks pulled into the band are masked/skipped in-kernel
    (the run predicate uses the ACTUAL block index)."""
    return jnp.clip(raw, 0, n_inner - band)


def _split_aux(rest, has_mask, has_seg, has_pos, has_alibi=False):
    """Pop the optional (mask, segments, positions, alibi) ref groups off
    the input tail shared by every kernel signature (the block-skip
    summary rides the scalar-prefetch slot instead, always ref 0).
    Segments and positions each contribute (vec_q, vec_k, qmm, kmm) refs;
    alibi is one (nb,) SMEM slope table."""
    mask_ref = seg = pos = alibi_ref = None
    if has_mask:
        mask_ref, *rest = rest
    if has_seg:
        vq, vk, qmm, kmm, *rest = rest
        seg = (vq, vk, qmm, kmm)
    if has_pos:
        vq, vk, qmm, kmm, *rest = rest
        pos = (vq, vk, qmm, kmm)
    if has_alibi:
        alibi_ref, *rest = rest
    return mask_ref, seg, pos, alibi_ref, rest


def _run_pred(causal, off_ref, qi, ki, bq, bk, b, seg, pos, runsum_ref,
              window=None):
    """Combined block-skip predicate from scalar SMEM tables (vector
    reductions to scalars trip Mosaic relayouts, and (1, 1, ·) VMEM blocks
    are rejected outright — SMEM with program-id indexing is the TPU way):

    - causal: the K block lies strictly in every query row's future;
    - segments (per-block [min, max] id intervals): disjoint intervals
      cannot contain an equal pair — true for ANY id layout, tight for
      the sorted ids of packed sequences;
    - positions (per-block [min, max] global positions): a block whose
      every query position precedes its every key position is fully in
      the causal future — the zigzag/striped analog of the causal skip;
    - dense mask (``runsum``, precomputed any-unmasked-entry per block
      pair): skips the matmuls of fully-masked tiles (their mask block DMA
      is already paid — compute only).

    Exactness: masked logits are -inf ⇒ weights exactly 0 (see
    ``_apply_masks``), so skipping a fully-masked block is identical to
    folding it.
    """
    run = _causal_run(causal, off_ref, qi, ki, bq, bk, window)

    def _and(a, x):
        return x if a is True else jnp.logical_and(a, x)

    if seg is not None:
        _, _, qmm, kmm = seg
        run = _and(run, jnp.logical_and(qmm[b, qi, 0] <= kmm[b, ki, 1],
                                        kmm[b, ki, 0] <= qmm[b, qi, 1]))
    if pos is not None:
        _, _, qmm, kmm = pos
        run = _and(run, qmm[b, qi, 1] >= kmm[b, ki, 0])
        if window is not None:
            # Whole block ≥ window in the past when even its NEWEST key
            # precedes its OLDEST query by window or more.
            run = _and(run, qmm[b, qi, 0] - kmm[b, ki, 1] < window)
    if runsum_ref is not None:
        run = _and(run, runsum_ref[b, qi, ki] != 0)
    return run


def _dropout_keep(seed_ref, b, qi, ki, bq, bk, rate, off_ref, pos=None):
    """Per-block keep mask for attention-weight dropout, as a PURE
    function of (seed, flat batch, GLOBAL element coordinates) — a
    counter-based murmur3-finalizer hash, not a stateful PRNG. Element
    coordinates make the mask independent of the block decomposition, so
    the dq and dk/dv passes (whose block sizes legitimately differ from
    the forward's at large head dims / streamed masks) regenerate the
    forward's EXACT mask from any grid, banded or not — and the same
    code runs under the plain interpreter (no TPU PRNG primitives).
    Coordinates are GLOBAL on both axes: rows/columns come from the
    explicit ``pos`` vectors when given (zigzag/striped layouts), else
    from ``off_ref``'s (row, column) offsets — so sequence-parallel
    shards AND ring folds sharing one replicated seed hash different
    global elements instead of repeating one block's pattern, and a ring
    fold draws the identical mask a single-device kernel would for the
    same elements. Returns a (bq, bk) bool and the 1/(1−rate) scale."""
    u = jnp.uint32
    if pos is not None:
        rows = jnp.broadcast_to(pos[0][0], (bq, bk)).astype(u)
        cols = jnp.broadcast_to(pos[1][0], (bq, bk)).astype(u)
    else:
        rows = (off_ref[0, 0] + qi * bq
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                ).astype(u)
        cols = (off_ref[0, 1] + ki * bk
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                ).astype(u)
    x = (rows * u(2654435761)
         ^ cols * u(2246822519)
         ^ (seed_ref[0, 0].astype(u)
            + jnp.asarray(b, jnp.int32).astype(u) * u(668265263)))
    # murmur3 fmix32: full avalanche, so adjacent coordinates decorrelate.
    x = x ^ (x >> u(16))
    x = x * u(2246822507)
    x = x ^ (x >> u(13))
    x = x * u(3266489909)
    x = x ^ (x >> u(16))
    threshold = u(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return x >= threshold, 1.0 / (1.0 - rate)


def _score_block(q_ref, k_ref, quant):
    """(BQ, BK) score block in log2 logit units. Standard path: q arrived
    pre-folded by scale·log2e (the exp2 trick), one bf16 MXU dot.
    Quantized path (``quant = (sqf_ref, skr_ref)``; q/k refs hold int8):
    an int8×int8→int32 MXU dot — measured ~1.65× the bf16 rate on v5e
    (245 vs 148 TOP/s) — then a row-vector and a column-vector multiply
    apply the per-row dequantization scales (``sqf`` carries the
    scale·log2e fold, ``skr`` is the raw k-row scale)."""
    if quant is None:
        return jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    sqf_ref, skr_ref = quant
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    return s * sqf_ref[0] * skr_ref[0]


def _make_fwd_kernel(causal, bq, bk, kv_len, has_mask, has_seg, has_pos,
                     has_alibi, has_mask_skip, save_lse, window=None,
                     band_fn=None, quantized=False, dropout=None,
                     trap=False):
    def kernel(*refs):
        if trap:
            tq_ref, tk_ref, ext_ref, *refs = refs
        elif band_fn is not None:
            bandoff_ref, *refs = refs
        if has_mask_skip:
            runsum_ref, *refs = refs
        else:
            runsum_ref = None
        off_ref, *refs = refs
        if dropout is not None:
            seed_ref, *refs = refs
        q_ref, k_ref, v_ref, *rest = refs
        quant = None
        if quantized:
            sqf_ref, skr_ref, *rest = rest
            quant = (sqf_ref, skr_ref)
        mask_ref, seg, pos, alibi_ref, rest = _split_aux(
            rest, has_mask, has_seg, has_pos, has_alibi)
        if save_lse:
            o_ref, lse_ref, m_s, l_s, acc_s = rest
        else:
            (o_ref, m_s, l_s, acc_s), lse_ref = rest, None
        if trap:
            # Trapezoid pair grid: program_id(1) walks the flattened
            # valid (Q block, K block) pairs Q-major; each Q block's run
            # starts at K block 0 and ends at its causal extent.
            p = pl.program_id(1)
            qi = tq_ref[p]
            ki = tk_ref[p]
            first_k = ki == 0
            last_k_cond = ki == ext_ref[qi] - 1
        else:
            qi = pl.program_id(1)
            kj = pl.program_id(2)
            # Banded window grid: the K sweep covers only this Q block's
            # band; ki is the ACTUAL K block index (all masking/skip
            # arithmetic uses it), kj the program position (init/finalize
            # conditions).
            ki = kj if band_fn is None else band_fn(qi, bandoff_ref[0]) + kj
            first_k = kj == 0
            last_k_cond = kj == pl.num_programs(2) - 1

        @pl.when(first_k)
        def _():
            m_s[:] = jnp.full_like(m_s, _NEG_BIG)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        # Block skip: K block strictly in the causal future of every query
        # row, fully past the sliding window, or provably fully masked →
        # contributes nothing.
        pid_b = pl.program_id(0)  # hoisted: program_id inside a
        # pl.when body is not substituted by the plain interpreter
        slope = None if alibi_ref is None else alibi_ref[pid_b]
        run = _run_pred(causal, off_ref, qi, ki, bq, bk,
                        pl.program_id(0), seg, pos, runsum_ref, window)
        interior = _block_interior(causal, off_ref, qi, ki, bq, bk,
                                   kv_len, window,
                                   has_mask or has_seg or has_pos)

        def body(interior):
            # Keep matmul operands in their native dtype (bf16 in, fp32
            # accumulate) — upcasting to fp32 before the dot halves MXU
            # throughput. The softmax scale and exp's internal log2(e)
            # multiply are BOTH pre-folded into q by the wrapper (the
            # "exp2 trick"), so the softmax's own per-score-element VPU
            # work is max / subtract / exp2 / sum / downcast. At head dim
            # 128 the kernel is VPU-bound, and what ``_apply_masks`` adds
            # an element weighs as much again — which is why it is done
            # by the block's kind: an interior block (most of a causal
            # call's) pays none of the position compares, a boundary
            # block all of them (``_apply_masks`` counts both).
            v = v_ref[0]                                    # (BK, dv)
            s = _score_block(q_ref, k_ref, quant)  # (BQ, BK), log2 units
            mask_live = (None if runsum_ref is None else
                         runsum_ref[pl.program_id(0), qi, ki] == 1)
            s = _apply_masks(s, qi, ki, bq, bk, causal, kv_len,
                             mask_ref, off_ref, seg, pos, mask_live,
                             window, slope, interior)

            m_prev = m_s[:]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            m_s[:] = m_new
            # Dropout acts on the NORMALIZED weights, so the denominator
            # accumulates the undropped p while the numerator folds the
            # kept entries (inverted-dropout scaled) — algebraically
            # identical to dropout(softmax(s))·v.
            l_s[:] = l_s[:] * corr + p.sum(axis=-1, keepdims=True)
            p_num = p
            if dropout is not None:
                keep, inv = _dropout_keep(seed_ref, pid_b, qi, ki,
                                          bq, bk, dropout, off_ref, pos)
                p_num = jnp.where(keep, p, 0.0) * inv
            acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
                p_num.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _enter_by_kind(run, interior, body)

        @pl.when(last_k_cond)
        def _():
            l = l_s[:]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            out = acc_s[:] / safe_l
            # l == 0 ⇔ the row has no attendable key (every logit -inf,
            # every weight exactly 0) — out is then 0 with zero grads,
            # in-kernel (the reference NaNs here, SURVEY §4).
            o_ref[0] = out.astype(o_ref.dtype)
            if save_lse:
                # Convert from log2 back to natural-log units for the
                # backward: lse = ln2·(m₂ + log2 l) = m + ln l — less
                # the row's part of an ALiBi bias by place, which the
                # running max carried for the whole row.
                lse2 = m_s[:] + jnp.log2(safe_l)
                if slope is not None and pos is None:
                    lse2 = lse2 - _alibi_row_shift(slope, bq)
                lse_ref[0] = _LN2 * lse2

    return kernel


def _aux_setup(mask, segment_ids, positions, batch, tq, tk, tq_p, tk_p,
               bq, bk, allow_redirect=True, k_of=None, q_of_t=None,
               alibi=None):
    """Specs (both grid orders) + args + presence flags for the optional
    (mask, segments, block-skip table) kernel inputs, shared by the
    forward and both backward passes — args are computed ONCE (the int8
    mask copy and the skip tables are O(T²)-read reductions; the dq and
    dk/dv passes must not each pay them again). ``specs_t`` carries index
    maps for the dk/dv grid ``(b, kj, qi)`` (Q innermost).

    The skip tables (segment per-block [min, max], dense any-unmasked
    summary) are whole-array SMEM inputs pre-broadcast to the flat batch —
    kernels index them by raw program ids, no per-input batch maps.

    ``k_of`` / ``q_of_t``: banded-window grid translations — map the
    (batch, outer, inner, prefetch-refs) grid coordinates to the ACTUAL
    K block (normal grids) / Q block (transposed grid). None = identity
    (the grid axis IS the block index). Banded grids carry no dense mask
    (asserted), so only the per-position vec specs need them."""
    kof = k_of or (lambda b, i, j, rs: j)
    qot = q_of_t or (lambda b, j, i, rs: i)
    assert mask is None or (k_of is None and q_of_t is None), \
        'banded window grids do not support dense masks'
    nqb, nkb = tq_p // bq, tk_p // bk
    nb = int(math.prod(batch)) if batch else 1
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    specs, specs_t, args = [], [], []
    runsum = None
    maskf = mask_idx = mlead = None
    if mask is not None:
        maskf, mask_idx, mlead = _mask_setup(mask, batch, tq, tk,
                                             tq_p, tk_p)
        # 3-state per-tile summary: 0 = every entry masked (tile skipped),
        # 1 = mixed (mask block streamed + applied), 2 = no entry masked
        # (tile computed, mask block neither streamed nor applied).
        # Dropped when it would crowd SMEM (mask then streams for every
        # tile, the round-2 behavior) — and off-TPU (``allow_redirect``):
        # the redirect needs a scalar-prefetch grid, which only the slow
        # Mosaic interpreter emulates, and the DMA it saves isn't real on
        # the test mesh anyway.
        if allow_redirect and nb * nqb * nkb * 4 <= _RUNSUM_SMEM_CAP:
            tile = maskf.reshape(maskf.shape[0], nqb, bq, nkb, bk)
            state = jnp.where(tile.min(axis=(2, 4)) == 1, 0,
                              jnp.where(tile.max(axis=(2, 4)) == 0, 2, 1))
            runsum = jnp.broadcast_to(
                state.reshape(*mlead, nqb, nkb),
                (*batch, nqb, nkb)).reshape(nb, nqb, nkb).astype(jnp.int32)

        if runsum is None:
            mask_map = lambda b, i, j, *rs: (mask_idx(b), i, j)  # noqa: E731
        else:  # scalar-prefetch mode: maps receive the summary ref
            # Scalar-prefetch redirection: non-mixed tiles (skipped, or
            # computed mask-free) alias block (0, 0, 0), so consecutive
            # programs re-use the resident copy and their O(bq·bk) mask
            # DMA disappears.
            def mask_map(b, i, j, *rs):
                mixed = rs[0][b, i, j] == 1
                return (jnp.where(mixed, mask_idx(b), 0),
                        jnp.where(mixed, i, 0), jnp.where(mixed, j, 0))
        specs.append(pl.BlockSpec((1, bq, bk), mask_map))
        specs_t.append(pl.BlockSpec(
            (1, bq, bk), lambda b, j, i, *rs: mask_map(b, i, j, *rs)))
        args.append(maskf)
    for pair, setup in ((segment_ids, _seg_setup), (positions, _pos_setup)):
        if pair is None:
            continue
        vqf, vq_idx, qlead, vkf, vk_idx, klead = setup(
            pair, batch, tq, tk, tq_p, tk_p)
        specs.append(pl.BlockSpec(
            (1, bq, 1), lambda b, i, j, *rs, f=vq_idx: (f(b), i, 0)))
        specs.append(pl.BlockSpec(
            (1, 1, bk),
            lambda b, i, j, *rs, f=vk_idx: (f(b), 0, kof(b, i, j, rs))))
        specs_t.append(pl.BlockSpec(
            (1, bq, 1),
            lambda b, j, i, *rs, f=vq_idx: (f(b), qot(b, j, i, rs), 0)))
        specs_t.append(pl.BlockSpec(
            (1, 1, bk), lambda b, j, i, *rs, f=vk_idx: (f(b), 0, j)))
        args.extend([vqf, vkf])
        # Per-block [min, max] intervals, (nb, n_blocks, 2) in SMEM —
        # these drive the cross-segment / causal-future block skips.
        sq = vqf[..., 0].reshape(vqf.shape[0], nqb, bq)
        sk = vkf[:, 0].reshape(vkf.shape[0], nkb, bk)
        qmm = jnp.stack([sq.min(-1), sq.max(-1)], -1)
        kmm = jnp.stack([sk.min(-1), sk.max(-1)], -1)
        qmm = jnp.broadcast_to(qmm.reshape(*qlead, nqb, 2),
                               (*batch, nqb, 2)).reshape(nb, nqb, 2)
        kmm = jnp.broadcast_to(kmm.reshape(*klead, nkb, 2),
                               (*batch, nkb, 2)).reshape(nb, nkb, 2)
        specs.extend([smem_spec, smem_spec])
        specs_t.extend([smem_spec, smem_spec])
        args.extend([qmm, kmm])
    if alibi is not None:
        # Per-head ALiBi slopes: one f32 scalar per flat batch entry,
        # whole-array SMEM (kernels index by program id 0). Lead dims
        # broadcast like a mask's (e.g. (H,) against (B, H)).
        alead = _bcast_lead('alibi_slopes', alibi.shape, batch, 0)
        aflat = jnp.broadcast_to(
            jnp.asarray(alibi, jnp.float32).reshape(alead),
            tuple(batch)).reshape(nb)
        specs.append(smem_spec)
        specs_t.append(smem_spec)
        args.append(aflat)
    # prefetch == a live summary: the call becomes a scalar-prefetch grid
    # and kernels pop the summary as ref 0.
    flags = (mask is not None, segment_ids is not None,
             positions is not None, alibi is not None, runsum is not None)
    return specs, specs_t, args, flags, runsum


# Kernel name -> the device scope its call runs under.
_KERNEL_SCOPES = {
    'flash_fwd': 'ops.flash_fwd',
    'flash_fwd_int8': 'ops.flash_fwd',
    'flash_fwd_bounded': 'ops.flash_fwd',
    'flash_bwd_dq': 'ops.flash_bwd_dq',
    'flash_bwd_dkv': 'ops.flash_bwd_dkv',
    'flash_bwd_fused': 'ops.flash_bwd_dkv',
}


def _pallas_call(name, kernel, grid, in_specs, out_specs, scratch,
                 out_shape, interpret, prefetch, vmem_limit_bytes=None):
    """Build + invoke: a scalar-prefetch grid when any prefetch operands
    are live (the dense-mask block-skip summary and/or the window band
    offset), a plain grid otherwise. Prefetch refs reach both the index
    maps (as trailing ``*rs`` args — the same lambdas serve both modes)
    and the kernel (as leading refs). ``interpret=True`` under prefetch
    upgrades to the Mosaic TPU interpreter — the default HLO interpreter
    cannot evaluate scalar-prefetch grids ("MLIR translation rule for
    primitive 'program_id' not found for platform cpu").

    ``name`` is the kernel's stable name in a device trace (a plain
    identifier: Mosaic takes it as a symbol); the call runs under the
    :func:`~distributed_dot_product_tpu.utils.scopes.device_scope` of the
    kernel's family (``_KERNEL_SCOPES``). ``vmem_limit_bytes``: the
    scoped-VMEM limit the call states (None: the compiler's default)."""
    prefetch = [p for p in prefetch if p is not None]
    interp = interpret
    if interpret is True and prefetch:
        interp = pltpu.InterpretParams()
    params = {}
    if vmem_limit_bytes is not None:
        params['compiler_params'] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes)
    if prefetch:
        call = kernel_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape, interpret=interp, name=name, **params)
    else:
        call = kernel_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, scratch_shapes=scratch,
                              out_shape=out_shape, interpret=interp,
                              name=name, **params)

    def run(*args):
        with device_scope(_KERNEL_SCOPES[name]):
            return call(*prefetch, *args)
    return run


def _quantize_rows(x, nb_x, t, d):
    """Per-row symmetric int8 quantization: ``x ≈ x_i8 · s_row`` with
    ``s_row = max|row|/127`` (eps-clamped so all-zero rows stay finite).
    The rounding error is ≤ s_row/2 per element — ~0.4% of the row's max,
    the class of error bf16 inputs already carry."""
    x32 = x.astype(jnp.float32).reshape(nb_x, t, d)
    sx = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0,
                     1e-20)
    xi = jnp.round(x32 / sx).astype(jnp.int8)
    return xi, sx


def _kv_group(q, k):
    """Grouped-query (GQA/MQA) factor: q may carry more heads than k/v —
    lead dims must match except the head axis (-3), which must divide.
    Returns how many consecutive flat q-batch indices share one kv block
    (1 = standard multi-head). The flat mapping is ``b_kv = b // group``
    because the head axis is the innermost lead dim."""
    if tuple(k.shape[:-2]) == tuple(q.shape[:-2]):
        return 1
    if (q.ndim < 3 or k.ndim != q.ndim
            or k.shape[:-3] != q.shape[:-3]
            or q.shape[-3] % k.shape[-3]):
        raise ValueError(
            f'k/v lead dims {k.shape[:-2]} must equal q lead dims '
            f'{q.shape[:-2]} or differ only on the head axis (-3) with '
            f'q heads divisible by kv heads (GQA)')
    return q.shape[-3] // k.shape[-3]


def _flash_fwd_impl(q, k, v, mask, causal_offset, scale, causal, interpret,
                    mode='exact', save_lse=False, segment_ids=None,
                    positions=None, window=None, alibi=None, qk_quant=None,
                    dropout_rate=0.0, dropout_seed=None, kv_offset=0):
    *batch, tq, d = q.shape
    tk = k.shape[-2]
    d_v = v.shape[-1]
    # Canonicalize the softmax mode BEFORE any grid/chunk eligibility
    # check: dropout rides the exact kernel only, quantization's running
    # max is already correct on the dequantized scores, and the
    # Cauchy-Schwarz bound does not cover the additive ALiBi term (≤ 0
    # only for non-negative slopes, and slopes may be traced) — in each
    # case 'bounded' is an optimization hint that resolves to the exact
    # kernel, which must then still be eligible for the trapezoid pair
    # grid (both the beyond-cap chunking below and the in-cap selection).
    if mode == 'bounded' and (dropout_rate or qk_quant == 'int8'
                              or alibi is not None):
        mode = 'exact'
    if _trap_eligible(causal, window, mask, positions, causal_offset,
                      kv_offset, mode, interpret):
        # Beyond-cap pair tables: split the Q rows into chunks that each
        # fit, and run each chunk through this same impl with a shifted
        # row offset — every chunk then takes the trapezoid grid. Row
        # chunking is exact: outputs are per-row, per-row int8 scales are
        # per-row, the dropout hash keys on global coordinates (which the
        # shifted offset preserves), and seg_q slices with its rows.
        bq0, bk0 = _block_sizes(tq, tk, q.dtype, d_total=d + d_v)
        bounds = _trap_chunk_bounds(
            int(causal_offset) - int(kv_offset), tq, tk, bq0, bk0)
        if len(bounds) > 1:
            outs, lses = [], []
            for r0, r1 in bounds:
                seg = segment_ids
                if seg is not None:
                    seg = (seg[0][..., r0:r1], seg[1])
                res = _flash_fwd_impl(
                    q[..., r0:r1, :], k, v, None, causal_offset + r0,
                    scale, causal, interpret, mode, save_lse=save_lse,
                    segment_ids=seg, alibi=alibi, qk_quant=qk_quant,
                    dropout_rate=dropout_rate, dropout_seed=dropout_seed,
                    kv_offset=kv_offset)
                if save_lse:
                    outs.append(res[0])
                    lses.append(res[1])
                else:
                    outs.append(res)
            out = jnp.concatenate(outs, axis=-2)
            if save_lse:
                return out, jnp.concatenate(lses, axis=-1)
            return out
    nb = int(math.prod(batch)) if batch else 1
    kv_group = _kv_group(q, k)
    nbk = nb // kv_group
    # (1, 2) int32 input: the global indices of query row 0 and key
    # column 0 (possibly traced, e.g. lax.axis_index under shard_map /
    # the ring fold's rotating owner). Always fed — a dead scalar read
    # costs nothing and keeps the kernel signatures uniform.
    off = jnp.stack([jnp.asarray(causal_offset, jnp.int32),
                     jnp.asarray(kv_offset, jnp.int32)]).reshape(1, 2)
    off_spec = pl.BlockSpec((1, 2), lambda b, i, j, *rs: (0, 0))

    allow_redirect = (not interpret) or _REDIRECT_ON_INTERPRET
    streams_mask = mask is not None and _mask_streams_per_tile(
        nb, tq, tk, q.dtype, d + d_v, allow_redirect)
    bq, bk = _block_sizes(tq, tk, q.dtype, d_total=d + d_v,
                          has_mask=streams_mask)
    # exp2 trick: fold scale·log2(e) into q so the kernel's score block
    # needs no per-element multiply (exp2 replaces exp, whose hardware
    # lowering is exp2(x·log2e) anyway). One extra rounding of q, same
    # class of error as the bf16 inputs themselves.
    quantized = qk_quant == 'int8'
    sqf = skr = None
    if quantized:
        # int8 QK^T: the fwd score matmul runs on the int8 MXU path
        # (~1.65x bf16, measured on v5e); the scale*log2e fold rides the
        # q-row scale vector instead of q itself.
        qi8, sq = _quantize_rows(q, nb, tq, d)
        ki8, sk = _quantize_rows(k, nbk, tk, d)
        qf = _pad_dim(qi8, 1, bq)
        kf = _pad_dim(ki8, 1, bk)
        sqf = _pad_dim(sq * (scale * _LOG2E), 1, bq)       # (nb, Tq_p, 1)
        skr = _pad_dim(jnp.swapaxes(sk, 1, 2), 2, bk)      # (nbk, 1, Tk_p)
    else:
        q2 = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
        qf = _pad_dim(q2.reshape(nb, tq, d), 1, bq)
        kf = _pad_dim(k.reshape(nbk, tk, d), 1, bk)
    vf = _pad_dim(v.reshape(nbk, tk, d_v), 1, bk)
    tq_p, tk_p = qf.shape[1], kf.shape[1]
    nqb, nkb = tq_p // bq, tk_p // bk

    # Banded window grid: with a contiguous causal window, each Q block
    # only ever folds the ~window/bk K blocks of its band — shrink the K
    # grid axis to the band and select the actual K block in the index
    # maps from the (scalar-prefetched) global row offset. Out-of-band
    # blocks then cost NOTHING (no grid step, no DMA): compute and HBM
    # traffic are O(Tq·window). Dense masks keep the full grid (their
    # runsum tables are indexed by absolute blocks and T²-masks don't
    # arise in the long-context configs that use windows); explicit
    # positions keep it too (a shard's rows are not one contiguous band).
    banded = (window is not None and causal and mask is None
              and positions is None
              and ((not interpret) or _BAND_ON_INTERPRET))
    band_fn = bandoff = kof = None
    trap = trap_pre = None
    if banded:
        band = _band_size(bq, bk, window, nkb)

        def band_fn(i, off_s):
            return _band_lo((off_s + i * bq - (window - 1)) // bk,
                            nkb, band)

        def kof(b, i, j, rs):
            # Single source of truth for the band's K-block translation —
            # the q/k/v BlockSpec maps and the aux (segment) maps both
            # derive from it (rs[0] is the prefetched row−column offset).
            return band_fn(i, rs[0][0]) + j
        bandoff = (off[0, 0] - off[0, 1]).reshape(1)
        grid = (nb, nqb, band)
    else:
        grid = (nb, nqb, nkb)
        if _trap_eligible(causal, window, mask, positions, causal_offset,
                          kv_offset, mode, interpret):
            qtab, ktab, ext = _trap_tables(
                int(causal_offset) - int(kv_offset), nqb, nkb, bq, bk)
            if qtab.shape[0] <= _TRAP_MAX_PAIRS:
                trap = True
                trap_pre = [qtab, ktab, ext]
                grid = (nb, int(qtab.shape[0]))
    k_map = lambda b, i, j, *rs: (  # noqa: E731
        b // kv_group, j if kof is None else kof(b, i, j, rs), 0)

    specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j, *rs: (b, i, 0)),
        pl.BlockSpec((1, bk, d), k_map),
        pl.BlockSpec((1, bk, d_v), k_map),
    ]
    args = [qf, kf, vf]
    if quantized:
        specs += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j, *rs: (
                b // kv_group, 0,
                j if kof is None else kof(b, i, j, rs))),
        ]
        args += [sqf, skr]
    dropout = float(dropout_rate) if dropout_rate else None
    seed_specs, seed_args = [], []
    if dropout is not None:
        seed_specs = [pl.BlockSpec((1, 1), lambda b, i, j, *rs: (0, 0))]
        seed_args = [jnp.asarray(dropout_seed, jnp.int32).reshape(1, 1)]
    aux_specs, _, aux_args, flags, runsum = _aux_setup(
        mask, segment_ids, positions, batch, tq, tk, tq_p, tk_p, bq, bk,
        allow_redirect=allow_redirect, k_of=kof,
        alibi=(None if alibi is None else alibi * _LOG2E))

    out_specs = pl.BlockSpec((1, bq, d_v), lambda b, i, j, *rs: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((nb, tq_p, d_v), v.dtype)
    if save_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, bq, 1),
                                  lambda b, i, j, *rs: (b, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((nb, tq_p, 1), jnp.float32)]

    def walk(rel):
        if trap:
            return _trap_tables(rel, nqb, nkb, bq, bk)[:2]
        return _grid_walk(nqb, grid[2],
                          (lambda i: band_fn(i, rel)) if banded else None)

    grid_kind = 'trap' if trap else 'band' if banded else 'full'

    def run_exact(*_):
        name = 'flash_fwd_int8' if quantized else 'flash_fwd'
        _note_blocks(name, grid_kind, walk, causal, causal_offset,
                     kv_offset, bq, bk, tk, window, flags)
        kernel = _make_fwd_kernel(causal, bq, bk, tk, *flags, save_lse,
                                  window, band_fn, quantized, dropout,
                                  trap=bool(trap))
        in_specs = [off_spec] + seed_specs + specs + aux_specs
        o_specs = out_specs
        if trap:
            in_specs = _wrap_specs_pairs(in_specs)
            o_specs = (_wrap_specs_pairs(o_specs) if save_lse
                       else _wrap_specs_pairs([o_specs])[0])
        return _pallas_call(
            name, kernel, grid, in_specs, o_specs,
            _scratch(bq, d_v), out_shape,
            interpret, trap_pre if trap else [bandoff, runsum],
        )(off, *seed_args, *args, *aux_args)

    if mode == 'bounded':
        # Per-row upper bound on the (log2-unit) scores via Cauchy-Schwarz:
        # |s2_ij| ≤ ‖q2_i‖·‖k_j‖ ≤ ‖q2_i‖·max_j‖k_j‖. The +1 covers fp32
        # accumulation rounding in the kernel's dot.
        q32 = q2.reshape(nb, tq, d).astype(jnp.float32)
        k32 = k.reshape(nbk, tk, d).astype(jnp.float32)
        qn = jnp.sqrt(jnp.sum(q32 * q32, axis=-1, keepdims=True))
        kn = jnp.sqrt(jnp.max(jnp.sum(k32 * k32, axis=-1), axis=-1))
        if kv_group > 1:   # per-kv-head max norm → its q-head group
            kn = jnp.repeat(kn, kv_group)
        mvec = qn * kn[:, None, None] + 1.0                 # (nb, Tq, 1)
        mvecf = _pad_dim(mvec, 1, bq)
        mvec_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0))

        def run_bounded(*_):
            _note_blocks('flash_fwd_bounded', grid_kind, walk, causal,
                         causal_offset, kv_offset, bq, bk, tk, window,
                         flags)
            kernel = _make_fwd_kernel_bounded(
                causal, bq, bk, tk, *flags, save_lse, window, band_fn)
            return _pallas_call(
                'flash_fwd_bounded',
                kernel, grid, [off_spec] + specs + [mvec_spec] + aux_specs,
                out_specs, _scratch(bq, d_v)[1:],  # no m buffer
                out_shape, interpret, [bandoff, runsum],
            )(off, *args, mvecf, *aux_args)

        # Safety net: the bound shift is only exact while
        # bound − true_rowmax stays inside fp32's exponent range; since
        # true_rowmax ≥ −‖q2_i‖·max‖k‖, the worst-case gap is 2·bound.
        # When any row could exceed the safe gap, run the exact kernel
        # instead (lax.cond: both are compiled, one executes) — 'bounded'
        # is then an optimization hint, never a correctness trade.
        worst_gap = 2.0 * jnp.max(mvec)
        res = jax.lax.cond(worst_gap <= _BOUNDED_SAFE_GAP,
                           run_bounded, run_exact)
    else:
        res = run_exact()
    out, lse = res if save_lse else (res, None)
    out = out[:, :tq].reshape(*batch, tq, d_v)
    # No post-hoc empty-row zeroing: -inf masking makes the kernels emit
    # exactly 0 for rows with no attendable key (see _apply_masks), so the
    # O(Tq·Tk) any-valid reduction the wrapper used to run is pure cost.
    if save_lse:
        return out, lse[:, :tq, 0].reshape(*batch, tq)
    return out


def _scratch(bq, d_v):
    return [pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d_v), jnp.float32)]


def _make_fwd_kernel_bounded(causal, bq, bk, kv_len, has_mask, has_seg,
                             has_pos, has_alibi, has_mask_skip, save_lse,
                             window=None, band_fn=None):
    """Forward kernel for ``softmax_mode='bounded'``: the per-row shift is
    a precomputed upper bound on the row max (Cauchy-Schwarz,
    ``‖q_i‖·max_j‖k_j‖``, fed as an input), so the kernel drops the
    running-max lane reduction, both correction multiplies and the m
    scratch — the ablated cost is ~15% of kernel time at d=64 (the max
    reduce is the single most expensive VPU op in the exact kernel).

    Softmax is shift-invariant, so the result matches the exact kernel
    whenever ``bound − true_rowmax`` stays within fp32's exponent range
    (the wrapper guarantees this by falling back to the exact kernel when
    the worst-case gap ``2·max(bound)`` exceeds ``_BOUNDED_SAFE_GAP``).
    The bound does not cover an ALiBi bias: the wrapper sends such calls
    to the exact kernel.
    """
    assert not has_alibi, 'the norm bound does not cover the ALiBi bias'

    def kernel(*refs):
        if band_fn is not None:
            bandoff_ref, *refs = refs
        if has_mask_skip:
            runsum_ref, *refs = refs
        else:
            runsum_ref = None
        off_ref, q_ref, k_ref, v_ref, m_ref, *rest = refs
        mask_ref, seg, pos, _, rest = _split_aux(
            rest, has_mask, has_seg, has_pos)
        if save_lse:
            o_ref, lse_ref, l_s, acc_s = rest
        else:
            (o_ref, l_s, acc_s), lse_ref = rest, None
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        ki = kj if band_fn is None else band_fn(qi, bandoff_ref[0]) + kj
        last_k = pl.num_programs(2) - 1

        @pl.when(kj == 0)
        def _():
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        run = _run_pred(causal, off_ref, qi, ki, bq, bk,
                        pl.program_id(0), seg, pos, runsum_ref, window)
        interior = _block_interior(causal, off_ref, qi, ki, bq, bk,
                                   kv_len, window,
                                   has_mask or has_seg or has_pos)

        def body(interior):
            q = q_ref[0]                                    # (BQ, d)
            k = k_ref[0]                                    # (BK, d)
            v = v_ref[0]                                    # (BK, dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (BQ, BK), log2 units
            mask_live = (None if runsum_ref is None else
                         runsum_ref[pl.program_id(0), qi, ki] == 1)
            s = _apply_masks(s, qi, ki, bq, bk, causal, kv_len,
                             mask_ref, off_ref, seg, pos, mask_live,
                             window, None, interior)
            p = jnp.exp2(s - m_ref[0])                      # bound shift
            l_s[:] += p.sum(axis=-1, keepdims=True)
            acc_s[:] += jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _enter_by_kind(run, interior, body)

        @pl.when(kj == last_k)
        def _():
            l = l_s[:]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            # l == 0: fully-masked rows (all weights underflowed to 0) —
            # acc is 0 too, so the output is the required 0.
            o_ref[0] = (acc_s[:] / safe_l).astype(o_ref.dtype)
            if save_lse:
                lse_ref[0] = _LN2 * (m_ref[0] + jnp.log2(safe_l))

    return kernel


# The fused backward keeps dq for one batch-head in VMEM: a float32
# (Tq_p, d) accumulator and the double-buffered output block it is cast
# into. v5e has 128 MiB of VMEM; the budget keeps the whole call under
# half of it when the gradients are float32 (16 MiB: T = 32768 at d 128).
_FUSED_DQ_BYTES = 16 * 1024 * 1024
# What the K-major body needs beside dq at the largest blocks
# (_bwd_block_sizes): its streams and the (bq, bk) score temporaries.
_BWD_VMEM_BASE = 16 * 1024 * 1024

_BWD_TRACES = TraceSinks()


def flash_bwd_traces():
    """Collect which form each flash backward takes while the block
    runs: one dict ``{'form', 'reason', 'only', 'dq_bytes',
    'vmem_limit_bytes'}`` per TRACE of ``_flash_bwd_impl``'s kernels.
    ``form`` is ``'fused'`` (one K-major kernel gives dq, dk and dv) or
    ``'split'`` (the dq and dk/dv kernels), ``reason`` says why a split
    (None when fused), ``dq_bytes`` is the float32 dq accumulator of one
    batch-head that the choice was made by::

        with flash_bwd_traces() as traces:
            step.lower(*args).compile()
        assert {t['form'] for t in traces} == {'fused'}
    """
    return _BWD_TRACES.open()


_BLOCK_TRACES = TraceSinks()


def flash_block_traces():
    """Collect what each flash kernel's blocks are by KIND while the
    block runs: one dict ``{'kernel', 'grid', 'run_blocks',
    'interior_blocks', 'alibi'}`` per TRACED kernel. ``kernel`` is the
    Pallas name (``flash_fwd``, ``flash_bwd_fused``, …); ``grid`` is
    ``'trap'`` (the causal pair grid), ``'band'`` (the window's) or
    ``'full'``; ``run_blocks`` counts, a batch-head, the grid's blocks
    that ``_causal_run`` lets compute and ``interior_blocks`` those of
    them that ``_block_interior`` sends through the branch without the
    causal / window / padding selects (0 for a call whose masks are
    data: it has no kinds). Both are None where an offset is traced (the
    kind is then a run-time scalar), ``run_blocks`` also where data
    decides which blocks run. ``alibi`` says how the bias is built:
    None, ``'vector'`` (a ``(1, bk)`` vector a block, the row's part
    carried in the logsumexp's domain: ``_alibi_bias``) or
    ``'positions'`` (a full block from explicit position vectors)::

        with flash_block_traces() as traces:
            step.lower(*args)
        assert traces[0]['interior_blocks'] == 120      # of 136
    """
    return _BLOCK_TRACES.open()


def _note_blocks(kernel, grid, walk, causal, causal_offset, kv_offset, bq,
                 bk, kv_len, window, flags):
    """Tell the open :func:`flash_block_traces` blocks about one kernel:
    ``walk(rel)`` gives the (Q block, K block) index arrays of the grid's
    programs at the static row − column offset ``rel``, counted by the
    kernels' own two predicates."""
    if not _BLOCK_TRACES:
        return
    has_mask, has_seg, has_pos, has_alibi, _ = flags
    data = has_mask or has_seg or has_pos
    run_blocks = interior_blocks = None
    static = _static_offsets(causal_offset, kv_offset)
    if static is not None:
        with jax.ensure_compile_time_eval():
            off = np.asarray([static])
            qi, ki = (np.asarray(x) for x in walk(static[0] - static[1]))
            run = np.broadcast_to(
                _causal_run(causal, off, qi, ki, bq, bk, window), qi.shape)
            inside = _block_interior(causal, off, qi, ki, bq, bk, kv_len,
                                     window, data)
        if not data:
            run_blocks = int(run.sum())
        interior_blocks = (0 if inside is None
                           else int((run & inside).sum()))
    alibi = None
    if has_alibi:
        alibi = 'positions' if has_pos else 'vector'
    _BLOCK_TRACES.note({'kernel': kernel, 'grid': grid,
                        'run_blocks': run_blocks,
                        'interior_blocks': interior_blocks, 'alibi': alibi})


def _grid_walk(n_outer, n_inner, inner_lo=None):
    """Block indices ``(outer, inner)`` of a 3-axis grid's programs, a
    batch-head: every pair, or with ``inner_lo`` (a band's first inner
    block by outer block) the band's."""
    outer = np.repeat(np.arange(n_outer), n_inner)
    inner = np.tile(np.arange(n_inner), n_outer)
    if inner_lo is not None:
        inner = np.asarray(inner_lo(outer)) + inner
    return outer, inner


def _bwd_form(only, tq_p, d, dq_dtype):
    """Fused or split, from what the call can see: which passes are asked
    and whether one batch-head's float32 dq ``(tq_p, d)``, as VMEM holds
    it, fits its budget.
    Returns ``(fused, vmem_limit_bytes)`` and tells the open
    :func:`flash_bwd_traces` blocks."""
    lanes = -(-d // 128) * 128      # VMEM tiles pad the minor dim
    dq_bytes = tq_p * lanes * 4
    reason = vmem_limit = None
    if only != 'both':
        reason = f'only={only!r}: a pass asked alone'
    elif dq_bytes > _FUSED_DQ_BYTES:
        reason = (f'dq accumulator {dq_bytes} B a batch-head is past the '
                  f'{_FUSED_DQ_BYTES} B budget')
    else:
        vmem_limit = (_BWD_VMEM_BASE + dq_bytes
                      + 2 * tq_p * lanes * jnp.dtype(dq_dtype).itemsize)
    _BWD_TRACES.note({'form': 'split' if reason else 'fused',
                      'reason': reason, 'only': only, 'dq_bytes': dq_bytes,
                      'vmem_limit_bytes': vmem_limit})
    return reason is None, vmem_limit


def _make_dq_kernel(scale, causal, bq, bk, kv_len, has_mask, has_seg,
                    has_pos, has_alibi, has_mask_skip, window=None,
                    band_fn=None, quantized=False, dropout=None,
                    trap=False):
    def kernel(*refs):
        if trap:
            tq_ref, tk_ref, ext_ref, *refs = refs
        elif band_fn is not None:
            bandoff_ref, *refs = refs
        if has_mask_skip:
            runsum_ref, *refs = refs
        else:
            runsum_ref = None
        off_ref, *refs = refs
        if dropout is not None:
            seed_ref, *refs = refs
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         *rest) = refs
        quant = None
        if quantized:
            sqf_ref, skr_ref, sqc_ref, skc_ref, *rest = rest
            quant = (sqf_ref, skr_ref)
        mask_ref, seg, pos, alibi_ref, rest = _split_aux(
            rest, has_mask, has_seg, has_pos, has_alibi)
        shift_s = None
        if has_alibi and not has_pos:
            *rest, shift_s = rest
        dq_ref, dq_acc = rest
        if trap:
            p = pl.program_id(1)
            qi = tq_ref[p]
            ki = tk_ref[p]
            first_k = ki == 0
            last_k_cond = ki == ext_ref[qi] - 1
        else:
            qi = pl.program_id(1)
            kj = pl.program_id(2)
            ki = kj if band_fn is None else band_fn(qi, bandoff_ref[0]) + kj
            first_k = kj == 0
            last_k_cond = kj == pl.num_programs(2) - 1

        @pl.when(first_k)
        def _():
            dq_acc[:] = jnp.zeros_like(dq_acc)

        pid_b = pl.program_id(0)  # hoisted: program_id inside a
        # pl.when body is not substituted by the plain interpreter
        slope = None if alibi_ref is None else alibi_ref[pid_b]
        if shift_s is not None:
            _fill_row_shift(shift_s, slope, bq, 2 if trap else 3)
        run = _run_pred(causal, off_ref, qi, ki, bq, bk,
                        pl.program_id(0), seg, pos, runsum_ref, window)
        interior = _block_interior(causal, off_ref, qi, ki, bq, bk,
                                   kv_len, window,
                                   has_mask or has_seg or has_pos)

        def body(interior):
            # q_ref holds q·(scale·log2e) and lse_ref holds lse·log2e (both
            # pre-folded by the wrapper, mirroring the forward's exp2
            # trick) so no per-score-element multiply is needed here:
            # p = exp(s−lse) = exp2(s₂ − lse₂). Quantized: the score
            # recompute reuses the int8 dot (consistent with the saved
            # lse); the ds·k contraction dequantizes k in-block.
            v = v_ref[0]                                    # (BK, dv)
            g = g_ref[0]                                    # (BQ, dv)
            s = _score_block(q_ref, k_ref, quant)  # (BQ, BK), log2 units
            mask_live = (None if runsum_ref is None else
                         runsum_ref[pl.program_id(0), qi, ki] == 1)
            s = _apply_masks(s, qi, ki, bq, bk, causal, kv_len,
                             mask_ref, off_ref, seg, pos, mask_live,
                             window, slope, interior)
            lse = lse_ref[0]                                # (BQ, 1)
            if shift_s is not None:
                lse = lse + shift_s[:]     # the scores' row-shifted domain
            p = jnp.exp2(s - lse)                           # (BQ, BK)
            dp = jax.lax.dot_general(
                g, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BQ, BK)
            if dropout is not None:
                # Same element-coordinate mask as the forward; Δ already
                # equals rowsum(m̃·a ⊙ dp) by the rowsum(dO⊙O) identity.
                keep, inv = _dropout_keep(seed_ref, pid_b, qi, ki,
                                          bq, bk, dropout, off_ref, pos)
                dp = jnp.where(keep, dp, 0.0) * inv
            if quantized:
                k_op = (k_ref[0].astype(jnp.float32)
                        * skc_ref[0]).astype(v.dtype)
            else:
                k_op = k_ref[0]
            ds = (p * (dp - delta_ref[0])).astype(k_op.dtype)
            dq_acc[:] += scale * jax.lax.dot_general(
                ds, k_op, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BQ, d)

        _enter_by_kind(run, interior, body)

        @pl.when(last_k_cond)
        def _():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(scale, causal, bq, bk, kv_len, has_mask, has_seg,
                     has_pos, has_alibi, has_mask_skip, window=None,
                     band_fn=None, quantized=False, dropout=None,
                     trap=False, nqb=None, fused=False):
    """The K-major backward body: dk/dv accumulate over each K block's Q
    run. ``fused``: the same walk also gives dq — ``ds`` of a pair is
    built once and ``ds·k`` is added to row block ``qi`` of a float32
    ``(nqb, bq, d)`` accumulator that stays in VMEM for the whole
    batch-head (zeroed at its first pair, cast out at its last). K blocks
    are walked in ascending order, so each dq row block sums its terms in
    the order the dq kernel sums them."""
    def kernel(*refs):
        if trap:
            tq_ref, tk_ref, qlo_ref, *refs = refs
        elif band_fn is not None:
            bandoff_ref, *refs = refs
        if has_mask_skip:
            runsum_ref, *refs = refs
        else:
            runsum_ref = None
        off_ref, *refs = refs
        if dropout is not None:
            seed_ref, *refs = refs
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         *rest) = refs
        quant = None
        if quantized:
            sqf_ref, skr_ref, sqc_ref, skc_ref, *rest = rest
            quant = (sqf_ref, skr_ref)
        mask_ref, seg, pos, alibi_ref, rest = _split_aux(
            rest, has_mask, has_seg, has_pos, has_alibi)
        shift_s = None
        if has_alibi and not has_pos:
            *rest, shift_s = rest
        if fused:
            dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = rest
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = rest
        if trap:
            # Transposed trapezoid: K-major pair walk; each K block's Q
            # run starts at its first causally-visible Q block and always
            # ends at the bottom row block.
            p = pl.program_id(1)
            qi = tq_ref[p]
            kj = tk_ref[p]
            first_q = qi == qlo_ref[kj]
            last_q_cond = qi == nqb - 1
        else:
            kj = pl.program_id(1)
            qr = pl.program_id(2)
            # Banded: qr sweeps only the Q blocks whose window band
            # touches this K block; qi is the ACTUAL Q block index.
            qi = qr if band_fn is None else band_fn(kj, bandoff_ref[0]) + qr
            first_q = qr == 0
            last_q_cond = qr == pl.num_programs(2) - 1

        if fused:
            # The batch-head's first and last program of the walk.
            if trap:
                first_pair = p == 0
                last_pair = p == pl.num_programs(1) - 1
            else:
                first_pair = jnp.logical_and(kj == 0, first_q)
                last_pair = jnp.logical_and(
                    kj == pl.num_programs(1) - 1, last_q_cond)

            @pl.when(first_pair)
            def _():
                def zero(i, carry):
                    dq_acc[i] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)
                    return carry
                jax.lax.fori_loop(0, dq_acc.shape[0], zero, 0)

        @pl.when(first_q)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        pid_b = pl.program_id(0)  # hoisted: program_id inside a
        # pl.when body is not substituted by the plain interpreter
        slope = None if alibi_ref is None else alibi_ref[pid_b]
        if shift_s is not None:
            _fill_row_shift(shift_s, slope, bq, 2 if trap else 3)
        run = _run_pred(causal, off_ref, qi, kj, bq, bk,
                        pl.program_id(0), seg, pos, runsum_ref, window)
        interior = _block_interior(causal, off_ref, qi, kj, bq, bk,
                                   kv_len, window,
                                   has_mask or has_seg or has_pos)

        def body(interior):
            # q_ref / lse_ref are pre-folded by ·(scale·log2e) / ·log2e as
            # in the dq kernel. dk wants scale·dsᵀ·q with the ORIGINAL q;
            # the dot below uses the folded q, so divide the accumulator
            # update by log2e once per (BK, d) block. Quantized: q is
            # dequantized in-block with its RAW row scales, so the update
            # multiplies by the plain softmax scale instead.
            v = v_ref[0]                                    # (BK, dv)
            g = g_ref[0]                                    # (BQ, dv)
            s = _score_block(q_ref, k_ref, quant)  # (BQ, BK), log2 units
            mask_live = (None if runsum_ref is None else
                         runsum_ref[pl.program_id(0), qi, kj] == 1)
            s = _apply_masks(s, qi, kj, bq, bk, causal, kv_len,
                             mask_ref, off_ref, seg, pos, mask_live,
                             window, slope, interior)
            lse = lse_ref[0]                                # (BQ, 1)
            if shift_s is not None:
                lse = lse + shift_s[:]     # the scores' row-shifted domain
            p = jnp.exp2(s - lse)                           # (BQ, BK)
            p_num = p
            if dropout is not None:
                keep, inv = _dropout_keep(seed_ref, pid_b, qi, kj,
                                          bq, bk, dropout, off_ref, pos)
                p_num = jnp.where(keep, p, 0.0) * inv
            dv_acc[:] += jax.lax.dot_general(
                p_num.astype(g.dtype), g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BK, dv)
            dp = jax.lax.dot_general(
                g, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BQ, BK)
            if dropout is not None:
                dp = jnp.where(keep, dp, 0.0) * inv
            if quantized:
                q_op = (q_ref[0].astype(jnp.float32)
                        * sqc_ref[0]).astype(v.dtype)
                dk_scale = scale
            else:
                q_op = q_ref[0]
                dk_scale = 1.0 / _LOG2E
            ds32 = p * (dp - delta_ref[0])
            ds = ds32.astype(q_op.dtype)
            dk_acc[:] += dk_scale * jax.lax.dot_general(
                ds, q_op, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BK, d)
            if fused:
                # The dq kernel's product, operands as it takes them.
                if quantized:
                    k_op = (k_ref[0].astype(jnp.float32)
                            * skc_ref[0]).astype(v.dtype)
                else:
                    k_op = k_ref[0]
                if k_op.dtype != ds.dtype:
                    ds = ds32.astype(k_op.dtype)
                dq_acc[qi] += scale * jax.lax.dot_general(
                    ds, k_op, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (BQ, d)

        _enter_by_kind(run, interior, body)

        @pl.when(last_q_cond)
        def _():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

        if fused:
            @pl.when(last_pair)
            def _():
                def flush(i, carry):
                    dq_ref[0, i] = dq_acc[i].astype(dq_ref.dtype)
                    return carry
                jax.lax.fori_loop(0, dq_acc.shape[0], flush, 0)

    return kernel


def _flash_bwd_impl(q, k, v, mask, causal_offset, out, lse, g, scale,
                    causal, interpret, grad_dtype=None, segment_ids=None,
                    positions=None, window=None, alibi=None, qk_quant=None,
                    dropout_rate=0.0, dropout_seed=None, kv_offset=0,
                    only='both'):
    """Blockwise flash backward, O(block²) score memory. Algebra: with
    ``p = exp(s − lse)`` (the softmax weights), ``dv = pᵀ·dO``,
    ``ds = p ⊙ (dO·vᵀ − Δ)`` where ``Δ = rowsum(dO ⊙ O)``,
    ``dq = scale·ds·k``, ``dk = scale·dsᵀ·q``. One fused K-major kernel
    gives all three where ``_bwd_form`` allows it; else (``only`` naming
    one pass, or dq past its VMEM budget) a dq pass and a dk/dv pass.

    Empty-row cotangents need no explicit zeroing: with -inf masking the
    recomputed weights of such rows are exactly 0 (``lse`` clamps to
    ``_NEG_BIG``), so every gradient term dies in-kernel. ``grad_dtype``
    overrides the output gradient dtype (the ring path accumulates
    per-block grads across W steps and wants fp32 partials rather than W
    roundings to bf16).
    """
    *batch, tq, d = q.shape
    tk = k.shape[-2]
    d_v = v.shape[-1]
    if only == 'both' and _trap_eligible(causal, window, mask, positions,
                                         causal_offset, kv_offset,
                                         'exact', interpret):
        rel = int(causal_offset) - int(kv_offset)
        bq0, bk0 = _bwd_block_sizes(tq, tk, q.dtype, d_total=d + d_v)
        q_bounds = _trap_chunk_bounds(rel, tq, tk, bq0, bk0)
        k_bounds = _trap_chunk_bounds_t(rel, tq, tk, bq0, bk0)
        if max(len(q_bounds), len(k_bounds)) > 1:
            # Beyond-cap chunking: every chunk's output is a DISJOINT
            # slice (dq rows from Q chunks, dk/dv rows from K chunks),
            # so nothing is partial-summed and peak memory matches the
            # unchunked program (an earlier Q-only variant summed fp32
            # dk/dv partials per chunk and OOMed a 16 GiB chip at
            # T=512K). Each per-chunk call runs only its own pass.
            dqs = []
            for r0, r1 in q_bounds:
                seg = segment_ids
                if seg is not None:
                    seg = (seg[0][..., r0:r1], seg[1])
                dq_c, _, _ = _flash_bwd_impl(
                    q[..., r0:r1, :], k, v, None, causal_offset + r0,
                    out[..., r0:r1, :], lse[..., r0:r1],
                    g[..., r0:r1, :], scale, causal, interpret,
                    grad_dtype=grad_dtype, segment_ids=seg, alibi=alibi,
                    qk_quant=qk_quant, dropout_rate=dropout_rate,
                    dropout_seed=dropout_seed, kv_offset=kv_offset,
                    only='dq')
                dqs.append(dq_c)
            dks, dvs = [], []
            for c0, c1 in k_bounds:
                seg = segment_ids
                if seg is not None:
                    seg = (seg[0], seg[1][..., c0:c1])
                _, dk_c, dv_c = _flash_bwd_impl(
                    q, k[..., c0:c1, :], v[..., c0:c1, :], None,
                    causal_offset, out, lse, g, scale, causal, interpret,
                    grad_dtype=grad_dtype, segment_ids=seg, alibi=alibi,
                    qk_quant=qk_quant, dropout_rate=dropout_rate,
                    dropout_seed=dropout_seed, kv_offset=kv_offset + c0,
                    only='dkv')
                dks.append(dk_c)
                dvs.append(dv_c)
            return (jnp.concatenate(dqs, axis=-2),
                    jnp.concatenate(dks, axis=-2),
                    jnp.concatenate(dvs, axis=-2))
    nb = int(math.prod(batch)) if batch else 1
    kv_group = _kv_group(q, k)
    nbk = nb // kv_group

    off = jnp.stack([jnp.asarray(causal_offset, jnp.int32),
                     jnp.asarray(kv_offset, jnp.int32)]).reshape(1, 2)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # (*batch, Tq, 1)

    allow_redirect = (not interpret) or _REDIRECT_ON_INTERPRET
    streams_mask = mask is not None and _mask_streams_per_tile(
        nb, tq, tk, q.dtype, d + d_v, allow_redirect, bwd=True)
    bq, bk = _bwd_block_sizes(tq, tk, q.dtype, d_total=d + d_v,
                              has_mask=streams_mask)
    # Same exp2 pre-folding as the forward: q carries scale·log2e, lse is
    # converted to log2 units, so the kernels' (BQ, BK) score blocks need
    # no per-element multiply.
    quantized = qk_quant == 'int8'
    if quantized:
        # Recompute the SAME quantization as the forward (deterministic),
        # so the rebuilt p matches the saved lse exactly; gradients are
        # straight-through in the rounding (the standard treatment).
        qi8, sq = _quantize_rows(q, nb, tq, d)
        ki8, sk = _quantize_rows(k, nbk, tk, d)
        qf = _pad_dim(qi8, 1, bq)
        kf = _pad_dim(ki8, 1, bk)
        sqf = _pad_dim(sq * (scale * _LOG2E), 1, bq)
        skr = _pad_dim(jnp.swapaxes(sk, 1, 2), 2, bk)
        sqc = _pad_dim(sq, 1, bq)                # raw: in-kernel dequant
        skc = _pad_dim(sk, 1, bk)
    else:
        q2 = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
        qf = _pad_dim(q2.reshape(nb, tq, d), 1, bq)
        kf = _pad_dim(k.reshape(nbk, tk, d), 1, bk)
    vf = _pad_dim(v.reshape(nbk, tk, d_v), 1, bk)
    gf = _pad_dim(g.reshape(nb, tq, d_v), 1, bq)            # zero-padded
    # Clamp: a fully-masked row's lse is ln2·_NEG_BIG, whose ·log2e
    # conversion overflows fp32 to -inf — and the kernels' recompute
    # exp2(s − lse₂) with s = -inf (masked) would then be NaN. Clamped to
    # the (finite) _NEG_BIG shift, masked entries recompute p = 0 exactly.
    lsef = _pad_dim(jnp.maximum(lse * _LOG2E, _NEG_BIG)
                    .reshape(nb, tq, 1), 1, bq)
    deltaf = _pad_dim(delta.reshape(nb, tq, 1), 1, bq)
    tq_p, tk_p = qf.shape[1], kf.shape[1]

    args = [qf, kf, vf, gf, lsef, deltaf]
    if quantized:
        args += [sqf, skr, sqc, skc]
    nqb, nkb = tq_p // bq, tk_p // bk
    fused, vmem_limit = _bwd_form(only, tq_p, d, grad_dtype or q.dtype)

    # Banded window grids (see _flash_fwd_impl): the dq pass sweeps only
    # each Q block's K band; the dk/dv pass sweeps only each K block's Q
    # band (the transposed band, width ~window/bq).
    banded = (window is not None and causal and mask is None
              and positions is None
              and ((not interpret) or _BAND_ON_INTERPRET))
    kband_fn = qband_fn = bandoff = kof = qot = None
    trap = trap_pre = trap_pre_t = None
    if not banded and _trap_eligible(causal, window, mask, positions,
                                     causal_offset, kv_offset, 'exact',
                                     interpret):
        rel = int(causal_offset) - int(kv_offset)
        tabs = _trap_tables(rel, nqb, nkb, bq, bk)
        tabs_t = _trap_tables_t(rel, nqb, nkb, bq, bk)
        if max(tabs[0].shape[0], tabs_t[0].shape[0]) <= _TRAP_MAX_PAIRS:
            trap = True
            trap_pre = list(tabs)
            trap_pre_t = list(tabs_t)
    if banded:
        kband = _band_size(bq, bk, window, nkb)
        qband = _band_size(bk, bq, window, nqb)

        def kband_fn(i, off_s):
            return _band_lo((off_s + i * bq - (window - 1)) // bk,
                            nkb, kband)

        def qband_fn(j, off_s):
            # First Q block with a causal view of K block j:
            # ceil((j·bk − off − bq + 1)/bq) = floor((j·bk − off)/bq).
            return _band_lo((j * bk - off_s) // bq, nqb, qband)

        # Single source of truth for each grid's band translation — the
        # main BlockSpec maps and the aux (segment) maps derive from
        # these (rs[0] is the prefetched global row offset).
        def kof(b, i, j, rs):
            return kband_fn(i, rs[0][0]) + j

        def qot(b, j, i, rs):
            return qband_fn(j, rs[0][0]) + i
        bandoff = (off[0, 0] - off[0, 1]).reshape(1)
    k_map = lambda b, i, j, *rs: (  # noqa: E731
        b // kv_group, j if kof is None else kof(b, i, j, rs), 0)
    # dk/dv are computed as PER-Q-HEAD partials (the K/V INPUT blocks are
    # group-shared via b // kv_group, the outputs are not) and group-summed
    # after the call — the sequential grid cannot carry one accumulator
    # across the group's separated kj sweeps.
    kv_map_t = lambda b, j, i, *rs: (b // kv_group, j, 0)  # noqa: E731
    q_map_t = lambda b, j, i, *rs: (  # noqa: E731
        b, i if qot is None else qot(b, j, i, rs), 0)

    aux_specs, aux_specs_t, aux_args, flags, runsum = _aux_setup(
        mask, segment_ids, positions, batch, tq, tk, tq_p, tk_p, bq, bk,
        allow_redirect=allow_redirect, k_of=kof, q_of_t=qot,
        alibi=(None if alibi is None else alibi * _LOG2E))

    off_spec = pl.BlockSpec((1, 2), lambda b, i, j, *rs: (0, 0))
    dropout = float(dropout_rate) if dropout_rate else None
    seed_specs, seed_args = [], []
    if dropout is not None:
        seed_specs = [pl.BlockSpec((1, 1), lambda b, i, j, *rs: (0, 0))]
        seed_args = [jnp.asarray(dropout_seed, jnp.int32).reshape(1, 1)]
    grid_kind = 'trap' if trap else 'band' if banded else 'full'

    quant_specs = quant_specs_t = []
    if quantized:
        def _kj(b, i, j, rs):
            return j if kof is None else kof(b, i, j, rs)
        quant_specs = [
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j, *rs: (
                b // kv_group, 0, _kj(b, i, j, rs))),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, i, j, *rs: (
                b // kv_group, _kj(b, i, j, rs), 0)),
        ]
        quant_specs_t = [
            pl.BlockSpec((1, bq, 1), q_map_t),
            pl.BlockSpec((1, 1, bk), lambda b, j, i, *rs: (
                b // kv_group, 0, j)),
            pl.BlockSpec((1, bq, 1), q_map_t),
            pl.BlockSpec((1, bk, 1), lambda b, j, i, *rs: (
                b // kv_group, j, 0)),
        ]

    # --- dq pass (split form): grid (batch, Q block, K band), K innermost
    dq = dk = dv = None
    if only == 'dq' or (only == 'both' and not fused):
        dq_in_specs = [
            off_spec,
            *seed_specs,
            pl.BlockSpec((1, bq, d), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, d_v), k_map),
            pl.BlockSpec((1, bq, d_v), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, *rs: (b, i, 0)),
        ] + quant_specs + aux_specs
        dq_out_spec = pl.BlockSpec((1, bq, d),
                                   lambda b, i, j, *rs: (b, i, 0))
        if trap:
            dq_grid = (nb, int(trap_pre[0].shape[0]))
            dq_in_specs = _wrap_specs_pairs(dq_in_specs)
            dq_out_spec = _wrap_specs_pairs([dq_out_spec])[0]
        else:
            dq_grid = (nb, nqb, kband if banded else nkb)
        def walk(rel):
            if trap:
                return _trap_tables(rel, nqb, nkb, bq, bk)[:2]
            return _grid_walk(
                nqb, dq_grid[2],
                (lambda i: kband_fn(i, rel)) if banded else None)

        _note_blocks('flash_bwd_dq', grid_kind, walk, causal, causal_offset,
                     kv_offset, bq, bk, tk, window, flags)
        dq = _pallas_call(
            'flash_bwd_dq',
            _make_dq_kernel(scale, causal, bq, bk, tk, *flags,
                            window=window, band_fn=kband_fn,
                            quantized=quantized, dropout=dropout,
                            trap=bool(trap)),
            dq_grid, dq_in_specs, dq_out_spec,
            [pltpu.VMEM((bq, d), jnp.float32)] + _shift_scratch(flags, bq),
            jax.ShapeDtypeStruct((nb, tq_p, d), grad_dtype or q.dtype),
            interpret, trap_pre if trap else [bandoff, runsum],
        )(off, *seed_args, *args, *aux_args)
        dq = dq[:, :tq].reshape(q.shape)

    # --- dk/dv pass, and dq with it when fused: grid (batch, K block,
    # Q band), Q innermost ---
    if only in ('both', 'dkv'):
        dkv_in_specs = [
            off_spec,
            *seed_specs,
            pl.BlockSpec((1, bq, d), q_map_t),
            pl.BlockSpec((1, bk, d), kv_map_t),
            pl.BlockSpec((1, bk, d_v), kv_map_t),
            pl.BlockSpec((1, bq, d_v), q_map_t),
            pl.BlockSpec((1, bq, 1), q_map_t),
            pl.BlockSpec((1, bq, 1), q_map_t),
        ] + quant_specs_t + aux_specs_t
        dkv_out_specs = [
            pl.BlockSpec((1, bk, d), lambda b, j, i, *rs: (b, j, 0)),
            pl.BlockSpec((1, bk, d_v), lambda b, j, i, *rs: (b, j, 0)),
        ]
        dkv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                       pltpu.VMEM((bk, d_v), jnp.float32)]
        dkv_out_shape = [
            jax.ShapeDtypeStruct((nb, tk_p, d), grad_dtype or k.dtype),
            jax.ShapeDtypeStruct((nb, tk_p, d_v), grad_dtype or v.dtype),
        ]
        if fused:
            # dq rides the same walk: one (nqb, bq, d) block a batch-head,
            # written back when the walk moves to the next one.
            dkv_out_specs.append(pl.BlockSpec(
                (1, nqb, bq, d), lambda b, j, i, *rs: (b, 0, 0, 0)))
            dkv_scratch.append(pltpu.VMEM((nqb, bq, d), jnp.float32))
            dkv_out_shape.append(jax.ShapeDtypeStruct(
                (nb, nqb, bq, d), grad_dtype or q.dtype))
        if trap:
            dkv_grid = (nb, int(trap_pre_t[0].shape[0]))
            dkv_in_specs = _wrap_specs_pairs(dkv_in_specs, transposed=True)
            dkv_out_specs = _wrap_specs_pairs(dkv_out_specs,
                                              transposed=True)
        else:
            dkv_grid = (nb, nkb, qband if banded else nqb)
        def walk_t(rel):
            if trap:
                return _trap_tables_t(rel, nqb, nkb, bq, bk)[:2]
            kj, qi = _grid_walk(
                nkb, dkv_grid[2],
                (lambda j: qband_fn(j, rel)) if banded else None)
            return qi, kj

        dkv_name = 'flash_bwd_fused' if fused else 'flash_bwd_dkv'
        _note_blocks(dkv_name, grid_kind, walk_t, causal, causal_offset,
                     kv_offset, bq, bk, tk, window, flags)
        dk, dv, *dq_fused = _pallas_call(
            dkv_name,
            _make_dkv_kernel(scale, causal, bq, bk, tk, *flags,
                             window=window, band_fn=qband_fn,
                             quantized=quantized, dropout=dropout,
                             trap=bool(trap), nqb=nqb, fused=fused),
            dkv_grid, dkv_in_specs, dkv_out_specs,
            dkv_scratch + _shift_scratch(flags, bq),
            dkv_out_shape, interpret,
            trap_pre_t if trap else [bandoff, runsum],
            vmem_limit_bytes=vmem_limit,
        )(off, *seed_args, *args, *aux_args)
        if fused:
            dq = dq_fused[0].reshape(nb, tq_p, d)[:, :tq].reshape(q.shape)

        dk = dk[:, :tk]
        dv = dv[:, :tk]
        if kv_group > 1:
            # Group members are consecutive flat q-batch indices (head
            # axis is the innermost lead dim): sum each group's partials
            # in fp32.
            dk = dk.reshape(nbk, kv_group, tk, d).astype(jnp.float32
                                                         ).sum(1)
            dv = dv.reshape(nbk, kv_group, tk, d_v).astype(jnp.float32
                                                           ).sum(1)
            dk = dk.astype(grad_dtype or k.dtype)
            dv = dv.astype(grad_dtype or v.dtype)
        dk = dk.reshape(k.shape)
        dv = dv.reshape(v.shape)
    return dq, dk, dv


def _reference_math(q, k, v, mask, scale, causal):
    """Identical math in jnp — the test oracle."""
    tq, tk = q.shape[-2], k.shape[-2]
    s = jnp.einsum('...td,...od->...to', q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if mask is not None:
        s = jnp.where(mask, _NEG_BIG, s)
    if causal:
        future = jnp.arange(tq)[:, None] < jnp.arange(tk)[None, :]
        s = jnp.where(future, _NEG_BIG, s)
    attn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('...to,...od->...td', attn, v.astype(jnp.float32))
    if mask is not None:
        out = jnp.where(_row_has_valid(mask, causal, tq, tk), out, 0.0)
    return out.astype(v.dtype)


def _seg_pair(seg_q, seg_k):
    return None if seg_q is None else (seg_q, seg_k)


def _offsets(causal_offset, kv_offset, static_off):
    """The call's (row, column) offsets: the static pair where the caller
    gave plain ints (``flash_attention``), else the traced operands."""
    return (causal_offset, kv_offset) if static_off is None else static_off


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(12, 13, 14, 15, 16, 17, 18, 19))
def _flash(q, k, v, mask, causal_offset, kv_offset, seg_q, seg_k, pos_q,
           pos_k, alibi, dropout_seed, scale, causal, interpret, mode,
           window, qk_quant, dropout_rate, static_off):
    causal_offset, kv_offset = _offsets(causal_offset, kv_offset,
                                        static_off)
    return _flash_fwd_impl(q, k, v, mask, causal_offset, scale, causal,
                           interpret, mode,
                           segment_ids=_seg_pair(seg_q, seg_k),
                           positions=_seg_pair(pos_q, pos_k),
                           window=window, alibi=alibi, qk_quant=qk_quant,
                           dropout_rate=dropout_rate,
                           dropout_seed=dropout_seed, kv_offset=kv_offset)


def _flash_fwd(q, k, v, mask, causal_offset, kv_offset, seg_q, seg_k,
               pos_q, pos_k, alibi, dropout_seed, scale, causal, interpret,
               mode, window, qk_quant, dropout_rate, static_off):
    row_off, col_off = _offsets(causal_offset, kv_offset, static_off)
    out, lse = _flash_fwd_impl(q, k, v, mask, row_off, scale, causal,
                               interpret, mode, save_lse=True,
                               segment_ids=_seg_pair(seg_q, seg_k),
                               positions=_seg_pair(pos_q, pos_k),
                               window=window, alibi=alibi,
                               qk_quant=qk_quant,
                               dropout_rate=dropout_rate,
                               dropout_seed=dropout_seed,
                               kv_offset=col_off)
    # Identities unless a checkpoint policy names them. The NAMED ``out``
    # is the primal output too, so nothing of the kernel stays live in a
    # rematerialized forward that kept both; ``lse`` is the squeezed
    # (*batch, Tq) form, not the kernel's lane-padded (nb, Tq_p, 1).
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    # The backward kernel's other operands, whole.
    q, k, v = (checkpoint_name(x, FLASH_QKV_NAME) for x in (q, k, v))
    return out, (q, k, v, mask, causal_offset, kv_offset, seg_q, seg_k,
                 pos_q, pos_k, alibi, dropout_seed, out, lse)


def _flash_bwd(scale, causal, interpret, mode, window, qk_quant,
               dropout_rate, static_off, res, g):
    # The backward is mode-independent: lse = log Σ exp(s) is invariant to
    # the forward's shift choice, and the bwd kernels recompute p from it.
    (q, k, v, mask, causal_offset, kv_offset, seg_q, seg_k, pos_q, pos_k,
     alibi, dropout_seed, out, lse) = res
    causal_offset, kv_offset = _offsets(causal_offset, kv_offset,
                                        static_off)
    dq, dk, dv = _flash_bwd_impl(q, k, v, mask, causal_offset, out, lse, g,
                                 scale, causal, interpret,
                                 segment_ids=_seg_pair(seg_q, seg_k),
                                 positions=_seg_pair(pos_q, pos_k),
                                 window=window, alibi=alibi,
                                 qk_quant=qk_quant,
                                 dropout_rate=dropout_rate,
                                 dropout_seed=dropout_seed,
                                 kv_offset=kv_offset)
    return (dq, dk, dv, None, None, None, None, None, None, None, None,
            None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, mask=None, *, causal=False, causal_offset=0,
                    kv_offset=0, scale=None, interpret=None,
                    softmax_mode='exact', segment_ids=None, positions=None,
                    window=None, alibi_slopes=None, qk_quant=None,
                    dropout_rate=0.0, dropout_seed=None):
    """Fused attention ``softmax(q·kᵀ·scale [+mask])·v`` as TPU kernels.

    ``q (..., Tq, d)``, ``k (..., Tk, d)``, ``v (..., Tk, d_v)``; optional
    boolean ``mask (..., Tq, Tk)`` broadcastable over the leading dims
    (True = masked out, the reference's convention, reference README.md:67).

    Grouped-query attention (GQA/MQA): k/v may carry FEWER heads than q —
    lead dims equal except the head axis (-3), q heads divisible by kv
    heads (``Hkv = 1`` is multi-query). Each group of ``Hq/Hkv``
    consecutive q heads attends the same K/V head; K/V HBM residency is
    O(Hkv·T·d). Backward returns kv-head-shaped dk/dv (per-q-head
    partials group-summed in fp32). No reference analog.
    ``segment_ids``: the compact packed-sequence mask form — a
    ``(seg_q, seg_kv)`` pair of non-negative int arrays with trailing
    shapes ``(Tq,)`` / ``(Tk,)`` (leading dims broadcastable like the
    mask's), or a single ``(..., T)`` array used for both sides when
    ``Tq == Tk``. Positions in different segments don't attend — the same
    semantics as the dense ``mask[i, j] = seg_q[i] != seg_kv[j]`` with
    O(T) instead of O(Tq·Tk) HBM traffic, and (Q block, K block) pairs
    with provably disjoint id ranges are skipped outright. Composes with
    ``mask`` and ``causal`` (union of maskings); rows left with no
    attendable key output 0 with zero gradients.

    ``positions``: causal masking over EXPLICIT global positions — a
    ``(pos_q, pos_kv)`` pair (or single array, same rules as
    ``segment_ids``) of non-negative ints; pair (i, j) is masked when
    ``pos_q[i] < pos_kv[j]``. This is ``causal=True`` generalized to
    arbitrary row layouts (zigzag/striped sequence sharding, where a
    shard's rows are not one contiguous run and a scalar
    ``causal_offset`` cannot describe them); blocks whose positions are
    provably all-future are skipped like the contiguous causal skip.
    Mutually exclusive with ``causal``; composes with ``mask`` and
    ``segment_ids``.

    ``dropout_rate``/``dropout_seed``: attention-weight dropout
    (inverted scaling, applied to the normalized weights) with the mask
    generated IN-KERNEL as a pure hash of (seed, batch, global element
    coordinates) — no O(Tq·Tk) mask tensor, no RNG state, and because
    the mask depends only on element coordinates it is identical across
    block decompositions (the backward's blocks legitimately differ),
    grid orders AND backends: a given seed reproduces the same mask on
    CPU and TPU. The seed is explicit (int or traced int32 scalar;
    derive it from your ``jax.random`` key).

    ``qk_quant='int8'``: per-row symmetric int8 quantization of q and k —
    the score matmul runs on the MXU's int8 path (2× the bf16 rate raw;
    measured end-to-end it wins only at LARGE head dim, e.g. ~+11% at
    d=256, because the per-block dequant multiplies cost VPU time and at
    small d the kernel is VPU-bound anyway). This is a deliberate,
    self-consistent approximation: outputs differ from the exact kernel
    by int8 rounding noise (~1% of row scale), and the VJP is exactly the
    straight-through gradient of the quantized forward (verified against
    a dense STE oracle). Composes with every mask form, GQA and windows;
    ``softmax_mode='bounded'`` falls back to exact.

    ``alibi_slopes``: ALiBi — per-head additive bias
    ``slope·(pos_k − pos_q)`` on the logits (lead dims broadcastable
    against q/k/v's, e.g. ``(H,)``; the classic geometric slopes are the
    user's choice). Needs ``causal=True`` or ``positions`` so the kernel
    knows global positions; computed in-kernel from the same position
    arithmetic as the causal triangle, so it costs no O(T²) input.
    Treated as a constant in the VJP (no slope gradients — standard
    ALiBi trains them frozen). With ``softmax_mode='bounded'`` the exact
    kernel runs instead (the norm bound does not cover the bias term).

    ``window``: sliding-window (local) attention — a static positive int;
    query at global position ``p`` attends only keys in
    ``(p − window, p]``. Requires causal semantics (``causal=True`` or
    ``positions``), composing as the intersection; K blocks wholly past
    the window are skipped via the same SMEM tables as the causal skip,
    so compute AND HBM traffic drop to O(Tq·window) — long-context cost
    becomes linear in T. No reference analog (its module materializes
    every (T/N, T) score row, reference module.py:66-67).

    Differentiable end-to-end with blockwise Pallas kernels in both
    directions — peak memory is O(T·d) for forward AND backward (the
    backward recomputes score blocks from the saved row logsumexp).
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so the
    CPU test mesh runs the same code.

    ``causal_offset``: the GLOBAL index of query row 0 (int or traced
    scalar, e.g. ``lax.axis_index(...) * (T // N)`` under ``shard_map``) —
    lets sequence-sharded callers run causal attention of local query rows
    against gathered keys with no materialized O(Tq·Tk) triangle; the
    causal comparison and the block-skip predicate use
    ``causal_offset + row`` as the global row position. ``kv_offset`` is
    the same for key column 0 — callers whose k/v slab is itself a slice
    of a longer global sequence (the ring path's rotating blocks) pass it
    so causal masking AND the dropout hash see true global columns.

    ``softmax_mode``:

    - ``'exact'`` (default): numerically-stable online softmax with a
      running row max — safe for any input magnitudes.
    - ``'bounded'``: replaces the running max with the per-row
      Cauchy-Schwarz bound ``scale·‖q_i‖·max_j‖k_j‖``, removing the most
      expensive VPU op of the kernel (~15% faster at small head dim).
      Softmax is shift-invariant, so this changes results only through
      fp32 underflow of weights far below the bound; a built-in guard
      runs the exact kernel instead whenever the worst-case gap
      (``2·scale·log2e·max‖q‖·max‖k‖``, e.g. huge-norm yet near-orthogonal
      q/k) could reach fp32's exponent limits — 'bounded' is an
      optimization hint, never a correctness trade. Typical normalized
      activations stay far under the threshold and take the fast path.
    """
    if softmax_mode not in ('exact', 'bounded'):
        raise ValueError(f"softmax_mode must be 'exact' or 'bounded', "
                         f'got {softmax_mode!r}')
    if v.shape[:-2] != k.shape[:-2] or v.shape[-2] != k.shape[-2]:
        raise ValueError(
            f'k and v must agree on lead dims and Tk; got k {k.shape}, '
            f'v {v.shape}')
    _kv_group(q, k)  # validate GQA lead-dim contract up front
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'

    def _pair(value, name):
        if value is None:
            return None, None
        if isinstance(value, (tuple, list)):
            return value
        if q.shape[-2] != k.shape[-2]:
            raise ValueError(
                f'a single {name} array needs Tq == Tk; pass a '
                f'(q-side, kv-side) pair for cross-length attention')
        return value, value

    seg_q, seg_k = _pair(segment_ids, 'segment_ids')
    pos_q, pos_k = _pair(positions, 'positions')
    if positions is not None and causal:
        raise ValueError(
            'positions IS causal masking (over explicit global positions) '
            '— pass one or the other, not both')
    if window is not None:
        if not isinstance(window, int) or window < 1:
            raise ValueError(f'window must be a positive int, got {window!r}')
        if not causal and positions is None:
            raise ValueError(
                'window is a lookback cap and needs causal semantics: pass '
                'causal=True (contiguous rows) or positions (explicit '
                'layouts)')
    if alibi_slopes is not None:
        alibi_slopes = jnp.asarray(alibi_slopes, jnp.float32)
        if not causal and positions is None:
            raise ValueError(
                'alibi_slopes bias by relative GLOBAL position: pass '
                'causal=True (contiguous rows) or positions (explicit '
                'layouts) so the kernel knows the positions')
    if qk_quant not in (None, 'int8'):
        raise ValueError(f"qk_quant must be None or 'int8', "
                         f'got {qk_quant!r}')
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f'dropout_rate must be in [0, 1), '
                         f'got {dropout_rate}')
    if dropout_rate and dropout_seed is None:
        raise ValueError(
            'dropout needs an explicit dropout_seed (int or traced int32 '
            'scalar) — the kernel holds no hidden RNG state; derive it '
            'from your jax.random key, e.g. '
            'jax.random.randint(key, (), 0, 2**31 - 1)')
    # Offsets given as plain ints are the call's PLACE, not data: they ride
    # the custom_vjp as a static argument. As operands they would reach
    # the forward rule as tracers wherever the call is staged (a scanned
    # or rematerialized layer), and the forward would lose the trapezoid
    # grid, which needs its pair count at trace time (``_trap_eligible``).
    static_off = _static_offsets(causal_offset, kv_offset)
    if static_off is not None:
        causal_offset = kv_offset = None
    return _flash(q, k, v, mask, causal_offset, kv_offset, seg_q, seg_k,
                  pos_q, pos_k, alibi_slopes, dropout_seed, float(scale),
                  bool(causal), bool(interpret), softmax_mode, window,
                  qk_quant, dropout_rate, static_off)
