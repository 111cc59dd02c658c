# -*- coding: utf-8 -*-
"""
Fused KV-cache decode kernel (the serving hot path): one token per
slot per step, or — VERIFY-k — up to k new rows per slot in one
program, the fused verify step of draft-verify speculative decoding
(Leviathan et al.; each of the k query rows keeps its own online-
softmax state and masks the intra-step causal triangle among the k
appended rows).

``models/decode.py``'s XLA formulation runs a decode step as two ops —
``append_kv_slots`` (a masked gather over the whole ``t_max`` axis) and
``decode_attention`` (a masked einsum softmax over the full buffer) —
which is correct and backend-portable, but between the two ops XLA
materializes cache-shaped copies, and its s8 dot lowering at few-row
operands never turns the int8 K mirror's halved bytes into halved
traffic.

This kernel is ONE Pallas program per decode step that

- **appends in place**: the K/V buffers (and the int8 mirror, when the
  cache carries one) are passed as aliased outputs
  (``input_output_aliases``), and only the sublane tile containing the
  append row is ever written (16 rows of bf16 — its own output block,
  filled from the resident K-split block at the step that holds it; a
  verify-k step, a paged pool and the int8 mirror write back the whole
  split or page); unwritten rows keep their bits by the aliasing
  contract. A scanned stack's caches keep that property
  through the layer loop: the LAYER-STACKED buffers are the loop's
  carry, the step takes them whole with a ``layer`` index
  (scalar-prefetched, added to every cache block's row), and the whole
  stacked operand aliases the result — so no layer is sliced out of
  the stack, written back, or copied on the way to the donated
  buffer. (As a scan's xs → ys the same caches cost six cache-sized
  operations a token: an ``xs[l]`` slice cannot alias a ``ys[l]``
  slot.) ``tests/test_tpu_compile.py`` holds the compiled step to it;
- **splits K over the time axis**: the grid sweeps ``t_max`` in
  ``block_k`` chunks with running ``(max, denom, acc)`` accumulators in
  VMEM scratch (the flash-decoding work partition; on TPU the grid is
  sequential per core, so the split is what lets Pallas double-buffer
  the HBM→VMEM cache stream while the MXU works);
- **takes several KV heads a grid step**: a step costs ~0.46 µs that
  are not bytes, most of what streaming one head's 512 KB of K and V
  takes, so
  a step holds ``hb`` heads of one slot (consecutive flat rows of the
  cache, sharing its lengths) — :func:`decode_geometry` picks ``hb``
  and the split from the call's shapes against a VMEM plan, and
  ``block_k`` stays small so that a slot's unfilled tail is skipped
  at a fine grain;
- **substitutes the new rows where they land**: the step's new rows
  replace the cache's columns at ``append_at …`` in the one split (two,
  when a verify-k step straddles a boundary) that holds them; every
  other tile is scored as it lies in the cache, with no select over its
  scores or its V block — the same sums in the same order whichever
  tile the rows fall in, so a verify-k step stays bit-identical to its
  sequential single-token steps;
- **masks per slot**: the per-slot valid lengths arrive as a
  scalar-prefetch vector that both the kernel (causal/window masking,
  which split holds the new rows) and the BlockSpec index maps read —
  blocks past a slot's fill are never even DMA'd (the index map clamps
  to the last useful block, and Pallas skips re-fetching a resident
  block), so a half-empty serving batch streams half the bytes; the
  grid steps behind a slot's last block carry the NEXT grid row's
  first block, so the row change waits for nothing;
- **moves what is filled of the last split, not its 1024 rows**: where
  no more than ``geom.tail`` rows (256, :func:`decode_geometry`) are
  filled of the split that holds a slot's last valid column — every
  token of the first 256 behind a multiple of 1024 — the whole-split
  stream stops on the split before it and those rows alone are moved,
  by a copy of the kernel's own from the aliased result in HBM, started
  under the grid row before, and scored beside the last whole split. A
  buffer cannot be handed to the call a second time for this: XLA
  copies an operand that is aliased to a result and read through
  another (measured: 5x the step). Further into the split the tail's
  scoring would cost more than its bytes save, and the split is moved
  whole as before;
- **streams a narrow head's keys and values as ONE row** (the packed
  mode, ``cache_v=None``): a 64-wide head stored as a K and a V buffer
  is tiled to 128 lanes in each, half of every byte a step moves is
  padding and ``decode_geometry`` gives such rows no tail; packed, a
  token's key sits in lanes 0-63 and its value in lanes 64-127 of one
  ``(…, t_max, 128)`` buffer, the query rides zero-extended (``q̃ · [k |
  v] = q · k``), the resident block enters both products and the output
  is the value lanes of ``p · [k | v]`` — one stream where there were
  two, nothing padded in HBM. Scored a head at a time that is the MXU
  passes and the softmax tiles of a 128-wide head behind HALF its bytes
  a step, and the step is no longer the stream's (chip, PR 51: the
  cell's call 4.11 ms at 66 % of its bytes' roofline); so a step of an
  even number of heads is scored TWO HEADS A PASS — heads A and B as
  ``[k_A | k_B]`` and ``[v_A | v_B]``, formed in VMEM by a lane rotation
  and a lane select each way, their queries block-diagonal in one tile,
  one score product, one softmax tile and one ``p · v`` product a pair
  (``pair_block`` in the kernel body; ``DecodeGeometry.step()`` says
  ``heads_a_pass``). The slab in HBM is what it was;
- **dequantizes int8 in kernel**: the quantized path streams the 1-byte
  ``k_q`` mirror plus its per-row scales and scores s8×s8→s32 on the
  MXU with the dequantization applied to the s32 block — the halved K
  bytes finally reach the memory system as halved traffic instead of
  dying in XLA's s8 lowering.

Numerics: the same exp2-trick online softmax as
:mod:`~distributed_dot_product_tpu.ops.pallas_attention` (scale·log2e
pre-folded into q, masked logits −inf against a ``_NEG_BIG``-clamped
running max, empty rows → exact 0). Outputs are the UN-normalized
``(num, max, denom)`` triple so sequence-sharded callers can merge
shards by the flash-decoding pmax/psum rule; local callers divide once
outside (G rows — noise).

Off-TPU the kernel runs under the Pallas interpreter like the training
kernels (``interpret=None`` auto-selects), so the CPU tier-1 suite
covers the identical code path.
"""

import contextlib
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.ops.kernel_call import kernel_call
from distributed_dot_product_tpu.ops.pallas_attention import (
    _LOG2E, _NEG_BIG, _quantize_rows,
)
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['flash_decode', 'decode_block_k', 'decode_geometry',
           'latent_geometry', 'flash_decode_geometry', 'DecodeGeometry',
           'PairedGeometry']

# K-split cap, in cache rows: the granularity at which a slot's unfilled
# tail is skipped (never streamed). 1024 rows stream 7 % over a 12.4k
# fill where 4096 would stream 23 % over, so a grid step grows by KV
# HEADS (decode_geometry), not by coarser time blocks. A paged pool's
# page is its K split and may not exceed it.
_BLOCK_K_CAP = 1024
# What one grid step should stream (K + V blocks of all its heads): a
# step costs ~0.46 us of pipeline bookkeeping whatever it moves (chip,
# PR 27), and 4 MB is ~5 us of HBM time on a v5e. That figure was read
# at 4 MiB steps of 128-wide heads, where the body — two MXU passes and
# one softmax tile a head, whatever the head's width — hides under the
# DMA; a packed step of narrow heads moves half the bytes for the same
# body and was bound by it until its heads were paired (PERF.md
# section 6, PR 52).
_STEP_STREAM_BYTES = 4 << 20
# VMEM the geometry may plan for (double-buffered streams and write-back
# tiles, one head's temporaries): the v5e compiler's default scoped
# limit is 16 MiB; stay a quarter under it.
_VMEM_BUDGET = 12 << 20
# The tail: where no more than this many rows are filled of the split
# that holds a slot's last valid column, those rows alone are moved and
# not the split's 1024 (decode_geometry takes the first that divides the
# split and that the VMEM plan has room for).
_TAIL_ROWS = (256, 128)


def decode_block_k(t_max, cap=_BLOCK_K_CAP):
    """Largest usable K-split for a ``t_max``-row cache, or None when the
    kernel doesn't apply. The cache buffers are ALIASED outputs, so they
    cannot be padded — the split must divide ``t_max`` exactly. Any
    ``t_max <= cap`` is one split; larger caches take the biggest
    power-of-two divisor (serving caches are powers of two; an odd
    131071-row cache falls back to the XLA path rather than running a
    degenerate grid)."""
    if t_max <= cap:
        return t_max
    for bk in (1024, 512, 256, 128):
        if bk <= cap and t_max % bk == 0:
            return bk
    return None


class DecodeGeometry(NamedTuple):
    """One grid step of the decode kernel: ``heads`` KV heads of one
    slot, ``block_k`` cache rows of each; ``write_rows`` rows of each
    head's buffers written back for the append; ``bytes`` the cache
    bytes the step streams; ``tail`` the most rows filled of the split
    that holds a slot's last valid column at which those rows alone are
    moved, or None where that split is always moved whole."""
    heads: int
    block_k: int
    write_rows: int
    bytes: int
    tail: int = None

    @property
    def heads_a_pass(self):
        """KV heads one pass of the body scores: 2 where the packed
        mode's pair body runs (:class:`PairedGeometry`), else 1."""
        return 1

    def step(self):
        """What ``decode_impl_traces()`` reports of it."""
        return {'heads': self.heads, 'block_k': self.block_k,
                'bytes': self.bytes, 'heads_a_pass': self.heads_a_pass}


class PairedGeometry(DecodeGeometry):
    """The grid step of a PACKED call that holds an even number of KV
    heads: the same tuple (the step, its bytes and its tail are what
    they were), scored two heads a pass."""
    __slots__ = ()

    @property
    def heads_a_pass(self):
        return 2


def _lanes(x):
    """Columns as VMEM (and tiled HBM) hold them: whole 128-lane tiles."""
    return -(-x // 128) * 128


def decode_geometry(t_max, h_kv, d, dv, rows, k_dtype, v_dtype, *,
                    n=1, quantized=False, page_size=None, block_k=None,
                    ring=False, packed=False):
    """The decode kernel's grid step for a call of these shapes, or None
    where no K split divides ``t_max`` (the caller takes the XLA path).

    ``rows`` is the query rows a KV head scores (``group · n``);
    ``page_size`` a paged pool's page, which IS the split; ``block_k``
    the tests' override of the split; ``ring`` the ring mode; ``packed``
    the one-buffer mode (``flash_decode``: ``d`` is then the packed
    row's width, keys and values together, ``dv = d``, and a row is
    streamed ONCE; a packed step of an even number of heads comes back
    as a :class:`PairedGeometry`, the same tuple scored two heads a
    pass, and its plan counts the pair's two block-sized temporaries).
    (The latent cache's kernel has a rule of its own,
    :func:`latent_geometry`.)

    The K split stays at :data:`_BLOCK_K_CAP` rows (skip granularity);
    the step then takes the most KV heads ``hb | h_kv`` whose K + V
    blocks stay within :data:`_STEP_STREAM_BYTES` and whose VMEM plan —
    streams and write-back blocks double-buffered, the softmax state,
    one head's temporaries — stays within :data:`_VMEM_BUDGET`
    (``tests/test_tpu_compile.py`` compiles the plan's edges for a v5e).
    A slot's heads are consecutive flat rows of the cache and share
    its lengths, so one step's heads always belong to one slot.

    The append is written back as the sublane tile(s) holding the new
    row (16 rows of bf16) where a single row is appended to a slab; a
    verify-k step (its rows may straddle tiles of ONE resident block), a
    paged pool (its page is the block) and the int8 mirror (its scale
    row vector tiles by lanes, not rows) write back the whole split.

    The same calls — one row appended to a slab of more than one split,
    head dims whole lane tiles (Mosaic refuses a copy of a lane-padded
    row) — get a ``tail``: the most rows of :data:`_TAIL_ROWS` that the
    plan, with the heads chosen, still has room for as one more buffer
    a head. Where no more than those are filled of the split that holds
    a slot's last column the kernel moves them alone; a verify-k step,
    a paged pool, the int8 mirror and the ring (``ring``; its newest
    split is this case, its oldest the mirror image) move that split
    whole, as a slab does further into it."""
    bk = page_size or block_k or decode_block_k(t_max)
    if bk is None or t_max % bk:
        return None
    sub = max(_sublane(k_dtype), _sublane(v_dtype))
    wr = bk
    if n == 1 and not quantized and page_size is None and bk % sub == 0:
        wr = sub
    # Bytes of one cache row (one head, one position) as a step streams
    # it, and as its blocks lie in VMEM: quantized scoring streams the
    # int8 mirror row and its f32 scale in place of the K row, which is
    # then fetched at its write block alone.
    k_row = _lanes(d) * jnp.dtype(k_dtype).itemsize
    v_row = 0 if packed else _lanes(dv) * jnp.dtype(v_dtype).itemsize
    stream_row = (_lanes(d) + 4 if quantized else k_row) + v_row
    held_row = stream_row + (k_row if quantized else 0)
    q_sub = _sublane(jnp.int8) if quantized else sub
    g_pad = -(-rows // q_sub) * q_sub
    # A head's q, new rows and (num, m, l) blocks, double-buffered, and
    # its f32 softmax state: an upper bound, at 4 bytes an element.
    small = 3 * (g_pad + sub) * (_lanes(d) + _lanes(dv) + 256) * 4
    # One head's temporaries at a time (the body walks the step's
    # heads): scores, probabilities and masks; the V block of the tile
    # that takes the new rows, as a value beside its row index; the
    # write-back block. Mosaic's own count (bisected vmem_limit_bytes
    # over 19 extreme shapes, PERF.md section 6, PR 27) stays under it.
    v_item = jnp.dtype(v_dtype).itemsize
    temps = (8 * g_pad * bk * 4 + bk * _lanes(dv) * (v_item + 4)
             + wr * held_row)

    def vmem(hb, tail=0):
        # Streams and write-back blocks are double-buffered; the tail's
        # rows are one buffer (moved under the grid row before). A
        # packed step of an even number of heads scores them in pairs:
        # the pair's keys and its values, formed from the two resident
        # blocks, are two more block-sized temporaries, and its score
        # tile holds both heads' query rows.
        pair = 0
        if packed and hb % 2 == 0:
            g_pair = -(-2 * rows // q_sub) * q_sub
            pair = 2 * bk * k_row + 8 * (g_pair - g_pad) * bk * 4
        return hb * (2 * (bk + wr) * held_row + tail * stream_row
                     + small) + temps + pair

    hb = max(c for c in range(1, h_kv + 1)
             if h_kv % c == 0 and (c == 1 or (
                 c * bk * stream_row <= _STEP_STREAM_BYTES
                 and vmem(c) <= _VMEM_BUDGET)))
    tail = None
    if (wr != bk and not ring and t_max > bk and d == _lanes(d)
            and dv == _lanes(dv)):
        tail = next((rows for rows in _TAIL_ROWS
                     if rows < bk and bk % rows == 0 and rows % wr == 0
                     and vmem(hb, rows) <= _VMEM_BUDGET), None)
    kind = PairedGeometry if packed and hb % 2 == 0 else DecodeGeometry
    return kind(hb, bk, wr, hb * bk * stream_row, tail)


def flash_decode_geometry(q, cache_k, cache_v=None, *, page_table=None,
                          qk_quant=None, block_k=None, latent_v=None,
                          ring=False):
    """The grid step :func:`flash_decode` takes for these operands, of
    which only shapes and dtypes are read (abstract values will do) —
    or None where no K split divides the cache. ``flash_decode`` asks
    this itself, so a caller that reports the step (the ``'step'`` of
    ``models.decode.decode_impl_traces``) hands over what it hands the
    kernel and cannot drift from it."""
    h, n, d = q.shape[-3:]
    if latent_v is not None:
        return latent_geometry(cache_k.shape[-1], d, latent_v, h * n,
                               cache_k.dtype, block_k=block_k)
    h_kv, t_max, page = cache_k.shape[-3], cache_k.shape[-2], None
    if page_table is not None:
        page, t_max = t_max, page_table.shape[1] * t_max
    # No value buffer: the packed mode, whose one buffer is both.
    packed = cache_v is None
    values = cache_k if packed else cache_v
    return decode_geometry(
        t_max, h_kv, d, values.shape[-1], n * (h // h_kv), cache_k.dtype,
        values.dtype, n=n, quantized=qk_quant == 'int8', page_size=page,
        block_k=block_k, ring=ring, packed=packed)


def _sublane(dtype):
    """Rows of one TPU sublane tile at ``dtype`` (f32 8, bf16 16,
    int8 32)."""
    return 32 // jnp.dtype(dtype).itemsize


def _pad_rows(x, mult):
    """Pad axis -2 up to a multiple of ``mult``."""
    n = x.shape[-2]
    target = -(-n // mult) * mult
    if target == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, target - n)
    return jnp.pad(x, pad)


def _write_tile(ki, ap, nn, geom, t_max):
    """The write-back tile — in units of ``geom.write_rows`` cache rows —
    that the aliased outputs sit on at K split ``ki`` of a slot
    appending ``nn`` rows at column ``ap``. ONE definition for the OUT
    BlockSpec index maps and the kernel body, which must agree exactly.
    ``ap < 0`` ⇒ tile 0, a copy-through (Pallas writes every output
    block back; an unwritten one would clobber the aliased cache with
    garbage). The rows span at most TWO tiles, and two only where the
    tile is the whole split (``decode_geometry``): clamping ki's first
    tile into the span walks the write ref over each tile at the step
    whose resident block holds it, right before Pallas flushes it."""
    wr = geom.write_rows
    tiles = t_max // wr
    first = jnp.clip(ap // wr, 0, tiles - 1)
    last = jnp.clip((ap + jnp.maximum(nn, 1) - 1) // wr, 0, tiles - 1)
    return jnp.where(ap >= 0,
                     jnp.clip(ki * (geom.block_k // wr), first, last), 0)


def _ring_hits(ki, bk, head, span, t_max):
    """Does K split ``ki`` of a ring of ``t_max`` columns hold one of
    the ``span`` valid columns that end at column ``head`` (going back,
    wrapping below column 0 onto the ring's end)? ONE definition for the
    kernel's block skip and the stream index map, which must agree."""
    lo = head - span + 1
    return jnp.where(
        lo >= 0,
        jnp.logical_and(ki * bk <= head, ki * bk + bk - 1 >= lo),
        jnp.logical_or(ki * bk <= head, ki * bk + bk - 1 >= lo + t_max))


def _sweep_end(vt, ap, geom, t_max, n=1):
    """Where a slot's sweep ends: ``(whole, last, tails)`` — ``last`` the
    K split that holds its last useful column (the LAST new row attends
    up to ``vt + n − 1``; or the append column, should a caller append
    past it), ``tails`` whether what is filled of that split fits the
    tail's ``geom.tail`` rows and is moved as those rows alone, and
    ``whole`` the last split the whole-split stream moves (``last``,
    or the one before it where the tail takes ``last``). Split 0 is
    never a tail: the stream moves one block a grid row whatever
    happens. ONE definition for the stream index maps and the kernel
    body, which must agree."""
    bk = geom.block_k
    edge = jnp.maximum(vt + (n - 1), ap)
    last = jnp.clip(edge // bk, 0, t_max // bk - 1)
    if geom.tail is None:
        return last, last, False
    tails = jnp.logical_and(last > 0, edge - last * bk < geom.tail)
    return last - tails.astype(jnp.int32), last, tails


def _pair_lanes(xa, xb):
    """``([a_lo | b_lo], [a_hi | b_hi])`` of two ``(rows, w)`` values
    whose halves are ``[lo | hi]``: one lane rotation by ``w / 2`` of
    one operand and one lane select against the other, each way. Mosaic
    rotates 32-bit lanes alone, so a narrower type goes as the words
    its rows pack into — whole rows of the same lane, which a lane
    rotation and a lane select move together."""
    dtype, half = xa.dtype, xa.shape[-1] // 2
    narrow = dtype.itemsize < 4
    if narrow:
        xa, xb = (pltpu.bitcast(x, jnp.uint32) for x in (xa, xb))
    low = jax.lax.broadcasted_iota(jnp.int32, xa.shape, 1) < half
    lo = jnp.where(low, xa, pltpu.roll(xb, half, 1))
    hi = jnp.where(low, pltpu.roll(xa, half, 1), xb)
    if narrow:
        lo, hi = pltpu.bitcast(lo, dtype), pltpu.bitcast(hi, dtype)
    return lo, hi


def _make_decode_kernel(geom, ns, n, group, g_pad, h_kv, window,
                        quantized, has_alibi, paged=False, stacked=False,
                        ring=False, packed=False):
    """Kernel body; refs are ordered to match ``flash_decode``'s spec
    list below. Grid = (B·H_kv / hb, ns) with the K split innermost:
    one step holds ``hb = geom.heads`` KV heads of ONE slot (every
    block gains that leading axis; the body walks the heads, so its
    temporaries stay one head's) and the running softmax state lives in
    scratch across splits.

    VERIFY-k: ``n`` is the static number of new rows per step (1 =
    classic decode). The per-(b, h_kv) query block carries ``n · group``
    rows laid out new-row-major (row ``j·group + g`` is query head ``g``
    of new row ``j``), so per-row masking reads the row's intra-step
    index ``j = row // group`` — new row ``j`` attends columns
    ``<= vt + j``, which is exactly the intra-step causal triangle among
    the k new rows plus the shared prefix. A third scalar-prefetch
    vector ``nn`` carries the PER-SLOT number of rows actually appended
    (mixed spec/non-spec batches: a non-spec slot rides the same program
    with ``nn = 1``); rows ``m >= nn`` are never substituted into scores
    or written back,
    and query rows past a slot's real count only ever produce
    don't-care outputs the caller discards.

    THE NEW ROWS ARE SUBSTITUTED WHERE THEY LAND. The scored tile of
    the split(s) holding columns ``ap … ap + nn − 1`` takes the new
    rows' scores and values at those columns (a select over the tile);
    every other split runs the same body without the selects, which
    would change nothing there. Either way a column's score enters the
    online softmax with its tile, so the sums and their order do not
    depend on the variant — nor on whether a row arrived in this step
    or an earlier one (verify-k ≡ its sequential steps, bit for bit).

    The PAGED variant is the same body plus ONE extra predicate: grid
    step ``ki`` is the LOGICAL page ordinal, so every mask/score/append
    computation below already speaks logical positions — the BlockSpec
    index maps (which translate logical ordinal → pool page, clamping
    unallocated/−1 entries to the sink) live in ``flash_decode``, and
    the body additionally gates its scoring block on
    ``pt_ref[slot·ns + ki] >= 0`` (and a new row's score on the entry
    of the page it lands in): a −1 table entry means the slot does
    not hold that ordinal's page in THIS pool — beyond the fill on a
    single-pool cache, or owned by ANOTHER mesh shard on a sequence-
    sharded page table — and its sink-redirected bytes must not enter
    the softmax (their garbage scores would land below the causal fill
    and pollute the denominator). For a single pool the predicate is
    redundant with the fill check; for the sharded table it is the
    whole shard-local page-range view.

    RING (``ring``): the buffer's columns are a ring, not positions.
    ``vt`` is the column of the newest valid row (the append column)
    and a fourth prefetched vector ``span`` the number of valid rows
    ending there, cyclically; the two predicates that speak positions —
    which splits hold a valid column (``run``) and which columns of a
    split are valid (``masked``) — ask the cyclic interval instead.
    Everything else (scores, substitution, online softmax, write-back)
    is the body above, unchanged: a column is a column.

    TAIL (``geom.tail``): the K/V results are refs to the buffers in HBM
    and not write-back blocks, and four scratch refs and a semaphore
    array follow the softmax state. ``_sweep_end`` tells from the
    prefetched lengths whether a slot's last split is taken as its
    first ``tail`` rows; those are scored by the body above as one
    more block (``score_block``), after the last whole split's.

    PACKED (``packed``): there is no value operand, new rows, buffer or
    result: every name of the body that says V is the K ref of the same
    kind (a resident block enters both products), and the append is the
    one buffer's.

    THE PAIR PASS (``geom.heads_a_pass == 2``: a packed step of an even
    number of heads) is a body of the packed mode's own, ``pair_block``.
    A packed block ``[k | v]`` in both products pushes every byte
    through the MXU twice, half of each push a contraction against
    zeros or an output nobody reads, behind a query tile that is mostly
    padding. So heads ``2j`` and ``2j + 1`` of the step are scored
    together: their keys ``[k_A | k_B]`` and their values ``[v_A | v_B]``
    are formed in VMEM (:func:`_pair_lanes`), ``q_ref[j]`` holds A's
    query rows and then B's, each ``[q | 0]`` as the mode's caller
    sends them and each head's new-row-major (so a row's intra-step
    index is ``(row mod n·group) // group``), B's are turned half a row
    to ``[0 | q_B]`` (``pair_queries``), one score product, one softmax
    tile, one
    ``(m, l, acc)`` state and one ``p · [v_A | v_B]`` serve the pair;
    the context is lanes ``[0, w/2)`` of A's rows and ``[w/2, w)`` of
    B's, and the last step turns A's half a row so that both leave in
    the value lanes, as the single-head body's does (a stack of two
    lane-offset slices in the caller read WRONG on XLA:TPU; chip, PR
    52). A column's score is the same products summed in the same
    split order as the single-head body's. The write-back is a head's
    own rows in the buffer's own layout, as everywhere."""
    hb, bk, wr, tail = (geom.heads, geom.block_k, geom.write_rows,
                       geom.tail)
    per_slot = h_kv // hb                       # grid rows a slot
    pair = geom.heads_a_pass == 2

    def kernel_body(vt_ref, ap_ref, nn_ref, *refs, pt_ref=None,
                    span_ref=None, row0_ref=None):
        b = pl.program_id(0)
        ki = pl.program_id(1)
        br = b // per_slot                      # cache batch row
        vt = vt_ref[br]                         # first new row's column
        ap = ap_ref[br]                         # append column (−1 none)
        appends = ap >= 0
        nn = jnp.where(appends, nn_ref[br], 0)  # rows appended (0..n)
        tile = _write_tile(ki, ap, nn, geom, ns * bk)

        it = iter(refs)
        q_ref = next(it)
        sqf_ref = next(it) if quantized else None
        kn_ref = next(it)
        kqn_ref = next(it) if quantized else None
        ksn_ref = next(it) if quantized else None
        vn_ref = kn_ref if packed else next(it)
        k_ref = next(it)
        kq_ref = next(it) if quantized else None
        ks_ref = next(it) if quantized else None
        v_ref = k_ref if packed else next(it)
        alibi_ref = next(it) if has_alibi else None
        o_ref, m_ref, l_ref, ko_ref = (
            next(it), next(it), next(it), next(it))
        vo_ref = None if packed else next(it)
        kqo_ref = next(it) if quantized else None
        kso_ref = next(it) if quantized else None
        m_s, l_s, acc_s = next(it), next(it), next(it)
        if tail:
            # The tail's rows, the write-back tile's staging rows and
            # their DMA semaphores.
            ktail_ref = next(it)
            vtail_ref = ktail_ref if packed else next(it)
            kw_ref = next(it)
            vw_ref = None if packed else next(it)
            sems = next(it)
            # K then V: the result in HBM, the tail's rows, the staging
            # tile, the new rows.
            kv = [(ko_ref, ktail_ref, kw_ref, kn_ref)]
            if not packed:
                kv.append((vo_ref, vtail_ref, vw_ref, vn_ref))

        # What scoring reads: the int8 mirror and its scales if any.
        score_ref, score_new_ref = ((kq_ref, kqn_ref) if quantized
                                    else (k_ref, kn_ref))

        def scores(h, k_rows, k_scales=None):
            """Head ``h``'s query rows against ``k_rows`` (int8 rows
            and their scales when quantized): base-2 logits."""
            s = jax.lax.dot_general(
                q_ref[h], k_rows, (((1,), (1,)), ((), ())),
                preferred_element_type=(jnp.int32 if quantized
                                        else jnp.float32))
            if quantized:
                s = s.astype(jnp.float32) * sqf_ref[h] * k_scales
            return s

        # Intra-step row index: row j·group + g is new row j's head g,
        # so j = row // group (padded rows land past n — don't-care).
        jrow = jax.lax.broadcasted_iota(jnp.int32, (g_pad, 1), 0)
        if pair:
            # Two heads' rows a tile, each head's new-row-major.
            jrow = jrow % (n * group)
        jrow = jrow // group

        @pl.when(ki == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, _NEG_BIG)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        def hits(col0, size):
            """Does the block of ``size`` columns from ``col0`` hold a
            valid one? None at all lies strictly past the LAST new
            row's fill (row n−1 attends up to vt + n − 1), or — with a
            window — wholly before row 0's lookback (later rows look
            back from later positions, so row 0's bound is the earliest
            column any row can attend)."""
            run = col0 <= vt + (n - 1)
            if window is not None:
                run = jnp.logical_and(run, col0 + size - 1 > vt - window)
            return run

        # Block-skip: no valid column in this split.
        if ring:
            span = span_ref[br]
            run = _ring_hits(ki, bk, vt, span, ns * bk)
        else:
            run = hits(ki * bk, bk)
        if pt_ref is not None:
            # Paged: only score pages this table actually holds — a −1
            # ordinal streams the sink (see flash_decode's index-map
            # clamp) and must stay out of the online softmax. On a
            # sequence-sharded page table this is the shard-local
            # page-range restriction; the cross-shard pmax/psum merge
            # of the (num, m, l) partials reassembles exact full
            # attention.
            run = jnp.logical_and(run, pt_ref[br * ns + ki] >= 0)
        if tail:
            # Where the tail takes the split that holds the slot's last
            # column, that split is not this stream's (its block stays
            # on the split before): the tail below moves what is filled
            # of it.
            whole, last, tails = _sweep_end(vt, ap, geom, ns * bk)
            run = jnp.logical_and(run, ki <= whole)

        def lands(col0, size):
            # Does the block hold a column the step appends? (nn is 0
            # where nothing is appended, so no block does.)
            return jnp.logical_and(col0 < ap + nn, ap < col0 + size)

        def positions(col0, size):
            """``(cols, masked, relf)`` of a block of ``size`` columns
            from ``col0``, a query tile's rows against them: the column
            numbers, which pairs are masked, and the float32 distance
            ALiBi scales (None without it)."""
            cols = (col0 + jax.lax.broadcasted_iota(
                jnp.int32, (g_pad, size), 1))
            if ring:
                # How far behind the newest row a column lies, around
                # the ring; every column nearer than span is in-window
                # by construction (the caller's span never exceeds it).
                back = vt - cols
                back = jnp.where(back < 0, back + ns * bk, back)
                masked = back >= span
            else:
                rel = cols - vt - jrow            # ≤ 0 on valid columns
                masked = rel > 0
                if window is not None:
                    masked = jnp.logical_or(masked, rel <= -window)
            relf = rel.astype(jnp.float32) if has_alibi else None
            return cols, masked, relf

        def online_softmax(i, s, masked, v):
            """Fold the base-2 logits ``s`` of query tile ``i`` and
            their values ``v`` into the tile's running state."""
            s = jnp.where(masked, -jnp.inf, s)
            m_prev = m_s[i]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            m_s[i] = m_new
            l_s[i] = l_s[i] * corr + p.sum(axis=-1, keepdims=True)
            acc_s[i] = acc_s[i] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        def score_block(col0, size, score_blk, scale_blk, k_blk, v_blk,
                        substitute):
            """Fold the resident block of ``size`` cache rows from
            column ``col0`` into every head's softmax state: the whole
            split (``score_ref`` …) or a sub-block of the tail."""
            cols, masked, relf = positions(col0, size)
            for h in range(hb):
                s = scores(h, score_blk[h],
                           scale_blk[h] if quantized else None)
                v = v_blk[h]
                if substitute:
                    # New row m replaces whatever the buffer held at
                    # column ap + m (the nn guard keeps rows a
                    # mixed-batch slot did NOT append from leaking in).
                    s_new = scores(h, score_new_ref[h],
                                   ksn_ref[h, 0, 0] if quantized else None)
                    rows_v = col0 + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 0)
                    for m in range(n):
                        s = jnp.where(
                            jnp.logical_and(cols == ap + m, m < nn),
                            s_new[:, m:m + 1], s)
                        v = jnp.where(
                            jnp.logical_and(rows_v == ap + m, m < nn),
                            vn_ref[h, m:m + 1, :], v)
                if has_alibi:
                    s = s + alibi_ref[h] * relf
                online_softmax(h, s, masked, v)

        # Of a pair's tile, the rows that are head A's.
        is_a = pair and jax.lax.broadcasted_iota(
            jnp.int32, (g_pad, 1), 0) < n * group

        def pair_queries(j):
            """Pair ``j``'s queries block-diagonally: A's rows as they
            arrive, ``[q_A | 0]``, B's turned half a row, ``[0 | q_B]``
            (their value lanes are zeros by the mode's contract). Here
            and not in the caller: XLA:TPU would not be trusted with it
            (chip, PR 52 — a ``jnp.roll`` of these few rows aborted its
            compiler when called eagerly). Mosaic rotates 32-bit lanes
            alone; the round trip through float32 is exact."""
            q2 = q_ref[j]
            turned = pltpu.roll(q2.astype(jnp.float32), q2.shape[-1] // 2,
                                1).astype(q2.dtype)
            return jnp.where(is_a, q2, turned)

        def pair_block(col0, size, blk, _scales, _k, _v, substitute):
            """``score_block`` for the packed mode's pairs, two heads a
            pass (the mode's blocks are ONE ref: ``blk`` is what is
            scored, the keys and the values)."""
            cols, masked, relf = positions(col0, size)
            for j in range(hb // 2):
                k, v = _pair_lanes(blk[2 * j], blk[2 * j + 1])
                q2 = pair_queries(j)
                s = jax.lax.dot_general(
                    q2, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if substitute:
                    k_new, v_new = _pair_lanes(kn_ref[2 * j],
                                               kn_ref[2 * j + 1])
                    s_new = jax.lax.dot_general(
                        q2, k_new, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    rows_v = col0 + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 0)
                    for m in range(n):
                        s = jnp.where(
                            jnp.logical_and(cols == ap + m, m < nn),
                            s_new[:, m:m + 1], s)
                        v = jnp.where(
                            jnp.logical_and(rows_v == ap + m, m < nn),
                            v_new[m:m + 1, :], v)
                if has_alibi:
                    s = s + alibi_ref[j] * relf
                online_softmax(j, s, masked, v)

        fold = pair_block if pair else score_block

        def score_split(substitute):
            fold(ki * bk, bk, score_ref, ks_ref, k_ref, v_ref, substitute)

        landing = lands(ki * bk, bk)
        pl.when(jnp.logical_and(run, landing))(
            lambda: score_split(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(landing)))(
            lambda: score_split(False))

        def put(h, src_ref, new_ref, dst_ref, off, row0):
            """The write-back tile of head ``h`` — ``wr`` rows from row
            ``off`` of the resident block, cache rows ``row0 …`` — with
            the new rows substituted."""
            if src_ref.shape[1] == wr:
                old = src_ref[h]
            else:
                old = src_ref[h, pl.ds(pl.multiple_of(off, wr), wr), :]
            rows = row0 + jax.lax.broadcasted_iota(
                jnp.int32, old.shape, 0)
            for m in range(n):
                hit = jnp.logical_and(rows == ap + m, m < nn)
                old = jnp.where(hit, new_ref[h, m:m + 1, :], old)
            dst_ref[h] = old

        # In-place append: substitute the new rows into the tile of the
        # resident block and write it back — the ONLY cache rows written
        # this step (every other aliased row keeps its bits untouched).
        split = tile * wr // bk                  # the split of the tile
        at_split = ki == split
        if tail:
            # … which the whole-split stream holds unless it is the
            # tail's. The tail's outputs are the buffers in HBM, written
            # by a copy of the kernel's own: a slot that appends nothing
            # writes nothing (no tile to copy through).
            in_tail = jnp.logical_and(
                appends, jnp.logical_and(tails, split == last))
            at_split = jnp.logical_and(at_split, jnp.logical_and(
                appends, jnp.logical_not(in_tail)))
            # The grid row's first flat row of the cache buffers.
            row0 = b * hb
            if row0_ref is not None:
                row0 = row0 + row0_ref[0] * hb

            def stage(srcs, off):
                """The tile from the resident rows of ``srcs`` (K, V)
                into the staging blocks, the new rows substituted, and
                on to the cache."""
                for h in range(hb):
                    for src, (_, _, tile_ref, new_ref) in zip(srcs, kv):
                        put(h, src, new_ref, tile_ref, off, tile * wr)
                at = pl.ds(pl.multiple_of(tile * wr, wr), wr)
                back = [pltpu.make_async_copy(
                    tile_ref, hbm.at[pl.ds(row0, hb), at, :], sems.at[i, 1])
                    for i, (hbm, _, tile_ref, _) in enumerate(kv)]
                for copy in back:
                    copy.start()
                for copy in back:
                    copy.wait()

        @pl.when(at_split)
        def _():
            off = tile * wr - ki * bk
            if tail:
                stage((k_ref, v_ref), off)
                return
            # Head by head, like the scores: one head's tile in flight.
            for h in range(hb):
                put(h, k_ref, kn_ref, ko_ref, off, tile * wr)
                if not packed:
                    put(h, v_ref, vn_ref, vo_ref, off, tile * wr)
                if quantized:
                    put(h, kq_ref, kqn_ref, kqo_ref, off, tile * wr)
                    # (1, bk) scale row vector: the appended row is a
                    # lane, not a sublane.
                    lanes = tile * wr + jax.lax.broadcasted_iota(
                        jnp.int32, (1, wr), 1)
                    kso = ks_ref[h]
                    for m in range(n):
                        hit = jnp.logical_and(lanes == ap + m, m < nn)
                        kso = jnp.where(hit, ksn_ref[h, :, m:m + 1], kso)
                    kso_ref[h] = kso

        if tail:
            # THE TAIL: where what is filled of the split that holds
            # the slot's last column fits ``tail`` rows, those rows
            # alone are moved — by a copy of the kernel's own from the
            # aliased result (the same buffer in HBM), started under
            # the grid row BEFORE this one — and scored beside the last
            # whole split. Rows that were not moved are not scored: an
            # unfilled VMEM row may hold anything, NaN too, and 0 · NaN
            # is NaN.
            def moves(first_row, split):
                """The copies of ``tail`` rows of ``split`` for the grid
                row whose heads start at flat row ``first_row``."""
                at = pl.ds(pl.multiple_of(split * bk, bk), tail)
                return [pltpu.make_async_copy(
                    hbm.at[pl.ds(first_row, hb), at, :], rows, sems.at[i, 0])
                    for i, (hbm, rows, _, _) in enumerate(kv)]

            @pl.when(jnp.logical_and(jnp.logical_and(b == 0, ki == 0),
                                     tails))
            def _():                 # nobody ran before the first row
                for copy in moves(row0, last):
                    copy.start()

            @pl.when(jnp.logical_and(ki == whole, tails))
            def _():
                for copy in moves(row0, last):
                    copy.wait()
                col0 = last * bk
                pl.when(hits(col0, tail))(lambda: fold(
                    col0, tail, ktail_ref, None, ktail_ref, vtail_ref,
                    True))
                pl.when(in_tail)(lambda: stage(
                    (ktail_ref, vtail_ref), tile * wr - col0))

            # Under this row's last step — behind its own tail, which a
            # step before the last one took — the next row's.
            rows_grid = pl.num_programs(0)
            ahead = jnp.minimum(b + 1, rows_grid - 1) // per_slot
            _, last_ahead, tails_ahead = _sweep_end(
                vt_ref[ahead], ap_ref[ahead], geom, ns * bk)

            @pl.when(jnp.logical_and(
                jnp.logical_and(ki == ns - 1, b + 1 < rows_grid),
                tails_ahead))
            def _():
                for copy in moves(row0 + hb, last_ahead):
                    copy.start()

        @pl.when(ki == ns - 1)
        def _():
            if pair:
                # p · [v_A | v_B]: B's rows hold their context in the
                # value lanes, where the packed mode's caller looks for
                # it; A's hold theirs in the key lanes, half a row off.
                for j in range(hb // 2):
                    acc = acc_s[j]
                    o_ref[j] = jnp.where(
                        is_a, pltpu.roll(acc, acc.shape[-1] // 2, 1), acc)
            else:
                o_ref[...] = acc_s[...]
            m_ref[...] = m_s[...]
            l_ref[...] = l_s[...]

    if stacked:
        # The layer index steers the BlockSpec index maps (and the rows
        # of the tail's own copies): the body sees one layer's blocks.
        def kernel_stacked(vt_ref, ap_ref, nn_ref, row0_ref, *refs):
            kernel_body(vt_ref, ap_ref, nn_ref, *refs, row0_ref=row0_ref)

        return kernel_stacked
    if ring:
        def kernel_ring(vt_ref, ap_ref, nn_ref, span_ref, *refs):
            kernel_body(vt_ref, ap_ref, nn_ref, *refs, span_ref=span_ref)

        return kernel_ring
    if not paged:
        return kernel_body

    def kernel_paged(vt_ref, ap_ref, nn_ref, pt_ref, *refs):
        kernel_body(vt_ref, ap_ref, nn_ref, *refs, pt_ref=pt_ref)

    return kernel_paged


def flash_decode(q, k_new, v_new, cache_k, cache_v, valid_to, append_at,
                 *, n_new=None, page_table=None, layer=None, k_q=None,
                 k_scale=None, scale=None, window=None, alibi_slopes=None,
                 qk_quant=None, interpret=None, block_k=None,
                 partials=False, latent_v=None, ring_span=None):
    """One fused decode step: in-place cache append + masked online-
    softmax attention of each slot's queries against its own prefix.

    ``q (B, H, k, d)``; ``k_new/v_new (B, H_kv, k, d·)`` the step's new
    rows per slot; ``cache_k/cache_v (B, H_kv, t_max, d·)`` the (static-
    shape) cache buffers, returned UPDATED — aliased in place on TPU,
    so jit callers should donate them. GQA is native: each group of
    ``H/H_kv`` query heads attends its cache head.

    VERIFY-k: ``k = q.shape[-2]`` may exceed 1 (draft-verify decoding's
    fused verify step): the k new rows append at consecutive columns
    ``append_at .. append_at + k − 1`` and query row ``j`` attends
    columns ``<= valid_to + j`` — the shared prefix plus the intra-step
    causal triangle among the new rows, each row with its own online-
    softmax state. ``k`` must not exceed the K split (the rows then
    span at most two blocks — both written in place, everything else
    untouched; a single-token step on a slab writes back only the
    sublane tile holding its row); the int8 mirror stays single-token
    (``qk_quant='int8'`` requires ``k == 1`` — the XLA path covers
    quantized verify-k).
    ``n_new (B,) int32`` (optional): per-slot count of rows ACTUALLY
    appended (mixed spec/non-spec batches — a slot with ``n_new = 1``
    rides the verify program as a classic decode step; rows past a
    slot's count are neither appended nor scored into it, and its query
    rows past the count produce don't-care outputs). Default: k rows
    wherever ``append_at >= 0``.

    ``valid_to (B,) int32``: per slot, the highest cache column its
    FIRST query row attends (its own global position, localized by the
    caller for sharded slabs; −1 or less = fully masked row → zero
    output). ``append_at (B,) int32``: the local column where
    ``k_new/v_new`` row 0 lands, or −1 to append nothing (inactive
    slot / non-owning shard). When ``append_at[i] >= 0`` it must equal
    ``valid_to[i]`` (standard causal decode ordering: each query row
    attends the rows at and before its own append column).

    ``qk_quant='int8'`` requires the cache's append-time mirror
    (``k_q``/``k_scale``) and scores s8×s8→s32 with in-kernel
    dequantization — the mirror's halved K bytes become halved stream
    traffic. The mirror and the bf16 buffer are BOTH appended in place.

    ``page_table (B, pages_per_slot) int32``: PAGED mode —
    ``cache_k``/``cache_v`` are global ``(pages + 1, H_kv, page_size,
    d·)`` pools whose LAST row is the reserved write-sink page
    (``init_paged_cache`` reserves it) and each slot's K split streams
    the pool pages its table row names (−1 = ordinal not held by this
    pool → the sink, and the kernel's run-gate skips scoring it; a
    slot appending nothing also writes its mandatory block flush to
    the sink, so no grid row ever writes a live page it doesn't own).
    A −1 below the causal fill is how a SEQUENCE-SHARDED page table
    expresses "another mesh shard owns this ordinal": each shard calls
    this kernel on its local pool + local table (``partials=True``)
    and the ``(num, m, l)`` triples pmax/psum-merge into exact full
    attention — the paged ring-decode step. The K split IS
    the page size, the grid and kernel body are unchanged — paging
    costs one prefetched index lookup per block, not a new kernel —
    and aliasing still writes only the single append page. With
    ``qk_quant='int8'``, ``k_q``/``k_scale`` are the MIRROR POOLS
    (``(pages + 1, H_kv, page_size, d) int8`` /
    ``(pages + 1, H_kv, page_size, 1) f32``,
    ``init_paged_cache(qk_quant='int8')``): scoring streams the int8
    pages through the same page-table redirect — halved K traffic at
    paged concurrency — and the mirror pages are appended in place
    alongside the bf16 pool.

    ``layer`` (int32 scalar, may be traced): STACKED mode — the buffers
    (and the mirror) carry a leading layer axis, ``(L, B, H_kv, t_max,
    d·)``, and the step reads and appends layer ``layer`` of them in
    place: the stack is viewed as ``(L·B·H_kv, t_max, d·)`` rows (a
    bitcast), the layer's first row ``layer · B·H_kv`` rides as one
    more scalar-prefetch operand and every cache index map adds it to
    its row. The WHOLE stacked operand is aliased to the result, so a
    layer loop that carries the stack updates it in place — no
    per-layer slice, no write-back; every other layer keeps its bits.
    Not with ``page_table`` (no stack builds paged caches).

    ``latent_v`` (static int): LATENT mode, for a cache that keeps ONE
    row a token for all heads (multi-head latent attention's compressed
    row, ``[c_kv ; k_rope]``), stored TIME-MINOR with no padding:
    ``cache_k ([L,] B, 1, d, t_max)``, a token a column. It is a
    program of its own (:func:`_latent_decode`: the Pallas program and
    its device scope are named ``mla_decode`` / ``ops.mla_decode``),
    single-token: ``cache_v`` and ``v_new`` are None, ``k_new (B, 1, d,
    128)`` is the new row as a lane tile of 128 identical columns (what
    the value block and the write-back tile take a column from), the
    one buffer is appended to and streamed ONCE, all ``H`` query heads
    are the rows of one score matmul over its ``d`` sublanes, and the
    values are the first ``latent_v`` sublanes of the same resident
    block. ``out`` is ``(B, H, 1, latent_v)``; the returned ``cache_v``
    is None. With ``layer`` or without; no other mode goes with it.

    ``ring_span (B,) int32``: RING mode, for a window layer's recycled
    cache (``models.decode.RingCache``), whose ``t_max`` columns hold
    position ``p`` at column ``p mod t_max``. The append column and the
    valid rows are then two things: ``append_at`` is the column the new
    row lands in, ``valid_to`` the column of the newest row the query
    attends (the same column where the slot appends), and
    ``ring_span[i]`` the number of valid rows ending there, going back
    around the ring — ``min(length + 1, window)`` of a decode step, so
    every one of them is in the window and no position is compared;
    rows the ring has not filled yet, and rows a reset left behind past
    the span, are masked. Splits that hold no valid column are neither
    scored nor streamed. Single-token (``k == 1``), not with ``window``
    (the span is the window), ``alibi_slopes``, ``page_table``,
    ``layer``, ``qk_quant`` or ``latent_v``. The same kernel body; the
    Pallas program is named ``flash_decode_ring`` and its device scope
    ``ops.flash_decode_ring``, opened inside ``ops.flash_decode``.

    PACKED mode (``cache_v=None`` and ``v_new=None``, no ``latent_v``):
    for heads narrower than a lane tile, whose K and V buffers would
    each be stored and streamed padded to 128 lanes. ``cache_k (B, H_kv,
    t_max, w)`` holds BOTH: a token's key (as scored: normed, rotated)
    in lanes ``[0, w/2)`` and its value in lanes ``[w/2, w)`` — at
    64-wide heads one 128-lane row with nothing padded, half the bytes
    of the two padded buffers. ``q (B, H, k, w)`` arrives with zeros in
    the value lanes, so ``q · [k | v] = q · k``; ``k_new (B, H_kv, k,
    w)`` is the new packed rows; a resident block enters both products,
    streamed once, and ``out (B, H, k, w/2)`` is the value lanes of
    ``p · [k | v]``. Pass ``scale`` (the default reads ``q``'s width).
    Not with ``page_table``, ``layer``, ``qk_quant`` or ``ring_span``.
    The same program name; where a grid step holds an even number of
    KV heads (``flash_decode_geometry(...).heads_a_pass == 2``) the
    body scores them two a pass — a pair's query rows share one tile,
    which the kernel makes block-diagonal — and the single-head body
    otherwise. Same operands, same results to
    the order of float32 sums inside a pass.

    THE TAIL (no argument: :func:`decode_geometry` gives a call its
    ``tail`` rows or None, ``valid_to`` decides a slot's step at run
    time): where no more than ``tail`` rows are filled of the K split
    that holds a slot's last valid column, those rows alone are moved
    and scored and the split's other rows never enter a product; the
    aliased results are then the buffers in HBM, the kernel copies the
    one tile that holds the appended row into them, and a slot that
    appends nothing writes nothing.

    Returns ``(out, cache_k, cache_v, k_q, k_scale)`` with
    ``out (B, H, k, dv)`` in ``cache_v.dtype`` — or, with
    ``partials=True``, ``((num, m, l), cache_k, cache_v, k_q, k_scale)``
    where ``num (B, H, k, dv) f32`` is the un-normalized context and
    ``m/l (B, H, k, 1)`` the base-2 running max / denominator per query
    row, for the flash-decoding cross-shard merge (pmax the maxes,
    rescale, psum).
    """
    if latent_v is not None:
        if not (cache_v is None and v_new is None and n_new is None
                and page_table is None and k_q is None and k_scale is None
                and window is None and alibi_slopes is None
                and qk_quant is None and ring_span is None
                and not partials):
            raise ValueError(
                'flash_decode: latent_v reads the values from the one '
                'time-minor buffer cache_k (…, 1, d >= latent_v, t_max): '
                'pass cache_v=None and v_new=None, and no other mode '
                'than layer')
        out, rows = _latent_decode(
            q, k_new, cache_k, valid_to, append_at, latent_v, layer=layer,
            scale=scale, interpret=interpret, block_k=block_k)
        return out, rows, None, None, None
    b, h, n, d = q.shape
    h_kv = cache_k.shape[-3]
    paged = page_table is not None
    stacked = layer is not None
    packed = cache_v is None
    if packed and (v_new is not None or paged or stacked or d % 2
                   or qk_quant is not None or ring_span is not None
                   or k_q is not None or k_scale is not None):
        raise ValueError(
            'flash_decode: the packed mode (cache_v=None) keeps keys and '
            'values in the two halves of ONE slab: pass v_new=None, and '
            'no page_table, layer, qk_quant or ring_span')
    dv, v_dtype = ((d, cache_k.dtype) if packed
                   else (cache_v.shape[-1], cache_v.dtype))
    ring = ring_span is not None
    if ring and (n != 1 or window is not None or paged or stacked
                 or alibi_slopes is not None or qk_quant is not None):
        raise ValueError(
            'flash_decode: ring_span is single-token and masks by the '
            'ring\'s valid interval alone: no window, alibi_slopes, '
            'page_table, layer, qk_quant or latent_v with it')
    if stacked and paged:
        raise ValueError('flash_decode: layer addresses a layer-stacked '
                         'slab cache; a paged pool has no layer axis')
    if cache_k.ndim != 4 + stacked:
        raise ValueError(
            f'flash_decode: cache_k {cache_k.shape} needs '
            f'{4 + stacked} axes — a layer-stacked (L, B, H_kv, t_max, '
            f'd) buffer goes with layer=, one layer\'s without')
    if n < 1:
        raise ValueError(f'flash_decode needs at least one query row, '
                         f'got {n}')
    if h % h_kv:
        raise ValueError(f'query heads {h} must be a multiple of cache '
                         f'kv heads {h_kv}')
    quantized = qk_quant == 'int8'
    if qk_quant not in (None, 'int8'):
        raise ValueError(f"qk_quant must be None or 'int8', "
                         f'got {qk_quant!r}')
    if quantized and n != 1:
        raise ValueError(
            f"qk_quant='int8' is single-token in the fused kernel "
            f'(got {n} rows) — the XLA decode path covers quantized '
            f'verify-k')
    if quantized and (k_q is None or k_scale is None):
        raise ValueError(
            "qk_quant='int8' needs the cache's k_q/k_scale mirror — "
            "init_cache(qk_quant='int8') for the slab buffers, "
            "init_paged_cache(qk_quant='int8') for the mirror pools")
    group = h // h_kv
    if paged:
        n_pages, page = cache_k.shape[0], cache_k.shape[2]
        ns = page_table.shape[1]            # logical pages per slot
        t_max = ns * page
        if block_k not in (None, page):
            raise ValueError(f'paged decode splits K at the page size '
                             f'{page}; block_k={block_k} cannot differ')
    else:
        t_max = cache_k.shape[-2]
    geom = flash_decode_geometry(
        q, cache_k, cache_v, page_table=page_table, qk_quant=qk_quant,
        block_k=block_k, ring=ring)
    if geom is None:
        raise ValueError(
            f'no usable K split for t_max={t_max} (block_k must '
            f'divide it); use the XLA decode path for this cache '
            f'shape')
    hb, bk, wr, tail = (geom.heads, geom.block_k, geom.write_rows,
                        geom.tail)
    ns = t_max // bk
    if n > bk:
        raise ValueError(
            f'verify-k width {n} exceeds the K split {bk} '
            f'({"page size" if paged else "block"}) — k rows must span '
            f'at most two blocks; use the XLA decode path for wider '
            f'verify steps')
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    nb = b * h_kv
    per_slot = h_kv // hb                   # grid rows a slot

    # Query tiles (and softmax states, and result blocks) of a grid
    # step, and of the call: a head a tile, or — the packed mode's pair
    # pass — heads 2j and 2j + 1 one after the other in tile j.
    tiles, nt = hb // geom.heads_a_pass, nb // geom.heads_a_pass
    # Query rows grouped per cache head, NEW-ROW-major (row j·group + g
    # = new row j, query head g — the layout the kernel's per-row
    # intra-step mask assumes), padded to the sublane multiple of their
    # kernel dtype; padded rows are sliced off the output.
    qg = jnp.swapaxes(q.reshape(b, h_kv, group, n, d), 2, 3
                      ).reshape(nt, geom.heads_a_pass * n * group, d)
    rows = n * group
    sub = _sublane(jnp.int8 if quantized else cache_k.dtype)
    g_pad = -(-qg.shape[1] // sub) * sub
    if quantized:
        qi, sq = _quantize_rows(qg, nb, rows, d)
        qf = _pad_rows(qi, sub)
        sqf = _pad_rows(sq * (scale * _LOG2E), sub)
        kni, kns = _quantize_rows(
            k_new.astype(cache_k.dtype).reshape(nb, 1, d), nb, 1, d)
        kni = _pad_rows(kni, sub)
    else:
        qf = _pad_rows(
            (qg.astype(jnp.float32) * (scale * _LOG2E)
             ).astype(cache_k.dtype), sub)

    # The new rows ride padded to the sublane tile of their dtype: the
    # Pallas-TPU lowering refuses a dot against a one-row operand (the
    # n = 1 score of the query rows against the appended row), and an
    # unaligned (n, d) block would relayout on every load. Rows >= n
    # are zeros the kernel never substitutes (its loops stop at n).
    knf = _pad_rows(k_new.astype(cache_k.dtype).reshape(nb, n, d),
                    _sublane(cache_k.dtype))
    if not packed:
        vnf = _pad_rows(v_new.astype(cache_v.dtype).reshape(nb, n, dv),
                        _sublane(cache_v.dtype))
    if paged:
        # Pool flattening mirrors the slab's (B, H_kv) fold: pool page
        # p's head hh lives at flat row p·H_kv + hh, so one BlockSpec
        # row index addresses (page, head) exactly like (slot, head).
        kf = cache_k.reshape(n_pages * h_kv, bk, d)
        vf = cache_v.reshape(n_pages * h_kv, bk, dv)
        # The table rides the prefetch RAW (−1s intact): the kernel
        # body's run-gate reads the sign to skip ordinals this pool
        # does not hold — beyond-fill on a single pool, another shard's
        # range on a sequence-sharded table — while the index maps
        # below clamp −1 to the pool's reserved SINK row (last page,
        # never allocated — init_paged_cache): a skipped ordinal
        # streams sink garbage (never scored) and, crucially, never
        # WRITES a page another slot owns — Pallas flushes every
        # output block, and grid rows have no cross-row write ordering
        # on real TPU, so parking idle write-backs on a live page
        # would race an in-flight append.
        sink = n_pages - 1
        ptf = jnp.asarray(page_table, jnp.int32).reshape(-1)
    else:
        # A stacked buffer folds its layer axis into the rows too:
        # layer l's (slot, head) row r lives at flat row l·nb + r.
        kf = cache_k.reshape(-1, t_max, d)
        vf = None if packed else cache_v.reshape(-1, t_max, dv)
    valid_to = jnp.asarray(valid_to, jnp.int32)
    append_at = jnp.asarray(append_at, jnp.int32)
    # Per-slot appended-row count: callers without mixed batches get
    # the full k wherever an append happens at all.
    if n_new is None:
        nnv = jnp.where(append_at >= 0, n, 0).astype(jnp.int32)
    else:
        nnv = jnp.asarray(n_new, jnp.int32)

    # Every index map below speaks GRID rows: grid row bi holds heads
    # bi·hb … bi·hb + hb − 1 of the flat (slot, head) rows — hb of one
    # slot's, since hb | H_kv — and a block's leading axis is hb rows
    # long, so bi is also the block index along it.
    def const_idx(bi, ki, *rs):
        return (bi, 0, 0)

    def _stream_blk(bi, ki, vt, ap):
        # Never DMA past a slot's last useful block (the LAST new row
        # attends up to vt + n − 1): beyond-fill splits alias the
        # resident block (skipped in-kernel), so a half-empty slot
        # streams half the bytes. With a tail the last useful block may
        # be the tail's: this stream then stops on the split before it.
        br = bi // per_slot
        return jnp.minimum(
            ki, _sweep_end(vt[br], ap[br], geom, t_max, n)[0])

    def _write_blk(bi, ki, ap, nn):
        # In units of the write-back block's wr rows.
        br = bi // per_slot
        return _write_tile(ki, ap[br], nn[br], geom, t_max)

    if paged:
        # The tentpole redirect: the index map translates the LOGICAL
        # block ordinal through the prefetched page-table row instead
        # of using it as the physical block — the gather that makes
        # paging nearly free (same DMA skip, same aliasing).
        def stream_idx(bi, ki, vt, ap, nn, pt):
            blk = _stream_blk(bi, ki, vt, ap)
            pg = pt[(bi // per_slot) * ns + blk]
            # −1 (ordinal not held by this pool) → the sink page; the
            # kernel's run-gate skips scoring it.
            return (jnp.where(pg >= 0, pg, sink) * per_slot
                    + bi % per_slot, 0, 0)

        def write_idx(bi, ki, vt, ap, nn, pt):
            # Appending nothing → write-back lands on the sink page,
            # never on a page some other slot is appending into; same
            # for a −1 table entry (the table rides RAW — clamp here).
            # The write tile is the page (decode_geometry).
            br = bi // per_slot
            a = ap[br]
            blk = _write_blk(bi, ki, ap, nn)
            pg = pt[br * ns + blk]
            page = jnp.where(jnp.logical_and(a >= 0, pg >= 0), pg, sink)
            return (page * per_slot + bi % per_slot, 0, 0)

        # Mirror-scale flat rows are (pages·H_kv, 1, page_size): one
        # K-split block per pool page, so the block index is always 0
        # and the ROW rides the same page-table redirect as the data
        # pages — the data-pool maps ARE the scale maps (one
        # definition, so a sink-redirect fix cannot miss its twin).
        stream_idx_row = stream_idx
        write_idx_row = write_idx
    else:
        # Stacked: the prefetched first grid row of the layer
        # (layer · nb / hb) redirects every cache block to its layer's
        # run of rows (``lay`` is empty otherwise) — the same kind of
        # redirect the page table does above.
        def _row(bi, lay):
            return bi + lay[0][0] if lay and not ring else bi

        def _ring_blk(bi, ki, vt, span):
            # Split ki where it holds a valid column of the slot's
            # cyclic interval; else a split that does and is resident
            # or next (the last one before ki, else the first), so a
            # split that is skipped in-kernel costs no DMA either.
            br = bi // per_slot
            head, lo = vt[br], vt[br] - span[br] + 1
            near = jnp.where(ki * bk > head, head // bk,
                             jnp.maximum(lo, 0) // bk)
            return jnp.where(_ring_hits(ki, bk, head, span[br], t_max),
                             ki, near)

        def _slab_blk(bi, ki, vt, ap):
            # (grid row, split). The steps behind a slot's last split
            # are idle: the stream spends them on the NEXT grid row's
            # first split (every row starts on split 0 and finds it
            # resident), where staying put would leave the last split's
            # scoring with nothing in flight and the row change with
            # nothing to score.
            blk = _stream_blk(bi, ki, vt, ap)
            ahead = jnp.logical_and(ki > blk, bi + 1 < nb // hb)
            return (jnp.where(ahead, bi + 1, bi),
                    jnp.where(ahead, 0, blk))

        def stream_idx(bi, ki, vt, ap, nn, *lay):
            if ring:
                return (bi, _ring_blk(bi, ki, vt, lay[0]), 0)
            row, blk = _slab_blk(bi, ki, vt, ap)
            return (_row(row, lay), blk, 0)

        def write_idx(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), _write_blk(bi, ki, ap, nn), 0)

        # The int8 scale mirror rides as a (nb, 1, t_max) ROW vector (a
        # size-1-axis reshape — a bitcast, not a transpose), blocked on
        # the LAST axis, so the kernel consumes (1, BK) scale rows
        # directly.
        def stream_idx_row(bi, ki, vt, ap, nn, *lay):
            row, blk = _slab_blk(bi, ki, vt, ap)
            return (_row(row, lay), 0, blk)

        def write_idx_row(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), 0, _write_blk(bi, ki, ap, nn))

    in_specs = [pl.BlockSpec((tiles, g_pad, d), const_idx)]
    args = [qf]
    if quantized:
        in_specs.append(pl.BlockSpec((hb, g_pad, 1), const_idx))
        args.append(sqf)
    in_specs.append(pl.BlockSpec((hb,) + knf.shape[1:], const_idx))
    args.append(knf)
    if quantized:
        in_specs += [pl.BlockSpec((hb,) + kni.shape[1:], const_idx),
                     pl.BlockSpec((hb, 1, 1), const_idx)]
        args += [kni, kns.reshape(nb, 1, 1)]
    if not packed:
        in_specs.append(pl.BlockSpec((hb,) + vnf.shape[1:], const_idx))
        args.append(vnf)
    # The bf16 K buffer: streamed for scoring in the plain path; in the
    # quantized path scoring reads the mirror instead, so K is fetched
    # ONLY at its write block (one DMA per slot, to seed the append).
    in_specs.append(pl.BlockSpec((hb, bk, d),
                                 write_idx if quantized else stream_idx))
    k_in_pos = len(args)
    args.append(kf)
    kq_in_pos = ks_in_pos = None
    if quantized:
        if paged:
            # Mirror POOLS flatten exactly like the data pools: pool
            # page p's head hh at flat row p·H_kv + hh; the scale pool
            # folds its size-1 last axis into a (…, 1, page_size) row
            # vector per flat row (a bitcast, not a transpose).
            kqf = k_q.reshape(n_pages * h_kv, bk, d)
            ksf = k_scale.reshape(n_pages * h_kv, 1, bk)
        else:
            kqf = k_q.reshape(-1, t_max, d)
            ksf = k_scale.reshape(-1, 1, t_max)
        in_specs += [pl.BlockSpec((hb, bk, d), stream_idx),
                     pl.BlockSpec((hb, 1, bk), stream_idx_row)]
        kq_in_pos = len(args)
        args.append(kqf)
        ks_in_pos = len(args)
        args.append(ksf)
    if not packed:
        in_specs.append(pl.BlockSpec((hb, bk, dv), stream_idx))
        v_in_pos = len(args)
        args.append(vf)
    has_alibi = alibi_slopes is not None
    if has_alibi:
        # Per-query-head slopes, pre-folded by log2e (the kernel's
        # logits are in log2 units), laid out (nb, g_pad, 1) so slope
        # rows align with their grouped query rows (tiled over the n
        # new rows — row j·group + g carries head g's slope).
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(
            h_kv, group, 1) * _LOG2E
        slopes = jnp.broadcast_to(slopes[None, :, None],
                                  (b, h_kv, n, group, 1))
        in_specs.append(pl.BlockSpec((tiles, g_pad, 1), const_idx))
        args.append(_pad_rows(slopes.reshape(nt, -1, 1), sub))

    out_specs = [
        pl.BlockSpec((tiles, g_pad, dv), const_idx),  # num
        pl.BlockSpec((tiles, g_pad, 1), const_idx),    # m
        pl.BlockSpec((tiles, g_pad, 1), const_idx),    # l
        # k (aliased): the write-back tile, or — with a tail — the
        # buffer itself, which the kernel's own copies read and write.
        (pl.BlockSpec(memory_space=pl.ANY) if tail
         else pl.BlockSpec((hb, wr, d), write_idx)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nt, g_pad, dv), jnp.float32),
        jax.ShapeDtypeStruct((nt, g_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((nt, g_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct(kf.shape, kf.dtype),
    ]
    # +n_prefetch: alias indices count the scalar-prefetch operands
    # (valid_to, append_at, n_new, and — paged — the flattened page
    # table or — stacked — the layer's first flat row).
    prefetch = (valid_to, append_at, nnv)
    if paged:
        prefetch += (ptf,)
    elif stacked:
        prefetch += ((jnp.asarray(layer, jnp.int32)
                      * (nb // hb)).reshape(1),)
    elif ring:
        prefetch += (jnp.asarray(ring_span, jnp.int32),)
    n_prefetch = len(prefetch)
    aliases = {n_prefetch + k_in_pos: 3}
    if not packed:
        aliases[n_prefetch + v_in_pos] = 4
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY) if tail else
                         pl.BlockSpec((hb, wr, dv), write_idx))  # v (aliased)
        out_shape.append(jax.ShapeDtypeStruct(vf.shape, vf.dtype))
    if quantized:
        out_specs += [pl.BlockSpec((hb, wr, d), write_idx),
                      pl.BlockSpec((hb, 1, wr), write_idx_row)]
        out_shape += [jax.ShapeDtypeStruct(kqf.shape, kqf.dtype),
                      jax.ShapeDtypeStruct(ksf.shape, ksf.dtype)]
        aliases[n_prefetch + kq_in_pos] = 5
        aliases[n_prefetch + ks_in_pos] = 6

    scratch = [pltpu.VMEM((tiles, g_pad, 1), jnp.float32),
               pltpu.VMEM((tiles, g_pad, 1), jnp.float32),
               pltpu.VMEM((tiles, g_pad, dv), jnp.float32)]
    if tail:
        # The tail's rows and the write-back tile's staging rows, K
        # then V; a DMA semaphore each.
        kv = [(d, kf.dtype)] + ([] if packed else [(dv, vf.dtype)])
        scratch += [pltpu.VMEM((hb, tail, w), t) for w, t in kv]
        scratch += [pltpu.VMEM((hb, wr, w), t) for w, t in kv]
        scratch.append(pltpu.SemaphoreType.DMA((2, 2)))
    kernel = _make_decode_kernel(geom, ns, n, group, g_pad, h_kv, window,
                                 quantized, has_alibi, paged=paged,
                                 stacked=stacked, ring=ring, packed=packed)
    name = 'flash_decode'
    # The ring mode's scope opens INSIDE the kernel's own: a reader that
    # knows only ops.flash_decode still counts it as the decode kernel.
    inner = contextlib.nullcontext()
    if ring:
        name, inner = 'flash_decode_ring', device_scope(
            'ops.flash_decode_ring')
    with device_scope('ops.flash_decode'), inner:
        outs = kernel_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_prefetch,
                grid=(nb // hb, ns),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
            name=name)(*prefetch, *args)

    num, m, l, new_k = outs[:4]
    new_kq = new_ks = None
    if quantized:
        new_kq = outs[5].reshape(k_q.shape)
        new_ks = outs[6].reshape(k_scale.shape)   # same flat order
    new_k = new_k.reshape(cache_k.shape)
    new_v = None if packed else outs[4].reshape(cache_v.shape)

    def head_shape(x):
        # Rows are new-row-major per kv head (a pair's tile holds head
        # A's, then head B's): undo the (n, group) fold back to
        # (B, H, n, ·).
        x = x[:, :geom.heads_a_pass * n * group].reshape(
            b, h_kv, n, group, x.shape[-1])
        return jnp.swapaxes(x, 2, 3).reshape(b, h, n, x.shape[-1])

    num, m, l = head_shape(num), head_shape(m), head_shape(l)
    if packed:
        num = num[..., d // 2:]     # the value lanes of p · [k | v]
    if partials:
        return (num, m, l), new_k, new_v, new_kq, new_ks
    out = (num / jnp.where(l == 0.0, 1.0, l)).astype(v_dtype)
    return out, new_k, new_v, new_kq, new_ks


# ---------------------------------------------------------------------------
# The latent cache's kernel (``mla_decode``): ONE buffer, stored time-minor
# ---------------------------------------------------------------------------
#
# A latent cache keeps one row of ``d = kv_rank + rope_dim`` values a token
# for all heads (576 of bfloat16 where every accepted configuration is
# concerned), and 576 is no multiple of the 128-lane tile. Stored a token a
# ROW the buffer is either padded to 640 in HBM — a ninth of every byte the
# step moves is then a zero — or laid out by the chip with time minor
# anyway, and a row-block kernel pays a relayout. So the buffer IS
# time-minor, ``(rows, d, t_max)``: ``d`` lies on sublanes (576 = 36 tiles
# of 16), a token is a column, a K split a ``(d, block_k)`` block of whole
# lane tiles, and nothing in HBM is padding.
#
# Bytes alone move nothing here (chip, PR 47): with 32 query rows a session
# every value of a block enters an MXU twice, for a quarter of the array's
# rows, which takes as long as the block's bytes; and a grid step costs
# ~0.35 us whatever it moves. So the body is a program of its own. It shares
# the slab kernel's online softmax and its way with the idle steps, and none
# of its block shapes, copies or write-back:
#
# - the two products swap orientation: scores ``q · blk`` plain, the context
#   ``p · blk[:dv]ᵀ`` over the lanes of both;
# - the K split is LONG (``_LATENT_BLOCK_K``: up to 2048 columns), so the
#   step's own cost is a small part of it, and a block is scored in
#   SUB-BLOCKS of 512 columns whose score products are all issued before
#   the first softmax — the MXU has the next sub-block's product to run
#   under the VPU's max / exp2 chain of this one;
# - the split that holds a session's last valid column is NOT streamed
#   (a long split would move up to its whole length past the fill): it is
#   moved in PIECES of ``geom.tail`` columns, as many as hold a valid
#   column, by the kernel's own copies from the aliased result, started
#   under the session before;
# - the append is one COLUMN: a read-modify-write of the lane tile that
#   holds it.

# The K splits a latent buffer is tried at, longest first: multiples of
# the 512-column sub-block that the VMEM plan has room for four times
# (the stream and the last split's pieces, each double-buffered).
_LATENT_BLOCK_K = (2048, 1536, 1024, 512)
_LATENT_SUB = 512
_LATENT_PIECE = 256
# The latent kernel's VMEM plan, and the limit it asks the compiler for
# where the plan passes the slab kernel's (:data:`_VMEM_BUDGET`, what the
# default scoped limit of 16 MiB holds: a split of 1536 columns for 32
# query rows and not one of 2048; a v5e core has 128 MiB). A call within
# the default asks for nothing: scoped VMEM a kernel reserves is VMEM XLA
# cannot keep the next layer's prefetched weights in across it.
_LATENT_VMEM_BUDGET = 24 << 20
_LATENT_VMEM_LIMIT = 32 << 20


def _latent_vmem(bk, d, dv, rows, dtype):
    """The latent kernel's VMEM plan at a split of ``bk`` columns: the
    stream and the last split's pieces, each double-buffered, the
    staging tile and the new row's tile (double-buffered); the queries,
    the softmax state and the results at 4 bytes an element; a block's
    scores and a sub-block's probabilities, masks and value half with
    the new column selected in."""
    item = jnp.dtype(dtype).itemsize
    sub = _sublane(dtype)
    d_sub, g_pad = -(-d // sub) * sub, -(-rows // sub) * sub
    return ((4 * bk + 3 * 128) * d_sub * item
            + 3 * g_pad * (_lanes(d) + _lanes(dv) + 256) * 4
            + g_pad * (bk + 6 * min(bk, _LATENT_SUB)) * 4
            + min(bk, _LATENT_SUB) * dv * (item + 4))


def latent_geometry(t_max, d, dv, rows, dtype, *, block_k=None):
    """The latent kernel's grid step for a time-minor buffer of ``t_max``
    columns of ``d`` values (``dv`` of them the values), ``rows`` query
    rows (the heads) a session — or None where no K split divides
    ``t_max`` into whole lane tiles within the VMEM plan (the caller
    takes the XLA path).

    A step is ONE session's ``(d, block_k)`` block (``heads`` 1: the
    buffer has one shared head), ``bytes`` what it streams — stored
    bytes, nothing padded. The split is the longest of
    :data:`_LATENT_BLOCK_K` that divides ``t_max`` (else 256 or 128; a
    buffer of no more columns than the longest is one split).
    ``write_rows`` is the COLUMNS written back for the append: the
    128-lane tile that holds the new column (the whole split where it is
    no multiple of 128). ``tail`` is the PIECE, in columns,
    by which the split that holds a session's last valid column is
    moved (None for a buffer of one split, which is streamed whole)."""
    if block_k:
        splits = (block_k,)
    elif t_max <= _LATENT_BLOCK_K[0]:
        splits = (t_max,)
    else:
        splits = _LATENT_BLOCK_K + (256, 128)
    bk = next((c for c in splits if t_max % c == 0
               and (c % 128 == 0 or c == t_max)
               and _latent_vmem(c, d, dv, rows, dtype)
               <= _LATENT_VMEM_BUDGET), None)
    if bk is None:
        return None
    wr = 128 if bk % 128 == 0 else bk
    tail = None
    if t_max > bk:
        tail = _LATENT_PIECE if bk % _LATENT_PIECE == 0 else 128
    return DecodeGeometry(1, bk, wr,
                          bk * d * jnp.dtype(dtype).itemsize, tail)


def _latent_sweep(vt, ap, geom, t_max):
    """Where a session's sweep ends: ``(whole, last, pieces)`` — ``last``
    the K split that holds its last useful column (``vt``; or the append
    column, should a caller append past it), ``whole`` the last split the
    stream moves — the one before ``last``, whose filled part is moved in
    ``pieces`` pieces of ``geom.tail`` columns; or ``last`` itself, with
    no pieces, where that is split 0 (the stream moves one block a
    session whatever happens) or the buffer is one split. ONE definition
    for the stream's index map and the kernel body, which must agree."""
    bk = geom.block_k
    edge = jnp.maximum(vt, ap)
    last = jnp.clip(edge // bk, 0, t_max // bk - 1)
    if geom.tail is None:
        return last, last, 0
    tails = (last > 0).astype(jnp.int32)
    return (last - tails, last,
            tails * ((edge - last * bk) // geom.tail + 1))


def _columns(tile, width):
    """``width`` columns of the new row from its tile of identical
    ones."""
    if width == tile.shape[1]:
        return tile
    return jnp.broadcast_to(tile[:, :1], (tile.shape[0], width))


def _make_latent_kernel(geom, ns, nb, g_pad, dv, stacked):
    """The latent kernel's body. Grid = (sessions, ns), the K split
    innermost; refs in ``_latent_decode``'s order: the prefetched
    ``valid_to``, ``append_at`` (and, stacked, the layer's first flat
    row); the session's queries ``(1, g_pad, d)``, its new row as a tile
    of identical columns ``(1, 1, d, 128)``, the resident block of the
    stream ``(1, d, bk)``; the results ``num``, ``m``, ``l`` and the
    buffer itself in HBM (aliased: the pieces' copies read it, the
    write-back writes it); the softmax state, the staging tile, the DMA
    semaphores (pieces by parity, tile in, tile out) and, where the
    buffer is more than one split, the last split's pieces, a buffer a
    session's parity.

    THE APPEND is a read-modify-write of the lane tile that holds the
    new column, by the kernel's own copies from and to the aliased
    result: read under the session's first step, the column selected in
    and the tile sent back under its last, the copy back awaited under
    the NEXT session's first step (the last session's at once) — so a
    session that appends nothing writes nothing, and no step waits on a
    copy it has just started."""
    bk, wr, piece = geom.block_k, geom.write_rows, geom.tail
    t_max = ns * bk
    sub = _LATENT_SUB if bk % _LATENT_SUB == 0 else bk

    def kernel(vt_ref, ap_ref, *refs):
        first = 0
        if stacked:
            row0_ref, *refs = refs
            first = row0_ref[0]
        (q_ref, new_ref, k_ref, o_ref, m_ref, l_ref, hbm,
         m_s, l_s, acc_s, stage, sems, *last_refs) = refs
        b = pl.program_id(0)
        ki = pl.program_id(1)
        row = first + b                          # flat row of the buffer
        vt = vt_ref[b]                           # last column attended
        ap = ap_ref[b]                           # append column (−1 none)
        appends = ap >= 0
        whole, last, pieces = _latent_sweep(vt, ap, geom, t_max)

        @pl.when(ki == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, _NEG_BIG)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        def scores(blk):
            return jnp.dot(q_ref[0], blk,
                           preferred_element_type=jnp.float32)

        def fold(col0, s, v, substitute):
            """One sub-block's scores ``s (g_pad, size)`` and values ``v
            (dv, size)`` of the columns from ``col0`` into the softmax
            state; ``substitute``: the new row takes the place of
            whatever the buffer holds at column ``ap``."""
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if substitute:
                new = new_ref[0, 0]
                s = jnp.where(cols == ap, scores(new)[:, :1], s)
                v = jnp.where(cols[:1] == ap,
                              _columns(new[:dv], s.shape[1]), v)
            s = jnp.where(cols > vt, -jnp.inf, s)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            m_s[...] = m_new
            l_s[...] = l_s[...] * corr + p.sum(axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        def score_split(substitute):
            """The stream's resident split: every sub-block's score
            product first, then their folds."""
            at = [slice(i, i + sub) for i in range(0, bk, sub)]
            ss = [scores(k_ref[0, :, a]) for a in at]
            for a, s in zip(at, ss):
                fold(ki * bk + a.start, s, k_ref[0, :dv, a], substitute)

        # Scored where it holds a valid column and is not the pieces'
        # split; the new column selected in where it lands there.
        run = jnp.logical_and(ki * bk <= vt, ki <= whole)
        landing = jnp.logical_and(
            appends, jnp.logical_and(ki * bk <= ap, ap < ki * bk + bk))
        pl.when(jnp.logical_and(run, landing))(lambda: score_split(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(landing)))(
            lambda: score_split(False))

        if piece:
            # THE LAST SPLIT, in pieces: those that hold a valid column
            # are moved — copies from the aliased result, started under
            # the session BEFORE this one — and scored beside the last
            # whole split. What was not moved is not scored: a VMEM
            # column no copy landed in may hold anything, NaN too, and
            # 0 · NaN is NaN.
            last_ref, = last_refs   # (a session's parity, piece, d, piece)

            def moves(r, split, i, slot):
                at = pl.multiple_of(split * bk + i * piece, piece)
                return pltpu.make_async_copy(
                    hbm.at[pl.ds(r, 1), :, pl.ds(at, piece)],
                    last_ref.at[slot, pl.ds(i, 1)], sems.at[slot])

            def each(count, do):
                jax.lax.fori_loop(0, count, lambda i, _: do(i), None)

            def start(r, split, count, slot):
                each(count, lambda i: moves(r, split, i, slot).start())

            @pl.when(jnp.logical_and(b == 0, ki == 0))
            def _():                 # nobody ran before the first one
                start(row, last, pieces, 0)

            # The NEXT session's pieces start under this session's last
            # scored step, before its own pieces are scored: that step
            # computes a split and the pieces, and the stream alone
            # would leave the copy engine idle for the pieces' part.
            ahead = jnp.minimum(b + 1, nb - 1)
            _, last_ahead, pieces_ahead = _latent_sweep(
                vt_ref[ahead], ap_ref[ahead], geom, t_max)
            pl.when(jnp.logical_and(ki == whole, b + 1 < nb))(
                lambda: start(row + 1, last_ahead, pieces_ahead,
                              (b + 1) % 2))

            @pl.when(jnp.logical_and(ki == whole, pieces > 0))
            def _():
                slot = b % 2
                each(pieces, lambda i: moves(row, last, i, slot).wait())
                # piece by piece, those whose first column is valid: no
                # further than the copies reached
                each(jnp.clip((vt - last * bk) // piece + 1, 0, pieces),
                     lambda i: fold(last * bk + i * piece,
                                    scores(last_ref[slot, i]),
                                    last_ref[slot, i, :dv], True))

        # The append: the lane tile that holds column ``ap``.
        def tile_of(r, col):
            at = jnp.clip(col // wr, 0, t_max // wr - 1) * wr
            return hbm.at[pl.ds(r, 1), :, pl.ds(pl.multiple_of(at, wr), wr)]

        def sent(r, col):
            return pltpu.make_async_copy(stage, tile_of(r, col), sems.at[3])

        before = ap_ref[jnp.maximum(b - 1, 0)]

        @pl.when(jnp.logical_and(ki == 0,
                                 jnp.logical_and(b > 0, before >= 0)))
        def _():
            sent(row - 1, before).wait()

        fetched = pltpu.make_async_copy(tile_of(row, ap), stage, sems.at[2])
        pl.when(jnp.logical_and(ki == 0, appends))(fetched.start)

        @pl.when(jnp.logical_and(ki == ns - 1, appends))
        def _():
            fetched.wait()
            lanes = (ap // wr) * wr + jax.lax.broadcasted_iota(
                jnp.int32, (1, wr), 1)
            stage[0] = jnp.where(lanes == ap, _columns(new_ref[0, 0], wr),
                                 stage[0])
            sent(row, ap).start()
            pl.when(b == nb - 1)(sent(row, ap).wait)

        @pl.when(ki == ns - 1)
        def _():
            o_ref[0] = acc_s[...]
            m_ref[0] = m_s[...]
            l_ref[0] = l_s[...]

    return kernel


def _latent_decode(q, new, rows, valid_to, append_at, dv, *, layer=None,
                   scale=None, interpret=None, block_k=None):
    """:func:`flash_decode`'s latent mode: ``q (B, H, 1, d)``, ``new (B,
    1, d, 128)``, ``rows ([L,] B, 1, d, t_max)``; returns ``(out (B, H,
    1, dv), rows)``."""
    b, h, n, d = q.shape
    stacked = layer is not None
    t_max = rows.shape[-1]
    if (n != 1 or rows.ndim != 4 + stacked or rows.shape[-3:-1] != (1, d)
            or new.shape != (b, 1, d, 128) or not 0 < dv <= d):
        raise ValueError(
            f'flash_decode: latent_v takes one query row a head, q '
            f'(B, H, 1, d), the new row as k_new (B, 1, d, 128) and the '
            f'time-minor buffer cache_k ([L,] B, 1, d >= latent_v, t_max) '
            f'(with layer= where it has the L); got q {q.shape}, k_new '
            f'{new.shape}, cache_k {rows.shape}, latent_v {dv}')
    geom = latent_geometry(t_max, d, dv, h, rows.dtype, block_k=block_k)
    if geom is None:
        raise ValueError(
            f'no usable K split for the latent buffer\'s t_max={t_max} '
            f'(whole 128-column tiles that divide it); use the XLA '
            f'decode path for this cache shape')
    bk, wr = geom.block_k, geom.write_rows
    ns = t_max // bk
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    sub = _sublane(rows.dtype)
    g_pad = -(-h // sub) * sub
    qf = _pad_rows((q[:, :, 0].astype(jnp.float32) * (scale * _LOG2E)
                    ).astype(rows.dtype), sub)
    kf = rows.reshape(-1, d, t_max)
    prefetch = (jnp.asarray(valid_to, jnp.int32),
                jnp.asarray(append_at, jnp.int32))
    if stacked:
        prefetch += ((jnp.asarray(layer, jnp.int32) * b).reshape(1),)

    def const_idx(bi, ki, *rs):
        return (bi, 0, 0)

    def stream_idx(bi, ki, vt, ap, *lay):
        # Never past a session's last whole split; the steps behind it
        # carry the NEXT session's first split (the slab's ``_slab_blk``).
        blk = jnp.minimum(ki, _latent_sweep(vt[bi], ap[bi], geom,
                                            t_max)[0])
        ahead = jnp.logical_and(ki > blk, bi + 1 < b)
        row = jnp.where(ahead, bi + 1, bi)
        return (row + lay[0][0] if lay else row, 0,
                jnp.where(ahead, 0, blk))

    scratch = [pltpu.VMEM((g_pad, 1), jnp.float32),
               pltpu.VMEM((g_pad, 1), jnp.float32),
               pltpu.VMEM((g_pad, dv), jnp.float32),
               pltpu.VMEM((1, d, wr), rows.dtype),
               pltpu.SemaphoreType.DMA((4,))]
    if geom.tail:
        scratch.append(pltpu.VMEM((2, bk // geom.tail, d, geom.tail),
                                  rows.dtype))
    with device_scope('ops.mla_decode'):
        num, _, l, new_rows = kernel_call(
            _make_latent_kernel(geom, ns, b, g_pad, dv, stacked),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(b, ns),
                in_specs=[pl.BlockSpec((1, g_pad, d), const_idx),
                          pl.BlockSpec((1, 1, d, 128),
                                       lambda bi, ki, *rs: (bi, 0, 0, 0)),
                          pl.BlockSpec((1, d, bk), stream_idx)],
                out_specs=[pl.BlockSpec((1, g_pad, dv), const_idx),
                           pl.BlockSpec((1, g_pad, 1), const_idx),
                           pl.BlockSpec((1, g_pad, 1), const_idx),
                           pl.BlockSpec(memory_space=pl.ANY)],
                scratch_shapes=scratch),
            out_shape=[jax.ShapeDtypeStruct((b, g_pad, dv), jnp.float32),
                       jax.ShapeDtypeStruct((b, g_pad, 1), jnp.float32),
                       jax.ShapeDtypeStruct((b, g_pad, 1), jnp.float32),
                       jax.ShapeDtypeStruct(kf.shape, kf.dtype)],
            input_output_aliases={len(prefetch) + 2: 3},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_LATENT_VMEM_LIMIT if _latent_vmem(
                    bk, d, dv, h, rows.dtype) > _VMEM_BUDGET else None),
            interpret=interpret,
            name='mla_decode')(*prefetch, qf, new, kf)
    out = (num / jnp.where(l == 0.0, 1.0, l))[:, :h, None].astype(rows.dtype)
    return out, new_rows.reshape(rows.shape)
