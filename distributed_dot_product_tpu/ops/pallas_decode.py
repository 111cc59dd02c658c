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
  (``input_output_aliases``), and only the single block containing the
  append row is ever written; unwritten blocks keep their bits by the
  aliasing contract. A scanned stack's caches keep that property
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
- **masks per slot**: the per-slot valid lengths arrive as a
  scalar-prefetch vector that both the kernel (causal/window masking,
  the new row's score substitution) and the BlockSpec index maps read —
  blocks past a slot's fill are never even DMA'd (the index map clamps
  to the last useful block, and Pallas skips re-fetching a resident
  block), so a half-empty serving batch streams half the bytes;
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

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.obs.spans import device_scope
from distributed_dot_product_tpu.ops.pallas_attention import (
    _LOG2E, _NEG_BIG, _quantize_rows,
)

__all__ = ['flash_decode', 'decode_block_k']

# K-split cap: 1024 rows/block keeps the double-buffered K+V stream
# well inside VMEM at every head dim the repo uses (d=256 worst case:
# 2·(1024·256·2 B)·2 buffers ≈ 4 MB of the ~16 MB budget).
_BLOCK_K_CAP = 1024


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


def _make_decode_kernel(bk, ns, n, group, g_pad, h_kv, window,
                        quantized, has_alibi, paged=False, stacked=False,
                        latent_v=None):
    """Kernel body; refs are ordered to match ``flash_decode``'s spec
    list below. Grid = (B·H_kv, ns) with the K split innermost; the
    running softmax state lives in scratch across splits.

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
    or written back, and query rows past a slot's real count only ever
    produce don't-care outputs the caller discards.

    The PAGED variant is the same body plus ONE extra predicate: grid
    step ``ki`` is the LOGICAL page ordinal, so every mask/score/append
    computation below already speaks logical positions — the BlockSpec
    index maps (which translate logical ordinal → pool page, clamping
    unallocated/−1 entries to the sink) live in ``flash_decode``, and
    the body additionally gates its scoring block on
    ``pt_ref[slot·ns + ki] >= 0``: a −1 table entry means the slot does
    not hold that ordinal's page in THIS pool — beyond the fill on a
    single-pool cache, or owned by ANOTHER mesh shard on a sequence-
    sharded page table — and its sink-redirected bytes must not enter
    the softmax (their garbage scores would land below the causal fill
    and pollute the denominator). For a single pool the predicate is
    redundant with the fill check; for the sharded table it is the
    whole shard-local page-range view.

    LATENT (``latent_v``): there is ONE buffer. The values are the first
    ``latent_v`` columns of the very block the scores were taken from,
    so the V refs (new rows, cache in, cache out) are absent and every
    read of them below is a static lane slice of the K ones."""
    latent = latent_v is not None

    def kernel_body(vt_ref, ap_ref, nn_ref, *refs, pt_ref=None):
        b = pl.program_id(0)
        ki = pl.program_id(1)
        br = b // h_kv                          # cache batch row
        vt = vt_ref[br]                         # first new row's column
        ap = ap_ref[br]                         # append column (−1 none)
        nn = nn_ref[br]                         # rows appended (0..n)
        # The block(s) the append write targets — must equal the k/v OUT
        # BlockSpec index maps exactly (ap < 0 ⇒ a copy-through of
        # block 0, because Pallas writes every output block back and an
        # unwritten one would clobber the aliased cache with garbage).
        # n rows span at most TWO consecutive blocks (n <= bk is
        # enforced by flash_decode): the write index map clamps ki into
        # [wfirst, wlast], so the kernel writes the ref exactly when ki
        # lands on each physical block, right before Pallas flushes it.
        wfirst = jnp.where(ap >= 0, jnp.clip(ap // bk, 0, ns - 1), 0)
        wlast = jnp.where(
            ap >= 0,
            jnp.clip((ap + jnp.maximum(nn, 1) - 1) // bk, 0, ns - 1), 0)

        it = iter(refs)
        q_ref = next(it)
        sqf_ref = next(it) if quantized else None
        kn_ref = next(it)
        kqn_ref = next(it) if quantized else None
        ksn_ref = next(it) if quantized else None
        vn_ref = None if latent else next(it)
        k_ref = next(it)
        kq_ref = next(it) if quantized else None
        ks_ref = next(it) if quantized else None
        v_ref = None if latent else next(it)
        alibi_ref = next(it) if has_alibi else None
        o_ref, m_ref, l_ref, ko_ref = (
            next(it), next(it), next(it), next(it))
        vo_ref = None if latent else next(it)
        kqo_ref = next(it) if quantized else None
        kso_ref = next(it) if quantized else None
        m_s, l_s, acc_s = next(it), next(it), next(it)

        @pl.when(ki == 0)
        def _():
            m_s[:] = jnp.full_like(m_s, _NEG_BIG)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

        # Block-skip: no valid column in this split — strictly past the
        # LAST new row's fill (row n−1 attends up to vt + n − 1), or —
        # with a window — wholly before row 0's lookback (later rows
        # look back from later positions, so row 0's bound is the
        # earliest column any row can attend).
        run = ki * bk <= vt + (n - 1)
        if window is not None:
            run = jnp.logical_and(run, ki * bk + bk - 1 > vt - window)
        if pt_ref is not None:
            # Paged: only score pages this table actually holds — a −1
            # ordinal streams the sink (see flash_decode's index-map
            # clamp) and must stay out of the online softmax. On a
            # sequence-sharded page table this is the shard-local
            # page-range restriction; the cross-shard pmax/psum merge
            # of the (num, m, l) partials reassembles exact full
            # attention.
            run = jnp.logical_and(run, pt_ref[br * ns + ki] >= 0)

        @pl.when(run)
        def _():
            cols = (ki * bk
                    + jax.lax.broadcasted_iota(jnp.int32, (g_pad, bk), 1))
            # Intra-step row index: row j·group + g is new row j's head
            # g, so j = row // group (padded rows land past n — fully
            # masked below).
            jrow = (jax.lax.broadcasted_iota(jnp.int32, (g_pad, bk), 0)
                    // group)
            if quantized:
                # ks_ref blocks are (1, BK): the K-row scales already
                # laid out as a row vector (the training kernels'
                # convention — no in-kernel transpose/relayout).
                s = jax.lax.dot_general(
                    q_ref[0], kq_ref[0], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
                s = s * sqf_ref[0] * ks_ref[0]
                s_new = jax.lax.dot_general(
                    q_ref[0], kqn_ref[0], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
                s_new = s_new * sqf_ref[0] * ksn_ref[0, 0, 0]
            else:
                s = jax.lax.dot_general(
                    q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s_new = jax.lax.dot_general(
                    q_ref[0], kn_ref[0], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            # The appended rows' scores replace whatever the buffer held
            # at their columns (new row m lands at ap + m; the nn guard
            # keeps rows a mixed-batch slot did NOT append from leaking
            # in; ap == −1 matches no column: cols are ≥ 0 and nn is 0).
            for m in range(n):
                sel = jnp.logical_and(cols == ap + m, m < nn)
                s = jnp.where(sel, s_new[:, m:m + 1], s)
            rel = cols - vt - jrow                # ≤ 0 on valid columns
            if alibi_ref is not None:
                s = s + alibi_ref[0] * rel.astype(jnp.float32)
            masked = rel > 0
            if window is not None:
                masked = jnp.logical_or(masked, rel <= -window)
            s = jnp.where(masked, -jnp.inf, s)

            v = k_ref[0, :, :latent_v] if latent else v_ref[0]
            rows_v = (ki * bk
                      + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0))
            for m in range(n):
                sel = jnp.logical_and(rows_v == ap + m, m < nn)
                v = jnp.where(sel, (kn_ref[0, m:m + 1, :latent_v]
                                    if latent else vn_ref[0, m]), v)

            m_prev = m_s[:]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp2(s - m_new)
            corr = jnp.exp2(m_prev - m_new)
            m_s[:] = m_new
            l_s[:] = l_s[:] * corr + p.sum(axis=-1, keepdims=True)
            acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # In-place append: substitute the new rows into the resident
        # block(s) and write them back — the ONLY cache blocks written
        # this step (every other aliased block keeps its bits
        # untouched). With n > 1 the rows may straddle one block
        # boundary; the write index map clamps ki into [wfirst, wlast],
        # so writing at both gives each physical block its substituted
        # content before Pallas flushes it.
        @pl.when(jnp.logical_or(ki == wfirst, ki == wlast))
        def _():
            rows_k = (ki * bk
                      + jax.lax.broadcasted_iota(
                          jnp.int32, k_ref.shape[1:], 0))
            ko = k_ref[0]
            for m in range(n):
                ink = jnp.logical_and(rows_k == ap + m, m < nn)
                ko = jnp.where(ink, kn_ref[0, m], ko)
            ko_ref[0] = ko
            if not latent:
                rows_v = (ki * bk
                          + jax.lax.broadcasted_iota(
                              jnp.int32, v_ref.shape[1:], 0))
                vo = v_ref[0]
                for m in range(n):
                    inv = jnp.logical_and(rows_v == ap + m, m < nn)
                    vo = jnp.where(inv, vn_ref[0, m], vo)
                vo_ref[0] = vo
            if quantized:
                cols_s = (ki * bk
                          + jax.lax.broadcasted_iota(
                              jnp.int32, ks_ref.shape[1:], 1))
                kqo, kso = kq_ref[0], ks_ref[0]
                for m in range(n):
                    sel = jnp.logical_and(rows_k == ap + m, m < nn)
                    kqo = jnp.where(sel, kqn_ref[0, m], kqo)
                    kso = jnp.where(
                        jnp.logical_and(cols_s == ap + m, m < nn),
                        ksn_ref[0, 0, m], kso)
                kqo_ref[0] = kqo
                kso_ref[0] = kso

        @pl.when(ki == ns - 1)
        def _():
            o_ref[0] = acc_s[:]
            m_ref[0] = m_s[:]
            l_ref[0] = l_s[:]

    if stacked:
        # The layer index only steers the BlockSpec index maps: the
        # body sees one layer's blocks and needs no change.
        def kernel_stacked(vt_ref, ap_ref, nn_ref, row0_ref, *refs):
            kernel_body(vt_ref, ap_ref, nn_ref, *refs)

        return kernel_stacked
    if not paged:
        return kernel_body

    def kernel_paged(vt_ref, ap_ref, nn_ref, pt_ref, *refs):
        kernel_body(vt_ref, ap_ref, nn_ref, *refs, pt_ref=pt_ref)

    return kernel_paged


def flash_decode(q, k_new, v_new, cache_k, cache_v, valid_to, append_at,
                 *, n_new=None, page_table=None, layer=None, k_q=None,
                 k_scale=None, scale=None, window=None, alibi_slopes=None,
                 qk_quant=None, interpret=None, block_k=None,
                 partials=False, latent_v=None):
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
    untouched); the int8 mirror stays single-token (``qk_quant='int8'``
    requires ``k == 1`` — the XLA path covers quantized verify-k).
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
    row, ``[c_kv ; k_rope]``): ``cache_v`` and ``v_new`` are None, the
    one buffer ``cache_k (…, 1, t_max, d)`` is appended to and streamed
    ONCE, all ``H`` query heads are the rows of one score matmul over
    its ``d`` columns, and the values are the first ``latent_v`` columns
    of the same resident block. ``out`` is ``(B, H, k, latent_v)``; the
    returned ``cache_v`` is None. The Pallas program and its device
    scope are named ``mla_decode`` / ``ops.mla_decode``. Not with
    ``page_table`` or ``qk_quant``.

    Returns ``(out, cache_k, cache_v, k_q, k_scale)`` with
    ``out (B, H, k, dv)`` in ``cache_v.dtype`` — or, with
    ``partials=True``, ``((num, m, l), cache_k, cache_v, k_q, k_scale)``
    where ``num (B, H, k, dv) f32`` is the un-normalized context and
    ``m/l (B, H, k, 1)`` the base-2 running max / denominator per query
    row, for the flash-decoding cross-shard merge (pmax the maxes,
    rescale, psum).
    """
    b, h, n, d = q.shape
    h_kv = cache_k.shape[-3]
    latent = latent_v is not None
    paged = page_table is not None
    stacked = layer is not None
    if latent:
        if (cache_v is not None or v_new is not None or paged
                or qk_quant is not None or h_kv != 1
                or not 0 < latent_v <= d):
            raise ValueError(
                'flash_decode: latent_v reads the values from the one '
                'buffer cache_k (…, 1, t_max, d >= latent_v): pass '
                'cache_v=None and v_new=None, no page_table and no '
                'qk_quant')
        dv, v_dtype = latent_v, cache_k.dtype
    else:
        dv, v_dtype = cache_v.shape[-1], cache_v.dtype
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
    if paged:
        n_pages, bk = cache_k.shape[0], cache_k.shape[2]
        ns = page_table.shape[1]            # logical pages per slot
        t_max = ns * bk
        if block_k not in (None, bk):
            raise ValueError(f'paged decode splits K at the page size '
                             f'{bk}; block_k={block_k} cannot differ')
    else:
        t_max = cache_k.shape[-2]
        bk = block_k or decode_block_k(t_max)
        if bk is None or t_max % bk:
            raise ValueError(
                f'no usable K split for t_max={t_max} (block_k must '
                f'divide it); use the XLA decode path for this cache '
                f'shape')
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
    group = h // h_kv
    nb = b * h_kv

    # Query rows grouped per cache head, NEW-ROW-major (row j·group + g
    # = new row j, query head g — the layout the kernel's per-row
    # intra-step mask assumes), padded to the sublane multiple of their
    # kernel dtype; padded rows are sliced off the output.
    qg = jnp.swapaxes(q.reshape(b, h_kv, group, n, d), 2, 3
                      ).reshape(nb, n * group, d)
    rows = n * group
    sub = _sublane(jnp.int8 if quantized else cache_k.dtype)
    g_pad = -(-rows // sub) * sub
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
    vnf = None if latent else _pad_rows(
        v_new.astype(cache_v.dtype).reshape(nb, n, dv),
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
        vf = None if latent else cache_v.reshape(-1, t_max, dv)
    valid_to = jnp.asarray(valid_to, jnp.int32)
    append_at = jnp.asarray(append_at, jnp.int32)
    # Per-slot appended-row count: callers without mixed batches get
    # the full k wherever an append happens at all.
    if n_new is None:
        nnv = jnp.where(append_at >= 0, n, 0).astype(jnp.int32)
    else:
        nnv = jnp.asarray(n_new, jnp.int32)

    def const_idx(bi, ki, *rs):
        return (bi, 0, 0)

    def _stream_blk(bi, ki, vt):
        # Never DMA past a slot's last useful block (the LAST new row
        # attends up to vt + n − 1): beyond-fill splits alias the
        # resident block (skipped in-kernel), so a half-empty slot
        # streams half the bytes.
        last = jnp.clip((vt[bi // h_kv] + (n - 1)) // bk, 0, ns - 1)
        return jnp.minimum(ki, last)

    def _write_blk(bi, ki, ap, nn):
        # The k appended rows span blocks [first, last] (at most two,
        # n <= bk); clamping ki into the span walks the write ref over
        # each physical block exactly when the kernel body writes it.
        br = bi // h_kv
        a = ap[br]
        first = jnp.clip(a // bk, 0, ns - 1)
        last = jnp.clip((a + jnp.maximum(nn[br], 1) - 1) // bk,
                        0, ns - 1)
        return jnp.where(a >= 0, jnp.clip(ki, first, last), 0)

    if paged:
        # The tentpole redirect: the index map translates the LOGICAL
        # block ordinal through the prefetched page-table row instead
        # of using it as the physical block — the gather that makes
        # paging nearly free (same DMA skip, same aliasing).
        def stream_idx(bi, ki, vt, ap, nn, pt):
            blk = _stream_blk(bi, ki, vt)
            pg = pt[(bi // h_kv) * ns + blk]
            # −1 (ordinal not held by this pool) → the sink page; the
            # kernel's run-gate skips scoring it.
            return (jnp.where(pg >= 0, pg, sink) * h_kv + bi % h_kv,
                    0, 0)

        def write_idx(bi, ki, vt, ap, nn, pt):
            # Appending nothing → write-back lands on the sink page,
            # never on a page some other slot is appending into; same
            # for a −1 table entry (the table rides RAW — clamp here).
            br = bi // h_kv
            a = ap[br]
            blk = _write_blk(bi, ki, ap, nn)
            pg = pt[br * ns + blk]
            page = jnp.where(jnp.logical_and(a >= 0, pg >= 0), pg, sink)
            return (page * h_kv + bi % h_kv, 0, 0)

        # Mirror-scale flat rows are (pages·H_kv, 1, page_size): one
        # K-split block per pool page, so the block index is always 0
        # and the ROW rides the same page-table redirect as the data
        # pages — the data-pool maps ARE the scale maps (one
        # definition, so a sink-redirect fix cannot miss its twin).
        stream_idx_row = stream_idx
        write_idx_row = write_idx
    else:
        # Stacked: the prefetched first row of the layer (layer · nb)
        # redirects every cache row to its layer's run of nb rows
        # (``lay`` is empty otherwise) — the same kind of redirect the
        # page table does above.
        def _row(bi, lay):
            return bi + lay[0][0] if lay else bi

        def stream_idx(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), _stream_blk(bi, ki, vt), 0)

        def write_idx(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), _write_blk(bi, ki, ap, nn), 0)

        # The int8 scale mirror rides as a (nb, 1, t_max) ROW vector (a
        # size-1-axis reshape — a bitcast, not a transpose), blocked on
        # the LAST axis, so the kernel consumes (1, BK) scale rows
        # directly.
        def stream_idx_row(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), 0, _stream_blk(bi, ki, vt))

        def write_idx_row(bi, ki, vt, ap, nn, *lay):
            return (_row(bi, lay), 0, _write_blk(bi, ki, ap, nn))

    in_specs = [pl.BlockSpec((1, g_pad, d), const_idx)]
    args = [qf]
    if quantized:
        in_specs.append(pl.BlockSpec((1, g_pad, 1), const_idx))
        args.append(sqf)
    in_specs.append(pl.BlockSpec((1,) + knf.shape[1:], const_idx))
    args.append(knf)
    if quantized:
        in_specs += [pl.BlockSpec((1,) + kni.shape[1:], const_idx),
                     pl.BlockSpec((1, 1, 1), const_idx)]
        args += [kni, kns.reshape(nb, 1, 1)]
    if not latent:
        in_specs.append(pl.BlockSpec((1,) + vnf.shape[1:], const_idx))
        args.append(vnf)
    # The bf16 K buffer: streamed for scoring in the plain path; in the
    # quantized path scoring reads the mirror instead, so K is fetched
    # ONLY at its write block (one DMA per slot, to seed the append).
    in_specs.append(pl.BlockSpec((1, bk, d),
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
        in_specs += [pl.BlockSpec((1, bk, d), stream_idx),
                     pl.BlockSpec((1, 1, bk), stream_idx_row)]
        kq_in_pos = len(args)
        args.append(kqf)
        ks_in_pos = len(args)
        args.append(ksf)
    if not latent:
        in_specs.append(pl.BlockSpec((1, bk, dv), stream_idx))
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
        in_specs.append(pl.BlockSpec((1, g_pad, 1), const_idx))
        args.append(_pad_rows(slopes.reshape(nb, n * group, 1), sub))

    out_specs = [
        pl.BlockSpec((1, g_pad, dv), const_idx),   # num
        pl.BlockSpec((1, g_pad, 1), const_idx),    # m
        pl.BlockSpec((1, g_pad, 1), const_idx),    # l
        pl.BlockSpec((1, bk, d), write_idx),       # k (aliased)
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nb, g_pad, dv), jnp.float32),
        jax.ShapeDtypeStruct((nb, g_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((nb, g_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct(kf.shape, kf.dtype),
    ]
    # +n_prefetch: alias indices count the scalar-prefetch operands
    # (valid_to, append_at, n_new, and — paged — the flattened page
    # table or — stacked — the layer's first flat row).
    prefetch = (valid_to, append_at, nnv)
    if paged:
        prefetch += (ptf,)
    elif stacked:
        prefetch += ((jnp.asarray(layer, jnp.int32) * nb).reshape(1),)
    n_prefetch = len(prefetch)
    aliases = {n_prefetch + k_in_pos: 3}
    if not latent:
        out_specs.append(pl.BlockSpec((1, bk, dv), write_idx))  # v (aliased)
        out_shape.append(jax.ShapeDtypeStruct(vf.shape, vf.dtype))
        aliases[n_prefetch + v_in_pos] = 4
    if quantized:
        out_specs += [pl.BlockSpec((1, bk, d), write_idx),
                      pl.BlockSpec((1, 1, bk), write_idx_row)]
        out_shape += [jax.ShapeDtypeStruct(kqf.shape, kqf.dtype),
                      jax.ShapeDtypeStruct(ksf.shape, ksf.dtype)]
        aliases[n_prefetch + kq_in_pos] = 5
        aliases[n_prefetch + ks_in_pos] = 6

    kernel = _make_decode_kernel(bk, ns, n, group, g_pad, h_kv, window,
                                 quantized, has_alibi, paged=paged,
                                 stacked=stacked, latent_v=latent_v)
    name = 'mla_decode' if latent else 'flash_decode'
    with device_scope(f'ops.{name}'):
        outs = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_prefetch,
                grid=(nb, ns),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((g_pad, 1), jnp.float32),
                                pltpu.VMEM((g_pad, 1), jnp.float32),
                                pltpu.VMEM((g_pad, dv), jnp.float32)]),
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
    new_v = None if latent else outs[4].reshape(cache_v.shape)

    def head_shape(x):
        # Rows are new-row-major per kv head: undo the (n, group) fold
        # back to (B, H, n, ·).
        x = x[:, :n * group].reshape(b, h_kv, n, group, x.shape[-1])
        return jnp.swapaxes(x, 2, 3).reshape(b, h, n, x.shape[-1])

    num, m, l = head_shape(num), head_shape(m), head_shape(l)
    if partials:
        return (num, m, l), new_k, new_v, new_kq, new_ks
    out = (num / jnp.where(l == 0.0, 1.0, l)).astype(v_dtype)
    return out, new_k, new_v, new_kq, new_ks
