# -*- coding: utf-8 -*-
"""
The compiled substrate the scheduler drives: a minimal greedy LM over
the KV-cache decode kernels (``models/decode.py``), batched across
decode SLOTS with per-slot lengths.

Why a dedicated engine instead of :class:`TransformerLM`: continuous
batching needs every batch row on its OWN sequence clock, which is
exactly what the per-slot cache (``init_slot_cache`` /
``append_kv_slots`` / per-slot-masked ``decode_attention``) provides at
the kernel level. The flax stack's decode surface shares one scalar
length across the batch (lockstep generation); threading per-slot
lengths through it is a model-side project — the serving layer's job is
the scheduling around the kernels, so it drives them directly: token
embedding → q/k/v projections → per-slot cache append → per-slot masked
attention → logits. Fixed seeded weights (serving robustness doesn't
need trained weights; determinism does).

Three compiled programs serve the whole lifecycle, shapes fixed at
construction so nothing ever retraces mid-serve:

- ``decode``: one token for EVERY slot (inactive slots masked out of
  the append; their outputs ignored) + per-slot all-finite verdict on
  the logits. The append+attend pair is the FUSED step
  (``models.decode.decode_step``): on the kernel path it is one Pallas
  program with the cache aliased in place, so the donated buffers are
  never copied. The fault injector's NaN mask is applied IN-PROGRAM so
  the quarantine predicate sees real NaNs from the compiled step.
- ``prefill``: one padded prompt chunk into one slot's cache rows (no
  attention — only the last prompt position's logits matter, and the
  scheduler feeds that token through ``decode``).
- ``reset``: zero one slot's rows and length (eviction/quarantine).

Every computation is batch-row independent (embedding lookups, row-wise
matmuls, per-slot masked attention, per-row argmax), so a request's
tokens depend only on its prompt and the seed — NOT on which slot it
lands in or what its neighbors are doing. The scheduler's bit-identity
guarantees (quarantine leaves other slots' streams untouched; a
requeued request regenerates the same tokens) rest on this property,
and the tests pin it.
"""

import itertools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.models.decode import (
    PageChecksums, PagedDecodeCache, PagePool, ShardedPageTable,
    append_kv_slots, decode_step, init_paged_cache, init_slot_cache,
    init_sharded_paged_cache, paged_append_rows, paged_copy_attach,
    paged_reset_slot, paged_rollback_slots, paged_transfer_pages,
    reset_slot, rollback_slots, slots_all_finite,
)
from distributed_dot_product_tpu.obs import spans as obs_spans
from distributed_dot_product_tpu.obs.spans import span
from distributed_dot_product_tpu.serve.errors import ServeContractError
from distributed_dot_product_tpu.utils.retrace import watch_traces

__all__ = ['KernelEngine', 'PageCorruptionError']


class PageCorruptionError(RuntimeError):
    """A pool page's content no longer matches its recorded checksum.
    ``pages`` names the dirty pages, ``site`` the transfer/attach
    boundary that caught them ('scrub', 'attach', 'fork',
    'handoff_src', 'handoff_copy') — the router turns this into the
    `kv.corrupt` event + quarantine + heal arc."""

    def __init__(self, pages, site, shards=None):
        self.pages = sorted(int(p) for p in pages)
        self.site = site
        # kv_shards engines name the owning mesh member(s) of the dirty
        # pages (page ids are then STACKED-row ids); None on unsharded
        # engines — the router forwards this into `kv.corrupt`.
        self.shards = (sorted({int(s) for s in shards})
                       if shards else None)
        msg = (f'KV page corruption at {site}: page(s) {self.pages} '
               f'fail checksum verification')
        if self.shards:
            msg += f' (kv shard(s) {self.shards})'
        super().__init__(msg)


def _resolve_decode_impl(decode_impl):
    """Engine decode-path selection: an explicit argument wins; else the
    ``DDP_TPU_DECODE_KERNEL`` env knob (1/kernel → fused Pallas step,
    0/xla → portable step) — the hook ``scripts/smoke_serve.sh`` uses
    to prove the fault cocktail on the kernel path; else 'auto' (kernel
    on TPU, XLA elsewhere — see models/decode.decode_step)."""
    if decode_impl is not None:
        return decode_impl
    env = os.environ.get('DDP_TPU_DECODE_KERNEL', '').strip().lower()
    if env in ('1', 'true', 'kernel'):
        return 'kernel'
    if env in ('0', 'false', 'xla'):
        return 'xla'
    return 'auto'


def _resolve_weight_quant(weight_quant):
    """Weight-precision selection: explicit argument wins ('off'/None =
    float weights, 'int8' = per-output-channel int8 weights with
    in-program s8×s8→s32 dequant — models/dense.quantize_kernel's rule);
    else the ``DDP_TPU_WEIGHT_QUANT`` env knob — the deployment switch
    the quantized-serving benchmark rows flip."""
    if weight_quant is not None:
        if weight_quant == 'off':
            return None
        if weight_quant not in ('int8',):
            raise ValueError(f"weight_quant must be None/'off'/'int8', "
                             f'got {weight_quant!r}')
        return weight_quant
    env = os.environ.get('DDP_TPU_WEIGHT_QUANT', '').strip().lower()
    if env in ('1', 'true', 'int8'):
        return 'int8'
    return None


def _resolve_cache_mode(cache_mode):
    """Cache-layout selection: explicit argument wins; else the
    ``DDP_TPU_PAGED_CACHE`` env knob (1/paged → page-pool cache); else
    the slab reference layout."""
    if cache_mode is not None:
        if cache_mode not in ('slab', 'paged'):
            raise ValueError(f"cache_mode must be 'slab' or 'paged', "
                             f'got {cache_mode!r}')
        return cache_mode
    env = os.environ.get('DDP_TPU_PAGED_CACHE', '').strip().lower()
    if env in ('1', 'true', 'paged'):
        return 'paged'
    return 'slab'


class KernelEngine:
    """Greedy decode engine over ``slots`` independent sequences.

    ``prefill_chunk`` is the compiled chunk width for prompt ingestion
    (prompts append in ceil(len/chunk) calls — "chunked prefill", so a
    long prompt never monopolizes the loop between decode steps).

    ``decode_impl``: 'kernel' runs the decode step as the fused Pallas
    program (in-place aliased cache append + split-K attention —
    ops/pallas_decode.py; the three compiled programs then stop paying
    any cache round trip), 'xla' the portable append+einsum step, None
    reads ``DDP_TPU_DECODE_KERNEL`` then defaults to auto (kernel on
    TPU). Token streams are deterministic within an impl; the two
    impls agree to float tolerance (exp2 vs exp rounding), so
    bit-identity guarantees hold per-impl, not across.

    ``cache_mode='paged'`` (or ``DDP_TPU_PAGED_CACHE=1``) swaps the
    per-slot slab for the page-pool cache (``models/decode.py``
    ``PagedDecodeCache``): ``pages`` sizes the global pool (the memory
    budget — decoupled from ``slots × t_max``), ``page_size`` the page
    granularity (= the kernel's K split). The host :class:`PagePool`
    owns allocation; :meth:`step`/:meth:`prefill` auto-reserve the
    pages they need (raising on exhaustion), while the Scheduler calls
    :meth:`prepare_step`/:meth:`reserve_rows` itself so a deficit
    routes through its evict/preempt ladder instead of a raise.
    :meth:`register_prefix`/:meth:`start_with_prefix` give refcounted
    prefix sharing, :meth:`fork_slot` copy-on-write forks. Token
    streams are bit-identical to the slab engine per impl.

    ``kv_shards=N`` (paged engines only) shards every stream's page
    table across an N-wide ``seq`` mesh — cluster-scale long context:
    each mesh member owns a CONTIGUOUS run of the logical page
    ordinals (:class:`~distributed_dot_product_tpu.models.decode
    .ShardedPageTable`), runs the decode step over only its own pages,
    and the per-shard flash partials pmax/psum-merge into the exact
    full-attention result. ``pages`` then sizes each PER-SHARD pool,
    so ``capacity_tokens`` scales linearly with N. The host surface
    speaks GLOBAL page ids (= stacked pool rows); checksums are kept
    per owning shard; prefixes arrive via the shard-local
    :meth:`adopt_prefix` handoff (``register_prefix``, ``fork_slot``
    and ``verify_step`` raise — run those on unsharded replicas).
    Needs N devices (the 8-dev CPU mesh in tests/CI).

    ``weight_quant='int8'`` (or ``DDP_TPU_WEIGHT_QUANT=int8``) stores
    the four projection/head matrices int8 with per-output-channel
    scales (``models/dense.quantize_kernel``); every projection and
    the logits dot then quantize their activation rows on the fly and
    run s8×s8→s32 with the dequantization applied to the s32 result —
    half the weight bytes per step, deterministic streams (the
    bit-identity guarantees hold per weight_quant setting, exactly as
    they hold per decode impl), layout-oblivious (slab and paged
    engines with the same seed + weight_quant emit identical
    streams).
    """

    def __init__(self, slots, t_max, *, vocab=64, heads=2, head_dim=8,
                 prefill_chunk=8, seed=0, dtype=jnp.float32,
                 decode_impl=None, cache_mode=None, pages=None,
                 page_size=None, weight_quant=None, kv_checksums=True,
                 kv_shards=1):
        if slots < 1 or t_max < 2:
            raise ValueError(f'need slots >= 1 and t_max >= 2, got '
                             f'{slots}/{t_max}')
        self.decode_impl = _resolve_decode_impl(decode_impl)
        self.cache_mode = _resolve_cache_mode(cache_mode)
        self.kv_shards = int(kv_shards)
        if self.kv_shards < 1:
            raise ValueError(f'kv_shards must be >= 1, got {kv_shards}')
        if self.kv_shards > 1 and self.cache_mode != 'paged':
            raise ValueError("kv_shards > 1 needs cache_mode='paged' — "
                             'the sequence-sharded KV is a sharded page '
                             'table, there is no sharded slab')
        self.weight_quant = _resolve_weight_quant(weight_quant)
        self.slots = slots
        self.t_max = t_max
        self.vocab = vocab
        self.heads = heads
        self.head_dim = head_dim
        self.prefill_chunk = prefill_chunk
        self.seed = seed
        dim = heads * head_dim
        ks = jax.random.split(jax.random.key(seed), 5)
        scale = 1.0 / np.sqrt(dim)
        self._embed = jax.random.normal(ks[0], (vocab, dim), dtype) * scale
        self._wq = jax.random.normal(ks[1], (dim, dim), dtype) * scale
        self._wk = jax.random.normal(ks[2], (dim, dim), dtype) * scale
        self._wv = jax.random.normal(ks[3], (dim, dim), dtype) * scale
        self._wo = jax.random.normal(ks[4], (dim, vocab), dtype) * scale
        if self.weight_quant == 'int8':
            # Load/convert-time quantization — the engine analog of
            # models/dense.quantize_dense_params: weights stored int8
            # (half/quarter the bytes), per-output-channel scales. The
            # embedding stays float: it feeds a LOOKUP, not a matmul.
            from distributed_dot_product_tpu.models.dense import (
                quantize_kernel,
            )
            self._wq = quantize_kernel(self._wq)
            self._wk = quantize_kernel(self._wk)
            self._wv = quantize_kernel(self._wv)
            self._wo = quantize_kernel(self._wo)
        if self.cache_mode == 'paged':
            ps = page_size or min(16, t_max)
            if t_max % ps:
                raise ValueError(f'page_size {ps} must divide t_max '
                                 f'{t_max}')
            self.page_size = ps
            if self.kv_shards > 1:
                # Cluster-scale long context: one ShardedPageTable over
                # kv_shards PagePools (contiguous ordinal ownership),
                # the STACKED device cache placed P(SEQ_AXIS) over a
                # seq mesh. `pages` sizes each PER-SHARD pool, so
                # capacity_tokens sums linearly across the mesh.
                from distributed_dot_product_tpu.parallel.mesh import (
                    seq_mesh,
                )
                from distributed_dot_product_tpu.utils.comm import (
                    SEQ_AXIS,
                )
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                pps = t_max // ps
                if self.kv_shards > pps:
                    raise ValueError(
                        f'kv_shards {self.kv_shards} exceeds the '
                        f'{pps} logical page ordinals of t_max='
                        f'{t_max}/page_size={ps} — some shards would '
                        f'own an empty range')
                n_pages = pages if pages is not None \
                    else -(-slots * pps // self.kv_shards)
                self.pool = ShardedPageTable(self.kv_shards, n_pages,
                                             ps, slots, pps)
                self._mesh = seq_mesh(self.kv_shards)
                self._seq_axis = SEQ_AXIS
                self._pt_sharding = NamedSharding(self._mesh,
                                                 P(SEQ_AXIS))
                self._cache_sharding = PagedDecodeCache(
                    k_pool=self._pt_sharding,
                    v_pool=self._pt_sharding,
                    page_table=self._pt_sharding,
                    length=NamedSharding(self._mesh, P()),
                    k_q_pool=None, k_scale_pool=None)
                self.cache = jax.device_put(
                    init_sharded_paged_cache(
                        self.kv_shards, slots, heads, t_max, head_dim,
                        pages_per_shard=n_pages, page_size=ps,
                        dtype=dtype),
                    self._cache_sharding)
            else:
                # Default pool = the slab's bytes; the paged win comes
                # from sizing `pages` to the MEMORY budget while
                # raising `slots` past what a slab of the same bytes
                # could hold.
                n_pages = pages if pages is not None \
                    else slots * (t_max // ps)
                self.pool = PagePool(n_pages, ps, slots, t_max // ps)
                self.cache = init_paged_cache(slots, heads, t_max,
                                              head_dim, pages=n_pages,
                                              page_size=ps, dtype=dtype)
            self._prefix_registry = {}
            self._prefix_counter = itertools.count()
            # Per-page integrity table: registry/transfer pages only,
            # digested at transfer boundaries on the host — never
            # inside a compiled program ("verify at transfer, never
            # per step"). kv_checksums=False is the no-integrity twin.
            # kv_shards engines keep ONE table PER OWNING SHARD, keyed
            # by shard-local page ids (satellite: checksums stay
            # coherent under sharding).
            if not kv_checksums:
                self.checksums = None
            elif self.kv_shards > 1:
                self.checksums = [PageChecksums()
                                  for _ in range(self.kv_shards)]
            else:
                self.checksums = PageChecksums()
        else:
            self.page_size = None
            self.pool = None
            self.checksums = None
            self.cache = init_slot_cache(slots, heads, t_max, head_dim,
                                         dtype=dtype)
        self.verify_seconds = 0.0   # host wall time spent digesting
        # Dispatch-floor accounting (ROADMAP item 5): cumulative REAL
        # wall seconds spent INSIDE compiled-program invocations
        # (decode / verify / prefill / rollback). The scheduler diffs
        # this across a tick to split tick wall time into device
        # compute vs host-loop overhead (serve.dispatch events,
        # serve.dispatch_overhead_seconds histogram). Monotone
        # counter, never reset — consumers take deltas.
        self.program_seconds = 0.0
        # Donated caches: appends write in place — see models/decode.py's
        # performance note. One compiled program each for the lifetime —
        # and the retrace sentinel (utils/retrace.py) enforces it:
        # shapes are fixed at construction, so more than budget traces
        # of one program means something un-cacheable leaked into the
        # step (the round-5 retrace-storm class). Budget 2: the real
        # trace plus one registry lowering / weak-type respin.
        if self.cache_mode == 'paged' and self.kv_shards > 1:
            # Every kv_shards program is the SAME paged body the
            # unsharded engine runs, wrapped in ONE shard_map: each
            # mesh member squeezes its (1, slots, pps) page-table
            # block to the ordinary local view, runs the paged body
            # over its own pool block (non-owned ordinals are −1, so
            # their appends/copies drop on device), and re-expands.
            # The decode body additionally passes the mesh axis so
            # decode_step pmax/psum-merges the per-shard flash
            # partials into the exact full-attention result — the
            # paged ring/context-parallel decode step.
            from jax.sharding import PartitionSpec as P
            cspec = self._cache_pspec()
            rep, shv = P(), P(self._seq_axis)
            self._decode = jax.jit(
                watch_traces(self._sharded_program(
                    self._decode_body_sharded,
                    (cspec, rep, rep, rep), (cspec, rep, rep)),
                    'engine.decode', budget=2),
                donate_argnums=(0,))
            self._prefill = jax.jit(
                watch_traces(self._sharded_program(
                    self._prefill_body_sharded,
                    (cspec, rep, rep, rep), cspec),
                    'engine.prefill', budget=2),
                donate_argnums=(0,))
            self._reset = jax.jit(
                watch_traces(self._sharded_program(
                    self._reset_body_sharded,
                    (cspec, rep, shv), cspec),
                    'engine.reset', budget=2),
                donate_argnums=(0,))
            self._copy_attach = jax.jit(
                watch_traces(self._sharded_program(
                    self._copy_attach_body_sharded,
                    (cspec, shv, shv, rep, rep), cspec),
                    'engine.copy_attach', budget=2),
                donate_argnums=(0,))
            # register_prefix is rejected under kv_shards (shared
            # prefixes arrive via the shard-local handoff), so no
            # local prefix-fill program exists to mis-call.
            self._prefix_fill = None
        else:
            self._decode = jax.jit(
                watch_traces(self._decode_impl, 'engine.decode',
                             budget=2),
                donate_argnums=(0,))
            self._prefill = jax.jit(
                watch_traces(self._prefill_impl, 'engine.prefill',
                             budget=2),
                donate_argnums=(0,))
            if self.cache_mode == 'paged':
                self._reset = jax.jit(
                    watch_traces(paged_reset_slot, 'engine.reset',
                                 budget=2),
                    donate_argnums=(0,))
                # The sharing primitives: CoW/fork/attach page copy (+
                # length set) and registry prefix prefill — each one
                # fixed compiled program, dispatched only on page
                # crossings and prefix/fork events, never per token.
                self._copy_attach = jax.jit(
                    watch_traces(paged_copy_attach,
                                 'engine.copy_attach', budget=2),
                    donate_argnums=(0,))
                self._prefix_fill = jax.jit(
                    watch_traces(self._prefix_fill_impl,
                                 'engine.prefix_fill', budget=2),
                    donate_argnums=(0,))
            else:
                self._reset = jax.jit(
                    watch_traces(reset_slot, 'engine.reset', budget=2),
                    donate_argnums=(0,))
        # Speculative decoding programs, built LAZILY (a non-spec
        # engine never pays their traces): one verify program per
        # width W = k+1 and one rollback program per span, each a
        # fixed compiled shape under its own retrace budget.
        self._verifies = {}
        self._rollbacks = {}
        self._peek = None           # peek_logits' program, built lazily
        # Cross-cache KV handoff programs (disaggregated serving):
        # one per SOURCE pool shape — a topology has exactly one
        # prefill pool shape, so one program for the engine's life.
        self._transfers = {}

    # -- compiled bodies ------------------------------------------------
    def _dot(self, x, w):
        """``x (rows, in) · w`` — the one matmul body every engine
        program routes through, so a precision change cannot miss a
        call site. Float weights: a plain dot (the engine dtype is the
        accumulation dtype — f32 by default). int8 weights (``w`` is
        the ``(kernel_q, kernel_scale)`` pair): the SHARED
        ``models/dense.quantized_dot`` body — one definition of the
        s8×s8→s32 rule, so the engine's streams cannot drift from the
        module path's."""
        if self.weight_quant == 'int8':
            from distributed_dot_product_tpu.models.dense import (
                quantized_dot,
            )
            w_q, w_s = w
            return quantized_dot(x, w_q, w_s).astype(self._embed.dtype)
        return x @ w

    def _project(self, tokens):
        """tokens (S,) → q, k, v each (S, H, 1, D)."""
        s = tokens.shape[0]
        x = jnp.take(self._embed, tokens, axis=0)          # (S, dim)
        shape = (s, self.heads, 1, self.head_dim)
        return (self._dot(x, self._wq).reshape(shape),
                self._dot(x, self._wk).reshape(shape),
                self._dot(x, self._wv).reshape(shape))

    def _logits_impl(self, cache, tokens, active, axis_name=None):
        q, k, v = self._project(tokens)
        # Fused append+attend (one Pallas program on the kernel path —
        # the cache buffers are aliased in place and, with the jit
        # donation above, never copied). With `axis_name` (a kv_shards
        # engine's shard_map body) the step runs over this member's
        # page range only and flash-merges partials across the mesh.
        cache, out = decode_step(q, cache, k, v, slot_mask=active,
                                 impl=self.decode_impl,
                                 axis_name=axis_name)      # (S, H, 1, D)
        return cache, self._dot(out.reshape(self.slots, -1),
                                self._wo)                  # (S, vocab)

    def _decode_impl(self, cache, tokens, active, poison,
                     axis_name=None):
        cache, logits = self._logits_impl(cache, tokens, active,
                                          axis_name=axis_name)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        finite = slots_all_finite(logits)
        # Fully-masked argmax input for a poisoned row would be NaN-
        # ordered garbage; the scheduler discards non-finite slots'
        # tokens, so the value only needs to be deterministic.
        next_tok = jnp.argmax(
            jnp.where(jnp.isfinite(logits), logits, -jnp.inf),
            axis=-1).astype(jnp.int32)
        return cache, next_tok, finite

    def _verify_impl(self, cache, tokens, counts, active, poison):
        """Verify-k body (speculative decoding's fused verify):
        ``tokens (S, W)`` — per slot, row 0 the committed input token
        and rows 1.. the proposed continuation, ``counts[i]`` of the W
        rows real (1 = a plain non-spec slot riding the same program).
        Projections, head reshapes and the logits dot all run PER
        COLUMN with the exact ``(S, dim)`` shapes of the n=1 program —
        XLA lowers an (S, dim) and an (S·W, dim) matmul with different
        accumulation orders, and the committed stream must be the n=1
        stream bit for bit wherever the math allows it. The fused
        append+attend step keeps the same per-row identity
        (models/decode.py: a verify-k step == counts sequential
        steps)."""
        w = tokens.shape[1]
        qs, ks, vs = zip(*(self._project(tokens[:, j])
                           for j in range(w)))
        q = jnp.concatenate(qs, axis=2)            # (S, H, W, D)
        k = jnp.concatenate(ks, axis=2)
        v = jnp.concatenate(vs, axis=2)
        cache, out = decode_step(q, cache, k, v, slot_mask=active,
                                 counts=counts, impl=self.decode_impl)
        logits = jnp.stack(
            [self._dot(out[:, :, j].reshape(self.slots, -1), self._wo)
             for j in range(w)], axis=1)           # (S, W, vocab)
        logits = jnp.where(poison[:, None, None], jnp.nan, logits)
        finite = slots_all_finite(logits)
        next_tok = jnp.argmax(
            jnp.where(jnp.isfinite(logits), logits, -jnp.inf),
            axis=-1).astype(jnp.int32)             # (S, W)
        return cache, next_tok, finite

    def _project_kv(self, tokens):
        """Chunk tokens ``(C,)`` → cache-layout k, v each ``(H, C, D)``
        — the ONE projection both prefill paths share (a projection
        change must hit slot prefill and registry prefix fill alike,
        or shared-prefix pages would attend with different K/V)."""
        x = jnp.take(self._embed, tokens, axis=0)          # (C, dim)
        c = tokens.shape[0]
        k = jnp.moveaxis(self._dot(x, self._wk).reshape(
            c, self.heads, self.head_dim), 0, 1)           # (H, C, D)
        v = jnp.moveaxis(self._dot(x, self._wv).reshape(
            c, self.heads, self.head_dim), 0, 1)
        return k, v

    def _prefill_impl(self, cache, slot, tokens, count):
        """Append ``count`` of the ``prefill_chunk`` padded ``tokens``
        into ``slot``'s rows. Projections are computed once and
        broadcast — the masked write only lands on the one slot."""
        k, v = self._project_kv(tokens)
        k = jnp.broadcast_to(k[None], (self.slots,) + k.shape)
        v = jnp.broadcast_to(v[None], (self.slots,) + v.shape)
        sel = jnp.arange(self.slots) == slot
        counts = jnp.where(sel, count, 0).astype(jnp.int32)
        return append_kv_slots(cache, k, v, slot_mask=sel, counts=counts)

    def _prefix_fill_impl(self, cache, tokens, count, page_row, start):
        """Registry prefill: project one padded chunk and scatter its
        first ``count`` rows into the REGISTRY-owned ``page_row`` pages
        at logical positions ``start..`` — no slot, no length."""
        k, v = self._project_kv(tokens)
        return paged_append_rows(cache, k, v, page_row, start, count)

    # -- kv_shards shard_map plumbing (cache_mode='paged', shards>1) ----
    def _cache_pspec(self):
        """PartitionSpec pytree of the stacked sharded cache: pools and
        page-table blocks P(seq) on axis 0, the fill vector replicated
        (it is a global property every member advances identically)."""
        from jax.sharding import PartitionSpec as P
        ax = self._seq_axis
        return PagedDecodeCache(k_pool=P(ax), v_pool=P(ax),
                                page_table=P(ax), length=P(),
                                k_q_pool=None, k_scale_pool=None)

    def _sharded_program(self, body, in_specs, out_specs):
        return jax.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _decode_body_sharded(self, cache, tokens, active, poison):
        local = cache._replace(page_table=cache.page_table[0])
        local, tok, finite = self._decode_impl(
            local, tokens, active, poison, axis_name=self._seq_axis)
        return (local._replace(page_table=local.page_table[None]),
                tok, finite)

    def _prefill_body_sharded(self, cache, slot, tokens, count):
        # The unsharded prefill body verbatim on the local view:
        # rows whose page ordinal another shard owns scatter through a
        # −1 table entry and drop — each member keeps exactly its own
        # slice of the prompt, no cross-member traffic at all.
        local = cache._replace(page_table=cache.page_table[0])
        out = self._prefill_impl(local, slot, tokens, count)
        return out._replace(page_table=out.page_table[None])

    def _reset_body_sharded(self, cache, slot, freed):
        # `freed` is (kv_shards, pages_per_slot) stacked per-shard
        # freed-page vectors (−1-padded); each member zeroes its own.
        local = cache._replace(page_table=cache.page_table[0])
        out = paged_reset_slot(local, slot, freed[0])
        return out._replace(page_table=out.page_table[None])

    def _copy_attach_body_sharded(self, cache, src, dst, slot, length):
        # `src`/`dst` are (kv_shards,) stacked per-shard scalars (−1 =
        # no copy on that member) — one program serves CoW copies and
        # attach tail copies wherever the page lives.
        local = cache._replace(page_table=cache.page_table[0])
        out = paged_copy_attach(local, src[0], dst[0], slot, length)
        return out._replace(page_table=out.page_table[None])

    def _gpage(self, shard, page):
        """Shard-local page id → GLOBAL page id (= the page's stacked
        pool row — each member's block ends with its own sink row).
        Global ids are what the kv_shards engine's host surface speaks
        (registry, checksums verdicts, quarantine), so the router/
        scheduler page arithmetic works unchanged. The stride layout
        itself lives in :meth:`ShardedPageTable.gpage` — flowlint's
        shard-ownership rule keeps it from leaking back here."""
        return self.pool.gpage(shard, page)

    def _gsplit(self, gpage):
        """GLOBAL page id → ``(shard, local page)``."""
        return self.pool.gsplit(gpage)

    def page_shard(self, page):
        """Mesh member owning GLOBAL page id ``page`` on a kv_shards
        engine; None on unsharded engines (the router's kv.corrupt
        shard naming probes any engine through this)."""
        if self.kv_shards <= 1:
            return None
        return self.pool.page_shard(page)

    # -- host surface (numpy in, numpy out) -----------------------------
    def step(self, tokens, active, poison=None, request_ids=None):
        """One decode step for all slots. ``tokens (S,) int`` — each
        ACTIVE slot's input token (its previous output, or the last
        prompt token right after prefill); inactive entries ignored.
        Returns ``(next_tokens (S,), finite (S,))`` numpy arrays.

        ``request_ids`` (optional, per-slot) is observability-only: it
        labels the host-side span so a profiler/span tree ties a decode
        dispatch back to the requests it served — it never reaches the
        compiled program (strings can't; the program is id-oblivious by
        design)."""
        poison = (np.zeros(self.slots, bool) if poison is None
                  else np.asarray(poison, bool))
        if self.cache_mode == 'paged':
            # Auto-prepare only when something actually needs a page
            # (a vectorized check — the scheduler's _ensure_pages
            # already prepared, so the per-token hot path pays one
            # numpy mask, not a per-slot Python loop). Direct callers
            # just work; exhaustion raises here because a bare loop
            # has no evict/preempt ladder to resolve it.
            act = np.asarray(active, bool)
            if not self._writable_mask(act).all():
                ok = self.prepare_step(act)
                if not ok.all():
                    bad = np.nonzero(~ok)[0]
                    by_shard = (
                        f', free by shard '
                        f'{self.pool.free_pages_by_shard} — one '
                        f"shard's contiguous range is out of pages "
                        f'even though others have headroom'
                        if self.kv_shards > 1 else '')
                    raise RuntimeError(
                        f'page pool exhausted for slot(s) '
                        f'{bad.tolist()} ({self.pool.free_pages} pages '
                        f'free{by_shard}) — retire or evict sequences '
                        f'(the Scheduler ladder does), or size the '
                        f'pool larger')
            self._sync_page_table()
        # Span attrs are built ONLY when spans are on: this is the
        # per-token hot path, and the disabled default must not pay a
        # per-step tuple build for labels nobody will read.
        ids = (tuple(r for r in (request_ids or ()) if r)
               if obs_spans.enabled() else ())
        with span('engine.decode_step', requests=ids):
            # Timed through the host round-trip (np.asarray blocks on
            # the async dispatch) — program_seconds measures the wall
            # time the loop actually waits on the device, the quantity
            # the dispatch-floor split subtracts from tick time.
            t0 = time.perf_counter()
            self.cache, tok, finite = self._decode(
                self.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(active, bool), jnp.asarray(poison))
            out = np.asarray(tok), np.asarray(finite)
            self.program_seconds += time.perf_counter() - t0
            if self.cache_mode == 'paged':
                self.pool.lengths[np.asarray(active, bool)] += 1
            return out

    def peek_logits(self, tokens, active):
        """The NEXT decode step's logits ``(S, vocab)`` for ``tokens``,
        without taking the step: the cache is neither donated nor
        replaced, so the engine's streams are unchanged. A parity
        probe (kernel vs XLA impl, ``kv_shards`` vs one pool) — it
        compiles its own program and the undonated call copies the
        cache, so it is no serving path."""
        if self._peek is None:
            def body(cache, tokens, active):
                if self.kv_shards > 1:      # a shard_map member's view
                    cache = cache._replace(
                        page_table=cache.page_table[0])
                return self._logits_impl(
                    cache, tokens, active,
                    axis_name=(self._seq_axis if self.kv_shards > 1
                               else None))[1]

            if self.kv_shards > 1:
                from jax.sharding import PartitionSpec as P
                body = self._sharded_program(
                    body, (self._cache_pspec(), P(), P()), P())
            self._peek = jax.jit(body)
        act = np.asarray(active, bool)
        if (self.cache_mode == 'paged'
                and not self.prepare_step(act).all()):
            raise RuntimeError('page pool exhausted preparing the '
                               'peeked step')
        return np.asarray(self._peek(
            self.cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(act)))

    def _verify_program(self, w):
        """One compiled verify program per width W = k+1, built lazily
        under its own retrace budget (the width is a compile-time
        shape; a serving run uses ONE k, so one program — the dict
        exists for benchmarks sweeping k in-process)."""
        prog = self._verifies.get(w)
        if prog is None:
            prog = self._verifies[w] = jax.jit(
                watch_traces(self._verify_impl, f'engine.verify_w{w}',
                             budget=2),
                donate_argnums=(0,))
        return prog

    def verify_step(self, tokens, counts, active, poison=None,
                    request_ids=None):
        """One fused verify-k step for all slots: ``tokens (S, W)
        int`` — per ACTIVE slot, ``[input_token, p_1, .., p_c, pad]``
        with ``counts[i] = c_i + 1`` rows real (1 = plain decode: a
        mixed spec/non-spec batch rides one program). Returns
        ``(next_tokens (S, W), finite (S,))``: ``next_tokens[i, j]``
        is the greedy target token AFTER consuming input row j — the
        caller accepts the longest prefix with ``p_{j+1} ==
        next_tokens[i, j]``, commits one extra "free" token, and rolls
        the cache back to the accepted prefix (:meth:`rollback`).
        Rows past ``counts[i]`` are don't-care outputs.

        The cache appends ``counts[i]`` rows per active slot (paged
        engines auto-reserve the pages, raising on exhaustion — the
        Scheduler reserves through its evict/preempt ladder instead)."""
        if self.kv_shards > 1:
            raise ServeContractError(
                'verify_step (speculative decoding) is not supported '
                'with kv_shards > 1 — the sharded ring-decode step is '
                'single-token; run spec decode on unsharded replicas')
        tokens = np.asarray(tokens, np.int32)
        s, w = tokens.shape
        if s != self.slots:
            raise ServeContractError(
                f'tokens rows {s} != slots {self.slots}')
        counts = np.clip(np.asarray(counts, np.int64), 0, w)
        act = np.asarray(active, bool)
        poison = (np.zeros(self.slots, bool) if poison is None
                  else np.asarray(poison, bool))
        if self.cache_mode == 'paged':
            for i in np.nonzero(act)[0]:
                c = int(counts[i])
                if c and not self.reserve_rows(int(i), c):
                    raise RuntimeError(
                        f'page pool exhausted reserving {c} verify '
                        f'rows for slot {int(i)} '
                        f'({self.pool.free_pages} pages free) — '
                        f'retire or evict sequences (the Scheduler '
                        f'ladder does), or size the pool larger')
            self._sync_page_table()
        ids = (tuple(r for r in (request_ids or ()) if r)
               if obs_spans.enabled() else ())
        with span('engine.verify_step', requests=ids, width=w):
            t0 = time.perf_counter()
            self.cache, tok, finite = self._verify_program(w)(
                self.cache, jnp.asarray(tokens),
                jnp.asarray(counts, jnp.int32), jnp.asarray(act),
                jnp.asarray(poison))
            out = np.asarray(tok), np.asarray(finite)
            self.program_seconds += time.perf_counter() - t0
            if self.cache_mode == 'paged':
                self.pool.lengths[act] += counts[act]
            return out

    def _rollback_program(self, span_rows):
        prog = self._rollbacks.get(span_rows)
        if prog is None:
            if self.cache_mode == 'paged' and self.kv_shards > 1:
                from jax.sharding import PartitionSpec as P

                def _body(cache, lengths):
                    local = cache._replace(
                        page_table=cache.page_table[0])
                    out = paged_rollback_slots(local, lengths,
                                               span_rows)
                    return out._replace(
                        page_table=out.page_table[None])

                body = self._sharded_program(
                    _body, (self._cache_pspec(), P()),
                    self._cache_pspec())
            elif self.cache_mode == 'paged':
                def body(cache, lengths):
                    return paged_rollback_slots(cache, lengths,
                                                span_rows)
            else:
                def body(cache, lengths):
                    return rollback_slots(cache, lengths,
                                          span=span_rows)
            prog = self._rollbacks[span_rows] = jax.jit(
                watch_traces(body, f'engine.rollback_s{span_rows}',
                             budget=2),
                donate_argnums=(0,))
        return prog

    def rollback(self, lengths):
        """Acceptance-prefix rollback: truncate each slot to
        ``lengths[i]`` rows and zero the rejected tail —
        ``min(current, target)`` semantics, so a past-fill sentinel
        (e.g. ``np.iinfo(np.int32).max``) leaves a slot untouched and
        ONE batched call serves a mixed tick. The zeroing is surgical
        (a span-bounded scatter, not a cache rewrite); spans compile
        per power-of-two bucket, so a whole serving run uses one or
        two programs. Paged engines additionally return now-empty tail
        pages to the pool (refcount--, freed pages zeroed — the alloc
        invariant) and resync the device page table."""
        tgt = np.asarray(lengths, np.int64)
        cur = (self.pool.lengths.astype(np.int64)
               if self.cache_mode == 'paged'
               else np.asarray(self.cache.length, np.int64))
        new = np.minimum(cur, tgt)
        need = int((cur - new).max()) if cur.size else 0
        if need == 0:
            return
        bucket = 1 << (need - 1).bit_length()
        with span('engine.rollback', rows=need):
            t0 = time.perf_counter()
            self.cache = self._rollback_program(bucket)(
                self.cache, jnp.asarray(new, jnp.int32))
            self.program_seconds += time.perf_counter() - t0
        if self.cache_mode == 'paged':
            if self.kv_shards > 1:
                freed = {}
                for i in np.nonzero(cur > new)[0]:
                    for s, pgs in self.pool.truncate(
                            int(i), int(new[i])).items():
                        freed.setdefault(s, []).extend(pgs)
                if freed:
                    self._zero_freed_sharded(freed)
            else:
                freed = []
                for i in np.nonzero(cur > new)[0]:
                    freed += self.pool.truncate(int(i), int(new[i]))
                if freed:
                    self._zero_freed(freed)
            self._sync_page_table()

    def prefill(self, slot, tokens, request_id=None):
        """Append one prompt chunk (``len(tokens) <= prefill_chunk``)
        into ``slot``. Pads to the compiled chunk width; padded rows
        never land (counts mask). ``request_id`` labels the span only
        (see :meth:`step`)."""
        n = len(tokens)
        if n > self.prefill_chunk:
            raise ServeContractError(
                f'chunk of {n} exceeds prefill_chunk='
                f'{self.prefill_chunk}')
        buf = np.zeros(self.prefill_chunk, np.int32)
        buf[:n] = np.asarray(tokens, np.int32)
        if self.cache_mode == 'paged':
            # Auto-reserve the chunk's pages (no-op when the scheduler
            # already reserved the whole prompt at admission).
            pos = int(self.pool.lengths[slot])
            covered = (self.pool.covered_rows(slot)
                       if self.kv_shards > 1
                       else int(self.pool.counts[slot]) * self.page_size)
            if (pos + n) > covered and not self.reserve_rows(slot, n):
                raise RuntimeError(
                    f'page pool exhausted prefilling rows '
                    f'[{pos}, {pos + n}) of slot {slot} '
                    f'({self.pool.free_pages} pages free)')
            self._sync_page_table()
        with span('engine.prefill', slot=int(slot),
                  request=request_id or ''):
            t0 = time.perf_counter()
            self.cache = self._prefill(self.cache, jnp.int32(slot),
                                       jnp.asarray(buf), jnp.int32(n))
            self.program_seconds += time.perf_counter() - t0
        if self.cache_mode == 'paged':
            self.pool.lengths[slot] += n

    def _zero_freed(self, freed, slot=-1):
        """Zero freed pool pages (and clear ``slot``'s rows/length when
        one is named; slot −1 touches no slot) through the ONE compiled
        reset program — the freed-page zeroing contract lives here."""
        vec = np.full(self.pool.pages_per_slot, -1, np.int32)
        vec[:len(freed)] = freed
        self.cache = self._reset(self.cache, jnp.int32(slot),
                                 jnp.asarray(vec))
        if self.checksums is not None:
            self.checksums.drop(freed)

    def _zero_freed_sharded(self, freed, slot=-1):
        """kv_shards twin of :meth:`_zero_freed`: ``freed`` is
        ``{shard: [local pages]}``; the stacked per-shard vectors go
        through the ONE sharded reset program (each member zeroes its
        own list), and each shard's checksum table forgets its own."""
        vec = np.full((self.kv_shards, self.pool.pages_per_slot), -1,
                      np.int32)
        for s, pages in freed.items():
            vec[s, :len(pages)] = pages
        self.cache = self._reset(self.cache, jnp.int32(slot),
                                 jnp.asarray(vec))
        if self.checksums is not None:
            for s, pages in freed.items():
                self.checksums[s].drop(pages)

    def reset(self, slot):
        """Evict ``slot`` (zero rows + length); other slots untouched.
        Paged: drops the slot's page references and zeroes exactly the
        pages that reached refcount 0 (still-shared prefix/fork pages
        keep their bits — they are someone else's context)."""
        if self.cache_mode == 'paged':
            if self.kv_shards > 1:
                self._zero_freed_sharded(self.pool.release(slot), slot)
            else:
                self._zero_freed(self.pool.release(slot), slot)
            self._sync_page_table()
        else:
            self.cache = self._reset(self.cache, jnp.int32(slot))

    def lengths(self):
        # np.array, NOT np.asarray: on the CPU backend asarray is a
        # ZERO-COPY view of the device buffer, and every engine program
        # donates the cache — the next step would recycle the buffer
        # under the caller's snapshot. The verify-k commit loop anchors
        # its rollback targets on this vector across exactly such a
        # donating call, so a view here silently inflates every target
        # by the committed width (one token per slot per step leaks).
        return np.array(self.cache.length)

    # -- paged-pool surface (cache_mode='paged') ------------------------
    def _sync_page_table(self):
        if self.pool.dirty:
            if self.kv_shards > 1:
                # Stacked local views, explicitly re-placed P(seq) so
                # the donated device mirror never bounces through a
                # single-device layout on its way into the programs.
                pt = jax.device_put(
                    jnp.asarray(self.pool.local_tables()),
                    self._pt_sharding)
            else:
                pt = jnp.asarray(self.pool.table)
            self.cache = self.cache._replace(page_table=pt)
            self.pool.dirty = False

    def _apply_copies(self, copies):
        if self.kv_shards > 1:
            # (shard, src, dst) triples → stacked per-shard scalar
            # vectors (−1 = no copy on that member).
            for s, src, dst in copies:
                vs = np.full(self.kv_shards, -1, np.int32)
                vd = np.full(self.kv_shards, -1, np.int32)
                vs[s], vd[s] = src, dst
                self.cache = self._copy_attach(
                    self.cache, jnp.asarray(vs), jnp.asarray(vd),
                    jnp.int32(-1), jnp.int32(0))
            return
        for src, dst in copies:
            self.cache = self._copy_attach(
                self.cache, jnp.int32(src), jnp.int32(dst),
                jnp.int32(-1), jnp.int32(0))

    def _writable_mask(self, active):
        """Per active slot: does a PRIVATE page already cover its next
        append position (the prepare_step()/reserve_rows()
        postcondition)? Vectorized — this is the per-token fast path
        that lets step() skip re-preparing when the scheduler already
        did. A slot AT ``t_max`` counts as writable: there is no page
        to prepare — the device write drops while the length advances
        (the slab engine's frozen-write contract), so stepping it must
        not raise."""
        idx = np.nonzero(active)[0]
        ok = np.ones(len(active), bool)
        if not idx.size:
            return ok
        pool = self.pool
        if self.kv_shards > 1:
            # Route each slot's append ordinal to its OWNING shard's
            # table/refcount (slots are few; the owner lookup is the
            # cost of the sharded layout's locality).
            for i in idx:
                pi = int(pool.lengths[i]) // self.page_size
                if pi >= pool.pages_per_slot:
                    continue                    # full: writable no-op
                sp = pool.shards[pool.owner(pi)]
                pg = int(sp.table[i, pi])
                ok[i] = pg >= 0 and int(sp.refcount[pg]) == 1
            return ok
        pi = pool.lengths[idx] // self.page_size
        full = pi >= pool.pages_per_slot
        pg = pool.table[idx, np.minimum(pi, pool.pages_per_slot - 1)]
        good = (pg >= 0)
        good &= pool.refcount[np.maximum(pg, 0)] == 1
        ok[idx] = full | good
        return ok

    def prepare_step(self, active):
        """Make every active slot's next append position writable:
        allocate the page a slot crossing a page boundary needs, and
        copy-on-write any shared append page (first divergent append
        after a fork/prefix attach). Returns a ``(slots,) bool`` mask —
        False means the pool is EXHAUSTED for that slot and nothing was
        allocated; the scheduler owns the evict/preempt policy. A slot
        already at ``t_max`` is True (``'full'``): nothing to allocate,
        its append drops on device like the slab path's."""
        active = np.asarray(active, bool)
        ok = np.ones(self.slots, bool)
        # Vectorized fast path first: the per-token cost is one numpy
        # mask; the Python allocator loop below runs only for slots
        # that actually need a page (boundary crossing or shared
        # append page) — the same contract step()'s auto-prepare uses.
        todo = active & ~self._writable_mask(active)
        for i in np.nonzero(todo)[0]:
            if self.kv_shards > 1:
                # The sharded pool names WHICH shard's contiguous
                # range answered (exhaustion there is typed back
                # through the scheduler's evict/preempt ladder even
                # while other shards have headroom — never a stall).
                st, sh, src, dst = self.pool.prepare_append(int(i))
                if st == 'exhausted':
                    ok[i] = False
                elif st == 'cow':
                    self._apply_copies([(sh, src, dst)])
                continue
            st, src, dst = self.pool.prepare_append(int(i))
            if st == 'exhausted':
                ok[i] = False
            elif st == 'cow':
                self._apply_copies([(src, dst)])
        self._sync_page_table()
        return ok

    def reserve_rows(self, slot, rows):
        """Admission-time reservation: every page covering ``slot``'s
        next ``rows`` logical rows (so chunked prefill can never fail
        mid-prompt). False = pool exhausted, nothing changed."""
        ok, copies = self.pool.reserve_rows(slot, rows)
        if ok:
            self._apply_copies(copies)
            self._sync_page_table()
        return ok

    def register_prefix(self, tokens):
        """Prefill ``tokens`` ONCE into registry-owned pool pages and
        return a prefix id. Sequences started with
        :meth:`start_with_prefix` share the prefix's full pages
        read-only (refcounted) — N sequences riding a system prompt
        cost its pages once plus one partial tail page each."""
        if self.cache_mode != 'paged':
            raise ValueError("prefix sharing needs cache_mode='paged'")
        if self.kv_shards > 1:
            raise ValueError(
                'register_prefix (local prefix prefill) is not '
                'supported with kv_shards > 1 — shared prefixes arrive '
                'through the shard-local prefill→decode handoff '
                '(adopt_prefix)')
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens)
        if n < 1:
            raise ValueError('empty prefix')
        if n + 1 > self.t_max:
            raise ValueError(f'prefix of {n} tokens leaves no room to '
                             f'generate in a t_max={self.t_max} cache')
        needed = self.pool.pages_for_rows(n)
        pages = self.pool.alloc_block(needed)
        if pages is None:
            raise RuntimeError(
                f'page pool exhausted registering a {n}-token '
                f'prefix ({needed} pages needed, '
                f'{self.pool.free_pages} free)')
        row = np.full(self.pool.pages_per_slot, -1, np.int32)
        row[:needed] = pages
        row_j = jnp.asarray(row)
        for start in range(0, n, self.prefill_chunk):
            chunk = tokens[start:start + self.prefill_chunk]
            buf = np.zeros(self.prefill_chunk, np.int32)
            buf[:len(chunk)] = chunk
            self.cache = self._prefix_fill(
                self.cache, jnp.asarray(buf), jnp.int32(len(chunk)),
                row_j, jnp.int32(start))
        return self._register_pages(pages, n)

    def _register_pages(self, pages, n):
        """Enter ``pages`` (already allocated and filled, covering
        ``n`` rows) into the prefix registry — the one place prefix
        ids are minted, shared by :meth:`register_prefix` (local
        prefill) and :meth:`adopt_prefix` (cross-cache handoff)."""
        pid = next(self._prefix_counter)
        self._prefix_registry[pid] = (pages, n)
        self._checksum_record(pages)
        return pid

    # -- page integrity (host-side, transfer boundaries only) -----------
    def _by_shard(self, pages):
        """Group GLOBAL page ids → ``{shard: [local pages]}`` (kv_shards
        surface; the order within a shard follows the input)."""
        per = {}
        for g in pages:
            s, p = self._gsplit(g)
            per.setdefault(s, []).append(p)
        return per

    def _checksum_record(self, pages):
        if self.checksums is None:
            return
        t0 = time.perf_counter()
        if self.kv_shards > 1:
            # Per-owning-shard tables, keyed by LOCAL page ids; the
            # digest reads the page's stacked pool row. A shard's
            # table never holds another shard's pages — the coherence
            # contract the sharded transfer boundaries maintain.
            for s, locs in self._by_shard(pages).items():
                tab = self.checksums[s]
                for p in locs:
                    tab.record_at(self.cache, p,
                                  row=self._gpage(s, p))
        else:
            self.checksums.record(self.cache, pages)
        self.verify_seconds += time.perf_counter() - t0

    def verify_pages(self, pages=None):
        """Re-digest ``pages`` (default: every tracked page — the
        scrub) against the recorded checksums. Returns the sorted
        dirty-page list without raising; [] when clean or when
        checksums are disabled. Host work only. kv_shards engines
        speak GLOBAL page ids here (in and out)."""
        if self.checksums is None:
            return []
        t0 = time.perf_counter()
        if self.kv_shards > 1:
            bad = []
            per = (self._by_shard(pages) if pages is not None
                   else {s: tab.pages()
                         for s, tab in enumerate(self.checksums)})
            for s, locs in per.items():
                tab = self.checksums[s]
                for p in locs:
                    want = tab.get(p)
                    if want is not None and PageChecksums.digest(
                            self.cache,
                            self._gpage(s, p)) != want:
                        bad.append(self._gpage(s, p))
            bad = sorted(bad)
        else:
            bad = self.checksums.verify(self.cache, pages)
        self.verify_seconds += time.perf_counter() - t0
        return bad

    def verify_prefix(self, prefix_id):
        """Scrub one registered prefix's pages (dirty list, no raise)."""
        pages, _ = self._prefix_registry[prefix_id]
        return self.verify_pages(pages)

    def check_pages(self, pages, site):
        """Raise :class:`PageCorruptionError` naming ``site`` if any of
        ``pages`` fails verification (untracked pages are skipped). On
        kv_shards engines the error also names the dirty shard(s)."""
        bad = self.verify_pages(pages)
        if bad:
            shards = ([self.page_shard(p) for p in bad]
                      if self.kv_shards > 1 else None)
            raise PageCorruptionError(bad, site, shards=shards)

    def quarantine_pages(self, pages):
        """Withdraw dirty pages from circulation (they never return to
        the free list) and forget their digests so scrubs stop
        re-flagging them. Returns the pages newly quarantined —
        GLOBAL ids in and out on kv_shards engines, routed to each
        page's owning shard."""
        if self.kv_shards > 1:
            newly = []
            for s, locs in self._by_shard(pages).items():
                if self.checksums is not None:
                    self.checksums[s].drop(locs)
                newly += [self._gpage(s, p)
                          for p in self.pool.quarantine(s, locs)]
            return sorted(newly)
        if self.checksums is not None:
            self.checksums.drop(pages)
        return self.pool.quarantine(pages)

    def slots_sharing(self, pages):
        """Slots whose page tables name any of ``pages`` — the live
        victims of a corruption verdict."""
        if self.pool is None:
            return []
        if self.kv_shards > 1:
            per = {s: set(locs)
                   for s, locs in self._by_shard(pages).items()}
            hit = []
            for slot in range(self.slots):
                for s, locs in per.items():
                    sp = self.pool.shards[s]
                    if any(int(sp.table[slot, i]) in locs
                           for i in range(int(sp.counts[slot]))):
                        hit.append(slot)
                        break
            return hit
        bad = {int(p) for p in pages}
        hit = []
        for slot in range(self.slots):
            n = int(self.pool.counts[slot])
            if any(int(self.pool.table[slot, i]) in bad
                   for i in range(n)):
                hit.append(slot)
        return hit

    def prefixes_on(self, pages):
        """Registered prefix ids built on any of ``pages`` — the
        entries a corruption verdict must invalidate."""
        bad = {int(p) for p in pages}
        return [pid for pid, (pgs, _) in self._prefix_registry.items()
                if bad.intersection(int(p) for p in pgs)]

    def _transfer_program(self, src_shape):
        prog = self._transfers.get(src_shape)
        if prog is None:
            if self.kv_shards > 1:
                # Shard-local handoff: source pages arrive as a
                # shard-STACKED slab (kv_shards, width, ...) laid out
                # P(seq) — each mesh member holds, and copies from,
                # ONLY the pages whose ordinals it owns. No member
                # ever materializes the full sequence; the transfer
                # unit stays the page.
                from jax.sharding import PartitionSpec as P

                def _body(cache, src_k, src_v, vsrc, vdst):
                    local = cache._replace(
                        page_table=cache.page_table[0])
                    out = paged_transfer_pages(local, src_k[0],
                                               src_v[0],
                                               vsrc[0], vdst[0])
                    return out._replace(
                        page_table=out.page_table[None])

                fn = self._sharded_program(
                    _body,
                    (self._cache_pspec(), P(self._seq_axis),
                     P(self._seq_axis),
                     P(self._seq_axis), P(self._seq_axis)),
                    self._cache_pspec())
            else:
                fn = paged_transfer_pages
            prog = self._transfers[src_shape] = jax.jit(
                watch_traces(fn, 'engine.adopt', budget=2),
                donate_argnums=(0,))
        return prog

    def adopt_prefix(self, src_cache, src_pages, length,
                     src_checksums=None):
        """The prefill→decode KV handoff (disaggregated serving): copy
        ``length`` rows living in ``src_pages`` of ANOTHER paged cache
        (a prefill pool's — same page size and head geometry, its own
        pool size) into freshly allocated pages of THIS engine's pool
        and register them as a shared prefix. One compiled program
        moves whole pages — the transfer unit is the page, exactly as
        :meth:`register_prefix`'s product is, so sequences started
        with :meth:`start_with_prefix` cannot tell a handed-off prefix
        from a locally prefilled one. Raises on pool exhaustion (the
        router checks headroom first) and on geometry mismatch.

        ``src_checksums`` (the source pool's :class:`PageChecksums`)
        makes the handoff end-to-end verifiable: the source pages are
        verified BEFORE the transfer (dirty source →
        :class:`PageCorruptionError` at site 'handoff_src') and the
        landed copies' KV digests are compared to the source's AFTER
        (a corrupted transfer → site 'handoff_copy', with the adopted
        prefix unregistered — never handed to a caller). Only
        ``kv_crc`` crosses caches: the destination int8 mirror is
        re-quantized from the adopted K with eps-scale tail rows, so
        mirror bytes legitimately differ between pools."""
        if self.cache_mode != 'paged':
            raise ValueError("prefix adoption needs cache_mode='paged'")
        if src_cache.page_size != self.page_size:
            raise ValueError(
                f'page-size mismatch: source {src_cache.page_size} vs '
                f'{self.page_size} — the page is the transfer unit, '
                f'both pools must agree')
        if src_cache.k_pool.shape[1:] != self.cache.k_pool.shape[1:] \
                or src_cache.v_pool.shape[1:] != self.cache.v_pool.shape[1:]:
            raise ValueError(
                f'KV geometry mismatch: source pages '
                f'{src_cache.k_pool.shape[1:]} vs '
                f'{self.cache.k_pool.shape[1:]}')
        if length < 1 or length + 1 > self.t_max:
            raise ValueError(f'prefix of {length} rows leaves no room '
                             f'to generate in a t_max={self.t_max} '
                             f'cache')
        src_pages = [int(p) for p in src_pages]
        needed = self.pool.pages_for_rows(length)
        if len(src_pages) != needed:
            raise ValueError(f'{len(src_pages)} source pages for '
                             f'{length} rows (need {needed})')
        if src_checksums is not None:
            t0 = time.perf_counter()
            bad = src_checksums.verify(src_cache, src_pages)
            self.verify_seconds += time.perf_counter() - t0
            if bad:
                raise PageCorruptionError(bad, 'handoff_src')
        if self.kv_shards > 1:
            return self._adopt_prefix_sharded(
                src_cache, src_pages, length, src_checksums, needed)
        pages = self.pool.alloc_block(needed)
        if pages is None:
            raise RuntimeError(
                f'page pool exhausted adopting a {length}-row prefix '
                f'({needed} pages needed, {self.pool.free_pages} free)')
        # Fixed-width −1-padded vectors: one compiled transfer program
        # per source pool shape, whatever the prefix length.
        width = max(self.pool.pages_per_slot, needed)
        vec_src = np.full(width, -1, np.int32)
        vec_dst = np.full(width, -1, np.int32)
        vec_src[:needed] = src_pages
        vec_dst[:needed] = pages
        key = (src_cache.k_pool.shape, src_cache.v_pool.shape, width)
        # The source pool lives on the PREFILL pool's mesh. Bring it to
        # this cache's own placement first: fed as it is, the transfer's
        # outputs follow it onto that mesh, and every fixed-shape
        # program of this engine then traces again for a cache whose
        # type names the mesh (the retrace sentinel's third trace).
        home = self.cache.k_pool.sharding
        self.cache = self._transfer_program(key)(
            self.cache, jax.device_put(src_cache.k_pool, home),
            jax.device_put(src_cache.v_pool, home),
            jnp.asarray(vec_src), jnp.asarray(vec_dst))
        pid = self._register_pages(pages, length)
        if self.checksums is not None and src_checksums is not None:
            # Landed-copy verification: the transfer moves whole pages
            # (unfilled tail rows are zero on both sides), so the KV
            # digest must survive the copy bit-exactly.
            bad = []
            for sp, dp in zip(src_pages, pages):
                want = src_checksums.get(sp)
                have = self.checksums.get(dp)
                if want is not None and have is not None \
                        and have[0] != want[0]:
                    bad.append(dp)
            if bad:
                self.unregister_prefix(pid)
                raise PageCorruptionError(bad, 'handoff_copy')
        return pid

    def _adopt_prefix_sharded(self, src_cache, src_pages, length,
                              src_checksums, needed):
        """kv_shards tail of :meth:`adopt_prefix` (validation and the
        source verify already ran): allocate, per shard, exactly the
        pages covering the ordinals that shard OWNS, then run ONE
        stacked transfer program in which each mesh member copies only
        its own ordinals' source pages into its own pool block — the
        shard-local handoff, page-granular, with no full-sequence
        gather anywhere. All-or-nothing allocation: any shard's
        exhaustion rolls the other shards' fresh blocks back."""
        alloc = {}                       # shard -> local pages, by ordinal
        for s in range(self.kv_shards):
            lo, hi = self.pool.owned_range(s)
            k = max(0, min(hi, needed) - lo)
            if k == 0:
                continue
            pgs = self.pool.shards[s].alloc_block(k)
            if pgs is None:
                for s2, got in alloc.items():
                    self.pool.shards[s2].release_pages(got)
                raise RuntimeError(
                    f'page pool exhausted adopting a {length}-row '
                    f'prefix: shard {s} has '
                    f'{self.pool.shards[s].free_pages} of the {k} '
                    f'pages its ordinal range [{lo}, {min(hi, needed)})'
                    f' needs (free by shard '
                    f'{self.pool.free_pages_by_shard})')
            alloc[s] = pgs
        width = self.pool.pages_per_slot
        vec_src = np.full((self.kv_shards, width), -1, np.int32)
        vec_dst = np.full((self.kv_shards, width), -1, np.int32)
        sel = np.zeros((self.kv_shards, width), np.int64)
        gpages = [0] * needed
        for s, pgs in alloc.items():
            lo, _ = self.pool.owned_range(s)
            for j, p in enumerate(pgs):
                sel[s, j] = src_pages[lo + j]
                vec_src[s, j] = j          # row WITHIN the staged slab
                vec_dst[s, j] = p
                gpages[lo + j] = self._gpage(s, p)
        # Stage only the referenced source pages, shard-stacked and
        # laid out P(seq) on THIS engine's mesh: each member receives
        # exactly the pages covering its own ordinal range (the
        # single-controller analog of a per-shard point-to-point send
        # — the source pool may live on a different mesh entirely).
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        slab_sh = NamedSharding(self._mesh, P(self._seq_axis))
        flat = sel.reshape(-1)
        src_k = jax.device_put(
            jnp.asarray(np.asarray(src_cache.k_pool)[flat]).reshape(
                self.kv_shards, width, *src_cache.k_pool.shape[1:]),
            slab_sh)
        src_v = jax.device_put(
            jnp.asarray(np.asarray(src_cache.v_pool)[flat]).reshape(
                self.kv_shards, width, *src_cache.v_pool.shape[1:]),
            slab_sh)
        key = (src_k.shape, src_v.shape, width)
        self.cache = self._transfer_program(key)(
            self.cache, src_k, src_v,
            jnp.asarray(vec_src), jnp.asarray(vec_dst))
        pid = self._register_pages(gpages, length)
        if self.checksums is not None and src_checksums is not None:
            # Landed-copy verification, per owning shard: each landed
            # page's KV digest (recorded against its stacked row just
            # above) must equal the source page's.
            bad = []
            for o, g in enumerate(gpages):
                want = src_checksums.get(src_pages[o])
                s, p = self._gsplit(g)
                have = self.checksums[s].get(p)
                if want is not None and have is not None \
                        and have[0] != want[0]:
                    bad.append(g)
            if bad:
                self.unregister_prefix(pid)
                raise PageCorruptionError(
                    bad, 'handoff_copy',
                    shards=[self.page_shard(g) for g in bad])
        return pid

    def prefix_length(self, prefix_id):
        return self._prefix_registry[prefix_id][1]

    def unregister_prefix(self, prefix_id):
        """Release the registry's page references; pages still shared
        by live sequences survive until those retire."""
        pages, _ = self._prefix_registry.pop(prefix_id)
        if self.kv_shards > 1:
            freed = {}
            for s, locs in self._by_shard(pages).items():
                got = self.pool.release_pages_on(s, locs)
                if got:
                    freed[s] = got
            if freed:
                self._zero_freed_sharded(freed)
            return
        freed = self.pool.release_pages(pages)
        if freed:
            self._zero_freed(freed)

    def start_with_prefix(self, slot, prefix_id):
        """Point an EMPTY slot at a registered prefix: full pages
        shared (refcount++), partial tail page copied private, length
        set — the slot then prefills/decodes its own continuation.
        False = pool exhausted (no tail page available). The prefix's
        pages are verified first — attaching a sequence to a corrupted
        prefix raises before any token can read it. kv_shards engines
        attach per owning shard (the tail copy lands on the tail
        ordinal's owner)."""
        pages, plen = self._prefix_registry[prefix_id]
        self.check_pages(pages, 'attach')
        if self.kv_shards > 1:
            ord_pages = np.full(self.pool.pages_per_slot, -1, np.int32)
            for o, g in enumerate(pages):
                ord_pages[o] = self._gsplit(g)[1]
            ok, tsh, tsrc, tdst = self.pool.attach(slot, ord_pages,
                                                   plen)
            if not ok:
                return False
            vs = np.full(self.kv_shards, -1, np.int32)
            vd = np.full(self.kv_shards, -1, np.int32)
            if tsh >= 0:
                vs[tsh], vd[tsh] = tsrc, tdst
            self.cache = self._copy_attach(
                self.cache, jnp.asarray(vs), jnp.asarray(vd),
                jnp.int32(slot), jnp.int32(plen))
            self._sync_page_table()
            return True
        ok, src, dst = self.pool.attach(slot, pages, plen)
        if not ok:
            return False
        self.cache = self._copy_attach(self.cache, jnp.int32(src),
                                       jnp.int32(dst), jnp.int32(slot),
                                       jnp.int32(plen))
        self._sync_page_table()
        return True

    def fork_slot(self, src, dst):
        """Copy-on-write fork for parallel sampling: ``dst`` (an empty
        slot) shares ``src``'s full pages and gets a private copy of
        the partial tail page — O(1 page) device work however long the
        context. False = pool exhausted. The source's TRACKED pages
        (shared prefix pages — private append pages are out of
        coverage) are verified before the branch shares them."""
        if self.kv_shards > 1:
            raise ValueError(
                'fork_slot (copy-on-write forks) is not supported with '
                'kv_shards > 1 — run parallel sampling on unsharded '
                'replicas')
        if self.checksums is not None:
            shared = [int(self.pool.table[src, i])
                      for i in range(int(self.pool.counts[src]))]
            self.check_pages(shared, 'fork')
        ok, tail_src, tail_dst = self.pool.fork(src, dst)
        if not ok:
            return False
        self.cache = self._copy_attach(
            self.cache, jnp.int32(tail_src), jnp.int32(tail_dst),
            jnp.int32(dst), jnp.int32(int(self.pool.lengths[dst])))
        self._sync_page_table()
        return True

    @property
    def weight_bytes(self):
        """Bytes of the four projection/head matrices a decode step
        streams (int8 engines count the int8 kernels + their scales) —
        the weights column of the quantized-vs-float benchmark twins.
        The embedding is excluded: a step gathers S rows of it, not
        the table."""
        from distributed_dot_product_tpu.models.dense import (
            dense_param_bytes,
        )
        return dense_param_bytes(
            [self._wq, self._wk, self._wv, self._wo])

    @property
    def free_pages(self):
        return self.pool.free_pages if self.pool is not None else None

    @property
    def pinned_pages(self):
        """Distinct pool pages the prefix registry holds a permanent
        reference on — they can never return to the free list while
        their prefix stays registered (each prefix allocates fresh
        pages, so the per-prefix page lists are disjoint). 0 on slab
        engines, like the other probe-any-engine accessors."""
        if self.pool is None:
            return 0
        return sum(len(pages)
                   for pages, _ in self._prefix_registry.values())

    @property
    def capacity_tokens(self):
        """Most rows ONE fresh sequence can ever hold: the per-slot
        table reach capped by the pool itself."""
        if self.pool is None:
            return self.t_max
        return min(self.t_max, self.pool.pages * self.page_size)

    def slot_pages(self, slot):
        return self.pool.slot_pages(slot) if self.pool is not None else 0

    def cache_stats(self):
        """Occupancy snapshot for the scheduler's gauges. A slab
        engine has no pool (everything statically reserved) — report
        zeros so generic dashboard code can probe any engine, matching
        the ``free_pages``/``slot_pages`` guards."""
        pool = self.pool
        if pool is None:
            return {'pages': 0, 'pages_used': 0, 'pages_free': 0,
                    'shared_pages': 0, 'page_size': 0,
                    'pages_quarantined': 0}
        out = {'pages': pool.pages, 'pages_used': pool.used_pages,
               'pages_free': pool.free_pages,
               'shared_pages': pool.shared_pages,
               'page_size': pool.page_size,
               'pages_quarantined': len(pool.quarantined)}
        if self.kv_shards > 1:
            # Shard-aware occupancy: the aggregate rows above already
            # sum across shards; the per-shard free vector is what an
            # operator needs to see a single shard's range running dry
            # while the aggregate still looks healthy.
            out['kv_shards'] = self.kv_shards
            out['pages_free_by_shard'] = pool.free_pages_by_shard
        return out

    # -- chaos seam (utils/faults.py page_corrupt knob) -----------------
    def tracked_pages(self):
        """Registry-tracked pages, sorted (GLOBAL ids on kv_shards
        engines) — the population the page_corrupt chaos knob indexes
        so a seeded trace corrupts the same prefix page whatever the
        pool's allocation history."""
        return sorted({int(p)
                       for pages, _ in self._prefix_registry.values()
                       for p in pages})

    def flip_page_bit(self, page):
        """Flip an EXPONENT bit of ``page``'s first K value (byte 3 of
        a little-endian float32) host-side — the chaos injector's
        corruption primitive. The corruption is semantically loud: an
        undetected flip changes delivered tokens, which is exactly
        what the no-integrity twin must demonstrate; the checksum does
        not care which bit flipped. On kv_shards engines ``page`` is
        the GLOBAL id, which IS the stacked pool row, and the rebuilt
        buffer is re-placed on the mesh so the donated decode step
        keeps its layout."""
        k_pool = np.array(self.cache.k_pool)
        k_pool[int(page)].reshape(-1).view(np.uint8)[3] ^= 0x40
        # jnp.array (NOT asarray): the device buffer must OWN its
        # bytes. On CPU asarray can alias the numpy host copy, and the
        # next decode step donates the cache buffer — XLA would free
        # memory Python owns.
        buf = jnp.array(k_pool)
        if self.kv_shards > 1:
            buf = jax.device_put(buf, self._pt_sharding)
        self.cache = self.cache._replace(k_pool=buf)
