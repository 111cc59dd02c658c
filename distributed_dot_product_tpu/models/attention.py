# -*- coding: utf-8 -*-
"""
Sequence-parallel multi-head dot-product attention (model layer).

TPU-native rebuild of the reference L4 layer
(reference module.py:22-76, ``DistributedDotProductAttn``): a flax module
over sequence-sharded inputs — every array the module sees is the local
``(B, T/N, d)`` shard; cross-device coupling happens only inside the
distributed matmul operators.

Behavioral parity with the reference forward (reference module.py:41-76):

- four projections ``keys/queries/values/composition`` with dims
  ``key_dim→key_dim``, ``query_dim→key_dim``, ``value_dim→value_dim``,
  ``value_dim→value_dim`` and a shared ``add_bias`` flag (default False)
  (reference module.py:36-39);
- multi-head split applied **only when num_heads > 1**, reshaping to
  ``(B, H, T/N, dh)`` and broadcasting the mask over heads (reference
  module.py:47-58);
- scores = ``matmul_nt(keys, queries, offset)`` — **K first, Q second**,
  i.e. scores = ``K·Qᵀ`` (reference module.py:60-62), scaled by
  ``1/√(key_dim/num_heads)`` (reference module.py:35,65);
- boolean mask → ``-inf`` fill, then softmax over the **full global-T last
  axis** (reference module.py:66-67). Score rows ``(T/N, T)`` are fully
  materialized — O(T²/N) per shard, the reference's memory behavior; pass
  ``softmax_impl='online'`` to route through
  :mod:`distributed_dot_product_tpu.models.ring_attention` instead
  (O((T/N)²) score memory, no full-row materialization);
- context = ``matmul_all(attn, values, offset)`` (reference module.py:68-69),
  head merge, output projection (reference module.py:72-75);
- ``distributed=False`` computes the identical math with local matmuls — the
  single-process oracle branch the reference tests against (reference
  module.py:26,63-64,70-71; test_gradient.py:45-47).

Unlike the reference, importing this module does **not** initialize any
distributed runtime (the reference calls ``hvd.init()`` at import,
reference module.py:19).
"""

import math
import warnings
import zlib
from collections import OrderedDict
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_dot_product_tpu.models import features
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.models.ring_attention import (
    _layout_positions, local_attention_reference, ring_attention,
)
from distributed_dot_product_tpu.ops.rope import rope, rope_interleaved
from distributed_dot_product_tpu.models.remat import (
    LAYER_MATMUL_NAMES, named, note_named,
)
from distributed_dot_product_tpu.models.ulysses_attention import (
    ulysses_attention,
)
from distributed_dot_product_tpu.ops.pallas_attention import (
    FLASH_QKV_NAME, flash_attention,
)
from distributed_dot_product_tpu.models.sparse import (
    SparseSpec, pool_rows, pooled_after_chunk, sparse_attention,
    sparse_step,
)
from distributed_dot_product_tpu.ops.ops import matmul_all, matmul_nt
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.retrace import watch_traces
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['DistributedDotProductAttn', 'apply_seq_parallel',
           'decode_seq_parallel', 'make_decode_step']


class DistributedDotProductAttn(nn.Module):
    """Multi-head dot-product attention over sequence-sharded inputs.

    Constructor surface matches the reference (reference module.py:23-26)
    plus TPU-specific knobs (``axis_name``, ``impl``, ``dtype``).

    Call: ``module.apply(params, keys, queries, values, attn_mask)`` with
    local shards ``keys (B, T/N, key_dim)``, ``queries (B, T/N, query_dim)``,
    ``values (B, T/N, value_dim)`` and boolean ``attn_mask (B, T/N, T)``
    (True = masked out, reference README.md:67). When ``distributed=True``
    the call must run inside a ``shard_map`` over ``axis_name`` — use
    :func:`apply_seq_parallel` for global arrays on a mesh.
    """
    key_dim: int
    value_dim: Optional[int] = None
    # The composition's output width where it is not ``value_dim``: a
    # model whose heads together are wider than its residual stream
    # (128 heads x 128 on a 4096-wide stream) sets ``key_dim`` to the
    # heads' width and this to the stream's.
    out_dim: Optional[int] = None
    query_dim: Optional[int] = None
    num_heads: int = 1
    # Grouped-query attention (GQA; None = standard multi-head). The
    # module's K-first convention (scores = K·Qᵀ softmaxed over the
    # gathered axis, reference module.py:60-67) means its *queries* and
    # *values* play standard attention's K/V role — they are the
    # softmax-table side that gets gathered across shards — while output
    # rows follow the keys. ``num_kv_heads`` therefore shrinks the
    # queries/values projections to ``num_kv_heads`` heads (each group of
    # ``num_heads // num_kv_heads`` key heads shares one); the gathered
    # operand volume, K/V-analog memory and (on the flash path) ICI bytes
    # all drop by that factor. ``num_kv_heads=1`` is multi-query.
    # Extends the reference constructor (reference module.py:23-39, which
    # has no GQA); supported on every softmax_impl — the fused kernels
    # handle groups natively, the 'full' parity path repeats heads (it
    # densifies everything anyway).
    num_kv_heads: Optional[int] = None
    add_bias: bool = False
    offset: int = 32
    # Causal (autoregressive) masking over GLOBAL positions: output row i
    # only mixes positions j <= i. The reference has no causal flag (users
    # must encode the triangle into attn_mask, O(T²/N) per shard anyway);
    # this derives it from the shard's global offset and ORs it into the
    # mask, so it works identically in every softmax_impl.
    causal: bool = False
    # Sliding-window lookback cap over GLOBAL positions (requires
    # causal=True): row i attends columns (i − window, i]. Native in the
    # flash/online/ulysses kernels with whole-block skipping — compute and
    # HBM traffic per shard become O(window·T/N), linear in T; the 'full'
    # parity path densifies it into the mask. No reference analog.
    window: Optional[int] = None
    distributed: bool = True
    axis_name: str = SEQ_AXIS
    impl: str = 'allgather'
    # 'full' (parity) | 'online' (ring) | 'flash' | 'ulysses'
    softmax_impl: str = 'full'
    # softmax_impl='online' + causal only: 'zigzag' balances the causal
    # ring's critical path (shard i holds half-stripes {i, 2W-1-i}; feed
    # inputs permuted by models.ring_attention.zigzag_indices and invert
    # on the output). segment_ids ride the permuted layout directly (ids
    # need only equality); a dense attn_mask needs its ROW axis permuted
    # like the inputs (columns stay global — the ring folds gather them
    # per owner, see ring_attention).
    ring_layout: str = 'contiguous'
    # For softmax_impl='flash': 'exact' running-max softmax, or 'bounded'
    # (norm-bound shift — faster at small head dim; see
    # ops.pallas_attention.flash_attention for the accuracy contract).
    flash_softmax_mode: str = 'exact'
    # Attention-weight dropout (flash/online/ulysses): flax-idiomatic —
    # pass rngs={'dropout': key} to apply() (or deterministic=True to
    # disable, e.g. at eval). The in-kernel mask needs no O(T²) tensor
    # and hashes GLOBAL element coordinates, so the ring path's folds
    # draw exactly the single-device mask; see
    # ops.pallas_attention.flash_attention.
    dropout_rate: float = 0.0
    # ALiBi slopes, shape (num_heads,) (flash/online/ulysses; requires
    # causal=True). In the K-first convention attention rows follow
    # keys, so the bias is over key-vs-query global positions — the same
    # relative-distance bias as standard attention.
    alibi_slopes: Optional[Any] = None
    # 'int8' = quantized QK^T scoring in the fused kernels
    # (flash/online/ulysses; see flash_attention — the ring path's folds
    # quantize per resident block, which the row-local rule makes
    # identical to one big kernel's quantization).
    qk_quant: Optional[str] = None
    # Rotary position embeddings on the projected score operands (keys
    # AND queries — both sides of the K-first scoring, so logits depend
    # on relative global distance; values are never rotated). Positions
    # are GLOBAL: each shard rotates by its offset (or its zigzag
    # position vector under ring_layout='zigzag'), so the sharded result
    # equals the full-array rotation exactly (see ops/rope.py). No
    # reference analog (it has no positional encoding); the natural
    # companion to causal long-context training here. Reference anchor
    # for where the rotation lands: the projections in the forward,
    # reference module.py:41-58.
    use_rope: bool = False
    rope_base: float = 10000.0
    # Which features make a rotary pair: 'half' (i, i + d/2 — NeoX /
    # LLaMA, ops.rope.rope) or 'interleaved' (2i, 2i + 1 — GPT-J /
    # Cohere, ops.rope.rope_interleaved). The same rotation on other
    # pairs: a checkpoint's layout, not a choice of the math.
    rope_layout: str = 'half'
    # A window layer's decode cache as a RING of this many columns
    # (models.decode.RingCache; needs ``window <= ring_cache``): it
    # holds the newest rows whatever the context's length, where the
    # slab stores all t_max and only skips reading them. Where t_max is
    # no more than this the slab is the smaller one and is built.
    ring_cache: Optional[int] = None
    # The decode cache of heads NARROWER than a lane tile as ONE slab of
    # keys and values side by side (models.decode.PackedCache: at 64-wide
    # heads a 128-lane row a token a KV head, nothing padded, where the
    # two buffers of a DecodeCache are each stored and streamed at 128
    # lanes). Keys and values of one width whose double is whole lane
    # tiles; no int8 mirror, ring or sparse cache beside it. False
    # builds the cache this module always built.
    kv_packed: bool = False
    # Decode-step implementation: None/'auto' picks the fused Pallas
    # decode kernel (in-place aliased cache append + split-K masked
    # attention, ops/pallas_decode.py) on TPU and the portable XLA
    # append+einsum step elsewhere; 'kernel'/'xla' force a path (the
    # kernel runs interpreted off-TPU, mirroring the flash-kernel
    # gating). Applies to decode/decode_sharded; prefill always runs
    # the flash kernel.
    decode_impl: Optional[str] = None
    # The number the scores are multiplied by before the softmax, where
    # it is not ``head_dim ** -0.5`` (None; Granite's
    # ``attention_multiplier``): ONE field read by every route — the
    # parity path, the flash, ring and Ulysses routes, prefill and both
    # decode steps — so that they agree.
    softmax_scale: Optional[float] = None
    # An elementwise output gate (``solar_open2``'s ``use_gqa_gate``): a
    # second projection ``gate`` of the same input as ``keys`` — the
    # literature's query side under the K-first convention — as wide as
    # the heads' output, ``sigmoid``, times that output before the
    # ``composition``; at every entry point, inside ``lm.attn_proj``.
    # False adds no parameter and no operation.
    out_gate: bool = False
    # A per-head RMSNorm with a learned ``(head_dim,)`` scale on both
    # score operands after the head split and before any rotation
    # (``minicpm_sala``'s ``qk_norm``; parameters ``keys_norm`` /
    # ``queries_norm``), read at every entry point. False adds no
    # parameter and no operation.
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # Learned block-sparse attention (``models/sparse.py``): a dict of
    # :class:`~distributed_dot_product_tpu.models.sparse.SparseSpec`'s
    # sizes (``{}`` for the published ones). A token attends the blocks
    # it picks by its scores against POOLED keys; the decode cache is a
    # ``SparseCache`` (the slab and the pooled keys), a step the kernel
    # ``sparse_decode``, a prompt chunk the flash forward under the
    # picks' block mask. Causal, local (no sequence axis), and none of
    # window / ALiBi / segments / int8 scores / dropout. The picks are
    # sown into the ``'counters'`` collection (``sparse_picks``,
    # ``sparse_count``) where a caller makes it mutable.
    sparse: Optional[Any] = None
    # 'int8' = int8 WEIGHT quantization for the four projection
    # matmuls (models/dense.py): kernels stored int8 with per-output-
    # channel scales (quantize_dense_params at load/convert time),
    # activations quantized per row in the forward, dot on the MXU
    # s8×s8→s32 path with in-kernel dequant — half the weight bytes a
    # bandwidth-bound decode step streams. Orthogonal to qk_quant
    # (which quantizes the SCORE operands).
    weight_quant: Optional[str] = None
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        if self.key_dim % self.num_heads:
            raise ValueError(
                f'key_dim {self.key_dim} must be divisible by num_heads '
                f'{self.num_heads} (reference module.py:29)')
        if self.softmax_impl not in ('full', 'online', 'flash', 'ulysses'):
            raise ValueError(
                f"softmax_impl must be 'full', 'online', 'flash' or "
                f"'ulysses', got {self.softmax_impl!r}")
        if self.impl not in ('allgather', 'ring'):
            raise ValueError(
                f"impl must be 'allgather' or 'ring', got {self.impl!r}")
        # Per-path knob support comes from the declarative matrix —
        # models/features.py is the single source of truth shared with the
        # README table and the matrix test. Knob-interaction rules
        # (features.INTERACTION_RULES) stay explicit below.
        if self.window is not None:
            if not isinstance(self.window, int) or self.window < 1:
                raise ValueError(
                    f'window must be a positive int, got {self.window!r}')
            if not self.causal:
                raise ValueError('window is a lookback cap and requires '
                                 'causal=True')
            features.check('window', self.softmax_impl)
        if self.dropout_rate:
            features.check('dropout_rate', self.softmax_impl)
        if self.alibi_slopes is not None:
            features.check('alibi_slopes', self.softmax_impl)
            if not self.causal:
                raise ValueError('alibi_slopes bias by relative global '
                                 'position and require causal=True')
        if self.qk_quant is not None:
            features.check('qk_quant', self.softmax_impl)
        if self.weight_quant not in (None, 'int8'):
            raise ValueError(f"weight_quant must be None or 'int8', "
                             f'got {self.weight_quant!r}')
        if self.decode_impl not in (None, 'auto', 'kernel', 'xla'):
            raise ValueError(f"decode_impl must be None, 'auto', "
                             f"'kernel' or 'xla', got "
                             f'{self.decode_impl!r}')
        if self.ring_layout == 'zigzag':
            features.check('ring_layout=zigzag', self.softmax_impl)
        if self.flash_softmax_mode == 'bounded':
            features.check('flash_softmax_mode=bounded', self.softmax_impl)
        value_dim = self.value_dim if self.value_dim is not None \
            else self.key_dim
        if value_dim % self.num_heads:
            # The reference only checks key_dim and fails later with an
            # opaque view() error; validate up front.
            raise ValueError(
                f'value_dim {value_dim} must be divisible by num_heads '
                f'{self.num_heads}')
        self.head_dim = self.key_dim // self.num_heads
        self._value_dim = value_dim
        kv_heads = (self.num_kv_heads if self.num_kv_heads is not None
                    else self.num_heads)
        if not 1 <= kv_heads <= self.num_heads \
                or self.num_heads % kv_heads:
            raise ValueError(
                f'num_kv_heads {kv_heads} must divide num_heads '
                f'{self.num_heads} (and lie in [1, num_heads])')
        if kv_heads != self.num_heads:
            features.check('num_kv_heads', self.softmax_impl)
        self._kv_heads = kv_heads
        if self.rope_layout not in ('half', 'interleaved'):
            raise ValueError(f"rope_layout must be 'half' or "
                             f"'interleaved', got {self.rope_layout!r}")
        if self.ring_cache is not None and not (
                self.window is not None and self.window <= self.ring_cache):
            raise ValueError(
                f'ring_cache {self.ring_cache} recycles rows: it needs a '
                f'window no larger, got {self.window!r}')
        if self.use_rope:
            features.check('use_rope', self.softmax_impl)
            if self.head_dim % 2:
                raise ValueError(
                    f'use_rope needs an even head dim, got {self.head_dim}')
        # OwnedDense, not nn.Dense: the projection dots request fp32
        # accumulation explicitly (and carry the int8 weight path) —
        # see models/dense.py for why flax Dense can't be linted.
        dense = lambda feat, name: OwnedDense(  # noqa: E731
            feat, use_bias=self.add_bias, name=name, dtype=self.dtype,
            param_dtype=self.param_dtype, weight_quant=self.weight_quant)
        # Same four projections as reference module.py:36-39. Under GQA
        # the queries/values projections (the gathered, softmax-table
        # side — standard attention's K/V under the module's K-first
        # convention, see the num_kv_heads field comment) emit only
        # kv_heads · head_dim features.
        self.keys_proj = dense(self.key_dim, 'keys')
        self.queries_proj = dense(kv_heads * self.head_dim, 'queries')
        self.values_proj = dense(
            kv_heads * (value_dim // self.num_heads), 'values')
        self.composition = dense(self.out_dim or value_dim, 'composition')
        if self.out_gate:
            self.gate_proj = dense(value_dim, 'gate')
        if self.qk_norm:
            ones = nn.initializers.ones_init()
            self.keys_norm = self.param('keys_norm', ones,
                                        (self.head_dim,), jnp.float32)
            self.queries_norm = self.param('queries_norm', ones,
                                           (self.head_dim,), jnp.float32)
        self._sparse = None
        if self.sparse is not None:
            refused = [name for name, on in (
                ('causal=False', not self.causal),
                ('window', self.window is not None),
                ('alibi_slopes', self.alibi_slopes is not None),
                ('qk_quant', self.qk_quant is not None),
                ('dropout_rate', bool(self.dropout_rate)),
                (f'softmax_impl={self.softmax_impl!r}',
                 self.softmax_impl != 'flash')) if on]
            if refused:
                raise ValueError(
                    f'sparse attention is causal flash attention over '
                    f'picked blocks; it does not take {refused}')
            self._sparse = SparseSpec(**dict(self.sparse))

    def _norm_heads(self, keys, queries):
        """``qk_norm``: the per-head RMSNorm of both score operands
        ``(…, heads, T, head_dim)``, statistics in float32."""
        if not self.qk_norm:
            return keys, queries

        def norm(x, scale):
            xf = x.astype(jnp.float32)
            xf = xf * jax.lax.rsqrt(
                jnp.mean(jnp.square(xf), -1, keepdims=True)
                + self.qk_norm_eps)
            return (xf * scale).astype(x.dtype)
        return norm(keys, self.keys_norm), norm(queries, self.queries_norm)

    def _sow_picks(self, picks, count):
        self.sow('counters', 'sparse_picks', picks,
                 reduce_fn=lambda _, new: new)
        self.sow('counters', 'sparse_count', count,
                 reduce_fn=lambda _, new: new)

    def _gate(self, keys):
        """The output gate of the UNPROJECTED ``keys`` input, float32
        ``(…, T, value_dim)``; None without ``out_gate``."""
        if not self.out_gate:
            return None
        return jax.nn.sigmoid(self.gate_proj(keys).astype(jnp.float32))

    def _compose(self, outputs, gate):
        """The output projection of the merged heads' ``outputs (…, T,
        value_dim)``, under ``gate`` where there is one."""
        if gate is not None:
            outputs = (outputs * gate).astype(outputs.dtype)
        return self.composition(outputs)

    @property
    def _scale(self):
        if self.softmax_scale is None:
            return 1.0 / math.sqrt(self.head_dim)
        return float(self.softmax_scale)

    def __call__(self, keys, queries, values, attn_mask=None,
                 segment_ids=None, deterministic=False,
                 dropout_seed=None):
        # Everything here that is not a kernel (or the sequence
        # all-gather, scoped further in) is 'lm.attn_proj' in a device
        # trace.
        with device_scope('lm.attn_proj'):
            # Named on every softmax path: a checkpoint that keeps it
            # rebuilds no output projection (LAYER_MATMUL_NAMES).
            return named(
                self._attend(keys, queries, values, attn_mask,
                             segment_ids, deterministic, dropout_seed),
                LAYER_MATMUL_NAMES[2])

    def _attend(self, keys, queries, values, attn_mask, segment_ids,
                deterministic, dropout_seed):
        # ``deterministic=True`` disables dropout (eval). ``dropout_seed``:
        # explicit traced int32 scalar for the in-kernel mask (e.g. the
        # step counter) — the SPMD-simplest source; omitted, the seed is
        # derived from the flax 'dropout' rng (pass
        # ``apply(..., rngs={'dropout': key})``).
        # ``segment_ids``: optional non-negative int ``(B, T/N)`` local
        # shard — the compact packed-sequence mask (positions in different
        # segments don't attend; equivalent to the dense
        # ``mask[i, j] = seg[i] != seg[j]`` but O(T), not O(T²)).
        # flash/ulysses apply it in-kernel with whole-block skipping;
        # full/online densify it into the boolean mask (those paths build
        # (T/N, T) score rows anyway). Composes with ``attn_mask`` and
        # ``causal`` as a union of maskings.
        # ``attn_mask=None`` means "no masking" — an extension over the
        # reference (whose example passes an all-False mask,
        # example.py:29). It matters at long context: the mask is the only
        # O(T²) input left on the flash/ulysses/ring paths, so dropping it
        # (or using causal=True, handled blockwise in-kernel) is what lets
        # one chip train at T in the hundreds of thousands.
        gate = self._gate(keys)
        keys = self.keys_proj(keys)
        queries = self.queries_proj(queries)
        values = self.values_proj(values)

        kv_group = self.num_heads // self._kv_heads
        if self.num_heads > 1:
            # (B, T/N, D) -> (B, H, T/N, dh); mask broadcasts over H
            # (reference module.py:47-58). Under GQA queries/values split
            # into their OWN (fewer) heads — the fused kernels consume the
            # grouped layout directly.
            def split(x, heads, dh):
                x = x.reshape(*x.shape[:-1], heads, dh)
                return jnp.swapaxes(x, -2, -3)
            keys = split(keys, self.num_heads, self.head_dim)
            queries = split(queries, self._kv_heads, self.head_dim)
            values = split(values, self._kv_heads,
                           self._value_dim // self.num_heads)
            if attn_mask is not None:
                attn_mask = attn_mask[..., None, :, :]
            keys, queries = self._norm_heads(keys, queries)
        elif self.qk_norm:
            raise ValueError('qk_norm norms a head: it needs num_heads > 1')

        # During flax init the body runs outside any shard_map (no mesh axis
        # bound), and parameter shapes don't depend on the comm pattern —
        # use the local math path so plain ``model.init(...)`` works.
        distributed = self.distributed and not self.is_initializing()

        softmax_impl = self.softmax_impl
        if softmax_impl == 'ulysses' and not (distributed
                                              and self.num_heads > 1):
            # No head axis to scatter (single head) or the local oracle
            # branch: the math is identical through the flash path — route
            # there instead of duplicating it.
            softmax_impl = 'flash'

        if self.use_rope:
            # Rotate BOTH score operands by their GLOBAL positions (the
            # rotation is orthogonal, so k_i·q_j then depends on i−j
            # only). Keys and queries are both time-sharded local shards
            # here — on every path — so one shard-offset (or zigzag
            # position vector) serves both; the flash path's query gather
            # happens AFTER rotation, reassembling exactly the full-array
            # rotation.
            tn = keys.shape[-2]
            if distributed:
                idx = jax.lax.axis_index(self.axis_name)
                world = jax.lax.psum(1, self.axis_name)
            else:
                idx, world = 0, 1
            if softmax_impl == 'online' and self.ring_layout == 'zigzag':
                pos = _layout_positions('zigzag', idx, world, tn)
            else:
                pos = idx * tn + jnp.arange(tn)
            keys = self._rope(keys, pos)
            queries = self._rope(queries, pos)

        if self._sparse is not None:
            if (distributed or attn_mask is not None
                    or segment_ids is not None or keys.ndim != 4):
                raise ValueError(
                    'sparse attention runs on one device, batched, with '
                    'no mask but its own: distributed=False, no '
                    'attn_mask, no segment_ids')
            outputs = self._sparse_forward(keys, queries, values)
            outputs = jnp.swapaxes(outputs, -3, -2)
            outputs = outputs.reshape(*outputs.shape[:-2], self._value_dim)
            return self._compose(outputs, gate)

        # Causal handling: ring/ulysses/flash take causal=True natively —
        # the kernels skip whole future blocks and need no materialized
        # triangle (the distributed flash kernel takes the shard's global
        # row offset as a scalar input). Only the 'full' parity path
        # densifies causality into the mask.
        native_causal = self.causal and softmax_impl in ('online', 'ulysses',
                                                         'flash')
        if self.causal and not native_causal:
            # Rows of the score block are this shard's GLOBAL positions
            # (idx·T/N + local row); columns are global already. In the
            # K-first convention scores[i, j] = k_i·q_j with softmax over
            # j, so "causal" is the same j <= i triangle.
            tn = keys.shape[-2]
            if distributed:
                idx = jax.lax.axis_index(self.axis_name)
                world = jax.lax.psum(1, self.axis_name)
            else:
                idx, world = 0, 1
            t_global = (attn_mask.shape[-1] if attn_mask is not None
                        else tn * world)
            rows = idx * tn + jnp.arange(tn)
            cols = jnp.arange(t_global)
            future = rows[:, None] < cols[None, :]
            if self.window is not None:
                future = jnp.logical_or(
                    future, rows[:, None] - cols[None, :] >= self.window)
            attn_mask = (future if attn_mask is None
                         else jnp.logical_or(attn_mask, future))

        seg_local = None
        if segment_ids is not None:
            seg_local = segment_ids.astype(jnp.int32)
            if softmax_impl == 'full':
                # The parity path materializes (T/N, T) rows regardless —
                # the compact form densifies into the boolean mask (rows =
                # this shard's positions, columns global). Every other
                # path consumes the O(T) vector form in-kernel.
                seg_full = seg_local
                if distributed:
                    with device_scope('lm.attn_gather'):
                        seg_full = jax.lax.all_gather(
                            seg_local, self.axis_name, axis=-1, tiled=True)
                dense = seg_local[..., :, None] != seg_full[..., None, :]
                if self.num_heads > 1:
                    dense = dense[..., None, :, :]
                attn_mask = (dense if attn_mask is None
                             else jnp.logical_or(attn_mask, dense))
                seg_local = None  # consumed

        drop_rate, drop_seed = 0.0, None
        if (self.dropout_rate and not deterministic
                and not self.is_initializing()):
            drop_rate = self.dropout_rate
            if dropout_seed is not None:
                # Per-layer salt: stacked layers sharing one explicit seed
                # (the step counter) would otherwise draw IDENTICAL
                # coordinate-hash masks — fold a hash of this module's
                # flax path in, so each layer instance decorrelates while
                # staying deterministic (the make_rng branch already
                # decorrelates per path).
                salt = zlib.crc32('/'.join(self.path).encode()) & 0x7fffffff
                drop_seed = jnp.bitwise_xor(
                    jnp.asarray(dropout_seed, jnp.int32), jnp.int32(salt))
            else:
                drop_seed = jax.random.randint(
                    self.make_rng('dropout'), (), 0,
                    jnp.iinfo(jnp.int32).max, dtype=jnp.int32)

        if softmax_impl == 'flash':
            # Fused-kernel path: the module's K-first scoring + softmax over
            # the gathered axis (reference module.py:61,67) is standard
            # attention with q := keys, k := queries, v := values.
            # Distributed, the *small* O(T·d) operands (queries, values) are
            # all-gathered — one tiled collective each — and the whole
            # score/mask/softmax/context chain runs as one Pallas kernel
            # with no (T/N, T) score materialization
            # (:mod:`..ops.pallas_attention`). Fully-masked rows give 0
            # (reference: NaN).
            scale = self._scale
            q_full, v_full = queries, values
            if distributed:
                with device_scope('lm.attn_gather'):
                    q_full = jax.lax.all_gather(
                        queries, self.axis_name, axis=queries.ndim - 2,
                        tiled=True)
                    v_full = jax.lax.all_gather(
                        values, self.axis_name, axis=values.ndim - 2,
                        tiled=True)
            # In the distributed K-first layout the kernel's query rows are
            # this shard's keys — global positions start at idx·T/N. Fed
            # whenever distributed: causal/windows need it, and the
            # dropout mask decorrelates shards through it (a dead scalar
            # read otherwise). On a 1-wide axis the offset is STATICALLY
            # zero — keeping it a Python int lets the causal kernel take
            # the trapezoid pair grid (static offsets only; see
            # ops.pallas_attention._trap_eligible).
            causal_offset = 0
            if distributed and jax.lax.psum(1, self.axis_name) > 1:
                causal_offset = (jax.lax.axis_index(self.axis_name)
                                 * keys.shape[-2])
            seg_pair = None
            if seg_local is not None:
                # K-first layout: the kernel's query rows are this shard's
                # keys (local segs), its key columns the gathered queries.
                seg_kv = seg_local
                if distributed:
                    with device_scope('lm.attn_gather'):
                        seg_kv = jax.lax.all_gather(
                            seg_local, self.axis_name, axis=-1, tiled=True)
                sq, sk = seg_local, seg_kv
                if self.num_heads > 1:
                    sq, sk = sq[..., None, :], sk[..., None, :]
                seg_pair = (sq, sk)
            note_named(FLASH_QKV_NAME, keys, q_full, v_full)
            outputs = flash_attention(keys, q_full, v_full, attn_mask,
                                      scale=scale, causal=native_causal,
                                      causal_offset=causal_offset,
                                      softmax_mode=self.flash_softmax_mode,
                                      segment_ids=seg_pair,
                                      window=(self.window if native_causal
                                              else None),
                                      alibi_slopes=self.alibi_slopes,
                                      qk_quant=self.qk_quant,
                                      dropout_rate=drop_rate,
                                      dropout_seed=drop_seed)
            if self.num_heads > 1:
                outputs = jnp.swapaxes(outputs, -3, -2)
                outputs = outputs.reshape(*outputs.shape[:-2],
                                          self._value_dim)
            return self._compose(outputs, gate)

        if softmax_impl == 'ulysses':
            # Head all-to-all path (distributed, num_heads > 1 guaranteed
            # by the resolution above): heads↔time re-sharding, then the
            # fused flash kernel locally over the FULL sequence for H/N
            # heads (see models/ulysses_attention.py). Same q:=keys
            # convention as the flash path.
            scale = self._scale
            outputs = ulysses_attention(
                keys, queries, values, attn_mask,
                axis_name=self.axis_name, scale=scale,
                causal=native_causal,
                softmax_mode=self.flash_softmax_mode,
                segment_ids=seg_local, window=self.window,
                alibi_slopes=self.alibi_slopes, qk_quant=self.qk_quant,
                dropout_rate=drop_rate, dropout_seed=drop_seed)
            outputs = jnp.swapaxes(outputs, -3, -2)
            outputs = outputs.reshape(*outputs.shape[:-2], self._value_dim)
            return self._compose(outputs, gate)

        if softmax_impl == 'online':
            # Long-context path: ring attention with online softmax — the
            # module's K-first scoring + softmax over the gathered axis
            # (reference module.py:61,67) is standard attention with
            # q := keys, k := queries (see ring_attention docstring), so no
            # (T/N, T) score block is ever materialized. Fully-masked rows
            # give 0 here (reference: NaN). Segments ride the ring as
            # O(T/N) vectors; dropout/ALiBi run in the per-fold kernels
            # over global coordinates.
            scale = self._scale
            seg_ring = seg_local
            if seg_ring is not None and self.num_heads > 1:
                seg_ring = seg_ring[..., None, :]
            if distributed:
                outputs = ring_attention(
                    keys, queries, values, attn_mask,
                    axis_name=self.axis_name, scale=scale,
                    causal=native_causal, layout=self.ring_layout,
                    window=self.window, segment_ids=seg_ring,
                    alibi_slopes=self.alibi_slopes,
                    qk_quant=self.qk_quant,
                    dropout_rate=drop_rate, dropout_seed=drop_seed)
            elif (seg_ring is not None or self.alibi_slopes is not None
                    or self.qk_quant is not None or drop_rate):
                # Local oracle with in-kernel features: the fused kernel
                # IS the local math for segments/ALiBi/dropout/int8 (the
                # plain einsum oracle has none of them); GQA is native
                # there too.
                note_named(FLASH_QKV_NAME, keys, queries, values)
                outputs = flash_attention(
                    keys, queries, values, attn_mask, scale=scale,
                    causal=native_causal, window=self.window,
                    segment_ids=(None if seg_ring is None
                                 else (seg_ring, seg_ring)),
                    alibi_slopes=self.alibi_slopes,
                    qk_quant=self.qk_quant,
                    dropout_rate=drop_rate, dropout_seed=drop_seed)
            else:
                q_loc, v_loc = queries, values
                if kv_group > 1:
                    q_loc = jnp.repeat(q_loc, kv_group, axis=-3)
                    v_loc = jnp.repeat(v_loc, kv_group, axis=-3)
                outputs = local_attention_reference(
                    keys, q_loc, v_loc, attn_mask, scale=scale,
                    causal=native_causal, window=self.window)
            if self.num_heads > 1:
                outputs = jnp.swapaxes(outputs, -3, -2)
                outputs = outputs.reshape(*outputs.shape[:-2],
                                          self._value_dim)
            return self._compose(outputs, gate)

        if kv_group > 1:
            # Parity path under GQA: repeat the grouped heads up to H —
            # this path materializes full (T/N, T) score rows anyway, so
            # the repeat costs nothing it wasn't already paying; the fused
            # paths consume the grouped layout natively.
            queries = jnp.repeat(queries, kv_group, axis=-3)
            values = jnp.repeat(values, kv_group, axis=-3)
        if distributed:
            scores = matmul_nt(keys, queries, self.offset,
                               axis_name=self.axis_name, impl=self.impl)
        else:
            scores = jnp.matmul(keys, jnp.swapaxes(queries, -1, -2))
        # K-first convention kept (reference module.py:60-62): row i of
        # `scores` is key_i against every query.
        if self.softmax_scale is None:
            scores = scores / math.sqrt(self.head_dim)
        else:
            scores = scores * self.softmax_scale
        if attn_mask is not None:
            big_neg = jnp.asarray(-jnp.inf, dtype=scores.dtype)
            scores = jnp.where(attn_mask, big_neg, scores)
        attn = jax.nn.softmax(scores, axis=-1)
        if distributed:
            outputs = matmul_all(attn, values, self.offset,
                                 axis_name=self.axis_name, impl=self.impl)
        else:
            outputs = jnp.matmul(attn, values)
        if self.num_heads > 1:
            outputs = jnp.swapaxes(outputs, -3, -2)
            outputs = outputs.reshape(*outputs.shape[:-2], self._value_dim)
        return self._compose(outputs, gate)

    def _sparse_forward(self, keys, queries, values):
        """A whole sequence from position 0 over its own picks: the
        pooled keys are taken from the sequence's keys."""
        spec = self._sparse
        t = keys.shape[-2]
        pad = (-t) % spec.block
        if pad:
            queries, values = (
                jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                for x in (queries, values))
        pooled = pool_rows(queries, spec)        # whole blocks: strides
        with device_scope('ops.sparse_prefill'):
            out, picks, count = sparse_attention(
                keys, queries, values, pooled, 0, spec, self._scale)
        self._sow_picks(picks, count)
        return out

    def make_decode_cache(self, batch, t_max, dtype=None):
        """A KV cache sized for this module's projections (GQA-aware:
        ``num_kv_heads`` heads of queries/values — the softmax-table side
        under the K-first convention). Plain Python (reads constructor
        fields only), so no ``apply`` is needed."""
        from distributed_dot_product_tpu.models.decode import (
            init_cache, init_packed_cache, init_ring_cache,
            init_sparse_cache,
        )
        kv_heads = (self.num_kv_heads if self.num_kv_heads is not None
                    else self.num_heads)
        value_dim = (self.value_dim if self.value_dim is not None
                     else self.key_dim)
        if self.kv_packed:
            head_dim = self.key_dim // self.num_heads
            if (value_dim != self.key_dim or (2 * head_dim) % 128
                    or self.qk_quant is not None or self.sparse is not None
                    or self.ring_cache is not None):
                raise ValueError(
                    f'kv_packed keeps keys and values of ONE width '
                    f'({head_dim} and {value_dim // self.num_heads} here) '
                    f'side by side in whole lane tiles, with no int8 '
                    f'mirror, ring or sparse cache')
            return init_packed_cache(
                batch, kv_heads, t_max, head_dim,
                dtype=dtype or self.dtype or jnp.float32)
        if self.sparse is not None:
            spec = SparseSpec(**dict(self.sparse))
            if t_max % spec.block:
                raise ValueError(f't_max {t_max} is not whole blocks of '
                                 f'{spec.block}')
            return init_sparse_cache(
                batch, kv_heads, t_max, self.key_dim // self.num_heads,
                spec.stride, v_head_dim=value_dim // self.num_heads,
                dtype=dtype or self.dtype or jnp.float32)
        if self.ring_cache is not None and self.ring_cache < t_max:
            if self.qk_quant is not None:
                raise ValueError('a ring cache carries no int8 mirror')
            return init_ring_cache(
                batch, kv_heads, self.ring_cache,
                self.key_dim // self.num_heads,
                v_head_dim=value_dim // self.num_heads,
                dtype=dtype or self.dtype or jnp.float32)
        return init_cache(
            batch, kv_heads, t_max, self.key_dim // self.num_heads,
            v_head_dim=value_dim // self.num_heads,
            dtype=dtype or self.dtype or jnp.float32,
            qk_quant=self.qk_quant)

    def _project_for_decode(self, keys, queries, values, length):
        """Shared front half of :meth:`prefill`/:meth:`decode`: the four
        projections, GQA head split, and RoPE at the true global
        positions ``length + arange(n)`` (``length`` the cache's) — ONE
        definition so the two inference entry points cannot drift. The
        fourth result is the output gate (:meth:`_gate`)."""
        if not self.causal:
            raise ValueError('cached decoding is autoregressive and '
                             'requires causal=True')
        gate = self._gate(keys)
        keys = self.keys_proj(keys)
        queries = self.queries_proj(queries)
        values = self.values_proj(values)
        n = keys.shape[-2]

        def split(x, heads, dh):
            x = x.reshape(*x.shape[:-1], heads, dh)
            return jnp.swapaxes(x, -2, -3)
        keys = split(keys, self.num_heads, self.head_dim)
        queries = split(queries, self._kv_heads, self.head_dim)
        values = split(values, self._kv_heads,
                       self._value_dim // self.num_heads)
        keys, queries = self._norm_heads(keys, queries)
        if self.use_rope:
            pos = length + jnp.arange(n)
            keys = self._rope(keys, pos)
            queries = self._rope(queries, pos)
        return keys, queries, values, gate

    def _rope(self, x, pos):
        if self.rope_layout == 'half':
            return rope(x, pos, base=self.rope_base)
        d = x.shape[-1]
        return rope_interleaved(
            x, pos, self.rope_base ** (
                -jnp.arange(0, d, 2, dtype=jnp.float32) / d))

    def _merge_decode_heads(self, out, gate):
        out = jnp.swapaxes(out, -3, -2)
        out = out.reshape(*out.shape[:-2], self._value_dim)
        return self._compose(out, gate)

    def prefill(self, keys, queries, values, cache, segment_ids=None,
                seg_cache=None):
        """Prompt ingestion for :meth:`decode`: project the ``n`` new
        positions, append the projected queries/values to the cache, and
        compute their outputs with the FLASH kernel over the whole cache
        buffer — the causal mask (rows at global positions
        ``cache.length + i`` vs buffer columns ``0..t_max``) excludes
        both the future prompt rows and the not-yet-filled tail, so the
        result equals the causal forward over the filled prefix with
        O(block²) score memory (``decode`` would materialize an
        ``(n, t_max)`` score buffer — fine for a few rows, not a
        131K-token prompt). Same knob coverage as ``decode``
        (GQA/RoPE/window/ALiBi/int8/segments). Packed multi-turn
        prompts: ``segment_ids (B, n)`` holds the prompt rows' ids,
        ``seg_cache (B, t_max)`` the cached positions' — which, as in
        ``decode``, must already carry the ids of the positions being
        appended (rows attend their own columns). Returns
        ``(cache, out)``."""
        from distributed_dot_product_tpu.models.decode import (
            PackedCache, RingCache, append_kv, packed_append,
            packed_views, ring_append, ring_window,
        )
        if self._sparse is not None:
            return self._sparse_prefill(keys, queries, values, cache,
                                        segment_ids)
        with device_scope('lm.attn_proj'):
            keys, queries, values, gate = self._project_for_decode(
                keys, queries, values, cache.length)
            if isinstance(cache, RingCache):
                # The chunk sees the ring's previous rows (< window of
                # them) and itself, laid out as a slab of its own; the
                # ring then keeps what it keeps of the chunk.
                if (segment_ids is not None or self.qk_quant is not None
                        or self.alibi_slopes is not None):
                    raise ValueError(
                        'a ring cache is prefilled with causal + window '
                        'masking alone: no segment_ids, qk_quant or '
                        'alibi_slopes')
                k_all, v_all, start = ring_window(cache, queries, values,
                                                  self.window)
                out = flash_attention(
                    keys, k_all, v_all, causal=True, causal_offset=start,
                    scale=self._scale,
                    window=self.window)
                return (ring_append(cache, queries, values),
                        self._merge_decode_heads(out, gate))
            start = cache.length
            if isinstance(cache, PackedCache):
                # The chunk over the slab's two halves as buffers of
                # their own: the flash forward at the head's own width.
                if (segment_ids is not None or self.qk_quant is not None):
                    raise ValueError('a packed cache is prefilled with no '
                                     'segment_ids and no qk_quant')
                cache = packed_append(cache, queries, values)
                k_all, v_all = packed_views(cache)
                out = flash_attention(
                    keys, k_all, v_all, causal=True, causal_offset=start,
                    scale=self._scale, window=self.window,
                    alibi_slopes=self.alibi_slopes)
                return cache, self._merge_decode_heads(out, gate)
            cache = append_kv(cache, queries, values)
            seg_pair = None
            if segment_ids is not None:
                if seg_cache is None:
                    raise ValueError(
                        'segment_ids needs seg_cache (the cached '
                        "positions' ids, shape (B, t_max))")
                sq = segment_ids.astype(jnp.int32)[..., None, :]
                sk = seg_cache.astype(jnp.int32)[..., None, :]
                seg_pair = (sq, sk)
            out = flash_attention(
                keys, cache.k, cache.v, causal=True, causal_offset=start,
                scale=self._scale, window=self.window,
                alibi_slopes=self.alibi_slopes, qk_quant=self.qk_quant,
                segment_ids=seg_pair)
            return cache, self._merge_decode_heads(out, gate)

    def _sparse_prefill(self, keys, queries, values, cache, segment_ids):
        """The sparse layer's chunk: the slab's append, the pooled rows
        the chunk completes, then every row over its own picks. The
        selection's and the attention's scopes are SIBLINGS of
        ``lm.attn_proj``, which stays the projections alone (the
        benchmark's accepted reader takes it for them)."""
        from distributed_dot_product_tpu.models.decode import (
            DecodeCache, append_kv,
        )
        if segment_ids is not None:
            raise ValueError('sparse attention takes no segment_ids')
        spec, start, n = self._sparse, cache.length, keys.shape[-2]
        with device_scope('lm.attn_proj'):
            keys, queries, values, gate = self._project_for_decode(
                keys, queries, values, start)
            slab = append_kv(DecodeCache(cache.k, cache.v, start), queries,
                             values)
        with device_scope('ops.sparse_select'):
            pooled = pooled_after_chunk(slab.k, cache.pooled, start, n,
                                        spec)
        with device_scope('ops.sparse_prefill'):
            out, picks, count = sparse_attention(
                keys, slab.k, slab.v, pooled, start, spec, self._scale)
        self._sow_picks(picks, count)
        cache = cache._replace(k=slab.k, v=slab.v, length=slab.length,
                               pooled=pooled)
        with device_scope('lm.attn_proj'):
            return cache, self._merge_decode_heads(out, gate)

    def _sparse_decode(self, keys, queries, values, cache):
        """The sparse layer's token: projections under ``lm.attn_proj``,
        then the selection and the kernel under their own scopes, its
        siblings (see :meth:`_sparse_prefill`)."""
        if keys.shape[-2] != 1:
            raise ValueError('the sparse step is one token')
        with device_scope('lm.attn_proj'):
            keys, queries, values, gate = self._project_for_decode(
                keys, queries, values, cache.length)
        cache, out, picks, count = sparse_step(
            keys, cache, queries, values, self._sparse, self._scale,
            impl=self.decode_impl)
        self._sow_picks(picks, count)
        with device_scope('lm.attn_proj'):
            return cache, self._merge_decode_heads(out, gate)

    def decode(self, keys, queries, values, cache, segment_ids=None,
               seg_cache=None, layer=None):
        """Incremental (KV-cache) inference step — the module-level
        surface over :mod:`distributed_dot_product_tpu.models.decode`.

        ``keys/queries/values (B, n, d·)`` are the NEW positions (n=1
        token-by-token; the prompt for prefill). Projections, GQA head
        grouping, RoPE (rotated at the true global positions
        ``cache.length + arange(n)``), sliding window and ALiBi all
        follow this module's training-time configuration, so a model
        trained through ``__call__(causal=True)`` decodes identically:
        under the K-first convention output row t is key_t attending
        queries/values at positions ≤ t — exactly the causal forward's
        row t. ``qk_quant='int8'`` carries over too (the decode path
        reproduces the kernels' per-row quantization), as do packed
        segments: pass this step's ``segment_ids (B, n)`` with the
        cached positions' ``seg_cache (B, t_max)``. Requires
        ``causal=True`` (autoregressive semantics); dropout is
        inference-off. This method runs on ONE device's cache
        (replicate or batch-shard for serving); when the serving
        context outgrows one chip's HBM, the sequence-SHARDED decode
        surface is :meth:`decode_sharded` (slab-sharded cache inside a
        ``shard_map``) with :func:`decode_seq_parallel` /
        :func:`make_decode_step` as the global-array wrappers. The
        append+attend pair runs as one fused step
        (:func:`~distributed_dot_product_tpu.models.decode.decode_step`;
        the ``decode_impl`` field selects the Pallas kernel vs the XLA
        formulation). ``layer`` (traced int32 scalar): ``cache`` is a
        layer-stacked cache (a scanned stack's) and this call is layer
        ``layer``'s step, addressed in place — see ``decode_step``. Use
        ``apply(params, k, q, v, cache, method='decode')``; returns
        ``(cache, out (B, n, value_dim))``.
        """
        from distributed_dot_product_tpu.models.decode import (
            decode_step,
        )
        if self._sparse is not None:
            if layer is not None or segment_ids is not None:
                raise ValueError('the sparse step is an unrolled stack\'s, '
                                 'with no segment_ids')
            return self._sparse_decode(keys, queries, values, cache)
        with device_scope('lm.attn_proj'):
            length = cache.length
            if layer is not None:
                length = jax.lax.dynamic_index_in_dim(
                    length, layer, keepdims=False)
            keys, queries, values, gate = self._project_for_decode(
                keys, queries, values, length)
            cache, out = decode_step(
                keys, cache, queries, values,
                scale=self._scale,
                window=self.window, alibi_slopes=self.alibi_slopes,
                qk_quant=self.qk_quant, segment_ids=seg_cache,
                seg_q=segment_ids, impl=self.decode_impl, layer=layer)
            return cache, self._merge_decode_heads(out, gate)

    def decode_sharded(self, keys, queries, values, cache,
                       segment_ids=None, seg_cache=None, axis_name=None):
        """Sequence-sharded :meth:`decode` (run inside a ``shard_map``;
        :func:`decode_seq_parallel` wraps global arrays): the KV cache
        is slab-sharded on its ``t_max`` axis across the mesh — serving
        context scales past one chip's HBM — with the new token's write
        landing on the owning shard and the softmax merged by the
        flash-decoding pmax/psum rule (see
        :func:`~distributed_dot_product_tpu.models.decode.decode_attention`).
        Inputs/projections are replicated; ``seg_cache`` (if used) is
        the slab's LOCAL ``(B, t_max/N)`` shard. Same knob coverage as
        ``decode``; bit-for-tolerance parity with it is pinned by
        tests/test_decode_sharded.py. On the kernel path
        (``decode_impl``) each shard runs the fused Pallas step over
        its slab (owner appends in place) and the shards merge by the
        flash-decoding pmax/psum rule."""
        from distributed_dot_product_tpu.models.decode import (
            decode_step,
        )
        ax = axis_name or self.axis_name
        with device_scope('lm.attn_proj'):
            keys, queries, values, gate = self._project_for_decode(
                keys, queries, values, cache.length)
            cache, out = decode_step(
                keys, cache, queries, values,
                scale=self._scale,
                window=self.window, alibi_slopes=self.alibi_slopes,
                qk_quant=self.qk_quant, segment_ids=seg_cache,
                seg_q=segment_ids, axis_name=ax, impl=self.decode_impl)
            return cache, self._merge_decode_heads(out, gate)


def apply_seq_parallel(module, params, mesh, keys, queries, values,
                       attn_mask=None, mesh_axis=None, segment_ids=None,
                       deterministic=False, dropout_seed=None, rngs=None):
    """Apply a :class:`DistributedDotProductAttn` to **global** arrays on a
    mesh: params replicated (``P()``), activations sharded on the time axis
    (``P(None, 'seq', None)``); an optional global ``(B, T)``
    ``segment_ids`` is sharded on time too.

    Dropout modules take their randomness either from ``dropout_seed``
    (a scalar, e.g. the step counter — replicated; the in-kernel mask
    decorrelates shards by global position) or from
    ``rngs={'dropout': key}`` (the key is replicated so every shard
    derives the same seed, then decorrelates the same way).

    Replaces the reference's launch convention where ``horovodrun`` starts N
    processes that each construct the module and feed it their shard
    (reference example.py:16-31).
    """
    mesh_axis = mesh_axis or module.axis_name
    act_spec = P(*([None] * (keys.ndim - 2) + [mesh_axis, None]))
    seg_spec = P(*([None] * (keys.ndim - 2) + [mesh_axis]))
    drop_key = None if rngs is None else rngs.get('dropout')

    def fn(p, k, q, v, m, seg, seed, dkey):
        r = None if dkey is None else {'dropout': dkey}
        return module.apply(p, k, q, v, m, segment_ids=seg,
                            deterministic=deterministic,
                            dropout_seed=seed, rngs=r)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), act_spec, act_spec, act_spec, act_spec, seg_spec,
                  P(), P()),
        out_specs=act_spec, check_vma=False,
    )(params, keys, queries, values, attn_mask, segment_ids,
      dropout_seed, drop_key)


def _decode_cache_spec(module, mesh_axis):
    """PartitionSpecs of the slab-sharded decode cache (``t_max`` axis
    over the mesh, global length replicated)."""
    from distributed_dot_product_tpu.models.decode import DecodeCache
    spec4 = P(None, None, mesh_axis, None)
    quant = module.qk_quant == 'int8'
    return DecodeCache(k=spec4, v=spec4, length=P(),
                       k_q=spec4 if quant else None,
                       k_scale=spec4 if quant else None)


def make_decode_step(module, mesh, mesh_axis=None, donate=True):
    """Build the sequence-sharded decode step ONCE for a serving loop:
    ``step(params, keys, queries, values, cache) -> (cache, out)`` with
    the KV cache slab-sharded on its ``t_max`` axis over the mesh and —
    ``donate=True`` — DONATED to the jitted step, so the append's
    ``dynamic_update_slice`` writes the slab in place (without
    donation each token copies the full K/V slabs first). Reuse the
    returned step across tokens; rebuilding it per token would re-trace
    the whole module apply each time. The step routes through the fused
    decode path (``module.decode_impl``): on the kernel path each
    shard's append+attend is one Pallas program with the slab aliased
    in place — donation then means the slab is NEVER copied, not even
    once per step."""
    mesh_axis = mesh_axis or module.axis_name
    cache_spec = _decode_cache_spec(module, mesh_axis)

    def fn(p, k, q, v, c):
        return module.apply(p, k, q, v, c, method='decode_sharded',
                            axis_name=mesh_axis)

    step = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(), P(), P(), cache_spec),
        out_specs=(cache_spec, P()), check_vma=False)
    # Retrace sentinel (utils/retrace.py): a per-token serving loop
    # holds ONE of these steps, so more than budget traces of a single
    # instance is the round-5 retrace-storm class — raise (under
    # pytest / when enabled) instead of silently re-compiling. Budget 2:
    # one real trace plus one weak-type/lowering respin.
    step = watch_traces(step, name='attention.make_decode_step',
                        budget=2)
    return jax.jit(step, donate_argnums=(4,) if donate else ())


# Compiled decode steps keyed by (module, mesh, axis). BOUNDED: a
# serving host cycling many module/mesh configurations would otherwise
# grow this forever (each entry pins a compiled executable); least-
# recently-used entries are evicted past the cap — eviction only costs
# a re-trace on revisit, never correctness.
_DECODE_STEPS = OrderedDict()
_DECODE_STEPS_CAP = 16
_WARNED_UNHASHABLE = False


def decode_seq_parallel(module, params, mesh, keys, queries, values,
                        cache, mesh_axis=None):
    """One sequence-sharded decode step on **global** arrays: the KV
    cache is slab-sharded on its ``t_max`` axis over the mesh (build it
    with ``module.make_decode_cache(batch, t_max_global)`` and let this
    wrapper shard it), the new token's operands and the output are
    replicated. Returns ``(cache, out)`` with the cache still sharded —
    feed it straight back in for the next token (the input cache is
    DONATED: the slab append writes in place). Serving memory then
    scales linearly with mesh size (the slab per chip is ``t_max/N``),
    which is the whole point: one chip's HBM stops bounding the serving
    context.

    The compiled step is cached per ``(module, mesh, axis)`` — LRU-
    bounded to ``_DECODE_STEPS_CAP`` entries — so a per-token loop
    traces once. A module with an unhashable field (e.g. array ALiBi
    slopes) cannot be cached: that silently rebuilds AND re-traces the
    whole step EVERY token, so it warns once — pass hashable slopes
    (a tuple) or hold the step from :func:`make_decode_step` yourself.

    This wrapper shards a contiguous SLAB cache; the paged serving twin
    is ``KernelEngine(cache_mode='paged', kv_shards=N)``, which shards
    the page *table* over the same ``seq`` axis (contiguous page-
    ordinal ownership per member, per-shard flash partials psum/pmax-
    merged — see ``models.decode.ShardedPageTable``) and keeps paging's
    admission/eviction/prefix-sharing semantics at pooled-HBM context
    lengths."""
    global _WARNED_UNHASHABLE
    key = (module, mesh, mesh_axis)
    try:
        step = _DECODE_STEPS.get(key)
        if step is None:
            step = _DECODE_STEPS[key] = make_decode_step(
                module, mesh, mesh_axis)
        else:
            _DECODE_STEPS.move_to_end(key)
        while len(_DECODE_STEPS) > _DECODE_STEPS_CAP:
            _DECODE_STEPS.popitem(last=False)
    except TypeError:   # unhashable module field (e.g. array slopes)
        if not _WARNED_UNHASHABLE:
            _WARNED_UNHASHABLE = True
            warnings.warn(
                'decode_seq_parallel: module is unhashable (an array-'
                'valued field such as alibi_slopes?) — the compiled '
                'decode step cannot be cached and EVERY token will '
                're-trace and re-jit the full module apply. Use a '
                'hashable field (e.g. a tuple of slopes) or build the '
                'step once with make_decode_step.', stacklevel=2)
        step = make_decode_step(module, mesh, mesh_axis)
    # Shard the cache BEFORE the call (free once it is the step's own
    # output): to jit, a fresh single-device cache and the mesh-sharded
    # one the step returns are different signatures, and the loop would
    # trace and compile the step a second time at token two.
    cache = jax.device_put(cache, jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        _decode_cache_spec(module, mesh_axis or module.axis_name)))
    return step(params, keys, queries, values, cache)
