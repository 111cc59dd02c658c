# -*- coding: utf-8 -*-
"""
Multi-head latent attention (MLA, DeepSeek-V2/V3) and its cache.

The layer keeps ONE compressed row a token for all heads,
``[c_kv ; k_rope]`` (``kv_rank`` latent values after their RMSNorm, then
the ``rope_dim`` rotated key values every head shares), and reads both K
and V from it:

    c_q = RMSNorm(x W_qa)                 [q_nope_h ; q_rope_h] = c_q W_qb
    [c_kv ; k_r] = x W_kva                c_kv = RMSNorm(c_kv)
    [k_nope_h ; v_h] = c_kv W_kvb,h       k_rope = RoPE(k_r)
    score_h(t, s) = (q_nope_h(t)·k_nope_h(s) + RoPE(q_rope_h)(t)·k_rope(s)) · scale

Two forms of the same arithmetic:

- **expanded** (``__call__`` and ``prefill``): ``W_kvb`` expands the
  cached rows to per-head K ``(nope + rope)`` and V, and the flash
  forward kernel runs over them. Many query rows a key row: the
  expansion is paid once a chunk.
- **absorbed** (``decode``): ``W_kvb`` is folded into the query
  (``q~_h = q_nope_h W_kvb,h^K^T``) and out of the context
  (``out_h = (sum_s p c_kv(s)) W_kvb,h^V``), so a step reads the cache
  row itself, once, for all heads: ``flash_decode``'s latent mode
  (``ops.mla_decode``), or the XLA formulation off the chip.

Two switches cover the layer as ``bailing_hybrid`` (Ling 3.0) writes it:
``q_rank=None`` has no query rank (``q = x W_q``, one matrix, no
``q_a`` / ``q_norm``), and ``out_gate='head'`` multiplies each head's
context by ``sigmoid(x W_g)_h`` (``W_g (dim, H)``, from the layer's
normed input) before ``W_o`` — in the absorbed form AFTER ``W_kvb``'s V
half, where the expanded form has it.

The cache (:class:`LatentCache`) of a stack of latent layers alone is
one layer-stacked buffer ``(L, B, row_dim, t_max)`` with per-layer,
per-session lengths. It rides the layer loop's CARRY in prefill and in
decode alike and every layer writes its own columns of it in place by
index, so no layer is sliced out or written back. A latent layer among
layers of other kinds keeps ONE LAYER'S buffer ``(B, row_dim, t_max)``
with lengths ``(B,)`` beside their caches (``layer=None`` at every
entry point). The buffer is TIME-MINOR — a token is a column of
``row_dim = kv_rank + rope_dim`` values (576), nothing padded: 576 is no
multiple of the 128-lane tile, so a ``(…, t_max, 576)`` array the chip
lays out with time minor anyway (a row-block kernel then pays a
cache-sized relayout a layer a token: AOT for v5e, PR 26), and rows
padded to 640 make a ninth of every byte a decode step streams a zero
(until PR 47). As stored, ``row_dim`` is 36 whole sublane tiles of
bfloat16 and a K split of the decode kernel whole lane tiles; prefill
writes a chunk transposed and ``_expanded`` reads the latent with the
rank axis major.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.decode import (
    LatentCache, _take_layer, insert_session, record_decode_impl,
)
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.ops.pallas_attention import (
    flash_attention,
)
from distributed_dot_product_tpu.ops.pallas_decode import (
    flash_decode, flash_decode_geometry,
)
from distributed_dot_product_tpu.ops.rope import (
    rope_interleaved, yarn_inv_freq,
)
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['LatentCache', 'init_latent_cache', 'insert_session',
           'LatentAttention']

LANES = 128
HEAD_GROUP = 8


def init_latent_cache(layers, batch, t_max, row_dim, dtype=jnp.bfloat16):
    """A zero :class:`LatentCache`; ``layers=None``: one layer's."""
    lead = () if layers is None else (layers,)
    return LatentCache(
        rows=jnp.zeros((*lead, batch, row_dim, t_max), dtype),
        length=jnp.zeros((*lead, batch), jnp.int32))


class LatentAttention(nn.Module):
    """One MLA layer on ``x (B, T, dim)``. ``rope_scaling``: None, or
    the YaRN settings as a tuple of ``(key, value)`` pairs (hashable, so
    a model that carries them still keys the program caches):
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``. The softmax scale is
    ``(nope + rope)^-1/2 · m²`` with ``m = 0.1·mscale_all_dim·ln(factor)
    + 1`` and the rotation's own magnitude is
    ``yarn_mscale(mscale) / yarn_mscale(mscale_all_dim)`` (DeepSeek-V3's
    reading; both 1 where the two are equal).

    ``q_rank=None``: no query rank, ``q = x W_q`` (the subtree ``q``;
    no ``q_a`` / ``q_norm`` / ``q_b``). ``out_gate``: None, or ``'head'``
    — each head's context times ``sigmoid(x W_g)_h`` before ``W_o`` (the
    subtree ``gate``, ``(dim, H)``).

    ``decode_impl``: ``'auto'`` (the kernel on a TPU where the cache's
    ``t_max`` has a K split, else XLA), ``'kernel'``, ``'xla'``."""
    dim: int
    num_heads: int
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None
    decode_impl: str = 'auto'
    out_gate: Optional[str] = None

    def setup(self):
        h = self.num_heads
        dense = dict(use_bias=False, dtype=self.dtype)
        if self.out_gate not in (None, 'head'):
            raise ValueError(f"out_gate must be None or 'head', got "
                             f'{self.out_gate!r}')
        if self.q_rank is None:
            self.q = OwnedDense(h * (self.nope_dim + self.rope_dim),
                                name='q', **dense)
        else:
            self.q_a = OwnedDense(self.q_rank, name='q_a', **dense)
            self.q_norm = nn.RMSNorm(epsilon=self.norm_eps,
                                     dtype=self.dtype, name='q_norm')
            self.q_b = OwnedDense(h * (self.nope_dim + self.rope_dim),
                                  name='q_b', **dense)
        if self.out_gate:
            self.gate = OwnedDense(h, name='gate', **dense)
        self.kv_a = OwnedDense(self.kv_rank + self.rope_dim, name='kv_a',
                               **dense)
        self.kv_norm = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                                  name='kv_norm')
        # Held as a plain (kv_rank, H, nope + v) array: decode folds its
        # two halves into the query and out of the context.
        self.kv_b = self.param(
            'kv_b', nn.initializers.lecun_normal(in_axis=0,
                                                 out_axis=(1, 2)),
            (self.kv_rank, h, self.nope_dim + self.v_dim), jnp.float32)
        self.out = OwnedDense(self.dim, name='out', **dense)

    # -- static arithmetic ------------------------------------------------

    @property
    def row_dim(self):
        return self.kv_rank + self.rope_dim

    def _yarn(self):
        return dict(self.rope_scaling or ())

    def softmax_scale(self):
        y = self._yarn()
        m = 1.0
        if y.get('factor', 1.0) > 1.0:
            m = (0.1 * y.get('mscale_all_dim', 0.0)
                 * math.log(y['factor']) + 1.0)
        return m * m / math.sqrt(self.nope_dim + self.rope_dim)

    def _rotate(self, x, positions):
        y = self._yarn()
        factor = y.get('factor', 1.0)
        inv = yarn_inv_freq(
            self.rope_dim, base=self.rope_theta, factor=factor,
            original_max=y.get('original_max_position_embeddings', 4096),
            beta_fast=y.get('beta_fast', 32), beta_slow=y.get('beta_slow', 1))

        def mscale(s):
            return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0
        mag = mscale(y.get('mscale', 1.0)) / mscale(
            y.get('mscale_all_dim', 0.0))
        out = rope_interleaved(x, positions, inv)
        return out if mag == 1.0 else (out * mag).astype(x.dtype)

    def make_cache(self, layers, batch, t_max, dtype=None):
        """``layers=None``: one layer's buffer (a mixed stack's)."""
        return init_latent_cache(layers, batch, t_max, self.row_dim,
                                 dtype or self.dtype or jnp.float32)

    # -- the two halves every entry point shares --------------------------

    def _queries(self, x, positions):
        """``q_nope (B, H, T, nope)``, rotated ``q_rope (B, H, T, rope)``
        for ``x (B, T, dim)`` at ``positions (B, T)``."""
        b, t, _ = x.shape
        q = (self.q(x) if self.q_rank is None
             else self.q_b(self.q_norm(self.q_a(x))))
        q = q.reshape(b, t, self.num_heads, self.nope_dim + self.rope_dim)
        q = jnp.swapaxes(q, 1, 2)
        q_rope = self._rotate(q[..., self.nope_dim:],
                              positions[:, None, :])
        return q[..., :self.nope_dim], q_rope

    def _rows(self, x, positions):
        """The tokens' cache rows ``(B, T, row_dim)``: normalised
        latent, rotated shared key (a cache holds them as columns)."""
        ckv = self.kv_a(x)
        c = self.kv_norm(ckv[..., :self.kv_rank])
        k_rope = self._rotate(ckv[..., self.kv_rank:], positions)
        return jnp.concatenate([c, k_rope.astype(c.dtype)], axis=-1)

    def _kv_b(self, dtype):
        w = self.kv_b.astype(dtype)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def _gated(self, ctx, x):
        """The heads' contexts ``ctx (B, T, H, v)`` under the head-wise
        output gate of the layer's input ``x (B, T, dim)``."""
        if not self.out_gate:
            return ctx
        gate = nn.sigmoid(self.gate(x).astype(jnp.float32))
        return (ctx * gate[..., None]).astype(ctx.dtype)

    def _expanded(self, q_nope, q_rope, rows, offset, x):
        """Causal attention of the query rows (global positions
        ``offset + i``) over the keys and values ``W_kvb`` expands
        the columns ``rows (B, row_dim, S)`` to, through the flash forward
        kernel, gated by the layer's input ``x`` (``out_gate``);
        returns ``(B, T, dim)``. The heads go through
        ``HEAD_GROUP`` at a time, so the expanded keys and values that
        exist at once are a group's (a 32k-row session's are 0.8 GB for
        32 heads)."""
        b, h, t, _ = q_nope.shape
        g = HEAD_GROUP if h % HEAD_GROUP == 0 else h
        wk, wv = self._kv_b(rows.dtype)
        c = rows[:, :self.kv_rank]
        s_len = rows.shape[2]
        # K and q are built at the lane-tile width the flash kernel
        # would pad them to anyway (192 -> 256, the tail zeros), so the
        # expanded keys exist once, not unpadded and padded.
        tail = -(self.nope_dim + self.rope_dim) % LANES
        k_rope = jnp.swapaxes(rows[:, self.kv_rank:], 1, 2)
        k_tail = jnp.concatenate(
            [jnp.broadcast_to(k_rope[:, None],
                              (b, g, s_len, self.rope_dim)),
             jnp.zeros((b, g, s_len, tail), rows.dtype)], axis=-1)
        q = jnp.concatenate(
            [q_nope, q_rope, jnp.zeros((b, h, t, tail), q_nope.dtype)],
            axis=-1)

        def group(args):
            q_g, wk_g, wv_g = args
            k_nope = jnp.einsum('bcs,chd->bhsd', c, wk_g,
                                preferred_element_type=jnp.float32
                                ).astype(rows.dtype)
            v = jnp.einsum('bcs,chd->bhsd', c, wv_g,
                           preferred_element_type=jnp.float32
                           ).astype(rows.dtype)
            return flash_attention(
                q_g, jnp.concatenate([k_nope, k_tail], axis=-1), v,
                causal=True, causal_offset=offset,
                scale=self.softmax_scale())

        def by_group(x, axis):
            x = x.reshape(*x.shape[:axis], h // g, g, *x.shape[axis + 1:])
            return jnp.moveaxis(x, axis, 0)
        if g == h:
            out = group((q, wk, wv))
        else:
            out = lax.map(group, (by_group(q, 1), by_group(wk, 1),
                                  by_group(wv, 1)))
            out = jnp.moveaxis(out, 0, 1).reshape(b, h, t, self.v_dim)
        out = self._gated(jnp.swapaxes(out, 1, 2), x)
        return self.out(out.reshape(b, t, h * self.v_dim))

    # -- entry points -------------------------------------------------------

    def __call__(self, x):
        with device_scope('lm.attn_proj'):
            b, t, _ = x.shape
            pos = jnp.broadcast_to(jnp.arange(t), (b, t))
            q_nope, q_rope = self._queries(x, pos)
            return self._expanded(q_nope, q_rope,
                                  jnp.swapaxes(self._rows(x, pos), 1, 2),
                                  0, x)

    def prefill(self, x, cache: LatentCache, layer=None):
        """Append the chunk ``x (B, n, dim)`` to layer ``layer`` of the
        stacked cache (``layer=None``: to the one layer's buffer
        ``cache`` is), in place, and attend it over the rows held. The
        sessions of one call share a length (one causal offset a kernel
        call), as the slab caches' do."""
        with device_scope('lm.attn_proj'):
            b, n, _ = x.shape
            start = _take_layer(cache.length, layer)[0]
            pos = jnp.broadcast_to(start + jnp.arange(n), (b, n))
            q_nope, q_rope = self._queries(x, pos)
            new = jnp.swapaxes(self._rows(x, pos), 1, 2)
            zero = jnp.zeros((), jnp.int32)
            new = new.astype(cache.rows.dtype)
            if layer is None:
                rows = lax.dynamic_update_slice(cache.rows, new,
                                                (zero, zero, start))
            else:
                layer = jnp.asarray(layer, jnp.int32)
                rows = lax.dynamic_update_slice(
                    cache.rows, new[None], (layer, zero, zero, start))
            cache = LatentCache(rows=rows,
                                length=self._advanced(cache, layer, n))
            out = self._expanded(q_nope, q_rope, _take_layer(rows, layer),
                                 start, x)
            return cache, out

    @staticmethod
    def _advanced(cache, layer, n):
        """The lengths after ``n`` more rows of layer ``layer``."""
        if layer is None:
            return cache.length + n
        return cache.length.at[layer].add(n)

    def decode(self, x, cache: LatentCache, layer=None):
        """One token a session, ``x (B, 1, dim)``: the absorbed form
        over layer ``layer`` of the stacked cache (``layer=None``: over
        the one layer's buffer), appended in place."""
        with device_scope('lm.attn_proj'):
            b = x.shape[0]
            length = _take_layer(cache.length, layer)
            q_nope, q_rope = self._queries(x, length[:, None])
            new = self._rows(x, length[:, None])[:, 0].astype(
                cache.rows.dtype)
            wk, wv = self._kv_b(x.dtype)
            q_lat = jnp.einsum('bhd,chd->bhc', q_nope[:, :, 0], wk,
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)
            q = jnp.concatenate([q_lat, q_rope[:, :, 0]], axis=-1)
            # The kernel's view: one KV "head", one query row a session,
            # the new row as a lane tile of identical columns.
            shape = cache.rows.shape
            q4 = q[:, :, None]
            rows4 = cache.rows.reshape(*shape[:-2], 1, *shape[-2:])
            impl = self._resolve(q4, rows4, layer)
            if impl == 'kernel':
                tile = jnp.broadcast_to(new[:, None, :, None],
                                        (b, 1, self.row_dim, LANES))
        if impl == 'kernel':
            ctx, rows, *_ = flash_decode(
                q4, tile, None, rows4, None, length, length,
                layer=layer, latent_v=self.kv_rank,
                scale=self.softmax_scale())
            ctx, rows = ctx[:, :, 0], rows.reshape(shape)
        else:
            with device_scope('lm.attn_proj'):
                at = (jnp.arange(b), slice(None), length)
                rows = cache.rows.at[at if layer is None
                                     else (layer, *at)].set(new, mode='drop')
                held = _take_layer(rows, layer)
                s = jnp.einsum('bhc,bcs->bhs', q, held,
                               preferred_element_type=jnp.float32)
                seen = jnp.arange(held.shape[2]) <= length[:, None, None]
                p = jax.nn.softmax(
                    jnp.where(seen, s * self.softmax_scale(), -jnp.inf),
                    axis=-1)
                ctx = jnp.einsum('bhs,bcs->bhc', p.astype(held.dtype),
                                 held[:, :self.kv_rank],
                                 preferred_element_type=jnp.float32
                                 ).astype(x.dtype)
        with device_scope('lm.attn_proj'):
            out = jnp.einsum('bhc,chd->bhd', ctx.astype(x.dtype), wv,
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
            if self.out_gate:
                out = self._gated(out[:, None], x)
            out = self.out(out.reshape(b, 1, self.num_heads * self.v_dim))
            return LatentCache(rows=rows, length=self._advanced(
                cache, layer, 1)), out

    def _resolve(self, q, rows, layer):
        """``decode_impl`` for the kernel operands ``q (B, H, 1, d)`` and
        ``rows ([L,] B, 1, d, t_max)``, recorded for
        ``decode_impl_traces()`` with the grid step the kernel takes and
        the cache it was on: ``'stacked'``, or ``'latent'`` for one
        layer's buffer."""
        impl, reason = self.decode_impl, None
        if impl not in ('auto', 'kernel', 'xla'):
            raise ValueError(f"decode_impl must be 'auto', 'kernel' or "
                             f"'xla', got {impl!r}")
        t_max = rows.shape[-1]
        geom = flash_decode_geometry(q, rows, latent_v=self.kv_rank)
        if impl == 'kernel' and geom is None:
            raise ValueError(f'the latent decode kernel has no K split '
                             f'for t_max={t_max}')
        resolved = impl
        if impl == 'auto':
            resolved = 'kernel'
            if geom is None:
                resolved, reason = 'xla', f'no K split for {t_max}'
            elif jax.default_backend() != 'tpu':
                resolved = 'xla'
                reason = f'backend is {jax.default_backend()}, not tpu'
        record_decode_impl(impl, resolved, reason,
                           'latent' if layer is None else 'stacked',
                           geom if resolved == 'kernel' else None)
        return resolved
