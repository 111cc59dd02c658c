# -*- coding: utf-8 -*-
"""
A Lightning linear-attention mixer (Lightning Attention-2, Qin et al.
2024; the ``lightning-attn`` layers of ``minicpm_sala``): the plain
ADDITIVE recurrence with a constant decay a head, whose memory of the
sequence is a FIXED ``(head_dim, head_dim)`` float32 state a head — and
the first recurrent layer here that needs a POSITION. On the normed
stream ``h (…, T, dim)`` with ``inner = heads · head_dim``:

    [q | k | v | g] = h W_in                       (inner each)
    q = rope(RMSNorm_head(q));  k = rope(RMSNorm_head(k))    at the
                                token's position, theta ``rope_base``
    S_t = λ_head S_{t-1} + k_t v_tᵀ       λ_head = exp(−2^(−8 (head + 1) / heads))
    o_t = S_tᵀ q_t · head_dim^-1/2
    out = (RMSNorm(o_t) ⊙ sigmoid(g)) W_out        the norm over all heads

No convolution, no activation on q / k / v, no data-dependent decay.
The recurrence is ``models/ssm``'s with ``dt = 1``, one group a head
and ``P = N = head_dim``: ``decode`` is :func:`~distributed_dot_product_
tpu.models.ssm.state_step` (the state read once and written once, one
XLA fusion), ``__call__`` and ``prefill`` :func:`~distributed_dot_
product_tpu.models.ssm.chunked_scan`. Three entry points over one set of
parameters, as :class:`~distributed_dot_product_tpu.models.ssm.
Mamba2Mixer` has them; the cache is a :class:`StateCache` whose
``conv`` has no rows.

THE POSITION is an argument, not a part of the cache: ``prefill`` and
``decode`` take ``position`` (a scalar, the position of the first new
token — the stack hands every recurrent mixer its attention layers'
length), so a serving loop that restores the state and sets the slab's
length back has rewound the rotation with them. ``__call__`` starts at
position 0.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.decode import StateCache
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.models.ssm import chunked_scan, state_step
from distributed_dot_product_tpu.ops.rope import rope
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['LightningMixer', 'lightning_log_decay']


def lightning_log_decay(heads):
    """``log λ_head = −2^(−8 (head + 1) / heads)``, ``(heads,)`` float32:
    Lightning Attention-2's ALiBi-like slopes."""
    return -jnp.exp2(-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                     / heads)


class LightningMixer(nn.Module):
    """The mixer of the module docstring. ``dim`` is the stream's width;
    ``heads x head_dim`` the inner width; ``chunk`` the chunked form's
    chunk; ``use_rope`` / ``rope_base`` the rotation of q and k."""
    dim: int
    heads: int
    head_dim: int
    chunk: int = 128
    use_rope: bool = True
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None
    state_dtype: Any = jnp.float32

    @property
    def inner(self):
        return self.heads * self.head_dim

    def make_cache(self, batch, dtype=None):
        """A zero :class:`StateCache` for ``batch`` sessions — plain
        field arithmetic, no ``apply``. There is no convolution: the
        window has no rows."""
        return StateCache(
            state=jnp.zeros((batch, self.heads, self.head_dim,
                             self.head_dim), self.state_dtype),
            conv=jnp.zeros((batch, 0, self.inner),
                           dtype or self.dtype or jnp.float32))

    def setup(self):
        dense = dict(use_bias=False, dtype=self.dtype)
        self.in_proj = OwnedDense(4 * self.inner, name='in_proj', **dense)
        self.out_proj = OwnedDense(self.dim, name='out_proj', **dense)
        ones = nn.initializers.ones_init()
        self.q_norm = self.param('q_norm', ones, (self.head_dim,),
                                 jnp.float32)
        self.k_norm = self.param('k_norm', ones, (self.head_dim,),
                                 jnp.float32)
        self.norm_scale = self.param('norm_scale', ones, (self.inner,),
                                     jnp.float32)

    def _split(self, h, position):
        """The input projection, the per-head norms and the rotation at
        ``position + arange(n)``: ``q`` (scaled), ``k``, ``v (B, n, H,
        head_dim)`` in the stream's type and the gate's input ``g (B, n,
        inner)``."""
        q, k, v, g = jnp.split(self.in_proj(h), 4, axis=-1)
        lead = h.shape[:2] + (self.heads, self.head_dim)

        def normed(x, scale):
            xf = x.reshape(lead).astype(jnp.float32)
            return xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1,
                                           keepdims=True)
                                  + self.norm_eps) * scale
        q, k = normed(q, self.q_norm), normed(k, self.k_norm)
        if self.use_rope:
            if position is None:
                raise ValueError(
                    'a Lightning mixer rotates q and k: prefill and '
                    'decode need the position of the first new token')
            pos = position + jnp.arange(h.shape[1])
            # rope takes (…, T, d): heads before time.
            q, k = (jnp.swapaxes(rope(jnp.swapaxes(x, 1, 2), pos,
                                      base=self.rope_base), 1, 2)
                    for x in (q, k))
        q = q * (1.0 / math.sqrt(self.head_dim))
        return (q.astype(h.dtype), k.astype(h.dtype), v.reshape(lead), g)

    def _out(self, o, g):
        """The output norm under the sigmoid gate and the output
        projection: ``o (B, n, H, head_dim)`` float32, ``g (B, n,
        inner)``."""
        o = o.reshape(g.shape)
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + self.norm_eps) * self.norm_scale
        o = o * nn.sigmoid(g.astype(jnp.float32))
        return self.out_proj(o.astype(g.dtype))

    def _decay(self, lead):
        return jnp.broadcast_to(lightning_log_decay(self.heads),
                                lead + (self.heads,))

    def _chunk(self, h, cache, position):
        with device_scope('lm.lightning_proj'):
            q, k, v, g = self._split(h, position)
        with device_scope('ops.lightning_scan'):
            log_a = self._decay(h.shape[:2])
            o, state = chunked_scan(
                v, jnp.ones_like(log_a), log_a, k, q,
                cache.state.astype(jnp.float32), self.chunk)
        with device_scope('lm.lightning_proj'):
            out = self._out(o, g)
        return cache._replace(state=state.astype(cache.state.dtype)), out

    def __call__(self, h):
        return self._chunk(h, self.make_cache(h.shape[0], h.dtype), 0)[1]

    def prefill(self, h, cache, position=None):
        """``h (B, n, dim)`` continuing ``cache`` from ``position``:
        ``(cache, out)``."""
        return self._chunk(h, cache, position)

    def decode(self, h, cache, position=None):
        """One token ``h (B, 1, dim)`` at ``position``: ``(cache,
        out)``."""
        with device_scope('lm.lightning_proj'):
            q, k, v, g = self._split(h, position)
        with device_scope('ops.lightning_step'):
            log_a = self._decay(h.shape[:1])
            o, state = state_step(
                v[:, 0], jnp.ones_like(log_a), log_a, k[:, 0], q[:, 0],
                cache.state.astype(jnp.float32))
        with device_scope('lm.lightning_proj'):
            out = self._out(o[:, None], g)
        return cache._replace(state=state.astype(cache.state.dtype)), out
