# -*- coding: utf-8 -*-
"""
A Mamba-2 mixer: a selective state-space layer whose memory of the
sequence is a FIXED recurrent state (``nemotron_h``'s ``M`` layers; Dao &
Gu 2024, "Transformers are SSMs"). On the normed stream ``h (…, T, dim)``
with ``d_inner = heads · head_dim``, ``G`` groups and state size ``N``:

    [z | xBC | dt] = h W_in            (d_inner | d_inner + 2·G·N | heads)
    xBC_t = silu(b_c + sum_{j<K} w_c[j] · xBC_{t-K+1+j})   depthwise, causal
    [x | B | C] = xBC                  head i reads group i // (heads / G)
    dt_t = softplus(dt_t + dt_bias)    a_t = exp(-dt_t · exp(A_log))
    S_t = a_t S_{t-1} + dt_t · x_t ⊗ B_t        a head: (head_dim, N), float32
    y_t = S_t C_t + D · x_t
    out = GroupRMSNorm_G(y · silu(z)) W_out

Three entry points over one set of parameters, as the attention modules
have them: ``__call__`` (a whole sequence from a zero state), ``prefill``
(a chunk of any length continuing a :class:`StateCache`) and ``decode``
(one token). ``__call__`` and ``prefill`` run the recurrence in its
CHUNKED form (:func:`chunked_scan`): inside a chunk of ``chunk`` tokens
the outputs are a decay-masked ``(C Bᵀ)`` product against ``dt · x`` — a
matmul — plus what the state carried in gives; between chunks the state
steps once. ``decode`` is one read-modify-write pass over the state
(:func:`state_step`). What is carried from call to call is the state and
the convolution's last ``K - 1`` inputs; nothing grows.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.decode import StateCache
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['Mamba2Mixer', 'chunked_scan', 'state_step']


def chunked_scan(x, dt, log_a, b, c, state, chunk):
    """The recurrence over ``T`` tokens from ``state``: ``x (B, T, H, P)``,
    ``dt``, ``log_a (B, T, H)`` float32 (``log_a = -dt · exp(A_log)``),
    ``b``, ``c (B, T, G, N)``, ``state (B, H, P, N)`` float32. Returns
    ``y (B, T, H, P)`` float32 (without the ``D · x`` skip) and the
    state after the last token. ``T`` is padded up to whole chunks with
    tokens of ``dt = 0``: they decay nothing and add nothing."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[-2:]
    per = heads // groups
    pad = (-t) % chunk
    if pad:
        x, dt, log_a, b, c = (
            jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (u.ndim - 2))
            for u in (x, dt, log_a, b, c))
    nc = (t + pad) // chunk
    dtype = x.dtype

    def chunks(u):                      # (B, T, …) -> (nc, B, Q, …)
        return jnp.moveaxis(u.reshape(bsz, nc, chunk, *u.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, args):
        x, dt, log_a, b, c = args
        cum = jnp.cumsum(log_a, axis=1)                     # (B, Q, H)
        xdt = (x.astype(jnp.float32) * dt[..., None]).astype(dtype)
        xdt = xdt.reshape(bsz, chunk, groups, per, p)
        # Inside the chunk: (C_t · B_s) exp(cum_t - cum_s), s <= t.
        cb = jnp.einsum('btgn,bsgn->bgts', c, b,
                        preferred_element_type=jnp.float32)
        cum_h = jnp.moveaxis(cum, 1, 2).reshape(bsz, groups, per, chunk)
        decay = jnp.exp(jnp.where(
            causal, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        scores = (cb[:, :, None] * decay).astype(dtype)     # (B,G,per,Q,Q)
        y = jnp.einsum('bgrts,bsgrp->btgrp', scores, xdt,
                       preferred_element_type=jnp.float32)
        # What the state carried in gives: exp(cum_t) C_t · S.
        s_in = state.reshape(bsz, groups, per, p, n)
        y = y + jnp.einsum(
            'btgn,bgrpn->btgrp', c.astype(jnp.float32), s_in,
            precision=lax.Precision.HIGHEST) * jnp.exp(cum).reshape(
                bsz, chunk, groups, per, 1)
        # The state after the chunk.
        total = cum[:, -1]                                   # (B, H)
        left = jnp.exp(total[:, None] - cum)                 # (B, Q, H)
        xw = (xdt.astype(jnp.float32) * left.reshape(
            bsz, chunk, groups, per, 1)).astype(dtype)
        state = (state * jnp.exp(total)[..., None, None]
                 + jnp.einsum('bsgrp,bsgn->bgrpn', xw, b,
                              preferred_element_type=jnp.float32
                              ).reshape(state.shape))
        return state, y.reshape(bsz, chunk, heads, p)

    state, y = lax.scan(one, state, tuple(
        chunks(u) for u in (x, dt, log_a, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, heads, p)
    return y[:, :t], state


def state_step(x, dt, log_a, b, c, state):
    """One token: ``x (B, H, P)``, ``dt``, ``log_a (B, H)`` float32,
    ``b``, ``c (B, G, N)``, ``state (B, H, P, N)`` float32. Returns
    ``y (B, H, P)`` float32 (without the skip) and the new state; the
    state is read once and written once."""
    bsz, heads, p = x.shape
    groups, n = b.shape[-2:]
    per = heads // groups
    s = state.reshape(bsz, groups, per, p, n)
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(
        bsz, groups, per, p, 1)
    s = (s * jnp.exp(log_a).reshape(bsz, groups, per, 1, 1)
         + xdt * b.astype(jnp.float32)[:, :, None, None, :])
    y = jnp.sum(s * c.astype(jnp.float32)[:, :, None, None, :], axis=-1)
    return y.reshape(bsz, heads, p), s.reshape(state.shape)


class Mamba2Mixer(nn.Module):
    """The mixer of the module docstring. ``dim`` is the stream's width;
    ``heads x head_dim`` the inner width; ``groups`` the B / C groups;
    ``state`` their size ``N``; ``conv`` the convolution's taps;
    ``chunk`` the chunked form's chunk."""
    dim: int
    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    state_dtype: Any = jnp.float32

    @property
    def d_inner(self):
        return self.heads * self.head_dim

    @property
    def conv_channels(self):
        return self.d_inner + 2 * self.groups * self.state

    def make_cache(self, batch, dtype=None):
        """A zero :class:`StateCache` for ``batch`` sessions — plain
        field arithmetic, no ``apply``."""
        return StateCache(
            state=jnp.zeros((batch, self.heads, self.head_dim, self.state),
                            self.state_dtype),
            conv=jnp.zeros((batch, self.conv - 1, self.conv_channels),
                           dtype or self.dtype or jnp.float32))

    def setup(self):
        if self.heads % self.groups:
            raise ValueError(f'{self.heads} heads do not divide into '
                             f'{self.groups} groups')
        dense = dict(use_bias=False, dtype=self.dtype)
        self.in_proj = OwnedDense(
            self.d_inner + self.conv_channels + self.heads,
            name='in_proj', **dense)
        self.out_proj = OwnedDense(self.dim, name='out_proj', **dense)
        init = nn.initializers
        self.conv_kernel = self.param(
            'conv_kernel', init.lecun_normal(),
            (self.conv, self.conv_channels), jnp.float32)
        self.conv_bias = self.param('conv_bias', init.zeros_init(),
                                    (self.conv_channels,), jnp.float32)
        self.dt_bias = self.param('dt_bias', init.zeros_init(),
                                  (self.heads,), jnp.float32)
        self.A_log = self.param('A_log', init.zeros_init(),
                                (self.heads,), jnp.float32)
        self.D = self.param('D', init.ones_init(), (self.heads,),
                            jnp.float32)
        self.norm_scale = self.param('norm_scale', init.ones_init(),
                                     (self.d_inner,), jnp.float32)

    def _split(self, h, window):
        """The input projection and the convolution over ``window (B, K -
        1, C)`` then the chunk: ``z``, ``x (B, n, H, P)``, ``b``, ``c (B,
        n, G, N)``, ``dt``, ``log_a (B, n, H)`` float32, and the new
        window."""
        zxd = self.in_proj(h)
        z, xbc, dt = jnp.split(
            zxd, [self.d_inner, self.d_inner + self.conv_channels], -1)
        n = xbc.shape[1]
        seen = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
        w = self.conv_kernel.astype(jnp.float32)
        acc = self.conv_bias.astype(jnp.float32)
        for j in range(self.conv):
            acc = acc + w[j] * seen[:, j:j + n].astype(jnp.float32)
        xbc = nn.silu(acc).astype(xbc.dtype)
        x, b, c = jnp.split(
            xbc, [self.d_inner, self.d_inner + self.groups * self.state],
            -1)
        dt = nn.softplus(dt.astype(jnp.float32) + self.dt_bias)
        log_a = -dt * jnp.exp(self.A_log)
        lead = xbc.shape[:2]
        return (z, x.reshape(*lead, self.heads, self.head_dim),
                b.reshape(*lead, self.groups, self.state),
                c.reshape(*lead, self.groups, self.state), dt, log_a,
                seen[:, n:].astype(window.dtype))

    def _out(self, y, x, z):
        """Skip, gate, grouped norm and the output projection: ``y``
        float32 and ``x`` of shape ``(…, H, P)``, ``z (…, d_inner)``."""
        y = y + self.D[:, None] * x.astype(jnp.float32)
        y = y.reshape(z.shape) * nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(*y.shape[:-1], self.groups, -1)
        grouped = grouped * lax.rsqrt(
            jnp.mean(jnp.square(grouped), -1, keepdims=True)
            + self.norm_eps)
        y = grouped.reshape(y.shape) * self.norm_scale
        return self.out_proj(y.astype(z.dtype))

    def _chunk(self, h, cache):
        with device_scope('lm.ssm_proj'):
            z, x, b, c, dt, log_a, window = self._split(h, cache.conv)
        with device_scope('ops.ssm_scan'):
            y, state = chunked_scan(
                x, dt, log_a, b, c, cache.state.astype(jnp.float32),
                self.chunk)
        with device_scope('lm.ssm_proj'):
            out = self._out(y, x, z)
        return StateCache(state=state.astype(cache.state.dtype),
                          conv=window), out

    def __call__(self, h):
        return self._chunk(h, self.make_cache(h.shape[0], h.dtype))[1]

    def prefill(self, h, cache, position=None):
        """``h (B, n, dim)`` continuing ``cache``: ``(cache, out)``
        (``position``: every recurrent mixer is told it; nothing here
        depends on one)."""
        return self._chunk(h, cache)

    def decode(self, h, cache, position=None):
        """One token ``h (B, 1, dim)``: ``(cache, out)``."""
        with device_scope('lm.ssm_proj'):
            z, x, b, c, dt, log_a, window = self._split(h, cache.conv)
        with device_scope('ops.ssm_step'):
            y, state = state_step(
                x[:, 0], dt[:, 0], log_a[:, 0], b[:, 0], c[:, 0],
                cache.state.astype(jnp.float32))
        with device_scope('lm.ssm_proj'):
            out = self._out(y[:, None], x, z)
        return StateCache(state=state.astype(cache.state.dtype),
                          conv=window), out
