# -*- coding: utf-8 -*-
"""
A gated short-convolution mixer (the ``conv`` layers of ``lfm2_moe``): a
double-gated depthwise causal convolution of a few taps, and the first
recurrent layer here with NO state behind its window. On the normed
stream ``h (…, T, dim)``:

    [B | C | x̃] = h W_in                           (dim each, in this order)
    u = B ⊙ x̃
    v_t = sum_{j < taps} w_j ⊙ u_{t − (taps − 1) + j}      one filter a
                          channel, causal, NO activation and no bias
    out = (C ⊙ v) W_out

What a layer remembers of a session is the last ``taps − 1`` rows of
``u`` — two rows of ``dim`` at the published three taps — and nothing
else: its cache is a :class:`~distributed_dot_product_tpu.models.decode.
StateCache` whose ``state`` has NO elements (the mirror of the Lightning
mixer's, whose window has no rows) and whose ``conv`` is the window, in
the stream's type. ``u`` is rounded to that type BEFORE the filter reads
it, so a window carried from one prefill chunk to the next holds exactly
the rows the whole sequence would have read.

Three entry points over one set of parameters, as the other recurrent
mixers have them: ``__call__`` (a whole sequence from an empty window),
``prefill`` (a chunk that continues ``cache``) and ``decode`` (one
token: the filter over the window and the new row, and the window's
shift). All of it — both projections, both gates, the taps and the
shift — is plain XLA under the device scope ``lm.conv_proj``; a decode
step reads ``2 · dim`` values of window a session beside the two
projections' weights, so there is no kernel to write.
:func:`conv_step_traces` reports the form, taps and channels of every
traced ``decode``.
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from distributed_dot_product_tpu.models.decode import StateCache
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['ShortConvMixer', 'conv_step_traces']

_STEP_TRACES = TraceSinks()


def conv_step_traces():
    """Collect the form of every :class:`ShortConvMixer`'s decode step
    while the block runs: one dict ``{'form', 'taps', 'channels'}`` per
    TRACE of a mixer's ``decode`` — ``form`` is ``'shift'`` (the filter
    over the carried window and the new row, then the window shifted by
    one row: the one form there is), ``taps`` the filter's length and
    ``channels`` the rows' width::

        with conv_step_traces() as traces:
            step.lower(*args).compile()
        assert {t['form'] for t in traces} == {'shift'}
    """
    return _STEP_TRACES.open()


class ShortConvMixer(nn.Module):
    """The mixer of the module docstring. ``dim`` is the stream's width
    (and the convolution's channels), ``taps`` the filter's length
    (``conv_L_cache``); ``norm_eps`` is the block's and says nothing to
    a layer without a norm of its own."""
    dim: int
    taps: int = 3
    norm_eps: float = 1e-6
    dtype: Optional[jnp.dtype] = None

    def make_cache(self, batch, dtype=None):
        """A zero :class:`StateCache` for ``batch`` sessions — plain
        field arithmetic, no ``apply``. There is no recurrence: the
        state has no elements, the window ``taps − 1`` rows."""
        return StateCache(
            state=jnp.zeros((batch, 0, 0, 0), jnp.float32),
            conv=jnp.zeros((batch, self.taps - 1, self.dim),
                           dtype or self.dtype or jnp.float32))

    def setup(self):
        dense = dict(use_bias=False, dtype=self.dtype)
        self.in_proj = OwnedDense(3 * self.dim, name='in_proj', **dense)
        self.out_proj = OwnedDense(self.dim, name='out_proj', **dense)
        self.conv_kernel = self.param(
            'conv_kernel', nn.initializers.lecun_normal(in_axis=0,
                                                        out_axis=1),
            (self.taps, self.dim), jnp.float32)

    def _mix(self, h, window):
        """``h (B, n, dim)`` behind ``window (B, taps − 1, dim)``: the
        output ``(B, n, dim)`` and the window after the last row."""
        n = h.shape[1]
        b, c, x = jnp.split(self.in_proj(h), 3, axis=-1)
        u = (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(
            window.dtype)
        rows = jnp.concatenate([window, u], axis=1).astype(jnp.float32)
        w = self.conv_kernel.astype(jnp.float32)
        v = sum(w[j] * rows[:, j:j + n] for j in range(self.taps))
        out = self.out_proj((c.astype(jnp.float32) * v).astype(h.dtype))
        return out, rows[:, n:].astype(window.dtype)

    def _step(self, h, cache):
        with device_scope('lm.conv_proj'):
            out, window = self._mix(h, cache.conv)
        return cache._replace(conv=window), out

    def __call__(self, h):
        return self._step(h, self.make_cache(h.shape[0], h.dtype))[1]

    def prefill(self, h, cache, position=None):
        """``h (B, n, dim)`` continuing ``cache``: ``(cache, out)``. The
        ``position`` is the stack's to every recurrent mixer; a
        convolution has none."""
        return self._step(h, cache)

    def decode(self, h, cache, position=None):
        """One token ``h (B, 1, dim)``: ``(cache, out)``."""
        _STEP_TRACES.note({'form': 'shift', 'taps': self.taps,
                           'channels': self.dim})
        return self._step(h, cache)
