# -*- coding: utf-8 -*-
"""
A gated delta-rule mixer (Kimi Delta Attention; Kimi Linear 2025, the
linear-attention layers of ``solar_open2``): a recurrent layer whose
memory is a FIXED ``(d_k, d_v)`` float32 state a head, decayed by its
own rate in every KEY CHANNEL and corrected, a token, by what it already
holds for the token's key. On the normed stream ``h (…, T, dim)`` with
``inner = heads · head_dim`` (``d_k = d_v = head_dim``) and ``r`` the
rank of the two low-rank gates:

    [q | k | v | f | z | b] = h W_in       (3 · inner | r | r | heads)
    [q | k | v]_t = silu(sum_{j<K} w_c[j] · [q | k | v]_{t-K+1+j})
                                            depthwise, causal, no bias
    q = l2norm_head(q) · head_dim^-1/2      k = l2norm_head(k)
    g = -exp(A_log) · softplus(f W_f + dt_bias)     (heads, d_k); α = exp(g)
    β = beta_scale · sigmoid(b)             a head; 2: in (0, 2), which
                                            lets a head's transition have
                                            a negative eigenvalue
    S' = Diag(α_t) S_{t-1};   S_t = S' + β_t k_t (v_t − S'ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t
    out = (RMSNorm_head(o) ⊙ sigmoid(z W_z)) W_out

Two switches cover the mixer as ``bailing_hybrid`` (Ling 3.0) writes it.
``gate_rank=None``: the decay and the output gate are FULL matrices,
``f`` and ``z`` come out of the input projection ``inner`` wide each and
there is no ``W_f`` / ``W_z``. ``decay='bounded'``: ``g = lower ·
sigmoid(exp(A_log) · (f + dt_bias))`` with ``lower = decay_lower_bound``
(−5: ``α`` in ``(e^-5, 1)`` a channel), where the default is the
``softplus`` form above. The chunked form and the step's kernel take
the log-decay they are given.

Three entry points over one set of parameters, as
:class:`~distributed_dot_product_tpu.models.ssm.Mamba2Mixer` has them:
``__call__`` (a whole sequence from a zero state), ``prefill`` (a chunk
of any length continuing a :class:`StateCache`) and ``decode`` (one
token). What is carried is the state and the three convolutions' last
``K - 1`` inputs, as ONE window over the ``3 · inner`` channels.

``decode`` is :func:`delta_step`: one pass over the state — as the
kernel ``ops/pallas_delta.delta_step`` (a head's tile resident between
the reduction ``S'ᵀ k`` and the write), or as plain XLA
(``delta_step_reference``: two fusions, the state read twice).
:func:`delta_step_traces` reports which a traced mixer took.

``__call__`` and ``prefill`` run the recurrence in its CHUNKED form
(:func:`chunked_delta`). Inside a chunk of ``C`` tokens from the state
``S_0``, with ``G_t`` the cumulative log-decay up to and with token
``t`` and ``u_t = β_t (v_t − S'_tᵀ k_t)`` the token's correction,

    S_t = Diag(e^{G_t}) S_0 + sum_{i<=t} (e^{G_t − G_i} ⊙ k_i) u_iᵀ

so the corrections solve a unit lower-triangular system of the chunk,
``(I + A) U = β ⊙ (V − (e^G ⊙ K) S_0)`` with ``A_ti = β_t sum_c k_tc
k_ic e^{G_tc − G_ic}`` (``i < t``), and ``O = (e^G ⊙ Q) S_0 + M U`` with
``M_ti = sum_c q_tc k_ic e^{G_tc − G_ic}`` (``i <= t``). The decays
enter as DIFFERENCES of the cumulative log-decay, masked before the
exponential, never as a quotient by ``e^{G_i}``: a channel that decays
by ``e^-2`` a token is past float32's range inside one chunk of 64.
``A`` and ``M`` do not depend on the state, so they — and ``(I +
A)^-1``, ONE batched triangular solve over every chunk — are taken
before the scan over chunks, whose body is matmuls alone.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.decode import StateCache
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.ops.pallas_delta import (
    delta_step as delta_step_kernel, delta_step_reference, heads_tile,
)
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['GatedDeltaMixer', 'chunked_delta', 'delta_step',
           'delta_step_traces']

_STEP_TRACES = TraceSinks()


def delta_step_traces():
    """Collect which form each :class:`GatedDeltaMixer`'s decode step
    takes while the block runs: one dict ``{'form', 'tile', 'chunk'}``
    per TRACE of a mixer's ``decode``. ``form`` is ``'pallas'`` (the
    kernel ``delta_step``: the state read once) or ``'xla'``
    (``delta_step_reference``: read twice); ``tile`` the heads one grid
    step of the kernel holds (None off it); ``chunk`` the mixer's chunk
    (what its prefill ran with)::

        with delta_step_traces() as traces:
            step.lower(*args).compile()
        assert {t['form'] for t in traces} == {'pallas'}
    """
    return _STEP_TRACES.open()


def step_form(impl):
    """``'pallas'`` or ``'xla'`` for a mixer's ``step_impl``: None
    takes the kernel on a TPU and XLA elsewhere."""
    if impl is None:
        return 'pallas' if jax.default_backend() == 'tpu' else 'xla'
    return impl


def delta_step(q, k, v, log_a, beta, state, impl=None, interpret=None):
    """One token: ``q``, ``k``, ``log_a (B, H, d_k)`` (``log_a <= 0`` the
    log-decay a key channel), ``v (B, H, d_v)``, ``beta (B, H)``, all
    float32; ``state (B, H, d_k, d_v)`` float32. Returns ``o (B, H,
    d_v)`` float32 and the new state. ``impl``: :func:`step_form`'s."""
    a = jnp.exp(log_a)
    if step_form(impl) == 'pallas':
        return delta_step_kernel(q, k, v, a, beta, state,
                                 interpret=interpret)
    return delta_step_reference(q, k, v, a, beta, state)


def chunked_delta(q, k, v, log_a, beta, state, chunk):
    """The recurrence over ``T`` tokens from ``state``: ``q``, ``k``,
    ``log_a (B, T, H, d_k)``, ``v (B, T, H, d_v)``, ``beta (B, T, H)``,
    ``state (B, H, d_k, d_v)``, all float32. Returns ``o (B, T, H,
    d_v)`` and the state after the last token. ``T`` is padded up to
    whole chunks with tokens of ``log_a = 0`` and ``beta = 0``: they
    decay nothing and correct nothing."""
    bsz, t, heads, d_k = q.shape
    pad = (-t) % chunk
    if pad:
        q, k, v, log_a, beta = (
            jnp.pad(u, [(0, 0), (0, pad)] + [(0, 0)] * (u.ndim - 2))
            for u in (q, k, v, log_a, beta))
    nc = (t + pad) // chunk

    def chunks(u):                  # (B, T, H, …) -> (nc, B, H, C, …)
        u = u.reshape(bsz, nc, chunk, *u.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(u, 1, 0), 2, 3)

    q, k, v, log_a = (chunks(u) for u in (q, k, v, log_a))
    beta = chunks(beta[..., None])                      # (nc, B, H, C, 1)
    cum = jnp.cumsum(log_a, axis=-2)                    # (nc, B, H, C, d_k)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def pairs(args):
        """A chunk's ``A`` (strictly below the diagonal) and ``M``
        (with it), ``(B, H, C, C)`` each."""
        q, k, cum, beta = args
        decay = jnp.exp(jnp.where(
            seen[:, :, None],
            cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
        kd = k[..., None, :, :] * decay                 # (B, H, C, C, d_k)
        a = jnp.sum(k[..., :, None, :] * kd, -1) * beta
        m = jnp.sum(q[..., :, None, :] * kd, -1)
        return jnp.where(seen & ~jnp.eye(chunk, dtype=bool), a, 0.0), m

    a, m = lax.map(pairs, (q, k, cum, beta))
    # (I + A)^-1 of every chunk and head at once: the rows of a
    # triangular solve are sequential, the chunks are not.
    eye = jnp.eye(chunk, dtype=jnp.float32)
    inverse = lax.linalg.triangular_solve(
        a + eye, jnp.broadcast_to(eye, a.shape), left_side=True,
        lower=True, unit_diagonal=True)

    def mm(x, y, spec):
        return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST)

    def one(state, args):
        q, k, v, cum, beta, inverse, m = args
        grown = jnp.exp(cum)                            # e^{G_t} <= 1
        rhs = beta * (v - mm(k * grown, state, 'bhck,bhkv->bhcv'))
        u = mm(inverse, rhs, 'bhts,bhsv->bhtv')
        o = (mm(q * grown, state, 'bhck,bhkv->bhcv')
             + mm(m, u, 'bhts,bhsv->bhtv'))
        total = cum[..., -1:, :]                        # (B, H, 1, d_k)
        state = (state * jnp.swapaxes(jnp.exp(total), -1, -2)
                 + mm(k * jnp.exp(total - cum), u, 'bhck,bhcv->bhkv'))
        return state, o

    state, o = lax.scan(one, state, (q, k, v, cum, beta, inverse, m))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)       # (B, nc, C, H, d_v)
    return o.reshape(bsz, t + pad, heads, -1)[:, :t], state


class GatedDeltaMixer(nn.Module):
    """The mixer of the module docstring. ``dim`` is the stream's width;
    ``heads x head_dim`` the inner width (``d_k = d_v = head_dim``);
    the two low-rank gates' rank is ``gate_rank`` (``'head_dim'``, the
    default: ``head_dim``; None: full matrices inside ``in_proj``);
    ``decay`` the log-decay's form, ``'softplus'`` or ``'bounded'``
    below by ``decay_lower_bound``; ``conv`` the convolutions' taps;
    ``chunk`` the chunked form's chunk; ``beta_scale`` 2 where a head
    may have a negative eigenvalue, else 1; ``step_impl`` the decode
    step's form (:func:`delta_step`)."""
    dim: int
    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    beta_scale: float = 2.0
    norm_eps: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    state_dtype: Any = jnp.float32
    step_impl: Optional[str] = None
    gate_rank: Any = 'head_dim'
    decay: str = 'softplus'
    decay_lower_bound: float = -5.0

    @property
    def inner(self):
        return self.heads * self.head_dim

    @property
    def _gate_width(self):
        """Columns of ``in_proj`` each gate takes: its rank, or
        ``inner`` where it is a full matrix."""
        if self.gate_rank is None:
            return self.inner
        return (self.head_dim if self.gate_rank == 'head_dim'
                else self.gate_rank)

    def make_cache(self, batch, dtype=None):
        """A zero :class:`StateCache` for ``batch`` sessions — plain
        field arithmetic, no ``apply``."""
        return StateCache(
            state=jnp.zeros((batch, self.heads, self.head_dim,
                             self.head_dim), self.state_dtype),
            conv=jnp.zeros((batch, self.conv - 1, 3 * self.inner),
                           dtype or self.dtype or jnp.float32))

    def setup(self):
        if self.step_impl not in (None, 'pallas', 'xla'):
            raise ValueError(f"step_impl must be None, 'pallas' or "
                             f"'xla', got {self.step_impl!r}")
        if self.decay not in ('softplus', 'bounded'):
            raise ValueError(f"decay must be 'softplus' or 'bounded', "
                             f'got {self.decay!r}')
        dense = dict(use_bias=False, dtype=self.dtype)
        self.in_proj = OwnedDense(
            3 * self.inner + 2 * self._gate_width + self.heads,
            name='in_proj', **dense)
        if self.gate_rank is not None:
            self.decay_up = OwnedDense(self.inner, name='decay_up',
                                       **dense)
            self.gate_up = OwnedDense(self.inner, name='gate_up', **dense)
        self.out_proj = OwnedDense(self.dim, name='out_proj', **dense)
        init = nn.initializers
        self.conv_kernel = self.param(
            'conv_kernel', init.lecun_normal(),
            (self.conv, 3 * self.inner), jnp.float32)
        self.dt_bias = self.param('dt_bias', init.zeros_init(),
                                  (self.inner,), jnp.float32)
        self.A_log = self.param('A_log', init.zeros_init(),
                                (self.heads,), jnp.float32)
        self.norm_scale = self.param('norm_scale', init.ones_init(),
                                     (self.head_dim,), jnp.float32)

    def _split(self, h, window):
        """The input projection, the convolutions over ``window (B, K -
        1, 3 · inner)`` then the chunk, and the gates: ``q``, ``k``,
        ``v``, ``log_a (B, n, H, head_dim)`` and ``beta (B, n, H)``
        float32, the output gate's low-rank half ``z (B, n, rank)`` (the
        whole gate, ``inner`` wide, where ``gate_rank`` is None) and the
        new window."""
        rank = self._gate_width
        qkv, f, z, b = jnp.split(self.in_proj(h), [
            3 * self.inner, 3 * self.inner + rank,
            3 * self.inner + 2 * rank], -1)
        n = qkv.shape[1]
        seen = jnp.concatenate([window.astype(qkv.dtype), qkv], axis=1)
        w = self.conv_kernel.astype(jnp.float32)
        acc = w[0] * seen[:, :n].astype(jnp.float32)
        for j in range(1, self.conv):
            acc = acc + w[j] * seen[:, j:j + n].astype(jnp.float32)
        lead = qkv.shape[:2] + (self.heads, self.head_dim)
        q, k, v = (u.reshape(lead)
                   for u in jnp.split(nn.silu(acc), 3, -1))

        def unit(x):
            return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
        if self.gate_rank is not None:
            f = self.decay_up(f)
        if self.decay == 'softplus':
            decay = nn.softplus(f.astype(jnp.float32)
                                + self.dt_bias).reshape(lead)
            log_a = -jnp.exp(self.A_log)[:, None] * decay
        else:
            rate = (f.astype(jnp.float32) + self.dt_bias).reshape(lead)
            log_a = self.decay_lower_bound * nn.sigmoid(
                jnp.exp(self.A_log)[:, None] * rate)
        beta = self.beta_scale * nn.sigmoid(b.astype(jnp.float32))
        return (unit(q) * (1.0 / math.sqrt(self.head_dim)), unit(k), v,
                log_a, beta, z, seen[:, n:].astype(window.dtype))

    def _out(self, o, z):
        """The per-head norm under the sigmoid gate and the output
        projection: ``o (B, n, H, head_dim)`` float32, ``z (B, n,
        rank)``."""
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + self.norm_eps) * self.norm_scale
        if self.gate_rank is not None:
            z = self.gate_up(z)
        gate = nn.sigmoid(z.astype(jnp.float32))
        o = o.reshape(gate.shape) * gate
        return self.out_proj(o.astype(z.dtype))

    def _chunk(self, h, cache):
        with device_scope('lm.delta_proj'):
            q, k, v, log_a, beta, z, window = self._split(h, cache.conv)
        with device_scope('ops.delta_scan'):
            o, state = chunked_delta(
                q, k, v, log_a, beta, cache.state.astype(jnp.float32),
                self.chunk)
        with device_scope('lm.delta_proj'):
            out = self._out(o, z)
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
        impl = step_form(self.step_impl)
        _STEP_TRACES.note({'form': impl, 'chunk': self.chunk,
                           'tile': heads_tile(self.heads, self.head_dim,
                                              self.head_dim)
                           if impl == 'pallas' else None})
        with device_scope('lm.delta_proj'):
            q, k, v, log_a, beta, z, window = self._split(h, cache.conv)
        with device_scope('ops.delta_step'):
            o, state = delta_step(
                q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0],
                cache.state.astype(jnp.float32), impl)
        with device_scope('lm.delta_proj'):
            out = self._out(o[:, None], z)
        return StateCache(state=state.astype(cache.state.dtype),
                          conv=window), out
