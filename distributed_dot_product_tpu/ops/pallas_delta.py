# -*- coding: utf-8 -*-
"""
One token of a gated delta-rule layer: ONE Pallas program that reads
every head's state once and writes it once.

With ``S (d_k, d_v)`` a head's float32 state, ``a`` the decay a KEY
CHANNEL, ``k``, ``q (d_k,)``, ``v (d_v,)`` and ``b`` the step's rate:

    S' = Diag(a) S            u = S'ᵀ k
    S  = S' + k (b (v − u))ᵀ  o = Sᵀ q

``u`` is a reduction over the WHOLE decayed state and the update needs
it, so as two XLA fusions the state is read twice (the reduction, then
the update and the read against ``q``) and written once: 1.5 times the
bytes of a step that is nothing but bytes. Here a head's ``(d_k, d_v)``
tile stays in VMEM between the reduction and the write:

- grid ``(sessions, heads / hb)``; a step holds ``hb`` heads of one
  session (:func:`heads_tile`: the most heads whose state block stays
  within :data:`_STATE_BLOCK_BYTES`), the state block aliased in place
  (``input_output_aliases``);
- ``d_k`` lies on the sublanes and ``d_v`` on the lanes, so ``u`` and
  ``o`` are sublane reductions (vector adds and one eight-row fold) and
  come out as ``(1, d_v)`` rows, as ``v`` goes in; what multiplies the
  state along ``d_k`` — ``q``, ``k``, ``a`` — has to be a COLUMN, and
  turning a row into one in the kernel is a lane reduce a vector
  register. They ride transposed instead: :func:`_columns` lays ``q | k
  | a | b`` out as ``(sessions, heads / hb, d_k, 4 hb)`` in XLA (0.5 % of
  the state's bytes a vector), and the kernel takes head ``j``'s as four
  one-lane slices that broadcast along the lanes;
- everything is float32 on the VPU: no matmul, so nothing is rounded to
  bfloat16 on the way.

Off the TPU the kernel runs under the Pallas interpreter, as the other
kernels do. :func:`delta_step_reference` is the same step in plain
``jax.numpy``: the tests' oracle, what a mixer runs where it is told
``'xla'``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributed_dot_product_tpu.ops.kernel_call import kernel_call

__all__ = ['delta_step', 'delta_step_reference', 'heads_tile']

# The most bytes of state one grid step holds: a step costs ~0.4 µs that
# are not bytes (ops/pallas_decode.py), 1 MiB in and 1 MiB out take
# 2.6 µs of a v5e's HBM, and in + out double-buffered are 4 MiB of the
# compiler's 16 MiB of VMEM.
_STATE_BLOCK_BYTES = 1 << 20


def heads_tile(heads, d_k, d_v):
    """Heads of one session a grid step holds: the most that divide
    ``heads`` whose float32 state block stays within
    :data:`_STATE_BLOCK_BYTES`, in whole sublane tiles (eight rows of
    ``v`` and of the output) unless it is all of them."""
    fits = [c for c in range(1, heads + 1)
            if heads % c == 0 and (c % 8 == 0 or c == heads)
            and c * d_k * d_v * 4 <= _STATE_BLOCK_BYTES]
    return max(fits or [heads])


def delta_step_reference(q, k, v, a, b, state):
    """One token in plain ``jax.numpy``: ``q``, ``k``, ``a (B, H, d_k)``
    (``a`` the decay, in (0, 1]), ``v (B, H, d_v)``, ``b (B, H)``, all
    float32, ``state (B, H, d_k, d_v)`` float32. Returns ``o (B, H,
    d_v)`` float32 and the new state."""
    s = state * a[..., None]
    u = jnp.sum(s * k[..., None], axis=-2)
    s = s + k[..., None] * (b[..., None] * (v - u))[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def _columns(q, k, a, b, hb):
    """``q | k | a | b`` as columns: ``(B, H / hb, d_k, 4 hb)``, head
    ``j`` of a block in lanes ``4 j … 4 j + 3`` (``b`` repeated down its
    column)."""
    bsz, heads, d_k = q.shape
    cols = jnp.stack([q, k, a, jnp.broadcast_to(b[..., None], q.shape)],
                     axis=-1)                       # (B, H, d_k, 4)
    cols = cols.reshape(bsz, heads // hb, hb, d_k, 4)
    return jnp.swapaxes(cols, 2, 3).reshape(bsz, heads // hb, d_k, 4 * hb)


def _kernel(c_ref, v_ref, s_ref, o_ref, so_ref, *, hb):
    for j in range(hb):
        def col(i, rows=slice(None)):
            return c_ref[0, 0, rows, 4 * j + i:4 * j + i + 1]
        q, k, a = col(0), col(1), col(2)            # (d_k, 1)
        s = s_ref[0, j] * a                         # (d_k, d_v)
        u = jnp.sum(s * k, axis=0, keepdims=True)   # (1, d_v)
        s = s + k * (col(3, slice(0, 1)) * (v_ref[0, j:j + 1, :] - u))
        o_ref[0, j:j + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)
        so_ref[0, j] = s


def delta_step(q, k, v, a, b, state, *, interpret=None):
    """:func:`delta_step_reference`'s step as the one kernel of the
    module docstring (same operands, same results; ``state`` float32,
    donated by the caller's jit: it is written in place)."""
    if state.dtype != jnp.float32:
        raise ValueError(f'the kernel keeps a float32 state, got '
                         f'{state.dtype}')
    bsz, heads, d_k = q.shape
    d_v = v.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    hb = heads_tile(heads, d_k, d_v)
    f32 = jnp.float32
    cols = _columns(q.astype(f32), k.astype(f32), a.astype(f32),
                    b.astype(f32), hb)
    rows = pl.BlockSpec((1, hb, d_v), lambda i, h: (i, h, 0))
    tiles = pl.BlockSpec((1, hb, d_k, d_v), lambda i, h: (i, h, 0, 0))
    return kernel_call(
        functools.partial(_kernel, hb=hb),
        grid=(bsz, heads // hb),
        in_specs=[pl.BlockSpec((1, 1, d_k, 4 * hb),
                               lambda i, h: (i, h, 0, 0)), rows, tiles],
        out_specs=[rows, tiles],
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, d_v), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={2: 1},
        interpret=interpret,
        name='delta_step')(cols, v.astype(f32), state)
