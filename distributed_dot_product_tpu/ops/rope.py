# -*- coding: utf-8 -*-
"""
Rotary position embeddings (RoPE), sequence-shard-aware.

RoPE rotates each (even, odd-half) feature pair of q/k by an angle
proportional to the token's GLOBAL position, so attention logits depend
only on relative distance. No reference analog (the reference has no
positional encoding at all); provided because it is the standard
long-context companion to the attention stack here — and under sequence
parallelism the rotation MUST use global positions, which is exactly the
plumbing this framework already has (shard offsets, zigzag position
vectors).

Convention: NeoX/LLaMA "half" layout — the feature dim splits into two
halves ``(x1, x2)`` rotated as ``(x1·cos − x2·sin, x1·sin + x2·cos)``,
with frequencies ``base^(−2i/d)`` over the first half. Pure jnp: the
O(T·d) elementwise work is HBM-trivial next to attention and XLA fuses it
into the surrounding projections; it needs no Pallas kernel.
"""

import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

__all__ = ['rope', 'rope_seq_parallel', 'yarn_inv_freq',
           'rope_interleaved']


def rope(x, positions=None, *, base=10000.0, offset=0, dtype=jnp.float32):
    """Apply rotary embedding to ``x (..., T, d)`` (``d`` even).

    ``positions``: per-token GLOBAL positions ``(..., T)`` (leading dims
    broadcastable against x's); default ``offset + arange(T)`` —
    sequence-sharded callers pass their shard's global offset (a traced
    scalar like ``lax.axis_index(axis) * (T // N)`` works), or explicit
    ``positions`` for non-contiguous layouts (zigzag — the same vectors
    fed to ``flash_attention(positions=...)``).

    The rotation is computed in ``dtype`` (default f32 — bf16 angles lose
    relative-position precision beyond ~10K tokens) and cast back to
    ``x.dtype``.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f'rope needs an even feature dim, got {d}')
    t = x.shape[-2]
    if positions is None:
        positions = offset + jnp.arange(t)
    positions = jnp.asarray(positions, dtype)
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=dtype) / d)   # (d/2,)
    angles = positions[..., None] * inv_freq                     # (..., T, d/2)
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    x1 = x[..., : d // 2].astype(dtype)
    x2 = x[..., d // 2:].astype(dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_seq_parallel(x, *, axis_name=SEQ_AXIS, positions=None,
                      base=10000.0, dtype=jnp.float32):
    """``rope`` for a ``(..., T/N, d)`` shard inside ``shard_map``: global
    positions default to ``axis_index·T/N + arange`` (contiguous
    sharding); pass the shard's ``positions`` vector for zigzag/striped
    layouts."""
    if positions is None:
        tn = x.shape[-2]
        positions = lax.axis_index(axis_name) * tn + jnp.arange(tn)
    return rope(x, positions, base=base, dtype=dtype)


def yarn_inv_freq(dim, *, base=10000.0, factor=1.0, original_max=4096,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's blended inverse frequencies ``(dim/2,)`` (Peng et al.,
    arXiv 2309.00071, as DeepSeek-V3's ``YarnRotaryEmbedding`` computes
    them): pair ``i`` keeps its frequency ``base^(-2i/dim)`` where it
    turns more than ``beta_fast`` times over ``original_max`` positions,
    takes ``1/factor`` of it where it turns fewer than ``beta_slow``
    times, and a linear ramp between. Plain numpy on static sizes."""
    import math

    import numpy as np
    pairs = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** pairs
    inter = extra / factor

    def correction(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_interleaved(x, positions, inv_freq, dtype=jnp.float32):
    """Rotary embedding over INTERLEAVED pairs: features ``(2i, 2i+1)``
    of ``x (..., T, d)`` turn by ``positions (..., T) * inv_freq[i]``
    and stay where they were (the layout DeepSeek's and Cohere's
    checkpoints keep their rotary dims in; dot products equal the
    half-split form's on the same pairs).

    Written as ``x · cos + swap(x) · (∓sin)`` with ``swap`` the exchange
    inside each pair (a flip of the size-2 axis), never as the strided
    slices ``x[..., 0::2]`` / ``x[..., 1::2]``: XLA moves a slice of a
    matmul's result into the matmul, and where ``x`` is a projection's
    output that re-lays the WEIGHT out by even and odd columns on every
    call (two 134 MB copies a layer a token at 4096 x 16384; AOT for a
    v5e, PR 30). The products and sums are the same ones."""
    d = x.shape[-1]
    ang = (jnp.asarray(positions, dtype)[..., None]
           * jnp.asarray(inv_freq, dtype))
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1) * jnp.tile(
        jnp.asarray([-1.0, 1.0], dtype), d // 2)
    xf = x.astype(dtype)
    swapped = jnp.flip(xf.reshape(*x.shape[:-1], d // 2, 2),
                       axis=-1).reshape(x.shape)
    return (xf * cos + swapped * sin).astype(x.dtype)
