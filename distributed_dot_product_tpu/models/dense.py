# -*- coding: utf-8 -*-
"""
Owned dense layer — the repo's replacement for ``flax.linen.Dense``.

Why own a one-matmul module: flax's ``linen.Dense`` computes its dot in
the promoted operand dtype, so at ``dtype=bf16`` it emits a
bf16-ACCUMULATING ``dot_general`` — the exact class of silent precision
loss the graphlint ``f32-accum`` rule exists to catch, and (until this
module) the one place the rule could not reach: the offending dots
trace into flax's own source, where neither a line pragma nor a code
fix can live. Owning the projection dot puts the accumulation contract
IN the repo: the contraction always requests
``preferred_element_type=float32`` (int32 on the int8 path) and casts
back to the activation dtype afterwards — the contract is fp32
*accumulation*, not fp32 outputs — so every registered entrypoint now
lints clean at the serving dtype with zero waivers (ROADMAP item 3a,
retired).

Weight quantization (``weight_quant='int8'``): the serving-side win.
Decode is bandwidth-bound (``PERF.md`` section 5), and at B·1
query rows the projection weights are most of the bytes a step streams
— storing them int8 halves that traffic and roughly doubles the
parameters servable per 16 GiB chip. The treatment mirrors the int8 K
mirror that fixed the s8 decode regression: weights are quantized ONCE at load/convert time
(:func:`quantize_dense_params` — per OUTPUT channel symmetric scales,
``w ≈ w_i8 · s_col``), activations are quantized per row on the fly
(the training kernels' ``_quantize_rows`` rule), and the dot runs
s8×s8→s32 on the MXU with the dequantization applied to the s32 result
— the streamed operand is never widened (the earlier dequantize-first
formulation measured 0.49 ms vs 0.21; never widen the streamed
operand). Exactness contract: per-element error is bounded by one
rounding step of each side's scale (~0.4% of the row/column max — the
int8 class), pinned by tests/test_weight_quant.py the same way the
K-mirror contract is.
"""

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

__all__ = ['OwnedDense', 'quantized_dot', 'quantize_dense_params',
           'quantize_kernel', 'dense_param_bytes']

# Per-row activation scales share the kernels' eps clamp so all-zero
# rows stay finite (ops/pallas_attention._quantize_rows).
_EPS = 1e-20


def quantized_dot(x, w_q, w_s):
    """``x (..., in) · (w_q int8 (in, out) · w_s (out,))`` — THE int8
    weight matmul body, shared by :class:`OwnedDense` and the serving
    engine so the quantization rule cannot drift between them: the
    activation rows quantize symmetrically on the fly (per-row absmax
    scale, eps-clamped), the dot runs s8×s8→s32 on the MXU, and both
    scales dequantize the s32 result — the streamed operands are never
    widened before the dot. Returns f32 (callers cast back)."""
    x32 = x.astype(jnp.float32)
    sx = jnp.maximum(
        jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0, _EPS)
    xi = jnp.round(x32 / sx).astype(jnp.int8)
    y = lax.dot_general(
        xi, w_q, (((xi.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    return y * sx * w_s


class OwnedDense(nn.Module):
    """``y = x · W (+ b)`` with an owned accumulation contract.

    Drop-in for ``nn.Dense`` (same param tree — ``kernel (in, out)``,
    optional ``bias (out,)``, same default initializers — so existing
    checkpoints and init seeds carry over), except the contraction
    always requests a wide accumulator:

    - ``weight_quant=None``: ``dot_general(x, W,
      preferred_element_type=f32)`` then cast back to the activation
      dtype. At f32 this is bit-identical to ``nn.Dense``; at bf16 it
      is the fp32-accumulation the graphlint rule enforces.
    - ``weight_quant='int8'``: parameters are ``kernel_q (in, out)
      int8`` + ``kernel_scale (out,) f32`` (produced by
      :func:`quantize_dense_params` from a float checkpoint — ``init``
      creates zero placeholders of the right shape). The activation
      rows are quantized symmetrically on the fly and the dot runs
      s8×s8→s32 with both scales applied to the s32 result.
    """
    features: int
    use_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    weight_quant: Optional[str] = None
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        if self.weight_quant not in (None, 'int8'):
            raise ValueError(f"weight_quant must be None or 'int8', "
                             f'got {self.weight_quant!r}')
        d_in = x.shape[-1]
        bias = (self.param('bias', self.bias_init, (self.features,),
                           self.param_dtype)
                if self.use_bias else None)
        if self.weight_quant == 'int8':
            # Placeholder initializers: real values come from
            # quantize_dense_params at load/convert time (an int8 init
            # distribution makes no sense — init only fixes shapes).
            w_q = self.param('kernel_q', nn.initializers.zeros_init(),
                             (d_in, self.features), jnp.int8)
            w_s = self.param('kernel_scale', nn.initializers.ones_init(),
                             (self.features,), jnp.float32)
            out_dtype = self.dtype or jnp.result_type(x.dtype,
                                                      self.param_dtype)
            y = quantized_dot(x, w_q, w_s)
            if bias is not None:
                y = y + bias.astype(jnp.float32)
            return y.astype(out_dtype)
        kernel = self.param('kernel', self.kernel_init,
                            (d_in, self.features), self.param_dtype)
        # Promote operands exactly like nn.Dense (self.dtype wins; else
        # the x/param promotion), but request fp32 ACCUMULATION on the
        # dot and cast back — the one behavior flax Dense lacks.
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        y = lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        if bias is not None:
            y = y + bias
        return y


def quantize_kernel(kernel):
    """Per-OUTPUT-channel symmetric int8 quantization of a ``(in, out)``
    kernel: ``(kernel_q int8, kernel_scale (out,) f32)`` with
    ``scale_j = max|W[:, j]| / 127`` (eps-clamped). Per-channel (not
    per-tensor) because projection columns span orders of magnitude
    after training — a per-tensor scale would crush the small ones.
    Leading axes pass through (a scanned stack's layer-stacked
    ``(L, in, out)`` kernel quantizes each layer's channels
    independently — ``nn.scan`` slices the leading axis off before the
    module reads it)."""
    w32 = jnp.asarray(kernel).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2) / 127.0, _EPS)
    w_q = jnp.round(w32 / scale[..., None, :]).astype(jnp.int8)
    return w_q, scale


def quantize_dense_params(params):
    """Convert a float param tree to the int8-weight layout
    :class:`OwnedDense`'s ``weight_quant='int8'`` mode reads: every
    dict holding a 2-D ``kernel`` leaf (an owned/flax dense module's
    subtree) has it replaced by ``kernel_q``/``kernel_scale``; biases,
    LayerNorm scales, embedding tables and every other leaf pass
    through untouched. Load/convert-time — call once on the
    checkpoint, then ``apply`` the quantized module with the result."""
    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if (k == 'kernel' and hasattr(v, 'ndim')
                        and v.ndim >= 2):
                    out['kernel_q'], out['kernel_scale'] = \
                        quantize_kernel(v)
                else:
                    out[k] = walk(v)
            return out
        return node
    # flax FrozenDict (older trees) ducks as a Mapping; unfreeze via
    # plain-dict conversion so the walk stays structure-agnostic.
    if hasattr(params, 'unfreeze'):
        params = params.unfreeze()
    return walk(params)


def dense_param_bytes(params, dtype=None):
    """Total bytes of every array leaf in ``params`` (held as ``dtype``
    where one is given) — the weights-streamed-per-step column of the
    decode benchmark's quantized-vs-bf16 twin rows, and what a train
    step reckons it holds (``train.make_lm_train_step``)."""
    import jax
    return sum(int(x.size) * jnp.dtype(dtype or x.dtype).itemsize
               for x in jax.tree.leaves(params)
               if hasattr(x, 'dtype'))
