# -*- coding: utf-8 -*-
"""
A sparse-expert feed-forward layer: a router over ``n_experts`` gated
MLPs, ``top_k`` of them a token, beside ``n_shared`` shared experts that
every token takes (DeepSeek-V3's layer, ``noaux_tc`` routing with one
group):

    s = sigmoid(x W_g)                       float32, (n_experts,)
    picked = top_k(s + b)                    b: the correction bias
    g_i = s_i / sum_picked s · scaling       (norm_topk), i in picked
    y = sum_i g_i E_i(x) + E_shared(x)       E(x) = W_down(silu(W_gate x) * W_up x)

No capacity factor: no token is dropped. Two switches cover the same
layer as other families write it: ``router_bias=False`` has no
correction bias (the pick is the top-k of the scores themselves), and
``shared_combine='mean'`` adds the MEAN of the ``n_shared`` shared
experts' outputs where ``'sum'`` adds their sum (Cohere's
``shared_expert_combination_strategy: "average"``). The shared experts
are one gated MLP ``n_shared x hidden`` wide either way — their sum —
so the mean is that over ``n_shared``.

The layer is TOLD which experts it holds (``experts_held = (lo, hi)``, a
range; default all). It always routes over all ``n_experts``, computes
the part of ``y`` its own experts give for the tokens routed to them,
and adds the shared expert — what every holder computes alike — only
where ``add_shared`` says so, so the parts of a layer divided over
several holders add up to the whole layer with the shared expert
counted once. On one chip that holds every expert this is the whole
layer, with no exchange and nothing standing in for absent chips.

The routed part sorts the (token, pick) rows by expert and runs three
grouped matmuls over the sorted rows (``lax.ragged_dot``: on the TPU
XLA's own grouped-matmul kernel, which reads an expert's weights only
where its group has rows).
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.obs.spans import device_scope

__all__ = ['GatedMLP', 'SparseExperts']


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``, no biases."""
    hidden: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        dense = dict(use_bias=False, dtype=self.dtype)
        gate = OwnedDense(self.hidden, name='gate', **dense)(x)
        up = OwnedDense(self.hidden, name='up', **dense)(x)
        return OwnedDense(x.shape[-1], name='down', **dense)(
            nn.silu(gate) * up)


class SparseExperts(nn.Module):
    """``y, tokens_per_expert = layer(x)`` for ``x (..., dim)``;
    ``tokens_per_expert (n_experts,) int32`` counts this call's picks
    over ALL experts (also sown into the ``counters`` collection as
    ``expert_tokens``, with the picks ``expert_picks (tokens, top_k)``,
    where the caller makes that collection mutable)."""
    n_experts: int
    top_k: int
    hidden: int
    n_shared: int = 1
    scaling: float = 1.0
    norm_topk: bool = True
    experts_held: Optional[Tuple[int, int]] = None
    add_shared: bool = True
    shared_combine: str = 'sum'
    router_bias: bool = True
    dtype: Optional[jnp.dtype] = None
    kernel_init: Any = nn.initializers.lecun_normal(in_axis=-2,
                                                    out_axis=-1,
                                                    batch_axis=(0,))

    @nn.compact
    def __call__(self, x):
        lo, hi = self.experts_held or (0, self.n_experts)
        held = hi - lo
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f'experts_held {self.experts_held} is no '
                             f'range of {self.n_experts} experts')
        if self.shared_combine not in ('sum', 'mean'):
            raise ValueError(f"shared_combine must be 'sum' or 'mean', "
                             f'got {self.shared_combine!r}')
        dim = x.shape[-1]
        router = self.param('router', nn.initializers.lecun_normal(),
                            (dim, self.n_experts), jnp.float32)
        bias = (self.param('router_bias', nn.initializers.zeros_init(),
                           (self.n_experts,), jnp.float32)
                if self.router_bias else None)
        w_gate = self.param('w_gate', self.kernel_init,
                            (held, dim, self.hidden), jnp.float32)
        w_up = self.param('w_up', self.kernel_init,
                          (held, dim, self.hidden), jnp.float32)
        w_down = self.param('w_down', self.kernel_init,
                            (held, self.hidden, dim), jnp.float32)
        dtype = self.dtype or x.dtype
        flat = x.reshape(-1, dim).astype(dtype)
        n, k = flat.shape[0], self.top_k

        with device_scope('lm.moe_route'):
            scores = jax.nn.sigmoid(jnp.dot(
                flat.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            _, picked = lax.top_k(
                scores if bias is None else scores + bias, k)   # (n, k)
            gates = jnp.take_along_axis(scores, picked, axis=-1)
            if self.norm_topk:
                gates = gates / jnp.sum(gates, -1, keepdims=True)
            gates = gates * self.scaling
            expert = picked.reshape(-1)                          # (n·k,)
            counts = jnp.zeros((self.n_experts,), jnp.int32).at[
                expert].add(1)
            mine = (expert >= lo) & (expert < hi)
            # Rows of experts held elsewhere sort behind the last group
            # and are masked out of the combine.
            order = jnp.argsort(jnp.where(mine, expert - lo, held),
                                stable=True)
            rows = flat[order // k]                              # (n·k, dim)
            sizes = lax.dynamic_slice_in_dim(counts, lo, held)
        # Counters for a caller that makes the collection mutable (a
        # no-op otherwise): this call's tokens per expert and picks.
        self.sow('counters', 'expert_tokens', counts,
                 reduce_fn=lambda old, new: new,
                 init_fn=lambda: jnp.zeros((self.n_experts,), jnp.int32))
        self.sow('counters', 'expert_picks', picked,
                 reduce_fn=lambda old, new: new,
                 init_fn=lambda: jnp.zeros((n, k), jnp.int32))

        with device_scope('lm.moe_experts'):
            def grouped(a, w):
                return lax.ragged_dot(a, w.astype(dtype), sizes,
                                      preferred_element_type=jnp.float32
                                      ).astype(dtype)
            out = grouped(nn.silu(grouped(rows, w_gate))
                          * grouped(rows, w_up), w_down)

        with device_scope('lm.moe_route'):
            weight = (gates.reshape(-1) * mine)[order]
            out = jnp.where(weight[:, None] != 0,
                            out.astype(jnp.float32) * weight[:, None], 0.0)
            # Back to (token, pick) order, then the k picks add up.
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(n * k, dtype=order.dtype))
            y = out[back].reshape(n, k, dim).sum(axis=1).astype(dtype)

        if self.n_shared and self.add_shared:
            with device_scope('lm.mlp'):
                shared = GatedMLP(self.n_shared * self.hidden,
                                  dtype=self.dtype, name='shared')(flat)
                if self.shared_combine == 'mean':
                    shared = shared * (1.0 / self.n_shared)
                y = y + shared
        return y.reshape(x.shape[:-1] + (dim,)), counts
