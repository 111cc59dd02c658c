# -*- coding: utf-8 -*-
"""
Manifold-constrained hyper-connections (mHC; DeepSeek, arXiv 2512.24880,
on hyper-connections, arXiv 2409.19606): the residual path is
``mult`` parallel streams a token, ``X (mult, dim)``, and each branch
``F`` (attention, feed-forward) reads a learned mix of them and writes
back through learned, per-token matrices, in float32:

    x~ = RMSNorm(vec X)                              (mult·dim, no scale)
    H~ = alpha * (x~ Phi) + b                        pre (mult), post (mult), res (mult x mult)
    H_pre = sigmoid(H~_pre)      H_post = 2 sigmoid(H~_post)
    H_res = Sinkhorn(clip(H~_res, lo, hi))           doubly stochastic
    u = H_pre X      y = F(norm(u))      X' = H_res X + H_post^T y

``Sinkhorn`` starts from ``exp`` and normalises rows, then columns,
``iters`` times, each division by ``sum + eps``.
"""

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['HyperConnection', 'mix_back', 'sinkhorn']


def sinkhorn(logits, iters, eps):
    """Rows, then columns, of ``exp(logits) (..., m, m)`` normalised
    ``iters`` times."""
    h = jnp.exp(logits)
    for _ in range(iters):
        h = h / (jnp.sum(h, axis=-1, keepdims=True) + eps)
        h = h / (jnp.sum(h, axis=-2, keepdims=True) + eps)
    return h


class HyperConnection(nn.Module):
    """The mixing matrices of one branch: ``u, h_post, h_res =
    hc(X)`` for the stream ``X (..., mult, dim)`` float32 — the branch
    input ``u (..., dim)``, ``h_post (..., mult)`` and ``h_res (...,
    mult, mult)``; :func:`mix_back` writes the branch's output back."""
    mult: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    norm_eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)

    @nn.compact
    def __call__(self, x):
        m, dim = x.shape[-2:]
        if m != self.mult:
            raise ValueError(f'stream has {m} rows, mult is {self.mult}')
        # One (mult·dim, 2·mult + mult²) product: Phi_pre, Phi_post and
        # Phi_res side by side, as are their biases; one alpha a part.
        phi = self.param('phi', nn.initializers.lecun_normal(),
                         (m * dim, 2 * m + m * m), jnp.float32)
        bias = self.param('bias', nn.initializers.zeros_init(),
                          (2 * m + m * m,), jnp.float32)
        alpha = self.param('alpha', nn.initializers.constant(0.01),
                           (3,), jnp.float32)
        with device_scope('lm.hc'):
            x = x.astype(jnp.float32)
            flat = x.reshape(*x.shape[:-2], m * dim)
            flat = flat * lax.rsqrt(
                jnp.mean(jnp.square(flat), -1, keepdims=True)
                + self.norm_eps)
            h = jnp.dot(flat, phi.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
            h = h * jnp.repeat(alpha, jnp.array([m, m, m * m]),
                               total_repeat_length=2 * m + m * m) + bias
            h_pre = jax.nn.sigmoid(h[..., :m])
            h_post = 2.0 * jax.nn.sigmoid(h[..., m:2 * m])
            h_res = sinkhorn(
                jnp.clip(h[..., 2 * m:], *self.clamp).reshape(
                    *h.shape[:-1], m, m),
                self.sinkhorn_iters, self.eps)
            u = jnp.einsum('...m,...md->...d', h_pre, x)
            return u, h_post, h_res


def mix_back(x, y, h_post, h_res):
    """``H_res X + H_post^T y``: the stream after a branch whose output
    is ``y (..., dim)``."""
    with device_scope('lm.hc'):
        return (jnp.einsum('...nm,...md->...nd', h_res, x)
                + h_post[..., :, None] * y.astype(jnp.float32)[..., None, :])
