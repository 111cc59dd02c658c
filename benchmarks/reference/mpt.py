"""MPT's attention particulars for the plain reference (``common.py``):
no positional embedding on q or k, multi-head attention, and ALiBi: head
``h`` adds ``slope_h * (key position - query position)`` to its scores,
with ``slope_h = 2^(-alibi_bias_max * (h + 1) / n_heads)``."""

import jax.numpy as jnp


def rotate(x, positions, sizes):
    return x


def score_bias(dist, sizes):
    slopes = jnp.asarray(sizes['attn_kwargs']['alibi_slopes'], jnp.float32)
    return slopes[:, None, None] * dist.astype(jnp.float32)[None]
