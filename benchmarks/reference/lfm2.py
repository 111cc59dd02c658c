"""The plain reference of the ``lfm2_moe`` architecture (LFM2-8B-A1B): a
sequential decoder in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no kernel, no cache, no batching, no grouped matmul and
nothing imported from the program. With ``h`` the stream, every norm an
RMSNorm (eps ``norm_eps``, a scale) and no bias anywhere:

    h0 = E[token]
    layer l:  h = h + op_l(RMSNorm_1(h))
              h = h + ffn_l(RMSNorm_2(h))
    logits = RMSNorm_f(h_L) Eᵀ                      the TIED head

``op`` by ``layer_types``:

``conv`` (gated short convolution, ``K`` = ``conv_L_cache`` taps):

    [B | C | x̃] = u W_in                           (dim each, this order)
    w = B ⊙ x̃
    v_t = sum_{j<K} f_j ⊙ w_{t-K+1+j}      depthwise, causal, one filter
                                           a channel, NO activation
    op = (C ⊙ v) W_out

``full_attention``: ``q = u Wq`` (32 x 64), ``k = u Wk``, ``v = u Wv``
(8 x 64); ``q`` and ``k`` each through a per-head RMSNorm (eps
``norm_eps``, a ``(64,)`` scale) BEFORE the rotation; RoPE in the half
layout on all 64 channels (theta ``rope_theta``); causal (the mask a
comparison of positions), ``softmax(q kᵀ · 64^-1/2) v``, then ``Wo``;
query head g uses KV head g // 4.

``ffn``: the first ``num_dense_layers`` layers a SiLU-gated MLP
``intermediate_size`` wide; every other layer ``num_experts`` experts,
each a SiLU-gated MLP ``moe_intermediate_size`` wide, NO shared expert:

    s = sigmoid(u W_r)                       float32, all experts
    P = top-k(s + b)                         (``use_expert_bias``)
    g = s[P] / (Σ s[P] + 1e-6) · routed_scaling_factor   (``norm_topk_prob``)
    ffn = Σ_{i∈P} g_i W_down,i (silu(u W_gate,i) ⊙ u W_up,i)

A top-k pick is a discrete decision: a caller that compares logits feeds
the served program's picks back (``forced_picks``), as it feeds its
tokens back, and judges the picks apart by this file's own router scores
(``route``'s regret), as the other expert references do.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1``, ONE of ``conv`` (``in_proj``,
``conv_kernel (K, dim)``: row j multiplies the input K - 1 - j steps
back, ``out_proj``) and ``attn`` (the module's K-first names: ``keys`` =
Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo,
``keys_norm`` / ``queries_norm`` the per-head scales of q / k), ``ln2``
and ONE of ``mlp`` (``gate``, ``up``, ``down``) and ``moe`` (``router``,
``router_bias``, ``w_gate`` / ``w_up`` / ``w_down`` stacked over the
experts). Every leaf is widened to float32 where it is used. Each branch
rewrites the stream block by block IN PLACE, so that the published
widths fit the chip; a conv mixer carries its window — the last K - 1
rows of ``w`` — from block to block; the attention mixer first takes
keys and values of every row.

Two controls. ``operand_dtype`` (``common.operands_in``) rounds every
matmul's operands AND the convolution's — the rows of ``w`` as the
filter reads them, the filter itself — to a lower precision, so a window
kept below the stream's type shows as the matmuls' rounding does
(bfloat16 by ``lax.reduce_precision``: the TPU compiler may drop a
float32 -> bfloat16 -> float32 round trip; float8 by a convert pair).
``kv_dtype`` rounds the keys (normed, rotated) and the values alone, as
a cache below bfloat16 would hold them. A caller can also compare the
windows themselves (``logits_at``'s fourth result): every conv layer's
last K - 1 rows of ``w`` after the sequence's last real token — and what
a cache HOLDS (its fifth): every attention layer's keys (normed,
rotated) and values of every row, side by side as a packed slab keeps
them. The logits of 4 000 attended rows average a cache's rounding away;
the rows themselves do not.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import common

ROW_BLOCK = 128
GATE_EPS = 1e-6


def f32(x):
    return x.astype(jnp.float32)


def rounded(x, dtype):
    x = f32(x)
    if dtype is None:
        return x
    if jnp.dtype(dtype) == jnp.bfloat16:
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(jnp.float32)


def lowp(x):
    """``x`` in float32, rounded to the control's operand type
    (``common.operands_in``)."""
    return rounded(x, common._OPERANDS[-1])


def mm(a, b):
    return lowp(a) @ lowp(b)


def rms(x, eps, scale):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * f32(scale)


def kinds(cfg):
    return list(cfg['layer_types'][:cfg['num_hidden_layers']])


def expert_layers(cfg):
    return list(range(cfg['num_dense_layers'], cfg['num_hidden_layers']))


def norm(cfg, p, x):
    return rms(x, cfg['norm_eps'], p['scale'])


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: nothing is added to the attention scores."""
    return None


# -- the gated short convolution ----------------------------------------------

def conv_block(cfg, cp, u, window):
    """The conv mixer on the normed rows ``u (n, dim)`` behind ``window
    (K - 1, dim)``, the rows of ``w`` before the block: the mixer's
    output ``(n, dim)`` and every row the filter saw ``(K - 1 + n,
    dim)``, the window first."""
    taps, rows = cfg['conv_L_cache'], u.shape[0]
    b, c, x = jnp.split(mm(u, cp['in_proj']['kernel']), 3, -1)
    seen = jnp.concatenate([window, b * x], axis=0)
    f = f32(cp['conv_kernel'])
    v = jnp.zeros_like(b)
    for j in range(taps):                     # K shifted products
        v = v + lowp(f[j]) * lowp(seen[j:j + rows])
    return mm(c * v, cp['out_proj']['kernel']), seen


# -- attention ----------------------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def head_dim(cfg):
    return cfg['hidden_size'] // cfg['num_attention_heads']


def normed_rotated(cfg, x, scale, positions):
    """A per-head RMSNorm, then the rotation: ``x (heads, n, d)``."""
    return common.rope_half(rms(x, cfg['norm_eps'], scale), positions,
                            float(cfg['rope_theta']))


def keys_values(cfg, ap, u, positions, kv_dtype=None):
    kv = cfg['num_key_value_heads']
    k = normed_rotated(cfg, heads_of(mm(u, ap['queries']['kernel']), kv),
                       ap['queries_norm'], positions)
    v = heads_of(mm(u, ap['values']['kernel']), kv)
    return rounded(k, kv_dtype), rounded(v, kv_dtype)


def attend(cfg, ap, u, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``u (n, dim)`` at
    ``positions`` over ``keys`` / ``values (KV heads, S, head_dim)`` at
    ``key_positions``."""
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = normed_rotated(cfg, heads_of(mm(u, ap['keys']['kernel']), heads),
                       ap['keys_norm'], positions)
    seen = positions[:, None] >= key_positions[None, :]
    scale = 1.0 / math.sqrt(head_dim(cfg))

    def group(args):                  # one KV head, its query heads
        qg, k, v = args
        s = jnp.einsum('gqd,sd->gqs', lowp(qg), lowp(k)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum('gqs,sd->gqd', lowp(p), lowp(v))

    ctx = lax.map(group, (q.reshape(kv, heads // kv, *q.shape[1:]),
                          keys, values))
    ctx = ctx.reshape(heads, *ctx.shape[2:]).transpose(1, 0, 2)
    return mm(ctx.reshape(ctx.shape[0], -1), ap['composition']['kernel'])


# -- feed-forward ---------------------------------------------------------------

def gated(w_gate, w_up, w_down, u):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def route(cfg, mp, u, forced=None):
    """Gates ``(n, experts)`` (zero where not picked), the picks ``(n,
    k)`` and the regret ``(n,)``: sigmoid scores, the top-k of the scores
    plus the bias, the picked scores over their sum, times the scaling
    factor. ``forced (n, k)``: gate THESE experts (the served program's
    own picks); the picks returned are still the reference's own, and
    the regret is how far the worst forced pick's biased score lies below
    the reference's k-th best."""
    scores = jax.nn.sigmoid(u @ f32(mp['router']))
    biased = scores
    if cfg['use_expert_bias']:
        biased = scores + f32(mp['router_bias'])
    best, own = lax.top_k(biased, cfg['num_experts_per_tok'])
    picked = own if forced is None else forced
    regret = best[:, -1] - jnp.min(
        jnp.take_along_axis(biased, picked, -1), -1)
    g = jnp.take_along_axis(scores, picked, -1)
    if cfg['norm_topk_prob']:
        g = g / (jnp.sum(g, -1, keepdims=True) + GATE_EPS)
    g = g * cfg['routed_scaling_factor']
    onehot = jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def expert_layer(cfg, mp, u, forced=None):
    """``sum_e gate_e E_e(u)``, the picks and the regret (``route``):
    every expert runs on every row, its gate zero where it was not
    picked."""
    gates, picked, regret = route(cfg, mp, u, forced)

    def one(total, e):
        return total + e[3][:, None] * gated(e[0], e[1], e[2], u), None

    y, _ = lax.scan(one, jnp.zeros_like(u), (
        mp['w_gate'], mp['w_up'], mp['w_down'], gates.T))
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def _blocks(t):
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    return block


def conv_branch(cfg, lp, x, valid=None):
    """``x + conv(RMSNorm_1(x))`` over the stream ``x (T, dim)``, block
    by block in place, the window carried. Returns the stream and the
    window after row ``valid - 1`` (default: the last): rows ``valid -
    K + 1 … valid - 1`` of ``w``."""
    t = x.shape[0]
    block = _blocks(t)
    keep = cfg['conv_L_cache'] - 1
    valid = t if valid is None else valid

    def rewrite(i, carry):
        x, window, kept = carry
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        f, seen = conv_block(cfg, lp['conv'], norm(cfg, lp['ln1'], xb),
                             window)
        # seen[j] is row i·block + j − keep of w: the window after row
        # valid − 1 starts at seen[valid − i·block].
        at = valid - i * block
        here = jnp.logical_and(at > 0, at <= block)
        kept = jnp.where(here, lax.dynamic_slice_in_dim(
            seen, jnp.clip(at, 0, block), keep, 0), kept)
        return (lax.dynamic_update_slice_in_dim(x, xb + f, i * block, 0),
                seen[block:], kept)

    zeros = jnp.zeros((keep, x.shape[1]))
    x, _, kept = lax.fori_loop(0, t // block, rewrite, (x, zeros, zeros))
    return x, kept


def attention_branch(cfg, lp, x, kv_dtype=None):
    """``x + attention(RMSNorm_1(x))`` and the rows attended, ``[k | v]
    (KV heads, T, 2 d)``."""
    t = x.shape[0]
    block = _blocks(t)
    positions = jnp.arange(t)

    keys, values = lax.map(
        lambda a: keys_values(cfg, lp['attn'], norm(cfg, lp['ln1'], a[0]),
                              a[1], kv_dtype),
        (x.reshape(t // block, block, -1),
         positions.reshape(t // block, block)))
    # (blocks, KV heads, block, d) -> (KV heads, T, d)
    keys = keys.transpose(1, 0, 2, 3).reshape(keys.shape[1], t, -1)
    values = values.transpose(1, 0, 2, 3).reshape(values.shape[1], t, -1)

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        a = attend(cfg, lp['attn'], norm(cfg, lp['ln1'], xb),
                   i * block + jnp.arange(block), keys, values, positions)
        return lax.dynamic_update_slice_in_dim(x, xb + a, i * block, 0)

    return (lax.fori_loop(0, t // block, rewrite, x),
            jnp.concatenate([keys, values], axis=-1))


def mlp_branch(cfg, lp, x):
    """``x + mlp(RMSNorm_2(x))``: a dense layer's feed-forward."""
    t = x.shape[0]
    block = _blocks(t)
    mp = lp['mlp']

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        y = gated(mp['gate']['kernel'], mp['up']['kernel'],
                  mp['down']['kernel'], norm(cfg, lp['ln2'], xb))
        return lax.dynamic_update_slice_in_dim(x, xb + y, i * block, 0)

    return lax.fori_loop(0, t // block, rewrite, x)


def experts_branch(cfg, lp, x, forced=None):
    """``x + experts(RMSNorm_2(x))`` over the stream: the new stream,
    the layer's own picks ``(T, k)`` and the regret ``(T,)``."""
    t = x.shape[0]
    block = _blocks(t)
    k = cfg['num_experts_per_tok']

    def rewrite(i, carry):
        x, picks, regrets = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        y, picked, regret = expert_layer(
            cfg, lp['moe'], norm(cfg, lp['ln2'], xb),
            None if forced is None else
            lax.dynamic_slice_in_dim(forced, start, block, 0))
        return (lax.dynamic_update_slice_in_dim(x, xb + y, start, 0),
                lax.dynamic_update_slice_in_dim(
                    picks, picked.astype(jnp.int32), start, 0),
                lax.dynamic_update_slice_in_dim(regrets, regret, start, 0))

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((t, k), jnp.int32), jnp.zeros((t,))))


def mixer_branch(cfg, kind, lp, x, valid=None, kv_dtype=None):
    """Layer ``lp``'s first branch: the stream and what the layer's
    cache would hold — a conv layer's window after row ``valid - 1``, an
    attention layer's rows."""
    if kind == 'conv':
        return conv_branch(cfg, lp, x, valid)
    return attention_branch(cfg, lp, x, kv_dtype)


def stack(cfg, sp, x, forced=None, valid=None, kv_dtype=None):
    """Every layer over the stream; returns it, the expert layers' own
    picks ``(expert layers, T, k)``, the regrets ``(expert layers, T)``,
    the conv layers' windows after row ``valid - 1`` ``(conv layers, K -
    1, dim)`` and the attention layers' rows ``(attention layers, KV
    heads, T, 2 d)``. ``forced (expert layers, T, k)``: see
    ``route``."""
    picks, regrets, windows, rows = [], [], [], []
    experts = expert_layers(cfg)
    for i, kind in enumerate(kinds(cfg)):
        lp = sp[f'block_{i}']
        x, held = mixer_branch(cfg, kind, lp, x, valid, kv_dtype)
        (windows if kind == 'conv' else rows).append(held)
        if i not in experts:
            x = mlp_branch(cfg, lp, x)
            continue
        x, picked, regret = experts_branch(
            cfg, lp, x, None if forced is None
            else forced[experts.index(i)])
        picks.append(picked)
        regrets.append(regret)
    return (x, jnp.stack(picks), jnp.stack(regrets), jnp.stack(windows),
            jnp.stack(rows))


def head(cfg, norm_scale, table, x):
    return mm(rms(x, cfg['norm_eps'], norm_scale), f32(table).T)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None, valid=None, kv_dtype=None):
    """Logits ``(last, vocab)`` of the final ``last`` positions of
    ``tokens (T,)``, the expert layers' own picks at every position
    ``(expert layers, T, k)``, the regret of the forced ones ``(expert
    layers, T)``, every conv layer's window after token ``valid - 1``
    (default: the last; rows behind it are padding) and every attention
    layer's rows ``[k | v]``. ``forced_picks
    (expert layers, T, k)``: see ``route``; ``kv_dtype``: the second
    control (module docstring)."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            common.operands_in(operand_dtype):
        x = jax.jit(lambda e, t: f32(e[t]))(p['embed']['embedding'], tokens)
        x, picks, regrets, windows, rows = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, valid, kv_dtype),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, w, x: head(cfg, n, w, x))(
            p['ln_f']['scale'], p['embed']['embedding'], x[-last:])
    return logits, picks, regrets, windows, rows
