"""The plain reference of the ``xing4_0`` architecture (Xing4.0-29B-A4B):
a sequential decoder in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no kernel, no cache, no grouped matmul and nothing
imported from the program. What it computes, per layer, on a token's
residual stream ``X (hc_mult, d)``:

    hyper(F, X):  x~ = rms(vec X)   H~ = alpha * (x~ Phi) + b
                  H_pre = sigmoid(H~_pre)   H_post = 2 sigmoid(H~_post)
                  H_res = sinkhorn(clip(H~_res))
                  X' = H_res X + H_post^T F(rms_w(H_pre X))
    X <- hyper(attention, X);   X <- hyper(feed-forward, X)

attention (expanded multi-head latent attention, YaRN rotary on the
shared 64 dims, causal), the feed-forward (a gated SiLU MLP in the
leading ``first_k_dense_replace`` layers; after them a sigmoid router's
top-k of ``n_routed_experts`` gated MLPs as a DENSE sum over one-hot
gates, plus the shared expert), an untied head after the sum of the
streams and a final RMSNorm. A top-k pick is a discrete decision: where
two scores are nearly tied, rounding flips it and a quarter of the
token's routed output with it, so a caller that compares logits feeds
the served program's picks back (``forced_picks``), as it feeds its
tokens back, and judges the picks themselves apart, by this file's own
router scores (``route``'s regret). The module docstrings of
``models/latent.py``, ``models/moe.py`` and ``models/hyper.py`` give the
same equations; the configuration file lists what they assume.

The weight tree is the program's (the driver's shape table makes it),
in the type the configuration serves in; every leaf is widened to
float32 where it is used, so one bfloat16 copy is all that is held.
Everything but attention's keys is row-wise, so a layer first takes the
latent row of every token (a map over row blocks), expands keys and
values once, and then rewrites the stream block by block IN PLACE (a
block needs the other rows' keys and values only, which were taken from
the stream as it was): one 33 024-row session is 1.9 GB of stream, held
once.
"""

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 128

# The control: every matmul's operands rounded to a lower precision
# (float8_e4m3fn, the step below the cell's bfloat16), accumulated in
# float32. None is the reference itself.
_OPERANDS = [None]


@contextlib.contextmanager
def operands_in(dtype):
    _OPERANDS.append(dtype)
    try:
        yield
    finally:
        _OPERANDS.pop()


def lowp(x):
    dtype = _OPERANDS[-1]
    x = x.astype(jnp.float32)
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def mm(a, b):
    return lowp(a) @ lowp(b)


def f32(x):
    return x.astype(jnp.float32)


def rms(x, eps, scale=None):
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x if scale is None else x * f32(scale)


# -- rotary ---------------------------------------------------------------

def yarn_inv_freq(cfg):
    """DeepSeek-V3's ``YarnRotaryEmbedding`` frequencies over the
    rotary dims."""
    dim, base = cfg['qk_rope_head_dim'], float(cfg['rope_theta'])
    rs = cfg['rope_scaling']
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** exps
    inter = 1.0 / (rs['factor'] * base ** exps)

    def correction_dim(rotations):
        return (dim * math.log(rs['original_max_position_embeddings']
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(rs['beta_fast'])), 0)
    high = min(math.ceil(correction_dim(rs['beta_slow'])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotate(x, positions, cfg):
    """Interleaved pairs ``(2i, 2i+1)`` of ``x (..., T, rope)`` turned
    by ``positions (T,) * inv_freq[i]``; the magnitude is
    ``mscale(mscale) / mscale(mscale_all_dim)`` (1 here)."""
    rs = cfg['rope_scaling']
    mag = (yarn_mscale(rs['factor'], rs['mscale'])
           / yarn_mscale(rs['factor'], rs['mscale_all_dim']))
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def softmax_scale(cfg):
    rs = cfg['rope_scaling']
    m = yarn_mscale(rs['factor'], rs['mscale_all_dim'])
    return m * m / math.sqrt(cfg['qk_nope_head_dim']
                             + cfg['qk_rope_head_dim'])


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation (``tests/test_loader.py`` asks each cell's reference for
    it): this architecture adds nothing to its attention scores."""
    return None


# -- hyper-connections ------------------------------------------------------

def sinkhorn(logits, iters, eps):
    h = jnp.exp(logits)
    for _ in range(iters):
        h = h / (jnp.sum(h, -1, keepdims=True) + eps)
        h = h / (jnp.sum(h, -2, keepdims=True) + eps)
    return h


def hyper_matrices(cfg, p, x):
    """``H_pre (n, m)``, ``H_post (n, m)``, ``H_res (n, m, m)`` for the
    streams ``x (n, m, d)``."""
    n, m, d = x.shape
    flat = rms(x.reshape(n, m * d), cfg['rms_norm_eps'])
    h = flat @ f32(p['phi'])
    alpha, bias = f32(p['alpha']), f32(p['bias'])
    pre = alpha[0] * h[:, :m] + bias[:m]
    post = alpha[1] * h[:, m:2 * m] + bias[m:2 * m]
    res = (alpha[2] * h[:, 2 * m:] + bias[2 * m:]).reshape(n, m, m)
    res = jnp.clip(res, cfg['mhc_h_res_clamp_min'],
                   cfg['mhc_h_res_clamp_max'])
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, cfg['hc_sinkhorn_iters'], cfg['hc_eps']))


def hyper(cfg, p, x, branch):
    """``X' = H_res X + H_post^T branch(H_pre X)``."""
    h_pre, h_post, h_res = hyper_matrices(cfg, p, x)
    y = branch(jnp.einsum('nm,nmd->nd', h_pre, x))
    return (jnp.einsum('nkm,nmd->nkd', h_res, x)
            + h_post[:, :, None] * y[:, None, :])


# -- attention ----------------------------------------------------------------

def latent_row(cfg, ap, h, positions):
    """``[c_kv ; k_rope] (n, kv_lora_rank + rope)`` of the normed layer
    input ``h (n, d)``."""
    rank = cfg['kv_lora_rank']
    ckv = mm(h, ap['kv_a']['kernel'])
    c = rms(ckv[:, :rank], cfg['rms_norm_eps'], ap['kv_norm']['scale'])
    return jnp.concatenate([c, rotate(ckv[:, rank:], positions, cfg)], -1)


def expand(cfg, ap, rows):
    """Per-head keys ``(H, S, nope + rope)`` and values ``(H, S, v)``
    from the latent rows ``(S, rank + rope)``."""
    rank, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    heads = cfg['num_attention_heads']
    w = f32(ap['kv_b'])                               # (rank, H, nope + v)
    kv = jnp.einsum('sc,chd->hsd', lowp(rows[:, :rank]), lowp(w))
    k_rope = jnp.broadcast_to(rows[None, :, rank:],
                              (heads, rows.shape[0], rows.shape[1] - rank))
    return (jnp.concatenate([kv[..., :nope], k_rope], -1), kv[..., nope:])


def attend(cfg, ap, h, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``h (n, d)`` over the
    expanded keys and values."""
    heads, nope = cfg['num_attention_heads'], cfg['qk_nope_head_dim']
    rope = cfg['qk_rope_head_dim']
    cq = rms(mm(h, ap['q_a']['kernel']), cfg['rms_norm_eps'],
             ap['q_norm']['scale'])
    q = mm(cq, ap['q_b']['kernel']).reshape(-1, heads, nope + rope)
    q = q.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], positions, cfg)], -1)
    s = jnp.einsum('hqd,hsd->hqs', lowp(q), lowp(keys)) * softmax_scale(cfg)
    seen = key_positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum('hqs,hsd->qhd', lowp(p), lowp(values))
    return mm(ctx.reshape(ctx.shape[0], -1), ap['out']['kernel'])


# -- feed-forward -------------------------------------------------------------

def gated(p, x):
    return mm(jax.nn.silu(mm(x, p['gate']['kernel']))
              * mm(x, p['up']['kernel']), p['down']['kernel'])


def route(cfg, mp, x, forced=None):
    """One-hot gates ``(n, n_routed_experts)`` (zero where not picked),
    the picks ``(n, k)`` and the regret ``(n,)``: sigmoid scores, the
    top-k of score + correction bias, the picked scores normalised and
    scaled. ``forced (n, k)``: gate THESE experts (the served program's
    own picks, fed back as its tokens are); the picks returned are
    still the reference's own, and the regret is how far the worst
    forced pick's score + bias lies below the reference's k-th best: 0
    where the two sets agree, the width of the tie where they differ at
    a near-tie, large where the program's router decided wrongly."""
    scores = jax.nn.sigmoid(x @ f32(mp['router']))
    ranked = scores + f32(mp['router_bias'])
    best, own = lax.top_k(ranked, cfg['num_experts_per_tok'])
    picked = own if forced is None else forced
    regret = best[:, -1] - jnp.min(
        jnp.take_along_axis(ranked, picked, -1), -1)
    g = jnp.take_along_axis(scores, picked, -1)
    if cfg['norm_topk_prob']:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * cfg['routed_scaling_factor']
    onehot = jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def expert_layer(cfg, mp, x, forced=None):
    """``sum_e gate_e(x) E_e(x) + E_shared(x)``, the picks and the
    regret (``route``): every expert runs on every row, its gate zero
    where it was not picked."""
    gates, picked, regret = route(cfg, mp, x, forced)

    def one(total, e):
        w = {'gate': {'kernel': e[0]}, 'up': {'kernel': e[1]},
             'down': {'kernel': e[2]}}
        return total + e[3][:, None] * gated(w, x), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (mp['w_gate'], mp['w_up'], mp['w_down'], gates.T))
    if cfg['n_shared_experts']:
        y = y + gated(mp['shared'], x)
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def layer(cfg, lp, x, dense, forced=None):
    """One layer on the streams ``x (T, m, d)``, ``T`` a multiple of
    the row block; returns the new streams, the picks ``(T, k)`` and
    the regret ``(T,)`` (zeros for a dense layer). ``forced (T, k)``:
    see ``route``."""
    t = x.shape[0]
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    eps = cfg['rms_norm_eps']
    positions = jnp.arange(t)

    def normed_input(xb):
        h_pre, _, _ = hyper_matrices(cfg, lp['hc_attn'], xb)
        return rms(jnp.einsum('nm,nmd->nd', h_pre, xb), eps,
                   lp['ln1']['scale'])

    rows = lax.map(
        lambda a: latent_row(cfg, lp['attn'], normed_input(a[0]), a[1]),
        (x.reshape(t // block, block, *x.shape[1:]),
         positions.reshape(t // block, block)))
    keys, values = expand(cfg, lp['attn'], rows.reshape(t, -1))
    k = cfg['num_experts_per_tok']

    def rewrite(i, carry):
        x, picks, regrets = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        pos = start + jnp.arange(block)
        xb = hyper(cfg, lp['hc_attn'], xb, lambda u: attend(
            cfg, lp['attn'], rms(u, eps, lp['ln1']['scale']), pos, keys,
            values, positions))
        routed = [jnp.zeros((block, k), jnp.int32), jnp.zeros((block,))]

        def feed_forward(u):
            h = rms(u, eps, lp['ln2']['scale'])
            if dense:
                return gated(lp['mlp'], h)
            y, routed[0], routed[1] = expert_layer(
                cfg, lp['moe'], h, None if forced is None else
                lax.dynamic_slice_in_dim(forced, start, block, 0))
            return y
        xb = hyper(cfg, lp['hc_ffn'], xb, feed_forward)
        return (lax.dynamic_update_slice_in_dim(x, xb, start, 0),
                lax.dynamic_update_slice_in_dim(
                    picks, routed[0].astype(jnp.int32), start, 0),
                lax.dynamic_update_slice_in_dim(regrets, routed[1], start,
                                                0))

    return lax.fori_loop(0, t // block, rewrite,
                         (x, jnp.zeros((t, k), jnp.int32), jnp.zeros((t,))))


def streams(cfg, table, tokens):
    """The residual streams ``(T, hc_mult, d)``: ``hc_mult`` copies of
    the embedding."""
    x = f32(table[tokens])
    return jnp.broadcast_to(x[:, None, :],
                            (x.shape[0], cfg['hc_mult'], x.shape[1]))


def stack(cfg, sp, x, forced=None):
    """Every layer over the streams; returns them, the expert layers'
    own picks ``(expert layers, T, k)`` and the regrets ``(expert
    layers, T)``; a tree a layer
    (``block_i``), as the program keeps them. ``forced (expert layers,
    T, k)``: see ``route``."""
    n_dense = cfg['first_k_dense_replace']
    n_sparse = cfg['num_hidden_layers'] - n_dense
    for i in range(n_dense):
        x, _, _ = layer(cfg, sp[f'block_{i}'], x, dense=True)
    picks, regrets = [], []
    for i in range(n_sparse):
        x, picked, regret = layer(cfg, sp[f'block_{n_dense + i}'], x,
                                  False,
                                  None if forced is None else forced[i])
        picks.append(picked)
        regrets.append(regret)
    return x, jnp.stack(picks), jnp.stack(regrets)


def head(cfg, norm_scale, kernel, x):
    """Logits of the streams ``x (n, m, d)``: their sum, the final
    RMSNorm, the untied head."""
    return mm(rms(jnp.sum(x, axis=1), cfg['rms_norm_eps'], norm_scale),
              kernel)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              consume=False, forced_picks=None):
    """Logits ``(last, vocab)`` of the final ``last`` positions of
    ``tokens (T,)``, the expert layers' own picks at every position
    ``(expert layers, T, k)`` and the regret of the forced ones
    ``(expert layers, T)``. ``consume``: drop each part of ``params``
    once it has been used (the embedding, then the layers), so that at
    the published sizes the tree is never held whole beside the
    streams. ``forced_picks (expert layers, T, k)``: see ``route``."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            operands_in(operand_dtype):
        x = jax.jit(lambda e, t: streams(cfg, e, t))(
            p['embed']['embedding'], tokens)
        if consume:
            del p['embed']
        x, picks, regrets = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        if consume:
            del p['stack']
        logits = jax.jit(lambda n, k, x: head(cfg, n, k, x))(
            p['ln_f']['scale'], p['lm_head_kernel'], x[-last:])
    return logits, picks, regrets
