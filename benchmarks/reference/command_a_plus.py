"""The plain reference of the ``cohere2_moe`` architecture (Command A+,
``command-a-plus-05-2026``): a sequential decoder in float32
``jax.numpy`` at ``highest`` matmul precision, with no kernel, no cache,
no grouped matmul and nothing imported from the program. One layer, on a
token's residual ``x (d,)`` with ``h = LN(x)`` (Cohere's LayerNorm:
mean-subtracting, a scale, no bias; ONE a layer):

    q = h Wq (128 heads x 128)   k = h Wk, v = h Wv (8 heads x 128)
    sliding_attention layers (l mod 4 in {0, 1, 2}): interleaved-pair
      RoPE over the whole head on q and k (theta 50000); key j is seen by
      query i iff 0 <= i - j < sliding_window
    full_attention layers (l mod 4 == 3): no rotation, causal
    A = softmax(q k^T / sqrt(128)) v · Wo       query head g uses KV head g // 16
    s = sigmoid(h W_r) over all ``router_width`` experts, float32
    P = top8(s)          g_i = s_i / sum_{j in P} s_j
    R = sum_{i in P, held here} g_i E_i(h)     E(h) = W_down(silu(W_gate h) * W_up h)
    S = (1 / 4) sum_{j < 4} E^sh_j(h)
    x' = x + A + R + S                          (the parallel block)

and ``logits = logit_scale · LN_f(x_L) · Emb^T`` (tied) over the
vocabulary slice held. The banded and the causal mask are written out as
comparisons of positions. What the configuration file lists as assumed
(one expert's width, the reading of "average", the pick without a bias,
no positions on full layers) is what is assumed here.

The chip's SHARE: the router scores and picks over all ``router_width``
experts; ``R`` sums the picks that fall in ``experts_held`` (the weights
given hold those experts alone) and what the absent ones would add is
left out, as the program leaves it out. A top-k pick is a discrete
decision: a caller that compares logits feeds the served program's
picks back (``forced_picks``), as it feeds its tokens back, and judges
the picks apart by this file's own router scores (``route``'s regret),
as ``reference/xing4.py`` does.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``), ``attn`` with the module's K-first names
(``keys`` = Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo),
``moe`` with ``router``, ``w_gate`` / ``w_up`` / ``w_down`` stacked over
the held experts, and ``shared``: the four shared experts side by side
in one gated MLP ``4 x 4096`` wide, taken apart here. Every leaf is
widened to float32 where it is used. A layer first takes keys and values
of every row (a map over row blocks), then rewrites the stream block by
block in place; a block's scores are taken one KV head (16 query heads)
at a time, over the band's rows alone in a window layer. The LAST layer
rewrites only the rows whose logits are asked for: no later layer reads
the others.
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


def layer_norm(x, eps, scale):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * f32(scale)


def kinds(cfg):
    return cfg['layer_types'][:cfg['num_hidden_layers']]


def rotate(x, positions, theta):
    """Interleaved (GPT-J) pairs ``(2i, 2i + 1)`` of ``x (..., T, d)``
    turned by ``positions (T,) * theta^(-2i/d)``, over the whole head
    (``rotary_pct`` 1)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: this architecture adds nothing to its attention scores."""
    return None


# -- attention ----------------------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def keys_values(cfg, ap, h, positions, sliding):
    """``k, v (8, n, 128)`` of the normed rows ``h (n, d)``; the keys
    rotated in a sliding layer."""
    kv = cfg['num_key_value_heads']
    k = heads_of(mm(h, ap['queries']['kernel']), kv)
    v = heads_of(mm(h, ap['values']['kernel']), kv)
    if sliding:
        k = rotate(k, positions, float(cfg['rope_theta']))
    return k, v


def attend(cfg, ap, h, positions, keys, values, key_positions, sliding):
    """Attention of the normed rows ``h (n, d)`` at ``positions`` over
    ``keys`` / ``values (8, S, 128)`` at ``key_positions``: causal, and
    in a sliding layer within the window."""
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = heads_of(mm(h, ap['keys']['kernel']), heads)
    if sliding:
        q = rotate(q, positions, float(cfg['rope_theta']))
    dist = positions[:, None] - key_positions[None, :]
    seen = dist >= 0
    if sliding:
        seen = seen & (dist < cfg['sliding_window'])
    scale = 1.0 / math.sqrt(cfg['head_dim'])

    def group(args):                  # one KV head, its 16 query heads
        qg, k, v = args
        s = jnp.einsum('gqd,sd->gqs', lowp(qg), lowp(k)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum('gqs,sd->gqd', lowp(p), lowp(v))

    ctx = lax.map(group, (q.reshape(kv, heads // kv, *q.shape[1:]),
                          keys, values))
    ctx = ctx.reshape(heads, *ctx.shape[2:]).transpose(1, 0, 2)
    return mm(ctx.reshape(ctx.shape[0], -1), ap['composition']['kernel'])


# -- feed-forward -------------------------------------------------------------

def gated(gate, up, down, x):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def router_width(cfg):
    return cfg['published']['num_experts']


def held(cfg):
    return tuple(cfg.get('experts_held') or (0, router_width(cfg)))


def route(cfg, mp, x, forced=None):
    """Gates ``(n, router_width)`` (zero where not picked), the picks
    ``(n, k)`` and the regret ``(n,)``: sigmoid scores, their top-k, the
    picked scores normalised to sum to one. ``forced (n, k)``: gate
    THESE experts (the served program's own picks); the picks returned
    are still the reference's own, and the regret is how far the worst
    forced pick's score lies below the reference's k-th best."""
    scores = jax.nn.sigmoid(x @ f32(mp['router']))
    best, own = lax.top_k(scores, cfg['num_experts_per_tok'])
    picked = own if forced is None else forced
    regret = best[:, -1] - jnp.min(
        jnp.take_along_axis(scores, picked, -1), -1)
    g = jnp.take_along_axis(scores, picked, -1)
    if cfg['norm_topk_prob']:
        g = g / jnp.sum(g, -1, keepdims=True)
    onehot = jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def shared_mean(cfg, sp, x):
    """The mean of the shared experts' outputs: expert ``j`` is columns
    ``j·w … (j + 1)·w`` of the gate and up kernels and those rows of the
    down kernel."""
    n, w = cfg['num_shared_experts'], cfg['intermediate_size']
    total = jnp.zeros_like(x)
    for j in range(n):
        cols = slice(j * w, (j + 1) * w)
        total = total + gated(sp['gate']['kernel'][:, cols],
                              sp['up']['kernel'][:, cols],
                              sp['down']['kernel'][cols], x)
    return total / n


def expert_layer(cfg, mp, x, forced=None):
    """``sum_{e held} gate_e(x) E_e(x) + mean_j E^sh_j(x)``, the picks
    and the regret (``route``): every held expert runs on every row, its
    gate zero where it was not picked."""
    gates, picked, regret = route(cfg, mp, x, forced)
    lo, hi = held(cfg)

    def one(total, e):
        return total + e[3][:, None] * gated(e[0], e[1], e[2], x), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (mp['w_gate'], mp['w_up'], mp['w_down'],
                     gates[:, lo:hi].T))
    if cfg['num_shared_experts']:
        y = y + shared_mean(cfg, mp['shared'], x)
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def layer(cfg, lp, x, sliding, forced=None, rows_from=0):
    """One layer on the stream ``x (T, d)``, ``T`` a multiple of the row
    block: returns the new stream, the picks ``(T, k)`` and the regret
    ``(T,)``. Rows before ``rows_from`` (a multiple of the row block)
    are left as they were, with zero picks and regrets: the last
    layer's, which nothing reads. ``forced (T, k)``: see ``route``."""
    t = x.shape[0]
    block = min(ROW_BLOCK, t)
    if t % block or rows_from % block:
        raise ValueError(f'{t} rows from {rows_from} do not divide into '
                         f'blocks of {block}')
    eps, k = cfg['layer_norm_eps'], cfg['num_experts_per_tok']
    positions = jnp.arange(t)

    def norm(xb):
        return layer_norm(xb, eps, lp['ln1']['scale'])

    keys, values = lax.map(
        lambda a: keys_values(cfg, lp['attn'], norm(a[0]), a[1], sliding),
        (x.reshape(t // block, block, -1),
         positions.reshape(t // block, block)))
    # (blocks, 8, block, 128) -> (8, T, 128)
    keys = keys.transpose(1, 0, 2, 3).reshape(keys.shape[1], t, -1)
    values = values.transpose(1, 0, 2, 3).reshape(values.shape[1], t, -1)
    # A sliding layer's block reads the band's rows alone.
    band = cfg['sliding_window'] + block
    banded = sliding and band < t

    def rewrite(i, carry):
        x, picks, regrets = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        pos = start + jnp.arange(block)
        h = norm(xb)
        if banded:
            first = jnp.clip(start + block - band, 0, t - band)
            kb = lax.dynamic_slice_in_dim(keys, first, band, 1)
            vb = lax.dynamic_slice_in_dim(values, first, band, 1)
            kpos = first + jnp.arange(band)
        else:
            kb, vb, kpos = keys, values, positions
        a = attend(cfg, lp['attn'], h, pos, kb, vb, kpos, sliding)
        y, picked, regret = expert_layer(
            cfg, lp['moe'], h, None if forced is None else
            lax.dynamic_slice_in_dim(forced, start, block, 0))
        return (lax.dynamic_update_slice_in_dim(x, xb + a + y, start, 0),
                lax.dynamic_update_slice_in_dim(
                    picks, picked.astype(jnp.int32), start, 0),
                lax.dynamic_update_slice_in_dim(regrets, regret, start, 0))

    return lax.fori_loop(rows_from // block, t // block, rewrite,
                         (x, jnp.zeros((t, k), jnp.int32), jnp.zeros((t,))))


def stack(cfg, sp, x, forced=None, last=None):
    """Every layer over the stream; returns it, the layers' own picks
    ``(layers, T, k)``, the regrets ``(layers, T)`` and which (layer,
    row) pairs were computed ``(layers, T) bool``. ``last``: only the
    final ``last`` rows are wanted of the result, so the last layer
    rewrites those alone. ``forced (layers, T, k)``: see ``route``."""
    picks, regrets, judged = [], [], []
    names = kinds(cfg)
    for i, kind in enumerate(names):
        rows_from = 0
        if last is not None and i == len(names) - 1:
            rows_from = (x.shape[0] - last) // ROW_BLOCK * ROW_BLOCK
        x, picked, regret = layer(
            cfg, sp[f'block_{i}'], x, kind == 'sliding_attention',
            None if forced is None else forced[i], rows_from)
        picks.append(picked)
        regrets.append(regret)
        judged.append(jnp.arange(x.shape[0]) >= rows_from)
    return x, jnp.stack(picks), jnp.stack(regrets), jnp.stack(judged)


def head(cfg, norm_scale, table, x):
    """``logit_scale · LN_f(x) · Emb^T`` over the rows of the vocabulary
    held."""
    return cfg['logit_scale'] * mm(
        layer_norm(x, cfg['layer_norm_eps'], norm_scale), f32(table).T)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None):
    """Logits ``(last, vocab held)`` of the final ``last`` positions of
    ``tokens (T,)``, every layer's own picks at every position
    ``(layers, T, k)``, the regret of the forced ones ``(layers, T)``
    and which of those pairs were computed ``(layers, T) bool`` (the
    last layer's rows before the ones asked for are not).
    ``forced_picks (layers, T, k)``: see ``route``."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            operands_in(operand_dtype):
        x = jax.jit(lambda e, t: f32(e[t]))(p['embed']['embedding'],
                                            tokens)
        x, picks, regrets, judged = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, last),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, e, x: head(cfg, n, e, x))(
            p['ln_f']['scale'], p['embed']['embedding'], x[-last:])
    return logits, picks, regrets, judged
