"""The plain reference of the ``nemotron_h`` architecture (Nemotron 3
Super 120B-A12B): a sequential decoder in float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernel, no cache, no grouped
matmul, no chunked form of the recurrence and nothing imported from the
program. Every layer is ``x' = x + f(h)``, ``h = RMSNorm(x)`` (eps
``layer_norm_epsilon``, a scale; ONE norm a layer), with ``f`` by the
layer's letter in ``hybrid_override_pattern``:

``M`` (Mamba-2; ``d_inner`` = heads x head_dim, ``G`` groups, state
``N``, ``K`` taps):

    [z | xBC | dt] = h W_in            (d_inner | d_inner + 2 G N | heads)
    xBC_t = silu(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j})   four shifted products
    [x | B | C] = xBC                  head i reads group i // (heads / G)
    dt_t = softplus(dt_t + dt_bias)    a_t = exp(-dt_t exp(A_log))
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t     a head: (head_dim, N); t by t
    y_t = S_t C_t + D x_t
    f = GroupRMSNorm_G(y silu(z)) W_out

``*`` (attention): ``q = h Wq`` (32 x 128), ``k = h Wk``, ``v = h Wv``
(2 x 128), NO rotation, causal (the mask a comparison of positions),
``f = softmax(q k^T / sqrt(128)) v Wo``; query head g uses KV head
g // 16.

``E`` (latent experts): ``s = sigmoid(h W_r)`` float32 over all
``router_width`` experts; ``P = topk(s + b)``; ``g_i = scaling s_i /
sum_{j in P} s_j``; ``u = h W_dn``; ``R = sum_{i in P, held here} g_i
W2_i relu(W1_i u)^2``; ``S = W2_s relu(W1_s h)^2``; ``f = R W_up + S``.

``logits = RMSNorm_f(x_L) W_head`` (untied) over the vocabulary slice
held. What the configuration file lists as assumed (no rotation, where
the latent projections sit, a float32 state) is what is assumed here.

The chip's SHARE: the router scores and picks over all ``router_width``
experts; ``R`` sums the picks that fall in ``experts_held`` (the weights
given hold those experts alone). A top-k pick is a discrete decision: a
caller that compares logits feeds the served program's picks back
(``forced_picks``), as it feeds its tokens back, and judges the picks
apart by this file's own router scores (``route``'s regret), as
``reference/xing4.py`` does.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1`` and ONE of ``ssm`` (``in_proj``,
``conv_kernel (K, channels)``: row j multiplies the input K - 1 - j
steps back, ``conv_bias``, ``dt_bias``, ``A_log``, ``D``,
``norm_scale``, ``out_proj``), ``attn`` (the module's K-first names:
``keys`` = Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo)
or ``moe`` (``router``, ``router_bias``, ``latent_down``, ``latent_up``,
``w_up`` / ``w_down`` stacked over the held experts, ``shared``). Every
leaf is widened to float32 where it is used. Each layer rewrites the
stream block by block IN PLACE; a Mamba layer carries its state and the
convolution's last inputs from block to block and steps the recurrence
one token at a time inside a block; the attention layer first takes keys
and values of every row. The LAST layer rewrites only the rows whose
logits are asked for, where it carries nothing (it is the attention
layer in the period held).

The control (``operands_in``) rounds every matmul's operands to a lower
precision AND the recurrence's: the state as it is read each step, the
decay and the outer product's factors, so a state kept below float32
shows as the matmuls' rounding does.
"""

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 128

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


def rms(x, eps, scale):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * f32(scale)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def kinds(cfg):
    return cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: this architecture rotates nothing and adds nothing to its
    attention scores (the Mamba-2 layers carry the order)."""
    return None


# -- Mamba-2 ------------------------------------------------------------------

def ssm_sizes(cfg):
    heads, p = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    return heads, p, cfg['n_groups'], cfg['ssm_state_size']


def recurrence(cfg, sp, x, b, c, dt, state):
    """The selective recurrence, literally: one token a step of a scan
    over time. ``x (n, H, P)``, ``b``, ``c (n, G, N)``, ``dt (n, H)``
    (already ``softplus(. + dt_bias)``), ``state (H, P, N)``. Returns
    ``y (n, H, P)`` (with the ``D x`` skip) and the final state."""
    heads, p, groups, n = ssm_sizes(cfg)
    decay_rate = jnp.exp(f32(sp['A_log']))

    def step(s, args):
        x_t, b_t, c_t, dt_t = args
        a_t = jnp.exp(-dt_t * decay_rate)                      # (H,)
        b_h = jnp.repeat(b_t, heads // groups, axis=0)         # (H, N)
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        s = (lowp(a_t)[:, None, None] * lowp(s)
             + lowp(dt_t[:, None] * x_t)[:, :, None]
             * lowp(b_h)[:, None, :])
        y_t = jnp.sum(lowp(s) * lowp(c_h)[:, None, :], axis=-1)
        return s, y_t + f32(sp['D'])[:, None] * x_t

    state, y = lax.scan(step, state, (x, b, c, dt))
    return y, state


def ssm_block(cfg, sp, h, state, window):
    """``f`` of a Mamba-2 layer on the normed rows ``h (n, d)``,
    continuing ``state (H, P, N)`` and ``window (K - 1, channels)``, the
    convolution's inputs before the block. Returns ``f (n, d)``, the
    state and the window after it."""
    heads, p, groups, n = ssm_sizes(cfg)
    inner, taps = heads * p, cfg['conv_kernel']
    rows = h.shape[0]
    zxd = mm(h, sp['in_proj']['kernel'])
    z, xbc, dt = jnp.split(zxd, [inner, 2 * inner + 2 * groups * n], -1)
    seen = jnp.concatenate([window, xbc], axis=0)
    w = f32(sp['conv_kernel'])
    conv = f32(sp['conv_bias'])
    for j in range(taps):                     # four shifted products
        conv = conv + lowp(w[j]) * lowp(seen[j:j + rows])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], -1)
    dt = jax.nn.softplus(dt + f32(sp['dt_bias']))
    y, state = recurrence(
        cfg, sp, x.reshape(rows, heads, p), b.reshape(rows, groups, n),
        c.reshape(rows, groups, n), dt, state)
    y = y.reshape(rows, inner) * jax.nn.silu(z)
    y = rms(y.reshape(rows, groups, -1), cfg['layer_norm_epsilon'],
            f32(sp['norm_scale']).reshape(groups, -1)).reshape(rows, inner)
    return mm(y, sp['out_proj']['kernel']), state, seen[rows:]


# -- attention ----------------------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def keys_values(cfg, ap, h):
    kv = cfg['num_key_value_heads']
    return (heads_of(mm(h, ap['queries']['kernel']), kv),
            heads_of(mm(h, ap['values']['kernel']), kv))


def attend(cfg, ap, h, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``h (n, d)`` at ``positions``
    over ``keys`` / ``values (KV heads, S, 128)`` at ``key_positions``;
    nothing is rotated."""
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = heads_of(mm(h, ap['keys']['kernel']), heads)
    seen = positions[:, None] >= key_positions[None, :]
    scale = 1.0 / math.sqrt(cfg['head_dim'])

    def group(args):                  # one KV head, its query heads
        qg, k, v = args
        s = jnp.einsum('gqd,sd->gqs', lowp(qg), lowp(k)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum('gqs,sd->gqd', lowp(p), lowp(v))

    ctx = lax.map(group, (q.reshape(kv, heads // kv, *q.shape[1:]),
                          keys, values))
    ctx = ctx.reshape(heads, *ctx.shape[2:]).transpose(1, 0, 2)
    return mm(ctx.reshape(ctx.shape[0], -1), ap['composition']['kernel'])


# -- latent experts -------------------------------------------------------------

def router_width(cfg):
    return cfg['published']['n_routed_experts']


def held(cfg):
    return tuple(cfg.get('experts_held') or (0, router_width(cfg)))


def route(cfg, mp, h, forced=None):
    """Gates ``(n, router_width)`` (zero where not picked), the picks
    ``(n, k)`` and the regret ``(n,)``: sigmoid scores, the top-k of the
    scores plus the correction bias, the picked scores normalised to sum
    to one, times the scaling factor. ``forced (n, k)``: gate THESE
    experts (the served program's own picks); the picks returned are
    still the reference's own, and the regret is how far the worst
    forced pick's biased score lies below the reference's k-th best."""
    scores = jax.nn.sigmoid(h @ f32(mp['router']))
    biased = scores + f32(mp['router_bias'])
    best, own = lax.top_k(biased, cfg['num_experts_per_tok'])
    picked = own if forced is None else forced
    regret = best[:, -1] - jnp.min(
        jnp.take_along_axis(biased, picked, -1), -1)
    g = jnp.take_along_axis(scores, picked, -1)
    if cfg['norm_topk_prob']:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * cfg['routed_scaling_factor']
    onehot = jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def plain(up, down, x):
    return mm(relu2(mm(x, up)), down)


def expert_layer(cfg, mp, h, forced=None):
    """``(sum_{e held} gate_e E_e(h W_dn)) W_up + E_shared(h)``, the
    picks and the regret (``route``): every held expert runs on every
    row, its gate zero where it was not picked."""
    gates, picked, regret = route(cfg, mp, h, forced)
    lo, hi = held(cfg)
    u = mm(h, mp['latent_down']['kernel'])

    def one(total, e):
        return total + e[2][:, None] * plain(e[0], e[1], u), None

    r, _ = lax.scan(one, jnp.zeros_like(u),
                    (mp['w_up'], mp['w_down'], gates[:, lo:hi].T))
    y = mm(r, mp['latent_up']['kernel'])
    y = y + plain(mp['shared']['up']['kernel'],
                  mp['shared']['down']['kernel'], h)
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def _blocks(t):
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    return block


def ssm_layer(cfg, lp, x):
    """A Mamba-2 layer over the stream ``x (T, d)``, block by block in
    place, the state and the window carried."""
    t = x.shape[0]
    block = _blocks(t)
    heads, p, groups, n = ssm_sizes(cfg)
    channels = heads * p + 2 * groups * n

    def rewrite(i, carry):
        x, state, window = carry
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        h = rms(xb, cfg['layer_norm_epsilon'], lp['ln1']['scale'])
        f, state, window = ssm_block(cfg, lp['ssm'], h, state, window)
        return (lax.dynamic_update_slice_in_dim(x, xb + f, i * block, 0),
                state, window)

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((heads, p, n)),
        jnp.zeros((cfg['conv_kernel'] - 1, channels))))[0]


def attention_layer(cfg, lp, x, rows_from=0):
    t = x.shape[0]
    block = _blocks(t)
    positions = jnp.arange(t)

    def norm(xb):
        return rms(xb, cfg['layer_norm_epsilon'], lp['ln1']['scale'])

    keys, values = lax.map(
        lambda xb: keys_values(cfg, lp['attn'], norm(xb)),
        x.reshape(t // block, block, -1))
    # (blocks, KV heads, block, 128) -> (KV heads, T, 128)
    keys = keys.transpose(1, 0, 2, 3).reshape(keys.shape[1], t, -1)
    values = values.transpose(1, 0, 2, 3).reshape(values.shape[1], t, -1)

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        a = attend(cfg, lp['attn'], norm(xb), i * block + jnp.arange(block),
                   keys, values, positions)
        return lax.dynamic_update_slice_in_dim(x, xb + a, i * block, 0)

    return lax.fori_loop(rows_from // block, t // block, rewrite, x)


def experts_layer(cfg, lp, x, forced=None):
    """An expert layer over the stream: the new stream, the layer's own
    picks ``(T, k)`` and the regret ``(T,)``."""
    t = x.shape[0]
    block = _blocks(t)
    k = cfg['num_experts_per_tok']

    def rewrite(i, carry):
        x, picks, regrets = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        h = rms(xb, cfg['layer_norm_epsilon'], lp['ln1']['scale'])
        y, picked, regret = expert_layer(
            cfg, lp['moe'], h, None if forced is None else
            lax.dynamic_slice_in_dim(forced, start, block, 0))
        return (lax.dynamic_update_slice_in_dim(x, xb + y, start, 0),
                lax.dynamic_update_slice_in_dim(
                    picks, picked.astype(jnp.int32), start, 0),
                lax.dynamic_update_slice_in_dim(regrets, regret, start, 0))

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((t, k), jnp.int32), jnp.zeros((t,))))


def stack(cfg, sp, x, forced=None, last=None):
    """Every layer over the stream; returns it, the expert layers' own
    picks ``(expert layers, T, k)`` and the regrets ``(expert layers,
    T)``. ``last``: only the final ``last`` rows are wanted, so a last
    layer that carries nothing (attention) rewrites those alone.
    ``forced (expert layers, T, k)``: see ``route``."""
    picks, regrets = [], []
    names = kinds(cfg)
    for i, kind in enumerate(names):
        lp = sp[f'block_{i}']
        if kind == 'M':
            x = ssm_layer(cfg, lp, x)
        elif kind == '*':
            rows_from = 0
            if last is not None and i == len(names) - 1:
                rows_from = (x.shape[0] - last) // ROW_BLOCK * ROW_BLOCK
            x = attention_layer(cfg, lp, x, rows_from)
        elif kind == 'E':
            x, picked, regret = experts_layer(
                cfg, lp, x, None if forced is None else forced[len(picks)])
            picks.append(picked)
            regrets.append(regret)
        else:
            raise ValueError(f'layer {i}: no kind {kind!r} in nemotron_h')
    return x, jnp.stack(picks), jnp.stack(regrets)


def head(cfg, norm_scale, kernel, x):
    return mm(rms(x, cfg['layer_norm_epsilon'], norm_scale), kernel)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None):
    """Logits ``(last, vocab held)`` of the final ``last`` positions of
    ``tokens (T,)``, the expert layers' own picks at every position
    ``(expert layers, T, k)`` and the regret of the forced ones
    ``(expert layers, T)``. ``forced_picks (expert layers, T, k)``: see
    ``route``."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            operands_in(operand_dtype):
        x = jax.jit(lambda e, t: f32(e[t]))(p['embed']['embedding'],
                                            tokens)
        x, picks, regrets = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, last),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, k, x: head(cfg, n, k, x))(
            p['ln_f']['scale'], p['lm_head_kernel'], x[-last:])
    return logits, picks, regrets
