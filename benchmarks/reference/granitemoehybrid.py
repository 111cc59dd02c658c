"""The plain reference of the ``granitemoehybrid`` architecture (Granite
4.0-H Small 32B-A9B): a sequential decoder in float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernel, no cache, no grouped
matmul, no chunked form of the recurrence and nothing imported from the
program. With ``h`` the stream, ``r`` = ``residual_multiplier`` and
every norm an RMSNorm (eps ``rms_norm_eps``, a scale):

    h0 = embedding_multiplier · E[token]
    layer l:  h = h + r · mixer_l(RMSNorm_1(h))
              v = RMSNorm_2(h)
              s = v W_r                  float32, all ``router_width`` experts
              P = top-k(s);   g = softmax(s[P])          the RAW logits' top-k
              y = Σ_{i∈P, held here} g_i W_down,i (silu(v W_gate,i) ⊙ v W_up,i)
              y = y + W_down,s (silu(v W_gate,s) ⊙ v W_up,s)      shared MLP
              h = h + r · y
    logits = RMSNorm_f(h_L) Eᵀ / logits_scaling              the tied table

``mixer`` by the layer's name in ``layer_types``:

``mamba`` (Mamba-2; ``d_inner`` = heads x head_dim, ``G`` groups — ONE
here — state ``N``, ``K`` taps):

    [z | xBC | dt] = u W_in            (d_inner | d_inner + 2 G N | heads)
    xBC_t = silu(b_c + sum_{j<K} w_c[j] xBC_{t-K+1+j})   four shifted products
    [x | B | C] = xBC                  head i reads group i // (heads / G)
    dt_t = softplus(dt_t + dt_bias)    a_t = exp(-dt_t exp(A_log))
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t     a head: (head_dim, N); t by t
    y_t = S_t C_t + D x_t
    mixer = GroupRMSNorm_G(y silu(z)) W_out      one group: all of d_inner

``attention``: ``q = u Wq`` (32 x 128), ``k = u Wk``, ``v = u Wv`` (8 x
128), NO rotation, causal (the mask a comparison of positions), ``mixer
= softmax(q kᵀ · attention_multiplier) v Wo``; query head g uses KV head
g // 4.

Departures from the published description, each also in the
configuration file: the fused input matrix ``[a | b] = W_in v`` of an
expert and of the shared MLP is held as its two halves (``w_gate``,
``w_up``; the same numbers); the recurrent state is float32.

The chip's SHARE: the router scores and picks over all ``router_width``
experts; ``y`` sums the picks that fall in ``experts_held`` (the weights
given hold those experts alone). A top-k pick is a discrete decision: a
caller that compares logits feeds the served program's picks back
(``forced_picks``), as it feeds its tokens back, and judges the picks
apart by this file's own router logits (``route``'s regret), as
``reference/nemotron_h.py`` does.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1``, ONE of ``ssm`` (``in_proj``,
``conv_kernel (K, channels)``: row j multiplies the input K - 1 - j
steps back, ``conv_bias``, ``dt_bias``, ``A_log``, ``D``,
``norm_scale``, ``out_proj``) and ``attn`` (the module's K-first names:
``keys`` = Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo),
``ln2`` and ``moe`` (``router``, ``w_gate`` / ``w_up`` / ``w_down``
stacked over the held experts, ``shared``: ``gate``, ``up``, ``down``).
Every leaf is widened to float32 where it is used. Each branch rewrites
the stream block by block IN PLACE; a Mamba mixer carries its state and
the convolution's last inputs from block to block and steps the
recurrence one token at a time inside a block; the attention mixer first
takes keys and values of every row.

The control (``common.operands_in``) rounds every matmul's operands to a
lower precision AND the recurrence's: the state as it is read each step,
the decay and the outer product's factors, so a state kept below float32
shows as the matmuls' rounding does. A caller can also compare the
states themselves (``logits_at``'s fourth result): every recurrent layer's
state after the sequence's last real token.
"""

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import common

ROW_BLOCK = 128


def f32(x):
    return x.astype(jnp.float32)


def lowp(x):
    """``x`` in float32, rounded to the control's operand type
    (``common.operands_in``). bfloat16 is rounded by an explicit
    ``reduce_precision``: the TPU compiler may drop a float32 ->
    bfloat16 -> float32 round trip (excess precision is allowed), and
    did (chip, PR 36: a bfloat16 control that read as a sound run in
    every number)."""
    x = f32(x)
    dtype = common._OPERANDS[-1]
    if dtype is None:
        return x
    if jnp.dtype(dtype) == jnp.bfloat16:
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(jnp.float32)


def mm(a, b):
    return lowp(a) @ lowp(b)


def rms(x, eps, scale):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * f32(scale)


def kinds(cfg):
    return cfg['layer_types'][:cfg['num_hidden_layers']]


def norm(cfg, p, x):
    return rms(x, cfg['rms_norm_eps'], p['scale'])


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: this architecture rotates nothing and adds nothing to its
    attention scores (``position_embedding_type`` nope; the Mamba-2
    layers carry the order)."""
    return None


# -- Mamba-2 ------------------------------------------------------------------

def ssm_sizes(cfg):
    return (cfg['mamba_n_heads'], cfg['mamba_d_head'],
            cfg['mamba_n_groups'], cfg['mamba_d_state'])


def recurrence(cfg, sp, x, b, c, dt, state, live=None):
    """The selective recurrence, literally: one token a step of a scan
    over time. ``x (n, H, P)``, ``b``, ``c (n, G, N)``, ``dt (n, H)``
    (already ``softplus(. + dt_bias)``), ``state (H, P, N)``. Returns
    ``y (n, H, P)`` (with the ``D x`` skip) and the final state.
    ``live (n,)``: a row that is not live (padding behind the sequence's
    end) leaves the state as it was."""
    heads, p, groups, n = ssm_sizes(cfg)
    decay_rate = jnp.exp(f32(sp['A_log']))
    if live is None:
        live = jnp.ones((x.shape[0],), bool)

    def step(s, args):
        x_t, b_t, c_t, dt_t, live_t = args
        a_t = jnp.exp(-dt_t * decay_rate)                      # (H,)
        b_h = jnp.repeat(b_t, heads // groups, axis=0)         # (H, N)
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        new = (lowp(a_t)[:, None, None] * lowp(s)
               + lowp(dt_t[:, None] * x_t)[:, :, None]
               * lowp(b_h)[:, None, :])
        y_t = jnp.sum(lowp(new) * lowp(c_h)[:, None, :], axis=-1)
        return (jnp.where(live_t, new, s),
                y_t + f32(sp['D'])[:, None] * x_t)

    state, y = lax.scan(step, state, (x, b, c, dt, live))
    return y, state


def ssm_block(cfg, sp, u, state, window, live=None):
    """The Mamba-2 mixer on the normed rows ``u (n, d)``, continuing
    ``state (H, P, N)`` and ``window (K - 1, channels)``, the
    convolution's inputs before the block. Returns the mixer's output
    ``(n, d)``, the state and the window after it (``live``: see
    ``recurrence``)."""
    heads, p, groups, n = ssm_sizes(cfg)
    inner, taps = heads * p, cfg['mamba_d_conv']
    rows = u.shape[0]
    zxd = mm(u, sp['in_proj']['kernel'])
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
        c.reshape(rows, groups, n), dt, state, live)
    y = y.reshape(rows, inner) * jax.nn.silu(z)
    y = rms(y.reshape(rows, groups, -1), cfg['rms_norm_eps'],
            f32(sp['norm_scale']).reshape(groups, -1)).reshape(rows, inner)
    return mm(y, sp['out_proj']['kernel']), state, seen[rows:]


# -- attention ----------------------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def keys_values(cfg, ap, u):
    kv = cfg['num_key_value_heads']
    return (heads_of(mm(u, ap['queries']['kernel']), kv),
            heads_of(mm(u, ap['values']['kernel']), kv))


def attend(cfg, ap, u, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``u (n, d)`` at ``positions``
    over ``keys`` / ``values (KV heads, S, head_dim)`` at
    ``key_positions``; nothing is rotated; the scores are multiplied by
    ``attention_multiplier``."""
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = heads_of(mm(u, ap['keys']['kernel']), heads)
    seen = positions[:, None] >= key_positions[None, :]
    scale = cfg['attention_multiplier']

    def group(args):                  # one KV head, its query heads
        qg, k, v = args
        s = jnp.einsum('gqd,sd->gqs', lowp(qg), lowp(k)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum('gqs,sd->gqd', lowp(p), lowp(v))

    ctx = lax.map(group, (q.reshape(kv, heads // kv, *q.shape[1:]),
                          keys, values))
    ctx = ctx.reshape(heads, *ctx.shape[2:]).transpose(1, 0, 2)
    return mm(ctx.reshape(ctx.shape[0], -1), ap['composition']['kernel'])


# -- experts --------------------------------------------------------------------

def router_width(cfg):
    return cfg['published']['num_local_experts']


def held(cfg):
    return tuple(cfg.get('experts_held') or (0, router_width(cfg)))


def route(cfg, mp, v, forced=None):
    """Gates ``(n, router_width)`` (zero where not picked), the picks
    ``(n, k)`` and the regret ``(n,)``: the top-k of the raw logits, the
    gates the softmax of the picked logits. ``forced (n, k)``: gate
    THESE experts (the served program's own picks); the picks returned
    are still the reference's own, and the regret is how far the worst
    forced pick's logit lies below the reference's k-th best."""
    logits = v @ f32(mp['router'])
    best, own = lax.top_k(logits, cfg['num_experts_per_tok'])
    picked = own if forced is None else forced
    taken = jnp.take_along_axis(logits, picked, -1)
    regret = best[:, -1] - jnp.min(taken, -1)
    g = jax.nn.softmax(taken, axis=-1)
    onehot = jax.nn.one_hot(picked, logits.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def gated(w_gate, w_up, w_down, v):
    return mm(jax.nn.silu(mm(v, w_gate)) * mm(v, w_up), w_down)


def expert_layer(cfg, mp, v, forced=None):
    """``sum_{e held} gate_e E_e(v) + E_shared(v)``, the picks and the
    regret (``route``): every held expert runs on every row, its gate
    zero where it was not picked."""
    gates, picked, regret = route(cfg, mp, v, forced)
    lo, hi = held(cfg)

    def one(total, e):
        return total + e[3][:, None] * gated(e[0], e[1], e[2], v), None

    y, _ = lax.scan(one, jnp.zeros_like(v), (
        mp['w_gate'], mp['w_up'], mp['w_down'], gates[:, lo:hi].T))
    shared = mp['shared']
    y = y + gated(shared['gate']['kernel'], shared['up']['kernel'],
                  shared['down']['kernel'], v)
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def _blocks(t):
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    return block


def ssm_branch(cfg, lp, x, valid=None):
    """``x + r · mamba(RMSNorm_1(x))`` over the stream ``x (T, d)``,
    block by block in place, the state and the window carried. Returns
    the stream and the state after row ``valid - 1`` (default: the
    last)."""
    t = x.shape[0]
    block = _blocks(t)
    heads, p, groups, n = ssm_sizes(cfg)
    channels = heads * p + 2 * groups * n
    r = cfg['residual_multiplier']

    def rewrite(i, carry):
        x, state, window = carry
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        f, state, window = ssm_block(
            cfg, lp['ssm'], norm(cfg, lp['ln1'], xb), state, window,
            i * block + jnp.arange(block) < (t if valid is None else valid))
        return (lax.dynamic_update_slice_in_dim(x, xb + r * f, i * block, 0),
                state, window)

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((heads, p, n)),
        jnp.zeros((cfg['mamba_d_conv'] - 1, channels))))[:2]


def attention_branch(cfg, lp, x):
    """``x + r · attention(RMSNorm_1(x))``."""
    t = x.shape[0]
    block = _blocks(t)
    positions = jnp.arange(t)
    r = cfg['residual_multiplier']

    keys, values = lax.map(
        lambda xb: keys_values(cfg, lp['attn'], norm(cfg, lp['ln1'], xb)),
        x.reshape(t // block, block, -1))
    # (blocks, KV heads, block, 128) -> (KV heads, T, 128)
    keys = keys.transpose(1, 0, 2, 3).reshape(keys.shape[1], t, -1)
    values = values.transpose(1, 0, 2, 3).reshape(values.shape[1], t, -1)

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        a = attend(cfg, lp['attn'], norm(cfg, lp['ln1'], xb),
                   i * block + jnp.arange(block), keys, values, positions)
        return lax.dynamic_update_slice_in_dim(x, xb + r * a, i * block, 0)

    return lax.fori_loop(0, t // block, rewrite, x)


def experts_branch(cfg, lp, x, forced=None):
    """``x + r · (experts + shared MLP)(RMSNorm_2(x))`` over the stream:
    the new stream, the layer's own picks ``(T, k)`` and the regret
    ``(T,)``."""
    t = x.shape[0]
    block = _blocks(t)
    k = cfg['num_experts_per_tok']
    r = cfg['residual_multiplier']

    def rewrite(i, carry):
        x, picks, regrets = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        y, picked, regret = expert_layer(
            cfg, lp['moe'], norm(cfg, lp['ln2'], xb),
            None if forced is None else
            lax.dynamic_slice_in_dim(forced, start, block, 0))
        return (lax.dynamic_update_slice_in_dim(x, xb + r * y, start, 0),
                lax.dynamic_update_slice_in_dim(
                    picks, picked.astype(jnp.int32), start, 0),
                lax.dynamic_update_slice_in_dim(regrets, regret, start, 0))

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((t, k), jnp.int32), jnp.zeros((t,))))


def stack(cfg, sp, x, forced=None, valid=None):
    """Every layer over the stream; returns it, the layers' own picks
    ``(layers, T, k)``, the regrets ``(layers, T)`` and the recurrent
    layers' states after row ``valid - 1`` ``(recurrent layers, H, P,
    N)``. ``forced (layers, T, k)``: see ``route``."""
    picks, regrets, states = [], [], []
    for i, kind in enumerate(kinds(cfg)):
        lp = sp[f'block_{i}']
        if kind == 'mamba':
            x, state = ssm_branch(cfg, lp, x, valid)
            states.append(state)
        elif kind == 'attention':
            x = attention_branch(cfg, lp, x)
        else:
            raise ValueError(f'layer {i}: no mixer {kind!r} in '
                             f'granitemoehybrid')
        x, picked, regret = experts_branch(
            cfg, lp, x, None if forced is None else forced[i])
        picks.append(picked)
        regrets.append(regret)
    return x, jnp.stack(picks), jnp.stack(regrets), jnp.stack(states)


def head(cfg, norm_scale, table, x):
    return mm(rms(x, cfg['rms_norm_eps'], norm_scale),
              f32(table).T) / cfg['logits_scaling']


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None, valid=None):
    """Logits ``(last, vocab held)`` of the final ``last`` positions of
    ``tokens (T,)``, the layers' own picks at every position ``(layers,
    T, k)``, the regret of the forced ones ``(layers, T)`` and every
    recurrent layer's state after token ``valid - 1`` (default: the
    last; rows behind it are padding). ``forced_picks (layers, T, k)``:
    see ``route``."""
    p = params['params']
    table = p['embed']['embedding']
    with jax.default_matmul_precision('highest'), \
            common.operands_in(operand_dtype):
        x = jax.jit(lambda e, t: cfg['embedding_multiplier'] * f32(e[t]))(
            table, tokens)
        x, picks, regrets, states = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, valid),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, e, x: head(cfg, n, e, x))(
            p['ln_f']['scale'], table, x[-last:])
    return logits, picks, regrets, states

