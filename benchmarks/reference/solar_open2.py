"""The plain reference of the ``solar_open2`` architecture (Solar Open 2
250B-A15B): a sequential decoder in float32 ``jax.numpy`` at ``highest``
matmul precision, with no kernel, no cache, no grouped matmul, no
chunked form of the recurrence, no triangular solve and nothing
imported from the program. With ``h`` the stream, ``u = RMSNorm(h)`` and
every norm an RMSNorm (eps ``rms_norm_eps``, a scale), no biases:

    h0 = E[token]
    layer l:  h = h + mixer_l(RMSNorm_1(h))
              u = RMSNorm_2(h)
              s = sigmoid(u W_r)         float32, all ``router_width`` experts
              P = top-k(s + b_corr);   g = s[P] / Σ s[P] · routed_scaling_factor
              y = Σ_{i∈P, held here} g_i W_down,i (silu(u W_gate,i) ⊙ u W_up,i)
              y = y + W_down,s (silu(u W_gate,s) ⊙ u W_up,s)     shared expert
              h = h + y
    logits = RMSNorm_f(h_L) W_head                          the untied head

``mixer`` by whether the layer is in ``gqa_layers``:

``kda`` (Kimi Delta Attention; ``H`` heads of ``d`` = ``head_dim``,
``K`` taps, the two gates through a rank-``d`` pair):

    [q | k | v | f | z | b] = u W_in            (3 H d | d | d | H)
    [q | k | v]_t = silu(sum_{j<K} w_c[j] [q | k | v]_{t-K+1+j})
                                        four shifted products, no bias
    q = q / sqrt(Σ_head q² + 1e-6) · d^-1/2     k = k / sqrt(Σ_head k² + 1e-6)
    g = -exp(A_log[head]) · softplus(f W_f + dt_bias)   a head AND key channel
    β = 2 · sigmoid(b)   (``kda_allow_neg_eigval``; else sigmoid(b))
    S' = Diag(exp(g_t)) S_{t-1};   S_t = S' + β_t k_t (v_t − S'ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t                      S (d, d) a head, float32; t by t
    mixer = (RMSNorm_head(o) ⊙ sigmoid(z W_z)) W_out

``gqa``: ``q = u Wq`` (64 x 128), ``k = u Wk``, ``v = u Wv`` (8 x 128),
NO rotation, causal (the mask a comparison of positions), ``mixer =
(softmax(q kᵀ · d^-1/2) v ⊙ sigmoid(u Wz)) Wo`` (``use_gqa_gate``);
query head g uses KV head g // 8.

Departures from the published description, each also in the
configuration file: the six input projections of a KDA layer are the
column blocks of one matrix and its three convolutions one kernel over
``q | k | v`` (the same numbers); the GQA layer's fused ``W_qz`` is held
as its two halves (``keys``, ``gate``); the recurrent state is float32.

The chip's SHARE: the router scores and picks over all ``router_width``
experts; ``y`` sums the picks that fall in ``experts_held`` (the weights
given hold those experts alone). A top-k pick is a discrete decision: a
caller that compares logits feeds the served program's picks back
(``forced_picks``), as it feeds its tokens back, and judges the picks
apart by this file's own router scores (``route``'s regret), as
``reference/granitemoehybrid.py`` does.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1``, ONE of ``delta`` (``in_proj``,
``conv_kernel (K, 3 H d)``: row j multiplies the input K - 1 - j steps
back, ``decay_up`` = W_f, ``dt_bias``, ``A_log``, ``gate_up`` = W_z,
``norm_scale``, ``out_proj``) and ``attn`` (the module's K-first names:
``keys`` = Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo,
``gate`` = Wz), ``ln2`` and ``moe`` (``router``, ``router_bias``,
``w_gate`` / ``w_up`` / ``w_down`` stacked over the held experts,
``shared``: ``gate``, ``up``, ``down``). Every leaf is widened to
float32 where it is used. Each branch rewrites the stream block by
block IN PLACE; a KDA mixer carries its state and the convolutions'
last inputs from block to block and steps the recurrence one token at a
time inside a block; the attention mixer first takes keys and values of
every row.

The control (``common.operands_in``) rounds every matmul's operands to a
lower precision AND the recurrence's: the state as it is read each
step, the decay, the rate and the vectors, so a state kept below float32
shows as the matmuls' rounding does. bfloat16 is rounded by
``lax.reduce_precision`` (the TPU compiler may drop a float32 ->
bfloat16 -> float32 round trip, and did: PR 36); float8 by a convert
pair, which excess precision does not cover. A caller can also compare
the states themselves (``logits_at``'s fourth result): every recurrent
layer's state after the sequence's last real token.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import common

ROW_BLOCK = 128
L2_EPS = 1e-6


def f32(x):
    return x.astype(jnp.float32)


def lowp(x):
    """``x`` in float32, rounded to the control's operand type
    (``common.operands_in``)."""
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
    return ['gqa' if i in cfg['gqa_layers'] else 'kda'
            for i in range(cfg['num_hidden_layers'])]


def norm(cfg, p, x):
    return rms(x, cfg['rms_norm_eps'], p['scale'])


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: this architecture rotates nothing and adds nothing to its
    attention scores (``use_rope`` false; the delta-rule layers carry
    the order)."""
    return None


# -- the gated delta rule ---------------------------------------------------------

def delta_sizes(cfg):
    """``(heads, head_dim, taps)``."""
    linear = cfg['linear_attn_config']
    return (linear['num_heads'], linear['head_dim'],
            linear['short_conv_kernel_size'])


def recurrence(q, k, v, g, beta, state, live=None):
    """The gated delta rule, literally: one token a step of a scan over
    time. ``q``, ``k``, ``g (n, H, d)`` (``g`` the log-decay a key
    channel), ``v (n, H, d)``, ``beta (n, H)``, ``state (H, d, d)``
    (key channel, then value channel). Returns ``o (n, H, d)`` and the
    final state. ``live (n,)``: a row that is not live (padding behind
    the sequence's end) leaves the state as it was."""
    if live is None:
        live = jnp.ones((q.shape[0],), bool)

    def step(s, args):
        q_t, k_t, v_t, g_t, b_t, live_t = args
        k_t = lowp(k_t)
        decayed = lowp(jnp.exp(g_t))[:, :, None] * lowp(s)    # S'
        held = jnp.sum(decayed * k_t[:, :, None], axis=1)     # S'ᵀ k
        new = decayed + k_t[:, :, None] * lowp(
            b_t[:, None] * (lowp(v_t) - held))[:, None, :]
        o_t = jnp.sum(lowp(new) * lowp(q_t)[:, :, None], axis=1)
        return jnp.where(live_t, new, s), o_t

    state, o = lax.scan(step, state, (q, k, v, g, beta, live))
    return o, state


def unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def delta_block(cfg, dp, u, state, window, live=None):
    """The KDA mixer on the normed rows ``u (n, dim)``, continuing
    ``state (H, d, d)`` and ``window (K - 1, 3 H d)``, the convolutions'
    inputs before the block. Returns the mixer's output ``(n, dim)``,
    the state and the window after it (``live``: see ``recurrence``)."""
    heads, d, taps = delta_sizes(cfg)
    inner, rows = heads * d, u.shape[0]
    rank = dp['decay_up']['kernel'].shape[0]
    qkv, f, z, b = jnp.split(mm(u, dp['in_proj']['kernel']), [
        3 * inner, 3 * inner + rank, 3 * inner + 2 * rank], -1)
    seen = jnp.concatenate([window, qkv], axis=0)
    w = f32(dp['conv_kernel'])
    conv = jnp.zeros_like(qkv)
    for j in range(taps):                     # four shifted products
        conv = conv + lowp(w[j]) * lowp(seen[j:j + rows])
    q, k, v = (x.reshape(rows, heads, d)
               for x in jnp.split(jax.nn.silu(conv), 3, -1))
    g = -jnp.exp(f32(dp['A_log']))[:, None] * jax.nn.softplus(
        mm(f, dp['decay_up']['kernel']) + f32(dp['dt_bias'])).reshape(
            rows, heads, d)
    beta = jax.nn.sigmoid(b)
    if cfg['kda_allow_neg_eigval']:
        beta = 2.0 * beta
    o, state = recurrence(unit(q) / math.sqrt(d), unit(k), v, g, beta,
                          state, live)
    o = rms(o, cfg['rms_norm_eps'], dp['norm_scale']).reshape(rows, inner)
    o = o * jax.nn.sigmoid(mm(z, dp['gate_up']['kernel']))
    return mm(o, dp['out_proj']['kernel']), state, seen[rows:]


# -- attention ----------------------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def keys_values(cfg, ap, u):
    kv = cfg['num_key_value_heads']
    return (heads_of(mm(u, ap['queries']['kernel']), kv),
            heads_of(mm(u, ap['values']['kernel']), kv))


def attend(cfg, ap, u, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``u (n, dim)`` at
    ``positions`` over ``keys`` / ``values (KV heads, S, head_dim)`` at
    ``key_positions``; nothing is rotated; the heads' output passes the
    sigmoid gate ``u Wz`` before ``Wo`` (``use_gqa_gate``)."""
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = heads_of(mm(u, ap['keys']['kernel']), heads)
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
    ctx = ctx.reshape(ctx.shape[0], -1)
    if cfg['use_gqa_gate']:
        ctx = ctx * jax.nn.sigmoid(mm(u, ap['gate']['kernel']))
    return mm(ctx, ap['composition']['kernel'])


# -- experts --------------------------------------------------------------------

def router_width(cfg):
    return cfg['published']['n_routed_experts']


def held(cfg):
    return tuple(cfg.get('experts_held') or (0, router_width(cfg)))


def route(cfg, mp, u, forced=None):
    """Gates ``(n, router_width)`` (zero where not picked), the picks
    ``(n, k)`` and the regret ``(n,)``: sigmoid scores, the top-k of the
    scores plus the correction bias, the picked scores normalised to sum
    to one, times the scaling factor. ``forced (n, k)``: gate THESE
    experts (the served program's own picks); the picks returned are
    still the reference's own, and the regret is how far the worst
    forced pick's biased score lies below the reference's k-th best."""
    scores = jax.nn.sigmoid(u @ f32(mp['router']))
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


def gated(w_gate, w_up, w_down, u):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def expert_layer(cfg, mp, u, forced=None):
    """``sum_{e held} gate_e E_e(u) + E_shared(u)``, the picks and the
    regret (``route``): every held expert runs on every row, its gate
    zero where it was not picked."""
    gates, picked, regret = route(cfg, mp, u, forced)
    lo, hi = held(cfg)

    def one(total, e):
        return total + e[3][:, None] * gated(e[0], e[1], e[2], u), None

    y, _ = lax.scan(one, jnp.zeros_like(u), (
        mp['w_gate'], mp['w_up'], mp['w_down'], gates[:, lo:hi].T))
    shared = mp['shared']
    y = y + gated(shared['gate']['kernel'], shared['up']['kernel'],
                  shared['down']['kernel'], u)
    return y, picked, regret


# -- the model ------------------------------------------------------------------

def _blocks(t):
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    return block


def delta_branch(cfg, lp, x, valid=None):
    """``x + kda(RMSNorm_1(x))`` over the stream ``x (T, dim)``, block
    by block in place, the state and the window carried. Returns the
    stream and the state after row ``valid - 1`` (default: the last)."""
    t = x.shape[0]
    block = _blocks(t)
    heads, d, taps = delta_sizes(cfg)

    def rewrite(i, carry):
        x, state, window = carry
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        f, state, window = delta_block(
            cfg, lp['delta'], norm(cfg, lp['ln1'], xb), state, window,
            i * block + jnp.arange(block) < (t if valid is None else valid))
        return (lax.dynamic_update_slice_in_dim(x, xb + f, i * block, 0),
                state, window)

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((heads, d, d)),
        jnp.zeros((taps - 1, 3 * heads * d))))[:2]


def attention_branch(cfg, lp, x):
    """``x + gqa(RMSNorm_1(x))``."""
    t = x.shape[0]
    block = _blocks(t)
    positions = jnp.arange(t)

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
        return lax.dynamic_update_slice_in_dim(x, xb + a, i * block, 0)

    return lax.fori_loop(0, t // block, rewrite, x)


def experts_branch(cfg, lp, x, forced=None):
    """``x + (experts + shared expert)(RMSNorm_2(x))`` over the stream:
    the new stream, the layer's own picks ``(T, k)`` and the regret
    ``(T,)``."""
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


def stack(cfg, sp, x, forced=None, valid=None):
    """Every layer over the stream; returns it, the layers' own picks
    ``(layers, T, k)``, the regrets ``(layers, T)`` and the recurrent
    layers' states after row ``valid - 1`` ``(recurrent layers, H, d,
    d)``. ``forced (layers, T, k)``: see ``route``."""
    picks, regrets, states = [], [], []
    for i, kind in enumerate(kinds(cfg)):
        lp = sp[f'block_{i}']
        if kind == 'kda':
            x, state = delta_branch(cfg, lp, x, valid)
            states.append(state)
        else:
            x = attention_branch(cfg, lp, x)
        x, picked, regret = experts_branch(
            cfg, lp, x, None if forced is None else forced[i])
        picks.append(picked)
        regrets.append(regret)
    return x, jnp.stack(picks), jnp.stack(regrets), jnp.stack(states)


def head(cfg, norm_scale, kernel, x):
    return mm(rms(x, cfg['rms_norm_eps'], norm_scale), kernel)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None, valid=None):
    """Logits ``(last, vocab held)`` of the final ``last`` positions of
    ``tokens (T,)``, the layers' own picks at every position ``(layers,
    T, k)``, the regret of the forced ones ``(layers, T)`` and every
    recurrent layer's state after token ``valid - 1`` (default: the
    last; rows behind it are padding). ``forced_picks (layers, T, k)``:
    see ``route``."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            common.operands_in(operand_dtype):
        x = jax.jit(lambda e, t: f32(e[t]))(p['embed']['embedding'], tokens)
        x, picks, regrets, states = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, valid),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, w, x: head(cfg, n, w, x))(
            p['ln_f']['scale'], p['lm_head_kernel'], x[-last:])
    return logits, picks, regrets, states
