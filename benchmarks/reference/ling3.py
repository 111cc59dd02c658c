"""The plain reference of the ``bailing_hybrid`` architecture (Ling 3.0
flash, ~125B-A5.5B): a sequential decoder in float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernel, no cache, no grouped
matmul, no chunked form of the recurrence, no absorbed form of the
latent attention and nothing imported from the program. With ``h`` the
stream, ``u = RMSNorm(h)`` and every norm an RMSNorm (eps
``rms_norm_eps``, a scale), no biases:

    h0 = E[token]
    layer l:  h = h + mixer_l(RMSNorm_1(h))
              h = h + ffn_l(RMSNorm_2(h))
    logits = RMSNorm_f(h_L) W_head                          the untied head

``mixer`` by the layer's PUBLISHED index ``i`` (``layers_held``): MLA
where ``(i + 1) % layer_group_size == 0``, else KDA.

``kda`` (Kimi Delta Attention; ``H`` heads of ``d`` = ``head_dim``, ``K``
taps; the decay and the output gate FULL matrices, ``no_kda_lora``):

    [q | k | v | f | z | b] = u W_in            (3 H d | H d | H d | H)
    [q | k | v]_t = silu(sum_{j<K} w_c[j] [q | k | v]_{t-K+1+j})
                                        four shifted products, no bias
    q = q / sqrt(Σ_head q² + 1e-6) · d^-1/2     k = k / sqrt(Σ_head k² + 1e-6)
    g = kda_lower_bound · sigmoid(exp(A_log[head]) · (f + dt_bias))
                        a head AND key channel, in (kda_lower_bound, 0)
    β = sigmoid(b)                      a head, in (0, 1)
    S' = Diag(exp(g_t)) S_{t-1};   S_t = S' + β_t k_t (v_t − S'ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t                      S (d, d) a head, float32; t by t
    mixer = (RMSNorm_head(o) ⊙ sigmoid(z)) W_out

``mla`` (``q_lora_rank`` null): ``q = u W_q`` (H x (nope + rope)),
``[c ; k_r] = u W_kva`` (``kv_lora_rank`` + rope), ``c = RMSNorm(c)``,
``[k_nope_h ; v_h] = c W_kvb,h``, the rope channels of q and the shared
``k_r`` turned as interleaved pairs ``(2i, 2i + 1)`` by ``position ·
rope_theta^(-2i/rope)`` (no scaling), scores ``(q_nope·k_nope +
q_rope·k_rope) · (nope + rope)^-1/2``, causal (the mask a comparison of
positions) over ALL rows, and a head-wise output gate before ``W_o``:
``mixer = concat_h(sigmoid(u W_g)_h · softmax(...)_h v_h) W_o``.

``ffn``: the first ``first_k_dense_replace`` layers held a dense gated
MLP ``W_down (silu(u W_gate) ⊙ u W_up)``; every other the experts:

    s = sigmoid(u W_r)                 float32, all ``router_width`` experts
    c = s + b_corr                     the correction bias only chooses
    group score = the sum of a group's two best c (n_group groups of
                  router_width / n_group consecutive experts)
    kept = the topk_group best groups;   P = top-k of c over kept groups
    g = s[P] / Σ s[P] · routed_scaling_factor
    y = Σ_{i∈P, held here} g_i E_i(u) + E_shared(u)

Departures from the published description, each also in the
configuration file: the six input projections of a KDA layer are the
column blocks of one matrix and its three convolutions one kernel over
``q | k | v`` (the same numbers); the recurrent state is float32; the
clamped SiLU of ``expert_swiglu_limit_list`` /
``share_expert_swiglu_limit_list`` is NOT written (its form is not
published here): a non-zero entry raises.

The chip's SHARE: the router scores, groups and picks are over all
``router_width`` experts; ``y`` sums the picks that fall in
``experts_held`` (the weights given hold those experts alone). A top-k
pick is a discrete decision: a caller that compares logits feeds the
served program's picks back (``forced_picks``), as it feeds its tokens
back, and judges the picks apart by this file's own group-limited rule
(``route``'s regret).

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1``, ONE of ``delta`` (``in_proj``,
``conv_kernel (K, 3 H d)``: row j multiplies the input K - 1 - j steps
back, ``dt_bias``, ``A_log``, ``norm_scale``, ``out_proj``) and ``attn``
(``q``, ``kv_a``, ``kv_norm``, ``kv_b (rank, H, nope + v)``, ``gate``,
``out``), ``ln2`` and ONE of ``mlp`` (``gate``, ``up``, ``down``) and
``moe`` (``router``, ``router_bias``, ``w_gate`` / ``w_up`` / ``w_down``
stacked over the held experts, ``shared``: ``gate``, ``up``, ``down``).
Every leaf is widened to float32 where it is used. Each branch rewrites
the stream block by block IN PLACE; a KDA mixer carries its state and
the convolutions' last inputs from block to block and steps the
recurrence one token at a time inside a block; the MLA mixer first
takes the latent rows of every row and expands them.

The control (``common.operands_in``) rounds every matmul's operands to a
lower precision AND the recurrence's, as ``reference/solar_open2.py``
does. A caller can also compare the states themselves (``logits_at``'s
fourth result): every KDA layer's state after the sequence's last real
token.
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
    (``common.operands_in``; bfloat16 by ``reduce_precision``, which the
    TPU compiler does not drop)."""
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


def norm(cfg, p, x):
    return rms(x, cfg['rms_norm_eps'], p['scale'])


def kinds(cfg):
    """The layers held, ``(mixer, ffn)`` each: ``'mla'`` or ``'kda'`` by
    the layer's published index, ``'dense'`` for the first
    ``first_k_dense_replace`` of them, else ``'experts'``."""
    if any(cfg['expert_swiglu_limit_list']) or any(
            cfg['share_expert_swiglu_limit_list']):
        raise ValueError('a non-zero swiglu limit clamps the SiLU in a '
                         'form this reference does not guess')
    held = cfg['layers_held']
    if len(held) != cfg['num_hidden_layers']:
        raise ValueError(f'layers_held {held} names '
                         f"{cfg['num_hidden_layers']} layers")
    return [('mla' if (i + 1) % cfg['layer_group_size'] == 0 else 'kda',
             'dense' if j < cfg['first_k_dense_replace'] else 'experts')
            for j, i in enumerate(held)]


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: this architecture adds nothing to its attention scores
    (the MLA layers rotate half of each head; the KDA layers carry the
    order)."""
    return None


# -- the gated delta rule ---------------------------------------------------------

def delta_sizes(cfg):
    """``(heads, head_dim, taps)``."""
    return (cfg['num_attention_heads'], cfg['head_dim'],
            cfg['short_conv_kernel_size'])


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
    qkv, f, z, b = jnp.split(mm(u, dp['in_proj']['kernel']), [
        3 * inner, 4 * inner, 5 * inner], -1)
    seen = jnp.concatenate([window, qkv], axis=0)
    w = f32(dp['conv_kernel'])
    conv = jnp.zeros_like(qkv)
    for j in range(taps):                     # four shifted products
        conv = conv + lowp(w[j]) * lowp(seen[j:j + rows])
    q, k, v = (x.reshape(rows, heads, d)
               for x in jnp.split(jax.nn.silu(conv), 3, -1))
    # the bounded decay (kda_safe_gate): in (kda_lower_bound, 0)
    g = cfg['kda_lower_bound'] * jax.nn.sigmoid(
        jnp.exp(f32(dp['A_log']))[:, None]
        * (f + f32(dp['dt_bias'])).reshape(rows, heads, d))
    o, state = recurrence(unit(q) / math.sqrt(d), unit(k), v, g,
                          jax.nn.sigmoid(b), state, live)
    o = rms(o, cfg['rms_norm_eps'], dp['norm_scale']).reshape(rows, inner)
    return (mm(o * jax.nn.sigmoid(z), dp['out_proj']['kernel']), state,
            seen[rows:])


# -- latent attention ---------------------------------------------------------------

def rotate(cfg, x, positions):
    """Interleaved pairs ``(2i, 2i+1)`` of ``x (..., T, rope)`` turned
    by ``positions (T,) · rope_theta^(-2i/rope)``."""
    rope = cfg['qk_rope_head_dim']
    inv = cfg['rope_theta'] ** (
        -jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_rows(cfg, ap, u, positions):
    """``[c_kv ; k_rope] (n, kv_lora_rank + rope)`` of the normed rows
    ``u (n, dim)``."""
    rank = cfg['kv_lora_rank']
    ckv = mm(u, ap['kv_a']['kernel'])
    c = rms(ckv[:, :rank], cfg['rms_norm_eps'], ap['kv_norm']['scale'])
    return jnp.concatenate([c, rotate(cfg, ckv[:, rank:], positions)], -1)


def expand(cfg, ap, rows):
    """Per-head keys ``(H, S, nope + rope)`` and values ``(H, S, v)``
    from the latent rows ``(S, rank + rope)``."""
    rank, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    heads = cfg['num_attention_heads']
    kv = jnp.einsum('sc,chd->hsd', lowp(rows[:, :rank]),
                    lowp(ap['kv_b']))                 # (rank, H, nope + v)
    k_rope = jnp.broadcast_to(rows[None, :, rank:],
                              (heads, rows.shape[0], rows.shape[1] - rank))
    return jnp.concatenate([kv[..., :nope], k_rope], -1), kv[..., nope:]


def attend(cfg, ap, u, positions, keys, values, key_positions):
    """Causal attention of the normed rows ``u (n, dim)`` over the
    expanded keys and values, each head's context under its gate
    ``sigmoid(u W_g)_h`` before ``W_o``."""
    heads, nope = cfg['num_attention_heads'], cfg['qk_nope_head_dim']
    rope = cfg['qk_rope_head_dim']
    q = mm(u, ap['q']['kernel']).reshape(-1, heads, nope + rope)
    q = q.transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope],
                         rotate(cfg, q[..., nope:], positions)], -1)
    s = jnp.einsum('hqd,hsd->hqs', lowp(q), lowp(keys)) / math.sqrt(
        nope + rope)
    seen = key_positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    ctx = jnp.einsum('hqs,hsd->qhd', lowp(p), lowp(values))
    ctx = ctx * jax.nn.sigmoid(mm(u, ap['gate']['kernel']))[:, :, None]
    return mm(ctx.reshape(ctx.shape[0], -1), ap['out']['kernel'])


# -- feed-forward -----------------------------------------------------------------

def router_width(cfg):
    return cfg['published']['num_experts']


def held(cfg):
    return tuple(cfg.get('experts_held') or (0, router_width(cfg)))


def kept_groups(cfg, group_score, first=None):
    """``(n, n_group)`` bool: each row's ``topk_group`` best groups by
    ``group_score``, those of ``first (n, n_group)`` taken before any
    other."""
    if first is not None:
        group_score = jnp.where(first, jnp.inf, group_score)
    _, kept = lax.top_k(group_score, cfg['topk_group'])
    keep = jnp.sum(jax.nn.one_hot(kept, cfg['n_group']), 1) > 0
    return keep if first is None else keep | first


def route(cfg, mp, u, forced=None):
    """Gates ``(n, router_width)`` (zero where not picked), the picks
    ``(n, k)`` and the regret ``(n,)``: sigmoid scores; a group's score
    the sum of its two best scores plus correction bias; the top-k of
    the biased scores over the ``topk_group`` best groups' experts; the
    picked scores (unbiased) normalised to sum to one, times the scaling
    factor. ``forced (n, k)``: gate THESE experts (the served program's
    own picks); the picks returned are still the reference's own, and
    the regret is the larger of two distances in units of a biased
    score: how far the worst GROUP a forced pick lies in is below the
    reference's ``topk_group``-th best group score (0 where every such
    group is one the reference keeps), and how far the worst forced
    pick's biased score lies below the k-th best over the groups the
    forced picks imply (theirs, filled up with the reference's best)."""
    groups = cfg['n_group']
    size = router_width(cfg) // groups
    scores = jax.nn.sigmoid(u @ f32(mp['router']))
    biased = scores + f32(mp['router_bias'])
    best2, _ = lax.top_k(biased.reshape(-1, groups, size), 2)
    group_score = jnp.sum(best2, -1)                        # (n, groups)

    def top_of(keep):
        return lax.top_k(jnp.where(jnp.repeat(keep, size, axis=1), biased,
                                   -jnp.inf), cfg['num_experts_per_tok'])

    _, own = top_of(kept_groups(cfg, group_score))
    if forced is None:
        picked, regret = own, jnp.zeros(u.shape[:1])
    else:
        picked = forced
        used = jnp.sum(jax.nn.one_hot(forced // size, groups), 1) > 0
        last_kept = lax.top_k(group_score, cfg['topk_group'])[0][:, -1]
        of_groups = last_kept - jnp.min(
            jnp.where(used, group_score, jnp.inf), -1)
        best, _ = top_of(kept_groups(cfg, group_score, used))
        of_picks = best[:, -1] - jnp.min(
            jnp.take_along_axis(biased, picked, -1), -1)
        regret = jnp.maximum(jnp.maximum(of_groups, 0.0), of_picks)
    g = jnp.take_along_axis(scores, picked, -1)
    if cfg['norm_topk_prob']:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * cfg['routed_scaling_factor']
    onehot = jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum('nk,nke->ne', g, onehot), own, regret


def gated(w_gate, w_up, w_down, u):
    return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)


def gated_mlp(p, u):
    return gated(p['gate']['kernel'], p['up']['kernel'],
                 p['down']['kernel'], u)


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
    return y + gated_mlp(mp['shared'], u), picked, regret


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


def latent_branch(cfg, lp, x):
    """``x + mla(RMSNorm_1(x))``: the latent rows of every row first,
    expanded to every head's keys and values, then the rows' attention
    block by block."""
    t = x.shape[0]
    block = _blocks(t)
    positions = jnp.arange(t)
    rows = lax.map(
        lambda args: latent_rows(cfg, lp['attn'],
                                 norm(cfg, lp['ln1'], args[0]), args[1]),
        (x.reshape(t // block, block, -1),
         positions.reshape(t // block, block)))
    keys, values = expand(cfg, lp['attn'], rows.reshape(t, -1))

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        a = attend(cfg, lp['attn'], norm(cfg, lp['ln1'], xb),
                   i * block + jnp.arange(block), keys, values, positions)
        return lax.dynamic_update_slice_in_dim(x, xb + a, i * block, 0)

    return lax.fori_loop(0, t // block, rewrite, x)


def dense_branch(cfg, lp, x):
    """``x + mlp(RMSNorm_2(x))``, block by block."""
    t = x.shape[0]
    block = _blocks(t)

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        y = gated_mlp(lp['mlp'], norm(cfg, lp['ln2'], xb))
        return lax.dynamic_update_slice_in_dim(x, xb + y, i * block, 0)

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
    """Every layer over the stream; returns it, the EXPERT layers' own
    picks ``(expert layers, T, k)``, their regrets ``(expert layers,
    T)`` and the KDA layers' states after row ``valid - 1`` ``(KDA
    layers, H, d, d)``. ``forced (expert layers, T, k)``: see
    ``route``."""
    picks, regrets, states = [], [], []
    for i, (mixer, ffn) in enumerate(kinds(cfg)):
        lp = sp[f'block_{i}']
        if mixer == 'kda':
            x, state = delta_branch(cfg, lp, x, valid)
            states.append(state)
        else:
            x = latent_branch(cfg, lp, x)
        if ffn == 'dense':
            x = dense_branch(cfg, lp, x)
            continue
        x, picked, regret = experts_branch(
            cfg, lp, x, None if forced is None else forced[len(picks)])
        picks.append(picked)
        regrets.append(regret)
    return x, jnp.stack(picks), jnp.stack(regrets), jnp.stack(states)


def head(cfg, norm_scale, kernel, x):
    return mm(rms(x, cfg['rms_norm_eps'], norm_scale), kernel)


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None, valid=None):
    """Logits ``(last, vocab held)`` of the final ``last`` positions of
    ``tokens (T,)``, the expert layers' own picks at every position
    ``(expert layers, T, k)``, the regret of the forced ones ``(expert
    layers, T)`` and every KDA layer's state after token ``valid - 1``
    (default: the last; rows behind it are padding). ``forced_picks
    (expert layers, T, k)``: see ``route``."""
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
