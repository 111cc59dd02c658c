"""The plain reference of the ``minicpm_sala`` architecture (MiniCPM-SALA
9B): a sequential decoder in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no kernel, no cache, no pooled-key buffer, no chunked
form of the recurrence and nothing imported from the program. With ``h``
the stream, every norm an RMSNorm (eps ``rms_norm_eps``, a scale), no
biases, ``r = scale_depth / sqrt(published num_hidden_layers)``:

    h0 = scale_emb · E[token]
    layer l:  h = h + r · mixer_l(RMSNorm_1(h))
              h = h + r · W_down (silu(W_gate u) ⊙ W_up u),  u = RMSNorm_2(h)
    logits = RMSNorm_f(h_L) W_head / (hidden_size / dim_model_base)

``mixer`` by ``mixer_types``:

``lightning-attn`` (``H`` = ``lightning_nh`` heads of ``d`` =
``lightning_head_dim``; no convolution, no activation):

    [q | k | v | g] = u W_in                          (H d each)
    q = rope(RMSNorm_head(q));  k = rope(RMSNorm_head(k))   half-split
                                pairs, theta ``rope_theta``, the row's position
    S_t = λ_head S_{t-1} + v_t k_tᵀ     S (d, d) a head: VALUE channel,
                                        then key channel; float32; t by t
    λ_head = exp(−2^(−8 (head + 1) / H))
    o_t = S_t q_t · d^-1/2
    mixer = (RMSNorm(o_t over all H d) ⊙ sigmoid(g)) W_out

``minicpm4`` (32 query heads over 2 KV heads of 128, NO rotation,
``qk_norm``, an output gate) with ``n`` the keys so far, the row's own
included, and ``sparse_config``'s sizes:

    q = RMSNorm_head(u Wq);  k = RMSNorm_head(u Wk);  v = u Wv
    n <= dense_len:  a = softmax(q kᵀ · d^-1/2) v          causal
    else, a KV head g with its 16 query heads:
      K̄_j = mean(k_i, i in [stride j, stride j + kernel)),  stride j + kernel <= n
      p_head = softmax_j(q_head · K̄_j · d^-1/2);   s_j = Σ_{head in g} p_head,j
      B_b = max(s_j : row j's window overlaps block b);  +inf at block 0
            (init_blocks) and at the last window / block blocks up to the row's own
      P = top-k(B_b, b <= (n − 1) // block)
      a = softmax(q kᵀ · d^-1/2 over the rows of P, causal) v
    mixer = (a ⊙ sigmoid(u Wz)) Wo

A top-k pick is a discrete decision: a caller that compares logits feeds
the served program's BLOCK picks back (``forced_picks``), as it feeds its
tokens back, and judges the picks apart by this file's own block scores:
where its own top-k is another set (``differ``) and how far the worst
forced pick's score lies under its own k-th best (``regret``; the forced
blocks score ``+inf`` on both sides and never count). ``dense=True``
attends EVERY row up to the row's own instead: the control that a program
reading the picked rows must fail.

The weight tree is the program's (the driver's shape table makes it): a
tree a layer (``block_i``) with ``ln1``, ONE of ``lightning``
(``in_proj`` = [Wq | Wk | Wv | Wg], ``q_norm``, ``k_norm``,
``norm_scale``, ``out_proj``) and ``attn`` (the module's K-first names:
``keys`` = Wq, ``queries`` = Wk, ``values`` = Wv, ``composition`` = Wo,
``gate`` = Wz, ``keys_norm`` / ``queries_norm`` the per-head scales),
``ln2`` and ``mlp`` (``gate``, ``up``, ``down``). Every leaf is widened
to float32 where it is used. Each branch rewrites the stream block by
block IN PLACE; a Lightning mixer carries its state from block to block
and steps the recurrence one token at a time inside a block; the
attention mixer first takes keys and values of every row.

The control (``common.operands_in``) rounds every matmul's operands to a
lower precision AND the recurrence's: the state as it is read each step
and the vectors, so a state kept below float32 shows as the matmuls'
rounding does (``lax.reduce_precision`` for bfloat16, a convert pair for
float8: ``reference/solar_open2.py`` says why).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import common

ROW_BLOCK = 128


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
    return list(cfg['mixer_types'][:cfg['num_hidden_layers']])


def norm(cfg, p, x):
    return rms(x, cfg['rms_norm_eps'], p['scale'])


def residual_scale(cfg):
    return cfg['scale_depth'] / math.sqrt(
        cfg['published']['num_hidden_layers'])


def score_bias(dist, sizes=None):
    """What every family file of ``reference/`` states beside its
    rotation: the sparse layers rotate nothing and add nothing to their
    scores; the Lightning layers rotate (``rope``)."""
    return None


def rope(x, positions, theta):
    """Half-split rotary embedding of ``x (n, H, d)`` at ``positions
    (n,)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


# -- Lightning linear attention -------------------------------------------------

def lightning_sizes(cfg):
    """``(heads, head_dim)``."""
    return cfg['lightning_nh'], cfg['lightning_head_dim']


def decay(heads):
    return jnp.exp(-jnp.exp2(-8.0 * (jnp.arange(heads) + 1.0) / heads))


def recurrence(q, k, v, state, live):
    """The additive recurrence, literally: one token a step of a scan
    over time. ``q``, ``k``, ``v (n, H, d)``, ``state (H, d, d)`` (value
    channel, then key channel). Returns ``o (n, H, d)`` and the final
    state. ``live (n,)``: a row that is not live (padding behind the
    sequence's end) leaves the state as it was."""
    lam = lowp(decay(q.shape[1]))[:, None, None]

    def step(s, args):
        q_t, k_t, v_t, live_t = args
        new = lam * lowp(s) + lowp(v_t)[:, :, None] * lowp(k_t)[:, None, :]
        o_t = jnp.sum(lowp(new) * lowp(q_t)[:, None, :], axis=-1)
        return jnp.where(live_t, new, s), o_t

    state, o = lax.scan(step, state, (q, k, v, live))
    return o, state


def lightning_block(cfg, lp, u, positions, state, live):
    """The Lightning mixer on the normed rows ``u (n, dim)`` at
    ``positions``, continuing ``state``: its output ``(n, dim)`` and the
    state after the block."""
    heads, d = lightning_sizes(cfg)
    rows = u.shape[0]
    q, k, v, g = jnp.split(mm(u, lp['in_proj']['kernel']), 4, -1)
    q, k, v = (x.reshape(rows, heads, d) for x in (q, k, v))
    eps = cfg['rms_norm_eps']
    q, k = rms(q, eps, lp['q_norm']), rms(k, eps, lp['k_norm'])
    if cfg['lightning_use_rope']:
        q = rope(q, positions, cfg['rope_theta'])
        k = rope(k, positions, cfg['rope_theta'])
    o, state = recurrence(q / math.sqrt(d), k, v, state, live)
    o = rms(o.reshape(rows, heads * d), eps, lp['norm_scale'])
    o = o * jax.nn.sigmoid(g)
    return mm(o, lp['out_proj']['kernel']), state


# -- block-sparse attention -----------------------------------------------------

def heads_of(x, heads):
    """``(n, heads · d) -> (heads, n, d)``."""
    return x.reshape(x.shape[0], heads, -1).transpose(1, 0, 2)


def keys_values(cfg, ap, u):
    kv = cfg['num_key_value_heads']
    k = heads_of(mm(u, ap['queries']['kernel']), kv)
    if cfg['qk_norm']:
        k = rms(k, cfg['rms_norm_eps'], ap['queries_norm'])
    return k, heads_of(mm(u, ap['values']['kernel']), kv)


def pooled_keys(cfg, keys):
    """``K̄ (KV heads, J, d)`` of ``keys (KV heads, T, d)``: row ``j``
    the mean of rows ``[stride j, stride j + kernel)``."""
    sp = cfg['sparse_config']
    t = keys.shape[1]
    rows = (t - sp['kernel_size']) // sp['kernel_stride'] + 1
    at = (jnp.arange(rows)[:, None] * sp['kernel_stride']
          + jnp.arange(sp['kernel_size']))
    return jnp.mean(keys[:, at], axis=2)


def block_scores(cfg, q, pooled, positions, n_blocks):
    """``B_b (KV heads, n, n_blocks)`` of the rows ``q (heads, n, d)`` at
    ``positions``: ``+inf`` at a row's forced blocks, ``-inf`` past its
    own, ``-1`` where no pooled row that overlaps the block is complete
    yet."""
    sp = cfg['sparse_config']
    kv = cfg['num_key_value_heads']
    stride, kernel, block = (sp['kernel_stride'], sp['kernel_size'],
                             sp['block_size'])
    n_keys = positions + 1
    rows = pooled.shape[1]
    complete = (jnp.arange(rows) * stride + kernel) <= n_keys[:, None]
    qg = q.reshape(kv, -1, *q.shape[1:])
    s = jnp.einsum('ghqd,gjd->ghqj', lowp(qg), lowp(pooled)) / math.sqrt(
        q.shape[-1])
    p = jax.nn.softmax(jnp.where(complete, s, -1e30), axis=-1)
    s = jnp.where(complete, jnp.sum(p, axis=1), -1.0)         # (kv, n, J)
    b = jnp.arange(n_blocks)
    # Pooled row j covers [stride j, stride j + kernel): it overlaps
    # block b for j from per b - reach to per b + per - 1 (per = block /
    # stride rows begin in a block, reach = kernel / stride - 1 more
    # begin before it and reach in): one strided slice an offset.
    per, reach = block // stride, kernel // stride - 1
    s = jnp.pad(s, ((0, 0), (0, 0),
                    (reach, max(per * n_blocks - rows, 0))),
                constant_values=-1.0)
    best = jnp.full(s.shape[:2] + (n_blocks,), -1.0)
    for o in range(per + reach):
        best = jnp.maximum(best, s[..., o:o + per * n_blocks:per])
    own = (positions // block)[:, None]
    forced = (b < sp['init_blocks']) | (b > own - sp['window_size'] // block)
    best = jnp.where(forced, jnp.inf, best)
    return jnp.where(b <= own, best, -jnp.inf)


def attend(cfg, ap, u, positions, keys, values, pooled, forced, dense):
    """The sparse layer on the normed rows ``u (n, dim)`` at
    ``positions`` over ``keys`` / ``values (KV heads, S, d)``: its
    output, whether each (KV head, row)'s own top-k is another set than
    the forced picks, and the forced picks' regret. ``forced (KV heads,
    n, k)``: attend THESE blocks (None: the reference's own)."""
    sp = cfg['sparse_config']
    heads, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    block, top = sp['block_size'], sp['topk']
    n_blocks = keys.shape[1] // block
    q = heads_of(mm(u, ap['keys']['kernel']), heads)
    if cfg['qk_norm']:
        q = rms(q, cfg['rms_norm_eps'], ap['keys_norm'])
    scores = block_scores(cfg, q, pooled, positions, n_blocks)
    best, own = lax.top_k(scores, top)
    picked = own if forced is None else forced
    sparse = ((positions + 1) > sp['dense_len'])[None, :]        # (1, n)
    differ = sparse & jnp.any(
        jnp.sort(own, -1) != jnp.sort(picked, -1), axis=-1)
    worst = jnp.min(jnp.take_along_axis(scores, picked, -1), -1)
    regret = jnp.where(sparse, jnp.maximum(best[..., -1] - worst, 0.0), 0.0)
    allowed = jnp.any(picked[..., None] == jnp.arange(n_blocks), axis=-2)
    allowed = allowed | ~sparse[..., None] | dense               # (kv, n, NB)
    seen = positions[:, None] >= jnp.arange(keys.shape[1])[None, :]
    scale = 1.0 / math.sqrt(cfg['head_dim'])

    def group(args):                  # one KV head, its query heads
        qg, k, v, ok = args
        mask = seen & jnp.repeat(ok, block, axis=-1)
        s = jnp.einsum('hqd,sd->hqs', lowp(qg), lowp(k)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum('hqs,sd->hqd', lowp(p), lowp(v))

    ctx = lax.map(group, (q.reshape(kv, heads // kv, *q.shape[1:]),
                          keys, values, allowed))
    ctx = ctx.reshape(heads, *ctx.shape[2:]).transpose(1, 0, 2)
    ctx = ctx.reshape(ctx.shape[0], -1)
    if cfg['attn_use_output_gate']:
        ctx = ctx * jax.nn.sigmoid(mm(u, ap['gate']['kernel']))
    return mm(ctx, ap['composition']['kernel']), differ, regret


# -- the model ------------------------------------------------------------------

def _blocks(t):
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    return block


def lightning_branch(cfg, lp, x, valid=None):
    """``x + r · lightning(RMSNorm_1(x))`` over the stream ``x (T,
    dim)``, block by block in place, the state carried. Returns the
    stream and the state after row ``valid - 1`` (default: the last)."""
    t = x.shape[0]
    block = _blocks(t)
    heads, d = lightning_sizes(cfg)
    r = residual_scale(cfg)

    def rewrite(i, carry):
        x, state = carry
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        at = i * block + jnp.arange(block)
        f, state = lightning_block(
            cfg, lp['lightning'], norm(cfg, lp['ln1'], xb), at, state,
            at < (t if valid is None else valid))
        return (lax.dynamic_update_slice_in_dim(x, xb + r * f, i * block,
                                                0), state)

    return lax.fori_loop(0, t // block, rewrite,
                         (x, jnp.zeros((heads, d, d))))


def attention_branch(cfg, lp, x, forced=None, dense=False):
    """``x + r · minicpm4(RMSNorm_1(x))``: the stream, where the own
    picks differ from the forced ones ``(KV heads, T)`` and the forced
    picks' regret ``(KV heads, T)``."""
    t = x.shape[0]
    block = _blocks(t)
    kv = cfg['num_key_value_heads']
    sp = cfg['sparse_config']
    if t % sp['block_size']:
        raise ValueError(f"{t} rows are not whole blocks of "
                         f"{sp['block_size']}")
    r = residual_scale(cfg)
    keys, values = lax.map(
        lambda xb: keys_values(cfg, lp['attn'], norm(cfg, lp['ln1'], xb)),
        x.reshape(t // block, block, -1))
    # (blocks, KV heads, block, 128) -> (KV heads, T, 128)
    keys = keys.transpose(1, 0, 2, 3).reshape(kv, t, -1)
    values = values.transpose(1, 0, 2, 3).reshape(kv, t, -1)
    pooled = pooled_keys(cfg, keys)

    def rewrite(i, carry):
        x, differ, regret = carry
        start = i * block
        xb = lax.dynamic_slice_in_dim(x, start, block, 0)
        a, d, g = attend(
            cfg, lp['attn'], norm(cfg, lp['ln1'], xb),
            start + jnp.arange(block), keys, values, pooled,
            None if forced is None else
            lax.dynamic_slice_in_dim(forced, start, block, 1), dense)
        return (lax.dynamic_update_slice_in_dim(x, xb + r * a, start, 0),
                lax.dynamic_update_slice_in_dim(differ, d, start, 1),
                lax.dynamic_update_slice_in_dim(regret, g, start, 1))

    return lax.fori_loop(0, t // block, rewrite, (
        x, jnp.zeros((kv, t), bool), jnp.zeros((kv, t))))


def mlp_branch(cfg, lp, x):
    """``x + r · W_down (silu(W_gate u) ⊙ W_up u)``, block by block."""
    t = x.shape[0]
    block = _blocks(t)
    r = residual_scale(cfg)
    mp = lp['mlp']

    def rewrite(i, x):
        xb = lax.dynamic_slice_in_dim(x, i * block, block, 0)
        u = norm(cfg, lp['ln2'], xb)
        y = mm(jax.nn.silu(mm(u, mp['gate']['kernel']))
               * mm(u, mp['up']['kernel']), mp['down']['kernel'])
        return lax.dynamic_update_slice_in_dim(x, xb + r * y, i * block, 0)

    return lax.fori_loop(0, t // block, rewrite, x)


def stack(cfg, sp, x, forced=None, valid=None, dense=False):
    """Every layer over the stream; returns it, where the sparse layers'
    own picks differ from the forced ones ``(sparse layers, KV heads,
    T)``, the forced picks' regrets (the same shape) and the Lightning
    layers' states after row ``valid - 1`` ``(Lightning layers, H, d,
    d)``. ``forced (sparse layers, KV heads, T, k)``: see ``attend``."""
    differs, regrets, states = [], [], []
    for i, kind in enumerate(kinds(cfg)):
        lp = sp[f'block_{i}']
        if kind == 'lightning-attn':
            x, state = lightning_branch(cfg, lp, x, valid)
            states.append(state)
        else:
            x, differ, regret = attention_branch(
                cfg, lp, x, None if forced is None
                else forced[len(differs)], dense)
            differs.append(differ)
            regrets.append(regret)
        x = mlp_branch(cfg, lp, x)
    return x, jnp.stack(differs), jnp.stack(regrets), jnp.stack(states)


def head(cfg, norm_scale, kernel, x):
    return mm(rms(x, cfg['rms_norm_eps'], norm_scale), kernel) / (
        cfg['hidden_size'] / cfg['dim_model_base'])


def logits_at(cfg, params, tokens, last, operand_dtype=None,
              forced_picks=None, valid=None, dense=False):
    """Logits ``(last, vocab)`` of the final ``last`` positions of
    ``tokens (T,)``, where each sparse layer's own picks differ from the
    forced ones ``(sparse layers, KV heads, T) bool`` (False at a row
    below ``dense_len``), the forced picks' regret (the same shape) and
    every Lightning layer's state after token ``valid - 1`` (default:
    the last; rows behind it are padding). ``forced_picks (sparse
    layers, KV heads, T, k)``: see ``attend``; ``dense``: attend every
    row (the control)."""
    p = params['params']
    with jax.default_matmul_precision('highest'), \
            common.operands_in(operand_dtype):
        x = jax.jit(lambda e, t: cfg['scale_emb'] * f32(e[t]))(
            p['embed']['embedding'], tokens)
        x, differ, regret, states = jax.jit(
            lambda s, x, f: stack(cfg, s, x, f, valid, dense),
            donate_argnums=(1,))(p['stack'], x, forced_picks)
        logits = jax.jit(lambda n, w, x: head(cfg, n, w, x))(
            p['ln_f']['scale'], p['lm_head_kernel'], x[-last:])
    return logits, differ, regret, states
