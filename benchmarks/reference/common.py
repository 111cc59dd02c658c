"""The plain reference both families share: a sequential pre-LN decoder
in float32 ``jax.numpy`` at ``highest`` matmul precision, with no kernel,
no cache and no ``shard_map``, and nothing imported from the program.

Per layer, with ``h = LN1(x)``:
``x1 = x + (softmax(q k^T / sqrt(d) + bias, causal [, window]) v) Wo + bo``,
``q = h Wq + bq``, ``k = h Wk + bk``, ``v = h Wv + bv``, then
``y = x1 + gelu_tanh(LN2(x1) W1 + b1) W2 + b2``. The head is the tied
embedding after a final LayerNorm; the loss is the mean next-token
cross-entropy. A family file supplies what differs: how ``q``/``k`` are
rotated and which additive score bias applies.

Everything but attention is position-wise, so a layer runs as a map over
blocks of rows, each block re-materialised in the backward pass: the
(rows x keys) scores and the (rows x 4d) MLP activations exist for one
block at a time and the whole step fits beside its own optimizer state.

The weight tree is the benchmark's (``weights.py``). Its attention leaves
keep the program's K-first names: ``keys`` is what the literature calls
the query projection Wq, ``queries`` is Wk, ``values`` is Wv and
``composition`` is Wo.
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-6     # flax's LayerNorm default, which the program runs with
ROW_BLOCK = 256
LOSS_CHUNK = 2048

# The control: the same reference with every matmul's operands rounded
# to a lower precision (float8_e4m3fn, the step below the cells'
# bfloat16) and accumulated in float32. None is the reference itself.
_OPERANDS = [None]


@contextlib.contextmanager
def operands_in(dtype):
    _OPERANDS.append(dtype)
    try:
        yield
    finally:
        _OPERANDS.pop()


def lowp(x):
    """``x`` rounded to the control's operand type. The gradient passes
    straight through: a cotangent cast to float8 would underflow to
    zero, and a control with no gradient reads nothing."""
    dtype = _OPERANDS[-1]
    if dtype is None:
        return x
    return x + lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p['scale'] + p['bias']


def dense(x, p):
    y = lowp(x) @ lowp(p['kernel'])
    return y + p['bias'] if 'bias' in p else y


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def rope_half(x, positions, theta):
    """Rotary embedding in the half-split (rotate_half) convention on
    ``x (..., T, d)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def split_heads(x, n_heads):
    t, width = x.shape
    return x.reshape(t, n_heads, width // n_heads).transpose(1, 0, 2)


def layer(family, sizes, lp, x):
    """One block on ``x (T, dim)``."""
    t, dim = x.shape
    n_heads = sizes['num_heads']
    n_kv = sizes['attn_kwargs'].get('num_kv_heads') or n_heads
    window = sizes['attn_kwargs'].get('window')
    d = dim // n_heads
    positions = jnp.arange(t)
    h = layer_norm(x, lp['ln1'])
    k = family.rotate(split_heads(dense(h, lp['attn']['queries']), n_kv),
                      positions, sizes)
    v = split_heads(dense(h, lp['attn']['values']), n_kv)
    block = min(ROW_BLOCK, t)
    if t % block:
        raise ValueError(f'{t} rows do not divide into blocks of {block}')
    span = t if window is None else min(t, window + block)

    @jax.checkpoint
    def rows(args):
        xb, start = args
        pos_q = start + jnp.arange(block)
        hb = layer_norm(xb, lp['ln1'])
        q = family.rotate(split_heads(dense(hb, lp['attn']['keys']),
                                      n_heads), pos_q, sizes)
        lo = jnp.clip(start + block - span, 0, t - span)
        kb = lax.dynamic_slice_in_dim(k, lo, span, 1)
        vb = lax.dynamic_slice_in_dim(v, lo, span, 1)
        pos_k = lo + jnp.arange(span)
        qg = q.reshape(n_kv, n_heads // n_kv, block, d)
        s = jnp.einsum('kgqd,ksd->kgqs', lowp(qg), lowp(kb)) / math.sqrt(d)
        dist = pos_k[None, :] - pos_q[:, None]          # <= 0 when seen
        bias = family.score_bias(dist, sizes)           # (heads, q, s)
        if bias is not None:
            s = s + bias.reshape(s.shape)
        seen = dist <= 0
        if window is not None:
            seen = seen & (dist > -window)
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum('kgqs,ksd->kgqd', lowp(p), lowp(vb))
        ctx = ctx.reshape(n_heads, block, d).transpose(1, 0, 2)
        x1 = xb + dense(ctx.reshape(block, dim), lp['attn']['composition'])
        mlp = dense(gelu_tanh(dense(layer_norm(x1, lp['ln2']),
                                    lp['mlp_in'])), lp['mlp_out'])
        return x1 + mlp

    starts = jnp.arange(0, t, block)
    out = lax.map(rows, (x.reshape(t // block, block, dim), starts))
    return out.reshape(t, dim)


def hidden(family, sizes, params, tokens):
    """Final-LayerNorm hidden states ``(T, dim)`` for ``tokens (T,)``."""
    p = params['params']
    x = p['embed']['embedding'][tokens]

    @jax.checkpoint
    def body(x, lp):
        return layer(family, sizes, lp, x), None

    x, _ = lax.scan(body, x, p['stack']['layers']['block'])
    return layer_norm(x, p['ln_f'])


def logits_at(family, sizes, params, tokens, last, operand_dtype=None):
    """Logits ``(last, vocab)`` of the final ``last`` positions."""
    with jax.default_matmul_precision('highest'), \
            operands_in(operand_dtype):
        x = hidden(family, sizes, params, tokens)[-last:]
        return lowp(x) @ lowp(params['params']['embed']['embedding'].T)


def loss(family, sizes, params, tokens, targets):
    """Mean cross-entropy over positions with ``targets >= 0``."""
    x = hidden(family, sizes, params, tokens)
    table = params['params']['embed']['embedding']
    t = x.shape[0]
    chunk = min(LOSS_CHUNK, t)

    @jax.checkpoint
    def part(carry, args):
        xc, tc = args
        logits = lowp(xc) @ lowp(table.T)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[:, None], -1)[:, 0]
        return carry + jnp.sum(jnp.where(tc >= 0, lse - picked, 0.0)), None

    total, _ = lax.scan(part, jnp.float32(0.0),
                        (x.reshape(t // chunk, chunk, -1),
                         targets.reshape(t // chunk, chunk)))
    return total / jnp.maximum(jnp.sum(targets >= 0), 1)


def adamw(params, grads, m, v, count, hyper):
    """One AdamW step as optax.adamw defines it (decay on every leaf,
    bias-corrected moments, eps outside the root)."""
    b1, b2 = hyper['b1'], hyper['b2']
    count = count + 1
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        u = (m / c1) / (jnp.sqrt(v / c2) + hyper['eps'])
        return p - hyper['lr'] * (u + hyper['weight_decay'] * p), m, v

    out = jax.tree.map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), count


def make_train_step(family, sizes, hyper, operand_dtype=None):
    """``step(params, m, v, count, tokens, targets) -> (params, m, v,
    count, loss, grads)`` for one sequence ``tokens (T,)``."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, tokens, targets):
        with jax.default_matmul_precision('highest'), \
                operands_in(operand_dtype):
            value, grads = jax.value_and_grad(
                lambda p: loss(family, sizes, p, tokens, targets))(params)
            params, m, v, count = adamw(params, grads, m, v, count, hyper)
        return params, m, v, count, value, grads

    return step
