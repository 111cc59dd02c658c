# -*- coding: utf-8 -*-
"""
TransformerLM (models/lm.py) — the capstone composition. Contracts:
target construction respects packed-segment boundaries; the sharded LM
train step computes EXACTLY the unsharded cross-entropy loss and
gradient (SGD(1.0) makes the updated params a direct gradient probe);
the copy task trains below threshold on the 8-device mesh and greedy
generation through the KV caches reproduces the prefix; checkpoint /
resume mid-run continues the same trajectory.
"""

import functools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_dot_product_tpu import TransformerLM, lm_targets
from distributed_dot_product_tpu.models.lm import head_loss_traces
from distributed_dot_product_tpu.parallel.mesh import (
    data_seq_mesh, seq_mesh,
)
from distributed_dot_product_tpu.train import make_lm_train_step

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, 'examples'))
from train_lm import make_copy_batch  # noqa: E402

VOCAB, DIM, HEADS, LAYERS = 32, 32, 4, 2


def _model(**kw):
    kw.setdefault('vocab_size', VOCAB)
    kw.setdefault('dim', DIM)
    kw.setdefault('num_heads', HEADS)
    kw.setdefault('n_layers', LAYERS)
    return TransformerLM(**kw)


def test_lm_targets_shift_boundaries_and_padding():
    tokens = jnp.asarray([[5, 6, 7, 8, 9, 10]], jnp.int32)
    seg = jnp.asarray([[0, 0, 0, 1, 1, 1]], jnp.int32)
    got = lm_targets(tokens, seg)
    # position 2 is segment 0's last token: must not predict token 8;
    # the final position has no next token.
    np.testing.assert_array_equal(np.asarray(got),
                                  [[6, 7, -1, 9, 10, -1]])
    got_pad = lm_targets(jnp.asarray([[5, 6, 0, 0]], jnp.int32),
                         pad_id=0)
    np.testing.assert_array_equal(np.asarray(got_pad),
                                  [[6, -1, -1, -1]])


def test_lm_forward_shape_and_finite():
    m = _model(attn_kwargs=dict(distributed=False))
    toks = jnp.arange(16, dtype=jnp.int32).reshape(1, 16) % VOCAB
    params = m.init(jax.random.key(0), toks)
    out = m.apply(params, toks)
    assert out.shape == (1, 16, VOCAB)
    assert bool(jnp.isfinite(out).all())


@pytest.mark.parametrize('mesh_kind', ['seq', 'data_seq'])
def test_lm_step_matches_unsharded_loss_and_grad(mesh_kind):
    """SGD(1.0) probe: sharded step's loss AND updated params must equal
    the unsharded cross-entropy's (params - grad) — the loss psum /
    grad psum wiring is exactly the invariant under test."""
    if mesh_kind == 'seq':
        mesh, data_axis = seq_mesh(8), None
    else:
        mesh, data_axis = data_seq_mesh(2, 4), 'data'
    b, t = 2, 64
    tokens, targets, seg = make_copy_batch(jax.random.key(3), b, t,
                                           VOCAB, 16)
    m = _model()
    m_local = _model(attn_kwargs=dict(distributed=False))
    params = m.init(jax.random.key(1), tokens[:, :16])
    opt = optax.sgd(1.0)
    step = make_lm_train_step(m, opt, mesh, data_axis=data_axis,
                              donate=False)
    new_params, _, loss = step(params, opt.init(params),
                               (tokens, targets, seg))

    def local_loss(p):
        logits = m_local.apply(p, tokens, segment_ids=seg)
        valid = targets >= 0
        tgt = jnp.where(valid, targets, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
        return (jnp.sum(jnp.where(valid, nll, 0.0))
                / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0))

    want_loss, g = jax.value_and_grad(local_loss)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    want = jax.tree.map(lambda p, gg: p - gg, params, g)
    for got_l, want_l in zip(jax.tree.leaves(new_params),
                             jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                                   atol=2e-5, rtol=1e-4)


def _plain_nll(m, params, tokens, targets, seg):
    """The loss as plain autodiff sees it, written here and not taken
    from the module: the final norm's output against the float32 table,
    the whole ``(T, vocab)`` logits at once, ``logsumexp`` minus the
    target's logit by ``take_along_axis`` — no chunk, no fused rule."""
    def nll(mdl):
        x = mdl._embed(tokens)
        x = mdl.stack(x, x, x, None, segment_ids=seg, deterministic=False,
                      dropout_seed=None)
        x = mdl.ln_f(mdl._collapse(x)).astype(jnp.float32)
        logits = mdl.logit_scale * jnp.einsum(
            '...d,vd->...v', x, mdl._head_table().astype(jnp.float32))
        valid = targets >= 0
        ll = jnp.take_along_axis(
            logits, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (jnp.sum(jnp.where(valid, lse - ll, 0.0)),
                jnp.sum(valid.astype(jnp.float32)))

    return nn.apply(nll, m)(params)


def _assert_grads_close(got, want, dtype, tol=lambda name: 1e-4):
    """float32: element for element. bfloat16 compute: a parameter's
    gradient as a whole, by the norm of its error against ``tol`` (of
    the parameter's name) times the norm of the reference (single
    elements flip a rounding) — tight all the same, because ``dx``
    leaves the head in float32 and is rounded where plain autodiff
    rounds it: a ``dx`` rounded before the cotangent scales it as well
    reads 1e-2 here."""
    got = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), b in zip(got, jax.tree.leaves(want), strict=True):
        name = jax.tree_util.keystr(path)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype is None:
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                       err_msg=name)
        else:
            err = np.linalg.norm(a - b)
            assert err <= tol(name) * np.linalg.norm(b) + 1e-9, (
                name, err / np.linalg.norm(b))


def _mean(nll):
    def loss(p):
        s, c = nll(p)
        return s / c
    return loss


@functools.cache
def _nll_case(tie, logit_scale, dtype):
    """A model, its batch — the copy task's prefixes are rows whose
    target is -1, and here a whole chunk of them is — and what the plain
    loss gives there: the sum, the count, the mean and its gradient."""
    m = _model(attn_kwargs=dict(distributed=False), tie_embeddings=tie,
               logit_scale=logit_scale, dtype=dtype)
    tokens, targets, seg = make_copy_batch(jax.random.key(5), 2, 64,
                                           VOCAB, 16)
    targets = targets.at[0, 16:32].set(-1)
    params = m.init(jax.random.key(1), tokens[:, :16])

    def plain(p):
        return _plain_nll(m, p, tokens, targets, seg)

    return (m, params, (tokens, targets, seg), plain(params),
            jax.value_and_grad(_mean(plain))(params))


@pytest.mark.parametrize('chunk', [16, 24, 64, None])
@pytest.mark.parametrize('dtype', [None, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('logit_scale', [1.0, 0.5])
@pytest.mark.parametrize('tie', [True, False], ids=['tied', 'untied'])
def test_lm_chunked_nll_matches_unchunked(tie, logit_scale, dtype, chunk):
    """The chunked cross-entropy takes its gradient in the forward pass
    (``models.lm.head_loss``): the same math as the plain loss — the
    sum, the count and EVERY parameter's gradient — tied or untied head,
    scaled logits, either compute type, a chunk that does not divide T
    (24), one chunk (64, None), rows whose target is -1."""
    m, params, (tokens, targets, seg), (want_s, want_c), (
        want, want_g) = _nll_case(tie, logit_scale, dtype)

    def nll(p):
        return m.apply(p, tokens, targets, segment_ids=seg, chunk=chunk,
                       method='nll_sum')

    with head_loss_traces() as traces:
        got_s, got_c = nll(params)      # un-differentiated: the primal
        got, got_g = jax.value_and_grad(_mean(nll))(params)  # the fused rule
    assert float(got_c) == float(want_c) == float(jnp.sum(targets >= 0))
    np.testing.assert_allclose(float(got_s), float(want_s), rtol=2e-6)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    _assert_grads_close(got_g, want_g, dtype)
    # A 32-wide model over a 32-token vocabulary is under the kernel's
    # tiles: both traces keep the two einsums, and say why.
    assert [t['route'] for t in traces] == ['xla', 'xla']
    assert 'not differentiated' in traces[0]['why']
    assert ('float32' if dtype is None else 'width 32') in traces[1]['why']


KERNEL_DIM = 128        # the narrowest width and the smallest vocabularies
KERNEL_ROWS = 128       # and chunk ``ops.pallas_head.head_tiles`` takes


@functools.cache
def _kernel_case(tie, logit_scale, rows, vocab):
    """A one-layer bfloat16 model at the smallest shapes the head's
    kernel takes, a batch of ``rows`` tokens scanned in 128-row chunks
    — ``'whole'``: two chunks; ``'pad'``: 192 rows, the second chunk
    half padding; ``'ignored'``: the second chunk all -1 — and the plain
    loss there."""
    m = _model(vocab_size=vocab, dim=KERNEL_DIM, n_layers=1,
               attn_kwargs=dict(distributed=False, softmax_impl='full'),
               tie_embeddings=tie, logit_scale=logit_scale,
               dtype=jnp.bfloat16)
    t = 192 if rows == 'pad' else 256
    tokens = jax.random.randint(jax.random.key(11), (1, t), 0, vocab)
    targets = lm_targets(tokens)
    if rows == 'ignored':
        targets = targets.at[:, KERNEL_ROWS:].set(-1)
    # Parameters that bfloat16 holds exactly: the kernel's route reads
    # the table in the compute type, the plain loss in float32.
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16).astype(p.dtype),
        m.init(jax.random.key(1), tokens[:, :16]))

    def plain(p):
        return _plain_nll(m, p, tokens, targets, None)

    return (m, params, (tokens, targets), jax.jit(plain)(params),
            jax.jit(jax.value_and_grad(_mean(plain)))(params))


@pytest.mark.parametrize('vocab', [512, 576], ids=['whole-tiles', 'ragged'])
@pytest.mark.parametrize('rows', ['whole', 'pad', 'ignored'])
@pytest.mark.parametrize('logit_scale', [1.0, 0.5])
@pytest.mark.parametrize('tie', [True, False], ids=['tied', 'untied'])
def test_lm_chunked_nll_on_the_kernel_route(tie, logit_scale, rows, vocab):
    """The same contract where the chunk's ``dx`` and ``dW`` are ONE
    Pallas program (``ops.pallas_head.head_grad``, under the
    interpreter here): the sum, the count and every parameter's gradient
    against the plain loss, with a vocabulary of whole tiles and one
    whose last block hangs over (576 = 4.5 tiles of 128). The kernel's
    operands are bfloat16 — ``dlogits`` rounded as the MXU reads it —
    where this machine's plain loss multiplies in float32, so the form
    of the bfloat16 cases' tolerance holds at that rounding and not at
    1e-4: 2^-8 of the norm for what the head itself gives (an untied
    table's gradient and, through ``dx``, the final norm's), 2^-6 below
    it, where a ``dx`` that differs in its last bfloat16 bit sends every
    rounding of the bfloat16 backward pass another way."""
    m, params, (tokens, targets), (want_s, want_c), (
        want, want_g) = _kernel_case(tie, logit_scale, rows, vocab)

    def nll(p):
        return m.apply(p, tokens, targets, chunk=KERNEL_ROWS,
                       method='nll_sum')

    with head_loss_traces() as traces:
        got, got_g = jax.jit(jax.value_and_grad(_mean(nll)))(params)
    assert traces == [{'route': 'kernel', 'rows': KERNEL_ROWS,
                       'row_group': 128, 'vocab_tile': 128,
                       'row_tile': 128, 'why': None}]
    got_s, got_c = jax.jit(nll)(params)
    assert float(got_c) == float(want_c) == float(jnp.sum(targets >= 0))
    np.testing.assert_allclose(float(got_s), float(want_s), rtol=2e-6)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    _assert_grads_close(
        got_g, want_g, jnp.bfloat16, tol=lambda name: (
            2 ** -8 if 'ln_f' in name or 'lm_head' in name else 2 ** -6))


def test_lm_kernel_route_through_the_sharded_train_step():
    """The kernel's route inside the step's ``shard_map``: four shards
    each scan their own 256 rows in two chunks through ``head_grad``
    (the accumulator aliased in and out of each shard's scan), and the
    psum'd loss and SGD(1.0) update are the single-shard step's."""
    m = _model(vocab_size=512, dim=KERNEL_DIM, n_layers=1,
               dtype=jnp.bfloat16)
    tokens = jax.random.randint(jax.random.key(3), (1, 1024), 0, 512)
    batch = (tokens, lm_targets(tokens))
    params = m.init(jax.random.key(1), tokens[:, :16])
    opt = optax.sgd(1.0)
    got = {}
    for width in (4, 1):
        step = make_lm_train_step(m, opt, seq_mesh(width), donate=False,
                                  loss_chunk=KERNEL_ROWS)
        with head_loss_traces() as traces:
            new_params, _, loss = step(params, opt.init(params), batch)
        assert [(t['route'], t['rows']) for t in traces] == [
            ('kernel', KERNEL_ROWS)]
        got[width] = (float(loss), jax.tree.map(
            lambda new, old: np.asarray(new - old, np.float32),
            new_params, params))
    np.testing.assert_allclose(got[4][0], got[1][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got[4][1]), jax.tree.leaves(got[1][1]),
                    strict=True):
        assert np.linalg.norm(a - b) <= 2 ** -6 * np.linalg.norm(b) + 1e-9


def test_head_tiles_takes_the_cells_and_refuses_what_is_under_a_tile():
    """The one rule behind the route: both training cells' chunks take
    the kernel at a 1024-row group (16 / 12 MiB of float32 dx resident);
    a float32 model, a width that is no multiple of 128, a vocabulary
    under four tiles and a chunk that is no multiple of the row tile
    keep the XLA body, each with its reason."""
    from distributed_dot_product_tpu.ops.pallas_head import head_tiles
    for dim, vocab in ((4096, 50432), (3072, 49152)):
        tiles, why = head_tiles(4096, dim, vocab, jnp.bfloat16)
        assert why is None and tiles == {
            'row_group': 1024, 'vocab_tile': 512, 'row_tile': 1024}
    assert head_tiles(128, 128, 512, jnp.bfloat16)[0] == {
        'row_group': 128, 'vocab_tile': 128, 'row_tile': 128}
    for args, reason in (
            ((4096, 4096, 50432, jnp.float32), 'float32'),
            ((16, 32, 32, jnp.bfloat16), 'width 32'),
            ((4096, 4096, 500, jnp.bfloat16), 'vocabulary 500'),
            ((200, 4096, 50432, jnp.bfloat16), '200 rows')):
        tiles, why = head_tiles(*args)
        assert tiles is None and reason in why


@pytest.mark.parametrize('tie', [True, False], ids=['tied', 'untied'])
def test_lm_chunked_nll_through_the_train_step(tie):
    """The step divides the psum'd sum by the psum'd count, so the
    sum's cotangent is ``1 / count`` and not 1, and each shard scans its
    own rows in two chunks: SGD(1.0) makes the updated parameters the
    plain loss's ``params - grad``."""
    tokens, targets, seg = make_copy_batch(jax.random.key(7), 2, 64,
                                           VOCAB, 16)
    m = _model(tie_embeddings=tie, logit_scale=0.5)
    m_local = m.clone(attn_kwargs=dict(distributed=False))
    params = m.init(jax.random.key(1), tokens[:, :16])
    opt = optax.sgd(1.0)
    step = make_lm_train_step(m, opt, seq_mesh(4), donate=False,
                              loss_chunk=8)
    new_params, _, loss = step(params, opt.init(params),
                               (tokens, targets, seg))

    def plain(p):
        s, c = _plain_nll(m_local, p, tokens, targets, seg)
        return s / c

    want_loss, g = jax.value_and_grad(plain)(params)
    assert float(jnp.sum(targets >= 0)) > 1
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for got_l, p_l, g_l in zip(jax.tree.leaves(new_params),
                               jax.tree.leaves(params),
                               jax.tree.leaves(g), strict=True):
        np.testing.assert_allclose(np.asarray(got_l),
                                   np.asarray(p_l - g_l),
                                   atol=2e-5, rtol=1e-4)


def test_greedy_generate_validates_steps():
    from distributed_dot_product_tpu import greedy_generate
    m = _model(attn_kwargs=dict(distributed=False))
    toks = jnp.zeros((1, 4), jnp.int32)
    params = m.init(jax.random.key(0), toks)
    with pytest.raises(ValueError, match='steps'):
        greedy_generate(m, params, toks, steps=0, t_max=8)
    with pytest.raises(ValueError, match='t_max'):
        greedy_generate(m, params, toks, steps=8, t_max=8)


def test_greedy_generate_exact_capacity_boundary():
    """Prefill writes n rows and the loop writes steps − 1 more (the
    first token comes from the prefill logits), so n + steps − 1 ==
    t_max must GENERATE — the earlier check rejected it off by one —
    while one more step must raise."""
    from distributed_dot_product_tpu import greedy_generate
    m = _model(attn_kwargs=dict(distributed=False))
    toks = jnp.zeros((1, 4), jnp.int32)
    params = m.init(jax.random.key(0), toks)
    out = greedy_generate(m, params, toks, steps=5, t_max=8)  # 4+5-1=8
    assert out.shape == (1, 5)
    with pytest.raises(ValueError, match='t_max'):
        greedy_generate(m, params, toks, steps=6, t_max=8)
    # The boundary run used every cache row and the capacity-checked
    # stream equals a roomier run's prefix (no silent tail corruption).
    roomy = greedy_generate(m, params, toks, steps=5, t_max=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(roomy))


def test_greedy_generate_reuses_compiled_programs():
    """Two greedy_generate calls with identical (model, shapes) trace
    the prefill and step programs ONCE each — the round-8 recompile
    finding: the old implementation wrapped both in fresh jax.jit
    closures per invocation, so every call paid a full trace. The
    compiled pair now lives in an LRU cache keyed by every
    shape-determining input, and the watchers are retrace-budgeted so
    a regression raises rather than silently rebuilding."""
    from distributed_dot_product_tpu import greedy_generate
    from distributed_dot_product_tpu.utils import retrace
    m = _model(attn_kwargs=dict(distributed=False))
    # Shapes unique to this test: the program cache is module-global,
    # so reusing another test's (b, n, t_max) would read its entry and
    # vacuously count zero traces.
    toks = jnp.arange(6, dtype=jnp.int32).reshape(2, 3) % VOCAB
    params = m.init(jax.random.key(0), toks)
    before_p = retrace.total('lm.generate_prefill')
    before_s = retrace.total('lm.generate_step')
    first = greedy_generate(m, params, toks, steps=4, t_max=12)
    second = greedy_generate(m, params, toks, steps=4, t_max=12)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))
    assert retrace.total('lm.generate_prefill') - before_p == 1
    assert retrace.total('lm.generate_step') - before_s == 1


def test_lm_dropout_requires_seed():
    mesh = seq_mesh(8)
    m = _model(attn_kwargs=dict(dropout_rate=0.1))
    tokens, targets, seg = make_copy_batch(jax.random.key(3), 2, 64,
                                           VOCAB, 16)
    params = m.init(jax.random.key(1), tokens[:, :16])
    opt = optax.adam(1e-3)
    step = make_lm_train_step(m, opt, mesh, donate=False)
    with pytest.raises(ValueError, match='dropout_seed'):
        step(params, opt.init(params), (tokens, targets, seg))


@pytest.mark.slow
def test_lm_copy_task_trains_and_generates_on_mesh():
    """The capstone criterion: copy-region loss below threshold on the
    8-device mesh AND greedy generation through the stacked KV caches
    reproduces the prefix."""
    from train_lm import main
    res = main(['--steps', '250', '--seq-len', '128', '--seg-len', '32',
                '--dim', '64', '--vocab', '32', '--lr', '3e-3',
                '--log-every', '100', '--remat', '--generate'])
    assert res['loss'] < 0.5, f'copy loss stayed high: {res}'
    assert res['acc'] > 0.9, f'generation failed the copy: {res}'


@pytest.mark.slow
def test_lm_checkpoint_resume_continues(tmp_path):
    """Mid-run save → restore must resume the exact trajectory (same
    step counter, same params, loss keeps improving)."""
    from distributed_dot_product_tpu import TrainState, restore, save
    mesh = seq_mesh(8)
    b, t = 2, 64
    m = _model()
    tokens, targets, seg = make_copy_batch(jax.random.key(7), b, t,
                                           VOCAB, 16)
    params = m.init(jax.random.key(1), tokens[:, :16])
    opt = optax.adam(1e-3)
    step = make_lm_train_step(m, opt, mesh, donate=False)
    ost = opt.init(params)
    for i in range(3):
        params, ost, loss0 = step(params, ost, (tokens, targets, seg))
    save(str(tmp_path), TrainState(3, params, ost))

    restored = restore(str(tmp_path), TrainState(0, params, ost))
    assert restored.step == 3
    for a, b_ in zip(jax.tree.leaves(restored.params),
                     jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    p2, o2 = restored.params, restored.opt_state
    losses = []
    for i in range(3, 6):
        p2, o2, loss = step(p2, o2, (tokens, targets, seg))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < float(loss0)


# ---------------------------------------------------------------------------
# Scanned decode: the layer loop carries the stacked caches (PR 25)
# ---------------------------------------------------------------------------

_SLOPES = tuple(2.0 ** -(i + 1) for i in range(HEADS))
_DECODE_CONFIGS = {
    'mha-alibi': dict(use_rope=False, alibi_slopes=_SLOPES),
    'gqa-window-rope': dict(num_kv_heads=2, window=6, use_rope=True),
    'int8-mirror': dict(qk_quant='int8'),
}
_T_MAX, _FILL = 16, 9


def _scanned_params(params):
    """The unrolled LM's parameters in the scanned layout (``block_i``
    subtrees stacked under ``layers/block``)."""
    stack = params['params']['stack']
    blocks = [stack[f'block_{i}'] for i in range(LAYERS)]
    rest = {k: v for k, v in params['params'].items() if k != 'stack'}
    return {'params': dict(rest, stack={'layers': {
        'block': jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)}})}


def _filled_caches(model, batch):
    """Per-layer caches holding ``_FILL`` random rows each (the int8
    mirror, where the model has one, kept by ``append_kv``)."""
    from distributed_dot_product_tpu.models.decode import append_kv
    out = []
    for i, cache in enumerate(model.make_decode_caches(batch, _T_MAX)):
        kk, kv = jax.random.split(jax.random.key(100 + i))
        rows = cache.k.shape[:2] + (_FILL, cache.k.shape[-1])
        out.append(append_kv(cache, jax.random.normal(kk, rows),
                             jax.random.normal(kv, rows)))
    return out


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
@pytest.mark.parametrize('config', sorted(_DECODE_CONFIGS))
def test_scanned_decode_matches_unrolled(config, impl):
    """The scanned stack's decode (stacked caches carried by the layer
    loop, layer l addressed in place) against the unrolled stack's (one
    cache a layer): the same logits and, layer by layer, the same
    caches — through the XLA step and the interpreted kernel."""
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    kw = dict(distributed=False, decode_impl=impl,
              **_DECODE_CONFIGS[config])
    unrolled = _model(attn_kwargs=kw, scan_layers=False)
    scanned = _model(attn_kwargs=kw, scan_layers=True)
    batch = 2
    params = unrolled.init(jax.random.key(0),
                           jnp.zeros((batch, 4), jnp.int32))
    sparams = _scanned_params(params)
    caches = _filled_caches(unrolled, batch)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    assert (jax.tree.structure(stacked) == jax.tree.structure(
        scanned.make_decode_caches(batch, _T_MAX)))

    step_u, step_s = (
        jax.jit(lambda p, t, c, m=m: m.apply(p, t, c, method='decode'))
        for m in (unrolled, scanned))
    # What decode_impl_traces says of each: the unrolled stack hands
    # every step one layer's buffers, the scanned one the stack.
    tok = jnp.zeros((batch, 1), jnp.int32)
    with decode_impl_traces() as seen:
        step_u.lower(params, tok, caches)
    assert {(t['resolved'], t['cache']) for t in seen} == {
        (impl, 'layer')}
    with decode_impl_traces() as seen:
        step_s.lower(sparams, tok, stacked)
    assert {(t['resolved'], t['cache']) for t in seen} == {
        (impl, 'stacked')}
    for i in range(3):
        tok = jnp.full((batch, 1), 3 + i, jnp.int32)
        caches, want = step_u(params, tok, caches)
        stacked, got = step_s(sparams, tok, stacked)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(stacked.length),
                                  [_FILL + 3] * LAYERS)
    for l, cache in enumerate(caches):
        for name in ('k', 'v', 'k_q', 'k_scale'):
            a, b = getattr(cache, name), getattr(stacked, name)
            assert (a is None) == (b is None), name
            if a is not None:
                np.testing.assert_allclose(
                    np.asarray(b[l]), np.asarray(a), atol=2e-5,
                    err_msg=f'layer {l} {name}')


def test_decode_step_layer_refuses_paged_cache():
    from distributed_dot_product_tpu.models.decode import (
        decode_step, init_paged_cache,
    )
    cache = init_paged_cache(2, 2, 16, 8, pages=4, page_size=8)
    q = jnp.zeros((2, 2, 1, 8))
    with pytest.raises(ValueError, match='layer'):
        decode_step(q, cache, q, q, impl='xla', layer=0)
