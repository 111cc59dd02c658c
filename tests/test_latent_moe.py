# -*- coding: utf-8 -*-
"""The latent-attention / sparse-expert / hyper-connection block
(``models/latent.py``, ``moe.py``, ``hyper.py``, composed by
``TransformerBlock``) against the plain reference of the architecture
(``benchmarks/reference/xing4.py``, loaded by path: it imports nothing
from the program), at the tiny preset of
``benchmarks/tests/tiny_latent``: d 64, 4 heads, latent 32 + rope 16,
8 experts top-2 + 1 shared, ``hc_mult`` 4, 1 dense + 2 expert layers.

Tolerances. Both sides float32 on the CPU: the reference sums in another
order (dense sum over experts, whole-row softmax), so logits of unit
scale differ by < 1e-4 (``F32_TOL``; measured 8e-6). The bfloat16
program against the float32 reference: a top-2 pick that flips at a
near-tie moves ONE token's logits by O(1) at this size (measured: 3 of
64 positions off by 0.55-2.2, the rest by 0.02-0.17), which no rounding
tolerance covers, so the reference is fed the program's picks as it is
fed its tokens (``forced_picks``) and the picks are judged apart, by the
reference's own router scores: ``BF16_TOL`` 0.5 on the largest logit
error (measured 0.15); the float8 control must exceed it (measured
1.9), and its own picks differ at more than a tenth of the (token,
layer) pairs where the float32 reference's differ at fewer. The regret
of the bfloat16 program's picks (how far its worst pick lies below the
reference's k-th best score + bias) is the width of a flipped near-tie,
under ``REGRET_TOL`` 0.05 (measured 0.005-0.011 over four seeds; the
float8 control reads 0.16-0.22); a pick that is simply wrong reads ten
times the tolerance.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import decode_latent as driver  # noqa: E402
from distributed_dot_product_tpu.models import hyper, latent  # noqa: E402
from distributed_dot_product_tpu.models.moe import SparseExperts  # noqa: E402

F32_TOL = 1e-4
BF16_TOL = 0.5
REGRET_TOL = 0.05
T, T_MAX, SEED = 32, 128, 4_000_000_007


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = load(os.path.join(ROOT, 'benchmarks', 'reference', 'xing4.py'),
           'reference_xing4')


def config(dtype='float32'):
    with open(os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_latent',
                           'benchmarks', 'configs',
                           'tiny-xing4.json')) as f:
        cfg = json.load(f)
    cfg['precision'] = {'params': dtype, 'compute': dtype}
    return cfg


@pytest.fixture(scope='module')
def tokens():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, 128, (2, T)), jnp.int32)


@pytest.fixture(scope='module')
def sound(tokens):
    """float32 weights and the reference's logits ``(2, T, vocab)``."""
    cfg = config()
    params = driver.make(cfg, SEED, jnp.float32)
    ref = np.stack([np.asarray(REF.logits_at(cfg, params, tokens[b], T)[0])
                    for b in range(2)])
    return cfg, params, ref


def served(model, params, tokens, chunks=(16, 8), steps=8, cfg=None):
    """Logits of chunked prefill then ``steps`` decode steps, the
    caches, and (with ``cfg``) the expert picks the program made,
    ``(batch, expert layers, T, k)``."""
    b = tokens.shape[0]
    caches = model.make_decode_caches(b, T_MAX)
    out, picks, at = [], [], 0
    spans = [(n, 'prefill') for n in chunks] + [(1, 'decode')] * steps
    for n, method in spans:
        (caches, lg), sown = model.apply(
            params, tokens[:, at:at + n], caches, method=method,
            mutable=['counters'])
        out.append(lg)
        at += n
        if cfg is not None:
            picked = driver.sown_counters(cfg, sown)['expert_picks']
            picks.append(picked.reshape(picked.shape[0], b, n, -1))
    logits = np.asarray(jnp.concatenate(out, axis=1), np.float32)
    if cfg is None:
        return logits, caches
    return logits, caches, np.moveaxis(np.concatenate(picks, axis=2), 0, 1)


def tree_shapes(init):
    """``{path: shape}`` of the parameters ``init()`` would make."""
    tree = jax.eval_shape(init)['params']
    return {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_call_matches_reference(sound, tokens):
    cfg, params, ref = sound
    got = driver.build_lm(cfg).apply(params, tokens)
    assert np.max(np.abs(np.asarray(got) - ref)) < F32_TOL


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
def test_prefill_then_decode_matches_reference(sound, tokens, impl):
    cfg, params, ref = sound
    got, caches = served(driver.build_lm(cfg, decode_impl=impl), params,
                         tokens)
    assert np.max(np.abs(got - ref)) < F32_TOL
    assert np.all(np.asarray(caches.length) == T)


@pytest.mark.parametrize('impl', ['xla', 'kernel'])
def test_bfloat16_program_within_tolerance_float8_reference_outside(
        tokens, impl):
    cfg = config('bfloat16')
    params = driver.make(cfg, SEED, jnp.bfloat16)
    got, _, picks = served(driver.build_lm(cfg, decode_impl=impl), params,
                           tokens, cfg=cfg)
    errs, differ, regret = {}, {}, {}
    for name, operands in (('float32', None),
                           ('float8', jnp.float8_e4m3fn)):
        ref, own, regrets = zip(*[REF.logits_at(
            cfg, params, tokens[b], T, operands,
            forced_picks=jnp.asarray(picks[b])) for b in range(2)])
        errs[name] = float(np.max(np.abs(got - np.stack(ref))))
        differ[name] = float(np.mean(np.any(
            np.sort(np.stack(own), -1) != np.sort(picks, -1), -1)))
        regret[name] = float(np.max(np.stack(regrets)))
    assert errs['float32'] < BF16_TOL < errs['float8'], errs
    assert differ['float32'] < 0.1 < differ['float8'], differ
    assert regret['float32'] < REGRET_TOL < regret['float8'], regret


def test_absorbed_decode_equals_expanded_attention_on_the_same_cache():
    attn = latent.LatentAttention(
        dim=64, num_heads=4, q_rank=24, kv_rank=32, nope_dim=16,
        rope_dim=16, v_dim=16, decode_impl='xla',
        rope_scaling=(('factor', 64),
                      ('original_max_position_embeddings', 64),
                      ('mscale', 1), ('mscale_all_dim', 1)))
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    params = attn.init(jax.random.key(2), x)
    cache = attn.make_cache(3, 2, 64)
    cache, _ = attn.apply(params, x[:, :23], cache, 1, method='prefill')
    # The 24th token through both forms, from the same 23 cached rows.
    _, expanded = attn.apply(params, x[:, 23:], cache, 1, method='prefill')
    filled, absorbed = attn.apply(params, x[:, 23:], cache, 1,
                                  method='decode')
    assert np.max(np.abs(np.asarray(expanded - absorbed))) < 1e-5
    assert np.asarray(filled.length).tolist() == [[0, 0], [24, 24], [0, 0]]
    whole = attn.apply(params, x)
    assert np.max(np.abs(np.asarray(whole[:, 23:] - absorbed))) < 1e-5


def test_head_groups_of_expanded_attention_agree(monkeypatch):
    attn = latent.LatentAttention(
        dim=64, num_heads=4, q_rank=24, kv_rank=32, nope_dim=16,
        rope_dim=16, v_dim=16)
    x = jax.random.normal(jax.random.key(3), (1, 16, 64))
    params = attn.init(jax.random.key(4), x)
    whole = attn.apply(params, x)
    monkeypatch.setattr(latent, 'HEAD_GROUP', 2)
    by_two = attn.apply(params, x)
    assert np.max(np.abs(np.asarray(whole - by_two))) < 1e-5


def test_shares_of_experts_held_add_up_to_the_uncut_layer(sound):
    cfg, params, _ = sound
    lp = params['params']['stack']['block_1']['moe']
    x = jax.random.normal(jax.random.key(5), (24, 64))
    want, want_picks, _ = REF.expert_layer(cfg, lp, x)
    total = 0.0
    for i, lo in enumerate(range(0, 8, 2)):
        layer = SparseExperts(n_experts=8, top_k=2, hidden=32, n_shared=1,
                              scaling=2.0, experts_held=(lo, lo + 2),
                              add_shared=i == 0)
        share = {k: (v[lo:lo + 2] if k.startswith('w_') else v)
                 for k, v in lp.items()}
        if i:
            del share['shared']
        y, counts = layer.apply({'params': share}, x)
        total = total + y
        assert np.asarray(counts).tolist() == np.bincount(
            np.asarray(want_picks).ravel(), minlength=8).tolist()
    assert np.max(np.abs(np.asarray(total - want))) < 1e-5


def test_h_res_is_doubly_stochastic(sound):
    cfg, params, _ = sound
    hc = hyper.HyperConnection()
    x = 3.0 * jax.random.normal(jax.random.key(6), (2, 8, 4, 64))
    p = {'params': params['params']['stack']['block_0']['hc_attn']}
    _, h_post, h_res = hc.apply(p, x)
    assert np.max(np.abs(np.asarray(h_res.sum(-1)) - 1)) < 1e-5
    assert np.max(np.abs(np.asarray(h_res.sum(-2)) - 1)) < 1e-5
    assert np.all(np.asarray(h_post) > 0)
    ref = REF.hyper_matrices(cfg, p['params'], x.reshape(16, 4, 64))
    assert np.max(np.abs(np.asarray(h_res).reshape(16, 4, 4)
                         - np.asarray(ref[2]))) < 1e-5


@pytest.mark.parametrize('dropped', ['h_post', 'h_res'])
def test_a_dropped_mix_fails_the_comparison(sound, tokens, monkeypatch,
                                            dropped):
    from distributed_dot_product_tpu.models import transformer
    cfg, params, ref = sound

    def mix_back(x, y, h_post, h_res):
        if dropped == 'h_post':
            h_post = jnp.ones_like(h_post)
        else:
            h_res = jnp.broadcast_to(jnp.eye(4), h_res.shape)
        return hyper.mix_back(x, y, h_post, h_res)
    monkeypatch.setattr(transformer, 'mix_back', mix_back)
    got, _ = served(driver.build_lm(cfg, decode_impl='xla'), params,
                    tokens)
    # dropping H_res moves least (the streams start as equal copies,
    # which any doubly stochastic mix leaves alone): 3.9e-3, 39 x F32_TOL
    assert np.max(np.abs(got - ref)) > 10 * F32_TOL


def test_reference_judges_the_programs_picks_by_its_own_scores(
        sound, tokens):
    """float32 against float32 the program's picks are the reference's
    (regret 0 up to an exact tie); with one token's last pick swapped
    for its worst-ranked expert the regret is the distance the
    reference's scores put between them."""
    cfg, params, ref = sound
    _, _, picks = served(driver.build_lm(cfg, decode_impl='xla'), params,
                         tokens, cfg=cfg)
    assert picks.shape == (2, 2, T, 2)
    _, own, regret = REF.logits_at(cfg, params, tokens[0], T,
                                   forced_picks=jnp.asarray(picks[0]))
    assert np.array_equal(np.sort(own, -1), np.sort(picks[0], -1))
    assert float(np.max(regret)) < 1e-6
    lp = params['params']['stack']['block_1']['moe']
    x = jax.random.normal(jax.random.key(8), (4, 64))
    gates, own, regret = REF.route(cfg, lp, x)
    assert np.all(np.asarray(regret) == 0)
    ranked = (jax.nn.sigmoid(x @ lp['router']) + lp['router_bias'])
    wrong = np.array(own)
    wrong[2, -1] = int(np.argmin(ranked[2]))
    forced_gates, still_own, regret = REF.route(cfg, lp, x,
                                                jnp.asarray(wrong))
    assert np.array_equal(still_own, own)
    assert np.asarray(regret)[[0, 1, 3]].tolist() == [0, 0, 0]
    np.testing.assert_allclose(
        regret[2], np.sort(ranked[2])[-2] - np.min(ranked[2]), rtol=1e-5)
    assert float(regret[2]) > 10 * REGRET_TOL
    assert float(forced_gates[2, wrong[2, -1]]) > 0


@pytest.mark.parametrize('why', ['latent', 'experts', 'dense_prefix'])
def test_a_scanned_stack_of_the_new_blocks_is_refused(why):
    """XLA's grouped matmul takes an expert layer's weights whole, so a
    scanned layer's slice of the stack is a copy of them a token; the
    latent cache is carried from block to block; a dense prefix makes
    two layer kinds."""
    from distributed_dot_product_tpu import TransformerLM
    kw = {'latent': dict(block_kwargs={'mixer': 'latent'},
                         attn_kwargs={'q_rank': 8, 'kv_rank': 8,
                                      'nope_dim': 8, 'rope_dim': 8,
                                      'v_dim': 8}),
          'experts': dict(block_kwargs={
              'ffn': 'experts',
              'ffn_kwargs': {'n_experts': 4, 'top_k': 2, 'hidden': 8}}),
          'dense_prefix': dict(dense_prefix=1)}[why]
    model = TransformerLM(vocab_size=16, dim=16, num_heads=2, n_layers=2,
                          scan_layers=True, **kw)
    with pytest.raises(ValueError, match='scan_layers=False'):
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))


def test_shape_table_is_the_models_tree():
    cfg = config()
    flat = tree_shapes(lambda: driver.build_lm(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert flat == {k: v[0] for k, v in driver.shapes(cfg).items()}


@pytest.mark.parametrize('name', ['starcoder2-3b', 'mpt-7b',
                                  'mpt-7b-serve'])
def test_accepted_configurations_keep_their_parameter_trees(name):
    from benchmarks import system, weights
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           f'{name}.json')) as f:
        cfg = json.load(f)
    flat = tree_shapes(lambda: system.build_lm(cfg).init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))
    assert flat == {k: v[0] for k, v in weights.shapes(cfg).items()}


def _latent_operands(held, new):
    """The latent kernel's operands from row-major ones: the buffer
    ``([L,] B, t, d)`` time-minor with its unit head axis, the new rows
    ``(B, d)`` as lane tiles of identical columns."""
    rows = jnp.swapaxes(held, -1, -2)[..., None, :, :]
    tile = jnp.broadcast_to(new[:, None, :, None],
                            (new.shape[0], 1, new.shape[1], 128))
    return rows, tile


def test_latent_kernel_mode_matches_xla_formulation():
    """``flash_decode(latent_v=)`` (interpret mode) against a plain
    softmax over the one time-minor buffer: unequal fills, an idle
    slot, the append in place, other layers untouched."""
    from distributed_dot_product_tpu.ops.pallas_decode import flash_decode
    rng = np.random.default_rng(7)
    layers, b, h, t_max, d, dv = 3, 3, 4, 512, 72, 48
    held = jnp.asarray(rng.normal(size=(layers, b, t_max, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    length = jnp.asarray([5, 300, 140], jnp.int32)
    append = jnp.asarray([5, 300, -1], jnp.int32)
    cache, tile = _latent_operands(held, new)
    out, rows, v, *_ = flash_decode(
        q, tile, None, cache, None, length, append, layer=1, latent_v=dv,
        scale=0.2, block_k=128, interpret=True)
    assert v is None
    want_rows = np.array(held)
    for i in range(2):
        want_rows[1, i, int(length[i])] = np.asarray(new[i])
    assert np.array_equal(np.asarray(rows[:, :, 0]),
                          np.swapaxes(want_rows, -1, -2))
    held = want_rows[1]
    s = np.einsum('bhd,btd->bht', np.asarray(q[:, :, 0]), held) * 0.2
    s = np.where(np.arange(t_max) <= np.asarray(length)[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum('bht,btd->bhd', p, held[..., :dv])
    assert np.max(np.abs(np.asarray(out[:, :, 0]) - want)) < 1e-4


def test_yarn_frequencies_match_the_reference():
    from distributed_dot_product_tpu.ops.rope import yarn_inv_freq
    cfg = config()
    rs = cfg['rope_scaling']
    got = yarn_inv_freq(
        cfg['qk_rope_head_dim'], base=cfg['rope_theta'],
        factor=rs['factor'],
        original_max=rs['original_max_position_embeddings'],
        beta_fast=rs['beta_fast'], beta_slow=rs['beta_slow'])
    np.testing.assert_allclose(got, np.asarray(REF.yarn_inv_freq(cfg)),
                               rtol=1e-6)
    # Published sizes too: 64 rotary dims, factor 64 over 4096.
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           'xing4-29b-a4b-serve.json')) as f:
        big = json.load(f)
    rs = big['rope_scaling']
    got = yarn_inv_freq(64, base=10000.0, factor=64, original_max=4096)
    np.testing.assert_allclose(got, np.asarray(REF.yarn_inv_freq(big)),
                               rtol=1e-6)
    assert got[0] == 1.0 and np.isclose(got[-1] * 64, 10000.0 ** (-62 / 64))
