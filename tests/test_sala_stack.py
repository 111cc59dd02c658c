# -*- coding: utf-8 -*-
"""A tiny ``minicpm_sala`` stack (one block-sparse attention layer to
three Lightning layers, muP multipliers, an untied head) through
``TransformerLM``'s normal entry points against the plain reference
``benchmarks/reference/minicpm_sala.py`` on seeded weights: prefill +
decode through the caches against the reference's full forward, the
program's block picks judged by the reference's own block scores — and
the same comparison failing where the program is broken: a state kept in
bfloat16, dense attention in place of the picked rows, a residual
multiplier left out."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loader  # noqa: E402
from distributed_dot_product_tpu.models.decode import (  # noqa: E402
    SparseCache, StateCache,
)

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_sala')
T, PROMPT = 192, 160


@pytest.fixture(scope='module')
def cell():
    return loader.Cell('tiny-sala.decode', root=TINY)


@pytest.fixture(scope='module')
def seeded(cell):
    driver = cell.driver()
    params = driver.make(cell.config, 11, jnp.float32)
    tokens = np.random.default_rng(5).integers(
        0, cell.config['vocab_size'], size=T).astype(np.int32)
    return driver, params, tokens


def served(driver, config, params, tokens, **attn_overrides):
    """Prefill ``PROMPT`` tokens in two chunks, then decode the rest one
    token at a time: the logits of every position, the block picks
    ``(1, KV heads, T, topk)`` and the three states."""
    model = driver.build_lm(config, **attn_overrides)
    caches = model.make_decode_caches(1, T)
    assert [type(c) for c in caches] == [SparseCache] + 3 * [StateCache]
    tok = jnp.asarray(tokens)[None]
    logits, picks = [], []
    programs = {method: jax.jit(
        lambda p, t, c, m=method: model.apply(p, t, c, method=m,
                                              mutable=['counters']))
        for method in ('prefill', 'decode')}

    def run(method, chunk, caches):
        (caches, out), sown = programs[method](params, chunk, caches)
        got = driver.sown_picks(config, sown)[0]
        return caches, out, got
    for lo, hi in ((0, 80), (80, PROMPT)):
        caches, out, got = run('prefill', tok[:, lo:hi], caches)
        logits.append(out[0])
        picks.append(got[:, 0])                      # (L, G, n, k)
    for t in range(PROMPT, T):
        caches, out, got = run('decode', tok[:, t:t + 1], caches)
        logits.append(out[0])
        picks.append(got[:, 0, :, None])             # (L, G, 1, k)
    states = np.stack([np.asarray(c.state[0]) for c in caches[1:]])
    return (np.concatenate(logits), np.concatenate(picks, axis=2), states)


def readings(cell, driver, params, tokens, logits, picks, states, **kw):
    """What the driver's ``correct`` reads, over all ``T`` positions:
    the widest logit difference, the share of picks the reference would
    have made otherwise, their regret and the states' distance."""
    ref = cell.reference()
    ref.ROW_BLOCK = 64
    want, differ, regret, ref_states = ref.logits_at(
        cell.config, params, jnp.asarray(tokens), T,
        forced_picks=jnp.asarray(picks), **kw)
    sparse = (np.arange(T) + 1) > cell.config['sparse_config']['dense_len']
    from benchmarks.drivers.decode_granite import state_gap
    return (float(np.max(np.abs(np.asarray(want) - logits))),
            float(np.mean(np.asarray(differ)[..., sparse])),
            float(np.max(np.asarray(regret)[..., sparse])),
            state_gap(states, ref_states))


def test_prefill_and_decode_are_the_references_forward(cell, seeded):
    """float32 on both sides. Tolerances: the logits have a standard
    deviation of ~0.06 (a 64-wide head over 4) and agree to float32
    rounding through four layers (1e-5); the picks are the reference's
    own at every position (a flipped near-tie would read 1 / 256); the
    states agree to 1e-5 of a head's norm. Every position from 65 on
    picks 4 of up to 16 blocks."""
    driver, params, tokens = seeded
    logits, picks, states = served(driver, cell.config, params, tokens)
    assert picks.shape == (1, 2, T, 4)
    gap, differ, regret, off = readings(cell, driver, params, tokens,
                                        logits, picks, states)
    assert gap < 1e-5 and differ == 0.0 and regret < 1e-6 and off < 1e-5
    assert float(np.std(logits)) > 0.02
    # the reference without the program's picks picks the same blocks
    ref = cell.reference()
    ref.ROW_BLOCK = 64
    own = ref.logits_at(cell.config, params, jnp.asarray(tokens), T)[0]
    assert float(np.max(np.abs(np.asarray(own) - logits))) < 1e-5


def test_the_kernels_step_is_the_references_forward_too(cell, seeded):
    driver, params, tokens = seeded
    logits, picks, states = served(driver, cell.config, params, tokens,
                                   decode_impl='kernel')
    gap, differ, regret, off = readings(cell, driver, params, tokens,
                                        logits, picks, states)
    assert gap < 1e-5 and differ == 0.0 and off < 1e-5


def test_dense_attention_in_place_of_the_picked_rows_fails(cell, seeded):
    """The reference attending EVERY row where the program attended its
    picks: the logits part by 1000 times the sound tolerance."""
    driver, params, tokens = seeded
    logits, picks, states = served(driver, cell.config, params, tokens)
    gap, _, _, off = readings(cell, driver, params, tokens, logits, picks,
                              states, dense=True)
    assert gap > 1e-2 and off > 1e-3


def test_a_state_kept_in_bfloat16_fails(cell, seeded):
    driver, params, tokens = seeded
    config = {**cell.config, 'precision': {
        **cell.config['precision'], 'state': 'bfloat16'}}
    logits, picks, states = served(driver, config, params, tokens)
    gap, _, _, off = readings(cell, driver, params, tokens, logits, picks,
                              states.astype(np.float32))
    assert off > 1e-3 and gap > 1e-4


def test_the_multipliers_are_in_the_numbers(cell, seeded):
    """``scale_depth`` read at the PUBLISHED depth, ``scale_emb`` and
    ``dim_model_base``: a stack built with another value of each reads
    far from the reference."""
    driver, params, tokens = seeded
    for key, value in (('scale_depth', 1.0), ('scale_emb', 1.0),
                       ('dim_model_base', 64)):
        config = {**cell.config, key: value}
        logits, picks, states = served(driver, config, params, tokens)
        gap = readings(cell, driver, params, tokens, logits, picks,
                       states)[0]
        assert gap > 1e-3, key
    model = driver.build_lm(cell.config)
    assert model.block_kwargs['residual_scale'] == pytest.approx(
        1.4 / 32 ** 0.5)
    assert model.embed_scale == 12.0 and model.logit_scale == 16 / 64


def test_the_shape_table_is_the_models_own_tree(cell, seeded):
    driver, params, _ = seeded
    model = driver.build_lm(cell.config)
    mine = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert jax.tree.map(lambda x: x.shape, mine['params']) == jax.tree.map(
        lambda x: x.shape, params['params'])
    scales = params['params']['stack']['block_0']['attn']['keys_norm']
    assert scales.dtype == jnp.float32
    assert abs(float(jnp.mean(scales)) - 3 ** 0.5) < 0.05
