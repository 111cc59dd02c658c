# -*- coding: utf-8 -*-
"""Recurrent (Mamba-2), attention and latent-expert layers in one stack
(the ``nemotron_h`` block: Nemotron 3 Super): the mixer's three entry
points against the literal recurrence, a fixed-size ``StateCache`` beside
a slab and no cache, snapshot and restore, one-branch blocks, the shares
of a latent expert layer against the whole layer — all against the plain
reference ``benchmarks/reference/nemotron_h.py`` at tiny widths, float32,
seeded weights."""

import hashlib
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
    StateCache, insert_session, restore_states, snapshot_states,
)
from distributed_dot_product_tpu.models.moe import (  # noqa: E402
    SparseExperts, expert_route_traces,
)
from distributed_dot_product_tpu.ops.pallas_experts import (  # noqa: E402
    HIT_LIST_ROWS, hidden_tile, hit_experts_reference, hit_list_rows,
)
from distributed_dot_product_tpu.models.ssm import (  # noqa: E402
    Mamba2Mixer,
)
from distributed_dot_product_tpu.models.transformer import (  # noqa: E402
    TransformerBlock, TransformerStack,
)

TINY = os.path.join(ROOT, 'benchmarks', 'tests', 'tiny_hybrid')
CELL = loader.Cell('tiny-nemotron.decode', root=TINY)
DRIVER, REF, CFG = CELL.driver(), CELL.reference(), CELL.config
TOL = 2e-5          # float32 on both sides; logits are O(3)


# -- (a) the mixer against the literal recurrence ------------------------

MIX = dict(dim=24, heads=8, head_dim=4, state=8, groups=2, conv=4, chunk=8)
MIX_CFG = {'mamba_num_heads': 8, 'mamba_head_dim': 4, 'n_groups': 2,
           'ssm_state_size': 8, 'conv_kernel': 4,
           'layer_norm_epsilon': 1e-5}


@pytest.fixture(scope='module')
def mixer():
    """A mixer with heads > groups, its seeded parameters, 37 normed rows
    of 2 sessions, and what the literal recurrence gives for them: the
    outputs and the state and window after the last row."""
    model = Mamba2Mixer(**MIX)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 37, 24)), jnp.float32)
    params = model.init(jax.random.key(1), h)['params']
    params = {**params,
              'A_log': jnp.log(jnp.linspace(1.0, 16.0, 8)),
              'dt_bias': jnp.asarray(rng.normal(size=8) - 3.0, jnp.float32),
              'D': jnp.asarray(rng.normal(size=8), jnp.float32),
              'conv_bias': jnp.asarray(rng.normal(size=64) * 0.1,
                                       jnp.float32),
              'norm_scale': jnp.asarray(1 + rng.normal(size=32) * 0.1,
                                        jnp.float32)}
    want = []
    with jax.default_matmul_precision('highest'):
        for b in range(2):
            want.append(REF.ssm_block(
                MIX_CFG, params, h[b], jnp.zeros((8, 4, 8)),
                jnp.zeros((3, 64))))
    out, state, window = (np.stack([np.asarray(w[i]) for w in want])
                          for i in range(3))
    return model, {'params': params}, h, out, state, window


def test_whole_sequence_matches_the_literal_recurrence(mixer):
    model, params, h, want, _, _ = mixer
    np.testing.assert_allclose(model.apply(params, h), want, atol=TOL)


@pytest.mark.parametrize('chunks', [(37,), (5, 11, 21), (8, 16, 13)],
                         ids=['whole', 'splits-a-chunk', 'aligned-then-not'])
def test_prefill_in_chunks_then_decode_continue_the_state(mixer, chunks):
    """Chunks whose lengths are no multiple of the module's chunk (8)
    and that split one, then the last 6 tokens one at a time: outputs,
    final state and window are the literal recurrence's."""
    model, params, h, want, state, window = mixer
    cache = model.make_cache(2)
    assert cache.state.shape == (2, 8, 4, 8) and cache.state.dtype == (
        jnp.float32) and cache.conv.shape == (2, 3, 64)
    got, at = [], 0
    for n in chunks:
        n = min(n, 31 - at)
        if n <= 0:
            break
        cache, out = model.apply(params, h[:, at:at + n], cache,
                                 method='prefill')
        got.append(out)
        at += n
    for i in range(at, 37):
        cache, out = model.apply(params, h[:, i:i + 1], cache,
                                 method='decode')
        got.append(out)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=TOL)
    np.testing.assert_allclose(cache.state, state, atol=TOL)
    np.testing.assert_allclose(cache.conv, window, atol=TOL)


def test_reference_recurrence_is_the_hand_computation():
    """64 tokens of one head by hand: ``y_t = sum_s (prod_{s < r <= t}
    a_r) dt_s (B_s · C_t) x_s + D x_t``."""
    rng = np.random.default_rng(2)
    cfg = {**MIX_CFG, 'mamba_num_heads': 1, 'n_groups': 1}
    x = rng.normal(size=(64, 1, 4))
    b, c = rng.normal(size=(2, 64, 1, 8))
    dt = np.exp(rng.uniform(-5, -1, size=(64, 1)))
    a_log, d = np.log(3.0), 0.7
    y, state = REF.recurrence(
        cfg, {'A_log': jnp.asarray([a_log]), 'D': jnp.asarray([d])},
        *(jnp.asarray(u, jnp.float32) for u in (x, b, c, dt)),
        jnp.zeros((1, 4, 8)))
    log_a = -dt[:, 0] * 3.0
    want = np.zeros((64, 4))
    for t in range(64):
        for s in range(t + 1):
            want[t] += (np.exp(log_a[s + 1:t + 1].sum()) * dt[s, 0]
                        * (b[s, 0] @ c[t, 0]) * x[s, 0])
        want[t] += d * x[t, 0]
    np.testing.assert_allclose(np.asarray(y)[:, 0], want, atol=1e-4)
    last = sum(np.exp(log_a[s + 1:].sum()) * dt[s, 0]
               * np.outer(x[s, 0], b[s, 0]) for s in range(64))
    np.testing.assert_allclose(np.asarray(state)[0], last, atol=1e-4)


# -- (b) the LM through state, slab and no cache against the reference ---

@pytest.fixture(scope='module')
def tokens():
    return np.random.default_rng(0).integers(
        0, CFG['vocab_size'], size=(3, 56)).astype(np.int32)


@pytest.fixture(scope='module')
def served(tokens):
    """Weights, the reference's logits of session 0, and a 3-session
    batch prefilled together in chunks of 13, 20 and 7 tokens."""
    params = DRIVER.make(CFG, 7, jnp.float32)
    REF.ROW_BLOCK = 8
    want, _, _ = REF.logits_at(CFG, params, jnp.asarray(tokens[0]), 56)
    model = DRIVER.build_lm(CFG)
    caches = model.make_decode_caches(3, 64)
    assert [type(c).__name__ for c in caches] == 5 * [
        'NoneType', 'StateCache'] + ['DecodeCache']
    logits = []
    for i, n in ((0, 13), (13, 20), (33, 7)):
        caches, out = model.apply(params, tokens[:, i:i + n], caches,
                                  method='prefill')
        logits.append(out)
    return model, params, np.asarray(want), caches, np.concatenate(
        logits, axis=1)


def _serve(model, params, caches, tokens, n):
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'))
    out = []
    for i in range(40, 40 + n):
        caches, logits = step(params, tokens[:, i:i + 1], caches)
        out.append(logits)
    return caches, np.concatenate(out, axis=1)


def test_full_forward_matches_the_reference(tokens, served):
    _, params, want, _, _ = served
    model = DRIVER.build_lm(CFG, distributed=False)
    np.testing.assert_allclose(model.apply(params, tokens[:1])[0], want,
                               atol=TOL)


def test_prefill_and_decode_match_the_reference(tokens, served):
    model, params, want, caches, prefilled = served
    _, first = _serve(model, params, caches, tokens, 16)
    np.testing.assert_allclose(
        np.concatenate([prefilled[0], first[0]]), want, atol=TOL)


def test_a_request_after_restore_reads_what_the_first_did(tokens, served):
    """The snapshot at the prompt's end, 16 tokens, the states put back
    and the slab's length set back: the same logits bit for bit; with
    the length alone set back (what a slab or a ring needs) they
    differ."""
    model, params, _, caches, _ = served
    taken = snapshot_states(caches)
    assert [type(s).__name__ for s in taken] == 5 * [
        'NoneType', 'StateCache'] + ['NoneType']
    after, first = _serve(model, params, caches, tokens, 16)

    def rewind(layers):
        return [c._replace(length=jnp.asarray(40, jnp.int32))
                if hasattr(c, 'length') else c for c in layers]
    restore = jax.jit(lambda c, s: rewind(restore_states(c, s)),
                      donate_argnums=(0,))
    lengths_only = rewind(after)
    _, stale = _serve(model, params, lengths_only, tokens, 16)
    assert np.max(np.abs(stale - first)) > 100 * TOL
    _, again = _serve(model, params, restore(after, taken), tokens, 16)
    np.testing.assert_array_equal(again, first)
    # The snapshot is left intact: a third request restores from it too.
    assert all(not s.state.is_deleted() for s in taken if s is not None)


def test_sessions_prefilled_alone_and_inserted_equal_the_batch(tokens,
                                                               served):
    model, params, _, together, _ = served
    batch = model.make_decode_caches(3, 64)
    for s in range(3):
        one = model.make_decode_caches(1, 64)
        for i, n in ((0, 13), (13, 20), (33, 7)):
            one, _ = model.apply(params, tokens[s:s + 1, i:i + n], one,
                                 method='prefill')
        batch = [insert_session(c, s, o) for c, o in zip(batch, one)]
    for got, want in zip(batch, together):
        assert type(got) is type(want)
        if isinstance(want, StateCache):
            np.testing.assert_allclose(got.state, want.state, atol=TOL)
            np.testing.assert_allclose(got.conv, want.conv, atol=TOL)
        elif want is not None:
            assert int(got.length) == int(want.length) == 40
            np.testing.assert_allclose(got.k, want.k, atol=TOL)
            np.testing.assert_allclose(got.v, want.v, atol=TOL)


def test_the_drivers_shape_table_is_the_models_tree():
    model = DRIVER.build_lm(CFG)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))['params']
    flat = {tuple(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {path: shape for path, (shape, _) in
                    DRIVER.shapes(CFG).items()}


# -- (c) the shares of a latent expert layer add up to the layer ----------

@pytest.mark.parametrize('dense_tokens', [0, 24], ids=['sorted', 'dense'])
def test_four_shares_of_a_latent_layer_add_up_to_the_uncut_layer(
        dense_tokens):
    """16 plain relu2 experts in a latent of 12 over 4 holders of 4,
    top-6 with a correction bias and scaling 5, each holder through its
    own copy of ``W_up``, the shared expert counted once (holder 0 adds
    it): the parts add up to the reference's whole layer — through the
    sorted grouped matmuls, and with every held expert run on all 24
    tokens (``dense_tokens``)."""
    dim, lat, hidden, shared, n_exp, k = 16, 12, 10, 20, 16, 6
    cfg = {'num_experts_per_tok': k, 'norm_topk_prob': True,
           'routed_scaling_factor': 5,
           'published': {'n_routed_experts': n_exp}}
    rng = np.random.default_rng(1)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    whole = {'router': draw(dim, n_exp),
             'router_bias': jnp.asarray(rng.normal(size=n_exp) * 0.05,
                                        jnp.float32),
             'latent_down': {'kernel': draw(dim, lat)},
             'latent_up': {'kernel': draw(lat, dim)},
             'w_up': draw(n_exp, lat, hidden),
             'w_down': draw(n_exp, hidden, lat),
             'shared': {'up': {'kernel': draw(dim, shared)},
                        'down': {'kernel': draw(shared, dim)}}}
    x = jnp.asarray(rng.normal(size=(24, dim)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want, picks, _ = REF.expert_layer(cfg, whole, x)
    total = 0
    for share in range(4):
        lo, hi = 4 * share, 4 * share + 4
        layer = SparseExperts(
            n_experts=n_exp, top_k=k, hidden=hidden, latent=lat,
            shared_hidden=shared, expert_form='plain',
            activation='relu2', scaling=5.0, experts_held=(lo, hi),
            add_shared=share == 0, dense_tokens=dense_tokens)
        mine = {**whole, 'w_up': whole['w_up'][lo:hi],
                'w_down': whole['w_down'][lo:hi]}
        if share:
            del mine['shared']
        (y, counts), sown = layer.apply({'params': mine}, x,
                                        mutable=['counters'])
        assert 'w_gate' not in jax.eval_shape(
            lambda: layer.init(jax.random.key(0), x))['params']
        np.testing.assert_array_equal(
            np.sort(sown['counters']['expert_picks'], -1),
            np.sort(picks, -1))
        assert int(counts.sum()) == 24 * k
        total = total + y
    np.testing.assert_allclose(total, want, atol=TOL)


# The text jax lowers the commit before this architecture's to (CPU):
# the expert layer, and prefill and decode of the two expert cells' tiny
# presets, on the SORTED route (``dense_tokens=0``: a call of these few
# rows takes the hit list by the rule). The switches since are
# Python-level branches only.
PARENT_LOWERED = {
    'experts':
        '3ee5a11d5306ab89107c9c4389ffa206c0cc05e51e0b049f90807a8f6335e10e',
    # (the two xing4 entries: as they lower since PR 47 — the latent
    # cache is time-minor, 576 values a token and not 640, so prefill
    # writes a chunk transposed, the expansion reads the latent with the
    # rank axis major and the XLA step scatters a column: by necessity
    # another text; before it '7ceed997…' / 'f8d9c828…')
    'xing4.prefill':
        '077d39206758d21cfa6bbace2a432ff246fd98498b7823aeb692d08cf40d86fb',
    'xing4.decode':
        'a68c9dc891550dcb2ede3d8905d35095532f8cb1fd59a392f8186197af595a15',
    'command-a.prefill':
        'fbdb22fb6692f9668153737612b7941d38b78890936b992fea05e6f31ccf4db9',
    'command-a.decode':
        '77b4a278f438d0348bb3fb405253ebd737bfb712dc16375d112330032ac8f4cc',
}
# The commit before the ``granitemoehybrid`` fields (a residual's scale,
# the embedding's, the softmax's, the router's score form): the hybrid
# cell's tiny preset as its driver builds it, the two training cells'
# loss and gradient (``system.build_lm``, one device), and the slab
# decode cell's prefill and step. Every new field at its default adds
# no operation.
PARENT_LOWERED.update({
    'nemotron.prefill':
        '641a893b06658614a73289cbbe8537c00018ba45134cdc2235e9f95f658b7c0e',
    'nemotron.decode':
        '29cca6473706468a888f6b950ff60344f8c9b8eb03221748e1b07596747eeee8',
    'mpt.prefill':
        '10e556381e01ad86b88bfba14996ee41e20c32e1f01456153b48e99785384ffc',
    'mpt.decode':
        '225cd013e0f58c88df7ca82bb05f6ccbb244788f6d84d910e166e89f6ed64b33',
})
# The two training cells' loss and gradient are the program of the commit
# that changed what a rematted layer keeps (PR 37: the flash residuals
# and all of ``LAYER_MATMUL_NAMES`` where no device limit is reported;
# before it '78a3b10d…' / '5b25661c…'). The serving programs above run
# the same named MLP and attention output and are still the parents'
# text.
PARENT_LOWERED.update({
    'mpt.train':
        '46ba319f6662428fd948107bb9e6f30e991f832e233aaa0819cc71790e9af6c1',
    'starcoder2.train':
        '141ef923cfceebdff205047b3e7c94bcfdb121b72cfd08b5829cc2f2c2433129',
})
# The commit before the ``solar_open2`` fields (a third recurrent mixer
# kind, the attention module's output gate): the two-branch recurrent +
# expert cell's tiny preset, the seventh accepted cell.
PARENT_LOWERED.update({
    'granite.prefill':
        '9e4fed301e5aaaf94dd54ceeb359fdd6754bc80911c060a908f66b0ac8a2cb39',
    'granite.decode':
        '18dfff1be57a8be18ecef2dc1ae688f97e0613f46b9511ab82944e699a0b6f6b',
})
# PR 40 changed the flash kernels' bodies (a block's position arithmetic
# goes by the block's kind, ALiBi's bias is a vector a block, offsets
# given as ints are static through the custom_vjp), so every program
# above that holds a flash kernel — the five prefills and the two
# training steps — is by necessity another text and is pinned to that
# commit's. Before it they read: command-a.prefill '1a428526…',
# granite.prefill '8833b977…', mpt.prefill '815742e4…', mpt.train
# '34cf8c89…', nemotron.prefill '031b8d9a…', starcoder2.train 'a1eb34db…',
# xing4.prefill '204781b5…'.
# Every ``*.decode`` entry and ``experts`` are the values they were: no
# decode step moved.
# The commit before the ``bailing_hybrid`` fields (PR 46: a latent mixer
# as one KIND of a mixed stack with ``q_rank=None`` / ``out_gate``, the
# delta mixer's ``gate_rank`` / ``decay``, the experts' ``n_group`` /
# ``topk_group``, ``LatentCache`` moved beside the other caches): the
# delta-rule cell's and the block-sparse / Lightning cell's tiny presets
# as their drivers build them, the eighth and ninth accepted cells. With
# these, every accepted cell's programs are pinned: each new field at
# its default adds no operation.
PARENT_LOWERED.update({
    'solar.prefill':
        'ae6062cbb942cbee3e8574b213a639c35eca7f896b3224c563e3f7061983488b',
    'solar.decode':
        '4d37eb3e635a0288704d0714139e4be9e5e970e433a7621bf69c4480fd4a4943',
    'sala.prefill':
        'be003dd10ce02b306b0bca6125a4910d71480ca882cff06569d66948160653a6',
    'sala.decode':
        'ff86461db157c36d6d46cb3c6a95423b30f337842b411a9eead072f00b161fdc',
})
# The commit before the hit-list route's choices stopped sorting on a TPU
# (PR 49): the tenth cell's tiny preset, its prefill held to the SORTED
# route as the cell's 4096-row chunk takes it and its step as built (the
# hit list). Off the TPU both make their choices by ``lax.top_k`` as the
# parent did, and on a TPU the sorted route does: the parent's text.
PARENT_LOWERED.update({
    'ling.prefill':
        '2f87789b3fd68f201ab9d197b0bd8ec2003adc251522b7e879dcba9d80275fb1',
    'ling.decode':
        '74f022ef4fa7db0154742184af4b0a7d3598740cbf03d1f9b9726c61d6e4677c',
})
# PR 51 (the ``lfm2_moe`` fields: a fourth recurrent mixer kind, the
# packed slab ``kv_packed``, the route rule's second quantity) left every
# entry above as it was: all ten accepted cells' programs lower to the
# parent's text. The eleventh cell's tiny preset is pinned as this PR
# lowers it — the conv mixer, the packed slab's XLA step, two 16-row
# expert calls on the hit list — so that the next field is held to it.
PARENT_LOWERED.update({
    'lfm2.prefill':
        '2c7bd95a34b9987a11b77f432d6622a72ac78826ef487f47442b787626591e64',
    'lfm2.decode':
        '42af8d237d55ca4564c9bbbe781af6402f14fe4aac564a7c561069666463f643',
})
PRESETS = {'granite': ('tiny_granite', 'tiny-granite.decode'),
           'lfm2': ('tiny_lfm2', 'tiny-lfm2.decode'),
           'ling': ('tiny_ling', 'tiny-ling.decode'),
           'solar': ('tiny_solar', 'tiny-solar.decode'),
           'sala': ('tiny_sala', 'tiny-sala.decode'),
           'xing4': ('tiny_latent', 'tiny-xing4.decode'),
           'command-a': ('tiny_mixed', 'tiny-command-a.decode'),
           'nemotron': ('tiny_hybrid', 'tiny-nemotron.decode'),
           'mpt': ('tiny', 'tiny-mpt.decode'),
           'starcoder2': ('tiny', 'tiny-starcoder2.decode')}


# Since PR 37 a gated MLP names its pre-activation for a checkpoint. A
# ``name`` equation lowers to nothing, so every decode step above is
# still its parent's text (xing4's two programs, re-pinned at PR 40 and
# PR 47, are pinned as they lower).
def _sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize('what', sorted(PARENT_LOWERED))
def test_accepted_programs_lower_to_the_parents_text(what):
    if what == 'experts':
        layer = SparseExperts(n_experts=8, top_k=2, hidden=8,
                              dense_tokens=0)
        x = jnp.zeros((2, 6, 16), jnp.float32)
        params = jax.eval_shape(lambda: layer.init(jax.random.key(0), x))
        assert set(params['params']) == {
            'router', 'router_bias', 'w_gate', 'w_up', 'w_down', 'shared'}
        assert _sha(jax.jit(layer.apply).lower(params, x)) == (
            PARENT_LOWERED[what])
        return
    name, method = what.split('.')
    root, cell = PRESETS[name]
    cell = loader.Cell(cell, root=os.path.join(ROOT, 'benchmarks', 'tests',
                                               root))
    if name in ('mpt', 'starcoder2'):
        from benchmarks import system
        model = system.build_lm(cell.config, distributed=False)
    else:
        model = cell.driver().build_lm(cell.config)
    if method == 'train':
        tok = jnp.zeros((1, 64), jnp.int32)
        params = jax.eval_shape(lambda: model.init(jax.random.key(0), tok))

        def loss(p, t):
            total, count = model.apply(p, t, t, method='nll_sum', chunk=32)
            return total / count
        assert _sha(jax.jit(jax.value_and_grad(loss)).lower(
            params, tok)) == PARENT_LOWERED[what]
        return
    if name in ('xing4', 'command-a') or what == 'ling.prefill':
        experts = {**model.block_kwargs['ffn_kwargs'], 'dense_tokens': 0}
        model = model.clone(block_kwargs={**model.block_kwargs,
                                          'ffn_kwargs': experts})
    tok = jnp.zeros((2, 8 if method == 'prefill' else 1), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((2, 8), jnp.int32)))
    caches = jax.eval_shape(lambda: model.make_decode_caches(2, 32))
    fn = jax.jit(lambda p, t, c: model.apply(p, t, c, method=method))
    assert _sha(fn.lower(params, tok, caches)) == PARENT_LOWERED[what]


def test_the_dense_route_of_a_gated_layer_is_the_sorted_one():
    """The accepted layer's form (three gated matmuls at the stream's
    width) through both routes, a held share and a shared expert: one
    result, and the dense route sorts nothing."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 5, 16)),
                    jnp.float32)
    kw = dict(n_experts=8, top_k=3, hidden=12, experts_held=(2, 7))
    params = SparseExperts(**kw).init(jax.random.key(0), x)
    sorted_y, counts = SparseExperts(**kw, dense_tokens=0).apply(params, x)
    dense = SparseExperts(**kw, dense_tokens=10)
    dense_y, dense_counts = dense.apply(params, x)
    np.testing.assert_allclose(dense_y, sorted_y, atol=TOL)
    np.testing.assert_array_equal(dense_counts, counts)
    text = jax.jit(dense.apply).lower(params, x).as_text()
    assert 'stablehlo.sort' not in text     # no sort by expert
    # past the bound the same module takes the sorted route
    assert 'stablehlo.sort' in jax.jit(dense.apply).lower(
        params, jnp.zeros((3, 5, 16))).as_text()


# -- (c') the dense route's kernel: the hit experts and no others ---------

def _layer_and_input(form, latent, held, seed=7, tokens=(3, 5)):
    """A layer of 8 experts, top-3, scaling 2, and an input of which
    tokens pick experts inside AND outside ``held``."""
    kw = dict(n_experts=8, top_k=3, hidden=12, scaling=2.0,
              experts_held=held, expert_form=form,
              activation='relu2' if form == 'plain' else 'silu',
              latent=latent, shared_hidden=20 if latent else None)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=tokens + (16,)),
                    jnp.float32)
    return kw, x, SparseExperts(**kw).init(jax.random.key(seed), x)


@pytest.mark.parametrize('held', [None, (2, 7)], ids=['whole', 'share'])
@pytest.mark.parametrize('latent', [None, 8], ids=['stream', 'latent'])
@pytest.mark.parametrize('form', ['plain', 'gated'])
def test_the_dense_route_is_the_sorted_route(form, latent, held):
    """One result through the sorted grouped matmuls and through the
    kernel over the call's hit list, for both forms of an expert, in the
    stream and in a latent, holding every expert and a share that some
    picks fall outside of; the dense route sorts nothing."""
    kw, x, params = _layer_and_input(form, latent, held)
    want, counts = SparseExperts(**kw, dense_tokens=0).apply(params, x)
    dense = SparseExperts(**kw, dense_tokens=15)
    with expert_route_traces() as traces:
        got, dense_counts = dense.apply(params, x)
    assert traces == [{'route': 'hit_list', 'select': 'sort', 'n': 15,
                       'bound': 15, 'bound_by': 'caller', 'tile': 12}]
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_array_equal(dense_counts, counts)
    if held:
        lo, hi = held
        assert int(counts[:lo].sum() + counts[hi:].sum()) > 0
    assert 'stablehlo.sort' not in jax.jit(dense.apply).lower(
        params, x).as_text()


@pytest.mark.parametrize('rows', [2 * HIT_LIST_ROWS, 2 * HIT_LIST_ROWS + 1],
                         ids=['at-the-bound', 'one-past'])
@pytest.mark.parametrize('dense_tokens', [None, 200, 0],
                         ids=['rule', 'caller', 'never'])
def test_the_calls_rows_choose_the_route(dense_tokens, rows):
    """The accepted gated layer (silu, ``w_gate``) holding a sub-range
    beside two shared experts averaged: with no bound passed the call's
    rows choose — the hit list up to ``hit_list_rows`` of the stream's
    width (two MXU passes of ``HIT_LIST_ROWS`` here: a 16-wide row keeps
    next to nothing resident; one pass for a stream past 2048), the
    sorted route one row past it — and a caller's integer is its own
    bound (0: never). Whichever route, one result: the sorted route's, and for the
    routed part the batched form's over every held expert.
    ``expert_route_traces`` says which, by whose bound, at what tile."""
    kw = dict(n_experts=8, top_k=3, hidden=256, n_shared=2, scaling=2.0,
              shared_combine='mean', experts_held=(2, 7))
    x = jnp.asarray(np.random.default_rng(rows).normal(size=(rows, 16)),
                    jnp.float32)
    params = SparseExperts(**kw).init(jax.random.key(1), x[:4])
    want, counts = SparseExperts(**kw, dense_tokens=0).apply(params, x)
    layer = SparseExperts(**kw, dense_tokens=dense_tokens)
    with expert_route_traces() as traces:
        got, got_counts = layer.apply(params, x)
    assert hit_list_rows(16) == hit_list_rows(2048) == 2 * HIT_LIST_ROWS
    assert hit_list_rows(2049) == hit_list_rows(4096) == HIT_LIST_ROWS
    bound = hit_list_rows(16) if dense_tokens is None else dense_tokens
    assert traces == [{
        'route': 'hit_list' if rows <= bound else 'sorted',
        'select': 'sort', 'n': rows, 'bound': bound,
        'bound_by': 'rule' if dense_tokens is None else 'caller',
        'tile': hidden_tile(16, 256, 3, 4) if rows <= bound else None}]
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_array_equal(got_counts, counts)
    assert int(counts[:2].sum() + counts[7:].sum()) > 0

    p = params['params']
    scores = jax.nn.sigmoid(jnp.dot(x, p['router'],
                                    precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + p['router_bias'], 3)
    gates = jnp.take_along_axis(scores, picked, -1)
    gates = 2.0 * gates / gates.sum(-1, keepdims=True)
    table = jnp.zeros((rows, 8)).at[jnp.arange(rows)[:, None],
                                    picked].set(gates)
    routed, _ = layer.clone(add_shared=False).apply(params, x)
    np.testing.assert_allclose(
        routed, hit_experts_reference(x, table[:, 2:7], p['w_gate'],
                                      p['w_up'], p['w_down'], jax.nn.silu),
        atol=TOL)


@pytest.mark.parametrize('preset', ['xing4', 'command-a', 'nemotron'])
def test_a_decode_step_of_every_expert_cell_takes_the_hit_list(preset):
    """The three expert cells' tiny presets: every expert layer of a
    decode step is on the hit-list route — the two gated cells' by the
    rule (their drivers pass no bound), the hybrid cell's by its
    driver's own — and a 300-row prefill chunk of a gated cell is on the
    sorted one."""
    root, name = {**PRESETS, 'nemotron': (
        'tiny_hybrid', 'tiny-nemotron.decode')}[preset]
    cell = loader.Cell(name, root=os.path.join(ROOT, 'benchmarks', 'tests',
                                               root))
    model = cell.driver().build_lm(cell.config)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((2, 8), jnp.int32)))
    caches = jax.eval_shape(lambda: model.make_decode_caches(2, 256))

    def routes(method, tokens):
        with expert_route_traces() as traces:
            jax.jit(lambda p, t, c: model.apply(
                p, t, c, method=method)).lower(
                    params, jnp.zeros((2, tokens), jnp.int32), caches)
        assert traces
        return ({t['route'] for t in traces}, {t['n'] for t in traces},
                {t['bound_by'] for t in traces})
    by = 'caller' if preset == 'nemotron' else 'rule'
    assert routes('decode', 1) == ({'hit_list'}, {2}, {by})
    if by == 'rule':
        assert routes('prefill', 150) == ({'sorted'}, {300}, {by})


def _routed(hit, n=10, held=6, wide=16, hidden=12, gated=False, seed=3):
    """Operands of ``hit_experts`` whose tokens pick exactly the experts
    ``hit`` (two of them a token where there are two)."""
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)
    gates = np.zeros((n, held), np.float32)
    counts = np.zeros((held,), np.int32)
    for row in range(n if hit else 0):
        for e in {hit[row % len(hit)], hit[(row + 1) % len(hit)]}:
            gates[row, e] = rng.uniform(0.2, 1.0)
            counts[e] += 1
    return dict(tokens=draw(n, wide), gates=jnp.asarray(gates),
                w_gate=draw(held, wide, hidden) if gated else None,
                w_up=draw(held, wide, hidden),
                w_down=draw(held, hidden, wide)), jnp.asarray(counts)


@pytest.mark.parametrize('hit', [(), (4,), (1, 2, 5), (0, 3, 5),
                                 tuple(range(6))],
                         ids=['none', 'one', 'some', 'gaps', 'all'])
@pytest.mark.parametrize('gated', [False, True], ids=['plain', 'gated'])
def test_the_kernel_is_the_batched_form_over_the_hit_experts(gated, hit):
    """``moe_hit_experts`` against the two (three) batched matmuls over
    every held expert, at hit counts of none (zeros), one, some (and
    some with an unhit expert between every two hit ones) and all held
    experts — and whatever the UNHIT experts' weights hold: they are NaN
    here, and are never read."""
    from distributed_dot_product_tpu.models.moe import ACTIVATIONS
    from distributed_dot_product_tpu.ops.pallas_experts import (
        hit_experts, hit_experts_reference, hit_list,
    )
    act = ACTIVATIONS['silu' if gated else 'relu2']
    ops, counts = _routed(list(hit), gated=gated)
    hits, count = hit_list(counts)
    assert int(count) == len(hit)
    assert tuple(np.asarray(hits)[:len(hit)]) == hit
    want = hit_experts_reference(**ops, act=act)
    if not hit:
        assert not np.any(want)
    unhit = jnp.asarray([e not in hit for e in range(6)])[:, None, None]
    poisoned = {k: v if v is None or k in ('tokens', 'gates')
                else jnp.where(unhit, jnp.nan, v) for k, v in ops.items()}
    got = hit_experts(hits=hits, count=count, act=act, **poisoned)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_two_hit_counts_share_one_compiled_program():
    """The hit count is a value, not a shape: steps that hit one and
    five of six held experts run one trace of one program (the retrace
    sentinel's budget of 1 holds), and hidden tiles narrower than the
    layer (two of 128 here) give the same numbers."""
    from distributed_dot_product_tpu.utils import retrace
    from distributed_dot_product_tpu.models.moe import ACTIVATIONS
    from distributed_dot_product_tpu.ops.pallas_experts import (
        hit_experts, hit_experts_reference, hit_list,
    )
    act = ACTIVATIONS['relu2']

    def step(ops, counts):
        hits, count = hit_list(counts)
        return hit_experts(ops['tokens'], ops['gates'], hits, count, None,
                           ops['w_up'], ops['w_down'], act, tile=128)
    watched = retrace.watch_traces(step, 'test.moe_hit_experts', budget=1)
    jitted = jax.jit(watched)
    for hit in [(3,), (0, 1, 2, 4, 5)]:
        ops, counts = _routed(list(hit), hidden=256, seed=len(hit))
        np.testing.assert_allclose(
            jitted(ops, counts),
            hit_experts_reference(**ops, act=act), atol=TOL)
    assert watched._graphlint_counter.count == 1


@pytest.mark.parametrize('form', ['plain', 'gated'])
def test_the_dense_route_differentiates_as_the_sorted_route(form):
    """``jax.grad`` reaches the kernel through the batched form (its
    differentiation rule): the gradients of every parameter and of the
    input are the sorted route's."""
    kw, x, params = _layer_and_input(form, 8, (2, 7))
    params = {'params': params['params']}       # init sows counters too

    def loss(params, x, dense_tokens):
        y, _ = SparseExperts(**kw, dense_tokens=dense_tokens).apply(
            params, x)
        return jnp.sum(jnp.sin(y))
    want = jax.grad(loss, argnums=(0, 1))(params, x, 0)
    got = jax.grad(loss, argnums=(0, 1))(params, x, 15)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=TOL),
                 got, want)


# -- (d) one-branch blocks ------------------------------------------------

def _rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(np.square(x), -1, keepdims=True)
                       + eps) * scale


@pytest.mark.parametrize('kind', ['mixer-alone', 'ffn-alone'])
def test_one_branch_block_is_one_norm_one_branch_one_residual(kind):
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 6, 16)),
                    jnp.float32)
    common = dict(dim=16, num_heads=2, norm='rmsnorm', norm_eps=1e-5)
    if kind == 'mixer-alone':
        block = TransformerBlock(
            **common, ffn='none', attn_kwargs=dict(
                distributed=False, causal=True, softmax_impl='full'))
        params = block.init(jax.random.key(0), x)
        assert set(params['params']) == {'ln1', 'attn'}
        h = _rms(np.asarray(x), np.asarray(
            params['params']['ln1']['scale']))
        branch = block.bind(params).attn(h, h, h, None)
    else:
        block = TransformerBlock(**common, mixer='none', ffn='gated',
                                 ffn_kwargs={'hidden': 24})
        params = block.init(jax.random.key(0), x)
        assert set(params['params']) == {'ln1', 'mlp'}
        h = _rms(np.asarray(x), np.asarray(
            params['params']['ln1']['scale']))
        branch = block.bind(params).mlp(h)
        assert block.apply(params, x, None, method='decode')[0] is None
    np.testing.assert_allclose(block.apply(params, x), x + branch,
                               atol=TOL)


def test_a_block_without_either_branch_is_refused():
    x = jnp.zeros((1, 4, 16))
    for kw in (dict(mixer='none', ffn='none'),
               dict(mixer='none', parallel=True),
               dict(mixer='state-space')):
        with pytest.raises(ValueError):
            TransformerBlock(dim=16, num_heads=2, **kw).init(
                jax.random.key(0), x)
    with pytest.raises(ValueError, match='unrolled'):
        TransformerStack(
            dim=16, num_heads=2, scan_layers=True,
            block_kwargs={'mixer': 'ssm', 'ffn': 'none', 'ssm_kwargs': {
                'heads': 4, 'head_dim': 4, 'state': 4}}).init(
                    jax.random.key(0), x, x, x)


def test_the_configuration_file_states_its_cut():
    import json
    with open(os.path.join(ROOT, 'benchmarks', 'configs',
                           'nemotron-3-super-serve.json')) as f:
        cfg = json.load(f)
    with open('/opt/skills/guides/model-configs/architectures.jsonl'
              ) if os.path.exists(
            '/opt/skills/guides/model-configs/architectures.jsonl'
    ) else open(os.devnull) as f:
        rows = [json.loads(line) for line in f]
    assert cfg['reduced'] == [
        'num_hidden_layers', 'hybrid_override_pattern', 'n_routed_experts',
        'vocab_size', 'num_nextn_predict_layers']
    assert set(cfg['reduced_why']) == set(cfg['published']) == set(
        cfg['reduced'])
    assert cfg['published']['hybrid_override_pattern'].count(
        cfg['hybrid_override_pattern']) == 4
    widths = dict(
        hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64,
        ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
        num_attention_heads=32, num_key_value_heads=2, head_dim=128,
        moe_latent_size=1024, moe_intermediate_size=2688,
        moe_shared_expert_intermediate_size=5376, num_experts_per_tok=22,
        routed_scaling_factor=5, layer_norm_epsilon=1e-5)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg['published']['n_routed_experts'] == 512
    assert cfg['experts_held'] == [0, cfg['n_routed_experts']] == [0, 128]
    for row in rows:
        if row['source_url'] == cfg['source']:
            differ = {k for k, v in row['config'].items()
                      if cfg.get(k) != v}
            assert differ == set(cfg['reduced'])
            assert {k: cfg['published'][k] for k in differ} == {
                k: row['config'][k] for k in differ}
    # The arithmetic of the cut: 4.648 B parameters.
    count = sum(int(np.prod(shape))
                for shape, _ in DRIVER.shapes(cfg).values())
    assert abs(count - 4.648e9) < 1e6
