# -*- coding: utf-8 -*-
"""The device-side vocabulary (``obs.spans.DEVICE_SCOPES``): every scope
that applies shows up in the compiled programs' ``op_name``s, every
Pallas build carries its kernel name, and a scope adds no operation."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_dot_product_tpu.analysis.jaxpr_rules import (
    _iter_eqns, _sub_jaxprs,
)
from distributed_dot_product_tpu.models import (
    attention, lm, remat, transformer,
)
from distributed_dot_product_tpu.models.dense import dense_param_bytes
from distributed_dot_product_tpu.models.lm import (
    TransformerLM, head_loss_traces, lm_targets,
)
from distributed_dot_product_tpu.models.remat import LAYER_MATMUL_NAMES
from distributed_dot_product_tpu.obs.spans import (
    DEVICE_SCOPES, device_scope,
)
from distributed_dot_product_tpu.ops import pallas_attention, pallas_decode
from distributed_dot_product_tpu.ops.pallas_attention import (
    FLASH_RESIDUAL_NAMES, flash_attention,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh
from distributed_dot_product_tpu import train

TRAIN_SCOPES = ['ops.flash_fwd', 'ops.flash_bwd_dq', 'ops.flash_bwd_dkv',
                'lm.attn_gather', 'lm.attn_proj', 'lm.mlp', 'lm.embed',
                'lm.head_loss', 'lm.stack_carry', 'train.grad_sync',
                'train.optimizer']
DECODE_SCOPES = ['ops.flash_decode', 'lm.attn_proj', 'lm.mlp', 'lm.embed',
                 'lm.head', 'lm.stack_carry']
# The decode step of the latent-attention / sparse-expert / hyper-
# connection block (lm.mlp there: the dense layer's MLP and the shared
# experts).
LATENT_SCOPES = ['ops.mla_decode', 'lm.attn_proj', 'lm.mlp', 'lm.moe_route',
                 'lm.moe_experts', 'lm.hc', 'lm.embed', 'lm.head',
                 'lm.stack_carry']
# The decode step of a stack of window and full attention layers: the
# window layers' kernel step is the ring mode.
MIXED_SCOPES = ['ops.flash_decode', 'ops.flash_decode_ring', 'lm.attn_proj',
                'lm.mlp', 'lm.embed', 'lm.head', 'lm.stack_carry']
# A stack of recurrent, attention and latent-expert layers: the decode
# step, a prefill chunk (the chunked scan) and the reset between
# requests (snapshot and restore of the states).
HYBRID_SCOPES = ['ops.ssm_step', 'ops.ssm_scan', 'lm.ssm_proj',
                 'lm.moe_latent', 'lm.state_restore', 'ops.flash_decode',
                 'lm.attn_proj', 'lm.mlp', 'lm.moe_route', 'lm.moe_experts',
                 'lm.embed', 'lm.head', 'lm.stack_carry']
# The decode step of the two-branch recurrent / attention + expert stack
# with every multiplier set (the ``granitemoehybrid`` block): no scope of
# its own, each new multiply inside its producer's.
GRANITE_SCOPES = ['ops.ssm_step', 'lm.ssm_proj', 'ops.flash_decode',
                  'lm.attn_proj', 'lm.mlp', 'lm.moe_route',
                  'lm.moe_experts', 'lm.embed', 'lm.head',
                  'lm.stack_carry']
# The ``solar_open2`` stack (gated delta-rule layers beside a gated GQA
# layer, experts in every layer): the decode step and a prefill chunk
# (the chunked delta rule).
DELTA_SCOPES = ['ops.delta_step', 'ops.delta_scan', 'lm.delta_proj',
                'ops.flash_decode', 'lm.attn_proj', 'lm.mlp',
                'lm.moe_route', 'lm.moe_experts', 'lm.embed', 'lm.head',
                'lm.stack_carry']
# The block-sparse attention / Lightning linear-attention stack: the
# decode step (the kernel ``sparse_decode``, the state's one pass) and a
# prefill chunk (the picks' block mask under the flash forward, the
# chunked scan).
SALA_SCOPES = ['ops.sparse_select', 'ops.sparse_decode',
               'ops.sparse_prefill', 'ops.lightning_step',
               'ops.lightning_scan', 'lm.lightning_proj', 'ops.flash_fwd',
               'lm.attn_proj', 'lm.mlp', 'lm.embed', 'lm.head',
               'lm.stack_carry']
# The ``lfm2_moe`` stack (gated short-convolution layers beside a GQA
# layer at 64-wide heads on a packed slab, a dense layer and an expert
# one): the decode step (the kernel on the packed cache) and a prefill
# chunk (the flash forward over the slab's two halves).
LFM2_SCOPES = ['lm.conv_proj', 'ops.flash_decode', 'ops.flash_fwd',
               'lm.attn_proj', 'lm.mlp', 'lm.moe_route', 'lm.moe_experts',
               'lm.embed', 'lm.head', 'lm.stack_carry']


def tiny_lm(remat_policy=None, **attn_kwargs):
    return TransformerLM(vocab_size=64, dim=32, num_heads=2, n_layers=2,
                         remat=True, remat_policy=remat_policy,
                         attn_kwargs=attn_kwargs or None)


def train_step_and_args(width=2, remat_policy=None, **attn_kwargs):
    model = tiny_lm(remat_policy, **attn_kwargs)
    tokens = jax.random.randint(jax.random.key(1), (1, 64), 0, 64)
    params = model.init(jax.random.key(0), tokens)
    optimizer = optax.adamw(1e-3)
    step = train.make_lm_train_step(model, optimizer, seq_mesh(width),
                                    loss_chunk=16)
    return step, (params, optimizer.init(params),
                  (tokens, lm_targets(tokens)))


def op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


@functools.cache
def train_names(remat_policy):
    """The step as it runs (the flash backward fused: dq rides the dk/dv
    walk under ``ops.flash_bwd_dkv``) and, joined to it, the step with dq
    held past its VMEM budget: the split form, the one that opens
    ``ops.flash_bwd_dq``."""
    step, args = train_step_and_args(remat_policy=remat_policy)
    names = op_names(step.lower(*args).compile())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_attention, '_FUSED_DQ_BYTES', 0)
        step, args = train_step_and_args(remat_policy=remat_policy)
        return names | op_names(step.lower(*args).compile())


@pytest.fixture(scope='module')
def train_op_names():
    return train_names(None)


@pytest.fixture(scope='module')
def decode_op_names():
    model = tiny_lm(distributed=False, decode_impl='kernel')
    tokens = jnp.zeros((2, 1), jnp.int32)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)

    def step(p, tok, c):
        return model.apply(p, tok, c, method='decode')

    return op_names(jax.jit(step).lower(params, tokens, caches).compile())


@pytest.fixture(scope='module')
def latent_op_names():
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=2, n_layers=2,
        tie_embeddings=False, scan_layers=False,
        attn_kwargs=dict(q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8,
                         v_dim=8, decode_impl='kernel'),
        block_kwargs=dict(norm='rmsnorm', mixer='latent', ffn='experts',
                          ffn_kwargs=dict(n_experts=4, top_k=2, hidden=16),
                          residual='hyper'),
        dense_prefix=1,
        prefix_kwargs=dict(ffn='gated', ffn_kwargs=dict(hidden=48)))
    tokens = jnp.zeros((2, 1), jnp.int32)
    params = {'params': model.init(
        jax.random.key(0), jnp.zeros((2, 8), jnp.int32))['params']}
    caches = model.make_decode_caches(2, 128)

    def step(p, tok, c):
        return model.apply(p, tok, c, method='decode')

    return op_names(jax.jit(step).lower(params, tokens, caches).compile())


@pytest.fixture(scope='module')
def mixed_op_names():
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=2, n_layers=2, scan_layers=False,
        attn_kwargs=dict(distributed=False, decode_impl='kernel'),
        layer_kinds={'window': {'attn_kwargs': {'window': 16,
                                                'ring_cache': 32}},
                     'full': {}},
        layer_pattern=('window', 'full'))
    tokens = jnp.zeros((2, 1), jnp.int32)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)

    def step(p, tok, c):
        return model.apply(p, tok, c, method='decode')

    return op_names(jax.jit(step).lower(params, tokens, caches).compile())


@pytest.fixture(scope='module')
def hybrid_op_names():
    from distributed_dot_product_tpu.models.decode import (
        restore_states, snapshot_states,
    )
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=2, n_layers=3, scan_layers=False,
        tie_embeddings=False,
        attn_kwargs=dict(distributed=False, decode_impl='kernel',
                         use_rope=False),
        block_kwargs=dict(norm='rmsnorm'),
        layer_kinds={
            'E': dict(mixer='none', ffn='experts', ffn_kwargs=dict(
                n_experts=4, top_k=2, hidden=16, latent=16,
                shared_hidden=24, expert_form='plain',
                activation='relu2')),
            'M': dict(mixer='ssm', ffn='none', ssm_kwargs=dict(
                heads=4, head_dim=8, state=8, groups=2, chunk=8)),
            '*': dict(mixer='attention', ffn='none')},
        layer_pattern=('E', 'M', '*'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    names = set()
    for method, n in (('decode', 1), ('prefill', 8)):
        names |= op_names(jax.jit(
            lambda p, tok, c, m=method: model.apply(p, tok, c, method=m)
        ).lower(params, jnp.zeros((2, n), jnp.int32), caches).compile())
    return names | op_names(jax.jit(
        lambda c: restore_states(c, snapshot_states(c))
    ).lower(caches).compile())


@pytest.fixture(scope='module')
def granite_op_names():
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=2, n_layers=2, scan_layers=False,
        embed_scale=12.0, logit_scale=1 / 16,
        attn_kwargs=dict(distributed=False, decode_impl='kernel',
                         use_rope=False, softmax_scale=0.1),
        block_kwargs=dict(
            norm='rmsnorm', residual_scale=0.22, ffn='experts',
            ffn_kwargs=dict(n_experts=8, top_k=2, hidden=16,
                            shared_hidden=24, router_bias=False,
                            score='softmax_picked', experts_held=(0, 4))),
        layer_kinds={
            'mamba': dict(mixer='ssm', ssm_kwargs=dict(
                heads=4, head_dim=8, state=8, groups=1, chunk=8)),
            'attention': dict(mixer='attention')},
        layer_pattern=('mamba', 'attention'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    return op_names(jax.jit(
        lambda p, tok, c: model.apply(p, tok, c, method='decode')
    ).lower(params, jnp.zeros((2, 1), jnp.int32), caches).compile())
# The ``bailing_hybrid`` stack (delta-rule layers beside ONE latent
# layer with a head-wise output gate, group-limited experts): no scope
# of its own — the gate is ``lm.attn_proj``'s, the group selection
# ``lm.moe_route``'s.
LING_SCOPES = ['ops.delta_step', 'ops.delta_scan', 'lm.delta_proj',
               'ops.mla_decode', 'ops.flash_fwd', 'lm.attn_proj', 'lm.mlp',
               'lm.moe_route', 'lm.moe_experts', 'lm.embed', 'lm.head',
               'lm.stack_carry']


@pytest.fixture(scope='module')
def ling_op_names():
    """``{'decode': …, 'prefill': …}`` of a delta-rule + latent stack
    with full-rank KDA gates, the bounded decay, the MLA layer's
    head-wise gate and group-limited routing, both kernels on."""
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=4, n_layers=3, scan_layers=False,
        tie_embeddings=False,
        block_kwargs=dict(
            norm='rmsnorm', mixer='delta', ssm_kwargs=dict(
                heads=4, head_dim=8, chunk=8, step_impl='pallas',
                beta_scale=1.0, gate_rank=None, decay='bounded'),
            ffn='experts', ffn_kwargs=dict(
                n_experts=16, top_k=3, hidden=16, experts_held=(4, 8),
                n_group=4, topk_group=2)),
        layer_kinds={
            'D': dict(ffn='gated', ffn_kwargs=dict(hidden=48)), 'K': {},
            'A': dict(mixer='latent', attn_kwargs=dict(
                q_rank=None, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                out_gate='head', decode_impl='kernel'))},
        layer_pattern=('D', 'K', 'A'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    return {method: op_names(jax.jit(
        lambda p, tok, c, m=method: model.apply(p, tok, c, method=m)
    ).lower(params, jnp.zeros((2, n), jnp.int32), caches).compile())
        for method, n in (('decode', 1), ('prefill', 8))}


@pytest.fixture(scope='module')
def delta_op_names():
    """``{'decode': …, 'prefill': …}`` of the delta-rule / gated GQA +
    expert stack, the step's state pass as the kernel."""
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=2, n_layers=2, scan_layers=False,
        tie_embeddings=False,
        attn_kwargs=dict(distributed=False, decode_impl='kernel',
                         use_rope=False, out_gate=True),
        block_kwargs=dict(
            norm='rmsnorm', ffn='experts',
            ffn_kwargs=dict(n_experts=8, top_k=2, hidden=16,
                            experts_held=(0, 4))),
        layer_kinds={
            'kda': dict(mixer='delta', ssm_kwargs=dict(
                heads=4, head_dim=8, chunk=8, step_impl='pallas')),
            'gqa': dict(mixer='attention')},
        layer_pattern=('gqa', 'kda'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    return {method: op_names(jax.jit(
        lambda p, tok, c, m=method: model.apply(p, tok, c, method=m)
    ).lower(params, jnp.zeros((2, n), jnp.int32), caches).compile())
        for method, n in (('decode', 1), ('prefill', 8))}


@pytest.fixture(scope='module')
def sala_op_names():
    """``{'decode': …, 'prefill': …}`` of the block-sparse / Lightning
    stack, the sparse layer's step as the kernel."""
    model = TransformerLM(
        vocab_size=64, dim=32, num_heads=4, n_layers=2, scan_layers=False,
        tie_embeddings=False, embed_scale=12.0, logit_scale=0.0625,
        attn_kwargs=dict(distributed=False, decode_impl='kernel',
                         use_rope=False, out_gate=True, qk_norm=True,
                         num_kv_heads=2),
        block_kwargs=dict(norm='rmsnorm', residual_scale=0.25,
                          ffn='gated', ffn_kwargs=dict(hidden=64)),
        layer_kinds={
            'sparse': dict(mixer='attention', attn_kwargs=dict(sparse=dict(
                kernel=8, stride=4, block=16, window=32, topk=4,
                dense_len=64))),
            'lightning': dict(mixer='lightning', ssm_kwargs=dict(
                heads=4, head_dim=8, chunk=8))},
        layer_pattern=('sparse', 'lightning'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    return {method: op_names(jax.jit(
        lambda p, tok, c, m=method: model.apply(p, tok, c, method=m)
    ).lower(params, jnp.zeros((2, n), jnp.int32), caches).compile())
        for method, n in (('decode', 1), ('prefill', 16))}


@pytest.fixture(scope='module')
def lfm2_op_names():
    """``{'decode': …, 'prefill': …}`` of the short-convolution / GQA
    stack, the attention layer's step as the kernel on its packed
    cache."""
    model = TransformerLM(
        vocab_size=64, dim=128, num_heads=2, n_layers=3, scan_layers=False,
        attn_kwargs=dict(distributed=False, decode_impl='kernel',
                         num_kv_heads=1, qk_norm=True, kv_packed=True),
        block_kwargs=dict(norm='rmsnorm', ssm_kwargs=dict(taps=3),
                          ffn='experts', ffn_kwargs=dict(
                              n_experts=4, top_k=2, hidden=16, n_shared=0)),
        layer_kinds={
            'dense': dict(mixer='conv', ffn='gated',
                          ffn_kwargs=dict(hidden=48)),
            'conv': dict(mixer='conv'), 'attn': dict(mixer='attention')},
        layer_pattern=('dense', 'conv', 'attn'))
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))
    caches = model.make_decode_caches(2, 128)
    return {method: op_names(jax.jit(
        lambda p, tok, c, m=method: model.apply(p, tok, c, method=m)
    ).lower(params, jnp.zeros((2, n), jnp.int32), caches).compile())
        for method, n in (('decode', 1), ('prefill', 16))}


def opened(scope, names):
    return any(f'/{scope}/' in f'/{name}/' for name in names)


@pytest.mark.parametrize('scope', TRAIN_SCOPES)
def test_train_step_opens(scope, train_op_names):
    assert opened(scope, train_op_names)


@pytest.mark.parametrize('scope', DECODE_SCOPES)
def test_decode_step_opens(scope, decode_op_names):
    assert opened(scope, decode_op_names)


@pytest.mark.parametrize('scope', LATENT_SCOPES)
def test_latent_decode_step_opens(scope, latent_op_names):
    assert opened(scope, latent_op_names)


@pytest.mark.parametrize('scope', MIXED_SCOPES)
def test_mixed_decode_step_opens(scope, mixed_op_names):
    assert opened(scope, mixed_op_names)


@pytest.mark.parametrize('scope', HYBRID_SCOPES)
def test_hybrid_stack_opens(scope, hybrid_op_names):
    assert opened(scope, hybrid_op_names)


@pytest.mark.parametrize('scope', GRANITE_SCOPES)
def test_granite_decode_step_opens(scope, granite_op_names):
    assert opened(scope, granite_op_names)


@pytest.mark.parametrize('scope', DELTA_SCOPES)
def test_delta_stack_opens(scope, delta_op_names):
    assert opened(scope, delta_op_names['decode']
                  | delta_op_names['prefill'])


@pytest.mark.parametrize('scope', SALA_SCOPES)
def test_sala_stack_opens(scope, sala_op_names):
    assert opened(scope, sala_op_names['decode']
                  | sala_op_names['prefill'])


@pytest.mark.parametrize('scope', LFM2_SCOPES)
def test_lfm2_stack_opens(scope, lfm2_op_names):
    assert opened(scope, lfm2_op_names['decode']
                  | lfm2_op_names['prefill'])


def test_the_convolution_mixers_arithmetic_sits_in_its_scope(
        lfm2_op_names):
    """Nothing of the step's or the prefill's own arithmetic is
    unscoped; ALL of a conv mixer's — both projections, both gates, the
    taps and the window's shift — is ``lm.conv_proj``, never the
    stack's; the decode kernel sits in ``ops.flash_decode`` inside
    ``lm.attn_proj`` as on the padded slab, and a chunk's flash forward
    in ``ops.flash_fwd``."""
    def innermost(name):
        return [part for part in name.split('/') if part in DEVICE_SCOPES][
            -1]
    for method in ('decode', 'prefill'):
        mine = [n for n in lfm2_op_names[method] if n.startswith('jit(')]
        assert mine and all(
            any(opened(scope, [n]) for scope in DEVICE_SCOPES)
            for n in mine)
        inside = {innermost(n) for n in mine if '/conv.' in n}
        assert inside == {'lm.conv_proj'}
        assert any(n.endswith('/dot_general') for n in mine
                   if innermost(n) == 'lm.conv_proj')
    kernel = [n for n in lfm2_op_names['decode'] if '/flash_decode/' in n]
    assert kernel and all('/lm.attn_proj/ops.flash_decode/' in n
                          for n in kernel)


def test_the_sparse_and_lightning_arithmetic_sits_in_its_scopes(
        sala_op_names):
    """Nothing of the step's or the prefill's own arithmetic is
    unscoped; the Lightning mixer's is ``ops.lightning_step`` /
    ``ops.lightning_scan`` or ``lm.lightning_proj``, never the stack's;
    the sparse layer's selection (its ``top_k`` among it) is
    ``ops.sparse_select``, the kernel ``sparse_decode`` sits in
    ``ops.sparse_decode`` and the flash forward of a chunk inside
    ``ops.sparse_prefill``."""
    def innermost(name):
        return [part for part in name.split('/') if part in DEVICE_SCOPES][
            -1]
    for method, recurrence in (('decode', 'ops.lightning_step'),
                               ('prefill', 'ops.lightning_scan')):
        mine = [n for n in sala_op_names[method] if n.startswith('jit(')]
        assert mine and all(
            any(opened(scope, [n]) for scope in DEVICE_SCOPES)
            for n in mine)
        inside = {innermost(n) for n in mine if '/lightning.' in n}
        assert inside == {recurrence, 'lm.lightning_proj'}
        assert any(innermost(n) == 'ops.sparse_select'
                   and n.endswith('/top_k') for n in mine)
    kernel = [n for n in sala_op_names['decode'] if '/sparse_decode/' in n]
    assert kernel and all('/ops.sparse_decode/sparse_decode/' in n
                          for n in kernel)
    flash = [n for n in sala_op_names['prefill'] if '/ops.flash_fwd/' in n]
    assert flash and all('/ops.sparse_prefill/' in n for n in flash)


def test_the_delta_rules_arithmetic_sits_in_its_scopes(delta_op_names):
    """Nothing of the step's or the prefill's own arithmetic is
    unscoped, and nothing of the new mixer's is left to the stack: the
    recurrence is ``ops.delta_step`` (the kernel ``delta_step`` and the
    transposition of its column operands) or ``ops.delta_scan`` (the
    triangular solve among it), everything around it ``lm.delta_proj``,
    the attention layer's gate (a ``logistic`` of its own projection)
    ``lm.attn_proj``."""
    def innermost(name):
        return [part for part in name.split('/') if part in DEVICE_SCOPES][
            -1]
    for method, recurrence in (('decode', 'ops.delta_step'),
                               ('prefill', 'ops.delta_scan')):
        mine = [n for n in delta_op_names[method] if n.startswith('jit(')]
        assert mine and all(
            any(opened(scope, [n]) for scope in DEVICE_SCOPES)
            for n in mine)
        inside = {innermost(n) for n in mine if '/delta.' in n}
        assert inside == {recurrence, 'lm.delta_proj'}
        # the attention module's own operations, its gate among them
        assert {innermost(n) for n in mine if '/attn.' in n} <= {
            'lm.attn_proj', 'ops.flash_decode', 'ops.flash_fwd'}
    kernel = [n for n in delta_op_names['decode'] if '/delta_step/' in n]
    assert kernel and all('/ops.delta_step/delta_step/' in n
                          for n in kernel)
    assert any(innermost(n) == 'ops.delta_scan'
               and n.endswith('/triangular_solve')
               for n in delta_op_names['prefill'] if n.startswith('jit('))


@pytest.mark.parametrize('scope', LING_SCOPES)
def test_ling_stack_opens(scope, ling_op_names):
    assert opened(scope, ling_op_names['decode']
                  | ling_op_names['prefill'])


def test_the_gate_and_the_group_selection_sit_in_their_scopes(
        ling_op_names):
    """Nothing of the step's or the prefill's own arithmetic is
    unscoped. The latent layer's head-wise gate (its projection, the
    ``logistic`` and the product) is ``lm.attn_proj``'s in both forms,
    the latent kernel stays that scope's SIBLING; the group selection
    (two more ``top_k`` a layer beside the pick's own, the kept-group
    mask, the row count) is ``lm.moe_route``'s; the full-rank gates and
    the bounded decay's ``logistic`` are ``lm.delta_proj``'s."""
    def innermost(name):
        return [part for part in name.split('/') if part in DEVICE_SCOPES][
            -1]
    for method in ('decode', 'prefill'):
        mine = [n for n in ling_op_names[method] if n.startswith('jit(')]
        assert mine and all(
            any(opened(scope, [n]) for scope in DEVICE_SCOPES)
            for n in mine)
        gate = [n for n in mine if '/attn.' in n and (
            '/gate/' in n or n.endswith('/logistic'))]
        assert gate and {innermost(n) for n in gate} == {'lm.attn_proj'}
        # (the decode step's view of the cache as the kernel's one KV
        # head, a reshape between the two scopes, is the stack's)
        assert {innermost(n) for n in mine if '/attn.' in n} <= {
            'lm.attn_proj', 'ops.mla_decode', 'ops.flash_fwd',
            'lm.stack_carry'}
        picks = [n for n in mine if n.endswith('/top_k')]
        assert len(picks) >= 3
        assert {innermost(n) for n in picks} == {'lm.moe_route'}
        assert {innermost(n) for n in mine if '/delta.' in n} == {
            'lm.delta_proj',
            'ops.delta_step' if method == 'decode' else 'ops.delta_scan'}
    kernel = [n for n in ling_op_names['decode']
              if '/ops.mla_decode/' in f'/{n}/']
    assert kernel and not any('lm.attn_proj' in n for n in kernel)


def test_the_multipliers_sit_inside_their_producers_scopes(
        granite_op_names):
    """Nothing of the step's own arithmetic is unscoped; the embedding's
    multiply is ``lm.embed``'s, the logits' ``lm.head``'s, a residual's
    the stack's (no sub-scope takes it) and the gates' softmax
    ``lm.moe_route``'s."""
    mine = [n for n in granite_op_names if n.startswith('jit(')]
    assert mine and all(
        any(opened(scope, [n]) for scope in DEVICE_SCOPES) for n in mine)

    def innermost(name):
        return [part for part in name.split('/') if part in DEVICE_SCOPES][
            -1]
    ends = {(innermost(n), '/'.join(n.split('/')[-2:])) for n in mine}
    assert ('lm.embed', 'lm.embed/mul') in ends
    assert ('lm.head', 'lm.head/mul') in ends
    assert {('lm.stack_carry', f'block_{i}._scaled/mul')
            for i in (0, 1)} <= ends
    # (the softmax lowers to its parts: the gates' ``exp`` is the only
    # one in the routing)
    assert ('lm.moe_route', 'lm.moe_route/exp') in ends


def test_ring_mode_opens_inside_the_decode_kernels_scope(mixed_op_names):
    """A reader that knows only ``ops.flash_decode`` (the benchmark's
    accepted patterns) takes the ring mode for the decode kernel, never
    for the projections around it; the full layer's step has no ring
    scope."""
    assert 'ops.flash_decode_ring' in DEVICE_SCOPES
    ring = [n for n in mixed_op_names if 'ops.flash_decode_ring' in n]
    assert ring and all(
        '/ops.flash_decode/ops.flash_decode_ring/' in n for n in ring)
    assert any('/ops.flash_decode/' in f'{n}/'
               and 'ops.flash_decode_ring' not in n
               for n in mixed_op_names)


def test_latent_kernel_is_outside_the_projection_scope(latent_op_names):
    """The benchmark's accepted reader takes ``lm.attn_proj`` for the
    projections alone: the latent kernel's scope is its sibling."""
    kernel = [n for n in latent_op_names if '/ops.mla_decode/' in f'/{n}/']
    assert kernel and not any('lm.attn_proj' in n for n in kernel)


def test_the_steps_cover_the_vocabulary():
    assert (set(TRAIN_SCOPES) | set(DECODE_SCOPES) | set(LATENT_SCOPES)
            | set(MIXED_SCOPES) | set(HYBRID_SCOPES) | set(DELTA_SCOPES)
            | set(SALA_SCOPES) | set(LING_SCOPES) | set(LFM2_SCOPES)
            == set(DEVICE_SCOPES))


def test_unknown_scope_raises():
    with pytest.raises(ValueError, match='unknown device scope'):
        device_scope('nope')


@pytest.mark.parametrize('remat_policy', [None, 'nothing_saveable'])
def test_passes_show_in_op_names(remat_policy):
    """What the trace reader tells the passes by. The stack's own remat
    keeps the flash forward's output and logsumexp, so no flash forward
    is rematerialized while the projections and the MLP are; under full
    remat (``'nothing_saveable'``) it is, which is what the benchmark's
    ``recompute`` reader finds on such a program."""
    names = train_names(remat_policy)
    flash_fwd = [n for n in names if '/ops.flash_fwd/' in n]
    assert any('rematted_computation' in n for n in flash_fwd) == (
        remat_policy == 'nothing_saveable')
    for scope in ('lm.mlp', 'lm.attn_proj'):
        assert any('rematted_computation' in n and f'/{scope}/' in n
                   for n in names)
    assert any('transpose(jvp(' not in n and 'jvp(' in n for n in flash_fwd)
    flash_bwd = [n for n in names if '/ops.flash_bwd_d' in n]
    assert {n.rsplit('/', 2)[-2] for n in flash_bwd} >= {
        'flash_bwd_fused', 'flash_bwd_dq', 'flash_bwd_dkv'}
    assert all('transpose(jvp(' in n for n in flash_bwd)


def head_scan_vocab_dots(fn, *args, vocab=64):
    """The ``dot_general``s with a vocabulary-sized dimension and the
    names of the Pallas calls inside the scans opened under
    ``lm.head_loss``, and the primitives of every equation under that
    scope (``tiny_lm``'s vocabulary is 64 where its width is 32 and the
    loss's chunk 16). A sub-jaxpr's name stacks are relative to the
    equation that holds it, so the walk carries what it is under."""
    dots, kernels, under = [], [], set()

    def walk(jaxpr, in_head, in_scan):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            head = in_head or 'lm.head_loss' in str(
                eqn.source_info.name_stack)
            if head:
                under.add(name)
            if head and in_scan and name == 'dot_general' and any(
                    vocab in v.aval.shape
                    for v in (*eqn.invars, *eqn.outvars)):
                dots.append(eqn)
            if head and in_scan and name == 'pallas_call':
                kernels.append(eqn.params['name'])
                continue        # the kernel's own body is not the scan's
            for sub in _sub_jaxprs(eqn):
                walk(sub, head, in_scan or (head and name == 'scan'))

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False, False)
    return dots, kernels, under


@pytest.mark.parametrize('route', ['xla', 'kernel'])
def test_head_takes_its_gradient_in_the_forward_pass(route):
    """The "counter" of a static mechanism: differentiated, the loss's
    scan over its chunks builds a chunk's logits once and takes dx and
    dW from them, with no checkpoint to rebuild them — by two einsums
    (three vocabulary-wide matmuls a chunk) where the shapes are under
    the head kernel's tiles, by ONE Pallas program beside the logits
    matmul where ``ops.pallas_head.head_tiles`` takes them (the
    narrowest bfloat16 model it takes, here);
    ``models.lm.head_loss_traces()`` says which. Un-differentiated it
    holds the logits matmul alone on either route."""
    if route == 'xla':
        model, vocab, chunk, t = tiny_lm(distributed=False), 64, 16, 64
    else:
        vocab, chunk, t = 512, 128, 256
        model = TransformerLM(vocab_size=vocab, dim=128, num_heads=2,
                              n_layers=1, remat=True, dtype=jnp.bfloat16,
                              attn_kwargs=dict(distributed=False))
    tokens = jax.random.randint(jax.random.key(1), (1, t), 0, vocab)
    params = model.init(jax.random.key(0), tokens[:, :64])

    def loss(p):
        return model.apply(p, tokens, lm_targets(tokens), chunk=chunk,
                           method='nll_sum')[0]

    with head_loss_traces() as traces:
        dots, kernels, under = head_scan_vocab_dots(
            jax.value_and_grad(loss), params, vocab=vocab)
    assert [t['route'] for t in traces] == [route]
    assert (len(dots), kernels) == (
        (3, []) if route == 'xla' else (1, ['head_grad']))
    assert not {'checkpoint', 'custom_vjp_call'} & under
    dots, kernels, under = head_scan_vocab_dots(loss, params, vocab=vocab)
    assert len(dots) == 1 and not kernels
    assert 'custom_vjp_call' in under and 'checkpoint' not in under
    if route == 'kernel':
        return      # its compiled step: tests/test_tpu_compile.py
    # The step as compiled: the same three under the forward pass's
    # name, nothing of the head rematerialized.
    head = [n for n in train_names(None) if '/lm.head_loss/' in n]
    assert not any('rematted_computation' in n or 'checkpoint' in n
                   for n in head)
    assert len({n for n in head if n.endswith('/dot_general')
                and 'transpose(jvp(' not in n and 'jvp(' in n}) == 3
    assert not any(n.endswith('/dot_general') for n in head
                   if 'transpose(jvp(' in n)


def kernel_names(fn, *args):
    return [eqn.params['name']
            for eqn in _iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == 'pallas_call']


Q = jnp.zeros((1, 2, 64, 16), jnp.float32)


def flash_sum(**kw):
    return lambda q: flash_attention(q, q, q, causal=True, **kw).sum()


@pytest.mark.parametrize('fn, expected', [
    (flash_sum(), ['flash_fwd']),
    (jax.grad(flash_sum()), ['flash_fwd', 'flash_bwd_fused']),
    (flash_sum(qk_quant='int8'), ['flash_fwd_int8']),
    (flash_sum(softmax_mode='bounded'), ['flash_fwd_bounded', 'flash_fwd']),
], ids=['fwd', 'grad', 'int8', 'bounded'])
def test_flash_builds_carry_their_kernel_names(fn, expected):
    assert sorted(kernel_names(fn, Q)) == sorted(expected)


def checkpoint_names(fn, *args):
    return {eqn.params['name']
            for eqn in _iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == 'name'}


def assert_bitwise(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def rebuilt_matmuls(fn, *args):
    """The dense layers whose matmul the rematerialized layer body runs
    again, by their module names."""
    return {str(eqn.source_info.name_stack).rsplit('/', 1)[-1]
            for eqn in _iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == 'dot_general'
            and 'rematted_computation' in str(eqn.source_info.name_stack)}


PROJECTIONS = {'keys', 'queries', 'values'}
LAYER_MATMULS = PROJECTIONS | {'composition', 'mlp_in'}


@pytest.mark.parametrize('width', [1, 4])
def test_remat_runs_the_flash_forward_once(width):
    """The scanned stack's remat keeps the flash forward's two residuals
    by their names, so the layer body holds ONE ``flash_fwd``; full remat
    holds two. The same kernels on the same inputs: the step's loss,
    parameters and optimizer state are bit for bit the same."""
    kept, kept_args = train_step_and_args(width)
    full, full_args = train_step_and_args(width, 'nothing_saveable')
    assert kernel_names(kept, *kept_args) == [
        'flash_fwd', 'flash_bwd_fused']
    assert kernel_names(full, *full_args) == [
        'flash_fwd', 'flash_fwd', 'flash_bwd_fused']
    assert checkpoint_names(kept, *kept_args) == {
        *FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES}
    assert_bitwise(kept(*kept_args), full(*full_args))


@pytest.mark.parametrize('width', [1, 4])
def test_remat_rebuilds_no_matmul_it_kept(width):
    """The CPU has no limit to fit, so the default keeps the whole of
    ``LAYER_MATMUL_NAMES``: the rematerialized body holds no ``mlp_in``,
    q / k / v or output-projection matmul (``mlp_out``'s output is read
    by nothing in the backward: never rebuilt). Full remat, by its
    name, rebuilds all five."""
    kept, kept_args = train_step_and_args(width)
    full, full_args = train_step_and_args(width, 'nothing_saveable')
    assert rebuilt_matmuls(kept, *kept_args) == set()
    assert rebuilt_matmuls(full, *full_args) == LAYER_MATMULS
    assert checkpoint_names(full, *full_args) == {
        *FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES}


def fit_record(width=1, **attn_kwargs):
    """What ``remat_traces()`` says of the tiny train step's one stack."""
    step, args = train_step_and_args(width, **attn_kwargs)
    with remat.remat_traces() as traces:
        jax.make_jaxpr(step)(*args)
    (record,) = traces
    return record


def limit_for(record, n):
    """The least ``bytes_limit`` at which the first ``n`` names fit."""
    need = sum((record['n_layers'] - 1) * record['layer_bytes'][name]
               for name in LAYER_MATMUL_NAMES[:n])
    fixed = (record['held'] + record['layer_inputs']
             + max(record['transient'], record['layer_work']))
    return int((need + fixed) / (1 - remat._HEADROOM)) + 2


@pytest.mark.parametrize('n', [0, 1, 2, 3])
def test_the_fit_takes_each_prefix_as_the_limit_shrinks(monkeypatch, n):
    """With a device that reports a limit, the default keeps the longest
    prefix of ``LAYER_MATMUL_NAMES`` whose stacked bytes fit beside what
    the step holds; ``remat_traces()`` says which names, the first one
    refused and the budget's parts, and the rematerialized body rebuilds
    exactly the matmuls behind the names it did not keep."""
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: 1 << 40)
    roomy = fit_record()
    assert roomy['first_refused'] is None and roomy['limit'] == 1 << 40
    assert roomy['layer_bytes'] == {
        # (1, 64, 4 x 32) float32; q, k and v (1, 2, 64, 16); (1, 64, 32)
        'mlp_hidden': 32768, 'flash_qkv': 3 * 8192, 'attn_out': 8192}
    limit = limit_for(roomy, n)
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: limit)
    record = fit_record()
    assert record['kept'] == (*FLASH_RESIDUAL_NAMES,
                              *LAYER_MATMUL_NAMES[:n])
    assert record['first_refused'] == (LAYER_MATMUL_NAMES + (None,))[n]
    assert record['kept_bytes'] == sum(
        roomy['layer_bytes'][name] for name in LAYER_MATMUL_NAMES[:n])
    assert 0 <= record['budget'] - record['kept_bytes'] < 8
    assert record['budget'] == (
        limit * (1 - remat._HEADROOM) - record['held']
        - record['layer_inputs']
        - max(record['transient'], record['layer_work']))
    assert record['headroom'] == limit * remat._HEADROOM
    step, args = train_step_and_args(1)
    assert rebuilt_matmuls(step, *args) == [
        LAYER_MATMULS, PROJECTIONS | {'composition'}, {'composition'},
        set()][n]
    if n:   # a few bytes less and the n-th name is refused
        monkeypatch.setattr(remat, 'device_bytes_limit',
                            lambda _: limit - 16)
        assert fit_record()['first_refused'] == LAYER_MATMUL_NAMES[n - 1]


def test_the_step_tells_the_stack_what_it_holds(monkeypatch):
    """Inside ``make_lm_train_step`` the budget is reckoned from the
    step's own account (parameters twice, optimizer state, the
    compute-type copy; the head's chunk less the stack's gradients);
    a stack differentiated outside one assumes its own parameters four
    times and their copy."""
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: 1 << 40)
    step, (params, opt_state, _) = train_step_and_args(1)
    record = fit_record()
    size = dense_param_bytes
    assert record['held'] == 3 * size(params) + size(opt_state)
    stack = params['params']['stack']
    # 16 rows of 64 float32 logits and their gradient: less than the
    # stack's gradients, which are not live yet beside them.
    assert record['transient'] == max(0, 2 * 4 * 16 * 64 - size(stack)) == 0

    model = transformer.TransformerStack(
        dim=32, num_heads=2, n_layers=2, scan_layers=True, remat=True,
        attn_kwargs=dict(distributed=False))
    x = jnp.zeros((1, 64, 32))
    alone = model.init(jax.random.key(0), x, x, x)
    with remat.remat_traces() as traces:
        jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, x, x, x).sum()))(alone)
    assert traces[0]['held'] == 5 * size(alone)
    assert traces[0]['transient'] == 0


@pytest.mark.parametrize('width', [1, 4])
def test_remat_is_bitwise_whatever_it_keeps(monkeypatch, width):
    """The default with every name kept, with the flash residuals alone
    (a limit nothing more fits) and full remat: the same matmuls, fewer
    times — loss, parameters and optimizer state bit for bit the same,
    on one device and over four."""
    every, every_args = train_step_and_args(width)
    want = every(*every_args)
    full, full_args = train_step_and_args(width, 'nothing_saveable')
    assert_bitwise(full(*full_args), want)
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: 1 << 40)
    monkeypatch.setattr(
        remat, 'device_bytes_limit',
        lambda _, limit=limit_for(fit_record(width), 0): limit)
    flash_only, flash_args = train_step_and_args(width)
    with remat.remat_traces() as traces:
        got = flash_only(*flash_args)
    assert traces[0]['kept'] == FLASH_RESIDUAL_NAMES
    assert_bitwise(got, want)


@pytest.mark.parametrize('n', [0, 1, 2, 3])
def test_remat_policy_takes_a_policy_itself(monkeypatch, n):
    """The way back where the fitted default does not fit: a prefix of
    the names by hand, as a ``jax.checkpoint_policies`` policy. It is the
    program the fit gives at a limit that admits that prefix, equation
    for equation, and the fit is not asked."""
    by_hand = jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES[:n])
    step, args = train_step_and_args(1, by_hand)
    with remat.remat_traces() as traces:
        got = equations(jax.make_jaxpr(step)(*args).jaxpr)
    assert not traces
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: 1 << 40)
    limit = limit_for(fit_record(), n)
    monkeypatch.setattr(remat, 'device_bytes_limit', lambda _: limit)
    fitted, fitted_args = train_step_and_args(1)
    assert got == equations(jax.make_jaxpr(fitted)(*fitted_args).jaxpr)


class DescribedDevice:
    """A device as ``topologies.get_topology_desc`` describes one: it
    has a kind and no memory statistics."""
    platform = 'tpu'

    def __init__(self, device_kind):
        self.device_kind = device_kind

    def memory_stats(self):
        raise jax.errors.JaxRuntimeError(
            'INVALID_ARGUMENT: MemoryStats is only supported for '
            'addressable PjRt devices.')


class AttachedDevice(DescribedDevice):
    def memory_stats(self):
        return {'bytes_limit': 1 << 34, 'bytes_in_use': 1 << 20}


@pytest.mark.parametrize('device, limit', [
    (None, float('inf')),
    (AttachedDevice('TPU v5 lite'), 1 << 34),
    (DescribedDevice('TPU v5 lite'), 16909336064),
    (DescribedDevice('TPU v9'), 0),
], ids=['cpu', 'attached', 'described-v5e', 'described-unknown'])
def test_the_limit_is_the_compile_targets(monkeypatch, device, limit):
    """The fit's limit is the device's the step's mesh compiles for:
    what it reports; a described device's kind's, so an AOT compile
    gives the chip's program; none on the CPU (everything is kept); and
    where an accelerator says nothing and its kind is unknown, 0 — the
    flash residuals alone, the set that fitted before these names."""
    device = device or jax.devices()[0]
    assert remat.device_bytes_limit(device) == limit
    asked = []
    monkeypatch.setattr(remat, 'device_bytes_limit',
                        lambda d: asked.append(d) or limit)
    record = fit_record()
    assert set(asked) == {seq_mesh(1).devices.flat[0]}
    assert record['limit'] == limit
    assert record['kept'] == (
        *FLASH_RESIDUAL_NAMES, *(LAYER_MATMUL_NAMES if limit else ()))
    assert record['first_refused'] == (None if limit else 'mlp_hidden')


@pytest.mark.parametrize('softmax_impl', ['online', 'full'])
def test_remat_keeps_the_blocks_names_where_no_flash_forward_runs(
        softmax_impl):
    """Only ``flash_attention``'s differentiated forward emits the flash
    names and q / k / v's (the flash route, ulysses' local attention):
    the ring fold calls the kernels inside its own rule and the full
    path has none. Such a stack keeps what the block and the attention
    module's call name — the MLP's pre-activation and the output
    projection — and rebuilds its q / k / v projections; the outputs are
    full remat's."""
    kept, kept_args = train_step_and_args(softmax_impl=softmax_impl)
    full, full_args = train_step_and_args(
        remat_policy='nothing_saveable', softmax_impl=softmax_impl)
    assert checkpoint_names(kept, *kept_args) == {'mlp_hidden', 'attn_out'}
    assert rebuilt_matmuls(kept, *kept_args) == PROJECTIONS
    assert rebuilt_matmuls(full, *full_args) == LAYER_MATMULS
    record = fit_record(2, softmax_impl=softmax_impl)
    assert record['layer_bytes']['flash_qkv'] == 0
    assert record['kept'] == (*FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES)
    assert_bitwise(kept(*kept_args), full(*full_args))


def test_split_backward_build_carries_its_kernel_names(monkeypatch):
    """dq past its VMEM budget: the two backward kernels, by their names."""
    monkeypatch.setattr(pallas_attention, '_FUSED_DQ_BYTES', 0)
    assert sorted(kernel_names(jax.grad(flash_sum()), Q)) == [
        'flash_bwd_dkv', 'flash_bwd_dq', 'flash_fwd']


def test_decode_build_carries_its_kernel_name():
    cache = jnp.zeros((1, 2, 128, 16), jnp.float32)
    new = jnp.zeros((1, 2, 1, 16), jnp.float32)
    at = jnp.zeros((1,), jnp.int32)

    def step(q, k, v, ck, cv):
        return pallas_decode.flash_decode(q, k, v, ck, cv, at, at,
                                          interpret=True)[0]

    assert kernel_names(step, new, new, new, cache, cache) == [
        'flash_decode']


def test_ring_decode_build_carries_its_kernel_name():
    cache = jnp.zeros((1, 2, 128, 16), jnp.float32)
    new = jnp.zeros((1, 2, 1, 16), jnp.float32)
    at = jnp.zeros((1,), jnp.int32)

    def step(q, k, v, ck, cv):
        return pallas_decode.flash_decode(q, k, v, ck, cv, at, at,
                                          ring_span=at + 1,
                                          interpret=True)[0]

    assert kernel_names(step, new, new, new, cache, cache) == [
        'flash_decode_ring']


def test_latent_decode_build_carries_its_kernel_name():
    # the latent buffer is time-minor: (B, 1, d, t_max), the new row a
    # lane tile of identical columns
    cache = jnp.zeros((1, 1, 72, 128), jnp.float32)
    q = jnp.zeros((1, 2, 1, 72), jnp.float32)
    new = jnp.zeros((1, 1, 72, 128), jnp.float32)
    at = jnp.zeros((1,), jnp.int32)

    def step(q, k, ck):
        return pallas_decode.flash_decode(q, k, None, ck, None, at, at,
                                          latent_v=64, interpret=True)[0]

    assert kernel_names(step, q, new, cache) == ['mla_decode']


def test_hit_experts_build_carries_its_kernel_name_under_its_scope():
    """The hit-list route of an expert layer — by a caller's bound or,
    with none passed, by the rule over the call's rows — is ONE
    ``pallas_call`` named ``moe_hit_experts``, opened inside
    ``lm.moe_experts`` (whose time the expert readers take by scope);
    the sorted route holds none."""
    from distributed_dot_product_tpu.models.moe import SparseExperts
    kw = dict(n_experts=4, top_k=2, hidden=16, latent=16,
              expert_form='plain', activation='relu2')
    x = jnp.zeros((2, 3, 32), jnp.float32)
    params = SparseExperts(**kw).init(jax.random.key(0), x)
    dense = SparseExperts(**kw, dense_tokens=6).apply
    assert kernel_names(dense, params, x) == ['moe_hit_experts']
    assert kernel_names(SparseExperts(**kw).apply, params, x) == [
        'moe_hit_experts']
    assert kernel_names(SparseExperts(**kw, dense_tokens=0).apply, params,
                        x) == []
    kernel = [n for n in op_names(jax.jit(dense).lower(params, x).compile())
              if 'moe_hit_experts' in n]
    assert kernel and all('/lm.moe_experts/moe_hit_experts/' in n
                          for n in kernel)


def test_delta_step_build_carries_its_kernel_name():
    from distributed_dot_product_tpu.ops.pallas_delta import delta_step
    vec = jnp.zeros((2, 4, 8), jnp.float32)
    assert kernel_names(delta_step, vec, vec, vec, vec,
                        jnp.zeros((2, 4), jnp.float32),
                        jnp.zeros((2, 4, 8, 8), jnp.float32)) == [
        'delta_step']


def test_every_kernel_name_has_its_scope():
    assert set(pallas_attention._KERNEL_SCOPES.values()) <= set(DEVICE_SCOPES)


def equations(jaxpr):
    return [(eqn.primitive.name,
             tuple((v.aval.shape, str(v.aval.dtype)) for v in eqn.outvars
                   if hasattr(v.aval, 'shape')))
            for eqn in _iter_eqns(jaxpr)]


def test_a_scope_adds_no_operation(monkeypatch):
    """The train step as shipped against the same step traced with
    ``device_scope`` a null context (patched here, not switched in the
    program): the same primitives with the same shapes, in order."""
    step, args = train_step_and_args()
    shipped = equations(jax.make_jaxpr(step)(*args).jaxpr)
    for module in (attention, lm, transformer, train, pallas_attention,
                   pallas_decode):
        monkeypatch.setattr(module, 'device_scope',
                            lambda name: contextlib.nullcontext())
    step, args = train_step_and_args()
    bare = equations(jax.make_jaxpr(step)(*args).jaxpr)
    assert len(shipped) > 100
    assert shipped == bare
