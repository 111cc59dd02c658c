# -*- coding: utf-8 -*-
"""
The names a compiled program gives its DEVICE time.

:func:`device_scope` is a ``jax.named_scope`` from the fixed
:data:`DEVICE_SCOPES` vocabulary: it puts the name on the JAX name stack
and so into every HLO instruction's ``op_name`` that is traced under it.
It reads no clock, adds no operation and costs nothing at run time; a
profiler trace carries the names (the benchmark's
``benchmarks/scopes.py`` reads device time by scope and by pass from
them). Pallas kernels carry the matching ``name=`` (``flash_fwd`` for
``ops.flash_fwd``: Mosaic takes the kernel name as a symbol, hence no
dot).

A leaf: the kernels and the model open these scopes, so this module
imports nothing of the package. Host time is named by
:func:`distributed_dot_product_tpu.obs.spans.span`, which reads the
clock and never goes inside a jitted function; ``obs.spans`` re-exports
the two names below for the readers that learned them there.
"""

__all__ = ['DEVICE_SCOPES', 'device_scope']


# Every name a compiled program may put on its operations, with what it
# covers. Scopes nest (a kernel's inside ``lm.attn_proj`` inside
# ``lm.stack_carry``); a reader attributes an operation to the
# innermost one, so each entry below reads "… that is in no scope
# further in". Prefixes follow the host spans' (``ops.``, ``lm.``,
# ``train.``).
DEVICE_SCOPES = {
    'ops.flash_fwd': 'the Pallas flash-attention forward kernel (exact, '
                     'bounded and int8-score builds; the remat forward '
                     'is the same kernel under a checkpoint name stack)',
    'ops.flash_bwd_dq': 'the Pallas flash-attention dq kernel',
    'ops.flash_bwd_dkv': 'the Pallas flash-attention dk/dv kernel, and '
                         'the fused dq/dk/dv kernel that walks as it does',
    'ops.flash_decode': 'the fused Pallas decode step kernel (append + '
                        'attend, any number of new rows)',
    'ops.flash_decode_ring': 'the same kernel in its ring mode, on a '
                             'window layer\'s recycled cache (append '
                             'column and valid interval apart); opened '
                             'INSIDE ops.flash_decode, so a reader that '
                             'knows only that name still takes it for '
                             'the decode kernel',
    'ops.mla_decode': 'the same kernel in its latent mode: one buffer of '
                      'compressed rows appended and streamed once, all '
                      'heads the rows of one score matmul, values the '
                      'leading columns of the same block',
    'lm.attn_gather': 'the all-gather of the softmax-table side (queries, '
                      'values, segment ids) over the sequence axis',
    'lm.attn_proj': 'the attention module outside its kernels: the four '
                    'projections, RoPE / ALiBi preparation, head '
                    'reshapes, padding, cache append',
    'lm.mlp': 'ln2, mlp_in, GELU and mlp_out of a block; the gated '
              'SiLU MLP of a dense layer and the shared expert of an '
              'expert layer',
    'lm.moe_route': 'an expert layer around its experts: router matmul, '
                    'sigmoid, top-k, gates, the sort by expert and the '
                    'gather into it, the weighted combine back to token '
                    'order, the per-expert token counts; on the '
                    'hit-list route (a call of few rows: a decode step) '
                    'the gate table and the step\'s hit list',
    'lm.moe_experts': 'the routed experts\' grouped matmuls (gate, up, '
                      'down over the rows sorted by expert) and their '
                      'activation; on the hit-list route (a call of few '
                      'rows: a decode step) the Pallas kernel '
                      'moe_hit_experts, which streams the experts the '
                      'step picked, scales by the gates and adds the '
                      'picks up',
    'lm.moe_latent': 'an expert layer whose experts live in a latent: the '
                     'projection of the stream down to it (once a token) '
                     'and of the combined expert output back up',
    'lm.ssm_proj': 'a Mamba-2 mixer outside its recurrence: the input '
                   'and output projections, the causal depthwise '
                   'convolution, softplus / decay, the skip, the gate and '
                   'the grouped norm',
    'ops.ssm_step': 'decode: the one read-modify-write pass over a '
                    'recurrent layer\'s state (decay, outer-product '
                    'update, the read against C)',
    'ops.ssm_scan': 'prefill / whole sequence: the recurrence in its '
                    'chunked form (decay-masked C·B^T products inside a '
                    'chunk, the state stepped between chunks)',
    'lm.delta_proj': 'a gated delta-rule mixer outside its recurrence: '
                     'the input projection, the three causal depthwise '
                     'convolutions, the L2 norms of q and k, the '
                     'low-rank decay (softplus) and rate (sigmoid), the '
                     'per-head norm under its sigmoid gate and the '
                     'output projection',
    'ops.delta_step': 'decode: the one pass over a delta-rule layer\'s '
                      'state (decay a key channel, the reduction '
                      'against k, the rank-one correction, the read '
                      'against q): the Pallas kernel delta_step and the '
                      'transposition of its column operands, or the two '
                      'XLA fusions that stand for it',
    'ops.delta_scan': 'prefill / whole sequence: the delta rule in its '
                      'chunked form (the chunks\' decay-difference '
                      'products, the batched triangular inverse, the '
                      'scan over chunks)',
    'lm.lightning_proj': 'a Lightning linear-attention mixer outside its '
                         'recurrence: the input projection, the '
                         'per-head norms of q and k, their rotation, '
                         'the output norm under its sigmoid gate and the '
                         'output projection',
    'ops.lightning_step': 'decode: the one read-modify-write pass over a '
                          'Lightning layer state (constant decay a '
                          'head, outer-product update, the read against '
                          'q): ssm.state_step, one XLA fusion',
    'ops.lightning_scan': 'prefill / whole sequence: the Lightning '
                          'recurrence in its chunked form '
                          '(ssm.chunked_scan)',
    'lm.conv_proj': 'a gated short-convolution mixer, all of it: the input '
                    'and output projections, both gates, the depthwise '
                    'taps over the carried window and the new rows, and '
                    'the window\'s shift',
    'ops.sparse_select': 'the selection of a block-sparse attention '
                         'layer: the pooled-key rows a step or a chunk '
                         'completes, the scores against the pooled keys, '
                         'softmax, the sum over a KV head\'s query heads, '
                         'the max-pool to blocks, the forced blocks, '
                         'top-k and the sort of the picks',
    'ops.sparse_decode': 'decode: the Pallas kernel sparse_decode (the '
                         'new row appended in place, the picked blocks '
                         'moved by the kernel\'s own copies, an online '
                         'softmax over them), or the gathered softmax '
                         'that stands for it',
    'ops.sparse_prefill': 'prefill / whole sequence of a block-sparse '
                          'layer outside its selection: the block mask '
                          'of the picks and the flash forward under it',
    'lm.state_restore': 'copies of the recurrent layers\' states: the '
                        'snapshot at a prompt\'s end and the restore '
                        'from it between requests',
    'lm.hc': 'a hyper-connection residual: the norm over the widened '
             'stream, the three Phi products, sigmoid / Sinkhorn, the '
             'pre-mix into the branch input and the post / residual '
             'mix back into the stream',
    'lm.embed': 'the embedding gather (and its scatter-add backward)',
    'lm.head_loss': 'training: ln_f and the chunked scan that takes '
                    'loss, dx and dW from one set of logits (the logits '
                    'matmul, logsumexp, and the head_grad kernel or the '
                    'two einsums it stands for)',
    'lm.head': 'prefill / decode: ln_f and the head matmul',
    'lm.stack_carry': 'the layer stack outside its blocks\' sub-scopes: '
                      'ln1, residual adds, and the scan\'s own slicing, '
                      'copying and updating of stacked parameters, '
                      'gradients and KV caches',
    'train.grad_sync': 'the cross-shard psums of token count, loss and '
                       'gradients',
    'train.optimizer': 'optimizer.update and the parameter apply',
}


def device_scope(name):
    """``jax.named_scope(name)`` for a name in :data:`DEVICE_SCOPES`;
    any other name raises, so the vocabulary a trace reader matches
    cannot drift from the one the program opens. For use INSIDE jitted
    code (see the module docstring)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f'unknown device scope {name!r}; '
                         f'DEVICE_SCOPES has {sorted(DEVICE_SCOPES)}')
    import jax
    return jax.named_scope(name)
