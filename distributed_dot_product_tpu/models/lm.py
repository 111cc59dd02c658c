# -*- coding: utf-8 -*-
"""
A causal language model over the sequence-parallel transformer stack —
the framework's capstone composition.

The reference stops at one attention layer (reference module.py:22-76);
a framework claiming its capabilities must prove the composition trains
something real. This module is that proof: token embedding →
:class:`~distributed_dot_product_tpu.models.transformer.TransformerStack`
(scanned, remat-able, every attention knob available) → final LayerNorm
→ tied LM head, trained with next-token cross-entropy over packed
segments and decoded through the stack's KV caches.

TPU-first notes:

- Everything outside attention is position-wise, so the whole model runs
  under the same time-axis ``shard_map`` as one attention layer; the
  embedding table and LM head are replicated parameters whose gradients
  ride the same cross-shard ``psum`` as every other weight.
- The LM head is the transposed embedding (``embed.attend``) by default
  — one (dim, vocab) matmul on the MXU, half the parameter bytes, the
  standard weight-tying win.
- Cross-entropy masks ``target < 0`` (ignore positions): the natural
  encoding for packed segments, where each segment's LAST token must not
  predict the next segment's first. Target construction is a GLOBAL
  (pre-shard) concern — see :func:`lm_targets` — because the shift
  crosses shard boundaries.
- Generation: ``prefill`` ingests the prompt through the stack's flash
  kernels; ``decode`` is the one-token cached step. Both return logits,
  so sampling loops (greedy here; any sampler outside) stay trivial.
"""

import dataclasses
import functools
import warnings
from collections import OrderedDict
from typing import Any, Callable, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.models.transformer import (
    TransformerStack, make_norm,
)
from distributed_dot_product_tpu.ops.pallas_head import head_grad, head_tiles
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.retrace import watch_traces
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['TransformerLM', 'greedy_generate', 'head_loss_traces',
           'lm_targets']


def lm_targets(tokens, segment_ids=None, pad_id=None):
    """Next-token targets for ``tokens (B, T)``: ``targets[t] =
    tokens[t+1]``, with ignore (−1) at the final position, at segment
    boundaries (a segment's last token must not predict the next
    segment's first — packed-sequence training's correctness subtlety),
    and at padding. GLOBAL arrays in, global out: the shift crosses
    shard boundaries, so build targets before sharding (the train step
    shards them like any activation)."""
    t = tokens.shape[-1]
    nxt = jnp.roll(tokens, -1, axis=-1)
    ignore = jnp.zeros(tokens.shape, bool).at[..., t - 1].set(True)
    if segment_ids is not None:
        boundary = segment_ids != jnp.roll(segment_ids, -1, axis=-1)
        ignore = jnp.logical_or(ignore, boundary)
    if pad_id is not None:
        ignore = jnp.logical_or(ignore, nxt == pad_id)
        ignore = jnp.logical_or(ignore, tokens == pad_id)
    return jnp.where(ignore, -1, nxt)


class TransformerLM(nn.Module):
    """Causal LM: embed → stack → LayerNorm → (tied) head.

    ``attn_kwargs`` passes to the stack's attention modules;
    ``causal=True``, ``softmax_impl='flash'`` and ``use_rope=True`` are
    defaulted in (a language model without causality is an error — pass
    them explicitly to override the other two). ``scan_layers``/
    ``remat``/``remat_policy`` forward to the stack (deep models compile
    O(1) in depth and fit backward memory per layer; ``remat=True`` with
    no policy keeps the flash kernel's output and logsumexp a layer —
    ``(B, H, T, d_v)`` in the compute type + ``(B, H, T)`` float32 — and,
    while they fit the chip beside what the train step holds, the MLP's
    hidden pre-activation, q / k / v as the kernel takes them and the
    attention output projection's result, in that order
    (:class:`~distributed_dot_product_tpu.models.transformer.TransformerStack`
    has the bytes, the fit and the way back where it does not fit);
    norms, activations and residual adds are rebuilt;
    ``remat_policy='nothing_saveable'`` keeps nothing).

    Call: ``apply(params, tokens (B, T/N int32), segment_ids=None,
    deterministic=False, dropout_seed=None) -> logits (B, T/N, vocab)``
    — local shards under ``shard_map`` like every module here; use
    :func:`~distributed_dot_product_tpu.train.make_lm_train_step` for
    global arrays on a mesh.
    """
    vocab_size: int
    dim: int
    num_heads: int
    n_layers: int = 2
    mlp_ratio: int = 4
    axis_name: str = SEQ_AXIS
    dtype: Optional[jnp.dtype] = None
    # 'int8': int8 weight quantization for every block's projection and
    # MLP matmuls (models/dense.py — convert a float checkpoint with
    # quantize_dense_params, then apply as usual). The embedding table
    # and the (tied) LM head stay at the activation dtype: the table
    # feeds the embedding LOOKUP, and the head einsum already owns its
    # fp32 accumulation below.
    weight_quant: Optional[str] = None
    attn_kwargs: Any = None
    scan_layers: bool = True
    remat: bool = False
    remat_policy: Optional[Union[str, Callable]] = None
    tie_embeddings: bool = True
    # The logits are ``logit_scale · LN_f(x) · Eᵀ`` (Cohere's models
    # state one; 1 adds no operation).
    logit_scale: float = 1.0
    # The stream starts as ``embed_scale · E[token]`` (Granite's
    # ``embedding_multiplier``; 1 adds no operation). The tied head
    # reads the table itself, not the scaled rows.
    embed_scale: float = 1.0
    # The block's composition and the kinds of its layers —
    # TransformerStack's fields of the same names (block_kwargs' 'norm'
    # and 'norm_eps' also choose the final norm). ``dense_prefix`` /
    # ``prefix_kwargs`` say the commonest case of layer kinds in two
    # words: the first ``dense_prefix`` layers are of a kind that lays
    # ``prefix_kwargs`` over ``block_kwargs`` (a model's leading dense
    # layers before its expert layers); not beside ``layer_pattern``.
    # With block_kwargs['residual'] == 'hyper' the embedding is widened
    # to ``mult`` equal float32 streams before the stack and the
    # streams are summed before the final norm.
    block_kwargs: Any = None
    layer_kinds: Any = None
    layer_pattern: Any = None
    dense_prefix: int = 0
    prefix_kwargs: Any = None

    def _attn_kw(self):
        """The stack's attention kwargs with the LM defaults applied —
        plain field arithmetic (shared by ``setup`` and the
        outside-apply cache constructor)."""
        kw = dict(self.attn_kwargs or {})
        if (self.block_kwargs or {}).get('mixer') == 'latent':
            return kw                 # causal by construction, own RoPE
        if not kw.setdefault('causal', True):
            raise ValueError('TransformerLM is autoregressive: '
                             'causal=False makes no sense here')
        kw.setdefault('softmax_impl', 'flash')
        kw.setdefault('use_rope', True)
        return kw

    def _stack_fields(self):
        kinds, pattern = self.layer_kinds, self.layer_pattern
        if self.dense_prefix:
            if pattern:
                raise ValueError('dense_prefix is a layer_pattern of its '
                                 'own: pass one or the other')
            kinds = {'prefix': self.prefix_kwargs or {}, 'rest': {}}
            pattern = (('prefix',) * self.dense_prefix + ('rest',) * (
                self.n_layers - self.dense_prefix))
        return dict(dim=self.dim, num_heads=self.num_heads,
                    n_layers=self.n_layers, mlp_ratio=self.mlp_ratio,
                    axis_name=self.axis_name, dtype=self.dtype,
                    weight_quant=self.weight_quant,
                    attn_kwargs=self._attn_kw(),
                    scan_layers=self.scan_layers, remat=self.remat,
                    remat_policy=self.remat_policy,
                    block_kwargs=self.block_kwargs,
                    layer_kinds=kinds, layer_pattern=pattern)

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.dim,
                              dtype=self.dtype, name='embed')
        self.stack = TransformerStack(**self._stack_fields(),
                                      name='stack')
        kw = self.block_kwargs or {}
        self.ln_f = make_norm(kw.get('norm', 'layernorm'),
                              kw.get('norm_eps', 1e-6), self.dtype, 'ln_f')
        if not self.tie_embeddings:
            # An explicit (dim, vocab) kernel rather than nn.Dense: the
            # chunked loss below reads the table directly (a bound
            # Dense doesn't expose its kernel), and a bias on an LM
            # head is non-standard anyway.
            self.lm_head_kernel = self.param(
                'lm_head_kernel', nn.initializers.lecun_normal(),
                (self.dim, self.vocab_size), jnp.float32)

    def _head_table(self):
        """(vocab, dim) logit table — the tied embedding or the
        transposed explicit head kernel."""
        if self.tie_embeddings:
            return self.embed.embedding
        return self.lm_head_kernel.T

    def _streams(self):
        kw = self.block_kwargs or {}
        if kw.get('residual') != 'hyper':
            return None
        return (kw.get('residual_kwargs') or {}).get('mult', 4)

    def _embed(self, tokens):
        with device_scope('lm.embed'):
            x = self.embed(tokens.astype(jnp.int32))
            if self.embed_scale != 1.0:
                x = x * self.embed_scale
            if self._streams():
                x = jnp.broadcast_to(
                    x.astype(jnp.float32)[..., None, :],
                    x.shape[:-1] + (self._streams(), x.shape[-1]))
            return x

    def _collapse(self, x):
        if self._streams():
            x = jnp.sum(x, axis=-2).astype(self.dtype or x.dtype)
        return x

    def _head(self, x):
        with device_scope('lm.head'):
            x = self.ln_f(self._collapse(x))
            # logits = x · Eᵀ on the MXU, fp32 accumulation — requested
            # explicitly (preferred_element_type) so the contraction
            # accumulates in fp32 on EVERY backend, not just where it's
            # the hardware default; the result is cast back to the
            # activation dtype (the contract is fp32 accumulation, not
            # fp32 logits).
            logits = jnp.einsum('...d,vd->...v', x,
                                self._head_table().astype(x.dtype),
                                preferred_element_type=jnp.float32)
            if self.logit_scale != 1.0:
                logits = logits * self.logit_scale
            return logits.astype(x.dtype)

    def __call__(self, tokens, segment_ids=None, deterministic=False,
                 dropout_seed=None):
        x = self._embed(tokens)
        x = self.stack(x, x, x, None, segment_ids=segment_ids,
                       deterministic=deterministic,
                       dropout_seed=dropout_seed)
        return self._head(x)

    def nll_sum(self, tokens, targets, segment_ids=None,
                deterministic=False, dropout_seed=None, chunk=None):
        """Summed next-token negative log-likelihood + valid-token
        count for this shard — the training loss primitive
        (:func:`~distributed_dot_product_tpu.train.make_lm_train_step`
        psums both and divides).

        ``chunk``: CHUNKED cross-entropy — :func:`head_loss` scans row
        chunks of the final hidden states and a chunk's ``(C, vocab)``
        logits never leave their scan iteration, differentiated or
        not, so no pass materializes the full ``(T, vocab)`` logits
        (fp32 logits at T=131K × 32K vocab are 17 GiB — measured OOM on
        a 16 GiB chip; chunked, the live score memory is
        O(chunk·vocab)). ``None`` = one chunk (fine at short T)."""
        x = self._embed(tokens)
        x = self.stack(x, x, x, None, segment_ids=segment_ids,
                       deterministic=deterministic,
                       dropout_seed=dropout_seed)
        with device_scope('lm.head_loss'):
            return self._nll(x, targets, chunk)

    def _nll(self, x, targets, chunk):
        x = self.ln_f(self._collapse(x))
        table = self._head_table().astype(jnp.float32)
        return head_loss(x, table, targets.astype(jnp.int32), chunk,
                         self.logit_scale)

    # -- cached generation --------------------------------------------

    def make_decode_caches(self, batch, t_max, dtype=None):
        """KV caches for generation (stacked pytree when
        ``scan_layers``, else a list) — plain field arithmetic, no
        ``apply`` needed (a throwaway stack instance reads the same
        fields; ``self.stack`` only exists inside apply, and
        ``parent=None`` keeps flax from adopting the throwaway as a
        child of this module)."""
        stack = TransformerStack(**self._stack_fields(), parent=None)
        return stack.make_decode_caches(batch, t_max, dtype=dtype)

    def prefill(self, tokens, caches):
        """Ingest a prompt chunk: returns ``(caches, logits (B, n,
        vocab))`` — the last position's logits seed generation."""
        caches, x = self.stack.prefill(self._embed(tokens), caches)
        return caches, self._head(x)

    def decode(self, tokens, caches):
        """One cached generation step for ``tokens (B, 1)``."""
        caches, x = self.stack.decode(self._embed(tokens), caches)
        return caches, self._head(x)


_HEAD_TRACES = TraceSinks()


def head_loss_traces():
    """Collect which route each :func:`head_loss` takes while the block
    runs: one dict per TRACE of its scan — ``route`` (``'kernel'``: a
    chunk's ``dx`` and ``dW`` are ONE Pallas program,
    ``ops.pallas_head.head_grad``; ``'xla'``: the two einsums), ``rows``
    (a chunk's rows, batch × chunk), ``row_group`` / ``vocab_tile`` /
    ``row_tile`` (the kernel's tiles, None on the XLA route) and ``why``
    (the reason for the XLA route, None on the kernel's)::

        with head_loss_traces() as traces:
            step.lower(*args).compile()
        assert [t['route'] for t in traces] == ['kernel']
    """
    return _HEAD_TRACES.open()


def _head_route(rows, dim, vocab, dtype, with_grad):
    """The kernel's tiles for one chunk, or None: the rule is
    ``ops.pallas_head.head_tiles``'s, asked only where there is a
    gradient to take. Tells the open :func:`head_loss_traces` blocks."""
    tiles, why = None, 'not differentiated: the logits product alone'
    if with_grad:
        tiles, why = head_tiles(rows, dim, vocab, dtype)
    _HEAD_TRACES.note({'route': 'kernel' if tiles else 'xla', 'rows': rows,
                       **(tiles or dict.fromkeys(
                           ('row_group', 'vocab_tile', 'row_tile'))),
                       'why': why})
    return tiles


def _head_loss_scan(x, table, targets, chunk, logit_scale, with_grad):
    """The scan behind :func:`head_loss`. A chunk builds its float32
    logits ONCE; from them come the summed loss and the valid count
    and, ``with_grad``, the chunk's whole gradient while they are still
    live: ``dlogits = valid · logit_scale · (softmax − onehot)``, its
    ``dx`` rows (the scan's ``ys``) and its share of ``dW`` (an
    accumulator in the carry) — one Pallas program for both where
    ``ops.pallas_head.head_tiles`` takes the chunk's shapes (the
    operands in ``x``'s type, the table cast once, outside the scan),
    else two einsums. Returns ``(s, count)``, and with them ``(dx, dW)``
    at a unit cotangent of ``s``, both float32: ``dx`` is rounded to
    ``x``'s type once, after the cotangent has scaled it, as plain
    autodiff rounds it."""
    tn, dim = x.shape[-2:]
    if chunk is None or chunk >= tn:
        chunk = tn
    pad = (-tn) % chunk
    if pad:
        # Padded targets are -1: their rows count nothing and their
        # dlogits are zero.
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
        targets = jnp.pad(targets, [(0, 0)] * (targets.ndim - 1)
                          + [(0, pad)], constant_values=-1)
    n = (tn + pad) // chunk
    xr = jnp.moveaxis(x.reshape(*x.shape[:-2], n, chunk, dim), -3, 0)
    tr = jnp.moveaxis(targets.reshape(*targets.shape[:-1], n, chunk),
                      -2, 0)
    rows = xr.size // (n * dim)
    tiles = _head_route(rows, dim, table.shape[0], x.dtype, with_grad)
    if tiles:
        # The kernel's operands are the compute type's, as the MXU reads
        # the float32 ones at the default precision: the table is cast
        # ONCE, here, and the logits product reads the same copy.
        table = table.astype(x.dtype)

    def body(carry, xs):
        x_c, t_c = xs
        if not tiles:
            x_c = x_c.astype(jnp.float32)
        logits = jnp.einsum('...cd,vd->...cv', x_c, table,
                            preferred_element_type=jnp.float32)
        if logit_scale != 1.0:
            logits = logits * logit_scale
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        valid = t_c >= 0
        # The target's column by comparison, not by index: the same
        # select gives the log-likelihood and the onehot of dlogits
        # (no gather, no scatter); -1 matches no column.
        hit = t_c[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1)
        ll = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        s = carry[0] + jnp.sum(jnp.where(valid, lse - ll, 0.0))
        c = carry[1] + jnp.sum(valid.astype(jnp.float32))
        if not with_grad:
            return (s, c), None
        if tiles:
            dx_c, dw = head_grad(
                logits.reshape(rows, -1), lse.reshape(rows),
                t_c.reshape(rows), x_c.reshape(rows, dim), table,
                carry[2], logit_scale=logit_scale, tiles=tiles)
            return (s, c, dw), dx_c.reshape(x_c.shape)
        dlogits = jnp.where(
            valid[..., None],
            jnp.exp(logits - lse[..., None]) - hit.astype(jnp.float32),
            0.0)
        if logit_scale != 1.0:
            dlogits = dlogits * logit_scale
        dx_c = jnp.einsum('...cv,vd->...cd', dlogits, table)
        dw = carry[2] + jnp.einsum('...cv,...cd->vd', dlogits, x_c)
        return (s, c, dw), dx_c

    zero = jnp.float32(0.0)
    if not with_grad:
        return jax.lax.scan(body, (zero, zero), (xr, tr))[0]
    (s, c, dw), dxs = jax.lax.scan(
        body, (zero, zero, jnp.zeros(table.shape, jnp.float32)), (xr, tr))
    dx = jnp.moveaxis(dxs, 0, -3).reshape(*x.shape[:-2], n * chunk, dim)
    return (s, c), (dx[..., :tn, :], dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def head_loss(x, table, targets, chunk, logit_scale):
    """Summed negative log-likelihood and valid-target count of the
    head over ``x (..., T, d)`` (the final norm's output) against the
    float32 ``table (vocab, d)`` and int32 ``targets (..., T)``
    (``< 0``: ignored), scanned in ``chunk``-row chunks (``None`` or
    ``>= T``: one chunk).

    The loss is the last thing a forward computes and its cotangent is
    a scalar, so differentiated it takes its gradient IN THE FORWARD
    pass: a chunk's logits give the loss, ``dx`` and ``dW`` and are
    dropped — three vocabulary-wide products a chunk, nothing
    ``(chunk, vocab)``-sized kept or rebuilt; the backward rule scales
    ``(dx, dW)`` by the sum's cotangent (the count carries none).
    Where ``ops.pallas_head.head_tiles`` takes the chunk's shapes (a
    bfloat16 ``x``, a width in 128-column tiles, a vocabulary of four
    and rows in 128-row tiles) the two gradient products are ONE Pallas
    program that builds ``dlogits`` once, in VMEM
    (``ops.pallas_head.head_grad``); else they are two einsums.
    :func:`head_loss_traces` says which. Un-differentiated it is the
    same scan with the logits matmul alone. Reverse mode, first order
    only (a ``custom_vjp``)."""
    return _head_loss_scan(x, table, targets, chunk, logit_scale, False)


def _head_loss_fwd(x, table, targets, chunk, logit_scale):
    out, grads = _head_loss_scan(x, table, targets, chunk, logit_scale,
                                 True)
    # An empty array carries x's type to the backward rule.
    return out, (*grads, jnp.zeros((0,), x.dtype))


def _head_loss_bwd(chunk, logit_scale, residuals, cotangents):
    dx, dw, like = residuals
    g = cotangents[0]
    return (g * dx).astype(like.dtype), g * dw, None


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


# Compiled generation programs keyed by (module, donate, batch,
# prompt_len, t_max) — every shape that forces a retrace is IN the key,
# so each cached entry traces exactly once and repeated
# greedy_generate calls reuse the compiled pair instead of rebuilding
# fresh jit closures per invocation (the round-8 recompile finding:
# every call paid a full prefill + step trace). BOUNDED like
# models/attention.py's _DECODE_STEPS: LRU past the cap — eviction
# costs a re-trace on revisit, never correctness.
_GENERATE_PROGRAMS = OrderedDict()
_GENERATE_PROGRAMS_CAP = 8
_GENERATE_WARNED_UNHASHABLE = False


def _build_generate_programs(model, donate):
    def prefill_fn(p, tok, c):
        return model.apply(p, tok, c, method='prefill')

    def step_fn(p, tok, c):
        c, logits = model.apply(p, tok, c, method='decode')
        return c, jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    # Budget 2: the real trace plus one weak-type/registry respin —
    # shapes live in the cache key, so a retrace past that is a storm.
    prefill = jax.jit(
        watch_traces(prefill_fn, 'lm.generate_prefill', budget=2))
    step = jax.jit(
        watch_traces(step_fn, 'lm.generate_step', budget=2),
        donate_argnums=(2,) if donate else ())
    return prefill, step


def _freeze_for_key(x):
    """Recursively turn dict/list values into hashable tuples so a
    module carrying ``attn_kwargs={'window': 128}`` — the repo's normal
    construction idiom — still keys the program cache. Array-valued
    fields stay unhashable and take the warn-once fallback."""
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze_for_key(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze_for_key(v) for v in x)
    return x


def _generate_programs(model, donate, b, n, t_max):
    global _GENERATE_WARNED_UNHASHABLE
    key = (type(model),
           tuple((f.name, _freeze_for_key(getattr(model, f.name)))
                 for f in dataclasses.fields(model)),
           donate, b, n, t_max)
    try:
        entry = _GENERATE_PROGRAMS.get(key)
        if entry is None:
            entry = _GENERATE_PROGRAMS[key] = \
                _build_generate_programs(model, donate)
        else:
            _GENERATE_PROGRAMS.move_to_end(key)
        while len(_GENERATE_PROGRAMS) > _GENERATE_PROGRAMS_CAP:
            _GENERATE_PROGRAMS.popitem(last=False)
    except TypeError:   # unhashable module field (e.g. array slopes)
        if not _GENERATE_WARNED_UNHASHABLE:
            _GENERATE_WARNED_UNHASHABLE = True
            warnings.warn(
                'greedy_generate: model is unhashable (an array-valued '
                'field such as alibi_slopes?) — the compiled '
                'prefill/step pair cannot be cached and EVERY call '
                're-traces both. Use hashable fields (e.g. a tuple of '
                'slopes).', stacklevel=3)
        entry = _build_generate_programs(model, donate)
    return entry


def greedy_generate(model, params, prompt, steps, t_max, donate=True):
    """Greedy sampling through the KV caches: prefill the prompt, then
    ``steps`` jitted decode steps (cache donated so appends write in
    place — see models/decode.py). Returns ``(B, steps) int32``.

    The compiled prefill/step pair is cached per (model, shapes) —
    LRU-bounded, retrace-budgeted — so calling this in a loop traces
    once, not per call.

    A deliberately simple reference sampler (argmax); the
    ``prefill``/``decode`` surface returns full logits, so temperature /
    top-k samplers are a drop-in replacement outside the model."""
    b, n = prompt.shape
    if steps < 1:
        raise ValueError(f'steps must be >= 1, got {steps} (the prefill '
                         'logits already commit the first token)')
    # Capacity: prefill appends the n prompt rows and the loop appends
    # steps − 1 more (the FIRST generated token comes from the prefill
    # logits and its k/v land on the first loop iteration), so exactly
    # n + steps − 1 cache rows are written.
    if n + steps - 1 > t_max:
        raise ValueError(f'prompt {n} + steps {steps} needs '
                         f'{n + steps - 1} cache rows but t_max is '
                         f'{t_max}')
    prefill, step = _generate_programs(model, donate, b, n, t_max)
    caches = model.make_decode_caches(b, t_max)
    caches, logits = prefill(params, prompt, caches)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    for _ in range(steps - 1):
        caches, tok = step(params, tok, caches)
        out.append(tok)
    return jnp.concatenate(out, axis=1)
