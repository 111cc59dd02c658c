# -*- coding: utf-8 -*-
"""
Sharded training-step construction.

The reference stops at per-rank gradients: its example computes
``loss.backward()`` and leaves cross-rank weight-gradient summation to the
user (reference example.py:31-33; the sum-over-ranks identity is only
*verified* in tests, reference test_gradient.py:116-121), and it ships no
optimizer integration at all. Here the full training step — forward, global
loss, cross-shard gradient reduction, optax update — is one compiled SPMD
program over an explicit device mesh, with data parallelism (an optional
``'data'`` mesh axis) composing with sequence parallelism (``'seq'``).

Gradient math: inside the shard_map body the loss is the global mean
(local mean followed by ``lax.pmean`` over every mesh axis). ``jax.grad``
then yields this shard's partial derivative with respect to its copy of the
replicated parameters; the true gradient is the sum of those partials over
all shards — one ``lax.psum``. That psum is precisely the reference's
"sum of per-rank weight grads = full-sequence weight grad" invariant
(reference test_gradient.py:116-121), now executed inside the step instead
of left as an exercise.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_dot_product_tpu.models.dense import dense_param_bytes
from distributed_dot_product_tpu.models.remat import step_holds
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['make_train_step', 'make_lm_train_step', 'mse_loss']

# Record pytree returned by guarded steps (guard=True): replicated scalars.
# bad_step is a bool: the update was SKIPPED because loss or gradients
# contained NaN/Inf. grad_norm is the global L2 norm of the (psum'd)
# gradient — NaN/Inf exactly when any gradient leaf is.
_RECORD_SPECS = {'loss': None, 'bad_step': None, 'grad_norm': None}


def _resolve_donate(donate, guard):
    """``donate=None`` picks the compatible default (True unguarded,
    False guarded); an EXPLICIT donate=True with guard=True is an error
    — the driver's rollback-to-initial-state path reuses the first
    call's input buffers, which donation would have deleted."""
    if donate is None:
        return not guard
    if donate and guard:
        raise ValueError(
            'guard=True requires donate=False: the resilient driver may '
            'roll back to earlier params/opt_state buffers, which '
            'donation would delete')
    return donate


def _global_grad_norm(grads):
    import optax
    # f32 upcast first: bf16 leaves can overflow the squared sum.
    return optax.global_norm(
        jax.tree.map(lambda g: g.astype(jnp.float32), grads))


def _guarded_update(optimizer, params, opt_state, grads, loss):
    """All-finite predicate + ``lax.cond``-selected update, INSIDE the
    compiled step: a NaN/Inf loss or gradient skips the optax update
    (params/opt_state pass through untouched) at zero extra host
    round-trips. The predicate is computed from already-reduced values
    (loss is pmean'd, grads psum'd), so every shard takes the same
    branch."""
    grad_norm = _global_grad_norm(grads)
    finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)

    def apply(_):
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        new_params = jax.tree.map(lambda p, u: p + u, params, updates)
        return new_params, new_opt_state

    def skip(_):
        return params, opt_state

    params, opt_state = lax.cond(finite, apply, skip, None)
    record = {'loss': loss, 'bad_step': jnp.logical_not(finite),
              'grad_norm': grad_norm}
    return params, opt_state, record


def mse_loss(pred, target):
    """Per-shard mean-squared error (reference example.py:23 uses
    ``nn.MSELoss``)."""
    return jnp.mean((pred - target) ** 2)


def make_train_step(module, optimizer, mesh, seq_axis=SEQ_AXIS,
                    data_axis=None, loss_fn=mse_loss, donate=None,
                    guard=False):
    """Build a jitted SPMD train step for a sequence-parallel attention
    module.

    ``module``: a :class:`DistributedDotProductAttn`-like flax module whose
    ``__call__`` takes ``(keys, queries, values, attn_mask)`` local shards.
    ``optimizer``: an optax ``GradientTransformation``.
    ``mesh``: 1-D ``(seq,)`` or 2-D ``(data, seq)`` mesh
    (:func:`~distributed_dot_product_tpu.parallel.mesh.data_seq_mesh`).
    ``data_axis``: name of the batch mesh axis, or None for pure SP.

    Returns ``step(params, opt_state, batch, dropout_seed=None) ->
    (params, opt_state, loss)`` where
    ``batch = (keys, queries, values, attn_mask, target)`` — or
    ``(..., target, segment_ids)`` with a global ``(B, T)`` packed-sequence
    id array — holds *global* arrays; activations are sharded
    ``(batch→data, time→seq)``, parameters and optimizer state stay
    replicated (the reference's weight-replication convention, reference
    test_gradient.py:48). ``dropout_seed`` (a traced int32 scalar — pass
    the step counter) feeds modules with ``dropout_rate > 0``; for those
    modules it is REQUIRED — omitting it raises, because a constant
    fallback seed would silently draw the identical dropout mask every
    step (correlated dropout degrades training with no error signal).
    Modules without dropout ignore it.

    ``guard=True`` builds the NaN/Inf-guarded variant for the resilient
    driver (:func:`~distributed_dot_product_tpu.train_loop.run_training`):
    the update is applied through an all-finite ``lax.cond`` (a bad step
    leaves params/opt_state untouched) and the third return value becomes
    a ``{'loss', 'bad_step', 'grad_norm'}`` record instead of the bare
    loss. Guarded steps refuse donation (``donate`` defaults to the
    compatible value): the driver's rollback paths must keep old
    buffers alive across steps.
    """
    donate = _resolve_donate(donate, guard)
    axes = (seq_axis,) if data_axis is None else (data_axis, seq_axis)
    needs_seed = _module_has_dropout(module)

    def local_step(params, opt_state, keys, queries, values, mask, target,
                   seg, drop_seed):
        def local_loss(p):
            out = module.apply(p, keys, queries, values, mask,
                               segment_ids=seg, dropout_seed=drop_seed)
            l = loss_fn(out, target)
            for ax in axes:
                l = lax.pmean(l, ax)
            return l

        loss, grads = jax.value_and_grad(local_loss)(params)
        # Partials -> global gradient of the replicated params (see module
        # docstring; reference test_gradient.py:116-121).
        grads = lax.psum(grads, axes)
        if guard:
            return _guarded_update(optimizer, params, opt_state, grads,
                                   loss)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    def act_spec(ndim):
        names = [None] * ndim
        names[ndim - 2] = seq_axis
        if data_axis is not None:
            names[0] = data_axis
        return P(*names)

    a3 = act_spec(3)
    # segment_ids (B, T): time on the LAST axis (not -2 like activations).
    seg_spec = (P(None, seq_axis) if data_axis is None
                else P(data_axis, seq_axis))
    rec_spec = ({k: P() for k in _RECORD_SPECS} if guard else P())
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), a3, a3, a3, a3, a3, seg_spec, P()),
        out_specs=(P(), P(), rec_spec),
        check_vma=False)

    def step(params, opt_state, batch, dropout_seed=None):
        dropout_seed = _resolve_dropout_seed(needs_seed, dropout_seed)
        keys, queries, values, mask, target, *rest = batch
        seg = rest[0] if rest else None
        return sharded(params, opt_state, keys, queries, values, mask,
                       target, seg, dropout_seed)

    return _jit_step(step, donate)


def make_lm_train_step(model, optimizer, mesh, seq_axis=SEQ_AXIS,
                       data_axis=None, donate=None, loss_chunk=4096,
                       guard=False):
    """Sharded next-token training step for a
    :class:`~distributed_dot_product_tpu.models.lm.TransformerLM`.

    Returns ``step(params, opt_state, batch, dropout_seed=None) ->
    (params, opt_state, loss)`` with ``batch = (tokens, targets)`` or
    ``(tokens, targets, segment_ids)`` — GLOBAL ``(B, T)`` int arrays
    (build ``targets`` with
    :func:`~distributed_dot_product_tpu.models.lm.lm_targets` BEFORE
    sharding: the next-token shift crosses shard boundaries). Tokens
    shard ``(batch→data, time→seq)``; parameters/optimizer state stay
    replicated and their gradients cross-shard ``psum`` exactly as in
    :func:`make_train_step`.

    The loss is token-mean cross-entropy over valid targets
    (``target >= 0``): per-shard sums of (-log p, count) are each
    ``psum``'d so the mean weights every valid token equally however
    the valid positions distribute across shards — a plain pmean of
    per-shard means would over-weight shards with few valid tokens.
    ``loss_chunk`` bounds the live logit memory: the model's
    ``nll_sum`` scans row chunks of that size and takes each chunk's
    gradient while its logits are live (``models.lm.head_loss``), so
    no pass materializes the (T, vocab) logits (None = one chunk).
    ``guard=True``: NaN/Inf-guarded update + ``{'loss', 'bad_step',
    'grad_norm'}`` record, exactly as in :func:`make_train_step`
    (donation refused for the same rollback reason).
    """
    donate = _resolve_donate(donate, guard)
    axes = (seq_axis,) if data_axis is None else (data_axis, seq_axis)
    needs_seed = _module_has_dropout(model)

    def local_step(params, opt_state, tokens, targets, seg, drop_seed):
        def local_obj(p):
            loss_sum, count = model.apply(
                p, tokens, targets, segment_ids=seg,
                dropout_seed=drop_seed, chunk=loss_chunk,
                method='nll_sum')
            # Only the (param-independent) count is psum'd INSIDE the
            # differentiated objective. A psum of the param-dependent
            # loss_sum here would inflate every gradient by the axis
            # size: shard_map transposes psum to psum, so the scalar
            # cotangent 1/C comes back as W/C (make_train_step's pmean
            # cancels the same factor with its /W; here the weighting
            # is by global token count, so the shape is explicit).
            with device_scope('train.grad_sync'):
                count = lax.psum(count, axes)
            return loss_sum / jnp.maximum(count, 1.0)

        # What this step holds whatever a rematted stack keeps (the
        # stack's default policy fits its kept tensors beside it, see
        # TransformerStack): parameters, their gradients, the optimizer
        # state and the compute-type copy of the parameters; while no
        # layer is live, the head's chunk of float32 logits and their
        # gradient; on the device the mesh compiles for.
        rows = tokens.size if loss_chunk is None else min(
            tokens.size, tokens.shape[0] * loss_chunk)
        with step_holds(
                2 * dense_param_bytes(params) + dense_param_bytes(opt_state)
                + dense_param_bytes(params, model.dtype),
                2 * 4 * rows * model.vocab_size, mesh.devices.flat[0]):
            local_val, grads = jax.value_and_grad(local_obj)(params)
        with device_scope('train.grad_sync'):
            # Shard-sum OUTSIDE the grad: the global token-mean loss
            # value…
            loss = lax.psum(local_val, axes)
            # …and the true gradient of it (sum of per-shard partials).
            grads = lax.psum(grads, axes)
        with device_scope('train.optimizer'):
            if guard:
                return _guarded_update(optimizer, params, opt_state,
                                       grads, loss)
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss

    tok_spec = (P(None, seq_axis) if data_axis is None
                else P(data_axis, seq_axis))
    rec_spec = ({k: P() for k in _RECORD_SPECS} if guard else P())
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), tok_spec, tok_spec, tok_spec, P()),
        out_specs=(P(), P(), rec_spec),
        check_vma=False)

    def step(params, opt_state, batch, dropout_seed=None):
        dropout_seed = _resolve_dropout_seed(needs_seed, dropout_seed)
        tokens, targets, *rest = batch
        seg = rest[0] if rest else None
        return sharded(params, opt_state, tokens, targets, seg,
                       dropout_seed)

    return _jit_step(step, donate)


def _jit_step(step, donate):
    """Jit a step fn with the donation policy, tagging the wrapper so
    the resilient driver can refuse donating steps up front (it saves
    and rolls back through buffers a donating step would delete)."""
    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    try:
        jitted._ddp_donates = donate
    except AttributeError:      # jit wrapper without attribute support
        pass
    return jitted


def _resolve_dropout_seed(needs_seed, dropout_seed):
    """Shared missing-seed policy for every train-step builder: a
    dropout-enabled module without an explicit per-step seed is an
    error (a constant fallback would reuse ONE dropout mask for the
    whole run — silently correlated dropout); modules without dropout
    get the free constant."""
    if dropout_seed is None:
        if needs_seed:
            raise ValueError(
                'this module has dropout_rate > 0: pass '
                'dropout_seed=<step counter> to every step() call — '
                'a constant fallback would reuse ONE dropout mask '
                'for the whole run (silently correlated dropout)')
        dropout_seed = 0
    return jnp.asarray(dropout_seed, jnp.int32)


def _module_has_dropout(module):
    """Does this module (or a stack over the attention module) apply
    attention dropout? Reads constructor fields only — the attention
    module exposes ``dropout_rate``; the transformer stack carries it in
    ``attn_kwargs``."""
    if getattr(module, 'dropout_rate', 0.0):
        return True
    # attn_kwargs is typed Any — normalize like the stack itself does
    # (transformer.py accepts any pair-iterable via dict(...)).
    kw = dict(getattr(module, 'attn_kwargs', None) or {})
    return bool(kw.get('dropout_rate', 0.0))
