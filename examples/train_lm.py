# -*- coding: utf-8 -*-
"""
Train a real language model end-to-end on the framework — the capstone
demo (no reference analog: the reference's example stops at one
attention forward + backward, reference example.py:16-33).

The task is long-context copying: each packed segment is

    [BOS, a_1 .. a_L, SEP, a_1 .. a_L]

with the a_i uniform over the data vocabulary. The first half is
incompressible (loss → log V); the second half is exactly predictable —
but ONLY through attention back to the prefix (an induction task, the
canonical long-context probe). Success is therefore crisp: the
copy-region loss falls to ~0 and greedy generation reproduces the
prefix token-for-token through the KV caches.

Pipeline proved here, all sharded over the (data, seq) mesh:

  tokens → TransformerLM (embed → scanned+remat'd TransformerStack with
  flash/ring attention, RoPE, GQA → tied head) → packed-segment
  cross-entropy (make_lm_train_step) → orbax checkpoint mid-run →
  resume → greedy_generate through per-layer KV caches.

Run (CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python examples/train_lm.py --steps 300
Run (one TPU chip, bigger):
  python examples/train_lm.py --seq-len 32768 --dim 512 --layers 8 \\
      --steps 50 --batch 1 --softmax-impl flash
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from distributed_dot_product_tpu import (  # noqa: E402
    TrainLoopConfig, TrainState, TransformerLM, greedy_generate,
    lm_targets, run_training,
)
from distributed_dot_product_tpu.parallel.mesh import (  # noqa: E402
    data_seq_mesh, seq_mesh,
)
from distributed_dot_product_tpu.train import make_lm_train_step  # noqa: E402
from distributed_dot_product_tpu.utils.compile_cache import (  # noqa: E402
    setup_compile_cache,
)

BOS_OFF, SEP_OFF = 1, 2   # vocab layout: [0..V-3]=data, V-2=SEP, V-1=BOS


def make_copy_batch(key, batch, t, vocab, seg_len):
    """Packed copy-task batch: tokens, targets (copy region only — the
    incompressible prefix is ignore (−1), giving a loss whose floor is
    ~0 instead of ~log V/2), and segment ids. ``seg_len`` must be even:
    each segment is [BOS, prefix(L), SEP, copy(L)] with L = seg_len/2−1.
    """
    if seg_len % 2 or seg_len < 4:
        raise ValueError(f'seg_len must be even and >= 4, got {seg_len}')
    if t % seg_len:
        raise ValueError(f'seq len {t} must pack whole segments of '
                         f'{seg_len}')
    half = seg_len // 2
    n_seg = t // seg_len
    bos, sep = vocab - BOS_OFF, vocab - SEP_OFF
    prefix = jax.random.randint(key, (batch, n_seg, half - 1), 0,
                                vocab - 2)
    seg = jnp.concatenate([
        jnp.full((batch, n_seg, 1), bos), prefix,
        jnp.full((batch, n_seg, 1), sep), prefix], axis=-1)
    tokens = seg.reshape(batch, t).astype(jnp.int32)
    seg_ids = jnp.broadcast_to(
        jnp.repeat(jnp.arange(n_seg, dtype=jnp.int32), seg_len)[None],
        (batch, t))
    targets = lm_targets(tokens, seg_ids)
    # Score the copy region only: positions SEP..end-1 predict the copy.
    pos = jnp.tile(jnp.arange(seg_len), n_seg)
    in_copy = jnp.logical_and(pos >= half, pos < seg_len - 1)
    targets = jnp.where(in_copy[None], targets, -1)
    return tokens, targets, seg_ids


def build_model(args):
    return TransformerLM(
        vocab_size=args.vocab, dim=args.dim, num_heads=args.heads,
        n_layers=args.layers, scan_layers=not args.no_scan,
        remat=args.remat, dtype=jnp.bfloat16 if args.bf16 else None,
        attn_kwargs=dict(softmax_impl=args.softmax_impl,
                         num_kv_heads=args.kv_heads,
                         dropout_rate=args.dropout))


def main(argv=None):
    setup_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument('--steps', type=int, default=300)
    p.add_argument('--batch', type=int, default=2)
    p.add_argument('--seq-len', type=int, default=256)
    p.add_argument('--seg-len', type=int, default=64,
                   help='packed segment length (copy span = half - 1)')
    p.add_argument('--vocab', type=int, default=64)
    p.add_argument('--dim', type=int, default=64)
    p.add_argument('--heads', type=int, default=4)
    p.add_argument('--kv-heads', type=int, default=None)
    p.add_argument('--layers', type=int, default=2)
    p.add_argument('--lr', type=float, default=3e-3)
    p.add_argument('--dropout', type=float, default=0.0)
    p.add_argument('--softmax-impl', default='flash',
                   choices=['full', 'online', 'flash', 'ulysses'])
    p.add_argument('--no-scan', action='store_true',
                   help='unrolled layers instead of nn.scan')
    p.add_argument('--remat', action='store_true')
    p.add_argument('--bf16', action='store_true')
    p.add_argument('--ckpt-dir', default=None)
    p.add_argument('--ckpt-every', type=int, default=100)
    p.add_argument('--keep-last', type=int, default=3,
                   help='checkpoint retention (old step dirs GCed)')
    p.add_argument('--generate', action='store_true',
                   help='after training, greedy-generate a copy and '
                        'report token accuracy')
    p.add_argument('--log-every', type=int, default=25)
    args = p.parse_args(argv)

    import optax

    n_dev = jax.device_count()
    if n_dev >= 4 and n_dev % 2 == 0 and args.batch % 2 == 0:
        mesh, data_axis = data_seq_mesh(2, n_dev // 2), 'data'
    else:
        mesh, data_axis = seq_mesh(n_dev), None
    print(f'devices={n_dev} mesh={dict(mesh.shape)} '
          f'backend={jax.default_backend()}')

    model = build_model(args)
    tokens, targets, seg_ids = make_copy_batch(
        jax.random.key(0), args.batch, args.seq_len, args.vocab,
        args.seg_len)
    params = model.init(jax.random.key(1), tokens[:, :args.seg_len])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f'model: {args.layers}L dim={args.dim} heads={args.heads} '
          f'vocab={args.vocab} — {n_params:,} params')

    optimizer = optax.adam(args.lr)
    opt_state = optimizer.init(params)
    # guard=True: NaN/Inf steps skip the update inside the compiled
    # program and surface as bad_step records to the driver.
    step_fn = make_lm_train_step(model, optimizer, mesh,
                                 data_axis=data_axis, donate=False,
                                 guard=True)

    base_key = jax.random.key(2)

    def batch_fn(i):
        # fold_in(step): the data stream is a function of the step
        # index, so a resumed run consumes exactly the batches an
        # uninterrupted run would (a split-chain restarted from the
        # base key would replay the pre-checkpoint batches).
        return make_copy_batch(jax.random.fold_in(base_key, i),
                               args.batch, args.seq_len,
                               args.vocab, args.seg_len)

    # The resilient driver: auto-resume, periodic async saves with
    # retry/backoff, SIGTERM/SIGINT -> final save + clean exit,
    # NaN-guarded stepping with rollback, keep_last retention.
    cfg = TrainLoopConfig(
        num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        keep_last=args.keep_last, max_bad_steps=3,
        log_every=args.log_every)
    t0 = time.time()
    result = run_training(step_fn, TrainState(0, params, opt_state),
                          batch_fn, cfg)
    params, opt_state = result.state.params, result.state.opt_state
    start = result.resumed_from or 0
    loss = jnp.asarray(result.losses.get(result.state.step - 1, jnp.nan))
    dt = time.time() - t0
    executed = result.state.step - start   # != args.steps when preempted
    tok = executed * args.batch * args.seq_len
    print(f'trained {executed} steps in {dt:.1f}s '
          f'({tok / max(dt, 1e-9):,.0f} tok/s incl. data+compile)')
    if result.preempted:
        print(f'preempted (exit code {result.exit_code}); state saved '
              f'at step {result.state.step}')
        sys.exit(result.exit_code)

    if args.generate:
        # One fresh segment: prompt = [BOS, prefix, SEP]; the model must
        # reproduce the prefix through its KV caches.
        half = args.seg_len // 2
        tokens, _, _ = make_copy_batch(jax.random.key(99), 1,
                                       args.seg_len, args.vocab,
                                       args.seg_len)
        prompt, answer = tokens[:, :half + 1], tokens[:, half + 1:]
        steps = answer.shape[1]
        out = greedy_generate(model, params, prompt, steps,
                              t_max=args.seg_len)
        acc = float(jnp.mean((out == answer).astype(jnp.float32)))
        print(f'generation: copy accuracy {acc:.1%} over {steps} tokens')
        return {'loss': float(loss), 'acc': acc}
    return {'loss': float(loss), 'acc': None}


if __name__ == '__main__':
    main()
