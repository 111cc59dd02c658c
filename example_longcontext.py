# -*- coding: utf-8 -*-
"""
Long-context training demo — the beyond-parity flagship configuration.

The reference example (example.py here, reference example.py) trains the
parity module at T=4096 with a dense mask. This demo shows what the
TPU-native stack adds on top: the fused flash path with in-kernel causal
masking and no dense mask (memory linear in T), plus
checkpoint/resume.

Run (CPU simulation, 8 virtual devices):

    JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python example_longcontext.py

On real TPU hardware, raise --seq-len (e.g. 131072) and use bf16.
"""

import argparse
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import optax

import distributed_dot_product_tpu as ddp
from distributed_dot_product_tpu.train import make_train_step


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--seq-len', type=int, default=None,
                    help='global T (default: 512 on CPU, 16384 on TPU)')
    ap.add_argument('--dim', type=int, default=256)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--kv-heads', type=int, default=None,
                    help='grouped-query K/V heads (default: --heads)')
    ap.add_argument('--no-rope', action='store_true',
                    help='disable rotary position embeddings')
    ap.add_argument('--dropout', type=float, default=0.0,
                    help='attention-weight dropout rate (in-kernel mask; '
                         'seeded by the step counter)')
    ap.add_argument('--steps', type=int, default=4)
    ap.add_argument('--generate', type=int, default=8,
                    help='after training, decode this many tokens with '
                         'the KV cache (0 to skip)')
    ap.add_argument('--ckpt-dir', default=None,
                    help='checkpoint directory (default: a temp dir)')
    ap.add_argument('--ckpt-every', type=int, default=0,
                    help='checkpoint every N steps (0: only at the end)')
    ap.add_argument('--keep-last', type=int, default=3,
                    help='checkpoint retention (old step dirs GCed)')
    args = ap.parse_args()

    on_tpu = jax.default_backend() == 'tpu'
    t = args.seq_len or (16384 if on_tpu else 512)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    mesh = ddp.seq_mesh()
    world = mesh.devices.size
    t -= t % world
    print(f'{world}-device mesh, T={t}, dim={args.dim}, '
          f'heads={args.heads}, dtype={dtype.__name__}')

    # RoPE on by default: rotary embeddings over GLOBAL positions are the
    # standard causal long-context setup, and the sharded rotation equals
    # the full-array one exactly (ops/rope.py).
    model = ddp.DistributedDotProductAttn(
        key_dim=args.dim, num_heads=args.heads, num_kv_heads=args.kv_heads,
        causal=True, use_rope=not args.no_rope,
        dropout_rate=args.dropout, softmax_impl='flash', dtype=dtype)

    key = jax.random.key(111)
    x = jax.random.normal(key, (1, t, args.dim), dtype)
    target = jnp.roll(x, -1, axis=1)        # next-step prediction target

    t0 = max(world * 2, 16)
    x0 = jnp.zeros((1, t0, args.dim), dtype)
    params = model.init(jax.random.key(0), x0, x0, x0, None)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    # guard=True: the compiled step skips the update on a NaN/Inf step
    # and returns the {loss, bad_step, grad_norm} record the driver
    # consumes. donate=False: the driver's rollback path keeps old
    # buffers alive across steps.
    step = make_train_step(model, optimizer, mesh, donate=False,
                           guard=True)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix='ddp_tpu_ckpt_')
    # Restored arrays adopt the template's shardings — commit the
    # template to the mesh (params/opt state replicated) so training
    # can resume on it directly.
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    template = ddp.TrainState(
        0, jax.tree.map(lambda p: jax.device_put(p, rep), params),
        jax.tree.map(lambda p: jax.device_put(p, rep), opt_state))

    # The resilient driver owns the loop: auto-resume from the latest
    # finalized checkpoint, periodic async saves with retry/backoff,
    # SIGTERM/SIGINT -> final save + clean exit, NaN-guarded stepping
    # with rollback, keep_last retention (see README "Fault tolerance
    # and resume"; fault-injection knobs: DDP_TPU_FAULT_*).
    # Recover crash leftovers BEFORE deriving the resume point, so the
    # step count agrees with what run_training (which recovers again,
    # idempotently) will actually resume from.
    ddp.recover_interrupted(ckpt_dir)
    start = ddp.latest_step(ckpt_dir) or 0
    batch = (x, x, x, None, target)          # attn_mask=None: no O(T^2) input
    cfg = ddp.TrainLoopConfig(
        num_steps=start + args.steps, ckpt_dir=ckpt_dir,
        ckpt_every=args.ckpt_every, keep_last=args.keep_last,
        log_every=1)
    result = ddp.run_training(step, template, lambda i: batch, cfg)
    params, opt_state = result.state.params, result.state.opt_state
    if result.resumed_from is not None:
        print(f'(resumed from step {result.resumed_from})')
    print(f'checkpointed -> {ckpt_dir} (step {result.state.step})')
    if result.preempted:
        sys.exit(result.exit_code)

    if args.generate:
        # Inference with the SAME weights and configuration: prefill the
        # prompt with the flash kernel (module.prefill — decode() would
        # materialize an (prompt, t_max) score buffer), then decode
        # autoregressively (each step feeds the previous output back in
        # — the attention-only analog of LM generation).
        local = model.bind(params)
        prompt = 64
        cache = model.make_decode_cache(1, prompt + args.generate + 1)
        xp = jax.device_get(x)[:, :prompt]
        cache, out = local.prefill(xp, xp, xp, cache)
        tok = out[:, -1:]
        # ONE jitted step reused across tokens (an eager bound-module
        # loop re-traces every token); the cache is donated so appends
        # write in place.
        decode_step = jax.jit(
            lambda p, t_, c: model.apply(p, t_, t_, t_, c,
                                         method='decode'),
            donate_argnums=(2,))
        cache, out = decode_step(params, tok, cache)   # warm the compile
        tok = jax.block_until_ready(out[:, -1:])
        tic = time.perf_counter()
        for _ in range(args.generate):
            cache, out = decode_step(params, tok, cache)
            tok = out[:, -1:]
        jax.block_until_ready(tok)
        dt = (time.perf_counter() - tic) * 1000 / args.generate
        print(f'decoded {args.generate} tokens with the KV cache '
              f'({dt:.2f} ms/token; cache length '
              f'{int(cache.length)}/{cache.t_max})')


if __name__ == '__main__':
    main()
