# -*- coding: utf-8 -*-
"""
Driver benchmark: ONE JSON line with the headline metric.

Metric (BASELINE.json): ``A·Bᵀ`` (nt) GFLOP/s per chip on the reference
workload T=75000, d=768. Baseline of record: the reference's best nt
configuration — offset=25000 on 3× Quadro RTX 6000 over Horovod/NCCL —
at **2287 GFLOP/s per chip** (BASELINE.md, nt_benchmark_25000.json; its
per-chip useful FLOPs are ``2·(T/3)·T·768 / t``). ``vs_baseline`` is
ours / theirs.

Runs the sequence-sharded kernel over every visible device (on the driver's
hardware: one TPU v5e chip, a W=1 mesh — per-chip FLOPs are directly
comparable). bf16 inputs: the MXU-native dtype is the point of a TPU
rebuild; the fp32 number is also measured and included in the JSON line.

Every number here is a device metric, so a run that finds no TPU fails
with a message and prints none: there is no shrunken CPU fallback.
"""

import json
import sys

import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.ops.functions import \
    distributed_matmul_nt_global
from distributed_dot_product_tpu.parallel.mesh import seq_mesh, shard_seq
from distributed_dot_product_tpu.utils.compile_cache import (
    setup_compile_cache,
)
from distributed_dot_product_tpu.utils.tracing import time_fn

BASELINE_GFLOPS_PER_CHIP = 2287.0  # BASELINE.md nt offset=25000
DIM = 768


def measure(t, dtype, mesh, offset, iters=3, inner=5, precision=None):
    world = mesh.devices.size
    k1, k2 = jax.random.split(jax.random.key(111))
    left = shard_seq(jax.random.normal(k1, (t, DIM), dtype), mesh)
    right = shard_seq(jax.random.normal(k2, (t, DIM), dtype), mesh)
    # Reduce to a scalar inside the jit: keeps queued async dispatches from
    # each holding an 11 GiB output buffer, and stops XLA dead-code-
    # eliminating the matmul. The extra full-output HBM pass is charged to
    # us (conservative).
    fn = jax.jit(lambda l, r: jnp.sum(distributed_matmul_nt_global(
        l, r, offset=offset, mesh=mesh, precision=precision),
        dtype=jnp.float32))
    best, _ = time_fn(fn, left, right, iters=iters, inner=inner)
    return 2.0 * t * t * DIM / world / best / 1e9, best


def main():
    setup_compile_cache()
    platform = jax.devices()[0].platform
    if platform != 'tpu':
        sys.exit(f'bench.py measures the TPU and found platform '
                 f'{platform!r}: no number is printed off-chip (the CPU '
                 f'functional checks are the tests)')
    mesh = seq_mesh()
    world = mesh.devices.size

    # Reference workload T=75000; the nt output alone is T^2 elements,
    # so fp32 uses T/2 (22.5 GiB would not fit a 16 GiB chip — the same
    # reason the reference needed 3 GPUs).
    t_bf16 = 75000 - 75000 % world
    t_f32 = 75000 // 2 - (75000 // 2) % world
    offset = 25000  # the baseline's best config

    gflops_bf16, time_bf16 = measure(t_bf16, jnp.bfloat16, mesh, offset)
    # True fp32 accumulate-and-multiply (the reference baseline is fp32
    # cuBLAS; TPU 'float32' matmuls otherwise default to bf16 compute).
    gflops_f32, time_f32 = measure(t_f32, jnp.float32, mesh, offset,
                                   precision='highest')

    # Fused flash-attention kernel (no reference analog — its module path
    # materializes full score rows): report TFLOP/s on a standard
    # long-context attention shape as secondary evidence.
    from distributed_dot_product_tpu.ops.pallas_attention import \
        flash_attention
    h, d, t_attn = 8, 64, 16384
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (1, h, t_attn, d), jnp.bfloat16)
               for kk in ks)
    # iters=6: RESULTS.md's record saw ±15% between samples; best-of-6
    # keeps one bad sample window from distorting the recorded rate.
    fa = jax.jit(lambda q, k, v: jnp.sum(flash_attention(q, k, v),
                                         dtype=jnp.float32))
    attn_best, _ = time_fn(fa, q, k, v, iters=6)
    attn_gflops = 4.0 * h * t_attn * t_attn * d / attn_best / 1e9
    # softmax_mode='bounded' drops the running-max reduce (see
    # ops/pallas_attention.py) — the faster large-T configuration.
    fb = jax.jit(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, softmax_mode='bounded'),
        dtype=jnp.float32))
    attn_b_best, _ = time_fn(fb, q, k, v, iters=6)
    attn_b_gflops = 4.0 * h * t_attn * t_attn * d / attn_b_best / 1e9

    # Whole training step (fwd+bwd+adam, flash path, mask-free) at the
    # long-context shape — the integration-level rate (RESULTS.md). Reuses
    # benchmark.measure_train_step so the setup/FLOP accounting can't
    # drift from the committed corpus records.
    from benchmark import measure_lm_step, measure_train_step
    rec = measure_train_step(seq_len=16384, attn_impl='flash',
                             dtype='bf16', no_mask=True, iters=3)
    train_gflops, train_t = rec['step_gflops_per_chip'], rec['T']
    # The capstone: a whole LM training step (embed -> scanned
    # remat'd stack -> tied head -> chunked cross-entropy) — the
    # framework training the thing it is architected for.
    lm_rec = measure_lm_step(seq_len=16384, n_layers=8,
                             dtype='bf16', remat=True, iters=3)
    lm_tok_s = lm_rec['tokens_per_s']
    lm_gflops = lm_rec['step_gflops_per_chip']

    print(json.dumps({
        'metric': 'nt_gflops_per_chip',
        'value': round(gflops_bf16, 1),
        'unit': 'GFLOP/s/chip',
        'vs_baseline': round(gflops_bf16 / BASELINE_GFLOPS_PER_CHIP, 2),
        'detail': {
            'T_bf16': t_bf16, 'time_bf16_s': round(time_bf16, 4),
            'f32_gflops_per_chip': round(gflops_f32, 1),
            'T_f32': t_f32, 'time_f32_s': round(time_f32, 4),
            'f32_vs_baseline': round(
                gflops_f32 / BASELINE_GFLOPS_PER_CHIP, 2),
            'flash_attn_gflops': round(attn_gflops, 1),
            'flash_attn_bounded_gflops': round(attn_b_gflops, 1),
            'flash_attn_T': t_attn, 'flash_attn_time_s': round(attn_best, 4),
            'train_step_gflops': round(train_gflops, 1),
            'train_step_T': train_t,
            'lm_8l_16k_tokens_per_s': round(lm_tok_s, 1),
            'lm_8l_16k_gflops': round(lm_gflops, 1),
            'world': world, 'platform': platform,
            'device_kind': jax.devices()[0].device_kind,
            'baseline': 'reference nt offset=25000, 3x RTX6000/NCCL, '
                        '2287 GFLOP/s/chip (BASELINE.md)',
        },
    }))
    return 0


if __name__ == '__main__':
    sys.exit(main())
