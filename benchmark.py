# -*- coding: utf-8 -*-
"""
Benchmark CLI for the distributed sequence matmuls.

Port of the reference benchmark harness (reference benchmark.py:1-258) with
the same flags and JSON-append result files, minus its two measurement
defects (SURVEY §6 / BASELINE.md): timings here block on device completion
(the reference never called ``torch.cuda.synchronize()``, reference
benchmark.py:56-67) and ``--offset`` is honored by every mode (the
reference's nt path hardcoded offset=1000, reference benchmark.py:95).

Workload (reference benchmark.py:72-102): sequence length ``T =
75000/scale``, feature dim ``d = 768``; the "local" baseline is the
full-size matmul on ONE device; the "distributed" measurement runs the
sequence-sharded kernel over all visible devices. Extra TPU-native knobs:
``--dtype bf16`` (MXU-native) and ``--impl ring`` (ppermute ring instead of
chunked all-gather). ``--offset``/``--impl`` apply to nt and all; tn has
neither knob (reference functions.py:103) and records them as null.

    python benchmark.py --mode nt --offset 1000 --scale 2 --file out.json
"""

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.obs import spans as obs_spans
from distributed_dot_product_tpu.obs.spans import span
from distributed_dot_product_tpu.ops.functions import (
    distributed_matmul_all_global, distributed_matmul_nt_global,
    distributed_matmul_tn_global,
)
from distributed_dot_product_tpu.parallel.mesh import seq_mesh, shard_seq
from distributed_dot_product_tpu.utils import tracing
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.compile_cache import (
    setup_compile_cache,
)
from distributed_dot_product_tpu.utils.tracing import (
    device_peak_bytes, time_fn,
)

FULL_T = 75000   # reference benchmark.py:73
DIM = 768        # reference benchmark.py:74


def parse_args():
    # Same surface as reference benchmark.py:29-39, plus TPU-native extras.
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--mode', choices=['nt', 'all', 'tn', 'attn',
                                           'train', 'decode', 'lm',
                                           'decode-serve', 'serve-load'],
                        default='nt')
    parser.add_argument('--serve-requests', type=int, default=None,
                        help='decode-serve mode: burst size (default '
                             '4x slots)')
    parser.add_argument('--layers', type=int, default=8,
                        help='lm mode: transformer depth')
    parser.add_argument('--vocab', type=int, default=32768,
                        help='lm mode: vocabulary size')
    parser.add_argument('--remat', action='store_true',
                        help='lm mode: per-layer rematerialization '
                             '(scanned stack)')
    parser.add_argument('--no-scan', action='store_true',
                        help='lm mode: unrolled layers instead of '
                             'nn.scan')
    parser.add_argument('--batch', type=int, default=1,
                        help='decode mode: sequences decoded per step')
    parser.add_argument('--decode-chain', type=int, default=1,
                        help='decode mode: tokens decoded per dispatch '
                             '(a lax.scan of steps inside ONE jit — '
                             'amortizes the per-dispatch floor that '
                             'otherwise hides small-cache/GQA wins)')
    parser.add_argument('--seq-len', type=int, default=None,
                        help='global sequence length (train mode default '
                             '16384; attn mode default 75000//scale)')
    parser.add_argument('--no-mask', action='store_true',
                        help='train mode: attn_mask=None — drops the only '
                             'O(T^2) input on the flash path (long-context '
                             'configuration)')
    parser.add_argument('--mask-kind', choices=['dense', 'none', 'segments'],
                        default=None,
                        help='train mode mask form (overrides --no-mask): '
                             "'segments' = packed-sequence ids, O(T) "
                             'traffic + cross-segment block skipping')
    parser.add_argument('--segments', type=int, default=8,
                        help='number of packed spans for '
                             '--mask-kind segments')
    parser.add_argument('--causal', action='store_true',
                        help='train mode: autoregressive masking (handled '
                             'blockwise in-kernel on ring/flash/ulysses)')
    parser.add_argument('--window', type=int, default=None,
                        help='train mode: sliding-window lookback cap '
                             '(requires --causal) — attention compute '
                             'becomes O(T·window), linear in T')
    parser.add_argument('--attn-impl',
                        choices=['full', 'online', 'flash', 'flash_bounded',
                                 'ulysses'],
                        default='flash',
                        help='attention softmax/fusion path (attn mode)')
    parser.add_argument('--heads', type=int, default=8,
                        help='attention heads (attn mode)')
    parser.add_argument('--head-dim', type=int, default=64,
                        help='per-head feature dim (attn mode)')
    parser.add_argument('--qk-quant', choices=['int8'], default=None,
                        help='attn mode (flash impls): int8-quantized '
                             'QK^T on the MXU int8 path; decode mode: '
                             'an int8-trained model decoding through '
                             'its append-time int8 K mirror')
    parser.add_argument('--weight-quant', choices=['off', 'int8'],
                        default='off',
                        help='decode/decode-serve modes: int8 WEIGHT '
                             'quantization for the projection/head '
                             'matmuls (per-output-channel scales, '
                             's8xs8->s32 with in-kernel dequant — '
                             'models/dense.py). Rows record weight '
                             'bytes + kv bytes next to time, so the '
                             'quantized row is judged against its '
                             'bf16 twin on BYTES MOVED as well')
    parser.add_argument('--kv-heads', type=int, default=None,
                        help='attn/train modes: grouped-query K/V head '
                             'count (< --heads, must divide it); default '
                             '= --heads (standard multi-head)')
    parser.add_argument('--decode-impl', choices=['auto', 'kernel', 'xla'],
                        default='auto',
                        help='decode/decode-serve modes: decode-step '
                             'path — the fused Pallas kernel (in-place '
                             'aliased cache append + split-K attention) '
                             'vs the XLA append+einsum step; auto = '
                             'kernel on TPU. Recorded in the result row '
                             'so kernel-vs-XLA tables read straight off '
                             'the JSON')
    parser.add_argument('--cache-mode', choices=['slab', 'paged'],
                        default='slab',
                        help='decode-serve mode: KV-cache layout — the '
                             'dense per-slot slab, or the paged pool '
                             '(same KV byte budget, 4x the slots; rows '
                             'record pool utilization + peak '
                             'concurrency so slab/paged twin rows '
                             'compare at fixed memory)')
    parser.add_argument('--page-size', type=int, default=16,
                        help='decode-serve --cache-mode paged: pool '
                             'page granularity in rows (= the fused '
                             "kernel's K split; must divide --seq-len)")
    parser.add_argument('--kv-shards', type=int, default=None,
                        help='decode / decode-serve: shard each paged '
                             "KV pool across the mesh's seq axis (N "
                             'members, each owning a contiguous page '
                             'range and a fixed per-shard pool) — '
                             'rows record capacity_tokens per shard '
                             'count, the linear-scaling acceptance '
                             'column')
    parser.add_argument('--spec', choices=['off', 'ngram', 'draft'],
                        default='off',
                        help='decode mode: speculative (draft-verify) '
                             'generation rows — the scheduler drives '
                             'the fused verify-k program with the '
                             'named proposer on a repetitive prompt '
                             'and the row records accepted-tokens/'
                             'step, tokens/s and the non-spec '
                             "baseline's tokens/s on the same "
                             'engine/prompts (greedy verification '
                             'keeps both streams identical — the run '
                             'asserts it)')
    parser.add_argument('--spec-k', type=int, default=4,
                        help='--spec: most proposals per slot per '
                             'verify step (verify width k+1)')
    # serve-load mode (the SLO observatory row, ROADMAP item 5): the
    # DEFAULTS here ARE the CI smoke config — scripts/ci.sh runs this
    # mode bare and gates its event log against the committed
    # SLO_BASELINE.json, so changing a default is a baseline refresh.
    parser.add_argument('--load-seed', type=int, default=7,
                        help='serve-load mode: trace seed (same seed = '
                             'identical trace and goodput report)')
    parser.add_argument('--load-rate', type=float, default=600.0,
                        help='serve-load mode: aggregate offered rate, '
                             'requests per VIRTUAL second (the default '
                             'runs the stock engine at ~85%% goodput — '
                             'contended enough that scheduling policy '
                             'moves the number)')
    parser.add_argument('--load-requests', type=int, default=48,
                        help='serve-load mode: trace length')
    parser.add_argument('--load-tenants', type=int, default=2,
                        help='serve-load mode: tenant count (stock '
                             'interactive/batchy mix)')
    parser.add_argument('--arrival',
                        choices=['poisson', 'bursty', 'ramp', 'step'],
                        default='poisson',
                        help='serve-load mode: arrival process (bursty '
                             '= ON/OFF modulated Poisson; ramp/step '
                             'climb the rate toward rate*ramp-factor '
                             'across the trace — the deterministic '
                             'autoscaling exercisers)')
    parser.add_argument('--ramp-factor', type=float, default=4.0,
                        help='serve-load mode, --arrival ramp/step: '
                             'peak rate multiple')
    parser.add_argument('--control', action='store_true',
                        help='serve-load mode: arm the closed-loop '
                             'controller (serve/control.py) on the '
                             "run's virtual clock — watchdog-driven "
                             'watermark/queue actuation, and with '
                             '--topology elastic decode autoscaling '
                             '(scale-up to --control-max-replicas); '
                             'every action lands in the event log as '
                             'a control.* record')
    parser.add_argument('--control-max-replicas', type=int, default=3,
                        help='--control + --topology: autoscaling '
                             'ceiling for the decode pool')
    parser.add_argument('--load-tick', type=float, default=0.002,
                        help='serve-load mode: virtual seconds one '
                             'scheduler tick costs (the simulated '
                             'decode-step duration)')
    parser.add_argument('--slo-ttft', type=float, default=0.25,
                        help='serve-load mode: TTFT deadline (s)')
    parser.add_argument('--slo-token', type=float, default=0.05,
                        help='serve-load mode: max inter-token gap (s)')
    parser.add_argument('--queue-limit', type=int, default=12,
                        help='serve-load mode: admission queue bound '
                             '(the overload ladder input)')
    parser.add_argument('--event-log', default=None,
                        help='serve-load mode: write the run\'s JSONL '
                             'event log here (the goodput report is '
                             'computed from it ALONE; default: a '
                             'temp file). With --topology it is the '
                             'log DIRECTORY: one log per member '
                             '(router/prefill/r0/r1/... + twin)')
    parser.add_argument('--topology', default=None,
                        help="serve-load mode: run the trace against a "
                             "disaggregated 'PxD' topology (P prefill "
                             "pools x D decode replicas, e.g. 1x2) "
                             "through the router, AND against its "
                             "single-process twin (one replica's "
                             "engine) on the identical trace — the "
                             "row records both goodputs and the "
                             "routing telemetry")
    parser.add_argument('--prefill-threshold', type=int, default=8,
                        help='--topology: prefix rows at/above which a '
                             'fresh prompt offloads to the prefill '
                             'pool (below it the replica prefills '
                             'locally)')
    parser.add_argument('--chaos', action='store_true',
                        help='--topology: seeded replica-crash chaos '
                             'row — kill --chaos-victim at virtual '
                             'tick --chaos-tick mid-trace, let the '
                             "router's probes declare the loss and "
                             'the recovery ledger re-place every '
                             'in-flight stream, then run the SAME '
                             'crash against a max_recoveries=0 '
                             'no-recovery twin; the row records both '
                             'goodputs, the recovered stream set and '
                             'their bit-identity against the '
                             'crash-free single-process twin, and the '
                             'replica_lost flight bundle')
    parser.add_argument('--chaos-victim', default='r1',
                        help='--chaos: decode replica to kill')
    parser.add_argument('--chaos-tick', type=int, default=40,
                        help='--chaos: loadgen tick (virtual time '
                             'coordinate) at which the victim dies')
    parser.add_argument('--chaos-corrupt', default=None,
                        metavar='PAGE:TICK',
                        help='--topology: seeded KV-corruption chaos '
                             'row — flip one bit in tracked page index '
                             'PAGE of --chaos-victim at tick TICK, '
                             'assert every flip is detected before any '
                             'poisoned token is emitted and the victim '
                             'streams heal bit-identical to the '
                             'crash-free twin, then run the SAME flip '
                             'against a checksums-off twin to count '
                             'the silent wrong streams integrity '
                             'prevents; the row records the detection/'
                             'heal ledger, verify-time cost and both '
                             'goodputs')
    parser.add_argument('--chaos-prefill-crash', type=int, default=None,
                        metavar='TICK',
                        help='--topology: kill the prefill pool at '
                             'tick TICK mid-trace — the router probes '
                             'it like a replica, declares prefill.lost '
                             'and falls back to flat prefill on the '
                             'decode replicas (no stream blocks, every '
                             'stream classified); the row records the '
                             'fallback accounting')
    parser.add_argument('--no-ttft', action='store_true',
                        help='decode mode: skip the time-to-first-token '
                             'prefill-latency row (it compiles a full '
                             'prefill flash pass at the cache fill)')
    parser.add_argument('--use-rope', action='store_true',
                        help='train mode: rotary position embeddings on '
                             'the projected score operands (module '
                             'use_rope knob)')
    parser.add_argument(
        '--offset', default=32,
        type=lambda s: None if s.lower() in ('none', 'full') else int(s),
        help="gathered-chunk size; 'none' = single full gather")
    parser.add_argument('--scale', type=int, default=1,
                        help='T = 75000 // scale')
    parser.add_argument('--file', default='benchmark_results.json')
    parser.add_argument('--metrics-out', default=None,
                        help='write an observability snapshot JSON for '
                             'this run: the metrics-registry snapshot '
                             '(serve counters/histograms when mode '
                             'drives the scheduler) plus the phase-span '
                             'tree (compile vs measure wall time). '
                             'Enables span collection for the run.')
    parser.add_argument('--dtype', choices=['f32', 'bf16'], default='f32')
    parser.add_argument('--impl', choices=['allgather', 'ring'],
                        default='allgather')
    parser.add_argument('--devices', type=int, default=None,
                        help='mesh width (default: all visible)')
    parser.add_argument('--iters', type=int, default=5)
    parser.add_argument('--skip-local', action='store_true',
                        help='skip the single-device full-size baseline')
    parser.add_argument('--profile-dir', default=None,
                        help='write a jax.profiler trace here')
    # Multi-host measurement surface (the reference gathers per-rank stats
    # to rank 0 via MPI.gather and averages, reference benchmark.py:104-117)
    parser.add_argument('--multihost', action='store_true',
                        help='join a multi-process run via comm.init(); '
                             'per-process measurements are allgathered, '
                             'process 0 writes the averaged record. One '
                             'process per HOST: a chip belongs to one '
                             'process, so several processes on one '
                             'machine must all be CPU-only '
                             '(JAX_PLATFORMS=cpu)')
    parser.add_argument('--coordinator', default=None,
                        help='coordinator address host:port (multihost)')
    parser.add_argument('--num-processes', type=int, default=None)
    parser.add_argument('--process-id', type=int, default=None)
    return parser.parse_args()


def make_inputs(mode, t, dtype, key=111):  # seed: reference benchmark.py:47
    k1, k2 = jax.random.split(jax.random.key(key))
    if mode == 'nt':
        left = jax.random.normal(k1, (t, DIM), dtype)
        right = jax.random.normal(k2, (t, DIM), dtype)
    else:  # 'all' and 'tn': left is a score-shaped (T, T) operand
        left = jax.random.normal(k1, (t, t), dtype)
        right = jax.random.normal(k2, (t, DIM), dtype)
    return left, right


LOCAL = {
    'nt': lambda l, r: jnp.matmul(l, r.T),
    'all': lambda l, r: jnp.matmul(l, r),
    'tn': lambda l, r: jnp.matmul(l.T, r),
}


def _summed(fn):
    """Reduce the op's output to a scalar inside the jit: timing queues many
    async dispatches, and full outputs (up to GiBs for nt) would all stay
    live at once. The extra reduction pass is charged to both the local and
    distributed measurements equally (and biases *against* us vs the
    reference, whose timings exclude any output read)."""
    return jax.jit(lambda *a: jnp.sum(fn(*a), dtype=jnp.float32))


def _device_bytes_limit():
    """Per-device HBM limit from the runtime's own ``memory_stats()``.
    None off-TPU (the CPU functional runs have no HBM to pre-flight
    against); on a TPU a missing statistic raises rather than skipping
    the check."""
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        return None
    return dev.memory_stats()['bytes_limit']


def run_attn(args):
    """Attention-op benchmark (no reference analog — the reference only
    benchmarks the L2 kernels, reference benchmark.py:23-26): time the
    fused/online/full attention paths ``softmax(q·kᵀ/√d [+mask])·v`` at
    ``T = 75000 // scale``, reporting the 2·matmul FLOP rate."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu.models.ring_attention import (
        ring_attention,
    )
    from distributed_dot_product_tpu.ops.functions import (
        _shard_mapped, distributed_matmul_all, distributed_matmul_nt,
    )
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )

    mesh = seq_mesh(args.devices)
    world = mesh.devices.size
    t = args.seq_len or FULL_T // args.scale
    t -= t % world
    h, d = args.heads, args.head_dim
    dtype = jnp.float32 if args.dtype == 'f32' else jnp.bfloat16
    flops = 4.0 * h * t * t * d

    if args.attn_impl == 'full':
        # Full softmax materializes the per-shard (H, T/N, T) scores —
        # refuse what can't fit rather than dying in an opaque device OOM
        # (the reference's module path has the same ceiling, SURVEY §5).
        # Sized per device; ×2 for scores + softmax output both live.
        limit = _device_bytes_limit()
        need = 2 * h * (t // world) * t * jnp.dtype(dtype).itemsize
        if limit and need > 0.45 * limit:
            raise SystemExit(
                f'attn_impl=full needs ~{need / 2**30:.1f} GiB of score '
                f'buffers per device; raise --scale or use more devices')

    from distributed_dot_product_tpu.parallel.mesh import globalize
    keys = jax.random.split(jax.random.key(111), 3)
    h_kv = args.kv_heads or h
    if args.kv_heads and args.attn_impl not in ('flash', 'flash_bounded',
                                                'online', 'ulysses'):
        raise SystemExit('--kv-heads (GQA) needs a fused attn impl '
                         '(flash/flash_bounded/online/ulysses)')
    if args.qk_quant and args.attn_impl not in ('flash', 'ulysses'):
        raise SystemExit('--qk-quant applies to --attn-impl flash or '
                         'ulysses (the record must name the path actually '
                         'measured; flash_bounded would silently coerce '
                         'to the exact kernel when quantized)')
    spec = P(None, None, SEQ_AXIS, None)
    q = globalize(jax.random.normal(keys[0], (1, h, t, d), dtype),
                  NamedSharding(mesh, spec))
    k, v = (globalize(jax.random.normal(kk, (1, h_kv, t, d), dtype),
                      NamedSharding(mesh, spec)) for kk in keys[1:])

    # Every impl runs through shard_map (a W=1 mesh degenerates cleanly), so
    # the recorded attn_impl always names the code path actually measured.
    if args.attn_impl == 'online':
        body = lambda q, k, v: ring_attention(q, k, v)  # noqa: E731
    elif args.attn_impl == 'ulysses':
        from distributed_dot_product_tpu.models.ulysses_attention import (
            ulysses_attention,
        )
        body = lambda q, k, v: ulysses_attention(  # noqa: E731
            q, k, v, qk_quant=args.qk_quant)
    elif args.attn_impl in ('flash', 'flash_bounded'):
        smode = 'bounded' if args.attn_impl == 'flash_bounded' else 'exact'

        def body(q, k, v):
            kf = jax.lax.all_gather(k, SEQ_AXIS, axis=2, tiled=True)
            vf = jax.lax.all_gather(v, SEQ_AXIS, axis=2, tiled=True)
            return flash_attention(q, kf, vf, softmax_mode=smode,
                                   qk_quant=args.qk_quant)
    else:
        def body(q, k, v):
            s = distributed_matmul_nt(q, k, args.offset) / np.sqrt(d)
            a = jax.nn.softmax(s, axis=-1)
            return distributed_matmul_all(a, v, args.offset)
    fn = _shard_mapped(body, mesh, (4, 4, 4), 4)

    # AOT-compile once: the executable feeds both the timing loop and the
    # memory analysis (a second .lower().compile() would double the
    # per-config cost — compiles dominate the sweep).
    with span('benchmark.compile', mode='attn'):
        timed = _summed(fn).lower(q, k, v).compile()
    with span('benchmark.measure', mode='attn'):
        best, mean = time_fn(timed, q, k, v, iters=args.iters)
    peak = device_peak_bytes()
    record = {
        'mode': 'attn', 'attn_impl': args.attn_impl, 'scale': args.scale,
        'T': t, 'heads': h, 'kv_heads': h_kv, 'head_dim': d,
        'qk_quant': args.qk_quant, 'world': world,
        'dtype': args.dtype, 'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'dist_time': best, 'dist_time_mean': mean,
        'dist_gflops_per_chip': flops / world / best / 1e9,
        'dist_peak_bytes_per_chip': peak,
        'dist_memory_analysis': _memory_analysis(timed),
        'perf_model': _perf_model(timed, best),
    }
    gq = '' if h_kv == h else f'/kv{h_kv}'
    print(f"attn[{args.attn_impl}] T={t} H={h}{gq} d={d} {world}-device: "
          f"{best:.4f}s ({record['dist_gflops_per_chip']:.0f} GFLOP/s/chip"
          + (f", peak {peak / 2**30:.2f} GiB)" if peak else ")"))
    _append_record(args.file, record)
    return record


def _perf_model(compiled, measured_seconds=None):
    """Compiler-counted model-vs-measured columns for a timed program
    (obs/perf.py): XLA's own FLOP/byte accounting, arithmetic
    intensity, the compute-vs-bandwidth roofline class against the
    live chip's published peaks, and — when a measured time is passed —
    achieved GFLOP/s / GB/s over the compiler-counted work plus the
    fraction of roofline reached. None off-TPU: a CPU functional run
    takes no roofline. On a TPU an unknown device kind raises."""
    if jax.devices()[0].platform != 'tpu':
        return None
    from distributed_dot_product_tpu.obs.perf import program_model
    return program_model(compiled, measured_seconds=measured_seconds)


def _memory_analysis(compiled):
    """Compiler-reported per-device HBM footprint of the compiled program.

    The reference records ``torch.cuda.max_memory_allocated`` (reference
    benchmark.py:57-62); XLA's own buffer assignment is exact and
    reproducible, and it captures the offset↔memory trade the same way
    (bigger gathered chunks = bigger temp buffers).
    """
    ma = compiled.memory_analysis()
    return {
        'argument_bytes': ma.argument_size_in_bytes,
        'output_bytes': ma.output_size_in_bytes,
        'temp_bytes': ma.temp_size_in_bytes,
        'total_bytes': (ma.argument_size_in_bytes
                        + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes),
    }


def measure_train_step(*, seq_len, attn_impl='flash', dtype='bf16',
                       no_mask=False, causal=False, iters=3, devices=None,
                       impl='allgather', offset=32, heads=8,
                       mask_kind=None, n_segments=8, window=None,
                       kv_heads=None, use_rope=False):
    """Measure one full training step — forward, loss, gradient psum, optax
    update as ONE compiled SPMD program (``train.make_train_step``).
    Returns the result record; shared by ``--mode train`` and ``bench.py``
    so the FLOP accounting and setup cannot drift apart.

    ``mask_kind``: 'dense' (reference-style boolean (B, T, T) zeros mask),
    'none' (attn_mask=None) or 'segments' (packed-sequence ids, O(T) —
    ``n_segments`` equal spans); default resolves from the legacy
    ``no_mask`` flag.

    FLOPs: 4 projections (2·T·768² each) + scores/context matmuls
    (2·T²·768 each) forward; backward ≈ 2× forward; adam is negligible.
    The segment FLOP count is NOT discounted for cross-segment skipping,
    so reported GFLOP/s includes the skip as apparent speedup (same
    convention as the causal discount, which IS applied, being exactly 2×).
    ``window`` (requires causal) counts only in-window pairs — attention
    work is then O(T·window), so s/step is the honest headline and
    GFLOP/s shows kernel efficiency on the remaining work.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.train import make_train_step

    mesh = seq_mesh(devices)
    world = mesh.devices.size
    t = seq_len - seq_len % world
    jdtype = jnp.float32 if dtype == 'f32' else jnp.bfloat16

    model = DistributedDotProductAttn(
        key_dim=DIM, num_heads=heads, num_kv_heads=kv_heads, offset=offset,
        softmax_impl=attn_impl.replace('_bounded', ''),
        flash_softmax_mode=('bounded' if attn_impl == 'flash_bounded'
                            else 'exact'),
        causal=causal, window=window, impl=impl, dtype=jdtype,
        use_rope=use_rope)

    if mask_kind is None:
        mask_kind = 'none' if no_mask else 'dense'
    if mask_kind not in ('dense', 'none', 'segments'):
        raise ValueError(f'unknown mask_kind {mask_kind!r}')

    from distributed_dot_product_tpu.parallel.mesh import globalize

    k1, k2 = jax.random.split(jax.random.key(111))
    x_host = jax.random.normal(k1, (1, t, DIM), jdtype)
    target_host = jax.random.normal(k2, (1, t, DIM), jdtype)
    act = NamedSharding(mesh, P(None, SEQ_AXIS, None))
    # globalize: same-seeded host arrays exist in every process, so this
    # works unchanged when --multihost splits the mesh across processes.
    x = globalize(x_host, act)
    target = globalize(target_host, act)
    mask = None if mask_kind != 'dense' else globalize(
        jnp.zeros((1, t, t), dtype=bool),
        NamedSharding(mesh, P(None, SEQ_AXIS, None)))
    seg = None
    if mask_kind == 'segments':
        # n_segments equal packed spans — the compact O(T) mask form.
        seg = globalize(
            (jnp.arange(t, dtype=jnp.int32) * n_segments // t)[None],
            NamedSharding(mesh, P(None, SEQ_AXIS)))

    # Init at a tiny T: parameter shapes depend only on DIM, and a
    # full-length init forward would cost an extra whole-T compile per
    # sweep config.
    t0 = max(world * 2, 16)
    x0 = jnp.zeros((1, t0, DIM), jdtype)
    params = model.init(jax.random.key(0), x0, x0, x0,
                        jnp.zeros((1, t0, t0), dtype=bool))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step = make_train_step(model, optimizer, mesh, donate=False)

    batch = (x, x, x, mask, target, seg)
    with span('benchmark.compile', mode='train'):
        compiled = step.lower(params, opt_state, batch).compile()
    with span('benchmark.measure', mode='train'):
        best, mean = time_fn(compiled, params, opt_state, batch,
                             iters=iters)
    # Attended (query, key) pairs: full square, causal lower triangle, or
    # the sliding-window band (row i attends min(i+1, window) keys).
    if causal and window is not None:
        w = min(window, t)
        pairs = w * (w + 1) / 2.0 + (t - w) * float(w)
    elif causal:
        pairs = t * t / 2.0
    else:
        pairs = float(t) * t
    # GQA shrinks the queries/values projections to kv_heads/heads of
    # their features (keys/composition unchanged); the attention matmuls
    # stay per-q-head, so their FLOPs don't change.
    kvfrac = (kv_heads / heads) if kv_heads else 1.0
    flops = 3.0 * (4.0 * t * DIM * DIM * (1.0 + kvfrac)
                   + 4.0 * pairs * DIM)
    return {
        'mode': 'train', 'attn_impl': attn_impl, 'T': t, 'dim': DIM,
        'heads': heads, 'kv_heads': kv_heads or heads,
        'use_rope': use_rope, 'world': world, 'dtype': dtype,
        # offset/impl shape only the 'full' softmax path's matmuls, but are
        # recorded always so any run is reproducible from its record.
        'offset': offset, 'impl': impl,
        'mask': mask_kind == 'dense', 'mask_kind': mask_kind,
        'n_segments': n_segments if mask_kind == 'segments' else None,
        'causal': causal, 'window': window,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'step_time': best, 'step_time_mean': mean,
        'step_gflops_per_chip': flops / world / best / 1e9,
        'memory_analysis': _memory_analysis(compiled),
        'perf_model': _perf_model(compiled, best),
    }


def measure_lm_step(*, seq_len, n_layers=8, vocab=32768, dtype='bf16',
                    heads=8, kv_heads=None, iters=3, devices=None,
                    causal=True, window=None, scan_layers=True,
                    remat=False, attn_impl='flash'):
    """One full LM training step — embed → scanned transformer stack →
    tied head → packed-segment cross-entropy → grad psum → adam — as one
    compiled SPMD program (``train.make_lm_train_step``). The capstone
    measurement: the framework training the thing it is architected for.

    FLOPs (per fwd, ×3 for the step): per layer the 4 attention
    projections ``4·T·D²·(1+kv/H)``, the two attention matmuls
    ``4·pairs·D``, and the MLP ``16·T·D²``; plus the tied head
    ``2·T·D·V``. Tokens/s is the honest end-to-end headline (it charges
    the head and loss too); GFLOP/s shows kernel efficiency.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu import TransformerLM, lm_targets
    from distributed_dot_product_tpu.parallel.mesh import globalize
    from distributed_dot_product_tpu.train import make_lm_train_step

    mesh = seq_mesh(devices)
    world = mesh.devices.size
    t = seq_len - seq_len % world
    jdtype = jnp.float32 if dtype == 'f32' else jnp.bfloat16

    model = TransformerLM(
        vocab_size=vocab, dim=DIM, num_heads=heads, n_layers=n_layers,
        scan_layers=scan_layers, remat=remat, dtype=jdtype,
        attn_kwargs=dict(softmax_impl=attn_impl, num_kv_heads=kv_heads,
                         causal=causal, window=window))

    toks_host = jax.random.randint(jax.random.key(111), (1, t), 0, vocab,
                                   dtype=jnp.int32)
    spec = NamedSharding(mesh, P(None, SEQ_AXIS))
    tokens = globalize(toks_host, spec)
    targets = globalize(lm_targets(toks_host), spec)

    params = model.init(jax.random.key(0),
                        toks_host[:, :max(world * 2, 16)])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step = make_lm_train_step(model, optimizer, mesh, donate=False)

    batch = (tokens, targets)
    with span('benchmark.compile', mode='lm'):
        compiled = step.lower(params, opt_state, batch).compile()
    with span('benchmark.measure', mode='lm'):
        best, mean = time_fn(compiled, params, opt_state, batch,
                             iters=iters)
    if causal and window is not None:
        w = min(window, t)
        pairs = w * (w + 1) / 2.0 + (t - w) * float(w)
    elif causal:
        pairs = t * t / 2.0
    else:
        pairs = float(t) * t
    kvfrac = (kv_heads / heads) if kv_heads else 1.0
    fwd = (n_layers * (4.0 * t * DIM * DIM * (1.0 + kvfrac)
                       + 16.0 * t * DIM * DIM + 4.0 * pairs * DIM)
           + 2.0 * t * DIM * vocab)
    return {
        'mode': 'lm', 'attn_impl': attn_impl, 'T': t, 'dim': DIM,
        'heads': heads, 'kv_heads': kv_heads or heads,
        'n_layers': n_layers, 'vocab': vocab, 'n_params': n_params,
        'scan_layers': scan_layers, 'remat': remat, 'world': world,
        'dtype': dtype, 'causal': causal, 'window': window,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'step_time': best, 'step_time_mean': mean,
        'tokens_per_s': t / best,
        'step_gflops_per_chip': 3.0 * fwd / world / best / 1e9,
        'memory_analysis': _memory_analysis(compiled),
        'perf_model': _perf_model(compiled, best),
    }


def run_lm(args):
    """``--mode lm``: the capstone workload — no reference analog (the
    reference has no model layer at all; anchor: its single-attention
    example, reference example.py:16-33)."""
    record = measure_lm_step(
        seq_len=args.seq_len or 16384, n_layers=args.layers,
        vocab=args.vocab, dtype=args.dtype, heads=args.heads,
        kv_heads=args.kv_heads, iters=args.iters, devices=args.devices,
        causal=True, window=args.window,
        scan_layers=not args.no_scan, remat=args.remat,
        attn_impl=args.attn_impl)
    ma = record['memory_analysis'] or {}
    print(f"lm[{record['attn_impl']}] T={record['T']} "
          f"{record['n_layers']}L dim={DIM} vocab={record['vocab']} "
          f"({record['n_params'] / 1e6:.1f}M params"
          f"{', remat' if record['remat'] else ''}): "
          f"{record['step_time']:.4f}s/step "
          f"{record['tokens_per_s']:,.0f} tok/s "
          f"({record['step_gflops_per_chip']:.0f} GFLOP/s/chip, "
          f"temp {ma.get('temp_bytes', 0) / 2**30:.2f} GiB)")
    _append_record(args.file, record)
    return record


def run_train(args):
    """``--mode train``: the reference example workload scaled up
    (reference example.py runs T=4096, dim 768, heads 2 with no optimizer;
    here T defaults to 16384 with an adam update)."""
    record = measure_train_step(
        seq_len=args.seq_len or 16384, attn_impl=args.attn_impl,
        dtype=args.dtype,
        no_mask=args.no_mask, causal=args.causal, iters=args.iters,
        devices=args.devices, impl=args.impl, offset=args.offset,
        heads=args.heads, mask_kind=args.mask_kind, window=args.window,
        n_segments=args.segments, kv_heads=args.kv_heads,
        use_rope=args.use_rope)
    ma = record['memory_analysis'] or {}
    gq = ('' if record['kv_heads'] == record['heads']
          else f"/kv{record['kv_heads']}")
    print(f"train[{args.attn_impl}] T={record['T']} dim={DIM} "
          f"H={record['heads']}{gq} {record['world']}-device: "
          f"{record['step_time']:.4f}s/step "
          f"({record['step_gflops_per_chip']:.0f} GFLOP/s/chip, "
          f"temp {ma.get('temp_bytes', 0) / 2**30:.2f} GiB)")
    _append_record(args.file, record)
    return record


# Per-process measurements averaged across hosts (the reference's
# MPI.gather-to-rank-0-and-average, reference benchmark.py:104-117); the
# throughput fields derived from them are rescaled to match.
_MH_TIME_KEYS = ('local_time', 'local_time_mean', 'dist_time',
                 'dist_time_mean', 'step_time', 'step_time_mean')
_MH_RATE_KEYS = {'dist_gflops_per_chip': 'dist_time',
                 'step_gflops_per_chip': 'step_time',
                 'local_gflops': 'local_time'}


def _multihost_aggregate(record):
    """Average the timing fields over all processes; every process returns
    the same aggregated record (process 0 is the only writer)."""
    if jax.process_count() == 1:
        return record
    import numpy as np
    from jax.experimental import multihost_utils

    local = np.array([float(record[k]) if record.get(k) is not None
                      else np.nan for k in _MH_TIME_KEYS], np.float64)
    gathered = np.asarray(multihost_utils.process_allgather(local))
    rec = dict(record)
    for i, k in enumerate(_MH_TIME_KEYS):
        if record.get(k) is not None:
            rec[k] = float(np.mean(gathered[:, i]))
    for rate, timek in _MH_RATE_KEYS.items():
        if record.get(rate) is not None and record.get(timek):
            rec[rate] = record[rate] * record[timek] / rec[timek]
    rec['n_processes'] = jax.process_count()
    return rec


def _append_record(path, record):
    # Append-to-JSON-file convention (reference benchmark.py:42-44,241-253).
    # Multihost: aggregate everywhere (collective), write on process 0 only.
    record = _multihost_aggregate(record)
    if jax.process_index() != 0:
        return record
    results = []
    if os.path.exists(path):
        with open(path) as f:
            results = json.load(f)
    results.append(record)
    with open(path, 'w') as f:
        json.dump(results, f, indent=2)
    return record


def _probe_paged_int8(h_kv, d):
    """A FIXED-SHAPE mirror-carrying paged cache for the eligibility
    flag recorded on decode rows — a code canary for the categorical
    capability (mirror pools ride the fused kernel), not a probe of
    this row's page geometry (eligibility depends on page size vs the
    VMEM cap, not on h_kv/d; the row's slab cache has no page size)."""
    from distributed_dot_product_tpu.models.decode import (
        init_paged_cache,
    )
    return init_paged_cache(1, h_kv, 64, d, pages=2, page_size=16,
                            qk_quant='int8')


def run_decode(args):
    """``--mode decode``: steady-state KV-cache decode latency through
    the module surface (one token per step against a part-filled cache).
    No reference analog (the reference has no inference path); the
    honest metric is ms/token at a given cache fill — decode is
    HBM-bandwidth-bound (the step streams the K/V cache once), so the
    record also derives achieved GB/s over the cache bytes."""
    from distributed_dot_product_tpu import DistributedDotProductAttn

    t_max = args.seq_len or 16384
    h, d = args.heads, args.head_dim
    h_kv = args.kv_heads or h
    dtype = jnp.float32 if args.dtype == 'f32' else jnp.bfloat16
    # qk_quant='int8': the cache carries an append-time int8 K mirror —
    # the decode step streams it instead of the bf16 K (half the K
    # bytes on a bandwidth-bound step).
    weight_quant = (None if args.weight_quant == 'off'
                    else args.weight_quant)
    model = DistributedDotProductAttn(
        key_dim=h * d, num_heads=h, num_kv_heads=args.kv_heads,
        causal=True, use_rope=args.use_rope, softmax_impl='flash',
        qk_quant=args.qk_quant, weight_quant=weight_quant, dtype=dtype,
        decode_impl=(None if args.decode_impl == 'auto'
                     else args.decode_impl))
    b = args.batch
    x0 = jnp.zeros((b, 16, h * d), dtype)
    if weight_quant == 'int8':
        # Load/convert-time quantization: init the FLOAT twin's params
        # and convert — exactly the deployment flow (a trained float
        # checkpoint quantized once at load).
        from distributed_dot_product_tpu.models.dense import (
            quantize_dense_params,
        )
        float_model = DistributedDotProductAttn(
            key_dim=h * d, num_heads=h, num_kv_heads=args.kv_heads,
            causal=True, use_rope=args.use_rope, softmax_impl='flash',
            qk_quant=args.qk_quant, dtype=dtype)
        params = quantize_dense_params(
            float_model.init(jax.random.key(0), x0, x0, x0, None))
    else:
        params = model.init(jax.random.key(0), x0, x0, x0, None)
    fill = t_max - 64  # leave headroom for the timed decode steps
    cache = model.make_decode_cache(b, t_max, dtype=dtype)
    # Fill the cache directly with random projected operands: the timed
    # quantity is the per-token step against a full cache, and its cost
    # doesn't depend on the cached values (module.prefill would work too
    # but compiles a full flash pass this measurement doesn't need).
    from distributed_dot_product_tpu.models.decode import append_kv
    kf = jax.random.normal(jax.random.key(1), (b, h_kv, fill, d), dtype)
    vf = jax.random.normal(jax.random.key(4), (b, h_kv, fill, d), dtype)
    cache = append_kv(cache, kf, vf)

    tok = jax.random.normal(jax.random.key(2), (b, 1, h * d), dtype)
    # donate the cache: the append's dynamic_update_slice then writes in
    # place instead of copying the whole K/V buffer pair per token —
    # without donation an MHA 131K-cache step pays ~1 ms of pure copy.
    chain = max(1, args.decode_chain)
    if chain == 1:
        jitted = jax.jit(lambda p, xt, c: model.apply(p, xt, xt, xt, c,
                                                      method='decode'),
                         donate_argnums=(2,))
    else:
        # Chained decode: `chain` tokens per dispatch via lax.scan — the
        # per-dispatch overhead (~0.14 ms in RESULTS.md's record)
        # divides by `chain`, exposing the true per-token HBM cost that the
        # floor otherwise masks for small/GQA caches. The same token
        # feeds every step (its value doesn't change the cost); the
        # cache rides the scan carry in place.
        def chained(p, xt, c):
            def body(carry, _):
                c, out = model.apply(p, xt, xt, xt, carry,
                                     method='decode')
                return c, out[:, 0, :1]   # tiny per-step residue
            c, outs = jax.lax.scan(body, c, None, length=chain)
            return c, outs

        jitted = jax.jit(chained, donate_argnums=(2,))
    # AOT-compile the step once (the same executable feeds the timing
    # loop and the cost/roofline model — a jit dispatch would hide the
    # compiled object the model needs). Donation declared on the jit
    # carries through to the compiled callable.
    with span('benchmark.compile', mode='decode'):
        step = jitted.lower(params, tok, cache).compile()
    cache_box = [cache]

    def timed(p, xt):
        # The timed unit: one decode step (in-place cache append + masked
        # attention over the full buffer + 4 projections). The cache
        # cycles through the step so donation stays legal. The chained
        # timing steps exhaust the 64-slot headroom and then hit
        # append_kv's traced-overflow guard (the write-back no-op:
        # buffers unchanged, length keeps advancing) — the per-step cost
        # matches a real append (same row read+write, same full-buffer
        # attention), only the buffer contents stop being meaningful,
        # which timing doesn't read. (An attempt to pin the length
        # on-device made XLA drop the in-place aliasing for some configs
        # — whole-buffer copies again; recorded here so it isn't
        # retried.)
        c2, out = step(p, xt, cache_box[0])
        cache_box[0] = c2
        return out
    # Donated in-place steps are fast enough that the default 512-dispatch
    # window can fall below the measured sync overhead — let the
    # auto-scaler chain more steps per sample. One throwaway measurement
    # pass first: RESULTS.md's record saw per-token rates keep improving
    # over the first few thousand steps (0.59 → 0.23 ms/token across
    # three back-to-back measurements), so the recorded number is the
    # WARM steady state.
    with span('benchmark.warmup', mode='decode'):
        time_fn(timed, params, tok, iters=2, max_inner=16384)
    with span('benchmark.measure', mode='decode'):
        best, mean = time_fn(timed, params, tok, iters=args.iters,
                             max_inner=16384)
    if best * 1e3 < 1e-3:
        # A sample window that fell under the measured sync overhead
        # clamps to ~0 — a 17 ns "token" is not a measurement. Fall back
        # to the mean, which averages real windows.
        best = mean
    # One timed call decodes `chain` steps of `b` sequences: a STEP
    # emits b tokens, so ms_per_token = step_time / b (keeps the key's
    # round-4 semantics, where b was always 1) and ms_per_step carries
    # the per-step latency the batched table reads.
    step_time = best / chain

    # Time-to-first-token: cold cache → whole prompt ingested through
    # the prefill flash pass → the logits that commit token 1. Timed as
    # (fresh cache + prefill) per call so repeats don't overflow the
    # buffer; the decode-step latency above is added so the headline is
    # prompt-to-first-EMITTED-token, matching how a serving loop feeds
    # the prefill's last logits through one decode dispatch.
    prefill_time = None
    if not args.no_ttft:
        prompt = jax.random.normal(jax.random.key(3), (b, fill, h * d),
                                   dtype)

        def prefill_fn(p, toks):
            c = model.make_decode_cache(b, t_max, dtype=dtype)
            c, out = model.apply(p, toks, toks, toks, c,
                                 method='prefill')
            return out[:, -1:]            # tiny residue forces the pass

        prefill_jit = jax.jit(prefill_fn)
        with span('benchmark.ttft', mode='decode'):
            prefill_time, _ = time_fn(prefill_jit, params, prompt,
                                      iters=max(2, args.iters // 2))
    # Bytes the attention actually streams per step: V at the cache
    # dtype plus K at the cache dtype — or the 1-byte int8 mirror (and
    # its small per-row scales) when qk_quant carries one, so the GB/s
    # column stays an achieved-bandwidth figure for int8 rows too.
    elem = jnp.dtype(dtype).itemsize
    k_bytes = (t_max * d * 1 + t_max * 4 if args.qk_quant == 'int8'
               else t_max * d * elem)
    cache_bytes = b * h_kv * (t_max * d * elem + k_bytes)
    # Weight bytes the step streams (the four projection kernels +
    # scales/biases) — int8 weights roughly quarter the f32 twin's and
    # halve the bf16 twin's, so the quantized row must beat its twin
    # on kv+weight bytes, not just kv bytes.
    from distributed_dot_product_tpu.models.dense import (
        dense_param_bytes,
    )
    weight_bytes = dense_param_bytes(params)
    # The path actually measured (auto resolves per backend), so
    # kernel-vs-XLA tables read straight off the records — resolved by
    # the SAME function decode_step uses, so the label cannot drift
    # from the code path.
    from distributed_dot_product_tpu.models.decode import (
        _resolve_decode_impl, decode_kernel_eligible,
    )
    impl_resolved = _resolve_decode_impl(
        None if args.decode_impl == 'auto' else args.decode_impl,
        cache_box[0], 1, None, args.qk_quant)
    record = {
        'mode': 'decode', 't_max': t_max, 'fill': fill, 'heads': h,
        'kv_heads': h_kv, 'head_dim': d, 'dtype': args.dtype,
        'use_rope': args.use_rope, 'world': 1,
        'batch': b, 'chain': chain, 'qk_quant': args.qk_quant,
        'weight_quant': weight_quant,
        'weight_bytes': weight_bytes,
        'kv_bytes': cache_bytes,
        'step_bytes': cache_bytes + weight_bytes,
        # The tentpole-c acceptance probe: quantized decode must be
        # kernel-eligible ON THE PAGE POOL (mirror pools present) —
        # recorded on every row so the CI smoke reads it off the twin.
        'paged_int8_kernel_eligible': bool(decode_kernel_eligible(
            _probe_paged_int8(h_kv, d), qk_quant='int8')),
        'decode_impl': impl_resolved,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'ms_per_step': step_time * 1e3,
        'ms_per_token': step_time / b * 1e3,
        'ms_per_token_mean': mean / chain / b * 1e3,
        'tokens_per_s': b * chain / best,
        'cache_gb_per_s': cache_bytes / step_time / 1e9,
        'prefill_ms': (None if prefill_time is None
                       else prefill_time * 1e3),
        'ttft_ms': (None if prefill_time is None
                    else (prefill_time + step_time) * 1e3),
        # Model-vs-measured over ONE dispatch (= `chain` decode steps):
        # the compiler-counted bytes next to the analytic cache_gb_per_s
        # column, and the roofline class (decode should read
        # bandwidth-bound — if it ever flips, the step stopped
        # streaming the cache).
        'perf_model': _perf_model(step, best),
    }
    gq = '' if h_kv == h else f'/kv{h_kv}'
    bc = '' if (b == 1 and chain == 1) else f' B={b} chain={chain}'
    wq = '' if weight_quant is None else f'/w{weight_quant}'
    ttft = ('' if prefill_time is None
            else f" TTFT {record['ttft_ms']:.1f} ms")
    print(f"decode[{impl_resolved}{wq}] t_max={t_max} fill={fill} "
          f"H={h}{gq} d={d}{bc}: "
          f"{record['ms_per_step']:.3f} ms/step "
          f"{record['tokens_per_s']:,.0f} tok/s "
          f"({record['cache_gb_per_s']:.0f} GB/s over the cache, "
          f"{record['step_bytes'] / 2**20:.2f} MiB kv+weights/step)"
          + ttft)
    _append_record(args.file, record)
    return record


def _dispatch_split(registry, n_tokens):
    """Dispatch-floor columns from a scheduler run's registry: the
    host-dispatch vs device-compute split the scheduler's per-tick
    accounting observed (``serve.dispatch_overhead_seconds`` /
    ``serve.device_seconds`` histograms — the same numbers /metrics
    exports and ``obs critpath`` folds from serve.dispatch events).
    Empty dict when the scheduler recorded no decode ticks."""
    h_over = registry.peek('histogram',
                           'serve.dispatch_overhead_seconds')
    h_dev = registry.peek('histogram', 'serve.device_seconds')
    if h_over is None or not h_over.total_count:
        return {}
    over_s = h_over.total_sum
    dev_s = h_dev.total_sum if h_dev is not None else 0.0
    tick_s = over_s + dev_s
    return {
        'dispatch_ticks': h_over.total_count,
        'dispatch_overhead_s': over_s,
        'dispatch_device_s': dev_s,
        'dispatch_overhead_pct': (100.0 * over_s / tick_s
                                  if tick_s > 0 else None),
        'dispatch_overhead_ms_per_token': (over_s / n_tokens * 1e3
                                           if n_tokens else None),
        'dispatch_overhead_p99_ms': h_over.percentile(99) * 1e3,
    }


def run_decode_serve(args):
    """``--mode decode-serve``: what the continuous-batching scheduler
    COSTS over the bare kernels. Two measurements on the same
    :class:`~distributed_dot_product_tpu.serve.engine.KernelEngine`
    shape: (a) a bare lockstep decode loop (all slots always active, no
    admission/health/accounting — the ceiling) and (b) the scheduler
    draining a request burst end to end (admission, chunked prefill,
    per-slot retirement, metrics, watchdog). The gap is the serving
    layer's host-side overhead at this batch size; at real cache sizes
    the compiled step dominates and the gap vanishes into it."""
    import time as _time

    import numpy as np

    from distributed_dot_product_tpu.serve import (
        KernelEngine, Scheduler, ServeConfig,
    )
    from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

    slots_slab = args.batch if args.batch > 1 else 4
    t_max = args.seq_len or 256
    h, d = args.heads, args.head_dim
    max_new = 16
    prompt_len = min(8, t_max - max_new - 1)
    steps_per_seq = prompt_len + max_new
    paged = args.cache_mode == 'paged'
    # Fixed-memory framing: the slab row's KV budget is slots × t_max
    # rows; the paged twin holds the SAME bytes as a page pool and
    # raises the slot count toward 4× — capped by what the pool can
    # hold at this run's per-sequence fill, so the recorded
    # max_concurrent is an honest same-budget number.
    budget_rows = slots_slab * t_max
    kv_shards = args.kv_shards or 1
    if kv_shards > 1 and not paged:
        raise SystemExit('--kv-shards needs --cache-mode paged (the '
                         'sharded unit is the page pool)')
    if paged:
        page_size = args.page_size
        if t_max % page_size:
            raise SystemExit(f'--page-size {page_size} must divide '
                             f'the cache length {t_max}')
        # Under --kv-shards the slab-budget pool is PER SHARD (the
        # fixed-per-shard-pool framing): replica capacity is
        # kv_shards x the slab budget, and the row records
        # capacity_tokens so shard-count sweeps trace the line.
        pages = budget_rows // page_size
        pages_per_seq = -(-steps_per_seq // page_size)
        if kv_shards > 1:
            # Contiguous ordinal ownership concentrates every stream's
            # EARLY pages on the low shards — short sequences gain no
            # concurrency from extra shards (the feature buys context
            # length, not batch). Size slots by the tightest shard.
            pps_total = t_max // page_size
            ops = -(-pps_total // kv_shards)
            by_shard = [0] * kv_shards
            for o in range(pages_per_seq):
                by_shard[min(o // ops, kv_shards - 1)] += 1
            per_shard_cap = min(pages // c for c in by_shard if c)
            slots = max(1, min(4 * slots_slab, per_shard_cap))
        else:
            slots = max(1, min(4 * slots_slab, pages // pages_per_seq))
    else:
        page_size = pages = None
        slots = slots_slab
    # Whole rounds of `slots` concurrent sequences: both measurements
    # then serve the same token volume, and the bare loop's per-round
    # resets keep every sequence inside t_max (an unreset loop would
    # cross the traced-overflow guard and silently decode against a
    # frozen cache).
    n_rounds = -(-(args.serve_requests or 4 * slots) // slots)
    n_requests = n_rounds * slots
    # f32 engine dtype, K + V buffers.
    kv_budget_bytes = budget_rows * h * d * 4 * 2

    def make_engine():
        extra = (dict(cache_mode='paged', pages=pages,
                      page_size=page_size, kv_shards=kv_shards)
                 if paged else {})
        return KernelEngine(slots=slots, t_max=t_max, vocab=256, heads=h,
                            head_dim=d, prefill_chunk=8, seed=0,
                            decode_impl=(None if args.decode_impl == 'auto'
                                         else args.decode_impl),
                            weight_quant=args.weight_quant, **extra)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    # (a) bare kernel loop: every slot decodes every step, nothing else
    # but the per-round slot resets a real serving loop would also do.
    eng = make_engine()
    tokens = np.zeros(slots, np.int32)
    active = np.ones(slots, bool)

    # step() auto-prepares pages (vectorized fast-path mask, allocator
    # only on page crossings) — the same per-token cost the scheduler
    # path pays, so the bare row must not add an explicit per-step
    # prepare_step() pass only the paged twin would be charged for.
    eng.step(tokens, active)                      # compile + warm
    for i in range(slots):
        eng.reset(i)                              # warm append undone
    t0 = _time.perf_counter()
    for _ in range(n_rounds):
        for _ in range(steps_per_seq):
            tokens, _ = eng.step(tokens, active)
        for i in range(slots):
            eng.reset(i)
    bare_s = _time.perf_counter() - t0
    n_steps = n_rounds * steps_per_seq
    bare_tps = slots * n_steps / bare_s

    # Cost/roofline model of the decode program both measurements
    # drive (the engine's one compiled step): AOT-lower the exact
    # jitted callable the engine holds, measured time = the bare
    # loop's per-step wall time.
    with span('benchmark.compile', mode='decode-serve'):
        step_model = _perf_model(
            eng._decode.lower(
                eng.cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(active), jnp.zeros(slots, bool)
            ).compile(),
            bare_s / n_steps)

    # Time-to-first-token through the engine surface: chunked prefill
    # of one prompt + the first decode step, host-clocked on warm
    # compiled programs — what a request admitted to an idle slot waits
    # before its first token.
    chunks = [prompts[0][i:i + eng.prefill_chunk]
              for i in range(0, prompt_len, eng.prefill_chunk)]
    def _reserve_ttft_pages():
        # Page allocation happens here, OUTSIDE the timed window (and
        # not via an assert — `python -O` must not move the pool work
        # into the TTFT measurement).
        if paged and not eng.reserve_rows(0, prompt_len + 1):
            raise RuntimeError(
                'page pool too small for the TTFT probe prompt — the '
                'pool is sized from the slab twin (--batch × --seq-len '
                'rows): raise --batch/--seq-len or lower --page-size')
    _reserve_ttft_pages()
    for c in chunks:                              # warm the prefill jit
        eng.prefill(0, c)
    eng.step(tokens, active)
    eng.reset(0)
    _reserve_ttft_pages()
    t0 = _time.perf_counter()
    for c in chunks:
        eng.prefill(0, c)
    eng.step(tokens, active)
    ttft_s = _time.perf_counter() - t0

    # (b) the scheduler serving the same token volume as a burst.
    eng = make_engine()
    eng.step(tokens, active)                      # same warm start
    for i in range(slots):
        eng.reset(i)                              # slots handed over clean
    cfg = ServeConfig(queue_limit=max(8, n_requests),
                      max_new_tokens=max_new, watchdog=False,
                      degrade_watermark=1.1)      # measure undegraded
    # Peak concurrency and pool fill, observed per tick — the
    # fixed-memory comparison columns of the slab/paged twin rows.
    peak = {'busy': 0, 'pages_used': 0}

    def _on_tick(s):
        peak['busy'] = max(peak['busy'],
                           sum(sl.request is not None
                               for sl in s._slots))
        if paged:
            peak['pages_used'] = max(peak['pages_used'],
                                     eng.pool.used_pages)

    # --metrics-out: route the serve metrics (TTFT/queue-wait/per-token
    # histograms, counters) into the process registry the snapshot
    # serializes; otherwise keep them isolated from other runs.
    registry = (tracing.get_registry()
                if getattr(args, 'metrics_out', None)
                else MetricsRegistry())
    sched = Scheduler(eng, cfg, on_tick=_on_tick, registry=registry)
    # Live device telemetry across the scheduled burst (the serving
    # row, not just a one-shot snapshot at artifact-write time):
    # device.memory.* gauges land in the row's registry — and so in
    # --metrics-out — polled while the burst runs.
    from distributed_dot_product_tpu.obs import DeviceMonitor
    devmon = DeviceMonitor(registry=registry, interval=0.2).start()
    t0 = _time.perf_counter()
    try:
        with span('benchmark.scheduler_burst', mode='decode-serve'):
            for i, p in enumerate(prompts):
                sched.submit(p, request_id=f'b{i}')
            results = sched.run_until_idle()
        sched_s = _time.perf_counter() - t0
    finally:
        devmon.stop()
    sched.close()
    devmon.poll_once()      # final poll: end-of-burst device state
    device_polls = registry.counter('device.memory.polls').value
    n_tok = sum(len(r.tokens) for r in results.values())
    sched_tps = n_tok / sched_s

    from distributed_dot_product_tpu.models.decode import (
        _resolve_decode_impl,
    )
    impl_resolved = _resolve_decode_impl(
        None if eng.decode_impl == 'auto' else eng.decode_impl,
        eng.cache, 1, None, None)
    record = {
        'mode': 'decode-serve', 'slots': slots, 't_max': t_max,
        'heads': h, 'head_dim': d, 'requests': n_requests,
        'prompt_len': prompt_len, 'max_new_tokens': max_new,
        'decode_impl': impl_resolved,
        'cache_mode': args.cache_mode,
        'weight_quant': eng.weight_quant,
        'weight_bytes': eng.weight_bytes,
        'kv_budget_bytes': kv_budget_bytes,
        'max_concurrent': peak['busy'],
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'bare_tokens_per_s': bare_tps,
        'sched_tokens_per_s': sched_tps,
        'sched_overhead_pct': 100.0 * (bare_tps - sched_tps)
                              / bare_tps,
        'ttft_ms': ttft_s * 1e3,
        'completed': sum(r.status == 'completed'
                         for r in results.values()),
        'perf_model': step_model,
        'device_polls': device_polls,
        'devices_reporting': registry.gauge(
            'device.memory.devices_reporting').value,
    }
    record.update(_dispatch_split(registry, n_tok))
    if paged:
        record.update({
            'page_size': page_size, 'pages': pages,
            'kv_shards': kv_shards,
            'capacity_tokens': eng.capacity_tokens,
            'pages_used_peak': peak['pages_used'],
            'page_utilization_peak': peak['pages_used']
                                     / (kv_shards * pages),
        })
    paged_note = ('' if not paged else
                  f" pages={peak['pages_used']}/{kv_shards * pages} "
                  f"({100.0 * record['page_utilization_peak']:.0f}% "
                  f"peak"
                  + (f', kv_shards={kv_shards}' if kv_shards > 1
                     else '') + ')')
    disp_note = ''
    if record.get('dispatch_overhead_ms_per_token') is not None:
        disp_note = (f", dispatch overhead "
                     f"{record['dispatch_overhead_ms_per_token']:.3f} "
                     f"ms/tok "
                     f"({record['dispatch_overhead_pct']:.0f}% of tick)")
    print(f"decode-serve[{impl_resolved}/{args.cache_mode}] "
          f"slots={slots} t_max={t_max} "
          f"req={n_requests}: scheduler {sched_tps:,.0f} tok/s vs bare "
          f"{bare_tps:,.0f} tok/s "
          f"({record['sched_overhead_pct']:.1f}% overhead, "
          f"TTFT {record['ttft_ms']:.1f} ms, "
          f"peak {peak['busy']} concurrent at "
          f"{kv_budget_bytes / 2**20:.1f} MiB KV{paged_note}"
          f"{disp_note})")
    _append_record(args.file, record)
    return record


def run_decode_kv_sharded(args):
    """``--mode decode --kv-shards N``: the cluster-scale long-context
    row. One stream decodes against a paged pool sharded across the
    mesh's ``seq`` axis with a FIXED per-shard pool (a quarter of
    ``t_max``'s pages per shard), so ``capacity_tokens`` — the longest
    stream this engine can hold — is the linear-scaling acceptance
    column: ~N/4 × ``t_max``, clamped at ``t_max``. The timed unit is
    the steady-state sharded decode step (psum/pmax flash merge over
    per-shard page ranges) at a near-capacity fill."""
    import time as _time

    import numpy as np

    from distributed_dot_product_tpu.serve import KernelEngine

    t_max = args.seq_len or 4096
    page_size = args.page_size
    if t_max % page_size:
        raise SystemExit(f'--page-size {page_size} must divide the '
                         f'cache length {t_max}')
    n = args.kv_shards
    # The fixed per-shard pool: one shard covers a quarter of t_max,
    # four shards cover it exactly — the sweep over --kv-shards 1..4
    # traces the capacity line without moving any other knob.
    pages_per_shard = max(1, t_max // page_size // 4)
    eng = KernelEngine(
        slots=1, t_max=t_max, vocab=256, heads=args.heads,
        head_dim=args.head_dim, prefill_chunk=8, seed=0,
        decode_impl=(None if args.decode_impl == 'auto'
                     else args.decode_impl),
        cache_mode='paged', page_size=page_size,
        pages=pages_per_shard, kv_shards=n)
    capacity = eng.capacity_tokens
    pool_tokens = eng.pool.pages * page_size
    # Fill to near capacity, leaving headroom for the timed steps —
    # decode cost is what the row is about, measured against a stream
    # as long as this shard count can hold.
    timed_steps = 48
    fill = max(8, capacity - timed_steps - 8)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, size=fill).astype(np.int32)
    with span('benchmark.prefill', mode='decode-kv-sharded'):
        for i in range(0, fill, eng.prefill_chunk):
            eng.prefill(0, prompt[i:i + eng.prefill_chunk])
    tokens = np.asarray([int(prompt[-1])], np.int32)
    active = np.ones(1, bool)
    with span('benchmark.compile', mode='decode-kv-sharded'):
        tokens, _ = eng.step(tokens, active)      # compile + warm
    with span('benchmark.measure', mode='decode-kv-sharded'):
        t0 = _time.perf_counter()
        for _ in range(timed_steps):
            tokens, _ = eng.step(tokens, active)
        np.asarray(tokens)                        # flush the last step
        elapsed = _time.perf_counter() - t0
    ms_per_token = elapsed / timed_steps * 1e3
    record = {
        'mode': 'decode', 'kv_shards': n, 't_max': t_max,
        'heads': args.heads, 'head_dim': args.head_dim,
        'page_size': page_size, 'pages_per_shard': pages_per_shard,
        'capacity_tokens': capacity, 'pool_tokens': pool_tokens,
        'fill': fill, 'decode_impl': eng.decode_impl,
        'ms_per_token': ms_per_token,
        'tokens_per_s': 1e3 / ms_per_token,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
    }
    print(f'decode[kv_shards={n}] t_max={t_max} '
          f'capacity={capacity} tokens '
          f'({pages_per_shard} pages/shard x {page_size} rows x {n}): '
          f'{ms_per_token:.3f} ms/token at fill={fill}')
    _append_record(args.file, record)
    return record


def run_serve_load_topology(args):
    """``--mode serve-load --topology 1x2``: the disaggregated-serving
    row. The SAME seeded trace (serialized to ``trace.json`` and read
    back — both runs consume the byte-identical file) drives (a) the
    router over a P-prefill-pool / D-decode-replica topology (each
    replica its own paged engine + scheduler + event log; long prompts
    prefill sequence-sharded across the mesh and hand off as pool
    pages) and (b) the single-process twin (ONE replica's engine
    behind one scheduler). Goodput for the topology is computed over
    the MERGED per-member logs — the run asserts every submitted
    request reconstructs exactly once across them — and the twin's
    over its own log; the row records both plus the routing telemetry
    (per-replica placements, prefix hits, handoffs)."""
    import tempfile

    from distributed_dot_product_tpu import obs
    from distributed_dot_product_tpu.obs import slo as obs_slo
    from distributed_dot_product_tpu.serve import (
        KernelEngine, LoadGenConfig, RouterConfig, Scheduler,
        ServeConfig, TopologyConfig, VirtualClock, build_serving,
        default_tenants, generate_trace, load_trace, parse_topology,
        run_trace, save_trace,
    )
    from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

    prefill_pools, decode_replicas = parse_topology(args.topology)
    slots = args.batch if args.batch > 1 else 4
    t_max = args.seq_len or 96
    if t_max % args.page_size:
        raise SystemExit(f'--page-size {args.page_size} must divide '
                         f'the cache length {t_max}')
    decode_impl = (None if args.decode_impl == 'auto'
                   else args.decode_impl)
    log_dir = args.event_log or tempfile.mkdtemp(
        prefix='ddp_serve_topo_')
    os.makedirs(log_dir, exist_ok=True)
    # Fresh logs per run: EventLog APPENDS (resuming seq), and a stale
    # previous run would double every merged timeline. Decode-member
    # logs sweep by GLOB: autoscaling (--control) names replicas with
    # a never-reused sequence, so a scale-down/up cycle can leave
    # rN.jsonl files past any configured ceiling.
    import glob
    for name in ['router'] + (['prefill'] if prefill_pools else []) \
            + ['twin']:
        obs.remove_log(os.path.join(log_dir, f'{name}.jsonl'))
    for stale in glob.glob(os.path.join(log_dir, 'r[0-9]*.jsonl')):
        obs.remove_log(stale)
    cfg = LoadGenConfig(
        seed=args.load_seed, rate=args.load_rate,
        requests=args.load_requests, arrival=args.arrival,
        ramp_factor=args.ramp_factor,
        tenants=default_tenants(args.load_tenants), vocab=64,
        tick_seconds=args.load_tick)
    trace_path = os.path.join(log_dir, 'trace.json')
    save_trace(trace_path, generate_trace(cfg))
    serve_cfg = ServeConfig(
        queue_limit=args.queue_limit,
        max_new_tokens=max(t.new_hi for t in cfg.tenants),
        watchdog=False, spec=args.spec, spec_k=args.spec_k)
    # The twin must run the STATIC config: the controller actuates
    # knobs by mutating the schedulers' (shared) ServeConfig, so a
    # controlled run would otherwise leak its final tightened
    # watermark into the twin built afterwards.
    twin_cfg = dataclasses.replace(serve_cfg)
    topo = TopologyConfig(
        prefill_pools=prefill_pools, decode_replicas=decode_replicas,
        slots=slots, t_max=t_max, page_size=args.page_size, vocab=64,
        heads=args.heads, head_dim=args.head_dim, seed=0,
        decode_impl=decode_impl)
    router_cfg = RouterConfig(prefill_threshold=args.prefill_threshold)
    chaos_any = (args.chaos or args.chaos_corrupt
                 or args.chaos_prefill_crash is not None)
    chaos = chaos_plan = flight_rec = flight_prev = None
    corrupt_page = corrupt_tick = None
    if chaos_any:
        from distributed_dot_product_tpu.obs import flight as obs_flight
        from distributed_dot_product_tpu.serve import ChaosSchedule
        from distributed_dot_product_tpu.utils.faults import (
            ChaosInjector, ChaosPlan,
        )
        # Fast probe cadence on the virtual clock: the loss must be
        # declared (and recovery land) inside the trace's own virtual
        # window, not long after the survivors drained.
        router_cfg = dataclasses.replace(
            router_cfg, probe_interval=0.01, probe_backoff_max=0.02)
        plan_kw = {}
        if args.chaos:
            if decode_replicas < 2:
                raise SystemExit(f'--chaos kills one decode replica '
                                 f'mid-trace: the topology needs >= 2 '
                                 f'for a survivor, got {args.topology}')
            plan_kw['replica_crash'] = (args.chaos_victim,
                                        args.chaos_tick)
        if args.chaos_corrupt:
            try:
                page_s, tick_s = args.chaos_corrupt.split(':')
                corrupt_page, corrupt_tick = int(page_s), int(tick_s)
            except ValueError:
                raise SystemExit(f'--chaos-corrupt wants PAGE:TICK, '
                                 f'got {args.chaos_corrupt!r}')
            if decode_replicas < 2:
                raise SystemExit(f'--chaos-corrupt heals the victim '
                                 f'streams on a CLEAN replica: the '
                                 f'topology needs >= 2, got '
                                 f'{args.topology}')
            plan_kw['page_corrupt'] = (args.chaos_victim, corrupt_page,
                                       corrupt_tick)
            # Scrub every tick: detection latency must be one tick,
            # never a token (transfer/attach sites verify regardless).
            router_cfg = dataclasses.replace(
                router_cfg, integrity_interval=0.0)
        if args.chaos_prefill_crash is not None:
            if not prefill_pools:
                raise SystemExit('--chaos-prefill-crash kills the '
                                 'prefill pool: the topology needs '
                                 'P=1 (e.g. 1x2), got '
                                 f'{args.topology}')
            plan_kw['prefill_crash'] = args.chaos_prefill_crash
        chaos_plan = ChaosPlan(**plan_kw)
        chaos = ChaosInjector(chaos_plan)
        # The black box armed for the whole run: the router's
        # replica_lost / kv_corrupt / prefill_lost triggers auto-dump
        # a bundle the moment the fault is declared.
        flight_rec = obs_flight.FlightRecorder(
            os.path.join(log_dir, 'flight'))
        flight_prev = obs_flight.install(flight_rec)
    clock = VirtualClock()
    router = build_serving(
        topo, serve_config=serve_cfg, router_config=router_cfg,
        clock=clock, log_dir=log_dir, chaos=chaos)
    controller = None
    if args.control:
        from distributed_dot_product_tpu.serve import (
            ControlConfig, Controller,
        )
        controller = Controller(
            router=router,
            config=ControlConfig(
                interval=0.01, scale_up_after=1, scale_down_after=20,
                max_replicas=args.control_max_replicas),
            clock=clock, event_log=router.event_log)
    on_tick = controller.tick if controller else None
    chaos_sched = None
    if chaos is not None:
        on_tick = chaos_sched = ChaosSchedule(chaos, router,
                                              on_tick=on_tick)
    try:
        with span('benchmark.serve_load_topology', seed=args.load_seed,
                  topology=args.topology):
            res = run_trace(router, load_trace(trace_path), clock,
                            tick_seconds=cfg.tick_seconds,
                            on_tick=on_tick)
    finally:
        # Member logs must close (flushing their tails) even when the
        # run under them crashes — those logs ARE the debugging record.
        router.close()
        if flight_rec is not None:
            # Disarm before the twin runs: the bundle must record the
            # chaos run alone, and the no-recovery twin's loss must
            # not be cooldown-shadowed into silence.
            obs_flight.install(flight_prev)
            flight_rec.stop()
    sources = router.pool.logs()
    spec = obs_slo.SloSpec(ttft=args.slo_ttft,
                           per_token=args.slo_token)
    report = obs_slo.goodput(sources, spec)
    if not res.accounted:
        raise SystemExit('serve-load: a submitted request has no '
                         'terminal record across the topology — '
                         'router accounting bug, not a measurable row')
    if report.requests != len(res.submitted):
        raise SystemExit(
            f'serve-load: {report.requests} requests classified from '
            f'the merged logs vs {len(res.submitted)} submitted — a '
            f'request reconstructed zero or several times')
    bad = [rid for rid, tl in obs.reconstruct(sources).items()
           if not tl.complete]
    if bad:
        raise SystemExit(
            f'serve-load: {len(bad)} request lifecycle(s) do not '
            f'reconstruct across the merged replica logs: {bad[:5]}')

    # The single-process twin on the identical serialized trace: ONE
    # replica's engine behind one scheduler, its own virtual clock.
    clock_twin = VirtualClock()
    twin_path = os.path.join(log_dir, 'twin.jsonl')
    twin_log = obs.EventLog(twin_path, clock=clock_twin)
    twin_engine = KernelEngine(
        slots=slots, t_max=t_max, vocab=64, heads=args.heads,
        head_dim=args.head_dim, prefill_chunk=8, seed=0,
        decode_impl=decode_impl, cache_mode='paged',
        page_size=args.page_size)
    twin = Scheduler(twin_engine, twin_cfg, clock=clock_twin,
                     event_log=twin_log, fault_injector=False,
                     registry=MetricsRegistry())
    try:
        res_twin = run_trace(twin, load_trace(trace_path), clock_twin,
                             tick_seconds=cfg.tick_seconds)
    finally:
        twin.close()
        twin_log.close()
    report_twin = obs_slo.goodput(twin_path, spec)

    chaos_extra = {}
    if args.chaos:
        # -- what the recovery actually did (from the router log) -----
        revents = list(obs.read_events(dict(sources)['router']))
        losses = [r for r in revents if r.get('event') == 'replica.lost']
        recovered = [r['request_id'] for r in revents
                     if r.get('event') == 'request.recovered'
                     and r.get('requeued')]
        lost_rejects = [r['request_id'] for r in revents
                        if r.get('event') == 'request.recovered'
                        and not r.get('requeued')]
        probe_events = sum(1 for r in revents
                           if r.get('event') == 'replica.probe')
        if not losses:
            raise SystemExit(
                f'chaos: killing {args.chaos_victim} at tick '
                f'{args.chaos_tick} never became a replica.lost '
                f'declaration — the probe path is broken')
        if not recovered:
            raise SystemExit(
                f'chaos: replica {args.chaos_victim} died with no '
                f'stream to recover — move --chaos-tick into the busy '
                f'part of the trace (died at tick {args.chaos_tick} '
                f'of {res.ticks})')
        if not flight_rec.dumps:
            raise SystemExit('chaos: the replica loss produced no '
                             'flight bundle (trigger replica_lost)')
        # -- bit-identity: a recovered stream IS the crash-free stream.
        # Degradation caps are load policy, not determinism — compare
        # the streams both runs completed uncapped.
        compared, mismatched = 0, []
        for rid in recovered:
            a, b = res.results.get(rid), res_twin.results.get(rid)
            if (a is not None and b is not None
                    and a.status == b.status == 'completed'
                    and not a.degraded and not b.degraded):
                compared += 1
                if list(a.tokens) != list(b.tokens):
                    mismatched.append(rid)
        if mismatched:
            raise SystemExit(
                f'chaos: {len(mismatched)} recovered stream(s) '
                f'diverged from the crash-free twin: '
                f'{mismatched[:5]} — replay-prefill recovery broke '
                f'the determinism contract')
        # -- the no-recovery twin: SAME topology, SAME trace, SAME
        # crash, max_recoveries=0 — every in-flight stream on the
        # victim terminates as a typed REPLICA_LOST reject. What
        # recovery is worth is the goodput gap between these two runs.
        norec_dir = os.path.join(log_dir, 'norec')
        os.makedirs(norec_dir, exist_ok=True)
        for name in ['router'] + (['prefill'] if prefill_pools else []):
            obs.remove_log(os.path.join(norec_dir, f'{name}.jsonl'))
        for stale in glob.glob(os.path.join(norec_dir,
                                            'r[0-9]*.jsonl')):
            obs.remove_log(stale)
        norec_chaos = ChaosInjector(chaos_plan)
        clock_norec = VirtualClock()
        router_norec = build_serving(
            topo, serve_config=dataclasses.replace(twin_cfg),
            router_config=dataclasses.replace(router_cfg,
                                              max_recoveries=0),
            clock=clock_norec, log_dir=norec_dir, chaos=norec_chaos)
        try:
            res_norec = run_trace(
                router_norec, load_trace(trace_path), clock_norec,
                tick_seconds=cfg.tick_seconds,
                on_tick=ChaosSchedule(norec_chaos, router_norec))
        finally:
            router_norec.close()
        report_norec = obs_slo.goodput(router_norec.pool.logs(), spec)
        if not res_norec.accounted:
            raise SystemExit('chaos: the no-recovery twin dropped a '
                             'request without a typed terminal')
        norec_lost = sorted(
            rid for rid, rr in res_norec.results.items()
            if rr.status == 'rejected'
            and getattr(rr.reason, 'value', rr.reason)
            == 'replica_lost')
        if not norec_lost:
            raise SystemExit('chaos: the no-recovery twin lost the '
                             'same replica yet rejected nothing '
                             'replica_lost — the typed terminal path '
                             'is broken')
        if report.goodput_pct < report_norec.goodput_pct:
            raise SystemExit(
                f'chaos: goodput WITH recovery '
                f'({report.goodput_pct:.1f}%) fell below the '
                f'no-recovery twin ({report_norec.goodput_pct:.1f}%) '
                f'— recovery made things worse')
        chaos_extra = {
            'chaos': {'victim': args.chaos_victim,
                      'tick': args.chaos_tick},
            'replica_lost': [r.get('target') for r in losses],
            'recovered': sorted(recovered),
            'recovered_compared': compared,
            'recovered_bitident': compared > 0 and not mismatched,
            'replica_lost_rejects': sorted(lost_rejects),
            'probe_events': probe_events,
            'flight_bundle': flight_rec.dumps[-1]['path'],
            'norec_goodput_pct': report_norec.goodput_pct,
            'norec_counts': report_norec.counts,
            'norec_replica_lost_rejects': norec_lost,
        }

    corrupt_extra = {}
    if args.chaos_corrupt:
        # -- what the integrity layer actually did (router log) --------
        revents = list(obs.read_events(dict(sources)['router']))
        corrupt_events = [r for r in revents
                          if r.get('event') == 'kv.corrupt']
        injected = [r for r in revents
                    if r.get('event') == 'fault.inject'
                    and r.get('kind') == 'page_corrupt']
        healed = [r['request_id'] for r in revents
                  if r.get('event') == 'request.recovered'
                  and r.get('reason') == 'kv_corrupt'
                  and r.get('requeued')]
        corrupt_rejects = [r['request_id'] for r in revents
                           if r.get('event') == 'request.recovered'
                           and r.get('reason') == 'kv_corrupt'
                           and not r.get('requeued')]
        if not chaos_sched.corrupted:
            raise SystemExit(
                f'chaos-corrupt: the bit flip never landed (no '
                f'tracked page on {args.chaos_victim} from tick '
                f'{corrupt_tick} of {res.ticks}) — move the tick into '
                f'the busy part of the trace or lower '
                f'--prefill-threshold')
        if not corrupt_events:
            raise SystemExit(
                f'chaos-corrupt: {len(chaos_sched.corrupted)} flip(s) '
                f'landed but NO kv.corrupt verdict was declared — the '
                f'checksum verification path is broken')
        if not flight_rec.dumps:
            raise SystemExit('chaos-corrupt: the corruption produced '
                             'no flight bundle (trigger kv_corrupt)')
        # -- zero silent wrong tokens: greedy streams are prompt-pure,
        # so EVERY delivered token must match the crash-free twin's
        # stream PREFIX — whatever either run's terminal was (an
        # evicted/expired stream's delivered tokens are still
        # delivered). A single divergence means a poisoned page
        # decoded into a delivered token.
        compared, mismatched = 0, []
        for rid, a in res.results.items():
            b = res_twin.results.get(rid)
            if b is None:
                continue
            n = min(len(a.tokens), len(b.tokens))
            if n:
                compared += 1
                if list(a.tokens)[:n] != list(b.tokens)[:n]:
                    mismatched.append(rid)
        if mismatched:
            raise SystemExit(
                f'chaos-corrupt: {len(mismatched)} completed '
                f'stream(s) diverged from the crash-free twin: '
                f'{mismatched[:5]} — a corrupted page leaked into a '
                f'delivered token')
        # Verify-time cost, summed across every engine that digested
        # (the row's price-of-integrity column).
        verify_seconds = sum(r.engine.verify_seconds
                             for r in router.pool.replicas)
        if router.pool.prefill is not None:
            verify_seconds += router.pool.prefill.engine.verify_seconds
        # -- the no-integrity twin: SAME topology, SAME trace, SAME
        # flip, kv_checksums=False — whatever completes WRONG there is
        # exactly what the checksum layer is worth.
        nointeg_dir = os.path.join(log_dir, 'nointeg')
        os.makedirs(nointeg_dir, exist_ok=True)
        for name in ['router'] + (['prefill'] if prefill_pools else []):
            obs.remove_log(os.path.join(nointeg_dir, f'{name}.jsonl'))
        for stale in glob.glob(os.path.join(nointeg_dir,
                                            'r[0-9]*.jsonl')):
            obs.remove_log(stale)
        nointeg_chaos = ChaosInjector(chaos_plan)
        clock_ni = VirtualClock()
        router_ni = build_serving(
            dataclasses.replace(topo, kv_checksums=False),
            serve_config=dataclasses.replace(twin_cfg),
            router_config=dataclasses.replace(
                router_cfg, integrity_interval=None),
            clock=clock_ni, log_dir=nointeg_dir, chaos=nointeg_chaos)
        nointeg_sched = ChaosSchedule(nointeg_chaos, router_ni)
        try:
            res_ni = run_trace(router_ni, load_trace(trace_path),
                               clock_ni, tick_seconds=cfg.tick_seconds,
                               on_tick=nointeg_sched)
        finally:
            router_ni.close()
        report_ni = obs_slo.goodput(router_ni.pool.logs(), spec)
        if not nointeg_sched.corrupted:
            raise SystemExit('chaos-corrupt: the flip landed in the '
                             'integrity run but not in the '
                             'no-integrity twin — the comparison is '
                             'meaningless')
        # Silently wrong = delivered tokens diverging from the twin
        # stream's prefix (same prefix-pure comparison as above: the
        # terminal class does not launder a poisoned token).
        wrong = []
        for rid, a in res_ni.results.items():
            b = res_twin.results.get(rid)
            if b is None:
                continue
            n = min(len(a.tokens), len(b.tokens))
            if n and list(a.tokens)[:n] != list(b.tokens)[:n]:
                wrong.append(rid)
        wrong.sort()
        corrupt_extra = {
            'chaos_corrupt': {'victim': args.chaos_victim,
                              'page': corrupt_page,
                              'tick': corrupt_tick},
            'corruptions_injected': len(chaos_sched.corrupted),
            'corruptions_detected': len(corrupt_events),
            'corrupt_sites': sorted({str(r.get('site'))
                                     for r in corrupt_events}),
            'corrupt_pages': sorted({int(p) for r in corrupt_events
                                     for p in (r.get('pages') or [])}),
            'corrupt_inject_events': len(injected),
            'corrupt_healed': sorted(healed),
            'corrupt_rejects': sorted(corrupt_rejects),
            'corrupt_compared': compared,
            'corrupt_bitident': compared > 0 and not mismatched,
            'verify_seconds': verify_seconds,
            'flight_bundle': flight_rec.dumps[-1]['path'],
            'nointeg_goodput_pct': report_ni.goodput_pct,
            'nointeg_counts': report_ni.counts,
            'nointeg_wrong_streams': wrong,
        }

    prefill_extra = {}
    if args.chaos_prefill_crash is not None:
        revents = list(obs.read_events(dict(sources)['router']))
        plost = [r for r in revents
                 if r.get('event') == 'prefill.lost']
        if not plost:
            raise SystemExit(
                f'chaos-prefill-crash: killing the prefill pool at '
                f'tick {args.chaos_prefill_crash} never became a '
                f'prefill.lost declaration — the probe path is broken')
        if router.pool.prefill is not None:
            raise SystemExit('chaos-prefill-crash: the router still '
                             'holds a live prefill pool after the '
                             'loss was declared')
        prefill_extra = {
            'chaos_prefill_crash': {'tick': args.chaos_prefill_crash},
            'prefill_lost': [r.get('target') for r in plost],
            'prefill_lost_reason': plost[-1].get('reason'),
        }

    counters = router.registry.snapshot()['counters']
    routed = {}
    for key, n in counters.items():
        # Per-(replica, tenant) labeled series sum to per-replica
        # placement counts: 'router.routed{replica=r0,tenant=t1}'.
        if key.startswith('router.routed{'):
            name = key.split('replica=', 1)[1].split(',')[0].rstrip('}')
            routed[name] = routed.get(name, 0) + n
    record = {
        'mode': 'serve-load', 'topology': args.topology,
        'seed': args.load_seed, 'arrival': cfg.arrival,
        'rate_requested': cfg.rate, 'rate_offered': res.offered_rate,
        'requests': report.requests, 'slots': slots, 't_max': t_max,
        'page_size': args.page_size, 'spec': args.spec,
        'decode_impl': args.decode_impl,
        'queue_limit': serve_cfg.queue_limit,
        'tick_seconds': cfg.tick_seconds,
        'prefill_threshold': args.prefill_threshold,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'slo': spec.to_dict(),
        'goodput_pct': report.goodput_pct,
        'counts': report.counts,
        'per_tenant': {t: tb['goodput_pct']
                       for t, tb in sorted(report.per_tenant.items())},
        'twin_goodput_pct': report_twin.goodput_pct,
        'twin_counts': report_twin.counts,
        'twin_ticks': res_twin.ticks,
        'routed': routed,
        'prefix_hits': counters.get('router.prefix_hits', 0),
        'prefix_misses': counters.get('router.prefix_misses', 0),
        'handoffs': counters.get('router.handoffs', 0),
        'handoff_pages': counters.get('router.handoff_pages', 0),
        'virtual_seconds': res.virtual_seconds,
        'wall_seconds': res.wall_seconds,
        'ticks': res.ticks,
        'trace': trace_path,
        'event_logs': dict(sources),
        'control': bool(args.control),
        'control_actions': (list(controller.actions)
                            if controller else []),
        'replicas_final': len(router.pool.replicas),
    }
    # Dispatch-floor split: the topology's replicas run on separate
    # registries, so the merged JSONL serve.dispatch stream is the
    # source of truth here (same numbers `obs critpath` reports).
    from distributed_dot_product_tpu.obs import critpath as _critpath
    disp = _critpath.dispatch_floor(sources)
    if disp['total']['ticks']:
        tot = disp['total']
        record['dispatch_ticks'] = tot['ticks']
        record['dispatch_overhead_s'] = tot['overhead_seconds']
        record['dispatch_overhead_ms_per_token'] = (
            None if tot['overhead_per_token'] is None
            else tot['overhead_per_token'] * 1e3)
        record['dispatch_per_replica'] = {
            name: {'ticks': agg['ticks'],
                   'overhead_s': agg['overhead_seconds'],
                   'overhead_share': agg['overhead_share']}
            for name, agg in sorted(disp['per_replica'].items())}
    record.update(chaos_extra)
    record.update(corrupt_extra)
    record.update(prefill_extra)
    if args.chaos_corrupt:
        print(f"chaos-corrupt[{args.chaos_victim} page {corrupt_page}"
              f"@tick {corrupt_tick}]: "
              f"{corrupt_extra['corruptions_injected']} flip(s) "
              f"injected, {corrupt_extra['corruptions_detected']} "
              f"kv.corrupt verdict(s) at "
              f"{corrupt_extra['corrupt_sites']}, "
              f"{len(corrupt_extra['corrupt_healed'])} victim(s) "
              f"healed + {len(corrupt_extra['corrupt_rejects'])} typed "
              f"kv_corrupt terminal(s), "
              f"{corrupt_extra['corrupt_compared']} completed streams "
              f"bit-identical to the twin; verify cost "
              f"{corrupt_extra['verify_seconds'] * 1e3:.1f}ms; goodput "
              f"with integrity {report.goodput_pct:.1f}% vs "
              f"no-integrity twin "
              f"{corrupt_extra['nointeg_goodput_pct']:.1f}% "
              f"({len(corrupt_extra['nointeg_wrong_streams'])} "
              f"SILENTLY WRONG stream(s) there); flight bundle "
              f"{corrupt_extra['flight_bundle']}")
    if args.chaos_prefill_crash is not None:
        print(f"chaos-prefill[tick {args.chaos_prefill_crash}]: "
              f"{prefill_extra['prefill_lost']} declared lost "
              f"({prefill_extra['prefill_lost_reason']}); every later "
              f"long prompt served by flat prefill "
              f"({record.get('handoffs', 0)} handoffs before the "
              f"loss); goodput {report.goodput_pct:.1f}%")
    if args.chaos:
        print(f"chaos[{args.chaos_victim}@tick {args.chaos_tick}]: "
              f"{len(chaos_extra['recovered'])} stream(s) recovered "
              f"({chaos_extra['recovered_compared']} bit-identical to "
              f"the crash-free twin), "
              f"{len(chaos_extra['replica_lost_rejects'])} typed "
              f"replica_lost terminal(s); goodput with recovery "
              f"{report.goodput_pct:.1f}% vs no-recovery twin "
              f"{chaos_extra['norec_goodput_pct']:.1f}%; "
              f"flight bundle {chaos_extra['flight_bundle']}")
    print(f"serve-load[topology {args.topology}"
          f"{'+control' if args.control else ''}] "
          f"seed={args.load_seed} "
          f"{cfg.arrival}@{cfg.rate:.0f}/s x{report.requests}: "
          f"goodput {report.goodput_pct:.1f}% vs single-process twin "
          f"{report_twin.goodput_pct:.1f}% "
          f"(routed {routed}, {record['handoffs']} handoffs, "
          f"{record['prefix_hits']} prefix hits"
          + (f", {len(record['control_actions'])} control actions, "
             f"{record['replicas_final']} replicas final"
             if args.control else '') + ')')
    print(obs_slo.render_report(report))
    print(f'event logs: {log_dir}')
    _append_record(args.file, record)
    return record


def run_serve_load(args):
    """``--mode serve-load``: goodput under SLO for a seeded open-loop
    trace (ROADMAP item 5's measurement half). The loadgen drives the
    scheduler in VIRTUAL time (Poisson or bursty arrivals, heavy-tailed
    per-tenant length mixes), the run's JSONL event log is written, and
    the goodput report is computed FROM THE LOG ALONE (obs/slo.py) —
    the row a scheduling-policy change will be graded on, per tenant.
    The flag defaults are the CI smoke config: scripts/ci.sh runs this
    bare and gates the log against SLO_BASELINE.json. With
    ``--topology PxD`` the run goes through the disaggregated router
    instead (:func:`run_serve_load_topology`)."""
    import tempfile

    from distributed_dot_product_tpu import obs
    from distributed_dot_product_tpu.obs import slo as obs_slo
    from distributed_dot_product_tpu.serve import (
        KernelEngine, LoadGenConfig, ServeConfig, VirtualClock,
        default_tenants, run_load,
    )
    from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

    if args.topology:
        return run_serve_load_topology(args)

    slots = args.batch if args.batch > 1 else 4
    t_max = args.seq_len or 96
    paged = args.cache_mode == 'paged'
    extra = {}
    if paged:
        if t_max % args.page_size:
            raise SystemExit(f'--page-size {args.page_size} must '
                             f'divide the cache length {t_max}')
        extra = dict(cache_mode='paged', page_size=args.page_size,
                     pages=slots * (t_max // args.page_size))
    engine = KernelEngine(
        slots=slots, t_max=t_max, vocab=64, heads=args.heads,
        head_dim=args.head_dim, prefill_chunk=8, seed=0,
        decode_impl=(None if args.decode_impl == 'auto'
                     else args.decode_impl), **extra)
    cfg = LoadGenConfig(
        seed=args.load_seed, rate=args.load_rate,
        requests=args.load_requests, arrival=args.arrival,
        ramp_factor=args.ramp_factor,
        tenants=default_tenants(args.load_tenants), vocab=64,
        tick_seconds=args.load_tick)
    serve_cfg = ServeConfig(
        queue_limit=args.queue_limit,
        max_new_tokens=max(t.new_hi for t in cfg.tenants),
        watchdog=False, spec=args.spec, spec_k=args.spec_k)
    control_cfg = None
    if args.control:
        from distributed_dot_product_tpu.serve import ControlConfig
        control_cfg = ControlConfig(interval=0.01)
    log_path = args.event_log or os.path.join(
        tempfile.gettempdir(), f'ddp_serve_load_{os.getpid()}.jsonl')
    # A fresh log per run: EventLog APPENDS (resuming seq), so a stale
    # file from a previous run would double every timeline.
    obs.remove_log(log_path)
    clock = VirtualClock()
    event_log = obs.EventLog(log_path, clock=clock)
    registry = (tracing.get_registry()
                if getattr(args, 'metrics_out', None)
                else MetricsRegistry())
    # Device telemetry across the load run (wall time — the monitor
    # polls real devices however fast the virtual clock spins); the
    # gauges ride the same registry --metrics-out snapshots.
    from distributed_dot_product_tpu.obs import DeviceMonitor
    devmon = DeviceMonitor(registry=registry, interval=0.2).start()
    try:
        with span('benchmark.serve_load', seed=args.load_seed):
            res = run_load(cfg, engine=engine, serve_config=serve_cfg,
                           registry=registry, event_log=event_log,
                           clock=clock, control=control_cfg)
    finally:
        devmon.stop()
    devmon.poll_once()      # end-of-run device state
    event_log.close()

    spec = obs_slo.SloSpec(ttft=args.slo_ttft,
                           per_token=args.slo_token)
    # Read + decode the log ONCE; goodput and the churn reconstruction
    # below both accept the decoded records.
    records = obs.read_events(log_path)
    report = obs_slo.goodput(records, spec)
    if not res.accounted:
        raise SystemExit('serve-load: a submitted request has no '
                         'terminal record — scheduler accounting bug, '
                         'not a measurable row')
    if report.requests != len(res.submitted):
        raise SystemExit(
            f'serve-load: {report.requests} requests classified from '
            f'the log vs {len(res.submitted)} submitted — the event '
            f'log is not a complete record')
    # Per-tenant churn counters the policy follow-up will be graded
    # on, reconstructed from the same log.
    preempts, requeues = {}, {}
    for tl in obs.reconstruct(records).values():
        tenant = tl.tenant or 'default'
        preempts[tenant] = preempts.get(tenant, 0) + tl.preempts
        requeues[tenant] = requeues.get(tenant, 0) + max(
            0, tl.admits - 1)
    per_tenant = {
        t: {'requests': tb['requests'],
            'goodput_pct': tb['goodput_pct'],
            'met': tb['counts']['met'],
            'rejected': tb['counts']['rejected'],
            'preempts': preempts.get(t, 0),
            'requeues': requeues.get(t, 0)}
        for t, tb in sorted(report.per_tenant.items())}
    record = {
        'mode': 'serve-load', 'seed': args.load_seed,
        'arrival': cfg.arrival, 'rate_requested': cfg.rate,
        'rate_offered': res.offered_rate,
        'requests': report.requests, 'slots': slots, 't_max': t_max,
        'cache_mode': args.cache_mode, 'spec': args.spec,
        'decode_impl': args.decode_impl,
        'queue_limit': serve_cfg.queue_limit,
        'control': bool(args.control),
        'tick_seconds': cfg.tick_seconds,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'slo': spec.to_dict(),
        'goodput_pct': report.goodput_pct,
        'counts': report.counts,
        'per_tenant': per_tenant,
        'ttft_ms': {k: (None if v is None else v * 1e3)
                    for k, v in report.percentiles['ttft'].items()
                    if k != 'count'},
        'gap_ms': {k: (None if v is None else v * 1e3)
                   for k, v in report.percentiles['gap'].items()
                   if k != 'count'},
        'queue_wait_ms': {k: (None if v is None else v * 1e3)
                          for k, v in
                          report.percentiles['queue_wait'].items()
                          if k != 'count'},
        'virtual_seconds': res.virtual_seconds,
        'wall_seconds': res.wall_seconds,
        'ticks': res.ticks,
        'event_log': log_path,
        'device_polls': registry.counter('device.memory.polls').value,
        'devices_reporting': registry.gauge(
            'device.memory.devices_reporting').value,
    }
    # Dispatch-floor split: host-loop overhead vs device-program time
    # per decode tick, from the scheduler's histograms on this
    # registry (REAL seconds — reporting only, never the timeline).
    tok_c = registry.peek('counter', 'serve.tokens_generated')
    record.update(_dispatch_split(
        registry, tok_c.value if tok_c is not None else 0))
    print(f"serve-load[{args.cache_mode}/"
          f"{args.spec}] seed={args.load_seed} "
          f"{cfg.arrival}@{cfg.rate:.0f}/s x{report.requests}: "
          f"goodput {report.goodput_pct:.1f}% under "
          f"ttft<{args.slo_ttft * 1e3:.0f}ms "
          f"gap<{args.slo_token * 1e3:.0f}ms")
    print(obs_slo.render_report(report))
    print(f'event log: {log_path}')
    _append_record(args.file, record)
    return record


def run_decode_spec(args):
    """``--mode decode --spec {ngram,draft}``: what draft-verify
    decoding BUYS over plain one-token-per-dispatch generation. Two
    scheduler runs over the same engine shape and the same repetitive
    prompts (the regime speculation targets — code, templates,
    quoting): (a) non-spec baseline, (b) the named proposer feeding
    the fused verify-k program. Both runs are timed warm (one
    throwaway burst compiles every program) and the row records
    tokens/s for each plus the amortization telemetry — mean
    accepted/proposed tokens per verify step out of the serve.spec
    histograms. Greedy verification makes speculation EXACT, so the
    run asserts the two bursts' streams are token-for-token identical
    before recording anything: a row from diverging streams would be
    a benchmark of a bug."""
    import time as _time

    import numpy as np

    from distributed_dot_product_tpu.serve import (
        KernelEngine, Scheduler, ServeConfig,
    )
    from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

    slots = args.batch                       # B=1 is the sweep twin
    t_max = args.seq_len or 512
    max_new = 64
    # A cyclic prompt (period 3) — the n-gram proposer's best case and
    # the draft twin's easiest stream; prompt_len rows + the generated
    # tokens must fit the cache.
    prompt_len = min(8, t_max - max_new - 1)
    if prompt_len < 2:
        raise SystemExit(f'--seq-len {t_max} leaves no room for a '
                         f'prompt + {max_new} generated tokens')
    prompt = [(i % 3) + 1 for i in range(prompt_len)]
    n_rounds = -(-(args.serve_requests or 2 * slots) // slots)
    n_requests = n_rounds * slots

    def burst(sched, tag):
        for i in range(n_requests):
            sched.submit(list(prompt), request_id=f'{tag}.{i}')
        # run_until_idle returns EVERY result since scheduler start —
        # keep only this burst's, or the warm burst's tokens would
        # inflate the timed rate.
        return {rid: r for rid, r in sched.run_until_idle().items()
                if rid.startswith(f'{tag}.')}

    def measure(spec):
        # seed=18: a random-init engine whose greedy continuation of
        # the cyclic prompt locks into the cycle under the installed
        # JAX's RNG (most seeds wander) — the repetitive regime this
        # row measures. The baseline twin shares the seed, so the
        # comparison is same-stream.
        eng = KernelEngine(
            slots=slots, t_max=t_max, vocab=256, heads=args.heads,
            head_dim=args.head_dim, prefill_chunk=8, seed=18,
            decode_impl=(None if args.decode_impl == 'auto'
                         else args.decode_impl))
        reg = (tracing.get_registry()
               if spec and getattr(args, 'metrics_out', None)
               else MetricsRegistry())
        sched = Scheduler(eng, ServeConfig(
            queue_limit=max(8, 2 * n_requests), max_new_tokens=max_new,
            watchdog=False, degrade_watermark=1.1,
            spec=spec, spec_k=args.spec_k), registry=reg)
        burst(sched, 'warm')                 # compile + warm every path
        steps0 = reg.snapshot()['counters'].get('serve.decode_steps', 0)
        t0 = _time.perf_counter()
        with span('benchmark.spec_burst', spec=spec or 'off'):
            results = burst(sched, 'r')
        dt = _time.perf_counter() - t0
        steps = (reg.snapshot()['counters']['serve.decode_steps']
                 - steps0)
        sched.close()
        n_tok = sum(len(r.tokens) for r in results.values())
        return results, n_tok / dt, steps, reg, eng

    # 'off', not None: None would consult the DDP_TPU_SPEC env knob
    # and — with it set — silently make the "baseline" speculative
    # too, recording a spec-vs-spec row as if it were the comparison.
    base, base_tps, base_steps, _, _ = measure('off')
    spec, spec_tps, spec_steps, reg, eng = measure(args.spec)
    for rid in base:
        if spec[rid].tokens != base[rid].tokens:
            raise SystemExit(
                f'spec stream diverged from the non-spec stream for '
                f'{rid} — greedy verification must be exact; this is '
                f'a decode bug, not a measurable row')
    acc = reg.histogram('serve.spec.accepted_per_step',
                        buckets=()).summary()
    prop = reg.histogram('serve.spec.proposed_per_step',
                         buckets=()).summary()

    from distributed_dot_product_tpu.models.decode import (
        _resolve_decode_impl,
    )
    impl_resolved = _resolve_decode_impl(
        None if eng.decode_impl == 'auto' else eng.decode_impl,
        eng.cache, 1, None, None)
    n_tok = sum(len(r.tokens) for r in spec.values())
    record = {
        'mode': 'decode', 'spec': args.spec, 'spec_k': args.spec_k,
        'slots': slots, 't_max': t_max, 'heads': args.heads,
        'head_dim': args.head_dim, 'requests': n_requests,
        'prompt_len': prompt_len, 'max_new_tokens': max_new,
        'decode_impl': impl_resolved,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
        'tokens': n_tok,
        'tokens_per_s': spec_tps,
        'baseline_tokens_per_s': base_tps,
        'spec_speedup': spec_tps / base_tps,
        'decode_steps': spec_steps,
        'baseline_decode_steps': base_steps,
        'accepted_per_step': acc['mean'],
        'proposed_per_step': prop['mean'],
        'completed': sum(r.status == 'completed'
                         for r in spec.values()),
    }
    print(f"decode-spec[{args.spec} k={args.spec_k}/{impl_resolved}] "
          f"B={slots} t_max={t_max}: {spec_tps:,.0f} tok/s vs "
          f"{base_tps:,.0f} non-spec ({record['spec_speedup']:.2f}x), "
          f"accepted {acc['mean']:.2f}/step of {prop['mean']:.2f} "
          f"proposed, {spec_steps} vs {base_steps} dispatches "
          f"for {n_tok} tokens")
    _append_record(args.file, record)
    return record


def run(args):
    if args.mode == 'attn':
        return run_attn(args)
    if args.mode == 'train':
        return run_train(args)
    if args.mode == 'decode' and args.spec != 'off':
        return run_decode_spec(args)
    if args.mode == 'decode' and args.kv_shards:
        # Explicit --kv-shards (1 included — the sweep's baseline row)
        # selects the sharded-pool capacity row.
        return run_decode_kv_sharded(args)
    if args.mode == 'decode':
        return run_decode(args)
    if args.mode == 'decode-serve':
        return run_decode_serve(args)
    if args.mode == 'serve-load':
        return run_serve_load(args)
    if args.mode == 'lm':
        return run_lm(args)
    mesh = seq_mesh(args.devices)
    world = mesh.devices.size
    t = FULL_T // args.scale
    t -= t % world  # shard evenly (reference assumes divisibility)
    dtype = jnp.float32 if args.dtype == 'f32' else jnp.bfloat16
    flops = 2.0 * t * t * DIM  # same count for all three ops (BASELINE.md)

    # Largest single-buffer estimate: the (T, T) score-shaped operand/output
    # (nt's output; all/tn's input). Refuse configs that cannot fit one
    # device rather than dying in an opaque device OOM mid-run — e.g. the
    # T=75000 fp32 default is 22.5 GiB against a 16 GiB v5e chip (use
    # --scale 2 or --dtype bf16 there; the reference needed 3 GPUs for the
    # same reason, reference benchmark.py:6-7).
    limit = _device_bytes_limit()
    score_bytes = t * t * jnp.dtype(dtype).itemsize
    if limit and score_bytes > 0.9 * limit:
        raise SystemExit(
            f'workload needs a {score_bytes / 2**30:.1f} GiB (T,T) buffer '
            f'per device but the device limit is {limit / 2**30:.1f} GiB; '
            f'raise --scale or use --dtype bf16')

    left, right = make_inputs(args.mode, t, dtype)
    record = {
        'mode': args.mode, 'scale': args.scale,
        # tn has no chunk/impl knobs (reference functions.py:103); record
        # null rather than attributing knobs that never executed.
        'offset': args.offset if args.mode != 'tn' else None,
        'impl': args.impl if args.mode != 'tn' else None,
        'T': t, 'dim': DIM, 'world': world, 'dtype': args.dtype,
        'platform': jax.devices()[0].platform,
        'device_kind': jax.devices()[0].device_kind,
    }

    if not args.skip_local:
        # Single-device full-size baseline (reference benchmark.py:72-86).
        local = _summed(LOCAL[args.mode])
        best, mean = time_fn(local, left, right, iters=args.iters)
        record.update(local_time=best, local_time_mean=mean,
                      local_gflops=flops / best / 1e9)
        print(f"local 1-device {args.mode}: {best:.4f}s "
              f"({record['local_gflops']:.0f} GFLOP/s)")

    # Distributed: global arrays sharded over the mesh, shard_map kernel.
    gleft, gright = shard_seq(left, mesh), shard_seq(right, mesh)
    kw = {'mesh': mesh}
    if args.mode == 'nt':
        fn = lambda l, r: distributed_matmul_nt_global(  # noqa: E731
            l, r, offset=args.offset, impl=args.impl, **kw)
    elif args.mode == 'all':
        fn = lambda l, r: distributed_matmul_all_global(  # noqa: E731
            l, r, offset=args.offset, impl=args.impl, **kw)
    else:
        fn = lambda l, r: distributed_matmul_tn_global(  # noqa: E731
            l, r, **kw)
    # AOT-compile once (see run_attn): one executable for profile, timing
    # and memory analysis.
    with span('benchmark.compile', mode=args.mode):
        fn = _summed(fn).lower(gleft, gright).compile()

    if args.profile_dir:
        jax.block_until_ready(fn(gleft, gright))  # warm outside trace
        with jax.profiler.trace(args.profile_dir):
            jax.block_until_ready(fn(gleft, gright))

    with span('benchmark.measure', mode=args.mode):
        best, mean = time_fn(fn, gleft, gright, iters=args.iters)
    peak = device_peak_bytes()
    record.update(
        dist_time=best, dist_time_mean=mean,
        dist_gflops_per_chip=flops / world / best / 1e9,
        dist_peak_bytes_per_chip=peak,
        dist_memory_analysis=_memory_analysis(fn),
        perf_model=_perf_model(fn, best),
    )
    print(f"dist {world}-device {args.mode} offset={args.offset} "
          f"impl={args.impl}: {best:.4f}s "
          f"({record['dist_gflops_per_chip']:.0f} GFLOP/s/chip, "
          f"peak {peak / 2**30:.2f} GiB)" if peak else
      f"dist {world}-device {args.mode}: {best:.4f}s "
          f"({record['dist_gflops_per_chip']:.0f} GFLOP/s/chip)")

    _append_record(args.file, record)
    return record


def _write_metrics_out(args, record):
    """One observability artifact per run: the metrics-registry
    snapshot (histograms carry reservoir percentiles + lifetime
    totals), the phase-span summary/tree, and the result record —
    enough to answer "where did this run's wall time go" offline."""
    from distributed_dot_product_tpu.obs.devmon import (
        device_stats_snapshot,
    )
    payload = {
        'mode': args.mode,
        'record': record,
        # Cost/roofline model duplicated at top level so the artifact
        # is self-explaining even when the record nests it deep.
        'perf_model': record.get('perf_model'),
        'metrics': tracing.metrics(),
        'spans': obs_spans.get_collector().summary(),
        'span_tree': obs_spans.get_collector().render().splitlines(),
        # memory_stats() of every visible device at artifact-write time
        # (None per device on backends without stats — e.g. this CPU
        # mesh; real on TPU, where it answers "how full was the chip").
        'devices': device_stats_snapshot(),
    }
    with open(args.metrics_out, 'w') as f:
        json.dump(payload, f, indent=2, default=str)
    print(f'metrics snapshot written to {args.metrics_out}')


def main():
    args = parse_args()
    setup_compile_cache()
    if args.kv_shards and args.kv_shards > 1 \
            and (os.environ.get('JAX_PLATFORMS', '') or 'cpu') \
            .startswith('cpu'):
        # The sharded-KV rows need a seq mesh of kv_shards members; on
        # the CPU backend that width is a config knob that must land
        # BEFORE the backend initializes (parse_args touches no
        # device, so this is early enough). Real accelerators bring
        # their own device count and skip this.
        from distributed_dot_product_tpu._compat import (
            ensure_cpu_devices,
        )
        ensure_cpu_devices(max(8, args.kv_shards), force_cpu=False)
    if args.multihost:
        from distributed_dot_product_tpu.utils import comm
        comm.init(coordinator_address=args.coordinator,
                  num_processes=args.num_processes,
                  process_id=args.process_id)
        comm.synchronize()
    if args.metrics_out:
        # Spans on for the run, mirrored into the process registry so
        # the snapshot carries span.<phase>.seconds histograms too.
        obs_spans.enable(True, registry=tracing.get_registry())
    record = run(args)
    if args.metrics_out:
        _write_metrics_out(args, record)
    return record


if __name__ == '__main__':
    main()
