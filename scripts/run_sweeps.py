# -*- coding: utf-8 -*-
"""
Run the full benchmark corpus on the current backend and write one JSON
result file per configuration under ``benchmark_results/``.

Reproduces the reference's committed evidence
(``/root/reference/benchmark_results/``, 27 files: nt/all offset sweeps,
nt/all/tn scale sweeps) with the same file-naming convention, plus the
TPU-only modes (ring impls, fused attention paths, bf16). Each
configuration is a separate ``benchmark.py`` subprocess so one OOM/compile
failure cannot take down the sweep, and partial progress is preserved.
A chip belongs to one process at a time, so this parent stays OFF JAX
(it imports nothing that touches a backend) and runs its children one
after another: each child takes the chip, measures, and releases it.

    python scripts/run_sweeps.py [--out benchmark_results] [--only nt]

Budget: ~30 configurations; each child pays its own start-up and first
compile (the persistent compile cache of utils/compile_cache.py is
shared between them).
"""

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (file stem, benchmark.py args). bf16 is the TPU-native dtype and the only
# one that fits T=75000 on one 16 GiB chip (the fp32 (T,T) buffer alone is
# 22.5 GiB — the reference needed 3×24 GiB GPUs for the same reason);
# fp32 runs cover the scales that fit.
SWEEPS = [
    # --- nt offset sweep (reference nt_benchmark_{offset}.json) ---
    # Offsets are divisors of T=75000, like every offset the reference's
    # own nt sweep used: a non-divisor pads the scan chunks, and at
    # T=75000 bf16 the resulting extra (T,T)-sized temp copy exceeds the
    # 16 GiB chip ((T,T) alone is 11.25 GiB). 30/750 are the small-offset
    # probes; the kernel itself supports non-divisors (tested at small T).
    *[(f'nt_benchmark_{o}', ['--mode', 'nt', '--offset', str(o),
                             '--dtype', 'bf16'])
      for o in (30, 750, 1000, 6250, 25000)],
    ('nt_benchmark_full', ['--mode', 'nt', '--offset', 'none',
                           '--dtype', 'bf16']),
    # --- nt scale sweep (reference nt_benchmark_size_{scale}.json) ---
    *[(f'nt_benchmark_size_{s}', ['--mode', 'nt', '--offset', '1000',
                                  '--scale', str(s), '--dtype', 'bf16'])
      for s in (1, 2, 4, 8)],
    *[(f'nt_benchmark_f32_size_{s}', ['--mode', 'nt', '--offset', '1000',
                                      '--scale', str(s)])
      for s in (2, 4, 8)],
    ('nt_benchmark_ring', ['--mode', 'nt', '--impl', 'ring',
                           '--dtype', 'bf16']),
    # --- all offset sweep (reference all_benchmark_{offset}.json) ---
    *[(f'all_benchmark_{o}', ['--mode', 'all', '--offset', str(o),
                              '--dtype', 'bf16'])
      for o in (24, 48, 96, 192, 384, 768)],
    ('all_benchmark_full', ['--mode', 'all', '--offset', 'none',
                            '--dtype', 'bf16']),
    # --- all scale sweep ---
    *[(f'all_benchmark_size_{s}', ['--mode', 'all', '--offset', '768',
                                   '--scale', str(s), '--dtype', 'bf16'])
      for s in (1, 2, 4, 8)],
    ('all_benchmark_f32_size_2', ['--mode', 'all', '--offset', '768',
                                  '--scale', '2']),
    ('all_benchmark_ring', ['--mode', 'all', '--impl', 'ring',
                            '--dtype', 'bf16']),
    # --- tn scale sweep (reference tn_benchmark_{scale}.json) ---
    *[(f'tn_benchmark_{s}', ['--mode', 'tn', '--scale', str(s),
                             '--dtype', 'bf16'])
      for s in (1, 2, 4, 8)],
    ('tn_benchmark_f32_2', ['--mode', 'tn', '--scale', '2']),
    # --- attention op: full vs online(ring) vs flash vs flash_bounded ---
    # (no reference analog; T = 75000/scale, H=8, d=64.) 'full'
    # materializes (H, T/N, T) scores, so it only fits at larger scales.
    # 'online' (ring) runs at scale=1 since the flash-backed block fold:
    # the old einsum fold materialized the whole (H, T, T) score block
    # (180 GB at T=75000); the fused fold holds O(block²) and matches
    # flash's rate. Its O((T/N)²) memory story still needs N>1; see
    # RESULTS.md and tests/test_ring_attention.py for CPU-mesh coverage.
    *[(f'attn_benchmark_{impl}', ['--mode', 'attn', '--attn-impl', impl,
                                  '--dtype', 'bf16', '--skip-local'])
      for impl in ('online', 'flash', 'flash_bounded', 'ulysses')],
    *[(f'attn_benchmark_{impl}_size_4',
       ['--mode', 'attn', '--attn-impl', impl, '--scale', '4',
        '--dtype', 'bf16', '--skip-local'])
      for impl in ('full', 'online', 'flash', 'flash_bounded', 'ulysses')],
    # --- flash head-dim sweep: d in {64, 128, 256} x T in {16K, 75K} ---
    # Grounds the "d=64 bounds MFU" analysis in data: per-head arithmetic
    # intensity grows with d, so the rate climbs toward the MXU peak.
    # Grouped-query attention: same compute rate as MHA (the kernel is
    # compute-bound), 4x smaller K/V residency.
    ('attn_benchmark_flash_gqa_kv2',
     ['--mode', 'attn', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--kv-heads', '2', '--skip-local']),
    ('attn_benchmark_flash_gqa_kv2_75k',
     ['--mode', 'attn', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--kv-heads', '2', '--skip-local']),
    # int8-quantized QK^T at the head dim where it wins (MXU-bound).
    ('attn_benchmark_flash_d256_16k_int8',
     ['--mode', 'attn', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--head-dim', '256', '--qk-quant', 'int8',
      '--skip-local']),
    # (d=64, T=75000 is exactly attn_benchmark_flash above — the RESULTS
    # head-dim table reads that record instead of re-measuring it.)
    *[(f'attn_benchmark_flash_d{d}_{tag}',
       ['--mode', 'attn', '--attn-impl', 'flash', '--dtype', 'bf16',
        '--head-dim', str(d), '--skip-local'] + extra)
      for d in (64, 128, 256)
      for tag, extra in (('16k', ['--seq-len', '16384']), ('75k', []))
      if (d, tag) != (64, '75k')],
    # --- full train step (fwd+bwd+adam as one SPMD program) ---
    # 'full'/'online' materialize (H, T, T) scores FORWARD AND BACKWARD —
    # they fit at T=8192 on 16 GiB; flash scales on (T=32768 included as
    # the memory-scaling point).
    *[(f'train_benchmark_{impl}',
       ['--mode', 'train', '--attn-impl', impl, '--dtype', 'bf16',
        '--seq-len', '16384'])
      for impl in ('flash', 'flash_bounded')],
    *[(f'train_benchmark_{impl}_8k',
       ['--mode', 'train', '--attn-impl', impl, '--dtype', 'bf16',
        '--seq-len', '8192'])
      for impl in ('full', 'online', 'flash')],
    ('train_benchmark_flash_32k',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '32768']),
    # --no-mask (attn_mask=None): the long-context configuration — the
    # dense mask is the only O(T^2) input on the flash path.
    ('train_benchmark_flash_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--no-mask']),
    ('train_benchmark_flash_128k_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '131072', '--no-mask']),
    ('train_benchmark_flash_256k_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '262144', '--no-mask', '--iters', '2']),
    ('train_benchmark_flash_512k_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '524288', '--no-mask', '--iters', '2']),
    ('train_benchmark_flash_128k_causal',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '131072', '--no-mask', '--causal', '--iters', '2']),
    # Sliding-window attention: O(T·window) compute — the linear-in-T
    # long-context configuration (window=4096 ≈ a Mistral-style cap).
    *[(f'train_benchmark_flash_{tag}_win4k',
       ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
        '--seq-len', tlen, '--no-mask', '--causal', '--window', '4096',
        '--iters', '2'])
      for tag, tlen in (('128k', '131072'), ('512k', '524288'))],
    # Segment-id (packed-sequence) mask: O(T) kernel inputs, cross-
    # segment block skipping — the compact-mask capability record.
    ('train_benchmark_flash_segments',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--mask-kind', 'segments', '--segments', '8']),
    # --- round-4 module-surface records: GQA projections, RoPE, and the
    # ring path carrying dropout + packed segments (the long-context
    # training combo that used to raise) ---
    ('train_benchmark_flash_gqa_kv2',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--no-mask', '--kv-heads', '2']),
    ('train_benchmark_flash_rope',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '16384', '--no-mask', '--causal', '--use-rope']),
    # --- KV-cache decode latency (inference; module decode surface) ---
    *[(f'decode_benchmark_{tag}{suff}',
       ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', tlen,
        '--heads', '8', '--head-dim', '96'] + extra)
      for tag, tlen in (('16k', '16384'), ('128k', '131072'))
      for suff, extra in (('', []), ('_kv2', ['--kv-heads', '2']))],
    # --- train-step head-dim sweep (dim=768 fixed, so d = 768/heads) ---
    *[(f'train_benchmark_flash_h{h}_{tag}_nomask',
       ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
        '--heads', str(h), '--no-mask', '--seq-len', tlen])
      for h in (12, 6, 3)
      for tag, tlen in (('16k', '16384'), ('75k', '75000'))],
    # --- round-5: chained decode (tokens per dispatch amortize the
    # per-dispatch floor) + batched serving — the GQA-wins records.
    # Pinned to the XLA step now that --decode-impl exists, so these
    # rows keep measuring what round 5 measured (the baseline the
    # kernel rows below are judged against). ---
    *[(f'decode_benchmark_128k{suff}_chain{kv}',
       ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', '131072',
        '--heads', '8', '--head-dim', '96', '--decode-chain', '32',
        '--decode-impl', 'xla']
       + extra + kvx)
      for suff, extra in (('', []), ('_b8', ['--batch', '8']))
      for kv, kvx in (('', []), ('_kv2', ['--kv-heads', '2']))],
    ('decode_benchmark_128k_chain_kv2_int8',
     ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', '131072',
      '--heads', '8', '--head-dim', '96', '--decode-chain', '32',
      '--kv-heads', '2', '--qk-quant', 'int8', '--decode-impl', 'xla']),
    # --- round-6: the fused Pallas decode kernel vs those baselines —
    # same shapes, same chained methodology, only the decode path
    # differs. The B=8 full-head pair is the acceptance benchmark
    # (kernel must land ≥1.5× under the 10.34 ms/step XLA row, near
    # the 4.25+0.9 ms component floor); the int8 pair is the mirror
    # regression (kernel int8 must be ≤ bf16, where XLA's s8 lowering
    # lost). TTFT rows ride every decode record now. ---
    *[(f'decode_benchmark_128k{suff}_chain{kv}_kernel',
       ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', '131072',
        '--heads', '8', '--head-dim', '96', '--decode-chain', '32',
        '--decode-impl', 'kernel']
       + extra + kvx)
      for suff, extra in (('', []), ('_b8', ['--batch', '8']))
      for kv, kvx in (('', []), ('_kv2', ['--kv-heads', '2']))],
    ('decode_benchmark_128k_chain_kv2_int8_kernel',
     ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', '131072',
      '--heads', '8', '--head-dim', '96', '--decode-chain', '32',
      '--kv-heads', '2', '--qk-quant', 'int8',
      '--decode-impl', 'kernel']),
    # --- round-9 (ISSUE 14): end-to-end low precision — int8 WEIGHTS
    # (+ the int8 K mirror) vs the bf16 twin rows above, both decode
    # paths. Acceptance: the wq8 row beats its bf16 twin on kv+weight
    # bytes AND time (rows record weight_bytes/step_bytes next to
    # ms_per_step, so the comparison reads straight off the pairs);
    # every row also records paged_int8_kernel_eligible. ---
    *[(f'decode_benchmark_128k_chain_kv2_wq8_{impl}',
       ['--mode', 'decode', '--dtype', 'bf16', '--seq-len', '131072',
        '--heads', '8', '--head-dim', '96', '--decode-chain', '32',
        '--kv-heads', '2', '--qk-quant', 'int8',
        '--weight-quant', 'int8', '--decode-impl', impl])
      for impl in ('xla', 'kernel')],
    # --- round-6: scheduler-vs-bare on both decode paths ---
    *[(f'decode_serve_{impl}',
       ['--mode', 'decode-serve', '--seq-len', '4096', '--batch', '8',
        '--serve-requests', '32', '--decode-impl', impl])
      for impl in ('xla', 'kernel')],
    # --- round-7: paged-cache twins of the rows above — SAME KV byte
    # budget (8 slots × 4096 rows) as a page pool, 4× the slots; the
    # rows record pool utilization + peak concurrency, so the
    # slots-per-chip win reads straight off slab-vs-paged pairs. ---
    *[(f'decode_serve_paged_{impl}',
       ['--mode', 'decode-serve', '--seq-len', '4096', '--batch', '8',
        '--serve-requests', '64', '--decode-impl', impl,
        '--cache-mode', 'paged', '--page-size', '256'])
      for impl in ('xla', 'kernel')],
    # --- round-9 (ISSUE 14): quantized-WEIGHT serving twins of the
    # slab/paged decode-serve rows — same shapes, engine weights int8
    # (DDP_TPU_WEIGHT_QUANT's programmatic twin); rows record
    # weight_bytes so the served-bytes win reads off the pairs. ---
    *[(f'decode_serve{suffix}_wq8_{impl}',
       ['--mode', 'decode-serve', '--seq-len', '4096', '--batch', '8',
        '--serve-requests', str(req), '--decode-impl', impl,
        '--weight-quant', 'int8'] + extra)
      for impl in ('xla', 'kernel')
      for suffix, req, extra in (
          ('', 32, []),
          ('_paged', 64, ['--cache-mode', 'paged',
                          '--page-size', '256']))],
    # --- ISSUE-18: cluster-scale long context — mesh-sharded paged KV.
    # The capacity sweep: a FIXED per-shard pool (a quarter of t_max's
    # pages) at 1/2/4 shards, so capacity_tokens reads ~N/4 × t_max
    # straight off the rows (the ≥3.5×-at-4-shards acceptance line),
    # plus the ms/token cost of the psum/pmax ring merge, both decode
    # paths. And the decode-serve twin at 4 shards: the sharded pool
    # behind the full scheduler. ---
    *[(f'decode_kv_shards_{n}_{impl}',
       ['--mode', 'decode', '--kv-shards', str(n), '--seq-len', '131072',
        '--heads', '8', '--head-dim', '96', '--page-size', '256',
        '--decode-impl', impl])
      for n in (1, 2, 4)
      for impl in ('xla', 'kernel')],
    ('decode_serve_kv_shards_4',
     ['--mode', 'decode-serve', '--seq-len', '4096', '--batch', '8',
      '--serve-requests', '64', '--decode-impl', 'xla',
      '--cache-mode', 'paged', '--page-size', '256',
      '--kv-shards', '4']),
    # --- round-8: speculative decoding B=1 twins — each row times a
    # non-spec scheduler burst AND the proposer-driven verify-k burst
    # on the same engine/prompts (baseline_tokens_per_s rides the
    # record), so the ISSUE-8 hardware acceptance (>2× tokens/s over
    # the measured non-spec rate on the repetitive stream) reads
    # straight off the spec_speedup column; accepted-tokens/step is
    # the amortization telemetry. The draft row is the self-draft
    # twin (machinery cost ceiling) until a distilled checkpoint
    # lands. ---
    *[(f'decode_spec_{name}_{impl}',
       ['--mode', 'decode', '--spec', name, '--seq-len', '4096',
        '--serve-requests', '4', '--spec-k', '4',
        '--heads', '2', '--head-dim', '8', '--decode-impl', impl])
      for name in ('ngram', 'draft')
      for impl in ('xla', 'kernel')],
    # --- round-5: LM capstone training (embed → scanned+remat stack →
    # tied head → chunked cross-entropy, one SPMD program) ---
    ('lm_32k',
     ['--mode', 'lm', '--dtype', 'bf16', '--seq-len', '32768',
      '--layers', '8', '--remat']),
    ('lm_128k_16l',
     ['--mode', 'lm', '--dtype', 'bf16', '--seq-len', '131072',
      '--layers', '16', '--remat', '--iters', '2']),
    ('lm_256k',
     ['--mode', 'lm', '--dtype', 'bf16', '--seq-len', '262144',
      '--layers', '8', '--remat', '--iters', '2']),
    # --- round-5: the dense-mask cost pairs (masked vs no-mask at three
    # lengths, measured back-to-back — the mask-share analysis data) ---
    ('train_benchmark_flash_32k_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '32768', '--no-mask']),
    ('train_benchmark_flash_65k',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '65536']),
    ('train_benchmark_flash_65k_nomask',
     ['--mode', 'train', '--attn-impl', 'flash', '--dtype', 'bf16',
      '--seq-len', '65536', '--no-mask']),
]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default=os.path.join(REPO, 'benchmark_results'))
    ap.add_argument('--only', default=None,
                    help='substring filter on the file stem')
    ap.add_argument('--iters', type=int, default=5)
    ap.add_argument('--rerun', action='store_true',
                    help='re-measure configs whose result file exists')
    ap.add_argument('--retries', type=int, default=1,
                    help='re-run a failed config this many times (transient '
                         'TPU-runtime failures; backoff doubles from '
                         '--retry-backoff seconds)')
    ap.add_argument('--retry-backoff', type=float, default=10.0)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for stem, bench_args in SWEEPS:
        if args.only and args.only not in stem:
            continue
        path = os.path.join(args.out, f'{stem}.json')
        if os.path.exists(path) and not args.rerun:
            print(f'== {stem}: exists, skipping (--rerun to redo)')
            continue
        # Default iters first so a per-config '--iters' in bench_args wins
        # (argparse keeps the last occurrence).
        cmd = [sys.executable, os.path.join(REPO, 'benchmark.py'),
               '--iters', str(args.iters), *bench_args, '--file', path]
        print(f'== {stem}: {" ".join(bench_args)}', flush=True)
        delay = args.retry_backoff
        for attempt in range(args.retries + 1):
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
            sys.stdout.write(proc.stdout)
            print(f'== {stem}: rc={proc.returncode} '
                  f'({time.time() - t0:.0f}s)', flush=True)
            if proc.returncode == 0:
                break
            # One OOM/compile failure must not take down the sweep; a
            # TRANSIENT failure (a preempted runtime) should not even
            # cost the config — retry with
            # backoff before recording it as failed.
            if attempt < args.retries:
                print(f'== {stem}: retry {attempt + 1}/{args.retries} '
                      f'in {delay:.0f}s', flush=True)
                time.sleep(delay)
                delay *= 2
        if proc.returncode != 0:
            failures.append(stem)
    if failures:
        print('FAILED configs:', ', '.join(failures))
        return 1
    print('all configs done')
    return 0


if __name__ == '__main__':
    sys.exit(main())
