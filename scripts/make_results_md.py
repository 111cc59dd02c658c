# -*- coding: utf-8 -*-
"""
Generate RESULTS.md from the benchmark_results/*.json corpus, side by side
with the reference baseline (BASELINE.md).

    python scripts/make_results_md.py > RESULTS.md
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Reference numbers transcribed from BASELINE.md (means over the committed
# runs of /root/reference/benchmark_results/): Dist GFLOP/s/chip and peak
# GiB/rank on 3x Quadro RTX 6000 fp32 over Horovod/NCCL.
BASE_NT_OFFSET = {1000: (1660, 14.26), 1250: (1695, 14.33), 2500: (1763, 14.69),
                  5000: (1794, 15.41), 6250: (1854, 15.77),
                  12500: (1876, 17.57), 25000: (2287, 21.17)}
BASE_NT_SIZE = {1: (1656, 14.26), 2: (986, 3.57), 4: (317, 0.89), 8: (88, 0.23)}
BASE_ALL_OFFSET = {24: (1300, 7.29), 48: (1954, 7.30), 96: (2553, 7.34),
                   192: (2835, 7.40), 384: (3179, 7.56), 768: (4404, 7.70)}
BASE_ALL_SIZE = {1: (3852, 7.70), 2: (1534, 2.10), 4: (492, 0.62),
                 8: (139, 0.20)}
BASE_TN_SIZE = {1: (3188, 3.20), 2: (1133, 0.75), 4: (304, 0.23),
                8: (79, 0.08)}


def load(stem):
    path = os.path.join(REPO, 'benchmark_results', f'{stem}.json')
    if not os.path.exists(path):
        return None
    with open(path) as f:
        recs = json.load(f)
    return recs[-1] if recs else None


def gib(rec):
    ma = rec.get('dist_memory_analysis') or {}
    total = ma.get('total_bytes')
    return f'{total / 2**30:.2f}' if total else 'n/a'


# bf16 matmul peak of the v5e chip: a measured rate above this is the
# readback-fenced timer's resolution floor, not physics — such rows keep
# their raw cells but are EXCLUDED from ours/ref ratio claims.
PEAK_GFLOPS = 197_000


def row(rec, base=None, pad=True):
    if rec is None:
        return None
    ours = rec['dist_gflops_per_chip']
    cells = [f"{rec['dist_time']:.4f}", f'{ours:,.0f}', gib(rec)]
    if base:
        b_gf, b_mem = base
        ratio = ('(timer floor)' if ours > PEAK_GFLOPS
                 else f'{ours / b_gf:.1f}×')
        cells += [f'{b_gf:,}', f'{b_mem:.2f}', ratio]
    elif pad:
        cells += ['—', '—', '—']
    return cells


def table(title, header, rows):
    print(f'\n### {title}\n')
    print('| ' + ' | '.join(header) + ' |')
    print('|' + '|'.join(['---'] * len(header)) + '|')
    for label, cells in rows:
        if cells is not None:
            print('| ' + ' | '.join([label] + cells) + ' |')


def main():
    dev = None
    for p in glob.glob(os.path.join(REPO, 'benchmark_results', '*.json')):
        with open(p) as f:
            recs = json.load(f)
        if recs:
            dev = recs[-1].get('device_kind')
            break

    print('# RESULTS — measured TPU benchmark corpus')
    print(f"""
All numbers measured on **one {dev or 'TPU'} chip** (the driver exposes a
single chip; multi-chip correctness is exercised on the virtual 8-device
CPU mesh and by `dryrun_multichip`). Method: `benchmark.py` per config via
`scripts/run_sweeps.py`; timings block on device completion
(`utils.tracing.time_fn` host-readback fence — the reference's timings
never synchronized, BASELINE.md); memory is XLA's compiled buffer
assignment (argument+output+temp bytes — exact and reproducible, where
runtime stats are not). Reference baseline: 3× Quadro RTX 6000 (24 GB) fp32 over
Horovod/NCCL, per-chip GFLOP/s from BASELINE.md. Our dtype is bf16 (the
MXU-native choice — fp32 rows included where the (T,T) buffer fits one
16 GiB chip). "ours/ref" compares per-chip throughput.

Caveats: (a) sub-millisecond configs (scale=8 rows) sit at the resolution
limit of the readback-fenced timer — rates above the 197 TF/s bf16 device
peak are timer floor, not physics, and their `ours/ref` cells say so
instead of printing a ratio; (b) the `mem GiB` column is the
compiled footprint of the *timed* program, which reduces the op's output
to a scalar — where XLA can fuse the whole pipeline into that reduction
(nt with a single full gather / ring) the (T,T) product is never
materialized and the footprint drops to the operands, which is a real
property of compiled XLA programs, not an accounting trick.

Model-vs-measured columns: rows recorded on a TPU after PR 6 carry a
`perf_model` dict (see README "Performance observability") — XLA
`cost_analysis()` FLOPs/bytes for the exact timed executable,
arithmetic intensity, a compute- vs bandwidth-bound roofline class
against the chip's published peaks (`obs/perf.py`
`PEAKS_BY_DEVICE_KIND`; v5e: 197 TF/s bf16, 819 GB/s), the roofline
model time, and the achieved GFLOP/s / GB/s + fraction-of-roofline over
the measured wall time. No row below carries one yet (the corpus
predates PR 6). The analytic GFLOP/s
columns in the tables count algorithmic work (e.g. the causal discount);
`perf_model` counts what the compiler actually scheduled — when the two
disagree, the gap itself is the finding (fusion, rematerialization, or
a masked-out region the analytic count discounts). The same accounting
gates CI: `PERF_BASELINE.json` + `scripts/ci.sh` stage [5/5].
""")

    hdr = ['config', 'time (s)', 'GFLOP/s/chip', 'mem GiB',
           'ref GFLOP/s/chip', 'ref peak GiB', 'ours/ref']
    table('nt (A·Bᵀ) — offset sweep, T=75000, d=768', hdr, [
        *[(f'offset={o} bf16', row(load(f'nt_benchmark_{o}'),
                                   BASE_NT_OFFSET.get(o)))
          for o in (30, 750, 1000, 6250, 25000)],
        ('offset=None (full gather) bf16', row(load('nt_benchmark_full'))),
        ('impl=ring bf16', row(load('nt_benchmark_ring'))),
    ])
    table('nt — scale sweep (offset=1000)', hdr, [
        *[(f'scale={s} (T={75000 // s}) bf16',
           row(load(f'nt_benchmark_size_{s}'), BASE_NT_SIZE.get(s)))
          for s in (1, 2, 4, 8)],
        *[(f'scale={s} f32', row(load(f'nt_benchmark_f32_size_{s}'),
                                 BASE_NT_SIZE.get(s)))
          for s in (2, 4, 8)],
    ])
    table('all (A·B) — offset sweep, T=75000, d=768', hdr, [
        *[(f'offset={o} bf16', row(load(f'all_benchmark_{o}'),
                                   BASE_ALL_OFFSET.get(o)))
          for o in (24, 48, 96, 192, 384, 768)],
        ('offset=None (full gather) bf16', row(load('all_benchmark_full'))),
        ('impl=ring bf16', row(load('all_benchmark_ring'))),
    ])
    table('all — scale sweep (offset=768)', hdr, [
        *[(f'scale={s} bf16', row(load(f'all_benchmark_size_{s}'),
                                  BASE_ALL_SIZE.get(s)))
          for s in (1, 2, 4, 8)],
        ('scale=2 f32', row(load('all_benchmark_f32_size_2'),
                            BASE_ALL_SIZE.get(2))),
    ])
    table('tn (Aᵀ·B) — scale sweep', hdr, [
        *[(f'scale={s} bf16', row(load(f'tn_benchmark_{s}'),
                                  BASE_TN_SIZE.get(s)))
          for s in (1, 2, 4, 8)],
        ('scale=2 f32', row(load('tn_benchmark_f32_2'),
                            BASE_TN_SIZE.get(2))),
    ])

    hdr_a = ['config', 'time (s)', 'GFLOP/s/chip', 'mem GiB']
    table('attention op (H=8, d=64, softmax(q·kᵀ/√d)·v; no reference '
          'analog — its module materializes full score rows)', hdr_a, [
        *[(f'{impl} T=75000', row(load(f'attn_benchmark_{impl}'),
                                  pad=False))
          for impl in ('online', 'flash', 'flash_bounded', 'ulysses')],
        *[(f'{impl} T=18750', row(load(f'attn_benchmark_{impl}_size_4'),
                                  pad=False))
          for impl in ('full', 'online', 'flash', 'flash_bounded',
                       'ulysses')],
    ])

    # Head-dim sweep: the "d=64 bounds MFU" ceiling argument as data.
    # (d=64, T=75000) IS the main attention table's flash config — read
    # that record rather than keeping a duplicate measurement.
    hd_rows = [
        (f'flash d={d} T={tlen}',
         row(load('attn_benchmark_flash' if (d, tag) == (64, '75k')
                  else f'attn_benchmark_flash_d{d}_{tag}'), pad=False))
        for d in (64, 128, 256)
        for tag, tlen in (('16k', 16384), ('75k', 75000))]
    if any(cells for _, cells in hd_rows):
        table('flash forward head-dim sweep (H=8, bf16; arithmetic '
              'intensity per score element grows with d, so the MXU rate '
              'climbs toward peak)', hdr_a, hd_rows)

    gqa_rows = [
        (f'flash H=8 kv=2 T={tlen}',
         row(load(f'attn_benchmark_flash_gqa_kv2{suf}'), pad=False))
        for suf, tlen in (('', 16384), ('_75k', 75000))]
    int8_row = row(load('attn_benchmark_flash_d256_16k_int8'), pad=False)
    if int8_row:
        gqa_rows.append(('flash d=256 T=16384 qk_quant=int8', int8_row))
    if any(cells for _, cells in gqa_rows):
        table('grouped-query attention (GQA, 4 q heads per K/V head: '
              'same rate as multi-head — the kernel is compute-bound — '
              'with 4× smaller K/V residency) and int8-quantized QK^T '
              '(MXU int8 path: +11% at d=256 where the kernel is '
              'MXU-bound; no win at d≤128 — dequant multiplies cost VPU '
              'time)', hdr_a, gqa_rows)

    def trow(rec):
        if rec is None:
            return None
        ma = rec.get('memory_analysis') or {}
        temp = ma.get('temp_bytes')
        return [f"{rec['step_time']:.4f}",
                f"{rec['step_gflops_per_chip']:,.0f}",
                f'{temp / 2**30:.2f}' if temp is not None else 'n/a']
    print("""
### Full train step (fwd + bwd + adam, one SPMD program; dim=768, H=8, bf16)

The reference has no train-step analog (its example stops at
`loss.backward()`, reference example.py:31-33). `temp GiB` is XLA's
compiled temporary-buffer total — the training-memory story: the
full/online softmax paths materialize (H, T/N, T) scores forward AND
backward, flash recomputes blockwise from the saved row logsumexp.
""")
    print('| config | s/step | GFLOP/s/chip | temp GiB |')
    print('|---|---|---|---|')
    for label, stem in [
            ('full T=8192', 'train_benchmark_full_8k'),
            ('online T=8192', 'train_benchmark_online_8k'),
            ('flash T=8192', 'train_benchmark_flash_8k'),
            ('flash T=16384', 'train_benchmark_flash'),
            ('flash_bounded T=16384', 'train_benchmark_flash_bounded'),
            ('flash T=32768', 'train_benchmark_flash_32k'),
            ('flash T=32768 (no mask)', 'train_benchmark_flash_32k_nomask'),
            ('flash T=65536', 'train_benchmark_flash_65k'),
            ('flash T=65536 (no mask)', 'train_benchmark_flash_65k_nomask'),
            ('flash T=16384 (no mask)', 'train_benchmark_flash_nomask'),
            ('flash T=16384 (segment ids, 8 spans)',
             'train_benchmark_flash_segments'),
            ('flash T=131072 (no mask)', 'train_benchmark_flash_128k_nomask'),
            ('flash T=131072 (causal, no mask)',
             'train_benchmark_flash_128k_causal'),
            ('flash T=262144 (no mask)', 'train_benchmark_flash_256k_nomask'),
            ('flash T=524288 (no mask)', 'train_benchmark_flash_512k_nomask'),
            ('flash T=131072 (causal, window=4096)',
             'train_benchmark_flash_128k_win4k'),
            ('flash T=524288 (causal, window=4096)',
             'train_benchmark_flash_512k_win4k'),
            ('flash T=524288 (causal, no mask)',
             'train_benchmark_flash_512k_causal'),
            ('flash T=16384 (no mask, GQA kv_heads=2)',
             'train_benchmark_flash_gqa_kv2'),
            ('flash T=16384 (causal, RoPE)',
             'train_benchmark_flash_rope'),
    ]:
        cells = trow(load(stem))
        if cells:
            print('| ' + ' | '.join([label] + cells) + ' |')

    # Train-step head-dim sweep (dim=768 fixed, so d = 768/heads).
    thd = [(f'flash H={h} (d={768 // h}) T={tlen} (no mask)',
            trow(load(f'train_benchmark_flash_h{h}_{tag}_nomask')))
           for h in (12, 6, 3)
           for tag, tlen in (('16k', 16384), ('75k', 75000))]
    if any(cells for _, cells in thd):
        print('\nTrain-step head-dim sweep (dim=768 held fixed, heads '
              'varied so d = 768/H; no-mask flash path):\n')
        print('| config | s/step | GFLOP/s/chip | temp GiB |')
        print('|---|---|---|---|')
        for label, cells in thd:
            if cells:
                print('| ' + ' | '.join([label] + cells) + ' |')
    # The no-mask prose cites specific rows — print it only when both
    # records exist (partial regeneration must not fabricate claims, and
    # must not drop the analysis section below either).
    if all(load(s) is not None for s in (
            'train_benchmark_flash_nomask',
            'train_benchmark_flash_128k_nomask',
            'train_benchmark_flash_256k_nomask',
            'train_benchmark_flash_512k_nomask')):
        print("""
No-mask rows use `--no-mask` (`attn_mask=None`, an extension over the
reference API): the dense mask is the only O(T²) input on the flash path.

**Dense-mask cost: a flat ~10% share, and the round-4 "32K cliff" is
dead.** Round 4 recorded masked T=32K at 58.2 TF/s vs 82.6 at 16K and
flagged a scaling cliff. Round-5 re-measurement — all six configs
back-to-back in ONE session — gives masked/no-mask pairs of
0.0323/0.0295 s (16K, 9.5% mask cost), 0.1279/0.1153 s (32K, 10.9%),
0.4967/0.4517 s (65K, 10.0%): the share is FLAT in T and the 58.2
record was the same transient-session class as the diagnosed 512K
cliff (the corpus rows above now carry the fresh records). Component
isolation (same session) shows where the ~10% lives: NOT in kernel
mask streaming — the 3-state tile summary + scalar-prefetch redirect
means an all-False mask streams no blocks at all — but in the
wrapper's O(T²) mask preprocessing (bool→int8 conversion + per-tile
min/max summary), pure HBM bandwidth on the T² bytes: 2.3 ms at 16K,
12.8 ms at 32K per pass, computed once per step (XLA CSEs the
identical fwd/bwd subexpressions). Both that tax and the attention
FLOPs are O(T²), which is why the share is flat — a dense T² mask
cannot cost less than touching T² bytes once. Steering: the segment-id
form is O(T) and *faster* than no-mask (cross-segment tiles never
execute) — any mask expressible as packed segments should use it;
dense masks are for genuinely irregular patterns and cost ~10%
flat. The natural single-chip boundary is the mask's own footprint:
at T=131K a dense mask is 16 GiB of bool input before the int8 copy —
it does not fit 16 GiB of HBM regardless of kernel strategy, so past
~65K the dense form is not merely slower, it is infeasible on one
chip; segments / causal / no-mask are the long-context forms (sharded,
the per-device mask slab is T²/N and the same analysis applies per
chip).

Dropping the mask still matters at long context — it
leaves training memory linear in T — ONE 16 GiB chip trains
dim-768 8-head attention at **T=524,288 at ~89 TFLOP/s/step** (the
reference's full-score materialization would need ~2 TiB per device at
that length). Scaling is exactly quadratic from 131K through 512K — each
doubling of T costs 4× the step time at a flat ~89 TFLOP/s, with
temporaries linear in T (2.5 → 5 → 10 GiB).

A round-2 record showed 195.7 s/step (13 TF/s) at T=512K — a 7× cliff.
Round-3 diagnosis (`scripts/diag_cliff.py`): it does not reproduce. In a
fresh process every component scales perfectly — flash fwd alone 1.82 s →
7.28 s, fwd+bwd 7.14 s → 28.5 s, and the full step 7.15 s → 28.6 s going
262K → 512K — and re-running the UNCHANGED round-2 code from a worktree at
its commit also gives 28.6 s, with the compiled executable reporting
identical buffer totals (temp 10.00 GiB) then and now. So the cliff was
transient device state during the original one-shot `--iters 1`
sweep measurement, not the compiled program; the corpus now carries the
reproducible record (`train_benchmark_flash_512k_nomask.json`, last
entry) and the sweep runs this config at `--iters 2`.""")
    if load('train_benchmark_flash_bounded') is not None:
        print("""
**`flash_softmax_mode='bounded'` train-step inversion: resolved as a
measurement artifact.** Round 3 recorded the bounded train step at
0.0454 s vs exact's 0.0314 s at T=16K — alarming, because the backward
kernels are mode-independent (the saved logsumexp is shift-invariant),
so bounded could only ever differ in the forward, where it *wins* the
forward-only sweep. Round-4 re-measurement (within one process,
alternating configs, 5 iters): exact 0.0327/0.0327 s vs bounded
0.0296/0.0315 s — and re-running the UNCHANGED round-3 code from a
worktree at its commit gives the same ordering (exact 0.0325, bounded
0.0315/0.0313). The recorded inversion was transient device state
in a one-shot sweep (the same failure class as the diagnosed T=512K
cliff); the corpus rows above now carry the reproducible records, and
the bounded mode's contract is unchanged: a forward-only optimization,
identical backward.""")
    if load('train_benchmark_flash_128k_causal') is not None:
        print("""
The causal row runs the kernels' in-kernel triangle with the round-4
**trapezoid pair grid**: with a static shard offset the (Q block,
K block) triangle flattens into one grid axis of exactly the valid
pairs, driven by scalar-prefetched SMEM block-index tables — the
out-of-triangle half of the grid costs no DMA and no sequencing at all
(the same overhead RESULTS measured at 19× on the window path before its
banded grid). T=131,072 causal went 68.8 → **81.8 TF/s/chip**
(1.20 → 0.99 s/step) with bitwise-identical results; the GFLOP/s figure
counts only the lower-triangle work. The pair tables are gated at 64K
pairs (~0.5 MiB SMEM); beyond the cap the rows CHUNK — the forward and
dq pass split over Q rows, the dk/dv pass over K blocks (disjoint output
slices, so nothing is partial-summed; an earlier Q-only chunking that
summed fp32 dk/dv partials OOMed the 16 GiB chip at T=512K and was
replaced) — and every chunk takes the trapezoid. T=524,288 causal:
full-grid 18.83 s/step (67.7 TF/s) → chunked trapezoid **17.20 s/step
(74.1 TF/s)**, both records in
`train_benchmark_flash_512k_causal.json`. Traced (multi-shard SPMD)
offsets keep the full grid — each shard's triangle differs, and a grid
size cannot be data-dependent.

A DMA-aliasing variant for those full-grid cases (clamp out-of-triangle
K/V block indices to the row's last valid block via dynamic index maps,
so skipped programs re-use the resident copy) was built and measured —
and REJECTED: traced-offset causal forward at T=16K ran 7.45 ms aliased
vs 4.80 ms plain (the scalar-prefetch dynamic maps cost ~2-3 µs of
scalar-core work per program, more than the skipped blocks' DMA), while
the trapezoid's 4.55 ms wins by halving the program count outright, not
by saving DMA per skipped program. Negative result recorded so the next
round doesn't re-derive it.""")

    def dec_row(label, stem):
        rec = load(stem)
        if rec is None:
            return None
        tps = rec.get('tokens_per_s')
        ms_step = rec.get('ms_per_step', rec['ms_per_token'])
        return (f"| {label} | {rec.get('batch', 1)} | "
                f"{rec.get('chain', 1)} | {ms_step:.3f} | "
                + (f'{tps:,.0f}' if tps else '—')
                + f" | {rec['cache_gb_per_s']:.0f} |")
    dec_rows = [r for r in [
        dec_row('t_max=16384', 'decode_benchmark_16k'),
        dec_row('t_max=16384, GQA kv_heads=2', 'decode_benchmark_16k_kv2'),
        dec_row('t_max=131072', 'decode_benchmark_128k'),
        dec_row('t_max=131072, GQA kv_heads=2',
                'decode_benchmark_128k_kv2'),
        dec_row('t_max=131072, chained', 'decode_benchmark_128k_chain'),
        dec_row('t_max=131072, chained, GQA kv_heads=2',
                'decode_benchmark_128k_chain_kv2'),
        dec_row('t_max=131072, chained, batched',
                'decode_benchmark_128k_b8_chain'),
        dec_row('t_max=131072, chained, batched, GQA kv_heads=2',
                'decode_benchmark_128k_b8_chain_kv2'),
        dec_row('t_max=131072, chained, GQA kv2, int8-trained (K mirror)',
                'decode_benchmark_128k_chain_kv2_int8'),
    ] if r is not None]
    if dec_rows:
        print("""
### KV-cache decode (inference; dim=768, H=8, bf16, one chip)

Steady-state latency through the module surface
(`DistributedDotProductAttn.decode`) against a ~full cache, with the
cache DONATED to the jitted step (`donate_argnums`) so the append's
`dynamic_update_slice` writes in place — without donation each token
paid a full K/V buffer copy (~1 ms at T=131K: a first measurement read
1.81 ms/token before a probe isolated the copy).

`chain` = tokens decoded per dispatch (`--decode-chain`: a `lax.scan`
of decode steps inside ONE jit). Round 4's single-dispatch rows sat on
a ~0.14 ms per-DISPATCH floor that masked every small-cache effect —
chained, the floor divides by the chain length and the table finally
shows the structural story: at t_max=131K the full-head and
`kv_heads=2` configurations stream at the SAME ~450-475 GB/s, so GQA
wins by exactly its bytes ratio H/H_kv — 0.21 vs 0.89 ms/step, the
4× the feature exists for (round 4 could only assert this; the
chained within-process pair demonstrates it). Batched serving rows
(`--batch 8`) decode 8 sequences per step — the GQA row clears ~5,000
tok/s against 131K-token contexts on one chip. `ms/step` is the time
per decode step (a step emits `batch` tokens); single-step rows
(chain=1) are kept for the dispatch-path story but read them as
PIPELINED THROUGHPUT, not latency — independent dispatches overlap on
the chip, so a single-step row can report cache GB/s above
the ~820 GB/s HBM peak (the re-measured full-head row does), which no
real per-step latency can. The chained rows serialize on the cache
carry and are the honest steady-state numbers. No reference analog
(it has no inference path).

Measured negative result (int8 K mirror): an int8-TRAINED model's
decode streams the append-time int8 mirror and scores with an
s8×s8→s32 dot — exact, and strictly better than re-quantizing the
bf16 buffer on the fly — yet measures 0.32 ms/step vs the bf16
model's 0.21 at the same kv2/131K shape, despite reading HALF the K
bytes (a first formulation that dequantized the mirror to fp32 before
the dot was worse still, 0.49: the conversion doubled the traffic the
mirror saves). XLA's s8 dot lowering at 4-row operands doesn't cash
the bandwidth saving in; a Pallas decode kernel consuming the mirror
natively is the known next step if int8 serving latency ever matters.
The mirror's real job is exactness: int8-trained models decode to
their training-time logits.

A second measured negative closes the formulation question: routing
the decode step through the FLASH kernel (one fused pass, the prefill
path's kernel with `causal_offset=length`) measures ~0.9 ms/step at
full heads and ~0.7 ms at kv2 on the 131K cache — parity with the
serialized einsum step at full heads (0.89) and 3× WORSE at kv2
(0.21). The asymmetry is structural: the kernel's cost floor is its
grid (128+ K-block programs of sequencing + DMA setup for ≤8 query
rows of work each), which does not shrink with `kv_heads`, while the
einsum's cost is the streamed bytes, which do. (Methodology note: a
naive unrolled microbench of the einsum side reports impossible rates
— XLA batches the independent repeats into one K-streaming matmul;
the serialized chained rows above are the honest einsum numbers.)
Decode on TPU wants the einsum; the kernels earn their keep from
prefill upward, which is exactly how the module routes.

Where the chained numbers sit vs physics: component isolation puts the
ATTENTION of the B=8 full-head step at 4.25 ms (759 GB/s — near the
~820 peak) and the appends at ~0.9 ms, yet the full chained step
measures 10.3 — the in-scan body (append, then read the whole buffer)
makes XLA copy the cache through the loop carry (~4 ms at B=8's
3.2 GB; the kv2 step carries the same proportional tax). So the
chained rows are CONSERVATIVE upper bounds on per-step latency: true
steady-state sits between the attention-only floor and the chained
figure, single-dispatch donated steps avoid the copy but measure
pipelined, and the GQA ratio — the structural claim — holds in every
formulation because both configurations pay proportionally. Two fixes
were tried and rejected with data: reordering the body to
attend-then-append (write-after-read) makes XLA hold MORE buffer
versions live and OOMs the compile at B=8, with the copy visible in
the failed allocation ("output of copy", a full cache-shaped temp) —
the loop-carry aliasing limit lives in XLA's scan machinery, below
anything an operand-level restructure can reach.

| config | batch | chain | ms/step | tok/s | cache GB/s |
|---|---|---|---|---|---|""")
        for r in dec_rows:
            print(r)

    lm_rows = []
    for label, stem in [
            ('8L, T=32768', 'lm_32k'),
            ('16L, T=131072', 'lm_128k_16l'),
            ('8L, T=262144', 'lm_256k'),
    ]:
        rec = load(stem)
        if rec:
            ma = rec.get('memory_analysis') or {}
            temp = ma.get('temp_bytes')
            lm_rows.append(
                f"| {label} ({rec['n_params'] / 1e6:.0f}M params"
                f"{', remat' if rec.get('remat') else ''}) | "
                f"{rec['step_time']:.3f} | {rec['tokens_per_s']:,.0f} | "
                f"{rec['step_gflops_per_chip']:,.0f} | "
                + (f'{temp / 2**30:.2f} |' if temp is not None
                   else 'n/a |'))
    if lm_rows:
        print("""
### Language-model training (capstone; dim=768, H=8, vocab=32768, bf16, one chip)

A REAL model end-to-end — token embedding → scanned (`nn.scan`) pre-LN
transformer stack over the sequence-parallel attention module → tied LM
head → packed-segment cross-entropy → cross-shard grad psum → adam — as
ONE compiled step (`benchmark.py --mode lm`). `remat` wraps each scanned
layer in `jax.checkpoint`, so backward activation memory is one layer's,
and the loss is CHUNKED cross-entropy (`TransformerLM.nll_sum`): the
(T, vocab) logits are never materialized (fp32 logits at T=131K are
17 GiB — the measured OOM without chunking; scanned chunks of 4096 rows
with per-chunk remat bound live score memory at ~0.5 GiB). The
end-to-end proof of the same pipeline (train → checkpoint mid-run →
resume → greedy generation through per-layer KV caches, on the 8-device
mesh) is `examples/train_lm.py` / `tests/test_lm.py`: the long-context
copy task trains to <0.5 copy-loss and >90% generation accuracy. No
reference analog — the reference stops at one attention layer (its
example.py:16-33).

| config | s/step | tokens/s | GFLOP/s/chip | temp GiB |
|---|---|---|---|---|""")
        for lm_row in lm_rows:
            print(lm_row)
        print("""
The counted rate is the full-remat ceiling, not overhead: with every
layer rematerialized the step executes ~4 attention passes (fwd,
recompute, bwd≈2×) while the GFLOP column counts 3, so the expected
counted rate is ~75% of the causal kernel's ~82 TF/s ≈ 61 TF/s — the
measured 60-62, FLAT from 32K through 262K — the whole-model analog of
the attention-layer quadratic-scaling rows above: 8× the context costs
49× the step time (between linear and the T² attention term's 64×,
because the projections/MLP/head grow only linearly), at constant rate
and with temporaries linear in T. Saving all layers' attention residuals
instead would need ~810 MB/layer at T=131K (13 GiB at depth 16, on
top of the 9.8 GiB step) — full remat is the right trade at this
memory, and the knob (`remat_policy`) exists for chips where it
isn't.""")

    print("""
### Communication model (multi-chip, analytic + HLO-validated)

One real chip means multi-chip ICI traffic cannot be measured here; this
is the checkable substitute (`scripts/comm_model.py`, validated by
`tests/test_comm_model.py`): closed-form per-device bytes per train step
for each attention path, with the collective *schedule* (op kinds,
counts, per-op shapes) asserted equal to what XLA actually compiles on
the virtual 8-device mesh. Numbers below: N=8, B=1, H=8, d=96 (dim 768),
T=131,072, bf16 activations (ring dk/dv partials fp32 by design).
""")
    try:
        import sys
        sys.path.insert(0, os.path.join(REPO, 'scripts'))
        import comm_model
        print(comm_model.table_markdown(n=8, h=8, t=131072, d=96))
    except Exception as e:  # pragma: no cover
        print(f'(comm_model table unavailable: {e})')
    print("""
How to read it: the ring moves the same K/V volume forward as one
all-gather ((N−1)/N of the global array per device) but as N−1
neighbour hops that overlap the folds; its fwd+bwd total lands at ~2.1×
the allgather path because the backward rotates fp32 dk/dv partials
along with the k/v buffers. Ulysses is the bytes-per-step winner at N/2×
below allgather but caps the mesh at H_kv | N; GQA (`num_kv_heads`)
multiplies the allgather/ulysses paths' bytes by H_kv/H directly — the
module's headline ICI lever. Pick allgather+GQA for small N, ulysses
while heads divide, ring when N > H or when score memory (not bytes)
binds.""")

    print("""
### Reading the numbers

- **North star: beaten.** The driver baseline (BASELINE.json) asks ≥2× the
  reference's best per-chip rate (2,287 GFLOP/s, nt offset=25000). The bf16
  nt kernel at the same workload runs ~60× that on one v5e chip; even the
  strict-fp32 runs at the scales that fit clear ~9×.
- **The offset↔time trade survives the port, memory-side inverted by
  design.** Larger offsets are faster here too (fewer, larger collectives →
  fewer scan steps). The reference's memory grew with offset because each
  `hvd.allgather` materialized a (W, *, offset, d) buffer per rank; our
  compiled memory is dominated by the (T, T) operand/output, with the
  gathered chunk a rounding error — the XLA totals are flat across offsets
  (see nt rows). The knob still exists and still bounds gathered-operand
  memory; it just no longer dominates at these shapes.
- **Ring vs allgather (1 chip):** on a W=1 mesh the ring (and the
  offset=None full gather) compile to ONE fused local matmul (~192 TF/s,
  97% of bf16 peak), while the chunked-offset path pays for its `lax.scan`
  structure (~142 TF/s) — the knob exists for multi-chip memory control,
  and a W=1 chip shows its pure overhead. The variants only diverge on
  real multi-chip ICI, which this driver cannot measure;
  multi-device correctness of both paths is pinned by the 8-device
  CPU-mesh tests (`tests/test_ops_grad.py`, parametrized over impl).
- **Ring/online now runs at flash-class rates — and at T=75000 on one
  chip.** The round-2 einsum block fold ran at 13.6 TF/s (T=18750) and
  could not run at T=75000 at all (it materialized the (H, T, T) score
  block — 180 GB). With the flash-kernel block fold, online = 64.2 TF/s at
  T=18750 (93% of plain flash's 69.3) and 73.6 TF/s at T=75000 — the
  scale-out path no longer trades throughput for its O((T/N)²) memory
  story. Remaining gap vs flash: the LSE merge between blocks (fp32 VPU
  work per fold).
- **Head-dim sweep (forward + train): d=64 is the VPU-bound floor, not
  the kernel's ceiling.** The score matmul's MXU contraction depth is d,
  so the rate ~doubles from d=64 to d=128 (76 → 161 TF/s fwd at T=16K;
  71 → 127 at T=75K) and holds at d=256 (161/152). The train-step sweep
  (dim=768 fixed, heads varied) shows the same: H=12 (d=64) 60.9 →
  H=6 (d=128) 121.4 → H=3 (d=256) 114.9 TF/s. The "~95% of practical
  ceiling" claim below is a d=64 statement; at d≥128 the kernels run at
  ~80-84% of the chip's 192 TF/s matmul peak.
- **Sliding-window attention is linear in T — and the banded grid is
  what makes it real.** `window=4096` causal training: 0.110 s/step at
  T=131K, 0.401 s at T=524K (3.6× time for 4× T ≈ linear; the full
  triangle at 524K costs ~14.3 s — ~36×). The first implementation kept
  the full (Tq/bq × Tk/bk) Pallas grid and only `pl.when`-skipped
  out-of-window blocks — it measured 7.6 s at T=524K because skipped
  programs still pay their K/V block DMA and grid sequencing. The banded
  grid (K axis = only each Q block's ~window/bk band, selected by
  scalar-prefetch index maps) removes those cells entirely: 19× on the
  same config, and the skipped work never touches HBM.
- **Masked flash after round 3: dense masks cost ~5%, segments are
  FASTER than no-mask.** Block-skip + mask-DMA redirect take the dense-
  masked train step from 59.3 (round 2) to 86.3 TF/s = 95% of the no-mask
  90.7; the segment-id form (8 packed spans, O(T) input) measures 238
  TF/s *apparent* because cross-segment tiles never execute (the FLOP
  count deliberately ignores the skip — see the table note).
- **Flash kernel at d=64**: exact-softmax ~76 TF/s at T=16K (the measured
  matmul-only ceiling of the same grid is ~90; Google's splash-attention
  kernel measures ~75 on this chip/shape). `softmax_mode='bounded'` trades
  the running-max reduce for a norm bound (auto-falls back when unsafe) and
  reaches ~85-90 TF/s. The VERDICT round-1 target of 100 TF/s at d=64
  assumed nt-style full-MXU rates; at d=64 the score matmul runs the MXU at
  half contraction depth, capping the theoretical mix at ~131 TF/s — the
  kernel sits at ~95% of the chip's practical (0.72-efficiency) ceiling.
""")


if __name__ == '__main__':
    sys.exit(main())
