# -*- coding: utf-8 -*-
"""
Smoke tests for the benchmark CLI (the driver's measurement surface).

The reference benchmark harness is part of its capability surface
(reference benchmark.py:29-39); ours additionally feeds the per-round
driver artifacts, so a broken flag or record schema would surface only at
measurement time on real hardware. These run every mode end-to-end at tiny
shapes on the CPU mesh in subprocesses (mirroring how run_sweeps.py
invokes it) and validate the appended JSON records.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow

def _run(tmp_path, name, *bench_args):
    out = tmp_path / f'{name}.json'
    # Strip backend pins AND the serving knobs (DDP_TPU_DECODE_KERNEL /
    # DDP_TPU_FAULT_*): the decode-impl assertions below test the
    # benchmark's own resolution, and an inherited fault plan would
    # inject faults into the benchmarked scheduler.
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')
           and not k.startswith('DDP_TPU_')}
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    env['PYTHONPATH'] = _REPO + os.pathsep + env.get('PYTHONPATH', '')
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, 'benchmark.py'),
         *bench_args, '--iters', '1', '--file', str(out)],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout
    with open(out) as f:
        records = json.load(f)
    assert len(records) == 1
    return records[0]


def test_nt_mode(tmp_path):
    # scale 2344 -> T = 32 over 8 devices (4 rows per shard).
    rec = _run(tmp_path, 'nt', '--mode', 'nt', '--scale', '2344',
               '--offset', '2')
    assert rec['mode'] == 'nt' and rec['world'] == 8
    assert rec['dist_gflops_per_chip'] > 0
    assert rec['local_gflops'] > 0


def test_all_and_tn_modes(tmp_path):
    rec = _run(tmp_path, 'all', '--mode', 'all', '--scale', '2344',
               '--offset', '2', '--skip-local')
    assert rec['mode'] == 'all' and 'local_gflops' not in rec
    rec = _run(tmp_path, 'tn', '--mode', 'tn', '--scale', '2344',
               '--skip-local')
    assert rec['offset'] is None and rec['impl'] is None


def test_offset_none_and_ring(tmp_path):
    rec = _run(tmp_path, 'ntf', '--mode', 'nt', '--scale', '2344',
               '--offset', 'none', '--skip-local')
    assert rec['offset'] is None
    rec = _run(tmp_path, 'ntr', '--mode', 'nt', '--scale', '2344',
               '--impl', 'ring', '--skip-local')
    assert rec['impl'] == 'ring'


def test_attn_mode(tmp_path):
    rec = _run(tmp_path, 'attn', '--mode', 'attn', '--attn-impl', 'online',
               '--scale', '2344', '--skip-local')
    assert rec['attn_impl'] == 'online'
    assert rec['T'] == 24  # 75000 // 2344 = 31, floored to the 8-mesh
    assert rec['dist_gflops_per_chip'] > 0


def test_attn_mode_seq_len_override(tmp_path):
    # --seq-len overrides the reference's T = 75000/scale convention
    # (used by the head-dim sweep to pin T exactly).
    rec = _run(tmp_path, 'attn_sl', '--mode', 'attn', '--attn-impl',
               'online', '--seq-len', '64', '--head-dim', '32',
               '--skip-local')
    assert rec['T'] == 64 and rec['head_dim'] == 32


def test_train_mode(tmp_path):
    rec = _run(tmp_path, 'train', '--mode', 'train', '--attn-impl', 'online',
               '--seq-len', '64')
    assert rec['mode'] == 'train' and rec['mask'] is True
    assert rec['step_gflops_per_chip'] > 0
    rec = _run(tmp_path, 'train_nm', '--mode', 'train', '--attn-impl',
               'online', '--seq-len', '64', '--no-mask')
    assert rec['mask'] is False
    rec = _run(tmp_path, 'train_c', '--mode', 'train', '--attn-impl',
               'online', '--seq-len', '64', '--no-mask', '--causal')
    assert rec['causal'] is True and rec['step_gflops_per_chip'] > 0


def test_decode_serve_mode(tmp_path):
    """The serving microbenchmark: scheduler vs bare decode loop on the
    same engine shape, both rates recorded, plus the decode path and
    the engine-surface TTFT row."""
    rec = _run(tmp_path, 'dserve', '--mode', 'decode-serve',
               '--seq-len', '48', '--serve-requests', '4')
    assert rec['mode'] == 'decode-serve'
    assert rec['completed'] == 4
    assert rec['bare_tokens_per_s'] > 0
    assert rec['sched_tokens_per_s'] > 0
    assert rec['decode_impl'] == 'xla'        # auto resolves off-TPU
    assert rec['ttft_ms'] > 0


def test_decode_serve_mode_paged_twin(tmp_path):
    """--cache-mode paged: the fixed-memory twin row — same KV byte
    budget as the slab row, more slots, pool-utilization and
    peak-concurrency columns recorded."""
    rec_s = _run(tmp_path, 'dserve_s', '--mode', 'decode-serve',
                 '--seq-len', '64', '--batch', '2',
                 '--serve-requests', '8')
    rec_p = _run(tmp_path, 'dserve_p', '--mode', 'decode-serve',
                 '--seq-len', '64', '--batch', '2',
                 '--serve-requests', '8', '--cache-mode', 'paged',
                 '--page-size', '8')
    assert rec_s['cache_mode'] == 'slab'
    assert rec_p['cache_mode'] == 'paged'
    # The twin framing: identical KV budget, strictly more concurrency.
    assert rec_p['kv_budget_bytes'] == rec_s['kv_budget_bytes']
    assert rec_p['slots'] > rec_s['slots']
    assert rec_p['max_concurrent'] > rec_s['max_concurrent']
    assert rec_p['pages'] * rec_p['page_size'] \
        == rec_s['slots'] * rec_s['t_max']
    assert 0 < rec_p['pages_used_peak'] <= rec_p['pages']
    # The burst rounds up to whole rounds of `slots` requests.
    assert rec_p['completed'] == rec_p['requests'] >= 8
    assert rec_p['sched_tokens_per_s'] > 0


def test_decode_serve_mode_kernel_path(tmp_path):
    """--decode-impl kernel routes the engine through the fused Pallas
    step (interpreted on CPU) and records it."""
    rec = _run(tmp_path, 'dserve_k', '--mode', 'decode-serve',
               '--seq-len', '48', '--serve-requests', '4',
               '--decode-impl', 'kernel')
    assert rec['decode_impl'] == 'kernel'
    assert rec['completed'] == 4
    assert rec['sched_tokens_per_s'] > 0


def test_decode_mode_kernel_vs_xla_rows(tmp_path):
    """--mode decode grows kernel-vs-XLA rows: one invocation per path,
    each recording its decode_impl and the TTFT/prefill columns."""
    rec_x = _run(tmp_path, 'dec_x', '--mode', 'decode', '--seq-len',
                 '128', '--heads', '2', '--head-dim', '8',
                 '--decode-impl', 'xla', '--decode-chain', '2')
    rec_k = _run(tmp_path, 'dec_k', '--mode', 'decode', '--seq-len',
                 '128', '--heads', '2', '--head-dim', '8',
                 '--decode-impl', 'kernel')
    for rec in (rec_x, rec_k):
        assert rec['mode'] == 'decode'
        assert rec['ms_per_step'] > 0
        assert rec['ttft_ms'] > rec['prefill_ms'] > 0
    assert rec_x['decode_impl'] == 'xla'
    assert rec_k['decode_impl'] == 'kernel'


def test_decode_spec_row(tmp_path):
    """--mode decode --spec ngram: the draft-verify generation row —
    spec and non-spec tokens/s on the same engine/prompts plus the
    amortization telemetry, and the ISSUE-8 CPU acceptance numbers
    (accepted-tokens/step > 2, fewer dispatches than tokens) on the
    repetitive stream. The run itself asserts stream identity before
    recording, so a passing row IS an exactness check."""
    rec = _run(tmp_path, 'dspec', '--mode', 'decode', '--spec', 'ngram',
               '--seq-len', '128', '--heads', '2', '--head-dim', '8')
    assert rec['mode'] == 'decode' and rec['spec'] == 'ngram'
    assert rec['spec_k'] == 4
    assert rec['tokens_per_s'] > 0
    assert rec['baseline_tokens_per_s'] > 0
    assert rec['accepted_per_step'] > 2.0
    assert rec['proposed_per_step'] >= rec['accepted_per_step']
    assert rec['decode_steps'] < rec['baseline_decode_steps']
    assert rec['completed'] == rec['requests'] == 2


def test_train_mode_window(tmp_path):
    rec = _run(tmp_path, 'train_w', '--mode', 'train', '--attn-impl',
               'flash', '--seq-len', '64', '--no-mask', '--causal',
               '--window', '16')
    assert rec['window'] == 16 and rec['step_gflops_per_chip'] > 0


def test_metrics_out_snapshot(tmp_path):
    """--metrics-out writes the observability artifact: the metrics
    snapshot (serve histograms when the mode drives the scheduler,
    span-mirror histograms always) plus the phase-span summary."""
    mpath = tmp_path / 'metrics.json'
    rec = _run(tmp_path, 'dserve_m', '--mode', 'decode-serve',
               '--seq-len', '48', '--serve-requests', '4',
               '--metrics-out', str(mpath))
    assert rec['completed'] == 4
    with open(mpath) as f:
        payload = json.load(f)
    assert payload['mode'] == 'decode-serve'
    assert payload['record']['completed'] == 4
    # Phase spans were collected and mirrored into histograms.
    assert payload['spans']['benchmark.scheduler_burst']['count'] == 1
    assert payload['metrics']['histograms'][
        'span.benchmark.scheduler_burst.seconds']['total_count'] == 1
    # The scheduler's request-latency decomposition is in the snapshot.
    hists = payload['metrics']['histograms']
    assert hists['serve.ttft_seconds']['total_count'] > 0
    assert hists['serve.queue_wait_seconds']['total_count'] > 0


def test_serve_load_topology_mode(tmp_path):
    """--mode serve-load --topology 1x2: the disaggregated row runs the
    trace through the router AND the single-process twin, merges the
    per-member logs, and records both goodputs plus the routing
    telemetry. The per-member JSONL logs must exist and the placements
    must cover every decode replica."""
    logs = tmp_path / 'topo'
    rec = _run(tmp_path, 'topo', '--mode', 'serve-load',
               '--topology', '1x2', '--load-requests', '24',
               '--event-log', str(logs))
    assert rec['topology'] == '1x2'
    assert rec['requests'] == 24
    assert set(rec['routed']) == {'r0', 'r1'}
    assert sum(rec['routed'].values()) + rec['counts']['rejected'] >= 24
    assert rec['handoffs'] >= 1          # the long-prompt tail offloads
    # 2x the capacity on the same trace: the topology never does worse.
    assert rec['goodput_pct'] >= rec['twin_goodput_pct']
    for name in ('router', 'prefill', 'r0', 'r1'):
        assert (logs / f'{name}.jsonl').exists(), name
    assert (logs / 'twin.jsonl').exists()
    assert (logs / 'trace.json').exists()
