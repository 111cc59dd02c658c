#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a short profiled window. Earlier
lines give set-up's parts and every number compared beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result: a CPU number never stands under a device metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


class Run:
    """What a per-layer reader is given."""

    def __init__(self, cell, result, device, trace, patterns, peaks):
        self.cell, self.device = cell, device
        self.observed = result['observed']
        self.trace, self.patterns, self.peaks = trace, patterns, peaks


def device_record(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get('peak_bytes_in_use', 0))
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices), 'memory_peak_bytes': peak}


def run_cell(cell, seed, seconds, trace, devices, **driver_kwargs):
    """Everything after the look for a chip. Returns the result line as
    a dict."""
    from benchmarks import harness, loader, trace as tr
    tracer = harness.Tracer(os.path.join(ROOT, '.bench_trace', cell.name))
    result = cell.driver().run(cell, seed, seconds, trace, tracer,
                               **driver_kwargs)
    setup_s = (result['setup_done'] - T_START
               - harness.uncounted_seconds(result['setup_done']))
    device = device_record(devices[:cell.chips])
    values = dict(result['end_to_end'], setup_s=setup_s)
    metrics, extra = {}, {}
    if not trace:
        for m in cell.end_to_end():
            metrics[m['name']] = {'value': values[m['name']],
                                  'unit': m['unit']}
    else:
        pats = tr.patterns()
        reduced = tr.load_xplane(tracer.xplane_path(), pats)
        run = Run(cell, result, device, reduced, pats,
                  loader.peaks_for(device['kind']))
        for m in cell.per_layer():
            value = cell.reducer(m['reducer']).read(run, m)
            if value is not None:
                metrics[m['name']] = {'value': value, 'unit': m['unit']}
        busy = tr.busy_seconds(reduced)
        span = tr.window_span(reduced)
        device['busy_s'] = sum(busy.values()) / max(1, len(busy))
        device['window_s'] = (span[1] - span[0]) / 1e9 if span else 0.0
        extra['breakdown'] = tr.breakdown(reduced, pats)
    compare = result['compare']
    return {'correct': compare.correct, 'attempted': result['attempted'],
            'failed': result['failed'], 'metrics': metrics,
            'device': device, **extra}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from benchmarks import loader
    cell = loader.Cell(args.workload)

    import jax
    t0 = time.perf_counter()
    devices = jax.devices()
    runtime_init = time.perf_counter() - t0
    if devices[0].platform != 'tpu' or len(devices) < cell.chips:
        print(f'benchmarks/run.py: {args.workload} needs {cell.chips} TPU '
              f'chip(s); JAX found {len(devices)} x {devices[0].platform}',
              file=sys.stderr)
        return 2
    from distributed_dot_product_tpu.utils.compile_cache import (
        setup_compile_cache,
    )
    # Bringing the chip's runtime up took 7.5 to 12.9 s from run to run
    # of one cell (chip, PR 23) against 3.8 s of everything else in
    # set-up; no PR can move work into it, so it is printed apart.
    from benchmarks import harness
    harness.record_phase('runtime_init', runtime_init, counted=False)
    cache_dir = setup_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    print(json.dumps({'compile_cache': cache_dir, 'workload': args.workload,
                      'seed': args.seed}), flush=True)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
