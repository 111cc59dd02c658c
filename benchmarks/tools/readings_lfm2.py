"""Read what the short-convolution / packed-slab decode cell's limits
are set from, as ``readings_ling.py`` reads the Ling cell's: one whole
run of the cell a seed (its driver, its timed path, its comparison),
sound, or as a control — the plain reference with every matmul operand
AND the windows rounded to ``--control-dtype`` (float8_e4m3fn, the
precision below the cell's bfloat16), or with the keys and values alone
rounded to ``--kv-dtype`` (``--kv-seeds``: a cache held below bfloat16).
``--requests`` serves that many whole requests a run in place of the
traffic's ``min_requests`` and ``--sessions`` that many sessions in place
of the traffic's (a session's numbers do not hang on its neighbours: the
batch rows are independent; set-up is prefill, a session at a time — but
fewer than 129 sessions put the step's expert calls on another side of
nothing: the rule sends every call of at most 256 rows to the kernel):
the comparison takes the last request, after a restore. Prints the
driver's own lines and one JSON line a reading; sets no limit.

    python3 benchmarks/tools/readings_lfm2.py \
        --workload lfm2-8b-a1b.decode-4k --seeds 11,12 \
        --control-seeds 13 --kv-seeds 14 --requests 2 --sessions 32
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', default='')
    ap.add_argument('--control-seeds', default='')
    ap.add_argument('--control-dtype', default='float8_e4m3fn')
    ap.add_argument('--kv-seeds', default='')
    ap.add_argument('--kv-dtype', default='float8_e4m3fn')
    ap.add_argument('--requests', type=int, default=2)
    ap.add_argument('--sessions', type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import harness, loader
    from distributed_dot_product_tpu.utils.compile_cache import (
        setup_compile_cache,
    )
    setup_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    cell = loader.Cell(args.workload)
    cell.traffic = dict(cell.traffic, min_requests=args.requests)
    if args.sessions:
        cell.traffic['sessions'] = args.sessions
    tracer = harness.Tracer(os.path.join(ROOT, '.bench_trace', cell.name))

    def seeds(text):
        return [int(s) for s in text.split(',') if s]
    control = f'control:{args.control_dtype}'
    runs = [(s, 'sound', {}) for s in seeds(args.seeds)]
    runs += [(s, control, {'operand_dtype': jnp.dtype(args.control_dtype)})
             for s in seeds(args.control_seeds)]
    runs += [(s, f'control:kv:{args.kv_dtype}',
              {'kv_dtype': jnp.dtype(args.kv_dtype)})
             for s in seeds(args.kv_seeds)]
    for seed, kind, kwargs in runs:
        result = cell.driver().run(cell, seed, 0.0, False, tracer, **kwargs)
        print(json.dumps({'reading': {
            'workload': cell.name, 'seed': seed, 'kind': kind,
            'sessions': cell.traffic['sessions'],
            'device': jax.devices()[0].device_kind,
            'numbers': {r['compared']: r['value']
                        for r in result['compare'].rows},
            'tokens_per_s': result['end_to_end']['decode_tokens_per_s'],
        }}), flush=True)
        del result
    return 0


if __name__ == '__main__':
    sys.exit(main())
