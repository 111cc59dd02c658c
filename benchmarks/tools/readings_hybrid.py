"""Read what the hybrid-stack decode cell's limits are set from, as
``readings_latent.py`` reads the latent cell's: one whole run of the
cell a seed (its driver, its timed path, its comparison), sound, or as
the control — the plain reference with every matmul operand AND the
recurrence's operands rounded to ``--control-dtype`` (float8_e4m3fn, the
precision below the cell's bfloat16; bfloat16 reads what a state kept in
bfloat16 would). ``--requests`` serves that many whole requests a run
in place of the traffic's ``min_requests``: the comparison takes the
last one, after a restore, whatever the window's length. Prints the
driver's own lines and one JSON line a reading; sets no limit.

    python3 benchmarks/tools/readings_hybrid.py \
        --workload nemotron-3-super.decode-32k --seeds 11,12 \
        --control-seeds 13 --requests 2
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
    ap.add_argument('--requests', type=int, default=2)
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
    tracer = harness.Tracer(os.path.join(ROOT, '.bench_trace', cell.name))
    runs = [(int(s), None) for s in args.seeds.split(',') if s]
    runs += [(int(s), jnp.dtype(args.control_dtype))
             for s in args.control_seeds.split(',') if s]
    for seed, operands in runs:
        result = cell.driver().run(cell, seed, 0.0, False, tracer,
                                   operand_dtype=operands)
        print(json.dumps({'reading': {
            'workload': cell.name, 'seed': seed,
            'kind': 'sound' if operands is None else f'control:{operands}',
            'device': jax.devices()[0].device_kind,
            'numbers': {r['compared']: r['value']
                        for r in result['compare'].rows},
            'tokens_per_s': result['end_to_end']['decode_tokens_per_s'],
        }}), flush=True)
        del result
    return 0


if __name__ == '__main__':
    sys.exit(main())
