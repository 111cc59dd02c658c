"""Read what the latent-attention decode cell's limits are set from:
one whole run of the cell a seed (its driver, its timed path, its
comparison), sound, or as the control: the plain reference with every
matmul operand rounded to float8_e4m3fn, the precision below the cell's
bfloat16. Prints the driver's own lines (every number compared, the
quantiles of the logit gap) and one JSON line a reading; sets no limit.

    python3 benchmarks/tools/readings_latent.py \
        --workload xing4-29b-a4b.decode-32k --seeds 11,12 --control-seeds 13
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
    ap.add_argument('--seconds', type=float, default=1.0)
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
    tracer = harness.Tracer(os.path.join(ROOT, '.bench_trace', cell.name))
    runs = [(int(s), None) for s in args.seeds.split(',') if s]
    runs += [(int(s), jnp.float8_e4m3fn)
             for s in args.control_seeds.split(',') if s]
    for seed, operands in runs:
        result = cell.driver().run(cell, seed, args.seconds, False, tracer,
                                   operand_dtype=operands)
        print(json.dumps({'reading': {
            'workload': cell.name, 'seed': seed,
            'kind': 'control' if operands is not None else 'sound',
            'device': jax.devices()[0].device_kind,
            'numbers': {r['compared']: r['value']
                        for r in result['compare'].rows},
            'tokens_per_s': result['end_to_end']['decode_tokens_per_s'],
        }}), flush=True)
        del result
    return 0


if __name__ == '__main__':
    sys.exit(main())
