"""Read what a limit is set from: every number the check compares, for
sound runs of the program over many seeds and for the control over a
few, in ONE process so that set-up is paid once. Prints one JSON line a
reading; sets no limit and times nothing.

The control is the plain reference put in the program's place with every
matmul operand rounded to float8_e4m3fn, the precision below the cells'
bfloat16. ``--program-int8`` also reads the program with its own int8
score path (``qk_quant='int8'``) and, in a decode cell, its int8 weights
(``weight_quant='int8'``) switched on; PERF.md says how those read.

    python3 benchmarks/tools/readings.py --workload mpt-7b.train-16k \
        --seeds 11,12,13 --control-seeds 11,12,13
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

CONTROL_DTYPE = jnp.float8_e4m3fn
PROGRAM_INT8 = {'qk_quant': 'int8'}
PROGRAM_W8 = {'weight_quant': 'int8'}


def emit(**rec):
    print(json.dumps({'reading': rec}), flush=True)


def train(cell, seeds, control_seeds, sound=True, program_int8=False):
    from benchmarks.drivers import train as drv
    from benchmarks.harness import Compare
    t = cell.traffic

    def read(kind, seed, got, want):
        compare = Compare()
        drv.compare_first_steps(compare, got, want, {})
        emit(workload=cell.name, seed=seed, kind=kind,
             numbers={r['compared']: r['value'] for r in compare.rows},
             where={r['compared']: r['detail'] for r in compare.rows})

    def program(seed, overrides=None):
        trainer = drv.Trainer(cell, seed, attn_overrides=overrides)
        trainer.init_state()
        trainer.compile()
        return trainer.first_steps(t['check_steps'])

    for seed in seeds:
        batches = drv.Trainer(cell, seed).batches
        want = drv.reference_steps(cell, seed, batches, t['optimizer'],
                                   t['check_steps'])
        if sound:
            read('sound', seed, program(seed), want)
        if seed in control_seeds:
            read('control', seed, drv.reference_steps(
                cell, seed, batches, t['optimizer'], t['check_steps'],
                operand_dtype=CONTROL_DTYPE), want)
            if program_int8:
                read('program_int8', seed, program(seed, PROGRAM_INT8), want)


def decode(cell, seeds, control_seeds, sound=True, program_int8=False):
    from benchmarks.drivers import decode as drv

    def numbers(logits, tokens, **more):
        gaps = drv.logit_gaps(logits, tokens)
        return {'served_logit_gap': float(np.max(gaps)),
                'served_logit_gap_p99': float(np.percentile(gaps, 99)),
                **more}

    for seed in seeds:
        server = drv.Server(cell, seed)
        server.load()
        first, tokens, gaps, bad = server.request()
        context, sessions = server.context_tokens, server.sessions
        server.free()
        del server
        for s in range(sessions):
            if not sound and (s or seed not in control_seeds):
                continue
            logits = drv.reference_logits(cell, seed, context[s], first[s],
                                          tokens[s])
            emit(workload=cell.name, seed=seed, kind='sound', session=s,
                 numbers=numbers(logits, tokens[s],
                                 nonfinite_logit_steps=bad,
                                 gap_ms_p50=1e3 * float(np.median(gaps))))
            if seed not in control_seeds or s:
                continue
            low = drv.reference_logits(cell, seed, context[s], first[s],
                                       tokens[s], CONTROL_DTYPE)
            picked = low.argmax(-1)
            emit(workload=cell.name, seed=seed, kind='control', session=s,
                 numbers=numbers(logits, picked, tokens_differing=int(
                     np.sum(picked != tokens[s]))))
            if not program_int8:
                continue
            from distributed_dot_product_tpu.models.dense import (
                quantize_dense_params,
            )
            for kind, overrides, convert in (
                    ('program_int8', PROGRAM_INT8, None),
                    ('program_w8', PROGRAM_W8, quantize_dense_params)):
                control = drv.Server(cell, seed, attn_overrides=overrides,
                                     only_session=s)
                control.load(convert)
                _, picked, _, cbad = control.request(
                    forced=tokens[s:s + 1], request_index=0)
                control.free()
                del control
                emit(workload=cell.name, seed=seed, kind=kind, session=s,
                     numbers=numbers(
                         logits, picked[0], nonfinite_logit_steps=cbad,
                         tokens_differing=int(np.sum(
                             picked[0] != tokens[s]))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--control-seeds', default='')
    ap.add_argument('--no-sound', action='store_true',
                    help='only the control (sound runs were read before)')
    ap.add_argument('--program-int8', action='store_true')
    args = ap.parse_args()
    import jax
    from benchmarks import loader
    from distributed_dot_product_tpu.utils.compile_cache import (
        setup_compile_cache,
    )
    setup_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    cell = loader.Cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(',')]
    control = [int(x) for x in args.control_seeds.split(',') if x]
    emit(device=str(jax.devices()[0].device_kind), workload=cell.name)
    {'train': train, 'decode': decode}[cell.kind](
        cell, seeds, control, sound=not args.no_sound,
        program_int8=args.program_int8)


if __name__ == '__main__':
    main()
