"""Per-layer readings of a decode step whose stack holds a block-sparse
attention layer and Lightning linear-attention layers: ``delta_scopes``'s
readings, under ``scope_patterns_sparse.json``, which puts the sparse
layer's scopes (``ops.sparse_select``, ``ops.sparse_decode``,
``ops.sparse_prefill``) and the Lightning layer's
(``ops.lightning_step``, ``ops.lightning_scan``, ``lm.lightning_proj``)
in front of the accepted rows. ``metric['reads']`` says what is read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``;
- ``roofline``: the least time the chip could take for one step's needed
  work (``observed[metric['needs']]``: ``flops`` / ``bytes``) over the
  time under ``metric['scopes']``;
- ``counter``: ``observed[metric['group']][metric['counter']]``, what
  the program itself counted (the rows its picks held over the rows
  valid; the bytes of the pooled keys it built).

The trace's events name an instruction and not its program, and the six
programs of a serving process (prefill, insert, snapshot, finite check,
restore, step) number their fusions alike: where two programs give one
instruction name different classes, the STEP program's class stands
(``STEP_PROGRAM``; these are readings a step, and the step runs 256
times where the restore runs once) — ``scopes.instruction_map`` files
such a name as unattributed, which read this stack's head and most of
its attention projections as unscoped (chip, PR 43).

A trace without programs to read names from, or of a program that opens
none of the new scopes (as a parent commit does not), gives no number
and raises nothing.
"""

import functools
import re

from benchmarks import loader, scopes, trace as tr

STEP_PROGRAM = re.compile(r'step_fn')

NEW_SCOPES = ('ops.sparse_select', 'ops.sparse_decode', 'ops.sparse_prefill',
              'ops.lightning_step', 'ops.lightning_scan',
              'lm.lightning_proj')


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns_sparse.json')


@functools.lru_cache(maxsize=4)
def instruction_map(path):
    """``scopes.instruction_map`` under this file's patterns, the step
    program's rows laid over the other programs'."""
    pats = patterns()
    with open(path, 'rb') as f:
        data = f.read()
    merged, step = {}, {}
    for program, proto in scopes.hlo_protos(data):
        into = step if STEP_PROGRAM.search(program) else merged
        for name, (op_name, how) in scopes.op_names(proto).items():
            row = (*scopes.classify(op_name, pats), op_name, how)
            if name in into and into[name][:2] != row[:2]:
                row = (scopes.UNATTRIBUTED, scopes.NO_PASS, '', 'ambiguous')
            into[name] = row
    return {**merged, **step}


def seconds_by_class(run):
    """``{class: seconds}`` on the busiest device; None where the
    program opens none of the new scopes."""
    path = scopes.xplane_for(run.cell)
    if path is None or not run.trace['devices']:
        return None
    mapping = instruction_map(path)
    if not any(row[0] in NEW_SCOPES for row in mapping.values()):
        return None
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    out = {}
    for (cls, _), sec in scopes.seconds_by_scope(ops, mapping,
                                                 run.patterns).items():
        out[cls] = out.get(cls, 0.0) + sec
    return out


def read(run, metric):
    seen = run.observed
    if metric['reads'] == 'counter':
        return seen.get(metric['group'], {}).get(metric['counter'])
    steps = seen.get('steps')
    by_class = seconds_by_class(run) if steps else None
    if by_class is None:
        return None
    seconds = sum(by_class.get(cls, 0.0) for cls in metric['scopes'])
    if metric['reads'] != 'roofline':
        return 1e3 * seconds / steps
    need = seen.get(metric['needs'])
    if seconds == 0 or not need:
        return None
    least = max(need.get('flops', 0) / run.peaks['flops_per_s'],
                need.get('bytes', 0) / run.peaks['hbm_bytes_per_s'])
    return 100.0 * least * steps / seconds
