"""Per-layer readings of a decode step whose stack holds gated
short-convolution layers beside attention and expert layers
(``lfm2_moe``): ``sparse_scopes``'s readings — the step program's class
stands where two programs of the process class one instruction name
differently — under ``scope_patterns_lfm2.json``, which puts the
convolution mixer's scope (``lm.conv_proj``) in front of the accepted
rows. ``metric['reads']`` says what is read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``.

A trace without programs to read names from, or of a program that does
not open ``lm.conv_proj`` (as a parent commit does not), gives no number
and raises nothing.
"""

import functools

from benchmarks import loader, scopes, trace as tr
from benchmarks.reducers.sparse_scopes import STEP_PROGRAM

NEW_SCOPES = ('lm.conv_proj',)


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns_lfm2.json')


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
    program does not open ``lm.conv_proj``."""
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
    steps = run.observed.get('steps')
    by_class = seconds_by_class(run) if steps else None
    if by_class is None:
        return None
    return 1e3 * sum(by_class.get(cls, 0.0)
                     for cls in metric['scopes']) / steps
