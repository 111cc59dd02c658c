"""Per-layer readings of a decode step whose stack holds gated
delta-rule layers beside attention and expert layers:
``hybrid_scopes``'s readings, under ``scope_patterns_delta.json``, which
puts the delta-rule layer's scopes (``ops.delta_step``,
``ops.delta_scan``, ``lm.delta_proj``) in front of the accepted rows.
``metric['reads']`` says what is read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``;
- ``roofline``: the least time the chip could take for one step's needed
  work (``observed[metric['needs']]``: ``flops`` / ``bytes``) over the
  time under ``metric['scopes']``.

A trace without programs to read names from, or of a program that opens
none of the new scopes (as a parent commit does not), gives no number
and raises nothing.
"""

import functools

from benchmarks import loader, scopes, trace as tr

NEW_SCOPES = ('ops.delta_step', 'ops.delta_scan', 'lm.delta_proj')


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns_delta.json')


@functools.lru_cache(maxsize=4)
def instruction_map(path):
    """``scopes.instruction_map`` under this file's patterns."""
    pats = patterns()
    with open(path, 'rb') as f:
        data = f.read()
    merged = {}
    for _, proto in scopes.hlo_protos(data):
        for name, (op_name, how) in scopes.op_names(proto).items():
            row = (*scopes.classify(op_name, pats), op_name, how)
            if name in merged and merged[name][:2] != row[:2]:
                row = (scopes.UNATTRIBUTED, scopes.NO_PASS, '', 'ambiguous')
            merged[name] = row
    return merged


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
