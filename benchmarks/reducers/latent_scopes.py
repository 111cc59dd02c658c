"""Per-layer readings of the latent-attention / sparse-expert decode
step. Device time is split as ``scope_ms_per_step`` splits it
(``scopes.py``: the busiest device's self time by the program's own
scopes, from the trace and the programs it carries) but with
``scope_patterns_latent.json``, which puts this model's scopes in front
of the accepted rows. ``metric['reads']`` says what is read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``;
- ``roofline``: the least time the chip could take for one step's needed
  work over the time under ``metric['scopes']``. The needed work is
  ``observed[metric['needs']]`` (``flops`` / ``bytes``), or, with
  ``metric['needs'] == 'expert_stream'``, the bytes of the distinct
  experts the program's counter saw a step;
- ``counter``: ``observed['moe'][metric['counter']]``, what the step's
  own counters said of the window's routing.

A trace without programs to read names from, or of a program that opens
none of this model's scopes, gives no number and raises nothing.
"""

import functools

from benchmarks import loader, scopes, trace as tr

NEW_SCOPES = ('ops.mla_decode', 'lm.moe_experts', 'lm.moe_route', 'lm.hc')


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns_latent.json')


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
    """``{class: seconds}`` on the busiest device; None where there is
    nothing of this model to read."""
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
        return seen.get('moe', {}).get(metric['counter'])
    steps = seen.get('steps')
    by_class = seconds_by_class(run) if steps else None
    if by_class is None:
        return None
    seconds = sum(by_class.get(cls, 0.0) for cls in metric['scopes'])
    if metric['reads'] == 'scope_ms':
        return 1e3 * seconds / steps
    if seconds == 0:
        return None
    if metric['needs'] == 'expert_stream':
        moe = seen.get('moe')
        if not moe:
            return None
        need = {'bytes': moe['active_experts_per_step']
                * moe['expert_bytes']}
    else:
        need = seen.get(metric['needs'])
        if not need:
            return None
    least = max(need.get('flops', 0) / run.peaks['flops_per_s'],
                need.get('bytes', 0) / run.peaks['hbm_bytes_per_s'])
    return 100.0 * least * steps / seconds
