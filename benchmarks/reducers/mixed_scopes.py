"""Per-layer readings of a decode step whose stack mixes window and full
attention layers: ``latent_scopes``'s readings, under
``scope_patterns_mixed.json``, which puts the decode kernel's ring mode
(``ops.flash_decode_ring``, opened inside ``ops.flash_decode``) in front
of the accepted rows so that the two modes' device time is told apart.
``metric['reads']`` says what is read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``;
- ``roofline``: the least time the chip could take for one step's needed
  work (``observed[metric['needs']]``: ``flops`` / ``bytes``) over the
  time under ``metric['scopes']``;
- ``counter``: ``observed[metric['group']][metric['counter']]``, what
  the program itself counted (the bytes of the caches it built).

A trace without programs to read names from, or of a program that has no
ring mode (as a parent commit has not), gives no number and raises
nothing.
"""

import functools

from benchmarks import loader, scopes, trace as tr

RING = 'ops.flash_decode_ring'


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns_mixed.json')


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
    program has no ring mode to tell apart."""
    path = scopes.xplane_for(run.cell)
    if path is None or not run.trace['devices']:
        return None
    mapping = instruction_map(path)
    if not any(row[0] == RING for row in mapping.values()):
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
    if metric['reads'] == 'scope_ms':
        return 1e3 * seconds / steps
    need = seen.get(metric['needs'])
    if seconds == 0 or not need:
        return None
    least = max(need.get('flops', 0) / run.peaks['flops_per_s'],
                need.get('bytes', 0) / run.peaks['hbm_bytes_per_s'])
    return 100.0 * least * steps / seconds
