"""Per-layer readings of a decode step whose stack holds gated delta-rule
layers beside ONE latent-attention layer and group-limited experts
(``bailing_hybrid``): ``sparse_scopes``'s instruction map — its patterns
hold a row for every scope this program opens (``ops.delta_step``,
``lm.delta_proj``, ``ops.mla_decode``, ``lm.moe_*``,
``lm.state_restore``; the sparse and Lightning rows in front match
nothing here), and where two programs of the process class one
instruction name differently the STEP program's class stands
(``scopes.instruction_map`` files such a name as unattributed, which
reads a stack's head — fused with the driver's finite check — as
unscoped: PERF.md section 7 (vv)). ``metric['reads']`` says what is
read:

- ``scope_ms``: milliseconds a step under ``metric['scopes']``.

A trace without programs to read names from, or of a program that lacks
one of this stack's scopes (as a parent commit's does), gives no number
and raises nothing.
"""

from benchmarks import scopes, trace as tr
from benchmarks.reducers import sparse_scopes

NEW_SCOPES = ('ops.delta_step', 'lm.delta_proj', 'ops.mla_decode')


def seconds_by_class(run):
    """``{class: seconds}`` on the busiest device; None where the
    program opens not every one of ``NEW_SCOPES``."""
    path = scopes.xplane_for(run.cell)
    if path is None or not run.trace['devices']:
        return None
    mapping = sparse_scopes.instruction_map(path)
    if not set(NEW_SCOPES) <= {row[0] for row in mapping.values()}:
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
