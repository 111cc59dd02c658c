"""Milliseconds a step in which a collective is in flight on the busiest
device (synchronous and asynchronous ones merged), from the trace."""

from benchmarks import trace as tr


def read(run, metric):
    if not run.trace['devices'] or not run.observed.get('steps'):
        return None
    plane = tr.busiest(run.trace, run.patterns)
    total = tr.length(tr.collective_intervals(run.trace, plane,
                                              run.patterns))
    return 1e-6 * total / run.observed['steps'] if total else None
