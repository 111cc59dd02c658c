"""The share of collective time during which no compute operation runs
on the busiest device, from the trace."""

from benchmarks import trace as tr


def read(run, metric):
    if not run.trace['devices']:
        return None
    plane = tr.busiest(run.trace, run.patterns)
    total = tr.length(tr.collective_intervals(run.trace, plane,
                                              run.patterns))
    if total == 0:
        return None
    return 100.0 * 1e9 * tr.exposed_seconds(run.trace, plane,
                                            run.patterns) / total
