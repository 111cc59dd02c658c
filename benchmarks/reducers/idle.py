"""Idle share of the busiest device over the traced window: one minus
the union of its operations' intervals over the span from the first
operation to the last."""

from benchmarks import trace as tr


def read(run, metric):
    span = tr.window_span(run.trace)
    if span is None:
        return None
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    busy = tr.length(tr.union(tr.op_intervals(ops)))
    return 100.0 * (1.0 - busy / (span[1] - span[0]))
