"""Device milliseconds a step in one class of operations (``kernel``:
Mosaic custom calls; ``xla``: every other operation that is no
collective), on the busiest device, from the trace."""

from benchmarks import trace as tr


def read(run, metric):
    if not run.trace['devices'] or not run.observed.get('steps'):
        return None
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    seconds = tr.class_seconds(ops, run.patterns, metric['op_class'])
    return 1e3 * seconds / run.observed['steps']
