"""Device milliseconds a step under the program's own scopes
(``metric['scopes']``: classes of ``scope_patterns.json``; absent: all)
in the given passes (``metric['passes']``; absent: all), on the busiest
device, from the trace and the programs it carries (``scopes.py``). A
trace without programs to read names from, or of a program that opens
no device scope, gives no number and raises nothing."""

from benchmarks import scopes


def read(run, metric):
    if not run.observed.get('steps'):
        return None
    by_scope = scopes.run_seconds_by_scope(run)
    if by_scope is None:
        return None
    classes, passes = metric.get('scopes'), metric.get('passes')
    seconds = sum(sec for (cls, pas), sec in by_scope.items()
                  if (classes is None or cls in classes)
                  and (passes is None or pas in passes))
    return 1e3 * seconds / run.observed['steps']
