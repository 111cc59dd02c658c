"""A kernel's share of its roofline: the least time the chip could take
for the needed operations and bytes of one step (``flops.py``, for the
cell's shapes), ``max(flops / peak flops, bytes / peak bytes)``, over the
kernels' device time a step on the busiest device."""

from benchmarks import trace as tr


def read(run, metric):
    need = run.observed.get(metric['needs'])
    if not need or not run.trace['devices'] or not run.observed.get('steps'):
        return None
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    seconds = tr.class_seconds(ops, run.patterns, 'kernel')
    if seconds == 0:
        return None
    least = max(need.get('flops', 0) / run.peaks['flops_per_s'],
                need.get('bytes', 0) / run.peaks['hbm_bytes_per_s'])
    return 100.0 * least * run.observed['steps'] / seconds
