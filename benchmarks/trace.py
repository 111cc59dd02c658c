"""From the profiler's trace to what the per-layer readers take.

``load_xplane`` turns an ``.xplane.pb`` into a plain dict,

    {'devices': {plane name: [[op, start_ns, duration_ns, self_ns], ...]},
     'async':   {plane name: [[op, start_ns, duration_ns], ...]},
     'host':    [[span name, start_ns, duration_ns], ...]}

``devices`` holds the ``XLA Ops`` line, whose operations run one at a
time; ``async`` the collectives of the ``Async XLA Ops`` line (a
``-start`` to its ``-done``), which run beside them. An ``op`` is the HLO instruction's name and opcode (``%attn.38
custom-call``); the trace itself names an event by the instruction's
whole text. Operations nest (a ``while`` holds its body's), so every sum
over a class of operations takes ``self_ns``, an event's time less its
children's.

which is also the form of the small recorded traces the tests keep.
Which planes are devices, which line holds their operations and which
host events count as spans are patterns in ``trace_patterns.json``.
"""

import re

from benchmarks import loader


def patterns():
    return loader.read_json(loader.HERE, 'trace_patterns.json')


_OPCODE = re.compile(r'\s([a-z][a-z0-9\-]*)\(')


def short_name(text):
    """``%attn.38 custom-call`` from an HLO instruction's text."""
    head, eq, rest = text.partition(' = ')
    if not eq:
        return text[:80]
    m = _OPCODE.search(' ' + rest)
    return f'{head} {m.group(1)}' if m else head[:80]


def with_self_time(events):
    """``[name, start, dur]`` rows to ``[name, start, dur, self]``."""
    rows = sorted(([n, s, d, d] for n, s, d in events),
                  key=lambda r: (r[1], -r[2]))
    stack = []
    for row in rows:
        while stack and stack[-1][1] + stack[-1][2] <= row[1]:
            stack.pop()
        if stack:
            stack[-1][3] -= row[2]
        stack.append(row)
    return rows


def load_xplane(path, pats=None):
    from jax.profiler import ProfileData
    pats = pats or patterns()
    device_re = re.compile(pats['device_plane'])
    span_re = re.compile(pats['host_span'])
    data = ProfileData.from_file(path)
    out = {'devices': {}, 'async': {}, 'host': []}
    for plane in data.planes:
        if device_re.search(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == pats['op_line']:
                    ops = with_self_time(
                        [short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events)
                elif line.name == pats['async_line']:
                    out['async'][plane.name] = [
                        row for row in (
                            [short_name(e.name), int(e.start_ns),
                             int(e.duration_ns)] for e in line.events)
                        if op_class(row[0], pats) == 'collective']
            out['devices'][plane.name] = ops
        elif re.search(pats['host_plane'], plane.name):
            for line in plane.lines:
                for e in line.events:
                    if span_re.search(e.name):
                        out['host'].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def op_class(name, pats):
    """'kernel' (a Mosaic custom call), 'collective', or 'xla'."""
    for cls in ('kernel', 'collective'):
        if any(re.search(p, name) for p in pats[cls]):
            return cls
    return 'xla'


def union(intervals):
    """Merged ``[start, end]`` intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals):
    return sum(end - start for start, end in intervals)


def op_intervals(ops, keep=None):
    return [[s, s + d] for name, s, d, _ in ops
            if d > 0 and (keep is None or keep(name))]


def busy_seconds(trace):
    """Seconds in which an operation ran, per device plane."""
    return {name: length(union(op_intervals(ops))) / 1e9
            for name, ops in trace['devices'].items()}


def busiest(trace, pats):
    """The device plane with the most non-collective time: on a
    contiguous causal split the chips wait for the last one."""
    def compute(ops):
        return sum(own for name, _, _, own in ops
                   if op_class(name, pats) != 'collective')
    return max(trace['devices'], key=lambda n: compute(trace['devices'][n]))


def class_seconds(ops, pats, cls):
    return sum(own for name, _, _, own in ops
               if op_class(name, pats) == cls) / 1e9


def collective_intervals(trace, plane, pats):
    """Merged intervals in which a collective is in flight on ``plane``:
    the synchronous ones of its operation line and the asynchronous
    ones beside it."""
    ops = trace['devices'][plane]
    sync = op_intervals(ops, lambda n: op_class(n, pats) == 'collective')
    beside = [[s, s + d] for _, s, d in trace.get('async', {}).get(plane, [])
              if d > 0]
    return union(sync + beside)


def exposed_seconds(trace, plane, pats):
    """Collective time during which no compute operation runs there. A
    container (a ``while`` around its body) is no compute of its own:
    only events without children count."""
    ops = trace['devices'][plane]
    coll = collective_intervals(trace, plane, pats)
    leaves = [op for op in ops if op[3] == op[2]]
    rest = union(op_intervals(
        leaves, lambda n: op_class(n, pats) != 'collective'))
    hidden, j = 0, 0
    for start, end in coll:
        while j < len(rest) and rest[j][1] <= start:
            j += 1
        k = j
        while k < len(rest) and rest[k][0] < end:
            hidden += min(end, rest[k][1]) - max(start, rest[k][0])
            k += 1
    return (length(coll) - hidden) / 1e9


def window_span(trace):
    """First operation start to last operation end over all devices."""
    starts = [s for ops in trace['devices'].values() for _, s, d, _ in ops]
    ends = [s + d for ops in trace['devices'].values()
            for _, s, d, _ in ops]
    if not starts:
        return None
    return min(starts), max(ends)


def breakdown(trace, pats, top_ops=10, top_gaps=10):
    """The device operations with most self time (averaged over
    devices), and the idle time of the busiest device by the host span
    open in the middle of each gap."""
    totals = {}
    for ops in trace['devices'].values():
        for name, _, _, own in ops:
            totals[name] = totals.get(name, 0) + own
    n = max(1, len(trace['devices']))
    device_ops = sorted(([k, v / n / 1e9] for k, v in totals.items()),
                        key=lambda kv: -kv[1])[:top_ops]
    gaps = {}
    if trace['devices']:
        busy = union(op_intervals(trace['devices'][busiest(trace, pats)]))
        spans = sorted(trace['host'], key=lambda e: e[2])   # innermost first
        for (_, end), (start, _) in zip(busy, busy[1:]):
            mid = (end + start) // 2
            label = next((name for name, s, d in spans
                          if s <= mid <= s + d), 'no host span open')
            gaps[label] = gaps.get(label, 0) + (start - end)
    idle_gaps = sorted(([k, v / 1e9] for k, v in gaps.items()),
                       key=lambda kv: -kv[1])[:top_gaps]
    return {'device_ops': device_ops, 'idle_gaps': idle_gaps}
