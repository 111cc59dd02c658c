# -*- coding: utf-8 -*-
"""
The build ledger: one record for every program this process BUILDS —
what it cost to trace, to lower and to compile, and whether the
persistent cache had it — on ``time.perf_counter``, the clock of
``obs.spans.SpanRecord.start`` and of the benchmark's set-up phases, so
a record can be put inside the span or phase that caused it.

A leaf: it imports JAX and nothing of the package, so ``ops/`` may use
it (``tests/test_layering.py``). ``utils/compile_cache.
setup_compile_cache()`` installs it; there is no switch and no
environment name, because it does nothing between builds: a timed
window holds no compile and a kernel's :func:`build_span` runs at trace
time only.

Where the records come from:

- ``jax.monitoring`` hands a listener the three stages of a build with
  the program's ``fun_name`` and their start and end: ``trace`` (the
  program's Python under ``jit``), ``lower`` (jaxpr -> MLIR; Pallas ->
  Mosaic inside it) and ``compile`` (XLA and Mosaic, or the cache's
  read). ``jit(…)`` is taken off the name, so a program's three stages
  share one. Events of inner ``jit`` s and primitives arrive nested
  inside the outer trace's, children first.
- the persistent cache's events: ``cache_hit`` / ``cache_miss`` (points)
  and ``cache_read`` (the retrieval's seconds). They carry no name on
  this JAX and take the ``compile`` record whose interval holds them.
- :func:`build_span` — the third kind of name beside the host
  ``span`` and the ``device_scope`` (``obs/spans.py``): BUILD time,
  Python that runs while a program is being traced. It reads the clock
  twice, notes a ``build`` record and leaves nothing in the program.
  ``ops/kernel_call.py`` opens one around every Pallas kernel under the
  kernel's ``name=``, so a trace's seconds divide into the model's
  Python and each kernel's body.

**Self time, not sums.** A record's ``self_seconds`` is its duration
less what its children cover; its children are the records of the same
thread whose interval lies inside it. The sum of ``self_seconds`` over
any set of records never exceeds the wall time they span, where a plain
sum of durations counts a nested second twice. A ``trace`` record that
lies inside a ``build`` record counts to the kernel's body
(``BuildRecord.stage``): Pallas traces a body as an inner ``jit``.

``utils/retrace.py`` is the other contract on builds — a budget of
traces a watched callable that RAISES; it sees no seconds and no
unwatched program. This ledger sees every program and raises nothing.
"""

import collections
import dataclasses
import functools
import threading
import time
from typing import Optional

from jax import monitoring
from jax._src import monitoring as _monitoring

__all__ = ['KINDS', 'TIMED', 'FOLD_SECONDS', 'BuildRecord', 'BuildLedger',
           'build_span', 'install', 'uninstall', 'installed', 'records',
           'summary', 'costliest', 'get_ledger']

KINDS = ('trace', 'lower', 'compile', 'build', 'cache_hit', 'cache_miss',
         'cache_read')
# The kinds that take time (the other two are points).
TIMED = ('trace', 'lower', 'compile', 'build', 'cache_read')

_STAGES = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'lower',
    '/jax/core/compile/backend_compile_duration': 'compile',
}
_CACHE_POINTS = {
    '/jax/compilation_cache/cache_hits': 'cache_hit',
    '/jax/compilation_cache/cache_misses': 'cache_miss',
}
_CACHE_READ = '/jax/compilation_cache/cache_retrieval_time_sec'
_CACHE_KINDS = ('cache_hit', 'cache_miss', 'cache_read')

# A few thousand records a serving process: see PERF.md section 6, PR 48
# for what the cells' set-ups note.
MAX_RECORDS = 16384

# A stage shorter than this is an inner ``jit`` whose trace was already
# cached (two thirds of all events, 0.02 s of a set-up in sum): it is
# counted in ``folded`` and keeps no record, so its microseconds stay in
# the self time of the trace it ran inside.
FOLD_SECONDS = 2e-5

# The monitoring events are stamped with time.time(), a build span with
# perf_counter: a child may seem to start this much before its parent.
_SLACK = 1e-4


@dataclasses.dataclass
class BuildRecord:
    """One build event. ``start`` is a ``perf_counter`` reading
    (comparable within the process only), ``parent`` the ``seq`` of the
    record whose interval holds this one, ``name`` the program (a
    kernel's Pallas name for a ``build`` record), ``within`` the name of
    the innermost :func:`build_span` open around it (Pallas traces a
    kernel's body as an inner ``jit``: its ``trace`` records lie inside
    the kernel's ``build`` record and are the kernel's body, not the
    model's Python)."""
    seq: int
    kind: str
    name: Optional[str]
    start: float
    seconds: float
    self_seconds: float
    parent: Optional[int] = None
    within: Optional[str] = None
    thread: int = 0

    @property
    def stage(self):
        """``kind``, but ``build`` for a trace inside a build span."""
        return ('build' if self.kind == 'trace' and self.within
                else self.kind)

    @property
    def end(self):
        return self.start + self.seconds


def _program(fun_name):
    """``step_fn`` from ``jit(step_fn)``."""
    if fun_name and fun_name.startswith('jit(') and fun_name.endswith(')'):
        return fun_name[4:-1]
    return fun_name


class BuildLedger:
    """The records, bounded and thread-safe. What falls off the far end
    is counted in ``dropped``, what was too short to keep in
    ``folded``."""

    def __init__(self, max_records=MAX_RECORDS):
        self._lock = threading.Lock()
        self._records = collections.deque()     # guarded-by: self._lock
        self._max = max_records
        self._seq = 0                           # guarded-by: self._lock
        self.dropped = 0                        # guarded-by: self._lock
        self._dropped_to = float('-inf')        # guarded-by: self._lock
        self.folded = 0                         # guarded-by: self._lock
        self.listener_seconds = 0.0             # guarded-by: self._lock

    def note(self, kind, name, start, seconds, entered=None):
        """Append one record that has just ENDED and adopt, as its
        children, the parentless records of this thread that lie inside
        it. ``entered``: when the caller began its own bookkeeping, so
        the ledger can count what it costs."""
        thread = threading.get_ident()
        with self._lock:
            self._seq += 1
            rec = BuildRecord(self._seq, kind, name, start, seconds,
                              seconds, thread=thread)
            covered = 0.0
            # The cache's events hold nothing: a hit fired as the read
            # ends is the compile's child, not the read's.
            inside = () if kind in _CACHE_KINDS else reversed(self._records)
            for old in inside:
                if old.thread != thread:
                    continue
                if old.end <= start - _SLACK:
                    break
                if old.start < start - _SLACK:
                    continue
                if kind == 'build' and old.within is None:
                    old.within = name       # every record inside it
                if old.parent is None:
                    old.parent = rec.seq
                    if old.name is None:
                        old.name = name
                    covered += old.seconds
            rec.self_seconds = max(0.0, seconds - covered)
            self._records.append(rec)
            while len(self._records) > self._max:
                gone = self._records.popleft()
                self.dropped += 1
                self._dropped_to = max(self._dropped_to, gone.start)
            if entered is not None:
                self.listener_seconds += time.perf_counter() - entered
        return rec

    def fold(self, entered):
        """Count one stage too short to keep (``FOLD_SECONDS``)."""
        with self._lock:
            self.folded += 1
            self.listener_seconds += time.perf_counter() - entered

    def records(self, since=None, until=None):
        """Copies of the records whose start lies in ``[since, until)``
        on ``perf_counter``, oldest first."""
        with self._lock:
            return [dataclasses.replace(r) for r in self._records
                    if (since is None or r.start >= since)
                    and (until is None or r.start < until)]

    def summary(self, since=None, until=None):
        """Self seconds by stage (a record's kind; ``build`` for a trace
        inside a build span) and by program, the kernels' bodies by
        name and the cache's counts, over the records whose start lies
        in ``[since, until)``. A record's program is the name of its
        outermost ancestor: an inner ``jit`` 's trace and a kernel's
        body count under the program that was being traced. ``dropped``
        is 0 where the cut starts behind everything that fell off the
        bound, else every record that ever did (some may be the
        cut's)."""
        with self._lock:
            # copies: `note` rewrites a live record's parent and name
            every = {r.seq: dataclasses.replace(r) for r in self._records}
            dropped, cost = self.dropped, self.listener_seconds
            folded = self.folded
            if since is not None and since > self._dropped_to:
                dropped = 0

        def root(rec):
            while rec.parent in every:
                rec = every[rec.parent]
            return rec.name

        seconds = dict.fromkeys(TIMED, 0.0)
        programs, kernels = {}, {}
        cache = {'hits': 0, 'misses': 0}
        n = 0
        for rec in every.values():
            if ((since is not None and rec.start < since)
                    or (until is not None and rec.start >= until)):
                continue
            n += 1
            if rec.kind == 'cache_hit':
                cache['hits'] += 1
                continue
            if rec.kind == 'cache_miss':
                cache['misses'] += 1
                continue
            stage = rec.stage
            seconds[stage] += rec.self_seconds
            by = programs.setdefault(root(rec), {})
            by[stage] = by.get(stage, 0.0) + rec.self_seconds
            if stage == 'build':
                kernel = rec.name if rec.kind == 'build' else rec.within
                kernels[kernel] = (kernels.get(kernel, 0.0)
                                   + rec.self_seconds)
        return {'seconds': seconds, 'programs': programs,
                'kernels': kernels, 'cache': cache, 'records': n,
                'dropped': dropped, 'folded': folded,
                'listener_seconds': cost}

    def clear(self):
        with self._lock:
            self._records.clear()
            self.dropped = self.folded = 0
            self._dropped_to = float('-inf')
            self.listener_seconds = 0.0


_LEDGER = BuildLedger()


def get_ledger() -> BuildLedger:
    """The process's ledger."""
    return _LEDGER


def costliest(summed, n=3):
    """``[(program, seconds, {stage: seconds}), ...]``: the ``n``
    programs of a :func:`summary` that cost most to build."""
    rows = sorted(((sum(stages.values()), program, stages)
                   for program, stages in summed['programs'].items()),
                  key=lambda row: -row[0])[:n]
    return [(program, total, stages) for total, program, stages in rows]


def records(since=None, until=None):
    return _LEDGER.records(since, until)


def summary(since=None, until=None):
    return _LEDGER.summary(since, until)


class build_span:
    """``with build_span('flash_fwd'):`` around Python that runs while a
    program is being TRACED: two clock reads and one ``build`` record,
    nothing in the program. Not a host ``span`` (that one times the
    dispatch of a compiled step and is refused inside jitted code) and
    not a ``device_scope`` (that one names device time).
    ``@build_span('ops.nt')`` on a function opens one around every
    call."""

    __slots__ = ('name', 'start')

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _LEDGER.note('build', self.name, self.start, end - self.start,
                     entered=end)
        return False

    def __call__(self, fn):
        name = self.name

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with build_span(name):
                return fn(*args, **kwargs)
        return spanned


# -- the listeners ------------------------------------------------------

def _on_time_span(event, start_time, end_time, **kwargs):
    kind = _STAGES.get(event)
    if kind is None:
        return
    now = time.perf_counter()
    if end_time - start_time < FOLD_SECONDS:
        _LEDGER.fold(now)
        return
    # The event's stamps are time.time()'s; this callback runs as the
    # stage ends, so the two clocks are read microseconds apart.
    start = start_time + (now - time.time())
    _LEDGER.note(kind, _program(kwargs.get('fun_name')), start,
                 end_time - start_time, entered=now)


def _on_event(event, **kwargs):
    kind = _CACHE_POINTS.get(event)
    if kind is not None:
        now = time.perf_counter()
        _LEDGER.note(kind, None, now, 0.0, entered=now)


def _on_duration(event, duration, **kwargs):
    if event == _CACHE_READ:
        now = time.perf_counter()
        _LEDGER.note('cache_read', None, now - duration, duration,
                     entered=now)


_LISTENERS = (
    (_on_time_span, monitoring.register_event_time_span_listener,
     monitoring.unregister_event_time_span_listener,
     _monitoring.get_event_time_span_listeners),
    (_on_event, monitoring.register_event_listener,
     monitoring.unregister_event_listener,
     _monitoring.get_event_listeners),
    (_on_duration, monitoring.register_event_duration_secs_listener,
     monitoring.unregister_event_duration_listener,
     _monitoring.get_event_duration_listeners),
)


def install():
    """Register the listeners; one set a process however often this is
    called (``setup_compile_cache()`` calls it)."""
    for callback, register, _, registered in _LISTENERS:
        if callback not in registered():
            register(callback)


def uninstall():
    """Take the listeners off again (tests). The records stay."""
    for callback, _, unregister, registered in _LISTENERS:
        if callback in registered():
            unregister(callback)


def installed():
    return all(callback in registered()
               for callback, _, _, registered in _LISTENERS)
