# -*- coding: utf-8 -*-
"""
Retrace sentinel: trace-count budgets for jitted serving/decode
entrypoints.

The hazard class this automates: a jitted per-token step that silently
re-traces every call. One concrete instance already happened here — an
unhashable module field made ``decode_seq_parallel`` rebuild and
re-trace its compiled step EVERY token (caught by hand in round 5; the
LRU step cache + warn-once in models/attention.py is the fix). Nothing mechanical guarded against the next instance: a retrace
storm shows up only as mysterious slowness, because each trace produces
a *correct* program.

The sentinel closes that gap. Wrap the **pre-jit python callable** with
:func:`watch_traces` — ``jax.jit`` executes the wrapped body exactly
once per cache miss, so the wrapper's call count IS the trace count —
and the wrapper raises :class:`RetraceBudgetExceeded` the moment a
function traces more often than its declared budget.

Enablement: the ``DDP_TPU_RETRACE_SENTINEL`` env var (1/0). Unset, the
sentinel is ON under pytest (``PYTEST_CURRENT_TEST`` present — every
decode/serve suite then runs under its budgets, which is the point:
retrace storms become test failures, not perf mysteries) and OFF
otherwise (production keeps counting — the counters are cheap and
:func:`snapshot` exposes them — but never raises).

Budget semantics: a budget of ``n`` allows ``n`` traces over the
wrapper's lifetime. Per-token loops own ONE wrapper per compiled step
(e.g. ``make_decode_step`` wraps at build time), so legitimate
shape-driven retraces of a *new* step get a fresh budget while the
per-token storm on a single step trips immediately.

This module counts traces of WATCHED callables and raises; it sees no
seconds and no unwatched program. What a build COST — trace, lowering,
compile, cache, by program — is in ``utils/build_ledger.py``, which sees
every program and raises nothing.
"""

import collections
import functools
import os
import threading
import weakref

__all__ = ['RetraceBudgetExceeded', 'TraceCounter', 'watch_traces',
           'sentinel_enabled', 'snapshot', 'total', 'reset', 'ENV_VAR']

ENV_VAR = 'DDP_TPU_RETRACE_SENTINEL'


class RetraceBudgetExceeded(RuntimeError):
    """A watched entrypoint traced more often than its declared budget."""


def sentinel_enabled():
    """Raise-on-exceed policy: the env var wins; unset, on under pytest
    (so the suites enforce budgets) and off elsewhere (counters still
    count — see :func:`snapshot`)."""
    v = os.environ.get(ENV_VAR)
    if v is not None:
        return v.strip().lower() in ('1', 'true', 'on', 'yes')
    return 'PYTEST_CURRENT_TEST' in os.environ


class TraceCounter:
    """Count + budget for one watched callable (thread-safe: serving
    watchdog threads may trigger traces)."""

    __slots__ = ('name', 'budget', 'count', '_lock', '__weakref__')

    def __init__(self, name, budget):
        if budget < 1:
            raise ValueError(f'trace budget must be >= 1, got {budget}')
        self.name = name
        self.budget = budget
        self.count = 0
        self._lock = threading.Lock()

    def __del__(self):
        # Hand the final count to the per-name retired total so total()
        # stays exact however the GC times wrapper teardown (the
        # rebuild-storm path discards one wrapper per token). NO lock
        # here: the collector runs a finalizer on whatever thread
        # allocates, and that thread may hold _COUNTERS_LOCK (seen: a
        # tier-1 worker hung in _live_counters). A deque's append is
        # atomic; the readers fold it in under the lock.
        try:
            _DYING.append((self.name, self.count))
        except Exception:  # graphlint: allow[silent-except]
            pass           # interpreter shutdown: globals may be gone

    def hit(self):
        with self._lock:
            self.count += 1
            count = self.count
        if count > self.budget and sentinel_enabled():
            raise RetraceBudgetExceeded(
                f'retrace budget exceeded: {self.name!r} traced {count} '
                f'times (budget {self.budget}). A jitted decode/serve '
                f'step re-tracing per call is a silent throughput '
                f'collapse — hold ONE compiled step across calls (check '
                f'for unhashable static args, python-object keys, or a '
                f'step rebuilt inside the token loop).')


# Counter registry for snapshot()/total()/reset() and the pytest
# fixture: WEAK references, so a counter lives exactly as long as its
# wrapper (the pathological case the sentinel observes — a step rebuilt
# per token — discards one wrapper per token; holding them strongly
# here would turn the observer into its own leak). A dying counter
# leaves its count in _DYING (TraceCounter.__del__, lock-free) and the
# readers fold that into the per-name _RETIRED total, so total() is
# exact regardless of GC timing, and reset() always reaches every
# counter that could still raise.
_COUNTERS = []                   # weakref.ref(TraceCounter)
_RETIRED = {}                    # name -> folded count from dead
_DYING = collections.deque()     # (name, count) from finalizers
_COUNTERS_LOCK = threading.Lock()


def _fold_dying():
    """Move the finalizers' counts into ``_RETIRED``. Callers must hold
    _COUNTERS_LOCK."""
    while _DYING:
        name, count = _DYING.popleft()
        _RETIRED[name] = _RETIRED.get(name, 0) + count


def _live_counters():
    """Strong refs to the live counters; prunes dead weakrefs in place.
    Callers must hold _COUNTERS_LOCK."""
    live, refs = [], []
    for ref in _COUNTERS:
        c = ref()
        if c is not None:
            live.append(c)
            refs.append(ref)
    _COUNTERS[:] = refs
    return live


def watch_traces(fn, name, budget=2):
    """Wrap a **pre-jit** python callable so every trace of the jitted
    result counts against ``budget``. Returns the wrapped callable;
    pass THAT to ``jax.jit`` / ``shard_map``::

        step = jax.jit(watch_traces(step_fn, 'decode_step', budget=2))

    The counter rides the wrapper as ``_graphlint_counter`` (tests and
    budget assertions read it)."""
    counter = TraceCounter(name, budget)
    with _COUNTERS_LOCK:
        _live_counters()             # prune dead refs opportunistically
        _COUNTERS.append(weakref.ref(counter))

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        counter.hit()
        return fn(*args, **kwargs)

    counted._graphlint_counter = counter
    return counted


def snapshot():
    """``{name: (count, budget)}`` over every live counter (names can
    repeat across instances; later registrations win the key — use the
    per-wrapper ``_graphlint_counter`` for exact assertions)."""
    with _COUNTERS_LOCK:
        return {c.name: (c.count, c.budget) for c in _live_counters()}


def total(name):
    """Cumulative trace count across EVERY counter registered under
    ``name`` (live + folded-at-death). Per-instance budgets can't see
    the rebuild-storm variant (a step rebuilt per token gets a fresh
    counter each time — each counts 1); the name total exposes it: N
    tokens through a properly cached step total 1 trace, through a
    rebuilt-per-token step they total N. tests/test_graphlint.py pins
    both numbers for decode_seq_parallel's LRU step cache."""
    with _COUNTERS_LOCK:
        _fold_dying()
        return (_RETIRED.get(name, 0)
                + sum(c.count for c in _live_counters()
                      if c.name == name))


def reset():
    """Zero every live counter and the folded totals (test isolation —
    the pytest fixture calls this so one test's traces never charge
    another's budget; weak registration means every counter that could
    still raise is reachable here)."""
    with _COUNTERS_LOCK:
        for c in _live_counters():
            c.count = 0
        _fold_dying()
        _RETIRED.clear()
