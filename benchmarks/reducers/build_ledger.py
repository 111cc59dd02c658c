"""Set-up by layer: the program's build ledger
(``distributed_dot_product_tpu/utils/build_ledger.py``: one record for
every trace, kernel body, lowering, compile and cache event of the
process, on ``time.perf_counter``) laid over the harness's phase list
(``harness.PHASES``: name, seconds, counted, ended at — the same clock).
A record lies in the phase that holds its start, and only the phases
that are part of ``setup_s`` count: not ``runtime_init``, not
``reference``, and nothing of the window or after it, which no phase
holds. ``metric['reads']`` says what is read:

- ``self_seconds``: the records of ``metric['kinds']``, each its
  duration less what its children cover, so a second that an inner
  ``jit`` or a kernel's body spent inside an outer trace counts once; a
  record's kind is its ``stage`` here: ``build`` for a ``trace`` record
  that lies inside a kernel's ``build`` record (Pallas traces a body as
  an inner ``jit``), so ``trace`` is the model's Python alone;
- ``count``: how many records of ``metric['kinds']``;
- ``execute``: the counted phases' seconds in which no record is open —
  set-up that is running, not building (weights drawn, prefill, the
  snapshot, the warm request).

The four ``self_seconds`` metrics and ``execute`` partition the counted
phases. What ``setup_s`` holds outside every phase (imports, the cell's
files, the seeded tokens) is ``setup_s`` less their sum and no metric.

A metric whose file says ``"says": "ledger"`` (one does) also prints
the ledger's account of the run on a line of its own: records kept and
dropped, what the listeners themselves cost, the cache's counts, self
seconds by kind in every phase, the costliest programs, and every
program built after set-up in no phase at all — inside the timed
window, where ``harness.window_compiles`` counts a compile and cannot
name it, or in a driver's comparison behind the window (a state
sliced eagerly for ``recurrent_state_gap``).

A package with no ``utils/build_ledger`` module at all (a parent commit
under this benchmark, which the driver lays over it for its traced
runs) gives no number and raises nothing; any other failure of the
import raises, and ``tests/test_build_ledger.py`` (tier-1) holds this
tree to having the module the reader looks for.
"""

import importlib
import json

from benchmarks import harness, trace as tr


LEDGER = 'distributed_dot_product_tpu.utils.build_ledger'


def ledger():
    """The program's build ledger, or None where the package predates
    it: that module missing and nothing else."""
    try:
        return importlib.import_module(LEDGER)
    except ModuleNotFoundError as e:
        if e.name != LEDGER:
            raise
        return None


def counted_phases(phases):
    """``[[start, end], ...]`` of the phases that are part of
    ``setup_s``."""
    return [[end - seconds, end]
            for _, seconds, counted, end in phases if counted]


def in_phases(records, spans):
    return [r for r in records
            if any(start <= r.start < end for start, end in spans)]


def building_seconds(records, spans):
    """Seconds of ``spans`` in which a record is open."""
    busy = tr.union([r.start, r.start + r.seconds]
                    for r in records if r.seconds > 0)
    return sum(max(0.0, min(end, b1) - max(start, b0))
               for start, end in spans for b0, b1 in busy)


def reduce(records, phases, metric):
    spans = counted_phases(phases)
    if not spans:
        return None
    if metric['reads'] == 'execute':
        return tr.length(spans) - building_seconds(records, spans)
    mine = [r for r in in_phases(records, spans)
            if r.stage in metric['kinds']]
    if metric['reads'] == 'count':
        return len(mine)
    return sum(r.self_seconds for r in mine)


def account(module, phases, top=5):
    """What the ledger holds of this run, for a line of diagnostics."""
    whole = module.summary()
    by_phase = {}
    for name, seconds, _, end in phases:
        part = module.summary(since=end - seconds, until=end)
        by_phase[name] = {
            **{k: round(v, 3) for k, v in part['seconds'].items() if v},
            **{k: v for k, v in part['cache'].items() if v}}
    every = [[end - seconds, end] for _, seconds, _, end in phases]
    setup_done = max((end for _, _, counted, end in phases if counted),
                     default=float('inf'))
    late = [[r.name, r.kind, round(r.seconds, 3)]
            for r in module.records(since=setup_done)
            if r.parent is None and not in_phases([r], every)]
    return {'records': whole['records'], 'dropped': whole['dropped'],
            'built_after_setup_in_no_phase': late[:top],
            'folded': whole['folded'],
            'listener_seconds': round(whole['listener_seconds'], 4),
            'cache': whole['cache'], 'by_phase': by_phase,
            'kernels': {k: round(v, 3)
                        for k, v in whole['kernels'].items()},
            'costliest': [[name, round(total, 3),
                           {k: round(v, 3) for k, v in kinds.items()}]
                          for name, total, kinds
                          in module.costliest(whole, top)]}


def read(run, metric):
    module = ledger()
    if module is None:
        return None
    if metric.get('says') == 'ledger':
        print(json.dumps({'build_ledger': account(module, harness.PHASES)}),
              flush=True)
    return reduce(module.records(), harness.PHASES, metric)
