"""Prefill's device time by scope: what a ``perf_opt`` on ``setup_s``
starts from. A cell's set-up is mostly prefill (Ling 136 of 165 s, SALA
112 of 124 s) and the benchmark's traced window never holds it, so this
tool puts the profiler around SET-UP instead.

It builds the named cell through the cell's OWN driver (``Server(cell,
seed).load()``: the driver's programs, its prefill loop, its phases) on
an in-memory copy of the traffic with the sessions cut to ``SESSIONS``
(a session's prefill program has one session's shape whatever the
count), takes the device operations that ran inside the prefill
programs' executions alone — the program's build ledger
(``utils/build_ledger.py``) names the programs the process built, and
those names are the trace's ``XLA Modules`` names — and prints one JSON
line:

- device ms a prefill call by scope, through the cell's own patterns
  file (the one of its readers' that knows most scopes) and
  ``benchmarks/scopes.py`` as the readers use it, joined by (program,
  instruction): only the prefill programs' own HLO is read, so two
  programs that number their fusions alike cannot clash;
- the device's idle share between a session's first and last prefill
  operation;
- the seconds a call takes on the host clock (the driver's ``prefill``
  phase over its calls), and what the ledger says building the program
  cost.

    python3 benchmarks/tools/trace_prefill.py \
        --workload ling-3.0-flash.decode-32k --seed 48001

On the chip only (exit 2 elsewhere): a CPU number never stands under a
device metric. The trace stays under ``.bench_trace/<cell>.prefill/``
(``tools/trace_inventory.py`` shows its planes and lines).
"""

import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MODULE_LINE = 'XLA Modules'
# Two sessions show a session's calls and the gap between sessions; every
# decode driver names its prefill program ``prefill_fn``.
SESSIONS = 2
PREFILL = re.compile('prefill')
_MODULE = re.compile(r'^(?:jit_)?(.+?)(?:\(\d+\))?$')


def program_of(module_name):
    """``prefill_fn`` from the trace's ``jit_prefill_fn(1234567)``."""
    return _MODULE.match(module_name).group(1)


def cell_patterns(cell):
    """The scope patterns of the cell's reader that knows most classes
    (the accepted ``scope_patterns.json`` where none brings its own)."""
    from benchmarks import scopes
    best = scopes.patterns()
    for name in sorted({m['reducer'] for m in cell.per_layer()}):
        module = cell.reducer(name)
        if hasattr(module, 'patterns'):
            pats = module.patterns()
            if len(pats['classes']) > len(best['classes']):
                best = pats
    return best


def module_events(plane):
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for line in plane.lines if line.name == MODULE_LINE
            for e in line.events]


def inside(ops, spans):
    """The rows of ``ops`` that start inside one of the sorted,
    disjoint ``spans``."""
    out, j = [], 0
    for row in sorted(ops, key=lambda r: r[1]):
        while j < len(spans) and spans[j][1] <= row[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= row[1]:
            out.append(row)
    return out


def reduce(path, cell, wanted, calls_a_session, top=12):
    """The prefill programs' device time from the trace at ``path``."""
    from jax.profiler import ProfileData

    from benchmarks import scopes, trace as tr
    pats, trace_pats = cell_patterns(cell), tr.patterns()
    data = ProfileData.from_file(path)
    plane = next((p for p in data.planes
                  if re.search(trace_pats['device_plane'], p.name)), None)
    mods = [m for m in module_events(plane)
            if wanted.search(program_of(m[0]))] if plane else []
    if not mods:
        return None
    mods.sort(key=lambda m: m[1])
    spans = [[s, s + d] for _, s, d in mods]
    ops = tr.load_xplane(path, trace_pats)['devices'][plane.name]
    mine = inside(ops, spans)
    # (program, instruction) -> class: the prefill programs' HLO alone
    with open(path, 'rb') as f:
        raw = f.read()
    mapping = {}
    for program, proto in scopes.hlo_protos(raw):
        if wanted.search(program_of(program)):
            for name, (op_name, _) in scopes.op_names(proto).items():
                mapping[name] = scopes.classify(op_name, pats)[0]
    by_scope, by_op = {}, {}
    for op, _, _, own in mine:
        if tr.op_class(op, trace_pats) == 'collective':
            continue
        cls = mapping.get(scopes.instruction_of(op), scopes.UNATTRIBUTED)
        by_scope[cls] = by_scope.get(cls, 0) + own
        by_op[(op, cls)] = by_op.get((op, cls), 0) + own
    calls = len(mods)
    ms = 1e-6 / calls
    idle = []
    for i in range(0, calls - calls_a_session + 1, calls_a_session):
        start, end = spans[i][0], spans[i + calls_a_session - 1][1]
        busy = tr.length(tr.union(
            [max(s, start), min(s + d, end)] for _, s, d, _ in ops
            if d > 0 and s < end and s + d > start))
        idle.append(100.0 * (1.0 - busy / (end - start)))
    return {
        'programs': sorted({program_of(m[0]) for m in mods}),
        'calls': calls, 'calls_a_session': calls_a_session,
        'module_ms_a_call': sum(d for _, _, d in mods) * ms,
        'device_ms_a_call': sum(by_scope.values()) * ms,
        'by_scope_ms_a_call': {k: v * ms for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        'idle_pct_by_session': idle,
        'top_ops_ms_a_call': [[op, cls, v * ms] for (op, cls), v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmarks import harness, loader
    from distributed_dot_product_tpu.utils import build_ledger
    from distributed_dot_product_tpu.utils.compile_cache import (
        setup_compile_cache,
    )
    cell = loader.Cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell.chips:
        print(f'trace_prefill.py: {args.workload} needs {cell.chips} TPU '
              f'chip(s); JAX found {len(devices)} x {devices[0].platform}',
              file=sys.stderr)
        return 2
    setup_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    cell.traffic = dict(cell.traffic, sessions=SESSIONS)
    t = cell.traffic
    directory = os.path.join(ROOT, '.bench_trace', f'{cell.name}.prefill')
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    server = cell.driver().Server(cell, args.seed)
    # Set-up is mostly Python under jit: the profiler's own Python
    # tracer would record every frame of it.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        server.load()
    finally:
        jax.profiler.stop_trace()
    path = harness.Tracer(directory).xplane_path()
    built = {name: {k: round(v, 3) for k, v in kinds.items()}
             for name, kinds in build_ledger.summary()['programs'].items()
             if name and PREFILL.search(name)}
    # One call a chunk where sessions are prefilled alone, else one a
    # chunk for all of them together (the dense cell).
    chunks = -(-t['context'] // t['prefill_chunk'])
    reduced = reduce(path, cell, PREFILL, chunks)
    phases = {name: round(seconds, 3)
              for name, seconds, _, _ in harness.PHASES}
    line = {'workload': cell.name, 'seed': args.seed,
            'sessions': SESSIONS,
            'device': {'platform': devices[0].platform,
                       'kind': devices[0].device_kind},
            'phases_s': phases, 'built_s': built}
    if reduced is not None:
        line.update(reduced)
        line['host_s_a_call'] = phases.get('prefill', 0.0) / reduced['calls']
    print(json.dumps({'prefill': line}), flush=True)
    return 0 if reduced is not None else 1


if __name__ == '__main__':
    sys.exit(main())
