# -*- coding: utf-8 -*-
"""
Compiled-program performance accounting: the compiler/device half of the
observability layer.

The host-side spans/events (PR 5) record what a run *did*; this module
records what its compiled programs *cost* — without touching hardware.
For every entrypoint registered in ``analysis/registry.py`` it lowers
and compiles hermetically (the same 8-virtual-device CPU mesh graphlint
traces on) and extracts:

- XLA ``cost_analysis()``: FLOPs and bytes accessed — the compiler's own
  accounting of the program, independent of any timer floor.
- ``memory_analysis()``: argument/output/temp/alias bytes, the exact
  buffer-assignment footprint of the program.
- Compile wall time and HLO structure counts (collectives by kind,
  fusion count) — a fusion that splits or a collective that multiplies
  is a perf regression even when the numerics stay right.
- Retrace totals (``analysis/retrace.py``) incurred while building the
  snapshot — a registry build that suddenly traces a step twice is the
  round-5 retrace-storm class resurfacing.

From FLOPs and bytes it derives **arithmetic intensity** and classifies
each entry compute- vs bandwidth-bound against the chip's PUBLISHED
peaks (``PEAKS_BY_DEVICE_KIND`` — one table keyed by ``device_kind``,
each row naming its source), giving each program a roofline model time
— the "how fast could this possibly run" column next to every measured
number. A roofline against a live device looks its kind up and raises
on an unknown one; the hermetic CPU-mesh gate names its target chip
(``TARGET_DEVICE_KIND``) instead of asking the device present.

CLI (``scripts/ci.sh`` stage [5/5] drives it)::

    python -m distributed_dot_product_tpu.obs.perf snapshot -o PERF_BASELINE.json
    python -m distributed_dot_product_tpu.obs.perf check --against PERF_BASELINE.json
    python -m distributed_dot_product_tpu.obs.perf report

``snapshot`` writes a schema-versioned JSON baseline; ``check`` exits 1
on per-entry tolerance violations (flops / bytes / peak memory /
compile seconds / retrace counts), naming the offending entry and
metric — and emits ``perf.regression`` events when an event log is
active; ``report`` renders the roofline table. Refresh the committed
baseline after an intentional program change with the ``snapshot``
command above.

These are counts of toy-shape programs compiled for the CPU mesh: a
change detector, never a speed. Speeds come from ``benchmarks/run.py``
on the chip (``PERF.md``).
"""

import dataclasses
import json
import re
import time
from typing import Optional

__all__ = ['PERF_SCHEMA_VERSION', 'HardwarePeaks',
           'PEAKS_BY_DEVICE_KIND', 'TARGET_DEVICE_KIND', 'peaks_for',
           'device_peaks', 'Tolerances', 'program_model', 'analyze_spec',
           'snapshot', 'check_snapshots', 'render_report', 'main']

PERF_SCHEMA_VERSION = 1

# Fields compared with a symmetric relative tolerance by `check`.
# argument_bytes is in the set because it is fully determined by the
# registered example shapes/dtypes — a widened cache dtype shows up
# here as an exact 2x, even when fusion jitter muddies bytes_accessed.
_REL_FIELDS = ('flops', 'bytes_accessed', 'argument_bytes',
               'peak_bytes')


@dataclasses.dataclass(frozen=True)
class HardwarePeaks:
    """Roofline ceilings of one chip, with where they were published."""
    flops_per_s: float
    bytes_per_s: float
    source: str

    @property
    def ridge_flops_per_byte(self) -> float:
        """Arithmetic intensity at which the roofline knee sits: above
        it a program can saturate the MXU, below it HBM is the wall."""
        return self.flops_per_s / self.bytes_per_s

    def as_dict(self):
        return {'flops_per_s': self.flops_per_s,
                'bytes_per_s': self.bytes_per_s,
                'ridge_flops_per_byte': self.ridge_flops_per_byte,
                'source': self.source}


# Published per-chip peaks keyed by ``jax.Device.device_kind``. A kind
# that is not here is an error, never a default: add the row with its
# source when the code meets a new chip.
PEAKS_BY_DEVICE_KIND = {
    'TPU v5 lite': HardwarePeaks(
        flops_per_s=197e12, bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 819 GB/s HBM per chip'),
}

# The chip the hermetic CPU-mesh gate models its programs for.
TARGET_DEVICE_KIND = 'TPU v5 lite'


def peaks_for(device_kind):
    """Published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f'no published peaks for device kind {device_kind!r} '
            f'(known: {sorted(PEAKS_BY_DEVICE_KIND)}) — a roofline is '
            f'taken against a known chip; add its row, with its '
            f'source, to obs/perf.py PEAKS_BY_DEVICE_KIND') from None


def device_peaks():
    """Peaks of the device this process runs on."""
    import jax
    return peaks_for(jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class Tolerances:
    """Per-entry gate widths for :func:`check_snapshots`. ``rel`` bounds
    flops / bytes / peak-memory drift symmetrically (CPU-mesh lowering
    is deterministic for a fixed jax version, but the gate must survive
    fusion-order jitter across point releases); compile time passes
    while ``current <= baseline * compile_factor + compile_slack_s``
    (machines differ — only an order-of-magnitude blowup is a finding);
    retrace totals allow ``retrace_slack`` extra traces (default 0: one
    extra trace of a cached step IS the regression). ``abs_floor``
    exempts absolute drifts below it (units of the compared field):
    the smallest registered entries are a few KiB total, where a
    single re-fused buffer moves the relative number by half without
    meaning anything at real scale."""
    rel: float = 0.25
    compile_factor: float = 10.0
    compile_slack_s: float = 5.0
    retrace_slack: int = 0
    abs_floor: float = 64 * 1024.0


# -- program-level extraction -------------------------------------------

_HLO_COLLECTIVES = ('all-gather', 'all-reduce', 'collective-permute',
                    'all-to-all', 'reduce-scatter',
                    'collective-broadcast')


def _hlo_counts(hlo_text):
    """Collective call sites by kind (async ``-start`` forms folded into
    their base op) and fusion count from compiled HLO text."""
    coll = {}
    for op in _HLO_COLLECTIVES:
        n = len(re.findall(rf'\b{re.escape(op)}(?:-start)?\(', hlo_text))
        if n:
            coll[op] = n
    fusions = len(re.findall(r'\bfusion\(', hlo_text))
    return coll, fusions


def program_model(compiled, *, measured_seconds=None, peaks=None):
    """Cost/roofline model of one compiled XLA program, as a plain JSON-
    serializable dict — one entry of a snapshot. ``peaks=None`` takes the roofline against
    the LIVE device (:func:`device_peaks` — raises on a device kind
    with no published peaks, e.g. a CPU). Returns None for a program
    the compiler counts no work in.

    With ``measured_seconds``, also derives the model-vs-measured
    columns: achieved GFLOP/s and GB/s over the *compiler-counted*
    flops/bytes (as opposed to the analytic FLOP formulas the benchmark
    rows already carry) and the measured/model time ratio (1.0 = the
    program runs at its roofline)."""
    peaks = peaks or device_peaks()
    cost = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    mem = {
        'argument_bytes': ma.argument_size_in_bytes,
        'output_bytes': ma.output_size_in_bytes,
        'temp_bytes': ma.temp_size_in_bytes,
        'alias_bytes': ma.alias_size_in_bytes,
    }
    flops = float(cost.get('flops', 0.0) or 0.0)
    nbytes = float(cost.get('bytes accessed', 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    ai = (flops / nbytes) if nbytes else float('inf')
    roofline = ('compute-bound' if ai >= peaks.ridge_flops_per_byte
                else 'bandwidth-bound')
    model_s = max(flops / peaks.flops_per_s, nbytes / peaks.bytes_per_s)
    out = {
        'flops': flops,
        'bytes_accessed': nbytes,
        'arithmetic_intensity': ai,
        'roofline': roofline,
        'model_seconds': model_s,
        **mem,
        'peak_bytes': (mem['argument_bytes'] + mem['output_bytes']
                       + mem['temp_bytes'] - mem['alias_bytes']),
    }
    if measured_seconds and measured_seconds > 0:
        out['measured_seconds'] = measured_seconds
        out['measured_gflops_per_s'] = flops / measured_seconds / 1e9
        out['measured_gb_per_s'] = nbytes / measured_seconds / 1e9
        out['fraction_of_roofline'] = model_s / measured_seconds
    return out


# -- entrypoint-level analysis ------------------------------------------

def _lower_spec(spec):
    """Lower a TraceSpec the way its declaration asks (mirrors the
    donation rule in analysis/jaxpr_rules.py, so the program analyzed
    here is the program the linter certifies)."""
    import jax
    if spec.prejitted:
        return spec.fn.lower(*spec.args)
    return jax.jit(
        spec.fn,
        donate_argnums=spec.donate_argnums or (),
        static_argnums=spec.static_argnums or (),
    ).lower(*spec.args)


def analyze_spec(spec, *, peaks=None):
    """Compile one registered entrypoint and return its cost record.
    Never raises for a broken entry: the record then carries an
    ``error`` field (check treats that as a violation, mirroring the
    jaxpr linter's trace-error isolation)."""
    peaks = peaks or peaks_for(TARGET_DEVICE_KIND)
    t0 = time.perf_counter()
    try:
        compiled = _lower_spec(spec).compile()
    except Exception as e:  # graphlint: allow[silent-except]
        msg = str(e).splitlines()[0] if str(e) else repr(e)
        return {'error': f'lower/compile failed: {msg}'}  # reported, not lost
    compile_s = time.perf_counter() - t0
    rec = program_model(compiled, peaks=peaks)
    if rec is None:
        return {'error': 'the compiler counted no flops or bytes'}
    coll, fusions = _hlo_counts(compiled.as_text())
    rec.update(compile_seconds=compile_s, collectives=coll,
               n_collectives=sum(coll.values()), n_fusions=fusions)
    return rec


def _build_entry(name, build):
    """Builder → spec with the registry-name override the linter also
    applies; builder failures become error records."""
    spec = build()
    if spec.name != name:
        spec = spec.replace(name=name)
    return spec


def snapshot(entrypoints=None, *, peaks=None):
    """Compile every registered entrypoint and return the schema-
    versioned snapshot dict ``check``/``report`` consume. Retrace totals
    are recorded as the *delta* incurred while building this snapshot,
    so the number is deterministic regardless of what the process traced
    before."""
    import jax

    from distributed_dot_product_tpu.analysis import retrace
    from distributed_dot_product_tpu.analysis.registry import (
        default_entrypoints,
    )
    peaks = peaks or peaks_for(TARGET_DEVICE_KIND)
    if entrypoints is None:
        entrypoints = default_entrypoints()

    # retrace.totals() spans live AND retired counters, so the
    # before/after diff is immune to GC timing and to whatever the
    # process traced (and discarded) before this snapshot began.
    before = retrace.totals()
    entries = {}
    for name, build in entrypoints.items():
        try:
            spec = _build_entry(name, build)
        except Exception as e:  # graphlint: allow[silent-except]
            msg = str(e).splitlines()[0] if str(e) else repr(e)
            entries[name] = {'error': f'builder failed: {msg}'}  # reported
            continue
        entries[name] = analyze_spec(spec, peaks=peaks)
    after = retrace.totals()
    retrace_totals = {
        name: after[name] - before.get(name, 0)
        for name in sorted(after)
    }
    return {
        'schema': PERF_SCHEMA_VERSION,
        'created_unix': time.time(),
        'jax_version': jax.__version__,
        'platform': jax.devices()[0].platform,
        'n_devices': len(jax.devices()),
        'peaks': peaks.as_dict(),
        'entries': entries,
        'retrace_totals': retrace_totals,
    }


# -- the regression gate ------------------------------------------------

def check_snapshots(current, baseline, *, tol: Optional[Tolerances] = None,
                    emit_events=True):
    """Compare a current snapshot against a baseline; returns a list of
    human-readable violation strings (empty = gate passes). Every
    violation also lands in the active observability event log as a
    ``perf.regression`` event (when one is active), so a CI run's
    findings share the durable stream with everything else."""
    tol = tol or Tolerances()
    violations = []

    def _flag(entry, metric, msg, cur=None, base=None):
        violations.append(f'{entry}: {metric}: {msg}')
        if emit_events:
            from distributed_dot_product_tpu.obs import events
            if events.get_active() is not None:
                events.emit('perf.regression', entry=entry, metric=metric,
                            current=cur, baseline=base, detail=msg)

    for snap, label in ((current, 'current'), (baseline, 'baseline')):
        if snap.get('schema') != PERF_SCHEMA_VERSION:
            return [f'<snapshot>: schema: {label} snapshot has schema='
                    f'{snap.get("schema")!r} (expected '
                    f'{PERF_SCHEMA_VERSION}) — refresh it with '
                    f'`perf snapshot`']

    base_entries = baseline.get('entries', {})
    cur_entries = current.get('entries', {})
    for name, base in base_entries.items():
        cur = cur_entries.get(name)
        if cur is None:
            _flag(name, 'coverage', 'entry present in the baseline but '
                  'missing from the current snapshot (deregistered? '
                  'refresh the baseline if intentional)')
            continue
        if 'error' in cur:
            _flag(name, 'error', cur['error'])
            continue
        if 'error' in base:
            # The baseline itself recorded a failure; a now-working
            # entry is an improvement — require a refresh, not a pass,
            # so the baseline never rots silently.
            _flag(name, 'error', f'baseline recorded an error '
                  f'({base["error"]}) — refresh the baseline')
            continue
        for field in _REL_FIELDS:
            b, c = float(base[field]), float(cur[field])
            limit = max(tol.rel * abs(b), tol.abs_floor)
            if abs(c - b) > limit:
                _flag(name, field,
                      f'{c:,.0f} vs baseline {b:,.0f} '
                      f'(|Δ|={abs(c - b):,.0f} > ±{limit:,.0f} at '
                      f'rel tol {tol.rel})', cur=c, base=b)
        b, c = float(base['compile_seconds']), float(cur['compile_seconds'])
        limit = b * tol.compile_factor + tol.compile_slack_s
        if c > limit:
            _flag(name, 'compile_seconds',
                  f'{c:.2f}s vs baseline {b:.2f}s (limit {limit:.2f}s '
                  f'= x{tol.compile_factor} + {tol.compile_slack_s}s)',
                  cur=c, base=b)
    for name in cur_entries:
        if name not in base_entries:
            _flag(name, 'coverage', 'entry not in the baseline — refresh '
                  'PERF_BASELINE.json (`perf snapshot -o '
                  'PERF_BASELINE.json`) in the same change that '
                  'registered it')

    base_rt = baseline.get('retrace_totals', {})
    cur_rt = current.get('retrace_totals', {})
    for name, b in base_rt.items():
        c = cur_rt.get(name, 0)
        if c > b + tol.retrace_slack:
            _flag(name, 'retrace_total',
                  f'{c} traces during snapshot vs baseline {b} '
                  f'(+{tol.retrace_slack} allowed) — a cached step is '
                  f'being rebuilt (the round-5 retrace-storm class)',
                  cur=c, base=b)
    for name, c in cur_rt.items():
        # Current-only watcher names gate against an implicit baseline
        # of 0 — a storm under a NEW counter name must not slip past
        # the gate it was built for (the entry gate already demands a
        # baseline refresh for new registrations; same discipline).
        if name not in base_rt and c > tol.retrace_slack:
            _flag(name, 'retrace_total',
                  f'{c} traces during snapshot under a name not in '
                  f'the baseline — refresh PERF_BASELINE.json in the '
                  f'same change that added the watcher',
                  cur=c, base=0)
    return violations


# -- reporting ----------------------------------------------------------

def _si(value, unit=''):
    for scale, suffix in ((1e12, 'T'), (1e9, 'G'), (1e6, 'M'),
                          (1e3, 'K')):
        if abs(value) >= scale:
            return f'{value / scale:.2f} {suffix}{unit}'
    return f'{value:.0f} {unit}'.rstrip()


def render_report(snap):
    """Roofline table over a snapshot: one line per entry — compiler-
    counted FLOPs/bytes, arithmetic intensity, the bound classification
    and the roofline model time at the snapshot's peaks."""
    peaks = snap.get('peaks') or peaks_for(TARGET_DEVICE_KIND).as_dict()
    head = (f'perf snapshot: {len(snap.get("entries", {}))} entrypoints '
            f'on {snap.get("platform")}[{snap.get("n_devices")}] '
            f'jax {snap.get("jax_version")}\n'
            f'roofline peaks: '
            f'{peaks["flops_per_s"] / 1e12:.0f} TF/s, '
            f'{peaks["bytes_per_s"] / 1e9:.0f} GB/s '
            f'(ridge {peaks["ridge_flops_per_byte"]:.0f} FLOP/byte)')
    rows = [f'{"entrypoint":34} {"flops":>10} {"bytes":>10} '
            f'{"FLOP/B":>7} {"bound":>10} {"model µs":>9} '
            f'{"peak KiB":>9} {"coll":>4} {"fus":>4} {"compile":>8}']
    for name, e in sorted(snap.get('entries', {}).items()):
        if 'error' in e:
            rows.append(f'{name:34} ERROR: {e["error"]}')
            continue
        bound = e['roofline'].replace('-bound', '')
        rows.append(
            f'{name:34} {_si(e["flops"]):>10} '
            f'{_si(e["bytes_accessed"], "B"):>10} '
            f'{e["arithmetic_intensity"]:7.2f} {bound:>10} '
            f'{e["model_seconds"] * 1e6:9.2f} '
            f'{e["peak_bytes"] / 1024:9.1f} '
            f'{e["n_collectives"]:4d} {e["n_fusions"]:4d} '
            f'{e["compile_seconds"]:7.2f}s')
    rt = snap.get('retrace_totals', {})
    tail = ('retrace totals during snapshot: '
            + (' '.join(f'{k}={v}' for k, v in sorted(rt.items()))
               if rt else '(none watched)'))
    return '\n'.join([head, ''] + rows + ['', tail])


# -- CLI ----------------------------------------------------------------

def _fresh_snapshot(args):
    entrypoints = None
    if args.registry:
        from distributed_dot_product_tpu.analysis.registry import (
            resolve_registry_arg,
        )
        try:
            entrypoints = resolve_registry_arg(args.registry)
        except ValueError as e:
            raise SystemExit(str(e))
    return snapshot(entrypoints)


def _cmd_snapshot(args):
    snap = _fresh_snapshot(args)
    text = json.dumps(snap, indent=2, sort_keys=True, default=str)
    if args.out in (None, '-'):
        print(text)
    else:
        with open(args.out, 'w') as f:
            f.write(text + '\n')
        n_err = sum('error' in e for e in snap['entries'].values())
        print(f'perf snapshot: {len(snap["entries"])} entrypoints '
              f'({n_err} errored) -> {args.out}')
    return 0


def _cmd_check(args):
    with open(args.against) as f:
        baseline = json.load(f)
    if args.current:
        with open(args.current) as f:
            current = json.load(f)
    else:
        current = _fresh_snapshot(args)
    tol = Tolerances(rel=args.rel_tol,
                     compile_factor=args.compile_factor,
                     compile_slack_s=args.compile_slack,
                     retrace_slack=args.retrace_slack,
                     abs_floor=args.abs_floor)
    violations = check_snapshots(current, baseline, tol=tol)
    for v in violations:
        print(f'PERF REGRESSION: {v}')
    n = len(current.get('entries', {}))
    print(f'perf check: {n} entrypoints vs {args.against}: '
          + ('OK' if not violations
             else f'{len(violations)} violation'
                  f'{"s" if len(violations) != 1 else ""}'))
    return 1 if violations else 0


def _cmd_report(args):
    if args.snapshot_file:
        with open(args.snapshot_file) as f:
            snap = json.load(f)
    else:
        snap = _fresh_snapshot(args)
    print(render_report(snap))
    return 0


def main(argv=None):
    import argparse
    import os

    parser = argparse.ArgumentParser(
        prog='python -m distributed_dot_product_tpu.obs.perf',
        description='compiled-program cost/roofline accounting and the '
                    'perf-regression gate')
    parser.add_argument('--registry', metavar='MODULE:ATTR',
                        help='analyze this {name: builder} mapping '
                             'instead of the central registry (the '
                             'seeded-regression tests drive the gate '
                             'through fixtures this way)')
    sub = parser.add_subparsers(dest='cmd', required=True)

    s = sub.add_parser('snapshot', help='compile every entrypoint and '
                                        'write the cost snapshot')
    s.add_argument('-o', '--out', default=None,
                   help='output JSON path (default: stdout)')
    s.set_defaults(fn=_cmd_snapshot)

    c = sub.add_parser('check', help='gate a snapshot against a baseline '
                                     '(exit 1 on violations)')
    c.add_argument('--against', required=True,
                   help='baseline snapshot JSON (the committed '
                        'PERF_BASELINE.json in CI)')
    c.add_argument('--current', default=None,
                   help='pre-computed current snapshot JSON (default: '
                        'compile a fresh one)')
    c.add_argument('--rel-tol', type=float, default=Tolerances.rel,
                   help='relative tolerance on flops/bytes/peak-memory')
    c.add_argument('--compile-factor', type=float,
                   default=Tolerances.compile_factor)
    c.add_argument('--compile-slack', type=float,
                   default=Tolerances.compile_slack_s)
    c.add_argument('--retrace-slack', type=int,
                   default=Tolerances.retrace_slack)
    c.add_argument('--abs-floor', type=float,
                   default=Tolerances.abs_floor,
                   help='ignore absolute drifts below this (field '
                        'units) — keeps KiB-scale entries from '
                        'tripping on fusion jitter')
    c.set_defaults(fn=_cmd_check)

    r = sub.add_parser('report', help='render the roofline table')
    r.add_argument('snapshot_file', nargs='?', default=None,
                   help='render this snapshot JSON (default: compile a '
                        'fresh one)')
    r.set_defaults(fn=_cmd_report)

    args = parser.parse_args(argv)

    needs_devices = not (
        (args.cmd == 'check' and args.current)
        or (args.cmd == 'report' and args.snapshot_file))
    if needs_devices:
        # Hermetic platform, forced BEFORE jax commits to a backend —
        # same everywhere (TPU host, CI runner, laptop), so snapshots
        # and baselines are comparable by construction.
        os.environ.setdefault('JAX_PLATFORMS', 'cpu')
        from distributed_dot_product_tpu._compat import ensure_cpu_devices
        ensure_cpu_devices(8)

    return args.fn(args)


if __name__ == '__main__':
    import sys
    sys.exit(main())
