# -*- coding: utf-8 -*-
"""
Seeded load driver for the serving layer — VIRTUAL time: a behaviour
check, never a speed.

A seeded open-loop trace (Poisson / bursty / ramp / step arrivals,
heavy-tailed per-tenant length mixes) drives a ``Scheduler``, or with
``--topology PxD`` the disaggregated router AND its single-process
twin, on a ``VirtualClock``: one scheduler tick costs ``--load-tick``
simulated seconds whatever the host or the device took. The goodput
report is then computed from the JSONL event log ALONE
(``obs/slo.py``). What this proves is determinism, accounting and
recovery: every submitted request reconstructs exactly once, recovered
and healed streams are bit-identical to the crash-free twin, the
controller holds the per-tenant floors under a ramp. Goodput,
percentiles and rates printed here are functions of the trace and the
tick, not of any machine. Speeds come from ``python3 benchmarks/run.py
--workload W`` on the chip and are written in ``PERF.md`` /
``PERF_LEDGER.jsonl``.

The constants below and the flag defaults ARE the smoke configuration
``SLO_BASELINE.json`` was made from (``scripts/ci.sh`` runs this bare
and gates its log against that file): changing one is a baseline
refresh.

    JAX_PLATFORMS=cpu python examples/serve_load.py --event-log /tmp/slo.jsonl
    JAX_PLATFORMS=cpu python examples/serve_load.py --topology 1x2 \\
        --chaos --event-log /tmp/chaos_logs --file /tmp/row.json
"""

import argparse
import dataclasses
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

from distributed_dot_product_tpu import obs  # noqa: E402
from distributed_dot_product_tpu.obs import critpath  # noqa: E402
from distributed_dot_product_tpu.obs import flight as obs_flight  # noqa: E402
from distributed_dot_product_tpu.obs import slo as obs_slo  # noqa: E402
from distributed_dot_product_tpu.serve import (  # noqa: E402
    ChaosSchedule, ControlConfig, Controller, KernelEngine, LoadGenConfig,
    RouterConfig, Scheduler, ServeConfig, TopologyConfig, VirtualClock,
    build_serving, default_tenants, generate_trace, load_trace,
    parse_topology, run_load, run_trace, save_trace,
)
from distributed_dot_product_tpu.utils.compile_cache import (  # noqa: E402
    setup_compile_cache,
)
from distributed_dot_product_tpu.utils.faults import (  # noqa: E402
    ChaosInjector, ChaosPlan,
)
from distributed_dot_product_tpu.utils.tracing import (  # noqa: E402
    MetricsRegistry,
)

SEED = 7                  # trace seed: same seed = same trace and report
TENANTS = 2               # the stock interactive/batchy mix
SLOTS, T_MAX = 4, 96
HEADS, HEAD_DIM, VOCAB = 8, 64, 64
PAGE_SIZE = 16            # --topology: the replicas' paged pools
QUEUE_LIMIT = 12          # admission queue bound (the overload ladder)
PREFILL_THRESHOLD = 8     # --topology: rows at which a prompt offloads
CHAOS_TICK = 40           # --chaos: virtual tick at which the victim dies
MAX_REPLICAS = 3          # --control + --topology: autoscaling ceiling


def parse_args():
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument('--load-rate', type=float, default=600.0,
                        help='aggregate offered rate, requests per '
                             'VIRTUAL second (the default runs the '
                             'stock engine at ~85%% goodput)')
    parser.add_argument('--load-requests', type=int, default=48,
                        help='trace length')
    parser.add_argument('--arrival',
                        choices=['poisson', 'bursty', 'ramp', 'step'],
                        default='poisson',
                        help='arrival process (ramp/step climb the rate '
                             'toward rate*ramp-factor across the trace)')
    parser.add_argument('--ramp-factor', type=float, default=4.0,
                        help='--arrival ramp/step: peak rate multiple')
    parser.add_argument('--load-tick', type=float, default=0.002,
                        help='virtual seconds one scheduler tick costs')
    parser.add_argument('--slo-ttft', type=float, default=0.25,
                        help='TTFT deadline (virtual s)')
    parser.add_argument('--slo-token', type=float, default=0.05,
                        help='max inter-token gap (virtual s)')
    parser.add_argument('--control', action='store_true',
                        help='arm the closed-loop controller '
                             '(serve/control.py) on the virtual clock; '
                             'with --topology it also autoscales the '
                             'decode pool')
    parser.add_argument('--event-log', default=None,
                        help='the JSONL event log the report is '
                             'computed from (default: a temp file). '
                             'With --topology the log DIRECTORY: one '
                             'log per member (router/prefill/r0/... + '
                             'twin)')
    parser.add_argument('--topology', default=None,
                        help="run the trace through a 'PxD' topology (P "
                             'prefill pools x D decode replicas, e.g. '
                             '1x2) behind the router AND through its '
                             'single-process twin')
    parser.add_argument('--chaos', action='store_true',
                        help='--topology: kill --chaos-victim at tick '
                             f'{CHAOS_TICK}, require every in-flight '
                             'stream recovered bit-identical to the '
                             'crash-free twin, and compare with a '
                             'max_recoveries=0 twin of the same crash')
    parser.add_argument('--chaos-victim', default='r1',
                        help='--chaos / --chaos-corrupt: the replica')
    parser.add_argument('--chaos-corrupt', default=None,
                        metavar='PAGE:TICK',
                        help='--topology: flip one bit in tracked page '
                             'PAGE of --chaos-victim at tick TICK, '
                             'require it detected before any poisoned '
                             'token is delivered, and count the '
                             'silently wrong streams of a checksums-off '
                             'twin')
    parser.add_argument('--file', default=None,
                        help='append the run\'s row to this JSON file')
    return parser.parse_args()


def _append_record(path, record):
    if path is None:
        return
    results = []
    if os.path.exists(path):
        with open(path) as f:
            results = json.load(f)
    results.append(record)
    with open(path, 'w') as f:
        json.dump(results, f, indent=2)


def _dispatch_split(registry, n_tokens):
    """Dispatch-floor columns from a scheduler run's registry: the
    host-dispatch vs device-compute split the scheduler's per-tick
    accounting observed (``serve.dispatch_overhead_seconds`` /
    ``serve.device_seconds`` histograms — the same numbers /metrics
    exports and ``obs critpath`` folds from serve.dispatch events).
    REAL seconds of whatever machine ran the check: reporting only.
    Empty dict when the scheduler recorded no decode ticks."""
    h_over = registry.peek('histogram',
                           'serve.dispatch_overhead_seconds')
    h_dev = registry.peek('histogram', 'serve.device_seconds')
    if h_over is None or not h_over.total_count:
        return {}
    over_s = h_over.total_sum
    dev_s = h_dev.total_sum if h_dev is not None else 0.0
    tick_s = over_s + dev_s
    return {
        'dispatch_ticks': h_over.total_count,
        'dispatch_overhead_s': over_s,
        'dispatch_device_s': dev_s,
        'dispatch_overhead_pct': (100.0 * over_s / tick_s
                                  if tick_s > 0 else None),
        'dispatch_overhead_ms_per_token': (over_s / n_tokens * 1e3
                                           if n_tokens else None),
        'dispatch_overhead_p99_ms': h_over.percentile(99) * 1e3,
    }


def _loadgen_config(args):
    return LoadGenConfig(
        seed=SEED, rate=args.load_rate, requests=args.load_requests,
        arrival=args.arrival, ramp_factor=args.ramp_factor,
        tenants=default_tenants(TENANTS), vocab=VOCAB,
        tick_seconds=args.load_tick)


def _serve_config(cfg):
    return ServeConfig(
        queue_limit=QUEUE_LIMIT,
        max_new_tokens=max(t.new_hi for t in cfg.tenants),
        watchdog=False, spec='off')


def _fresh_member_logs(log_dir, prefill_pools, twin=False):
    """EventLog APPENDS (resuming seq), and a stale previous run would
    double every merged timeline. Decode-member logs sweep by GLOB:
    autoscaling (--control) names replicas with a never-reused
    sequence, so a scale-down/up cycle can leave rN.jsonl files past
    any configured ceiling."""
    os.makedirs(log_dir, exist_ok=True)
    for name in ['router'] + (['prefill'] if prefill_pools else []) \
            + (['twin'] if twin else []):
        obs.remove_log(os.path.join(log_dir, f'{name}.jsonl'))
    for stale in glob.glob(os.path.join(log_dir, 'r[0-9]*.jsonl')):
        obs.remove_log(stale)


def _diverged(results, twin_results):
    """``(compared, mismatched ids)``: greedy streams are prompt-pure,
    so EVERY delivered token must match the crash-free twin's stream
    PREFIX — whatever either run's terminal was (an evicted/expired
    stream's delivered tokens are still delivered)."""
    compared, mismatched = 0, []
    for rid, a in results.items():
        b = twin_results.get(rid)
        if b is None:
            continue
        n = min(len(a.tokens), len(b.tokens))
        if n:
            compared += 1
            if list(a.tokens)[:n] != list(b.tokens)[:n]:
                mismatched.append(rid)
    return compared, sorted(mismatched)


def run_serve_load_topology(args):
    """``--topology 1x2``: the SAME seeded trace (serialized to
    ``trace.json`` and read back — both runs consume the byte-identical
    file) drives (a) the router over a P-prefill-pool /
    D-decode-replica topology (each replica its own paged engine +
    scheduler + event log; long prompts prefill sequence-sharded across
    the mesh and hand off as pool pages) and (b) the single-process
    twin (ONE replica's engine behind one scheduler). Goodput for the
    topology is computed over the MERGED per-member logs — the run
    asserts every submitted request reconstructs exactly once across
    them — and the twin's over its own log; the row records both plus
    the routing telemetry (per-replica placements, prefix hits,
    handoffs)."""
    prefill_pools, decode_replicas = parse_topology(args.topology)
    log_dir = args.event_log or tempfile.mkdtemp(
        prefix='ddp_serve_topo_')
    _fresh_member_logs(log_dir, prefill_pools, twin=True)
    cfg = _loadgen_config(args)
    trace_path = os.path.join(log_dir, 'trace.json')
    save_trace(trace_path, generate_trace(cfg))
    serve_cfg = _serve_config(cfg)
    # The twin must run the STATIC config: the controller actuates
    # knobs by mutating the schedulers' (shared) ServeConfig, so a
    # controlled run would otherwise leak its final tightened
    # watermark into the twin built afterwards.
    twin_cfg = dataclasses.replace(serve_cfg)
    topo = TopologyConfig(
        prefill_pools=prefill_pools, decode_replicas=decode_replicas,
        slots=SLOTS, t_max=T_MAX, page_size=PAGE_SIZE, vocab=VOCAB,
        heads=HEADS, head_dim=HEAD_DIM, seed=0, decode_impl=None)
    router_cfg = RouterConfig(prefill_threshold=PREFILL_THRESHOLD)
    chaos = chaos_plan = flight_rec = flight_prev = None
    corrupt_page = corrupt_tick = None
    if args.chaos or args.chaos_corrupt:
        if decode_replicas < 2:
            raise SystemExit(f'--chaos / --chaos-corrupt recover the '
                             f"victim's streams on a SURVIVING replica: "
                             f'the topology needs >= 2, got '
                             f'{args.topology}')
        # Fast probe cadence on the virtual clock: the loss must be
        # declared (and recovery land) inside the trace's own virtual
        # window, not long after the survivors drained.
        router_cfg = dataclasses.replace(
            router_cfg, probe_interval=0.01, probe_backoff_max=0.02)
        plan_kw = {}
        if args.chaos:
            plan_kw['replica_crash'] = (args.chaos_victim, CHAOS_TICK)
        if args.chaos_corrupt:
            try:
                page_s, tick_s = args.chaos_corrupt.split(':')
                corrupt_page, corrupt_tick = int(page_s), int(tick_s)
            except ValueError:
                raise SystemExit(f'--chaos-corrupt wants PAGE:TICK, '
                                 f'got {args.chaos_corrupt!r}')
            plan_kw['page_corrupt'] = (args.chaos_victim, corrupt_page,
                                       corrupt_tick)
            # Scrub every tick: detection latency must be one tick,
            # never a token (transfer/attach sites verify regardless).
            router_cfg = dataclasses.replace(
                router_cfg, integrity_interval=0.0)
        chaos_plan = ChaosPlan(**plan_kw)
        chaos = ChaosInjector(chaos_plan)
        # The black box armed for the whole run: the router's
        # replica_lost / kv_corrupt triggers auto-dump a bundle the
        # moment the fault is declared.
        flight_rec = obs_flight.FlightRecorder(
            os.path.join(log_dir, 'flight'))
        flight_prev = obs_flight.install(flight_rec)
    clock = VirtualClock()
    router = build_serving(
        topo, serve_config=serve_cfg, router_config=router_cfg,
        clock=clock, log_dir=log_dir, chaos=chaos)
    controller = None
    if args.control:
        controller = Controller(
            router=router,
            config=ControlConfig(
                interval=0.01, scale_up_after=1, scale_down_after=20,
                max_replicas=MAX_REPLICAS),
            clock=clock, event_log=router.event_log)
    on_tick = controller.tick if controller else None
    chaos_sched = None
    if chaos is not None:
        on_tick = chaos_sched = ChaosSchedule(chaos, router,
                                              on_tick=on_tick)
    try:
        res = run_trace(router, load_trace(trace_path), clock,
                        tick_seconds=cfg.tick_seconds, on_tick=on_tick)
    finally:
        # Member logs must close (flushing their tails) even when the
        # run under them crashes — those logs ARE the debugging record.
        router.close()
        if flight_rec is not None:
            # Disarm before the twin runs: the bundle must record the
            # chaos run alone, and the no-recovery twin's loss must
            # not be cooldown-shadowed into silence.
            obs_flight.install(flight_prev)
            flight_rec.stop()
    sources = router.pool.logs()
    spec = obs_slo.SloSpec(ttft=args.slo_ttft,
                           per_token=args.slo_token)
    report = obs_slo.goodput(sources, spec)
    if not res.accounted:
        raise SystemExit('serve-load: a submitted request has no '
                         'terminal record across the topology — '
                         'router accounting bug')
    if report.requests != len(res.submitted):
        raise SystemExit(
            f'serve-load: {report.requests} requests classified from '
            f'the merged logs vs {len(res.submitted)} submitted — a '
            f'request reconstructed zero or several times')
    bad = [rid for rid, tl in obs.reconstruct(sources).items()
           if not tl.complete]
    if bad:
        raise SystemExit(
            f'serve-load: {len(bad)} request lifecycle(s) do not '
            f'reconstruct across the merged replica logs: {bad[:5]}')

    # The single-process twin on the identical serialized trace: ONE
    # replica's engine behind one scheduler, its own virtual clock.
    clock_twin = VirtualClock()
    twin_path = os.path.join(log_dir, 'twin.jsonl')
    twin_log = obs.EventLog(twin_path, clock=clock_twin)
    twin_engine = KernelEngine(
        slots=SLOTS, t_max=T_MAX, vocab=VOCAB, heads=HEADS,
        head_dim=HEAD_DIM, prefill_chunk=8, seed=0, decode_impl=None,
        cache_mode='paged', page_size=PAGE_SIZE)
    twin = Scheduler(twin_engine, twin_cfg, clock=clock_twin,
                     event_log=twin_log, fault_injector=False,
                     registry=MetricsRegistry())
    try:
        res_twin = run_trace(twin, load_trace(trace_path), clock_twin,
                             tick_seconds=cfg.tick_seconds)
    finally:
        twin.close()
        twin_log.close()
    report_twin = obs_slo.goodput(twin_path, spec)

    def faulted_twin(sub, twin_topo, twin_router_cfg):
        """SAME trace, SAME fault plan, one knob taken away."""
        sub_dir = os.path.join(log_dir, sub)
        _fresh_member_logs(sub_dir, prefill_pools)
        injector = ChaosInjector(chaos_plan)
        clock_sub = VirtualClock()
        router_sub = build_serving(
            twin_topo, serve_config=dataclasses.replace(twin_cfg),
            router_config=twin_router_cfg, clock=clock_sub,
            log_dir=sub_dir, chaos=injector)
        sched = ChaosSchedule(injector, router_sub)
        try:
            res_sub = run_trace(router_sub, load_trace(trace_path),
                                clock_sub,
                                tick_seconds=cfg.tick_seconds,
                                on_tick=sched)
        finally:
            router_sub.close()
        return (res_sub, sched,
                obs_slo.goodput(router_sub.pool.logs(), spec))

    # What recovery / the integrity layer actually did: the router's log.
    revents = (list(obs.read_events(dict(sources)['router']))
               if chaos is not None else [])
    chaos_extra = {}
    if args.chaos:
        losses = [r for r in revents if r.get('event') == 'replica.lost']
        recovered = [r['request_id'] for r in revents
                     if r.get('event') == 'request.recovered'
                     and r.get('requeued')]
        lost_rejects = [r['request_id'] for r in revents
                        if r.get('event') == 'request.recovered'
                        and not r.get('requeued')]
        probe_events = sum(1 for r in revents
                           if r.get('event') == 'replica.probe')
        if not losses:
            raise SystemExit(
                f'chaos: killing {args.chaos_victim} at tick '
                f'{CHAOS_TICK} never became a replica.lost '
                f'declaration — the probe path is broken')
        if not recovered:
            raise SystemExit(
                f'chaos: replica {args.chaos_victim} died with no '
                f'stream to recover (died at tick {CHAOS_TICK} of '
                f'{res.ticks})')
        if not flight_rec.dumps:
            raise SystemExit('chaos: the replica loss produced no '
                             'flight bundle (trigger replica_lost)')
        # -- bit-identity: a recovered stream IS the crash-free stream.
        # Degradation caps are load policy, not determinism — compare
        # the streams both runs completed uncapped.
        compared, mismatched = 0, []
        for rid in recovered:
            a, b = res.results.get(rid), res_twin.results.get(rid)
            if (a is not None and b is not None
                    and a.status == b.status == 'completed'
                    and not a.degraded and not b.degraded):
                compared += 1
                if list(a.tokens) != list(b.tokens):
                    mismatched.append(rid)
        if mismatched:
            raise SystemExit(
                f'chaos: {len(mismatched)} recovered stream(s) '
                f'diverged from the crash-free twin: '
                f'{mismatched[:5]} — replay-prefill recovery broke '
                f'the determinism contract')
        # -- the no-recovery twin: max_recoveries=0 — every in-flight
        # stream on the victim terminates as a typed REPLICA_LOST
        # reject. What recovery is worth is the goodput gap between
        # these two runs.
        res_norec, _, report_norec = faulted_twin(
            'norec', topo,
            dataclasses.replace(router_cfg, max_recoveries=0))
        if not res_norec.accounted:
            raise SystemExit('chaos: the no-recovery twin dropped a '
                             'request without a typed terminal')
        norec_lost = sorted(
            rid for rid, rr in res_norec.results.items()
            if rr.status == 'rejected'
            and getattr(rr.reason, 'value', rr.reason)
            == 'replica_lost')
        if not norec_lost:
            raise SystemExit('chaos: the no-recovery twin lost the '
                             'same replica yet rejected nothing '
                             'replica_lost — the typed terminal path '
                             'is broken')
        if report.goodput_pct < report_norec.goodput_pct:
            raise SystemExit(
                f'chaos: goodput WITH recovery '
                f'({report.goodput_pct:.1f}%) fell below the '
                f'no-recovery twin ({report_norec.goodput_pct:.1f}%) '
                f'— recovery made things worse')
        chaos_extra = {
            'chaos': {'victim': args.chaos_victim, 'tick': CHAOS_TICK},
            'replica_lost': [r.get('target') for r in losses],
            'recovered': sorted(recovered),
            'recovered_compared': compared,
            'recovered_bitident': compared > 0 and not mismatched,
            'replica_lost_rejects': sorted(lost_rejects),
            'probe_events': probe_events,
            'flight_bundle': flight_rec.dumps[-1]['path'],
            'norec_goodput_pct': report_norec.goodput_pct,
            'norec_counts': report_norec.counts,
            'norec_replica_lost_rejects': norec_lost,
        }

    corrupt_extra = {}
    if args.chaos_corrupt:
        corrupt_events = [r for r in revents
                          if r.get('event') == 'kv.corrupt']
        injected = [r for r in revents
                    if r.get('event') == 'fault.inject'
                    and r.get('kind') == 'page_corrupt']
        healed = [r['request_id'] for r in revents
                  if r.get('event') == 'request.recovered'
                  and r.get('reason') == 'kv_corrupt'
                  and r.get('requeued')]
        corrupt_rejects = [r['request_id'] for r in revents
                           if r.get('event') == 'request.recovered'
                           and r.get('reason') == 'kv_corrupt'
                           and not r.get('requeued')]
        if not chaos_sched.corrupted:
            raise SystemExit(
                f'chaos-corrupt: the bit flip never landed (no '
                f'tracked page on {args.chaos_victim} from tick '
                f'{corrupt_tick} of {res.ticks}) — move the tick into '
                f'the busy part of the trace')
        if not corrupt_events:
            raise SystemExit(
                f'chaos-corrupt: {len(chaos_sched.corrupted)} flip(s) '
                f'landed but NO kv.corrupt verdict was declared — the '
                f'checksum verification path is broken')
        if not flight_rec.dumps:
            raise SystemExit('chaos-corrupt: the corruption produced '
                             'no flight bundle (trigger kv_corrupt)')
        # -- zero silent wrong tokens: a single divergence means a
        # poisoned page decoded into a delivered token.
        compared, mismatched = _diverged(res.results, res_twin.results)
        if mismatched:
            raise SystemExit(
                f'chaos-corrupt: {len(mismatched)} completed '
                f'stream(s) diverged from the crash-free twin: '
                f'{mismatched[:5]} — a corrupted page leaked into a '
                f'delivered token')
        # Verify-time cost, summed across every engine that digested
        # (the row's price-of-integrity column; real seconds).
        verify_seconds = sum(r.engine.verify_seconds
                             for r in router.pool.replicas)
        if router.pool.prefill is not None:
            verify_seconds += router.pool.prefill.engine.verify_seconds
        # -- the no-integrity twin: kv_checksums=False — whatever
        # completes WRONG there is exactly what the checksum layer is
        # worth.
        res_ni, nointeg_sched, report_ni = faulted_twin(
            'nointeg', dataclasses.replace(topo, kv_checksums=False),
            dataclasses.replace(router_cfg, integrity_interval=None))
        if not nointeg_sched.corrupted:
            raise SystemExit('chaos-corrupt: the flip landed in the '
                             'integrity run but not in the '
                             'no-integrity twin — the comparison is '
                             'meaningless')
        _, wrong = _diverged(res_ni.results, res_twin.results)
        corrupt_extra = {
            'chaos_corrupt': {'victim': args.chaos_victim,
                              'page': corrupt_page,
                              'tick': corrupt_tick},
            'corruptions_injected': len(chaos_sched.corrupted),
            'corruptions_detected': len(corrupt_events),
            'corrupt_sites': sorted({str(r.get('site'))
                                     for r in corrupt_events}),
            'corrupt_pages': sorted({int(p) for r in corrupt_events
                                     for p in (r.get('pages') or [])}),
            'corrupt_inject_events': len(injected),
            'corrupt_healed': sorted(healed),
            'corrupt_rejects': sorted(corrupt_rejects),
            'corrupt_compared': compared,
            'corrupt_bitident': compared > 0 and not mismatched,
            'verify_seconds': verify_seconds,
            'flight_bundle': flight_rec.dumps[-1]['path'],
            'nointeg_goodput_pct': report_ni.goodput_pct,
            'nointeg_counts': report_ni.counts,
            'nointeg_wrong_streams': wrong,
        }

    counters = router.registry.snapshot()['counters']
    routed = {}
    for key, n in counters.items():
        # Per-(replica, tenant) labeled series sum to per-replica
        # placement counts: 'router.routed{replica=r0,tenant=t1}'.
        if key.startswith('router.routed{'):
            name = key.split('replica=', 1)[1].split(',')[0].rstrip('}')
            routed[name] = routed.get(name, 0) + n
    record = {
        'clock': 'virtual', 'topology': args.topology,
        'seed': SEED, 'arrival': cfg.arrival,
        'rate_requested': cfg.rate, 'rate_offered': res.offered_rate,
        'requests': report.requests, 'slots': SLOTS, 't_max': T_MAX,
        'page_size': PAGE_SIZE, 'queue_limit': serve_cfg.queue_limit,
        'tick_seconds': cfg.tick_seconds,
        'prefill_threshold': PREFILL_THRESHOLD,
        'platform': jax.devices()[0].platform,
        'slo': spec.to_dict(),
        'goodput_pct': report.goodput_pct,
        'counts': report.counts,
        'per_tenant': {t: tb['goodput_pct']
                       for t, tb in sorted(report.per_tenant.items())},
        'twin_goodput_pct': report_twin.goodput_pct,
        'twin_counts': report_twin.counts,
        'twin_ticks': res_twin.ticks,
        'routed': routed,
        'prefix_hits': counters.get('router.prefix_hits', 0),
        'prefix_misses': counters.get('router.prefix_misses', 0),
        'handoffs': counters.get('router.handoffs', 0),
        'handoff_pages': counters.get('router.handoff_pages', 0),
        'virtual_seconds': res.virtual_seconds,
        'ticks': res.ticks,
        'trace': trace_path,
        'event_logs': dict(sources),
        'control': bool(args.control),
        'control_actions': (list(controller.actions)
                            if controller else []),
        'replicas_final': len(router.pool.replicas),
    }
    # Dispatch-floor split: the topology's replicas run on separate
    # registries, so the merged JSONL serve.dispatch stream is the
    # source of truth here (same numbers `obs critpath` reports).
    disp = critpath.dispatch_floor(sources)
    if disp['total']['ticks']:
        tot = disp['total']
        record['dispatch_ticks'] = tot['ticks']
        record['dispatch_overhead_s'] = tot['overhead_seconds']
        record['dispatch_overhead_ms_per_token'] = (
            None if tot['overhead_per_token'] is None
            else tot['overhead_per_token'] * 1e3)
        record['dispatch_per_replica'] = {
            name: {'ticks': agg['ticks'],
                   'overhead_s': agg['overhead_seconds'],
                   'overhead_share': agg['overhead_share']}
            for name, agg in sorted(disp['per_replica'].items())}
    record.update(chaos_extra)
    record.update(corrupt_extra)
    if args.chaos_corrupt:
        print(f"chaos-corrupt[{args.chaos_victim} page {corrupt_page}"
              f"@tick {corrupt_tick}]: "
              f"{corrupt_extra['corruptions_injected']} flip(s) "
              f"injected, {corrupt_extra['corruptions_detected']} "
              f"kv.corrupt verdict(s) at "
              f"{corrupt_extra['corrupt_sites']}, "
              f"{len(corrupt_extra['corrupt_healed'])} victim(s) "
              f"healed + {len(corrupt_extra['corrupt_rejects'])} typed "
              f"kv_corrupt terminal(s), "
              f"{corrupt_extra['corrupt_compared']} completed streams "
              f"bit-identical to the twin; goodput with integrity "
              f"{report.goodput_pct:.1f}% vs no-integrity twin "
              f"{corrupt_extra['nointeg_goodput_pct']:.1f}% "
              f"({len(corrupt_extra['nointeg_wrong_streams'])} "
              f"SILENTLY WRONG stream(s) there); flight bundle "
              f"{corrupt_extra['flight_bundle']}")
    if args.chaos:
        print(f"chaos[{args.chaos_victim}@tick {CHAOS_TICK}]: "
              f"{len(chaos_extra['recovered'])} stream(s) recovered "
              f"({chaos_extra['recovered_compared']} bit-identical to "
              f"the crash-free twin), "
              f"{len(chaos_extra['replica_lost_rejects'])} typed "
              f"replica_lost terminal(s); goodput with recovery "
              f"{report.goodput_pct:.1f}% vs no-recovery twin "
              f"{chaos_extra['norec_goodput_pct']:.1f}%; "
              f"flight bundle {chaos_extra['flight_bundle']}")
    print(f"serve-load[topology {args.topology}"
          f"{'+control' if args.control else ''}] "
          f"(virtual time: a behaviour check, never a speed) "
          f"seed={SEED} "
          f"{cfg.arrival}@{cfg.rate:.0f}/s x{report.requests}: "
          f"goodput {report.goodput_pct:.1f}% vs single-process twin "
          f"{report_twin.goodput_pct:.1f}% "
          f"(routed {routed}, {record['handoffs']} handoffs, "
          f"{record['prefix_hits']} prefix hits"
          + (f", {len(record['control_actions'])} control actions, "
             f"{record['replicas_final']} replicas final"
             if args.control else '') + ')')
    print(obs_slo.render_report(report))
    print(f'event logs: {log_dir}')
    _append_record(args.file, record)
    return record


def run_serve_load(args):
    """Goodput under SLO for a seeded open-loop trace through ONE
    scheduler, in VIRTUAL time. The run's JSONL event log is written
    and the goodput report is computed FROM THE LOG ALONE
    (obs/slo.py), per tenant. Bare, this is the configuration
    ``scripts/ci.sh`` gates against SLO_BASELINE.json."""
    engine = KernelEngine(
        slots=SLOTS, t_max=T_MAX, vocab=VOCAB, heads=HEADS,
        head_dim=HEAD_DIM, prefill_chunk=8, seed=0, decode_impl=None)
    cfg = _loadgen_config(args)
    serve_cfg = _serve_config(cfg)
    control_cfg = ControlConfig(interval=0.01) if args.control else None
    log_path = args.event_log or os.path.join(
        tempfile.gettempdir(), f'ddp_serve_load_{os.getpid()}.jsonl')
    # A fresh log per run: EventLog APPENDS (resuming seq), so a stale
    # file from a previous run would double every timeline.
    obs.remove_log(log_path)
    clock = VirtualClock()
    event_log = obs.EventLog(log_path, clock=clock)
    registry = MetricsRegistry()
    res = run_load(cfg, engine=engine, serve_config=serve_cfg,
                   registry=registry, event_log=event_log,
                   clock=clock, control=control_cfg)
    event_log.close()

    spec = obs_slo.SloSpec(ttft=args.slo_ttft,
                           per_token=args.slo_token)
    # Read + decode the log ONCE; goodput and the churn reconstruction
    # below both accept the decoded records.
    records = obs.read_events(log_path)
    report = obs_slo.goodput(records, spec)
    if not res.accounted:
        raise SystemExit('serve-load: a submitted request has no '
                         'terminal record — scheduler accounting bug')
    if report.requests != len(res.submitted):
        raise SystemExit(
            f'serve-load: {report.requests} requests classified from '
            f'the log vs {len(res.submitted)} submitted — the event '
            f'log is not a complete record')
    # Per-tenant churn counters, reconstructed from the same log.
    preempts, requeues = {}, {}
    for tl in obs.reconstruct(records).values():
        tenant = tl.tenant or 'default'
        preempts[tenant] = preempts.get(tenant, 0) + tl.preempts
        requeues[tenant] = requeues.get(tenant, 0) + max(
            0, tl.admits - 1)
    per_tenant = {
        t: {'requests': tb['requests'],
            'goodput_pct': tb['goodput_pct'],
            'met': tb['counts']['met'],
            'rejected': tb['counts']['rejected'],
            'preempts': preempts.get(t, 0),
            'requeues': requeues.get(t, 0)}
        for t, tb in sorted(report.per_tenant.items())}

    def virtual_ms(name):
        return {k: (None if v is None else v * 1e3)
                for k, v in report.percentiles[name].items()
                if k != 'count'}

    record = {
        'clock': 'virtual', 'seed': SEED,
        'arrival': cfg.arrival, 'rate_requested': cfg.rate,
        'rate_offered': res.offered_rate,
        'requests': report.requests, 'slots': SLOTS, 't_max': T_MAX,
        'queue_limit': serve_cfg.queue_limit,
        'control': bool(args.control),
        'tick_seconds': cfg.tick_seconds,
        'platform': jax.devices()[0].platform,
        'slo': spec.to_dict(),
        'goodput_pct': report.goodput_pct,
        'counts': report.counts,
        'per_tenant': per_tenant,
        'ttft_ms': virtual_ms('ttft'),
        'gap_ms': virtual_ms('gap'),
        'queue_wait_ms': virtual_ms('queue_wait'),
        'virtual_seconds': res.virtual_seconds,
        'ticks': res.ticks,
        'event_log': log_path,
    }
    # Dispatch-floor split: host-loop overhead vs device-program time
    # per decode tick, from the scheduler's histograms on this
    # registry.
    tok_c = registry.peek('counter', 'serve.tokens_generated')
    record.update(_dispatch_split(
        registry, tok_c.value if tok_c is not None else 0))
    print(f"serve-load (virtual time: a behaviour check, never a "
          f"speed) seed={SEED} "
          f"{cfg.arrival}@{cfg.rate:.0f}/s x{report.requests}: "
          f"goodput {report.goodput_pct:.1f}% under "
          f"ttft<{args.slo_ttft * 1e3:.0f}ms "
          f"gap<{args.slo_token * 1e3:.0f}ms")
    print(obs_slo.render_report(report))
    print(f'event log: {log_path}')
    _append_record(args.file, record)
    return record


def main():
    args = parse_args()
    setup_compile_cache()
    if args.topology:
        return run_serve_load_topology(args)
    return run_serve_load(args)


if __name__ == '__main__':
    main()
