# -*- coding: utf-8 -*-
"""
Shared vocabulary of the analysis subsystem: the :class:`Violation`
record every engine emits, the rule catalog (id → what the rule guards
and which PR's contract it encodes), and the suppression pragma.

A violation is always anchored: ``file:line`` for AST rules, the
registered entrypoint name (plus the traced source line when jaxpr
equation metadata carries one) for jaxpr rules. The CLI and the tier-1
gate test both render these records, so an analyzer finding is
actionable from its one-line form.

Suppression: a trailing ``# graphlint: allow[<rule-id>]`` comment on
the offending line (or the line directly above) waives that rule for
that site — deliberate exceptions stay visible and greppable in the
source instead of accumulating in a config file. The flowlint family
spells the same pragma ``# flowlint: allow[<rule-id>]``; both prefixes
parse identically.
"""

import dataclasses
import re
from typing import Optional

__all__ = ['Violation', 'RULES', 'allowed_by_pragma',
           'active_violations', 'format_violations']

# Rule catalog. Jaxpr rules (J*) trace registered entrypoints and walk
# the ClosedJaxpr; AST rules (A*) parse source; R* is enforced at
# runtime by the retrace sentinel (utils/retrace.py) under pytest.
RULES = {
    'f32-accum': (
        'every dot_general on low-precision (bf16/f16/int8) operands '
        'must request a wide accumulator via preferred_element_type '
        '(f32, or i32 for int8) — encodes the fp32-accumulation '
        'contract of the matmul-heavy paths (PR 3: LM head; the Pallas '
        'kernels carry it throughout)'),
    'donation': (
        'entrypoints declared as donating (KV-cache serving steps) '
        'must actually alias their donated buffers in the lowered '
        'module — without donation every token copies the full cache '
        '(PR 3: in-place KV-cache aliasing)'),
    'cache-alias': (
        'cache buffers must flow input→output through surgical writes '
        'only (dynamic_update_slice / masked select / kernel '
        'input_output_aliases); a full-shape copy or re-materialization '
        'degrades the in-place append into a per-token cache copy '
        '(PR 3: aliased append contract)'),
    'cache-upcast': (
        'no convert_element_type may widen a cache-shaped tensor: '
        'upcasting the KV buffer (e.g. bf16→f32 before a matmul) '
        'materializes a full-size copy every step — request the wide '
        'accumulator on the dot instead (PR 3: cache streaming '
        'contract)'),
    'collective-axis': (
        'collectives inside shard_map must name axes that exist on the '
        "entrypoint's declared mesh — a stray axis name means the "
        'program is being built against the wrong topology (PR 0/2: '
        'mesh discipline)'),
    'trace-error': (
        'a registered entrypoint failed to trace at its declared '
        'example shapes — the registration or the entrypoint itself '
        'regressed'),
    'host-pull': (
        'float()/int()/bool()/np.asarray()/.item() on a value produced '
        'by jnp/lax in ops/ or models/ hot paths forces a device '
        'readback (or a tracer error) mid-graph'),
    'traced-bool-branch': (
        'python `if`/`while` on a traced predicate (jnp.any/all/'
        'isfinite/...) in ops/ or models/ either crashes under jit or '
        'silently fixes the branch at trace time — use lax.cond/'
        'jnp.where'),
    'clock-in-jit': (
        'time.time()/perf_counter()/monotonic() inside a jitted '
        'function reads the clock at TRACE time and bakes the constant '
        'into the program (PR 2: the health watchdog reads real time '
        'outside compiled code for exactly this reason)'),
    'parse-error': (
        'a scanned file does not parse as python — reported regardless '
        'of any --rule filter (a broken file can hide any violation)'),
    'silent-except': (
        'a broad except (bare / Exception / BaseException) that '
        'neither re-raises nor logs swallows real failures — log '
        'through utils.tracing.log_exception or narrow the type '
        '(PR 1/2: fault paths must stay observable)'),
    'retrace-budget': (
        'runtime rule (utils/retrace.py): a watched decode/serve '
        'entrypoint may not trace more often than its declared budget '
        '— automates the round-5 decode_seq_parallel retrace-storm '
        'finding'),
    # -- servelint: protocol / concurrency / determinism (PR 13) --------
    'event-vocab': (
        'protolint (analysis/protolint.py): a literal event kind at an '
        'emit() call site must exist in the closed obs/events.py '
        'EVENT_SCHEMA vocabulary — an unknown kind raises mid-incident '
        'at runtime; here it fails at PR time'),
    'event-fields': (
        'protolint: a literal emit() payload must carry every field '
        'EVENT_SCHEMA requires for its kind (calls forwarding **kwargs '
        'are skipped — only statically-complete payloads are judged)'),
    'reject-reason': (
        'protolint: a serve.reject `reason` must be a RejectReason '
        'member — a literal string must be one of the enum values, and '
        'a RejectReason attribute must name a member and emit its '
        '.value (the enum object would serialize as its repr)'),
    'guarded-by': (
        'conclint (analysis/conclint.py): a field annotated '
        '`# guarded-by: self._lock` may only be read or written inside '
        'a `with self._lock:` block (exempt: __init__, methods named '
        '*_locked — the caller holds the lock by convention)'),
    'thread-discipline': (
        'conclint: every threading.Thread(...) must be daemon=True and '
        'carry a name= — a non-daemon thread blocks interpreter '
        'shutdown on a wedged step, and an unnamed one is anonymous in '
        'the flight recorder\'s stack dumps'),
    'tick-determinism': (
        'determlint (analysis/determlint.py): no real-time reads '
        '(time.time/monotonic/sleep/perf_counter), `random` module '
        'calls, np.random, or os.environ reads inside a declared '
        'virtual-clock tick path (GRAPHLINT_TICK_ROOTS and their '
        'intra-module call closure) — the seeded bit-reproducible '
        'replay contract; intentional real-time sites live in '
        'determlint\'s REAL_TIME_CONTRACT table'),
    # -- flowlint: interprocedural typed-failure flow (PR 19) -----------
    'typed-escape': (
        'flowlint (analysis/flowlint.py): every exception class that '
        'can escape a declared serving root (SERVING_ROOTS — '
        'Scheduler.step/submit, Router.step/submit, KernelEngine.step/'
        'prefill/verify_step, run_trace) must be in the typed failure '
        'contract (TYPED_CONTRACT: RejectedError, PageCorruptionError, '
        'shard-exhaustion RuntimeError, ServeContractError, '
        'UnknownReplicaError) — a raw KeyError/IndexError/ValueError '
        'escape flags with its propagation chain file:line → file:line '
        '(the PR 17 deque.remove bug class, mechanized)'),
    'handler-totality': (
        'flowlint: an `except` of a typed serving error (RejectedError/'
        'PageCorruptionError or a subclass) must re-raise, route the '
        'failure into the event/metric ladder (emit/log_exception/'
        'count_reject/reject — directly or transitively), or consume '
        'the typed payload (.reason/.pages/.site) — silently dropping '
        'a typed failure un-types it'),
    'reason-coverage': (
        'flowlint: every RejectReason member needs ≥ 1 raise/convert '
        'reference site plus serve.reject emit and per-reason counter '
        'coverage — a dead enum member is taxonomy the operator '
        'dashboards promise but no code path can produce'),
    'shard-ownership': (
        'flowlint: host code outside models/decode.py must reach '
        'ShardedPageTable geometry through its helpers (gpage/gsplit/'
        'page_shard/owner/owned_range/tracked_pages), never raw '
        '`pages_per_shard + 1` stride arithmetic — the PR 18 '
        'contiguous-ownership layout has exactly one home'),
}

_PRAGMA = re.compile(
    r'#\s*(?:graphlint|flowlint):\s*allow\[([a-z0-9_,\s-]+)\]')


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str                       # id from RULES
    message: str
    file: Optional[str] = None      # repo-relative where possible
    line: Optional[int] = None
    entrypoint: Optional[str] = None  # registry name (jaxpr rules)
    # Waived-but-visible: a registration-level allowance (TraceSpec
    # .allow — the flax Dense bf16-accum debt) keeps the record in
    # `--format json` output without failing the CLI or the gate, so
    # known debt stays enumerable instead of disappearing into a
    # pragma. flowlint pragma waivers ride the same flag — a waived
    # failure-flow site is debt, not absence.
    allowed: bool = False
    # typed-escape only: the propagation chain root → origin raise as
    # ('file:line', ...) hops — the `--format json` shape README
    # documents (rule/file/line/chain are the stable keys).
    chain: Optional[tuple] = None

    def render(self):
        where = f'{self.file}:{self.line}' if self.file else '<registry>'
        entry = f' [{self.entrypoint}]' if self.entrypoint else ''
        mark = ' (allowed)' if self.allowed else ''
        return f'{where}: {self.rule}{entry}{mark}: {self.message}'


def allowed_by_pragma(source_lines, lineno, rule):
    """True when the 1-based ``lineno`` (or the line above) carries a
    ``# graphlint: allow[rule]`` pragma naming ``rule``."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(source_lines):
            m = _PRAGMA.search(source_lines[ln - 1])
            if m and rule in {r.strip() for r in m.group(1).split(',')}:
                return True
    return False


def active_violations(violations):
    """The violations that FAIL a run (``allowed=False``) — the CLI
    exit code and the tier-1 gate both judge this subset; allowed
    records stay visible in the rendered output."""
    return [v for v in violations if not v.allowed]


def format_violations(violations, fmt='text'):
    """Render a violation list for the CLI: ``text`` (one line each),
    ``json`` (a list of plain dicts, ``allowed`` records included), or
    ``sarif`` (a minimal SARIF 2.1.0 log — one run, ruleId/level/
    message/location per result — so CI can annotate findings inline;
    ``allowed`` records carry level ``note``, active ones ``error``)."""
    if fmt == 'json':
        import json
        return json.dumps([dataclasses.asdict(v) for v in violations],
                          indent=2)
    if fmt == 'sarif':
        import json
        results = []
        for v in violations:
            entry = f' [{v.entrypoint}]' if v.entrypoint else ''
            res = {
                'ruleId': v.rule,
                'level': 'note' if v.allowed else 'error',
                'message': {'text': f'{v.message}{entry}'},
            }
            if v.file:
                res['locations'] = [{'physicalLocation': {
                    'artifactLocation': {
                        'uri': v.file.replace('\\', '/')},
                    'region': {'startLine': int(v.line or 1)},
                }}]
            results.append(res)
        used = sorted({v.rule for v in violations})
        log = {
            '$schema': 'https://json.schemastore.org/sarif-2.1.0.json',
            'version': '2.1.0',
            'runs': [{
                'tool': {'driver': {
                    'name': 'graphlint',
                    'rules': [{'id': r,
                               'shortDescription':
                                   {'text': RULES.get(r, r)}}
                              for r in used],
                }},
                'results': results,
            }],
        }
        return json.dumps(log, indent=2)
    act = active_violations(violations)
    n_allowed = len(violations) - len(act)
    lines = [v.render() for v in violations]
    if not act:
        lines.append('graphlint: no violations'
                     + (f' ({n_allowed} allowed by registration)'
                        if n_allowed else ''))
    else:
        lines.append(f'graphlint: {len(act)} violation'
                     f'{"s" if len(act) != 1 else ""}'
                     + (f' (+{n_allowed} allowed)' if n_allowed else ''))
    return '\n'.join(lines)
