# -*- coding: utf-8 -*-
"""
graphlint (distributed_dot_product_tpu/analysis/) — the static-analysis
subsystem's own gate and rule tests.

Three layers:

- **Clean-tree gate** (tier-1): the full analyzer over the repo and the
  central registry reports ZERO violations — the mechanism that turns
  every rule into a standing CI contract.
- **Negative fixtures, one per rule**: deliberately violating code
  (tests/graphlint_fixtures/) must produce exactly the expected rule id
  with a usable file:line — so a rule can't bit-rot into always-pass.
  The fp32-accumulation, aliasing/donation and retrace-budget rules
  each catch a seeded regression here (the acceptance contract).
- **Retrace sentinel budgets**: decode_seq_parallel's LRU-cached step
  traces ONCE across a token loop (the round-5 advisor finding, now
  pinned mechanically); the rebuild-storm variant is visible in the
  name-total; the engine's fixed programs trace once; exceeding a
  budget raises.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_dot_product_tpu.analysis import run_analysis
from distributed_dot_product_tpu.analysis.astlint import lint_file
from distributed_dot_product_tpu.analysis.jaxpr_rules import lint_spec
from distributed_dot_product_tpu.analysis.registry import (
    default_entrypoints,
)
from distributed_dot_product_tpu.utils import retrace

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, 'tests', 'graphlint_fixtures')


def _negatives_module():
    """tests/ is not a package: `tests.graphlint_fixtures` resolves as
    a PEP-420 namespace package when the repo root is on sys.path
    (python -m pytest from the root) — fall back to inserting it."""
    try:
        from tests.graphlint_fixtures import jaxpr_negatives
    except ImportError:
        sys.path.insert(0, REPO)
        from tests.graphlint_fixtures import jaxpr_negatives
    return jaxpr_negatives


# -- clean-tree gate ----------------------------------------------------

def test_clean_tree_gate(devices):
    """THE gate: zero ACTIVE violations across the package AST scan
    (astlint + the servelint families + flowlint's typed-failure-flow
    rules) and every registered entrypoint's jaxpr — AND zero WAIVED
    records of any kind. The owned dense (models/dense.py) retired the
    flax ``linen.Dense`` bf16-accumulation debt the bf16 serving-dtype
    twins used to waive (14 allowed records across three entries), and
    flowlint reports pragma-waived sites as visible ``allowed``
    records, so this assertion also pins the tree at ZERO
    ``# flowlint: allow[...]`` waivers; new waived debt of either kind
    fails here and must be argued in review, not slipped in as an
    "allowed" record."""
    from distributed_dot_product_tpu.analysis import active_violations
    violations = run_analysis()
    active = active_violations(violations)
    assert active == [], '\n'.join(v.render() for v in active)
    waived = [v for v in violations if v.allowed]
    assert waived == [], (
        'the zero-waiver contract broke — the owned-dense refactor '
        'retired every f32-accum waiver, and new waived debt needs a '
        'reviewed decision, not an allow= entry:\n'
        + '\n'.join(v.render() for v in waived))


# The registry's names, in its order. PR 42 moved the examples out of
# the layer modules into analysis/entrypoints.py and changed none.
REGISTERED = [
    'ops.matmul_grad_allgather', 'ops.matmul_grad_ring',
    'ops.flash_fwd_bf16', 'ops.flash_bwd_bf16', 'ops.flash_fwd_int8',
    'attention.fwd_flash', 'attention.fwd_flash_bf16',
    'attention.fwd_flash_wq8', 'attention.bwd_full', 'attention.fwd_ring',
    'attention.fwd_ulysses', 'decode.seq_parallel_step',
    'decode.seq_parallel_step_bf16', 'decode.step_xla_slots',
    'decode.step_kernel_int8', 'decode.step_sharded',
    'decode.step_paged_xla', 'decode.step_paged_kernel',
    'decode.step_paged_kernel_int8', 'decode.step_paged_sharded',
    'decode.step_paged_sharded_kernel', 'decode.step_verify_slab',
    'decode.step_verify_paged', 'lm.head_bf16', 'lm.loss_f32',
    'lm.loss_bf16', 'serve.engine_decode', 'serve.engine_decode_paged',
    'serve.engine_decode_wq8', 'serve.engine_decode_kv_sharded',
    'train.lm_step', 'obs.spanned_decode',
]


def test_registry_is_the_pinned_list(devices):
    """The registry spans the whole stack — an example silently dropped
    would shrink the gate's coverage without failing it — and a caller
    gets a copy it may cut down."""
    entries = default_entrypoints()
    assert list(entries) == REGISTERED and len(REGISTERED) == 32
    entries.clear()
    assert list(default_entrypoints()) == REGISTERED


def test_duplicate_registration_is_an_error():
    from distributed_dot_product_tpu.analysis.entrypoints import (
        ENTRYPOINTS, entrypoint,
    )
    with pytest.raises(ValueError, match='duplicate entrypoint'):
        entrypoint('train.lm_step', lambda: None)
    with pytest.raises(ValueError, match='duplicate entrypoint'):
        @entrypoint('obs.spanned_decode')
        def again():
            pass
    assert list(ENTRYPOINTS) == REGISTERED


# -- AST rules: negative fixtures ---------------------------------------

def _expected_lines(path):
    """Lines carrying a '# VIOLATION' marker — the fixture annotates its
    own seeded regressions, so the assertion can't drift from the
    file."""
    with open(path, encoding='utf-8') as f:
        return [i for i, line in enumerate(f, 1) if '# VIOLATION' in line]


@pytest.mark.parametrize('fixture, rule', [
    (os.path.join('ops', 'fx_host_pull.py'), 'host-pull'),
    (os.path.join('ops', 'fx_traced_bool.py'), 'traced-bool-branch'),
    ('fx_clock_in_jit.py', 'clock-in-jit'),
    ('fx_span_in_jit.py', 'clock-in-jit'),
    ('fx_build_span_in_jit.py', 'clock-in-jit'),
    ('fx_silent_except.py', 'silent-except'),
])
def test_ast_rule_catches_fixture(fixture, rule):
    path = os.path.join(FIXTURES, fixture)
    violations = lint_file(path, repo_root=REPO)
    got = {(v.rule, v.line) for v in violations}
    want = {(rule, line) for line in _expected_lines(path)}
    assert want == got, (f'{fixture}: expected exactly {sorted(want)}, '
                         f'got {sorted(got)}')
    # file:line anchoring — every report names the fixture file.
    assert all(v.file and v.file.endswith(fixture) for v in violations)


# -- jaxpr rules: negative fixtures -------------------------------------

_NEGATIVE_NAMES = ('neg.f32_accum', 'neg.cache_rematerialize',
                   'neg.paged_pool_rematerialize', 'neg.full_shape_dus',
                   'neg.cache_upcast', 'neg.missing_donation',
                   'neg.collective_axis', 'neg.trace_error')


@pytest.mark.parametrize('name', _NEGATIVE_NAMES)
def test_jaxpr_rule_catches_fixture(name, devices):
    ALL = _negatives_module().ALL
    assert set(ALL) == set(_NEGATIVE_NAMES)
    builder, rule = ALL[name]
    violations = lint_spec(builder(), rules=[rule, 'trace-error'])
    fired = {v.rule for v in violations}
    assert rule in fired, (f'{name}: expected rule {rule!r}, got '
                           + '\n'.join(v.render() for v in violations)
                           if violations else f'{name}: no violations')
    for v in violations:
        assert v.entrypoint == name


def test_f32_accum_violation_names_fixture_line(devices):
    """The jaxpr rules anchor to source: the bf16-accumulation seeded
    regression is reported at its line in the fixture module."""
    builder, rule = _negatives_module().ALL['neg.f32_accum']
    (v,) = lint_spec(builder(), rules=[rule])
    assert v.file and v.file.endswith('jaxpr_negatives.py')
    assert v.line and v.line > 0


def test_clean_spec_restricted_rules_run_subset(devices):
    """--rule style filtering: a spec linted under a single rule only
    reports that rule (the CLI contract)."""
    builder, _ = _negatives_module().ALL['neg.cache_upcast']
    assert lint_spec(builder(), rules=['collective-axis']) == []


# -- CLI ----------------------------------------------------------------

def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, '-m', 'distributed_dot_product_tpu.analysis',
         *args], capture_output=True, text=True, cwd=REPO, env=env,
        timeout=540)


def test_cli_nonzero_on_ast_fixture():
    res = _cli('--no-jaxpr',
               os.path.join('tests', 'graphlint_fixtures', 'ops',
                            'fx_host_pull.py'))
    assert res.returncode == 1, res.stdout + res.stderr
    assert 'fx_host_pull.py:' in res.stdout      # file:line rendering
    assert 'host-pull' in res.stdout             # rule id named


@pytest.mark.slow
def test_cli_nonzero_on_jaxpr_fixtures():
    """CLI end-to-end over the seeded jaxpr regressions (subprocess
    with full registry import — slow tier)."""
    res = _cli('--no-ast', '--registry',
               'tests.graphlint_fixtures.jaxpr_negatives:REGISTRY')
    assert res.returncode == 1, res.stdout + res.stderr
    for rule in ('f32-accum', 'cache-alias', 'cache-upcast', 'donation',
                 'collective-axis', 'trace-error'):
        assert rule in res.stdout, f'{rule} missing from CLI output'


def test_cli_list_rules():
    res = _cli('--list-rules')
    assert res.returncode == 0
    for rule in ('f32-accum', 'cache-alias', 'retrace-budget',
                 'silent-except'):
        assert rule in res.stdout


# -- retrace sentinel ---------------------------------------------------

def test_retrace_budget_raises_on_seeded_storm():
    """Seeded regression: a watched step traced past its budget (here:
    shape-polymorphic calls against budget 1) raises loudly instead of
    silently recompiling per call."""
    watched = retrace.watch_traces(lambda x: x * 2, 'unit.storm',
                                   budget=1)
    step = jax.jit(watched)
    step(jnp.ones((2,)))
    step(jnp.ones((2,)))          # cache hit: no new trace
    assert watched._graphlint_counter.count == 1
    with pytest.raises(retrace.RetraceBudgetExceeded,
                       match='unit.storm'):
        step(jnp.ones((3,)))      # new shape → second trace > budget


def test_retrace_disabled_counts_but_never_raises(monkeypatch):
    monkeypatch.setenv(retrace.ENV_VAR, '0')
    watched = retrace.watch_traces(lambda x: x + 1, 'unit.disabled',
                                   budget=1)
    step = jax.jit(watched)
    step(jnp.ones((2,)))
    step(jnp.ones((3,)))          # over budget, but sentinel is off
    assert watched._graphlint_counter.count == 2


def test_a_counter_may_die_while_the_registry_is_locked():
    """The collector runs a finalizer on whatever thread allocates, and
    that thread may hold the registry's lock (``watch_traces`` prunes
    under it): a dying counter takes no lock, and its count still
    reaches ``total()``."""
    import threading
    counter = retrace.TraceCounter('unit.dying', budget=1)
    counter.count = 3
    done = threading.Event()

    def die_under_the_lock():
        nonlocal counter
        with retrace._COUNTERS_LOCK:
            counter = None          # the last reference: __del__ runs here
        done.set()

    t = threading.Thread(target=die_under_the_lock, daemon=True)
    t.start()
    assert done.wait(10), 'a finalizer waited for the lock its thread holds'
    assert retrace.total('unit.dying') == 3


def _decode_module(**kw):
    from distributed_dot_product_tpu.models.attention import (
        DistributedDotProductAttn,
    )
    return DistributedDotProductAttn(
        key_dim=8, num_heads=2, causal=True, softmax_impl='flash',
        dtype=jnp.float32, **kw)


def _decode_setup(module, devices):
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    mesh = seq_mesh(2, devices=devices)
    x = jnp.zeros((1, 8, 8), jnp.float32)
    params = module.init(jax.random.key(0), x, x, x, None)
    cache = module.make_decode_cache(1, 16)
    tok = jnp.zeros((1, 1, 8), jnp.float32)
    return mesh, params, cache, tok


def test_decode_seq_parallel_traces_once_across_tokens(devices):
    """The round-5 advisor finding, enforced mechanically: N tokens
    through decode_seq_parallel's LRU-cached step cost exactly ONE
    trace of the compiled decode step."""
    from distributed_dot_product_tpu.models import attention as A
    module = _decode_module()
    mesh, params, cache, tok = _decode_setup(module, devices)
    A._DECODE_STEPS.clear()
    retrace.reset()
    for _ in range(3):
        cache, _out = A.decode_seq_parallel(module, params, mesh, tok,
                                            tok, tok, cache)
    assert retrace.total('attention.make_decode_step') == 1


def test_decode_seq_parallel_rebuild_storm_is_visible(devices):
    """The storm variant (unhashable module → step rebuilt per token)
    can't trip a per-instance budget — each rebuild gets a fresh
    counter — but the name-total exposes it: N tokens, N traces."""
    from distributed_dot_product_tpu.models import attention as A
    module = _decode_module(
        alibi_slopes=np.array([0.25, 0.5], np.float32))  # unhashable
    mesh, params, cache, tok = _decode_setup(module, devices)
    A._DECODE_STEPS.clear()
    retrace.reset()
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter('ignore')      # warn-once may have fired already
        for _ in range(3):
            cache, _out = A.decode_seq_parallel(module, params, mesh,
                                                tok, tok, tok, cache)
    assert retrace.total('attention.make_decode_step') == 3


def test_engine_programs_trace_once(devices):
    """The serving engine's fixed-shape decode program traces exactly
    once across a multi-step serve loop."""
    from distributed_dot_product_tpu.serve.engine import KernelEngine
    retrace.reset()
    eng = KernelEngine(slots=2, t_max=8, decode_impl='xla')
    tokens = np.zeros(2, np.int32)
    active = np.ones(2, bool)
    for _ in range(4):
        tokens, _finite = eng.step(tokens, active)
    assert retrace.total('engine.decode') == 1


# -- satellite: log_exception -------------------------------------------

def test_log_exception_counts_into_registry():
    from distributed_dot_product_tpu.utils.tracing import (
        MetricsRegistry, log_exception,
    )
    reg = MetricsRegistry()
    log_exception('unit.site', ValueError('boom'), registry=reg)
    log_exception('unit.site', ValueError('boom'), registry=reg)
    snap = reg.snapshot()['counters']
    assert snap['exceptions_swallowed'] == 2
    assert snap['exceptions_swallowed.unit.site'] == 2
