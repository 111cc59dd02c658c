# -*- coding: utf-8 -*-
"""
The build ledger (``utils/build_ledger.py``): one record a trace,
lowering, compile, kernel body and cache event, as SELF time on the
spans' clock; ``build_span`` and the kernels' one helper
(``ops/kernel_call.py``) leave nothing in a program; every
``pl.pallas_call`` under ``ops/`` goes through that helper.
"""

import ast
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as _monitoring
from jax.experimental import pallas as pl

from distributed_dot_product_tpu.obs import spans
from distributed_dot_product_tpu.ops.kernel_call import kernel_call
from distributed_dot_product_tpu.utils import build_ledger as bl
from distributed_dot_product_tpu.utils.build_ledger import build_span
from distributed_dot_product_tpu.utils.compile_cache import (
    setup_compile_cache,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(ROOT, 'distributed_dot_product_tpu')


@pytest.fixture(autouse=True)
def _installed():
    """Every test starts and ends with the one set of listeners that
    ``tests/conftest.py`` installed through ``setup_compile_cache``."""
    bl.install()
    yield
    bl.install()


def _fresh(fn):
    """``fn`` under a name of its own, so no earlier test's jit cache
    (nor the persistent one) already holds the program."""
    fn.__name__ = fn.__qualname__ = f'{fn.__name__}_{time.monotonic_ns()}'
    return fn


def _by_kind(records, kind, name=None):
    return [r for r in records if r.kind == kind
            and (name is None or r.name == name)]


# -- self time, not sums ---------------------------------------------------

def test_inner_jits_are_children_and_self_times_do_not_double_count():
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    # two shapes, so two traces of `inner`; the third call's trace is
    # cached — microseconds, folded into the outer trace's self time
    outer = _fresh(lambda x: inner(x).sum() + inner(x[:2]).sum()
                   + inner(x * 3).sum())
    x = jnp.ones((4, 4))                # built eagerly: before the mark
    folded = bl.get_ledger().folded
    t0 = time.perf_counter()
    jax.jit(outer).lower(x)
    t1 = time.perf_counter()
    recs = bl.records(since=t0, until=t1)
    top, = _by_kind(recs, 'trace', outer.__name__)
    inside = [r for r in _by_kind(recs, 'trace') if r is not top]
    assert len(_by_kind(inside, 'trace', 'inner')) == 2
    assert bl.get_ledger().folded > folded
    assert all(r.seconds >= bl.FOLD_SECONDS for r in recs)
    # every inner trace lies inside the outer one, directly or not
    seqs = {r.seq: r for r in recs}

    def ancestors(r):
        while r.parent in seqs:
            r = seqs[r.parent]
            yield r.seq
    assert all(top.seq in set(ancestors(r)) for r in inside)
    assert top.parent is None
    # a plain sum counts the nested seconds twice; self time does not
    plain = sum(r.seconds for r in [top, *inside])
    own = sum(r.self_seconds for r in [top, *inside])
    assert own <= top.seconds + 1e-6 < plain
    assert 0.0 <= top.self_seconds < top.seconds


def test_a_kernel_body_is_a_build_child_out_of_its_traces_self_time():
    def body(x):
        with build_span('fake_kernel'):
            time.sleep(0.05)            # the "kernel body" being traced
            return x + 1

    fn = _fresh(body)
    x = jnp.ones(3)
    t0 = time.perf_counter()
    jax.jit(fn).lower(x)
    recs = bl.records(since=t0)
    trace, = _by_kind(recs, 'trace', fn.__name__)
    build, = _by_kind(recs, 'build', 'fake_kernel')
    assert build.parent == trace.seq
    assert build.seconds >= 0.05
    assert trace.seconds >= build.seconds
    assert trace.self_seconds <= trace.seconds - 0.05 + 1e-6
    s = bl.summary(since=t0)
    # the body's own inner traces (`x + 1`) count to the kernel too
    assert build.self_seconds <= s['kernels']['fake_kernel'] <= (
        build.seconds + 1e-6)
    # the body counts under the PROGRAM that was being traced
    assert s['programs'][fn.__name__]['build'] >= 0.05
    assert s['seconds']['build'] >= 0.05


def test_a_trace_inside_a_build_span_is_the_kernels_body():
    """Pallas traces a kernel's body as an inner ``jit``: its trace
    records lie inside the kernel's build record and count to the
    kernel, not to the model's Python."""
    @jax.jit
    def body_as_pallas_traces_it(x):
        time.sleep(0.03)
        return jnp.cos(x)

    def model(x):
        with build_span('fake_kernel'):
            y = body_as_pallas_traces_it(x)
        time.sleep(0.02)                # the model's own Python
        return y * 2

    fn = _fresh(model)
    x = jnp.ones(5)
    t0 = time.perf_counter()
    jax.jit(fn).lower(x)
    recs = bl.records(since=t0)
    inner, = _by_kind(recs, 'trace', 'body_as_pallas_traces_it')
    outer, = _by_kind(recs, 'trace', fn.__name__)
    assert (inner.within, inner.stage) == ('fake_kernel', 'build')
    assert (outer.within, outer.stage) == (None, 'trace')
    s = bl.summary(since=t0)
    assert s['seconds']['build'] >= 0.03
    assert s['kernels']['fake_kernel'] >= 0.03
    assert 0.02 <= s['seconds']['trace'] < 0.03 + 0.02
    assert s['programs'][fn.__name__]['build'] >= 0.03


def test_no_self_time_sum_exceeds_the_wall_time():
    @jax.jit
    def inner(x):
        return jnp.tanh(x)

    def body(x):
        with build_span('fake_kernel'):
            y = inner(x)
        return inner(y) @ y

    fn = _fresh(body)
    x = jnp.ones((8, 8))
    t0 = time.perf_counter()
    jax.jit(fn)(x).block_until_ready()
    wall = time.perf_counter() - t0
    s = bl.summary(since=t0)
    assert 0 < sum(s['seconds'].values()) <= wall
    assert s['records'] == len(bl.records(since=t0))


# -- nothing in the program ------------------------------------------------

def test_build_span_leaves_nothing_in_the_program():
    def plain(x):
        return jnp.dot(x, x.T) * 2

    def spanned(x):
        with build_span('fake_kernel'):
            return jnp.dot(x, x.T) * 2

    x = jnp.ones((8, 4))
    want = jax.jit(plain).lower(x).as_text().replace('plain', 'f')
    assert jax.jit(spanned).lower(x).as_text().replace(
        'spanned', 'f') == want
    decorated = build_span('ops.fake')(plain)
    assert decorated.__name__ == 'plain'
    assert jax.jit(decorated).lower(x).as_text().replace(
        'plain', 'f') == want


def test_a_kernel_lowers_to_one_text_with_and_without_the_helper():
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def through(call):
        def f(x):
            return call(kernel, grid=(2,),
                        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                        interpret=True, name='fake_kernel')(x)
        return f

    x = jnp.ones((16, 128), jnp.float32)
    t0 = time.perf_counter()
    helper = jax.jit(through(kernel_call)).lower(x).as_text()
    assert helper == jax.jit(through(pl.pallas_call)).lower(x).as_text()
    build, = _by_kind(bl.records(since=t0), 'build', 'fake_kernel')
    assert build.parent is not None


def test_the_helper_wants_the_kernels_name():
    with pytest.raises(TypeError, match='name'):
        kernel_call(lambda x_ref, o_ref: None, grid=(1,))


# -- the listeners ---------------------------------------------------------

def _mine():
    return (_monitoring.get_event_time_span_listeners().count(
                bl._on_time_span),
            _monitoring.get_event_listeners().count(bl._on_event),
            _monitoring.get_event_duration_listeners().count(
                bl._on_duration))


def test_install_is_idempotent_and_uninstall_takes_every_listener_off():
    bl.install()
    setup_compile_cache()         # every harness's call installs too
    bl.install()
    assert _mine() == (1, 1, 1) and bl.installed()
    bl.uninstall()
    assert _mine() == (0, 0, 0) and not bl.installed()
    bl.uninstall()                # twice is harmless
    x = jnp.ones(2)
    t0 = time.perf_counter()
    jax.jit(_fresh(lambda x: x - 7)).lower(x)
    assert bl.records(since=t0) == []
    # a build span needs no listener: it notes its record itself
    with build_span('fake_kernel'):
        pass
    assert [r.kind for r in bl.records(since=t0)] == ['build']


def test_a_cached_program_counts_a_hit_and_no_miss(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    cc.reset_cache()
    try:
        fn = _fresh(lambda x: jnp.cos(x) @ x.T + 11)
        x = jnp.ones((8, 8))
        t0 = time.perf_counter()
        jax.jit(fn).lower(x).compile()
        t1 = time.perf_counter()
        jax.clear_caches()
        jax.jit(fn).lower(x).compile()
        cold, warm = bl.summary(t0, t1), bl.summary(since=t1)
        assert cold['cache'] == {'hits': 0, 'misses': 1}
        assert warm['cache'] == {'hits': 1, 'misses': 0}
        assert cold['seconds']['cache_read'] == 0.0
        assert warm['seconds']['cache_read'] > 0.0
        # the cache's events carry no name: they take their compile's
        recs = bl.records(since=t1)
        compile_, = _by_kind(recs, 'compile', fn.__name__)
        for kind in ('cache_hit', 'cache_read'):
            event, = _by_kind(recs, kind)
            assert event.parent == compile_.seq
            assert event.name == fn.__name__
        read, = _by_kind(recs, 'cache_read')
        assert compile_.self_seconds == pytest.approx(
            compile_.seconds - read.seconds)
    finally:
        jax.config.update('jax_compilation_cache_dir', before)
        cc.reset_cache()
# -- the bound, the cut, the clock ------------------------------------------

# -- the bound, the cut, the clock ---------------------------------------------

def test_the_bound_drops_the_oldest_and_counts_them():
    ledger = bl.BuildLedger(max_records=3)
    for i in range(5):
        ledger.note('build', f'k{i}', float(i), 0.5)
    assert [r.name for r in ledger.records()] == ['k2', 'k3', 'k4']
    assert ledger.dropped == 2
    assert ledger.summary()['dropped'] == 2
    # a cut that starts behind everything dropped lost nothing
    assert ledger.summary(since=2.0)['dropped'] == 0
    assert ledger.summary(since=1.0)['dropped'] == 2
    ledger.clear()
    assert ledger.records() == [] and ledger.dropped == 0


def test_summary_cuts_by_the_clock():
    ledger = bl.BuildLedger()
    ledger.note('trace', 'inner', 10.2, 0.3)
    ledger.note('build', 'kern', 10.6, 0.2)
    ledger.note('trace', 'prog', 10.0, 1.0)       # holds both
    ledger.note('lower', 'prog', 11.0, 2.0)
    ledger.note('cache_miss', None, 13.5, 0.0)
    ledger.note('compile', 'prog', 13.0, 1.0)     # holds the miss
    whole = ledger.summary()
    assert whole['seconds'] == {
        'trace': pytest.approx(0.5 + 0.3), 'lower': 2.0, 'compile': 1.0,
        'build': pytest.approx(0.2), 'cache_read': 0.0}
    assert whole['cache'] == {'hits': 0, 'misses': 1}
    assert whole['programs'] == {'prog': {
        'trace': pytest.approx(0.8), 'build': pytest.approx(0.2),
        'lower': 2.0, 'compile': 1.0}}
    assert sum(whole['seconds'].values()) == pytest.approx(4.0)
    late = ledger.summary(since=11.0)
    assert late['seconds']['trace'] == 0.0 and late['records'] == 3
    assert late['cache']['misses'] == 1
    early = ledger.summary(until=11.0)
    assert early['seconds']['lower'] == 0.0 and early['records'] == 3
    assert [r.name for r in ledger.records(10.1, 10.7)] == ['inner', 'kern']
    miss, = [r for r in ledger.records() if r.kind == 'cache_miss']
    assert miss.name == 'prog'


def test_threads_do_not_adopt_each_others_records():
    import threading
    ledger = bl.BuildLedger()
    other = threading.Thread(
        target=lambda: ledger.note('trace', 'theirs', 1.1, 0.2))
    other.start()
    other.join()
    ledger.note('trace', 'mine', 1.0, 1.0)
    mine, = [r for r in ledger.records() if r.name == 'mine']
    theirs, = [r for r in ledger.records() if r.name == 'theirs']
    assert theirs.parent is None and mine.self_seconds == 1.0


def test_many_threads_lose_no_record():
    """More writers than cores on a short switch interval: every note
    lands once (kept or counted as dropped), sequence numbers are
    unique, and a thread's spans adopt its own records alone."""
    import sys
    import threading
    ledger = bl.BuildLedger(max_records=500)
    workers, each = 4 * (os.cpu_count() or 4), 50

    def work(i):
        for j in range(each):
            base = 1000.0 * i + j
            ledger.note('trace', f'inner{i}', base + 0.1, 0.2)
            ledger.note('build', f'k{i}', base, 0.5)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    kept = ledger.records()
    assert len(kept) == 500
    assert len(kept) + ledger.dropped == workers * each * 2
    assert len({r.seq for r in kept}) == len(kept)
    by_seq = {r.seq: r for r in kept}
    for r in kept:
        if r.kind == 'trace' and r.parent in by_seq:
            parent = by_seq[r.parent]
            assert (parent.kind, parent.name) == ('build', 'k' + r.name[5:])
            assert parent.self_seconds == pytest.approx(0.3)


def test_a_compile_lies_inside_the_span_that_caused_it():
    """The ledger's clock is ``SpanRecord.start``'s (and the benchmark's
    ``harness.PHASES``' "ended at"): ``time.perf_counter``."""
    fn = _fresh(lambda x: jnp.exp(x) - 5)
    x = jnp.ones(16)
    with spans.collecting() as col:
        with spans.span('warm'):
            jax.jit(fn)(x).block_until_ready()
        with spans.span('again'):
            jax.jit(fn)(x).block_until_ready()
    warm, again = col.records()
    assert (warm.name, again.name) == ('warm', 'again')
    mine = [r for r in bl.records(since=warm.start)
            if r.name == fn.__name__ and r.parent is None]
    assert [r.kind for r in mine] == ['trace', 'lower', 'compile']
    for r in mine:
        assert warm.start <= r.start
        assert r.start + r.seconds <= warm.start + warm.seconds
    # the second call built nothing
    assert bl.records(since=again.start,
                      until=again.start + again.seconds) == []


# -- one way to a Pallas kernel --------------------------------------------

def _pallas_calls(path):
    with open(path, encoding='utf-8') as f:
        tree = ast.parse(f.read(), path)
    return [node.lineno for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and getattr(node.func, 'attr', getattr(node.func, 'id', ''))
            == 'pallas_call']


def test_every_pallas_call_under_ops_goes_through_the_helper():
    ops = os.path.join(PKG_ROOT, 'ops')
    found = {f: _pallas_calls(os.path.join(ops, f))
             for f in sorted(os.listdir(ops)) if f.endswith('.py')}
    assert {f for f, lines in found.items() if lines} == {'kernel_call.py'}
    assert len(found['kernel_call.py']) == 1
    # and the walk sees what it guards against
    for text in ('pl.pallas_call(k)(x)', 'pallas_call(k, name="n")'):
        assert any(isinstance(n, ast.Call) and getattr(
            n.func, 'attr', getattr(n.func, 'id', '')) == 'pallas_call'
            for n in ast.walk(ast.parse(text)))


def test_the_kernels_of_a_step_are_in_the_ledger_by_name():
    """A real kernel through the helper: the flash forward's body shows
    as a ``build`` record under its Pallas name."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_attention,
    )
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    fn = _fresh(lambda q: flash_attention(q, q, q, causal=True))
    t0 = time.perf_counter()
    jax.jit(fn).lower(q)
    s = bl.summary(since=t0)
    assert 'flash_fwd' in s['kernels']
    assert s['programs'][fn.__name__]['build'] > 0


def test_the_benchmarks_reader_finds_this_ledger():
    """``benchmarks/reducers/build_ledger.py`` gives no number, quietly,
    where the package has no ledger module (a parent commit's): this
    tree must be the other case, or a rename here would take six
    metrics out of every cell with nothing failing."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks import loader
    reader = loader.Cell('mpt-7b.decode-12k').reducer('build_ledger')
    assert reader.LEDGER == bl.__name__
    assert reader.ledger() is bl
