# -*- coding: utf-8 -*-
"""
Hierarchical host-side wall-time spans — the structured successor of the
reference ``measure`` decorator (reference functions.py:24-41), grown
from a per-call print into a nestable tree an operator can read.

Two kinds of name live here, and which is for what:

- :func:`span` names HOST time (below). It reads the clock, so it never
  goes inside a jitted function.
- :func:`device_scope` names DEVICE time. It is the in-jit counterpart:
  a ``jax.named_scope`` from the fixed :data:`DEVICE_SCOPES` vocabulary,
  which puts the name on the JAX name stack and so into every HLO
  instruction's ``op_name`` that is traced under it. It reads no clock,
  adds no operation and costs nothing at run time; a profiler trace
  carries the names (the benchmark's ``benchmarks/scopes.py`` reads
  device time by scope and by pass from them). Pallas kernels carry the
  matching ``name=`` (``flash_fwd`` for ``ops.flash_fwd``: Mosaic takes
  the kernel name as a symbol, hence no dot).

Contract of ``span`` (the part graphlint enforces — see
analysis/astlint.py):

- Spans time HOST-side work: dispatch, readback, scheduling, I/O. A
  ``span`` inside a jitted function would read the clock at TRACE time
  and bake a constant into the compiled program, so the ``clock-in-jit``
  rule rejects ``span(...)`` calls in jit-decorated functions (negative
  fixture: tests/graphlint_fixtures/fx_span_in_jit.py). Wrap the
  *dispatch* of a compiled step, never its body.
- **Zero-overhead disabled path**: when collection is off (the default),
  :func:`span` returns a shared null context manager — no allocation,
  no lock, no clock read. Production code can leave spans in place.
- When enabled, each span additionally enters a
  ``jax.profiler.TraceAnnotation`` scope, so a ``jax.profiler.trace``
  capture shows the same names on the host timeline (the annotation is
  a no-op outside an active capture).
- Thread-safe: nesting is tracked per thread (thread-local stacks), the
  finished-span buffer is shared and lock-protected.

Usage::

    from distributed_dot_product_tpu.obs import span, spanned, enable

    enable(True)                      # or DDP_TPU_SPANS=1
    with span('train.step', step=i):
        record = step_fn(...)         # host dispatch + readback

    @spanned('benchmark.compile')
    def compile_phase(...): ...

    for rec in get_collector().records():
        print(rec.path, rec.seconds)
"""

import collections
import dataclasses
import functools
import os
import threading
import time
from typing import Optional, Tuple

__all__ = ['span', 'spanned', 'enable', 'enabled', 'collecting',
           'get_collector', 'SpanCollector', 'SpanRecord',
           'DEVICE_SCOPES', 'device_scope']

ENV_VAR = 'DDP_TPU_SPANS'


# Every name a compiled program may put on its operations, with what it
# covers. Scopes nest (a kernel's inside ``lm.attn_proj`` inside
# ``lm.stack_carry``); a reader attributes an operation to the
# innermost one, so each entry below reads "… that is in no scope
# further in". Prefixes follow the host spans' (``ops.``, ``lm.``,
# ``train.``).
DEVICE_SCOPES = {
    'ops.flash_fwd': 'the Pallas flash-attention forward kernel (exact, '
                     'bounded and int8-score builds; the remat forward '
                     'is the same kernel under a checkpoint name stack)',
    'ops.flash_bwd_dq': 'the Pallas flash-attention dq kernel',
    'ops.flash_bwd_dkv': 'the Pallas flash-attention dk/dv kernel, and '
                         'the fused dq/dk/dv kernel that walks as it does',
    'ops.flash_decode': 'the fused Pallas decode step kernel (append + '
                        'attend, any number of new rows)',
    'ops.flash_decode_ring': 'the same kernel in its ring mode, on a '
                             'window layer\'s recycled cache (append '
                             'column and valid interval apart); opened '
                             'INSIDE ops.flash_decode, so a reader that '
                             'knows only that name still takes it for '
                             'the decode kernel',
    'ops.mla_decode': 'the same kernel in its latent mode: one buffer of '
                      'compressed rows appended and streamed once, all '
                      'heads the rows of one score matmul, values the '
                      'leading columns of the same block',
    'lm.attn_gather': 'the all-gather of the softmax-table side (queries, '
                      'values, segment ids) over the sequence axis',
    'lm.attn_proj': 'the attention module outside its kernels: the four '
                    'projections, RoPE / ALiBi preparation, head '
                    'reshapes, padding, cache append',
    'lm.mlp': 'ln2, mlp_in, GELU and mlp_out of a block; the gated '
              'SiLU MLP of a dense layer and the shared expert of an '
              'expert layer',
    'lm.moe_route': 'an expert layer around its experts: router matmul, '
                    'sigmoid, top-k, gates, the sort by expert and the '
                    'gather into it, the weighted combine back to token '
                    'order, the per-expert token counts; on the '
                    'hit-list route (a call of few rows: a decode step) '
                    'the gate table and the step\'s hit list',
    'lm.moe_experts': 'the routed experts\' grouped matmuls (gate, up, '
                      'down over the rows sorted by expert) and their '
                      'activation; on the hit-list route (a call of few '
                      'rows: a decode step) the Pallas kernel '
                      'moe_hit_experts, which streams the experts the '
                      'step picked, scales by the gates and adds the '
                      'picks up',
    'lm.moe_latent': 'an expert layer whose experts live in a latent: the '
                     'projection of the stream down to it (once a token) '
                     'and of the combined expert output back up',
    'lm.ssm_proj': 'a Mamba-2 mixer outside its recurrence: the input '
                   'and output projections, the causal depthwise '
                   'convolution, softplus / decay, the skip, the gate and '
                   'the grouped norm',
    'ops.ssm_step': 'decode: the one read-modify-write pass over a '
                    'recurrent layer\'s state (decay, outer-product '
                    'update, the read against C)',
    'ops.ssm_scan': 'prefill / whole sequence: the recurrence in its '
                    'chunked form (decay-masked C·B^T products inside a '
                    'chunk, the state stepped between chunks)',
    'lm.delta_proj': 'a gated delta-rule mixer outside its recurrence: '
                     'the input projection, the three causal depthwise '
                     'convolutions, the L2 norms of q and k, the '
                     'low-rank decay (softplus) and rate (sigmoid), the '
                     'per-head norm under its sigmoid gate and the '
                     'output projection',
    'ops.delta_step': 'decode: the one pass over a delta-rule layer\'s '
                      'state (decay a key channel, the reduction '
                      'against k, the rank-one correction, the read '
                      'against q): the Pallas kernel delta_step and the '
                      'transposition of its column operands, or the two '
                      'XLA fusions that stand for it',
    'ops.delta_scan': 'prefill / whole sequence: the delta rule in its '
                      'chunked form (the chunks\' decay-difference '
                      'products, the batched triangular inverse, the '
                      'scan over chunks)',
    'lm.state_restore': 'copies of the recurrent layers\' states: the '
                        'snapshot at a prompt\'s end and the restore '
                        'from it between requests',
    'lm.hc': 'a hyper-connection residual: the norm over the widened '
             'stream, the three Phi products, sigmoid / Sinkhorn, the '
             'pre-mix into the branch input and the post / residual '
             'mix back into the stream',
    'lm.embed': 'the embedding gather (and its scatter-add backward)',
    'lm.head_loss': 'training: ln_f and the chunked scan that takes '
                    'loss, dx and dW from one set of logits (the logits '
                    'matmul, logsumexp, and the head_grad kernel or the '
                    'two einsums it stands for)',
    'lm.head': 'prefill / decode: ln_f and the head matmul',
    'lm.stack_carry': 'the layer stack outside its blocks\' sub-scopes: '
                      'ln1, residual adds, and the scan\'s own slicing, '
                      'copying and updating of stacked parameters, '
                      'gradients and KV caches',
    'train.grad_sync': 'the cross-shard psums of token count, loss and '
                       'gradients',
    'train.optimizer': 'optimizer.update and the parameter apply',
}


def device_scope(name):
    """``jax.named_scope(name)`` for a name in :data:`DEVICE_SCOPES`;
    any other name raises, so the vocabulary a trace reader matches
    cannot drift from the one the program opens. For use INSIDE jitted
    code (see the module docstring)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f'unknown device scope {name!r}; '
                         f'DEVICE_SCOPES has {sorted(DEVICE_SCOPES)}')
    import jax
    return jax.named_scope(name)


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One finished span. ``path`` is the slash-joined ancestry on this
    thread (``'serve.tick/engine.decode_step'``), ``depth`` its nesting
    level, ``start`` a ``perf_counter`` timestamp (comparable within the
    process only)."""
    name: str
    path: str
    start: float
    seconds: float
    depth: int
    thread: str
    attrs: Tuple[Tuple[str, object], ...] = ()
    ok: bool = True

    def as_dict(self):
        return {'name': self.name, 'path': self.path,
                'start': self.start, 'seconds': self.seconds,
                'depth': self.depth, 'thread': self.thread,
                'attrs': dict(self.attrs), 'ok': self.ok}


class SpanCollector:
    """Bounded buffer of finished spans plus per-thread nesting stacks.

    ``registry``: when set, every finished span also observes its
    duration into ``registry.histogram('span.<name>.seconds')`` — so a
    metrics snapshot / the Prometheus exporter carries span latency
    percentiles without a separate pipeline."""

    def __init__(self, *, registry=None, maxlen=65536):
        self.enabled = False
        self.registry = registry
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=maxlen)  # guarded-by: self._lock
        self._tls = threading.local()

    def _stack(self):
        stack = getattr(self._tls, 'stack', None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def add(self, record: SpanRecord):
        with self._lock:
            self._records.append(record)
        reg = self.registry
        if reg is not None:
            reg.histogram(f'span.{record.name}.seconds').observe(
                record.seconds)

    def records(self):
        with self._lock:
            return list(self._records)

    def clear(self):
        with self._lock:
            self._records.clear()

    def summary(self):
        """``{name: {'count', 'total_seconds', 'max_seconds'}}`` over the
        buffered records."""
        out = {}
        for rec in self.records():
            agg = out.setdefault(rec.name, {'count': 0,
                                            'total_seconds': 0.0,
                                            'max_seconds': 0.0})
            agg['count'] += 1
            agg['total_seconds'] += rec.seconds
            agg['max_seconds'] = max(agg['max_seconds'], rec.seconds)
        return out

    def render(self):
        """Indented one-line-per-span text tree (records are in finish
        order; depth carries the nesting)."""
        return '\n'.join(
            f'{"  " * rec.depth}{rec.name}: {rec.seconds * 1e3:.3f} ms'
            + ('' if rec.ok else ' [raised]')
            for rec in self.records())


_COLLECTOR = SpanCollector()
_COLLECTOR.enabled = bool(os.environ.get(ENV_VAR))


def get_collector() -> SpanCollector:
    return _COLLECTOR


def enable(on=True, *, registry=None):
    """Turn span collection on/off process-wide. ``registry`` (optional)
    mirrors span durations into that metrics registry's histograms."""
    _COLLECTOR.enabled = bool(on)
    if registry is not None:
        _COLLECTOR.registry = registry
    return _COLLECTOR


def enabled() -> bool:
    return _COLLECTOR.enabled


class collecting:
    """Scoped enablement (tests, ``--metrics-out`` runs)::

        with collecting() as col:
            ...
        col.records()
    """

    def __init__(self, *, registry=None):
        self._registry = registry

    def __enter__(self):
        self._prev = (_COLLECTOR.enabled, _COLLECTOR.registry)
        enable(True, registry=self._registry)
        return _COLLECTOR

    def __exit__(self, *exc):
        _COLLECTOR.enabled, _COLLECTOR.registry = self._prev
        return False


class _NullSpan:
    """The disabled path: a shared, stateless context manager. Also
    usable as a decorator (``@span('name')`` at import time with spans
    off): the wrapper re-checks enablement per call, so enabling later
    still records — the span NAME then falls back to the function's
    qualname (use :func:`spanned` to pin an explicit name)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __call__(self, fn):
        return spanned()(fn)


_NULL_SPAN = _NullSpan()


def _trace_annotation(name):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None when jax
    (or the annotation API) is unavailable. Imported lazily: the spans
    layer must stay importable without pulling jax at module load."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except (ImportError, AttributeError):
        return None


class _LiveSpan:
    """The enabled path. Created only by :func:`span` after the
    enablement check."""

    __slots__ = ('name', 'attrs', '_col', '_start', '_path', '_depth',
                 '_ann')

    def __init__(self, name, attrs, col):
        self.name = name
        self.attrs = attrs
        self._col = col

    def __enter__(self):
        stack = self._col._stack()
        self._depth = len(stack)
        stack.append(self.name)
        self._path = '/'.join(stack)
        self._ann = _trace_annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        seconds = time.perf_counter() - self._start
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._col._stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        self._col.add(SpanRecord(
            name=self.name, path=self._path, start=self._start,
            seconds=seconds, depth=self._depth,
            thread=threading.current_thread().name,
            attrs=tuple(sorted(self.attrs.items())),
            ok=exc_type is None))
        return False

    def __call__(self, fn):
        return spanned(self.name, **self.attrs)(fn)


def span(name, **attrs):
    """Nestable span context manager (see the module docstring).
    ``attrs`` are free-form key/values recorded on the span (kept small
    — they are materialized per finished span)."""
    col = _COLLECTOR
    if not col.enabled:
        return _NULL_SPAN
    return _LiveSpan(name, attrs, col)


def spanned(name=None, **attrs):
    """Decorator form: wrap every call of ``fn`` in a span. Enablement
    is re-checked per call, so decorating at import time is free until
    spans are switched on."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            col = _COLLECTOR
            if not col.enabled:
                return fn(*args, **kwargs)
            with _LiveSpan(label, attrs, col):
                return fn(*args, **kwargs)

        return wrapper

    return deco
