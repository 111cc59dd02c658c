"""Long-context decode cells: ``TransformerLM.prefill`` fills slab caches
with every session's context during set-up (in chunks; no logits are
kept), then a closed loop streams requests of ``new_tokens`` greedy
tokens through the jitted ``TransformerLM.decode`` step (cache donated,
``decode_impl`` left at the module's default), every token read back to
the host. Each request starts from the same prefilled context with its
own seeded first token, so requests differ; between requests the caches'
lengths are set back to the context (rows past a length are never read).

The loop keeps ``tokens_in_flight`` steps dispatched ahead of the token it
reads back, as a streaming server that hides the readback would: the next
greedy token is already on the device, so the host is never on the step's
path, and a stall of the host shorter than the queued work costs no
throughput (one of 80-160 ms came in half the runs on the shared
machine: chip, PR 23).
"""

import collections
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, system, weights
from benchmarks.harness import Compare, phase, window_compiles


def make_programs(model):
    """The two jitted programs of a cell: a context chunk into the
    caches (logits dropped, so the head is not built), and one token
    step returning the greedy next token and whether every logit was
    finite."""

    def prefill_fn(p, tok, c):
        return model.apply(p, tok, c, method='prefill')[0]

    def step_fn(p, tok, c):
        c, logits = model.apply(p, tok, c, method='decode')
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return c, nxt, jnp.all(jnp.isfinite(logits))

    return (jax.jit(prefill_fn, donate_argnums=(2,)),
            jax.jit(step_fn, donate_argnums=(2,)))


def seeded_tokens(seed, salt, shape, vocab):
    """Uniform tokens on the host, from numpy's generator: a few hundred
    kilobytes, and no device program to compile."""
    rng = np.random.default_rng([seed & 0xffffffff, seed >> 32, salt])
    return rng.integers(0, vocab, size=shape, dtype=np.int32)


class Server:
    """The compiled programs with weights and prefilled caches: ONE
    object that set-up builds and the window drives."""

    def __init__(self, cell, seed, attn_overrides=None, only_session=None,
                 step_wrapper=None):
        t = cell.traffic
        self.cell, self.seed = cell, seed
        # A control may serve one of the cell's sessions alone.
        self.rows = (slice(None) if only_session is None
                     else slice(only_session, only_session + 1))
        self.sessions = 1 if only_session is not None else t['sessions']
        self.context, self.new_tokens = t['context'], t['new_tokens']
        self.in_flight = t['tokens_in_flight']
        self.vocab = cell.config['vocab_size']
        self.model = system.build_lm(cell.config, **(attn_overrides or {}))
        self.context_tokens = seeded_tokens(
            seed, 1, (t['sessions'], self.context), self.vocab)[self.rows]
        self.step_wrapper = step_wrapper
        self.requests_done = 0

    def load(self, convert=None):
        t = self.cell.traffic
        with phase('init'):
            params = weights.make(self.cell.config, self.seed,
                                  self.cell.param_dtype())
            if convert is not None:
                params = convert(params)
            jax.block_until_ready(params)
        self.params = params
        prefill, step = make_programs(self.model)
        caches = self.model.make_decode_caches(self.sessions, t['t_max'])
        chunk = t['prefill_chunk']
        tok0 = jnp.asarray(self.context_tokens[:, :chunk])
        one = jnp.zeros((self.sessions, 1), jnp.int32)
        from distributed_dot_product_tpu.models.decode import (
            decode_impl_traces,
        )
        with phase('lower'), decode_impl_traces() as traces:
            low_prefill = prefill.lower(params, tok0, caches)
            low_step = step.lower(params, one, caches)
        self.decode_impl = sorted({t['resolved'] for t in traces})
        with phase('compile'):
            prefill = low_prefill.compile()
            step = low_step.compile()
        self.custom_calls = step.as_text().count('tpu_custom_call')
        with phase('prefill'):
            for i in range(0, self.context, chunk):
                caches = prefill(
                    params, jnp.asarray(self.context_tokens[:, i:i + chunk]),
                    caches)
            jax.block_until_ready(caches)
        self.caches = caches
        self.length0 = np.asarray(caches.length)
        if not np.all(self.length0 == self.context):
            raise RuntimeError(f'prefill left lengths {self.length0}')
        self._step = (self.step_wrapper(step) if self.step_wrapper
                      else step)

    def first_tokens(self, request):
        return seeded_tokens(self.seed, 1000 + request,
                             (self.cell.traffic['sessions'], 1),
                             self.vocab)[self.rows]

    def request(self, tracer=None, forced=None, steps=None,
                request_index=None):
        """One request: ``new_tokens`` steps from the prefilled context.
        Returns ``(first token, tokens (sessions, new_tokens), gaps in
        seconds between consecutive readbacks, steps with a non-finite
        logit)``. ``forced`` (sessions, new_tokens) feeds those tokens
        in place of the program's own (teacher forcing, for a control)."""
        span = tracer.span if tracer else (
            lambda name: contextlib.nullcontext())
        first = self.first_tokens(self.requests_done if request_index
                                  is None else request_index)
        self.requests_done += 1
        self.caches = self.caches._replace(
            length=jnp.asarray(self.length0))
        tok = jnp.asarray(first)
        out, stamps, bad = [], [], 0
        pending = collections.deque()

        def read_back():
            nxt, ok = pending.popleft()
            with span('bench.readback'):
                out.append(np.asarray(nxt))
                bad_step = not bool(ok)
            stamps.append(time.perf_counter())
            return bad_step

        for i in range(steps or self.new_tokens):
            with span('bench.dispatch'):
                self.caches, nxt, ok = self._step(self.params, tok,
                                                  self.caches)
            pending.append((nxt, ok))
            if len(pending) > self.in_flight:
                bad += read_back()
            tok = (nxt if forced is None
                   else jnp.asarray(forced[:, i:i + 1]))
        while pending:
            bad += read_back()
        gaps = np.diff(np.asarray(stamps))
        return first, np.concatenate(out, axis=1), gaps, bad

    def free(self):
        del self.params, self.caches, self._step


def reference_logits(cell, seed, context, first, tokens,
                     operand_dtype=None):
    """The plain reference once over one session's context, first token
    and served tokens: its logits ``(served tokens, vocab)`` at the
    positions that produced them."""
    from benchmarks.reference import common
    family = cell.reference()
    sizes = weights.model_sizes(cell.config)
    params = weights.make(cell.config, seed, cell.param_dtype(),
                          upcast=True)
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    block = common.ROW_BLOCK
    pad = (-len(seq)) % block
    # Rows are causal: padding after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    logits = jax.jit(lambda p, s: common.logits_at(
        family, sizes, p, s, n + pad, operand_dtype))(params, seq)[:n]
    del params
    return np.asarray(logits)


def logit_gaps(logits, tokens):
    """For each position, how far the token's logit lies below the
    best."""
    chosen = np.take_along_axis(logits, np.asarray(tokens)[:, None], -1)
    return logits.max(-1) - chosen[:, 0]


def sample_requests(seed, finished, sessions, count):
    """A sample, drawn from the seed, of (request, session) pairs among
    the finished requests, the last one finished in it."""
    rng = np.random.default_rng([seed & 0xffffffff, seed >> 32, 7])
    picks = [(len(finished) - 1, int(rng.integers(sessions)))]
    while len(picks) < min(count, len(finished) * sessions):
        pick = (int(rng.integers(len(finished))),
                int(rng.integers(sessions)))
        if pick not in picks:
            picks.append(pick)
    return picks


def run(cell, seed, seconds, trace, tracer, step_wrapper=None):
    t = cell.traffic
    compare = Compare()
    server = Server(cell, seed, step_wrapper=step_wrapper)
    server.load()
    with phase('warm'):
        server.request(steps=4)
        server.requests_done = 0
    print(json.dumps({'decode_impl': server.decode_impl,
                      'custom_calls_in_step': server.custom_calls}),
          flush=True)
    setup_done = time.perf_counter()

    finished, gaps, bad = [], [], 0
    with window_compiles() as compiles, tracer.window(trace):
        t0 = time.perf_counter()
        limit = t['trace_requests'] if trace else None
        while True:
            first, tokens, g, b = server.request(tracer)
            finished.append((first, tokens))
            gaps.append(g)
            bad += b
            if (time.perf_counter() - t0 >= seconds
                    or (limit and len(finished) >= limit)):
                break
        elapsed = time.perf_counter() - t0
    gaps = np.concatenate(gaps)
    steps = len(finished) * server.new_tokens
    served = steps * server.sessions
    print(json.dumps({
        'decode_gap_ms_p50': float(np.median(gaps)) * 1e3,
        'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3,
        'decode_gap_ms_max': float(np.max(gaps)) * 1e3,
        'window_s': elapsed, 'gaps': int(gaps.size), 'requests': len(finished)}), flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_logit_steps', bad, 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel'] else 1,
                cell.limits.get('decode_impl_is_kernel'))
    context = server.context_tokens
    sessions = server.sessions
    server.free()
    with phase('reference', counted=False):
        worst = 0.0
        for r, s in sample_requests(seed, finished, sessions,
                                    t['check_samples']):
            first, tokens = finished[r]
            logits = reference_logits(cell, seed, context[s], first[s],
                                      tokens[s])
            worst = max(worst, float(np.max(logit_gaps(logits,
                                                       tokens[s]))))
    compare.add('served_logit_gap', worst,
                cell.limits.get('served_logit_gap'))
    sizes = weights.model_sizes(cell.config)
    mid = server.context + server.new_tokens // 2
    return {
        'compare': compare, 'attempted': steps, 'failed': bad,
        'setup_done': setup_done,
        'end_to_end': {
            'decode_tokens_per_s': served / elapsed,
            'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3},
        'observed': {
            'steps': steps, 'window_s': elapsed, 'chips': cell.chips,
            'decode_per_step': flops.decode_step(sizes, sessions, mid),
        },
    }
