"""The long-context decode cell of a stack of window and full attention
layers over sparse experts (``cohere2_moe``: Command A+):
``drivers/decode.py``'s closed loop of greedy requests over prefilled
sessions, with what that architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: a period of layer kinds, the parallel block, the share
  of experts held) and its seeded weights from this file's shape table
  (``shapes`` / ``make``; leaf by leaf as ``decode_latent.make`` draws
  them, the router in float32).
- The caches are a LIST, one a layer, each of its kind's geometry: a
  ring of ``serving.ring_capacity`` columns for a window layer, a slab
  of ``t_max`` for a full one (``TransformerLM.make_decode_caches``).
  Set-up prefills ONE SESSION AT A TIME into one-session caches and puts
  it in its slot of the serving batch's (``models/decode.insert_session``).
  Between requests every layer's length is set back to the context; a
  ring needs no more than that while its capacity is at least the window
  plus a request's tokens (the rows a request overwrote lay outside the
  window already), which ``Server`` checks.
- The step also returns, accumulated on the device and read once a
  request, the expert layers' counters as ``decode_latent`` keeps them;
  the distinct experts a step are counted over the experts HELD here.
- ``correct``: the reference's logits (``reference/command_a_plus.py``,
  one whole session in row blocks) at the timed run's own tokens, fed
  the program's expert picks and judging them by its own router scores
  (``decode_latent``'s three numbers), non-finite values, compilations
  in the window, and BOTH kernel modes resolved: the slab step of the
  full layers and the ring step of the window layers. The sampled
  request is never the first of the window: an untimed run serves at
  least two, so the one compared follows a reset of the lengths and a
  ring that lost rows to it would show.
- The window is whole requests until ``--seconds`` have passed AND the
  traffic's ``min_requests`` are served: a step's time hangs on how many
  of the held experts its 96 picks hit, which a few hundred steps
  sample too coarsely for the metrics' bounds, and a host stall of
  ~120 ms (the four steps in flight cover 59) is that much less of a
  longer window.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_mixed
from benchmarks.drivers import decode
from benchmarks.drivers.decode import logit_gaps, sample_requests
from benchmarks.drivers.decode_latent import draw_leaf
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import split_seed

FLOAT32_LEAVES = ('router',)


def layer_kinds(config):
    return config['layer_types'][:config['num_hidden_layers']]


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    c = config
    if c['first_k_dense_replace'] or not c['use_parallel_block']:
        raise ValueError('this driver builds the parallel block with no '
                         'leading dense layer')
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=c['tie_word_embeddings'],
        logit_scale=float(c['logit_scale']),
        attn_kwargs={
            'key_dim': c['num_attention_heads'] * c['head_dim'],
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': c['attention_bias'],
            'rope_base': float(c['rope_theta']),
            'rope_layout': 'interleaved', **attn_overrides},
        block_kwargs={
            'norm': 'layernorm_nobias', 'norm_eps': c['layer_norm_eps'],
            'parallel': True, 'ffn': 'experts',
            'ffn_kwargs': {
                'n_experts': c['published']['num_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['intermediate_size'],
                'n_shared': c['num_shared_experts'],
                'shared_combine': 'mean', 'router_bias': False,
                'norm_topk': c['norm_topk_prob'],
                'experts_held': tuple(c['experts_held'])}},
        layer_kinds={
            'sliding_attention': {'attn_kwargs': {
                'use_rope': True, 'window': c['sliding_window'],
                'ring_cache': c['serving']['ring_capacity']}},
            'full_attention': {'attn_kwargs': {'use_rope': False}}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v, w = c['hidden_size'], c['vocab_size'], c['intermediate_size']
    q = c['num_attention_heads'] * c['head_dim']
    kv = c['num_key_value_heads'] * c['head_dim']
    lo, hi = c['experts_held']
    shared = c['num_shared_experts'] * w
    # Wq and Wk are drawn wider by sqrt(score_std) each, so that a score
    # q·k / sqrt(head_dim) has that standard deviation (the
    # configuration's ``init`` says why).
    peaked = d / c['init']['attention_score_std']
    block = {
        ('ln1', 'scale'): ((d,), None),
        ('attn', 'keys', 'kernel'): ((d, q), peaked),
        ('attn', 'queries', 'kernel'): ((d, kv), peaked),
        ('attn', 'values', 'kernel'): ((d, kv), d),
        ('attn', 'composition', 'kernel'): ((q, d), q),
        ('moe', 'router'): ((d, c['published']['num_experts']), d),
        ('moe', 'w_gate'): ((hi - lo, d, w), d),
        ('moe', 'w_up'): ((hi - lo, d, w), d),
        ('moe', 'w_down'): ((hi - lo, w, d), w),
        ('moe', 'shared', 'gate', 'kernel'): ((d, shared), d),
        ('moe', 'shared', 'up', 'kernel'): ((d, shared), d),
        # One expert's down kernel has fan-in w: the four side by side
        # are four experts, not one of four times the width.
        ('moe', 'shared', 'down', 'kernel'): ((shared, d), w)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None)}
    for i in range(c['num_hidden_layers']):
        for path, leaf in block.items():
            out[('stack', f'block_{i}') + path] = leaf
    return out


def make(config, seed, dtype):
    """The seeded tree ``{'params': ...}``, rounded to ``dtype`` (the
    router stays float32): ``decode_latent.make``'s one small jitted
    draw a leaf, each placed before the next is drawn."""
    init = tuple(sorted((k, v) for k, v in config['init'].items()
                        if not isinstance(v, str)))
    lo, hi = split_seed(seed)
    tree = {}
    for i, (path, (shape, fan_in)) in enumerate(
            sorted(shapes(config).items())):
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        to = jnp.float32 if path[-1] in FLOAT32_LEAVES else dtype
        leaf = draw_leaf(lo, hi, np.int32(i), path[-1], shape, fan_in,
                         jnp.dtype(to), init, 0)
        if (path[-1] == 'router'
                and config['init'].get('router_columns') == 'unit_norm'):
            leaf = unit_columns(leaf)
        node[path[-1]] = leaf.block_until_ready()
    return {'params': tree}


@jax.jit
def unit_columns(router):
    """Every expert's router column at unit norm (the configuration's
    ``init`` says why: a top-8 of 128 turns a 2 % difference in norm
    into a 7 % difference in how often an expert is picked)."""
    return router / jnp.linalg.norm(router, axis=0, keepdims=True)


class LayerCaches:
    """The layers' caches as the ONE object ``decode.Server.request``
    carries: ``length`` and ``_replace(length=)`` reach every layer (a
    buffer a layer, since the step donates them)."""

    def __init__(self, layers):
        self.layers = list(layers)

    @property
    def length(self):
        return self.layers[0].length

    def _replace(self, length):
        return LayerCaches(c._replace(length=jnp.array(length, jnp.int32))
                           for c in self.layers)


def check_ring_room(caches, window, new_tokens):
    """A reset sets the lengths back and restores nothing: a ring must
    hold a request's rows beside the window they would recycle."""
    for cache in caches:
        room = getattr(cache, 'capacity', None)
        if room is not None and room < window + new_tokens:
            raise ValueError(
                f'a ring of {room} columns would lose rows of the window '
                f'to a request of {new_tokens} tokens')


def zero_stats(config, traffic):
    layers = config['num_hidden_layers']
    return {
        'expert_tokens': jnp.zeros(
            (layers, config['published']['num_experts']), jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_counters(config, sown):
    """The expert layers' counters with a leading layer axis."""
    stack = sown['counters']['stack']
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        stack[f'block_{i}']['moe']
        for i in range(config['num_hidden_layers'])])


def make_programs(model, config):
    """A context chunk of one session into its caches, returning the
    chunk's expert picks ``(layers, chunk, k)`` (logits dropped, so the
    head is not built); a finished session into its slot of every
    layer's cache; and one token step returning the greedy next token,
    whether every logit was finite, and the expert counters added to
    ``stats``."""
    from distributed_dot_product_tpu.models.decode import insert_session
    lo, hi = config['experts_held']

    def prefill_fn(p, tok, c):
        (c, _), sown = model.apply(p, tok, c, method='prefill',
                                   mutable=['counters'])
        return c, sown_counters(config, sown)['expert_picks']

    def insert_fn(caches, session, one):
        return [insert_session(c, session, o)
                for c, o in zip(caches, one)]

    def step_fn(p, tok, c, stats):
        (c, logits), sown = model.apply(p, tok, c, method='decode',
                                        mutable=['counters'])
        moe = sown_counters(config, sown)
        counts = moe['expert_tokens']         # (layers, router width)
        stats = {
            'expert_tokens': stats['expert_tokens'] + counts,
            'active': stats['active'] + jnp.sum(counts[:, lo:hi] > 0),
            'picks': jax.lax.dynamic_update_index_in_dim(
                stats['picks'], moe['expert_picks'], stats['step'], 0),
            'step': stats['step'] + 1}
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return c, nxt, jnp.all(jnp.isfinite(logits)), stats

    return (jax.jit(prefill_fn, donate_argnums=(2,)),
            jax.jit(insert_fn, donate_argnums=(0,)),
            jax.jit(step_fn, donate_argnums=(2, 3)))


class Server(decode.Server):
    """``decode.Server``'s request loop over this model: a cache a
    layer, and the expert counters carried beside them."""

    def __init__(self, cell, seed, attn_overrides=None, step_wrapper=None):
        t = cell.traffic
        self.cell, self.seed = cell, seed
        self.rows = slice(None)
        self.sessions = t['sessions']
        self.context, self.new_tokens = t['context'], t['new_tokens']
        self.in_flight = t['tokens_in_flight']
        self.vocab = cell.config['vocab_size']
        self.model = build_lm(cell.config, **(attn_overrides or {}))
        self.context_tokens = decode.seeded_tokens(
            seed, 1, (t['sessions'], self.context), self.vocab)
        self.step_wrapper = step_wrapper
        self.requests_done = 0
        self.stats_read = []

    def load(self, convert=None):
        from distributed_dot_product_tpu.models.decode import (
            decode_impl_traces,
        )
        t, config = self.cell.traffic, self.cell.config
        with phase('init'):
            params = make(config, self.seed, self.cell.param_dtype())
            if convert is not None:
                params = convert(params)
            jax.block_until_ready(params)
        self.params = params
        prefill, insert, step = make_programs(self.model, config)
        caches = self.model.make_decode_caches(self.sessions, t['t_max'])
        one = self.model.make_decode_caches(1, t['t_max'])
        check_ring_room(caches, config['sliding_window'], self.new_tokens)
        self.cache_gib = flops_mixed.cache_gib(caches)
        chunk = t['prefill_chunk']
        per = self.context // chunk
        tok0 = jnp.asarray(self.context_tokens[:1, :chunk])
        tok1 = jnp.zeros((self.sessions, 1), jnp.int32)
        stats = zero_stats(config, t)
        with phase('lower'), decode_impl_traces() as traces:
            low_prefill = prefill.lower(params, tok0, one)
            low_insert = insert.lower(caches, 0, one)
            low_step = step.lower(params, tok1, caches, stats)
        # What every layer's step resolved to, by the cache it was on.
        self.decode_impl = sorted({f"{t['resolved']}:{t['cache']}"
                                   for t in traces})
        self.kernel_steps = [t['step'] for t in traces]
        with phase('compile'):
            prefill = low_prefill.compile()
            insert = low_insert.compile()
            step = low_step.compile()
        self.custom_calls = step.as_text().count('tpu_custom_call')
        with phase('prefill'):
            picks = []
            for s in range(self.sessions):
                one = [c._replace(length=jnp.zeros_like(c.length))
                       for c in one]
                for i in range(0, self.context, chunk):
                    one, picked = prefill(params, jnp.asarray(
                        self.context_tokens[s:s + 1, i:i + chunk]), one)
                    picks.append(picked)
                caches = insert(caches, s, one)
            jax.block_until_ready(caches)
            # (sessions, layers, context, k): every pick the program
            # made of the context, for the reference to follow.
            self.context_picks = np.stack([
                np.concatenate(jax.device_get(
                    picks[s * per:(s + 1) * per]), axis=1)
                for s in range(self.sessions)])
        del one, picks
        self.caches = LayerCaches(caches)
        self.length0 = np.asarray(self.caches.length)
        lengths = [int(c.length) for c in caches]
        if lengths != [self.context] * len(caches):
            raise RuntimeError(f'prefill left lengths {lengths}')
        self.stats = stats
        compiled = self.step_wrapper(step) if self.step_wrapper else step

        def with_stats(params, tok, caches):
            layers, nxt, ok, self.stats = compiled(
                params, tok, caches.layers, self.stats)
            return LayerCaches(layers), nxt, ok
        self._step = with_stats

    def request(self, *args, **kwargs):
        self.stats = zero_stats(self.cell.config, self.cell.traffic)
        out = super().request(*args, **kwargs)
        self.stats_read.append(jax.device_get(self.stats))
        return out

    def free(self):
        del self.caches, self._step, self.stats


def reference_logits(cell, params, context, first, tokens, picks,
                     operand_dtype=None):
    """The plain reference once over one session's context, first token
    and served tokens, following the program's expert picks ``(layers,
    context + served tokens, k)``: its logits ``(served tokens, vocab)``
    at the positions that produced them, the share of the (token, layer)
    pairs it computed at which its OWN pick is another set of experts,
    and the largest regret of the program's picks by its own scores
    (``reference/command_a_plus.route``)."""
    ref = cell.reference()
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    rows = len(seq)
    pad = (-rows) % ref.ROW_BLOCK
    # Rows are causal: padding after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    forced = np.pad(picks, ((0, 0), (0, pad), (0, 0)))
    logits, own, regret, judged = ref.logits_at(
        cell.config, params, jnp.asarray(seq), n + pad, operand_dtype,
        forced_picks=jnp.asarray(forced))
    judged = np.asarray(judged)[:, :rows]
    differ = np.any(np.sort(np.asarray(own)[:, :rows], axis=-1)
                    != np.sort(picks, axis=-1), axis=-1)
    return (np.asarray(logits[:n]), float(np.mean(differ[judged])),
            float(np.max(np.asarray(regret)[:, :rows][judged])))


def routing_readings(config, stats_read, sessions):
    """What the counters say of the window's routing, over the experts
    held here."""
    lo, hi = config['experts_held']
    tokens = sum(s['expert_tokens'] for s in stats_read)[:, lo:hi]
    steps = sum(int(s['step']) for s in stats_read)
    return {
        'active_experts_per_step': (
            sum(int(s['active']) for s in stats_read) / max(steps, 1)),
        'load_max_over_mean': float(np.max(
            tokens.max(axis=1) / np.maximum(tokens.mean(axis=1), 1e-9))),
        'expected_active_per_step': config['num_hidden_layers'] * (
            flops_mixed.expected_distinct_held(config, sessions)),
        'expert_bytes': flops_mixed.expert_bytes(config),
        'counted_steps': steps}


def run(cell, seed, seconds, trace, tracer, step_wrapper=None,
        operand_dtype=None):
    t = cell.traffic
    compare = Compare()
    server = Server(cell, seed, step_wrapper=step_wrapper)
    server.load()
    with phase('warm'):
        server.request(steps=4)
        server.requests_done = 0
        server.stats_read.clear()
    finished, gaps, bad = [], [], 0
    # Traced: trace_requests, which follow the warm request's reset.
    # Untimed: at least two, so the one compared follows a whole
    # request's rows and the reset after them.
    at_least = (t['trace_requests'] if trace
                else max(2, t.get('min_requests', 2)))
    print(json.dumps({'decode_impl': server.decode_impl,
                      'kernel_steps': server.kernel_steps,
                      'custom_calls_in_step': server.custom_calls,
                      'cache': server.cache_gib}), flush=True)
    setup_done = time.perf_counter()
    with window_compiles() as compiles, tracer.window(trace):
        t0 = time.perf_counter()
        while True:
            first, tokens, g, b = server.request(tracer)
            finished.append((first, tokens))
            gaps.append(g)
            bad += b
            if len(finished) >= at_least and (
                    trace or time.perf_counter() - t0 >= seconds):
                break
        elapsed = time.perf_counter() - t0
    gaps = np.concatenate(gaps)
    steps = len(finished) * server.new_tokens
    served = steps * server.sessions
    routing = routing_readings(cell.config, server.stats_read,
                               server.sessions)
    served_tokens = np.stack([tokens for _, tokens in finished])
    print(json.dumps({
        # Of the tokens served, how many differ: greedy continuations
        # that fall into one attractor route alike, and the experts hit
        # a step (so the step's time) then hang on the seed.
        'distinct_token_share': len(np.unique(served_tokens))
        / served_tokens.size,
        'decode_gap_ms_p50': float(np.median(gaps)) * 1e3,
        'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3,
        'decode_gap_ms_max': float(np.max(gaps)) * 1e3,
        'window_s': elapsed, 'gaps': int(gaps.size),
        'requests': len(finished), **routing}), flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_logit_steps', bad, 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel:layer', 'kernel:ring']
                else 1, cell.limits.get('decode_impl_is_kernel'))
    context, sessions = server.context_tokens, server.sessions
    params, served_picks = server.params, [s['picks']
                                           for s in server.stats_read]
    cache_gib = server.cache_gib
    server.free()
    del server.params
    if t['check_samples'] != 1:
        raise ValueError('one sample: the reference takes a minute')
    with phase('reference', counted=False):
        (r, s), = sample_requests(seed, finished, sessions, 1)
        first, tokens = finished[r]
        # (layers, context + served, k) of session s, request r
        picks = np.concatenate(
            [server.context_picks[s],
             np.moveaxis(served_picks[r][:, :, s], 0, 1)], axis=1)
        logits, differ, regret = reference_logits(
            cell, params, context[s], first[s], tokens[s], picks,
            operand_dtype)
        gaps_ref = logit_gaps(logits, tokens[s])
    print(json.dumps({'sampled_request': r, 'sampled_session': s,
                      'served_logit_gap_quantiles': [
        float(np.percentile(gaps_ref, q)) for q in (50, 90, 99, 100)]}),
        flush=True)
    compare.add('served_logit_gap', float(np.max(gaps_ref)),
                cell.limits.get('served_logit_gap'))
    compare.add('expert_pick_difference_share', differ,
                cell.limits.get('expert_pick_difference_share'))
    compare.add('router_pick_regret', regret,
                cell.limits.get('router_pick_regret'))
    mid = server.context + server.new_tokens // 2
    return {
        'compare': compare, 'attempted': steps, 'failed': bad,
        'setup_done': setup_done,
        'end_to_end': {
            'decode_tokens_per_s': served / elapsed,
            'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3},
        'observed': {
            'steps': steps, 'window_s': elapsed, 'chips': cell.chips,
            'full_decode_per_step': flops_mixed.full_decode_step(
                cell.config, sessions, mid),
            'ring_decode_per_step': flops_mixed.ring_decode_step(
                cell.config, sessions, mid),
            'moe': routing, 'cache': cache_gib,
        },
    }
