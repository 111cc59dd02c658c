"""The decode cell of a ``solar_open2`` stack (Solar Open 2: three gated
delta-rule (KDA) layers to one gated NoPE GQA layer, and gated experts
beside a shared one in EVERY layer): ``drivers/decode_granite.py``'s
closed loop of greedy requests over prefilled sessions — its programs
(prefill a session alone, insert it, snapshot, finite check, restore,
the token step with its expert counters), ``decode_hybrid``'s
``LayerCaches`` and seeded draws, its comparison of logits, picks and
STATES with the reference — with what this architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: the layers in ``gqa_layers`` a GQA mixer with its
  output gate, every other a ``'delta'`` mixer, each followed by the
  experts; sigmoid scores, a correction bias, normalised gates; an
  untied head) and its seeded weights from this file's shape table
  (``shapes`` / ``make``; the router, its bias and the recurrence's
  ``A_log`` / ``dt_bias`` stay float32), every router's columns then
  made orthogonal to the mean of its input (``level_routers``, by the
  plain reference over seeded tokens: weights from the seed alone).
- A program without the delta-rule mixer fails in ``build_lm``, at once,
  before a weight is drawn.
- ``correct`` also holds every traced delta mixer's step to the form its
  counter names (``models/delta.delta_step_traces``: the kernel
  ``delta_step``, one a recurrent layer), beside the slab step on the
  decode kernel and every ``SparseExperts`` trace on the hit list by the
  rule's bound — at 128 rows, the bound itself.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_solar
from benchmarks.drivers import decode, decode_granite
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_granite import (
    make_programs, reference_readings,
)
from benchmarks.drivers.decode_hybrid import (
    LayerCaches, draw_leaf, sampled_session, slab_length,
)
from benchmarks.drivers.decode_mixed import unit_columns
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import split_seed

FLOAT32_LEAVES = ('router', 'router_bias', 'A_log', 'dt_bias')

layer_kinds = flops_solar.layer_kinds


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    # A program without the delta-rule mixer fails HERE, at once.
    from distributed_dot_product_tpu.models.delta import (  # noqa: F401
        GatedDeltaMixer,
    )
    c = config
    linear = c['linear_attn_config']
    if (c['use_rope'] or c['tie_word_embeddings'] or c['kda_use_full_proj']
            or c['first_k_dense_replace'] or c['n_shared_experts'] != 1
            or linear['num_kv_heads'] not in (None, linear['num_heads'])):
        raise ValueError('this driver builds NoPE attention, low-rank KDA '
                         'gates with as many key as value heads, experts '
                         'in every layer beside one shared expert, and an '
                         'untied head')
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=False,
        attn_kwargs={
            'key_dim': c['num_attention_heads'] * c['head_dim'],
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': False, 'use_rope': False,
            'out_gate': c['use_gqa_gate'], **attn_overrides},
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['rms_norm_eps'],
            'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['published']['n_routed_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['moe_intermediate_size'],
                'n_shared': c['n_shared_experts'],
                'scaling': float(c['routed_scaling_factor']),
                'norm_topk': c['norm_topk_prob'],
                'experts_held': tuple(c['experts_held'])}},
        layer_kinds={
            'kda': {'mixer': 'delta', 'ssm_kwargs': {
                'heads': linear['num_heads'],
                'head_dim': linear['head_dim'],
                'conv': linear['short_conv_kernel_size'],
                'chunk': c['kda_chunk_size'],
                'beta_scale': 2.0 if c['kda_allow_neg_eigval'] else 1.0,
                'state_dtype': jnp.dtype(c['precision']['state'])}},
            'gqa': {'mixer': 'attention'}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v = c['hidden_size'], c['vocab_size']
    q = c['num_attention_heads'] * c['head_dim']
    kv = c['num_key_value_heads'] * c['head_dim']
    heads, dim, taps = flops_solar.delta_sizes(c)
    inner, rank = heads * dim, dim
    w = c['moe_intermediate_size']
    shared = c['n_shared_experts'] * w
    held = flops_solar.experts_held(c)
    # Wq and Wk are drawn wider by sqrt(score_std) each, so that a score
    # q·k / sqrt(head_dim) has that standard deviation (the
    # configuration's ``init`` says why).
    peaked = d / c['init']['attention_score_std']
    mixers = {
        'kda': {
            ('delta', 'in_proj', 'kernel'): (
                (d, 3 * inner + 2 * rank + heads), d),
            ('delta', 'conv_kernel'): ((taps, 3 * inner), taps),
            ('delta', 'decay_up', 'kernel'): ((rank, inner), rank),
            ('delta', 'gate_up', 'kernel'): ((rank, inner), rank),
            ('delta', 'dt_bias'): ((inner,), None),
            ('delta', 'A_log'): ((heads,), None),
            ('delta', 'norm_scale'): ((dim,), None),
            ('delta', 'out_proj', 'kernel'): ((inner, d), inner)},
        'gqa': {
            ('attn', 'keys', 'kernel'): ((d, q), peaked),
            ('attn', 'queries', 'kernel'): ((d, kv), peaked),
            ('attn', 'values', 'kernel'): ((d, kv), d),
            ('attn', 'gate', 'kernel'): ((d, q), d),
            ('attn', 'composition', 'kernel'): ((q, d), q)}}
    experts = {
        ('moe', 'router'): ((d, c['published']['n_routed_experts']), d),
        ('moe', 'router_bias'): ((c['published']['n_routed_experts'],),
                                 None),
        ('moe', 'w_gate'): ((held, d, w), d),
        ('moe', 'w_up'): ((held, d, w), d),
        ('moe', 'w_down'): ((held, w, d), w),
        ('moe', 'shared', 'gate', 'kernel'): ((d, shared), d),
        ('moe', 'shared', 'up', 'kernel'): ((d, shared), d),
        ('moe', 'shared', 'down', 'kernel'): ((shared, d), shared)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None),
           ('lm_head_kernel',): ((d, v), d)}
    for i, kind in enumerate(layer_kinds(c)):
        block = ('stack', f'block_{i}')
        out[block + ('ln1', 'scale')] = ((d,), None)
        out[block + ('ln2', 'scale')] = ((d,), None)
        for path, leaf in {**mixers[kind], **experts}.items():
            out[block + path] = leaf
    return out


def make(config, seed, dtype):
    """The seeded tree ``{'params': ...}`` of this file's shape table,
    drawn a leaf at a time as ``decode_hybrid.make`` draws its own
    (``draw_leaf``: the same rules by a leaf's name)."""
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
                         jnp.dtype(to), init)
        if (path[-1] == 'router'
                and config['init'].get('router_columns') == 'unit_norm'):
            leaf = unit_columns(leaf)
        node[path[-1]] = leaf.block_until_ready()
    return {'params': tree}


def level_routers(config, params, seed):
    """Every router's columns made orthogonal to the mean of its own
    input, and set back to unit norm (``init['router_level_tokens']``;
    a configuration without the key keeps its draw). Why: q, k and v of
    a delta-rule mixer come out of a SiLU, so the mixer's output has a
    component common to EVERY token; a seeded router reads it as a
    fixed offset an expert, the loads read 4-7x the mean and a step's
    hit count hangs on which held experts the seed favoured (PERF.md
    section 6, PR 39). A trained router has no such offset. The mean is
    taken by the PLAIN reference over that many seeded tokens, layer
    after layer (a layer's input follows the routers before it), so the
    weights are a function of the seed and of nothing the program
    computes."""
    n = config['init'].get('router_level_tokens')
    if not n:
        return params
    from benchmarks.reference import solar_open2 as ref
    p = params['params']
    tokens = decode.seeded_tokens(seed, 2, (n,), config['vocab_size'])

    def level(ln2, router, x):
        c = jnp.mean(ref.norm(config, ln2, x), axis=0)
        c = c / jnp.linalg.norm(c)
        return unit_columns(router - jnp.outer(c, c @ router))

    stack = dict(p['stack'])
    with jax.default_matmul_precision('highest'):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            p['embed']['embedding'], tokens)
        for i, kind in enumerate(layer_kinds(config)):
            lp = stack[f'block_{i}']
            x = jax.jit(
                (lambda lp, x: ref.delta_branch(config, lp, x)[0])
                if kind == 'kda' else
                (lambda lp, x: ref.attention_branch(config, lp, x)))(lp, x)
            router = jax.jit(level)(lp['ln2'], lp['moe']['router'], x)
            lp = stack[f'block_{i}'] = {
                **lp, 'moe': {**lp['moe'], 'router': router}}
            x = jax.jit(
                lambda lp, x: ref.experts_branch(config, lp, x)[0])(lp, x)
    return {'params': {**p, 'stack': stack}}


def zero_stats(config, traffic):
    layers = config['num_hidden_layers']
    return {
        'expert_tokens': jnp.zeros(
            (layers, config['published']['n_routed_experts']), jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


class Server(decode_granite.Server):
    """``decode_granite.Server`` (``decode_hybrid.Server``'s request
    loop, snapshot and counters; its check of the expert routes) over
    this model."""

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
        self.sampled = sampled_session(seed, self.sessions)
        self.step_wrapper = step_wrapper
        self.requests_done = 0
        self.stats_read = []

    def load(self, convert=None):
        from distributed_dot_product_tpu.models.decode import (
            decode_impl_traces,
        )
        from distributed_dot_product_tpu.models.delta import (
            delta_step_traces,
        )
        from distributed_dot_product_tpu.models.moe import (
            expert_route_traces,
        )
        t, config = self.cell.traffic, self.cell.config
        with phase('init'):
            params = level_routers(
                config, make(config, self.seed, self.cell.param_dtype()),
                self.seed)
            if convert is not None:
                params = convert(params)
            jax.block_until_ready(params)
        self.params = params
        prefill, insert, snapshot, finite, restore, step = make_programs(
            self.model, config)
        caches = self.model.make_decode_caches(self.sessions, t['t_max'])
        one = self.model.make_decode_caches(1, t['t_max'])
        self.cache_gib = flops_solar.cache_gib(caches)
        chunk = t['prefill_chunk']
        tok0 = jnp.asarray(self.context_tokens[:1, :chunk])
        tok1 = jnp.zeros((self.sessions, 1), jnp.int32)
        stats = zero_stats(config, t)
        states = [c if hasattr(c, 'state') else None for c in caches]
        with phase('lower'):
            low_prefill = prefill.lower(params, tok0, one)
            low_insert = insert.lower(caches, 0, one)
            low_snapshot = snapshot.lower(caches)
            low_finite = finite.lower(caches)
            low_restore = restore.lower(caches, states,
                                        jnp.zeros((), jnp.int32))
            with decode_impl_traces() as traces, \
                    expert_route_traces() as routes, \
                    delta_step_traces() as forms:
                low_step = step.lower(params, tok1, caches, stats)
        # What the step's attention layer resolved to, by the cache it
        # was on, the route each expert layer's call took and the form
        # of each delta mixer's step.
        self.decode_impl = sorted({f"{t['resolved']}:{t['cache']}"
                                   for t in traces})
        self.kernel_steps = [t['step'] for t in traces]
        self.expert_routes = routes
        self.delta_forms = forms
        with phase('compile'):
            prefill = low_prefill.compile()
            insert = low_insert.compile()
            snapshot = low_snapshot.compile()
            finite = low_finite.compile()
            restore = low_restore.compile()
            step = low_step.compile()
        self.custom_calls = step.as_text().count('tpu_custom_call')
        with phase('prefill'):
            for s in range(self.sessions):
                one = [jax.tree.map(jnp.zeros_like, c) for c in one]
                picks = []
                for i in range(0, self.context, chunk):
                    one, picked = prefill(params, jnp.asarray(
                        self.context_tokens[s:s + 1, i:i + chunk]), one)
                    if s == self.sampled:
                        picks.append(picked)
                if picks:
                    # (layers, context, k): every pick the program made
                    # of the sampled session's context, for the
                    # reference to follow.
                    self.context_picks = np.concatenate(
                        jax.device_get(picks), axis=1)
                caches = insert(caches, s, one)
            jax.block_until_ready(caches)
        del one, picks
        length = int(slab_length(caches))
        if length != self.context:
            raise RuntimeError(f'prefill left length {length}')
        with phase('snapshot'):
            taken = jax.block_until_ready(snapshot(caches))
        self.caches = LayerCaches(caches, taken, finite, restore)
        self.length0 = np.asarray(self.context, np.int32)
        self.stats = stats
        compiled = self.step_wrapper(step) if self.step_wrapper else step

        def with_stats(params, tok, caches):
            caches.layers, nxt, ok, self.stats = compiled(
                params, tok, caches.layers, self.stats)
            return caches, nxt, ok
        self._step = with_stats

    def request(self, *args, **kwargs):
        self.stats = zero_stats(self.cell.config, self.cell.traffic)
        out = decode.Server.request(self, *args, **kwargs)
        self.stats_read.append(jax.device_get(self.stats))
        return out

    def delta_steps_off_the_kernel(self):
        """Recurrent layers of the step whose pass over the state is
        not the kernel ``delta_step``."""
        layers = layer_kinds(self.cell.config).count('kda')
        off = sum(f['form'] != 'pallas' for f in self.delta_forms)
        return off + max(0, layers - len(self.delta_forms))


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
            flops_solar.expected_distinct_held(config, sessions)),
        'expert_bytes': flops_solar.expert_bytes(config),
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
    # Traced: trace_requests, which follow the warm request's restore.
    # Untimed: at least two, so the one compared follows a whole
    # request's steps and the restore after them.
    at_least = (t['trace_requests'] if trace
                else max(2, t.get('min_requests', 2)))
    print(json.dumps({'decode_impl': server.decode_impl,
                      'kernel_steps': server.kernel_steps,
                      'expert_routes': server.expert_routes,
                      'delta_forms': server.delta_forms,
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
        # that fall into one attractor route alike.
        'distinct_token_share': len(np.unique(served_tokens))
        / served_tokens.size,
        'decode_gap_ms_p50': float(np.median(gaps)) * 1e3,
        'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3,
        'decode_gap_ms_max': float(np.max(gaps)) * 1e3,
        'window_s': elapsed, 'gaps': int(gaps.size),
        'requests': len(finished), **routing}), flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_logit_steps', bad, 0)
    # The sampled session's states as the last request left them.
    served_states = np.stack([
        np.asarray(c.state[server.sampled]) for c in server.caches.layers
        if hasattr(c, 'state')])
    # The last request's states are looked at too: one more reset.
    server.caches._replace(server.length0)
    compare.add('nonfinite_state_resets', server.nonfinite_states(), 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel:layer'] else 1,
                cell.limits.get('decode_impl_is_kernel'))
    compare.add('expert_routes_off_the_rule', server.routes_off_the_rule(),
                cell.limits.get('expert_routes_off_the_rule'))
    compare.add('delta_steps_off_the_kernel',
                server.delta_steps_off_the_kernel(),
                cell.limits.get('delta_steps_off_the_kernel'))
    context, sessions = server.context_tokens, server.sessions
    params, served_picks = server.params, [s['picks']
                                           for s in server.stats_read]
    cache_gib = server.cache_gib
    server.free()
    del server.params
    if t['check_samples'] != 1:
        raise ValueError('one sample: the reference takes a minute')
    with phase('reference', counted=False):
        # The window's last request, of the session whose context picks
        # set-up kept.
        r, s = len(finished) - 1, server.sampled
        first, tokens = finished[r]
        # (layers, context + served, k) of session s, request r
        picks = np.concatenate(
            [server.context_picks,
             np.moveaxis(served_picks[r][:, :, s], 0, 1)], axis=1)
        logits, differ, regret, off = reference_readings(
            cell, params, context[s], first[s], tokens[s], picks,
            served_states, operand_dtype)
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
    compare.add('recurrent_state_gap', off,
                cell.limits.get('recurrent_state_gap'))
    mid = server.context + server.new_tokens // 2
    return {
        'compare': compare, 'attempted': steps, 'failed': bad,
        'setup_done': setup_done,
        'end_to_end': {
            'decode_tokens_per_s': served / elapsed,
            'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3},
        'observed': {
            'steps': steps, 'window_s': elapsed, 'chips': cell.chips,
            'requests': len(finished),
            'full_decode_per_step': flops_solar.attn_decode_step(
                cell.config, sessions, mid),
            'delta_step_per_step': flops_solar.delta_step(
                cell.config, sessions),
            'moe': routing, 'cache': cache_gib,
        },
    }
