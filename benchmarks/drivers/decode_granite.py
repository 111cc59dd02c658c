"""The decode cell of a ``granitemoehybrid`` stack (Granite 4.0-H Small:
nine Mamba-2 layers to one attention layer, and small gated experts
beside a shared MLP in EVERY layer): ``drivers/decode_hybrid.py``'s
closed loop of greedy requests over prefilled sessions — its
``LayerCaches``, its snapshot / restore between requests, its seeded
draws, its comparison with the reference — with what this architecture
changes.

- The model is built here from the configuration's published keys
  (``build_lm``: one period of ``layer_types``, every layer TWO branches,
  a ``'mamba'`` or an ``'attention'`` mixer and then the experts, each
  residual scaled by ``residual_multiplier``; the router's gates the
  softmax of the picked logits; ``embedding_multiplier``,
  ``attention_multiplier`` and ``logits_scaling`` under a tied head) and
  its seeded weights from this file's shape table (``shapes`` / ``make``;
  the router and the recurrence's ``A_log`` / ``dt_bias`` / ``D`` stay
  float32).
- Every layer has a cache (a ``StateCache`` of FIXED size or the one
  slab of ``t_max``) AND expert counters; the step's counters cover all
  layers.
- ``correct`` also holds every ``SparseExperts`` trace of the step to
  the hit-list route by the rule's bound (``models/moe.HIT_LIST_ROWS``;
  this driver passes no bound of its own), and compares the STATES: the
  sampled session's nine recurrent states as the window's last request
  left them against the reference's after the same tokens
  (``recurrent_state_gap``). The logits and the picks of a seeded model
  read alike whether the reference's state is float32 or bfloat16
  (chip, PR 36); a head's state itself does not.
"""

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_granite
from benchmarks.drivers import decode, decode_hybrid
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_hybrid import (
    LayerCaches, draw_leaf, sampled_session, slab_length,
)
from benchmarks.drivers.decode_mixed import unit_columns
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import split_seed

FLOAT32_LEAVES = ('router', 'A_log', 'dt_bias', 'D')

layer_kinds = flops_granite.layer_kinds


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    c = config
    if (c['hidden_act'] != 'silu' or c['position_embedding_type'] != 'nope'
            or c['normalization_function'] != 'rmsnorm'
            or not c['tie_word_embeddings'] or c['mamba_proj_bias']
            or not c['mamba_conv_bias'] or c['attention_bias']):
        raise ValueError('this driver builds silu-gated experts, RMSNorm, '
                         'attention without positions or biases and a '
                         'tied head')
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=True,
        embed_scale=float(c['embedding_multiplier']),
        logit_scale=1.0 / c['logits_scaling'],
        attn_kwargs={
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': False, 'use_rope': False,
            'softmax_scale': float(c['attention_multiplier']),
            **attn_overrides},
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['rms_norm_eps'],
            'residual_scale': float(c['residual_multiplier']),
            'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['published']['num_local_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['intermediate_size'],
                'shared_hidden': c['shared_intermediate_size'],
                'router_bias': False, 'score': 'softmax_picked',
                'experts_held': tuple(c['experts_held'])}},
        layer_kinds={
            'mamba': {'mixer': 'ssm', 'ssm_kwargs': {
                'heads': c['mamba_n_heads'],
                'head_dim': c['mamba_d_head'],
                'state': c['mamba_d_state'],
                'groups': c['mamba_n_groups'],
                'conv': c['mamba_d_conv'],
                'chunk': c['mamba_chunk_size'],
                'state_dtype': jnp.dtype(c['precision']['state'])}},
            'attention': {'mixer': 'attention'}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v = c['hidden_size'], c['vocab_size']
    head = flops_granite.head_dim(c)
    kv = c['num_key_value_heads'] * head
    heads, inner = c['mamba_n_heads'], (c['mamba_n_heads']
                                        * c['mamba_d_head'])
    channels = flops_granite.conv_channels(c)
    w, shared = c['intermediate_size'], c['shared_intermediate_size']
    held = flops_granite.experts_held(c)
    # Wq and Wk are drawn wider, so that a score q·k · attention_multiplier
    # has the standard deviation the configuration's ``init`` states (at
    # fan-in it has sqrt(head_dim) · attention_multiplier).
    peaked = d * math.sqrt(head) * c['attention_multiplier'] / (
        c['init']['attention_score_std'])
    mixers = {
        'mamba': {
            ('ssm', 'in_proj', 'kernel'): ((d, inner + channels + heads), d),
            ('ssm', 'conv_kernel'): ((c['mamba_d_conv'], channels),
                                     c['mamba_d_conv']),
            ('ssm', 'conv_bias'): ((channels,), None),
            ('ssm', 'dt_bias'): ((heads,), None),
            ('ssm', 'A_log'): ((heads,), None),
            ('ssm', 'D'): ((heads,), None),
            ('ssm', 'norm_scale'): ((inner,), None),
            ('ssm', 'out_proj', 'kernel'): ((inner, d), inner)},
        'attention': {
            ('attn', 'keys', 'kernel'): ((d, d), peaked),
            ('attn', 'queries', 'kernel'): ((d, kv), peaked),
            ('attn', 'values', 'kernel'): ((d, kv), d),
            ('attn', 'composition', 'kernel'): ((d, d), d)}}
    experts = {
        ('moe', 'router'): ((d, c['published']['num_local_experts']), d),
        ('moe', 'w_gate'): ((held, d, w), d),
        ('moe', 'w_up'): ((held, d, w), d),
        ('moe', 'w_down'): ((held, w, d), w),
        ('moe', 'shared', 'gate', 'kernel'): ((d, shared), d),
        ('moe', 'shared', 'up', 'kernel'): ((d, shared), d),
        ('moe', 'shared', 'down', 'kernel'): ((shared, d), shared)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None)}
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


def zero_stats(config, traffic):
    layers = config['num_hidden_layers']
    return {
        'expert_tokens': jnp.zeros(
            (layers, config['published']['num_local_experts']), jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_counters(config, sown):
    """Every layer's expert counters with a leading layer axis."""
    stack = sown['counters']['stack']
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        stack[f'block_{i}']['moe']
        for i in range(config['num_hidden_layers'])])


def make_programs(model, config):
    """``decode_hybrid.make_programs``'s six programs over this stack: a
    context chunk of one session into its caches, returning the chunk's
    expert picks ``(layers, chunk, k)`` (logits dropped, so the head is
    not built); a finished session into its slot of every layer's cache;
    the snapshot of the states; whether every state is finite; the reset
    (lengths back, states restored); and one token step returning the
    greedy next token, whether every logit was finite, and the expert
    counters added to ``stats``."""
    from distributed_dot_product_tpu.models.decode import (
        insert_session, restore_states, snapshot_states,
    )
    lo, hi = config['experts_held']

    def prefill_fn(p, tok, c):
        (c, _), sown = model.apply(p, tok, c, method='prefill',
                                   mutable=['counters'])
        return c, sown_counters(config, sown)['expert_picks']

    def insert_fn(caches, session, one):
        return [insert_session(c, session, o)
                for c, o in zip(caches, one)]

    def finite_fn(caches):
        return jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(c.state)) for c in caches
            if hasattr(c, 'state')]))

    def restore_fn(caches, snapshot, length):
        return [c._replace(length=length) if hasattr(c, 'length') else c
                for c in restore_states(caches, snapshot)]

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
            jax.jit(snapshot_states), jax.jit(finite_fn),
            jax.jit(restore_fn, donate_argnums=(0,)),
            jax.jit(step_fn, donate_argnums=(2, 3)))


class Server(decode_hybrid.Server):
    """``decode_hybrid.Server``'s request loop, snapshot and counters
    over this model."""

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
        from distributed_dot_product_tpu.models.moe import (
            expert_route_traces,
        )
        t, config = self.cell.traffic, self.cell.config
        with phase('init'):
            params = make(config, self.seed, self.cell.param_dtype())
            if convert is not None:
                params = convert(params)
            jax.block_until_ready(params)
        self.params = params
        prefill, insert, snapshot, finite, restore, step = make_programs(
            self.model, config)
        caches = self.model.make_decode_caches(self.sessions, t['t_max'])
        one = self.model.make_decode_caches(1, t['t_max'])
        self.cache_gib = flops_granite.cache_gib(caches)
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
                    expert_route_traces() as routes:
                low_step = step.lower(params, tok1, caches, stats)
        # What the step's attention layer resolved to, by the cache it
        # was on, and the route each expert layer's call took.
        self.decode_impl = sorted({f"{t['resolved']}:{t['cache']}"
                                   for t in traces})
        self.kernel_steps = [t['step'] for t in traces]
        self.expert_routes = routes
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

    def routes_off_the_rule(self):
        """Expert layers of the step that are not on the hit list by
        the rule's own bound."""
        off = sum(r['route'] != 'hit_list' or r['bound_by'] != 'rule'
                  for r in self.expert_routes)
        return off + max(0, self.cell.config['num_hidden_layers']
                         - len(self.expert_routes))


def state_gap(served, reference):
    """The largest, over recurrent layers and heads, of a head's
    ``|served - reference|`` over ``|reference|`` (Frobenius norms over
    its ``(head_dim, N)`` state): ``(layers, H, P, N)`` both."""
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    off = np.sqrt(np.sum(np.square(served - reference), axis=(-2, -1)))
    size = np.sqrt(np.sum(np.square(reference), axis=(-2, -1)))
    return float(np.max(off / np.maximum(size, 1e-30)))


def reference_readings(cell, params, context, first, tokens, picks, states,
                       operand_dtype=None):
    """The plain reference once over one session's context, first token
    and served tokens, following the program's expert picks ``(layers,
    context + served tokens, k)``: its logits ``(served tokens, vocab)``
    at the positions that produced them, the share of the (token, layer)
    pairs at which its OWN pick is another set of experts, the largest
    regret of the program's picks by its own router logits
    (``reference/granitemoehybrid.route``), and how far the program's
    ``states`` after the last of those tokens lie from its own
    (``state_gap``)."""
    ref = cell.reference()
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    rows = len(seq)
    pad = (-rows) % ref.ROW_BLOCK
    # Rows are causal and a padded row leaves the states alone: padding
    # after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    forced = np.pad(picks, ((0, 0), (0, pad), (0, 0)))
    logits, own, regret, ref_states = ref.logits_at(
        cell.config, params, jnp.asarray(seq), n + pad, operand_dtype,
        forced_picks=jnp.asarray(forced), valid=rows)
    differ = np.any(np.sort(np.asarray(own)[:, :rows], axis=-1)
                    != np.sort(picks, axis=-1), axis=-1)
    return (np.asarray(logits[:n]), float(np.mean(differ)),
            float(np.max(np.asarray(regret)[:, :rows])),
            state_gap(states, ref_states))


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
            flops_granite.expected_distinct_held(config, sessions)),
        'expert_bytes': flops_granite.expert_bytes(config),
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
            'full_decode_per_step': flops_granite.attn_decode_step(
                cell.config, sessions, mid),
            'ssm_step_per_step': flops_granite.ssm_step(
                cell.config, sessions),
            'moe': routing, 'cache': cache_gib,
        },
    }
