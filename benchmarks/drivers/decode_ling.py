"""The decode cell of a ``bailing_hybrid`` stack (Ling 3.0 flash: five
gated delta-rule (KDA) layers to one gated latent-attention (MLA) layer
in ONE stack, a dense MLP in the leading layer and group-limited experts
beside a shared one in the rest): ``drivers/decode_solar.py``'s closed
loop of greedy requests over prefilled sessions — ``decode_hybrid``'s
``LayerCaches`` and seeded draws, ``decode_granite``'s comparison of
logits, picks and STATES with the reference — with what this
architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: a layer's mixer by its PUBLISHED index, KDA with
  full-rank gates and the bounded decay or MLA with no query rank and a
  head-wise output gate; its feed-forward the dense MLP or the experts
  under group-limited routing; an untied head) and its seeded weights
  from this file's shape table (``shapes`` / ``make``; the router, its
  bias and the recurrence's ``A_log`` / ``dt_bias`` stay float32), every
  router's columns then made orthogonal to the mean of its input and
  scaled to one deviation of the logit over it (``level_routers``, by
  the plain reference over seeded tokens).
- A program without a latent mixer as one KIND of a mixed stack, the
  full-rank KDA gates or group-limited routing fails in ``build_lm``, at
  once, before a weight is drawn.
- The caches are a list: six ``StateCache``s and ONE layer's
  ``LatentCache`` (``(sessions, t_max, 640)`` with a length a session).
  Between requests the latent lengths are set back AND the six states
  restored from the snapshot, one program, inside the window.
- The step's counters also say how many rows each expert layer's step
  routed to the HELD GROUP (``group_rows``: the tokens whose kept groups
  include it), what the hit count hangs on.
- ``correct`` holds the MLA layer's step to the kernel ``mla_decode``
  on its own latent buffer (``kernel:latent``), every KDA mixer's step
  to ``delta_step`` and every expert layer to the hit list by the rule's
  bound; the reference judges the program's picks by its OWN
  group-limited rule (``plain_routing=True`` is the control that routes
  by the plain top-k of all experts and must fail it).
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_ling
from benchmarks.drivers import decode, decode_granite
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_granite import reference_readings
from benchmarks.drivers.decode_hybrid import (
    LayerCaches, draw_leaf, sampled_session,
)
from benchmarks.drivers.decode_mixed import unit_columns
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import split_seed

FLOAT32_LEAVES = ('router', 'router_bias', 'A_log', 'dt_bias')

layer_kinds = flops_ling.layer_kinds


def build_lm(config, plain_routing=False, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes. ``plain_routing``: the control, whose router
    picks the top-k of ALL experts (no groups)."""
    import inspect

    from distributed_dot_product_tpu import TransformerLM
    from distributed_dot_product_tpu.models.delta import GatedDeltaMixer
    from distributed_dot_product_tpu.models.latent import LatentAttention
    from distributed_dot_product_tpu.models.moe import SparseExperts
    # A program without what this stack needs fails HERE, at once.
    for module, fields in ((GatedDeltaMixer, ('gate_rank', 'decay')),
                           (LatentAttention, ('out_gate',)),
                           (SparseExperts, ('n_group', 'topk_group'))):
        missing = set(fields) - set(inspect.signature(module).parameters)
        if missing:
            raise ValueError(f'{module.__name__} has no {sorted(missing)}')
    c = config
    if (any(c['expert_swiglu_limit_list'])
            or any(c['share_expert_swiglu_limit_list'])):
        raise ValueError('a non-zero swiglu limit clamps the SiLU in a '
                         'form this driver does not guess')
    if (c['q_lora_rank'] is not None or c['rope_scaling'] is not None
            or not c['rope_interleave'] or c['use_mla_nope']
            or c['gated_attention_proj_granularity_type'] != 'head_wise'
            or not (c['no_kda_lora'] and c['kda_safe_gate'])
            or c['use_kda_lora'] or c['num_kv_heads_for_linear_attn']
            or c['group_norm_size'] != 1 or c['tie_word_embeddings']
            or c['num_shared_experts'] != 1 or c['use_bias']
            or c['score_function'] != 'sigmoid'
            or c['topk_method'] != 'noaux_tc'
            or c['num_nextn_predict_layers']
            or c['precision']['latent_rows'] != c['precision']['compute']):
        raise ValueError(
            'this driver builds MLA with no query rank, interleaved '
            'unscaled RoPE and a head-wise gate; KDA with full-rank '
            'gates, the bounded decay and ungrouped heads; sigmoid '
            'noaux_tc routing beside one shared expert; no biases, no '
            'multi-token-prediction module, an untied head and latent '
            'rows in the compute type')
    groups = {} if plain_routing else {'n_group': c['n_group'],
                                       'topk_group': c['topk_group']}
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=False,
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['rms_norm_eps'],
            'mixer': 'delta', 'ssm_kwargs': {
                'heads': c['num_attention_heads'],
                'head_dim': c['head_dim'],
                'conv': c['short_conv_kernel_size'],
                'chunk': c['kda_chunk_size'], 'beta_scale': 1.0,
                'gate_rank': None, 'decay': 'bounded',
                'decay_lower_bound': float(c['kda_lower_bound']),
                'state_dtype': jnp.dtype(c['precision']['state'])},
            'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['published']['num_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['moe_intermediate_size'],
                'n_shared': c['num_shared_experts'],
                'shared_hidden': c['moe_shared_expert_intermediate_size'],
                'scaling': float(c['routed_scaling_factor']),
                'norm_topk': c['norm_topk_prob'],
                'experts_held': tuple(c['experts_held']), **groups}},
        layer_kinds={
            'D': {'ffn': 'gated',
                  'ffn_kwargs': {'hidden': c['intermediate_size']}},
            'K': {},
            'A': {'mixer': 'latent', 'attn_kwargs': {
                'q_rank': None, 'kv_rank': c['kv_lora_rank'],
                'nope_dim': c['qk_nope_head_dim'],
                'rope_dim': c['qk_rope_head_dim'],
                'v_dim': c['v_head_dim'],
                'rope_theta': float(c['rope_theta']),
                'norm_eps': c['rms_norm_eps'], 'out_gate': 'head',
                **attn_overrides}}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v = c['hidden_size'], c['vocab_size']
    heads, dim, taps = flops_ling.delta_sizes(c)
    inner = heads * dim
    rank, nope = c['kv_lora_rank'], c['qk_nope_head_dim']
    rope, vd = c['qk_rope_head_dim'], c['v_head_dim']
    w, dense = c['moe_intermediate_size'], c['intermediate_size']
    shared = c['moe_shared_expert_intermediate_size']
    held = flops_ling.experts_held(c)
    width = c['published']['num_experts']
    # W_q alone is drawn wider, by the whole score_std: the latent
    # passes a norm and W_kvb's V half must not widen (the
    # configuration's ``init`` says why).
    peaked = d / c['init']['attention_score_std'] ** 2
    mixers = {
        'delta': {
            ('delta', 'in_proj', 'kernel'): ((d, 5 * inner + heads), d),
            ('delta', 'conv_kernel'): ((taps, 3 * inner), taps),
            ('delta', 'dt_bias'): ((inner,), None),
            ('delta', 'A_log'): ((heads,), None),
            ('delta', 'norm_scale'): ((dim,), None),
            ('delta', 'out_proj', 'kernel'): ((inner, d), inner)},
        'latent': {
            ('attn', 'q', 'kernel'): ((d, heads * (nope + rope)), peaked),
            ('attn', 'kv_a', 'kernel'): ((d, rank + rope), d),
            ('attn', 'kv_norm', 'scale'): ((rank,), None),
            ('attn', 'kv_b'): ((rank, heads, nope + vd), rank),
            ('attn', 'gate', 'kernel'): ((d, heads), d),
            ('attn', 'out', 'kernel'): ((heads * vd, d), heads * vd)}}
    ffns = {
        'dense': {
            ('mlp', 'gate', 'kernel'): ((d, dense), d),
            ('mlp', 'up', 'kernel'): ((d, dense), d),
            ('mlp', 'down', 'kernel'): ((dense, d), dense)},
        'experts': {
            ('moe', 'router'): ((d, width), d),
            ('moe', 'router_bias'): ((width,), None),
            ('moe', 'w_gate'): ((held, d, w), d),
            ('moe', 'w_up'): ((held, d, w), d),
            ('moe', 'w_down'): ((held, w, d), w),
            ('moe', 'shared', 'gate', 'kernel'): ((d, shared), d),
            ('moe', 'shared', 'up', 'kernel'): ((d, shared), d),
            ('moe', 'shared', 'down', 'kernel'): ((shared, d), shared)}}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None),
           ('lm_head_kernel',): ((d, v), d)}
    for i, kind in enumerate(layer_kinds(c)):
        block = ('stack', f'block_{i}')
        out[block + ('ln1', 'scale')] = ((d,), None)
        out[block + ('ln2', 'scale')] = ((d,), None)
        leaves = {**mixers['latent' if kind == 'A' else 'delta'],
                  **ffns['dense' if kind == 'D' else 'experts']}
        for path, leaf in leaves.items():
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
    input and scaled to ONE deviation of the logit over that input, the
    mean of what unit columns give (``init['router_level_tokens']``; a
    configuration without the key keeps its draw):
    ``decode_solar.level_routers`` over this stack, for its reason — q,
    k and v of a delta-rule mixer come out of a SiLU, the mixer's output
    has a component common to every token and a seeded router reads it
    as a fixed offset an expert — and one more: what is left of the
    input is not white, unit columns read it at deviations 1.5 % apart,
    and a group's score, its two BEST of 64, follows its widest experts,
    so the share of rows that keep the held group moved with the seed
    and the step's time with it (PERF.md section 6, PR 46). Mean and
    deviation are taken by the PLAIN reference over that many seeded
    tokens, layer after layer, so the weights are a function of the
    seed and of nothing the program computes."""
    n = config['init'].get('router_level_tokens')
    if not n:
        return params
    from benchmarks.reference import ling3 as ref
    p = params['params']
    tokens = decode.seeded_tokens(seed, 2, (n,), config['vocab_size'])

    def level(ln2, router, x):
        u = ref.norm(config, ln2, x)
        c = jnp.mean(u, axis=0)
        c = c / jnp.linalg.norm(c)
        router = unit_columns(router - jnp.outer(c, c @ router))
        deviation = jnp.std(u @ router, axis=0)
        return router * (jnp.mean(deviation) / deviation)

    stack = dict(p['stack'])
    with jax.default_matmul_precision('highest'):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            p['embed']['embedding'], tokens)
        for i, (mixer, ffn) in enumerate(ref.kinds(config)):
            lp = stack[f'block_{i}']
            x = jax.jit(
                (lambda lp, x: ref.delta_branch(config, lp, x)[0])
                if mixer == 'kda' else
                (lambda lp, x: ref.latent_branch(config, lp, x)))(lp, x)
            if ffn == 'dense':
                x = jax.jit(lambda lp, x: ref.dense_branch(config, lp, x))(
                    lp, x)
                continue
            router = jax.jit(level)(lp['ln2'], lp['moe']['router'], x)
            lp = stack[f'block_{i}'] = {
                **lp, 'moe': {**lp['moe'], 'router': router}}
            x = jax.jit(
                lambda lp, x: ref.experts_branch(config, lp, x)[0])(lp, x)
    return {'params': {**p, 'stack': stack}}


def zero_stats(config, traffic):
    layers = len(flops_ling.expert_layers(config))
    return {
        'expert_tokens': jnp.zeros(
            (layers, config['published']['num_experts']), jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'group_rows': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_counters(config, sown):
    """The expert layers' counters with a leading layer axis."""
    stack = sown['counters']['stack']
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        stack[f'block_{i}']['moe']
        for i in flops_ling.expert_layers(config)])


def make_programs(model, config):
    """``decode_granite.make_programs``'s six programs over this stack's
    caches — a context chunk of one session (returning the chunk's
    expert picks ``(expert layers, chunk, k)``), a finished session into
    its slot, the snapshot, the finite check, the reset and the token
    step — where the reset sets back a length A SESSION (the latent
    cache's) and the step also counts the rows routed to the held
    group."""
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
        return [c._replace(length=jnp.full_like(c.length, length))
                if hasattr(c, 'length') else c
                for c in restore_states(caches, snapshot)]

    def step_fn(p, tok, c, stats):
        (c, logits), sown = model.apply(p, tok, c, method='decode',
                                        mutable=['counters'])
        moe = sown_counters(config, sown)
        counts = moe['expert_tokens']         # (layers, router width)
        stats = {
            'expert_tokens': stats['expert_tokens'] + counts,
            'active': stats['active'] + jnp.sum(counts[:, lo:hi] > 0),
            # no groups (the control): every row may reach the held ones
            'group_rows': stats['group_rows'] + jnp.sum(moe.get(
                'group_rows', jnp.full((counts.shape[0],), tok.shape[0],
                                       jnp.int32))),
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


class Server(decode_granite.Server):
    """``decode_granite.Server`` (``decode_hybrid.Server``'s request
    loop, snapshot and counters) over this model."""

    def __init__(self, cell, seed, attn_overrides=None, step_wrapper=None,
                 plain_routing=False):
        t = cell.traffic
        self.cell, self.seed = cell, seed
        self.rows = slice(None)
        self.sessions = t['sessions']
        self.context, self.new_tokens = t['context'], t['new_tokens']
        self.in_flight = t['tokens_in_flight']
        self.vocab = cell.config['vocab_size']
        self.model = build_lm(cell.config, plain_routing=plain_routing,
                              **(attn_overrides or {}))
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
        self.cache_gib = flops_ling.cache_gib(caches)
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
        # What the step's latent layer resolved to, by the cache it was
        # on, the route each expert layer's call took and the form of
        # each delta mixer's step.
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
                    # (expert layers, context, k): every pick the program
                    # made of the sampled session's context, for the
                    # reference to follow.
                    self.context_picks = np.concatenate(
                        jax.device_get(picks), axis=1)
                caches = insert(caches, s, one)
            jax.block_until_ready(caches)
        del one, picks
        lengths = np.concatenate([np.ravel(c.length) for c in caches
                                  if hasattr(c, 'length')])
        if not np.all(lengths == self.context):
            raise RuntimeError(f'prefill left lengths {lengths}')
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
        return off + max(0, len(flops_ling.expert_layers(self.cell.config))
                         - len(self.expert_routes))

    def delta_steps_off_the_kernel(self):
        """Recurrent layers of the step whose pass over the state is
        not the kernel ``delta_step``."""
        layers = len(flops_ling.delta_layers(self.cell.config))
        off = sum(f['form'] != 'pallas' for f in self.delta_forms)
        return off + max(0, layers - len(self.delta_forms))


def routing_readings(config, stats_read, sessions):
    """What the counters say of the window's routing, over the experts
    held here."""
    lo, hi = config['experts_held']
    layers = len(flops_ling.expert_layers(config))
    tokens = sum(s['expert_tokens'] for s in stats_read)[:, lo:hi]
    steps = sum(int(s['step']) for s in stats_read)
    return {
        'active_experts_per_step': (
            sum(int(s['active']) for s in stats_read) / max(steps, 1)),
        'load_max_over_mean': float(np.max(
            tokens.max(axis=1) / np.maximum(tokens.mean(axis=1), 1e-9))),
        # rows a layer's step routed to the held group, a layer a step
        'group_rows_per_step': (
            sum(int(s['group_rows']) for s in stats_read)
            / max(steps * layers, 1)),
        'expected_group_rows_per_step': flops_ling.expected_group_rows(
            config, sessions),
        'expected_active_per_step': layers * (
            flops_ling.expected_distinct_held(config, sessions)),
        'expert_bytes': flops_ling.expert_bytes(config),
        'counted_steps': steps}


def run(cell, seed, seconds, trace, tracer, step_wrapper=None,
        operand_dtype=None, plain_routing=False):
    t = cell.traffic
    compare = Compare()
    server = Server(cell, seed, step_wrapper=step_wrapper,
                    plain_routing=plain_routing)
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
                0 if server.decode_impl == ['kernel:latent'] else 1,
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
        raise ValueError('one sample: the reference takes minutes')
    with phase('reference', counted=False):
        # The window's last request, of the session whose context picks
        # set-up kept.
        r, s = len(finished) - 1, server.sampled
        first, tokens = finished[r]
        # (expert layers, context + served, k) of session s, request r
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
            'mla_decode_per_step': flops_ling.mla_decode_step(
                cell.config, sessions, mid),
            'delta_step_per_step': flops_ling.delta_step(
                cell.config, sessions),
            'moe': routing, 'cache': cache_gib,
        },
    }
