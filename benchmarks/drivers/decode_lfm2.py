"""The decode cell of an ``lfm2_moe`` stack (LFM2-8B-A1B: gated
short-convolution layers beside GQA layers at 64-wide heads, a dense
gated MLP in the leading layer and 32 gated experts with NO shared one
in every other): ``drivers/decode_solar.py``'s closed loop of greedy
requests over prefilled sessions — ``decode_hybrid``'s ``LayerCaches``,
request loop, snapshot / restore between requests and seeded draws, its
comparison of logits, picks and STATES with the reference — with what
this architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: each entry of ``layer_types`` a ``'conv'`` mixer or an
  attention mixer with per-head q / k norms, RoPE and a PACKED cache
  (``kv_packed``: keys and values of a 64-wide head side by side in one
  unpadded 128-lane row); the first ``num_dense_layers`` layers a gated
  MLP, the rest experts at sigmoid scores with a bias and normalised
  gates; a tied head) and its seeded weights from this file's shape
  table (``shapes`` / ``make``; the router, its bias and the head norms'
  scales stay float32), every router then levelled
  (``level_routers``, by the plain reference over seeded tokens).
- A program without the convolution mixer fails in ``build_lm``, at
  once, before a weight is drawn.
- A conv layer's cache is its window alone (a ``StateCache`` whose state
  has no elements): the finite check, the comparison with the reference
  (``recurrent_state_gap``: the sampled session's seven windows after
  the window's last request against the reference's last two rows of
  ``u``) and ``cache.state_gib`` read the windows.
- The comparison also reads what the attention layers' caches HOLD
  (``kv_cache_gap``: the sampled session's packed rows, context and
  served, against the reference's keys and values): the logits of 4 000
  attended rows average a cache's rounding away (a float8 K/V control
  read inside every other limit: chip, PR 51), the rows do not.
- ``correct`` also holds both attention layers' step to the Pallas slab
  kernel on the unpadded form (``decode_impl_traces``: ``kernel:packed``
  and nothing else), every ``SparseExperts`` trace of the step to the
  route the rule names for its 256 rows by the rule's own bound, and
  every conv layer's step to its traced form
  (``models/shortconv.conv_step_traces``).
- Two controls: the reference with every matmul operand and the windows
  rounded (``operand_dtype``), and with the keys and values alone
  rounded (``kv_dtype``).
"""

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_lfm2
from benchmarks.drivers import decode, decode_granite
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_hybrid import (
    LayerCaches, sampled_session, slab_length,
)
from benchmarks.drivers.decode_mixed import unit_columns
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import seed_key, split_seed

FLOAT32_LEAVES = ('router', 'router_bias', 'keys_norm', 'queries_norm')

layer_kinds = flops_lfm2.layer_kinds
expert_layers = flops_lfm2.expert_layers


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    # A program without the convolution mixer fails HERE, at once.
    from distributed_dot_product_tpu.models.shortconv import (  # noqa: F401
        ShortConvMixer,
    )
    c = config
    if (c['conv_bias'] or not c['tie_embedding'] or not c['use_expert_bias']
            or c['hidden_size'] % c['num_attention_heads']):
        raise ValueError('this driver builds a convolution without bias, '
                         'sigmoid routing with a bias and a tied head')
    # A layer's kind: its mixer, under the dense MLP in the leading
    # layers (conv layers, as published) and the experts in the rest.
    kinds = {'conv': {'mixer': 'conv'}, 'attn': {'mixer': 'attention'},
             'dense_conv': {'mixer': 'conv', 'ffn': 'gated', 'ffn_kwargs': {
                 'hidden': c['intermediate_size']}}}
    names = [('dense_' if i < c['num_dense_layers'] else '') + kind
             for i, kind in enumerate(layer_kinds(c))]
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=True,
        attn_kwargs={
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': False, 'use_rope': True,
            'rope_base': float(c['rope_theta']), 'rope_layout': 'half',
            'qk_norm': True, 'qk_norm_eps': c['norm_eps'],
            'kv_packed': True, **attn_overrides},
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['norm_eps'],
            'ssm_kwargs': {'taps': c['conv_L_cache']},
            'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['num_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['moe_intermediate_size'],
                'n_shared': 0, 'score': 'sigmoid', 'router_bias': True,
                'scaling': float(c['routed_scaling_factor']),
                'norm_topk': c['norm_topk_prob']}},
        layer_kinds={name: kinds[name] for name in set(names)},
        layer_pattern=tuple(names))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v = c['hidden_size'], c['vocab_size']
    head = flops_lfm2.head_dim(c)
    kv = c['num_key_value_heads'] * head
    e, w, taps = c['num_experts'], c['moe_intermediate_size'], (
        c['conv_L_cache'])
    mixers = {
        'conv': {
            ('conv', 'in_proj', 'kernel'): ((d, 3 * d), d),
            ('conv', 'conv_kernel'): ((taps, d), taps),
            ('conv', 'out_proj', 'kernel'): ((d, d), d)},
        'attn': {
            ('attn', 'keys', 'kernel'): ((d, d), d),
            ('attn', 'queries', 'kernel'): ((d, kv), d),
            ('attn', 'values', 'kernel'): ((d, kv), d),
            ('attn', 'keys_norm'): ((head,), None),
            ('attn', 'queries_norm'): ((head,), None),
            ('attn', 'composition', 'kernel'): ((d, d), d)}}
    mlp = {('mlp', name, 'kernel'): (shape, shape[0]) for name, shape in (
        ('gate', (d, c['intermediate_size'])),
        ('up', (d, c['intermediate_size'])),
        ('down', (c['intermediate_size'], d)))}
    experts = {
        ('moe', 'router'): ((d, e), d),
        ('moe', 'router_bias'): ((e,), None),
        ('moe', 'w_gate'): ((e, d, w), d),
        ('moe', 'w_up'): ((e, d, w), d),
        ('moe', 'w_down'): ((e, w, d), w)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None)}
    for i, kind in enumerate(layer_kinds(c)):
        block = ('stack', f'block_{i}')
        out[block + ('ln1', 'scale')] = ((d,), None)
        out[block + ('ln2', 'scale')] = ((d,), None)
        ffn = experts if i in expert_layers(c) else mlp
        for path, leaf in {**mixers[kind], **ffn}.items():
            out[block + path] = leaf
    return out


def leaf_value(key, name, shape, fan_in, init):
    """One leaf's float32 draw: kernels N(0, 1/fan_in) (the 3-tap filter
    N(0, 1/3), so that ``v`` keeps ``u``'s scale), the rest as the
    configuration's ``init`` (a tuple of its items) says; the head
    norms' scales around ``qk_norm_scale`` (behind a per-head RMSNorm
    the width of Wq and Wk does not reach the scores, these scales
    do)."""
    init = dict(init)
    normal = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return normal / math.sqrt(fan_in)
    if name == 'embedding':
        return init['embedding_std'] * normal
    if name == 'router_bias':
        return init['router_bias_std'] * normal
    around = 1.0 + init['scale_std'] * normal
    if name in ('keys_norm', 'queries_norm'):
        return init['qk_norm_scale'] * around
    if name == 'scale':
        return around
    raise ValueError(f'no init rule for a leaf named {name!r}')


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def draw_leaf(lo, hi, index, name, shape, fan_in, dtype, init):
    key = jax.random.fold_in(seed_key(lo, hi), index)
    return leaf_value(key, name, shape, fan_in, init).astype(dtype)


def make(config, seed, dtype):
    """The seeded tree ``{'params': ...}`` of this file's shape table,
    rounded to ``dtype`` (the leaves of ``FLOAT32_LEAVES`` stay
    float32): one small jitted draw a leaf, each placed before the next
    is drawn."""
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
    input and scaled to ONE deviation of the logit over that input
    (``init['router_level_tokens']``; a configuration without the key
    keeps its draw): ``decode_ling.level_routers`` over this stack. A
    seeded router reads the component that every token's mixer output
    shares as a fixed offset an expert; a trained router has no such
    offset. Mean and deviation are taken by the PLAIN reference over
    that many seeded tokens, layer after layer, so the weights are a
    function of the seed and of nothing the program computes."""
    n = config['init'].get('router_level_tokens')
    if not n:
        return params
    from benchmarks.reference import lfm2 as ref
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
        for i, kind in enumerate(ref.kinds(config)):
            lp = stack[f'block_{i}']
            x = jax.jit(lambda lp, x, kind=kind: ref.mixer_branch(
                config, kind, lp, x)[0])(lp, x)
            if i not in expert_layers(config):
                x = jax.jit(lambda lp, x: ref.mlp_branch(config, lp, x))(
                    lp, x)
                continue
            router = jax.jit(level)(lp['ln2'], lp['moe']['router'], x)
            lp = stack[f'block_{i}'] = {
                **lp, 'moe': {**lp['moe'], 'router': router}}
            x = jax.jit(
                lambda lp, x: ref.experts_branch(config, lp, x)[0])(lp, x)
    return {'params': {**p, 'stack': stack}}


def zero_stats(config, traffic):
    layers = len(expert_layers(config))
    return {
        'expert_tokens': jnp.zeros((layers, config['num_experts']),
                                   jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_counters(config, sown):
    """The expert layers' counters with a leading layer axis."""
    stack = sown['counters']['stack']
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        stack[f'block_{i}']['moe'] for i in expert_layers(config)])


def make_programs(model, config):
    """``decode_granite.make_programs``'s six programs over this stack
    (its prefill, insert, snapshot, restore and token step, the counters
    of the EXPERT layers alone: the leading layer has none), with a
    finite check that reads the WINDOWS — a conv layer's state has no
    elements."""
    from distributed_dot_product_tpu.models.decode import (
        insert_session, restore_states, snapshot_states,
    )

    def prefill_fn(p, tok, c):
        (c, _), sown = model.apply(p, tok, c, method='prefill',
                                   mutable=['counters'])
        return c, sown_counters(config, sown)['expert_picks']

    def insert_fn(caches, session, one):
        return [insert_session(c, session, o)
                for c, o in zip(caches, one)]

    def finite_fn(caches):
        return jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(c.conv)) for c in caches
            if hasattr(c, 'conv')]))

    def restore_fn(caches, snapshot, length):
        return [c._replace(length=length) if hasattr(c, 'length') else c
                for c in restore_states(caches, snapshot)]

    def step_fn(p, tok, c, stats):
        (c, logits), sown = model.apply(p, tok, c, method='decode',
                                        mutable=['counters'])
        moe = sown_counters(config, sown)
        counts = moe['expert_tokens']         # (expert layers, experts)
        stats = {
            'expert_tokens': stats['expert_tokens'] + counts,
            'active': stats['active'] + jnp.sum(counts > 0),
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
        from distributed_dot_product_tpu.models.shortconv import (
            conv_step_traces,
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
        self.cache_gib = flops_lfm2.cache_gib(caches)
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
                    conv_step_traces() as forms:
                low_step = step.lower(params, tok1, caches, stats)
        # What each attention layer's step resolved to, by the cache it
        # was on, the route each expert layer's call took and the form
        # of each conv mixer's step.
        self.decode_impl = sorted({f"{t['resolved']}:{t['cache']}"
                                   for t in traces})
        self.kernel_steps = [dict(t['step'] or {},
                                  token_bytes=t['token_bytes'],
                                  tail=t['tail']) for t in traces]
        self.expert_routes = routes
        self.conv_forms = forms
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
        """Expert layers of the step that are not on the route the
        rule's own bound names for the call's rows."""
        off = sum(r['bound_by'] != 'rule' or r['n'] != self.sessions
                  or r['route'] != ('hit_list' if r['n'] <= r['bound']
                                    else 'sorted')
                  for r in self.expert_routes)
        return off + max(0, len(expert_layers(self.cell.config))
                         - len(self.expert_routes))

    def conv_steps_off_the_form(self):
        """Conv layers of the step whose traced form is not the window's
        shift at the configuration's taps and channels."""
        c = self.cell.config
        want = {'form': 'shift', 'taps': c['conv_L_cache'],
                'channels': c['hidden_size']}
        layers = layer_kinds(c).count('conv')
        return (sum(f != want for f in self.conv_forms)
                + max(0, layers - len(self.conv_forms)))


def window_gap(served, reference):
    """The largest, over conv layers, of ``|served - reference|`` over
    ``|reference|`` (Frobenius norms over a layer's ``(K - 1, dim)``
    window): ``(layers, K - 1, dim)`` both."""
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    off = np.sqrt(np.sum(np.square(served - reference), axis=(-2, -1)))
    size = np.sqrt(np.sum(np.square(reference), axis=(-2, -1)))
    return float(np.max(off / np.maximum(size, 1e-30)))


def rows_gap(served, reference):
    """The largest, over attention layers and over the key and the
    value half, of ``|served - reference|`` over ``|reference|``
    (Frobenius norms over a layer's half of every row): ``(layers, KV
    heads, T, 2 d)`` both."""
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    d = served.shape[-1] // 2
    worst = 0.0
    for half in (slice(None, d), slice(d, None)):
        off = np.sqrt(np.sum(np.square(
            served[..., half] - reference[..., half]), axis=(1, 2, 3)))
        size = np.sqrt(np.sum(np.square(reference[..., half]),
                              axis=(1, 2, 3)))
        worst = max(worst, float(np.max(off / np.maximum(size, 1e-30))))
    return worst


def reference_readings(cell, params, context, first, tokens, picks, windows,
                       kv_rows, operand_dtype=None, kv_dtype=None):
    """The plain reference once over one session's context, first token
    and served tokens, following the program's expert picks ``(expert
    layers, context + served tokens, k)``: its logits ``(served tokens,
    vocab)`` at the positions that produced them, the share of the
    (token, layer) pairs at which its OWN pick is another set of
    experts, the largest regret of the program's picks by its own router
    scores, how far the program's ``windows`` after the last of those
    tokens lie from its own (``window_gap``), and how far the rows its
    attention caches hold, ``kv_rows (attention layers, KV heads, context
    + served tokens, 2 d)``, lie from its own keys and values
    (``rows_gap``)."""
    ref = cell.reference()
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    rows = len(seq)
    pad = (-rows) % ref.ROW_BLOCK
    # Rows are causal and the window is read behind the last real row:
    # padding after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    forced = np.pad(picks, ((0, 0), (0, pad), (0, 0)))
    logits, own, regret, ref_windows, ref_rows = ref.logits_at(
        cell.config, params, jnp.asarray(seq), n + pad, operand_dtype,
        forced_picks=jnp.asarray(forced), valid=rows, kv_dtype=kv_dtype)
    differ = np.any(np.sort(np.asarray(own)[:, :rows], axis=-1)
                    != np.sort(picks, axis=-1), axis=-1)
    return (np.asarray(logits[:n]), float(np.mean(differ)),
            float(np.max(np.asarray(regret)[:, :rows])),
            window_gap(windows, ref_windows),
            rows_gap(kv_rows, np.asarray(ref_rows)[:, :, :rows]))


def routing_readings(config, stats_read):
    """What the counters say of the window's routing."""
    tokens = sum(s['expert_tokens'] for s in stats_read)
    steps = sum(int(s['step']) for s in stats_read)
    return {
        'active_experts_per_step': (
            sum(int(s['active']) for s in stats_read) / max(steps, 1)),
        'load_max_over_mean': float(np.max(
            tokens.max(axis=1) / np.maximum(tokens.mean(axis=1), 1e-9))),
        'expert_bytes': flops_lfm2.expert_bytes(config),
        'counted_steps': steps}


def run(cell, seed, seconds, trace, tracer, step_wrapper=None,
        operand_dtype=None, kv_dtype=None):
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
                      'conv_forms': server.conv_forms,
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
    routing = routing_readings(cell.config, server.stats_read)
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
    # The sampled session's windows as the last request left them.
    served_windows = np.stack([
        np.asarray(c.conv[server.sampled], np.float32)
        for c in server.caches.layers if hasattr(c, 'conv')])
    # … and the rows its attention caches hold: the context's and all
    # but the last served token's (that one was never fed back).
    held = server.context + server.new_tokens
    served_rows = np.stack([
        np.asarray(c.kv[server.sampled, :, :held], np.float32)
        for c in server.caches.layers if hasattr(c, 'kv')])
    # The last request's windows are looked at too: one more reset.
    server.caches._replace(server.length0)
    compare.add('nonfinite_state_resets', server.nonfinite_states(), 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel:packed'] else 1,
                cell.limits.get('decode_impl_is_kernel'))
    compare.add('expert_routes_off_the_rule', server.routes_off_the_rule(),
                cell.limits.get('expert_routes_off_the_rule'))
    compare.add('conv_steps_off_the_form', server.conv_steps_off_the_form(),
                cell.limits.get('conv_steps_off_the_form'))
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
        # (expert layers, context + served, k) of session s, request r
        picks = np.concatenate(
            [server.context_picks,
             np.moveaxis(served_picks[r][:, :, s], 0, 1)], axis=1)
        logits, differ, regret, off, rows_off = reference_readings(
            cell, params, context[s], first[s], tokens[s], picks,
            served_windows, served_rows, operand_dtype, kv_dtype)
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
    compare.add('kv_cache_gap', rows_off, cell.limits.get('kv_cache_gap'))
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
            'full_decode_per_step': flops_lfm2.attn_decode_step(
                cell.config, sessions, mid),
            'conv_step_per_step': flops_lfm2.conv_step(
                cell.config, sessions),
            'moe': routing, 'cache': cache_gib,
        },
    }
