"""The long-context decode cell of a stack of recurrent (Mamba-2),
attention and latent-expert layers (``nemotron_h``: Nemotron 3 Super):
``drivers/decode.py``'s closed loop of greedy requests over prefilled
sessions, as ``drivers/decode_mixed.py`` runs it over a list of caches,
with what this architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: one period of ``hybrid_override_pattern``, each letter a
  layer kind with ONE branch: ``M`` a Mamba-2 mixer, ``*`` attention,
  ``E`` latent experts) and its seeded weights from this file's shape
  table (``shapes`` / ``make``; the router, its bias and the recurrence's
  ``A_log`` / ``dt_bias`` / ``D`` stay float32).
- The caches are a LIST, one entry a layer: a ``StateCache`` of FIXED
  size for an ``M`` layer, a slab of ``t_max`` for ``*``, None for ``E``
  (``TransformerLM.make_decode_caches``). Set-up prefills ONE SESSION AT
  A TIME into one-session caches and puts it in its slot of the serving
  batch's (``models/decode.insert_session``), then takes a SNAPSHOT of
  every state at the prompt's end (``snapshot_states``).
- Between requests the slab's length is set back to the context AND
  every state is restored from the snapshot (``restore_states``; one
  program, inside the timed window as the other cells' resets are): a
  state has been advanced a request's tokens and no length rewinds it.
  A program before it says whether every state it overwrites was
  finite.
- The step also returns, accumulated on the device and read once a
  request, the expert layers' counters as ``decode_mixed`` keeps them.
- ``correct``: the reference's logits (``reference/nemotron_h.py``, one
  whole session, the recurrence token by token) at the timed run's own
  tokens, fed the program's expert picks and judging them by its own
  router scores, non-finite values (logits a step, states a request),
  compilations in the window, and the attention layer's step resolved to
  the kernel. The sampled request is never the first of the window, so
  it follows a restore: a state that was not put back would show.
"""

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_hybrid
from benchmarks.drivers import decode
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_mixed import unit_columns
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import seed_key, split_seed

FLOAT32_LEAVES = ('router', 'router_bias', 'A_log', 'dt_bias', 'D')


def layer_kinds(config):
    return config['hybrid_override_pattern'][:config['num_hidden_layers']]


def expert_layers(config):
    return [i for i, kind in enumerate(layer_kinds(config)) if kind == 'E']


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    # A program without recurrent layers fails HERE, at once, not after
    # it has drawn the weights.
    from distributed_dot_product_tpu.models.ssm import Mamba2Mixer  # noqa: F401
    c = config
    if (c['mlp_hidden_act'] != 'relu2' or c['n_group'] != 1
            or c['n_shared_experts'] != 1 or c['tie_word_embeddings']):
        raise ValueError('this driver builds relu2 experts in one group '
                         'beside one shared expert under an untied head')
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=False,
        attn_kwargs={
            'key_dim': c['num_attention_heads'] * c['head_dim'],
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': c['attention_bias'],
            'use_rope': False, **attn_overrides},
        block_kwargs={'norm': 'rmsnorm',
                      'norm_eps': c['layer_norm_epsilon']},
        layer_kinds={
            'M': {'mixer': 'ssm', 'ffn': 'none', 'ssm_kwargs': {
                'heads': c['mamba_num_heads'],
                'head_dim': c['mamba_head_dim'],
                'state': c['ssm_state_size'], 'groups': c['n_groups'],
                'conv': c['conv_kernel'], 'chunk': c['chunk_size'],
                'state_dtype': jnp.dtype(c['precision']['state'])}},
            '*': {'mixer': 'attention', 'ffn': 'none'},
            'E': {'mixer': 'none', 'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['published']['n_routed_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['moe_intermediate_size'],
                'latent': c['moe_latent_size'],
                'shared_hidden': c['moe_shared_expert_intermediate_size'],
                'expert_form': 'plain', 'activation': 'relu2',
                'scaling': float(c['routed_scaling_factor']),
                'norm_topk': c['norm_topk_prob'],
                'experts_held': tuple(c['experts_held']),
                'dense_tokens': c['serving']['dense_expert_tokens']}}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v = c['hidden_size'], c['vocab_size']
    q = c['num_attention_heads'] * c['head_dim']
    kv = c['num_key_value_heads'] * c['head_dim']
    heads, inner = c['mamba_num_heads'], (c['mamba_num_heads']
                                          * c['mamba_head_dim'])
    channels = flops_hybrid.conv_channels(c)
    lat, w = c['moe_latent_size'], c['moe_intermediate_size']
    shared = c['moe_shared_expert_intermediate_size']
    held = flops_hybrid.experts_held(c)
    # Wq and Wk are drawn wider by sqrt(score_std) each, so that a score
    # q·k / sqrt(head_dim) has that standard deviation (the
    # configuration's ``init`` says why).
    peaked = d / c['init']['attention_score_std']
    kinds = {
        'M': {('ssm', 'in_proj', 'kernel'): ((d, inner + channels + heads),
                                             d),
              ('ssm', 'conv_kernel'): ((c['conv_kernel'], channels),
                                       c['conv_kernel']),
              ('ssm', 'conv_bias'): ((channels,), None),
              ('ssm', 'dt_bias'): ((heads,), None),
              ('ssm', 'A_log'): ((heads,), None),
              ('ssm', 'D'): ((heads,), None),
              ('ssm', 'norm_scale'): ((inner,), None),
              ('ssm', 'out_proj', 'kernel'): ((inner, d), inner)},
        '*': {('attn', 'keys', 'kernel'): ((d, q), peaked),
              ('attn', 'queries', 'kernel'): ((d, kv), peaked),
              ('attn', 'values', 'kernel'): ((d, kv), d),
              ('attn', 'composition', 'kernel'): ((q, d), q)},
        'E': {('moe', 'router'): ((d, c['published']['n_routed_experts']),
                                  d),
              ('moe', 'router_bias'): (
                  (c['published']['n_routed_experts'],), None),
              ('moe', 'latent_down', 'kernel'): ((d, lat), d),
              ('moe', 'latent_up', 'kernel'): ((lat, d), lat),
              ('moe', 'w_up'): ((held, lat, w), lat),
              ('moe', 'w_down'): ((held, w, lat), w),
              ('moe', 'shared', 'up', 'kernel'): ((d, shared), d),
              ('moe', 'shared', 'down', 'kernel'): ((shared, d), shared)}}
    out = {('embed', 'embedding'): ((v, d), None),
           ('lm_head_kernel',): ((d, v), d),
           ('ln_f', 'scale'): ((d,), None)}
    for i, kind in enumerate(layer_kinds(c)):
        out[('stack', f'block_{i}', 'ln1', 'scale')] = ((d,), None)
        for path, leaf in kinds[kind].items():
            out[('stack', f'block_{i}') + path] = leaf
    return out


def leaf_value(key, name, shape, fan_in, init):
    """One leaf's float32 draw: kernels N(0, 1/fan_in), the rest as the
    configuration's ``init`` (a tuple of its items) says."""
    init = dict(init)
    if name in ('A_log', 'dt_bias'):
        uniform = jax.random.uniform(key, shape, jnp.float32)
        if name == 'A_log':                  # A = -U[lo, hi]
            lo, hi = init['A_min'], init['A_max']
            return jnp.log(lo + (hi - lo) * uniform)
        # softplus(dt_bias) = exp(U[log min, log max]), floored: the
        # time steps a trained Mamba-2 is initialised with.
        lo, hi = math.log(init['time_step_min']), math.log(
            init['time_step_max'])
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * uniform),
                         init['time_step_floor'])
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == 'D':
        return jnp.ones(shape, jnp.float32)
    normal = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return normal / math.sqrt(fan_in)
    if name == 'embedding':
        return init['embedding_std'] * normal
    if name in ('scale', 'norm_scale'):
        return 1.0 + init['scale_std'] * normal
    if name == 'router_bias':
        return init['router_bias_std'] * normal
    if name == 'conv_bias':
        return init['conv_bias_std'] * normal
    raise ValueError(f'no init rule for a leaf named {name!r}')


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def draw_leaf(lo, hi, index, name, shape, fan_in, dtype, init):
    key = jax.random.fold_in(seed_key(lo, hi), index)
    return leaf_value(key, name, shape, fan_in, init).astype(dtype)


def make(config, seed, dtype):
    """The seeded tree ``{'params': ...}``, rounded to ``dtype`` (the
    leaves of ``FLOAT32_LEAVES`` stay float32): ``decode_latent.make``'s
    one small jitted draw a leaf, each placed before the next is
    drawn."""
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


def slab_length(layers):
    return next(c.length for c in layers if hasattr(c, 'length'))


class LayerCaches:
    """The layers' caches as the ONE object ``decode.Server.request``
    carries. ``_replace(length=)``, the reset between requests, sets the
    growing caches' lengths back and RESTORES every state from the
    snapshot; whether the states it overwrote were finite is kept for
    the window's end. The step donates the buffers, so the one object
    is updated in place."""

    def __init__(self, layers, snapshot, check, restore):
        self.layers, self.snapshot = layers, snapshot
        self.check, self.restore, self.finite = check, restore, []

    @property
    def length(self):
        return slab_length(self.layers)

    def _replace(self, length):
        self.finite.append(self.check(self.layers))
        self.layers = self.restore(self.layers, self.snapshot,
                                   jnp.asarray(length, jnp.int32))
        return self


def zero_stats(config, traffic):
    layers = len(expert_layers(config))
    return {
        'expert_tokens': jnp.zeros(
            (layers, config['published']['n_routed_experts']), jnp.int32),
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
    """A context chunk of one session into its caches, returning the
    chunk's expert picks ``(expert layers, chunk, k)`` (logits dropped,
    so the head is not built); a finished session into its slot of every
    layer's cache; the snapshot of the states; whether every state is
    finite; the reset (lengths back, states restored); and one token
    step returning the greedy next token, whether every logit
    was finite, and the expert counters added to ``stats``."""
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
        # A program of its own: beside a read of the old states XLA
        # copies the snapshot twice, through a temporary (AOT, PR 32).
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


def sampled_session(seed, sessions):
    """The session ``decode.sample_requests`` draws first from this
    seed: the one whose context picks set-up keeps for the reference."""
    rng = np.random.default_rng([seed & 0xffffffff, seed >> 32, 7])
    return int(rng.integers(sessions))


class Server(decode.Server):
    """``decode.Server``'s request loop over this model: a cache a layer
    with the states' snapshot, and the expert counters carried beside
    them."""

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
        self.cache_gib = flops_hybrid.cache_gib(caches)
        chunk = t['prefill_chunk']
        tok0 = jnp.asarray(self.context_tokens[:1, :chunk])
        tok1 = jnp.zeros((self.sessions, 1), jnp.int32)
        stats = zero_stats(config, t)
        states = [c if hasattr(c, 'state') else None for c in caches]
        with phase('lower'), decode_impl_traces() as traces:
            low_prefill = prefill.lower(params, tok0, one)
            low_insert = insert.lower(caches, 0, one)
            low_snapshot = snapshot.lower(caches)
            low_finite = finite.lower(caches)
            low_restore = restore.lower(caches, states,
                                        jnp.zeros((), jnp.int32))
            low_step = step.lower(params, tok1, caches, stats)
        # What every layer's step resolved to, by the cache it was on.
        self.decode_impl = sorted({f"{t['resolved']}:{t['cache']}"
                                   for t in traces})
        self.kernel_steps = [t['step'] for t in traces]
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
                one = [None if c is None else jax.tree.map(
                    jnp.zeros_like, c) for c in one]
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
        out = super().request(*args, **kwargs)
        self.stats_read.append(jax.device_get(self.stats))
        return out

    def nonfinite_states(self):
        """Resets at which a state that was overwritten held a
        non-finite value."""
        return sum(not bool(f) for f in self.caches.finite)

    def free(self):
        del self.caches, self._step, self.stats


def reference_logits(cell, params, context, first, tokens, picks,
                     operand_dtype=None):
    """The plain reference once over one session's context, first token
    and served tokens, following the program's expert picks ``(expert
    layers, context + served tokens, k)``: its logits ``(served tokens,
    vocab)`` at the positions that produced them, the share of the
    (token, expert layer) pairs at which its OWN pick is another set of
    experts, and the largest regret of the program's picks by its own
    scores (``reference/nemotron_h.route``)."""
    ref = cell.reference()
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    rows = len(seq)
    pad = (-rows) % ref.ROW_BLOCK
    # Rows are causal: padding after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    forced = np.pad(picks, ((0, 0), (0, pad), (0, 0)))
    logits, own, regret = ref.logits_at(
        cell.config, params, jnp.asarray(seq), n + pad, operand_dtype,
        forced_picks=jnp.asarray(forced))
    differ = np.any(np.sort(np.asarray(own)[:, :rows], axis=-1)
                    != np.sort(picks, axis=-1), axis=-1)
    return (np.asarray(logits[:n]), float(np.mean(differ)),
            float(np.max(np.asarray(regret)[:, :rows])))


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
        'expected_active_per_step': len(expert_layers(config)) * (
            flops_hybrid.expected_distinct_held(config, sessions)),
        'expert_bytes': flops_hybrid.expert_bytes(config),
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
    # The last request's states are looked at too: one more reset.
    server.caches._replace(server.length0)
    compare.add('nonfinite_state_resets', server.nonfinite_states(), 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel:layer'] else 1,
                cell.limits.get('decode_impl_is_kernel'))
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
            'requests': len(finished),
            'full_decode_per_step': flops_hybrid.attn_decode_step(
                cell.config, sessions, mid),
            'ssm_step_per_step': flops_hybrid.ssm_step(
                cell.config, sessions),
            'moe': routing, 'cache': cache_gib,
        },
    }
