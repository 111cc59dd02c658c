"""The long-context decode cell of a latent-attention, sparse-expert
model (``xing4_0``): ``drivers/decode.py``'s closed loop of greedy
requests over prefilled sessions, with what that architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``) and its seeded weights from this file's shape table
  (``shapes`` / ``make``; leaf by leaf as ``weights.make`` draws them,
  the router and the hyper-connection mixers in float32).
- Set-up prefills ONE SESSION AT A TIME through ``TransformerLM.prefill``
  into a one-session latent cache and puts it in its slot of the serving
  batch's cache (``models/latent.insert_session``): the expanded keys
  and values of a 32k prompt are 0.7 GB a session, which the 16 of a
  batch would not fit beside the weights.
- The step also returns, accumulated on the device and read once a
  request: tokens per expert ``(expert layers, experts)``, the count of
  distinct experts hit a step, and the picks of every served token
  (compared with the reference's, as a reading with no limit).
- ``correct``: the reference's logits (``reference/xing4.py``, one whole
  session in row blocks) at the timed run's own tokens, and the same
  three program checks as the dense decode cell. The reference follows
  the program's expert picks as it follows its tokens (a pick that
  flips at a near-tie is a discrete decision: left free, 19 % of the
  (token, layer) picks differed and the sound program read 3.0-3.6;
  chip, PR 26), and the picks are judged apart, by the reference's own
  router scores: ``expert_pick_difference_share`` is the share at which
  the reference's own top-k is another set, ``router_pick_regret`` the
  furthest any pick of the program lies below the reference's k-th best
  score + bias (the width of a tie where a near-tie flipped; a router
  that decides wrongly reads many times that on its first such token).
"""

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_latent
from benchmarks.drivers import decode
from benchmarks.drivers.decode import logit_gaps, sample_requests
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import seed_key, split_seed

FLOAT32_LEAVES = ('router', 'router_bias', 'phi', 'bias', 'alpha')


def expert_blocks(config):
    return [f'block_{i}' for i in range(config['first_k_dense_replace'],
                                        config['num_hidden_layers'])]


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's block, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    c = config
    scaling = tuple(sorted((k, v) for k, v in c['rope_scaling'].items()
                           if k != 'type'))
    held = tuple(c.get('experts_held') or (0, c['n_routed_experts']))
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False,
        tie_embeddings=c['tie_word_embeddings'],
        attn_kwargs={
            'q_rank': c['q_lora_rank'], 'kv_rank': c['kv_lora_rank'],
            'nope_dim': c['qk_nope_head_dim'],
            'rope_dim': c['qk_rope_head_dim'], 'v_dim': c['v_head_dim'],
            'rope_theta': float(c['rope_theta']),
            'rope_scaling': scaling, 'norm_eps': c['rms_norm_eps'],
            **attn_overrides},
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['rms_norm_eps'],
            'mixer': 'latent', 'ffn': 'experts',
            'ffn_kwargs': {
                'n_experts': c['n_routed_experts'],
                'top_k': c['num_experts_per_tok'],
                'hidden': c['moe_intermediate_size'],
                'n_shared': c['n_shared_experts'],
                'scaling': float(c['routed_scaling_factor']),
                'norm_topk': c['norm_topk_prob'], 'experts_held': held},
            'residual': 'hyper',
            'residual_kwargs': {
                'mult': c['hc_mult'],
                'sinkhorn_iters': c['hc_sinkhorn_iters'],
                'eps': c['hc_eps'], 'norm_eps': c['rms_norm_eps'],
                'clamp': (float(c['mhc_h_res_clamp_min']),
                          float(c['mhc_h_res_clamp_max']))}},
        dense_prefix=c['first_k_dense_replace'],
        prefix_kwargs={'ffn': 'gated',
                       'ffn_kwargs': {'hidden': c['intermediate_size']}})


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block: the dense prefix's, then the expert layers'."""
    c = config
    d, h, v = c['hidden_size'], c['num_attention_heads'], c['vocab_size']
    qr, kr = c['q_lora_rank'], c['kv_lora_rank']
    nope, rope, dv = (c['qk_nope_head_dim'], c['qk_rope_head_dim'],
                      c['v_head_dim'])
    m, e = c['hc_mult'], c['n_routed_experts']
    lo, hi = c.get('experts_held') or (0, e)
    inter, wide = c['moe_intermediate_size'], c['intermediate_size']
    mix = 2 * m + m * m
    block = {
        ('attn', 'q_a', 'kernel'): ((d, qr), d),
        ('attn', 'q_norm', 'scale'): ((qr,), None),
        ('attn', 'q_b', 'kernel'): ((qr, h * (nope + rope)), qr),
        ('attn', 'kv_a', 'kernel'): ((d, kr + rope), d),
        ('attn', 'kv_norm', 'scale'): ((kr,), None),
        ('attn', 'kv_b'): ((kr, h, nope + dv), kr),
        ('attn', 'out', 'kernel'): ((h * dv, d), h * dv),
        ('ln1', 'scale'): ((d,), None), ('ln2', 'scale'): ((d,), None)}
    for hc in ('hc_attn', 'hc_ffn'):
        block[(hc, 'phi')] = ((m * d, mix), m * d)
        block[(hc, 'bias')] = ((mix,), None)
        block[(hc, 'alpha')] = ((3,), None)

    def gated(prefix, width):
        return {prefix + ('gate', 'kernel'): ((d, width), d),
                prefix + ('up', 'kernel'): ((d, width), d),
                prefix + ('down', 'kernel'): ((width, d), width)}
    dense = {**block, **gated(('mlp',), wide)}
    sparse = {**block, **gated(('moe', 'shared'),
                               c['n_shared_experts'] * inter),
              ('moe', 'router'): ((d, e), d),
              ('moe', 'router_bias'): ((e,), None),
              ('moe', 'w_gate'): ((hi - lo, d, inter), d),
              ('moe', 'w_up'): ((hi - lo, d, inter), d),
              ('moe', 'w_down'): ((hi - lo, inter, d), inter)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('lm_head_kernel',): ((d, v), d),
           ('ln_f', 'scale'): ((d,), None)}
    for i in range(c['num_hidden_layers']):
        table = dense if i < c['first_k_dense_replace'] else sparse
        for path, leaf in table.items():
            out[('stack', f'block_{i}') + path] = leaf
    return out


def leaf_value(key, name, shape, fan_in, init, streams):
    """One leaf's float32 draw: kernels N(0, 1/fan_in), the rest as the
    configuration's ``init`` (a tuple of its items) says."""
    init = dict(init)
    normal = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return normal / math.sqrt(fan_in)
    if name == 'embedding':
        return init['embedding_std'] * normal
    if name == 'scale':
        return 1.0 + init['scale_std'] * normal
    if name == 'router_bias':
        return init['router_bias_std'] * normal
    if name == 'alpha':
        return init['hc_alpha_std'] * normal
    if name == 'bias':              # a hyper-connection's: pre, post, res
        diag = jnp.concatenate([jnp.zeros((2 * streams,)),
                                jnp.eye(streams).reshape(-1)])
        return (init['hc_bias_std'] * normal
                + init['hc_res_diagonal'] * diag)
    raise ValueError(f'no init rule for a leaf named {name!r}')


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def draw_leaf(lo, hi, index, name, shape, fan_in, dtype, init, streams):
    key = jax.random.fold_in(seed_key(lo, hi), index)
    return leaf_value(key, name, shape, fan_in, init, streams).astype(dtype)


def make(config, seed, dtype):
    """The seeded tree ``{'params': ...}``, rounded to ``dtype`` (the
    router, its correction bias and the hyper-connection mixers stay
    float32). One small jitted draw a leaf, keyed by the leaf's place
    in the sorted table: the float32 draw that is live is one leaf's,
    and leaves of one shape share one compiled program (one program for
    the whole tree took 43 s to compile; chip, PR 26)."""
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
        # One leaf at a time ON THE DEVICE too: dispatched ahead, a
        # later leaf is placed while an earlier one's float32 draw is
        # still held, where it lands depends on the host's lead, and
        # the step's time on where the weights landed (the same seed in
        # four processes: 15.07, 15.08, 15.21, 15.22 ms; chip, PR 26).
        node[path[-1]] = draw_leaf(
            lo, hi, np.int32(i), path[-1], shape, fan_in, jnp.dtype(to),
            init, config['hc_mult']).block_until_ready()
    return {'params': tree}


def zero_stats(model_config, traffic):
    layers = flops_latent.expert_layers(model_config)
    return {
        'expert_tokens': jnp.zeros(
            (layers, model_config['n_routed_experts']), jnp.int32),
        'active': jnp.zeros((), jnp.int32),
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             model_config['num_experts_per_tok']), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_counters(config, sown):
    """The expert layers' counters with a leading layer axis."""
    stack = sown['counters']['stack']
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        stack[name]['moe'] for name in expert_blocks(config)])


def make_programs(model, config):
    """A context chunk of one session into its cache, returning the
    chunk's expert picks ``(expert layers, chunk, k)`` (logits dropped,
    so the head is not built); a finished session into its slot; and
    one token step returning the greedy next token, whether every logit
    was finite, and the expert counters added to ``stats``."""
    from distributed_dot_product_tpu.models.latent import insert_session

    def prefill_fn(p, tok, c):
        (c, _), sown = model.apply(p, tok, c, method='prefill',
                                   mutable=['counters'])
        return c, sown_counters(config, sown)['expert_picks']

    def step_fn(p, tok, c, stats):
        (c, logits), sown = model.apply(p, tok, c, method='decode',
                                        mutable=['counters'])
        moe = sown_counters(config, sown)
        counts = moe['expert_tokens']                # (layers, experts)
        stats = {
            'expert_tokens': stats['expert_tokens'] + counts,
            'active': stats['active'] + jnp.sum(counts > 0),
            'picks': jax.lax.dynamic_update_index_in_dim(
                stats['picks'], moe['expert_picks'], stats['step'], 0),
            'step': stats['step'] + 1}
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return c, nxt, jnp.all(jnp.isfinite(logits)), stats

    return (jax.jit(prefill_fn, donate_argnums=(2,)),
            jax.jit(insert_session, donate_argnums=(0,)),
            jax.jit(step_fn, donate_argnums=(2, 3)))


class Server(decode.Server):
    """``decode.Server``'s request loop over this model: the compiled
    step carries the expert counters beside the caches."""

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
        chunk = t['prefill_chunk']
        per = self.context // chunk
        tok0 = jnp.asarray(self.context_tokens[:1, :chunk])
        tok1 = jnp.zeros((self.sessions, 1), jnp.int32)
        stats = zero_stats(config, t)
        from distributed_dot_product_tpu.models.decode import (
            decode_impl_traces,
        )
        with phase('lower'), decode_impl_traces() as traces:
            low_prefill = prefill.lower(params, tok0, one)
            low_insert = insert.lower(caches, 0, one)
            low_step = step.lower(params, tok1, caches, stats)
        self.decode_impl = sorted({t['resolved'] for t in traces})
        with phase('compile'):
            prefill = low_prefill.compile()
            insert = low_insert.compile()
            step = low_step.compile()
        self.custom_calls = step.as_text().count('tpu_custom_call')
        with phase('prefill'):
            picks = []
            for s in range(self.sessions):
                one = one._replace(length=jnp.zeros_like(one.length))
                for i in range(0, self.context, chunk):
                    one, picked = prefill(params, jnp.asarray(
                        self.context_tokens[s:s + 1, i:i + chunk]), one)
                    picks.append(picked)
                caches = insert(caches, s, one)
            jax.block_until_ready(caches)
            # (sessions, expert layers, context, k): every pick the
            # program made of the context, for the reference to follow.
            self.context_picks = np.stack([
                np.concatenate(jax.device_get(
                    picks[s * per:(s + 1) * per]), axis=1)
                for s in range(self.sessions)])
        del one, picks
        self.caches = caches
        self.length0 = np.asarray(caches.length)
        if not np.all(self.length0 == self.context):
            raise RuntimeError(f'prefill left lengths {self.length0}')
        self.stats = stats
        compiled = self.step_wrapper(step) if self.step_wrapper else step

        def with_stats(params, tok, caches):
            caches, nxt, ok, self.stats = compiled(params, tok, caches,
                                                   self.stats)
            return caches, nxt, ok
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
    and served tokens, following the program's expert picks ``(expert
    layers, context + served tokens, k)`` as it follows its tokens: its
    logits ``(served tokens, vocab)`` at the positions that produced
    them, the share of (token, expert layer) pairs at which its OWN
    pick is another set of experts, and the largest regret of the
    program's picks by its own scores (``reference/xing4.route``).
    ``params`` is consumed."""
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
        consume=True, forced_picks=jnp.asarray(forced))
    differ = np.any(np.sort(np.asarray(own)[:, :rows], axis=-1)
                    != np.sort(picks, axis=-1), axis=-1)
    return (np.asarray(logits[:n]), float(np.mean(differ)),
            float(np.max(np.asarray(regret)[:, :rows])))


def routing_readings(config, stats_read, sessions):
    """What the counters say of the window's routing."""
    tokens = sum(s['expert_tokens'] for s in stats_read)     # (L, E)
    steps = sum(int(s['step']) for s in stats_read)
    layers = flops_latent.expert_layers(config)
    return {
        'active_experts_per_step': (
            sum(int(s['active']) for s in stats_read) / max(steps, 1)),
        'load_max_over_mean': float(np.max(
            tokens.max(axis=1) / np.maximum(tokens.mean(axis=1), 1e-9))),
        'expected_active_per_step': layers * (
            flops_latent.expected_distinct_experts(config, sessions)),
        'expert_bytes': flops_latent.expert_bytes(config),
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
    routing = routing_readings(cell.config, server.stats_read,
                               server.sessions)
    print(json.dumps({
        'decode_gap_ms_p50': float(np.median(gaps)) * 1e3,
        'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3,
        'decode_gap_ms_max': float(np.max(gaps)) * 1e3,
        'window_s': elapsed, 'gaps': int(gaps.size),
        'requests': len(finished), **routing}), flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_logit_steps', bad, 0)
    compare.add('decode_impl_is_kernel',
                0 if server.decode_impl == ['kernel'] else 1,
                cell.limits.get('decode_impl_is_kernel'))
    context, sessions = server.context_tokens, server.sessions
    params, served_picks = server.params, [s['picks']
                                           for s in server.stats_read]
    server.free()
    del server.params
    if t['check_samples'] != 1:
        raise ValueError('the reference consumes the weights: one sample')
    with phase('reference', counted=False):
        (r, s), = sample_requests(seed, finished, sessions, 1)
        first, tokens = finished[r]
        # (expert layers, context + served, k) of session s, request r
        picks = np.concatenate(
            [server.context_picks[s],
             np.moveaxis(served_picks[r][:, :, s], 0, 1)], axis=1)
        logits, differ, regret = reference_logits(
            cell, params, context[s], first[s], tokens[s], picks,
            operand_dtype)
        gaps_ref = logit_gaps(logits, tokens[s])
    print(json.dumps({'served_logit_gap_quantiles': [
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
            'mla_decode_per_step': flops_latent.mla_decode_step(
                cell.config, sessions, mid),
            'moe': routing,
        },
    }
