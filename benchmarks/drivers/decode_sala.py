"""The long-context decode cell of a ``minicpm_sala`` stack (MiniCPM-SALA:
one learned block-sparse NoPE attention layer to three Lightning
linear-attention layers, a dense gated MLP in every layer, muP
multipliers): ``drivers/decode_hybrid.py``'s closed loop of greedy
requests over prefilled sessions — a session prefilled alone and put in
its slot, the states' snapshot at the prompt's end, the slab's length
set back and the states restored between requests (``LayerCaches``) —
with what this architecture changes.

- The model is built here from the configuration's published keys
  (``build_lm``: ``mixer_types`` names each layer's kind; ``qk_norm``,
  the output gates, ``scale_emb`` / ``scale_depth`` / ``dim_model_base``;
  the selection's sizes from ``sparse_config``) and its seeded weights
  from this file's shape table (``shapes`` / ``make``; the four per-head
  norm scales stay float32).
- A program without the sparse layer or the Lightning mixer fails in
  ``build_lm``, at once, before a weight is drawn.
- The sparse layer's cache is a ``SparseCache`` (the slab and the pooled
  keys); its length set back rewinds both, so the reset restores the
  three recurrent states alone.
- The step also returns, accumulated on the device and read once a
  request, every session's BLOCK picks and how many cache rows they
  hold; prefill returns the picks of every prompt position, kept for the
  sampled session.
- ``correct``: the reference's logits (``reference/minicpm_sala.py``,
  one whole session, the recurrence token by token) at the timed run's
  own tokens, fed the program's block picks, context and served, and
  judging them by its own block scores; the three Lightning states after
  the window's last request; every sparse layer's step on the kernel
  ``sparse_decode`` with ``topk`` picks; non-finite values; compilations
  in the window. The sampled request is never the first of the window,
  so it follows a restore.
"""

import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_sala
from benchmarks.drivers import decode
from benchmarks.drivers.decode import logit_gaps
from benchmarks.drivers.decode_granite import state_gap
from benchmarks.drivers.decode_hybrid import (
    LayerCaches, sampled_session, slab_length,
)
from benchmarks.harness import Compare, phase, window_compiles
from benchmarks.weights import seed_key, split_seed

FLOAT32_LEAVES = ('keys_norm', 'queries_norm', 'q_norm', 'k_norm')

layer_kinds = flops_sala.layer_kinds
SPARSE, LIGHTNING = flops_sala.SPARSE, flops_sala.LIGHTNING


def sparse_layers(config):
    return [i for i, kind in enumerate(layer_kinds(config))
            if kind == SPARSE]


def sparse_spec(config):
    """``models/sparse.SparseSpec``'s fields from ``sparse_config``."""
    kernel, stride, block, init, window, topk, dense_len = (
        flops_sala.sparse_sizes(config))
    return {'kernel': kernel, 'stride': stride, 'block': block,
            'init_blocks': init, 'window': window, 'topk': topk,
            'dense_len': dense_len}


def build_lm(config, **attn_overrides):
    """``TransformerLM`` composed as this architecture's stack, at the
    configuration's sizes."""
    from distributed_dot_product_tpu import TransformerLM
    # A program without the two new layers fails HERE, at once.
    from distributed_dot_product_tpu.models.lightning import (  # noqa: F401
        LightningMixer,
    )
    from distributed_dot_product_tpu.models.sparse import (  # noqa: F401
        SparseSpec,
    )
    c = config
    if (c['attn_use_rope'] or c['tie_word_embeddings'] or c['attention_bias']
            or c['hidden_act'] != 'silu' or not c['use_output_norm']
            or not c['use_output_gate']
            or c['lightning_nkv'] != c['lightning_nh']
            or c['lightning_scale'] != '1/sqrt(d)'
            or set(layer_kinds(c)) - {SPARSE, LIGHTNING}):
        raise ValueError('this driver builds NoPE sparse attention, '
                         'Lightning layers with as many key as value '
                         'heads under an output norm and gate, a SiLU MLP '
                         'and an untied head')
    return TransformerLM(
        vocab_size=c['vocab_size'], dim=c['hidden_size'],
        num_heads=c['num_attention_heads'],
        n_layers=c['num_hidden_layers'],
        dtype=jnp.dtype(c['precision']['compute']),
        scan_layers=False, tie_embeddings=False,
        embed_scale=float(c['scale_emb']),
        logit_scale=c['dim_model_base'] / c['hidden_size'],
        attn_kwargs={
            'key_dim': c['num_attention_heads'] * c['head_dim'],
            'num_kv_heads': c['num_key_value_heads'],
            'add_bias': False, 'use_rope': False,
            'qk_norm': c['qk_norm'], 'qk_norm_eps': c['rms_norm_eps'],
            'out_gate': c['attn_use_output_gate'],
            'sparse': sparse_spec(c), **attn_overrides},
        block_kwargs={
            'norm': 'rmsnorm', 'norm_eps': c['rms_norm_eps'],
            'residual_scale': c['scale_depth'] / math.sqrt(
                c['published']['num_hidden_layers']),
            'ffn': 'gated', 'ffn_kwargs': {
                'hidden': c['intermediate_size']}},
        layer_kinds={
            LIGHTNING: {'mixer': 'lightning', 'ssm_kwargs': {
                'heads': c['lightning_nh'],
                'head_dim': c['lightning_head_dim'],
                'chunk': c['lightning_chunk_size'],
                'use_rope': c['lightning_use_rope'],
                'rope_base': float(c['rope_theta']),
                'norm_eps': c['rms_norm_eps'],
                'state_dtype': jnp.dtype(c['precision']['state'])}},
            SPARSE: {'mixer': 'attention'}},
        layer_pattern=tuple(layer_kinds(c)))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, a tree a
    block."""
    c = config
    d, v, ff = c['hidden_size'], c['vocab_size'], c['intermediate_size']
    head = c['head_dim']
    q = c['num_attention_heads'] * head
    kv = c['num_key_value_heads'] * head
    lh = c['lightning_head_dim']
    inner = c['lightning_nh'] * lh
    mixers = {
        LIGHTNING: {
            ('lightning', 'in_proj', 'kernel'): ((d, 4 * inner), d),
            ('lightning', 'q_norm'): ((lh,), None),
            ('lightning', 'k_norm'): ((lh,), None),
            ('lightning', 'norm_scale'): ((inner,), None),
            ('lightning', 'out_proj', 'kernel'): ((inner, d), inner)},
        SPARSE: {
            ('attn', 'keys', 'kernel'): ((d, q), d),
            ('attn', 'queries', 'kernel'): ((d, kv), d),
            ('attn', 'values', 'kernel'): ((d, kv), d),
            ('attn', 'gate', 'kernel'): ((d, q), d),
            ('attn', 'keys_norm'): ((head,), None),
            ('attn', 'queries_norm'): ((head,), None),
            ('attn', 'composition', 'kernel'): ((q, d), q)}}
    mlp = {('mlp', 'gate', 'kernel'): ((d, ff), d),
           ('mlp', 'up', 'kernel'): ((d, ff), d),
           ('mlp', 'down', 'kernel'): ((ff, d), ff)}
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None),
           ('lm_head_kernel',): ((d, v), d)}
    for i, kind in enumerate(layer_kinds(c)):
        block = ('stack', f'block_{i}')
        out[block + ('ln1', 'scale')] = ((d,), None)
        out[block + ('ln2', 'scale')] = ((d,), None)
        for path, leaf in {**mixers[kind], **mlp}.items():
            out[block + path] = leaf
    return out


def leaf_value(key, name, shape, fan_in, init):
    """One leaf's float32 draw: kernels N(0, 1/fan_in), the rest as the
    configuration's ``init`` (a tuple of its items) says. The sparse
    layer's two per-head norm scales are drawn around
    ``qk_norm_scale``: behind a per-head RMSNorm the width of Wq and Wk
    does not reach the scores, these scales do."""
    init = dict(init)
    normal = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return normal / math.sqrt(fan_in)
    if name == 'embedding':
        return init['embedding_std'] * normal
    around = 1.0 + init['scale_std'] * normal
    if name in ('keys_norm', 'queries_norm'):
        return init['qk_norm_scale'] * around
    if name in ('scale', 'norm_scale', 'q_norm', 'k_norm'):
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
        node[path[-1]] = draw_leaf(
            lo, hi, np.int32(i), path[-1], shape, fan_in, jnp.dtype(to),
            init).block_until_ready()
    return {'params': tree}


def zero_stats(config, traffic):
    layers = len(sparse_layers(config))
    return {
        'picks': jnp.zeros(
            (traffic['new_tokens'], layers, traffic['sessions'],
             config['num_key_value_heads'],
             config['sparse_config']['topk']), jnp.int32),
        'rows_read': jnp.zeros((), jnp.float32),
        'rows_valid': jnp.zeros((), jnp.float32),
        'steps_off_topk': jnp.zeros((), jnp.int32),
        'step': jnp.zeros((), jnp.int32)}


def sown_picks(config, sown):
    """``(picks, count)`` of the sparse layers with a leading layer
    axis, the picks cut to ``topk`` entries (what a row above
    ``dense_len`` reads)."""
    stack = sown['counters']['stack']
    topk = config['sparse_config']['topk']
    layers = [stack[f'block_{i}']['attn'] for i in sparse_layers(config)]
    return (jnp.stack([a['sparse_picks'][..., :topk] for a in layers]),
            jnp.stack([a['sparse_count'] for a in layers]))


def make_programs(model, config):
    """A context chunk of one session into its caches, returning the
    chunk's block picks ``(sparse layers, KV heads, chunk, topk)``
    (logits dropped, so the head is not built); a finished session into
    its slot of every layer's cache; the snapshot of the states; whether
    every state is finite; the reset (lengths back, states restored);
    and one token step returning the greedy next token, whether every
    logit was finite, and the picks and their row counts added to
    ``stats``."""
    from distributed_dot_product_tpu.models.decode import (
        insert_session, restore_states, snapshot_states,
    )
    block, topk = (config['sparse_config']['block_size'],
                   config['sparse_config']['topk'])

    def prefill_fn(p, tok, c):
        (c, _), sown = model.apply(p, tok, c, method='prefill',
                                   mutable=['counters'])
        return c, sown_picks(config, sown)[0][:, 0]

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
        rows = slab_length(c) + 1           # valid rows, the token's own
        (c, logits), sown = model.apply(p, tok, c, method='decode',
                                        mutable=['counters'])
        picks, count = sown_picks(config, sown)   # (L, B, G, k), (L,)
        live = jnp.arange(topk) < count[:, None, None, None]
        read = jnp.clip(rows - picks * block, 0, block)
        stats = {
            'picks': jax.lax.dynamic_update_index_in_dim(
                stats['picks'], picks, stats['step'], 0),
            'rows_read': stats['rows_read'] + jnp.sum(
                jnp.where(live, read, 0).astype(jnp.float32)),
            'rows_valid': stats['rows_valid'] + (
                rows * (picks.size // topk)).astype(jnp.float32),
            'steps_off_topk': stats['steps_off_topk'] + jnp.sum(
                count != topk),
            'step': stats['step'] + 1}
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return c, nxt, jnp.all(jnp.isfinite(logits)), stats

    return (jax.jit(prefill_fn, donate_argnums=(2,)),
            jax.jit(insert_fn, donate_argnums=(0,)),
            jax.jit(snapshot_states), jax.jit(finite_fn),
            jax.jit(restore_fn, donate_argnums=(0,)),
            jax.jit(step_fn, donate_argnums=(2, 3)))


class Server(decode.Server):
    """``decode.Server``'s request loop over this model: a cache a layer
    with the states' snapshot, and the picks carried beside them."""

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
            sparse_decode_traces,
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
        self.cache_gib = flops_sala.cache_gib(caches)
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
            with sparse_decode_traces() as forms:
                low_step = step.lower(params, tok1, caches, stats)
        # The form of each sparse layer's step.
        self.sparse_forms = forms
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
                    # (sparse layers, KV heads, context, topk): every
                    # pick the program made of the sampled session's
                    # context, for the reference to follow.
                    self.context_picks = np.concatenate(
                        jax.device_get(picks), axis=2)
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

    def nonfinite_states(self):
        """Resets at which a state that was overwritten held a
        non-finite value."""
        return sum(not bool(ok) for ok in self.caches.finite)

    def free(self):
        del self.caches, self._step, self.stats

    def sparse_steps_off_the_kernel(self):
        """Sparse layers of the step that are not the kernel
        ``sparse_decode`` reading ``topk`` picks, and steps of the
        window in which a layer read another number."""
        topk = self.cell.config['sparse_config']['topk']
        off = sum(f['impl'] != 'kernel' or f['topk'] != topk
                  for f in self.sparse_forms)
        off += max(0, len(sparse_layers(self.cell.config))
                   - len(self.sparse_forms))
        return off + sum(int(s['steps_off_topk']) for s in self.stats_read)


def reference_readings(cell, params, context, first, tokens, picks, states,
                       operand_dtype=None, dense=False):
    """The plain reference once over one session's context, first token
    and served tokens, following the program's block picks ``(sparse
    layers, KV heads, context + served tokens, topk)``: its logits
    ``(served tokens, vocab)`` at the positions that produced them, the
    share of the (layer, KV head, token) triples above ``dense_len`` at
    which its OWN top-k is another set of blocks, the largest regret of
    the program's picks by its own block scores, and how far the
    program's ``states`` after the last of those tokens lie from its own
    (``state_gap``). ``dense``: the reference attends every row (the
    control)."""
    ref = cell.reference()
    n = len(tokens)
    seq = np.concatenate([context, first, tokens[:-1]]).astype(np.int32)
    rows = len(seq)
    pad = (-rows) % ref.ROW_BLOCK
    # Rows are causal and a padded row leaves the states alone: padding
    # after the end changes nothing before it.
    seq = np.concatenate([seq, np.zeros(pad, np.int32)])
    forced = np.pad(picks, ((0, 0), (0, 0), (0, pad), (0, 0)))
    logits, differ, regret, ref_states = ref.logits_at(
        cell.config, params, jnp.asarray(seq), n + pad, operand_dtype,
        forced_picks=jnp.asarray(forced), valid=rows, dense=dense)
    sparse = (np.arange(rows) + 1) > cell.config['sparse_config'][
        'dense_len']
    differ = np.asarray(differ)[..., :rows][..., sparse]
    regret = np.asarray(regret)[..., :rows][..., sparse]
    return (np.asarray(logits[:n]),
            float(np.mean(differ)) if differ.size else 0.0,
            float(np.max(regret)) if regret.size else 0.0,
            state_gap(states, ref_states))


def run(cell, seed, seconds, trace, tracer, step_wrapper=None,
        operand_dtype=None, dense_reference=False):
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
    print(json.dumps({'sparse_forms': server.sparse_forms,
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
    share = (sum(float(s['rows_read']) for s in server.stats_read)
             / max(sum(float(s['rows_valid']) for s in server.stats_read),
                   1.0))
    served_tokens = np.stack([tokens for _, tokens in finished])
    print(json.dumps({
        # Of the tokens served, how many differ: greedy continuations
        # that fall into one attractor pick alike.
        'distinct_token_share': len(np.unique(served_tokens))
        / served_tokens.size,
        'decode_gap_ms_p50': float(np.median(gaps)) * 1e3,
        'decode_gap_ms_p95': float(np.percentile(gaps, 95)) * 1e3,
        'decode_gap_ms_max': float(np.max(gaps)) * 1e3,
        'window_s': elapsed, 'gaps': int(gaps.size),
        'requests': len(finished), 'picked_rows_share': share}),
        flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_logit_steps', bad, 0)
    # The sampled session's states as the last request left them.
    served_states = np.stack([
        np.asarray(c.state[server.sampled]) for c in server.caches.layers
        if hasattr(c, 'state')])
    # The last request's states are looked at too: one more reset.
    server.caches._replace(server.length0)
    compare.add('nonfinite_state_resets', server.nonfinite_states(), 0)
    compare.add('sparse_steps_off_the_kernel',
                server.sparse_steps_off_the_kernel(),
                cell.limits.get('sparse_steps_off_the_kernel'))
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
        # (sparse layers, KV heads, context + served, topk) of session s,
        # request r
        picks = np.concatenate(
            [server.context_picks,
             np.moveaxis(served_picks[r][:, :, s], (0, 1, 2), (2, 0, 1))],
            axis=2)
        logits, differ, regret, off = reference_readings(
            cell, params, context[s], first[s], tokens[s], picks,
            served_states, operand_dtype, dense_reference)
        gaps_ref = logit_gaps(logits, tokens[s])
    print(json.dumps({'sampled_request': r, 'sampled_session': s,
                      'served_logit_gap_quantiles': [
        float(np.percentile(gaps_ref, q)) for q in (50, 90, 99, 100)]}),
        flush=True)
    compare.add('served_logit_gap', float(np.max(gaps_ref)),
                cell.limits.get('served_logit_gap'))
    compare.add('block_pick_difference_share', differ,
                cell.limits.get('block_pick_difference_share'))
    compare.add('block_pick_regret', regret,
                cell.limits.get('block_pick_regret'))
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
            'sparse_decode_per_step': flops_sala.sparse_decode_step(
                cell.config, sessions, mid),
            'sparse_select_per_step': flops_sala.sparse_select_step(
                cell.config, sessions, mid),
            'lightning_step_per_step': flops_sala.lightning_step(
                cell.config, sessions),
            'attn': {'picked_rows_share': share},
            'cache': cache_gib,
        },
    }
