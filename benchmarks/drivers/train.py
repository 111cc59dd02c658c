"""Training cells: the program's donating LM train step
(``make_lm_train_step(..., guard=False)``) on one packed sequence a step,
driven as the training driver drives it: dispatch step i + 1, then fetch
step i's loss. The window ends on a fetched loss.

``run_training`` itself refuses a donating step, so a job under it holds
two copies of parameters and optimizer state and fits half the depth;
the loop here is the same pipelining around the step that fits.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks import flops, system, weights
from benchmarks.harness import Compare, phase, window_compiles

ATTN_LEAVES = ('keys', 'queries', 'values', 'composition')


def make_optimizer(h):
    """AdamW with the mix's hyper-parameters."""
    import optax
    return optax.adamw(h['lr'], b1=h['b1'], b2=h['b2'], eps=h['eps'],
                       weight_decay=h['weight_decay'])


def make_batches(seed, count, seq_len, vocab, sharding):
    """``count`` different sequences of seeded uniform tokens with their
    next-token targets (-1 at the last position), placed as the step
    takes them. One jitted call; the seed is an argument, so every seed
    runs the same program."""

    def build(lo, hi):
        key = weights.seed_key(lo, hi, salt=0x7a11)
        tokens = jax.random.randint(key, (count, 1, seq_len), 0, vocab,
                                    dtype=jnp.int32)
        targets = jnp.concatenate(
            [tokens[..., 1:], jnp.full((count, 1, 1), -1, jnp.int32)], -1)
        return tokens, targets

    shard = NamedSharding(sharding.mesh, P(None, *sharding.spec))
    tokens, targets = jax.jit(build, out_shardings=(shard, shard))(
        *weights.split_seed(seed))
    split = jax.jit(lambda a: tuple(a[i] for i in range(count)),
                    out_shardings=(sharding,) * count)
    return list(zip(split(tokens), split(targets)))


def leaf_norms(tree):
    """Norm of every leaf, a layer-stacked leaf giving one per layer,
    as ``{path: float32 vector}``."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = '/'.join(str(k.key) for k in path)
        x = leaf.astype(jnp.float32)
        stacked = 'layers' in name
        x = x.reshape(x.shape[0] if stacked else 1, -1)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=1))
    return out


@jax.jit
def delta_norms(params, params0):
    """Per-leaf norms of the parameters' change."""
    return leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))


def attn_kernels(tree):
    block = tree['params']['stack']['layers']['block']['attn']
    return {name: block[name]['kernel'] for name in ATTN_LEAVES}


def worst_norm_gap(got, want):
    """Largest ``|norm_got - norm_want|`` over leaves, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    want_all = np.concatenate([np.ravel(v) for v in want.values()])
    floor = float(np.median(want_all))
    worst, where = 0.0, None
    for name, w in want.items():
        gap = np.abs(np.asarray(got[name]) - w) / np.maximum(w, floor)
        i = int(np.argmax(gap))
        if not np.all(np.isfinite(gap)):
            return float('inf'), name
        if gap[i] > worst:
            worst, where = float(gap[i]), f'{name}[{i}]'
    return worst, where


def worst_rel_diff(got, want):
    """Largest ``|a - b| / |b|`` over the attention kernels, per layer."""
    worst, where = 0.0, None
    for name in ATTN_LEAVES:
        a = np.asarray(got[name], np.float32)
        b = np.asarray(want[name], np.float32)
        a, b = a.reshape(a.shape[0], -1), b.reshape(b.shape[0], -1)
        rel = (np.linalg.norm(a - b, axis=1)
               / np.maximum(np.linalg.norm(b, axis=1), 1e-30))
        i = int(np.argmax(rel))
        if not np.all(np.isfinite(rel)):
            return float('inf'), name
        if rel[i] > worst:
            worst, where = float(rel[i]), f'{name}[{i}]'
    return worst, where


def reference_steps(cell, seed, batches, hyper, n_steps,
                    operand_dtype=None):
    """The plain reference through the first ``n_steps`` steps from the
    seeded weights: losses, per-leaf norms of the first gradient and of
    the parameters' change, and the first gradient of the attention
    kernels. Runs before the program's state exists and frees its own.
    ``operand_dtype`` makes it the control (``common.operands_in``)."""
    from benchmarks.reference import common
    family = cell.reference()
    sizes = weights.model_sizes(cell.config)
    step = common.make_train_step(family, sizes, hyper, operand_dtype)
    params = weights.make(cell.config, seed)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v, count = zeros(params), zeros(params), jnp.int32(0)
    out = {'losses': []}
    for i in range(n_steps):
        tokens, targets = (np.asarray(a)[0] for a in batches[i])
        params, m, v, count, value, grads = step(params, m, v, count,
                                                 tokens, targets)
        if i == 0:
            out['grad_norms'] = jax.device_get(jax.jit(leaf_norms)(grads))
            out['attn_grads'] = jax.device_get(attn_kernels(grads))
        del grads
        out['losses'].append(float(value))
    del m, v
    out['delta_norms'] = jax.device_get(
        delta_norms(params, weights.make(cell.config, seed)))
    del params
    return out


class Trainer:
    """The compiled step with its state: ONE object that set-up builds,
    drives through its first steps and hands to the window."""

    def __init__(self, cell, seed, attn_overrides=None, step_wrapper=None):
        from distributed_dot_product_tpu.parallel.mesh import seq_mesh
        from distributed_dot_product_tpu.train import make_lm_train_step
        t = cell.traffic
        self.cell, self.seed = cell, seed
        self.seq_len = t['seq_len']
        self.mesh = seq_mesh(cell.chips)
        self.model = system.build_lm(cell.config, **(attn_overrides or {}))
        self.optimizer = make_optimizer(t['optimizer'])
        self.tok_sharding = NamedSharding(self.mesh, P(None, 'seq'))
        self.rep = NamedSharding(self.mesh, P())
        with phase('batches'):
            self.batches = make_batches(
                seed, t['distinct_batches'], self.seq_len,
                cell.config['vocab_size'], self.tok_sharding)
        self._jit_step = make_lm_train_step(
            self.model, self.optimizer, self.mesh, guard=False,
            loss_chunk=t['loss_chunk'])
        self.step_wrapper = step_wrapper
        self.steps_done = 0

    def init_state(self):
        with phase('init'):
            params = jax.device_put(
                weights.make(self.cell.config, self.seed), self.rep)
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=self.rep)(params)
            jax.block_until_ready(opt_state)
        self.params, self.opt_state = params, opt_state

    def compile(self):
        with phase('lower'):
            lowered = self._jit_step.lower(self.params, self.opt_state,
                                           self.batches[0])
        with phase('compile'):
            compiled = lowered.compile()
        step = compiled
        if self.step_wrapper is not None:
            step = self.step_wrapper(compiled)
        self._step = step

    def dispatch(self):
        """Enqueue the next step; returns its loss, not yet fetched."""
        batch = self.batches[self.steps_done % len(self.batches)]
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch)
        self.steps_done += 1
        return loss

    def first_steps(self, n_steps):
        """Steps 1..n through the window's own call and feed, reading
        what the check compares: each loss, the first gradient as the
        optimizer got it (Adam's first moment after one step is
        ``(1 - b1) * g``), and the parameters' change after ``n``."""
        b1 = self.cell.traffic['optimizer']['b1']
        mu_norms = jax.jit(lambda s: leaf_norms(jax.tree.map(
            lambda x: x / (1.0 - b1), s[0].mu)))
        mu_attn = jax.jit(lambda s: jax.tree.map(
            lambda x: x / (1.0 - b1), attn_kernels(s[0].mu)))
        losses = []
        out = {}
        for i in range(n_steps):
            losses.append(self.dispatch())
            if i == 0:
                out['grad_norms'] = mu_norms(self.opt_state)
                out['attn_grads'] = mu_attn(self.opt_state)
        # The steps have to end first: the seeded weights are drawn again
        # here, and must not sit beside a running step's temporaries.
        jax.block_until_ready(self.params)
        out['delta_norms'] = delta_norms(
            self.params, weights.make(self.cell.config, self.seed))
        out = jax.device_get(out)
        out['losses'] = [float(x) for x in jax.device_get(losses)]
        return out


def compare_first_steps(compare, got, want, limits):
    for i, (a, b) in enumerate(zip(got['losses'], want['losses'])):
        compare.add(f'loss_gap.step{i + 1}', abs(a - b),
                    limits.get('loss_gap'), detail=f'{a:.6f} vs {b:.6f}')
    gap, where = worst_norm_gap(got['grad_norms'], want['grad_norms'])
    compare.add('grad_norm_gap', gap, limits.get('grad_norm_gap'),
                detail=where)
    gap, where = worst_rel_diff(got['attn_grads'], want['attn_grads'])
    compare.add('attn_grad_rel_diff', gap,
                limits.get('attn_grad_rel_diff'), detail=where)
    gap, where = worst_norm_gap(got['delta_norms'], want['delta_norms'])
    compare.add('update_norm_gap', gap, limits.get('update_norm_gap'),
                detail=where)


def run(cell, seed, seconds, trace, tracer, step_wrapper=None):
    t = cell.traffic
    compare = Compare()
    trainer = Trainer(cell, seed, step_wrapper=step_wrapper)
    with phase('reference', counted=False):
        want = reference_steps(cell, seed, trainer.batches,
                               t['optimizer'], t['check_steps'])
    trainer.init_state()
    trainer.compile()
    with phase('check_steps'):
        got = trainer.first_steps(t['check_steps'])
    compare_first_steps(compare, got, want, cell.limits)
    del want, got
    with phase('warm'):
        # One more fetched step: the pipelined loop below starts from a
        # drained device.
        float(trainer.dispatch())
    setup_done = time.perf_counter()

    losses, stamps = [], []
    steps_at_start = trainer.steps_done
    with window_compiles() as compiles, tracer.window(trace):
        t0 = time.perf_counter()
        limit = t['trace_steps'] if trace else None
        pending = trainer.dispatch()
        while True:
            with tracer.span('bench.dispatch'):
                nxt = trainer.dispatch()
            with tracer.span('bench.fetch_loss'):
                losses.append(float(pending))
            pending = nxt
            stamps.append(time.perf_counter())
            if (stamps[-1] - t0 >= seconds
                    or (limit and len(losses) >= limit)):
                break
        with tracer.span('bench.fetch_loss'):
            losses.append(float(pending))
        stamps.append(time.perf_counter())
        elapsed = stamps[-1] - t0
    steps = trainer.steps_done - steps_at_start
    failed = sum(1 for x in losses if not np.isfinite(x))
    print(json.dumps({
        'steps': steps, 'window_s': elapsed,
        'step_ms_median': 1e3 * float(np.median(np.diff(stamps)))}),
        flush=True)
    compare.add('window_compiles', compiles.count, 0)
    compare.add('nonfinite_losses', failed, 0)
    tokens_per_s = steps * trainer.seq_len * t['batch'] / elapsed
    sizes = weights.model_sizes(cell.config)
    return {
        'compare': compare, 'attempted': steps + t['check_steps'] + 1,
        'failed': failed, 'setup_done': setup_done,
        'end_to_end': {'train_tokens_per_s': tokens_per_s},
        'observed': {
            'steps': steps, 'window_s': elapsed,
            'tokens_per_s': tokens_per_s, 'chips': cell.chips,
            'model_flops_per_token': flops.train_flops_per_token(
                sizes, trainer.seq_len),
            'flash_per_step': flops.flash_train_step(
                sizes, trainer.seq_len, cell.chips),
        },
    }
