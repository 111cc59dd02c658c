#!/usr/bin/env python3
# -*- coding: utf-8 -*-
"""
The quickest proof that the system still starts on the chip: drive the
train → generate → serve path once, through the entry points a user
calls, at the full width of the model the repo has trained since its
first round (vocab 32768, dim 768, 8 heads of 96,
8 scanned + remat'd layers, bf16, flash causal attention), and check
what comes out by the repo's own means. ``generate_latent`` repeats the
generate checks on a small-depth model of the other block
``TransformerLM`` composes (latent attention at its published head
sizes, sparse experts, hyper-connection streams), whose step is
``flash_decode``'s latent mode; ``generate_mixed`` on a stack of window
and full attention layers (the parallel block over sparse experts),
whose step runs the kernel's slab mode and its ring mode side by side,
so a ring-mode failure on a new runtime shows here before the benchmark.

    python chip_smoke.py             # one chip: train, generate (x4), serve
    python chip_smoke.py --chips 4   # ONLY the cross-chip phases
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

One process for every phase and every chip; it starts no child. Each
phase prints one JSON line; the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as JAX reports it. Any failed check, any exception, or a
platform other than ``tpu`` makes ``ok`` false and the exit code
non-zero. Without an accelerator the script refuses and prints no
result; ``--tiny`` shrinks the sizes so the control flow can be
rehearsed on the CPU — it relaxes no check, so such a run still fails.
"""

import argparse
import json
import os
import statistics
import sys
import time
import traceback

FULL = dict(
    vocab=32768, dim=768, heads=8, layers=8,
    train_t=16384, ref_t=512,                       # train
    prompt=2048, new_tokens=16, gen_t_max=4096,     # generate
    slots=8, serve_t_max=32768, page=256, chunk=256,  # serve
    requests=12, prompt_lo=256, prompt_hi=4096, serve_new=32,
    mm_t=75000, mm_offset=25000, lm4_t=65536,       # --chips 4
    shard_ctx=25000,
    # generate_latent: MLA + sparse experts + hyper-connections at the
    # published head sizes of benchmarks/configs/xing4-29b-a4b-serve
    # (top_k = experts: every expert gated, through the same sort and
    # grouped matmuls. A top-k of fewer flips at near-ties between the
    # kernel's step and its XLA twin, and a flipped pick moves the
    # logits by more than any rounding tolerance: chip, PR 26)
    latent=dict(dim=1024, heads=8, q_rank=256, kv_rank=512, nope=128,
                rope=64, v=128, dense_hidden=2048, experts=8, top_k=8,
                expert_hidden=256, layers=3),
    # generate_mixed: window and full attention layers in one stack
    # (the parallel block of benchmarks/configs/command-a-plus-serve at
    # its head size, GQA 4:1): the 2048-token prompt fills the window
    # layers' 2048-column ring, two K splits, and the first served
    # token wraps it
    mixed=dict(dim=1024, heads=8, kv_heads=2, window=512, ring=2048,
               experts=8, expert_hidden=256, shared=2, layers=4),
    # generate_hybrid: a latent-expert, a Mamba-2 and an attention layer
    # in one stack, each with ONE branch (the three kinds of
    # benchmarks/configs/nemotron-3-super-serve at its head sizes): a
    # fixed-size float32 state beside a slab and no cache
    hybrid=dict(dim=1024, heads=8, kv_heads=2, ssm_heads=32,
                ssm_head_dim=64, state=128, groups=8, experts=8,
                expert_hidden=256, latent=256, shared=512),
    # generate_sparse: a learned block-sparse attention layer and a
    # Lightning linear-attention layer in one stack (the two kinds of
    # benchmarks/configs/minicpm-sala-serve at its head size and block):
    # the 2048-token prompt ends above dense_len, so every served token
    # picks 8 of its 32 blocks
    sparse=dict(dim=1024, heads=8, kv_heads=2, hidden=2048,
                select=dict(kernel=32, stride=16, block=64, window=256,
                            topk=8, dense_len=1024)),
    # generate_ling: a delta-rule layer with full-rank gates and a
    # bounded decay beside ONE latent layer with no query rank and a
    # head-wise gate, experts under group-limited routing held as one
    # group (the kinds of benchmarks/configs/ling-3.0-flash-serve at its
    # head sizes): a LatentCache beside a StateCache in one list
    ling=dict(dim=1024, heads=8, head_dim=128, kv_rank=512, nope=128,
              rope=64, v=128, experts=32, top_k=4, groups=4, kept=2,
              held=(8, 16), expert_hidden=256),
    # generate_conv: a gated short-convolution layer under a dense MLP,
    # one under experts with no shared one, and a GQA layer at 64-wide
    # heads on the PACKED slab (the kinds of
    # benchmarks/configs/lfm2-8b-a1b-serve at its head size): a
    # StateCache that is a window alone beside a PackedCache
    conv=dict(dim=1024, heads=16, kv_heads=4, hidden=2048, experts=8,
              top_k=2, expert_hidden=256))
TINY = dict(
    vocab=128, dim=64, heads=2, layers=1,
    train_t=128, ref_t=32,
    prompt=16, new_tokens=3, gen_t_max=32,
    slots=2, serve_t_max=128, page=16, chunk=16,
    requests=3, prompt_lo=8, prompt_hi=40, serve_new=4,
    mm_t=512, mm_offset=128, lm4_t=256,
    shard_ctx=100,
    latent=dict(dim=64, heads=4, q_rank=24, kv_rank=32, nope=16, rope=16,
                v=16, dense_hidden=96, experts=4, top_k=4,
                expert_hidden=32, layers=3),
    mixed=dict(dim=64, heads=2, kv_heads=1, window=8, ring=16, experts=4,
               expert_hidden=32, shared=2, layers=4),
    hybrid=dict(dim=64, heads=2, kv_heads=1, ssm_heads=4, ssm_head_dim=16,
                state=16, groups=2, experts=4, expert_hidden=32, latent=32,
                shared=48),
    sparse=dict(dim=64, heads=4, kv_heads=2, hidden=96,
                select=dict(kernel=4, stride=2, block=8, window=8, topk=2,
                            dense_len=16)),
    ling=dict(dim=64, heads=4, head_dim=16, kv_rank=32, nope=16, rope=16,
              v=16, experts=8, top_k=2, groups=4, kept=2, held=(2, 4),
              expert_hidden=32),
    conv=dict(dim=128, heads=2, kv_heads=1, hidden=96, experts=4, top_k=2,
              expert_hidden=32))

# bf16 tolerance, relative to the compared tensor's own scale: two paths
# that are equal in exact arithmetic may differ by max|a - b| <=
# BF16_RTOL * max|b| (the repo's "atol ~ 2e-2 at unit scale" class —
# SKILL.md "Known expected behaviors", tests/test_paged_int8.py). The
# error is absolute, inherited from bf16 hidden states, so it does not
# shrink with the magnitude of the individual logit. Measured on the
# chip (PR 21): kernel-vs-XLA decode logits and flash-vs-plain logits
# differ by at most 0.03125 at max|logit| 4.5-5.5 — one bf16 ulp in
# [4, 8), 0.7e-2 of scale.
BF16_RTOL = 2e-2
LOSS_ATOL = 2e-2


def emit(rec):
    print(json.dumps(rec, default=str), flush=True)


class Programs:
    """Per-phase record of the programs compiled ahead of running them:
    whether the compiled HLO holds a Mosaic kernel (``tpu_custom_call``)
    — an interpreted or reference path cannot pass for a program that is
    supposed to contain one — and the collectives it must hold. What a
    program cost to build is the ledger's to say (the phase's ``build``
    line): ONE clock for a build."""

    def __init__(self):
        self.records = {}

    def compile(self, name, jitted, *args, pallas, collectives=()):
        compiled = jitted.lower(*args).compile()
        text = compiled.as_text()
        rec = {op: op in text for op in collectives}
        if pallas:
            rec['tpu_custom_call'] = 'tpu_custom_call' in text
        self.records[name] = rec
        return compiled

    def checks(self):
        return {f'{name}.{key}': val
                for name, rec in self.records.items()
                for key, val in rec.items()}


def run_phase(name, fn, *args):
    """Run one phase and print its line. An exception is reported as a
    failed phase (traceback on stderr) so the remaining phases still
    run in the same chip call; it can never turn into a pass."""
    t0 = time.perf_counter()
    progs = Programs()
    try:
        rec = fn(progs, *args)
    except Exception as e:
        traceback.print_exc()
        rec = {'checks': {}, 'error': f'{type(e).__name__}: {e}'[:800]}
    checks = {**progs.checks(), **rec.pop('checks')}
    ok = 'error' not in rec and bool(checks) and all(checks.values())
    emit({'phase': name, 'ok': ok,
          'seconds': round(time.perf_counter() - t0, 3),
          **rec, 'checks': checks})
    emit(build_line(name, t0))
    return ok


def build_line(name, since):
    """What the phase BUILT, from the program's own ledger
    (``utils/build_ledger.py``): self seconds by kind (a kernel's body
    is ``build``, out of its program's ``trace``), the persistent
    cache's counts, the kernels' bodies by name and the three costliest
    programs — the ones to look at when a phase's seconds move."""
    from distributed_dot_product_tpu.utils import build_ledger
    s = build_ledger.summary(since=since)
    return {'build': name,
            'seconds': {k: round(v, 3) for k, v in s['seconds'].items()},
            'cache': s['cache'],
            'kernels': {k: round(v, 3) for k, v in s['kernels'].items()},
            'costliest': [[program, round(total, 3)] for program, total, _
                          in build_ledger.costliest(s, 3)],
            'records': s['records'], 'dropped': s['dropped']}


def max_err(a, b):
    """``(max|a - b|, max|b|)`` in f32."""
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))), float(np.max(np.abs(b)))


def within_bf16(err, scale):
    return err <= BF16_RTOL * scale


# -- information lines ---------------------------------------------------

def sync_probe(tiny):
    """Does ``jax.block_until_ready`` fence on this machine, and what do
    it and ``utils.tracing.hard_sync`` cost on a finished array? (The
    timing helpers were shaped around a backend where it did not.)"""
    import jax
    import jax.numpy as jnp

    from distributed_dot_product_tpu.utils.tracing import hard_sync

    n, reps = (256, 8) if tiny else (4096, 64)
    x = jnp.ones((n, n), jnp.bfloat16)
    eye = jnp.eye(n, dtype=jnp.bfloat16)
    work = jax.jit(lambda a, w: jax.lax.fori_loop(
        0, reps, lambda _, c: c @ w, a))
    hard_sync(work(x, eye))                      # compile + warm
    t0 = time.perf_counter()
    y = work(x, eye)
    dispatched = time.perf_counter() - t0
    jax.block_until_ready(y)
    blocked = time.perf_counter() - t0
    hard_sync(y)
    synced = time.perf_counter() - t0

    def cost(fn):
        out = []
        for _ in range(50):
            t = time.perf_counter()
            fn(y)
            out.append(time.perf_counter() - t)
        return statistics.median(out)

    emit({'info': 'sync', 'work': f'{reps} x ({n},{n}) bf16 matmul',
          'dispatch_returned_s': dispatched,
          'block_until_ready_returned_s': blocked,
          'hard_sync_after_returned_s': synced,
          # Nearly all of the wait sat inside block_until_ready.
          'block_until_ready_fences': blocked >= 0.9 * synced,
          'block_until_ready_on_finished_s': cost(jax.block_until_ready),
          'hard_sync_on_finished_s': cost(hard_sync)})


# -- one chip: train -----------------------------------------------------

def lm(cfg, **attn_kwargs):
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=cfg['dim'], num_heads=cfg['heads'],
        n_layers=cfg['layers'], dtype=jnp.bfloat16, scan_layers=True,
        remat=True,
        attn_kwargs={'softmax_impl': 'flash', **attn_kwargs})


def seeded_batch(cfg, seed, t, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu import lm_targets
    from distributed_dot_product_tpu.parallel.mesh import globalize
    from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
    tokens = jax.random.randint(jax.random.key(seed), (1, t), 0,
                                cfg['vocab'], dtype='int32')
    spec = NamedSharding(mesh, P(None, SEQ_AXIS))
    return (globalize(tokens, spec),
            globalize(lm_targets(tokens), spec)), tokens


def phase_train(progs, cfg, seed, state):
    import jax
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu import (
        TrainLoopConfig, TrainState, lm_targets, run_training,
    )
    from distributed_dot_product_tpu.models.lm import head_loss_traces
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_block_traces, flash_bwd_traces,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_lm_train_step
    from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

    mesh = seq_mesh(1)
    model = lm(cfg)
    batch, tokens = seeded_batch(cfg, seed, cfg['train_t'], mesh)
    params = model.init(jax.random.key(seed + 1), tokens[:, :16])
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    # The step examples/train_lm.py builds: guarded, not donating.
    step = make_lm_train_step(model, optimizer, mesh, donate=False,
                              guard=True)
    # Which form the flash backward takes at this shape (the training
    # cells' form: one fused kernel, dq resident in VMEM), and how many
    # of each kernel's run blocks are interior (no position compares);
    # which route the head's gradient takes (the kernel, at real widths).
    with flash_bwd_traces() as bwd_traces, \
            flash_block_traces() as block_traces, \
            head_loss_traces() as head_traces:
        progs.compile('train_step', step, params, opt_state, batch,
                      pallas=True)
    result = run_training(
        step, TrainState(0, params, opt_state), lambda i: batch,
        TrainLoopConfig(num_steps=3, final_save=False,
                        tokens_per_step=cfg['train_t']))
    losses = [result.losses[i] for i in range(3)]
    state['model'], state['params'] = model, result.state.params

    # Same weights, short sequence: the flash path against the plain
    # one (no shard_map, full softmax) — loss and logits.
    ref = lm(cfg, distributed=False, softmax_impl='full')
    tok = tokens[:, :cfg['ref_t']]
    tgt = lm_targets(tok)

    def flash_local(p, tk, tg):
        s, c = model.apply(p, tk, tg, deterministic=True,
                           method='nll_sum')
        logits = model.apply(p, tk, deterministic=True)
        return lax.psum(s, SEQ_AXIS) / lax.psum(c, SEQ_AXIS), logits

    seq = P(None, SEQ_AXIS)
    flash = jax.jit(jax.shard_map(
        flash_local, mesh=mesh, in_specs=(P(), seq, seq),
        out_specs=(P(), P(None, SEQ_AXIS, None)), check_vma=False))

    def plain(p, tk, tg):
        s, c = ref.apply(p, tk, tg, deterministic=True,
                         method='nll_sum')
        return s / c, ref.apply(p, tk, deterministic=True)

    trained = result.state.params
    progs.compile('flash_forward', flash, trained, tok, tgt, pallas=True)
    loss_f, logits_f = flash(trained, tok, tgt)
    loss_p, logits_p = jax.jit(plain)(trained, tok, tgt)
    logit_err, logit_scale = max_err(logits_f, logits_p)
    return {
        'T': cfg['train_t'], 'losses': losses,
        'bad_steps': result.bad_steps,
        'flash_bwd': bwd_traces, 'flash_blocks': block_traces,
        'head_loss': head_traces,
        'ref_T': cfg['ref_t'], 'loss_flash': float(loss_f),
        'loss_plain': float(loss_p), 'logits_max_abs_err': logit_err,
        'logits_max_abs': logit_scale,
        'checks': {
            'losses_finite': bool(np.all(np.isfinite(losses))),
            'no_bad_steps': result.bad_steps == 0,
            'flash_bwd_fused': bool(bwd_traces) and all(
                t['form'] == 'fused' for t in bwd_traces),
            'loss_step3_below_step1': losses[2] < losses[0],
            'loss_matches_plain_path':
                abs(float(loss_f) - float(loss_p)) <= LOSS_ATOL,
            'logits_match_plain_path': within_bf16(logit_err,
                                                   logit_scale),
        }}


# -- one chip: generate --------------------------------------------------

def forced_logits(progs, tag, model, params, prompt, forced, t_max,
                  pallas_step):
    """Per-step logits of ``model`` along the token path ``forced``
    (teacher forcing: token ids flip on near-ties between decode impls,
    logits compare)."""
    import jax
    import numpy as np
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'),
                   donate_argnums=(2,))
    caches = model.make_decode_caches(prompt.shape[0], t_max)
    progs.compile(f'{tag}.prefill', prefill, params, prompt, caches,
                  pallas=True)
    progs.compile(f'{tag}.decode', step, params, forced[:, :1], caches,
                  pallas=pallas_step)
    caches, logits = prefill(params, prompt, caches)
    out = [np.asarray(logits[:, -1], np.float32)]
    for j in range(forced.shape[1] - 1):
        caches, logits = step(params, forced[:, j:j + 1], caches)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out)


def latent_lm(cfg, **attn_kwargs):
    """One dense and ``layers - 1`` expert layers of the latent-attention
    / sparse-expert / hyper-connection block (``cfg['latent']``)."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['latent']
    yarn = (('beta_fast', 32), ('beta_slow', 1), ('factor', 64),
            ('mscale', 1), ('mscale_all_dim', 1),
            ('original_max_position_embeddings', cfg['prompt'] // 2))
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=c['layers'], dtype=jnp.bfloat16, scan_layers=False,
        tie_embeddings=False,
        attn_kwargs={'q_rank': c['q_rank'], 'kv_rank': c['kv_rank'],
                     'nope_dim': c['nope'], 'rope_dim': c['rope'],
                     'v_dim': c['v'], 'rope_scaling': yarn,
                     **attn_kwargs},
        block_kwargs={'norm': 'rmsnorm', 'mixer': 'latent',
                      'ffn': 'experts',
                      'ffn_kwargs': {'n_experts': c['experts'],
                                     'top_k': c['top_k'],
                                     'hidden': c['expert_hidden'],
                                     'scaling': 2.0},
                      'residual': 'hyper'},
        dense_prefix=1,
        prefix_kwargs={'ffn': 'gated',
                       'ffn_kwargs': {'hidden': c['dense_hidden']}})


def mixed_lm(cfg, **attn_kwargs):
    """A period of three window layers and a full one in the parallel
    block over sparse experts (``cfg['mixed']``): the window layers
    decode on a ring cache through the kernel's ring mode, the full
    layer on a slab. Every expert is gated, as in ``latent_lm``."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['mixed']
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=c['layers'], dtype=jnp.bfloat16, scan_layers=False,
        attn_kwargs={'num_kv_heads': c['kv_heads'],
                     'rope_layout': 'interleaved', **attn_kwargs},
        block_kwargs={'norm': 'layernorm_nobias', 'parallel': True,
                      'ffn': 'experts',
                      'ffn_kwargs': {'n_experts': c['experts'],
                                     'top_k': c['experts'],
                                     'hidden': c['expert_hidden'],
                                     'n_shared': c['shared'],
                                     'shared_combine': 'mean',
                                     'router_bias': False}},
        layer_kinds={'window': {'attn_kwargs': {
            'window': c['window'], 'ring_cache': c['ring']}},
            'full': {'attn_kwargs': {'use_rope': False}}},
        layer_pattern=('window', 'window', 'window', 'full'))


def generate_once(progs, tag, cfg, seed, params, lm=lm, **attn_kwargs):
    """greedy_generate with the module's decode_impl left at its
    default, then the same token path through an ``decode_impl='xla'``
    twin: resolved impl, kernel presence, finite tokens, logit parity.
    ``lm`` builds the model (``lm``, ``latent_lm``)."""
    import jax
    import numpy as np

    from distributed_dot_product_tpu import greedy_generate
    from distributed_dot_product_tpu.models import lm as lm_mod
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    model = lm(cfg, **attn_kwargs)
    twin = lm(cfg, decode_impl='xla', **attn_kwargs)
    n, steps, t_max = cfg['prompt'], cfg['new_tokens'], cfg['gen_t_max']
    prompt = jax.random.randint(jax.random.key(seed + 2), (1, n), 0,
                                cfg['vocab'], dtype='int32')
    # The compiled pair greedy_generate itself runs.
    g_prefill, g_step = lm_mod._generate_programs(model, True, 1, n,
                                                  t_max)
    caches = model.make_decode_caches(1, t_max)
    with decode_impl_traces() as traces:
        progs.compile(f'{tag}.generate_prefill', g_prefill, params,
                      prompt, caches, pallas=True)
        progs.compile(f'{tag}.generate_step', g_step, params,
                      prompt[:, :1], caches, pallas=True)
        tokens = greedy_generate(model, params, prompt, steps,
                                 t_max=t_max)
    resolved = sorted({t['resolved'] for t in traces})
    # The caches the steps were on ('layer', 'stacked', 'ring').
    on = sorted({t['cache'] for t in traces})
    # What one grid step of the kernel holds (decode_geometry).
    kernel_step = next((t['step'] for t in traces if t['step']), None)
    # … and the rows it moves of a slot's last split where no more are
    # filled (None: that split is always moved whole), a cache kind.
    kernel_tail = {t['cache']: t['tail'] for t in traces if t['step']}
    tokens = np.asarray(tokens)
    ours = forced_logits(progs, f'{tag}.auto', model, params, prompt,
                         tokens, t_max, pallas_step=True)
    theirs = forced_logits(progs, f'{tag}.xla', twin, params, prompt,
                           tokens, t_max, pallas_step=False)
    err, scale = max_err(ours[1:], theirs[1:])
    return {
        f'{tag}_resolved_impl': resolved,
        f'{tag}_caches': on,
        f'{tag}_kernel_step': kernel_step,
        f'{tag}_kernel_tail': kernel_tail,
        f'{tag}_logits_max_abs_err': err,
        f'{tag}_logits_max_abs': scale,
        f'{tag}_replay_argmax_agrees': int(np.sum(
            ours.argmax(-1)[:, 0] == tokens[0])),
    }, {
        f'{tag}.resolved_kernel': resolved == ['kernel'],
        f'{tag}.tokens_shape': tokens.shape == (1, steps),
        f'{tag}.tokens_in_vocab': bool(
            np.all((tokens >= 0) & (tokens < cfg['vocab']))),
        f'{tag}.logits_finite': bool(np.all(np.isfinite(ours))),
        f'{tag}.decode_logits_match_xla': within_bf16(err, scale),
    }


def phase_generate(progs, cfg, seed, state):
    import jax
    if 'params' not in state:
        raise RuntimeError('the train phase left no weights to '
                           'generate with')
    rec, checks = generate_once(progs, 'bf16', cfg, seed,
                                state['params'])
    # The int8 K-mirror form of the kernel: a fresh model, no training.
    q_model = lm(cfg, qk_quant='int8')
    q_params = q_model.init(
        jax.random.key(seed + 3),
        jax.numpy.zeros((1, 16), 'int32'))
    q_rec, q_checks = generate_once(progs, 'int8', cfg, seed, q_params,
                                    qk_quant='int8')
    return {'prompt': cfg['prompt'], 'new_tokens': cfg['new_tokens'],
            't_max': cfg['gen_t_max'], **rec, **q_rec,
            'checks': {**checks, **q_checks}}


def phase_generate_latent(progs, cfg, seed):
    """The same generate checks on the latent-attention model: its
    prefill runs the expanded form through the flash forward kernel,
    its step the absorbed form through ``flash_decode``'s latent mode
    (``mla_decode``), against the XLA formulation of the same step."""
    import jax
    model = latent_lm(cfg)
    params = {'params': model.init(
        jax.random.key(seed + 5),
        jax.numpy.zeros((1, 16), 'int32'))['params']}
    rec, checks = generate_once(progs, 'latent', cfg, seed, params,
                                lm=latent_lm)
    return {**rec, 'checks': checks}


def phase_generate_mixed(progs, cfg, seed):
    """The same generate checks on a stack of window and full attention
    layers: the prompt is prefilled through ring and slab caches, and
    the step runs BOTH modes of ``flash_decode`` (``flash_decode_ring``
    on the window layers' rings, which the first served token wraps)
    against the XLA formulation of the same step."""
    import jax
    model = mixed_lm(cfg)
    params = {'params': model.init(
        jax.random.key(seed + 6),
        jax.numpy.zeros((1, 16), 'int32'))['params']}
    rec, checks = generate_once(progs, 'mixed', cfg, seed, params,
                                lm=mixed_lm)
    checks['mixed.both_modes_resolved_kernel'] = (
        rec['mixed_resolved_impl'] == ['kernel']
        and rec['mixed_caches'] == ['layer', 'ring'])
    return {**rec, 'checks': checks}


def hybrid_lm(cfg, **attn_kwargs):
    """One latent-expert layer, one Mamba-2 layer and one attention
    layer (``cfg['hybrid']``), each ``x + f(RMSNorm(x))``: no cache, a
    ``StateCache`` and a slab side by side. Every expert is gated, as in
    ``latent_lm``."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['hybrid']
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=3, dtype=jnp.bfloat16, scan_layers=False,
        tie_embeddings=False,
        attn_kwargs={'num_kv_heads': c['kv_heads'], 'use_rope': False,
                     **attn_kwargs},
        block_kwargs={'norm': 'rmsnorm'},
        layer_kinds={
            'E': {'mixer': 'none', 'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['experts'], 'top_k': c['experts'],
                'hidden': c['expert_hidden'], 'latent': c['latent'],
                'shared_hidden': c['shared'], 'expert_form': 'plain',
                'activation': 'relu2'}},
            'M': {'mixer': 'ssm', 'ffn': 'none', 'ssm_kwargs': {
                'heads': c['ssm_heads'], 'head_dim': c['ssm_head_dim'],
                'state': c['state'], 'groups': c['groups']}},
            '*': {'mixer': 'attention', 'ffn': 'none'}},
        layer_pattern=('E', 'M', '*'))


def phase_generate_hybrid(progs, cfg, seed):
    """The state path end to end: a prompt prefilled through the chunked
    scan into a ``StateCache`` beside a slab, the state SNAPSHOTTED at
    the prompt's end, a greedy request, the state restored and the
    slab's length set back, and the request again — which must read
    what the first did, bit for bit — with the attention layer's step
    on the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces, restore_states, snapshot_states,
    )
    model = hybrid_lm(cfg)
    n, steps, t_max = cfg['prompt'], cfg['new_tokens'], cfg['gen_t_max']
    params = {'params': model.init(
        jax.random.key(seed + 7), jnp.zeros((1, 16), 'int32'))['params']}
    prompt = jax.random.randint(jax.random.key(seed + 2), (1, n), 0,
                                cfg['vocab'], dtype='int32')
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'),
                   donate_argnums=(2,))

    def reset(caches, taken):
        return [c._replace(length=jnp.asarray(n, jnp.int32))
                if hasattr(c, 'length') else c
                for c in restore_states(caches, taken)]
    reset = jax.jit(reset, donate_argnums=(0,))
    caches = model.make_decode_caches(1, t_max)
    kinds = [type(c).__name__ for c in caches]
    with decode_impl_traces() as traces:
        progs.compile('hybrid.prefill', prefill, params, prompt, caches,
                      pallas=True)
        progs.compile('hybrid.decode', step, params, prompt[:, :1],
                      caches, pallas=True)
    caches, logits = prefill(params, prompt, caches)
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    taken = snapshot_states(caches)

    def request(caches):
        tok, out = first, []
        for _ in range(steps):
            caches, logits = step(params, tok, caches)
            out.append(np.asarray(logits[:, -1], np.float32))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return caches, np.stack(out)
    caches, once = request(caches)
    moved = float(np.max(np.abs(
        np.asarray(caches[1].state) - np.asarray(taken[1].state))))
    caches, again = request(reset(caches, taken))
    resolved = sorted({t['resolved'] for t in traces})
    return {
        'hybrid_caches': kinds,
        'hybrid_resolved_impl': resolved,
        'hybrid_state_moved_by_a_request': moved,
        'hybrid_logits_max_abs': float(np.max(np.abs(once))),
        'checks': {
            'hybrid.cache_kinds': kinds == ['NoneType', 'StateCache',
                                            'DecodeCache'],
            'hybrid.resolved_kernel': resolved == ['kernel'],
            'hybrid.logits_finite': bool(np.all(np.isfinite(once))),
            'hybrid.a_request_moves_the_state': moved > 0,
            'hybrid.restored_request_agrees': bool(
                np.array_equal(once, again)),
        }}


def sparse_lm(cfg, **attn_kwargs):
    """One learned block-sparse NoPE attention layer (``qk_norm``, an
    output gate) and one Lightning linear-attention layer
    (``cfg['sparse']``), each followed by a gated MLP: a ``SparseCache``
    (slab and pooled keys) beside a ``StateCache`` with no window."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['sparse']
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=2, dtype=jnp.bfloat16, scan_layers=False,
        tie_embeddings=False,
        attn_kwargs={'num_kv_heads': c['kv_heads'], 'use_rope': False,
                     'qk_norm': True, 'out_gate': True,
                     'sparse': c['select'], **attn_kwargs},
        block_kwargs={'norm': 'rmsnorm', 'ffn': 'gated',
                      'ffn_kwargs': {'hidden': c['hidden']}},
        layer_kinds={
            'S': {'mixer': 'attention'},
            'L': {'mixer': 'lightning', 'ssm_kwargs': {
                'heads': c['heads'], 'head_dim': c['dim'] // c['heads']}}},
        layer_pattern=('S', 'L'))


def phase_generate_sparse(progs, cfg, seed):
    """The picked-rows path end to end: a prompt prefilled under its
    picks' block mask into a ``SparseCache`` beside a Lightning state,
    the state SNAPSHOTTED at the prompt's end, a greedy request, the
    state restored and the slab's length set back (the pooled keys
    rewind with it), and the request again — which must read what the
    first did, bit for bit — with the sparse layer's step on the kernel
    ``sparse_decode`` (``sparse_decode_traces()``) and its pick list the
    threshold's (``sparse_pick``). The picks themselves are made twice
    from one set of block scores — seeded queries, a row a served step,
    against the served session's own pooled keys — as the layer makes
    them on this backend (``pick_blocks``) and as ``lax.top_k`` and a
    sort do: entry for entry the same."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_dot_product_tpu.models.decode import (
        restore_states, snapshot_states, sparse_decode_traces,
    )
    from distributed_dot_product_tpu.models.sparse import (
        SparseSpec, block_scores, pick_blocks,
    )
    from distributed_dot_product_tpu.ops.pallas_sparse import sorted_picks
    model = sparse_lm(cfg)
    n, steps, t_max = cfg['prompt'], cfg['new_tokens'], cfg['gen_t_max']
    params = {'params': model.init(
        jax.random.key(seed + 9), jnp.zeros((1, 16), 'int32'))['params']}
    prompt = jax.random.randint(jax.random.key(seed + 2), (1, n), 0,
                                cfg['vocab'], dtype='int32')
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))
    step = jax.jit(lambda p, t, c: model.apply(
        p, t, c, method='decode', mutable=['counters']),
        donate_argnums=(2,))

    def reset(caches, taken):
        return [c._replace(length=jnp.asarray(n, jnp.int32))
                if hasattr(c, 'length') else c
                for c in restore_states(caches, taken)]
    reset = jax.jit(reset, donate_argnums=(0,))
    caches = model.make_decode_caches(1, t_max)
    kinds = [type(c).__name__ for c in caches]
    with sparse_decode_traces() as forms:
        progs.compile('sparse.prefill', prefill, params, prompt, caches,
                      pallas=True)
        progs.compile('sparse.decode', step, params, prompt[:, :1],
                      caches, pallas=True)
    caches, logits = prefill(params, prompt, caches)
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    taken = snapshot_states(caches)
    topk = cfg['sparse']['select']['topk']

    def request(caches):
        tok, out, counts = first, [], []
        for _ in range(steps):
            (caches, logits), sown = step(params, tok, caches)
            out.append(np.asarray(logits[:, -1], np.float32))
            counts.append(int(sown['counters']['stack']['block_0']['attn'][
                'sparse_count']))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return caches, np.stack(out), counts
    caches, once, counts = request(caches)
    moved = float(np.max(np.abs(
        np.asarray(caches[1].state) - np.asarray(taken[1].state))))
    caches, again, _ = request(reset(caches, taken))
    spec = SparseSpec(**cfg['sparse']['select'])
    c = cfg['sparse']
    d = c['dim'] // c['heads']

    @jax.jit
    def both(pooled):
        keys = n + 1 + jnp.arange(steps)
        scores = block_scores(
            jax.random.normal(jax.random.key(seed + 3),
                              (1, c['heads'], steps, d), jnp.bfloat16),
            pooled, keys, spec, d ** -0.5, t_max // spec.block)
        return (pick_blocks(scores, keys, spec)[0][..., :topk],
                sorted_picks(scores, topk))
    progs.compile('sparse.picks', both, caches[0].pooled, pallas=True)
    routed, by_sort = (np.asarray(x) for x in both(caches[0].pooled))
    return {
        'sparse_caches': kinds,
        'sparse_decode': forms,
        'sparse_picks_a_step': counts,
        'sparse_state_moved_by_a_request': moved,
        'sparse_logits_max_abs': float(np.max(np.abs(once))),
        'checks': {
            'sparse.cache_kinds': kinds == ['SparseCache', 'StateCache'],
            'sparse.step_is_the_kernel': [
                (f['impl'], f['topk'], f['select']) for f in forms] == [
                    ('kernel', topk, 'threshold')],
            'sparse.picks_are_the_sorted_route_s': bool(
                routed.shape == (1, c['kv_heads'], steps, topk)
                and np.array_equal(routed, by_sort)),
            'sparse.every_step_picks_topk': counts == steps * [topk],
            'sparse.logits_finite': bool(np.all(np.isfinite(once))),
            'sparse.a_request_moves_the_state': moved > 0,
            'sparse.restored_request_agrees': bool(
                np.array_equal(once, again)),
        }}


def conv_lm(cfg, **attn_kwargs):
    """A gated short-convolution layer under a dense gated MLP, one
    under experts, and a GQA layer at 64-wide heads with per-head q / k
    norms on the packed slab under experts (``cfg['conv']``)."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['conv']
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=3, dtype=jnp.bfloat16, scan_layers=False,
        attn_kwargs={'num_kv_heads': c['kv_heads'], 'qk_norm': True,
                     'kv_packed': True, **attn_kwargs},
        block_kwargs={'norm': 'rmsnorm', 'ssm_kwargs': {'taps': 3},
                      'ffn': 'experts', 'ffn_kwargs': {
                          'n_experts': c['experts'], 'top_k': c['top_k'],
                          'hidden': c['expert_hidden'], 'n_shared': 0}},
        layer_kinds={
            'D': {'mixer': 'conv', 'ffn': 'gated',
                  'ffn_kwargs': {'hidden': c['hidden']}},
            'C': {'mixer': 'conv'}, 'A': {'mixer': 'attention'}},
        layer_pattern=('D', 'C', 'A'))


def phase_generate_conv(progs, cfg, seed):
    """The window-only recurrent layer and the packed slab end to end:
    a prompt prefilled, the windows SNAPSHOTTED at its end, a greedy
    request, the windows restored and the slab's length set back, and
    the request again — which must read what the first did, bit for bit
    — with the attention layer's step on the kernel over the PACKED
    cache (``decode_impl_traces()``: its form, bytes a token and the
    KV heads its body scores a pass) and the
    conv mixers' form (``conv_step_traces()``) and the expert calls'
    routes printed beside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces, restore_states, snapshot_states,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    from distributed_dot_product_tpu.models.shortconv import (
        conv_step_traces,
    )
    model = conv_lm(cfg)
    n, steps, t_max = cfg['prompt'], cfg['new_tokens'], cfg['gen_t_max']
    params = {'params': model.init(
        jax.random.key(seed + 11), jnp.zeros((1, 16), 'int32'))['params']}
    prompt = jax.random.randint(jax.random.key(seed + 2), (1, n), 0,
                                cfg['vocab'], dtype='int32')
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))
    step = jax.jit(lambda p, t, c: model.apply(p, t, c, method='decode'),
                   donate_argnums=(2,))

    def reset(caches, taken):
        return [c._replace(length=jnp.asarray(n, jnp.int32))
                if hasattr(c, 'length') else c
                for c in restore_states(caches, taken)]
    reset = jax.jit(reset, donate_argnums=(0,))
    caches = model.make_decode_caches(1, t_max)
    kinds = [type(c).__name__ for c in caches]
    progs.compile('conv.prefill', prefill, params, prompt, caches,
                  pallas=True)
    with decode_impl_traces() as traces, conv_step_traces() as forms, \
            expert_route_traces() as routes:
        progs.compile('conv.decode', step, params, prompt[:, :1], caches,
                      pallas=True)
    caches, logits = prefill(params, prompt, caches)
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    taken = snapshot_states(caches)

    def request(caches):
        tok, out = first, []
        for _ in range(steps):
            caches, logits = step(params, tok, caches)
            out.append(np.asarray(logits[:, -1], np.float32))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return caches, np.stack(out)
    caches, once = request(caches)
    moved = float(np.max(np.abs(
        np.asarray(caches[0].conv, np.float32)
        - np.asarray(taken[0].conv, np.float32))))
    caches, again = request(reset(caches, taken))
    slab = [{**{k: t[k] for k in ('resolved', 'cache', 'token_bytes',
                                  'tail')},
             'heads_a_pass': (t['step'] or {}).get('heads_a_pass')}
            for t in traces]
    # The slab's kernel step against its XLA step on the same operands,
    # at this layer's heads: what the chip computes, checked on the chip
    # (PR 52: a piece that read right under the interpreter and on
    # XLA:CPU read wrong on XLA:TPU, in one head of every pair).
    from distributed_dot_product_tpu.models.decode import (
        PackedCache, decode_step,
    )
    c = cfg['conv']
    ks = jax.random.split(jax.random.key(seed + 5), 4)
    shape = (2, c['kv_heads'], 1, c['dim'] // c['heads'])
    slab_cache = PackedCache(
        kv=jax.random.normal(ks[0], (2, c['kv_heads'], t_max,
                                     2 * shape[-1]), jnp.bfloat16),
        length=jnp.asarray(n, jnp.int32))
    q = jax.random.normal(ks[1], (2, c['heads'], 1, shape[-1]),
                          jnp.bfloat16)
    kn, vn = (jax.random.normal(k, shape, jnp.bfloat16) for k in ks[2:])
    slab_err = float(np.max(np.abs(
        np.asarray(decode_step(q, slab_cache, kn, vn, impl='kernel')[1],
                   np.float32)
        - np.asarray(decode_step(q, slab_cache, kn, vn, impl='xla')[1],
                     np.float32))))
    return {
        'conv_caches': kinds,
        'conv_slab_step': slab,
        'conv_slab_kernel_vs_xla': slab_err,
        'conv_step_forms': forms,
        'conv_expert_routes': routes,
        'conv_window_moved_by_a_request': moved,
        'conv_logits_max_abs': float(np.max(np.abs(once))),
        'checks': {
            'conv.cache_kinds': kinds == ['StateCache', 'StateCache',
                                          'PackedCache'],
            # … its KV heads scored two a pass where they pair up
            'conv.slab_resolved_kernel': [
                (t['resolved'], t['cache'], t['token_bytes'],
                 t['heads_a_pass']) for t in slab] == [
                     ('kernel', 'packed', 256,
                      1 if cfg['conv']['kv_heads'] % 2 else 2)],
            'conv.slab_kernel_is_its_xla_step': slab_err < 8e-3,
            'conv.steps_are_the_shift': forms == 2 * [
                {'form': 'shift', 'taps': 3,
                 'channels': cfg['conv']['dim']}],
            'conv.expert_calls_by_the_rule': [
                (r['route'], r['bound_by']) for r in routes] == 2 * [
                    ('hit_list', 'rule')],
            'conv.logits_finite': bool(np.all(np.isfinite(once))),
            'conv.a_request_moves_the_window': moved > 0,
            'conv.restored_request_agrees': bool(
                np.array_equal(once, again)),
        }}


# -- one chip: serve -----------------------------------------------------

def ling_lm(cfg, **attn_kwargs):
    """One delta-rule layer (full-rank gates, the bounded decay) and one
    latent layer (no query rank, a head-wise output gate), each followed
    by experts under group-limited routing of which ONE GROUP is held
    (``cfg['ling']``): a ``StateCache`` and one layer's ``LatentCache``
    side by side."""
    import jax.numpy as jnp

    from distributed_dot_product_tpu import TransformerLM
    c = cfg['ling']
    return TransformerLM(
        vocab_size=cfg['vocab'], dim=c['dim'], num_heads=c['heads'],
        n_layers=2, dtype=jnp.bfloat16, scan_layers=False,
        tie_embeddings=False,
        block_kwargs={
            'norm': 'rmsnorm', 'mixer': 'delta', 'ssm_kwargs': {
                'heads': c['heads'], 'head_dim': c['head_dim'],
                'beta_scale': 1.0, 'gate_rank': None, 'decay': 'bounded'},
            'ffn': 'experts', 'ffn_kwargs': {
                'n_experts': c['experts'], 'top_k': c['top_k'],
                'hidden': c['expert_hidden'], 'scaling': 2.5,
                'n_group': c['groups'], 'topk_group': c['kept'],
                'experts_held': c['held']}},
        layer_kinds={
            'K': {},
            'A': {'mixer': 'latent', 'attn_kwargs': {
                'q_rank': None, 'kv_rank': c['kv_rank'],
                'nope_dim': c['nope'], 'rope_dim': c['rope'],
                'v_dim': c['v'], 'rope_theta': 6e6, 'out_gate': 'head',
                **attn_kwargs}}},
        layer_pattern=('K', 'A'))


def phase_generate_ling(progs, cfg, seed):
    """A latent cache beside a recurrent state, end to end: a prompt
    prefilled through the chunked delta rule and the expanded latent
    form, the state SNAPSHOTTED, a greedy request, the state restored
    and the latent LENGTHS set back, and the request again — which must
    read what the first did, bit for bit. Prints the step's forms: the
    latent layer's (``mla_decode`` on its own buffer), the delta
    mixer's, each expert layer's route and how it made its choices, and
    the rows a step routed to the held group. Then ONE seeded ``(96,
    512)`` grouped call both ways: the hit list's choices (on the chip
    by compares and ``sparse_pick``, no sort) against the sorted
    route's (``lax.top_k`` everywhere) — the same picks as sets, the
    same counts, the same rows for the held group."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces, restore_states, snapshot_states,
    )
    from distributed_dot_product_tpu.models.delta import delta_step_traces
    from distributed_dot_product_tpu.models.moe import (
        SparseExperts, expert_route_traces,
    )
    model = ling_lm(cfg)
    n, steps, t_max = cfg['prompt'], cfg['new_tokens'], cfg['gen_t_max']
    params = {'params': model.init(
        jax.random.key(seed + 9), jnp.zeros((1, 16), 'int32'))['params']}
    prompt = jax.random.randint(jax.random.key(seed + 2), (1, n), 0,
                                cfg['vocab'], dtype='int32')
    prefill = jax.jit(lambda p, t, c: model.apply(p, t, c,
                                                  method='prefill'))

    def step_fn(p, t, c):
        (c, logits), sown = model.apply(p, t, c, method='decode',
                                        mutable=['counters'])
        stack = sown['counters']['stack']
        return c, logits, jnp.stack([
            stack[f'block_{i}']['moe']['group_rows'] for i in range(2)])
    step = jax.jit(step_fn, donate_argnums=(2,))

    def reset(caches, taken):
        return [c._replace(length=jnp.full_like(c.length, n))
                if hasattr(c, 'length') else c
                for c in restore_states(caches, taken)]
    reset = jax.jit(reset, donate_argnums=(0,))
    caches = model.make_decode_caches(1, t_max)
    kinds = [type(c).__name__ for c in caches]
    with decode_impl_traces() as traces, delta_step_traces() as forms, \
            expert_route_traces() as routes:
        progs.compile('ling.prefill', prefill, params, prompt, caches,
                      pallas=True)
        routes.clear()                  # the step's alone
        progs.compile('ling.decode', step, params, prompt[:, :1], caches,
                      pallas=True)
    caches, logits = prefill(params, prompt, caches)
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    taken = snapshot_states(caches)

    def request(caches):
        tok, out, rows = first, [], []
        for _ in range(steps):
            caches, logits, group_rows = step(params, tok, caches)
            out.append(np.asarray(logits[:, -1], np.float32))
            rows.append(np.asarray(group_rows))
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return caches, np.stack(out), np.stack(rows)
    caches, once, rows = request(caches)
    moved = float(np.max(np.abs(
        np.asarray(caches[0].state) - np.asarray(taken[0].state))))
    grown = np.asarray(caches[1].length).tolist()
    caches, again, _ = request(reset(caches, taken))
    resolved = sorted({f"{t['resolved']}:{t['cache']}" for t in traces})

    grouped = dict(n_experts=512, top_k=8, hidden=128, scaling=2.5,
                   n_group=8, topk_group=4, experts_held=(0, 64))
    x = jax.random.normal(jax.random.key(seed + 4), (96, 128), jnp.float32)
    router = SparseExperts(**grouped).init(jax.random.key(seed + 5), x)
    router['params']['router_bias'] = 0.05 * jax.random.normal(
        jax.random.key(seed + 6), (512,), jnp.float32)

    @jax.jit
    def both(p, x):
        out = []
        for dense_tokens in (None, 0):      # the hit list, the sorted route
            (_, counts), sown = SparseExperts(
                **grouped, dense_tokens=dense_tokens).apply(
                    p, x, mutable=['counters'])
            out.append((jnp.sort(sown['counters']['expert_picks'], -1),
                        counts, sown['counters']['group_rows']))
        return out
    with expert_route_traces() as selects:
        progs.compile('ling.picks', both, router, x, pallas=True)
    by_threshold, by_sort = jax.tree.map(np.asarray, both(router, x))
    return {
        'ling_caches': kinds,
        'ling_mla_decode': resolved,
        'ling_delta_step': forms,
        'ling_expert_routes': routes,
        'ling_group_rows': rows.tolist(),
        'ling_state_moved_by_a_request': moved,
        'ling_logits_max_abs': float(np.max(np.abs(once))),
        'checks': {
            'ling.cache_kinds': kinds == ['StateCache', 'LatentCache'],
            'ling.latent_resolved_kernel': resolved == ['kernel:latent'],
            'ling.delta_step_is_the_kernel': [f['form'] for f in forms] == [
                'pallas'],
            'ling.experts_on_the_hit_list': [
                (r['route'], r['bound_by']) for r in routes] == 2 * [
                    ('hit_list', 'rule')],
            # the step's two layers and the seeded call's hit-list half
            # choose by compares and ``sparse_pick``; its sorted half
            # by ``lax.top_k``, as everywhere
            'ling.select_step_is_the_kernel': [
                (r['route'], r['select']) for r in routes + selects] == (
                    3 * [('hit_list', 'threshold')] + [('sorted', 'sort')]),
            'ling.picks_are_top_k_s': bool(
                by_threshold[0].shape == (96, 8)
                and all(np.array_equal(a, b)
                        for a, b in zip(by_threshold, by_sort))),
            # one session: a step routes its row to the held group or not
            'ling.group_rows_counted': bool(
                np.all((rows == 0) | (rows == 1))),
            'ling.latent_rows_grew': grown == [n + steps],
            'ling.logits_finite': bool(np.all(np.isfinite(once))),
            'ling.a_request_moves_the_state': moved > 0,
            'ling.restored_request_agrees': bool(
                np.array_equal(once, again)),
        }}


def engine(cfg, seed, **kw):
    import jax.numpy as jnp

    from distributed_dot_product_tpu.serve import KernelEngine
    return KernelEngine(
        slots=cfg['slots'], t_max=cfg['serve_t_max'], vocab=cfg['vocab'],
        heads=cfg['heads'], head_dim=cfg['dim'] // cfg['heads'],
        dtype=jnp.bfloat16, cache_mode='paged', page_size=cfg['page'],
        prefill_chunk=cfg['chunk'], seed=seed, **kw)


def prefill_slot(eng, slot, prompt):
    for i in range(0, len(prompt), eng.prefill_chunk):
        eng.prefill(slot, prompt[i:i + eng.prefill_chunk])


def compile_engine_decode(progs, name, eng, **kw):
    import jax.numpy as jnp
    s = eng.slots
    return progs.compile(
        name, eng._decode, eng.cache, jnp.zeros(s, jnp.int32),
        jnp.ones(s, bool), jnp.zeros(s, bool), **kw)


def phase_serve(progs, cfg, seed, out_dir):
    import numpy as np

    from distributed_dot_product_tpu import obs
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.obs import critpath
    from distributed_dot_product_tpu.serve import ServeConfig
    from distributed_dot_product_tpu.serve.loadgen import (
        LoadGenConfig, TenantSpec, generate_trace, run_load,
    )
    load = LoadGenConfig(
        seed=seed, rate=200.0, requests=cfg['requests'],
        vocab=cfg['vocab'],
        tenants=[TenantSpec('t0', prompt_lo=cfg['prompt_lo'],
                            prompt_hi=cfg['prompt_hi'],
                            new_lo=cfg['serve_new'],
                            new_hi=cfg['serve_new'])])
    trace = generate_trace(load)

    eng = engine(cfg, seed)              # decode_impl left at default
    with decode_impl_traces() as traces:
        compile_engine_decode(progs, 'engine.decode', eng, pallas=True)
    resolved = sorted({t['resolved'] for t in traces})

    # First decode step after the first request's prompt: logits of
    # the default engine against an XLA-step twin, same seed.
    twin = engine(cfg, seed, decode_impl='xla')
    first = trace[0].prompt
    active = np.arange(cfg['slots']) == 0
    tokens = np.where(active, first[-1], 0)
    for e in (eng, twin):
        prefill_slot(e, 0, first[:-1])
    ours = eng.peek_logits(tokens, active)[0]
    theirs = twin.peek_logits(tokens, active)[0]
    err, scale = max_err(ours, theirs)
    eng.reset(0)
    del twin

    path = os.path.join(out_dir, 'serve_events.jsonl')
    if os.path.exists(path):
        os.remove(path)
    log = obs.EventLog(path)
    before = eng.program_seconds
    res = run_load(load, engine=eng, event_log=log,
                   serve_config=ServeConfig(
                       queue_limit=32, max_new_tokens=cfg['serve_new'],
                       watchdog=False))
    log.close()
    device_seconds = eng.program_seconds - before
    _, errors = obs.validate_file(path)
    timelines = obs.reconstruct(path)
    dispatch = critpath.dispatch_floor(path)['per_replica']
    emit({'info': 'serve.dispatch',
          'engine_program_seconds': device_seconds,
          'run_wall_seconds': res.wall_seconds, 'ticks': res.ticks,
          'per_replica': dispatch})

    ids = [rid for rid, _ in res.submitted]
    results = [res.results.get(rid) for rid in ids]
    return {
        'requests': len(ids), 'resolved_impl': resolved,
        'prompt_lens': [len(a.prompt) for a in trace],
        'tokens_generated': sum(len(r.tokens) for r in results if r),
        'first_step_logits_max_abs_err': err,
        'first_step_logits_max_abs': scale,
        'event_log': path, 'schema_errors': errors[:5],
        'checks': {
            'resolved_kernel': resolved == ['kernel'],
            'all_submitted': len(ids) == cfg['requests']
                             and not res.rejected_at_submit,
            'all_completed': all(r is not None
                                 and r.status == 'completed'
                                 for r in results),
            'all_full_length': all(
                r is not None and not r.degraded
                and len(r.tokens) == cfg['serve_new'] for r in results),
            'event_log_schema_clean': not errors,
            'all_timelines_complete': all(
                rid in timelines and timelines[rid].complete
                for rid in ids),
            'first_step_logits_finite': bool(np.all(np.isfinite(ours))),
            'first_step_logits_match_xla': within_bf16(err, scale),
        }}


# -- four chips ----------------------------------------------------------

def on_all(x, n):
    return len(x.sharding.device_set) == n


def phase_matmuls(progs, cfg, seed, mesh):
    """The three primitives on the reference workload, each against the
    unsharded product on sampled row blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.ops.functions import (
        distributed_matmul_all_global, distributed_matmul_nt_global,
        distributed_matmul_tn_global,
    )
    from distributed_dot_product_tpu.utils.comm import SEQ_AXIS

    n = mesh.devices.size
    t, d, offset = cfg['mm_t'], cfg['dim'], cfg['mm_offset']
    t -= t % n
    rows_n = min(256, t // n)

    def normal(key, shape):
        # Each shard draws its own rows on its own chip: a (T, T)
        # operand never exists whole on one device.
        def local(k):
            k = jax.random.fold_in(k, lax.axis_index(SEQ_AXIS))
            return jax.random.normal(k, (shape[0] // n, shape[1]),
                                     jnp.bfloat16)
        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=P(), out_specs=P(SEQ_AXIS, None),
            check_vma=False))(key)

    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    small_l, right = normal(k1, (t, d)), normal(k2, (t, d))
    square = normal(k3, (t, t))
    def shards(x):
        return sorted(x.addressable_shards,
                      key=lambda sh: sh.index[0].start or 0)

    def rows(x, s):
        """Rows ``s`` (inside ONE shard) of a row-sharded array, f32 on
        the host: a plain slice of that shard on its own chip. Indexing
        the global array instead compiles a gather over all of it — at
        (75000, 75000) on the four-chip v5e that compile alone outlasted
        a 30-minute call (PR 21)."""
        sh = next(sh for sh in shards(x)
                  if sh.index[0].start <= s.start
                  and s.stop <= sh.index[0].stop)
        lo = s.start - sh.index[0].start
        return np.asarray(lax.slice_in_dim(sh.data, lo, lo + rows_n),
                          np.float32)

    def cols(x, s):
        """Columns ``s`` of every shard, stacked in row order."""
        return np.concatenate([
            np.asarray(lax.slice_in_dim(sh.data, s.start, s.stop, axis=1),
                       np.float32) for sh in shards(x)])

    # name: (program, left operand, expected collective, rows [s] of
    # the unsharded product from host f32 copies of the operands)
    ops = {
        'nt': (lambda l, r: distributed_matmul_nt_global(
            l, r, offset=offset, mesh=mesh), small_l, 'all-gather',
            lambda l, r, s: rows(l, s) @ r.T),
        'all': (lambda l, r: distributed_matmul_all_global(
            l, r, offset=offset, mesh=mesh), square, 'all-gather',
            lambda l, r, s: rows(l, s) @ r),
        'tn': (lambda l, r: distributed_matmul_tn_global(
            l, r, mesh=mesh), square, 'reduce-scatter',
            lambda l, r, s: cols(l, s).T @ r),
    }
    right_host = np.concatenate([np.asarray(sh.data, np.float32)
                                 for sh in shards(right)])
    per = t // n
    rec, checks = {}, {}
    for name, (fn, left, collective, reference) in ops.items():
        compiled = progs.compile(name, jax.jit(fn), left, right,
                                 pallas=False, collectives=(collective,))
        out = compiled(left, right)
        errs = []
        # One block in the first, a middle and the last shard.
        for shard in sorted({0, n // 2, n - 1}):
            start = shard * per + (per - rows_n) // 2
            s = slice(start, start + rows_n)
            want = reference(left, right_host, s)
            err, scale = max_err(rows(out, s), want)
            errs.append((err, scale))
            checks[f'{name}.rows_{start}_match'] = within_bf16(err,
                                                               scale)
        rec[f'{name}_max_abs_err'] = max(e for e, _ in errs)
        rec[f'{name}_max_abs'] = max(s for _, s in errs)
        checks[f'{name}.output_on_{n}_devices'] = on_all(out, n)
        checks[f'{name}.inputs_on_{n}_devices'] = (on_all(left, n)
                                                   and on_all(right, n))
        del out, compiled
    return {'T': t, 'd': d, 'offset': offset, **rec, 'checks': checks}


def phase_lm_sharded(progs, cfg, seed, mesh):
    """One LM train step with the sequence split over the mesh — ring
    ('online') and all-gather ('flash') attention — each loss against
    the same step on a one-device sub-mesh of the same process."""
    import jax
    import numpy as np
    import optax

    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_lm_train_step

    n = mesh.devices.size
    one = seq_mesh(1)
    t = cfg['lm4_t']
    rec, checks = {'T': t}, {}
    for impl, collective in (('online', 'collective-permute'),
                             ('flash', 'all-gather')):
        model = lm(cfg, softmax_impl=impl)
        losses = {}
        for tag, m in ((f'{n}chip', mesh), ('1chip', one)):
            batch, tokens = seeded_batch(cfg, seed, t, m)
            params = model.init(jax.random.key(seed + 1),
                                tokens[:, :16 * n])
            optimizer = optax.adam(1e-3)
            opt_state = optimizer.init(params)
            step = make_lm_train_step(model, optimizer, m, donate=False)
            many = m is mesh
            compiled = progs.compile(
                f'{impl}.{tag}', step, params, opt_state, batch,
                pallas=True,
                collectives=(collective, 'all-reduce') if many else ())
            new_params, _, loss = compiled(params, opt_state, batch)
            losses[tag] = float(loss)
            if many:
                leaf = jax.tree.leaves(new_params)[0]
                checks[f'{impl}.tokens_on_{n}_devices'] = on_all(
                    batch[0], n)
                checks[f'{impl}.params_on_{n}_devices'] = on_all(leaf, n)
            del new_params, compiled
        rec[f'{impl}_losses'] = losses
        checks[f'{impl}.losses_finite'] = bool(
            np.all(np.isfinite(list(losses.values()))))
        checks[f'{impl}.loss_matches_one_device'] = (
            abs(losses[f'{n}chip'] - losses['1chip']) <= LOSS_ATOL)
    return {**rec, 'checks': checks}


def phase_kv_sharded(progs, cfg, seed, mesh):
    """One stream whose context spans every shard of a kv_shards
    engine: next-step logits and a few greedy tokens against the
    single-pool engine."""
    import numpy as np

    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    n = mesh.devices.size
    ctx = cfg['shard_ctx']
    prompt = np.random.default_rng(seed).integers(
        0, cfg['vocab'], size=ctx).astype(np.int32)
    active = np.arange(cfg['slots']) == 0
    tokens = np.where(active, prompt[-1], 0)
    sharded = engine(cfg, seed, kv_shards=n)
    single = engine(cfg, seed)
    with decode_impl_traces() as traces:
        compile_engine_decode(progs, f'engine.decode.kv_shards_{n}',
                              sharded, pallas=True,
                              collectives=('all-reduce',))
    resolved = sorted({t['resolved'] for t in traces})
    for e in (sharded, single):
        prefill_slot(e, 0, prompt[:-1])
    per_shard = [p.used_pages for p in sharded.pool.shards]
    ours = sharded.peek_logits(tokens, active)[0]
    theirs = single.peek_logits(tokens, active)[0]
    err, scale = max_err(ours, theirs)
    streams = []
    for e in (sharded, single):
        tok, out = tokens, []
        for _ in range(4):
            tok, finite = e.step(tok, active)
            out.append((int(tok[0]), bool(finite[0])))
        streams.append(out)
    return {
        'context': ctx, 'kv_shards': n, 'resolved_impl': resolved,
        'pages_used_by_shard': per_shard,
        'logits_max_abs_err': err, 'logits_max_abs': scale,
        'streams': streams,
        'checks': {
            'resolved_kernel': resolved == ['kernel'],
            f'pool_on_{n}_devices': on_all(sharded.cache.k_pool, n),
            'context_spans_every_shard': all(p > 0 for p in per_shard),
            'logits_finite': bool(np.all(np.isfinite(ours))),
            'logits_match_single_pool': within_bf16(err, scale),
            'steps_finite': all(f for _, f in streams[0]),
        }}


# -- driver --------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1,
                    help='4: run ONLY the cross-chip phases')
    ap.add_argument('--tiny', action='store_true',
                    help='CPU rehearsal sizes; relaxes no check')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.utils.compile_cache import (
        setup_compile_cache,
    )
    cache_dir = setup_compile_cache()
    # Every program, small ones too: a second run in the same machine
    # then compiles nothing.
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)

    devices = jax.devices()
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    on_chip = device['platform'] == 'tpu'
    if not on_chip and not args.tiny:
        print(f'chip_smoke: JAX found no TPU (platform '
              f'{device["platform"]!r}); nothing was run and no result '
              f'is printed. `--tiny` rehearses the control flow on the '
              f'CPU (and still fails).', file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f'chip_smoke: --chips {args.chips} needs {args.chips} '
              f'devices, JAX found {len(devices)}; nothing was run.',
              file=sys.stderr)
        return 2

    cfg = TINY if args.tiny else FULL
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'chiprun_out', 'chip_smoke')
    os.makedirs(out_dir, exist_ok=True)
    emit({'info': 'start', 'device': device, 'chips': args.chips,
          'sizes': 'tiny' if args.tiny else 'full', 'seed': args.seed,
          'jax': jax.__version__, 'compile_cache': cache_dir})

    if args.chips == 1:
        sync_probe(args.tiny)
        state = {}
        oks = [run_phase('train', phase_train, cfg, args.seed, state),
               run_phase('generate', phase_generate, cfg, args.seed,
                         state),
               run_phase('generate_latent', phase_generate_latent, cfg,
                         args.seed),
               run_phase('generate_mixed', phase_generate_mixed, cfg,
                         args.seed),
               run_phase('generate_hybrid', phase_generate_hybrid, cfg,
                         args.seed),
               run_phase('generate_sparse', phase_generate_sparse, cfg,
                         args.seed),
               run_phase('generate_ling', phase_generate_ling, cfg,
                         args.seed),
               run_phase('generate_conv', phase_generate_conv, cfg,
                         args.seed),
               run_phase('serve', phase_serve, cfg, args.seed, out_dir)]
    else:
        mesh = seq_mesh(args.chips)
        oks = [run_phase('matmuls', phase_matmuls, cfg, args.seed, mesh),
               run_phase('lm_sharded', phase_lm_sharded, cfg, args.seed,
                         mesh),
               run_phase('kv_sharded', phase_kv_sharded, cfg, args.seed,
                         mesh)]
    ok = on_chip and all(oks)
    print(json.dumps({'ok': ok, 'device': device}), flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
