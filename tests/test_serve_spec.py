# -*- coding: utf-8 -*-
"""
Speculative decoding at the serving layer (serve/spec.py proposers,
engine verify-k/rollback programs, scheduler spec ticks).

The standing contract: greedy verification makes a speculative stream
TOKEN-FOR-TOKEN IDENTICAL to the non-speculative stream on the same
decode impl — the proposer is an untrusted accelerator, so every test
here compares spec runs against their non-spec twins, including under
the stuck+NaN fault cocktail on both cache layouts and both decode
impls. The obs tests pin that a spec-decoded request reconstructs from
the JSONL event log alone with its accepted-token record.
"""

import numpy as np
import pytest

from distributed_dot_product_tpu.obs.events import EventLog, validate_file
from distributed_dot_product_tpu.obs.timeline import reconstruct
from distributed_dot_product_tpu.serve import (
    KernelEngine, Readiness, RejectedError, Scheduler, ServeConfig,
)
from distributed_dot_product_tpu.serve.spec import (
    DraftEngineProposer, NgramProposer, make_draft_engine, ngram_propose,
)
from distributed_dot_product_tpu.utils.faults import (
    ServeFaultInjector, ServeFaultPlan,
)
from distributed_dot_product_tpu.utils.tracing import MetricsRegistry

VOCAB = 16


# -- ngram lookahead ----------------------------------------------------

def test_ngram_propose_basic():
    # Suffix [2, 3] recurred at position 1; full-k continuation wins.
    assert ngram_propose([1, 2, 3, 9, 8, 2, 3], 2) == [9, 8]
    # Nothing recurs -> no proposal (the slot decodes normally).
    assert ngram_propose([1, 2, 3, 4], 3) == []
    assert ngram_propose([5], 3) == []
    assert ngram_propose([1, 2, 1, 2], 0) == []


def test_ngram_propose_prefers_full_continuation():
    """On a cyclic tail the MOST RECENT match truncates at the end of
    history — the proposer must fall back to an occurrence that can
    supply the full k guesses (that's where lookahead pays)."""
    h = [7] * 10
    assert ngram_propose(h, 4) == [7, 7, 7, 7]
    h = [1, 2, 3, 4] * 4
    assert ngram_propose(h, 4) == [1, 2, 3, 4]


def test_ngram_proposer_caps_to_budget():
    p = NgramProposer()
    out = p.propose_batch([(0, [7] * 10, 2), (1, [1, 2, 3, 4], 4)], 4)
    assert out == {0: [7, 7]}        # slot 1: nothing recurs
    with pytest.raises(ValueError):
        NgramProposer(max_ngram=0)


# -- draft proposer -----------------------------------------------------

def test_draft_proposer_cache_tracks_committed_stream():
    """Propose → commit → end_step leaves the draft cache holding
    exactly history[:-1] rows (acceptance-prefix rollback on the
    draft's own slot cache), whatever was accepted."""
    target = KernelEngine(slots=2, t_max=64, vocab=VOCAB,
                          decode_impl='xla')
    prop = DraftEngineProposer(make_draft_engine(target))
    hist = [3, 1, 4, 1, 5]
    prop.start(0, hist)
    assert int(prop.engine.lengths()[0]) == len(hist) - 1
    out = prop.propose_batch([(0, hist, 3)], 3)
    guesses = out.get(0, [])
    assert 1 <= len(guesses) <= 3
    # Pretend verify accepted 1 guess and committed 2 tokens.
    committed = [guesses[0], 9]
    prop.commit(0, committed, 1)
    prop.end_step()
    hist = hist + committed
    assert int(prop.engine.lengths()[0]) == len(hist) - 1
    # A slot it never drafted for must not roll anything back.
    prop.commit(1, [5], 0)
    prop.end_step()
    assert int(prop.engine.lengths()[1]) == 0
    prop.reset(0)
    assert int(prop.engine.lengths()[0]) == 0


def test_make_draft_engine_defaults_mirror_target():
    target = KernelEngine(slots=3, t_max=32, vocab=VOCAB, heads=2,
                          head_dim=4, seed=9, decode_impl='xla',
                          cache_mode='paged', page_size=8)
    draft = make_draft_engine(target)
    assert (draft.slots, draft.t_max, draft.vocab) == (3, 32, VOCAB)
    assert (draft.heads, draft.head_dim, draft.seed) == (2, 4, 9)
    assert draft.cache_mode == 'slab'     # the twin never pages


# -- stream identity ----------------------------------------------------

def _mk_sched(spec, cache_mode, *, decode_impl='xla', slots=3,
              t_max=64, max_new=12, spec_k=4, injector=None,
              event_log=None, seed=0):
    kw = {}
    if cache_mode == 'paged':
        kw.update(cache_mode='paged', page_size=8, pages=24)
    eng = KernelEngine(slots=slots, t_max=t_max, vocab=VOCAB, heads=2,
                       head_dim=4, prefill_chunk=4, seed=seed,
                       decode_impl=decode_impl, **kw)
    cfg = ServeConfig(queue_limit=16, max_new_tokens=max_new,
                      watchdog=False, evict_before_reject=False,
                      spec=spec, spec_k=spec_k)
    return Scheduler(eng, cfg, registry=MetricsRegistry(),
                     fault_injector=injector, event_log=event_log)


def _drive(sched, n_req=6, seed=7, interleave=False):
    rng = np.random.RandomState(seed)
    rejected = {}
    for i in range(n_req):
        p = [int(x) for x in rng.randint(1, VOCAB,
                                         size=rng.randint(2, 12))]
        try:
            sched.submit(p, request_id=f'r{i}')
        except RejectedError as e:
            rejected[f'r{i}'] = e.reason
        if interleave and i % 3 == 2:
            sched.step()
    results = sched.run_until_idle()
    sched.close()
    return results, rejected


@pytest.mark.parametrize('cache_mode', ['slab', 'paged'])
@pytest.mark.parametrize('spec', ['ngram', 'draft'])
def test_spec_streams_token_identical(cache_mode, spec):
    """Every request's status and FULL token stream match the non-spec
    run exactly — on both cache layouts, both proposers."""
    base, _ = _drive(_mk_sched(None, cache_mode))
    got, _ = _drive(_mk_sched(spec, cache_mode))
    assert set(base) == set(got)
    for rid in base:
        assert got[rid].status == base[rid].status, rid
        assert got[rid].tokens == base[rid].tokens, rid


def test_spec_streams_token_identical_kernel():
    """Same identity on the fused Pallas decode path (interpreted on
    CPU): the verify-k kernel's streams == the n=1 kernel's."""
    base, _ = _drive(_mk_sched(None, 'slab', decode_impl='kernel'),
                     n_req=4)
    got, _ = _drive(_mk_sched('ngram', 'slab', decode_impl='kernel'),
                    n_req=4)
    for rid in base:
        assert got[rid].status == base[rid].status, rid
        assert got[rid].tokens == base[rid].tokens, rid


def test_spec_amortizes_steps_and_reports_histograms():
    """A repetitive prompt: the run commits its tokens in FEWER decode
    dispatches than tokens generated, accepted-tokens/step > 2 through
    the serve.spec histograms (the ISSUE acceptance scenario, pinned
    on CPU with the n-gram proposer)."""
    # seed=18: a random-init engine whose greedy continuation locks
    # into the prompt's cycle under the installed JAX's RNG (most seeds
    # wander off it and acceptance collapses; re-pin if the RNG moves).
    eng = KernelEngine(slots=1, t_max=256, vocab=VOCAB,
                       decode_impl='xla', seed=18)
    cfg = ServeConfig(queue_limit=4, max_new_tokens=64, watchdog=False,
                      spec='ngram', spec_k=4)
    sched = Scheduler(eng, cfg, registry=MetricsRegistry())
    sched.submit([1, 2, 3, 1, 2, 3, 1, 2], request_id='r0')
    results = sched.run_until_idle()
    sched.close()
    assert len(results['r0'].tokens) == 64
    snap = sched.registry.snapshot()
    steps = snap['counters']['serve.decode_steps']
    assert steps < 32, f'{steps} dispatches for 64 tokens: no win'
    acc = sched.registry.histogram('serve.spec.accepted_per_step',
                                   buckets=()).summary()
    prop = sched.registry.histogram('serve.spec.proposed_per_step',
                                    buckets=()).summary()
    assert acc['count'] > 0 and prop['count'] >= acc['count']
    assert acc['mean'] > 2.0, f"accepted/step {acc['mean']:.2f} <= 2"


def test_plain_tick_after_dropped_proposals_rolls_back_draft():
    """A tick where the proposer drafted but EVERY proposal was shed
    (nothing guessed / paged reservation dropped them all) rides the
    plain n=1 program — the stateful draft proposer must still get its
    commit/end_step so the rows it speculatively appended roll back.
    Regression: that path skipped the proposer protocol entirely, so
    the draft cache grew ~k+1 rows per tick against 1 committed token
    and drifted into its overflow guard mid-serve."""
    class DropAll(DraftEngineProposer):
        def propose_batch(self, requests, k):
            super().propose_batch(requests, k)   # draft engine steps
            return {}                            # ...all guesses shed

    target = KernelEngine(slots=2, t_max=24, vocab=VOCAB, heads=2,
                          head_dim=4, prefill_chunk=4, seed=0,
                          decode_impl='xla')
    prop = DropAll(make_draft_engine(target))
    cfg = ServeConfig(queue_limit=8, max_new_tokens=12, watchdog=False,
                      spec_k=3)

    def draft_in_sync(s):
        # Between ticks the draft cache of an active slot holds exactly
        # history[:-1] = prompt + produced − 1 rows (the proposer's
        # documented invariant) — the drift the regression caused.
        lens = np.asarray(prop.engine.lengths())
        for slot in s._slots:
            if slot.state.name == 'ACTIVE' and slot.request is not None:
                expected = len(slot.request.prompt) + slot.produced - 1
                assert lens[slot.index] == expected, (
                    f'slot {slot.index}: draft cache at '
                    f'{lens[slot.index]} rows, committed stream at '
                    f'{expected} — rollback missed')

    sched = Scheduler(target, cfg, registry=MetricsRegistry(),
                      proposer=prop, on_tick=draft_in_sync)
    rng = np.random.RandomState(3)
    for i in range(4):
        sched.submit([int(x) for x in rng.randint(1, VOCAB, size=5)],
                     request_id=f'r{i}')
    got = sched.run_until_idle()     # overflow would raise mid-drain
    sched.close()
    # Same traffic through a non-spec scheduler for the identity check.
    eng2 = KernelEngine(slots=2, t_max=24, vocab=VOCAB, heads=2,
                        head_dim=4, prefill_chunk=4, seed=0,
                        decode_impl='xla')
    sched2 = Scheduler(eng2, ServeConfig(queue_limit=8,
                                         max_new_tokens=12,
                                         watchdog=False),
                       registry=MetricsRegistry())
    rng = np.random.RandomState(3)
    for i in range(4):
        sched2.submit([int(x) for x in rng.randint(1, VOCAB, size=5)],
                      request_id=f'r{i}')
    base = sched2.run_until_idle()
    sched2.close()
    for rid in base:
        assert got[rid].tokens == base[rid].tokens, rid


def test_spec_mixed_batch_with_non_spec_slot():
    """A slot whose history never recurs rides the same verify tick
    with counts=1 (no proposals) — both streams still match their
    non-spec twins."""
    prompts = {'cyc': [1, 2, 3] * 3, 'rnd': [9, 4, 11, 2, 7]}
    base = {}
    sched = _mk_sched(None, 'slab', slots=2, max_new=16)
    for rid, p in prompts.items():
        sched.submit(p, request_id=rid)
    base = sched.run_until_idle()
    sched.close()
    sched = _mk_sched('ngram', 'slab', slots=2, max_new=16)
    for rid, p in prompts.items():
        sched.submit(p, request_id=rid)
    got = sched.run_until_idle()
    sched.close()
    for rid in prompts:
        assert got[rid].tokens == base[rid].tokens, rid


def test_spec_respects_max_new_tokens_and_eos():
    """A verify commit never overshoots the token budget, and an EOS
    inside the accepted prefix truncates the commit exactly where the
    sequential stream would stop."""
    base_s = _mk_sched(None, 'slab', slots=1, max_new=7)
    base_s.submit([1, 2, 3] * 3, request_id='r0')
    base = base_s.run_until_idle()
    base_s.close()
    eos = base['r0'].tokens[3] if len(base['r0'].tokens) > 3 else None
    for eos_id in (None, eos):
        sched = _mk_sched('ngram', 'slab', slots=1, max_new=7)
        sched.cfg.eos_id = eos_id
        sched.submit([1, 2, 3] * 3, request_id='r0')
        got = sched.run_until_idle()
        sched.close()
        ref_s = _mk_sched(None, 'slab', slots=1, max_new=7)
        ref_s.cfg.eos_id = eos_id
        ref_s.submit([1, 2, 3] * 3, request_id='r0')
        ref = ref_s.run_until_idle()
        ref_s.close()
        assert got['r0'].tokens == ref['r0'].tokens
        assert got['r0'].status == ref['r0'].status
        assert len(got['r0'].tokens) <= 7


# -- fault cocktail -----------------------------------------------------

TERMINAL = {'completed', 'deadline_expired', 'evicted', 'abandoned',
            'failed_nan', 'rejected'}


@pytest.mark.parametrize('cache_mode,decode_impl',
                         [('slab', 'xla'), ('slab', 'kernel'),
                          ('paged', 'xla'), ('paged', 'kernel')])
def test_spec_soak_fault_cocktail_identical(cache_mode, decode_impl):
    """Stuck step + NaN slot against the SAME seeded burst, spec vs
    non-spec: every completed request's stream is bit-identical, every
    request terminal or typed, readiness restored — the quarantine/
    requeue churn must not leak a single speculative token."""
    def run(spec):
        plan = ServeFaultPlan(stuck_at_step=2, stuck_seconds=0.2,
                              nan_at_step=4, nan_slot=1)
        sched = _mk_sched(spec, cache_mode, decode_impl=decode_impl,
                          max_new=4, t_max=32,
                          injector=ServeFaultInjector(plan))
        results, rejected = _drive(sched, n_req=10, interleave=True)
        return sched, results, rejected

    sched_a, base, rej_a = run(None)
    sched_b, got, rej_b = run('ngram')
    assert rej_a == rej_b
    assert set(base) == set(got)
    compared = 0
    for rid in base:
        assert base[rid].status in TERMINAL
        assert got[rid].status in TERMINAL
        if base[rid].status == 'completed' \
                and got[rid].status == 'completed':
            assert got[rid].tokens == base[rid].tokens, rid
            compared += 1
    assert compared >= 4, 'soak too small to witness identity'
    for s in (sched_a, sched_b):
        assert s.registry.snapshot()['counters'][
            'serve.nan_quarantined'] >= 1
        assert s.health.readiness in (Readiness.READY,
                                      Readiness.STOPPED)


# -- observability ------------------------------------------------------

def test_spec_request_reconstructs_from_event_log(tmp_path):
    """A spec-decoded request's full lifecycle — including the
    spec.propose/spec.verify arcs and accepted-token counts —
    reconstructs from the JSONL alone, and the log passes offline
    schema validation."""
    log = EventLog(tmp_path / 'spec.jsonl')
    eng = KernelEngine(slots=1, t_max=256, vocab=VOCAB,
                       decode_impl='xla', seed=4)
    cfg = ServeConfig(queue_limit=4, max_new_tokens=32, watchdog=False,
                      spec='ngram', spec_k=4)
    sched = Scheduler(eng, cfg, registry=MetricsRegistry(),
                      event_log=log)
    sched.submit([1, 2, 3, 1, 2, 3, 1, 2], request_id='r0')
    results = sched.run_until_idle()
    sched.close()
    log.close()
    records, errors = validate_file(log.path)
    assert not errors, errors[:3]
    assert any(r['event'] == 'spec.propose' for r in records)
    tls = reconstruct(log.path)
    tl = tls['r0']
    assert tl.complete, tl.errors
    assert tl.status == 'completed'
    assert tl.tokens == len(results['r0'].tokens) == 32
    assert tl.spec_steps > 0
    assert tl.spec_proposed >= tl.spec_accepted > 0
    # The amortization record reconstructs: committed tokens =
    # accepted + one free token per verify step, plus the plain-tick
    # tokens — so accepted tokens are strictly fewer than the stream.
    assert tl.spec_accepted <= tl.tokens
    # Events carry the per-step accepted counts the histogram saw.
    acc = sched.registry.histogram('serve.spec.accepted_per_step',
                                   buckets=()).summary()
    ev_acc = sum(r['accepted'] for r in records
                 if r['event'] == 'spec.verify')
    assert ev_acc == tl.spec_accepted
    assert acc['count'] == tl.spec_steps


def test_spec_retrace_budget_one_program_per_width():
    """One verify program per width and one rollback program per span
    bucket over a whole serving run — the retrace sentinel (enabled
    suite-wide) would raise on a storm; this pins the totals."""
    from distributed_dot_product_tpu.utils import retrace
    sched = _mk_sched('ngram', 'slab', slots=2, max_new=16)
    for i, p in enumerate(([1, 2, 3] * 3, [4, 5] * 4)):
        sched.submit(list(p), request_id=f'r{i}')
    sched.run_until_idle()
    sched.close()
    w = sched.cfg.spec_k + 1
    assert retrace.total(f'engine.verify_w{w}') == 1
