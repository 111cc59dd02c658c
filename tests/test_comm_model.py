# -*- coding: utf-8 -*-
"""
The analytic ICI communication model (scripts/comm_model.py) must match
what XLA actually compiles: per path, the multiset of collective ops and
their per-op byte sizes in the compiled HLO equals the model's predicted
schedule. This is the checkable substitute for multi-chip measurement
(no benchmark cell spans chips yet).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..',
                                'scripts'))
import comm_model  # noqa: E402

pytestmark = pytest.mark.slow


def test_schedule_matches_compiled_hlo():
    results = comm_model.check_against_hlo(n=8)
    for path, r in results.items():
        assert r['match'], (
            f"{path}: model schedule {r['expected']} != compiled HLO "
            f"{r['got']}")


def test_gqa_cuts_allgather_bytes():
    full = comm_model.comm_model('allgather', n=8, h=8, t=4096, d=64)
    gqa = comm_model.comm_model('allgather', n=8, h=8, h_kv=2, t=4096,
                                d=64)
    assert gqa['total_bytes'] == full['total_bytes'] / 4


def test_ring_equals_allgather_volume_at_bf16():
    """The classic identity: ring rotation moves the same total K/V bytes
    as one all-gather — (N−1)/N of the global array per device — so the
    FORWARD volumes agree exactly; the ring backward additionally carries
    fp32 dk/dv partials."""
    n, h, t, d = 8, 8, 4096, 64
    ag = comm_model.comm_model('allgather', n=n, h=h, t=t, d=d)
    ring = comm_model.comm_model('ring', n=n, h=h, t=t, d=d)
    ag_fwd = ag['collectives'][0]
    ring_fwd = ring['collectives'][0]
    assert ag_fwd[1] * ag_fwd[2] == pytest.approx(
        ring_fwd[1] * ring_fwd[2])


def test_ulysses_is_n_over_2_cheaper():
    """Ulysses moves O(T·d·H/N) per device per tensor vs allgather's
    O(T·d·H): allgather ships 2 tensors each way (q, v — 4 collectives
    total), ulysses 4 each way but at 1/N volume, so the total ratio is
    N/2 (H_kv = H, same dtypes both ways)."""
    n, h, t, d = 8, 8, 4096, 64
    ag = comm_model.comm_model('allgather', n=n, h=h, t=t, d=d)
    ul = comm_model.comm_model('ulysses', n=n, h=h, t=t, d=d)
    assert ag['total_bytes'] / ul['total_bytes'] == pytest.approx(n / 2)
