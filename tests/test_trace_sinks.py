# -*- coding: utf-8 -*-
"""
utils/trace_sinks.py — the one mechanism behind the seven public
``*_traces()`` context managers: each name opens a block on its module's
:class:`TraceSinks`, and what the module notes reaches the open blocks.
"""

import importlib

import pytest

from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

PKG = 'distributed_dot_product_tpu'
TRACES = [
    ('models.decode', 'decode_impl_traces', '_IMPL_TRACES'),
    ('ops.pallas_attention', 'flash_bwd_traces', '_BWD_TRACES'),
    ('ops.pallas_attention', 'flash_block_traces', '_BLOCK_TRACES'),
    ('models.moe', 'expert_route_traces', '_ROUTE_TRACES'),
    ('models.remat', 'remat_traces', '_REMAT_TRACES'),
    ('models.delta', 'delta_step_traces', '_STEP_TRACES'),
    ('models.lm', 'head_loss_traces', '_HEAD_TRACES'),
]


@pytest.mark.parametrize('module, public, private', TRACES,
                         ids=[t[1] for t in TRACES])
def test_public_traces_name_opens_its_modules_sinks(module, public,
                                                    private):
    mod = importlib.import_module(f'{PKG}.{module}')
    traces, sinks = getattr(mod, public), getattr(mod, private)
    assert isinstance(sinks, TraceSinks) and public in mod.__all__
    assert traces.__doc__ and not sinks

    # Nested blocks each get the records noted while they are open.
    with traces() as outer:
        assert sinks
        sinks.note({'n': 1})
        with traces() as inner:
            sinks.note({'n': 2})
        sinks.note({'n': 3})
    assert [r['n'] for r in outer] == [1, 2, 3]
    assert [r['n'] for r in inner] == [2]

    # A block that raises is removed, and only it.
    with traces() as kept:
        with pytest.raises(RuntimeError):
            with traces() as lost:
                raise RuntimeError('inside the block')
        sinks.note({'n': 4})
    assert lost == [] and [r['n'] for r in kept] == [4]

    # A closed block receives nothing.
    assert not sinks
    sinks.note({'n': 5})
    assert [r['n'] for r in outer] == [1, 2, 3] and len(kept) == 1
