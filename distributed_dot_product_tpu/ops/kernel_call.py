# -*- coding: utf-8 -*-
"""
The one place a Pallas kernel is called from: ``kernel_call`` is
``pl.pallas_call`` with the kernel's ``name=`` required and a
:func:`~distributed_dot_product_tpu.utils.build_ledger.build_span` of
that name around the call — where Pallas traces the kernel's body — so
the build ledger divides a program's trace seconds into the model's
Python and each kernel's body. The span leaves nothing in the program:
with and without it a call lowers to one text
(``tests/test_build_ledger.py``, which also walks ``ops/`` for a
``pl.pallas_call`` that is not this one).
"""

from jax.experimental import pallas as pl

from distributed_dot_product_tpu.utils.build_ledger import build_span

__all__ = ['kernel_call']


def kernel_call(kernel, *, name, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)``, its invocation
    noted in the build ledger as a ``build`` record called ``name``."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def traced(*args):
        with build_span(name):
            return call(*args)
    return traced
