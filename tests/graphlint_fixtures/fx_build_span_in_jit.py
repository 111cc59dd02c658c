"""``build_span`` each way: inside a jitted function it is the one
clock-reading name ``clock-in-jit`` lets through (it times the TRACE on
purpose and leaves nothing in the program); an obs ``span`` and a bare
clock read beside it are still refused."""
import time

import jax

from distributed_dot_product_tpu.obs import span
from distributed_dot_product_tpu.utils import build_ledger
from distributed_dot_product_tpu.utils.build_ledger import build_span


@jax.jit
def kernel_body_timed(x):
    with build_span('fake_kernel'):          # build time: NOT flagged
        y = x * 2
    with build_ledger.build_span('again'):   # dotted: NOT flagged
        return y + 1


@jax.jit
def still_refused(x):
    with build_span('fake_kernel'):
        with span('step'):                   # VIOLATION: clock-in-jit
            y = x * 2
    t = time.perf_counter()                  # VIOLATION: clock-in-jit
    return y + t
