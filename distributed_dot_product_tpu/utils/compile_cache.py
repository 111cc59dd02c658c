# -*- coding: utf-8 -*-
"""
Where JAX's persistent compilation cache lives — ONE rule for every
harness (``chip_smoke.py``, ``benchmarks/run.py``, the examples and
``tests/conftest.py``).

The cache directory is part of the cache key, so a directory that moves
never hits: it is either the one the environment names or one fixed
path inside the checkout, never a per-user or per-process temp dir
(such a directory can also outlive the machine that filled it — XLA:CPU
then refuses its entries on load with "machine type … doesn't match").

Every harness calls this before its first compile, so it is also where
the build ledger's listeners go on (``utils/build_ledger.py``: what each
program costs to trace, lower and compile, and whether this cache had
it).
"""

import os

import jax

from distributed_dot_product_tpu.utils import build_ledger

__all__ = ['setup_compile_cache']

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache():
    """Point JAX at its persistent compilation cache and return the
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it on
    its own and nothing is set in code; otherwise the cache is
    ``<checkout>/.jax_cache`` (git-ignored). Installs the build ledger
    (idempotent: one set of listeners however often this is called)."""
    build_ledger.install()
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    path = os.path.join(_CHECKOUT, '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path
