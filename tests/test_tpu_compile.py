# -*- coding: utf-8 -*-
"""
Compile the main path's kernels for the REAL chip, without the chip.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``): what Mosaic
refuses — a dot against a one-row operand, an unaligned block, too much
VMEM — it refuses here, at no chip time. Interpret mode (every other
kernel test) cannot see those. Each case is ~1-2 s at the widths
``chip_smoke.py`` runs; nothing executes, so these say nothing about
results or times.

``interpret=False`` is passed explicitly: ``jax.default_backend()`` is
still ``cpu`` in this process.
"""

import os

os.environ.setdefault('TPU_LOG_DIR', 'disabled')  # else logs under /tmp
# Only the compiler is used, no chip: let several test workers (pytest-
# xdist) load libtpu at once instead of fighting over its lockfile.
os.environ.setdefault('ALLOW_MULTIPLE_LIBTPU_LOAD', '1')

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_dot_product_tpu.models.decode import (
    decode_step, init_cache, init_paged_cache, init_slot_cache,
)
from distributed_dot_product_tpu.ops.pallas_attention import (
    flash_attention,
)

# Widths of chip_smoke.py's serve phase.
B, H, D, T_MAX, PAGE = 8, 8, 96, 32768, 256


@pytest.fixture(scope='module')
def chip():
    """One described v5e chip. The persistent compilation cache is off
    around these compiles: an entry written for a described device
    cannot be read back without one, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except (RuntimeError, NotImplementedError) as e:  # no TPU compiler
        pytest.skip(f'cannot describe a v5e:2x2 topology here: {e}')
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', True)
    compilation_cache.reset_cache()


def _compile(chip, fn, *args, donate=()):
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        args)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()
    assert 'tpu_custom_call' in compiled.as_text()
    return compiled


@pytest.mark.parametrize('d', [96, 128])
@pytest.mark.parametrize('grad', [False, True], ids=['fwd', 'grad'])
def test_flash_attention_compiles_for_v5e(chip, d, grad):
    """Causal flash attention at T=16384, the train step's kernel."""
    x = jax.ShapeDtypeStruct((1, 8, 16384, d), jnp.bfloat16)

    def fwd(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False),
                       dtype=jnp.float32)

    _compile(chip, jax.grad(fwd, argnums=(0, 1, 2)) if grad else fwd,
             x, x, x)


def _cache(layout, h_kv, qk_quant):
    if layout == 'slab':
        return init_cache(B, h_kv, T_MAX, D, qk_quant=qk_quant)
    if layout == 'slot':
        return init_slot_cache(B, h_kv, T_MAX, D)
    return init_paged_cache(B, h_kv, T_MAX, D, pages=B * T_MAX // PAGE,
                            page_size=PAGE, qk_quant=qk_quant)


@pytest.mark.parametrize('layout,h_kv,qk_quant,n', [
    ('slab', 8, None, 1), ('slot', 8, None, 1), ('paged', 8, None, 1),
    ('slab', 8, 'int8', 1), ('paged', 8, 'int8', 1),      # K mirror
    ('slab', 2, None, 1), ('slot', 2, None, 1),           # GQA 8/2
    ('paged', 2, None, 1), ('paged', 2, 'int8', 1),
    ('slot', 8, None, 4), ('paged', 8, None, 4),          # verify-k
])
def test_decode_kernel_compiles_for_v5e(chip, layout, h_kv, qk_quant, n):
    """The fused decode step in every cache layout. n=1 is the form the
    chip's compiler refused before the new rows were padded to their
    sublane tile (a dot against a one-row operand)."""
    cache = jax.eval_shape(lambda: _cache(layout, h_kv, qk_quant))
    q = jax.ShapeDtypeStruct((B, H, n, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, h_kv, n, D), jnp.bfloat16)

    def step(q, cache, k_new, v_new):
        return decode_step(q, cache, k_new, v_new, qk_quant=qk_quant,
                           impl='kernel', interpret=False)

    _compile(chip, step, q, cache, kv, kv, donate=(1,))
