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

import math
import os
import re

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


# ---------------------------------------------------------------------------
# The scanned LM decode step: the layer loop carries the stacked caches
# ---------------------------------------------------------------------------

# Widths of the benchmark cell mpt-7b.decode-12k.
LM = dict(vocab_size=50432, dim=4096, num_heads=32, n_layers=8)
SESSIONS, LM_T_MAX = 2, 16384
LAYER_K_BYTES = SESSIONS * 32 * LM_T_MAX * 128 * 2

_HLO_INSTRUCTION = re.compile(
    r'^\s*(ROOT )?%?[\w.\-]+ = (.*?)\s([a-z][a-z0-9\-]*)\(')
_HLO_ARRAY = re.compile(r'\b(pred|[a-z]+\d+)\[([\d,]*)\]')
_MOVES = ('copy', 'copy-start', 'dynamic-slice', 'dynamic-update-slice')


def _result_bytes(result_type):
    """Bytes of the largest array in an HLO result type."""
    sizes = [0]
    for dtype, dims in _HLO_ARRAY.findall(result_type):
        bits = 8 if dtype == 'pred' else int(re.sub(r'\D', '', dtype))
        sizes.append(math.prod(int(d) for d in dims.split(',') if d)
                     * bits // 8)
    return max(sizes)


def _cache_sized_moves(hlo, at_least):
    """The optimized HLO's copies, dynamic slices and dynamic-update
    slices — bare, or as the root of a fusion — whose result is at
    least ``at_least`` bytes."""
    found, fused = [], False
    for line in hlo.splitlines():
        if line.rstrip().endswith('{'):              # a computation opens
            fused = 'fused_computation' in line.split('(')[0]
            continue
        m = _HLO_INSTRUCTION.match(line)
        if (m and m.group(3) in _MOVES and (m.group(1) or not fused)
                and _result_bytes(m.group(2)) >= at_least):
            found.append(line.strip()[:160])
    return found


def test_cache_sized_moves_reads_hlo():
    """The reader the test below trusts, on lines of the parent's
    program (whole-cache copy, slice fusion's root) and an innocent
    one."""
    hlo = '\n'.join([
        'ENTRY %main.1 (p: bf16[8,2,32,16384,128]) -> bf16[4] {',
        '  %copy.36 = bf16[8,2,32,16384,128]{4,3,2,1,0:T(8,128)(2,1)} '
        'copy(%get-tuple-element.3)',
        '  %small = s32[8]{0} dynamic-update-slice(%a, %b, %c)',
        '}',
        '%fused_computation.4 (p0: bf16[8,2,32,16384,128]) -> '
        'bf16[1,2,32,16384,128] {',
        '  %inner = bf16[1,2,32,16384,128]{4,3,2,1,0} '
        'dynamic-slice(%p0, %i), dynamic_slice_sizes={1,2,32,16384,128}',
        '  ROOT %ds = bf16[1,2,32,16384,128]{4,3,2,1,0} '
        'dynamic-slice(%p0, %i), dynamic_slice_sizes={1,2,32,16384,128}',
        '}'])
    found = _cache_sized_moves(hlo, LAYER_K_BYTES)
    assert [f.split(' = ')[0] for f in found] == ['%copy.36', 'ROOT %ds']


@pytest.mark.parametrize('qk_quant', [None, 'int8'],
                         ids=['bf16', 'int8-mirror'])
def test_scanned_lm_decode_step_moves_no_cache(chip, monkeypatch,
                                               qk_quant):
    """The scanned ``TransformerLM.decode`` step at the decode cell's
    widths, caches donated: the layer loop carries the stacked caches
    and the kernel appends layer l in place, so the program holds no
    copy / slice / write-back as large as one layer's K cache, the
    stacked buffers alias the results and nothing cache-sized is a
    temporary. (With the caches as the scan's xs → ys the same step
    held four such fusions, two whole-stack copies and 5 GiB of
    temporaries.)"""
    from distributed_dot_product_tpu import TransformerLM
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    # The program asks the backend whether to compile its kernel or
    # interpret it; answer for the described chip, here only.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    slopes = tuple(2.0 ** (-8.0 * (i + 1) / 32) for i in range(32))
    model = TransformerLM(**LM, dtype=jnp.bfloat16, scan_layers=True,
                          attn_kwargs=dict(use_rope=False,
                                           alibi_slopes=slopes,
                                           qk_quant=qk_quant))
    tok = jnp.zeros((SESSIONS, 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 128),
                                                        jnp.int32)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(SESSIONS, LM_T_MAX))
    assert caches.k.shape == (8, SESSIONS, 32, LM_T_MAX, 128)

    def step(p, t, c):
        return model.apply(p, t, c, method='decode')

    with decode_impl_traces() as traces:
        compiled = _compile(chip, step, params, tok, caches, donate=(2,))
    assert {(t['resolved'], t['cache']) for t in traces} == {
        ('kernel', 'stacked')}
    assert _cache_sized_moves(compiled.as_text(), LAYER_K_BYTES) == []
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes    # tiles pad upward
    assert mem.temp_size_in_bytes < LAYER_K_BYTES


def test_latent_decode_step_moves_no_cache_and_no_expert_stack(
        chip, monkeypatch):
    """The token step of the latent-attention / sparse-expert model at
    the published widths of ``xing4-29b-a4b.decode-32k`` (one dense and
    two expert layers of its six; 16 sessions x 33792 rows), cache
    donated: the step resolves to ``flash_decode``'s latent mode over
    the layer-stacked cache, which aliases the result; nothing as large
    as one layer of it is copied, sliced or held as a temporary; and no
    layer's 64 experts are moved on their way into XLA's grouped-matmul
    kernel (as a scanned layer's were, sliced out of the stack: 21.6 ms
    of a 36.6 ms step; chip, PR 26)."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_latent as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'xing4-29b-a4b-serve.json')) as f:
        cfg = dict(json.load(f), num_hidden_layers=3)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x16.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = {'params': {}}
    for path, (shape, _) in driver.shapes(cfg).items():
        node = params['params']
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = jax.ShapeDtypeStruct(
            shape, jnp.float32 if path[-1] in driver.FLOAT32_LEAVES
            else jnp.bfloat16)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    assert caches.rows.shape == (3, sessions, t_max, 640)
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    step = driver.make_programs(model, cfg)[2]
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (params, tok, caches, stats))
    with decode_impl_traces() as traces:
        compiled = step.lower(*shapes).compile()
    assert {(t['resolved'], t['cache']) for t in traces} == {
        ('kernel', 'stacked')}
    hlo = compiled.as_text()
    assert hlo.count('mla_decode') and 'ragged-dot' in hlo
    layer_bytes = sessions * t_max * 640 * 2
    experts_bytes = 64 * 3584 * 1024 * 2
    assert _cache_sized_moves(hlo, min(layer_bytes, experts_bytes)) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * layer_bytes
    assert mem.temp_size_in_bytes < experts_bytes
