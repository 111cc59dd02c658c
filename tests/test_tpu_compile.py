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


def _mpt_slopes(h):
    """MPT's ALiBi slopes (``alibi_bias_max`` 8) for ``h`` heads."""
    return tuple(2.0 ** (-8.0 * (i + 1) / h) for i in range(h))


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


# (heads, KV heads, T, flash_attention kwargs, the form the backward takes)
_BWD_SHAPES = {
    # the two training cells' calls
    'mpt-7b.train-16k': (32, 32, 16384, dict(alibi=True), 'fused'),
    'starcoder2-3b.train-16k': (24, 2, 16384, dict(window=4096), 'fused'),
    # the dq budget's edges at d 128: 16 MiB of float32 a batch-head
    'largest_inside_budget': (4, 4, 32768, {}, 'fused'),
    'first_past_budget': (4, 4, 32768 + 1024, {}, 'split'),
    'largest_inside_budget_gqa_window': (8, 2, 32768, dict(window=4096),
                                         'fused'),
}


@pytest.mark.parametrize('shape', sorted(_BWD_SHAPES))
def test_flash_backward_form_compiles_for_v5e(chip, shape):
    """The fused backward (dq of a batch-head resident in VMEM) under the
    ``vmem_limit_bytes`` it states: Mosaic's verdict on the budget at
    both training cells' shapes and at the budget's edge; one block past
    it the call keeps the two kernels, under the compiler's default."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        _BWD_VMEM_BASE, _FUSED_DQ_BYTES, flash_bwd_traces,
    )
    h, h_kv, t, kw, form = _BWD_SHAPES[shape]
    kw = dict(kw)
    if kw.pop('alibi', False):
        kw['alibi_slopes'] = jnp.asarray(_mpt_slopes(h), jnp.float32)
    q = jax.ShapeDtypeStruct((1, h, t, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, h_kv, t, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False, **kw),
                       dtype=jnp.float32)

    with flash_bwd_traces() as traces:
        hlo = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                       q, kv, kv).as_text()
    assert [tr['form'] for tr in traces] == [form], traces
    dq_bytes = t * 128 * 4
    assert traces[0]['dq_bytes'] == dq_bytes
    if form == 'fused':
        # float32 accumulator + the double-buffered bfloat16 block
        assert traces[0]['vmem_limit_bytes'] == (
            _BWD_VMEM_BASE + dq_bytes + 2 * t * 128 * 2)
        assert dq_bytes <= _FUSED_DQ_BYTES
        assert 'flash_bwd_fused' in hlo and 'flash_bwd_dq' not in hlo
    else:
        assert dq_bytes > _FUSED_DQ_BYTES
        assert 'past the' in traces[0]['reason']
        assert 'flash_bwd_dq' in hlo and 'flash_bwd_dkv/' in hlo
        assert 'flash_bwd_fused' not in hlo


# What ``flash_block_traces()`` must say of a head of each training cell.
_CELL_BLOCKS = {
    'mpt-7b.train-16k': dict(grid='trap', run_blocks=136,
                             interior_blocks=120, alibi='vector'),
    'starcoder2-3b.train-16k': dict(grid='band', run_blocks=70,
                                    interior_blocks=42, alibi=None),
}


@pytest.mark.parametrize('what', ['forward', 'grad'])
@pytest.mark.parametrize('cell', sorted(_CELL_BLOCKS))
def test_flash_kernels_by_kind_compile_for_v5e(chip, cell, what):
    """Both training cells' forward (alone, and with its logsumexp) and
    fused backward with the body entered by the block's kind — two
    branches of the body in one kernel, for MPT with the ALiBi bias as a
    vector a block: the forward under the compiler's default VMEM limit,
    the backward under the limit ``_bwd_form`` states. The counter gives
    the kinds a head."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        flash_block_traces,
    )
    h, h_kv, t, kw, _ = _BWD_SHAPES[cell]
    kw = dict(kw)
    if kw.pop('alibi', False):
        kw['alibi_slopes'] = jnp.asarray(_mpt_slopes(h), jnp.float32)
    q = jax.ShapeDtypeStruct((1, h, t, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, h_kv, t, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False, **kw),
                       dtype=jnp.float32)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if what == 'grad' else loss
    with flash_block_traces() as traces:
        hlo = _compile(chip, fn, q, kv, kv).as_text()
    kernels = ['flash_fwd'] + ['flash_bwd_fused'] * (what == 'grad')
    assert traces == [dict(_CELL_BLOCKS[cell], kernel=k) for k in kernels]
    assert all(f'{k}/pallas_call' in hlo for k in kernels)


def test_flash_backward_float32_grads_compile_at_the_budget(chip):
    """The ring fold's call (``grad_dtype=float32``: the output block is
    as wide as the accumulator) at the budget's edge: 64 MiB stated, half
    of the chip's VMEM."""
    from distributed_dot_product_tpu.ops.pallas_attention import (
        _flash_bwd_impl, flash_bwd_traces,
    )
    h, t, d = 2, 32768, 128
    x = jax.ShapeDtypeStruct((1, h, t, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, h, t), jnp.float32)

    def bwd(q, k, v, out, lse, g):
        return _flash_bwd_impl(q, k, v, None, 0, out, lse, g, d ** -0.5,
                               True, False, grad_dtype=jnp.float32)

    with flash_bwd_traces() as traces:
        _compile(chip, bwd, x, x, x, x, lse, x)
    assert [(tr['form'], tr['vmem_limit_bytes']) for tr in traces] == [
        ('fused', 64 * 1024 * 1024)]


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
    ('mpt-7b.decode-12k', 32, None, 1),                   # the cells' own
    ('xing4-29b-a4b.decode-32k', 1, None, 1),
    ('solar-open2-250b.decode-4k', 8, None, 1),
])
def test_decode_kernel_compiles_for_v5e(chip, layout, h_kv, qk_quant, n):
    """The fused decode step in every cache layout. n=1 is the form the
    chip's compiler refused before the new rows were padded to their
    sublane tile (a dot against a one-row operand). The two decode
    cells' own calls — the layer-stacked slab of ``mpt-7b.decode-12k``
    (8 KV heads a grid step) and the latent rows of
    ``xing4-29b-a4b.decode-32k`` — hold Mosaic's VMEM verdict on the
    geometry ``decode_geometry`` chooses for them, the tail's buffers
    (256 rows of every head of the step, the write-back tile's staging
    rows) counted; ``solar-open2-250b.decode-4k``'s call (128 slots of
    8 KV heads, 5120 rows) is the shape the tail was laddered at."""
    if '.' in layout:
        _compile_cell_kernel(chip, layout)
        return
    cache = jax.eval_shape(lambda: _cache(layout, h_kv, qk_quant))
    q = jax.ShapeDtypeStruct((B, H, n, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, h_kv, n, D), jnp.bfloat16)

    def step(q, cache, k_new, v_new):
        return decode_step(q, cache, k_new, v_new, qk_quant=qk_quant,
                           impl='kernel', interpret=False)

    _compile(chip, step, q, cache, kv, kv, donate=(1,))


def _compile_cell_kernel(chip, cell):
    """``flash_decode`` as a decode cell calls it, on its layer-stacked
    cache at the benchmark's widths."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        decode_geometry, flash_decode, latent_geometry,
    )
    bf16 = jnp.bfloat16
    if cell == 'mpt-7b.decode-12k':
        layers, b, h, t_max, d = 8, 2, 32, 16384, 128
        assert decode_geometry(t_max, h, d, d, 1, bf16, bf16) == (
            8, 1024, 16, 4 << 20, 256)
        slopes = _mpt_slopes(h)
        row = jax.ShapeDtypeStruct((b, h, 1, d), bf16)
        buf = jax.ShapeDtypeStruct((layers, b, h, t_max, d), bf16)

        def step(q, k_new, v_new, k, v, at, layer):
            return flash_decode(q, k_new, v_new, k, v, at, at,
                                layer=layer, alibi_slopes=slopes,
                                interpret=False)

        args, donate = (row, row, row, buf, buf), (3, 4)
    elif cell == 'solar-open2-250b.decode-4k':
        # One layer's buffers (the cell's stack is unrolled), GQA 64:8.
        b, h, h_kv, t_max, d = 128, 64, 8, 5120, 128
        assert decode_geometry(t_max, h_kv, d, d, h // h_kv, bf16,
                               bf16) == (8, 1024, 16, 4 << 20, 256)
        q = jax.ShapeDtypeStruct((b, h, 1, d), bf16)
        row = jax.ShapeDtypeStruct((b, h_kv, 1, d), bf16)
        buf = jax.ShapeDtypeStruct((b, h_kv, t_max, d), bf16)

        def step(q, k_new, v_new, k, v, at, layer):
            del layer
            return flash_decode(q, k_new, v_new, k, v, at, at,
                                interpret=False)

        args, donate = (q, row, row, buf, buf), (3, 4)
    else:
        # The latent buffer is time-minor, 576 values a token and nothing
        # padded (until PR 47 rows of 640): splits of 1536 columns, the
        # append's lane tile, the last split in pieces of 256.
        layers, b, h, t_max, d, dv = 6, 16, 32, 33792, 576, 512
        assert latent_geometry(t_max, d, dv, h, bf16) == (
            1, 1536, 128, 1536 * 576 * 2, 256)
        q = jax.ShapeDtypeStruct((b, h, 1, d), bf16)
        row = jax.ShapeDtypeStruct((b, 1, d, 128), bf16)
        buf = jax.ShapeDtypeStruct((layers, b, 1, d, t_max), bf16)

        def step(q, k_new, rows, at, layer):
            return flash_decode(q, k_new, None, rows, None, at, at,
                                layer=layer, latent_v=dv,
                                interpret=False)

        args, donate = (q, row, buf), (2,)
    at = jax.ShapeDtypeStruct((b,), jnp.int32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = _compile(chip, step, *args, at, layer, donate=donate)
    cache_bytes = sum(math.prod(x.shape) * 2 for x in args if x is buf)
    # Aliased whole, and no copy of a cache in front of the kernel (a
    # buffer handed to the call twice — once aliased, once to read its
    # tail — is copied whole by XLA: section 6, PR 45).
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 8


# The edges of ``decode_geometry``'s VMEM plan: shapes at which it returns
# the most heads a step for a wide head (whose plan has room for a tail
# of 128 rows and not of 256), a grouped one, the int8 mirror, a
# verify-k step (whose write-back is the whole split) and page pools
# (the last three move the last split whole: no tail).
# (name, h, h_kv, d, n, page, qk_quant, window) -> (heads a step, tail).
_PLAN_EDGES = {
    'mha-d256': ((8, 8, 256, 1, None, None, None), (4, 128)),
    'starcoder2-gqa-window': ((24, 2, 128, 1, None, None, 4096), (2, 256)),
    'gqa-group-64': ((512, 8, 128, 1, None, None, None), (4, 256)),
    'int8-d128': ((32, 32, 128, 1, None, 'int8', None), (2, None)),
    'int8-d256': ((8, 8, 256, 1, None, 'int8', None), (1, None)),
    'verify8-gqa': ((32, 8, 128, 8, None, None, None), (4, None)),
    'verify4-d256': ((8, 8, 256, 4, None, None, None), (2, None)),
    'paged16': ((32, 32, 128, 1, 16, None, None), (32, None)),
    'paged256-verify4': ((32, 32, 128, 4, 256, None, None), (16, None)),
    'paged1024-d256': ((8, 8, 256, 1, 1024, None, None), (2, None)),
    'paged1024-int8': ((32, 32, 128, 1, 1024, 'int8', None), (2, None)),
}


@pytest.mark.parametrize('edge', sorted(_PLAN_EDGES))
def test_decode_kernel_compiles_at_the_plans_edges(chip, edge):
    """Mosaic's verdict where ``decode_geometry`` packs the most into a
    step: the plan is arithmetic, the compiler's scoped VMEM limit is
    not, and a plan that is wrong is a compile error on the chip, not a
    step of fewer heads."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode, flash_decode_geometry,
    )
    (h, h_kv, d, n, page, qk_quant, window), (heads, tail) = (
        _PLAN_EDGES[edge])
    b, t_max, bf16 = 2, 16384, jnp.bfloat16
    lead = (b,) if page is None else (b * t_max // page + 1,)
    rows = t_max if page is None else page
    kv = jax.ShapeDtypeStruct((b, h_kv, n, d), bf16)
    ops = {'q': jax.ShapeDtypeStruct((b, h, n, d), bf16),
           'k_new': kv, 'v_new': kv,
           'k': jax.ShapeDtypeStruct(lead + (h_kv, rows, d), bf16),
           'v': jax.ShapeDtypeStruct(lead + (h_kv, rows, d), bf16),
           'at': jax.ShapeDtypeStruct((b,), jnp.int32)}
    if page is not None:
        ops['page_table'] = jax.ShapeDtypeStruct((b, t_max // page),
                                                 jnp.int32)
    if qk_quant:
        ops['k_q'] = jax.ShapeDtypeStruct(lead + (h_kv, rows, d), jnp.int8)
        ops['k_scale'] = jax.ShapeDtypeStruct(lead + (h_kv, rows, 1),
                                              jnp.float32)
    geom = flash_decode_geometry(
        ops['q'], ops['k'], ops['v'], page_table=ops.get('page_table'),
        qk_quant=qk_quant)
    assert (geom.heads, geom.tail) == (heads, tail)

    def step(o):
        return flash_decode(
            o['q'], o['k_new'], o['v_new'], o['k'], o['v'], o['at'],
            o['at'], page_table=o.get('page_table'), k_q=o.get('k_q'),
            k_scale=o.get('k_scale'), qk_quant=qk_quant, window=window,
            interpret=False)

    _compile(chip, step, ops)


# The edges of ``latent_geometry``'s plan: (heads, t_max, stacked) -> the
# split it takes. The cells' 32 query rows on their 33792 columns; a
# power-of-two buffer (the longest split, past the compiler's default
# scoped limit: the call asks for its own); 128 query rows (DeepSeek-V3's
# heads: four times the scores and probabilities), and in float32 (twice
# the bytes a column: the plan steps down to 1536); a buffer of one split
# (no pieces); one whose only divisor is 512.
_LATENT_EDGES = {
    'cells-32-rows': ((32, 33792, True, jnp.bfloat16), 1536),
    'power-of-two': ((32, 32768, False, jnp.bfloat16), 2048),
    '128-rows': ((128, 32768, True, jnp.bfloat16), 2048),
    'float32-128-rows': ((128, 32768 * 3, False, jnp.float32), 1536),
    'one-split': ((32, 2048, False, jnp.bfloat16), 2048),
    'split-512': ((32, 512 * 7, False, jnp.bfloat16), 512),
}


@pytest.mark.parametrize('edge', sorted(_LATENT_EDGES))
def test_latent_kernel_compiles_at_its_plans_edges(chip, edge):
    """Mosaic's verdict on the latent kernel where ``latent_geometry``
    packs the most into a step (the stream and the last split's pieces
    double-buffered, 256-column pieces, the 576-deep score product over
    sublanes that are no whole lane tile), the buffer aliased whole and
    nothing copied."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode, latent_geometry,
    )
    (h, t_max, stacked, dtype), bk = _LATENT_EDGES[edge]
    b, d, dv = 4, 576, 512
    geom = latent_geometry(t_max, d, dv, h, dtype)
    assert (geom.block_k, geom.tail) == (
        bk, 256 if t_max > bk else None)
    lead = (3,) if stacked else ()
    buf = jax.ShapeDtypeStruct((*lead, b, 1, d, t_max), dtype)

    def step(q, new, rows, at, layer):
        return flash_decode(q, new, None, rows, None, at, at,
                            layer=layer if stacked else None,
                            latent_v=dv, interpret=False)[:2]

    compiled = _compile(
        chip, step, jax.ShapeDtypeStruct((b, h, 1, d), dtype),
        jax.ShapeDtypeStruct((b, 1, d, 128), dtype), buf,
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), donate=(2,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (math.prod(buf.shape)
                                       * jnp.dtype(dtype).itemsize)
    assert mem.temp_size_in_bytes < 1 << 20


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


# ``moe_hit_experts`` at the edges of ``hidden_tile``'s plan: (tokens,
# held, wide, hidden, gated, weights' type) -> (the hidden tile a step
# takes, its ``vmem_limit_bytes``). The hybrid cell's own call (the
# compiler's default limit); the two gated expert cells' published
# layers at their decode steps' rows and at the rule's bound of rows
# (three matrices a step: the slab widened to 1 KB rows, the blocks'
# bytes asked for); weights stored in float32 (cast in VMEM, half the
# tile); a hidden width that is no multiple of 128 (one whole block); a
# stream so wide that a 1 KB slab would pass the ceiling (one lane tile,
# its blocks still asked for).
_EXPERT_SHAPES = {
    'nemotron-latent': ((48, 128, 1024, 2688, False, jnp.bfloat16),
                        (896, None)),
    'xing4-gated': ((16, 64, 3584, 1024, True, jnp.bfloat16),
                    (512, 37 << 20)),
    'command-a-gated': ((12, 16, 4096, 4096, True, jnp.bfloat16),
                        (512, 40 << 20)),
    'xing4-gated-at-the-bound': ((128, 64, 3584, 1024, True, jnp.bfloat16),
                                 (512, 37 << 20)),
    'command-a-gated-at-the-bound': (
        (128, 16, 4096, 4096, True, jnp.bfloat16), (512, 40 << 20)),
    'float32-weights': ((48, 8, 1024, 2688, False, jnp.float32),
                        (384, None)),
    'ragged-hidden': ((24, 8, 512, 200, True, jnp.bfloat16), (200, None)),
    'wide-stream': ((16, 2, 16384, 1024, True, jnp.bfloat16),
                    (128, 40 << 20)),
    # LFM2's layer at its decode step's rows: the rule's bound for a
    # 2048-wide stream, two MXU passes (32 experts x 2 tiles of 896).
    'lfm2-gated-256-rows': ((256, 32, 2048, 1792, True, jnp.bfloat16),
                            (896, 37 << 20)),
}


@pytest.mark.parametrize('shape', sorted(_EXPERT_SHAPES))
def test_hit_experts_kernel_compiles_for_v5e(chip, shape):
    """The expert kernel of the hit-list route under the VMEM plan its
    docstring states (the weight blocks double-buffered within 12 MiB
    and no ``vmem_limit_bytes``, or a slab of 1 KB rows and the blocks'
    bytes asked for): a plan that is wrong is a compile error."""
    from distributed_dot_product_tpu.models.moe import ACTIVATIONS
    from distributed_dot_product_tpu.ops.pallas_experts import (
        _vmem_limit, hidden_tile, hit_experts, hit_list_rows,
    )
    (n, held, wide, hidden, gated, w_dtype), (tile, limit) = (
        _EXPERT_SHAPES[shape])
    assert n <= hit_list_rows(wide)
    itemsize = jnp.dtype(w_dtype).itemsize
    assert hidden_tile(wide, hidden, 2 + gated, itemsize) == tile
    assert _vmem_limit(wide, tile, 2 + gated, itemsize) == limit
    act = ACTIVATIONS['silu' if gated else 'relu2']
    w_in = jax.ShapeDtypeStruct((held, wide, hidden), w_dtype)

    def step(x, gates, hits, count, w_gate, w_up, w_down):
        return hit_experts(x, gates, hits, count, w_gate, w_up, w_down,
                           act, interpret=False)

    compiled = _compile(
        chip, step, jax.ShapeDtypeStruct((n, wide), jnp.bfloat16),
        jax.ShapeDtypeStruct((n, held), jnp.float32),
        jax.ShapeDtypeStruct((held,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), w_in if gated else None,
        w_in, jax.ShapeDtypeStruct((held, hidden, wide), w_dtype))
    assert 'moe_hit_experts' in compiled.as_text()


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


def _relayouts(hlo, t_max, at_least):
    """The optimized HLO's copies and transposes of an array with a
    ``t_max`` axis and at least ``at_least`` bytes: what a latent buffer
    in a layout the chip would not pick (or a consumer that wants
    another) costs a layer a call. (An asynchronous copy between memory
    spaces that keeps the layout — the compiler parking a one-session
    buffer in VMEM — is none.)"""
    found = []
    for line in hlo.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not (m and m.group(3) in ('copy', 'copy-start', 'transpose')
                and re.search(rf'[\[,]{t_max}[\],]', m.group(2))
                and _result_bytes(m.group(2)) >= at_least):
            continue
        ends = re.findall(r'\w+\[[\d,]*\]\{[^}]*\}',
                          re.sub(r'S\(\d+\)', '', m.group(2)))
        if m.group(3) == 'copy-start' and len(set(ends[:2])) == 1:
            continue
        found.append(line.strip()[:160])
    return found


def _latent_prefill_chunk(chip, driver, cfg, traffic, latent_layers):
    """ONE prefill chunk (4096 tokens of one session into its
    33792-column latent buffer) of a latent cell's driver, compiled for
    a described v5e: every latent layer's chunk is written in place —
    the buffers aliased whole, one ``dynamic-update-slice`` a layer on
    the time-minor buffer as the program's parameter holds it — and no
    copy or transpose as large as one layer's buffer has a ``t_max``
    axis (a relayout of the cache, or of the latent on its way into the
    expansion, would hide in ``setup_s``)."""
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    t_max = traffic['t_max']
    caches = jax.eval_shape(lambda: model.make_decode_caches(1, t_max))
    tok = jnp.zeros((1, traffic['prefill_chunk']), jnp.int32)
    prefill = driver.make_programs(model, cfg)[0]
    compiled = prefill.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (params, tok, caches))).compile()
    hlo = compiled.as_text()
    layer_bytes = 576 * t_max * 2
    assert _relayouts(hlo, t_max, layer_bytes) == []
    updates = re.findall(
        rf'= bf16\[(?:{latent_layers},)?1,576,{t_max}\]\S* '
        r'dynamic-update-slice\(', hlo)
    assert len(updates) == latent_layers
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    return hlo


def test_xing4_prefill_chunk_writes_its_columns_in_place(chip, monkeypatch):
    """``xing4-29b-a4b.decode-32k``'s set-up step (three of its six
    layers): :func:`_latent_prefill_chunk`."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_latent as driver
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'xing4-29b-a4b-serve.json')) as f:
        cfg = dict(json.load(f), num_hidden_layers=3)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x16.json')) as f:
        traffic = json.load(f)
    _latent_prefill_chunk(chip, driver, cfg, traffic, 3)


def test_ling_prefill_chunk_writes_its_columns_in_place(chip, monkeypatch):
    """``ling-3.0-flash.decode-32k``'s set-up step (all seven layers,
    ONE of them latent): :func:`_latent_prefill_chunk`."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_ling as driver
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'ling-3.0-flash-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x96.json')) as f:
        traffic = json.load(f)
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    with expert_route_traces() as routes:
        hlo = _latent_prefill_chunk(chip, driver, cfg, traffic, 1)
    # The chunk's 4096 rows take the sorted route, whose picks stay
    # ``lax.top_k``'s on a TPU too (their order is the order its k-way
    # sum adds in): three ``top_k`` sorts a layer, as the parent's
    # chunk holds, and no threshold program.
    assert [(t['route'], t['select']) for t in routes] == 6 * [
        ('sorted', 'sort')]
    assert 'sparse_pick' not in hlo
    assert len([line for line in hlo.splitlines()
                if re.search(SORT, line) and 'lm.moe_route' in line
                and '/top_k' in line]) == 6 * 3


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
    slopes = _mpt_slopes(32)
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


def _shape_table_params(driver, cfg):
    """The parameter tree of a driver's shape table as abstract values,
    in the types the cell serves in."""
    params = {'params': {}}
    for path, (shape, _) in driver.shapes(cfg).items():
        node = params['params']
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = jax.ShapeDtypeStruct(
            shape, jnp.float32 if path[-1] in driver.FLOAT32_LEAVES
            else jnp.bfloat16)
    return params


def test_latent_decode_step_moves_no_cache_and_no_expert_stack(
        chip, monkeypatch):
    """The token step of the latent-attention / sparse-expert model at
    the published widths of ``xing4-29b-a4b.decode-32k`` (one dense and
    two expert layers of its six; 16 sessions x 33792 rows), cache
    donated: the step resolves to ``flash_decode``'s latent mode over
    the layer-stacked cache, which aliases the result; nothing as large
    as one layer of it is copied, sliced or held as a temporary; and no
    layer's 64 experts are moved on their way into the expert kernel (as
    a scanned layer's were, sliced out of the stack: 21.6 ms of a 36.6
    ms step; chip, PR 26). The 16 rows of the step put both expert
    layers on the hit-list route by the rule: one ``moe_hit_experts``
    kernel a layer at 512 hidden columns a grid step, no grouped
    matmul."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_latent as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'xing4-29b-a4b-serve.json')) as f:
        cfg = dict(json.load(f), num_hidden_layers=3)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x16.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    assert caches.rows.shape == (3, sessions, 576, t_max)
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    step = driver.make_programs(model, cfg)[2]
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (params, tok, caches, stats))
    with decode_impl_traces() as traces, expert_route_traces() as routes:
        compiled = step.lower(*shapes).compile()
    assert {(t['resolved'], t['cache']) for t in traces} == {
        ('kernel', 'stacked')}
    assert routes == 2 * [{'route': 'hit_list', 'select': 'threshold',
                           'n': sessions, 'bound': 128,
                           'bound_by': 'rule', 'tile': 512}]
    hlo = compiled.as_text()
    assert hlo.count('mla_decode') and 'ragged-dot' not in hlo
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_hit_experts',
        hlo)) == 2
    layer_bytes = sessions * t_max * 576 * 2
    experts_bytes = 64 * 3584 * 1024 * 2
    assert _cache_sized_moves(hlo, min(layer_bytes, experts_bytes)) == []
    assert _relayouts(hlo, t_max, layer_bytes) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * layer_bytes
    assert mem.temp_size_in_bytes < experts_bytes
    # The engage counter: the stored bytes a grid step, 1536 columns of
    # 576 values (640-wide rows of 1024 were 1 310 720).
    assert {t['step']['bytes'] for t in traces} == {1536 * 576 * 2}


def test_mixed_stack_decode_step_moves_no_cache_and_fits(chip,
                                                         monkeypatch):
    """The token step of the window + full attention stack at the
    published widths and the traffic of ``command-a-plus.decode-64k``
    (4 layers, 12 sessions, a 5120-column ring a window layer beside a
    66560-row slab), caches donated: every layer's step resolves to the
    kernel, the window layers' to its ring mode; nothing as large as one
    window layer's K ring (126 MB: far under the full layer's 1.6 GB
    cache, and under one 134 MB projection kernel too, which the
    strided-slice rotary re-laid out a layer a token) is copied, sliced
    or written back; every cache aliases the result; and arguments +
    temporaries stay under 15.0 GiB."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_mixed as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'command-a-plus-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-64k-x12.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    assert [c.k.shape for c in caches] == 3 * [
        (sessions, 8, 5120, 128)] + [(sessions, 8, t_max, 128)]
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    step = driver.make_programs(model, cfg)[2]
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (params, tok, caches, stats))
    with decode_impl_traces() as traces, expert_route_traces() as routes:
        compiled = step.lower(*shapes).compile()
    assert [(t['resolved'], t['cache']) for t in traces] == 3 * [
        ('kernel', 'ring')] + [('kernel', 'layer')]
    assert {tuple(t['step'].items()) for t in traces} == {
        (('heads', 8), ('block_k', 1024), ('bytes', 4 << 20),
         ('heads_a_pass', 1))}
    # 12 rows: the held experts' hit list, by the rule, in every layer
    assert routes == 4 * [{'route': 'hit_list', 'select': 'threshold',
                           'n': sessions, 'bound': 128,
                           'bound_by': 'rule', 'tile': 512}]
    hlo = compiled.as_text()
    assert hlo.count('flash_decode_ring') and 'ragged-dot' not in hlo
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_hit_experts',
        hlo)) == 4
    ring_k_bytes = sessions * 8 * 5120 * 128 * 2
    assert _cache_sized_moves(hlo, ring_k_bytes) == []
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < ring_k_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            ) <= 15.0 * 2 ** 30


def test_hybrid_stack_decode_step_aliases_every_state_and_fits(
        chip, monkeypatch):
    """The token step of the recurrent + attention + latent-expert stack
    at the published widths and the traffic of
    ``nemotron-3-super.decode-32k`` (11 layers, 48 sessions, five
    ``(48, 128, 64, 128)`` float32 states beside one 33792-row slab of 2
    KV heads), caches donated: the attention layer's step resolves to
    the kernel at 2 KV heads x 1024 rows; every state and the slab alias
    the result; nothing as large as one layer's 48 states (201 MB) is
    copied, sliced or written back, and no temporary is that large (the
    state update and its read against C are one fusion; an expert
    layer's 48-token step is the ``moe_hit_experts`` kernel over its hit
    list, whose activations never leave VMEM); arguments + temporaries
    stay under 15.0 GiB. The reset between requests writes every state
    over in place, under its scope's name, with no temporary."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_hybrid as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'nemotron-3-super-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x48.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    assert [None if c is None else tuple(x.shape for x in c[:2])
            for c in caches] == 5 * [None, (
                (sessions, 128, 64, 128), (sessions, 3, 10240))] + [
                    2 * ((sessions, 2, t_max, 128),)]
    assert caches[1].state.dtype == jnp.float32
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    programs = driver.make_programs(model, cfg)
    restore, step = programs[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with decode_impl_traces() as traces, expert_route_traces() as routes:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert [(t['resolved'], t['cache'], t['step']) for t in traces] == [
        ('kernel', 'layer', {'heads': 2, 'block_k': 1024,
                             'bytes': 1 << 20, 'heads_a_pass': 1})]
    # the driver's own bound, honoured as it was
    assert routes == 5 * [{'route': 'hit_list', 'select': 'threshold',
                           'n': sessions, 'bound': 64,
                           'bound_by': 'caller', 'tile': 896}]
    hlo = compiled.as_text()
    # 48 tokens: every hit expert on every token, one kernel an expert
    # layer, no grouped matmul and no batched one over all held experts
    assert 'flash_decode' in hlo and 'ragged-dot' not in hlo
    assert 'lm.moe_experts/moe_hit_experts/' in hlo
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_hit_experts',
        hlo)) == 5
    # ... whose (held, tokens, hidden) activations no longer exist
    assert not re.findall(r'(bf16|f32)\[128,48,2688\]', hlo)
    state_bytes = sessions * 128 * 64 * 128 * 4
    assert _cache_sized_moves(hlo, state_bytes) == []
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            ) <= 15.0 * 2 ** 30
    states = [c if hasattr(c, 'state') else None for c in caches]
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    # Everything but the slab's 4-byte length, which is set, not kept.
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 4)
    assert restored.as_text().count('lm.state_restore') >= 10


def test_granite_decode_step_aliases_nine_states_and_fits(chip, monkeypatch):
    """The token step of the two-branch recurrent / attention + expert
    stack at the published widths and the traffic of
    ``granite-4.0-h-small.decode-4k`` (10 layers, 80 sessions, nine
    ``(80, 128, 64, 128)`` float32 states of ONE B / C group beside one
    5120-row slab of 8 KV heads), caches donated: the attention layer's
    step resolves to the kernel; all nine states and the slab alias the
    result; nothing as large as one layer's 80 states (335 MB) is
    copied, sliced or written back and no temporary is that large (the
    state update and its read against C stay one fusion a layer); every
    layer's 80-row expert call is ONE ``moe_hit_experts`` kernel by the
    rule's bound, a whole 768-wide expert a grid step, and no grouped
    matmul; arguments + temporaries stay under 14.0 GiB with the
    snapshot counted. The reset between requests writes all nine states
    over in place, under its scope's name, with no temporary."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_granite as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    from distributed_dot_product_tpu.ops.pallas_experts import _vmem_limit
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'granite-4.0-h-small-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-4k-x80.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    state = ((sessions, 128, 64, 128), (sessions, 3, 8448))
    slab = 2 * ((sessions, 8, t_max, 128),)
    assert [tuple(x.shape for x in c[:2]) for c in caches] == (
        5 * [state] + [slab] + 4 * [state])
    assert caches[0].state.dtype == jnp.float32
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    restore, step = driver.make_programs(model, cfg)[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with decode_impl_traces() as traces, expert_route_traces() as routes:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert [(t['resolved'], t['cache']) for t in traces] == [
        ('kernel', 'layer')]
    # 768 has no 512-column divisor: the 1 KB slab rule takes the whole
    # expert, 18.9 MB a grid step, and asks for its blocks' room.
    assert routes == 10 * [{'route': 'hit_list', 'select': 'threshold',
                            'n': sessions, 'bound': 128,
                            'bound_by': 'rule', 'tile': 768}]
    assert _vmem_limit(4096, 768, 3, 2) == 52 << 20
    hlo = compiled.as_text()
    assert 'flash_decode' in hlo and 'ragged-dot' not in hlo
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*moe_hit_experts',
        hlo)) == 10
    state_bytes = sessions * 128 * 64 * 128 * 4
    assert _cache_sized_moves(hlo, state_bytes) == []
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes
    states = [c if hasattr(c, 'state') else None for c in caches]
    snapshot_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                         for x in jax.tree.leaves(states))
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + snapshot_bytes) <= 14.0 * 2 ** 30
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    # Everything but the slab's 4-byte length, which is set, not kept.
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 4)
    assert restored.as_text().count('lm.state_restore') >= 18


def _entry_readers(hlo, shape):
    """The ENTRY computation's fusions and custom calls that take an
    operand of type ``shape`` (``'f32[128,64,128,128]'``): who reads a
    buffer of that type."""
    entry = hlo.split('ENTRY ')[1]
    types = dict(re.findall(r'(%[\w.-]+) = \(?([a-z0-9]+\[[\d,]*\])', entry))
    return [line.strip()[:200] for line in entry.splitlines()
            if re.search(r' (fusion|custom-call)\(', line)
            and any(types.get(name) == shape for name in re.findall(
                r'%[\w.-]+', line.split('(', 1)[1].split('),')[0]))]


def test_delta_step_kernel_compiles_and_xla_reads_the_state_twice(chip):
    """One token of a gated delta-rule layer at the widths of
    ``solar-open2-250b.decode-4k`` (128 sessions x 64 heads of ``(128,
    128)`` float32), the state donated: the kernel ``delta_step`` is ONE
    custom call with the state aliased and no state-sized temporary; the
    plain XLA form is two fusions that each take the state — the
    reduction against k, then the update — which is why there is a
    kernel."""
    from distributed_dot_product_tpu.ops.pallas_delta import (
        delta_step, delta_step_reference, heads_tile,
    )
    b, h, d = 128, 64, 128
    assert heads_tile(h, d, d) == 16         # 1 MiB of state a grid step
    vec = jnp.zeros((b, h, d), jnp.float32)
    args = (vec, vec, vec, vec, jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, h, d, d), jnp.float32))
    state_bytes = b * h * d * d * 4
    state = f'f32[{b},{h},{d},{d}]'
    for fn, readers in ((lambda *a: delta_step(*a, interpret=False), 1),
                        (delta_step_reference, 2)):
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), args)
        compiled = jax.jit(fn, donate_argnums=(5,)).lower(*shapes).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == state_bytes
        assert mem.temp_size_in_bytes < state_bytes // 8
        takers = _entry_readers(compiled.as_text(), state)
        assert len(takers) == readers, takers
        assert all('delta_step' in t for t in takers) == (readers == 1)


def test_solar_decode_step_reads_three_states_once_and_fits(
        chip, monkeypatch):
    """The token step of the delta-rule / gated-attention + expert stack
    at the published widths and the traffic of
    ``solar-open2-250b.decode-4k`` (4 layers, 128 sessions, three ``(128,
    64, 128, 128)`` float32 states with 24576-channel windows beside one
    5120-row slab of 8 KV heads), caches donated: the attention layer's
    step resolves to the kernel; every delta mixer's step is the kernel
    ``delta_step``, 16 heads a grid step — three custom calls, each
    state aliased and read by nothing else; nothing as large as one
    layer's 128 states (537 MB) is copied, sliced or written back and no
    temporary is that large; every layer's 128-row expert call — the
    rule's bound itself — is ONE ``moe_hit_experts`` kernel, two 640-wide
    tiles an expert (1280 has no 512-column divisor) under the
    ``vmem_limit_bytes`` the plan states, and no grouped matmul;
    arguments + temporaries stay under 14.0 GiB with the snapshot
    counted. The reset between requests writes all three states over in
    place, under its scope's name, with no temporary."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_solar as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.delta import delta_step_traces
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    from distributed_dot_product_tpu.ops.pallas_experts import (
        HIT_LIST_ROWS, _vmem_limit, hidden_tile,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'solar-open2-250b-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-4k-x128.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    assert sessions == HIT_LIST_ROWS
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    state = ((sessions, 64, 128, 128), (sessions, 3, 24576))
    slab = 2 * ((sessions, 8, t_max, 128),)
    assert [tuple(x.shape for x in c[:2]) for c in caches] == (
        [slab] + 3 * [state])
    assert caches[1].state.dtype == jnp.float32
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    restore, step = driver.make_programs(model, cfg)[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with decode_impl_traces() as traces, expert_route_traces() as routes, \
            delta_step_traces() as forms:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert [(t['resolved'], t['cache']) for t in traces] == [
        ('kernel', 'layer')]
    assert forms == 3 * [{'form': 'pallas', 'tile': 16, 'chunk': 64}]
    assert hidden_tile(4096, 1280, 3, 2) == 640
    assert routes == 4 * [{'route': 'hit_list', 'select': 'threshold',
                           'n': sessions, 'bound': 128, 'bound_by': 'rule',
                           'tile': 640}]
    # three blocks of 5.24 MB, double-buffered, and the default on top
    assert _vmem_limit(4096, 640, 3, 2) == 46 << 20
    hlo = compiled.as_text()
    assert 'flash_decode' in hlo and 'ragged-dot' not in hlo
    for kernel, calls in (('moe_hit_experts', 4), ('delta_step', 3)):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel,
            hlo)) == calls
    state_bytes = sessions * 64 * 128 * 128 * 4
    assert _cache_sized_moves(hlo, state_bytes) == []
    # Each state is an operand of its kernel and of nothing else: read
    # once, written once.
    takers = _entry_readers(hlo, f'f32[{sessions},64,128,128]')
    assert len(takers) == 3 and all('delta_step' in t for t in takers)
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes
    states = [c if hasattr(c, 'state') else None for c in caches]
    snapshot_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                         for x in jax.tree.leaves(states))
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + snapshot_bytes)
    assert 11.0 * 2 ** 30 <= peak <= 14.0 * 2 ** 30
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    # Everything but the slab's 4-byte length, which is set, not kept.
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 4)
    assert restored.as_text().count('lm.state_restore') >= 6


def _head_ops(hlo):
    """``(convolutions, Mosaic calls)`` under ``lm.head_loss`` in a
    compiled step, by their ``op_name``."""
    under = r'op_name="([^"]*/lm\.head_loss/[^"]*)"'
    return (re.findall(r' convolution\([^\n]*' + under, hlo),
            re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                       + under, hlo))


@pytest.mark.parametrize('remat_policy, forwards, head', [
    (None, 1, 'kernel'), ('nothing_saveable', 2, 'kernel'),
    (None, 1, 'xla')], ids=['kept', 'full-remat', 'kept-xla-head'])
def test_scanned_lm_train_step_runs_the_flash_forward_once(
        chip, monkeypatch, remat_policy, forwards, head):
    """The scanned, rematted LM train step at the MPT training cell's
    shapes (2 layers x 16384 tokens, bfloat16 compute): the stack keeps
    the flash forward's output and logsumexp, and XLA drops the
    recompute's kernel with them — the compiled step holds ONE
    ``flash_fwd`` custom call beside the fused backward, none of them
    rematerialized, and the kept tensors ride the scan as stacked
    ``(L, B, H, T, d)`` / ``(L, B, H, T)`` buffers (not as the kernel's
    lane-padded ``(nb, T, 1)`` logsumexp). Full remat, by its name, holds
    the forward twice. The head takes its gradient in the forward pass,
    by the route ``head_loss_traces()`` names: a chunk's logits matmul
    and ONE ``head_grad`` program, or — the rule refusing, as it does a
    shape under its tiles — the three matmuls of the XLA body."""
    import optax
    from distributed_dot_product_tpu import TransformerLM
    from distributed_dot_product_tpu.models import lm
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_lm_train_step
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    if head == 'xla':
        monkeypatch.setattr(lm, 'head_tiles',
                            lambda *shape: (None, 'refused by the test'))
    t, layers = 16384, 2
    model = TransformerLM(**{**LM, 'n_layers': layers}, dtype=jnp.bfloat16,
                          scan_layers=True, remat=True,
                          remat_policy=remat_policy,
                          attn_kwargs=dict(use_rope=False,
                                           alibi_slopes=_mpt_slopes(32)))
    optimizer = optax.adamw(3e-4)
    (device,) = chip.device_set
    step = make_lm_train_step(model, optimizer,
                              seq_mesh(1, devices=[device]), guard=False,
                              loss_chunk=4096)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 128),
                                                        jnp.int32)))
    tok = jnp.zeros((1, t), jnp.int32)
    with lm.head_loss_traces() as traces:
        hlo = _compile(chip, step, params,
                       jax.eval_shape(optimizer.init, params),
                       (tok, tok), donate=(0, 1)).as_text()
    assert [r['route'] for r in traces] == [head]
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', hlo)
    fwd = [n for n in calls if n.endswith('flash_fwd/pallas_call')]
    assert len(fwd) == forwards
    assert len(calls) == forwards + 1 + (head == 'kernel')
    assert sum('rematted_computation' in n for n in fwd) == forwards - 1
    for stacked in (f'bf16[{layers},1,32,{t},128]', f'f32[{layers},1,32,{t}]'):
        assert (stacked in hlo) == (forwards == 1)
    assert f'f32[{layers},32,{t},1]' not in hlo
    # The head takes its gradient in the forward pass: a loss chunk's
    # logits matmul and the kernel that gives dx and dW (the XLA body:
    # the logits, dx and dW matmuls) and no further matmul, nothing of
    # it rebuilt. (Parameters and optimizer state donated, as a training
    # loop does: held twice they pass the chip's memory, and XLA's own
    # rematerialization then rebuilds a chunk's logits for dW.)
    matmuls, kernels = _head_ops(hlo)
    assert (len(matmuls), len(kernels)) == (
        (1, 1) if head == 'kernel' else (3, 0))
    assert all(n.endswith('head_grad/pallas_call') for n in kernels)
    assert all('transpose(jvp(' not in n and 'jvp(' in n
               and 'rematted_computation' not in n
               for n in matmuls + kernels)
    assert not re.findall(XLAS_OWN_REMAT, hlo)


@pytest.mark.parametrize('rows, dim, vocab, logit_scale', [
    (4096, 4096, 50432, 1.0), (1024, 1024, 50257, 0.5)],
    ids=['mpt-chunk', 'ragged-vocab'])
def test_head_grad_kernel_compiles_for_v5e(chip, rows, dim, vocab,
                                           logit_scale):
    """``ops.pallas_head.head_grad`` under the ``vmem_limit_bytes`` it
    states: the MPT training cell's chunk, where the rule's 1024-row
    group holds exactly the 16 MiB of float32 dx it budgets (dW blocks
    of 512 vocabulary rows, 118 MiB of the chip's 128 in all), and a
    vocabulary that is no multiple of any tile, whose last block is
    masked inside the kernel."""
    from distributed_dot_product_tpu.ops.pallas_head import (
        head_grad, head_tiles,
    )
    tiles, why = head_tiles(rows, dim, vocab, jnp.bfloat16)
    assert why is None and tiles['vocab_tile'] == 512
    assert tiles['row_group'] == 1024

    def fn(logits, lse, targets, x, table, dw):
        return head_grad(logits, lse, targets, x, table, dw,
                         logit_scale=logit_scale, tiles=tiles,
                         interpret=False)

    _compile(chip, fn, jax.ShapeDtypeStruct((rows, vocab), jnp.float32),
             jax.ShapeDtypeStruct((rows,), jnp.float32),
             jax.ShapeDtypeStruct((rows,), jnp.int32),
             jax.ShapeDtypeStruct((rows, dim), jnp.bfloat16),
             jax.ShapeDtypeStruct((vocab, dim), jnp.bfloat16),
             jax.ShapeDtypeStruct((vocab, dim), jnp.float32), donate=(5,))


V5E_BYTES_LIMIT = 16909336064      # what a v5e chip reports
XLAS_OWN_REMAT = r'%[\w.-]+\.remat\d* = '


def _train_step_for_v5e(chip, model, optimizer):
    """``make_lm_train_step`` at 16384 tokens compiled for the described
    chip under the stack's default fit; ``(compiled, what
    remat_traces() said, None of a policy by hand)``."""
    from distributed_dot_product_tpu.models import remat
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_lm_train_step
    (device,) = chip.device_set
    step = make_lm_train_step(model, optimizer,
                              seq_mesh(1, devices=[device]), guard=False,
                              loss_chunk=4096)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 128),
                                                        jnp.int32)))
    tok = jnp.zeros((1, 16384), jnp.int32)
    with remat.remat_traces() as traces:
        compiled = _compile(chip, step, params,
                            jax.eval_shape(optimizer.init, params),
                            (tok, tok), donate=(0, 1))
    assert (compiled.memory_analysis().peak_memory_in_bytes
            < V5E_BYTES_LIMIT)
    if not traces:
        return compiled, None
    # The limit is the described device's kind's: nothing is patched.
    assert traces[-1]['limit'] == V5E_BYTES_LIMIT
    return compiled, traces[-1]


def _rebuilt_matmuls(hlo):
    return re.findall(r' convolution\([^\n]*op_name="([^"]*'
                      r'rematted_computation[^"]*)"', hlo)


@pytest.mark.parametrize('config, kept, refused', [
    ('mpt-7b', ('mlp_hidden', 'flash_qkv', 'attn_out'), None),
    ('starcoder2-3b', ('mlp_hidden',), 'flash_qkv'),
])
def test_training_cells_keep_what_fits_a_v5e(chip, monkeypatch, config,
                                             kept, refused):
    """Both training cells' steps (``benchmarks/system.build_lm`` at the
    cell's depth, 16384 tokens, AdamW) with the fit reckoned against the
    described v5e's ``bytes_limit``: MPT's two layers keep all of
    ``LAYER_MATMUL_NAMES``, StarCoder2's five the MLP's pre-activation
    alone. The step compiles, so it fits — and WITHOUT a
    rematerialization of XLA's own (no ``.remat`` instruction: with q / k
    / v kept too StarCoder2's step compiles only because XLA rebuilds
    ``mlp_in`` itself, and runs slower than the parent's; chip, PR 37).
    The kept tensors ride the scan as stacked buffers with a leading
    layer axis, and no matmul behind a kept name is left in the
    rematerialized body."""
    import json
    from benchmarks import system
    from benchmarks.drivers.train import make_optimizer
    from distributed_dot_product_tpu.models.lm import head_loss_traces
    from distributed_dot_product_tpu.ops.pallas_attention import (
        FLASH_RESIDUAL_NAMES, flash_block_traces,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, 'benchmarks', 'configs',
                           f'{config}.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'train-16k.json')) as f:
        optimizer = make_optimizer(json.load(f)['optimizer'])
    model = system.build_lm(cfg)
    with flash_block_traces() as blocks, head_loss_traces() as heads:
        compiled, record = _train_step_for_v5e(chip, model, optimizer)
    # Both cells' loss chunks take the head's kernel, by the rule.
    assert heads == [{'route': 'kernel', 'rows': 4096, 'row_group': 1024,
                      'vocab_tile': 512, 'row_tile': 1024, 'why': None}]
    # Inside the scanned, rematted layer too the kernels see the call's
    # offsets as the plain ints they are: MPT's forward takes the
    # trapezoid grid like its backward (as custom_vjp operands the ints
    # reached the forward rule as tracers and the forward ran the full
    # grid, 120 skipped programs a head), and the kinds are counted.
    # (``model.init``'s 128-token call, traced in there too, is one block.)
    blocks = [b for b in blocks if b['run_blocks'] != 1]
    assert all({k: b[k] for k in _CELL_BLOCKS[f'{config}.train-16k']}
               == _CELL_BLOCKS[f'{config}.train-16k'] for b in blocks)
    assert {b['kernel'] for b in blocks} == {'flash_fwd',
                                             'flash_bwd_fused'}
    assert record['kept'] == (*FLASH_RESIDUAL_NAMES, *kept)
    assert record['first_refused'] == refused
    t, layers, hidden = 16384, model.n_layers, model.mlp_ratio * model.dim
    assert record['layer_bytes']['mlp_hidden'] == t * hidden * 2
    hlo = compiled.as_text()
    assert not re.findall(XLAS_OWN_REMAT, hlo)
    matmuls, kernels = _head_ops(hlo)
    assert len(matmuls) == len(kernels) == 1
    assert all('transpose(jvp(' not in n and 'jvp(' in n
               for n in matmuls + kernels)
    for stacked in (f'bf16[{layers},1,{t},{hidden}]',
                    f'bf16[{layers},1,{model.num_heads},{t},128]'):
        assert stacked in hlo
    rebuilt = _rebuilt_matmuls(hlo)
    assert not [n for n in rebuilt if '/mlp_in/' in n]
    assert bool([n for n in rebuilt
                 if re.search('/(keys|queries|values)/', n)]) == (
        'flash_qkv' not in kept)
    assert bool([n for n in rebuilt if '/composition/' in n]) == (
        'attn_out' not in kept)


def test_the_fit_holds_at_a_width_it_was_not_fitted_on(chip, monkeypatch):
    """The fit's two constants were fitted at the two training cells'
    widths. A step of another shape — 2048 wide, 16 heads, MLP 8192,
    eight layers, a 49152-token untied head, plain ``optax.adamw`` —
    compiled for the described v5e: the fit takes ``mlp_hidden`` and
    refuses q / k / v, the step fits, and XLA adds no rematerialization
    of its own to the pick. The refusal is not caution: with q / k / v
    kept by hand (``remat_policy`` takes a policy) the step compiles
    only with XLA's own ``.remat`` instructions. (At six and seven
    layers the fit keeps all three names, free of them too; AOT,
    PR 37.)"""
    import optax
    from distributed_dot_product_tpu import TransformerLM
    from distributed_dot_product_tpu.models.remat import LAYER_MATMUL_NAMES
    from distributed_dot_product_tpu.ops.pallas_attention import (
        FLASH_RESIDUAL_NAMES,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    model = TransformerLM(
        vocab_size=49152, dim=2048, num_heads=16, n_layers=8,
        dtype=jnp.bfloat16, scan_layers=True, remat=True,
        tie_embeddings=False, attn_kwargs=dict(causal=True))
    compiled, record = _train_step_for_v5e(chip, model, optax.adamw(3e-4))
    assert record['kept'] == (*FLASH_RESIDUAL_NAMES, 'mlp_hidden')
    assert record['first_refused'] == 'flash_qkv'
    hlo = compiled.as_text()
    assert not re.findall(XLAS_OWN_REMAT, hlo)
    assert 'bf16[8,1,16384,8192]' in hlo
    rebuilt = _rebuilt_matmuls(hlo)
    assert not [n for n in rebuilt if '/mlp_in/' in n]
    assert [n for n in rebuilt if '/queries/' in n]
    by_hand = model.clone(
        remat_policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES[:2]))
    two, fit = _train_step_for_v5e(chip, by_hand, optax.adamw(3e-4))
    assert fit is None and re.findall(XLAS_OWN_REMAT, two.as_text())


SORT = r'\bsort\('


def _mosaic_calls(hlo):
    """The Pallas ``name=`` of every Mosaic custom call, in the text's
    order."""
    return re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                      r'/(\w+)/pallas_call', hlo)


@pytest.mark.parametrize('shape', [
    # (sessions, heads, KV heads, t_max, block, picks): the cell's call,
    # a pick list that is one group, and a wide group of query heads.
    (64, 32, 2, 66560, 64, 128), (4, 8, 2, 4096, 64, 16),
    (2, 64, 1, 8192, 128, 64)], ids=['cell', 'one_group', 'mqa'])
def test_sparse_decode_kernel_compiles_for_v5e(chip, shape):
    """``ops/pallas_sparse.sparse_decode`` for a described v5e, the K/V
    buffers donated: ONE Mosaic call whose grid is (sessions, KV heads)
    — not a grid step a picked block —, both buffers aliased, no
    temporary as large as a session's rows."""
    from distributed_dot_product_tpu.ops.pallas_sparse import (
        picks_group, sparse_decode,
    )
    b, h, kv, t_max, block, picks = shape
    d = 128
    bf = jnp.bfloat16
    args = (jnp.zeros((b, h, 1, d), bf), jnp.zeros((b, kv, 1, d), bf),
            jnp.zeros((b, kv, 1, d), bf),
            jnp.zeros((b, kv, t_max, d), bf),
            jnp.zeros((b, kv, t_max, d), bf),
            jnp.zeros((b, kv, picks), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    assert picks_group(picks, block) * block <= 1024
    compiled = _compile(
        chip, lambda *a: sparse_decode(*a, block=block, interpret=False),
        *args, donate=(3, 4))
    hlo = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                          r'sparse_decode', hlo)) == 1
    mem = compiled.memory_analysis()
    buffers = 2 * b * kv * t_max * d * 2
    assert mem.alias_size_in_bytes == buffers
    assert mem.temp_size_in_bytes < 2 * kv * t_max * d * 2


@pytest.mark.parametrize('shape', [
    # (rows, experts, k): the six expert cells' hit-list calls, and the
    # one-session step of ``chip_smoke.py``.
    (96, 512, 8), (80, 72, 10), (48, 512, 22), (128, 320, 8),
    (12, 128, 8), (16, 64, 4), (1, 512, 8)],
    ids=['ling', 'granite', 'nemotron', 'solar', 'command-a', 'xing4',
         'one_row'])
def test_the_routers_pick_program_compiles_for_v5e(chip, shape):
    """``ops/pallas_sparse.threshold_picks`` with its MASK — what a
    hit-list expert layer picks by on a TPU since PR 49 — for a
    described v5e: ONE ``sparse_pick`` call that gives the ``(rows, k)``
    picks and the ``(rows, experts)`` mask, and no sort beside it."""
    from distributed_dot_product_tpu.ops.pallas_sparse import (
        threshold_picks,
    )
    rows, experts, k = shape
    compiled = _compile(
        chip, lambda s: threshold_picks(s, k, mask=True, interpret=False),
        jnp.zeros((rows, experts), jnp.float32))
    hlo = compiled.as_text()
    assert _mosaic_calls(hlo) == ['sparse_pick']
    assert not re.findall(SORT, hlo)
    picks, mask = compiled.out_info
    assert (picks.shape, picks.dtype) == ((rows, k), jnp.int32)
    assert (mask.shape, mask.dtype) == ((rows, experts), jnp.bool_)


def _sala_cell():
    """``minicpm-sala.decode-64k`` as its driver builds it: ``(driver,
    configuration, traffic, model, abstract parameters)``."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_sala as driver
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'minicpm-sala-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-64k-x64.json')) as f:
        traffic = json.load(f)
    return (driver, cfg, traffic, driver.build_lm(cfg),
            _shape_table_params(driver, cfg))


def test_sala_decode_step_picks_its_rows_reads_three_states_once_and_fits(
        chip, monkeypatch):
    """The token step of the block-sparse / Lightning stack at the
    published widths and the traffic of ``minicpm-sala.decode-64k`` (4
    layers, 64 sessions, one 66560-row slab of 2 KV heads with 4160
    pooled rows beside three ``(64, 32, 128, 128)`` float32 states),
    caches donated: the sparse layer's step is the kernel
    ``sparse_decode`` over a pick list of 128 entries of which 64 are
    read above ``dense_len``, 16 picks a group, and the list comes from
    ONE call of ``sparse_pick`` (the threshold over all 128 (session, KV
    head) rows) — the step's two custom calls, and no ``sort`` in its
    text; every Lightning state is taken by ONE fusion
    (read once, written once: no Pallas kernel is owed); nothing as
    large as a layer's 64 states (134 MB) is copied, sliced or written
    back but the pooled buffer's one-row update in place, and no
    temporary is that large; arguments + temporaries with
    the snapshot counted stay between 8 and 10 GiB. The reset between
    requests writes the three states over in place."""
    from distributed_dot_product_tpu.models.decode import (
        SparseCache, StateCache, sparse_decode_traces,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    driver, cfg, traffic, model, params = _sala_cell()
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    assert [type(c) for c in caches] == [SparseCache] + 3 * [StateCache]
    assert caches[0].k.shape == (sessions, 2, t_max, 128)
    assert caches[0].pooled.shape == (sessions, 2, t_max // 16, 128)
    assert caches[1].state.shape == (sessions, 32, 128, 128)
    assert caches[1].state.dtype == jnp.float32
    assert caches[1].conv.shape[1] == 0          # no convolution window
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    restore, step = driver.make_programs(model, cfg)[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with sparse_decode_traces() as forms:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert forms == [{'impl': 'kernel', 'picks': 128, 'topk': 64,
                      'group': 16, 'select': 'threshold'}]
    hlo = compiled.as_text()
    assert _mosaic_calls(hlo) == ['sparse_pick', 'sparse_decode']
    assert not re.findall(SORT, hlo)
    state_bytes = sessions * 32 * 128 * 128 * 4
    # (the one move of that size is the pooled buffer's in-place write
    # of the ONE row a step completes: 136 MB by its result type)
    moves = _cache_sized_moves(hlo, state_bytes)
    assert len(moves) == 1 and 'dynamic-update-slice(%c_0__pooled' in (
        moves[0])
    takers = _entry_readers(hlo, f'f32[{sessions},32,128,128]')
    assert len(takers) == 3 and all(' fusion(' in t for t in takers)
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes
    states = [c if hasattr(c, 'state') else None for c in caches]
    snapshot_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                         for x in jax.tree.leaves(states))
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + snapshot_bytes)
    assert 8.0 * 2 ** 30 <= peak <= 10.0 * 2 ** 30
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 4)
    assert restored.as_text().count('lm.state_restore') >= 6


def test_sala_prefill_chunk_picks_its_blocks_without_a_sort(
        chip, monkeypatch):
    """A 4096-token context chunk of one session of
    ``minicpm-sala.decode-64k`` for a described v5e: every row of the
    chunk picks its 64 blocks of 1040 through ``sparse_pick`` — one
    call in the text (the body of the map over groups of 512 query
    rows, 1024 (KV head, row) pairs a call: eight grid steps of 128) —
    and the program holds no ``sort``; the flash forward under the
    picks' block mask is the other kernel, a call a KV head."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    driver, cfg, traffic, model, params = _sala_cell()
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(1, traffic['t_max']))
    tok = jnp.zeros((1, traffic['prefill_chunk']), jnp.int32)
    prefill = driver.make_programs(model, cfg)[0]
    compiled = prefill.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (params, tok, caches))).compile()
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert calls.count('sparse_pick') == 1
    assert set(calls) - {'sparse_pick'} == {'flash_fwd'}
    assert not re.findall(SORT, hlo)


def test_ling_decode_step_reads_latent_rows_and_six_states_once_and_fits(
        chip, monkeypatch):
    """The token step of the delta-rule / latent-attention + expert stack
    at the published widths and the traffic of
    ``ling-3.0-flash.decode-32k`` (7 layers, 96 sessions: six ``(96, 32,
    128, 128)`` float32 states with 12288-channel windows beside ONE
    layer's time-minor latent buffer ``(96, 576, 33792)``), caches
    donated: the MLA
    layer's step resolves to the kernel's latent mode over its own
    buffer (``kernel:latent``), ONE ``mla_decode`` call; every delta
    mixer's step is the kernel ``delta_step`` — six custom calls, each
    state aliased and read by nothing else; the latent buffer is aliased
    and nothing as large as one layer's 96 states (201 MB) is copied,
    sliced or written back, no temporary is that large; every expert
    layer's 96-row call is ONE ``moe_hit_experts`` kernel by the rule's
    bound and no grouped matmul, and its choices — a group's two best,
    the kept groups, the 8 picks — hold no sort (since PR 49: the parent
    had three ``top_k`` a layer): ONE ``sparse_pick`` call a layer, the
    gate table and the counts from its mask, and the only scatter left
    under ``lm.moe_route`` is the hit list's; arguments + temporaries
    stay under 14.0 GiB with the snapshot counted. The reset between
    requests writes all six states over in place, under its scope's
    name, with no temporary, and sets the latent lengths back."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_ling as driver
    from distributed_dot_product_tpu.models.decode import (
        decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.delta import delta_step_traces
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    from distributed_dot_product_tpu.ops.pallas_experts import hidden_tile
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'ling-3.0-flash-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-32k-x96.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    assert driver.layer_kinds(cfg) == list('DKKKKKA')
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    state = ((sessions, 32, 128, 128), (sessions, 3, 12288))
    assert [tuple(x.shape for x in c[:2]) for c in caches] == (
        6 * [state] + [((sessions, 576, t_max), (sessions,))])
    assert caches[0].state.dtype == jnp.float32
    assert caches[6].rows.dtype == jnp.bfloat16
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    restore, step = driver.make_programs(model, cfg)[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with decode_impl_traces() as traces, expert_route_traces() as routes, \
            delta_step_traces() as forms:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert [(t['resolved'], t['cache'], t['step']['bytes'])
            for t in traces] == [('kernel', 'latent', 1536 * 576 * 2)]
    assert forms == 6 * [{'form': 'pallas', 'tile': 16, 'chunk': 64}]
    tile = hidden_tile(2560, 768, 3, 2)
    assert routes == 6 * [{'route': 'hit_list', 'select': 'threshold',
                           'n': sessions, 'bound': 128, 'bound_by': 'rule',
                           'tile': tile}]
    hlo = compiled.as_text()
    assert 'ragged-dot' not in hlo
    for kernel, calls in (('moe_hit_experts', 6), ('delta_step', 6),
                          ('mla_decode', 1), ('sparse_pick', 6)):
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*' + kernel,
            hlo)) == calls, kernel
    assert not re.findall(SORT, hlo)
    routing = [line for line in hlo.splitlines() if 'lm.moe_route' in line]
    assert routing and not [line for line in routing
                            if re.search(r'top-?k', line, re.I)]
    assert len([line for line in routing
                if re.search(r'\bscatter\(', line)]) == 6
    state_bytes = sessions * 32 * 128 * 128 * 4
    assert _cache_sized_moves(hlo, state_bytes) == []
    assert _relayouts(hlo, t_max, sessions * 576 * t_max * 2) == []
    # Each state is an operand of its kernel and of nothing else: read
    # once, written once.
    takers = _entry_readers(hlo, f'f32[{sessions},32,128,128]')
    assert len(takers) == 6 and all('delta_step' in t for t in takers)
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < state_bytes
    states = [c if hasattr(c, 'state') else None for c in caches]
    snapshot_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                         for x in jax.tree.leaves(states))
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + snapshot_bytes)
    assert 11.0 * 2 ** 30 <= peak <= 14.0 * 2 ** 30, peak / 2 ** 30
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    # Everything but the latent lengths (96 x 4 B), which are set.
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 4 * sessions)
    assert restored.as_text().count('lm.state_restore') >= 12


# ``flash_decode``'s PACKED mode (keys and values of a 64-wide head in
# the two halves of one 128-lane row) at the edges of its plan: the new
# cell's own call (8 KV heads of a slot a step, the tail), a multi-head
# layer whose 16 heads do not fit one step, a verify-k step (the whole
# split written back, no tail), a buffer of one split (no tail). Since
# PR 52 a step of an even number of heads is scored two heads a pass —
# the cell's call (256 x 8 x 5120 x 128, group 4) is the PAIR body with
# its two block-sized temporaries inside the plan, under the compiler's
# default VMEM; one head and three keep the single-head body.
# (b, h, h_kv, d, n, t_max) -> (heads a step, tail, bytes a token, heads
# a pass).
_PACKED_EDGES = {
    'lfm2-cell': ((256, 32, 8, 64, 1, 5120), (8, 256, 256, 2)),
    'mha-16-heads': ((4, 16, 16, 64, 1, 16384), (8, 256, 256, 2)),
    'verify4': ((4, 32, 8, 64, 4, 8192), (8, None, 256, 2)),
    'one-split': ((4, 32, 8, 64, 1, 1024), (8, None, 256, 2)),
    'd128-pairs': ((4, 8, 2, 128, 1, 8192), (2, 256, 512, 2)),
    'one-head': ((16, 4, 1, 64, 1, 8192), (1, 256, 256, 1)),
    'three-heads': ((16, 12, 3, 64, 1, 8192), (3, 256, 256, 1)),
}


@pytest.mark.parametrize('edge', sorted(_PACKED_EDGES))
def test_packed_decode_kernel_compiles_at_its_edges(chip, edge):
    """Mosaic's verdict on the packed mode: ONE buffer aliased whole, no
    cache-sized temporary, and the grid step and tail that
    ``decode_geometry`` reports for a row that is streamed once."""
    from distributed_dot_product_tpu.ops.pallas_decode import (
        flash_decode, flash_decode_geometry,
    )
    (b, h, h_kv, d, n, t_max), (heads, tail, token_bytes, a_pass) = (
        _PACKED_EDGES[edge])
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((b, h, n, 2 * d), bf16)
    new = jax.ShapeDtypeStruct((b, h_kv, n, 2 * d), bf16)
    kv = jax.ShapeDtypeStruct((b, h_kv, t_max, 2 * d), bf16)
    at = jax.ShapeDtypeStruct((b,), jnp.int32)
    geom = flash_decode_geometry(q, kv)
    assert (geom.heads, geom.tail) == (heads, tail)
    assert geom.bytes // (geom.heads * geom.block_k) == token_bytes
    assert geom.step()['heads_a_pass'] == a_pass

    def step(q, new, kv, at):
        return flash_decode(q, new, None, kv, None, at, at,
                            scale=d ** -0.5, interpret=False)[:2]

    compiled = _compile(chip, step, q, new, kv, at, donate=(2,))
    cache_bytes = math.prod(kv.shape) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        cache_bytes // 8, 32 << 20)


def test_lfm2_decode_step_streams_packed_rows_and_fits(chip, monkeypatch):
    """The token step of the short-convolution / GQA + expert stack at
    the published widths and the traffic of ``lfm2-8b-a1b.decode-4k`` (9
    layers, 256 sessions: seven ``(256, 2, 2048)`` bfloat16 windows
    beside two packed ``(256, 8, 5120, 128)`` slabs), caches donated:
    both attention layers' step resolves to the kernel on the PACKED
    cache at 256 bytes a token a KV head — 8 heads of a session a grid
    step, scored two heads a pass, the tail's 256 rows —, every conv
    mixer's step is its traced
    form, every expert layer's 256-row call takes the route the rule
    names by the rule's own bound — ONE ``moe_hit_experts`` kernel a
    layer, two 896-wide tiles an expert, and no grouped matmul —,
    nothing as large as one slab is copied, sliced or held as a
    temporary, and arguments + temporaries stay under 14.0 GiB with the
    snapshot counted. The reset between requests writes the seven
    windows over in place."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.drivers import decode_lfm2 as driver
    from distributed_dot_product_tpu.models.decode import (
        PackedCache, decode_impl_traces,
    )
    from distributed_dot_product_tpu.models.moe import expert_route_traces
    from distributed_dot_product_tpu.models.shortconv import (
        conv_step_traces,
    )
    from distributed_dot_product_tpu.ops.pallas_experts import (
        _vmem_limit, hidden_tile, hit_list_rows,
    )
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'lfm2-8b-a1b-serve.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(root, 'benchmarks', 'traffic',
                           'decode-4k-x256.json')) as f:
        traffic = json.load(f)
    model = driver.build_lm(cfg)
    params = _shape_table_params(driver, cfg)
    sessions, t_max = traffic['sessions'], traffic['t_max']
    caches = jax.eval_shape(
        lambda: model.make_decode_caches(sessions, t_max))
    kinds = driver.layer_kinds(cfg)
    assert kinds == ['conv'] * 4 + ['attn'] + ['conv'] * 3 + ['attn']
    for kind, cache in zip(kinds, caches):
        if kind == 'attn':
            assert isinstance(cache, PackedCache)
            assert cache.kv.shape == (sessions, 8, t_max, 128)
        else:
            assert cache.state.size == 0
            assert cache.conv.shape == (sessions, 2, 2048)
            assert cache.conv.dtype == jnp.bfloat16
    stats = jax.eval_shape(lambda: driver.zero_stats(cfg, traffic))
    tok = jnp.zeros((sessions, 1), jnp.int32)
    restore, step = driver.make_programs(model, cfg)[-2:]

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)
    with decode_impl_traces() as traces, expert_route_traces() as routes, \
            conv_step_traces() as forms:
        compiled = step.lower(
            *described((params, tok, caches, stats))).compile()
    assert [(t['resolved'], t['cache'], t['token_bytes'], t['tail'],
             t['step']['heads'], t['step']['heads_a_pass'])
            for t in traces] == 2 * [('kernel', 'packed', 256, 256, 8, 2)]
    assert forms == 7 * [{'form': 'shift', 'taps': 3, 'channels': 2048}]
    assert hidden_tile(2048, 1792, 3, 2) == 896
    bound = hit_list_rows(2048)
    assert routes == 8 * [{
        'route': 'hit_list' if sessions <= bound else 'sorted',
        'select': 'threshold' if sessions <= bound else 'sort',
        'n': sessions, 'bound': bound, 'bound_by': 'rule',
        'tile': 896 if sessions <= bound else None}]
    # three blocks of 3.67 MB, double-buffered, and the default on top
    assert _vmem_limit(2048, 896, 3, 2) == 37 << 20
    hlo = compiled.as_text()
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*flash_decode',
        hlo)) == 2
    if sessions <= bound:
        assert 'ragged-dot' not in hlo
        assert len(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*moe_hit_experts',
            hlo)) == 8
    slab_bytes = sessions * 8 * t_max * 128 * 2
    assert _cache_sized_moves(hlo, slab_bytes // 8) == []
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    assert cache_bytes == 2 * slab_bytes + 7 * sessions * 2 * 2048 * 2 + 8
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < slab_bytes // 8
    states = [c if hasattr(c, 'state') else None for c in caches]
    snapshot_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                         for x in jax.tree.leaves(states))
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
            + snapshot_bytes)
    assert 10.5 * 2 ** 30 <= peak <= 14.0 * 2 ** 30, peak / 2 ** 30
    restored = restore.lower(*described(
        (caches, states, jnp.zeros((), jnp.int32)))).compile()
    assert restored.memory_analysis().temp_size_in_bytes < 1 << 20
    # Everything but the two slabs' 4-byte lengths, which are set.
    assert restored.memory_analysis().alias_size_in_bytes >= (
        cache_bytes - 8)
    assert restored.as_text().count('lm.state_restore') >= 14
