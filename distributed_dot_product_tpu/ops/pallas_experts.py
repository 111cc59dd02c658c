# -*- coding: utf-8 -*-
"""
The routed experts of a decode step: ONE Pallas program a layer that
streams the experts the step's tokens picked, and no others.

A decode step of a sparse-expert layer is bound by the bytes of the
experts' weights: 48 tokens give an expert's matrices two or three rows
each, so XLA's grouped matmul pays a whole row tile a group for them
(``models/moe.py``, the sorted route) and a batched matmul over EVERY
held expert streams the quarter of them that no token picked. This
kernel keeps the batched form — every hit expert gets all ``n`` rows,
the gate zero where a token did not pick it — and takes the unhit
experts' bytes away:

- the step's **hit list** (the held experts with a pick, compacted to
  the front of a ``(held,)`` vector, and their number; :func:`hit_list`)
  is scalar-prefetched, and the weights' index maps read slot ``i`` of
  it: grid ``(held, hidden tiles)``, static, so the hit count changes no
  shape and no program;
- a slot past the count repeats the last hit expert's last tile — an
  unchanged block index is not fetched again (what
  ``ops/pallas_decode.py`` does for a slot's unfilled K/V blocks) — and
  its body is skipped;
- a grid step is one expert's ``(wide, tile)`` columns of ``w_up`` (and
  ``w_gate``) and the matching ``(tile, wide)`` rows of ``w_down``:
  ``h = act(x w_up)`` (times the ``w_gate`` product where gated), scaled
  by the expert's gate column, ``acc += h w_down`` into ONE ``(n, wide)``
  float32 accumulator that stays in VMEM for the whole grid and is
  written once, so the picks add up in float32;
- the gate table rides whole, ``(n, held)`` float32 with the experts on
  the lanes (24 KB at 48 x 128): a step takes its expert's column with
  one masked lane reduce.

VMEM plan (:func:`hidden_tile`): the weight blocks of a step,
double-buffered, within :data:`_VMEM_BUDGET` and within
:data:`_STEP_STREAM_BYTES` a step. At the hybrid cell's shapes (48
tokens, ``wide`` 1024, ``hidden`` 2688 = 3 x 896, bfloat16): two blocks
of 1.75 MiB, 7.0 MiB double-buffered; tokens, gates, output and
accumulator 0.6 MiB; the step's temporaries (``(48, 896)`` float32
activations, their bfloat16 copy, the ``(48, 1024)`` float32 product)
0.5 MiB: 8.1 MiB of the compiler's 16 MiB. A gated layer at the
stream's width has three blocks a step and that plan leaves it ONE lane
tile, a ``w_up`` slab of 256-byte rows, which streams 6 % under slabs of
1 KB rows (chip, PR 35): the slab is widened to
:data:`_SLAB_ROW_BYTES` a row, 512 columns of bfloat16, and
:func:`_vmem_limit` asks the compiler for the blocks' bytes on top of
its default. Xing4's layer (16 tokens, ``wide`` 3584, ``hidden`` 1024 =
2 x 512, 64 held): three blocks of 3.5 MiB, 21.0 MiB double-buffered,
the rest under 0.6 MiB (the ``(16, 3584)`` tokens, output, accumulator
and float32 product, three ``(16, 512)`` activations), limit 37 MiB.
Command A+'s (12 tokens, 4096 / 4096 = 8 x 512, 16 held): three blocks
of 4 MiB, 24 MiB double-buffered, the rest under 0.7 MiB, limit 40 MiB;
at :data:`HIT_LIST_ROWS` 128 rows the rest is 9 MiB (tokens and output
1 MiB each and double-buffered, accumulator and product 2 MiB each).
LFM2's layer at the rule's bound for its width (256 tokens, 2048 /
1792 = 2 x 896, 32 held; :func:`hit_list_rows`): three blocks of 3.5
MiB, 21 MiB double-buffered, limit 37 MiB; the rows' resident blocks 8
MiB (tokens and output 1 MiB each and double-buffered, accumulator and
product 2 MiB each) and three ``(256, 896)`` float32 activations 2.6
MiB. ``tests/test_tpu_compile.py`` compiles each for a v5e.

Off the TPU the kernel runs under the Pallas interpreter, as the other
kernels do. :func:`hit_experts_reference` is the same layer as two
(three) batched matmuls over every held expert: the tests' oracle, and
the kernel's differentiation rule (reverse mode, first order).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_dot_product_tpu.ops.kernel_call import kernel_call
from distributed_dot_product_tpu.ops.pallas_decode import (
    _STEP_STREAM_BYTES, _VMEM_BUDGET, _pad_rows, _sublane,
)

__all__ = ['hit_list', 'hit_experts', 'hit_experts_reference',
           'hidden_tile', 'hit_list_rows', 'HIT_LIST_ROWS']

# The most rows of a call that take this kernel (``models/moe.py``
# chooses by it): every hit expert gets ALL the call's rows, and one MXU
# pass takes up to 128 rows at the cost of one, so up to there an
# expert's matmuls stay behind the DMA of its weights (a row is 2 FLOPs
# a weight: 128 rows over a GB of bfloat16 weights are 0.65 ms of the
# MXU's peak behind 1.22 ms of HBM). Past it the work grows with rows x
# hit experts where the sorted route's grows with the picks. On the chip
# (PR 35, both gated shapes, a layer's routed part): level from 64 to
# 256 rows, 1.8x that at 512, and under the sorted route's all the way.
# This is the bound of EVERY call (:func:`hit_list_rows` never says
# less); what the rule reads past it is the bytes the call's rows keep
# resident.
HIT_LIST_ROWS = 128
# What the rows of a call may keep resident in VMEM for the whole grid —
# tokens and output double-buffered in the compute type, the float32
# accumulator and the float32 product, 16 bytes a row and column of
# ``wide`` — where the call is past :data:`HIT_LIST_ROWS`: what 128 rows
# of a 4096-wide stream take, the most the plan above was compiled at
# (``tests/test_tpu_compile.py``).
_RESIDENT_BYTES = 128 * 4096 * 16
# A block of ``w_up`` / ``w_gate`` is a column slab of a row-major
# matrix: its DMA moves ``wide`` rows of ``tile x itemsize`` bytes. Rows
# of 256 and 512 bytes streamed 6 % under rows of 1 KB and 2 KB at both
# gated shapes (chip, PR 35), so a slab is at least this wide.
_SLAB_ROW_BYTES = 1024
# The most VMEM the weight blocks may take double-buffered when the slab
# rule widens them past the plan: half of a v5e's 128 MiB.
_VMEM_CEILING = 64 << 20
# The compiler's default scoped VMEM limit on a v5e.
_SCOPED_VMEM = 16 << 20


def hit_list_rows(wide):
    """The most rows of a call over ``wide``-wide rows that take this
    kernel — the rule ``models/moe.py`` routes by: :data:`HIT_LIST_ROWS`
    whatever the width (one MXU pass: the expert's matmuls stay behind
    its weights' DMA), and TWICE that where the rows' resident blocks
    still fit :data:`_RESIDENT_BYTES` (a stream of 2048 or narrower). A
    second pass of 128 rows is 0.65 x 2 = 1.3 ms of the MXU's peak a GB
    of weights beside 1.22 ms of HBM: at the ridge. On the chip (PR 51,
    a layer of 32 experts of 2048 x 1792 at top-4, every expert hit) the
    kernel read 0.984 ms at 128 rows, 0.996 at 192, 1.016 at 256 (84 %
    of the HBM peak), then 1.475 at 384 and 1.935 at 512 — the work
    grows with rows x hit experts past the ridge — against the sorted
    route's 2.62, 2.01, 2.74, 2.87 and 2.99 ms (a sort of the picks, a
    gather, a scatter and a row tile a group before its first weight
    arrives). The kernel stayed ahead to 512 rows there and at 256 rows
    of a 4096-wide stream (40 experts of 4096 x 1280: 1.757 against
    4.69 ms); the rule takes the rows one cell times and the VMEM plan
    was compiled for, no more."""
    twice = 2 * HIT_LIST_ROWS
    return twice if twice * wide * 16 <= _RESIDENT_BYTES else HIT_LIST_ROWS


def hit_list(counts):
    """``(hits, count)`` for one call's ``counts (held,)`` picks a held
    expert: the experts with at least one pick, in rising order at the
    front of ``hits (held,) int32`` (zeros behind), and their number
    ``count () int32``. A cumulative sum and a scatter: nothing is
    sorted."""
    held = counts.shape[0]
    hit = counts > 0
    slot = jnp.where(hit, jnp.cumsum(hit) - 1, held)
    hits = jnp.zeros((held,), jnp.int32).at[slot].set(
        jnp.arange(held, dtype=jnp.int32), mode='drop')
    return hits, jnp.sum(hit, dtype=jnp.int32)


def hidden_tile(wide, hidden, matrices, itemsize):
    """Columns of ``hidden`` a grid step takes: the most 128-lane tiles
    that divide it whose ``matrices`` blocks of ``wide x tile`` stay
    within the step's stream target and, double-buffered, within the
    VMEM plan — but no narrower than :data:`_SLAB_ROW_BYTES` a row of
    the ``w_up`` slab (the fewest tiles that reach it, while the blocks
    stay within :data:`_VMEM_CEILING`; :func:`_vmem_limit` then asks for
    the room); one lane tile where even that is past them; all of
    ``hidden`` where it is no multiple of 128 (a block may be a whole
    axis, whatever its length)."""
    if hidden % 128:
        return hidden
    lanes = hidden // 128
    step = matrices * wide * 128 * itemsize
    divisors = [c for c in range(1, lanes + 1) if lanes % c == 0]
    planned = max([c for c in divisors if c * step <= _STEP_STREAM_BYTES
                   and 2 * c * step <= _VMEM_BUDGET] or [1])
    # (a slab that is all of ``hidden`` is whole rows, however short)
    slab = min([c for c in divisors if 2 * c * step <= _VMEM_CEILING and (
        c * 128 * itemsize >= _SLAB_ROW_BYTES or c == lanes)] or [1])
    return 128 * max(planned, slab)


def _vmem_limit(wide, tile, matrices, itemsize):
    """``vmem_limit_bytes`` for a step's weight blocks: None (the
    compiler's default, 16 MiB) where they are within the plan's budget
    double-buffered, else their bytes and the default on top for the
    rest (tokens, gates, accumulator, the step's temporaries)."""
    blocks = 2 * matrices * wide * tile * itemsize
    return None if blocks <= _VMEM_BUDGET else blocks + _SCOPED_VMEM


def hit_experts_reference(tokens, gates, w_gate, w_up, w_down, act):
    """Every held expert on every token: the hidden activations
    ``(held, n, hidden)`` scaled by the token's gate for that expert and
    contracted with ``w_down`` over expert and hidden together."""
    dtype = tokens.dtype

    def every(w):
        return jnp.einsum('nk,ekh->enh', tokens, w.astype(dtype),
                          preferred_element_type=jnp.float32)
    hid = (act(every(w_up)) if w_gate is None
           else act(every(w_gate)) * every(w_up))
    return jnp.einsum('enh,ehk->nk', (hid * gates.T[..., None]).astype(dtype),
                      w_down.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def _kernel(hits_ref, count_ref, x_ref, g_ref, *refs, act, gated):
    wg_ref = refs[0] if gated else None
    wu_ref, wd_ref, o_ref, acc_ref = refs[gated:]
    i, j = pl.program_id(0), pl.program_id(1)
    last = jnp.logical_and(i == pl.num_programs(0) - 1,
                           j == pl.num_programs(1) - 1)
    expert = hits_ref[i]

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < count_ref[0])
    def _():
        x = x_ref[...]

        def times(w_ref):
            return jnp.dot(x, w_ref[0].astype(x.dtype),
                           preferred_element_type=jnp.float32)
        hid = act(times(wg_ref)) * times(wu_ref) if gated else act(
            times(wu_ref))
        lanes = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
        gate = jnp.sum(jnp.where(lanes == expert, g_ref[...], 0.0),
                       axis=1, keepdims=True)
        acc_ref[...] += jnp.dot((hid * gate).astype(x.dtype),
                                wd_ref[0].astype(x.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _hit_experts(tokens, gates, hits, count, w_gate, w_up, w_down, act,
                 interpret, tile):
    n, wide = tokens.shape
    held, _, hidden = w_up.shape
    gated = w_gate is not None
    if interpret is None:
        interpret = jax.default_backend() != 'tpu'
    itemsize = jnp.dtype(w_up.dtype).itemsize
    tile = tile or hidden_tile(wide, hidden, 2 + gated, itemsize)
    tiles = hidden // tile
    vmem_limit = _vmem_limit(wide, tile, 2 + gated, itemsize)
    # Rows ride padded to their sublane tile (Mosaic refuses a dot
    # against a one-row operand); a padded token's gates are zero.
    x = _pad_rows(tokens, _sublane(tokens.dtype))
    g = _pad_rows(gates.astype(jnp.float32), _sublane(tokens.dtype))

    # Slot i's expert and tile j; a slot past the count stays on the
    # block the last hit slot ended on, which is then not fetched again.
    def slot(i, j, hits, count):
        live = i < count[0]
        # (slot 0 where the count is 0: maximum last)
        return (hits[jnp.maximum(jnp.minimum(i, count[0] - 1), 0)],
                jnp.where(live, j, tiles - 1))

    def up_idx(i, j, hits, count):
        e, t = slot(i, j, hits, count)
        return (e, 0, t)

    def down_idx(i, j, hits, count):
        e, t = slot(i, j, hits, count)
        return (e, t, 0)

    def whole(i, j, hits, count):
        return (0, 0)

    up_spec = pl.BlockSpec((1, wide, tile), up_idx)
    out = kernel_call(
        functools.partial(_kernel, act=act, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, tiles),
            in_specs=[pl.BlockSpec(x.shape, whole),
                      pl.BlockSpec(g.shape, whole),
                      *([up_spec] if gated else []), up_spec,
                      pl.BlockSpec((1, tile, wide), down_idx)],
            out_specs=pl.BlockSpec(x.shape, whole),
            scratch_shapes=[pltpu.VMEM(x.shape, jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(x.shape, tokens.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit) if vmem_limit else None,
        interpret=interpret,
        name='moe_hit_experts')(
            hits, count.reshape(1), x, g,
            *([w_gate] if gated else []), w_up, w_down)
    return out[:n]


def _hit_experts_fwd(tokens, gates, hits, count, w_gate, w_up, w_down, act,
                     interpret, tile):
    return (_hit_experts(tokens, gates, hits, count, w_gate, w_up, w_down,
                         act, interpret, tile),
            (tokens, gates, w_gate, w_up, w_down))


def _hit_experts_bwd(act, interpret, tile, kept, cotangent):
    # An unhit expert's gates are all zero: the batched form over every
    # held expert has the kernel's gradients.
    d_tokens, d_gates, d_gate, d_up, d_down = jax.vjp(
        functools.partial(hit_experts_reference, act=act), *kept)[1](
            cotangent)
    return d_tokens, d_gates, None, None, d_gate, d_up, d_down


_hit_experts.defvjp(_hit_experts_fwd, _hit_experts_bwd)


def hit_experts(tokens, gates, hits, count, w_gate, w_up, w_down, act, *,
                interpret=None, tile=None):
    """``sum_e gates[:, e] * E_e(tokens)`` over the experts of the hit
    list, ``(n, wide)`` in ``tokens``' type.

    ``tokens (n, wide)`` in the compute type; ``gates (n, held)``
    float32, a token's gate for each held expert, zero where it did not
    pick it; ``hits (held,) int32`` / ``count () int32`` from
    :func:`hit_list` (an expert outside the list adds nothing and its
    weights are not read: its gates must be zero); ``w_up (held, wide,
    hidden)``, ``w_down (held, hidden, wide)`` and, for a gated expert
    ``act(x w_gate) * (x w_up)``, ``w_gate`` like ``w_up`` (else None),
    as they are stored: a block is cast to the compute type in VMEM.
    ``act`` is the activation, a function of one float32 array.
    ``tile`` overrides :func:`hidden_tile` (tests)."""
    return _hit_experts(tokens, gates, hits, count, w_gate, w_up, w_down,
                        act, interpret, tile)
