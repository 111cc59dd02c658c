# -*- coding: utf-8 -*-
"""
A sparse-expert feed-forward layer: a router over ``n_experts`` gated
MLPs, ``top_k`` of them a token, beside ``n_shared`` shared experts that
every token takes (DeepSeek-V3's layer, ``noaux_tc`` routing):

    s = sigmoid(x W_g)                       float32, (n_experts,)
    picked = top_k(s + b)                    b: the correction bias
                                             (over the kept groups'
                                             experts, below)
    g_i = s_i / sum_picked s · scaling       (norm_topk), i in picked
    y = sum_i g_i E_i(x) + E_shared(x)       E(x) = W_down(silu(W_gate x) * W_up x)

GROUP-LIMITED routing (``n_group > 1``; DeepSeek-V3's, Ling 3.0's): the
experts are ``n_group`` consecutive groups of ``n_experts / n_group``, a
group's score is the sum of its two best BIASED scores, the best
``topk_group`` groups are kept, and the pick is the top-k of the biased
scores of the kept groups' experts alone; the gates are the picks'
unbiased scores as before. Where a deployment gives each chip one group
a token reaches ``topk_group`` chips at most; a holder of one whole
group (``experts_held``) gets no row from a token whose kept groups
leave its group out, and the ``counters`` collection says how many rows
it did get (``group_rows``).

No capacity factor: no token is dropped. Two switches cover the same
layer as other families write it: ``router_bias=False`` has no
correction bias (the pick is the top-k of the scores themselves), and
``shared_combine='mean'`` adds the MEAN of the ``n_shared`` shared
experts' outputs where ``'sum'`` adds their sum (Cohere's
``shared_expert_combination_strategy: "average"``). The shared experts
are one gated MLP ``n_shared x hidden`` wide either way — their sum —
so the mean is that over ``n_shared``. ``score='softmax_picked'`` is
Granite's router: the pick is the top-k of the RAW logits ``x W_g`` and
the gates are the softmax of those k logits — no sigmoid, no bias, no
second normalisation and no factor, so ``router_bias=True``,
``norm_topk=False`` and ``scaling != 1`` are refused beside it.

The layer is TOLD which experts it holds (``experts_held = (lo, hi)``, a
range; default all). It always routes over all ``n_experts``, computes
the part of ``y`` its own experts give for the tokens routed to them,
and adds the shared expert — what every holder computes alike — only
where ``add_shared`` says so, so the parts of a layer divided over
several holders add up to the whole layer with the shared expert
counted once. On one chip that holds every expert this is the whole
layer, with no exchange and nothing standing in for absent chips.

The routed part of a call of many rows (a prefill chunk, a training
batch) sorts the (token, pick) rows by expert and runs three grouped
matmuls over the sorted rows (``lax.ragged_dot``: on the TPU XLA's own
grouped-matmul kernel, which reads an expert's weights only where its
group has rows); a call of few rows takes the hit list (below).

Three more switches cover ``nemotron_h``'s layer. ``expert_form='plain'``
makes every expert, routed and shared, ``W_down act(W_up x)`` — two
matrices, no gate (there is no ``w_gate``; the shared expert is a
:class:`PlainMLP`) — and ``activation='relu2'`` makes ``act`` the
squared ReLU. ``latent`` puts the ROUTED experts in a latent of that
width: ``u = W_dn x`` once a token (``latent_down``), the sort, the
gather and the grouped matmuls run ``latent`` wide, and ``W_up``
(``latent_up``) is applied once to the combined ``(tokens, latent)``
result, not to the ``tokens x top_k`` rows; the router and the shared
expert still read the stream. ``shared_hidden`` is the shared expert's
width where it is not ``n_shared x hidden``. Both projections are
linear and bias-free, so the parts of a latent layer divided over
several holders, each through its own ``W_up``, still add up.

Two routes give the routed part, and the call's ROWS and their WIDTH
choose between them (:func:`ops.pallas_experts.hit_list_rows`: 128 rows
whatever the width, 256 where the rows' resident blocks still fit the
kernel's VMEM plan — a stream of 2048 or narrower). A call of at most
that many tokens — a decode step — runs every HIT held expert, one that
some token of the call picked, on every token, the gate zero where the token
did not pick it: one Pallas program a layer
(``ops/pallas_experts.hit_experts``, ``moe_hit_experts``) over the
call's hit list, which streams each hit expert's weights once and no
other expert's, adds the picks up in a float32 accumulator, and has no
sort, no gather and no grouped matmul. The same numbers; the trade is
rows for tiles: XLA's grouped matmul pays a whole row tile a group, so
where a step gives a hit expert one to three rows (16 tokens x top-4
over 64, 12 x top-8 over 128 with 16 held, 48 x top-22 over 512 with 128
held) giving every hit expert all the rows costs the MXU nothing more
and the weights stream at the HBM's practical peak. The hit count is a
value, not a shape: one program whatever the routing, its time the hit
experts' bytes. A call past the bound (a prefill chunk, a training
batch) takes the sorted route, whose work is the picks' rows and not
rows x hit experts. ``dense_tokens`` is a caller's bound in place of the
rule's (None, the default: the rule; 0: always the sorted route).
:func:`expert_route_traces` reports which route a traced call took and
by whose bound. Under ``jax.grad`` the hit-list route differentiates
through the batched matmuls over every held expert
(``hit_experts_reference``).

The hit-list route's discrete choices take one of two forms, by the
backend alone (:func:`select_form`). Off the TPU ``lax.top_k`` makes
them (three a layer under group-limited routing), a gather reads the
gates and scatters build the kept-group mask, the gate table and the
counts: the oracle. On a TPU each ``top_k`` is a sort and each scatter
costs what a sort does (chip, PR 49: 0.18 ms a layer of 96 rows x 512
experts), so there the layer compares instead: a group's two best from
two maxima, the kept groups by counting who beats whom, the k picks by
ONE Pallas program (``ops/pallas_sparse.threshold_picks``: the k-th
largest by bisection, ties to the lower index) that also gives the
picks' MASK, and the gate table and the counts from the mask. The picks
are ``lax.top_k``'s sets on every input and come ASCENDING by index;
nothing on this route reads their order but the sum that normalises the
gates, so a gate may differ from the sorted form's in its last float32
bit. The sorted route keeps ``lax.top_k`` everywhere: there the picks'
order is the order the k-way combine adds in.
"""

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.models.remat import (
    LAYER_MATMUL_NAMES, named,
)
from distributed_dot_product_tpu.ops.pallas_experts import (
    hidden_tile, hit_experts, hit_list, hit_list_rows,
)
from distributed_dot_product_tpu.ops.pallas_sparse import (
    order_image, threshold_picks,
)
from distributed_dot_product_tpu.utils.scopes import device_scope
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['GatedMLP', 'PlainMLP', 'SparseExperts', 'expert_route_traces',
           'select_form']

ACTIVATIONS = {'silu': nn.silu,
               'relu2': lambda x: jnp.square(nn.relu(x))}


_ROUTE_TRACES = TraceSinks()


def expert_route_traces():
    """Collect which route each :class:`SparseExperts` call takes while
    the block runs: one dict ``{'route', 'select', 'n', 'bound',
    'bound_by', 'tile'}`` per TRACE of a layer. ``route`` is
    ``'hit_list'`` (the ``moe_hit_experts`` kernel over the call's hit
    experts) or ``'sorted'`` (the grouped matmuls over the rows sorted
    by expert); ``select`` how the picks were made — ``'threshold'``
    (compares and the ``sparse_pick`` program, no sort) or ``'sort'``
    (``lax.top_k``), :func:`select_form`; ``n`` the call's rows,
    ``bound`` the most rows that take the hit list, ``bound_by`` whose
    it was — ``'rule'`` (``ops.pallas_experts.hit_list_rows``) or
    ``'caller'`` (``dense_tokens``) — and ``tile`` the columns of
    ``hidden`` one grid step of the kernel takes (None on the sorted
    route)::

        with expert_route_traces() as traces:
            step.lower(*args).compile()
        assert {t['route'] for t in traces} == {'hit_list'}
    """
    return _ROUTE_TRACES.open()


@functools.partial(jax.jit, static_argnames=('k', 'interpret'))
def _picks_and_mask(choice, k, interpret):
    """``threshold_picks`` with the mask, under ``jit`` so that the
    layers of one program share ONE trace of the kernel's body and one
    lowering of it (the body is 0.2 s of Python a call on the chip's
    host: chip, PR 49)."""
    return threshold_picks(choice, k, mask=True, interpret=interpret)


def select_form():
    """How a hit-list call makes its discrete choices: ``'threshold'``
    on a TPU — a group's two best by two maxima, the kept groups by
    counting who beats whom, the k picks by
    ``ops/pallas_sparse.threshold_picks``, and the gate table and the
    counts from the picks' mask: no sort, no scatter — and ``'sort'``
    (``lax.top_k``: the oracle) elsewhere. The same picks either way.
    The sorted route is ``'sort'`` everywhere: there the picks' order
    is the order its k-way sum adds in."""
    return 'threshold' if jax.default_backend() == 'tpu' else 'sort'


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``, no biases. Both halves of
    the hidden pre-activation carry a checkpoint's name
    (``LAYER_MATMUL_NAMES``: kept, SiLU and the product are rebuilt, the
    matmuls are not)."""
    hidden: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        dense = dict(use_bias=False, dtype=self.dtype)
        gate = OwnedDense(self.hidden, name='gate', **dense)(x)
        up = OwnedDense(self.hidden, name='up', **dense)(x)
        gate, up = (named(h, LAYER_MATMUL_NAMES[0]) for h in (gate, up))
        return OwnedDense(x.shape[-1], name='down', **dense)(
            nn.silu(gate) * up)


class PlainMLP(nn.Module):
    """``W_down act(W_up x)``, no gate, no biases."""
    hidden: int
    activation: str = 'relu2'
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        dense = dict(use_bias=False, dtype=self.dtype)
        up = OwnedDense(self.hidden, name='up', **dense)(x)
        return OwnedDense(x.shape[-1], name='down', **dense)(
            ACTIVATIONS[self.activation](up))


class SparseExperts(nn.Module):
    """``y, tokens_per_expert = layer(x)`` for ``x (..., dim)``;
    ``tokens_per_expert (n_experts,) int32`` counts this call's picks
    over ALL experts (also sown into the ``counters`` collection as
    ``expert_tokens``, with the picks ``expert_picks (tokens, top_k)``
    and, under group-limited routing, ``group_rows``: the tokens of the
    call whose kept groups include a group with an expert held here,
    where the caller makes that collection mutable)."""
    n_experts: int
    top_k: int
    hidden: int
    n_shared: int = 1
    scaling: float = 1.0
    norm_topk: bool = True
    experts_held: Optional[Tuple[int, int]] = None
    add_shared: bool = True
    shared_combine: str = 'sum'
    router_bias: bool = True
    score: str = 'sigmoid'
    expert_form: str = 'gated'
    activation: str = 'silu'
    latent: Optional[int] = None
    shared_hidden: Optional[int] = None
    dense_tokens: Optional[int] = None
    n_group: int = 1
    topk_group: int = 1
    dtype: Optional[jnp.dtype] = None
    kernel_init: Any = nn.initializers.lecun_normal(in_axis=-2,
                                                    out_axis=-1,
                                                    batch_axis=(0,))

    def _hit_list(self, tokens, table, counts, w_gate, w_up, w_down, act,
                  lo, hi):
        """Every HIT held expert on every token of ``tokens (n, wide)``,
        the token's gate for it — ``table (n, n_experts)`` — zero where
        it was not picked: the step's hit list, then one kernel that
        streams the hit experts' weights and adds the picks up in
        float32."""
        with device_scope('lm.moe_route'):
            hits, count = hit_list(counts[lo:hi])
        with device_scope('lm.moe_experts'):
            return hit_experts(tokens, table[:, lo:hi], hits, count,
                               w_gate, w_up, w_down, act)

    def _kept_groups(self, choice, lo, hi):
        """Group-limited routing's first half: ``choice (n, n_experts)``
        (the biased scores) with every expert outside the token's best
        ``topk_group`` groups at ``-inf`` — a group's score the sum of
        its two best — and how many of the ``n`` tokens kept a group
        that holds an expert of ``[lo, hi)``."""
        n, size = choice.shape[0], self.n_experts // self.n_group
        best2, _ = lax.top_k(choice.reshape(n, self.n_group, size), 2)
        _, kept = lax.top_k(jnp.sum(best2, -1), self.topk_group)
        keep = jnp.zeros((n, self.n_group), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        mine = jnp.any(keep[:, lo // size:(hi - 1) // size + 1], axis=-1)
        return (jnp.where(jnp.repeat(keep, size, axis=1), choice,
                          -jnp.inf),
                jnp.sum(mine, dtype=jnp.int32))

    def _kept_groups_by_count(self, choice, lo, hi):
        """:meth:`_kept_groups` with no sort and no scatter: a group's
        two best from a maximum, the removal of ONE occurrence of it
        (the lowest index) and a second maximum; a group is kept where
        fewer than ``topk_group`` groups beat it — a higher score in
        ``lax.top_k``'s order, or the same at a lower index."""
        n, size = choice.shape[0], self.n_experts // self.n_group
        part = choice.reshape(n, self.n_group, size)
        at = lax.broadcasted_iota(jnp.int32, part.shape, 2)
        best = jnp.max(part, -1, keepdims=True)
        first = jnp.min(jnp.where(part == best, at, size), -1,
                        keepdims=True)
        second = jnp.max(jnp.where(at == first, -jnp.inf, part), -1)
        key = order_image(best[..., 0] + second)          # (n, n_group)
        group = jnp.arange(self.n_group)
        other, own = key[:, None, :], key[:, :, None]
        beaten_by = (other > own) | ((other == own)
                                     & (group[None, :] < group[:, None]))
        keep = jnp.sum(beaten_by, -1) < self.topk_group
        mine = jnp.any(keep[:, lo // size:(hi - 1) // size + 1], axis=-1)
        return (jnp.where(keep[:, :, None], part, -jnp.inf).reshape(n, -1),
                jnp.sum(mine, dtype=jnp.int32))

    def _threshold_route(self, scores, choice, lo, hi):
        """The hit-list route's choices where :func:`select_form` says
        ``'threshold'``: ``(picked (n, k)`` ASCENDING by index, the gate
        table ``(n, n_experts)``, ``counts``, ``group_rows`` or None``)``
        from the ``scores (n, n_experts)`` the gates are made of and the
        ``choice`` the picks go by (the scores with their bias). The
        picks are the sorted form's SET on every input; a gate is the
        sorted form's but for the order its k-term normalising sum adds
        in."""
        group_rows = None
        if self.n_group > 1:
            choice, group_rows = self._kept_groups_by_count(choice, lo, hi)
        picked, mask = _picks_and_mask(
            lax.stop_gradient(choice), self.top_k,
            interpret=jax.default_backend() != 'tpu')
        if self.score == 'softmax_picked':
            top = jnp.max(jnp.where(mask, scores, -jnp.inf), -1,
                          keepdims=True)
            table = jnp.where(mask, jnp.exp(scores - top), 0.0)
            table = table / jnp.sum(table, -1, keepdims=True)
        else:
            table = jnp.where(mask, scores, 0.0)
            if self.norm_topk:
                table = table / jnp.sum(table, -1, keepdims=True)
            table = table * self.scaling
        return picked, table, jnp.sum(mask, 0, dtype=jnp.int32), group_rows

    @nn.compact
    def __call__(self, x):
        lo, hi = self.experts_held or (0, self.n_experts)
        held = hi - lo
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f'experts_held {self.experts_held} is no '
                             f'range of {self.n_experts} experts')
        if self.shared_combine not in ('sum', 'mean'):
            raise ValueError(f"shared_combine must be 'sum' or 'mean', "
                             f'got {self.shared_combine!r}')
        if self.expert_form not in ('gated', 'plain'):
            raise ValueError(f"expert_form must be 'gated' or 'plain', "
                             f'got {self.expert_form!r}')
        if self.score not in ('sigmoid', 'softmax_picked'):
            raise ValueError(f"score must be 'sigmoid' or "
                             f"'softmax_picked', got {self.score!r}")
        picked_softmax = self.score == 'softmax_picked'
        if picked_softmax and (self.router_bias or not self.norm_topk
                               or self.scaling != 1.0):
            raise ValueError(
                "score='softmax_picked' gates by the softmax of the "
                'picked logits: it sums to one and takes no bias and no '
                'factor (pass router_bias=False; norm_topk and scaling '
                'stay at their defaults)')
        grouped = self.n_group > 1
        if grouped and (picked_softmax or self.n_experts % self.n_group
                        or not 0 < self.topk_group <= self.n_group
                        or self.n_experts // self.n_group < 2
                        or self.topk_group * (self.n_experts
                                              // self.n_group) < self.top_k):
            raise ValueError(
                f'group-limited routing keeps topk_group '
                f'{self.topk_group} of n_group {self.n_group} equal '
                f'groups (two experts or more each, top_k {self.top_k} '
                f'or more in the kept ones) of the {self.n_experts} '
                f'experts, by sigmoid scores')
        act = ACTIVATIONS[self.activation]
        gated = self.expert_form == 'gated'
        dim = x.shape[-1]
        # The width the routed experts read and write.
        wide = self.latent or dim
        router = self.param('router', nn.initializers.lecun_normal(),
                            (dim, self.n_experts), jnp.float32)
        bias = (self.param('router_bias', nn.initializers.zeros_init(),
                           (self.n_experts,), jnp.float32)
                if self.router_bias else None)
        w_gate = (self.param('w_gate', self.kernel_init,
                             (held, wide, self.hidden), jnp.float32)
                  if gated else None)
        w_up = self.param('w_up', self.kernel_init,
                          (held, wide, self.hidden), jnp.float32)
        w_down = self.param('w_down', self.kernel_init,
                            (held, self.hidden, wide), jnp.float32)
        dtype = self.dtype or x.dtype
        flat = x.reshape(-1, dim).astype(dtype)
        n, k = flat.shape[0], self.top_k
        tokens = flat
        if self.latent:
            with device_scope('lm.moe_latent'):
                tokens = OwnedDense(self.latent, use_bias=False,
                                    dtype=self.dtype,
                                    name='latent_down')(flat)

        # Few enough rows that every hit expert can take them all behind
        # its weights' DMA: the call's own shape decides.
        by_rule = self.dense_tokens is None
        bound = hit_list_rows(wide) if by_rule else self.dense_tokens
        hit_route = n <= bound
        select = select_form() if hit_route else 'sort'
        with device_scope('lm.moe_route'):
            scores = jnp.dot(
                flat.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
            if not picked_softmax:
                scores = jax.nn.sigmoid(scores)
            # (the picked logits' softmax takes no bias: refused above)
            choice = scores if bias is None else scores + bias
            if select == 'threshold':
                picked, table, counts, group_rows = self._threshold_route(
                    scores, choice, lo, hi)
            else:
                if grouped:
                    choice, group_rows = self._kept_groups(choice, lo, hi)
                top, picked = lax.top_k(choice, k)               # (n, k)
                if picked_softmax:
                    gates = jax.nn.softmax(top, axis=-1)
                else:
                    gates = jnp.take_along_axis(scores, picked, axis=-1)
                    if self.norm_topk:
                        gates = gates / jnp.sum(gates, -1, keepdims=True)
                    gates = gates * self.scaling
                expert = picked.reshape(-1)                      # (n·k,)
                counts = jnp.zeros((self.n_experts,), jnp.int32).at[
                    expert].add(1)
                if hit_route:
                    table = jnp.zeros((n, self.n_experts), jnp.float32).at[
                        jnp.arange(n)[:, None], picked].set(gates)
                else:
                    mine = (expert >= lo) & (expert < hi)
                    # Rows of experts held elsewhere sort behind the last
                    # group and are masked out of the combine.
                    order = jnp.argsort(
                        jnp.where(mine, expert - lo, held), stable=True)
                    rows = tokens[order // k]                   # (n·k, wide)
                    sizes = lax.dynamic_slice_in_dim(counts, lo, held)
        # Counters for a caller that makes the collection mutable (a
        # no-op otherwise): this call's tokens per expert and picks.
        self.sow('counters', 'expert_tokens', counts,
                 reduce_fn=lambda old, new: new,
                 init_fn=lambda: jnp.zeros((self.n_experts,), jnp.int32))
        self.sow('counters', 'expert_picks', picked,
                 reduce_fn=lambda old, new: new,
                 init_fn=lambda: jnp.zeros((n, k), jnp.int32))
        if grouped:
            self.sow('counters', 'group_rows', group_rows,
                     reduce_fn=lambda old, new: new,
                     init_fn=lambda: jnp.zeros((), jnp.int32))

        tile = hidden_tile(wide, self.hidden, 2 + gated,
                           w_up.dtype.itemsize) if hit_route else None
        _ROUTE_TRACES.note({'route': 'hit_list' if hit_route else 'sorted',
                            'select': select, 'n': n, 'bound': bound,
                            'bound_by': 'rule' if by_rule else 'caller',
                            'tile': tile})
        if hit_route:
            y = self._hit_list(tokens, table, counts, w_gate, w_up, w_down,
                               act, lo, hi)
        else:
            with device_scope('lm.moe_experts'):
                def grouped(a, w):
                    return lax.ragged_dot(
                        a, w.astype(dtype), sizes,
                        preferred_element_type=jnp.float32).astype(dtype)
                if gated:
                    out = grouped(act(grouped(rows, w_gate))
                                  * grouped(rows, w_up), w_down)
                else:
                    out = grouped(act(grouped(rows, w_up)), w_down)

            with device_scope('lm.moe_route'):
                weight = (gates.reshape(-1) * mine)[order]
                out = jnp.where(weight[:, None] != 0,
                                out.astype(jnp.float32) * weight[:, None],
                                0.0)
                # Back to (token, pick) order, then the k picks add up.
                back = jnp.zeros_like(order).at[order].set(
                    jnp.arange(n * k, dtype=order.dtype))
                y = out[back].reshape(n, k, wide).sum(axis=1).astype(dtype)

        if self.latent:
            with device_scope('lm.moe_latent'):
                y = OwnedDense(dim, use_bias=False, dtype=self.dtype,
                               name='latent_up')(y)
        if self.n_shared and self.add_shared:
            with device_scope('lm.mlp'):
                hidden = self.shared_hidden or self.n_shared * self.hidden
                shared = (GatedMLP(hidden, dtype=self.dtype, name='shared')
                          if gated else PlainMLP(
                              hidden, self.activation, dtype=self.dtype,
                              name='shared'))(flat)
                if self.shared_combine == 'mean':
                    shared = shared * (1.0 / self.n_shared)
                y = y + shared
        return y.reshape(x.shape[:-1] + (dim,)), counts
