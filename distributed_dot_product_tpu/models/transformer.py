# -*- coding: utf-8 -*-
"""
A transformer stack over the sequence-parallel attention module — the
framework's "build a real model" layer.

The reference ships a single attention module and stops (reference
module.py:22-76); anything resembling a model is left to the user. This
module shows — and tests — that the pieces compose into one: pre-LN
transformer blocks (attention + MLP, residuals) whose attention is
:class:`~distributed_dot_product_tpu.models.attention.DistributedDotProductAttn`
with its full knob surface (softmax path, GQA, RoPE, windows, ALiBi,
dropout — stacked layers sharing one explicit dropout seed decorrelate
via the per-layer salt), trained by the same
:func:`~distributed_dot_product_tpu.train.make_train_step` /
:func:`~distributed_dot_product_tpu.models.attention.apply_seq_parallel`
machinery (everything except attention is position-wise, so sequence
sharding passes straight through LayerNorm/MLP), and decoded with one KV
cache per layer through the module's ``prefill``/``decode`` surface.

TPU-first notes: the MLP/LayerNorm are plain flax (XLA fuses them; the
attention kernels are where hand-written Pallas pays), activations stay
in the module ``dtype`` (bf16 on chip) with fp32 LayerNorm statistics
(flax's default). Layers either unroll at trace time (fine at demo
depths) or — ``scan_layers=True`` — run as ONE ``nn.scan`` over a
single block with layer-stacked parameters: trace/compile time is
O(1) in depth, and the ``remat`` knob wraps the block in
``jax.checkpoint`` so backward score memory is one layer's, not the
stack's (what a rematted layer keeps, and ``remat_policy``:
:class:`TransformerStack`).
"""

import contextlib
from typing import Any, Callable, Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.models.attention import (
    DistributedDotProductAttn,
)
from distributed_dot_product_tpu.models.delta import GatedDeltaMixer
from distributed_dot_product_tpu.models.dense import OwnedDense
from distributed_dot_product_tpu.models.hyper import (
    HyperConnection, mix_back,
)
from distributed_dot_product_tpu.models.latent import (
    LatentAttention, init_latent_cache,
)
from distributed_dot_product_tpu.models.lightning import LightningMixer
from distributed_dot_product_tpu.models.moe import GatedMLP, SparseExperts
from distributed_dot_product_tpu.models.remat import (
    LAYER_MATMUL_NAMES, KeepWhatFits, named, new_layer,
)
from distributed_dot_product_tpu.models.shortconv import ShortConvMixer
from distributed_dot_product_tpu.models.ssm import Mamba2Mixer
from distributed_dot_product_tpu.utils.comm import SEQ_AXIS
from distributed_dot_product_tpu.utils.scopes import device_scope

__all__ = ['TransformerBlock', 'TransformerStack']


# The recurrent mixers, by the name of a block's ``mixer`` AND of its
# subtree: each takes ``ssm_kwargs`` and keeps a ``StateCache`` (a state
# matrix a head and a convolution window; the Lightning mixer's window
# has no rows, the short convolution's state no elements); its
# ``prefill`` / ``decode`` are told the ``position`` of the first new
# token (the Lightning mixer rotates by it; the other three ignore it).
RECURRENT = {'ssm': Mamba2Mixer, 'delta': GatedDeltaMixer,
             'lightning': LightningMixer, 'conv': ShortConvMixer}


def make_norm(kind, eps, dtype, name):
    """The norm a block (and the LM's final norm) is built with."""
    if kind in ('layernorm', 'layernorm_nobias'):
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name,
                            use_bias=kind == 'layernorm')
    if kind == 'rmsnorm':
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError(f"norm must be 'layernorm', 'layernorm_nobias' or "
                     f"'rmsnorm', got {kind!r}")


class TransformerBlock(nn.Module):
    """Pre-norm block: the mixer's branch, then the feed-forward's, each
    around a residual.

    The block is a composition of four choices; the defaults are the
    block this file always built (``x + Attn(LN(x))`` then
    ``x + MLP(LN(x))``, LayerNorm, GELU), with its parameter tree:

    - ``norm``: ``'layernorm'`` | ``'layernorm_nobias'`` (Cohere's:
      mean-subtracting, a scale and no bias) | ``'rmsnorm'``
      (``norm_eps``);
    - ``mixer``: ``'attention'`` (``DistributedDotProductAttn``;
      ``attn_kwargs`` passes through: softmax_impl, num_kv_heads,
      use_rope, window, dropout_rate, ...; self-attention in the
      module's K-first convention, the same tensor feeding
      keys/queries/values, reference example.py:31's usage) |
      ``'latent'`` (``models/latent.LatentAttention``; ``attn_kwargs``
      are its ranks and head sizes, its cache one layer-stacked
      ``LatentCache`` addressed by ``layer`` in a stack of latent
      layers alone, one layer's ``LatentCache`` beside the other
      layers' caches in a mixed stack) | ``'ssm'``
      (``models/ssm.Mamba2Mixer(**ssm_kwargs)``, the subtree ``ssm``;
      its cache a fixed-size ``StateCache``) | ``'delta'``
      (``models/delta.GatedDeltaMixer(**ssm_kwargs)``, the subtree
      ``delta``; a ``StateCache`` too) | ``'lightning'``
      (``models/lightning.LightningMixer(**ssm_kwargs)``, the subtree
      ``lightning``: plain linear attention with RoPE, a ``StateCache``
      and the stack's position) | ``'conv'``
      (``models/shortconv.ShortConvMixer(**ssm_kwargs)``, the subtree
      ``conv``: a double-gated depthwise convolution of a few taps; a
      ``StateCache`` of the window alone) | ``'none'``. An ``'attention'`` mixer
      with ``attn_kwargs['sparse']`` is the learned block-sparse layer
      (``models/sparse.py``; its cache a ``SparseCache``);
    - ``ffn``: ``'gelu'`` (``mlp_ratio`` x dim) | ``'gated'``
      (``ffn_kwargs['hidden']``, SiLU-gated, no biases) | ``'experts'``
      (``models/moe.SparseExperts(**ffn_kwargs)``) | ``'none'``. A
      block with ``mixer='none'`` or ``ffn='none'`` is ``x +
      branch(LN(x))``: ONE norm (``ln1``), one branch, one residual,
      and nothing of the absent branch in its parameter tree (it has no
      cache where it has no mixer);
    - ``residual``: ``'add'`` | ``'hyper'`` (``models/hyper``: the input
      is a widened stream ``(..., mult, dim)`` float32 and each branch
      reads and writes it through its own ``HyperConnection(
      **residual_kwargs)``); ``residual_scale`` (``'add'`` only) makes
      every residual ``x + residual_scale · branch(LN(x))`` (Granite's
      ``residual_multiplier``; 1 adds no operation);
    - ``parallel``: ``x + Attn(h) + FFN(h)`` with ``h = LN(x)``, ONE
      norm a block (``ln1``; there is no ``ln2``) and both branches
      from it, where the default runs them one after the other, each
      with its norm (``residual='add'`` only)."""
    dim: int
    num_heads: int
    mlp_ratio: int = 4
    # Mirrors the attention module's field — apply_seq_parallel reads it
    # to pick the mesh axis.
    axis_name: str = SEQ_AXIS
    dtype: Optional[jnp.dtype] = None
    # 'int8': int8 weight quantization for the block's projection AND
    # MLP matmuls (models/dense.py; quantize_dense_params converts a
    # float checkpoint). Defaulted into attn_kwargs, so one knob
    # quantizes the whole block.
    weight_quant: Optional[str] = None
    attn_kwargs: Any = None
    norm: str = 'layernorm'
    norm_eps: float = 1e-6
    mixer: str = 'attention'
    ffn: str = 'gelu'
    ffn_kwargs: Any = None
    ssm_kwargs: Any = None
    residual: str = 'add'
    residual_kwargs: Any = None
    residual_scale: float = 1.0
    parallel: bool = False

    def _norm(self, name):
        return make_norm(self.norm, self.norm_eps, self.dtype, name)

    def setup(self):
        kw = dict(self.attn_kwargs or {})
        kw.setdefault('dtype', self.dtype)
        if self.mixer == 'latent':
            self.attn = LatentAttention(dim=self.dim,
                                        num_heads=self.num_heads, **kw)
        elif self.mixer == 'attention':
            kw.setdefault('axis_name', self.axis_name)
            kw.setdefault('weight_quant', self.weight_quant)
            # attn_kwargs' key_dim: heads wider together than the
            # stream; the composition then comes back to ``dim``.
            kw.setdefault('key_dim', self.dim)
            if kw['key_dim'] != self.dim:
                kw.setdefault('out_dim', self.dim)
            self.attn = DistributedDotProductAttn(
                num_heads=self.num_heads, **kw)
        elif self.mixer in RECURRENT:
            setattr(self, self.mixer, RECURRENT[self.mixer](
                dim=self.dim, name=self.mixer, **{
                    'dtype': self.dtype, 'norm_eps': self.norm_eps,
                    **(self.ssm_kwargs or {})}))
        elif self.mixer != 'none':
            raise ValueError(f"mixer must be 'attention', 'latent', "
                             f"{sorted(RECURRENT)} or 'none', got "
                             f'{self.mixer!r}')
        one_branch = 'none' in (self.mixer, self.ffn)
        if one_branch and (self.parallel or self.mixer == self.ffn):
            raise ValueError('a block has a mixer, a feed-forward or '
                             'both; parallel=True needs both')
        self.ln1 = self._norm('ln1')
        if self.parallel:
            if self.residual != 'add':
                raise ValueError("parallel=True is x + Attn(h) + FFN(h): "
                                 "it goes with residual='add'")
        elif not one_branch:
            self.ln2 = self._norm('ln2')
        ffn_kw = dict(self.ffn_kwargs or {})
        if self.ffn == 'gelu':
            # OwnedDense (explicit fp32 accumulation + the int8 weight
            # path) — see models/dense.py; param tree matches nn.Dense.
            self.mlp_in = OwnedDense(self.mlp_ratio * self.dim,
                                     dtype=self.dtype, name='mlp_in',
                                     weight_quant=self.weight_quant)
            self.mlp_out = OwnedDense(self.dim, dtype=self.dtype,
                                      name='mlp_out',
                                      weight_quant=self.weight_quant)
        elif self.ffn == 'gated':
            self.mlp = GatedMLP(dtype=self.dtype, name='mlp', **ffn_kw)
        elif self.ffn == 'experts':
            self.moe = SparseExperts(dtype=self.dtype, name='moe',
                                     **ffn_kw)
        elif self.ffn != 'none':
            raise ValueError(f"ffn must be 'gelu', 'gated', 'experts' or "
                             f"'none', got {self.ffn!r}")
        if self.residual == 'hyper':
            hc_kw = dict(self.residual_kwargs or {})
            if self.mixer != 'none':
                self.hc_attn = HyperConnection(name='hc_attn', **hc_kw)
            if self.ffn != 'none':
                self.hc_ffn = HyperConnection(name='hc_ffn', **hc_kw)
            if self.residual_scale != 1.0:
                raise ValueError("residual_scale scales the branch of "
                                 "residual='add': a hyper-connection "
                                 'weighs its branch itself')
        elif self.residual != 'add':
            raise ValueError(f"residual must be 'add' or 'hyper', got "
                             f'{self.residual!r}')

    def _scaled(self, y):
        return y if self.residual_scale == 1.0 else y * self.residual_scale

    def _around(self, which, x, branch):
        """The residual around one branch: ``x + branch(x)``, or the
        hyper-connection ``which`` ('attn' / 'ffn') mixing the stream
        into the branch and its output back."""
        if self.residual == 'add':
            return x + self._scaled(branch(x))
        u, h_post, h_res = getattr(self, f'hc_{which}')(x)
        y = branch(u.astype(self.dtype or u.dtype))
        return mix_back(x, y, h_post, h_res)

    def _mlp(self, x, norm=None):
        """The feed-forward on its own norm of ``x`` (``norm``: the
        norm to take; the parallel block's is none, its input is
        normed already)."""
        norm = norm or self.ln2
        if self.ffn == 'experts':
            return self.moe(norm(x))[0]
        with device_scope('lm.mlp'):
            if self.ffn == 'gated':
                return self.mlp(norm(x))
            # The pre-activation is what a checkpoint keeps; GELU is
            # rebuilt from it.
            hidden = named(self.mlp_in(norm(x)), LAYER_MATMUL_NAMES[0])
            return self.mlp_out(nn.gelu(hidden))

    def _both(self, x, mixer):
        """The block around ``mixer`` (normed input -> branch output):
        two residuals one after the other, the parallel form, or the one
        branch a block has, on the one norm."""
        if self.mixer == 'none':
            return self._around(
                'ffn', x, lambda u: self._mlp(u, norm=self.ln1))
        if self.ffn == 'none':
            return self._around('attn', x, lambda u: mixer(self.ln1(u)))
        if self.parallel:
            h = self.ln1(x)
            return (x + self._scaled(mixer(h))
                    + self._scaled(self._mlp(h, norm=lambda u: u)))
        x = self._around('attn', x, lambda u: mixer(self.ln1(u)))
        return self._around('ffn', x, self._mlp)

    def __call__(self, x, attn_mask=None, segment_ids=None,
                 deterministic=False, dropout_seed=None):
        def mixer(h):
            if self.mixer in RECURRENT:
                return getattr(self, self.mixer)(h)
            if self.mixer == 'latent':
                return self.attn(h)
            return self.attn(h, h, h, attn_mask, segment_ids=segment_ids,
                             deterministic=deterministic,
                             dropout_seed=dropout_seed)
        return self._both(x, mixer)

    def _cached(self, method, x, cache, layer, position=None):
        """``prefill`` / ``decode`` share this: the mixer's cached entry
        point in the attention branch. ``position``: the first new
        token's, for a recurrent mixer (an attention cache carries its
        own length)."""
        held = [cache]

        def mixer(h):
            if self.mixer in RECURRENT:
                held[0], a = getattr(getattr(self, self.mixer), method)(
                    h, held[0], position=position)
                return a
            step = getattr(self.attn, method)
            if self.mixer == 'latent':
                held[0], a = step(h, held[0], layer)
            else:
                held[0], a = step(h, h, h, held[0],
                                  **({} if layer is None
                                     else {'layer': layer}))
            return a
        x = self._both(x, mixer)
        return held[0], x

    def prefill(self, x, cache, layer=None, position=None):
        # layer: a latent mixer's cache is layer-stacked in prefill too.
        return self._cached('prefill', x, cache, layer, position)

    def decode(self, x, cache, layer=None, position=None):
        # layer: cache is a layer-stacked cache and this block is layer
        # ``layer`` of it (a scanned stack) — see attn.decode.
        return self._cached('decode', x, cache, layer, position)


class _ScanStackCore(nn.Module):
    """The scanned layer body: ONE :class:`TransformerBlock` whose three
    entry points (train forward, prefill, decode) are each lifted by
    ``nn.scan`` with their own axes — all binding the same ``block``
    child, so one layer-stacked parameter tree serves training and
    cached generation.

    ``layer``'s layer index arrives as the SCANNED input and salts the
    explicit dropout seed: a scanned stack's layers all share one flax
    module path, so the attention module's path-hash salt (attention.py,
    per-layer decorrelation) cannot tell them apart — the index fold
    does the same job."""
    dim: int
    num_heads: int
    mlp_ratio: int
    axis_name: str
    dtype: Any
    weight_quant: Any
    attn_kwargs: Any
    block_kwargs: Any = None

    def setup(self):
        self.block = TransformerBlock(
            dim=self.dim, num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio, axis_name=self.axis_name,
            dtype=self.dtype, weight_quant=self.weight_quant,
            attn_kwargs=self.attn_kwargs, name='block',
            **(self.block_kwargs or {}))

    def layer(self, x, layer_idx, attn_mask, segment_ids, deterministic,
              dropout_seed):
        seed = None
        if dropout_seed is not None:
            seed = jnp.bitwise_xor(
                jnp.asarray(dropout_seed, jnp.int32),
                layer_idx * jnp.int32(0x61C88647))
        new_layer()     # one trace of the body, one account of its names
        return self.block(x, attn_mask, segment_ids=segment_ids,
                          deterministic=deterministic,
                          dropout_seed=seed), None

    def prefill(self, x, cache):
        cache, x = self.block.prefill(x, cache)
        return x, cache

    def decode(self, carry, layer_idx):
        # The WHOLE stacked cache rides the carry beside x and the
        # layer's step addresses its own layer of it in place: as a
        # scanned input/output pair a layer's buffers would be sliced
        # out of the stack and written back on every token.
        x, caches = carry
        caches, x = self.block.decode(x, caches, layer=layer_idx)
        return (x, caches), None


class TransformerStack(nn.Module):
    """``n_layers`` blocks. Call signature mirrors the train-step
    contract — ``(keys, queries, values, attn_mask, ...)`` with the
    first tensor used as the block input — so ``make_train_step`` and
    ``apply_seq_parallel`` drive a whole stack exactly like one
    attention module. ``make_decode_caches``/``prefill``/``decode``
    carry one KV cache per layer (a model trained with this stack
    generates through them; stacked layers sharing an explicit
    ``dropout_seed`` draw distinct masks via the per-layer salt).

    ``scan_layers=True`` compiles the stack as one ``nn.scan`` over a
    single block with layer-stacked parameters
    (``params['layers']['block']`` with a leading ``n_layers`` axis vs
    the unrolled ``block_i`` subtrees) — same math, O(1) trace/compile
    in depth. Its KV caches are ONE pytree with a leading layer axis:
    ``prefill`` scans them (a layer's cache in, the filled one out),
    while ``decode`` — the per-token path — CARRIES the whole stack
    through the layer loop beside ``x`` and scans only the layer
    index, each layer's fused step appending to its own layer of the
    carried buffers in place (``decode_step(layer=)``), so a token
    moves no cache bytes but the rows it reads and the block it
    writes.
    ``remat=True`` (scan only) wraps the block in ``jax.checkpoint`` so
    the backward rematerializes one layer at a time — activation memory
    for the stack drops from O(n_layers) to O(1) layers plus the scan
    carry and what the policy keeps a layer. With no ``remat_policy``
    that is, by ``checkpoint_name``:

    - always, the flash route's two residuals (``FLASH_RESIDUAL_NAMES``):
      one ``(B, H, T, d_v)`` tensor in the compute type and one
      ``(B, H, T)`` float32 a layer (136 MB + 2 MB at 32 heads x 16384 x
      128 bfloat16), for which the backward runs the O(T²) forward
      kernel once a step, not twice;
    - while they fit, the longest prefix of ``LAYER_MATMUL_NAMES``, the
      layer's matmul outputs that the backward reads: ``mlp_hidden``
      (``B·T·mlp_ratio·dim`` elements; a gated MLP's two halves), then
      ``flash_qkv`` (``B·T·(H + 2·H_kv)·d``: q / k / v as the kernel takes
      them, rotated and split by head — under ``seq_mesh(N)`` k and v
      gathered, q the local shard), then ``attn_out`` (``B·T·dim``). Kept,
      the recompute holds no ``mlp_in``, q / k / v or output-projection
      matmul; norms, activations and residual adds are always rebuilt.

    THE FIT (:class:`~distributed_dot_product_tpu.models.remat.KeepWhatFits`)
    is reckoned once, when the layer scan is traced, from shapes and one
    constant of the chip — the same shapes on the same chip kind give
    the same program: ``budget = 0.9444 · bytes_limit`` (the rest is the
    fitted headroom under which the TPU compiler's schedule adds no
    rematerialization of its own; PERF.md section 6, PR 37) ``− held −
    n_layers · 2 · bytes(x)`` (the layer inputs the scan keeps and the
    flash residuals) ``− max(transient, 2.8 · a layer's named bytes)``
    (one layer's working set: its rebuilt tensors and their cotangents),
    and name by name ``(n_layers − 1) · bytes`` is taken from it (the
    layer being differentiated holds its own copy in the working set,
    kept or rebuilt). ``held``, ``transient`` and the device whose
    ``bytes_limit`` counts are the enclosing step's account
    (``models.remat.step_holds``): ``train.make_lm_train_step`` sees the
    whole model and its mesh and says parameters + gradients + optimizer
    state + the compute-type copy of the parameters, the head's chunk
    (less this stack's gradients, not live yet beside it) and the
    mesh's device. A stack differentiated OUTSIDE that step sees only
    its own parameters: it assumes four times their bytes (gradients,
    two moments) and their copy, on the default device. The limit is the
    device's reported one; a described device (an AOT compile) gets its
    kind's, so the program compiled for it is the chip's; the CPU has
    none and the whole tuple is kept; an accelerator that reports none
    and whose kind is unknown keeps the flash residuals alone.
    ``models.remat.remat_traces()`` reports what a trace took and why.

    Who emits what: ``flash_attention``'s differentiated forward the
    flash names and ``flash_qkv`` (the flash route, ulysses' local
    attention); the attention module's ``__call__`` ``attn_out`` on every
    softmax path; the block's MLP ``mlp_hidden``. So the ``'full'`` and
    ``'online'`` (ring) paths keep ``mlp_hidden`` and ``attn_out`` and
    rebuild their projections. A ``name`` equation lowers to nothing:
    prefill and decode are the same programs with or without them.

    ``remat_policy`` takes the default's place: a
    ``jax.checkpoint_policies`` name — ``'nothing_saveable'`` is full
    rematerialization, for a run at the memory limit; ``'dots_saveable'``
    etc. mean what they mean in JAX — or a policy itself. THE WAY BACK
    where the fitted default does not fit a step it was not fitted on
    (the compile fails, or XLA's text holds ``.remat`` instructions and
    the step runs slower) is a shorter prefix by hand,
    ``jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUAL_NAMES, *LAYER_MATMUL_NAMES[:n])``; ``n = 0`` is what
    the stack kept before it knew these names."""
    dim: int
    num_heads: int
    n_layers: int = 2
    mlp_ratio: int = 4
    axis_name: str = SEQ_AXIS
    dtype: Optional[jnp.dtype] = None
    # One knob quantizes every block's projections + MLP (see
    # TransformerBlock.weight_quant).
    weight_quant: Optional[str] = None
    attn_kwargs: Any = None
    scan_layers: bool = False
    remat: bool = False
    remat_policy: Optional[Union[str, Callable]] = None
    # The block's choices (TransformerBlock's norm / mixer / ffn /
    # residual / parallel fields, as a dict), and the KIND of each
    # layer: ``layer_kinds`` names the kinds, each with what it
    # overrides of ``block_kwargs`` (its ``'attn_kwargs'`` entry is laid
    # over the stack's ``attn_kwargs``, not in place of it — but for a
    # ``'latent'`` kind in a stack whose own mixer is another: the
    # stack's are another module's fields and say nothing to it), and
    # ``layer_pattern`` gives the layers' kinds in order — one name a
    # layer, or one period of names that the depth repeats (three window
    # layers then a full one; a model's leading dense layers before its
    # expert layers). A stack of more than one kind, and any with a
    # latent mixer or an expert feed-forward, runs unrolled
    # (``scan_layers=False``); its caches are a list, each layer's of
    # its own kind's geometry: a slab or a ring for an attention layer,
    # a ``StateCache`` for a recurrent one, one layer's ``LatentCache``
    # for a latent one, None for a layer without a mixer. (A stack whose
    # EVERY layer is latent keeps ONE layer-stacked ``LatentCache``.)
    block_kwargs: Any = None
    layer_kinds: Any = None
    layer_pattern: Any = None

    def _layer_kwargs(self, i):
        """Layer ``i``'s ``(attn_kwargs, block_kwargs)``: the stack's,
        with what the layer's kind overrides laid over them."""
        attn = dict(self.attn_kwargs or {})
        block = dict(self.block_kwargs or {})
        if self.layer_pattern:
            over = dict(self.layer_kinds[self.layer_pattern[
                i % len(self.layer_pattern)]] or {})
            own = over.pop('attn_kwargs', None) or {}
            if (over.get('mixer') == 'latent'
                    and block.get('mixer') != 'latent'):
                attn = {}
            attn.update(own)
            block.update(over)
        return attn, block

    def _block(self, name, i):
        attn, block = self._layer_kwargs(i)
        return TransformerBlock(
            dim=self.dim, num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio, axis_name=self.axis_name,
            dtype=self.dtype, weight_quant=self.weight_quant,
            attn_kwargs=attn, name=name, **block)

    @property
    def _mixed(self):
        return len(set(self.layer_pattern or ())) > 1

    @property
    def _latent(self):
        """Every layer a latent mixer: ONE layer-stacked cache, carried
        from block to block (:meth:`_latent_step`). A latent layer among
        other kinds is a kind like them, in the by-kind loop."""
        return all(self._layer_kwargs(i)[1].get('mixer') == 'latent'
                   for i in range(self.n_layers))

    def setup(self):
        if self.remat and not self.scan_layers:
            raise ValueError('remat=True requires scan_layers=True (the '
                             'unrolled stack has no scan body to wrap)')
        if isinstance(self.remat_policy, str) and not hasattr(
                jax.checkpoint_policies, self.remat_policy):
            raise ValueError(
                f'remat_policy {self.remat_policy!r} is not a '
                f'jax.checkpoint_policies name')
        kw = self.block_kwargs or {}
        if self.layer_pattern:
            unknown = set(self.layer_pattern) - set(self.layer_kinds or {})
            if unknown or self.n_layers % len(self.layer_pattern):
                raise ValueError(
                    f'layer_pattern {tuple(self.layer_pattern)} must name '
                    f'kinds of layer_kinds '
                    f'{sorted(self.layer_kinds or {})} and divide '
                    f'n_layers {self.n_layers}')
        if self.scan_layers and (self._mixed or self._latent
                                 or kw.get('mixer') in RECURRENT
                                 or kw.get('ffn') == 'experts'):
            # XLA's grouped-matmul kernel takes an expert layer's
            # weights whole, so nn.scan's slice of layer-stacked experts
            # is a copy of them a layer a token (21.6 of a 36.6 ms step;
            # chip, PR 26); a scan over several layer kinds would be a
            # scan over periods; the latent cache is carried from block
            # to block, and a recurrent state is no layer of a stack.
            raise ValueError("more than one layer kind, mixer='latent', "
                             f'a recurrent mixer {sorted(RECURRENT)} and '
                             "ffn='experts' run unrolled: pass "
                             'scan_layers=False')
        if not self.scan_layers:
            self.blocks = [self._block(f'block_{i}', i)
                           for i in range(self.n_layers)]
            return
        core = _ScanStackCore
        self.keep = None
        if self.remat:
            if self.remat_policy is None:
                policy = self.keep = KeepWhatFits(self.n_layers)
            elif isinstance(self.remat_policy, str):
                policy = getattr(jax.checkpoint_policies,
                                 self.remat_policy)
            else:
                policy = self.remat_policy
            # static_argnums indexes layer()'s args after self:
            # deterministic (a Python bool) is arg 4.
            core = nn.remat(core, policy=policy, prevent_cse=False,
                            static_argnums=(4,), methods=['layer'])
        bcast = nn.broadcast
        common = dict(variable_axes={'params': 0},
                      split_rngs={'params': True, 'dropout': True},
                      length=self.n_layers)
        self.layers = nn.scan(
            core,
            methods={
                'layer': dict(in_axes=(0, bcast, bcast, bcast, bcast),
                              **common),
                'prefill': dict(in_axes=0, out_axes=0, **common),
                'decode': dict(in_axes=0, **common),
            })(dim=self.dim, num_heads=self.num_heads,
               mlp_ratio=self.mlp_ratio, axis_name=self.axis_name,
               dtype=self.dtype, weight_quant=self.weight_quant,
               attn_kwargs=self._layer_kwargs(0)[0],
               block_kwargs=self._layer_kwargs(0)[1], name='layers')

    def __call__(self, keys, queries, values, attn_mask=None,
                 segment_ids=None, deterministic=False,
                 dropout_seed=None):
        # keys/queries/values are accepted for train-step signature
        # parity; a transformer block is self-attention on one stream.
        x = keys
        with device_scope('lm.stack_carry'):
            if self.scan_layers:
                tracing = contextlib.nullcontext()
                if self.keep is not None:
                    tracing = self.keep.tracing(
                        x, self.layers.variables.get('params', {}),
                        self.dtype)
                with tracing:
                    x, _ = self.layers.layer(
                        x, jnp.arange(self.n_layers, dtype=jnp.int32),
                        attn_mask, segment_ids, deterministic,
                        dropout_seed)
                return x
            for block in self.blocks:
                x = block(x, attn_mask, segment_ids=segment_ids,
                          deterministic=deterministic,
                          dropout_seed=dropout_seed)
            return x

    def make_decode_caches(self, batch, t_max, dtype=None):
        # Plain field arithmetic, no ``apply``: each layer's cache is
        # what ITS attention module builds (``parent=None`` keeps flax
        # from adopting the throwaway as a child of this one) — a window
        # kind's ring beside a full kind's slab. Scanned stacks get ONE
        # cache pytree with a leading layer axis (prefill's scanned
        # input, decode's loop carry); unrolled stacks a list.
        def latent_cache(layers, attn):
            return init_latent_cache(
                layers, batch, t_max, attn['kv_rank'] + attn['rope_dim'],
                dtype or attn.get('dtype') or self.dtype or jnp.float32)

        if self._latent:
            # ONE layer-stacked buffer: every block addresses its own
            # layer of it.
            return latent_cache(self.n_layers, self._layer_kwargs(0)[0])

        def layer_cache(i):
            attn, block = self._layer_kwargs(i)
            mixer = block.get('mixer', 'attention')
            if mixer == 'none':
                return None
            if mixer == 'latent':
                # one layer's buffer, beside the other kinds' caches
                return latent_cache(None, attn)
            if mixer in RECURRENT:
                # Of FIXED size: t_max says nothing to it.
                return RECURRENT[mixer](dim=self.dim, parent=None, **{
                    'dtype': self.dtype, **(block.get('ssm_kwargs') or {})
                }).make_cache(batch, dtype=dtype)
            return DistributedDotProductAttn(
                num_heads=self.num_heads, parent=None,
                **{'key_dim': self.dim, 'dtype': self.dtype, **attn}
            ).make_decode_cache(batch, t_max, dtype=dtype)
        caches = [layer_cache(i) for i in range(self.n_layers)]
        if self.scan_layers:
            return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        return caches

    def _latent_step(self, method, x, cache):
        """Prefill or decode over a layer-stacked latent cache: every
        block addresses its layer of the one buffer by number, and the
        buffer is carried from block to block."""
        for i, block in enumerate(self.blocks):
            cache, x = getattr(block, method)(x, cache, layer=i)
        return cache, x

    @staticmethod
    def _position(caches):
        """Where the call's first token stands: the length of the first
        layer cache that has one (the batch shares one clock: of a
        latent cache's lengths, one a session, the first), None in a
        stack of recurrent layers alone."""
        length = next((c.length for c in caches if hasattr(c, 'length')),
                      None)
        return length[0] if getattr(length, 'ndim', 0) else length

    def prefill(self, x, caches):
        with device_scope('lm.stack_carry'):
            if self._latent:
                return self._latent_step('prefill', x, caches)
            if self.scan_layers:
                x, caches = self.layers.prefill(x, caches)
                return caches, x
            # where the chunk's first token stands, for the recurrent
            # mixers: the attention layers' length
            position = self._position(caches)
            out = []
            for block, cache in zip(self.blocks, caches):
                cache, x = block.prefill(x, cache, position=position)
                out.append(cache)
            return out, x

    def decode(self, x, caches):
        with device_scope('lm.stack_carry'):
            if self._latent:
                return self._latent_step('decode', x, caches)
            if self.scan_layers:
                (x, caches), _ = self.layers.decode(
                    (x, caches), jnp.arange(self.n_layers,
                                            dtype=jnp.int32))
                return caches, x
            position = self._position(caches)
            out = []
            for block, cache in zip(self.blocks, caches):
                cache, x = block.decode(x, cache, position=position)
                out.append(cache)
            return out, x
