"""What a rematerialized layer keeps for its backward pass.

The names a transformer layer gives the tensors worth keeping
(``LAYER_MATMUL_NAMES``), the account of what the program around a
rematted stack holds anyway (:func:`step_holds`), the checkpoint policy
that fits the longest prefix of the names beside it
(:class:`KeepWhatFits`, ``TransformerStack``'s default) and the counter
that says what a trace took (:func:`remat_traces`).
"""

import contextlib
import math

import jax
from jax.ad_checkpoint import checkpoint_name

from distributed_dot_product_tpu.models.dense import dense_param_bytes
from distributed_dot_product_tpu.ops.pallas_attention import (
    FLASH_QKV_NAME, FLASH_RESIDUAL_NAMES,
)
from distributed_dot_product_tpu.utils.trace_sinks import TraceSinks

__all__ = ['LAYER_MATMUL_NAMES', 'KeepWhatFits', 'named', 'note_named',
           'remat_traces', 'step_holds']

# Tags of a transformer layer's three matmul outputs that its backward
# reads and a rematerialized layer would rebuild, IN THE ORDER a
# checkpoint takes them while they fit: the MLP's hidden pre-activation
# (``TransformerBlock._mlp``; both halves of a gated one), q / k / v as
# the flash kernel takes them (its own residuals, named in
# ``ops.pallas_attention._flash_fwd``), the attention output projection's
# result (the attention module's ``__call__``). Each is linear in the
# local tokens to hold and costs the stream's width in FLOPs a kept byte
# to rebuild; nothing elementwise is named.
LAYER_MATMUL_NAMES = ('mlp_hidden', FLASH_QKV_NAME, 'attn_out')

# The fit's two constants, FITTED and not derived: the LM train step
# compiled for a described v5e at both training cells' widths, 1-8
# layers, T 8192 to 32768, each prefix forced in turn (15 points; PERF.md
# section 6, PR 37). What binds is not "it compiles": near the limit XLA
# first rematerializes on its own (``.remat`` instructions: it rebuilt the
# very ``mlp_in`` matmul that was kept, and the step ran 1.3 % SLOWER than
# the parent's; chip, PR 37). Its schedule stayed free of that while the
# need reckoned here was at most 14.77 GiB of a ``bytes_limit`` of 15.75
# (which is already less the runtime's reservation), and not from 15.00
# on: the headroom puts the line at 14.87.
_HEADROOM = 0.0556      # share of the limit left to the compiler's schedule
_LAYER_WORK = 2.8       # a rebuilt layer and its cotangents, in named bytes

# ``bytes_limit`` by ``device_kind`` of a device that is described and
# not attached (an AOT compile: it has no ``memory_stats``), so that the
# program compiled for it is the one the chip would get.
_DESCRIBED_LIMITS = {'TPU v5 lite': 16909336064}

_OPEN = []              # the open step_holds() accounts, innermost last
_REMAT_TRACES = TraceSinks()


def device_bytes_limit(device):
    """``device``'s ``bytes_limit`` — a constant of the chip, not the
    moment's ``bytes_in_use``. A described device reports nothing and
    gets its kind's; the CPU has none (``inf``: everything fits, XLA's
    compile says otherwise); an accelerator of which nothing is known
    gets 0, so nothing more than the flash residuals is kept."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:      # described, not attached
        stats = None
    if stats and 'bytes_limit' in stats:
        return stats['bytes_limit']
    if device.platform == 'cpu':
        return math.inf
    return _DESCRIBED_LIMITS.get(device.device_kind, 0)


class step_holds:
    """The account of what the program around a rematted stack holds
    whatever the stack keeps, open as a ``with`` block around the trace:
    ``held`` bytes for the whole step (parameters, optimizer state,
    gradients, the compute-type copy of the parameters), ``transient``
    bytes that are live only while no layer is (the head's chunk), and
    the ``device`` the step is compiled for, whose limit they are fitted
    under. ``train.make_lm_train_step`` opens it; a stack differentiated
    outside one opens its own (:meth:`KeepWhatFits.tracing`). While it
    is open it also gathers ``named``: bytes by name of what the layer
    being traced tags (:func:`named`)."""

    def __init__(self, held, transient=0, device=None):
        self.held, self.transient = int(held), int(transient)
        self.limit = device_bytes_limit(device or jax.devices()[0])
        self.named = {}

    def __enter__(self):
        _OPEN.append(self)
        return self

    def __exit__(self, *exc):
        _OPEN.remove(self)


def note_named(name, *tensors):
    """Tell the open account the sizes of tensors that carry ``name``
    (or will, once differentiated: ``flash_attention``'s operands, which
    its forward rule names after the layer body is traced)."""
    for account in _OPEN[-1:]:
        account.named[name] = account.named.get(name, 0) + sum(
            x.size * x.dtype.itemsize for x in tensors)


def named(x, name):
    """``checkpoint_name(x, name)`` — an identity unless a checkpoint
    policy saves the name — told to the open account."""
    note_named(name, x)
    return checkpoint_name(x, name)


def new_layer():
    """A layer body's trace starts: the open account forgets what an
    earlier trace of the body named (scan may trace it again to settle
    the carry's type: the last one stands)."""
    for account in _OPEN[-1:]:
        account.named.clear()


def remat_traces():
    """Collect what each rematted ``TransformerStack`` keeps while the
    block runs: one dict per trace of a stack's layer scan under the
    default policy (flax asks the policy whenever it traces the scan,
    differentiated or not) — ``kept`` (the names, flash residuals
    first), ``first_refused`` (the first of ``LAYER_MATMUL_NAMES`` that
    did not fit, None when all did), ``layer_bytes`` (``{name: bytes a
    layer}`` as traced), ``kept_bytes`` (what the fitted prefix takes of
    the budget: ``n_layers - 1`` layers of it), ``n_layers``, and the
    ``budget`` with its parts ``limit`` (``inf`` on the CPU), ``headroom``,
    ``held``, ``transient``, ``layer_inputs``, ``layer_work``::

        with remat_traces() as traces:
            step.lower(*args).compile()
        assert traces[0]['first_refused'] is None
    """
    return _REMAT_TRACES.open()


class KeepWhatFits:
    """The default checkpoint policy of a rematted stack: the flash
    residuals and the longest prefix of ``LAYER_MATMUL_NAMES`` whose
    stacked bytes fit (``TransformerStack`` says what of). The stack's
    call opens :meth:`tracing` around the layer scan; JAX asks the
    policy when that scan is differentiated, after the body is traced,
    and the first question fixes the answer."""

    def __init__(self, n_layers):
        self.n_layers = n_layers
        self.policy = None

    @contextlib.contextmanager
    def tracing(self, x, own_params, compute_dtype):
        """Around the stack's layer scan on input ``x``: take the
        enclosing step's account, or open one of the stack's own
        parameters alone — gradients and two moments as large, and the
        compute-type copy — and keep what the fit will read of it."""
        own = dense_param_bytes(own_params)
        with contextlib.ExitStack() as stack:
            account = _OPEN[-1] if _OPEN else stack.enter_context(
                step_holds(4 * own
                           + dense_param_bytes(own_params, compute_dtype)))
            self.policy = None
            self.x_bytes = x.size * x.dtype.itemsize
            self.limit, self.held = account.limit, account.held
            # The stack's gradients are not live yet while the head's
            # chunk is.
            self.transient = max(0, account.transient - own)
            # This stack's layer body names into a dict of its own.
            account.named = self.layer_bytes = {}
            try:
                yield
            finally:
                account.named = {}

    def fit(self):
        """``(prefix, record)`` from the traced bytes and the chip."""
        per_layer = {n: self.layer_bytes.get(n, 0)
                     for n in LAYER_MATMUL_NAMES}
        parts = {
            'limit': self.limit,
            'headroom': self.limit * _HEADROOM,
            'held': self.held,
            'transient': self.transient,
            # The scan keeps each layer's input, and the policy the
            # flash residuals: one more stream-wide tensor and a
            # float32 row of logsumexps a head.
            'layer_inputs': self.n_layers * 2 * self.x_bytes,
            'layer_work': int(_LAYER_WORK * sum(per_layer.values())),
        }
        budget = (self.limit * (1 - _HEADROOM) - self.held
                  - parts['layer_inputs']
                  - max(self.transient, parts['layer_work']))
        kept, total, refused = [], 0, None
        for name in LAYER_MATMUL_NAMES:
            # The layer being differentiated holds its own copy inside
            # the working set, kept or rebuilt: the stack adds the others'.
            more = (self.n_layers - 1) * per_layer[name]
            if total + more > budget:
                refused = name
                break
            kept.append(name)
            total += more
        return kept, dict(
            parts, n_layers=self.n_layers, layer_bytes=per_layer,
            kept=(*FLASH_RESIDUAL_NAMES, *kept), first_refused=refused,
            kept_bytes=total, budget=budget)

    def __call__(self, prim, *avals, **params):
        if self.policy is None:
            kept, record = self.fit()
            _REMAT_TRACES.note(record)
            self.policy = jax.checkpoint_policies.save_only_these_names(
                *FLASH_RESIDUAL_NAMES, *kept)
        return self.policy(prim, *avals, **params)
