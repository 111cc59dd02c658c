# -*- coding: utf-8 -*-
"""
Central entrypoint registry: what one traceable example of a public
computation is (:class:`TraceSpec`) and where the jaxpr linter
(analysis/jaxpr_rules.py) finds them all (:func:`default_entrypoints`).

The examples themselves — the shapes, meshes and builders — are
analysis/entrypoints.py, which imports every layer it lints; this module
imports none of them, and no layer imports this one. The tier-1 gate
test (tests/test_graphlint.py) fails if any registered entrypoint
violates a rule — that is how the contracts survive growth.
"""

import dataclasses
from typing import Any, Callable, Optional, Tuple

__all__ = ['TraceSpec', 'default_entrypoints', 'resolve_registry_arg']


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One traceable entrypoint example.

    ``fn``/``args``: the callable and example arguments (concrete
    arrays or ShapeDtypeStructs — tracing never executes).
    ``mesh_axes``: mesh axis names this entrypoint is DECLARED to run
    over; collectives naming anything else violate ``collective-axis``.
    ``cache_in``/``cache_out``: identity selectors — given ``args`` /
    the ``eval_shape`` output, return the cache-buffer leaves, pairwise
    aligned — driving ``cache-alias`` and ``cache-upcast``.
    ``expect_donation``: run the ``donation`` rule. ``prejitted``: the
    fn already carries its jit (lower it directly); otherwise the rule
    jits with ``donate_argnums``. ``min_donated``: least number of
    aliased/donor arguments the lowered module must show.
    ``allow``: rule ids whose violations on THIS entry are known,
    documented debt — reported with ``allowed=True`` (visible in
    ``--format json``) but never failing the CLI or the gate. The
    registration line should carry a matching ``# graphlint:
    allow[...]`` comment so the waiver stays greppable. Currently
    UNUSED: the last waivers (the flax ``linen.Dense``
    bf16-accumulation debt) were retired by the owned dense
    (models/dense.py), and the gate test asserts the waiver set stays
    empty — adding one is a reviewed decision, not a default.
    """
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    mesh_axes: Tuple[str, ...] = ()
    cache_in: Optional[Callable] = None
    cache_out: Optional[Callable] = None
    expect_donation: bool = False
    prejitted: bool = False
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    min_donated: int = 1
    allow: Tuple[str, ...] = ()

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


def resolve_registry_arg(arg):
    """``MODULE:ATTR`` → a ``{name: builder}`` mapping (callables are
    called) — the graphlint CLI's ``--registry`` escape hatch. Raises
    ValueError on a malformed argument."""
    import importlib
    modpath, _, attr = arg.partition(':')
    if not attr:
        raise ValueError('--registry takes MODULE:ATTR')
    obj = getattr(importlib.import_module(modpath), attr)
    return obj() if callable(obj) else obj


def default_entrypoints():
    """The ordered ``{name: builder}`` registry of
    analysis/entrypoints.py (a copy: a caller may take a subset).
    Imported here, at the call, because that module imports every layer
    of the package."""
    from distributed_dot_product_tpu.analysis.entrypoints import (
        ENTRYPOINTS,
    )
    return dict(ENTRYPOINTS)
