# -*- coding: utf-8 -*-
"""
Jaxpr linter: trace a registered entrypoint at its example abstract
shapes (``jax.make_jaxpr`` — no execution, no device memory) and walk
the ClosedJaxpr enforcing the repo's compiled-graph contracts:

- ``f32-accum``   — every ``dot_general`` on low-precision operands
  (bf16/f16 → f32, int8 → i32) requests a wide accumulator via
  ``preferred_element_type``. The Pallas kernels carry this everywhere
  (ops/pallas_attention.py, ops/pallas_decode.py); the LM head einsum
  requests it explicitly (models/lm.py). This rule is what keeps the
  next refactor from silently dropping it.
- ``cache-alias`` — each declared cache buffer must flow input→output
  through *surgical* writes only: ``dynamic_update_slice`` (appends),
  ``select_n`` (masked slot writes), sub-operand ``scatter`` (the paged
  pool's page-write spine), same-dtype ``convert_element_type``, and
  kernel ``input_output_aliases`` — across ``pjit``/``shard_map``/
  custom-vjp boundaries. A buffer that is re-materialized (arithmetic,
  gather, full-shape copy) or overwritten by a full-buffer-shaped
  ``dynamic_update_slice``/full-operand ``scatter`` breaks the in-place
  append contract and degrades every decode step into a cache copy.
- ``cache-upcast`` — no ``convert_element_type`` widens a cache-shaped
  tensor (e.g. ``cache.k.astype(f32)`` before a matmul): that
  materializes a full-size high-precision copy per step. Request the
  wide accumulator on the dot instead.
- ``collective-axis`` — collectives only name axes on the entrypoint's
  DECLARED mesh (``TraceSpec.mesh_axes``); inner ``shard_map`` meshes
  must agree with the declaration.
- ``donation``     — entrypoints declared as donating actually alias
  their buffers in the lowered module (``tf.aliasing_output`` /
  ``jax.buffer_donor`` argument attributes).

Tracing failures are reported as ``trace-error`` violations rather than
crashing the whole run, so one broken registration never hides the
others' findings.
"""

import jax
import jax.numpy as jnp

from distributed_dot_product_tpu.analysis.base import Violation

__all__ = ['JAXPR_RULES', 'lint_spec', 'lint_entrypoints']

JAXPR_RULES = ('f32-accum', 'cache-alias', 'cache-upcast',
               'collective-axis', 'donation', 'trace-error')

_LOW_FLOAT = (jnp.bfloat16, jnp.float16)
_LOW_INT = (jnp.int8, jnp.uint8)

# Collective primitives; their named axes ride in either the 'axes' or
# the 'axis_name' param (both are read — see _check_axes).
_COLLECTIVES = frozenset({
    'psum', 'pmax', 'pmin', 'all_gather', 'all_to_all', 'ppermute',
    'pbroadcast', 'reduce_scatter', 'axis_index', 'psum_scatter',
})


def _src(eqn):
    """(file, line) of the user frame that traced this equation, or
    (None, None) — an equation may carry no traceback."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def _sub_jaxprs(eqn):
    """Every sub-jaxpr carried in an eqn's params (pjit's ClosedJaxpr,
    shard_map's open Jaxpr, custom-vjp call_jaxpr, pallas_call jaxpr,
    scan/while/cond bodies — found generically)."""
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            # ClosedJaxpr forwards .eqns, so unwrap .jaxpr FIRST.
            if hasattr(getattr(item, 'jaxpr', None), 'eqns'):
                yield item.jaxpr                # ClosedJaxpr
            elif hasattr(item, 'eqns'):
                yield item                      # open Jaxpr


def _iter_eqns(jaxpr):
    """Depth-first over every equation, descending through call-like
    primitives."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub)


def _axis_strs(val):
    """Normalize an axes param to the set of *named* axes (positional
    ints from vmap are not mesh axes)."""
    if val is None:
        return set()
    items = val if isinstance(val, (tuple, list, set, frozenset)) \
        else (val,)
    return {a for a in items if isinstance(a, str)}


# -- rule: f32-accum ----------------------------------------------------

def _check_dots(spec, jaxpr, out):
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != 'dot_general':
            continue
        dtypes = [v.aval.dtype for v in eqn.invars
                  if hasattr(v.aval, 'dtype')]
        pref = eqn.params.get('preferred_element_type')
        low_f = any(d in _LOW_FLOAT for d in dtypes)
        low_i = any(d in _LOW_INT for d in dtypes)
        if not (low_f or low_i):
            continue
        ok = pref is not None and (
            (low_i and jnp.issubdtype(pref, jnp.integer)
             and jnp.dtype(pref).itemsize >= 4)
            or (not low_i and jnp.issubdtype(pref, jnp.floating)
                and jnp.dtype(pref).itemsize >= 4))
        if not ok:
            f, ln = _src(eqn)
            shown = pref if pref is None else jnp.dtype(pref).name
            out.append(Violation(
                rule='f32-accum', file=f, line=ln,
                entrypoint=spec.name,
                message=f'dot_general on '
                        f'{"/".join(str(d) for d in dtypes)} operands '
                        f'accumulates at preferred_element_type='
                        f'{shown} — request '
                        f'{"int32" if low_i else "float32"} '
                        f'(preferred_element_type) so the contraction '
                        f'accumulates wide on every backend'))


# -- rule: cache-upcast -------------------------------------------------

def _check_upcasts(spec, jaxpr, cache_shapes, out):
    if not cache_shapes:
        return
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != 'convert_element_type':
            continue
        aval = eqn.invars[0].aval
        if getattr(aval, 'shape', None) not in cache_shapes:
            continue
        new = eqn.params.get('new_dtype')
        if new is None:
            continue
        if jnp.dtype(new).itemsize > jnp.dtype(aval.dtype).itemsize:
            f, ln = _src(eqn)
            out.append(Violation(
                rule='cache-upcast', file=f, line=ln,
                entrypoint=spec.name,
                message=f'cache-shaped {aval.shape} tensor upcast '
                        f'{aval.dtype} → {jnp.dtype(new).name}: this '
                        f'materializes a full-size copy of the cache '
                        f'every step — keep the buffer narrow and '
                        f'request the wide accumulator on the dot '
                        f'(preferred_element_type) instead'))


# -- rule: collective-axis ----------------------------------------------

def _check_axes(spec, jaxpr, out):
    declared = set(spec.mesh_axes)
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == 'shard_map':
            mesh = eqn.params.get('mesh')
            axes = set(getattr(mesh, 'axis_names', ()) or ())
            bad = axes - declared
            if bad:
                f, ln = _src(eqn)
                out.append(Violation(
                    rule='collective-axis', file=f, line=ln,
                    entrypoint=spec.name,
                    message=f'shard_map over mesh axes '
                            f'{sorted(axes)} but the entrypoint '
                            f'declares mesh_axes='
                            f'{sorted(declared) or "()"} — declaration '
                            f'and program disagree about the topology'))
            continue
        if name not in _COLLECTIVES:
            continue
        used = _axis_strs(eqn.params.get('axes')) \
            | _axis_strs(eqn.params.get('axis_name'))
        bad = used - declared
        if bad:
            f, ln = _src(eqn)
            out.append(Violation(
                rule='collective-axis', file=f, line=ln,
                entrypoint=spec.name,
                message=f'{name} over axis {sorted(bad)} which is not '
                        f'on the declared mesh '
                        f'(mesh_axes={sorted(declared) or "()"})'))


# -- rule: cache-alias --------------------------------------------------

# Spine-preserving primitives: ops through which a cache buffer may
# legitimately flow from input to output without being re-materialized.
# `reshape` is a layout view (the kernel path folds (B, H, T, d) to
# (B·H, T, d) around its pallas_call); `transpose` is NOT — it moves
# every byte on TPU, so it stays off-spine and gets reported.
# `scatter` (operand position only) is the PAGED pool's page-write
# spine: per-slot appends and freed-page zeroing are drop-mode
# scatters into the pool operand — a full-operand-sized scatter (the
# degenerate rewrite) is blocked like a full-shape DUS.
_SPINE_WALK = {
    'dynamic_update_slice': lambda eqn: [eqn.invars[0]],
    'select_n': lambda eqn: list(eqn.invars[1:]),
    'convert_element_type': lambda eqn: [eqn.invars[0]],
    'reshape': lambda eqn: [eqn.invars[0]],
    'scatter': lambda eqn: [eqn.invars[0]],
    'copy_p': lambda eqn: [],               # explicit copy breaks it
}


def _inner_jaxpr(eqn):
    """The single callee jaxpr of a call-like eqn, or None."""
    for key in ('jaxpr', 'call_jaxpr'):
        item = eqn.params.get(key)
        # ClosedJaxpr forwards .eqns, so unwrap .jaxpr FIRST.
        if hasattr(getattr(item, 'jaxpr', None), 'eqns'):
            return item.jaxpr
        if hasattr(item, 'eqns'):
            return item
    return None


def _spine_sources(jaxpr, out_var, blockers):
    """All jaxpr INVARS reachable from ``out_var`` through
    spine-preserving ops. Disallowed producers are recorded in
    ``blockers`` as (primitive_name, file, line)."""
    produced = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            produced[v] = eqn
    invar_set = set(jaxpr.invars)
    sources, seen, stack = set(), set(), [out_var]
    while stack:
        v = stack.pop()
        if v in seen:
            continue
        seen.add(v)
        if v in invar_set:
            sources.add(v)
            continue
        eqn = produced.get(v)
        if eqn is None:
            continue                      # literal / constvar
        name = eqn.primitive.name
        if name in _SPINE_WALK:
            if name == 'dynamic_update_slice':
                op, upd = eqn.invars[0].aval, eqn.invars[1].aval
                if getattr(op, 'shape', None) == getattr(upd, 'shape',
                                                         None):
                    f, ln = _src(eqn)
                    blockers.append(('full-shape dynamic_update_slice',
                                     f, ln))
                    continue
            if name == 'scatter':
                op, upd = eqn.invars[0].aval, eqn.invars[-1].aval
                if getattr(upd, 'size', 0) >= getattr(op, 'size', 1):
                    f, ln = _src(eqn)
                    blockers.append(('full-operand scatter', f, ln))
                    continue
            if name == 'convert_element_type':
                src_aval = eqn.invars[0].aval
                if eqn.params.get('new_dtype') != src_aval.dtype:
                    # dtype-changing convert re-materializes the buffer
                    f, ln = _src(eqn)
                    blockers.append((f'convert_element_type to '
                                     f'{eqn.params.get("new_dtype")}',
                                     f, ln))
                    continue
            stack.extend(_SPINE_WALK[name](eqn))
            continue
        if name == 'pallas_call':
            aliases = eqn.params.get('input_output_aliases') or ()
            out_idx = eqn.outvars.index(v)
            hit = [in_idx for in_idx, o in aliases if o == out_idx]
            if not hit:
                f, ln = _src(eqn)
                blockers.append(('pallas_call output without an '
                                 'input_output_alias', f, ln))
            for in_idx in hit:
                stack.append(eqn.invars[in_idx])
            continue
        inner = _inner_jaxpr(eqn)
        if inner is not None and len(inner.outvars) == len(eqn.outvars):
            # Call boundary (pjit/shard_map/custom-vjp/remat): map the
            # outer outvar to the callee outvar, recurse, and map the
            # reachable callee invars back to outer operands. Callee
            # invars align with the TRAILING outer invars (leading
            # outer invars may be consts).
            out_idx = eqn.outvars.index(v)
            inner_sources = _spine_sources(inner, inner.outvars[out_idx],
                                           blockers)
            offset = len(eqn.invars) - len(inner.invars)
            for i, iv in enumerate(inner.invars):
                if iv in inner_sources and 0 <= offset + i:
                    stack.append(eqn.invars[offset + i])
            continue
        f, ln = _src(eqn)
        blockers.append((name, f, ln))
    return sources


def _check_cache_alias(spec, closed, flat_in_idx, flat_out_idx, out):
    jaxpr = closed.jaxpr
    for in_idx, out_idx in zip(flat_in_idx, flat_out_idx):
        blockers = []
        sources = _spine_sources(jaxpr, jaxpr.outvars[out_idx], blockers)
        if jaxpr.invars[in_idx] in sources:
            continue
        detail = ''
        if blockers:
            name, f, ln = blockers[0]
            where = f' at {f}:{ln}' if f else ''
            detail = f' (first off-spine producer: {name}{where})'
        out.append(Violation(
            rule='cache-alias', entrypoint=spec.name,
            message=f'cache buffer (flat arg {in_idx} → flat output '
                    f'{out_idx}) does not flow through surgical writes '
                    f'— it is re-materialized, so the in-place append '
                    f'degrades into a full cache copy per '
                    f'step{detail}'))


# -- rule: donation -----------------------------------------------------

def _check_donation(spec, out):
    try:
        if spec.prejitted:
            lowered = spec.fn.lower(*spec.args)
        else:
            lowered = jax.jit(
                spec.fn,
                donate_argnums=spec.donate_argnums or (),
                static_argnums=spec.static_argnums or (),
            ).lower(*spec.args)
        text = lowered.as_text()
    except Exception as e:  # graphlint: allow[silent-except]
        out.append(Violation(   # reported AS a violation, not swallowed
            rule='trace-error', entrypoint=spec.name,
            message=f'lowering for the donation check failed: {e}'))
        return
    n_alias = text.count('tf.aliasing_output') \
        + text.count('jax.buffer_donor')
    needed = max(1, spec.min_donated)
    if n_alias < needed:
        out.append(Violation(
            rule='donation', entrypoint=spec.name,
            message=f'entrypoint declares donated buffers but the '
                    f'lowered module aliases {n_alias} argument(s) '
                    f'(expected >= {needed}) — without donation every '
                    f'step copies the full buffers before writing '
                    f'(check donate_argnums on the jit)'))


# -- driver -------------------------------------------------------------

def _flat_indices(tree, selected):
    """Flat-leaf indices (tree_flatten order) of the identity-selected
    leaves."""
    leaves = jax.tree_util.tree_leaves(tree)
    idx = []
    for leaf in selected:
        matches = [i for i, l in enumerate(leaves) if l is leaf]
        if not matches:
            raise ValueError('cache selector returned a leaf that is '
                             'not in the tree')
        idx.append(matches[0])
    return idx


def lint_spec(spec, rules=None):
    """Lint one TraceSpec; returns a Violation list."""
    rules = set(rules or JAXPR_RULES)
    out = []
    try:
        # return_shape=True: ONE trace yields both the jaxpr and the
        # output pytree (a separate eval_shape would trace the most
        # expensive entrypoints a second time and burn a unit of the
        # prejitted entries' retrace budget for nothing).
        closed, out_tree = jax.make_jaxpr(
            spec.fn, return_shape=True)(*spec.args)
    except Exception as e:  # graphlint: allow[silent-except]
        msg = str(e).splitlines()[0] if str(e) else repr(e)
        return [Violation(rule='trace-error', entrypoint=spec.name,
                          message=f'entrypoint failed to trace at its '
                                  f'registered shapes: {msg}')]
    jaxpr = closed.jaxpr

    cache_shapes = set()
    flat_in = flat_out = ()
    if spec.cache_in is not None:
        in_leaves = spec.cache_in(spec.args)
        cache_shapes = {tuple(l.shape) for l in in_leaves}
        flat_in = _flat_indices(spec.args, in_leaves)
        out_leaves = spec.cache_out(out_tree)
        flat_out = _flat_indices(out_tree, out_leaves)
        if len(flat_in) != len(flat_out):
            raise ValueError(f'{spec.name}: cache_in/cache_out '
                             f'selector arity mismatch')

    if 'f32-accum' in rules:
        _check_dots(spec, jaxpr, out)
    if 'cache-upcast' in rules:
        _check_upcasts(spec, jaxpr, cache_shapes, out)
    if 'collective-axis' in rules:
        _check_axes(spec, jaxpr, out)
    if 'cache-alias' in rules and flat_in:
        _check_cache_alias(spec, closed, flat_in, flat_out, out)
    if 'donation' in rules and (spec.expect_donation):
        _check_donation(spec, out)
    if spec.allow:
        # Registration-level waiver (TraceSpec.allow): the violation
        # stays in the output as visible debt, flagged allowed so the
        # CLI exit code and the clean-tree gate ignore it.
        import dataclasses
        out = [dataclasses.replace(v, allowed=True)
               if v.rule in spec.allow else v for v in out]
    return out


def lint_entrypoints(entrypoints, rules=None):
    """Lint a registry mapping ``{name: builder}``; builder errors are
    reported as trace-error violations, never raised."""
    out = []
    for name, build in entrypoints.items():
        try:
            spec = build()
            if spec.name != name:
                spec = spec.replace(name=name)
        except Exception as e:  # graphlint: allow[silent-except]
            msg = str(e).splitlines()[0] if str(e) else repr(e)
            out.append(Violation(  # reported AS a violation, not swallowed
                rule='trace-error', entrypoint=name,
                message=f'entrypoint builder failed: {msg}'))
            continue
        out.extend(lint_spec(spec, rules=rules))
    return out
