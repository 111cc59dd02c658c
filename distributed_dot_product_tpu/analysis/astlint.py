# -*- coding: utf-8 -*-
"""
AST ruleset: project-specific hazard patterns that a jaxpr can't show
(either because the code never traces — host branches, exception
handlers — or because the hazard *prevents* tracing).

Pure ``ast``, no third-party dependency: this is deliberately NOT a
generic style linter (ruff owns hygiene — see pyproject.toml); every
rule here encodes a contract this repo has already been burned by or
explicitly designed around. Scope is per rule: the traced-value rules
(``host-pull``, ``traced-bool-branch``) only police the jit hot paths
(``ops/``, ``models/``, ``serve/``, ``obs/``); ``clock-in-jit`` and
``silent-except`` apply package-wide plus ``scripts/``.

"Traced value" is approximated statically and conservatively: a local
name is *jax-derived* when it was assigned from a ``jnp.* / jax.* /
lax.*`` call (or an attribute/index of one) inside the same function.
Only jax-derived names and direct jnp-predicate calls trigger the
traced-value rules, so static-config idioms (``float(scale)`` on a
kwarg, ``jnp.asarray`` coercion) stay clean — zero false positives on
the current tree is a design requirement, because the clean-tree gate
runs in tier-1.

Suppression: ``# graphlint: allow[<rule>]`` on the line or the line
above (see analysis/base.py).
"""

import ast
import os

from distributed_dot_product_tpu.analysis.base import (
    Violation, allowed_by_pragma,
)

__all__ = ['lint_file', 'lint_paths', 'iter_python_files', 'AST_RULES']

AST_RULES = ('host-pull', 'traced-bool-branch', 'clock-in-jit',
             'silent-except')

# Rules whose scope is the jit hot paths only (path fragments matched
# against the repo-relative file path). serve/ and obs/ joined the
# sweep in PR 13: the serving tick and the obs sampling paths dispatch
# compiled programs per token, so a host pull of a jnp-derived value
# there stalls the same hot loop the kernel rules protect.
_HOT_PATH_FRAGMENTS = (os.sep + 'ops' + os.sep,
                       os.sep + 'models' + os.sep,
                       os.sep + 'serve' + os.sep,
                       os.sep + 'obs' + os.sep)

_JAX_ROOTS = {'jnp', 'jax', 'lax'}
_PREDICATE_FNS = {'any', 'all', 'isfinite', 'isnan', 'allclose',
                  'array_equal', 'isin'}
_HOST_CASTS = {'float', 'int', 'bool'}
_CLOCK_FNS = {'time', 'perf_counter', 'monotonic', 'process_time',
              'thread_time'}
_LOGGY_NAMES = {'log_exception', 'warn', 'warning', 'error', 'exception',
                'print', 'log', 'log_step', 'debug', 'info'}


def _root_name(node):
    """Leftmost Name of a dotted/indexed expression, or None."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_jax_call(node):
    """``jnp.foo(...)`` / ``jax.lax.bar(...)`` / ``lax.baz(...)``."""
    return (isinstance(node, ast.Call)
            and _root_name(node.func) in _JAX_ROOTS)


# The obs spans layer (obs/spans.py): roots its calls may appear under.
_SPAN_ROOTS = {'obs', 'spans', 'obs_spans'}
_SPAN_NAMES = {'span', 'spanned'}
# NOT among them, on purpose: utils/build_ledger.build_span, the one
# clock-reading name that belongs in jitted code — it times the TRACE
# (a kernel's body, a distributed matmul) and leaves nothing in the
# program (fixture each way: tests/graphlint_fixtures/
# fx_build_span_in_jit.py).


def _is_span_call(node):
    """``span(...)`` / ``spanned(...)`` / ``obs.span(...)`` /
    ``spans.span(...)`` — the obs layer's clock-reading context
    managers. A bare name matches only the exact identifiers (so a
    regex ``m.span()`` attribute on a non-obs object never fires: its
    root is the match object, not an obs module)."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id in _SPAN_NAMES
    if isinstance(fn, ast.Attribute):
        return fn.attr in _SPAN_NAMES and _root_name(fn) in _SPAN_ROOTS
    return False


def _is_jnp_predicate_call(node):
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _PREDICATE_FNS
            and _root_name(node.func) in _JAX_ROOTS)


def _jit_decorated(fn_node):
    """Does this function's decorator list mention jit?  Covers
    ``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``,
    ``@functools.partial(jit, ...)``."""
    for dec in fn_node.decorator_list:
        target = dec
        if isinstance(dec, ast.Call):
            # partial(jax.jit, ...): the jitted callable is arg 0.
            if (getattr(dec.func, 'attr', None) == 'partial'
                    or getattr(dec.func, 'id', None) == 'partial'):
                if dec.args:
                    target = dec.args[0]
            else:
                target = dec.func
        name = (target.attr if isinstance(target, ast.Attribute)
                else getattr(target, 'id', None))
        if name == 'jit':
            return True
    return False


class _FunctionChecker(ast.NodeVisitor):
    """Per-function pass: infer jax-derived locals, then flag host
    pulls and traced-bool branches on them."""

    def __init__(self, fn_node, rel, src_lines, out, hot, in_jit):
        self.fn = fn_node
        self.rel = rel
        self.lines = src_lines
        self.out = out
        self.hot = hot
        self.in_jit = in_jit or _jit_decorated(fn_node)
        self.jax_locals = set()
        # Pass 1: names assigned from jax calls anywhere in this
        # function body (order-insensitive — good enough statically,
        # and reassignment to host values is rare in kernel code).
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and _is_jax_value(node.value):
                for tgt in node.targets:
                    for el in _name_targets(tgt):
                        self.jax_locals.add(el)
            elif (isinstance(node, (ast.AugAssign, ast.AnnAssign))
                  and node.value is not None
                  and _is_jax_value(node.value)):
                for el in _name_targets(node.target):
                    self.jax_locals.add(el)

    def _emit(self, rule, node, msg):
        if not allowed_by_pragma(self.lines, node.lineno, rule):
            self.out.append(Violation(rule=rule, message=msg,
                                      file=self.rel, line=node.lineno))

    def _is_traced_expr(self, node):
        if _is_jnp_predicate_call(node):
            return True
        if isinstance(node, ast.Name) and node.id in self.jax_locals:
            return True
        if isinstance(node, ast.UnaryOp):
            return self._is_traced_expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self._is_traced_expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # Identity checks (`x is None` / `x is not None`) are host
            # predicates even on arrays — never traced.
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return any(self._is_traced_expr(n)
                       for n in [node.left, *node.comparators])
        return False

    # -- nested functions get their own checker (jit context inherits) --
    def visit_FunctionDef(self, node):
        if node is self.fn:
            self.generic_visit(node)
        else:
            _FunctionChecker(node, self.rel, self.lines, self.out,
                             self.hot, self.in_jit).visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        if self.hot:
            # .item() — always a host pull of a device value.
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == 'item' and not node.args):
                self._emit('host-pull', node,
                           '.item() forces a device readback (or a '
                           'tracer error under jit) — keep the value '
                           'on device or read it back once outside the '
                           'hot path')
            # float/int/bool/np.asarray/np.array on a jax-derived local.
            target = None
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _HOST_CASTS and node.args):
                target = node.args[0]
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in ('asarray', 'array')
                  and _root_name(node.func) in ('np', 'numpy')
                  and node.args):
                target = node.args[0]
            if (target is not None
                    and (self._is_traced_expr(target)
                         or _is_jax_value(target))):
                self._emit('host-pull', node,
                           f'host conversion of a traced value '
                           f'(`{ast.unparse(node)[:60]}`) blocks or '
                           f'crashes the jit hot path — use jnp/lax '
                           f'equivalents')
        if self.in_jit and isinstance(node.func, ast.Attribute):
            if (node.func.attr in _CLOCK_FNS
                    and _root_name(node.func) == 'time'):
                self._emit('clock-in-jit', node,
                           f'time.{node.func.attr}() inside a jitted '
                           f'function reads the clock at TRACE time '
                           f'and bakes a constant into the compiled '
                           f'program — time outside the jit boundary')
        if self.in_jit and _is_span_call(node):
            # The obs layer's spans read the host clock: inside a
            # jitted function they time the TRACE, not the execution,
            # and the recorded span silently describes compilation.
            # Spans wrap host-side dispatch — never traced code.
            self._emit('clock-in-jit', node,
                       'obs span inside a jitted function reads the '
                       'host clock at TRACE time — wrap the dispatch '
                       'of the compiled step, not its traced body')
        self.generic_visit(node)

    def visit_If(self, node):
        if self.hot and self._is_traced_expr(node.test):
            self._emit('traced-bool-branch', node,
                       'python `if` on a traced predicate fixes the '
                       'branch at trace time (or raises under jit) — '
                       'use lax.cond / jnp.where')
        self.generic_visit(node)

    def visit_While(self, node):
        if self.hot and self._is_traced_expr(node.test):
            self._emit('traced-bool-branch', node,
                       'python `while` on a traced predicate cannot '
                       'trace — use lax.while_loop')
        self.generic_visit(node)


def _is_jax_value(node):
    """Expression that produces a jax array: a jnp/lax call, or an
    attribute/index/binop over one."""
    if _is_jax_call(node):
        return True
    if isinstance(node, (ast.Attribute, ast.Subscript)):
        return _is_jax_value(node.value)
    if isinstance(node, ast.BinOp):
        return _is_jax_value(node.left) or _is_jax_value(node.right)
    return False


def _name_targets(tgt):
    if isinstance(tgt, ast.Name):
        yield tgt.id
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for el in tgt.elts:
            yield from _name_targets(el)


def _outermost_functions(tree):
    """Functions not nested inside another function (module-level and
    method definitions; recursion stops at each found function)."""
    found = []

    def scan(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                found.append(child)
            else:
                scan(child)

    scan(tree)
    return found


def _is_broad_handler(handler):
    t = handler.type
    if t is None:
        return True
    names = t.elts if isinstance(t, ast.Tuple) else [t]
    for n in names:
        name = n.attr if isinstance(n, ast.Attribute) else \
            getattr(n, 'id', None)
        if name in ('Exception', 'BaseException'):
            return True
    return False


def _handler_is_silent(handler):
    """No raise and no logging-ish call anywhere in the handler body."""
    for node in ast.walk(ast.Module(body=handler.body,
                                    type_ignores=[])):
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.Call):
            name = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else getattr(node.func, 'id', None))
            if name in _LOGGY_NAMES:
                return False
    return True


def _check_silent_except(tree, rel, lines, out):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        for handler in node.handlers:
            if _is_broad_handler(handler) and _handler_is_silent(handler):
                if not allowed_by_pragma(lines, handler.lineno,
                                         'silent-except'):
                    out.append(Violation(
                        rule='silent-except',
                        message='broad except that neither re-raises '
                                'nor logs swallows real failures — '
                                'log through utils.tracing.'
                                'log_exception or narrow the type',
                        file=rel, line=handler.lineno))


def lint_file(path, repo_root=None, rules=None):
    """Run the AST ruleset over one file; returns a Violation list."""
    rules = set(rules or AST_RULES)
    rel = (os.path.relpath(path, repo_root) if repo_root
           else os.fspath(path))
    with open(path, encoding='utf-8') as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        # Deliberately NOT subject to the rules filter: a file that
        # doesn't parse can hide any violation, so it always surfaces.
        return [Violation(rule='parse-error', file=rel,
                          line=e.lineno or 0,
                          message=f'file does not parse: {e.msg}')]
    lines = src.splitlines()
    hot = any(frag in os.sep + rel for frag in _HOT_PATH_FRAGMENTS)
    out = []
    if rules & {'host-pull', 'traced-bool-branch', 'clock-in-jit'}:
        # Checker roots are OUTERMOST functions only — nested defs are
        # reached through visit_FunctionDef's recursion, which is also
        # the only path that propagates the enclosing jit context.
        for node in _outermost_functions(tree):
            _FunctionChecker(node, rel, lines, out, hot,
                             in_jit=False).visit(node)
    if 'silent-except' in rules:
        _check_silent_except(tree, rel, lines, out)
    return [v for v in out if v.rule in rules]


def iter_python_files(paths, exclude_fragments=('graphlint_fixtures',
                                                '__pycache__')):
    """Yield .py files under the given files/directories, skipping
    deliberate-violation fixture trees and caches."""
    for p in paths:
        if os.path.isfile(p) and p.endswith('.py'):
            yield p          # explicitly-named files are never excluded
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if not any(f in d for f in exclude_fragments)]
            for fn in sorted(filenames):
                if fn.endswith('.py'):
                    yield os.path.join(dirpath, fn)


def lint_paths(paths, repo_root=None, rules=None):
    out = []
    for path in iter_python_files(paths):
        out.extend(lint_file(path, repo_root=repo_root, rules=rules))
    return out
