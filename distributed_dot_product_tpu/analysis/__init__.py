# -*- coding: utf-8 -*-
"""
``graphlint`` — static analysis that mechanically enforces the repo's
performance and correctness contracts.

Five engines (see README "Static analysis"):

- **Jaxpr linter** (:mod:`.jaxpr_rules` over :mod:`.registry`): traces
  every registered entrypoint at example abstract shapes and walks the
  ClosedJaxpr — fp32 accumulation on low-precision dots, surgical
  (aliased) KV-cache writes + real donation, no cache-shaped upcasts,
  collectives only on declared mesh axes.
- **Retrace sentinel** (:mod:`distributed_dot_product_tpu.utils.retrace`
  — a runtime guard the lower layers import, so it lives below this
  package): trace-count budgets on jitted decode/serve entrypoints; on
  by default under pytest.
- **AST ruleset** (:mod:`.astlint`): pure-``ast`` hazard patterns —
  host pulls of traced values and traced-bool branching in hot paths,
  clock reads inside jit, silent broad excepts.
- **servelint** (:mod:`.protolint` / :mod:`.conclint` /
  :mod:`.determlint`): the serving/obs layer's contracts — emit call
  sites vs the closed EVENT_SCHEMA vocabulary and the RejectReason
  taxonomy, ``# guarded-by:`` lock discipline plus daemon/named thread
  discipline, and real-time/random/environ reads inside declared
  virtual-clock tick paths (``GRAPHLINT_TICK_ROOTS`` closures, with the
  intentional real-time modules in determlint's REAL_TIME_CONTRACT).
- **flowlint** (:mod:`.flowlint`): interprocedural typed-failure flow
  — per-function may-raise sets over the intra-package call graph
  judged against the typed contract at the declared serving roots
  (typed-escape, with ``file:line → file:line`` propagation chains),
  handler totality on typed serving errors, RejectReason taxonomy
  liveness, and ShardedPageTable stride-ownership.

CLI: ``python -m distributed_dot_product_tpu.analysis`` (exit 0 = no
violations). The tier-1 gate test (tests/test_graphlint.py) asserts a
clean tree, so a contract break fails CI before it ships.

This ``__init__`` stays import-light (no jax): the linter's engines and
the example programs (:mod:`.entrypoints`, which imports every layer)
load only when :func:`run_analysis` asks for them.
"""

from distributed_dot_product_tpu.analysis.base import (     # noqa: F401
    RULES, Violation, active_violations, format_violations,
)

__all__ = ['RULES', 'Violation', 'active_violations',
           'format_violations', 'run_analysis']


def run_analysis(paths=None, rules=None, repo_root=None,
                 jaxpr=True, ast_rules=True, entrypoints=None):
    """Run the full analyzer; returns a list of
    :class:`~distributed_dot_product_tpu.analysis.base.Violation`.

    ``paths``: files/dirs for the AST pass (default: the installed
    package plus ``scripts/`` and ``tests/`` when resolvable).
    ``rules``: restrict to these rule ids (default: all).
    ``entrypoints``: a ``{name: builder}`` mapping for the jaxpr pass
    (default: the central registry).
    """
    import os
    violations = []
    if ast_rules:
        from distributed_dot_product_tpu.analysis import (
            astlint, conclint, determlint, flowlint, protolint,
        )
        if paths is None:
            pkg = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            root = os.path.dirname(pkg)
            paths = [pkg]
            for extra in ('scripts', 'tests'):
                p = os.path.join(root, extra)
                if os.path.isdir(p):
                    paths.append(p)
            repo_root = repo_root or root
        # 'parse-error' is emitted by the AST pass (unconditionally, on
        # unparseable files) — requesting it must run that pass.
        ast_rule_set = None if rules is None else \
            [r for r in rules
             if r in astlint.AST_RULES or r == 'parse-error']
        if ast_rule_set is None or ast_rule_set:
            violations.extend(astlint.lint_paths(
                paths, repo_root=repo_root, rules=ast_rule_set))
        # servelint families ride the same AST pass and path set.
        for mod, fam in ((protolint, protolint.PROTO_RULES),
                         (conclint, conclint.CONC_RULES),
                         (determlint, determlint.DETERM_RULES),
                         (flowlint, flowlint.FLOW_RULES)):
            fam_rules = None if rules is None else \
                [r for r in rules if r in fam]
            if fam_rules is None or fam_rules:
                violations.extend(mod.lint_paths(
                    paths, repo_root=repo_root, rules=fam_rules))
    if jaxpr:
        from distributed_dot_product_tpu.analysis import jaxpr_rules
        jaxpr_rule_set = None if rules is None else \
            [r for r in rules if r in jaxpr_rules.JAXPR_RULES]
        if jaxpr_rule_set is None or jaxpr_rule_set:
            if entrypoints is None:
                from distributed_dot_product_tpu.analysis.registry import (
                    default_entrypoints,
                )
                entrypoints = default_entrypoints()
            violations.extend(jaxpr_rules.lint_entrypoints(
                entrypoints, rules=jaxpr_rule_set))
    return violations
