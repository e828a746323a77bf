"""Recompile and host-sync hazard rules for the jitted hot paths.

Two real shipped bugs sit behind these:

* PR 10's compile watcher caught the trainer silently recompiling the
  ENTIRE train step on the first step after every threshold-adjustment
  epoch (an input's sharding drifted, changing the jit cache key).  The
  static cousins of that failure — re-``jit`` inside a loop,
  ``jax.jit(lambda ...)`` (a fresh cache key per evaluation), and
  device-constant literals built per hot-loop iteration — are all
  visible in the AST.
* PR 4 coalesced the serve hot path to ONE host pull per decode tick
  and per prefill; an accidental ``np.asarray``/``float()``/``.item()``
  on a traced value in those functions silently re-serialises the
  pipeline.  The rule taints locals assigned from device-producing
  calls (jnp.*, ``*_impl``, ``_programs()[...]``-style dispatches) and
  flags sync spellings applied to tainted values; the intentional
  single pulls are inline-suppressed at the site with their
  justification.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from trustworthy_dl_tpu.analysis import astutil
from trustworthy_dl_tpu.analysis.engine import (Finding, LintConfig,
                                                ModuleInfo, Project, Rule,
                                                match_any)

_JIT_CALLS = frozenset({"jax.jit", "jax.pmap"})

#: jnp constructors whose all-literal call builds a device constant.
_DEVICE_LITERAL_CTORS = frozenset({
    "jnp.array", "jnp.asarray", "jnp.zeros", "jnp.ones", "jnp.full",
    "jnp.arange",
})

#: Function-name shapes that mark a serving/training hot loop body.
_HOT_FUNCTION_PATTERNS = ("*tick*", "*decode*", "*prefill*", "*step*",
                          "train_epoch", "run_until_idle")

_SYNC_FUNCS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                         "numpy.array", "jax.device_get"})
_SYNC_BUILTINS = frozenset({"float", "int", "bool"})
_SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})


def _is_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return all(_is_literal(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp):
        return _is_literal(node.operand)
    return False


class RecompileHazardRule(Rule):
    """jit cache-key churn visible statically: re-jit inside loops,
    jit-of-lambda, and per-iteration device-constant literals in hot
    loops."""

    name = "recompile-hazard"
    description = ("no jax.jit in loops, no jax.jit(lambda), no "
                   "jnp.array literals inside hot loops")

    def applies(self, rel: str, config: LintConfig) -> bool:
        return rel.startswith(config.package_name + "/")

    def check(self, module: ModuleInfo, project: Project,
              config: LintConfig) -> Iterable[Finding]:
        hot_module = match_any(module.rel, config.hot_loop_modules)
        for node, parents in astutil.walk_with_parents(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.dotted(node.func)
            if name in _JIT_CALLS:
                if astutil.inside_loop(parents):
                    yield self.finding(
                        module, node,
                        f"{name}() inside a loop re-traces every "
                        f"iteration — build the jitted callable once "
                        f"outside")
                if node.args and isinstance(node.args[0], ast.Lambda):
                    yield self.finding(
                        module, node,
                        f"{name}(lambda ...) creates a fresh cache "
                        f"entry per evaluation — jit a named function")
            elif hot_module and name in _DEVICE_LITERAL_CTORS \
                    and node.args and _is_literal(node.args[0]):
                func = astutil.enclosing_function(parents)
                if func is None or not any(
                        astutil.match_name(func.name, p)
                        for p in _HOT_FUNCTION_PATTERNS):
                    continue
                if func.name.endswith("_impl"):
                    continue  # traced program body: constants fold
                if astutil.inside_loop(parents, within=func):
                    yield self.finding(
                        module, node,
                        f"{name}({ast.unparse(node.args[0])}) builds a "
                        f"device constant every {func.name}() loop "
                        f"iteration — hoist it (PR 10 storm pattern)")


def _device_producing(node: ast.Call) -> bool:
    func = node.func
    if isinstance(func, ast.Subscript):
        # _programs()["decode"](...) / prog["spec_draft"](...)
        return True
    name = astutil.dotted(func)
    if name is None:
        return False
    if name.startswith("jnp.") or name.startswith("jax."):
        return name not in _SYNC_FUNCS
    tail = name.rsplit(".", 1)[-1]
    return tail.endswith("_impl") or tail in ("_train_step",
                                              "_eval_step", "_jit_pack")


def _sync_kind(node: ast.Call) -> str:
    """'' when not a sync spelling, else a short description."""
    name = astutil.dotted(node.func)
    if name in _SYNC_FUNCS:
        return name
    if name in _SYNC_BUILTINS:
        return name
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _SYNC_METHODS:
        return f".{node.func.attr}()"
    return ""


_COMPS = (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)


class _TaintScan:
    """Per-function device-taint propagation (flow-insensitive to a
    fixpoint, which is conservative and cheap).  Comprehension targets
    are scoped, exactly as in Python 3: ``[np.asarray(d) for d in
    device_list]`` must not leak a tainted ``d`` over an unrelated
    host-side ``d`` later in the function."""

    def __init__(self, func: ast.AST):
        self.func = func
        self.tainted: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for node in ast.walk(func):
                names: Set[str] = set()
                if isinstance(node, ast.Assign) \
                        and self._expr_tainted(node.value):
                    for t in node.targets:
                        names.update(astutil.assigned_names(t))
                elif isinstance(node, (ast.AnnAssign, ast.AugAssign)) \
                        and node.value is not None \
                        and self._expr_tainted(node.value):
                    names.update(astutil.assigned_names(node.target))
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "append" \
                        and isinstance(node.func.value, ast.Name) \
                        and any(self._expr_tainted(a) for a in node.args):
                    # xs.append(device_value): the container now yields
                    # device values when iterated.
                    names.add(node.func.value.id)
                elif isinstance(node, (ast.For, ast.AsyncFor)) \
                        and self._expr_tainted(node.iter):
                    names.update(astutil.assigned_names(node.target))
                if not names <= self.tainted:
                    self.tainted |= names
                    changed = True

    def comp_scope(self, node: ast.AST,
                   extra: frozenset = frozenset()) -> frozenset:
        """Comprehension-local tainted targets (targets bound from a
        tainted iterable), given already-accumulated ``extra``."""
        out = set(extra)
        for gen in getattr(node, "generators", ()):
            if self._expr_tainted(gen.iter, frozenset(out)):
                out.update(astutil.assigned_names(gen.target))
        return frozenset(out)

    def _expr_tainted(self, expr: ast.AST,
                      extra: frozenset = frozenset()) -> bool:
        """Does the expression's VALUE carry a device buffer?  Sync
        calls are boundaries (their result is host memory); a
        comprehension's value is its element expression, evaluated with
        the comprehension targets scoped in."""
        stack = [(expr, extra)]
        while stack:
            node, ctx = stack.pop()
            if isinstance(node, ast.Call):
                if _sync_kind(node):
                    continue  # result is host-side
                if _device_producing(node):
                    return True
            if isinstance(node, _COMPS):
                scope = self.comp_scope(node, ctx)
                if isinstance(node, ast.DictComp):
                    stack.append((node.key, scope))
                    stack.append((node.value, scope))
                else:
                    stack.append((node.elt, scope))
                continue
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load) \
                    and (node.id in self.tainted or node.id in ctx):
                return True
            stack.extend((child, ctx)
                         for child in ast.iter_child_nodes(node))
        return False


class HostSyncRule(Rule):
    """No device→host pulls on traced values inside the decode tick /
    ``_train_step`` dispatch paths beyond the inline-suppressed
    intentional ones."""

    name = "host-sync"
    description = ("float()/int()/.item()/np.asarray on device values "
                   "is banned in the decode tick and train dispatch")

    def applies(self, rel: str, config: LintConfig) -> bool:
        return rel in config.host_sync_scopes

    def check(self, module: ModuleInfo, project: Project,
              config: LintConfig) -> Iterable[Finding]:
        scoped = set(config.host_sync_scopes.get(module.rel, ()))
        for func in module.functions():
            if func.name not in scoped:
                continue
            scan = _TaintScan(func)
            for node, parents in astutil.walk_with_parents(func):
                if not isinstance(node, ast.Call):
                    continue
                kind = _sync_kind(node)
                if not kind:
                    continue
                arg: ast.AST
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_METHODS:
                    arg = node.func.value
                elif node.args:
                    arg = node.args[0]
                else:
                    continue
                # A sync call INSIDE a comprehension sees that
                # comprehension's scoped targets (``np.asarray(d) for d
                # in device_list`` is a real sync on d).
                extra: frozenset = frozenset()
                for ancestor in parents:
                    if isinstance(ancestor, _COMPS):
                        extra = scan.comp_scope(ancestor, extra)
                if scan._expr_tainted(arg, extra):
                    yield self.finding(
                        module, node,
                        f"{kind} forces a device->host sync on a "
                        f"traced value inside {func.name}() — batch it "
                        f"into the tick's single pull or suppress with "
                        f"justification")
