"""Resource-locality rules: one-spelling contracts for shared device
resources.

The paged serving tier keeps its compile-once pin by funnelling every
shape- or sharding-relevant decision through a single home module; a
helpful second spelling elsewhere (a local ``adapter_page_row`` clone,
an ad-hoc adapter ``PartitionSpec``) compiles — and silently forks the
pin, so churn that must never recompile starts recompiling on the
replica that took the fork.  These rules make the locality contract a
lint invariant instead of a code-review hope.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from trustworthy_dl_tpu.analysis.engine import (Finding, LintConfig,
                                                ModuleInfo, Project, Rule,
                                                match_any)


def _partition_spec_aliases(module: ModuleInfo) -> Set[str]:
    """Local names ``jax.sharding.PartitionSpec`` is bound to in this
    module (``import ... as P`` included) — construction sites resolve
    through these the way the interpreter would."""
    names: Set[str] = set()
    for node in module.walk():
        if isinstance(node, ast.ImportFrom) and node.module \
                and "sharding" in node.module:
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    names.add(alias.asname or alias.name)
    return names


class AdapterLocalityRule(Rule):
    """The adapter page-table row and the adapter-pool PartitionSpecs
    are spelled ONLY in serve/adapters.py (contracts.
    ADAPTER_HOME_MODULE): a definition of ``adapter_page_row``/
    ``adapter_partition_specs`` elsewhere, or a ``PartitionSpec(...)``
    built inside an adapter-handling function elsewhere, forks the
    compile-once pin the paged programs key on.  Importing and CALLING
    the home spellings is the sanctioned path and is not flagged."""

    name = "adapter-locality"
    description = ("adapter page-table/PartitionSpec spellings live "
                   "only in serve/adapters.py")

    def applies(self, rel: str, config: LintConfig) -> bool:
        return (rel != config.adapter_home_module
                and rel.startswith(config.package_name + "/"))

    def check(self, module: ModuleInfo, project: Project,
              config: LintConfig) -> Iterable[Finding]:
        reserved = set(config.adapter_locality_names)
        spec_names = _partition_spec_aliases(module)
        for node in module.walk():
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node.name in reserved:
                    yield self.finding(
                        module, node,
                        f"{node.name}() redefined outside "
                        f"{config.adapter_home_module} — the adapter "
                        f"page table/PartitionSpecs have one spelling")
                    continue
                if "adapter" not in node.name.lower() or not spec_names:
                    continue
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) \
                            and isinstance(sub.func, ast.Name) \
                            and sub.func.id in spec_names:
                        yield self.finding(
                            module, sub,
                            f"adapter-targeted PartitionSpec built in "
                            f"{node.name}() — adapter sharding is "
                            f"spelled only in "
                            f"{config.adapter_home_module}")


class ShardingRegistryRule(Rule):
    """Every ``PartitionSpec(...)`` resolves through the logical-axis
    rule table in core/sharding.py (contracts.SHARDING_HOME_MODULE) —
    a spec constructed anywhere else hard-codes a mesh-axis name the
    registry can no longer retarget, and forks the layout the
    compile-once pins and elastic migrations key on.  Catches direct
    calls, ``... as P`` aliases, and attribute spellings
    (``jax.sharding.PartitionSpec(...)``); importing the registry's
    helpers is the sanctioned path and is not flagged."""

    name = "sharding-registry-only"
    description = ("PartitionSpec construction lives only in "
                   "core/sharding.py (the logical-axis registry)")

    def applies(self, rel: str, config: LintConfig) -> bool:
        if rel == config.sharding_home_module:
            return False
        if match_any(rel, config.sharding_spec_whitelist):
            return False
        return rel.startswith(config.package_name + "/")

    def check(self, module: ModuleInfo, project: Project,
              config: LintConfig) -> Iterable[Finding]:
        spec_names = _partition_spec_aliases(module)
        for node in module.walk():
            if not isinstance(node, ast.Call):
                continue
            direct = isinstance(node.func, ast.Name) \
                and node.func.id in spec_names
            attr = isinstance(node.func, ast.Attribute) \
                and node.func.attr == "PartitionSpec"
            if direct or attr:
                yield self.finding(
                    module, node,
                    f"PartitionSpec constructed outside "
                    f"{config.sharding_home_module} — shardings "
                    f"resolve through the logical-axis registry "
                    f"(core.sharding helpers), not ad-hoc specs")
