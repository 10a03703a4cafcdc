"""CL002 soft-dep-import-graph: the scalar/soa path never imports jax.

``Simulation(backend="scalar"|"soa")`` must import and run on a machine
with no jax installed (minimal containers, air-gapped CI); the runtime
guarantee is spot-checked by a blocked-jax subprocess test, but that
test only exercises the entry points it names. This rule is the static
closure: build the module-level import graph of every first-party
module, walk it from the configured entry modules, and fail if any
reachable module executes ``import jax`` (or ``from jax ...``) at
import time. Python's import machinery initializes every parent
package of an imported module, so ``a.b.c`` also edges to ``a`` and
``a.b`` — the exact mechanism by which an innocent-looking
``from pkg.sub import helper`` can drag a jax-importing sibling in
through ``pkg/sub/__init__.py``.

Fix a finding by deferring the import into the function that needs it
(as ``Simulation.__init__`` in ``repro/storage/sim.py`` imports
``repro.storage.device``, the storage layer's one jax module, only for
``backend="soa-jax"``) or, for a module that is *supposed* to need jax,
adding it to ``cl002_allowed`` in the lint config — an explicit,
reviewed exemption.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.caratlint.rules.base import Finding, Rule, module_level_imports


def _parents(module: str) -> List[str]:
    parts = module.split(".")
    return [".".join(parts[:i]) for i in range(1, len(parts))]


class SoftDepImportGraphRule(Rule):
    code = "CL002"
    name = "soft-dep-import-graph"
    contract = ("no module-level `import jax` reachable from the "
                "scalar/soa entry modules (jax is a soft dependency)")

    def check(self, project) -> List[Finding]:
        modules = project.modules
        if not modules:
            return []

        edges: Dict[str, Set[str]] = {}
        jax_import: Dict[str, Tuple[int, int, str]] = {}
        for mod, sf in modules.items():
            out: Set[str] = set()
            for node, imported in module_level_imports(sf.tree):
                for name in imported:
                    resolved = self._resolve(name, mod, sf.relpath)
                    if resolved is None:
                        continue
                    if resolved == "jax" or resolved.startswith("jax."):
                        jax_import.setdefault(
                            mod, (node.lineno,
                                  getattr(node, "end_lineno", None)
                                  or node.lineno, resolved))
                        continue
                    # the import binds `resolved` AND initializes every
                    # parent package on the way down
                    for cand in _parents(resolved) + [resolved]:
                        if cand in modules and cand != mod:
                            out.add(cand)
            edges[mod] = out

        allowed = set(project.config.cl002_allowed)
        findings: List[Finding] = []
        flagged: Set[str] = set()
        for entry in project.config.cl002_entries:
            roots = [m for m in _parents(entry) + [entry] if m in modules]
            if not roots:
                continue
            chain = self._bfs(roots, edges)
            for mod, parent in chain.items():
                if mod in jax_import and mod not in allowed \
                        and mod not in flagged:
                    flagged.add(mod)
                    line, end, what = jax_import[mod]
                    path = self._render_chain(chain, mod, entry)
                    sf = modules[mod]
                    findings.append(Finding(
                        code=self.code, path=sf.relpath, line=line,
                        end_line=end,
                        message=(f"module-level `import {what}` is "
                                 f"reachable from soft-dep entry "
                                 f"'{entry}' via {path}; defer the "
                                 f"import into the function that needs "
                                 f"it or add '{mod}' to cl002_allowed")))
        return findings

    @staticmethod
    def _resolve(name: str, mod: str, relpath: str) -> Optional[str]:
        """Absolute dotted module for one recorded import name;
        relative imports resolve against the importing module's
        package (``.x`` in ``a/b.py`` -> ``a.x``)."""
        if not name.startswith("."):
            return name
        level = len(name) - len(name.lstrip("."))
        rest = name.lstrip(".")
        pkg_parts = mod.split(".")
        # inside a package __init__, level 1 is the package itself
        is_pkg = relpath.endswith("__init__.py")
        drop = level - 1 if is_pkg else level
        if drop >= len(pkg_parts):
            return None
        base = pkg_parts[:len(pkg_parts) - drop]
        return ".".join(base + ([rest] if rest else []))

    @staticmethod
    def _bfs(roots: List[str],
             edges: Dict[str, Set[str]]) -> Dict[str, Optional[str]]:
        """Reachable set with parent pointers (roots map to None)."""
        chain: Dict[str, Optional[str]] = {r: None for r in roots}
        queue = list(roots)
        while queue:
            cur = queue.pop(0)
            for nxt in sorted(edges.get(cur, ())):
                if nxt not in chain:
                    chain[nxt] = cur
                    queue.append(nxt)
        return chain

    @staticmethod
    def _render_chain(chain: Dict[str, Optional[str]], mod: str,
                      entry: str) -> str:
        hops = [mod]
        while chain.get(hops[-1]) is not None:
            hops.append(chain[hops[-1]])  # type: ignore[arg-type]
        return " <- ".join(hops)
