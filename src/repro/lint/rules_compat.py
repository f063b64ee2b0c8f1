"""Compat-boundary rule: version-gated JAX symbols stay in src/repro/compat/.

The ROADMAP rule this enforces: every JAX symbol whose name, location or
semantics has moved between JAX releases is wrapped once in ``repro.compat``,
so moving to a new JAX touches that one module. A direct use anywhere else
breaks silently at the next upgrade (JAX 0.9, for one, made meshes Explicit
by default). The checker is import-resolution-aware: it builds the module's
alias table from its ``import``/``from`` statements and resolves dotted
chains back to their roots, so ``from jax.experimental.shard_map import
shard_map`` and ``import jax.experimental.shard_map as smap`` are both caught
while ``compat.shard_map`` (the sanctioned wrapper) is not.

Gated symbols (each has a wrapper in compat/__init__.py):

  shard_map            jax.experimental.shard_map -> jax.shard_map (0.6)
  AxisType             new in 0.5.x (explicit-sharding mesh axis types)
  set_mesh/use_mesh    0.5+ context-mesh API (0.4 uses mesh context managers)
  get_abstract_mesh    0.5+
  make_mesh(axis_types=...)   the kwarg is 0.5+; bare make_mesh is fine
  cost_analysis        Compiled.cost_analysis() return shape moved
  lax.axis_size        moved/renamed across the range
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional

from .engine import Module, analyzer
from .findings import Finding

GATED_TERMINALS = {"shard_map", "AxisType", "set_mesh", "use_mesh",
                   "get_abstract_mesh"}
GATED_EXACT = {"jax.lax.axis_size"}


def collect_import_aliases(tree: ast.AST) -> Dict[str, str]:
    """Local name -> fully dotted path it denotes, from import statements.

    ``import jax.lax`` binds ``jax``; ``from jax import lax as L`` binds
    ``L`` -> ``jax.lax``; relative imports are ignored.
    """
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    aliases[a.name.split(".")[0]] = a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _resolve(aliases: Dict[str, str], node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = aliases.get(node.id)
    if root is None:
        return None
    return ".".join([root] + list(reversed(parts)))


def _in_compat(path: str) -> bool:
    norm = path.replace("\\", "/")
    return "repro/compat/" in norm or norm.startswith("compat/")


def _is_gated(dotted: str) -> Optional[str]:
    if not (dotted == "jax" or dotted.startswith("jax.")):
        return None
    if dotted in GATED_EXACT:
        return dotted
    last = dotted.split(".")[-1]
    if last in GATED_TERMINALS:
        return dotted
    return None


class _CompatVisitor(ast.NodeVisitor):
    def __init__(self, mod: Module, aliases: Dict[str, str],
                 out: List[Finding]):
        self.mod = mod
        self.aliases = aliases
        self.out = out

    def _finding(self, node: ast.AST, what: str) -> None:
        self.out.append(Finding(
            "compat-boundary", self.mod.path, node.lineno, node.col_offset,
            f"{what} is version-gated across JAX releases — "
            "go through repro.compat (ROADMAP: no file outside "
            "src/repro/compat/ touches a gated symbol)"))

    def visit_Call(self, node: ast.Call) -> None:
        d = _resolve(self.aliases, node.func)
        if (d and (d == "jax" or d.startswith("jax."))
                and d.split(".")[-1] == "make_mesh"
                and any(kw.arg == "axis_types" for kw in node.keywords)):
            self._finding(node, f"{d}(axis_types=...)")
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "cost_analysis"):
            recv = _resolve(self.aliases, node.func.value)
            if recv is None or not recv.startswith("repro.compat"):
                self._finding(node, ".cost_analysis()")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        d = _resolve(self.aliases, node)
        gated = _is_gated(d) if d else None
        if gated and gated.split(".")[-1] != "cost_analysis":
            self._finding(node, gated)
            return  # don't re-flag inner segments of the same chain
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if not isinstance(node.ctx, ast.Load):
            return
        d = self.aliases.get(node.id)
        if d and _is_gated(d):
            # a bare name bound BY IMPORT to a gated jax symbol
            self._finding(node, d)


@analyzer
def check_compat_boundary(mod: Module) -> List[Finding]:
    if _in_compat(mod.path):
        return []
    aliases = collect_import_aliases(mod.tree)
    out: List[Finding] = []
    # flag gated from-imports at the import site too (the import alone is
    # already a floor break when the symbol moved modules)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            for a in node.names:
                full = f"{node.module}.{a.name}"
                if _is_gated(full):
                    out.append(Finding(
                        "compat-boundary", mod.path, node.lineno,
                        node.col_offset,
                        f"import of version-gated {full} — go through "
                        "repro.compat"))
    _CompatVisitor(mod, aliases, out).visit(mod.tree)
    # dedupe per (line, message)
    seen, uniq = set(), []
    for f in out:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq
