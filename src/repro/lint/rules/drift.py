"""RL003 — version-drift jax APIs only through ``repro.compat``."""
from __future__ import annotations

import ast
from typing import Dict, List

from repro.lint.astutil import ImportMap, resolve
from repro.lint.engine import Diagnostic, Project

CODE = "RL003"
NAME = "compat-firewall"
EXPLAIN = """\
RL003 (compat-firewall): APIs that moved between jax releases are shimmed
exactly once, in repro/compat.py, and every other module must go through
the shim:

    jax.experimental.shard_map.shard_map / jax.shard_map
        -> compat.shard_map          (kwarg renamed check_rep -> check_vma)
    jax.tree_util.tree_flatten_with_path / jax.tree.flatten_with_path
        -> compat.tree_flatten_with_path
    jax.tree_util.tree_map_with_path / jax.tree.map_with_path
        -> compat.tree_map_with_path
    compiled.cost_analysis()
        -> compat.cost_analysis_dict (list-of-dicts vs dict return drift)
    jax.make_mesh
        -> compat.make_mesh          (default axis type became Explicit)

A direct spelling works today and breaks on the next jax pin bump, after
the code has forked into two spellings.  Routing through compat keeps one
seam to patch.

Fix: `from repro import compat` and call the shim.  compat.py itself is
the only file allowed to touch the raw APIs.
"""

# resolved dotted name -> the compat shim to use instead
_FORBIDDEN: Dict[str, str] = {
    "jax.experimental.shard_map.shard_map": "compat.shard_map",
    "jax.shard_map": "compat.shard_map",
    "jax.tree_util.tree_flatten_with_path": "compat.tree_flatten_with_path",
    "jax.tree.flatten_with_path": "compat.tree_flatten_with_path",
    "jax.tree_util.tree_map_with_path": "compat.tree_map_with_path",
    "jax.tree.map_with_path": "compat.tree_map_with_path",
    "jax.make_mesh": "compat.make_mesh",
}


def _in_scope(display: str) -> bool:
    # Everything scanned except the shim itself (and this rule's own home).
    return not display.endswith("repro/compat.py")


def check(project: Project) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for f in project.files:
        if f.tree is None or not _in_scope(f.display):
            continue
        imports = ImportMap(f.tree)
        for node in ast.walk(f.tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    full = f"{node.module}.{a.name}"
                    if full in _FORBIDDEN:
                        diags.append(Diagnostic(
                            CODE, f.display, node.lineno,
                            f"import of {full} — use "
                            f"{_FORBIDDEN[full]} (from repro import "
                            f"compat)"))
            elif isinstance(node, ast.Attribute):
                name = resolve(node, imports)
                if name in _FORBIDDEN:
                    diags.append(Diagnostic(
                        CODE, f.display, node.lineno,
                        f"direct {name} — use {_FORBIDDEN[name]}"))
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "cost_analysis":
                diags.append(Diagnostic(
                    CODE, f.display, node.lineno,
                    "direct .cost_analysis() call — use "
                    "compat.cost_analysis_dict(compiled) (return type "
                    "drifted from list-of-dicts to dict across jax "
                    "releases)"))
    return diags
