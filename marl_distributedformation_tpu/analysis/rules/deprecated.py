"""deprecated-api: removed / superseded JAX spellings, allow/deny table.

The repo is written for the one installation there is (``pyproject.toml``
pins it): ``jax.shard_map`` is the spelling, and the table below denies
what that installation deprecates or no longer has —
``jax.experimental.shard_map`` (raises a ``DeprecationWarning``), the
xmap-era APIs, the pjit axis-resources spellings, ``jax.tree_map``.
"""

from __future__ import annotations

import ast
from typing import Iterator, Tuple

from marl_distributedformation_tpu.analysis.linter import (
    ModuleContext,
    Rule,
    dotted_name,
)

# Dotted-name prefixes -> guidance. Matched against attribute chains and
# import statements; the longest (most specific) match wins.
DENYLIST = {
    "jax.experimental.shard_map": "deprecated; use jax.shard_map",
    "jax.experimental.maps": "xmap-era API, removed from jax",
    "jax.experimental.pjit": (
        "use jax.jit with in_shardings/out_shardings"
    ),
    "jax.experimental.global_device_array": "removed; use jax.Array",
    "jax.tree_map": "removed in jax 0.6; use jax.tree_util.tree_map",
    "jax.tree_multimap": "removed; use jax.tree_util.tree_map",
}

_DEPRECATED_KWARGS = frozenset({"in_axis_resources", "out_axis_resources"})


def _match(name: str) -> Tuple[str, str]:
    best = ""
    for key in DENYLIST:
        if (name == key or name.startswith(key + ".")) and len(key) > len(best):
            best = key
    return (best, DENYLIST[best]) if best else ("", "")


class DeprecatedApi(Rule):
    name = "deprecated-api"
    default_severity = "error"
    description = (
        "removed / superseded JAX API spelling — see the "
        "allow/deny table in analysis/rules/deprecated.py"
    )

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        reported = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                name = dotted_name(node)
                if name is None:
                    continue
                key, why = _match(name)
                # Attribute chains nest (jax.experimental.shard_map is a
                # child of jax.experimental.shard_map.shard_map); the
                # whole chain shares one source position, so position
                # dedup reports it once.
                pos = (node.lineno, node.col_offset)
                if key and pos not in reported:
                    reported.add(pos)
                    yield (*pos, f"{key}: {why}")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    key, why = _match(alias.name)
                    if key:
                        yield (node.lineno, node.col_offset, f"{key}: {why}")
            elif isinstance(node, ast.ImportFrom):
                module = node.module or ""
                for alias in node.names:
                    full = f"{module}.{alias.name}" if module else alias.name
                    key, why = _match(full)
                    if key:
                        yield (
                            node.lineno, node.col_offset, f"{key}: {why}",
                        )
                        break  # one report per import statement
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in _DEPRECATED_KWARGS:
                        yield (
                            node.lineno,
                            node.col_offset,
                            f"{kw.arg}= is the removed pjit axis-resources "
                            "spelling; use in_shardings/out_shardings",
                        )
