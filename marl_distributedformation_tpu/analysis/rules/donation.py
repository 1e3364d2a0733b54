"""missing-donate: train-step-shaped jits that never donate their buffers.

A train step consumes its previous state and returns the next one; jit
without ``donate_argnums`` keeps both alive across the dispatch, doubling
live HBM for the largest buffers in the program (params + optimizer
moments + env state). The rule is deliberately NARROW: it fires only on
jit targets whose name says train-step (``train_step`` / ``update_step``
/ ``*iteration*``), and an assignment target containing ``no_donate``
documents the exception (timing twins, reusable-input evaluators) and is
skipped. Plain env steps and eval functions never match — their inputs
are legitimately reused.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional, Tuple

from marl_distributedformation_tpu.analysis.linter import (
    JIT_NAMES,
    ModuleContext,
    Rule,
    dotted_name,
)

_TRAIN_SHAPED = re.compile(r"(train_step|update_step|iteration)")
_DONATE_KWARGS = frozenset({"donate_argnums", "donate_argnames"})


def _callable_name(node: ast.AST) -> Optional[str]:
    """Last-segment name of the jitted target, peeling wrapping calls
    (``jax.jit(make_fused_chunk(iteration, k))`` -> ``make_fused_chunk``
    peels to its first arg ``iteration``)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Call) and node.args:
        inner = _callable_name(node.args[0])
        if inner is not None:
            return inner
        return _callable_name(node.func)
    return None


class MissingDonate(Rule):
    name = "missing-donate"
    default_severity = "error"
    description = (
        "jit of a train-step-shaped function without donate_argnums — "
        "doubles live HBM for the biggest buffers in the program"
    )

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in JIT_NAMES or not node.args:
                continue
            target = _callable_name(node.args[0])
            if target is None or not _TRAIN_SHAPED.search(target):
                continue
            if any(kw.arg in _DONATE_KWARGS for kw in node.keywords):
                continue
            if self._assignment_opts_out(ctx, node):
                continue
            yield (
                node.lineno,
                node.col_offset,
                f"jax.jit({target}, ...) looks like a train step but "
                "passes no donate_argnums — the previous state stays "
                "live across the dispatch (name the binding *_no_donate "
                "if the non-donating twin is intentional)",
            )

    @staticmethod
    def _assignment_opts_out(ctx: ModuleContext, node: ast.Call) -> bool:
        """``x_no_donate = jax.jit(...)`` documents a deliberate
        non-donating twin (e.g. profiling reruns on the same buffers)."""
        cur = ctx.parents.get(node)
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = ctx.parents.get(cur)
        if isinstance(cur, ast.Assign):
            for t in cur.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) and "no_donate" in n.id:
                        return True
                    if isinstance(n, ast.Attribute) and "no_donate" in n.attr:
                        return True
        return False
