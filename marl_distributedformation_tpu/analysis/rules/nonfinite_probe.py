"""host-nonfinite-probe-in-dispatch-loop: per-iteration divergence
polling that forces a device sync.

The tempting way to watch a training loop for NaNs is to probe every
dispatch from the host::

    while steps < total:
        metrics = jitted_step(...)
        if jnp.isnan(metrics["loss"]).any():   # <- full device sync
            break

Every such probe blocks the host on the device value — one host sync
per iteration, and under fused dispatch it
defeats the entire point of the scan (the host re-synchronizes per
chunk member). It is also K iterations TOO LATE: with ``fused_chunk=K``
the damage is committed before the host can see it. The repo's answer
is the in-program health word (train/recovery.py): finiteness is
computed ON DEVICE inside the compiled step, rides the stacked chunk
metrics through the ONE batched drain the loop already pays for, and
the ``jnp.where`` skip-update guard contains the poisoned iteration
without any host round trip. This rule statically rejects the
anti-pattern the health word exists to replace.

Detection, inside a host-side ``while``/``for`` loop body (loops in
traced scopes are rule 2's report; the serving/training dispatch loops
this rule polices are host loops):

- ``jnp.isnan`` / ``jnp.isinf`` / ``jnp.isfinite`` calls (any
  ``jnp``/``jax.numpy`` spelling, or the names from-imported from
  ``jax.numpy``) — applying them to a host value is itself the smell
  (that is numpy's job), and applying them to a device value is the
  sync;
- ``math.isnan(float(x))`` / ``np.isfinite(float(x))`` style probes —
  the ``float()`` call IS the forced transfer, the finiteness wrapper
  marks it as a divergence poll;
- a plain-name call into a helper chain that probes, followed on the
  shared call graph (``analysis/callgraph.py``) to its depth bound.
  Callees that are themselves traced scopes are pruned: a traced
  helper's ``jnp.isnan`` is the in-program health word — the sanctioned
  replacement, not the hazard.

What stays CLEAN, deliberately: ``np.isfinite`` over already-drained
numpy arrays (the drain seam's legitimate batched check), ``float(v)``
on drained host metrics (the trainer's log path), and any probe
OUTSIDE a loop (a one-shot end-of-run finiteness check is exactly how
the trainer guarantees finite final params).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from marl_distributedformation_tpu.analysis import callgraph
from marl_distributedformation_tpu.analysis.linter import (
    ModuleContext,
    Rule,
    dotted_name,
)

# Finiteness predicates. The jnp spellings are probes wherever they
# appear in a host loop; the host-math spellings only when their
# argument is a float(...) extraction (numpy over host data is fine).
_PROBE_ATTRS = frozenset({"isnan", "isinf", "isfinite"})
_JNP_ROOTS = frozenset({"jnp", "jax.numpy"})
_HOST_ROOTS = frozenset({"math", "np", "numpy"})


def _jnp_probe_name(fname: Optional[str]) -> bool:
    if not fname or "." not in fname:
        return False
    root, attr = fname.rsplit(".", 1)
    return attr in _PROBE_ATTRS and root in _JNP_ROOTS


def _host_probe_name(fname: Optional[str]) -> bool:
    if not fname or "." not in fname:
        return False
    root, attr = fname.rsplit(".", 1)
    return attr in _PROBE_ATTRS and root in _HOST_ROOTS


def _probe_pred(node: ast.Call, fname) -> "str | None":
    """Call-graph predicate: is this call site a host finiteness probe?
    (jnp spellings anywhere; math/np spellings only over a float()/
    .item() pull — see the module docstring.)"""
    if _jnp_probe_name(fname):
        return f"{fname}(...)"
    if _host_probe_name(fname) and _has_float_extraction(node):
        return f"{fname}(float(...))"
    return None


def _has_float_extraction(node: ast.Call) -> bool:
    """Does any argument contain a ``float(...)``/``.item()`` pull —
    the forced device->host transfer that turns a host-math finiteness
    check into a per-iteration sync?"""
    for arg in ast.walk(node):
        if isinstance(arg, ast.Call):
            if isinstance(arg.func, ast.Name) and arg.func.id == "float":
                return True
            if (
                isinstance(arg.func, ast.Attribute)
                and arg.func.attr == "item"
            ):
                return True
    return False


class HostNonfiniteProbeInDispatchLoop(Rule):
    name = "host-nonfinite-probe-in-dispatch-loop"
    default_severity = "error"
    description = (
        "host-side jnp.isnan/isinf/isfinite (or math/np probes over a "
        "float() pull) inside a while/for dispatch loop — one device "
        "sync per iteration, and K iterations too late under fused "
        "dispatch; compute the health word in-program instead "
        "(train/recovery.py)"
    )

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        jnp_imports = self._jnp_probe_imports(ctx.tree)
        reported: Set[Tuple[int, int]] = set()
        for loop in self._host_loops(ctx):
            for hit in self._scan_body(ctx, loop, jnp_imports):
                if hit[:2] not in reported:
                    reported.add(hit[:2])
                    yield hit

    @staticmethod
    def _host_loops(ctx: ModuleContext) -> List[ast.AST]:
        """Every while/for loop outside traced scopes (a traced loop is
        rule 2's business). Nested loops each appear; the reported set
        keeps one report per call site."""
        return [
            node
            for node in ast.walk(ctx.tree)
            if isinstance(node, (ast.While, ast.For))
            and not ctx._has_traced_ancestor(node)
        ]

    @staticmethod
    def _jnp_probe_imports(tree: ast.Module) -> Set[str]:
        """Local names bound from ``jax.numpy`` that ARE finiteness
        predicates (``from jax.numpy import isnan``)."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and (
                (node.module or "") in ("jax.numpy", "jnp")
            ):
                for alias in node.names:
                    if alias.name in _PROBE_ATTRS:
                        names.add(alias.asname or alias.name)
        return names

    def _scan_body(
        self, ctx: ModuleContext, loop: ast.AST, jnp_imports: Set[str]
    ) -> Iterator[Tuple[int, int, str]]:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            if ctx.enclosing_traced_scope(node) is not None:
                continue  # a jitted helper defined inside the loop
            hit = self._probe_call(ctx, node, jnp_imports)
            if hit:
                yield (
                    node.lineno,
                    node.col_offset,
                    f"{hit} inside a dispatch loop forces one device "
                    "sync per iteration (and sees fused divergence K "
                    "iterations late) — compute the health word "
                    "in-program and consume it at the chunk drain "
                    "(train/recovery.py, docs/recovery.md)",
                )

    def _probe_call(
        self, ctx: ModuleContext, node: ast.Call, jnp_imports: Set[str]
    ) -> Optional[str]:
        fname = dotted_name(node.func)
        if _jnp_probe_name(fname):
            return f"{fname}(...)"
        if fname in jnp_imports:
            return f"{fname}(...) (from jax.numpy)"
        if _host_probe_name(fname) and _has_float_extraction(node):
            return f"{fname}(float(...))"
        # Transitive plain-name chains on the shared call graph; traced
        # callees are pruned — their probes are the in-program health
        # word, i.e. the fix, not the hazard.
        if isinstance(node.func, ast.Name):
            hit = callgraph.reachable_call(
                ctx,
                node,
                _probe_pred,
                first_hops=frozenset({"local", "import"}),
                prune=lambda f: callgraph.traced_in_own_module(f, ctx),
            )
            if hit is not None:
                return f"{node.func.id}() reaches {hit.matched}"
        return None
