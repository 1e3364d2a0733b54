"""callback-in-hot-loop: host callbacks inside a compiled loop body.

``io_callback`` / ``pure_callback`` / ``jax.debug.print`` /
``jax.debug.callback`` inside the body of ``lax.scan`` / ``while_loop``
/ ``fori_loop`` / ``lax.map`` executes a device->host round trip EVERY
iteration of the compiled loop — under a fused training scan that is one
host round trip per rollout, which is precisely the overhead whole-loop
fusion exists to remove (train/trainer.py drains telemetry as stacked
scan outputs in ONE batched ``device_get`` per chunk instead). Outside a
loop body the same callbacks cost one transfer per dispatch and are
legitimate debugging tools, so this rule fires only where a compiled
loop multiplies them. Reachability runs on the shared call graph
(``analysis/callgraph.py``): a loop body calling into a chain of
same-module helpers or methods that performs the callback is the same
hazard wearing function names, followed to the engine's depth bound.
Chains that ENTER through an import are rule 14's report — the two
rules split on the first hop so a finding has exactly one owner.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from marl_distributedformation_tpu.analysis import callgraph
from marl_distributedformation_tpu.analysis.linter import (
    ModuleContext,
    Rule,
    dotted_name,
)

# First-hop kinds this rule owns; import-entered chains are rule 14's.
_LOCAL_HOPS = frozenset({"local", "method"})


def _callback_pred(node: ast.Call, fname) -> Optional[str]:
    return fname if fname in _CALLBACK_CALLS else None

# Compiled-loop entry points -> positions of the body callables among the
# positional args (the loop subset of linter.TRACING_ENTRY_ARGS: vmap/jit
# run their target once per dispatch, a loop body runs per iteration).
LOOP_ENTRY_ARGS: Dict[str, Tuple[int, ...]] = {
    "jax.lax.scan": (0,),
    "lax.scan": (0,),
    "jax.lax.map": (0,),
    "lax.map": (0,),
    "jax.lax.while_loop": (0, 1),
    "lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "lax.fori_loop": (2,),
}

_CALLBACK_CALLS = frozenset(
    {
        "jax.experimental.io_callback",
        "io_callback",
        "jax.pure_callback",
        "pure_callback",
        "jax.debug.print",
        "debug.print",
        "jax.debug.callback",
        "debug.callback",
        "jax.experimental.host_callback.call",
        "host_callback.call",
        "hcb.call",
    }
)


class CallbackInHotLoop(Rule):
    name = "callback-in-hot-loop"
    default_severity = "error"
    description = (
        "io_callback/pure_callback/jax.debug.print inside a compiled "
        "loop body — a host round trip every scanned iteration"
    )

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        reported: Set[Tuple[int, int]] = set()
        for body in self._loop_bodies(ctx):
            for hit in self._scan_body(ctx, body):
                if hit[:2] not in reported:
                    reported.add(hit[:2])
                    yield hit

    @staticmethod
    def _loop_bodies(ctx: ModuleContext) -> List[ast.AST]:
        bodies: List[ast.AST] = []
        seen: Set[int] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            positions = LOOP_ENTRY_ARGS.get(dotted_name(node.func) or "")
            if positions is None:
                continue
            for pos in positions:
                if pos < len(node.args):
                    for body in ctx._resolve_callable(node.args[pos]):
                        if id(body) not in seen:
                            seen.add(id(body))
                            bodies.append(body)
        return bodies

    def _scan_body(
        self, ctx: ModuleContext, body: ast.AST
    ) -> Iterator[Tuple[int, int, str]]:
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func)
            if fname in _CALLBACK_CALLS:
                yield (
                    node.lineno,
                    node.col_offset,
                    f"{fname}(...) inside a compiled loop body runs a "
                    "host callback every scanned iteration — stack the "
                    "values into the scan output and drain them once per "
                    "chunk instead",
                )
            else:
                hit = callgraph.reachable_call(
                    ctx, node, _callback_pred, first_hops=_LOCAL_HOPS
                )
                if hit is not None:
                    called = dotted_name(node.func) or "<callable>"
                    yield (
                        node.lineno,
                        node.col_offset,
                        f"{called}() is called from a compiled "
                        f"loop body and reaches {hit.matched}(...) — a "
                        "host callback every scanned iteration; hoist it "
                        "out of the loop or stack values into the scan "
                        "output",
                    )
