"""device-put-in-dispatch-loop: params re-placed per request.

``jax.device_put`` has exactly two sanctioned homes in a serving stack:
engine/registry construction and the reload coordinator's commit — the
once-per-SWAP placement events. A ``device_put`` inside a dispatch loop
(the ``while``-loop shape every serve/poll worker in this repo has) is
the per-request spelling of the same call: a full host->device weight
upload on EVERY iteration, which puts a host->device copy in front of
every request and silently caps throughput at the PCIe/link rate — the
serving twin of the per-iteration host-sync hazards rules 4 and 12
police on the training side. The fix is always the same: hoist the
placement to the swap/commit seam (``ModelRegistry.refresh``,
``FleetReloadCoordinator._load_and_commit``,
``ShardedPolicyEngine.shard_params``) and let dispatches reuse
device-resident buffers.

Scope, deliberately: ``jax.device_put``/``device_put`` calls inside a
host-side ``while``-loop body — directly, or through a chain of
plain-name helpers (same-module or imported) followed on the shared
call graph to its depth bound. METHOD calls are deliberately not
followed: the sanctioned placement homes in this repo are methods
(``ModelRegistry.refresh``, ``FleetReloadCoordinator._load_and_commit``)
invoked from poll loops at swap frequency, and following
``self.refresh()`` would flag exactly the once-per-swap seam the rule
exists to protect; the runtime ``no_host_transfers`` guard covers
per-request method paths. ``device_get`` is NOT this rule's
business: the trainer's host loop legitimately drains telemetry with
one amortized batched ``device_get`` per log interval, and policing
gets statically would flag exactly that idiom. Loops inside traced
scopes are skipped — a traced ``while`` is rule 2's report.
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

_TRANSFER_CALLS = frozenset({"jax.device_put", "device_put"})
_NAME_HOPS = frozenset({"local", "import"})


def _transfer_pred(node: ast.Call, fname) -> Optional[str]:
    return fname if fname in _TRANSFER_CALLS else None


class DevicePutInDispatchLoop(Rule):
    name = "device-put-in-dispatch-loop"
    default_severity = "error"
    description = (
        "jax.device_put inside a while-loop dispatch body — a "
        "host->device upload per request; place params once at "
        "swap/commit instead"
    )

    def check(self, ctx: ModuleContext) -> Iterator[Tuple[int, int, str]]:
        reported: Set[Tuple[int, int]] = set()
        for loop in self._host_while_loops(ctx):
            for hit in self._scan_body(ctx, loop):
                if hit[:2] not in reported:
                    reported.add(hit[:2])
                    yield hit

    @staticmethod
    def _host_while_loops(ctx: ModuleContext) -> List[ast.While]:
        """Every ``while`` loop outside traced scopes. Nested loops each
        appear; the ``reported`` de-dup keeps one report per call site."""
        return [
            node
            for node in ast.walk(ctx.tree)
            if isinstance(node, ast.While)
            and not ctx._has_traced_ancestor(node)
        ]

    def _scan_body(
        self, ctx: ModuleContext, loop: ast.While
    ) -> Iterator[Tuple[int, int, str]]:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func)
            if fname in _TRANSFER_CALLS:
                yield (
                    node.lineno,
                    node.col_offset,
                    f"{fname}(...) inside a dispatch loop re-uploads its "
                    "tree host->device every iteration — place params "
                    "once at the swap/commit seam and reuse the "
                    "device-resident buffers per dispatch",
                )
            elif isinstance(node.func, ast.Name):
                hit = callgraph.reachable_call(
                    ctx, node, _transfer_pred, first_hops=_NAME_HOPS
                )
                if hit is not None:
                    yield (
                        node.lineno,
                        node.col_offset,
                        f"{node.func.id}() is called from a dispatch "
                        f"loop and reaches {hit.matched}(...) — a "
                        "host->device upload every iteration; hoist the "
                        "placement out of the loop to the swap/commit "
                        "seam",
                    )
