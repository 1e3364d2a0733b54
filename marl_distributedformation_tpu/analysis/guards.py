"""Runtime tracing guards: the dynamic half of graftlint.

The AST linter (linter.py) sees one file at a time; these guards watch
the properties that only exist at run time:

- :class:`RetraceGuard` — counts how many times a jit target is actually
  traced and (optionally) fails the process past a budget. Accidental
  retracing is the #1 silent throughput killer in JAX: a weak-typed
  scalar or a drifting static arg recompiles a multi-second XLA program
  every iteration and nothing crashes.
- :func:`no_host_transfers` — a ``jax.transfer_guard_device_to_host``
  context for the trainer hot loop: any ``.item()`` / ``float()`` /
  implicit ``__array__`` sync inside the guarded region raises instead
  of silently serializing the dispatch pipeline.
- :func:`nan_guard` — scoped ``jax_debug_nans`` toggle: XLA re-runs any
  op that produced a NaN in op-by-op mode and raises at the source op.
- :func:`ledgered_jit` / :class:`LedgerDispatch` — the RetraceGuard seam
  extended into the ProgramLedger (``obs/ledger.py``): swap
  ``jax.jit(guard.wrap(f), **kw)`` for ``ledgered_jit(f, guard, **kw)``
  and every compilation of the target registers its executable's cost/
  memory facts and build timings automatically, plus a per-dispatch
  latency sample at the same host seam. This file owns ALL the
  jax-touching extraction (executable claiming, lowered cost analysis,
  ``jax.monitoring`` compile-event attribution); the ledger itself
  stays jax-free.

All are re-exported through ``utils.profiling`` and opt-in from
``train.trainer.TrainConfig`` (``guard_retraces`` / ``guard_transfers``
/ ``guard_nans``).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax

from marl_distributedformation_tpu.obs.ledger import get_ledger, sanitize_key


class RetraceError(RuntimeError):
    """A guarded jit target compiled more often than its budget allows."""


class RetraceGuard:
    """Count (and optionally bound) the traces of a jit target.

    Wrap the Python callable BEFORE handing it to ``jax.jit``: the
    wrapper body runs exactly once per trace (jit executes the Python
    function only on cache miss), so ``count`` equals the number of
    compilations this process triggered for it.

    >>> guard = RetraceGuard("train_iteration", max_traces=2)
    >>> step = jax.jit(guard.wrap(step_fn), donate_argnums=(0,))

    ``max_traces=None`` only counts. With a budget, the trace that
    exceeds it raises :class:`RetraceError` naming the argument
    signature that caused it — at the retrace, where the stack still
    shows which caller changed shapes/dtypes.
    """

    def __init__(
        self, name: str = "jit-target", max_traces: Optional[int] = None
    ) -> None:
        self.name = name
        self.max_traces = max_traces
        self._lock = threading.Lock()
        self.count = 0

    def reset(self) -> None:
        with self._lock:
            self.count = 0

    def _describe(self, args: Any, kwargs: Any) -> str:
        def leaf(x: Any) -> str:
            shape = getattr(x, "shape", None)
            dtype = getattr(x, "dtype", None)
            if shape is None or dtype is None:
                return f"{type(x).__name__}:{x!r}"[:40]
            return f"{dtype}{list(shape)}"

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        head = ", ".join(leaf(x) for x in leaves[:8])
        extra = len(leaves) - 8
        return head + (f", … +{extra} leaves" if extra > 0 else "")

    def wrap(self, fn: Callable[..., Any]) -> Callable[..., Any]:
        @functools.wraps(fn)
        def traced(*args: Any, **kwargs: Any) -> Any:
            if getattr(_INTROSPECT, "active", False):
                # A ledger-initiated re-lowering (cache-hit in the
                # common case; see _register_program) must never
                # consume trace budget — observability cannot become a
                # RetraceError.
                return fn(*args, **kwargs)
            with self._lock:
                self.count += 1
                count = self.count
            if self.max_traces is not None and count > self.max_traces:
                raise RetraceError(
                    f"{self.name!r} traced {count} times "
                    f"(budget {self.max_traces}) — a shape, dtype, "
                    "weak-type, or static-arg drift is forcing "
                    "recompilation every call; offending signature: "
                    f"[{self._describe(args, kwargs)}]"
                )
            try:
                return fn(*args, **kwargs)
            except Exception:
                # A trace that raises produced no compiled program (and
                # no jit cache entry), so it must not consume budget —
                # otherwise one malformed call poisons the target for
                # every valid caller after it (the serving engine leans
                # on this: budget-1 per bucket must mean one SUCCESSFUL
                # compile, not one attempt).
                with self._lock:
                    self.count -= 1
                raise

        return traced


@contextlib.contextmanager
def no_host_transfers(level: str = "disallow") -> Iterator[None]:
    """Forbid device->host transfers in the wrapped region.

    Device-to-host only: host-to-device constant uploads during
    compilation are part of tracing and stay allowed — the hot-loop
    poison is the reverse direction (``.item()``, ``float()``, implicit
    ``np.asarray``), which serializes the dispatch pipeline behind a
    sync. ``level`` follows ``jax.transfer_guard``: ``"disallow"``
    raises, ``"log"`` prints and continues (triage mode).

    Backend caveat: the XLA CPU backend aliases device and host memory,
    so readbacks there are zero-copy and the guard never fires — it is a
    no-op on CPU and enforceable on TPU/GPU. The static complement
    (graftlint's host-sync-in-jit rule) catches spelled-out syncs on
    every backend; this guard catches the implicit ones on hardware,
    which is where they cost real RTTs.
    """
    with jax.transfer_guard_device_to_host(level):
        yield


# ----------------------------------------------------------------------
# ProgramLedger glue: the RetraceGuard seam extended below the dispatch
# boundary (obs/ledger.py holds the jax-free record side).
# ----------------------------------------------------------------------

# Thread-local flag marking ledger-initiated introspection (a `.lower()`
# against the already-traced signature): RetraceGuard.wrap skips budget
# accounting under it, so analysis can never trip a budget-1 receipt.
_INTROSPECT = threading.local()

# Thread-local stack of per-dispatch timing sinks for jax.monitoring
# compile-event attribution: trace, MLIR lowering, and backend compile
# all happen on the dispatching thread between our call entry and exit,
# so the innermost active dispatch owns any event that fires.
_MONITOR = threading.local()
_MONITOR_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "compile_seconds",
}
_monitor_installed = False


def _on_compile_event(event: str, duration: float, **_: Any) -> None:
    stack = getattr(_MONITOR, "stack", None)
    if not stack:
        return
    field = _MONITOR_EVENTS.get(event)
    if field is not None:
        sink = stack[-1]
        sink[field] = sink.get(field, 0.0) + float(duration)


def _install_monitor() -> None:
    global _monitor_installed
    if _monitor_installed:
        return
    _monitor_installed = True  # one attempt only, even on failure
    try:
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event
        )
    except Exception:  # noqa: BLE001 — attribution is best-effort
        pass


@contextlib.contextmanager
def _ledger_introspection() -> Iterator[None]:
    prev = getattr(_INTROSPECT, "active", False)
    _INTROSPECT.active = True
    try:
        yield
    finally:
        _INTROSPECT.active = prev


def _abstract_signature(args: Any, kwargs: Any) -> Tuple[str, int]:
    """``(fingerprint, argument_bytes)`` of a call's abstract signature.
    Shape/dtype metadata only — safe on donated (deleted) arrays, whose
    avals outlive their buffers."""
    parts = []
    nbytes = 0
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            parts.append(f"py_{type(leaf).__name__}")
            continue
        parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        size = getattr(leaf, "nbytes", None)
        if size is not None:
            nbytes += int(size)
    head = ", ".join(parts[:24])
    if len(parts) > 24:
        head += f", … +{len(parts) - 24} leaves"
    return f"{len(parts)} leaves: {head}", nbytes


# Claimed backend executables (by wrapper identity — live_executables()
# returns stable Python objects) and their cached HLO module names, so
# N registrations never re-deserialize the same modules. The nanobind
# LoadedExecutable rejects weakrefs, so lifetime management is explicit:
# every claim scan prunes ids no longer among the live executables —
# which both bounds the dicts (by LIVE executables, not executables
# ever seen) and retires a dead executable's claim/name before CPython
# can hand its address to a new one (id-reuse misattribution).
_claim_lock = threading.Lock()
_claimed_executables: set = set()
_executable_names: Dict[int, str] = {}


def _claim_executable(module_name: str, expected_arg_bytes: int) -> Any:
    """The backend's newest unclaimed live executable whose HLO module
    name matches (preferring an exact argument-size match when several
    same-named programs exist). None when the backend exposes no
    executable handles — callers fall back to lowered-cost analysis."""
    try:
        exes = jax.devices()[0].client.live_executables()
    except Exception:  # noqa: BLE001 — backend without the handle API
        return None
    with _claim_lock:
        current = {id(exe) for exe in exes}
        for stale in [
            i for i in _executable_names if i not in current
        ]:
            _executable_names.pop(stale, None)
        _claimed_executables.intersection_update(current)
        matches = []
        for exe in reversed(exes):  # newest last in creation order
            ident = id(exe)
            if ident in _claimed_executables:
                continue
            name = _executable_names.get(ident)
            if name is None:
                try:
                    name = exe.hlo_modules()[0].name
                except Exception:  # noqa: BLE001
                    name = "?"
                _executable_names[ident] = name
            if name == module_name:
                matches.append(exe)
        if not matches:
            return None
        chosen = None
        if expected_arg_bytes:
            for exe in matches:
                try:
                    stats = exe.get_compiled_memory_stats()
                    if stats.argument_size_in_bytes == expected_arg_bytes:
                        chosen = exe
                        break
                except Exception:  # noqa: BLE001
                    break
        chosen = chosen if chosen is not None else matches[0]
        _claimed_executables.add(id(chosen))
        return chosen


def _executable_facts(exe: Any) -> Dict[str, float]:
    """Cost + memory facts off a backend LoadedExecutable (or a
    jax.stages.Compiled — same method surface for cost analysis)."""
    facts: Dict[str, float] = {}
    try:
        cost = exe.cost_analysis()
        first = (
            cost[0] if isinstance(cost, (list, tuple)) and cost else cost
        )
        if isinstance(first, dict):
            if first.get("flops") is not None:
                facts["flops"] = float(first["flops"])
            if first.get("bytes accessed") is not None:
                facts["bytes_accessed"] = float(first["bytes accessed"])
    except Exception:  # noqa: BLE001 — partial facts beat no facts
        pass
    stats = None
    for getter in ("get_compiled_memory_stats", "memory_analysis"):
        fn = getattr(exe, getter, None)
        if fn is None:
            continue
        try:
            stats = fn()
            break
        except Exception:  # noqa: BLE001
            continue
    if stats is not None:
        for field, attr in (
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes"),
        ):
            v = getattr(stats, attr, None)
            if v is not None:
                facts[field] = float(v)
    if not facts.get("generated_code_bytes"):
        try:
            v = getattr(exe, "size_of_generated_code_in_bytes", None)
            if callable(v):  # a method on backend LoadedExecutables
                v = v()
            if v:
                facts["generated_code_bytes"] = float(v)
        except Exception:  # noqa: BLE001
            pass
    return facts


class LedgerDispatch:
    """Callable wrapper around a guarded jitted program: the compile
    seam that feeds the ProgramLedger.

    Every call dispatches straight through; when the call compiled a
    new program (detected via the jit cache size, so a guard shared
    across several programs — the hetero sweep's per-chunk-length cache
    — attributes correctly), the new executable is registered with its
    cost/memory facts, abstract-signature fingerprint, donation map,
    and monitoring-attributed build timings. Each call also records one
    dispatch-latency sample under the wrapper's stable dispatch key
    (replicas sharing a program shape pool into one histogram).

    Disabled ledger: one attribute read, then the bare jitted call —
    and registration never raises into the dispatch path.
    """

    def __init__(
        self,
        jitted: Any,
        guard: RetraceGuard,
        *,
        subsystem: str,
        name: str,
        module_name: str,
        donate_argnums: Tuple[int, ...] = (),
    ) -> None:
        self._jitted = jitted
        self.guard = guard
        self.subsystem = subsystem
        self.name = name
        self.module_name = module_name
        self.donate_argnums = tuple(donate_argnums)
        self.dispatch_key = sanitize_key(f"{subsystem}_{name}")
        self._registered = 0
        self._traces = 0
        self._register_lock = threading.Lock()
        _install_monitor()

    # jit surface passthrough (.lower(), ._cache_size(), ...): callers
    # that treated the wrapped object as a jitted function keep working.
    def __getattr__(self, attr: str) -> Any:
        return getattr(self._jitted, attr)

    def _note_trace(self) -> None:
        """Called from inside the traced wrapper on each SUCCESSFUL
        trace of this program (never under ledger introspection) — the
        per-wrapper compile count. The guard's own count is not usable
        here: several programs can share one guard (the hetero sweep's
        per-chunk-length cache), and the C++ jit-cache size overcounts
        (donated outputs fed back as inputs mint new fastpath entries
        without any retrace)."""
        with self._register_lock:
            self._traces += 1

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        ledger = get_ledger()
        if not ledger.enabled:
            return self._jitted(*args, **kwargs)
        timings: Dict[str, float] = {}
        if self._registered == 0:
            # Compile-event attribution costs two thread-local touches
            # per call — paid only until the first registration. A
            # later re-compile (count-only guards) still registers,
            # with the first-dispatch wall as its build timing.
            stack = getattr(_MONITOR, "stack", None)
            if stack is None:
                stack = _MONITOR.stack = []
            stack.append(timings)
            t0 = time.perf_counter()
            try:
                out = self._jitted(*args, **kwargs)
            finally:
                stack.pop()
        else:
            t0 = time.perf_counter()
            out = self._jitted(*args, **kwargs)
        wall = time.perf_counter() - t0
        compiled = self._traces
        if compiled > self._registered:
            with self._register_lock:
                if compiled > self._registered:
                    self._registered = compiled
                    try:
                        self._register(ledger, args, kwargs, wall, timings)
                    except Exception:  # noqa: BLE001 — observability
                        pass  # must never fail the dispatch it observes
        else:
            # Steady-state dispatches only: the compiling call's wall
            # is a BUILD event (recorded as first_dispatch_seconds),
            # and folding it into the latency histogram would hand a
            # low-traffic program a compile-sized p95.
            ledger.dispatch(self.dispatch_key, wall)
        return out

    def _register(
        self,
        ledger: Any,
        args: Any,
        kwargs: Any,
        wall: float,
        timings: Dict[str, float],
    ) -> None:
        fingerprint, arg_bytes = _abstract_signature(args, kwargs)
        facts: Dict[str, float] = {}
        source = "unavailable"
        error: Optional[str] = None
        exe = _claim_executable(self.module_name, arg_bytes)
        if exe is not None:
            try:
                facts = _executable_facts(exe)
            except Exception as e:  # noqa: BLE001 — degrade to lowered
                facts, error = {}, repr(e)[:200]
            if facts:
                source = "executable"
        if source == "unavailable":
            # Pre-compile HLO estimates off the cached lowering: the
            # jaxpr cache holds this call's trace, so no re-trace in
            # the common case — and the introspection flag keeps a
            # cache miss out of the guard budget regardless.
            try:
                with _ledger_introspection():
                    lowered = self._jitted.lower(*args, **kwargs)
                facts = _executable_facts(lowered)
                if facts:
                    source = "lowered"
            except Exception as e:  # noqa: BLE001
                error = repr(e)[:200]
        all_timings = dict(timings)
        all_timings["first_dispatch_seconds"] = wall
        ledger.register(
            name=self.name,
            subsystem=self.subsystem,
            fingerprint=fingerprint,
            donate_argnums=self.donate_argnums,
            backend=jax.default_backend(),
            timings=all_timings,
            facts=facts,
            analysis_source=source,
            analysis_error=error,
            dispatch_key=self.dispatch_key,
        )


def ledgered_jit(
    fn: Callable[..., Any],
    guard: RetraceGuard,
    *,
    subsystem: str,
    program: Optional[str] = None,
    **jit_kwargs: Any,
) -> LedgerDispatch:
    """``jax.jit(guard.wrap(fn), **jit_kwargs)`` with automatic
    ProgramLedger registration — the one-line seam every budget-1
    compile site adopts.

    ``program`` names the ledger entry (default: the function's own
    name) and is stamped onto the traced function so the compiled HLO
    module carries it too — which is both nicer profiles and what lets
    the ledger claim the executable back from the backend by name.
    """
    name = program or getattr(fn, "__name__", None) or "program"
    stamped = sanitize_key(name)
    if getattr(fn, "__name__", None) != stamped:
        try:
            fn.__name__ = stamped
        except (AttributeError, TypeError):
            # functools.partial / vmap wrappers reject attribute writes:
            # interpose a named def so the module name still matches.
            inner = fn

            def _named(*args: Any, **kwargs: Any) -> Any:
                return inner(*args, **kwargs)

            _named.__name__ = stamped
            fn = _named
    # The trace-counting layer sits between the guard wrapper and jit:
    # it runs exactly once per successful trace of THIS program (the
    # guard has already enforced its budget underneath), feeding the
    # wrapper-local compile count registration keys off.
    guarded = guard.wrap(fn)
    holder: list = []

    @functools.wraps(guarded)
    def counted(*args: Any, **kwargs: Any) -> Any:
        out = guarded(*args, **kwargs)
        if holder and not getattr(_INTROSPECT, "active", False):
            holder[0]._note_trace()
        return out

    jitted = jax.jit(counted, **jit_kwargs)
    donate = jit_kwargs.get("donate_argnums") or ()
    if isinstance(donate, int):
        donate = (donate,)
    dispatch = LedgerDispatch(
        jitted,
        guard,
        subsystem=subsystem,
        name=name,
        module_name=f"jit_{stamped}",
        donate_argnums=tuple(donate),
    )
    holder.append(dispatch)
    return dispatch


def register_aot_program(
    *,
    name: str,
    subsystem: str,
    compiled: Any,
    fingerprint: str = "",
    donate_argnums: Tuple[int, ...] = (),
    timings: Optional[Dict[str, float]] = None,
    dispatch_key: Optional[str] = None,
) -> Optional[str]:
    """Register an explicitly lowered+compiled executable (the sharded
    serving AOT path): the caller already holds the ``jax.stages
    .Compiled``, so the facts come straight off it and the measured
    lower/compile walls ride as the timings. Returns the ledger key
    (None when the ledger is disabled)."""
    ledger = get_ledger()
    if not ledger.enabled:
        return None
    try:
        facts = _executable_facts(compiled)
    except Exception:  # noqa: BLE001
        facts = {}
    return ledger.register(
        name=name,
        subsystem=subsystem,
        fingerprint=fingerprint,
        donate_argnums=donate_argnums,
        backend=jax.default_backend(),
        timings=timings,
        facts=facts,
        analysis_source="aot" if facts else "unavailable",
        dispatch_key=dispatch_key,
    )


def device_memory_bytes() -> Optional[float]:
    """Device memory in use across local devices: the PJRT
    ``memory_stats`` gauge where the backend keeps one (TPU/GPU), the
    summed live-buffer footprint otherwise (CPU — exact, since device
    and host memory alias there). None when neither is answerable."""
    try:
        devices = jax.local_devices()
        total = 0.0
        counted = False
        for dev in devices:
            stats = getattr(dev, "memory_stats", lambda: None)()
            if stats and stats.get("bytes_in_use") is not None:
                total += float(stats["bytes_in_use"])
                counted = True
        if counted:
            return total
        client = devices[0].client
        return float(
            sum(
                int(getattr(buf, "nbytes", 0) or 0)
                for buf in client.live_buffers()
            )
        )
    except Exception:  # noqa: BLE001 — a gauge, not a contract
        return None


_watermark_lock = threading.Lock()
_watermark_last = 0.0


def sample_device_watermark(
    min_interval_s: float = 5.0, force: bool = False
) -> Optional[float]:
    """Record the current device-memory footprint into the ledger's
    watermark gauge (called at drain/swap boundaries — host seams
    where a sync already happened). One attribute read when the ledger
    is disabled.

    Rate-limited: the CPU fallback walks every live buffer (~35 ms at
    5k arrays), which a per-chunk drain seam must not pay per chunk —
    the watermark is a slow-moving gauge, so samples closer than
    ``min_interval_s`` are skipped. Rare boundaries (a fleet swap)
    pass ``force=True``."""
    global _watermark_last
    ledger = get_ledger()
    if not ledger.enabled:
        return None
    now = time.monotonic()
    if not force:
        with _watermark_lock:
            if now - _watermark_last < min_interval_s:
                return None
            _watermark_last = now
    else:
        with _watermark_lock:
            _watermark_last = now
    value = device_memory_bytes()
    if value is not None:
        ledger.record_watermark(value)
    return value


@contextlib.contextmanager
def nan_guard(enable: bool = True) -> Iterator[None]:
    """Scoped ``jax_debug_nans``: ops that produce NaN re-run op-by-op
    and raise at the source op instead of poisoning the whole rollout.
    Restores the previous setting on exit (compose freely with training
    code that toggles it)."""
    previous = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", enable)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", previous)
