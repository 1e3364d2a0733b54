"""graftlint tier-1 contract: every rule fires on a known-bad fixture,
stays quiet on the known-good twin, and the package itself is clean.

The package scan is the point of the subsystem (ISSUE: the linter
*proves* the loop stays compiled and device-resident, permanently, in
CI); the fixture pairs pin each rule's detection so a refactor of the
engine cannot silently lobotomize a rule while the package scan still
reports zero.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "marl_distributedformation_tpu"

from marl_distributedformation_tpu.analysis import (  # noqa: E402
    GraftlintConfig,
    lint_paths,
    lint_source,
)
from marl_distributedformation_tpu.analysis.config import (  # noqa: E402
    config_from_dict,
)
from marl_distributedformation_tpu.analysis.rules import rule_names  # noqa: E402


def lint(src):
    """Lint a fixture. A plain string is one in-memory module; a dict
    ``{filename: source}`` is a multi-file fixture written to a real
    temp directory (cross-module rules resolve imports on disk) with
    ``main.py`` as the linted module."""
    if isinstance(src, dict):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            d = Path(td)
            for name, content in src.items():
                (d / name).write_text(textwrap.dedent(content))
            return lint_source(
                textwrap.dedent(src["main.py"]), str(d / "main.py")
            )
    return lint_source(textwrap.dedent(src), "fixture.py")


def fired(src):
    return {v.rule for v in lint(src)}


# ---------------------------------------------------------------------------
# Rule fixtures: (rule, known-bad, known-good)
# ---------------------------------------------------------------------------

FIXTURES = [
    (
        "numpy-in-jit",
        """
        import jax, numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)  # host numpy on a traced arg
        """,
        """
        import jax, jax.numpy as jnp, numpy as np

        @jax.jit
        def f(x):
            table = np.arange(4)  # static constant: allowed
            return jnp.sum(x) + table[0]
        """,
    ),
    (
        "traced-python-control-flow",
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            s = jnp.sum(x)
            if s > 0:
                return x
            return -x
        """,
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x, params, with_obs=True):
            if params.strict_parity:   # static config: allowed
                x = x + 1
            if x.shape[0] > 2:         # static shape: allowed
                x = x * 2
            if with_obs:               # literal-default flag: allowed
                x = x - 1
            if x is None:              # structural: allowed
                return x
            return jnp.where(jnp.sum(x) > 0, x, -x)
        """,
    ),
    (
        "traced-python-control-flow",
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            while jnp.abs(x).max() > 1.0:
                x = x * 0.5
            return x
        """,
        """
        import jax
        from jax import lax

        @jax.jit
        def f(x):
            return lax.while_loop(lambda v: False, lambda v: v, x)
        """,
    ),
    (
        "prng-key-reuse",
        """
        import jax

        def sample(key):
            a = jax.random.uniform(key, (3,))
            b = jax.random.normal(key, (3,))  # same key: correlated draws
            return a + b
        """,
        """
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.uniform(k1, (3,))
            b = jax.random.normal(k2, (3,))
            return a + b
        """,
    ),
    (
        "prng-key-reuse",
        """
        import jax
        from jax import lax

        def rollout(key, carry, xs):
            # scan body as a lambda — the idiomatic home of per-step keys
            return lax.scan(
                lambda c, x: (c, jax.random.normal(key) + jax.random.uniform(key)),
                carry, xs,
            )
        """,
        """
        import jax
        from jax import lax

        def rollout(key, carry, xs):
            return lax.scan(
                lambda c, x: (c, jax.random.normal(x)), carry, xs
            )
        """,
    ),
    (
        "prng-key-reuse",
        """
        import jax

        def rollout(key, n):
            outs = []
            for _ in range(n):
                outs.append(jax.random.uniform(key))  # reused every iter
            return outs
        """,
        """
        import jax

        def rollout(key, n):
            outs = []
            for _ in range(n):
                key, k = jax.random.split(key)
                outs.append(jax.random.uniform(k))
            return outs
        """,
    ),
    (
        "host-sync-in-jit",
        """
        import jax

        @jax.jit
        def f(x):
            return float(x.sum())  # concretizes the tracer
        """,
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.float32(x.sum())
        """,
    ),
    (
        "host-sync-in-jit",
        """
        import jax, numpy as np

        @jax.jit
        def f(x):
            y = x * 2
            return np.asarray(y)  # device->host pull
        """,
        """
        import numpy as np

        def host_metrics(metrics):  # not traced: syncs are fine here
            return {k: float(v) for k, v in metrics.items()}
        """,
    ),
    (
        "mutable-capture-in-jit",
        """
        import jax

        @jax.jit
        def f(x, acc=[]):
            acc.append(1)  # trace-time side effect
            return x
        """,
        """
        import jax

        @jax.jit
        def f(x, scale=1.0):
            return x * scale
        """,
    ),
    (
        "mutable-capture-in-jit",
        """
        import jax

        _count = 0

        @jax.jit
        def f(x):
            global _count
            _count += 1  # advances once per COMPILE, not per step
            return x
        """,
        """
        import jax

        _TABLE = (1, 2, 3)

        @jax.jit
        def f(x):
            return x * _TABLE[0]  # reading module constants is fine
        """,
    ),
    (
        "deprecated-api",
        """
        import jax

        def make(mesh, spec, f):
            return jax.experimental.shard_map.shard_map(
                f, mesh=mesh, in_specs=spec, out_specs=spec
            )
        """,
        """
        import jax

        def make(mesh, spec, f):
            return jax.shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec)
        """,
    ),
    (
        "deprecated-api",
        """
        from jax.experimental.shard_map import shard_map
        """,
        """
        from jax.experimental import mesh_utils
        """,
    ),
    (
        "missing-donate",
        """
        import jax

        def make(train_iteration):
            return jax.jit(train_iteration)  # prev state stays live
        """,
        """
        import jax

        def make(train_iteration):
            donating = jax.jit(train_iteration, donate_argnums=(0, 1))
            iteration_no_donate = jax.jit(train_iteration)  # documented twin
            return donating, iteration_no_donate
        """,
    ),
    (
        "print-in-jit",
        """
        import jax

        @jax.jit
        def f(x):
            print("stepping", x)  # trace-time only
            return x
        """,
        """
        import jax

        @jax.jit
        def f(x):
            jax.debug.print("stepping {}", x)
            return x
        """,
    ),
    (
        "print-in-jit",
        """
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            msg = f"sum was {y}"  # bakes in the tracer repr
            return x, msg
        """,
        """
        import jax

        @jax.jit
        def f(x, k=4):
            n = x.shape[0]
            assert k < n, f"need k < N (k={k}, N={n})"  # static + failure path
            return x
        """,
    ),
    (
        "scan-carry-weak-type",
        """
        import jax
        from jax import lax

        def rollout(body, x, xs):
            # 0.0 is a weak-typed Python scalar: the body's arithmetic
            # promotes it and the carry comes back a different aval.
            return lax.scan(body, (x, 0.0), xs)
        """,
        """
        import jax, jax.numpy as jnp
        from jax import lax

        def rollout(body, x, xs):
            carry = (x, jnp.asarray(0.0, jnp.float32))
            out = lax.scan(body, carry, xs)
            # literals inside constructors are strong-typed: fine
            return lax.scan(body, (x, jnp.zeros((4,))), xs), out
        """,
    ),
    (
        "scan-carry-weak-type",
        """
        import jax

        def count(body, xs):
            # keyword init + unary sign both reach the literal
            return jax.lax.scan(body, init=-1, xs=xs)
        """,
        """
        import jax, jax.numpy as jnp

        def count(body, xs, n0):
            # int dict KEYS are pytree structure, not carry leaves
            out = jax.lax.scan(body, init={0: n0, 1: n0}, xs=xs)
            return jax.lax.scan(body, init=n0, xs=xs), out
        """,
    ),
    (
        "vmap-in-axes-arity",
        """
        import jax

        def f(x, y):
            return x + y

        def run(a, b):
            # signature drifted: f takes 2 args, the axes spec says 3
            return jax.vmap(f, in_axes=(0, None, 0))(a, b, b)
        """,
        """
        import jax, functools

        def f(x, y, scale=1.0):
            return (x + y) * scale

        def g(x, y):
            return x + y

        g = functools.partial(g, y=1)  # rebound: arity untrustworthy

        def run(a, b):
            two = jax.vmap(f, in_axes=(0, None))(a, b)       # default ok
            three = jax.vmap(f, in_axes=(0, None, None))(a, b, 2.0)
            # wrapped targets change the effective arity: out of scope
            part = jax.vmap(
                functools.partial(f, scale=2.0), in_axes=(0, None)
            )(a, b)
            one = jax.vmap(g, in_axes=(0,))(a)  # rebound name: skipped
            return two, three, part, one
        """,
    ),
    (
        "implicit-f64-promotion",
        """
        import jax, numpy as np

        @jax.jit
        def f(x):
            scale = np.float64(0.5)          # f64 scalar at trace time
            y = x * np.array([0.5, 1.5])     # host f64 mixed with traced
            return (y * scale).astype(np.float64)
        """,
        """
        import jax, jax.numpy as jnp, numpy as np

        @jax.jit
        def f(x):
            y = x * 0.5                      # weak literal: adopts x's dtype
            table = np.array([0.5, 1.5], dtype=np.float32)  # pinned
            z = y + jnp.asarray(table)
            counts = x + np.arange(4)        # int arange: not an f64 source
            return z.astype(jnp.float32), counts
        """,
    ),
    (
        "implicit-f64-promotion",
        """
        import jax, jax.numpy as jnp, numpy as np

        @jax.jit
        def g(x):
            grid = jnp.zeros((4,), dtype=float)  # builtin float == f64
            return x + grid, x * np.linspace(0.0, 1.0, 4)
        """,
        """
        import numpy as np

        def host_report(arr):
            # not traced: host-side f64 statistics are fine
            return np.float64(arr).mean() + np.linspace(0.0, 1.0, 4)
        """,
    ),
    (
        "vmap-in-axes-arity",
        """
        import jax

        def run(a, b, g):
            # g is imported/opaque — but the immediate call disagrees
            # with the axes tuple, which is checkable syntactically
            return jax.vmap(g, in_axes=(0, 0))(a, b, b)
        """,
        """
        import jax

        def run(a, b, g):
            mapped = jax.vmap(g, in_axes=(0, None))(a, b)
            star = jax.vmap(g, in_axes=(0, None))(*[a, b, b])  # skipped
            scalar = jax.vmap(g, in_axes=0)(a, b, b)  # int spec: skipped
            return mapped, star, scalar
        """,
    ),
    (
        "callback-in-hot-loop",
        """
        import jax, jax.numpy as jnp
        from jax import lax

        def train(xs):
            def body(carry, x):
                jax.debug.print("reward {r}", r=x)  # host RTT per step
                return carry + x, x
            return lax.scan(body, jnp.zeros(()), xs)
        """,
        """
        import jax, jax.numpy as jnp
        from jax import lax

        @jax.jit
        def debug_step(x):
            # one transfer per dispatch, not inside a compiled loop: fine
            jax.debug.print("x = {x}", x=x)
            return x * 2

        def train(xs):
            def body(carry, x):
                return carry + x, x  # telemetry stacked in the scan output
            carry, stacked = lax.scan(body, jnp.zeros(()), xs)
            jax.debug.print("chunk done: {c}", c=carry)  # once per chunk
            return carry, stacked
        """,
    ),
    (
        "callback-in-hot-loop",
        """
        import jax
        from jax import lax

        def emit(metrics):
            jax.experimental.io_callback(print, None, metrics)

        def train(steps, state):
            def body(i, state):
                emit(state)  # reaches io_callback: host RTT per step
                return state
            return lax.fori_loop(0, steps, body, state)
        """,
        """
        import jax
        from jax import lax

        def emit(metrics):
            jax.experimental.io_callback(print, None, metrics)

        def train(steps, state):
            def body(i, state):
                return state
            state = lax.fori_loop(0, steps, body, state)
            emit(state)  # outside the loop: once per chunk, fine
            return state
        """,
    ),
    (
        "scan-carry-sharding-drift",
        """
        import functools
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train(state, xs):
            def body(carry, x):
                h = carry + x
                h = lax.with_sharding_constraint(h, P())  # drifted
                return h, h
            init = lax.with_sharding_constraint(state, P("dp"))
            return lax.scan(body, init, xs)

        def shadowed(state, xs):
            # the body REUSES the init's name — its rebind is a
            # different scope and must not mask the init's spec
            state = lax.with_sharding_constraint(state, P("dp"))
            def walk(carry, x):
                state = lax.with_sharding_constraint(carry + x, P())
                return state, state
            return lax.scan(walk, state, xs)
        """,
        """
        import functools
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def other(x):
            # sibling function binding the same name at another spec:
            # never poisons train's init lookup
            init = lax.with_sharding_constraint(x, P(None))
            return init

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train(state, xs):
            def body(carry, x):
                h = lax.with_sharding_constraint(carry + x, P("dp"))
                return h, h
            init = lax.with_sharding_constraint(state, P("dp"))
            return lax.scan(body, init, xs)

        def train2(state, xs):
            def walk(carry, x):
                h = lax.with_sharding_constraint(carry + x, P("dp"))
                return h, h
            # init unannotated: propagation decides both consistently
            return lax.scan(walk, state, xs)
        """,
    ),
    (
        "scan-carry-sharding-drift",
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def step(nn_params, acc, xs):
            p0 = lax.with_sharding_constraint(nn_params, P("dp"))
            def body(carry, x):
                p, a = carry
                p = lax.with_sharding_constraint(p, P(None))  # drifted
                return (p, a + x), a
            return lax.scan(body, (p0, acc), xs)
        """,
        """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def step(nn_params, acc, xs):
            p0 = lax.with_sharding_constraint(nn_params, P("dp"))
            def body(carry, x):
                p, a = carry
                p = lax.with_sharding_constraint(p, P("dp"))
                return (p, a + x), a
            return lax.scan(body, (p0, acc), xs)
        """,
    ),
    (
        # Cross-module reachability: the callback hides one `from x
        # import f` away — invisible to rule 12's same-module hop.
        "cross-module-callback",
        {
            "main.py": """
            import jax
            from jax import lax
            from telemetry import emit

            def train(xs):
                def body(carry, x):
                    emit(x)  # io_callback lives in telemetry.py
                    return carry + x, x
                return lax.scan(body, 0.0, xs)
            """,
            "telemetry.py": """
            import jax

            def emit(metrics):
                jax.experimental.io_callback(print, None, metrics)
            """,
        },
        {
            "main.py": """
            import jax
            from jax import lax
            from telemetry import emit, fold

            def train(xs):
                def body(carry, x):
                    return fold(carry, x), x  # imported but pure: clean
                carry, stacked = lax.scan(body, 0.0, xs)
                emit(stacked)  # outside the loop: once per chunk, fine
                return carry, stacked
            """,
            "telemetry.py": """
            import jax

            def emit(metrics):
                jax.experimental.io_callback(print, None, metrics)

            def fold(carry, x):
                return carry + x
            """,
        },
    ),
    (
        # Same hazard via a module alias (`import pkg_mod as telem;
        # telem.emit(...)`) inside a fori_loop body.
        "cross-module-callback",
        {
            "main.py": """
            import jax
            from jax import lax
            import telem

            def train(steps, state):
                def body(i, state):
                    telem.emit(state)  # reaches jax.debug.callback
                    return state
                return lax.fori_loop(0, steps, body, state)
            """,
            "telem.py": """
            import jax

            def emit(state):
                jax.debug.callback(print, state)
            """,
        },
        {
            "main.py": """
            import jax
            from jax import lax
            import telem

            def emit(state):
                # same-module def SHADOWS the import target name space:
                # plain `emit(...)` here is rule 12's domain, not ours
                return state

            def train(steps, state):
                def body(i, state):
                    emit(state)  # resolves to the local, clean def
                    return telem.scale(state)  # imported but pure
                state = lax.fori_loop(0, steps, body, state)
                telem.emit(state)  # outside the loop: fine
                return state
            """,
            "telem.py": """
            import jax

            def emit(state):
                jax.debug.callback(print, state)

            def scale(state):
                return state * 2
            """,
        },
    ),
    (
        # Host-side tracing recorded INSIDE a jitted function: the span
        # closes at trace time, measuring one compile and zero
        # executions — and host work has leaked into the compiled scope.
        "span-in-traced-scope",
        """
        import jax
        from marl_distributedformation_tpu.obs import get_tracer

        tracer = get_tracer()

        @jax.jit
        def step(x):
            with tracer.span("step"):
                return x * 2
        """,
        """
        import jax
        from marl_distributedformation_tpu.obs import get_tracer

        tracer = get_tracer()

        @jax.jit
        def step(x):
            return x * 2

        def dispatch(x):
            # the dispatch seam: span wraps the jitted CALL, host-side
            with tracer.span("step"):
                return step(x)
        """,
    ),
    (
        # Same hazard one hop away inside a scan body: the helper's
        # event() call would record per trace, not per iteration — and
        # via get_tracer() it is invisible to a receiver-name check.
        "span-in-traced-scope",
        """
        import jax
        from jax import lax
        from marl_distributedformation_tpu.obs import get_tracer

        def note(x):
            get_tracer().event("iteration", value=0)

        def train(xs):
            def body(carry, x):
                note(x)
                return carry + x, x
            return lax.scan(body, 0.0, xs)
        """,
        """
        import jax
        from jax import lax
        from marl_distributedformation_tpu.obs import get_tracer

        def train(xs):
            def body(carry, x):
                return carry + x, x
            with get_tracer().span("train.chunk"):
                carry, stacked = lax.scan(body, 0.0, xs)
            get_tracer().event("chunk_done")
            return carry, stacked
        """,
    ),
    (
        # Params re-placed per request inside the serve loop: a full
        # host->device weight upload every dispatch. The good twin
        # places ONCE before the loop (the swap/commit seam) and
        # dispatches against the device-resident tree.
        "device-put-in-dispatch-loop",
        """
        import jax

        def serve_loop(q, params, device, engine):
            while True:
                req = q.get()
                placed = jax.device_put(params, device)  # per request!
                engine.act(placed, req)
        """,
        """
        import jax

        def serve_loop(q, params, device, engine):
            placed = jax.device_put(params, device)  # once, at build
            while True:
                req = q.get()
                engine.act(placed, req)
        """,
    ),
    (
        # The same hazard one plain-name call hop away: the loop calls
        # a helper that performs the placement. The good twin's helper
        # is only called outside the loop (and an amortized batched
        # device_get drain in the loop stays clean — gets are the
        # runtime guard's business, per the trainer's log-interval
        # drain idiom).
        "device-put-in-dispatch-loop",
        """
        import jax

        def _place(params, device):
            return jax.device_put(params, device)

        def serve_loop(q, params, device, engine):
            while not q.empty():
                req = q.get()
                engine.act(_place(params, device), req)
        """,
        """
        import jax

        def _place(params, device):
            return jax.device_put(params, device)

        def serve_loop(q, params, device, engine, metrics):
            placed = _place(params, device)
            i = 0
            while not q.empty():
                req = q.get()
                engine.act(placed, req)
                i += 1
                if i % 100 == 0:
                    jax.device_get(metrics)  # amortized drain: clean
        """,
    ),
    (
        # Rule 17: the evolutionary-search foot-gun — a lax loop body
        # selects candidates through a module-level helper that Python-
        # branches on a comparison of its (traced) arguments. Rule 2
        # cannot see it (the helper is not itself a traced scope); the
        # one-hop follow reports it at the call site.
        "traced-python-comparison-in-search",
        """
        import jax
        from jax import lax

        def better(best, cand):
            if cand > best:  # concretizes under the while_loop trace
                return cand
            return best

        def search(fitness):
            def body(state):
                i, best = state
                return i + 1, better(best, fitness[i])

            return lax.while_loop(lambda s: s[0] < 8, body, (0, fitness[0]))
        """,
        """
        import jax, jax.numpy as jnp
        from jax import lax

        def better(best, cand):
            return jnp.where(cand > best, cand, best)  # stays in-program

        def search(fitness):
            def body(state):
                i, best = state
                return i + 1, better(best, fitness[i])

            return lax.while_loop(lambda s: s[0] < 8, body, (0, fitness[0]))
        """,
    ),
    (
        # Rule 17, jitted-generation-loop shape: a host `for` loop fused
        # wholesale into a jitted search calls a threshold helper whose
        # `while` compares traced arguments.
        "traced-python-comparison-in-search",
        """
        import jax, jax.numpy as jnp

        def clamp(cur, cand, limit):
            while cand > cur + limit:  # traced comparison, Python loop
                cand = cand * 0.5
            return cand

        @jax.jit
        def evolve(pop, limit):
            best = pop[0]
            for _ in range(4):  # generation loop, jitted wholesale
                best = clamp(best, pop.max(), limit)
            return best
        """,
        """
        import jax, jax.numpy as jnp

        def clamp(cur, cand, keep_best=True):
            if keep_best:  # literal-default flag: static, allowed
                return jnp.maximum(cur, cand)
            return cand

        @jax.jit
        def evolve(pop):
            best = pop[0]
            for _ in range(4):
                best = clamp(best, pop.max())
            return best
        """,
    ),
    (
        # Rule 18: MetricsRegistry recording under trace — the counter
        # bumps once at COMPILE time, then never again, while the code
        # looks instrumented. The good twin records at the dispatch
        # seam around the jitted call.
        "metrics-in-traced-scope",
        """
        import jax
        from marl_distributedformation_tpu.obs.metrics import get_registry

        @jax.jit
        def step(x):
            get_registry().counter("steps_total").inc()
            return x * 2
        """,
        """
        import jax
        from marl_distributedformation_tpu.obs.metrics import get_registry

        @jax.jit
        def step(x):
            return x * 2

        def dispatch(x):
            out = step(x)
            get_registry().counter("steps_total").inc()
            return out
        """,
    ),
    (
        # Same hazard one hop away inside a scan body, through a
        # registry-receiver chain: the helper's observe() would record
        # per trace, not per iteration. The good twin's helper is only
        # called from the host-side drain.
        "metrics-in-traced-scope",
        """
        from jax import lax

        def note(registry, dt):
            registry.histogram("iter_seconds").observe(dt)

        def train(registry, xs):
            def body(carry, x):
                note(registry, x)
                return carry + x, x
            return lax.scan(body, 0.0, xs)
        """,
        """
        from jax import lax

        def note(registry, dt):
            registry.histogram("chunk_seconds").observe(dt)

        def train(registry, xs):
            def body(carry, x):
                return carry + x, x
            carry, stacked = lax.scan(body, 0.0, xs)
            note(registry, 0.1)  # the drain seam: host-side
            registry.gauge("steps_per_sec").set(1.0)
            return carry, stacked
        """,
    ),
    (
        # Rule 19: a chaos injection point under trace — the armed
        # fault fires once at COMPILE time (or unwinds the tracer
        # itself) while the campaign believes it exercises every step.
        # The good twin injects at the dispatch seam around the call.
        "fault-point-in-traced-scope",
        """
        import jax
        from marl_distributedformation_tpu.chaos import fault_point

        @jax.jit
        def step(x):
            fault_point("trainer.step")
            return x * 2
        """,
        """
        import jax
        from marl_distributedformation_tpu.chaos import fault_point

        @jax.jit
        def step(x):
            return x * 2

        def dispatch(x):
            fault_point("trainer.dispatch")
            return step(x)
        """,
    ),
    (
        # Same hazard one hop away inside a scan body, through the
        # plane-receiver chain: the helper's hit() would count per
        # trace, not per iteration. The good twin's helper is only
        # called from the host-side drain, and an unrelated .hit()
        # receiver stays clean.
        "fault-point-in-traced-scope",
        """
        from jax import lax
        from marl_distributedformation_tpu.chaos import get_fault_plane

        def poke():
            get_fault_plane().hit("sweep.member")

        def train(xs):
            def body(carry, x):
                poke()
                return carry + x, x
            return lax.scan(body, 0.0, xs)
        """,
        """
        from jax import lax
        from marl_distributedformation_tpu.chaos import get_fault_plane

        def poke():
            get_fault_plane().hit("sweep.drain")

        def train(xs, target):
            def body(carry, x):
                target.hit(x)  # not plane-like: stays clean
                return carry + x, x
            carry, stacked = lax.scan(body, 0.0, xs)
            poke()  # the drain seam: host-side
            return carry, stacked
        """,
    ),
    (
        # Ledger dispatch recording inside a jitted body measures the
        # trace, not the dispatches. The good twin records at the host
        # seam around the jitted call — the ledgered_jit discipline.
        "ledger-record-in-traced-scope",
        """
        import jax
        from marl_distributedformation_tpu.obs.ledger import get_ledger

        @jax.jit
        def step(x):
            get_ledger().dispatch("trainer_step", 0.001)
            return x * 2
        """,
        """
        import jax
        import time
        from marl_distributedformation_tpu.obs.ledger import get_ledger

        @jax.jit
        def step(x):
            return x * 2

        def dispatch(x):
            t0 = time.perf_counter()
            out = step(x)
            get_ledger().dispatch("trainer_step", time.perf_counter() - t0)
            return out
        """,
    ),
    (
        # Same hazard one hop away inside a scan body, through a
        # ledger-receiver chain; the good twin's helper runs at the
        # drain seam, and an unrelated ``.register()`` receiver
        # (atexit-shaped) stays clean.
        "ledger-record-in-traced-scope",
        """
        from jax import lax
        from marl_distributedformation_tpu.obs import ledger

        def note(ledger_handle):
            ledger_handle.record_watermark(1024.0)

        def train(xs, ledger_handle):
            def body(carry, x):
                note(ledger_handle)
                return carry + x, x
            return lax.scan(body, 0.0, xs)
        """,
        """
        import atexit
        from jax import lax
        from marl_distributedformation_tpu.obs import ledger

        def note():
            ledger.get_ledger().record_watermark(1024.0)

        def train(xs, hooks):
            def body(carry, x):
                hooks.register(x)  # not ledger-like: stays clean
                return carry + x, x
            carry, stacked = lax.scan(body, 0.0, xs)
            note()  # the drain seam: host-side
            return carry, stacked
        """,
    ),
    (
        # Rule 21: a mesh RPC round trip under trace fires once per
        # COMPILE and wedges the tracer on a dead peer. The good twin
        # makes the coordinator call at the dispatch seam around the
        # jitted call.
        "rpc-in-traced-scope",
        """
        import jax
        from marl_distributedformation_tpu.serving.mesh.rpc import rpc_call

        @jax.jit
        def step(x):
            rpc_call("http://127.0.0.1:9", "mesh.heartbeat", {})
            return x * 2
        """,
        """
        import jax
        from marl_distributedformation_tpu.serving.mesh.rpc import rpc_call

        @jax.jit
        def step(x):
            return x * 2

        def dispatch(x):
            out = step(x)
            rpc_call("http://127.0.0.1:9", "mesh.heartbeat", {})
            return out
        """,
    ),
    (
        # Same hazard one hop away inside a scan body, through a
        # mesh-receiver chain and a raw socket-module call; the good
        # twin's helper runs at the host seam, and an unrelated
        # ``registry.register(...)`` receiver stays clean.
        "rpc-in-traced-scope",
        """
        import socket
        from jax import lax

        def phone_home(coordinator):
            coordinator.global_reload("ckpt")
            socket.create_connection(("127.0.0.1", 9))

        def train(xs, coordinator):
            def body(carry, x):
                phone_home(coordinator)
                return carry + x, x
            return lax.scan(body, 0.0, xs)
        """,
        """
        import socket
        from jax import lax

        def phone_home(coordinator):
            coordinator.global_reload("ckpt")
            socket.create_connection(("127.0.0.1", 9))

        def train(xs, coordinator, registry):
            def body(carry, x):
                registry.register(x)  # not mesh-like: stays clean
                return carry + x, x
            carry, stacked = lax.scan(body, 0.0, xs)
            phone_home(coordinator)  # the dispatch seam: host-side
            return carry, stacked
        """,
    ),
    (
        # Rule 22: per-iteration host finiteness polling of a device
        # value forces one sync per dispatch (and sees fused divergence
        # K iterations late). The good twin computes the health word
        # in-program and drains it batched — np over the DRAINED numpy
        # stack is the legitimate spelling.
        "host-nonfinite-probe-in-dispatch-loop",
        """
        import jax
        import jax.numpy as jnp

        def train(step, state, total):
            i = 0
            while i < total:
                state, loss = step(state)
                if jnp.isnan(loss).any():  # device sync per iteration
                    break
                i += 1
            return state
        """,
        """
        import jax
        import numpy as np

        def train(step_chunk, state, chunks):
            stacks = []
            for _ in range(chunks):
                state, stacked = step_chunk(state)  # health word rides
                stacks.append(stacked)              # the chunk metrics
            drained = jax.device_get(stacks)  # ONE batched drain
            flags = np.concatenate([s["health_ok"] for s in drained])
            skipped = int((flags < 0.5).sum())  # np over host data: clean
            return state, skipped
        """,
    ),
    (
        # Same hazard spelled as float()-pull probes — math.isnan over
        # a forced transfer, one hop into a helper — in a for-loop
        # dispatch body. The good twin keeps the float() pulls (the
        # drain's legitimate log path) but probes finiteness only once,
        # AFTER the loop.
        "host-nonfinite-probe-in-dispatch-loop",
        """
        import math

        def diverged(metrics):
            return math.isnan(float(metrics["loss"]))

        def train(step, state, total):
            for _ in range(total):
                state, metrics = step(state)
                if diverged(metrics):  # reaches math.isnan(float(...))
                    break
            return state
        """,
        """
        import math

        def train(step, state, total):
            record = {}
            for _ in range(total):
                state, metrics = step(state)
                record = {k: float(v) for k, v in metrics.items()}
            final_ok = not math.isnan(float(record["loss"]))  # once, post-loop
            return state, final_ok
        """,
    ),
    (
        # Rule 23: three locks acquired pairwise in a ring (a→b, b→c,
        # c→a) — two threads entering from different edges deadlock.
        # The good twin acquires the same locks in one global order.
        "lock-ordering-cycle",
        """
        import threading

        class Pool:
            def __init__(self):
                self.a_lock = threading.Lock()
                self.b_lock = threading.Lock()
                self.c_lock = threading.Lock()

            def ab(self):
                with self.a_lock:
                    with self.b_lock:
                        pass

            def bc(self):
                with self.b_lock:
                    with self.c_lock:
                        pass

            def ca(self):
                with self.c_lock:
                    with self.a_lock:
                        pass
        """,
        """
        import threading

        class Pool:
            def __init__(self):
                self.a_lock = threading.Lock()
                self.b_lock = threading.Lock()
                self.c_lock = threading.Lock()

            def ab(self):
                with self.a_lock:
                    with self.b_lock:
                        pass

            def bc(self):
                with self.b_lock:
                    with self.c_lock:
                        pass

            def ac(self):
                with self.a_lock:
                    with self.c_lock:
                        pass
        """,
    ),
    (
        # Rule 23 again: a two-lock inversion hidden behind a call —
        # flush holds read_lock and calls a helper that takes
        # write_lock, while compact nests them the other way round.
        # The good twin gives compact the same read→write order.
        "lock-ordering-cycle",
        """
        import threading

        class Store:
            def __init__(self):
                self.read_lock = threading.Lock()
                self.write_lock = threading.Lock()

            def flush(self):
                with self.read_lock:
                    self._sync()

            def _sync(self):
                with self.write_lock:
                    pass

            def compact(self):
                with self.write_lock:
                    with self.read_lock:
                        pass
        """,
        """
        import threading

        class Store:
            def __init__(self):
                self.read_lock = threading.Lock()
                self.write_lock = threading.Lock()

            def flush(self):
                with self.read_lock:
                    self._sync()

            def _sync(self):
                with self.write_lock:
                    pass

            def compact(self):
                with self.read_lock:
                    with self.write_lock:
                        pass
        """,
    ),
    (
        # Rule 24: an attribute declared guarded-by a lock, written
        # from a thread-reachable method without holding it. The good
        # twin wraps the write.
        "unguarded-shared-mutation",
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0  # graftlock: guarded-by=_lock

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                self.total = self.total + 1
        """,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0  # graftlock: guarded-by=_lock

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                with self._lock:
                    self.total = self.total + 1
        """,
    ),
    (
        # Rule 24 again: the unguarded write hides one call deep — the
        # thread entry calls a helper that mutates. The good twin holds
        # the lock at the caller; the held context flows through the
        # call edge, so the helper needs no lock of its own.
        "unguarded-shared-mutation",
        """
        import threading

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self.head = 0  # graftlock: guarded-by=_lock

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                self._advance()

            def _advance(self):
                self.head = self.head + 1
        """,
        """
        import threading

        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self.head = 0  # graftlock: guarded-by=_lock

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._advance()

            def _advance(self):
                self.head = self.head + 1
        """,
    ),
    (
        # Rule 25: sleeping while the batch gate is held keeps every
        # replica's barrier closed for the duration. The good twin
        # sleeps after releasing it.
        "blocking-call-under-dispatch-lock",
        """
        import threading
        import time

        class Dispatcher:
            def __init__(self):
                self.batch_lock = threading.Lock()
                self.backoff_s = 0.5

            def flush(self):
                with self.batch_lock:
                    time.sleep(self.backoff_s)
        """,
        """
        import threading
        import time

        class Dispatcher:
            def __init__(self):
                self.batch_lock = threading.Lock()
                self.backoff_s = 0.5

            def flush(self):
                with self.batch_lock:
                    pending = self.backoff_s
                time.sleep(pending)
        """,
    ),
    (
        # Rule 25 again: a gate-annotated lock held across a device
        # drain — jax.device_get blocks on the accelerator stream. The
        # good twin snapshots the reference under the gate and drains
        # after releasing it.
        "blocking-call-under-dispatch-lock",
        """
        import threading
        import jax

        class DrainGate:
            def __init__(self):
                self._drain_gate = threading.Lock()  # graftlock: gate
                self._buf = None

            def drain(self):
                with self._drain_gate:
                    return jax.device_get(self._buf)
        """,
        """
        import threading
        import jax

        class DrainGate:
            def __init__(self):
                self._drain_gate = threading.Lock()  # graftlock: gate
                self._buf = None

            def drain(self):
                with self._drain_gate:
                    buf = self._buf
                    self._buf = None
                return jax.device_get(buf)
        """,
    ),
    (
        # Rule 26: a timer armed while a lock is held whose callback
        # re-acquires the same lock — if the timer can fire
        # synchronously (or the armer joins it) this deadlocks. The
        # good twin arms the timer after releasing the lock.
        "lock-released-across-await-seam",
        """
        import threading

        class Beat:
            def __init__(self):
                self._beat_lock = threading.Lock()
                self.beats = 0

            def arm(self):
                with self._beat_lock:
                    t = threading.Timer(1.0, self._fire)
                    t.start()

            def _fire(self):
                with self._beat_lock:
                    self.beats += 1
        """,
        """
        import threading

        class Beat:
            def __init__(self):
                self._beat_lock = threading.Lock()
                self.beats = 0

            def arm(self):
                with self._beat_lock:
                    interval = 1.0 + self.beats
                t = threading.Timer(interval, self._fire)
                t.start()

            def _fire(self):
                with self._beat_lock:
                    self.beats += 1
        """,
    ),
    (
        # Rule 26 again: an executor submit under the refresh lock
        # whose task transitively re-acquires it one call deep. The
        # good twin submits after the lock is released.
        "lock-released-across-await-seam",
        """
        import threading

        class Loader:
            def __init__(self, pool):
                self._refresh_lock = threading.Lock()
                self._pool = pool
                self.step = 0

            def kick(self):
                with self._refresh_lock:
                    self._pool.submit(self._reload)

            def _reload(self):
                self._commit()

            def _commit(self):
                with self._refresh_lock:
                    self.step += 1
        """,
        """
        import threading

        class Loader:
            def __init__(self, pool):
                self._refresh_lock = threading.Lock()
                self._pool = pool
                self.step = 0

            def kick(self):
                with self._refresh_lock:
                    stale = self.step
                if stale >= 0:
                    self._pool.submit(self._reload)

            def _reload(self):
                self._commit()

            def _commit(self):
                with self._refresh_lock:
                    self.step += 1
        """,
    ),
    (
        # blocking-transfer-in-actor-loop: a device_get + a method-
        # spelled block_until_ready inside the actor lane's while loop —
        # one sync per rollout on the acting critical path. The good
        # twin hands the device tree to the transfer-queue seam (method
        # calls are deliberately not followed: the queue's enqueue-time
        # device_put is the sanctioned off-critical-path home) and the
        # same calls OUTSIDE an actor/transfer scope stay clean.
        "blocking-transfer-in-actor-loop",
        """
        import jax

        def actor_loop(program, queue, bus, stop):
            state = None
            while not stop.is_set():
                version, params = bus.latest()
                state, batch = program(params, state)
                batch.block_until_ready()  # actor idles out the device
                queue.put(jax.device_get(batch), version)  # host round trip
        """,
        """
        import jax

        def actor_loop(program, queue, bus, stop):
            state = None
            while not stop.is_set():
                version, params = bus.latest()
                state, batch = program(params, state)
                queue.put(batch, version)  # device tree; the queue places it

        def drain(chunks):
            stacks = [c for c in chunks]
            return jax.device_get(stacks)  # learner-side amortized drain
        """,
    ),
    (
        # Same hazard one local hop deep: the transfer worker's for-loop
        # calls a same-module helper that device_puts per item. The good
        # twin keeps an IDENTICAL loop+helper under a name outside the
        # actor/transfer convention (the learner's drain loop) — the
        # rule is scoped to acting/transfer lanes, not to every loop.
        "blocking-transfer-in-actor-loop",
        """
        import jax

        def _place(item, device):
            return jax.device_put(item, device)

        def transfer_worker(items, device, out):
            for item in items:
                out.append(_place(item, device))  # upload per item
        """,
        """
        import jax

        def _place(item, device):
            return jax.device_put(item, device)

        def learner_drain(items, device, out):
            for item in items:
                out.append(_place(item, device))
        """,
    ),
    (
        "env-contract-impurity",
        """
        import numpy as np

        def step(state, velocity, params):
            noise = np.random.normal(size=velocity.shape)  # host RNG
            return state, velocity + noise
        """,
        """
        import jax, jax.numpy as jnp
        import numpy as np

        def step(state, velocity, params):
            key, k = jax.random.split(state.key)
            noise = jax.random.normal(k, velocity.shape)
            return state.replace(key=key), velocity + noise

        def make_table():
            # host RNG OUTSIDE the env contract surface: allowed
            return np.random.normal(size=(4,))
        """,
    ),
    (
        "env-contract-impurity",
        """
        _EPISODES = 0

        def reset(key, params):
            global _EPISODES  # trace-time rebind
            _EPISODES += 1
            return _EPISODES
        """,
        """
        import random
        from jax import random as jrandom

        def reset(key, params):
            # `random` here is jax.random under an alias: allowed
            return jrandom.uniform(key, (params.num_agents, 2))

        def pick_seed():
            return random.randint(0, 100)  # host code path: allowed
        """,
    ),
    (
        # Rule 24, tenancy-flavored: per-lane request counters shared
        # between a submitting caller and a background drain thread
        # (the serving/tenancy/fleet.py shape). The bad twin bumps the
        # lane's tally outside its annotated lock; the good twin holds
        # it.
        "unguarded-shared-mutation",
        """
        import threading

        class LaneCounters:
            def __init__(self, lanes):
                self._count_lock = threading.Lock()
                self.requests = dict()  # graftlock: guarded-by=_count_lock
                for mid in lanes:
                    self.requests[mid] = 0

            def start(self):
                threading.Thread(target=self._drain, daemon=True).start()

            def _drain(self):
                self.requests = {mid: 0 for mid in self.requests}
        """,
        """
        import threading

        class LaneCounters:
            def __init__(self, lanes):
                self._count_lock = threading.Lock()
                self.requests = dict()  # graftlock: guarded-by=_count_lock
                for mid in lanes:
                    self.requests[mid] = 0

            def start(self):
                threading.Thread(target=self._drain, daemon=True).start()

            def _drain(self):
                with self._count_lock:
                    self.requests = {mid: 0 for mid in self.requests}
        """,
    ),
]


@pytest.mark.parametrize(
    "rule,bad,good",
    FIXTURES,
    ids=[f"{r}-{i}" for i, (r, _, _) in enumerate(FIXTURES)],
)
def test_rule_fires_on_bad_and_not_on_good(rule, bad, good):
    assert rule in fired(bad), f"{rule} must fire on its known-bad fixture"
    assert rule not in fired(good), (
        f"{rule} must stay quiet on its known-good fixture: "
        f"{[str(v) for v in lint(good)]}"
    )


def test_every_rule_has_a_fixture():
    covered = {r for r, _, _ in FIXTURES}
    assert covered == set(rule_names())


# ---------------------------------------------------------------------------
# The package itself is clean — the acceptance gate.
# ---------------------------------------------------------------------------


def test_package_is_clean_at_default_severity():
    from marl_distributedformation_tpu.analysis import load_config

    violations = lint_paths([PACKAGE], load_config(REPO), root=REPO)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_package_scan_covers_serving():
    """The zero-violation pin must include the serving/ subsystem AND
    its fleet/ subpackage (a future exclude entry or package move
    cannot silently drop either)."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    served = [f for f in files if "serving" in f.parts]
    assert len(served) >= 6, f"serving/ missing from the lint scan: {files}"
    fleet = [f for f in served if "fleet" in f.parts]
    assert len(fleet) >= 6, f"serving/fleet/ missing from the scan: {served}"
    mesh = [f for f in served if "mesh" in f.parts]
    assert len(mesh) >= 6, (
        f"serving/mesh/ missing from the scan (rule 21's subject must "
        f"itself stay pinned at 0): {served}"
    )


def test_package_scan_covers_tenancy():
    """The zero-violation pin must include serving/tenancy/ — the
    multi-tenant lane layer mutates shared per-lane counters from
    client threads and arms coordinators per lane, exactly the shapes
    rules 24/25 police; an exclude entry or package move cannot
    silently drop it from the scan."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    tenancy = {f.name for f in files if "tenancy" in f.parts}
    assert {"directory.py", "fleet.py", "smoke.py"} <= tenancy, (
        f"serving/tenancy/ missing from the lint scan: {tenancy}"
    )


def test_package_scan_covers_elastic():
    """The zero-violation pin must include serving/elastic/ — the
    capacity controller mutates router topology from a background
    thread under the same locks the fleet's client threads take,
    exactly the cross-thread shapes the lock-discipline rules police;
    an exclude entry or package move cannot silently drop it from the
    scan."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    elastic = {f.name for f in files if "elastic" in f.parts}
    assert {"__init__.py", "controller.py"} <= elastic, (
        f"serving/elastic/ missing from the lint scan: {elastic}"
    )


def test_package_scan_covers_train_modules():
    """The zero-violation pin must include every train/ module (the
    fused-scan trainer is the hottest scan in the repo — exactly where
    callback-in-hot-loop and the donation/scan rules earn their keep)
    plus the scenario schedule the fused chunk samples from."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    train = {f.name for f in files if "train" in f.parts}
    assert {
        "trainer.py", "sweep.py", "curriculum.py", "hetero_sweep.py",
    } <= train, f"train/ modules missing from the lint scan: {train}"
    scenarios = {f.name for f in files if "scenarios" in f.parts}
    assert "schedule.py" in scenarios, (
        f"scenarios/schedule.py missing from the scan: {scenarios}"
    )


def test_package_scan_covers_analysis_engine():
    """The zero-violation pin must include the analysis package itself
    — the call-graph engine walks every other plane's locks, so its own
    source stays under the same discipline it enforces."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    analysis = {f.name for f in files if "analysis" in f.parts}
    assert {"callgraph.py", "linter.py", "graftlock.py"} <= analysis, (
        f"analysis/ engine missing from the lint scan: {analysis}"
    )


def test_package_scan_covers_envs():
    """The zero-violation pin must include the envs/ subsystem — the
    env-contract-impurity rule's subject (registered step/reset
    implementations) lives there, and a future exclude entry cannot
    silently drop it from the scan."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    envs = {f.name for f in files if "envs" in f.parts}
    assert {
        "spec.py", "registry.py", "formation.py", "pursuit.py",
    } <= envs, f"envs/ missing from the lint scan: {envs}"
    legacy = {f.name for f in files if "env" in f.parts}
    assert "formation.py" in legacy, (
        f"legacy env/ missing from the scan: {legacy}"
    )


def test_package_scan_covers_obs_instrumented_seams():
    """The zero-violation pin must include the tracing spine and the
    subsystems it instruments — rule 15 (span-in-traced-scope) only
    protects the budget-1 receipts if the files recording spans are in
    the scan."""
    from marl_distributedformation_tpu.analysis import load_config
    from marl_distributedformation_tpu.analysis.linter import iter_python_files

    files = list(iter_python_files([PACKAGE], load_config(REPO), root=REPO))
    obs = {f.name for f in files if "obs" in f.parts}
    assert {"tracer.py", "export.py", "flightrec.py"} <= obs, (
        f"obs/ missing from the lint scan: {obs}"
    )
    pipeline = {f.name for f in files if "pipeline" in f.parts}
    assert {"gate.py", "supervisor.py"} <= pipeline, (
        f"pipeline/ missing from the lint scan: {pipeline}"
    )


# ---------------------------------------------------------------------------
# Suppression + config machinery
# ---------------------------------------------------------------------------


def test_same_line_suppression():
    src = """
    import jax

    @jax.jit
    def f(x):
        print(x)  # graftlint: disable=print-in-jit
        return x
    """
    assert "print-in-jit" not in fired(src)


def test_comment_above_suppression():
    src = """
    import jax

    @jax.jit
    def f(x):
        # graftlint: disable=print-in-jit — tracing breadcrumb, deliberate
        print(x)
        return x
    """
    assert "print-in-jit" not in fired(src)


def test_file_level_suppression():
    src = """
    # graftlint: disable-file=print-in-jit
    import jax

    @jax.jit
    def f(x):
        print(x)
        return x
    """
    assert "print-in-jit" not in fired(src)


def test_suppression_is_rule_specific():
    src = """
    import jax

    @jax.jit
    def f(x):
        print(float(x))  # graftlint: disable=print-in-jit
        return x
    """
    rules = fired(src)
    assert "print-in-jit" not in rules
    assert "host-sync-in-jit" in rules, "other rules must survive"


def test_suppression_prose_cannot_name_other_rules():
    """The payload ends at the first non-rule token: prose after the
    suppressed rule may mention other rules by name without silencing
    them."""
    src = """
    import jax

    @jax.jit
    def f(x):
        print(float(x))  # graftlint: disable=print-in-jit unlike host-sync-in-jit this is fine
        return x
    """
    rules = fired(src)
    assert "print-in-jit" not in rules
    assert "host-sync-in-jit" in rules


def test_config_defaults_without_toml_parser(monkeypatch):
    """py3.10 with runtime-only deps has no TOML parser; load_config must
    degrade to all-default severities instead of crashing the CLI."""
    import builtins
    import sys

    from marl_distributedformation_tpu.analysis import load_config

    monkeypatch.delitem(sys.modules, "tomllib", raising=False)
    monkeypatch.delitem(sys.modules, "tomli", raising=False)
    real_import = builtins.__import__

    def no_toml(name, *args, **kwargs):
        if name in ("tomllib", "tomli"):
            raise ImportError(name)
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_toml)
    config = load_config(REPO)
    assert config == GraftlintConfig()


def test_severity_override_and_off():
    bad = """
    import jax

    @jax.jit
    def f(x):
        print(x)
        return x
    """
    config = config_from_dict({"severity": {"print-in-jit": "warn"}})
    vs = lint_source(textwrap.dedent(bad), "f.py", config)
    assert [v.severity for v in vs if v.rule == "print-in-jit"] == ["warn"]
    config_off = config_from_dict({"severity": {"print-in-jit": "off"}})
    assert lint_source(textwrap.dedent(bad), "f.py", config_off) == []


def test_exclude_list(tmp_path):
    (tmp_path / "skipme").mkdir()
    bad = "import jax\n\n@jax.jit\ndef f(x):\n    print(x)\n    return x\n"
    (tmp_path / "skipme" / "mod.py").write_text(bad)
    (tmp_path / "mod.py").write_text(bad)
    config = config_from_dict({"exclude": ["skipme"]})
    vs = lint_paths([tmp_path], config, root=tmp_path)
    assert {Path(v.path).parent.name for v in vs} == {tmp_path.name}


def test_pyproject_config_block_parses():
    """The repo's own [tool.graftlint] block loads through the real
    parser (a typo'd severity would otherwise only explode in CI)."""
    from marl_distributedformation_tpu.analysis import load_config

    config = load_config(REPO)
    for rule in rule_names():
        assert config.rule_severity(rule, "error") in ("error", "warn", "off")


def test_syntax_error_reported_not_raised():
    vs = lint_source("def broken(:\n", "bad.py")
    assert [v.rule for v in vs] == ["syntax-error"]


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


def test_cli_check_passes_on_package():
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "graftlint.py"), "--check"],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 error(s)" in out.stdout


def test_cli_survives_broken_tree_and_skips_jax(tmp_path):
    """The CLI is pure-AST: a syntax-broken tree must produce the
    dedicated syntax-error violation (exit 1 under --check), not an
    import traceback — and linting must never start a jax session (the
    stub-package import path in scripts/graftlint.py)."""
    (tmp_path / "broken.py").write_text("def broken(:\n")
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "graftlint.py"),
            "--check",
            str(tmp_path),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "syntax-error" in out.stdout
    assert "Traceback" not in out.stderr
    # jax stays unimported for the whole CLI run.
    probe_code = (
        "import sys, runpy\n"
        f"sys.argv = ['graftlint', {str(tmp_path / 'broken.py')!r}]\n"
        "try:\n"
        f"    runpy.run_path({str(REPO / 'scripts' / 'graftlint.py')!r},"
        " run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "print('jax-imported' if 'jax' in sys.modules else 'jax-not-imported')\n"
    )
    probe = subprocess.run(
        [sys.executable, "-c", probe_code],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert "jax-not-imported" in probe.stdout, probe.stdout + probe.stderr


def test_cli_check_fails_on_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n"
    )
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "graftlint.py"),
            "--check",
            str(bad),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "host-sync-in-jit" in out.stdout


def test_cli_sarif_output_shape(tmp_path):
    """--format sarif emits a SARIF 2.1.0 document: schema + version,
    the full rule catalogue in the driver, and per-result ruleId /
    level / physical location. A lock-ordering result's message must
    carry the complete acquisition chain."""
    (tmp_path / "cycle.py").write_text(
        textwrap.dedent(
            """
            import threading

            class Pool:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()
                    self.c_lock = threading.Lock()

                def ab(self):
                    with self.a_lock:
                        with self.b_lock:
                            pass

                def bc(self):
                    with self.b_lock:
                        with self.c_lock:
                            pass

                def ca(self):
                    with self.c_lock:
                        with self.a_lock:
                            pass
            """
        )
    )
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "graftlint.py"),
            "--format",
            "sarif",
            str(tmp_path),
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)  # stdout is ONLY the document
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graftlint"
    ids = [r["id"] for r in driver["rules"]]
    assert ids == rule_names()
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
        assert r["defaultConfiguration"]["level"] in ("error", "warning")
    results = run["results"]
    assert results, "the seeded cycle must produce at least one result"
    by_rule = {r["ruleId"]: r for r in results}
    cycle = by_rule["lock-ordering-cycle"]
    assert cycle["level"] == "error"
    assert cycle["ruleIndex"] == ids.index("lock-ordering-cycle")
    text = cycle["message"]["text"]
    # Full acquisition chain: all three edges, each with its site.
    assert text.count("holding") == 3
    for lock in ("a_lock", "b_lock", "c_lock"):
        assert lock in text
    assert "cycle.py:" in text
    loc = cycle["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("cycle.py")
    assert loc["region"]["startLine"] >= 1
    assert loc["region"]["startColumn"] >= 1
