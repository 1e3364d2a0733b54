"""Worst-case severity search: find the minimal falsifier of a checkpoint.

The robustness matrix (``matrix.py``) answers "how does this policy do at
severities someone chose ahead of time?". This module answers the harder
question the gate actually cares about: **what is the smallest severity
at which each scenario family breaks this policy?** — the minimal-severity
*falsifier*. Because every scenario knob is a traced input
(``params.py``), a whole candidate *population* of ``ScenarioParams``
evaluates in ONE vmapped compiled program: each search generation is a
single device dispatch over ``P = 1 + families x grid`` candidates on
identical initial states, with the model parameters traced too, so the
program compiles exactly once for the life of the search — across every
generation AND every same-architecture checkpoint it ever judges
(budget-1 ``RetraceGuard`` receipt, the ``matrix.MatrixProgram``
discipline).

The search itself is **grid-refine bracketing** (deterministic — the
auto-curriculum and the promotion gate both need reproducible
falsifiers): generation 0 lays a coarse severity grid over ``(0,
max_severity]`` per family; each later generation subdivides the bracket
``(lo, hi)`` between the highest severity observed SAFE below the break
and the lowest severity observed FALSIFIED, until the bracket is tighter
than ``resolution`` or the generation budget runs out. "Falsified" means
the candidate's metric drops more than ``drop_tolerance`` (relative)
below the *clean* cell — which rides as row 0 of every generation, so
the comparison point comes through the same compiled program as every
disturbed cell. Severity 0 can never be a falsifier: the disturbance
stack is bitwise-clean at zero (pinned in tests/test_scenarios.py), so
its relative drop is exactly 0.

Downstream: ``schedule.from_falsifiers`` turns a search report into an
auto-curriculum training stage, and ``pipeline.gate.PromotionGate``
(``adversarial=True``) runs this search as an extra promotion rung —
docs/adversarial.md has the full loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from marl_distributedformation_tpu.analysis.guards import (
    RetraceGuard,
    ledgered_jit,
)
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.eval import (
    policy_act_fn,
    run_episode_metrics,
)
from marl_distributedformation_tpu.scenarios.matrix import params_signature
from marl_distributedformation_tpu.scenarios.params import ScenarioParams
from marl_distributedformation_tpu.scenarios.registry import (
    ScenarioSpec,
    get_scenario,
    registered_scenarios,
)

Array = jax.Array

# Bump when the falsifier record / report shape changes
# (scripts/adversarial_search.py writes it, schedule.from_falsifiers and
# the gate verdicts consume it).
FALSIFIERS_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class AdversaryConfig:
    """What the search attacks and how hard it refines.

    ``scenarios=()`` attacks every registered family except ``clean``
    (attacking the identity stack is a no-op by construction). A family
    that survives ``max_severity`` is reported *robust*, not falsified —
    widen ``max_severity`` to keep pushing.
    """

    scenarios: Tuple[str, ...] = ()
    metric: str = "episode_return_per_agent"
    drop_tolerance: float = 0.2  # relative drop vs clean that "breaks"
    max_severity: float = 1.5
    grid: int = 6  # candidates per family per generation
    generations: int = 4
    resolution: float = 0.02  # stop refining below this bracket width
    num_formations: int = 64
    seed: int = 1234
    deterministic: bool = True

    def __post_init__(self) -> None:
        if self.grid < 1:
            raise ValueError(f"grid must be >= 1, got {self.grid}")
        if self.generations < 1:
            raise ValueError(
                f"generations must be >= 1, got {self.generations}"
            )
        if not (self.max_severity > 0.0):
            raise ValueError(
                f"max_severity must be positive, got {self.max_severity}"
            )


@dataclasses.dataclass(frozen=True)
class Falsifier:
    """One family's minimal discovered break point.

    ``params`` is the concrete knob dict at the falsifier severity
    (``ScenarioParams`` fields as host floats) — everything a training
    stage or an audit log needs to reproduce the disturbance without the
    registry.
    """

    scenario: str
    severity: float
    value: float  # the metric at the falsifier severity
    clean: float  # the same checkpoint's clean-cell metric
    drop: float  # relative drop vs clean (> drop_tolerance)
    params: Dict[str, object]

    def record(self) -> dict:
        return {
            "scenario": self.scenario,
            "severity": round(self.severity, 6),
            "value": self.value,
            "clean": self.clean,
            "drop": round(self.drop, 6),
            "params": self.params,
        }


def _relative_drop(candidate: float, baseline: float) -> float:
    """Scale-free drop of ``candidate`` below ``baseline`` (positive =
    worse) — same denomination as the promotion gate's regression checks
    (|baseline| floored at 1 so a near-zero clean return cannot turn
    noise into infinity)."""
    return (baseline - candidate) / max(abs(baseline), 1.0)


def scenario_knobs(spec: ScenarioSpec, severity: float) -> Dict[str, object]:
    """The concrete ``ScenarioParams`` knob dict of ``spec`` at
    ``severity`` (host floats; ``wind`` as a 2-list) — the portable
    falsifier payload."""
    built = spec.build(jnp.float32(severity))
    out: Dict[str, object] = {}
    for field in dataclasses.fields(ScenarioParams):
        leaf = np.asarray(getattr(built, field.name))
        out[field.name] = (
            float(leaf) if leaf.ndim == 0 else [float(v) for v in leaf]
        )
    return out


def make_population_runner(
    model,
    env_params: EnvParams,
    num_formations: int,
    deterministic: bool = True,
    max_traces: Optional[int] = 1,
) -> Tuple:
    """Build ``(run, guard)``: ``run(key, model_params, stacked_params)``
    -> per-candidate episode metrics, vmapped over a ``(P,)``-stacked
    ``ScenarioParams`` population. The key and model params broadcast, so
    every candidate rolls the SAME initial states and action-noise stream
    — cells differ only by their disturbance, exactly like the matrix.
    One jit for the whole search (``guard`` is the budget receipt)."""
    guard = RetraceGuard("adversary_population_eval", max_traces=max_traces)

    def population(key, model_params, stacked_params):
        act = policy_act_fn(model, model_params, env_params, deterministic)

        def one(sp):
            return run_episode_metrics(
                key, act, env_params, num_formations, sp
            )

        return jax.vmap(one)(stacked_params)

    run = ledgered_jit(
        population,
        guard,
        subsystem="adversary",
        program="adversary_population_eval",
    )
    return run, guard


def _stack_rows(rows: Sequence[Tuple[ScenarioSpec, float]]) -> ScenarioParams:
    """Stack per-candidate ``spec.build(severity)`` params to a leading
    ``(P,)`` axis (the vmapped program's population input). Severities
    stay host floats until ``build`` (validation without device syncs)."""
    built = [spec.build(float(sev)) for spec, sev in rows]
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *built)


class AdversarySearch:
    """The reusable falsifier-search program (``MatrixProgram``'s
    contract): construction jits nothing, the single compile happens on
    the first generation, and every later generation — for THIS
    checkpoint or any later same-architecture one — reuses it.
    ``guard.count`` is the receipt the gate records.
    """

    def __init__(
        self,
        model,
        env_params: EnvParams,
        config: AdversaryConfig = AdversaryConfig(),
        max_traces: Optional[int] = 1,
        device=None,
    ) -> None:
        self.env_params = env_params
        self.config = config
        # Slice assignment (train/sebulba): committed inputs pin the
        # population program to ``device`` so the search runs beside —
        # not on — the learner slice. None = default placement.
        self.device = device
        names = config.scenarios or tuple(
            n for n in registered_scenarios() if n != "clean"
        )
        self.specs: Tuple[ScenarioSpec, ...] = tuple(
            get_scenario(str(n)) for n in names  # fail fast, by name
        )
        if not self.specs:
            raise ValueError("adversary search needs at least one scenario")
        self._clean_spec = get_scenario("clean")
        # Fixed population: 1 clean anchor row + grid rows per family —
        # shapes never change, so neither does the compiled program.
        self.population = 1 + len(self.specs) * config.grid
        self.run, self.guard = make_population_runner(
            model,
            env_params,
            config.num_formations,
            config.deterministic,
            max_traces,
        )
        self.key = jax.random.PRNGKey(config.seed)
        if device is not None:
            self.key = jax.device_put(self.key, device)
        self._signature: Optional[Tuple] = None
        self.candidates_evaluated = 0
        self.search_seconds_total = 0.0

    @property
    def compile_count(self) -> int:
        """Traces of the shared population program so far (stays 1
        across every generation and checkpoint)."""
        return self.guard.count

    def check_params(self, params, origin: str = "<candidate>") -> None:
        """One-architecture contract, the matrix's rule: a different
        structure/shape would blow the budget-1 guard mid-search with a
        confusing retrace error — fail by name instead."""
        sig = params_signature(params)
        if self._signature is None:
            self._signature = sig
        elif sig != self._signature:
            raise ValueError(
                f"checkpoint {origin} has a different parameter "
                "structure/shape than the first candidate — the search "
                "shares one compiled population program, so all "
                "candidates must be one architecture"
            )

    # -- evaluation ------------------------------------------------------

    def _evaluate(
        self, params, rows: List[Tuple[ScenarioSpec, float]]
    ) -> np.ndarray:
        """One generation: pad ``rows`` to the fixed population with
        clean anchors, dispatch the compiled program once, return the
        config metric per row (host floats)."""
        padded = list(rows) + [
            (self._clean_spec, 0.0) for _ in range(self.population - len(rows))
        ]
        if self.device is not None:
            params = jax.device_put(params, self.device)
        out = self.run(self.key, params, _stack_rows(padded))
        metric = out.get(self.config.metric)
        if metric is None:
            raise ValueError(
                f"metric {self.config.metric!r} absent from the episode "
                f"eval output (emitted: {', '.join(sorted(out))})"
            )
        return np.asarray(jax.device_get(metric), np.float64)[: len(rows)]

    def evaluate_cells(
        self,
        params,
        cells: Sequence[Tuple[str, float]],
        origin: str = "<candidate>",
    ) -> List[float]:
        """The config metric at explicit ``(scenario, severity)`` cells —
        through the SAME compiled program (the worst-case
        comparison hook). ``len(cells)`` must fit the population."""
        self.check_params(params, origin)
        if len(cells) > self.population:
            raise ValueError(
                f"{len(cells)} cells exceed the population "
                f"({self.population}) — split into multiple calls"
            )
        rows = [
            (get_scenario(str(name)), float(sev)) for name, sev in cells
        ]
        return [float(v) for v in self._evaluate(params, rows)]

    # -- the search ------------------------------------------------------

    def _candidate_severities(
        self,
        lo: float,
        hi: Optional[float],
        done: bool,
    ) -> List[float]:
        """The next generation's probes for one family. Fresh families
        grid ``(0, max_severity]``; bracketed families subdivide
        ``(lo, hi)``; finished families re-probe their break point
        (population shape is fixed — repeats are the cheap filler)."""
        cfg = self.config
        if done:
            return [hi if hi is not None else cfg.max_severity] * cfg.grid
        if hi is None:
            return [
                cfg.max_severity * (i + 1) / cfg.grid
                for i in range(cfg.grid)
            ]
        return [
            lo + (hi - lo) * (i + 1) / (cfg.grid + 1)
            for i in range(cfg.grid)
        ]

    def search(self, params, origin: str = "<candidate>") -> dict:
        """Find the minimal-severity falsifier per scenario family.

        Host-side control flow only — the fitness values are drained to
        numpy before ANY Python comparison touches them (graftlint rule
        17's subject: a traced comparison in this loop would concretize),
        and every device round trip is one compiled population dispatch.
        Deterministic at fixed config+params. Returns the report dict
        (``falsifiers`` carry ``Falsifier.record()`` payloads).
        """
        self.check_params(params, origin)
        cfg = self.config
        t0 = time.perf_counter()
        lo: Dict[str, float] = {s.name: 0.0 for s in self.specs}
        hi: Dict[str, Optional[float]] = {s.name: None for s in self.specs}
        hi_value: Dict[str, float] = {}
        # A family is done when its bracket converged, or when a full
        # fresh grid up to max_severity found nothing to refine toward.
        done: Dict[str, bool] = {s.name: False for s in self.specs}
        clean: Optional[float] = None
        generations_run = 0
        for _ in range(cfg.generations):
            if all(done.values()):
                break
            rows: List[Tuple[ScenarioSpec, float]] = [(self._clean_spec, 0.0)]
            placements: List[Tuple[str, float]] = []
            for spec in self.specs:
                sevs = self._candidate_severities(
                    lo[spec.name], hi[spec.name], done[spec.name]
                )
                rows.extend((spec, s) for s in sevs)
                placements.extend((spec.name, s) for s in sevs)
            values = self._evaluate(params, rows)
            generations_run += 1
            self.candidates_evaluated += self.population
            if clean is None:
                clean = float(values[0])
            results: Dict[str, List[Tuple[float, float]]] = {}
            for (name, sev), value in zip(placements, values[1:]):
                results.setdefault(name, []).append((sev, float(value)))
            for spec in self.specs:
                name = spec.name
                if done[name]:
                    continue
                had_break = hi[name] is not None
                for sev, value in results[name]:
                    if _relative_drop(value, clean) > cfg.drop_tolerance:
                        if hi[name] is None or sev < hi[name]:
                            hi[name] = sev
                            hi_value[name] = value
                # Safe probes only raise the floor BELOW the break point
                # (returns are not guaranteed monotone in severity — a
                # safe pocket above the first break is not the bracket).
                for sev, value in results[name]:
                    if (
                        _relative_drop(value, clean) <= cfg.drop_tolerance
                        and sev > lo[name]
                        and (hi[name] is None or sev < hi[name])
                    ):
                        lo[name] = sev
                if hi[name] is None:
                    # A full grid up to max_severity stayed safe: the
                    # family is robust in range; re-gridding finds the
                    # same answer, so stop probing it.
                    done[name] = not had_break
                elif hi[name] - lo[name] <= cfg.resolution:
                    done[name] = True
        seconds = time.perf_counter() - t0
        self.search_seconds_total += seconds

        falsifiers: List[Falsifier] = []
        robust: List[str] = []
        for spec in self.specs:
            severity = hi[spec.name]
            if severity is None:
                robust.append(spec.name)
                continue
            value = hi_value[spec.name]
            falsifiers.append(
                Falsifier(
                    scenario=spec.name,
                    severity=float(severity),
                    value=value,
                    clean=float(clean),
                    drop=_relative_drop(value, float(clean)),
                    params=scenario_knobs(spec, float(severity)),
                )
            )
        return {
            "schema": FALSIFIERS_SCHEMA,
            "origin": str(origin),
            "metric": cfg.metric,
            "drop_tolerance": cfg.drop_tolerance,
            "max_severity": cfg.max_severity,
            "resolution": cfg.resolution,
            "scenarios": [s.name for s in self.specs],
            "clean": float(clean) if clean is not None else None,
            "falsifiers": [f.record() for f in falsifiers],
            "robust": robust,
            "generations": generations_run,
            "population": self.population,
            "candidates": generations_run * self.population,
            "num_formations": cfg.num_formations,
            "seed": cfg.seed,
            "deterministic": cfg.deterministic,
            "eval_compiles": self.compile_count,
            "search_seconds": round(seconds, 4),
        }

    # -- observability ---------------------------------------------------

    def candidates_per_sec(self) -> float:
        """Search throughput in scenario candidates evaluated per second
        (``scripts/adversarial_search.py`` reports it)."""
        if self.search_seconds_total <= 0:
            return 0.0
        return self.candidates_evaluated / self.search_seconds_total


class ContinuousAdversary:
    """Falsifier search as a CONTINUOUS lane over the live checkpoint
    stream — outside the promotion gate's latency budget.

    The gate's adversarial rung (``GateConfig.adversarial``) runs the
    search inline per candidate, which puts generations x population
    eval dispatches on the promotion critical path. This wrapper moves
    the same search off that path: it tails a trainer's checkpoint
    directory (``utils.checkpoint.latest_checkpoint`` — always the
    newest, skipping intermediates; worst-case coverage matters more
    than per-checkpoint coverage), attacks each new checkpoint with ONE
    long-lived :class:`AdversarySearch` (budget-1 compile receipt across
    every checkpoint it ever judges), and feeds discovered falsifiers
    back through ``on_schedule`` as a ``from_falsifiers`` curriculum
    stage — the train -> falsify -> train loop, decoupled from
    promotion. With a sebulba trainer the scenario seam applies the new
    schedule at the next actor dispatch with ZERO train-program
    recompiles (severity and knobs are traced inputs).

    ``device`` pins the search's compiled program to its own slice
    (train/sebulba's gate/adversary assignment) so continuous attacking
    never contends with the learner. Drive it deterministically with
    :meth:`poll_once` (tests, campaigns) or as a daemon via
    :meth:`run`/:meth:`stop`.
    """

    def __init__(
        self,
        log_dir,
        env_params: EnvParams,
        config: AdversaryConfig = AdversaryConfig(),
        device=None,
        on_schedule=None,
        feedback_rollouts: int = 50,
    ) -> None:
        from pathlib import Path

        self.log_dir = Path(log_dir)
        self.env_params = env_params
        self.config = config
        self.device = device
        self.on_schedule = on_schedule
        self.feedback_rollouts = int(feedback_rollouts)
        self.search: Optional[AdversarySearch] = None  # lazy, budget-1
        self.last_step = -1
        self.reports: List[dict] = []
        self.schedules_pushed = 0
        self.errors: List[str] = []
        self._stop = None  # threading.Event, created by run()
        self._thread = None

    def poll_once(self) -> Optional[dict]:
        """Attack the newest unseen checkpoint; None when there is
        nothing new. A bad candidate (corrupt file, architecture drift)
        is a recorded error, never a dead lane. On discovered
        falsifiers, pushes the feedback schedule through
        ``on_schedule`` (advisory: a failing callback is recorded,
        the lane keeps attacking)."""
        from marl_distributedformation_tpu.compat.policy import LoadedPolicy
        from marl_distributedformation_tpu.obs import get_registry
        from marl_distributedformation_tpu.utils.checkpoint import (
            checkpoint_step,
            latest_checkpoint,
        )

        path = latest_checkpoint(self.log_dir)
        if path is None:
            return None
        try:
            step = checkpoint_step(path)
        except ValueError:
            return None
        if step <= self.last_step:
            return None
        try:
            pol = LoadedPolicy.from_checkpoint(
                path,
                act_dim=self.env_params.act_dim,
                env_params=self.env_params,
            )
            if self.search is None:
                self.search = AdversarySearch(
                    pol.model,
                    self.env_params,
                    self.config,
                    device=self.device,
                )
            report = self.search.search(pol.params, origin=str(path))
        except Exception as e:  # noqa: BLE001 — a bad checkpoint must
            # not kill the lane; the next one may be fine.
            self.errors.append(f"{path.name}: {e!r}"[:300])
            del self.errors[:-32]
            self.last_step = step  # never re-attack a broken file
            return None
        self.last_step = step
        report["step"] = step
        self.reports.append(report)
        registry = get_registry()
        registry.counter("adversary_continuous_searches_total").inc()
        registry.gauge("adversary_continuous_falsifiers").set(
            float(len(report["falsifiers"]))
        )
        if report["falsifiers"] and self.on_schedule is not None:
            from marl_distributedformation_tpu.scenarios.schedule import (
                from_falsifiers,
            )

            try:
                self.on_schedule(
                    from_falsifiers(
                        report["falsifiers"],
                        rollouts=self.feedback_rollouts,
                    )
                )
                self.schedules_pushed += 1
            except Exception as e:  # noqa: BLE001 — feedback is advisory
                self.errors.append(f"on_schedule: {e!r}"[:300])
                del self.errors[:-32]
        return report

    # -- background lane -------------------------------------------------

    def run(self, interval_s: float = 1.0) -> "ContinuousAdversary":
        """Poll as a daemon thread every ``interval_s`` (the continuous
        mode scripts/always_learning.py wires next to a sebulba run)."""
        import threading

        if self._thread is not None:
            return self
        self._stop = threading.Event()

        def loop() -> None:
            while not self._stop.is_set():
                try:
                    self.poll_once()
                except Exception as e:  # noqa: BLE001 — keep the lane up
                    self.errors.append(repr(e)[:300])
                    del self.errors[:-32]
                self._stop.wait(interval_s)

        self._thread = threading.Thread(
            target=loop, name="continuous-adversary", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=30.0)
        self._thread = None

    def summary(self) -> dict:
        """Flat lane report (always_learning's JSON line picks it up)."""
        return {
            "adversary_searches": len(self.reports),
            "adversary_last_step": self.last_step,
            "adversary_schedules_pushed": self.schedules_pushed,
            "adversary_falsifiers_last": (
                len(self.reports[-1]["falsifiers"]) if self.reports else 0
            ),
            "adversary_compiles": (
                self.search.compile_count if self.search is not None else 0
            ),
            "adversary_errors": list(self.errors),
        }
