#!/usr/bin/env python
"""Headline benchmark: formation-env + PPO-train throughput on one chip.

Measures, inside one process and one JSON line:

- ``env_steps_per_sec`` (headline ``value``): M parallel N-agent formations
  driven by a uniform random policy inside one jitted ``lax.scan`` — the
  BASELINE.json north-star configuration (M=4096 x N=5 on one TPU core).
  The reference achieves 1,066 formation-steps/s at its default M=1000x5 on
  CPU (BASELINE.md, measured: sequential Python loop over torch simulators,
  reference vectorized_env.py:71-81); ``vs_baseline`` is the speedup over
  that number.
- ``train_env_steps_per_sec``: the FULL PPO training iteration
  (rollout + GAE + minibatch-epoch update — the ``Trainer._iteration`` XLA
  program), in formation-steps/s. This is the workload the framework exists
  for, not just env stepping.
- ``knn_env_steps_per_sec``: the large-swarm variant (N=100 agents, k-NN
  observation graph, BASELINE.json config 4).
- ``knn_big_env_steps_per_sec``: the N=1024 swarm past the fused kernel's
  VMEM cliff (chunked-streaming kernel on TPU, XLA elsewhere; the
  ``knn_big_impl`` field records which ran).
- ``scenario_env_steps_per_sec``: env stepping through the 3-layer
  "storm" disturbance stack (scenarios/) — the scenario engine's wrapper
  overhead vs the clean headline (``scenario_overhead_pct``).
- ``env_steps_per_sec_formation`` / ``env_steps_per_sec_pursuit_evasion``:
  the registered-env ladder (envs/) — every env in the registry timed
  through the same random-policy chunk via params-type dispatch, plus
  ``obstacle_overhead_pct``: the obstacle_field occlusion layer
  (layout-driven neighbor masking) vs the clean step on the same
  4-obstacle params.
- ``train_env_steps_per_sec_fused_scan``: the Anakin fused-scan trainer
  (``TrainConfig.fused_chunk``): K full PPO iterations per ``lax.scan``
  dispatch, best rate over the chunk ladder {1, 8, 32}, with the
  compile-once RetraceGuard receipts and ``dispatch_overhead_pct`` (the
  host loop's per-iteration dispatch/drain cost vs the fused program).
- ``sweep_env_steps_per_sec_fused_scan``: the Anakin POPULATION sweep
  (``SweepTrainer`` + ``fused_chunk``): K independent PPO runs advanced
  by one fused-scan program, rate counted across all members, vs the
  host-loop sweep at matched K/M (``sweep_env_steps_per_sec_host_loop``,
  ``sweep_dispatch_overhead_pct``) with per-rung compile-once receipts.
- ``serving_requests_per_sec_fleet`` / ``serving_fleet_p95_ms``: the
  serving-side number — a 2-replica fleet (serving/fleet/) driven by the
  mixed-size smoke storm on a forced 2-device CPU, measured in a
  subprocess (the multi-device CPU flag must land before backend init).
- ``promotion_latency_s_p50``/``p95`` + ``gate_eval_steps_per_sec``: the
  always-learning pipeline (pipeline/, scripts/always_learning.py) run
  end to end — trainer streaming checkpoints through the promotion gate
  into a 2-replica fleet; latency is train-step -> served ``model_step``
  wall time, with the gate's one-compile receipt
  (``pipeline_gate_compiles``) alongside.
- ``serving_req_per_sec_at_p95_slo``: the capacity number — max
  sustained OPEN-loop request rate holding a p95 latency target
  (serving/loadgen.py bisection) on the full sharded+bf16 fleet, with
  ``serving_sharded_512_p95_ms`` vs ``serving_replicated_512_p95_ms``
  (same trace, with/without the mesh-backed big-rung slice) and
  ``serving_bf16_speedup_pct`` beside it.
- ``telemetry_overhead_pct``: the live-metrics plane's cost — the
  phase-5 fused training loop re-timed through the real instrumented
  drain seam with the MetricsRegistry enabled vs disabled (interleaved
  passes, same methodology as ``tracing_overhead_pct``), with
  ``sentinel_checks_per_sec`` (RegressionSentinel poll cost vs the
  newest committed BENCH record) beside it.
- ``adversarial_candidates_per_sec``: the falsifier-search throughput
  (scenarios/adversary.py — one vmapped compiled eval per generation,
  ``adversarial_search_compiles`` == 1 across all generations and both
  trained policies) and ``worst_case_return_gap_pct``: the
  auto-curriculum payoff — curriculum-trained vs clean-trained return
  at the discovered worst cases, equal training steps.
- ``chaos_mttr_s`` / ``chaos_invariant_violations`` /
  ``fault_plane_overhead_pct``: the chaos plane (chaos/,
  scripts/chaos_storm.py) — one seeded fault campaign through the
  whole trainer -> gate -> fleet loop; MTTR is worst kill -> first
  served recovery, violations MUST be 0, and the disabled plane's
  per-request cost is ~0 (one attribute read per injection point).
- ``ledger_overhead_pct`` / ``ledger_program_count`` /
  ``ledger_compile_seconds_total``: the program ledger (obs/ledger.py)
  — the fused loop re-timed with per-dispatch ledger recording on vs
  off (interleaved, same methodology as phases 8/11), plus the census
  headlines off the whole bench run's process-global ledger: how many
  compiled executables registered and their attributed backend-compile
  wall. The census itself is what a chip window commits beside this
  record (``check_bench_record.py --census``).
- ``mesh_req_per_sec`` / ``mesh_global_swap_latency_s_p50``/``_p95`` /
  ``mesh_failover_lost_requests``: the cross-host tier
  (serving/mesh/, docs/mesh.md) — a loopback 2-host mesh (real host
  SUBPROCESSES behind the MetaRouter) hammered by client threads
  while the coordinator drives global barrier swaps and one host is
  killed with a real SIGKILL mid-load. Lost requests MUST be 0, step
  monotonicity must hold across hosts (``mesh_step_violations`` == 0),
  and every surviving host's compile receipts stay at 1
  (``mesh_host_compile_receipts_max``).
- ``health_overhead_pct`` / ``recovery_mttr_s`` /
  ``train_divergence_events``: the self-healing train lane
  (train/recovery.py, docs/recovery.md) — the fused loop re-timed with
  the in-program health word + skip guard ON vs OFF (interleaved,
  phase-11 methodology; the bar is <= 5%), plus a seeded NaN carry
  bomb through a live fused run with the recovery ladder armed:
  detection-at-drain -> rollback wall clock from recovery.jsonl, and
  the ladder's sustained-breach count (>= 1 or the detector is
  broken).
- ``graftlint_wall_s``: one full ``scripts/graftlint.py --check`` pass
  over the package (pure-AST, subprocess — the exact CI invocation).
  The call-graph engine rebuilds its whole-repo graph from a cold
  process, so this wall is the worst-case lint cost a pre-commit hook
  pays; check_bench_record.py holds it under a ceiling so the
  whole-package analyses (lock-ordering cycles, guarded-write DFS)
  cannot quietly go super-linear as the repo grows.
- ``sebulba_env_steps_per_sec`` / ``sebulba_learner_steps_per_sec`` /
  ``transfer_queue_occupancy_p95`` / ``param_staleness_p95_updates`` /
  ``gate_eval_p50_under_load_s``: the sebulba lane (train/sebulba/,
  docs/sebulba.md) — one pipelined actor/learner run with the bounded
  TransferQueue between the slices, per-slice budget-1 compile
  receipts (``sebulba_actor_compiles`` / ``sebulba_learner_compiles``
  MUST be 1), and the promotion gate evaluating live checkpoints from
  its OWN slice while the learner is saturated (steady-state eval
  wall, post-compile).

Phases skipped via
  ``BENCH_SKIP_*`` env vars record the explicit ``"skipped"`` sentinel
  in their rate fields plus a ``phases_skipped`` list, so "not run"
  never reads as "regressed to absent".

The device is named, never assumed:

- the first line says which platform, device kind and device count jax
  resolved, and the JSON carries them; without a TPU the bench exits
  non-zero before measuring anything — ``BENCH_FORCE_CPU=1`` asks for the
  CPU by name (tiny shapes, for tests of the script itself), and a CPU
  number is then recorded under ``platform: "cpu"``;
- every phase checks a global deadline (``BENCH_BUDGET_S``, default 600s);
  a phase that raises is named in ``phases_failed`` and makes the exit
  code non-zero after the JSON line prints (the other phases still run —
  one broken phase should not cost the rest of a chip call);
- the five serving-family phases (serving, pipeline, obs, serving-slo,
  elastic) run in CHILD processes pinned to ``JAX_PLATFORMS=cpu`` with two
  forced host devices, started after this process has taken the chip
  (the mesh phase's loopback hosts likewise): CPU numbers under serving
  names, and the bench's process-per-chip problem (ROADMAP S1).

Env-var knobs: BENCH_M, BENCH_N, BENCH_CHUNK, BENCH_TRAIN_M, BENCH_KNN_M,
BENCH_KNN_BIG_M, BENCH_KNN_BIG_N, BENCH_BUDGET_S,
BENCH_FUSED_CHUNKS (default "1,8,32"; empty disables the fused phase),
BENCH_SWEEP_CHUNKS (default "1,8"; empty disables the fused-sweep
rungs), BENCH_SWEEP_SEEDS, BENCH_SWEEP_M, BENCH_SWEEP_REPEATS
(interleaved best-of passes per rung, default 5), BENCH_SKIP_SWEEP=1,
BENCH_FORCE_CPU=1, BENCH_SKIP_TRAIN=1, BENCH_SKIP_KNN=1,
BENCH_SKIP_KNN_BIG=1, BENCH_SKIP_SCENARIO=1, BENCH_SKIP_ENVS=1,
BENCH_ENVS_M, BENCH_SKIP_SERVING=1,
BENCH_SERVING_DURATION_S, BENCH_SKIP_PIPELINE=1, BENCH_PIPELINE_M,
BENCH_PIPELINE_GATE_M, BENCH_PIPELINE_BUDGET_S, BENCH_SLO_DURATION_S,
BENCH_SLO_P95_MS, BENCH_SKIP_ADVERSARIAL=1, BENCH_ADV_M,
BENCH_ADV_ITERS, BENCH_ADV_EVAL_M, BENCH_TELEMETRY_CHUNK,
BENCH_TELEMETRY_PASSES, BENCH_SENTINEL_CHECKS, BENCH_SKIP_CHAOS=1,
BENCH_CHAOS_SEED, BENCH_CHAOS_FAULTS, BENCH_LEDGER_CHUNK,
BENCH_LEDGER_PASSES (the ledger phase shares BENCH_SKIP_TRAIN),
BENCH_SKIP_MESH=1, BENCH_MESH_HOSTS, BENCH_MESH_DURATION_S,
BENCH_MESH_SWAPS, BENCH_SKIP_LINT=1, BENCH_LINT_TIMEOUT_S,
BENCH_SKIP_SEBULBA=1, BENCH_SEBULBA_M, BENCH_SEBULBA_ITERS,
BENCH_SEBULBA_CHUNK.

Prints exactly one JSON line with at least:
    {"metric": ..., "value": N, "unit": "env-steps/s", "vs_baseline": N}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

REFERENCE_FORMATION_STEPS_PER_SEC = 1066.0  # BASELINE.md, M=1000 x N=5, CPU

# Honest denominator for the TRAIN metric: the reference's *full* SB3
# training loop, not just env stepping — estimated by measuring its three
# components with the same torch-CPU stack (env loop 1.07 vec-steps/s from
# BASELINE.md + measured MlpPolicy inference + measured minibatch
# fwd/bwd/Adam x 7810 per iteration at SB3 defaults). Method + raw numbers:
# scripts/estimate_reference_train.py, docs/reference_train_estimate.md.
REFERENCE_TRAIN_FORMATION_STEPS_PER_SEC = 255.2


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


# Explicit not-run marker for env-var-skipped phases. Before this, a
# BENCH_SKIP_SERVING=1 run simply lacked the serving fields —
# indistinguishable from a run where the phase silently regressed to
# absent. The sentinel value lands IN the rate fields (consumers must
# treat it as "not a number, not missing") and the skipped phase names
# accumulate in ``phases_skipped``.
SKIPPED = "skipped"


def _mark_skipped(result: dict, phase: str, fields) -> None:
    for f in fields:
        result[f] = SKIPPED
    result.setdefault("phases_skipped", []).append(phase)


def _num(rec: dict, key: str, default: float = 0.0) -> float:
    """A record field as a float, treating the ``"skipped"`` sentinel
    (and any other non-number) as absent."""
    try:
        return float(rec.get(key, default))
    except (TypeError, ValueError):
        return default


M = _env_int("BENCH_M", 4096)  # parallel formations (north-star config)
N = _env_int("BENCH_N", 5)  # agents per formation (default cfg)
CHUNK = _env_int("BENCH_CHUNK", 1024)  # env steps per jitted scan
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 600))
MIN_TIMED_S = 3.0  # keep timing until a phase has at least this much signal


def make_runner(params, m: int, chunk: int):
    """Jitted random-policy env-stepping chunk: ``chunk`` vec-steps of ``m``
    formations per call (amortizes host dispatch)."""
    import jax

    from marl_distributedformation_tpu.envs import spec_for_params

    # Registered-env dispatch (envs/): formation params resolve to the
    # legacy step_batch verbatim, PursuitParams to the pursuit step — the
    # same runner times every registered env.
    step_batch = spec_for_params(params).step_batch

    @jax.jit
    def run_chunk(state, key):
        def body(carry, _):
            state, key = carry
            key, k_act = jax.random.split(key)
            # Uniform random policy in [-1, 1], scaled like the adapter
            # (reference vectorized_env.py:69-70) — matches how BASELINE.md
            # measured the reference (env stepping only, no policy inference).
            actions = jax.random.uniform(
                k_act, (m, params.num_agents, 2), minval=-1.0, maxval=1.0
            )
            state, tr = step_batch(state, params.max_speed * actions, params)
            return (state, key), tr.reward.mean()

        (state, key), rewards = jax.lax.scan(
            body, (state, key), None, length=chunk
        )
        return state, key, rewards.mean()

    return run_chunk


def make_scenario_runner(params, m: int, chunk: int, sp):
    """Scenario-stacked twin of ``make_runner``: the same random-policy
    chunk through ``scenarios.scenario_step_batch`` with the disturbance
    params as a traced argument (measures the wrapper's true overhead —
    every layer's math is in the program, magnitudes are data)."""
    import jax

    from marl_distributedformation_tpu.scenarios import scenario_step_batch

    @jax.jit
    def run_chunk(state, key, sp):
        def body(carry, _):
            state, key = carry
            key, k_act = jax.random.split(key)
            actions = jax.random.uniform(
                k_act, (m, params.num_agents, 2), minval=-1.0, maxval=1.0
            )
            state, tr = scenario_step_batch(
                state, params.max_speed * actions, sp, params
            )
            return (state, key), tr.reward.mean()

        (state, key), rewards = jax.lax.scan(
            body, (state, key), None, length=chunk
        )
        return state, key, rewards.mean()

    def run(state, key):
        return run_chunk(state, key, sp)

    return run


def _time_env_phase(
    params, m: int, chunk: int, deadline: float, scenario=None
) -> float:
    """Adaptive timing: warm up (compile + 1 exec), then run timed chunks
    until MIN_TIMED_S of signal or the deadline. Returns formation-steps/s.
    ``scenario`` (ScenarioParams) times the disturbance-stacked step."""
    import jax

    from marl_distributedformation_tpu.envs import spec_for_params

    state = spec_for_params(params).reset_batch(jax.random.PRNGKey(0), params, m)
    if scenario is None:
        run_chunk = make_runner(params, m, chunk)
    else:
        run_chunk = make_scenario_runner(params, m, chunk, scenario)

    state, key, r = run_chunk(state, jax.random.PRNGKey(1))
    float(r)  # hard host sync: the value itself reaches the host

    repeats = 0
    t0 = time.perf_counter()
    while True:
        state, key, r = run_chunk(state, key)
        float(r)
        repeats += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= MIN_TIMED_S or time.time() > deadline or repeats >= 64:
            break
    return m * chunk * repeats / elapsed


def _time_train_phase(
    n_agents: int, m: int, deadline: float, ppo=None, iters_per_dispatch=1
):
    """Time the full jitted PPO iteration (rollout + GAE + update) —
    ``Trainer._iteration``. ``iters_per_dispatch > 1`` times the scan-fused
    multi-iteration program (TrainConfig.iters_per_dispatch). Returns
    (train_env_steps_per_sec, iters_per_sec, n_steps)."""
    from marl_distributedformation_tpu.algo import PPOConfig
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    ppo = ppo or PPOConfig()
    trainer = Trainer(
        EnvParams(num_agents=n_agents),
        ppo=ppo,
        config=TrainConfig(
            num_formations=m, checkpoint=False, use_wandb=False,
            name="bench", iters_per_dispatch=iters_per_dispatch,
        ),
    )
    # Warm up TWICE: the first execution's donated outputs adopt the
    # compiled program's shardings, which can retrace the second call —
    # timing after one warmup would include that compile.
    for _ in range(2):
        metrics = trainer.run_iteration()
        float(metrics["loss"])

    # Sync once per BURST of iterations, not per iteration: a host sync
    # stalls the dispatch pipeline, which at tuned-config speeds would be
    # a material fraction of every iteration. XLA executions on
    # one device are serialized, so syncing the last iteration's metrics
    # times the whole burst; the burst is small enough that the dispatch
    # queue stays bounded.
    burst = 8
    iters = 0
    t0 = time.perf_counter()
    while True:
        for _ in range(burst):
            metrics = trainer.run_iteration()
            iters += 1
            if time.time() > deadline:  # pure wall-clock, no host sync —
                break  # keep deadline responsiveness per-iteration
        float(metrics["loss"])  # host sync for the whole burst
        elapsed = time.perf_counter() - t0
        if elapsed >= MIN_TIMED_S or time.time() > deadline or iters >= 256:
            break
    iters *= iters_per_dispatch  # each dispatch ran this many iterations
    rate = ppo.n_steps * m * iters / elapsed
    return rate, iters / elapsed, ppo.n_steps


def _time_fused_phase(n_agents: int, m: int, deadline: float, ppo, chunk: int):
    """Time the Anakin fused-scan program (``TrainConfig.fused_chunk``):
    ``chunk`` full PPO iterations per ``lax.scan`` dispatch, per-iteration
    metrics stacked on-device. Returns
    ``(train_env_steps_per_sec, iters_per_sec, compile_count)`` —
    ``compile_count`` is the RetraceGuard receipt (the fused program must
    compile exactly once per config)."""
    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import TrainConfig, Trainer

    trainer = Trainer(
        EnvParams(num_agents=n_agents),
        ppo=ppo,
        config=TrainConfig(
            num_formations=m, checkpoint=False, use_wandb=False,
            name="bench_fused", fused_chunk=chunk,
        ),
    )
    # Warm up twice, same rationale as _time_train_phase (donated outputs
    # adopting the program's shardings can retrace the second call). A
    # large chunk's warmup is a whole compile + 2*K iterations, so check
    # the deadline between dispatches — a blown budget degrades to a
    # short timing window.
    for _ in range(2):
        stacked = trainer.run_chunk()
        float(stacked["loss"][-1])
        if time.time() > deadline:
            break

    # Keep >= 2 dispatches in flight between host syncs so the queue
    # pipelines like the real Anakin loop (drain overlapped with the
    # next chunk) — a sync after every dispatch would serialize the
    # mode whose point is not serializing.
    burst = max(8 // chunk, 2)
    dispatches = 0
    t0 = time.perf_counter()
    while True:
        for _ in range(burst):
            stacked = trainer.run_chunk()
            dispatches += 1
            if time.time() > deadline:
                break
        float(stacked["loss"][-1])  # host sync for the whole burst
        elapsed = time.perf_counter() - t0
        if (
            elapsed >= MIN_TIMED_S
            or time.time() > deadline
            or dispatches * chunk >= 256
        ):
            break
    iters = dispatches * chunk
    rate = trainer.ppo.n_steps * m * iters / elapsed
    return rate, iters / elapsed, trainer.retrace_guard.count


def _make_sweep_timer(
    n_agents: int, m: int, num_seeds: int, ppo, fused_chunk: int = 0
):
    """Build + warm a K-member population sweep (``SweepTrainer``) and
    return ``(run_timed, trainer)``: ``run_timed(deadline)`` times the
    already-compiled program for one pass and returns
    ``(population_env_steps_per_sec, iters_per_sec)``. One dispatch
    advances every member one iteration (host loop, ``fused_chunk=0``)
    or ``fused_chunk`` iterations (Anakin fused-scan population mode);
    rates count formation-steps across ALL members. Splitting
    construction from timing lets the sweep phase interleave repeated
    passes over every rung — on a contended host one long pass per
    config confounds the fused-vs-host comparison with load drift, and
    this comparison is the phase's whole point."""
    import jax

    from marl_distributedformation_tpu.env import EnvParams
    from marl_distributedformation_tpu.train import SweepTrainer, TrainConfig

    trainer = SweepTrainer(
        EnvParams(num_agents=n_agents),
        ppo=ppo,
        config=TrainConfig(
            num_formations=m, checkpoint=False, use_wandb=False,
            name="bench_sweep", fused_chunk=fused_chunk,
        ),
        num_seeds=num_seeds,
    )
    step = trainer.run_chunk if fused_chunk else trainer.run_iteration
    iters_per_dispatch = fused_chunk or 1
    # Warm up twice (donated outputs adopting the program's shardings can
    # retrace the second call — the _time_train_phase rationale).
    for _ in range(2):
        jax.block_until_ready(step())

    def run_timed(deadline: float):
        # Sync once per burst of >= 2 dispatches so the fused mode
        # pipelines like the real driver (drain overlapped with the
        # next chunk).
        burst = max(8 // iters_per_dispatch, 2)
        dispatches = 0
        t0 = time.perf_counter()
        while True:
            for _ in range(burst):
                metrics = step()
                dispatches += 1
                if time.time() > deadline:
                    break
            jax.block_until_ready(metrics)  # host sync for the burst
            elapsed = time.perf_counter() - t0
            if (
                elapsed >= MIN_TIMED_S
                or time.time() > deadline
                or dispatches * iters_per_dispatch >= 256
            ):
                break
        iters = dispatches * iters_per_dispatch
        rate = trainer.ppo.n_steps * m * num_seeds * iters / elapsed
        return rate, iters / elapsed

    return run_timed, trainer


def main() -> None:
    deadline = time.time() + BUDGET_S
    result = {
        "metric": f"env_steps_per_sec_{M}x{N}_single_chip",
        "value": 0.0,
        "unit": "env-steps/s",
        "vs_baseline": 0.0,
    }
    notes = []
    failed = []

    def phase_failed(phase: str, e: BaseException) -> None:
        """A phase that raised: named in the record, traceback on stderr,
        exit code non-zero at the end — the remaining phases still run."""
        failed.append(phase)
        notes.append(f"{phase} phase failed: {e!r}"[:200])
        traceback.print_exc(file=sys.stderr)

    from marl_distributedformation_tpu.utils import announce_device

    force_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    if force_cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    stamp = announce_device("bench", file=sys.stderr)
    platform = stamp["platform"]
    if platform != "tpu" and not force_cpu:
        print(
            f"[bench] no TPU (jax resolved {platform!r}): nothing measured. "
            "BENCH_FORCE_CPU=1 asks for the CPU by name.",
            file=sys.stderr,
        )
        sys.exit(2)
    on_accel = platform == "tpu"
    result.update(stamp)
    try:
        from marl_distributedformation_tpu.env import EnvParams

        # Phase 1 — headline: random-policy env stepping, north-star shape.
        rate = _time_env_phase(
            EnvParams(num_agents=N), M, CHUNK, deadline
        )
        result["value"] = round(rate, 1)
        result["vs_baseline"] = round(
            rate / REFERENCE_FORMATION_STEPS_PER_SEC, 2
        )
        result["agent_steps_per_sec"] = round(rate * N, 1)
        print(
            f"[bench] env: {rate:,.0f} formation-steps/s on {platform}",
            file=sys.stderr,
        )

        # Phase 1b — headroom: same env at 4x the formations. The
        # north-star M=4096 batch is small enough that a per-scan-step
        # latency floor (RNG chain, tiny fused kernels) can dominate; if
        # stepping is latency-bound rather than compute-bound, the
        # bigger batch raises throughput nearly for free and this field
        # records how far the single-chip ceiling actually sits above
        # the headline. Accelerator-only (on one vCPU it just splits the
        # same FLOPs) and skippable via BENCH_SKIP_ENV_MAX=1.
        if (
            on_accel
            and os.environ.get("BENCH_SKIP_ENV_MAX") != "1"
            and time.time() < deadline - 30
        ):
            try:
                m_max = _env_int("BENCH_ENV_MAX_M", 4 * M)
                rate_max = _time_env_phase(
                    EnvParams(num_agents=N), m_max, CHUNK, deadline
                )
                result["env_max_steps_per_sec"] = round(rate_max, 1)
                result["env_max_m"] = m_max
                print(
                    f"[bench] env-max (M={m_max}): {rate_max:,.0f} "
                    "formation-steps/s",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("env-max", e)

        # Phase 1c — scenario engine overhead: the same env stepping
        # through the 3-layer "storm" disturbance stack (wind + actuator
        # noise + sensor noise, scenarios/) at severity 1. The wrapper
        # keeps every layer's math in the compiled program with
        # magnitudes as traced data, so this rate vs the headline is the
        # full price of scenario-readiness — recorded so the perf
        # trajectory catches a regression in the stack.
        if (
            os.environ.get("BENCH_SKIP_SCENARIO") != "1"
            and time.time() < deadline - 30
        ):
            try:
                import jax.numpy as jnp

                from marl_distributedformation_tpu.scenarios import (
                    broadcast_params,
                    get_scenario,
                )

                storm = broadcast_params(
                    get_scenario("storm").build(jnp.float32(1.0)), M
                )
                rate_scen = _time_env_phase(
                    EnvParams(num_agents=N), M, CHUNK, deadline,
                    scenario=storm,
                )
                result["scenario_env_steps_per_sec"] = round(rate_scen, 1)
                result["scenario_stack"] = "storm@1.0"
                if rate:
                    result["scenario_overhead_pct"] = round(
                        max(0.0, (1.0 - rate_scen / rate) * 100.0), 1
                    )
                print(
                    f"[bench] scenario (storm, 3 layers): "
                    f"{rate_scen:,.0f} formation-steps/s "
                    f"({result.get('scenario_overhead_pct', 0.0):.1f}% "
                    "overhead vs clean)",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("scenario", e)

        # Phase 1d — registered-env ladder (envs/, docs/environments.md):
        # the SAME random-policy chunk through every registered
        # environment at matched M/N/chunk, via the registry's params-type
        # dispatch (spec_for_params) — the formation rate here re-times
        # the headline path through the registry (a materially lower
        # number than phase 1 would mean the indirection itself costs,
        # which it must not: the dispatch resolves at trace time), and
        # the pursuit rate is the second env's first perf number. Plus
        # obstacle_overhead_pct: the obstacle_field occlusion layer
        # (layout-driven neighbor masking, scenarios/layers.py) vs the
        # clean step on the SAME num_obstacles>0 params.
        if os.environ.get("BENCH_SKIP_ENVS") == "1":
            _mark_skipped(
                result,
                "envs",
                (
                    "env_steps_per_sec_formation",
                    "env_steps_per_sec_pursuit_evasion",
                    "obstacle_overhead_pct",
                ),
            )
        elif time.time() < deadline - 30:
            try:
                from marl_distributedformation_tpu.envs import (
                    get_env,
                    registered_envs,
                )
                from marl_distributedformation_tpu.scenarios import (
                    broadcast_params,
                    scenario_params_for,
                )

                envs_m = _env_int("BENCH_ENVS_M", M if on_accel else 256)
                envs_chunk = max(CHUNK // 8, 16)
                for env_name in registered_envs():
                    spec = get_env(env_name)
                    env_rate = _time_env_phase(
                        spec.default_params(num_agents=N),
                        envs_m, envs_chunk, deadline,
                    )
                    result[f"env_steps_per_sec_{env_name}"] = round(
                        env_rate, 1
                    )
                    print(
                        f"[bench] envs ({env_name}): {env_rate:,.0f} "
                        "formation-steps/s",
                        file=sys.stderr,
                    )
                result["envs_m"] = envs_m
                # Obstacle-layer overhead: clean vs obstacle_field@1.0
                # (80 px occlusion masking the layout-declared neighbor
                # blocks) on the same 4-obstacle formation params.
                obst_params = EnvParams(num_agents=N, num_obstacles=4)
                clean_rate = _time_env_phase(
                    obst_params, envs_m, envs_chunk, deadline
                )
                occl = broadcast_params(
                    scenario_params_for("obstacle_field", 1.0), envs_m
                )
                occl_rate = _time_env_phase(
                    obst_params, envs_m, envs_chunk, deadline, scenario=occl
                )
                if clean_rate:
                    result["obstacle_overhead_pct"] = round(
                        max(0.0, (1.0 - occl_rate / clean_rate) * 100.0), 1
                    )
                result["obstacle_stack"] = "obstacle_field@1.0 (K=4)"
                print(
                    f"[bench] obstacle_field occlusion: {occl_rate:,.0f} "
                    f"vs clean {clean_rate:,.0f} formation-steps/s "
                    f"({result.get('obstacle_overhead_pct', 0.0):.1f}% "
                    "overhead)",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("envs", e)
        else:
            notes.append("envs phase skipped: deadline")

        # Phase 2 — full PPO training iteration, at BOTH hyperparameter
        # points: the reference-parity config (SB3 batch_size=64 — tiny
        # sequential minibatches, the reference's own structure) and the
        # TPU-tuned preset (the REAL utils/config.py PRESETS["tpu"] batch —
        # same data, same epochs). vs_baseline for both uses the
        # measured full-SB3-loop estimate, not env-stepping-only (see
        # REFERENCE_TRAIN_FORMATION_STEPS_PER_SEC).
        if os.environ.get("BENCH_SKIP_TRAIN") != "1":
            if time.time() < deadline - 30:
                try:
                    from marl_distributedformation_tpu.algo import PPOConfig
                    from marl_distributedformation_tpu.utils.config import (
                        PRESETS,
                    )

                    tuned_batch = PRESETS["tpu"]["batch_size"]
                    train_m = _env_int(
                        "BENCH_TRAIN_M", M if on_accel else 256
                    )
                    t_rate, t_iters, n_steps = _time_train_phase(
                        N, train_m, deadline
                    )
                    result["train_env_steps_per_sec"] = round(t_rate, 1)
                    result["train_iters_per_sec"] = round(t_iters, 2)
                    result["train_m"] = train_m
                    result["train_n_steps"] = n_steps
                    result["train_vs_baseline"] = round(
                        t_rate / REFERENCE_TRAIN_FORMATION_STEPS_PER_SEC, 2
                    )
                    result["train_baseline_denominator"] = (
                        "full SB3 loop estimate "
                        f"{REFERENCE_TRAIN_FORMATION_STEPS_PER_SEC} "
                        "formation-steps/s (docs/reference_train_estimate.md)"
                    )
                    print(
                        f"[bench] train: {t_rate:,.0f} formation-steps/s "
                        f"({t_iters:.2f} iters/s at M={train_m})",
                        file=sys.stderr,
                    )
                    tuned_rate, tuned_iters, _ = _time_train_phase(
                        N, train_m, deadline,
                        ppo=PPOConfig(batch_size=tuned_batch),
                    )
                    result["train_env_steps_per_sec_tuned"] = round(
                        tuned_rate, 1
                    )
                    result["train_iters_per_sec_tuned"] = round(
                        tuned_iters, 2
                    )
                    result["train_tuned_batch_size"] = tuned_batch
                    result["train_tuned_vs_baseline"] = round(
                        tuned_rate / REFERENCE_TRAIN_FORMATION_STEPS_PER_SEC,
                        2,
                    )
                    print(
                        f"[bench] train (preset=tpu, batch={tuned_batch}): "
                        f"{tuned_rate:,.0f} formation-steps/s "
                        f"({tuned_iters:.2f} iters/s)",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("train", e)
            else:
                notes.append("train phase skipped: deadline")

        def run_knn_phase(prefix: str, n: int, default_m: int, chunk: int):
            """Time one knn env-stepping variant; record rate + which
            neighbor-search impl auto-dispatch resolves at this shape.
            Failures degrade to a note, like every other phase."""
            try:
                key = prefix.replace("-", "_")
                m = _env_int(f"BENCH_{key.upper()}_M", default_m)
                params = EnvParams(num_agents=n, obs_mode="knn", knn_k=4)
                rate = _time_env_phase(params, m, chunk, deadline)

                import jax.numpy as jnp

                from marl_distributedformation_tpu.ops.knn import (
                    _resolve_auto_impl,
                )

                result[f"{key}_env_steps_per_sec"] = round(rate, 1)
                result[f"{key}_m"] = m
                result[f"{key}_n"] = n
                result[f"{key}_impl"] = _resolve_auto_impl(
                    jnp.zeros((m, n, 2))
                )
                print(
                    f"[bench] {prefix} (N={n}): {rate:,.0f} "
                    f"formation-steps/s ({result[f'{key}_impl']})",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed(prefix, e)

        # Phase 3 — large-swarm knn variant (BASELINE.json config 4).
        # Kernel-vs-xla parity is measured, not replayed: chip_smoke.py
        # runs tests/tpu_compiled_parity.py's three legs on the chip.
        if os.environ.get("BENCH_SKIP_KNN") != "1":
            if time.time() < deadline - 30:
                run_knn_phase(
                    "knn", 100, 4096 if on_accel else 256,
                    max(CHUNK // 8, 16),
                )
            else:
                notes.append("knn phase skipped: deadline")

        # Phase 4 — swarm past the fused kernel's VMEM cliff (N=1024):
        # the chunked-streaming kernel (ops/knn_pallas.py
        # knn_batch_pallas_big) on TPU, XLA elsewhere.
        if os.environ.get("BENCH_SKIP_KNN_BIG") != "1":
            if time.time() < deadline - 30:
                run_knn_phase(
                    "knn-big",
                    _env_int("BENCH_KNN_BIG_N", 1024),
                    512 if on_accel else 32,
                    max(CHUNK // 32, 8),
                )
            else:
                notes.append("knn-big phase skipped: deadline")

        # Phase 5 — Anakin fused-scan training (TrainConfig.fused_chunk,
        # docs/training.md): the WHOLE rollout+update loop inside one
        # lax.scan program, K iterations per dispatch, per-iteration
        # metrics stacked on-device and drained once per chunk. Replaces
        # the retired iters_per_dispatch burst phase — at the tuned
        # config the burst never paid for itself (BENCH_r05:
        # iters_per_dispatch=2 measured 11,147 vs 11,476 plain on CPU;
        # see docs/training.md "Why the burst path lost"). Records the
        # best rate over the chunk ladder, the per-chunk rates, the
        # compile-once RetraceGuard receipts, and the dispatch overhead
        # the host loop pays relative to the fused program. Runs LAST
        # among train phases: its scan compiles are the most expensive
        # and must never starve the long-standing knn fields.
        if os.environ.get("BENCH_SKIP_TRAIN") != "1":
            try:
                chunks = [
                    int(c)
                    for c in os.environ.get(
                        "BENCH_FUSED_CHUNKS", "1,8,32"
                    ).split(",")
                    if c.strip() and int(c) > 0
                ]
            except ValueError as e:
                # A malformed knob degrades like any phase failure — the
                # JSON line (and every already-measured field) still
                # prints.
                notes.append(f"bad BENCH_FUSED_CHUNKS: {e!r}"[:200])
                chunks = []
            if chunks and time.time() < deadline - 30:
                try:
                    from marl_distributedformation_tpu.algo import PPOConfig
                    from marl_distributedformation_tpu.utils.config import (
                        PRESETS,
                    )

                    train_m = _env_int(
                        "BENCH_TRAIN_M", M if on_accel else 256
                    )
                    tuned_ppo = PPOConfig(
                        batch_size=PRESETS["tpu"]["batch_size"]
                    )
                    rates, receipts = {}, {}
                    for k_chunk in chunks:
                        if time.time() > deadline - 15:
                            notes.append(
                                f"fused-scan chunk {k_chunk} skipped: "
                                "deadline"
                            )
                            break
                        f_rate, f_iters, compiles = _time_fused_phase(
                            N, train_m, deadline, tuned_ppo, k_chunk
                        )
                        rates[k_chunk] = f_rate
                        receipts[str(k_chunk)] = compiles
                        print(
                            f"[bench] train (fused-scan, chunk={k_chunk}):"
                            f" {f_rate:,.0f} formation-steps/s "
                            f"({f_iters:.2f} iters/s, {compiles} "
                            "compile)",
                            file=sys.stderr,
                        )
                    if rates:
                        best = max(rates, key=rates.get)
                        result["train_env_steps_per_sec_fused_scan"] = (
                            round(rates[best], 1)
                        )
                        result["train_fused_scan_chunk"] = best
                        result["train_fused_scan_rates"] = {
                            str(kk): round(v, 1) for kk, v in rates.items()
                        }
                        # Compile-once receipt: every fused program must
                        # have compiled exactly once (tier-1 pins this;
                        # the bench records the evidence).
                        result["train_fused_scan_compiles"] = receipts
                        tuned_prev = result.get(
                            "train_env_steps_per_sec_tuned"
                        )
                        if tuned_prev:
                            # Share of the fused rate the host loop gives
                            # back to dispatch/drain overhead at the same
                            # totals (>= 0: the fused program IS the same
                            # math minus per-iteration host round trips).
                            result["dispatch_overhead_pct"] = round(
                                max(
                                    0.0,
                                    (1.0 - tuned_prev / rates[best])
                                    * 100.0,
                                ),
                                1,
                            )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("fused-scan", e)
            elif chunks:
                notes.append("fused-scan phase skipped: deadline")

        # Phase 5b — population-sweep training (train/sweep.py): K
        # independent PPO runs advanced by ONE program. The host-loop
        # sweep pays one dispatch+drain round trip per population
        # iteration; the fused-scan sweep (fused_chunk, round 6) pays it
        # once per chunk — this phase measures both at MATCHED K and
        # population size and records what the fusion buys
        # (sweep_dispatch_overhead_pct). Rates count formation-steps
        # across ALL members; compile receipts come from the sweep's
        # RetraceGuard (one compile per rung, ever).
        if os.environ.get("BENCH_SKIP_SWEEP") != "1":
            try:
                sweep_chunks = [
                    int(c)
                    for c in os.environ.get(
                        "BENCH_SWEEP_CHUNKS", "1,8"
                    ).split(",")
                    if c.strip() and int(c) > 0
                ]
            except ValueError as e:
                notes.append(f"bad BENCH_SWEEP_CHUNKS: {e!r}"[:200])
                sweep_chunks = []
            if sweep_chunks and time.time() < deadline - 30:
                try:
                    from marl_distributedformation_tpu.algo import PPOConfig
                    from marl_distributedformation_tpu.utils.config import (
                        PRESETS,
                    )

                    num_seeds = _env_int("BENCH_SWEEP_SEEDS", 4)
                    sweep_m = _env_int(
                        "BENCH_SWEEP_M", (M // 4) if on_accel else 16
                    )
                    repeats = _env_int("BENCH_SWEEP_REPEATS", 5)
                    tuned_ppo = PPOConfig(
                        batch_size=PRESETS["tpu"]["batch_size"]
                    )
                    # Build + compile every rung FIRST, then interleave
                    # `repeats` timing passes across all of them and keep
                    # each rung's best: back-to-back per-config passes
                    # would book host-load drift (heavy on this shared
                    # container) to whichever config ran in the bad
                    # window, which is the exact comparison
                    # sweep_dispatch_overhead_pct exists to make.
                    timers = {0: _make_sweep_timer(
                        N, sweep_m, num_seeds, tuned_ppo
                    )}
                    for k_chunk in sweep_chunks:
                        if time.time() > deadline - 20:
                            notes.append(
                                f"fused-sweep chunk {k_chunk} skipped: "
                                "deadline"
                            )
                            break
                        timers[k_chunk] = _make_sweep_timer(
                            N, sweep_m, num_seeds, tuned_ppo,
                            fused_chunk=k_chunk,
                        )
                    rates = {kk: 0.0 for kk in timers}
                    for _ in range(max(1, repeats)):
                        if time.time() > deadline - 10:
                            break
                        for kk, (run_timed, _t) in timers.items():
                            rate, _ips = run_timed(deadline)
                            rates[kk] = max(rates[kk], rate)
                    host_rate = rates.pop(0)
                    # Warmup/compile can eat the whole budget before any
                    # timed pass runs — degrade to a note instead of
                    # recording 0.0 rates (and dividing by one below).
                    rates = {kk: r for kk, r in rates.items() if r > 0}
                    if host_rate <= 0 or not rates:
                        raise RuntimeError(
                            "deadline expired before a timed pass ran"
                        )
                    receipts = {
                        str(kk): timers[kk][1].retrace_guard.count
                        for kk in rates
                    }
                    result["sweep_env_steps_per_sec_host_loop"] = round(
                        host_rate, 1
                    )
                    result["sweep_num_seeds"] = num_seeds
                    result["sweep_m"] = sweep_m
                    result["sweep_timing"] = (
                        f"best of {repeats} interleaved passes per rung"
                    )
                    print(
                        f"[bench] sweep (host loop, K={num_seeds}, "
                        f"M={sweep_m}): {host_rate:,.0f} "
                        "formation-steps/s "
                        f"({timers[0][1].retrace_guard.count} compile)",
                        file=sys.stderr,
                    )
                    for kk, rate in rates.items():
                        print(
                            f"[bench] sweep (fused-scan, chunk={kk}): "
                            f"{rate:,.0f} formation-steps/s "
                            f"({receipts[str(kk)]} compile)",
                            file=sys.stderr,
                        )
                    if rates:
                        best = max(rates, key=rates.get)
                        result["sweep_env_steps_per_sec_fused_scan"] = (
                            round(rates[best], 1)
                        )
                        result["sweep_fused_scan_chunk"] = best
                        result["sweep_fused_scan_rates"] = {
                            str(kk): round(v, 1) for kk, v in rates.items()
                        }
                        result["sweep_fused_scan_compiles"] = receipts
                        # Share of the fused-population rate the host
                        # loop gives back to per-iteration dispatch +
                        # drain at the same K and M (>= 0: same math,
                        # fewer host round trips).
                        result["sweep_dispatch_overhead_pct"] = round(
                            max(
                                0.0,
                                (1.0 - host_rate / rates[best]) * 100.0,
                            ),
                            1,
                        )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("sweep", e)
            elif sweep_chunks:
                notes.append("sweep phase skipped: deadline")
        # Phase 6 — serving fleet throughput: a 2-replica fleet
        # (serving/fleet/) under the mixed-size smoke storm. Runs in a
        # SUBPROCESS with a forced 2-device CPU backend — the
        # multi-device flag must land before backend init, which this
        # process's backend has long passed — and always on CPU: this
        # is a host-path (routing + coalescing + dispatch) number, the
        # layer the fleet adds; model FLOPs are noise at this size.
        # First serving-side perf number in the trajectory.
        if os.environ.get("BENCH_SKIP_SERVING") == "1":
            _mark_skipped(
                result,
                "serving",
                ("serving_requests_per_sec_fleet", "serving_fleet_p95_ms"),
            )
        else:
            if time.time() < deadline - 60:
                try:
                    serving_s = float(
                        os.environ.get("BENCH_SERVING_DURATION_S", 2.0)
                    )
                    cmd = [
                        sys.executable,
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "serve_policy.py",
                        ),
                        "--init-policy", "MLPActorCritic",
                        "--obs-dim", "8",
                        "--fleet", "--replicas", "2",
                        "--smoke",
                        "--duration", str(serving_s),
                    ]
                    env = dict(os.environ)
                    env["JAX_PLATFORMS"] = "cpu"
                    env["XLA_FLAGS"] = (
                        env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                    ).strip()
                    out = subprocess.run(
                        cmd, capture_output=True, text=True,
                        timeout=max(deadline - time.time(), 60),
                        env=env,
                    )
                    if out.returncode != 0:
                        raise RuntimeError(
                            f"fleet smoke exited {out.returncode}: "
                            + out.stderr[-200:]
                        )
                    rep = json.loads(out.stdout.strip().splitlines()[-1])
                    result["serving_requests_per_sec_fleet"] = round(
                        rep["requests_per_sec_fleet"], 1
                    )
                    result["serving_fleet_p95_ms"] = round(
                        rep["latency_p95_ms"], 2
                    )
                    result["serving_fleet_replicas"] = 2
                    result["serving_fleet_max_compiles_per_rung"] = rep[
                        "max_compiles_per_rung"
                    ]
                    print(
                        "[bench] serving fleet (2 replicas, CPU): "
                        f"{rep['requests_per_sec_fleet']:,.0f} req/s, "
                        f"p95 {rep['latency_p95_ms']:.1f} ms",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("serving", e)
            else:
                notes.append("serving phase skipped: deadline")
        # Phase 7 — the always-learning pipeline (pipeline/,
        # docs/pipeline.md): trainer -> promotion gate -> fleet as ONE
        # loop, in a subprocess on a forced 2-device CPU (same rationale
        # as phase 6 — host-path control-plane numbers; the multi-device
        # flag must land before backend init). Records the train-step ->
        # served-model_step wall time (p50/p95 over the run's
        # promotions), the gate's eval throughput, and the compile-once
        # receipts: the gate's whole candidate series must cost ONE eval
        # compile, and serving must stay at <= 1 compile per rung.
        if os.environ.get("BENCH_SKIP_PIPELINE") == "1":
            _mark_skipped(
                result,
                "pipeline",
                (
                    "promotion_latency_s_p50",
                    "promotion_latency_s_p95",
                    "gate_eval_steps_per_sec",
                ),
            )
        else:
            if time.time() < deadline - 90:
                try:
                    pipeline_budget = min(
                        float(
                            os.environ.get("BENCH_PIPELINE_BUDGET_S", 240.0)
                        ),
                        max(deadline - time.time() - 10, 60),
                    )
                    cmd = [
                        sys.executable,
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "always_learning.py",
                        ),
                        "name=bench_pipeline",
                        f"num_formation={_env_int('BENCH_PIPELINE_M', 16)}",
                        "total_timesteps=4800",
                        "max_steps=60",
                        "log_interval=100",
                        f"gate_formations="
                        f"{_env_int('BENCH_PIPELINE_GATE_M', 32)}",
                        "pipeline_replicas=2",
                        f"pipeline_budget_s={pipeline_budget}",
                    ]
                    env = dict(os.environ)
                    env["JAX_PLATFORMS"] = "cpu"
                    env["XLA_FLAGS"] = (
                        env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                    ).strip()
                    out = subprocess.run(
                        cmd, capture_output=True, text=True,
                        timeout=max(deadline - time.time(), 90),
                        env=env,
                    )
                    if out.returncode != 0:
                        raise RuntimeError(
                            f"pipeline run exited {out.returncode}: "
                            + out.stderr[-200:]
                        )
                    rep = json.loads(out.stdout.strip().splitlines()[-1])
                    p50 = rep.get("promotion_latency_s_p50")
                    p95 = rep.get("promotion_latency_s_p95")
                    if p50 is None or p95 is None:
                        raise RuntimeError(
                            "pipeline run produced no measured "
                            f"promotions: {rep}"
                        )
                    result["promotion_latency_s_p50"] = round(p50, 3)
                    result["promotion_latency_s_p95"] = round(p95, 3)
                    result["gate_eval_steps_per_sec"] = round(
                        rep["gate_eval_steps_per_sec"], 1
                    )
                    result["pipeline_promotions"] = int(rep["promotions"])
                    result["pipeline_rejections"] = int(rep["rejections"])
                    # Compile-once receipts: ONE gate eval program across
                    # every candidate, <= 1 serving compile per rung.
                    result["pipeline_gate_compiles"] = int(
                        rep["gate_eval_compiles"]
                    )
                    result["pipeline_serving_max_compiles_per_rung"] = int(
                        rep["serving_max_compiles_per_rung"]
                    )
                    # Phase 8's span decomposition (obs/): per-stage
                    # p50s over the run's traced promotions — where the
                    # promotion seconds actually go (stream poll vs gate
                    # eval vs publish vs barrier commit vs first serve).
                    breakdown = rep.get("promotion_span_breakdown")
                    if breakdown:
                        result["promotion_span_breakdown"] = {
                            str(k): round(float(v), 4)
                            for k, v in breakdown.items()
                        }
                    print(
                        "[bench] pipeline (train->gate->fleet, 2-replica "
                        f"CPU): {rep['promotions']} promotions, "
                        f"latency p50 {p50:.2f}s / p95 {p95:.2f}s, gate "
                        f"{rep['gate_eval_steps_per_sec']:,.0f} "
                        f"eval-steps/s ({rep['gate_eval_compiles']} "
                        "compile)",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("pipeline", e)
            else:
                notes.append("pipeline phase skipped: deadline")
        # Phase 8 — tracing overhead (obs/, docs/observability.md): the
        # phase-6 fleet smoke run twice back to back at equal duration,
        # obs tracing ON then OFF; tracing_overhead_pct is the relative
        # req/s cost of leaving the spine enabled on the serving hot
        # path (the ISSUE 8 bar is < 5% — one ring append per coalesced
        # batch, not per request, is why it holds). Same subprocess /
        # forced-2-device rationale as phase 6. The companion
        # promotion_span_breakdown field rides phase 7's pipeline rep.
        if os.environ.get("BENCH_SKIP_SERVING") == "1":
            _mark_skipped(result, "obs", ("tracing_overhead_pct",))
        else:
            if time.time() < deadline - 60:
                try:
                    obs_s = float(
                        os.environ.get("BENCH_OBS_DURATION_S", 2.0)
                    )
                    env = dict(os.environ)
                    env["JAX_PLATFORMS"] = "cpu"
                    env["XLA_FLAGS"] = (
                        env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                    ).strip()
                    # Best-of-N INTERLEAVED passes, the phase-5b
                    # rationale: back-to-back per-mode timing on a
                    # shared container books load drift to whichever
                    # mode hit the bad window; interleaving + best-of
                    # cancels it.
                    passes = _env_int("BENCH_OBS_PASSES", 2)
                    rates = {"on": 0.0, "off": 0.0}
                    for _ in range(max(1, passes)):
                        for mode in ("on", "off"):
                            cmd = [
                                sys.executable,
                                os.path.join(
                                    os.path.dirname(
                                        os.path.abspath(__file__)
                                    ),
                                    "scripts", "serve_policy.py",
                                ),
                                "--init-policy", "MLPActorCritic",
                                "--obs-dim", "8",
                                "--fleet", "--replicas", "2",
                                "--smoke",
                                "--duration", str(obs_s),
                                "--obs-trace", mode,
                            ]
                            out = subprocess.run(
                                cmd, capture_output=True, text=True,
                                timeout=max(deadline - time.time(), 60),
                                env=env,
                            )
                            if out.returncode != 0:
                                raise RuntimeError(
                                    f"obs-{mode} smoke exited "
                                    f"{out.returncode}: "
                                    + out.stderr[-200:]
                                )
                            rep = json.loads(
                                out.stdout.strip().splitlines()[-1]
                            )
                            rates[mode] = max(
                                rates[mode],
                                float(rep["requests_per_sec_fleet"]),
                            )
                    overhead = (
                        100.0 * (rates["off"] - rates["on"]) / rates["off"]
                    )
                    result["tracing_overhead_pct"] = round(overhead, 2)
                    result["tracing_smoke_req_s_on"] = round(rates["on"], 1)
                    result["tracing_smoke_req_s_off"] = round(
                        rates["off"], 1
                    )
                    print(
                        "[bench] tracing overhead (2-replica CPU smoke): "
                        f"{rates['on']:,.0f} req/s traced vs "
                        f"{rates['off']:,.0f} untraced "
                        f"({overhead:+.1f}%)",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("obs", e)
            else:
                notes.append("obs phase skipped: deadline")
        # Phase 9 — SLO-driven sharded serving (serving/sharded.py,
        # loadgen.py, docs/serving.md "Sharded rungs & the earned
        # ladder"): three fleets on a forced 2-device CPU driven by the
        # SAME open-loop trace — replicated baseline, + f32 sharded
        # big-rung slice, + bf16 slice — then a rate bisection for the
        # capacity headline: max sustained req/s holding the p95 target
        # with sharding AND bf16 on, budget-1 compile receipts per rung.
        # On CPU the sharded 512-rung p95 win is the serving-layer one
        # (dedicated slice = no queue contention with small requests);
        # the intra-dispatch compute split needs real multi-chip
        # hardware, and bf16 is recorded honestly (negative on CPU — a
        # chip-side number by construction).
        if os.environ.get("BENCH_SKIP_SERVING") == "1":
            _mark_skipped(
                result,
                "serving_slo",
                (
                    "serving_req_per_sec_at_p95_slo",
                    "serving_sharded_512_p95_ms",
                    "serving_replicated_512_p95_ms",
                    "serving_bf16_speedup_pct",
                ),
            )
        else:
            if time.time() < deadline - 90:
                try:
                    slo_s = float(
                        os.environ.get("BENCH_SLO_DURATION_S", 1.5)
                    )
                    slo_p95 = float(
                        os.environ.get("BENCH_SLO_P95_MS", 50.0)
                    )
                    cmd = [
                        sys.executable,
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "serve_policy.py",
                        ),
                        "--init-policy", "MLPActorCritic",
                        "--obs-dim", "8",
                        "--slo-bench", "--replicas", "2",
                        "--duration", str(slo_s),
                        "--slo-p95-ms", str(slo_p95),
                    ]
                    env = dict(os.environ)
                    env["JAX_PLATFORMS"] = "cpu"
                    env["XLA_FLAGS"] = (
                        env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                    ).strip()
                    out = subprocess.run(
                        cmd, capture_output=True, text=True,
                        timeout=max(deadline - time.time(), 90),
                        env=env,
                    )
                    if out.returncode != 0:
                        raise RuntimeError(
                            f"slo bench exited {out.returncode}: "
                            + out.stderr[-200:]
                        )
                    rep = json.loads(out.stdout.strip().splitlines()[-1])
                    result["serving_req_per_sec_at_p95_slo"] = round(
                        rep["req_per_sec_at_p95_slo"], 1
                    )
                    result["serving_slo_p95_target_ms"] = slo_p95
                    result["serving_sharded_512_p95_ms"] = round(
                        rep["sharded_512_p95_ms"], 2
                    )
                    result["serving_replicated_512_p95_ms"] = round(
                        rep["replicated_512_p95_ms"], 2
                    )
                    result["serving_bf16_speedup_pct"] = round(
                        rep["bf16_speedup_pct"], 1
                    )
                    result["serving_slo_max_compiles_per_rung"] = int(
                        rep["max_compiles_per_rung"]
                    )
                    result["serving_batch_preempted_total"] = int(
                        rep["batch_preempted_total"]
                    )
                    result["serving_autotuned_ladder"] = rep["autotuned"]
                    print(
                        "[bench] serving SLO (2-device CPU, sharded+bf16"
                        f" on): {rep['req_per_sec_at_p95_slo']:,.0f} "
                        f"req/s at p95<={slo_p95:.0f}ms; 512-rung p95 "
                        f"{rep['sharded_512_p95_ms']:.1f}ms sharded vs "
                        f"{rep['replicated_512_p95_ms']:.1f}ms "
                        "replicated",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("serving slo", e)
            else:
                notes.append("serving slo phase skipped: deadline")
        # Phase 9b — elastic capacity (serving/elastic/, docs/serving.md
        # "Elastic capacity"): one shifting-mix day — interactive-heavy
        # first half, big-rung storm second half — against a STATIC
        # fleet whose split+ladder were autotuned on the first half and
        # frozen, and an ELASTIC fleet whose CapacityController replays
        # the live TraceRecorder window through the same DP and
        # re-splits at the fleet batch barrier (prewarm-then-commit).
        # Both measured on the storm half by the same rate bisection;
        # the barrier pause, prewarm compile attribution (census diff:
        # zero programs registered during the measured storm), and
        # budget-1 receipts ride along.
        if os.environ.get("BENCH_SKIP_SERVING") == "1":
            _mark_skipped(
                result,
                "elastic",
                (
                    "serving_req_per_sec_at_p95_slo_elastic",
                    "serving_req_per_sec_at_p95_slo_static",
                    "elastic_resplit_pause_ms",
                    "elastic_prewarm_compiles",
                ),
            )
        else:
            if time.time() < deadline - 90:
                try:
                    ela_s = float(
                        os.environ.get("BENCH_ELASTIC_DURATION_S", 2.0)
                    )
                    ela_p95 = float(
                        os.environ.get("BENCH_ELASTIC_P95_MS", 80.0)
                    )
                    cmd = [
                        sys.executable,
                        os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "serve_policy.py",
                        ),
                        "--init-policy", "MLPActorCritic",
                        "--obs-dim", "8", "--hidden", "64,64",
                        "--elastic-bench", "--replicas", "2",
                        "--duration", str(ela_s),
                        "--load-rps", "120",
                        "--slo-p95-ms", str(ela_p95),
                        "--slo-iterations", "4",
                    ]
                    env = dict(os.environ)
                    env["JAX_PLATFORMS"] = "cpu"
                    env["XLA_FLAGS"] = (
                        env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                    ).strip()
                    out = subprocess.run(
                        cmd, capture_output=True, text=True,
                        timeout=max(deadline - time.time(), 90),
                        env=env,
                    )
                    if out.returncode != 0:
                        raise RuntimeError(
                            f"elastic bench exited {out.returncode}: "
                            + out.stderr[-200:]
                        )
                    rep = json.loads(out.stdout.strip().splitlines()[-1])
                    result["serving_req_per_sec_at_p95_slo_elastic"] = (
                        round(rep["req_per_sec_at_p95_slo_elastic"], 1)
                    )
                    result["serving_req_per_sec_at_p95_slo_static"] = (
                        round(rep["req_per_sec_at_p95_slo_static"], 1)
                    )
                    result["elastic_resplit_pause_ms"] = round(
                        rep["elastic_resplit_pause_ms"], 3
                    )
                    result["elastic_prewarm_compiles"] = int(
                        rep["elastic_prewarm_compiles"]
                    )
                    result["elastic_storm_new_programs"] = int(
                        rep["elastic_storm_new_programs"]
                    )
                    result["elastic_resplits_committed"] = int(
                        rep["elastic_resplits_committed"]
                    )
                    result["elastic_max_compiles_per_rung"] = int(
                        rep["max_compiles_per_rung"]
                    )
                    result["elastic_storm_p95_ms"] = round(
                        rep["elastic_storm_p95_ms"], 2
                    )
                    result["elastic_static_storm_p95_ms"] = round(
                        rep["static_storm_p95_ms"], 2
                    )
                    result["elastic_buckets"] = rep["elastic_buckets"]
                    print(
                        "[bench] elastic capacity (2-device CPU, storm "
                        "half): "
                        f"{rep['req_per_sec_at_p95_slo_elastic']:,.0f} "
                        "req/s elastic vs "
                        f"{rep['req_per_sec_at_p95_slo_static']:,.0f} "
                        f"static at p95<={ela_p95:.0f}ms; re-split "
                        f"pause {rep['elastic_resplit_pause_ms']:.2f}ms,"
                        f" {rep['elastic_prewarm_compiles']:.0f} prewarm"
                        " compiles (0 on the storm path)",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("elastic", e)
            else:
                notes.append("elastic phase skipped: deadline")
        # Phase 10 — adversarial robustness (scenarios/adversary.py,
        # docs/adversarial.md): the falsifier search throughput + its
        # budget-1 compile receipt, and the auto-curriculum payoff at
        # EQUAL training steps — two tiny policies from the same seed,
        # one trained clean throughout, one switched mid-run to the
        # from_falsifiers stage discovered by searching its own
        # half-trained params (the train -> search -> train loop the
        # gate automates). worst_case_return_gap_pct is the
        # curriculum-trained policy's relative improvement over the
        # clean-trained one at the clean policy's discovered worst
        # cases (positive = adversarial training helped); honest noise
        # caveat: at bench-sized budgets this is directional, and it is
        # recorded whatever its sign.
        if os.environ.get("BENCH_SKIP_ADVERSARIAL") == "1":
            _mark_skipped(
                result,
                "adversarial",
                (
                    "adversarial_candidates_per_sec",
                    "adversarial_search_compiles",
                    "worst_case_return_gap_pct",
                ),
            )
        else:
            if time.time() < deadline - 60:
                try:
                    from marl_distributedformation_tpu.algo import PPOConfig
                    from marl_distributedformation_tpu.scenarios import (
                        AdversaryConfig,
                        AdversarySearch,
                        ScenarioSchedule,
                        ScenarioStage,
                        from_falsifiers,
                    )
                    from marl_distributedformation_tpu.train import (
                        TrainConfig,
                        Trainer,
                    )

                    adv_env = EnvParams(num_agents=4, max_steps=60)
                    adv_m = _env_int("BENCH_ADV_M", 16)
                    adv_iters = _env_int("BENCH_ADV_ITERS", 24)
                    adv_ppo = PPOConfig(
                        n_steps=5, n_epochs=2, batch_size=64
                    )
                    per_iter = adv_ppo.n_steps * adv_m * adv_env.num_agents
                    clean_sched = ScenarioSchedule(stages=(ScenarioStage(
                        rollouts=1, scenarios=("clean",),
                        severity=0.0, severity_start=0.0,
                    ),))

                    def adv_trainer(name):
                        return Trainer(
                            adv_env,
                            ppo=adv_ppo,
                            config=TrainConfig(
                                num_formations=adv_m,
                                total_timesteps=adv_iters * per_iter,
                                checkpoint=False,
                                name=name,
                                log_dir=f"/tmp/bench_{name}",
                                seed=0,
                            ),
                            scenario_schedule=clean_sched,
                        )

                    clean_tr = adv_trainer("adv_clean")
                    curr_tr = adv_trainer("adv_curriculum")
                    # Same searcher (ONE compiled population program)
                    # serves the mid-run search, the final search, and
                    # the worst-case comparison cells.
                    search = AdversarySearch(
                        clean_tr.model,
                        adv_env,
                        AdversaryConfig(
                            scenarios=("wind", "sensor_noise",
                                       "actuator_noise"),
                            grid=4,
                            generations=3,
                            num_formations=_env_int("BENCH_ADV_EVAL_M", 16),
                            drop_tolerance=0.1,
                        ),
                    )
                    half = adv_iters // 2
                    for _ in range(half):
                        clean_tr.run_iteration()
                        curr_tr.run_iteration()
                    mid = search.search(
                        curr_tr.train_state.params, origin="half-trained"
                    )
                    if mid["falsifiers"]:
                        curr_tr.update_scenario_schedule(from_falsifiers(
                            mid["falsifiers"], rollouts=adv_iters - half,
                        ))
                    for _ in range(adv_iters - half):
                        clean_tr.run_iteration()
                        curr_tr.run_iteration()
                    # The recorded search: the CLEAN-trained policy's
                    # falsifiers (timed; candidates/sec headline).
                    final = search.search(
                        clean_tr.train_state.params, origin="clean-trained"
                    )
                    cells = [
                        (f["scenario"], f["severity"])
                        for f in final["falsifiers"]
                    ] or [
                        (name, search.config.max_severity)
                        for name in final["scenarios"]
                    ]
                    wc_clean = min(search.evaluate_cells(
                        clean_tr.train_state.params, cells,
                        origin="clean-trained",
                    ))
                    wc_curr = min(search.evaluate_cells(
                        curr_tr.train_state.params, cells,
                        origin="curriculum-trained",
                    ))
                    gap = (
                        100.0 * (wc_curr - wc_clean)
                        / max(abs(wc_clean), 1.0)
                    )
                    result["adversarial_candidates_per_sec"] = round(
                        search.candidates_per_sec(), 1
                    )
                    result["adversarial_search_compiles"] = (
                        search.compile_count
                    )
                    result["adversarial_search_generations"] = (
                        final["generations"]
                    )
                    result["adversarial_falsifiers"] = {
                        f["scenario"]: f["severity"]
                        for f in final["falsifiers"]
                    }
                    result["worst_case_return_gap_pct"] = round(gap, 2)
                    result["worst_case_return_clean_trained"] = round(
                        wc_clean, 2
                    )
                    result["worst_case_return_curriculum_trained"] = round(
                        wc_curr, 2
                    )
                    result["adversarial_train_timesteps"] = (
                        adv_iters * per_iter
                    )
                    print(
                        "[bench] adversarial (search + auto-curriculum, "
                        f"{adv_iters} iters each): "
                        f"{result['adversarial_candidates_per_sec']:,.0f} "
                        f"candidates/s ({search.compile_count} compile), "
                        f"worst-case return {wc_clean:,.0f} clean-trained "
                        f"vs {wc_curr:,.0f} curriculum-trained "
                        f"({gap:+.1f}%)",
                        file=sys.stderr,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                    phase_failed("adversarial", e)
            else:
                notes.append("adversarial phase skipped: deadline")
        # Phase 11 — telemetry overhead (obs/metrics.py,
        # docs/observability.md): the phase-5 fused-scan training loop
        # re-timed as the REAL Anakin driver (dispatch chunk N+1, drain
        # chunk N through Trainer._drain_chunk — the seam where the
        # MetricsRegistry records) with telemetry enabled vs disabled,
        # interleaved best-of-N passes (the phase-8 rationale:
        # back-to-back per-mode timing on a shared container books load
        # drift to whichever mode hit the bad window). The ISSUE 11 bar
        # is <= 5%; a handful of dict ops per chunk is why it holds.
        # Beside it, sentinel_checks_per_sec: how fast the
        # RegressionSentinel compares a live registry snapshot against
        # the newest committed BENCH record (the control-plane poll
        # cost an always_learning run pays per supervision step).
        if os.environ.get("BENCH_SKIP_TRAIN") == "1":
            _mark_skipped(
                result,
                "telemetry",
                ("telemetry_overhead_pct", "sentinel_checks_per_sec"),
            )
        elif time.time() < deadline - 30:
            try:
                from marl_distributedformation_tpu.algo import PPOConfig
                from marl_distributedformation_tpu.obs import (
                    RegressionSentinel,
                    configure_metrics,
                    default_watches,
                )
                from marl_distributedformation_tpu.train import (
                    TrainConfig,
                    Trainer,
                )
                from marl_distributedformation_tpu.utils import MetricsLogger
                from marl_distributedformation_tpu.utils.config import (
                    PRESETS,
                )
                from marl_distributedformation_tpu.utils.profiling import (
                    Throughput,
                )

                t_chunk = _env_int("BENCH_TELEMETRY_CHUNK", 8)
                train_m = _env_int("BENCH_TRAIN_M", M if on_accel else 256)
                trainer = Trainer(
                    EnvParams(num_agents=N),
                    ppo=PPOConfig(batch_size=PRESETS["tpu"]["batch_size"]),
                    config=TrainConfig(
                        num_formations=train_m, checkpoint=False,
                        use_wandb=False, name="bench_telemetry",
                        log_dir="/tmp/bench_telemetry",
                        fused_chunk=t_chunk,
                    ),
                )
                for _ in range(2):  # warm twice (_time_fused_phase)
                    stacked = trainer.run_chunk()
                    float(stacked["loss"][-1])
                    if time.time() > deadline:
                        break
                logger = MetricsLogger(
                    "/tmp/bench_telemetry", run_name="bench_telemetry"
                )
                meter = Throughput()

                def timed_pass() -> float:
                    # The double-buffered Anakin loop (_train_fused
                    # minus checkpoints): drain goes through the REAL
                    # instrumented seam, so the on/off delta is exactly
                    # the registry's recording cost.
                    dispatches, iteration, pending = 0, 0, None
                    t0 = time.perf_counter()
                    while True:
                        steps_before = trainer.num_timesteps
                        stacked = trainer.run_chunk()
                        dispatches += 1
                        if pending is not None:
                            trainer._drain_chunk(logger, meter, *pending)
                        pending = (stacked, iteration, steps_before, None)
                        iteration += t_chunk
                        if (
                            time.perf_counter() - t0 >= MIN_TIMED_S / 2
                            or time.time() > deadline
                            or dispatches * t_chunk >= 128
                        ):
                            break
                    trainer._drain_chunk(logger, meter, *pending)
                    elapsed = time.perf_counter() - t0
                    n_steps = trainer.ppo.n_steps
                    return (
                        n_steps * train_m * dispatches * t_chunk / elapsed
                    )

                passes = _env_int("BENCH_TELEMETRY_PASSES", 2)
                rates = {"on": 0.0, "off": 0.0}
                expired = False
                for _ in range(max(1, passes)):
                    for mode in ("on", "off"):
                        configure_metrics(enabled=(mode == "on"))
                        rates[mode] = max(rates[mode], timed_pass())
                        if time.time() > deadline:
                            expired = True
                            break
                    if expired:  # exit the OUTER loop too — no more
                        break  # full training chunks past the deadline
                configure_metrics(enabled=True)
                logger.close()
                if rates["on"] > 0.0 and rates["off"] > 0.0:
                    overhead = (
                        100.0 * (rates["off"] - rates["on"]) / rates["off"]
                    )
                    result["telemetry_overhead_pct"] = round(overhead, 2)
                    result["telemetry_fused_rate_on"] = round(
                        rates["on"], 1
                    )
                    result["telemetry_fused_rate_off"] = round(
                        rates["off"], 1
                    )
                    print(
                        "[bench] telemetry (fused-scan loop, chunk="
                        f"{t_chunk}): {rates['on']:,.0f} "
                        f"formation-steps/s recorded vs "
                        f"{rates['off']:,.0f} unrecorded "
                        f"({overhead:+.1f}%)",
                        file=sys.stderr,
                    )
                else:
                    # The deadline ate one mode's passes: the comparison
                    # is unmeasurable, not zero — degrade to a note and
                    # keep whatever the sentinel timing below salvages.
                    notes.append(
                        "telemetry overhead unmeasured: deadline before "
                        "both modes ran"
                    )
                # Sentinel poll cost over the live registry (the trainer
                # gauges were just recorded above) vs the newest
                # committed record; trip_after at the untrippable cap so
                # the timing never pays a flight dump.
                sentinel = RegressionSentinel(
                    default_watches(), trip_after=10**9
                )
                checks = _env_int("BENCH_SENTINEL_CHECKS", 500)
                t0 = time.perf_counter()
                for _ in range(checks):
                    sentinel.check()
                result["sentinel_checks_per_sec"] = round(
                    checks / (time.perf_counter() - t0), 1
                )
                print(
                    "[bench] sentinel: "
                    f"{result['sentinel_checks_per_sec']:,.0f} checks/s "
                    f"vs {sentinel.record_source or 'no committed record'}",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("telemetry", e)
        else:
            notes.append("telemetry phase skipped: deadline")

        # --- Phase 12: chaos plane (chaos/, scripts/chaos_storm.py,
        # docs/chaos.md): one seeded fault campaign through trainer ->
        # gate -> fleet. Three headline fields: chaos_mttr_s (worst
        # kill -> first-served-recovery over the campaign's disruptive
        # faults), chaos_invariant_violations (step monotonicity,
        # no-request-lost, budget-1 receipts, audit-log + checkpoint-dir
        # consistency — MUST be 0), and fault_plane_overhead_pct (the
        # disabled plane's per-request cost, ~0: one attribute read per
        # injection point). The campaign replays bit-identically from
        # chaos_seed (scripts/chaos_storm.py --print-schedule).
        chaos_fields = (
            "chaos_mttr_s",
            "chaos_invariant_violations",
            "fault_plane_overhead_pct",
        )
        if os.environ.get("BENCH_SKIP_CHAOS") == "1":
            _mark_skipped(result, "chaos", chaos_fields)
        elif time.time() < deadline - 60:
            try:
                import tempfile

                sys.path.insert(
                    0,
                    os.path.join(os.path.dirname(__file__), "scripts"),
                )
                try:
                    from chaos_storm import run_campaign
                finally:
                    sys.path.pop(0)

                chaos_seed = _env_int("BENCH_CHAOS_SEED", 0)
                chaos_report = run_campaign(
                    seed=chaos_seed,
                    faults=_env_int("BENCH_CHAOS_FAULTS", 25),
                    workdir=tempfile.mkdtemp(prefix="bench_chaos_"),
                    budget_s=max(30.0, deadline - time.time() - 15.0),
                )
                result["chaos_seed"] = chaos_seed
                result["chaos_invariant_violations"] = chaos_report[
                    "chaos_invariant_violations"
                ]
                result["chaos_faults_fired"] = chaos_report[
                    "chaos_faults_fired"
                ]
                if "chaos_mttr_s" in chaos_report:
                    result["chaos_mttr_s"] = chaos_report["chaos_mttr_s"]
                result["fault_plane_overhead_pct"] = chaos_report[
                    "fault_plane_overhead_pct"
                ]
                result["chaos_pipeline_restarts"] = chaos_report[
                    "pipeline_restarts"
                ]
                print(
                    "[bench] chaos: "
                    f"{chaos_report['chaos_faults_fired']} faults fired, "
                    f"{chaos_report['chaos_invariant_violations']} "
                    "invariant violations, MTTR "
                    f"{chaos_report.get('chaos_mttr_s', 'n/a')}s, "
                    "disabled-plane overhead "
                    f"{chaos_report['fault_plane_overhead_pct']}%",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("chaos", e)
        else:
            notes.append("chaos phase skipped: deadline")

        # --- Phase 13: program ledger (obs/ledger.py,
        # docs/observability.md "Program ledger"): the phase-11 fused
        # training loop re-timed with the ledger enabled vs disabled,
        # interleaved best-of-N passes (the phase-8/11 rationale:
        # back-to-back per-mode timing on a shared container books
        # load drift to whichever mode hit the bad window). The bar is
        # < 5%: steady-state ledger cost is a perf_counter pair plus a
        # per-thread shard append per dispatch; registration happens
        # once per COMPILE. Beside it, the census headline fields off
        # the process-global ledger, which by this point has seen every
        # program this bench run compiled: ledger_program_count and
        # ledger_compile_seconds_total (attributed backend-compile
        # wall, the number the chip window commits and the census diff
        # gate re-checks).
        ledger_fields = (
            "ledger_overhead_pct",
            "ledger_program_count",
            "ledger_compile_seconds_total",
        )
        if os.environ.get("BENCH_SKIP_TRAIN") == "1":
            _mark_skipped(result, "ledger", ledger_fields)
        elif time.time() < deadline - 30:
            try:
                from marl_distributedformation_tpu.algo import PPOConfig
                from marl_distributedformation_tpu.obs import (
                    configure_ledger,
                    get_ledger,
                )
                from marl_distributedformation_tpu.train import (
                    TrainConfig,
                    Trainer,
                )
                from marl_distributedformation_tpu.utils import (
                    MetricsLogger,
                )
                from marl_distributedformation_tpu.utils.config import (
                    PRESETS,
                )
                from marl_distributedformation_tpu.utils.profiling import (
                    Throughput,
                )

                l_chunk = _env_int("BENCH_LEDGER_CHUNK", 8)
                train_m = _env_int("BENCH_TRAIN_M", M if on_accel else 256)
                configure_ledger(enabled=True)  # registration pass
                trainer = Trainer(
                    EnvParams(num_agents=N),
                    ppo=PPOConfig(
                        batch_size=PRESETS["tpu"]["batch_size"]
                    ),
                    config=TrainConfig(
                        num_formations=train_m, checkpoint=False,
                        use_wandb=False, name="bench_ledger",
                        log_dir="/tmp/bench_ledger",
                        fused_chunk=l_chunk,
                    ),
                )
                for _ in range(2):  # warm twice (_time_fused_phase)
                    stacked = trainer.run_chunk()
                    float(stacked["loss"][-1])
                    if time.time() > deadline:
                        break
                logger = MetricsLogger(
                    "/tmp/bench_ledger", run_name="bench_ledger"
                )
                meter = Throughput()

                def ledger_pass() -> float:
                    # The double-buffered Anakin loop, same shape as
                    # phase 11: dispatch N+1, drain N through the real
                    # instrumented seam. The on/off delta is exactly
                    # the ledger's dispatch-recording cost.
                    dispatches, iteration, pending = 0, 0, None
                    t0 = time.perf_counter()
                    while True:
                        steps_before = trainer.num_timesteps
                        stacked = trainer.run_chunk()
                        dispatches += 1
                        if pending is not None:
                            trainer._drain_chunk(logger, meter, *pending)
                        pending = (stacked, iteration, steps_before, None)
                        iteration += l_chunk
                        if (
                            time.perf_counter() - t0 >= MIN_TIMED_S / 2
                            or time.time() > deadline
                            or dispatches * l_chunk >= 128
                        ):
                            break
                    trainer._drain_chunk(logger, meter, *pending)
                    elapsed = time.perf_counter() - t0
                    n_steps = trainer.ppo.n_steps
                    return (
                        n_steps * train_m * dispatches * l_chunk / elapsed
                    )

                passes = _env_int("BENCH_LEDGER_PASSES", 2)
                rates = {"on": 0.0, "off": 0.0}
                expired = False
                for _ in range(max(1, passes)):
                    for mode in ("on", "off"):
                        configure_ledger(enabled=(mode == "on"))
                        rates[mode] = max(rates[mode], ledger_pass())
                        if time.time() > deadline:
                            expired = True
                            break
                    if expired:
                        break
                configure_ledger(enabled=True)
                logger.close()
                if rates["on"] > 0.0 and rates["off"] > 0.0:
                    overhead = (
                        100.0 * (rates["off"] - rates["on"]) / rates["off"]
                    )
                    result["ledger_overhead_pct"] = round(overhead, 2)
                    result["ledger_fused_rate_on"] = round(rates["on"], 1)
                    result["ledger_fused_rate_off"] = round(
                        rates["off"], 1
                    )
                else:
                    notes.append(
                        "ledger overhead unmeasured: deadline before "
                        "both modes ran"
                    )
                # Census headlines off the whole bench run's ledger.
                ledger = get_ledger()
                census = ledger.census()
                result["ledger_program_count"] = census["totals"][
                    "programs"
                ]
                result["ledger_compile_seconds_total"] = round(
                    census["totals"]["compile_seconds"], 3
                )
                result["ledger_compile_seconds_max"] = round(
                    ledger.compile_seconds_max(), 3
                )
                by_source = {}
                for prog in census["programs"]:
                    src = prog.get("analysis_source", "unavailable")
                    by_source[src] = by_source.get(src, 0) + 1
                result["ledger_analysis_sources"] = by_source
                wm = census["totals"].get("watermark_bytes")
                if wm is not None:
                    result["device_memory_watermark_bytes"] = wm
                print(
                    "[bench] ledger (fused-scan loop, chunk="
                    f"{l_chunk}): {rates['on']:,.0f} formation-steps/s "
                    f"recorded vs {rates['off']:,.0f} unrecorded "
                    f"({result.get('ledger_overhead_pct', 'n/a')}%); "
                    f"census {result['ledger_program_count']} programs, "
                    f"{result['ledger_compile_seconds_total']:.1f}s "
                    "compile",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("ledger", e)
        else:
            notes.append("ledger phase skipped: deadline")

        # --- Phase 14: the mesh tier (serving/mesh/, docs/mesh.md):
        # a loopback 2-host mesh — real host subprocesses behind the
        # MetaRouter — hammered by client threads while the
        # coordinator drives global barrier swaps and one host eats a
        # real SIGKILL mid-load. Headlines: mesh_req_per_sec,
        # mesh_global_swap_latency_s_p50/p95 (wall of the two-phase
        # prepare+commit across every host, under load),
        # mesh_failover_lost_requests (MUST be 0 — the
        # no-accepted-request-lost invariant across a host death), and
        # the per-host budget-1 receipts.
        mesh_fields = (
            "mesh_req_per_sec",
            "mesh_global_swap_latency_s_p50",
            "mesh_global_swap_latency_s_p95",
            "mesh_failover_lost_requests",
        )
        if os.environ.get("BENCH_SKIP_MESH") == "1":
            _mark_skipped(result, "mesh", mesh_fields)
        elif time.time() < deadline - 90:
            try:
                import tempfile

                from marl_distributedformation_tpu.serving.mesh.smoke import (  # noqa: E501
                    run_mesh_smoke,
                )

                smoke = run_mesh_smoke(
                    tempfile.mkdtemp(prefix="bench_mesh_"),
                    hosts=_env_int("BENCH_MESH_HOSTS", 2),
                    duration_s=float(
                        os.environ.get("BENCH_MESH_DURATION_S", "8")
                    ),
                    swaps=_env_int("BENCH_MESH_SWAPS", 3),
                    ready_timeout_s=max(
                        30.0, deadline - time.time() - 30.0
                    ),
                )
                result["mesh_hosts"] = smoke["mesh_hosts"]
                result["mesh_req_per_sec"] = smoke["mesh_req_per_sec"]
                for key in (
                    "mesh_global_swap_latency_s_p50",
                    "mesh_global_swap_latency_s_p95",
                ):
                    if smoke.get(key) is not None:
                        result[key] = smoke[key]
                result["mesh_failover_lost_requests"] = smoke[
                    "mesh_failover_lost_requests"
                ]
                result["mesh_step_violations"] = smoke[
                    "mesh_step_violations"
                ]
                result["mesh_global_swaps"] = smoke["mesh_global_swaps"]
                result["mesh_host_compile_receipts_max"] = smoke[
                    "mesh_host_compile_receipts_max"
                ]
                print(
                    "[bench] mesh (2-host loopback): "
                    f"{smoke['mesh_req_per_sec']:,.0f} req/s, "
                    f"{smoke['mesh_global_swaps']} global swaps "
                    f"(p50 {smoke.get('mesh_global_swap_latency_s_p50')}"
                    "s), host killed "
                    f"{smoke['mesh_host_killed']!r}, "
                    f"{smoke['mesh_failover_lost_requests']} lost, "
                    f"{smoke['mesh_step_violations']} step violations",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("mesh", e)
        else:
            notes.append("mesh phase skipped: deadline")

        # --- Phase 15: train-lane recovery (train/recovery.py,
        # docs/recovery.md). Three headline fields:
        # health_overhead_pct — the phase-11 interleaved fused loop
        # (dispatch N+1, drain N through the REAL Trainer._drain_chunk)
        # with the in-program health word + skip guard ON vs OFF (two
        # trainers, one compiled program each; best-of-N passes
        # alternate modes so container load drift books to neither);
        # recovery_mttr_s — a seeded NaN carry bomb through a live
        # fused run with the ladder armed, detection-at-drain ->
        # rollback wall from recovery.jsonl; train_divergence_events —
        # the ladder's sustained-breach count for that run (MUST be
        # >= 1: a bomb that never registers is a broken detector, not
        # a fast one).
        recovery_fields = (
            "health_overhead_pct",
            "recovery_mttr_s",
            "train_divergence_events",
        )
        if os.environ.get("BENCH_SKIP_TRAIN") == "1":
            _mark_skipped(result, "recovery", recovery_fields)
        elif time.time() < deadline - 30:
            try:
                from marl_distributedformation_tpu.algo import PPOConfig
                from marl_distributedformation_tpu.chaos import (
                    FaultSchedule,
                    FaultSpec,
                    get_fault_plane,
                )
                from marl_distributedformation_tpu.train import (
                    TrainConfig,
                    Trainer,
                    read_recovery_log,
                )
                from marl_distributedformation_tpu.utils import MetricsLogger
                from marl_distributedformation_tpu.utils.config import (
                    PRESETS,
                )
                from marl_distributedformation_tpu.utils.profiling import (
                    Throughput,
                )

                r_chunk = _env_int("BENCH_RECOVERY_CHUNK", 8)
                train_m = _env_int("BENCH_TRAIN_M", M if on_accel else 256)

                def make_recovery_trainer(name: str, health: bool):
                    return Trainer(
                        EnvParams(num_agents=N),
                        ppo=PPOConfig(
                            batch_size=PRESETS["tpu"]["batch_size"]
                        ),
                        config=TrainConfig(
                            num_formations=train_m, checkpoint=False,
                            use_wandb=False, name=name,
                            log_dir=f"/tmp/{name}",
                            fused_chunk=r_chunk, health=health,
                        ),
                    )

                trainers = {
                    "on": make_recovery_trainer("bench_health_on", True),
                    "off": make_recovery_trainer("bench_health_off", False),
                }
                logger = MetricsLogger(
                    "/tmp/bench_health_on", run_name="bench_health"
                )
                meter = Throughput()
                for tr in trainers.values():  # warm twice (phase 5/11)
                    for _ in range(2):
                        stacked = tr.run_chunk()
                        float(stacked["loss"][-1])
                        if time.time() > deadline:
                            break

                def timed_pass(tr) -> float:
                    dispatches, iteration, pend = 0, 0, None
                    t0 = time.perf_counter()
                    while True:
                        steps_before = tr.num_timesteps
                        stacked = tr.run_chunk()
                        dispatches += 1
                        if pend is not None:
                            tr._drain_chunk(logger, meter, *pend)
                        pend = (stacked, iteration, steps_before, None)
                        iteration += r_chunk
                        if (
                            time.perf_counter() - t0 >= MIN_TIMED_S / 2
                            or time.time() > deadline
                            or dispatches * r_chunk >= 128
                        ):
                            break
                    tr._drain_chunk(logger, meter, *pend)
                    elapsed = time.perf_counter() - t0
                    n_steps = tr.ppo.n_steps
                    return (
                        n_steps * train_m * dispatches * r_chunk / elapsed
                    )

                passes = _env_int("BENCH_RECOVERY_PASSES", 2)
                rates = {"on": 0.0, "off": 0.0}
                expired = False
                for _ in range(max(1, passes)):
                    for mode in ("on", "off"):
                        rates[mode] = max(
                            rates[mode], timed_pass(trainers[mode])
                        )
                        if time.time() > deadline:
                            expired = True
                            break
                    if expired:
                        break
                logger.close()
                if rates["on"] > 0.0 and rates["off"] > 0.0:
                    overhead = (
                        100.0 * (rates["off"] - rates["on"]) / rates["off"]
                    )
                    result["health_overhead_pct"] = round(overhead, 2)
                    result["health_fused_rate_on"] = round(rates["on"], 1)
                    result["health_fused_rate_off"] = round(
                        rates["off"], 1
                    )
                    print(
                        "[bench] health word (fused-scan loop, chunk="
                        f"{r_chunk}): {rates['on']:,.0f} "
                        f"formation-steps/s guarded vs {rates['off']:,.0f}"
                        f" unguarded ({overhead:+.1f}%)",
                        file=sys.stderr,
                    )
                else:
                    notes.append(
                        "health overhead unmeasured: deadline before "
                        "both modes ran"
                    )
                # The recovery drill: one seeded NaN carry bomb through
                # a SMALL fused run with the full ladder + retention
                # ring armed; MTTR is the detection->restored wall the
                # ladder logged. Small shapes — the restore cost under
                # measurement is checkpoint IO + re-placement, not
                # model math.
                if time.time() < deadline - 20:
                    import tempfile
                    from pathlib import Path

                    drill_dir = tempfile.mkdtemp(prefix="bench_recovery_")
                    drill_m, drill_chunk = 8, 2
                    per_iter = 5 * drill_m * N
                    drill = Trainer(
                        EnvParams(num_agents=N),
                        ppo=PPOConfig(
                            n_steps=5, n_epochs=2, batch_size=64
                        ),
                        config=TrainConfig(
                            num_formations=drill_m,
                            total_timesteps=16 * per_iter,
                            save_freq=5, fused_chunk=drill_chunk,
                            name="bench_recovery", log_dir=drill_dir,
                            seed=_env_int("BENCH_CHAOS_SEED", 0),
                            health=True, recovery=True,
                            recovery_breach_iters=2, keep_last_n=4,
                        ),
                    )
                    plane = get_fault_plane()
                    was_enabled = plane.enabled
                    # Fresh counters: phase 12's campaign already drove
                    # a Trainer with the plane ENABLED, so the
                    # train-lane hit counters are far past at_hit=4 —
                    # without a reset the bomb would never fire and the
                    # drill would record a broken detector.
                    plane.reset()
                    plane.arm(FaultSchedule([
                        FaultSpec("train.carry_poison", "raise", at_hit=4)
                    ]))
                    plane.enabled = True
                    try:
                        drill.train()
                    finally:
                        plane.enabled = was_enabled
                        plane.disarm()
                    mttr = [
                        float(e["mttr_s"])
                        for e in read_recovery_log(
                            Path(drill_dir) / "recovery.jsonl"
                        )
                        if e["event"] == "rollback"
                    ]
                    ladder = drill.recovery_ladder
                    if mttr:
                        result["recovery_mttr_s"] = round(max(mttr), 4)
                    result["train_divergence_events"] = (
                        ladder.breaches if ladder is not None else 0
                    )
                    print(
                        "[bench] recovery drill: "
                        f"{ladder.recoveries} rollback(s), MTTR "
                        f"{result.get('recovery_mttr_s', 'n/a')}s, "
                        f"{ladder.skipped_total} skipped update(s), "
                        f"halted={drill.halted}",
                        file=sys.stderr,
                    )
                else:
                    notes.append("recovery drill skipped: deadline")
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("recovery", e)
        else:
            notes.append("recovery phase skipped: deadline")

        # --- Phase 16: graftlint wall (scripts/graftlint.py,
        # analysis/callgraph.py, docs/static_analysis.md). One full
        # --check pass over the package in a fresh subprocess — the
        # exact CI invocation, so the wall includes the cold-process
        # whole-repo call-graph rebuild (the worst case a pre-commit
        # hook pays). check_bench_record.py holds the field under a
        # ceiling: the lock-ordering / guarded-write analyses are
        # package-global DFS walks and must not go super-linear as the
        # repo grows. A non-zero lint exit is a note, not a crash —
        # the bench record must still emit on a dirty tree.
        if os.environ.get("BENCH_SKIP_LINT") == "1":
            _mark_skipped(result, "lint", ("graftlint_wall_s",))
        elif time.time() < deadline - 10:
            import pathlib

            lint_cmd = [
                sys.executable,
                str(
                    pathlib.Path(__file__).resolve().parent
                    / "scripts"
                    / "graftlint.py"
                ),
                "--check",
            ]
            lint_timeout = _env_int("BENCH_LINT_TIMEOUT_S", 300)
            t0 = time.perf_counter()
            try:
                lint = subprocess.run(
                    lint_cmd, capture_output=True, text=True,
                    timeout=lint_timeout,
                )
            except subprocess.TimeoutExpired as e:
                phase_failed("lint", e)
            else:
                result["graftlint_wall_s"] = round(
                    time.perf_counter() - t0, 3
                )
                if lint.returncode != 0:
                    notes.append("graftlint --check found errors")
                print(
                    "[bench] graftlint --check: "
                    f"{result['graftlint_wall_s']}s wall "
                    f"(exit {lint.returncode})",
                    file=sys.stderr,
                )
        else:
            notes.append("lint phase skipped: deadline")

        # --- Phase 17: the sebulba lane (train/sebulba/,
        # docs/sebulba.md). One pipelined actor/learner run at bench
        # scale: the actor thread streams rollouts through the bounded
        # TransferQueue while the learner drains K per fused chunk —
        # headlines sebulba_env_steps_per_sec (actor-side env
        # interaction wall rate), sebulba_learner_steps_per_sec
        # (batches consumed into updates per second), the queue /
        # staleness p95s, and the per-slice budget-1 compile receipts.
        # While the learner is SATURATED, the promotion gate — pinned
        # to its own slice via assign_gate_device — evaluates live
        # checkpoints: gate_eval_p50_under_load_s is the steady-state
        # (post-compile) eval wall beside a busy learner, the number
        # the gate's latency budget is written against.
        sebulba_fields = (
            "sebulba_env_steps_per_sec",
            "sebulba_learner_steps_per_sec",
            "transfer_queue_occupancy_p95",
            "param_staleness_p95_updates",
            "sebulba_actor_compiles",
            "sebulba_learner_compiles",
            "gate_eval_p50_under_load_s",
        )
        if os.environ.get("BENCH_SKIP_SEBULBA") == "1":
            _mark_skipped(result, "sebulba", sebulba_fields)
        elif time.time() < deadline - 30:
            try:
                import tempfile
                import threading as _threading

                from marl_distributedformation_tpu.algo import PPOConfig
                from marl_distributedformation_tpu.pipeline import (
                    GateConfig,
                    PromotionGate,
                )
                from marl_distributedformation_tpu.train import (
                    SebulbaDriver,
                    TrainConfig,
                    assign_gate_device,
                )
                from marl_distributedformation_tpu.utils.checkpoint import (
                    latest_checkpoint,
                )

                seb_m = _env_int("BENCH_SEBULBA_M", 64)
                seb_iters = _env_int("BENCH_SEBULBA_ITERS", 24)
                seb_chunk = _env_int("BENCH_SEBULBA_CHUNK", 2)
                seb_dir = tempfile.mkdtemp(prefix="bench_sebulba_")
                seb_env = EnvParams(num_agents=N)
                per_iter = 5 * seb_m * N
                driver = SebulbaDriver(
                    seb_env,
                    ppo=PPOConfig(n_steps=5, n_epochs=2, batch_size=64),
                    config=TrainConfig(
                        num_formations=seb_m,
                        total_timesteps=seb_iters * per_iter,
                        save_freq=4,
                        fused_chunk=seb_chunk,
                        name="bench_sebulba",
                        log_dir=seb_dir,
                        seed=0,
                        architecture="sebulba",
                    ),
                )
                t0 = time.perf_counter()
                train_box: list = []
                train_thread = _threading.Thread(
                    target=lambda: train_box.append(driver.train()),
                    name="bench-sebulba-train",
                    daemon=True,
                )
                train_thread.start()
                # Gate-beside-learner leg: wait for the run's first
                # checkpoint, then evaluate it from THIS thread on the
                # gate's own slice while the learner chews. One warm
                # eval absorbs the matrix compile (the gate's budget-1
                # bootstrap, not its steady state); the timed evals are
                # the under-load latency the budget is written against.
                gate_device = assign_gate_device(1)
                gate = PromotionGate(
                    seb_env,
                    GateConfig(
                        scenarios=("wind",),
                        severities=(1.0,),
                        eval_formations=8,
                        clean_tolerance=10.0,
                        rung_tolerance=10.0,
                    ),
                    device=gate_device,
                )
                candidate = None
                gate_deadline = min(deadline, time.time() + 120)
                while time.time() < gate_deadline and candidate is None:
                    candidate = latest_checkpoint(seb_dir)
                    if candidate is None:
                        time.sleep(0.2)
                gate_walls = []
                if candidate is not None:
                    gate.evaluate(candidate)  # warm: compile + baseline
                    for _ in range(5):
                        if (
                            time.time() > deadline
                            or not train_thread.is_alive()
                        ):
                            break
                        fresh = latest_checkpoint(seb_dir) or candidate
                        g0 = time.perf_counter()
                        gate.evaluate(fresh)
                        gate_walls.append(time.perf_counter() - g0)
                    if not gate_walls and time.time() < deadline:
                        # The run outran the gate's warm compile (short
                        # bench budgets) — still record the steady-state
                        # eval wall, honestly annotated: the learner was
                        # idle for these.
                        notes.append(
                            "sebulba gate evals ran after the learner "
                            "finished (run shorter than the gate's "
                            "warm compile)"
                        )
                        for _ in range(3):
                            fresh = latest_checkpoint(seb_dir) or candidate
                            g0 = time.perf_counter()
                            gate.evaluate(fresh)
                            gate_walls.append(time.perf_counter() - g0)
                else:
                    notes.append(
                        "sebulba gate leg skipped: no checkpoint "
                        "appeared before the gate deadline"
                    )
                train_thread.join(
                    timeout=max(10.0, deadline - time.time() + 60)
                )
                wall = time.perf_counter() - t0
                if train_thread.is_alive() or not train_box:
                    phase_failed(
                        "sebulba",
                        TimeoutError(
                            "pipelined run did not finish inside the "
                            "bench deadline"
                        ),
                    )
                else:
                    queue = driver.transfer_queue
                    result["sebulba_env_steps_per_sec"] = round(
                        driver.num_timesteps / wall, 1
                    )
                    result["sebulba_learner_steps_per_sec"] = round(
                        len(queue.consumed_seqs) / wall, 2
                    )
                    result["transfer_queue_occupancy_p95"] = round(
                        driver.occupancy_p95(), 2
                    )
                    result["param_staleness_p95_updates"] = round(
                        driver.staleness_p95(), 2
                    )
                    result["sebulba_actor_compiles"] = int(
                        driver.actor_guard.count
                    )
                    result["sebulba_learner_compiles"] = int(
                        driver.learner_guard.count
                    )
                    result["sebulba_stale_dropped"] = int(
                        driver.stale_dropped
                    )
                    result["sebulba_gate_device"] = str(gate_device)
                    if gate_walls:
                        result["gate_eval_p50_under_load_s"] = round(
                            sorted(gate_walls)[len(gate_walls) // 2], 4
                        )
                        result["sebulba_gate_compiles"] = int(
                            gate.program.compile_count
                            if gate.program is not None
                            else 0
                        )
                    print(
                        "[bench] sebulba (pipelined, chunk="
                        f"{seb_chunk}): "
                        f"{result['sebulba_env_steps_per_sec']:,.0f} "
                        "env-steps/s acted, "
                        f"{result['sebulba_learner_steps_per_sec']:.1f} "
                        "batches/s learned, occupancy p95 "
                        f"{result['transfer_queue_occupancy_p95']}, "
                        "staleness p95 "
                        f"{result['param_staleness_p95_updates']}, gate "
                        f"p50 {result.get('gate_eval_p50_under_load_s')}"
                        f"s on {gate_device}",
                        file=sys.stderr,
                    )
            except Exception as e:  # noqa: BLE001 — recorded; exit code says so
                phase_failed("sebulba", e)
        else:
            notes.append("sebulba phase skipped: deadline")
    except Exception as e:  # noqa: BLE001 — print what was measured, then fail
        failed.append("bench")
        result["error"] = repr(e)[:300]
        traceback.print_exc(file=sys.stderr)
    if notes:
        result["notes"] = "; ".join(notes)
    if failed:
        result["phases_failed"] = failed
    print(json.dumps(result), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
