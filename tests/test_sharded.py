"""Sharded serving, the earned ladder, and SLO classes (tier-1,
multi-device CPU): the acceptance pins from the sharded-serving ISSUE,
on the 8-virtual-device mesh tests/conftest.py provisions:

- mesh-sharded rungs run the replicated engine's per-row program (dp
  sharding replicates params and splits the batch): BITWISE its f32
  actions wherever each device's shard gets the matmul kernel the whole
  rung gets (XLA:CPU: from 4 rows per device), the action head's
  summation-order bound below — deterministic AND stochastic;
- bf16 rungs diverge within the explicit cast-rounding budget
  (tests/bf16_budget.py), never bitwise-silently serving f32;
- the ladder autotuner is deterministic given a fixed trace and its DP
  is exactly minimal against brute force;
- SLO-class admission: an interactive request is NEVER rejected while
  batch traffic is queued (the newest batch request yields, with the
  standard backpressure contract), and queued interactive work
  dispatches ahead of earlier-queued batch work.
"""

import queue
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bf16_budget import bf16_action_atol
from marl_distributedformation_tpu.compat.policy import LoadedPolicy
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.obs.export import prometheus_exposition
from marl_distributedformation_tpu.parallel.mesh import make_mesh
from marl_distributedformation_tpu.serving import (
    BackpressureError,
    BucketedPolicyEngine,
    MicroBatchScheduler,
    ShardedPolicyEngine,
    ShardedSpec,
    autotune_ladder,
    max_rate_at_slo,
    run_load,
    synthetic_trace,
)
from marl_distributedformation_tpu.serving.autotune import (
    choose_buckets,
    choose_window_ms,
    padded_cost,
)
from marl_distributedformation_tpu.serving.fleet import (
    FleetRouter,
    warmup_fleet,
)
from marl_distributedformation_tpu.serving.loadgen import (
    load_trace,
    save_trace,
)
from marl_distributedformation_tpu.serving.scheduler import (
    SLO_BATCH,
    SLO_INTERACTIVE,
    _ClassedQueue,
    _Request,
)
from marl_distributedformation_tpu.serving.sharded import (
    fit_spec_to_mesh,
    match_partition_rules,
)

OBS_DIM = 6
HIDDEN = (8, 8)
BUCKETS = (8, 64, 512)  # every rung ladder used by the parity gates


def _make_policy(seed=0):
    model = MLPActorCritic(act_dim=2, hidden=HIDDEN)
    variables = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, OBS_DIM))
    )
    return LoadedPolicy(dict(variables), model_kwargs={"hidden": HIDDEN})


def _obs(n, seed=0):
    return (
        np.random.default_rng(seed)
        .standard_normal((n, OBS_DIM))
        .astype(np.float32)
    )


# -- sharded == replicated parity ---------------------------------------


# XLA:CPU (jax 0.9.0) picks the kernel of a dot from its shape, and kernels
# sum the contraction in different orders: with the policy's 2-wide action
# head, a batch of fewer than 4 rows takes another kernel than 4 rows and
# up (measured: ``rows x 8 @ 8 x 2`` at 1, 2, 3 rows differs from the
# 512-row program in the last bit; 4, 8, ... 512 rows agree with it
# exactly). A dp shard is its own batch, so sharded == replicated is
# BITWISE from 4 rows per device, and summation-order noise below.
CPU_BITWISE_MIN_ROWS_PER_DEVICE = 4


def _head_reorder_bound(policy) -> float:
    """How far two f32 summation orders of the action head can land
    apart: each is within ``K * 2^-24 * sum|h_k w_k|`` of the exact sum
    (K = head fan-in; |h| <= 1 after tanh), so twice that."""
    kernel = np.asarray(policy.params["params"]["pi_head"]["kernel"])
    fan_in = kernel.shape[0]
    return 2.0 * fan_in * 2.0**-24 * float(np.abs(kernel).sum(axis=0).max())


def test_sharded_matches_replicated_at_every_rung():
    """dp-sharded rungs are the SAME per-row program as the replicated
    engine — params replicate, only the batch axis splits — so f32
    parity is bitwise wherever each device's shard is big enough to get
    the kernel the whole rung gets (see the constant above), and within
    the action head's summation-order bound where it is not (rung 8 at
    dp=4: 2 rows per device). Both action modes; the engines share seed
    and dispatch cadence, so the stochastic legs fold in identical
    per-dispatch keys."""
    assert jax.default_backend() == "cpu", (
        "the bitwise threshold above is XLA:CPU's; the TPU v5e answers "
        "bitwise at every rung at dp=4, which chip_smoke.py leg 4e "
        "asserts there (CHANGES.md PR 21)"
    )
    dp = 4
    policy = _make_policy()
    bound = _head_reorder_bound(policy)
    assert bound < 1e-6  # a last-bit bound, not a tolerance to hide in
    replicated = BucketedPolicyEngine(policy, buckets=BUCKETS, seed=5)
    sharded = ShardedPolicyEngine(
        policy, make_mesh({"dp": dp}), buckets=BUCKETS, seed=5
    )
    for deterministic, seed_base in ((True, 0), (False, 1000)):
        for n in BUCKETS:
            obs = _obs(n, seed=seed_base + n)
            a_rep = replicated.act(obs, deterministic=deterministic)
            a_sh = sharded.act(obs, deterministic=deterministic)
            assert a_rep.dtype == np.float32 == a_sh.dtype
            label = f"rung {n}, deterministic={deterministic}"
            if n // dp >= CPU_BITWISE_MIN_ROWS_PER_DEVICE:
                assert np.array_equal(a_rep, a_sh), f"bitwise at {label}"
            else:
                np.testing.assert_allclose(
                    a_sh, a_rep, rtol=0, atol=bound, err_msg=label
                )
    # Both modes rode ONE compiled program per rung (traced bool).
    assert all(c == 1 for c in sharded.compile_counts().values())
    assert all(c == 1 for c in replicated.compile_counts().values())


def test_cpu_dot_kernel_threshold_is_what_the_parity_test_assumes():
    """The measured fact the parity test rests on, pinned on its own so a
    jax upgrade that moves it fails HERE with a clear story: the 2-wide
    head dot on fewer than 4 rows is a different summation order than on
    4+ rows, and every row count from 4 up agrees with the 512-row
    program bit for bit."""
    rng = np.random.default_rng(0)
    w = (0.01 * rng.standard_normal((HIDDEN[-1], 2))).astype(np.float32)
    h = np.tanh(rng.standard_normal((512, HIDDEN[-1]))).astype(np.float32)
    dot = jax.jit(lambda a: a @ w)
    full = np.asarray(dot(h))
    for rows in (4, 8, 16, 128):
        assert np.array_equal(np.asarray(dot(h[:rows])), full[:rows]), rows
    small = np.concatenate(
        [np.asarray(dot(h[i : i + 2])) for i in range(0, 512, 2)]
    )
    assert not np.array_equal(small, full), (
        "2-row dots now agree with the 512-row program bitwise: XLA:CPU "
        "changed its kernel choice — CPU_BITWISE_MIN_ROWS_PER_DEVICE can "
        "go back to 1 and the docs' 'bitwise from 4 rows' sentences with it"
    )
    np.testing.assert_allclose(
        small, full, rtol=0,
        atol=2.0 * HIDDEN[-1] * 2.0**-24 * float(np.abs(w).sum(0).max()),
    )


def test_bf16_rungs_within_cast_rounding_budget():
    """bf16 rungs actually compute in bf16 (divergence is nonzero) and
    the deterministic-action divergence vs the f32 ladder stays inside
    the explicit cast-rounding budget — tests/bf16_budget.py's bound,
    not a flat tolerance."""
    policy = _make_policy()
    replicated = BucketedPolicyEngine(policy, buckets=BUCKETS)
    bf16 = ShardedPolicyEngine(
        policy, make_mesh({"dp": 4}), buckets=BUCKETS, dtype="bfloat16"
    )
    assert bf16.dtype_label == "bf16"
    atol = bf16_action_atol(num_layers=len(HIDDEN) + 1)
    for n in BUCKETS:
        obs = _obs(n, seed=n)
        a32 = replicated.act(obs, deterministic=True)
        a16 = bf16.act(obs, deterministic=True)
        assert a16.dtype == np.float32  # actions come back f32
        diff = np.max(np.abs(a32 - a16))
        assert 0.0 < diff <= atol, (
            f"rung {n}: bf16 divergence {diff:.2e} outside (0, {atol:.2e}]"
        )


def test_mp_axis_shards_kernels_and_stays_within_fp_noise():
    """A dp×mp mesh splits tower kernels over their OUTPUT features.
    The next layer then contracts over an mp-sharded activation, which
    re-orders that reduction — so the mp gate is fp-reduction noise,
    not bitwise (the dp-only fleet default keeps the bitwise gate)."""
    policy = _make_policy()
    mesh = make_mesh({"dp": 2, "mp": 2})
    engine = ShardedPolicyEngine(policy, mesh, buckets=(8,))
    specs = [
        (name, spec)
        for name, spec in _named_specs(engine.param_specs)
        if "mp" in tuple(spec)
    ]
    assert specs, "no param leaf sharded over the mp axis"
    replicated = BucketedPolicyEngine(policy, buckets=(8,))
    obs = _obs(8)
    np.testing.assert_allclose(
        replicated.act(obs, deterministic=True),
        engine.act(obs, deterministic=True),
        rtol=0,
        atol=1e-5,  # reduction-order noise, orders above measured
    )


def _named_specs(spec_tree):
    from marl_distributedformation_tpu.serving.sharded import _tree_paths
    from jax.sharding import PartitionSpec as P

    flat, _ = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    return [
        ("/".join(str(getattr(e, "key", e)) for e in path), leaf)
        for path, leaf in flat
    ]


def test_sharded_engine_rejects_bad_mesh_and_buckets():
    policy = _make_policy()
    with pytest.raises(ValueError, match="dp"):
        ShardedPolicyEngine(policy, make_mesh({"sp": 2}), buckets=(8,))
    with pytest.raises(ValueError, match="divide"):
        ShardedPolicyEngine(policy, make_mesh({"dp": 4}), buckets=(6,))


def test_fit_spec_degrades_to_what_the_mesh_supports():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"dp": 4})
    # Unknown axis -> replicated; known axis keeps only dividing dims.
    assert fit_spec_to_mesh(P(None, "mp"), (8, 8), mesh) == P()
    assert fit_spec_to_mesh(P("dp"), (8, 6), mesh) == P("dp")
    assert fit_spec_to_mesh(P("dp"), (6, 8), mesh) == P()


def test_partition_rules_require_a_match():
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"dp": 2})
    params = {"tower": {"kernel": np.ones((4, 4), np.float32)}}
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules((("nomatch", P()),), params, mesh)
    specs = match_partition_rules(
        ((r"kernel", P("dp")), (r".*", P())), params, mesh
    )
    assert specs["tower"]["kernel"] == P("dp")


# -- fleet routing + rung gauges ----------------------------------------


def test_router_routes_big_rungs_to_the_sharded_replica():
    """Big requests land on the mesh-backed replica, small ones on the
    replicated ladder, and the rung gauges surface both through the
    Prometheus folding (the tracing spine sees the new engine through
    the existing endpoint)."""
    policy = _make_policy()
    router = FleetRouter(
        policy,
        num_replicas=2,
        buckets=(1, 8, 64, 512),
        window_ms=0.0,
        sharded=ShardedSpec(axis_sizes={"dp": 2}, buckets=(64, 512)),
    )
    with router:
        warmup_fleet(router, (OBS_DIM,))
        big = router.submit(_obs(64), timeout_s=30.0).result(60.0)
        small = router.submit(_obs(1), timeout_s=30.0).result(60.0)
        assert big.replica == router.sharded_replica.index
        assert small.replica != router.sharded_replica.index
        snap = router.metrics.snapshot(router.replicas)
    assert snap["rung64_f32_sharded"] == 1.0
    assert snap["rung512_f32_sharded"] == 1.0
    # Compile receipts are kind-attributed: both engine kinds serve the
    # 64 rung here (warmup compiled each once), and folding them into
    # one number would make a receipt breach unattributable.
    assert snap["rung64_f32_sharded_compiles"] == 1.0
    assert snap["rung64_f32_replicated_compiles"] == 1.0
    assert snap["rung512_f32_sharded_compiles"] == 1.0
    text = prometheus_exposition(snap)
    assert (
        'marl_rung_sharded{dtype="f32",rung="64"} 1' in text
        or 'marl_rung_sharded{dtype="f32",rung="64"} 1.0' in text
    )
    assert 'marl_rung_compiles{dtype="f32",kind="sharded",rung="64"}' in text
    assert (
        'marl_rung_compiles{dtype="f32",kind="replicated",rung="64"}'
        in text
    )


# -- the earned ladder ---------------------------------------------------


def test_autotuner_is_deterministic_given_a_fixed_trace():
    """Same trace in, same plan out — twice from one trace object and
    once from an identically-seeded rebuild. An autotuner that flaps on
    identical input would churn compiled rungs."""
    t1 = synthetic_trace(20.0, 40.0, seed=3, batch_fraction=0.2)
    t2 = synthetic_trace(20.0, 40.0, seed=3, batch_fraction=0.2)
    kw = dict(p95_target_ms=50.0, mesh_divisor=4, sharded_min_rows=64)
    p1 = autotune_ladder(t1, **kw)
    p2 = autotune_ladder(t1, **kw)
    p3 = autotune_ladder(t2, **kw)
    assert p1 == p2 == p3
    assert all(b % 4 == 0 for b in p1.sharded_buckets)
    assert set(p1.sharded_buckets) | set(p1.replicated_buckets) == set(
        p1.buckets
    )
    # The earned ladder beats the hand-picked one on its own traffic.
    assert p1.expected_occupancy_pct >= p1.baseline_occupancy_pct


def test_choose_buckets_dp_is_exactly_minimal():
    """The rung DP against brute force: over every candidate subset (of
    the observed sizes, top size always covered) within the rung budget,
    no ladder pads fewer rows than the DP's."""
    import itertools

    sizes = np.array([1, 1, 1, 2, 7, 7, 9, 30, 30, 64], np.int64)
    got = choose_buckets(sizes, max_rungs=3)
    cands = sorted(set(int(s) for s in sizes))
    best = min(
        padded_cost(sizes, combo + (cands[-1],))
        for r in range(0, 3)
        for combo in itertools.combinations(cands[:-1], r)
    )
    assert padded_cost(sizes, got) == best
    assert len(got) <= 3 and max(got) == 64


def test_choose_window_caps_at_slo_fraction_and_shrinks_with_rate():
    slow = choose_window_ms(
        10.0, 1.0, fill_rows=32, p95_target_ms=50.0
    )
    fast = choose_window_ms(
        10_000.0, 1.0, fill_rows=32, p95_target_ms=50.0
    )
    assert slow == pytest.approx(0.2 * 50.0)  # capped, not 3200 ms
    assert 0.0 < fast < slow


def test_trace_roundtrip_and_rate_scaling(tmp_path):
    trace = synthetic_trace(5.0, 30.0, seed=1, batch_fraction=0.3)
    path = tmp_path / "trace.jsonl"
    save_trace(trace, path)
    back = load_trace(path)
    assert np.allclose(back.inter_arrival_s, trace.inter_arrival_s)
    assert np.array_equal(back.sizes, trace.sizes)
    assert back.slo_classes == trace.slo_classes
    doubled = trace.scaled_to_rate(trace.offered_rps * 2)
    assert doubled.offered_rps == pytest.approx(
        trace.offered_rps * 2
    )
    assert np.array_equal(doubled.sizes, trace.sizes)


def test_open_loop_replay_measures_a_live_scheduler():
    """run_load against a real engine: every request completes, the
    report carries per-size percentiles, and the SLO bisection finds a
    nonzero sustainable rate under a generous target."""
    policy = _make_policy()
    engine = BucketedPolicyEngine(policy, buckets=(1, 8))
    with MicroBatchScheduler(engine, window_ms=0.0) as sched:
        engine.act(_obs(1))  # warm both rungs outside the replay
        engine.act(_obs(8))
        trace = synthetic_trace(
            0.4, 150.0, seed=2, size_mix=((1, 0.7), (8, 0.3))
        )
        rep = run_load(sched, trace, (OBS_DIM,), seed=2)
        assert rep.submitted == len(trace)
        assert rep.ok == rep.submitted
        assert rep.p95_ms > 0.0
        assert set(rep.per_size_p95_ms) <= {1, 8}
        assert rep.meets(p95_target_ms=10_000.0, max_loss=0.0)
        best, reports = max_rate_at_slo(
            sched,
            (OBS_DIM,),
            p95_target_ms=500.0,
            lo_rps=20.0,
            hi_rps=80.0,
            probe_duration_s=0.25,
            iterations=1,
            seed=2,
            size_mix=((1, 0.7), (8, 0.3)),
        )
        assert best >= 20.0
        assert len(reports) >= 2


# -- SLO classes ---------------------------------------------------------


def _req(slo, tag):
    obs = np.full((1, OBS_DIM), float(tag), np.float32)
    return _Request(
        obs=obs,
        deterministic=True,
        future=Future(),
        enqueued=time.perf_counter(),
        timeout_s=None,
        slo_class=slo,
    )


def test_classed_queue_orders_interactive_first_fifo_within_class():
    q = _ClassedQueue(maxsize=8)
    b1, b2 = _req(SLO_BATCH, 1), _req(SLO_BATCH, 2)
    i1, i2 = _req(SLO_INTERACTIVE, 3), _req(SLO_INTERACTIVE, 4)
    for r in (b1, b2, i1, i2):
        assert q.put_nowait(r) is None
    assert [q.get_nowait() for _ in range(4)] == [i1, i2, b1, b2]
    with pytest.raises(queue.Empty):
        q.get_nowait()


def test_classed_queue_preempts_newest_batch_never_interactive():
    q = _ClassedQueue(maxsize=3)
    b1, b2, i1 = _req(SLO_BATCH, 1), _req(SLO_BATCH, 2), _req(
        SLO_INTERACTIVE, 3
    )
    for r in (b1, b2, i1):
        assert q.put_nowait(r) is None
    # Full + batch queued: interactive admission evicts the NEWEST
    # batch request (b2 — it has waited least).
    i2 = _req(SLO_INTERACTIVE, 4)
    assert q.put_nowait(i2) is b2
    # Full + batch arrival: plain reject.
    with pytest.raises(queue.Full):
        q.put_nowait(_req(SLO_BATCH, 5))
    # Full + all-interactive: only now may interactive be rejected.
    assert q.put_nowait(_req(SLO_INTERACTIVE, 6)) is b1
    with pytest.raises(queue.Full):
        q.put_nowait(_req(SLO_INTERACTIVE, 7))
    assert q.qsize() == 3


class _GatedEngine:
    """Engine stub whose first dispatch blocks until released, tagging
    dispatch order by the obs fill value."""

    max_bucket = 8

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Event()
        self.order = []

    def plan(self, n):
        return [self.max_bucket]

    def act(self, obs, deterministic=True, nn_params=None):
        self.entered.set()
        assert self.release.wait(30.0)
        self.order.append(int(obs[0, 0]))
        return np.zeros((obs.shape[0], 2), np.float32)


def test_scheduler_preempts_batch_for_interactive_under_backpressure():
    """End-to-end SLO-class contract through the scheduler: with the
    worker wedged and the queue full of batch work, interactive
    arrivals are admitted (never rejected while batch is queued), the
    evicted batch futures fail with the standard retryable
    backpressure, and the queue drains interactive-first."""
    engine = _GatedEngine()
    sched = MicroBatchScheduler(engine, max_queue=3, window_ms=0.0)
    with sched:
        blocker = sched.submit(
            np.full((1, OBS_DIM), 99.0, np.float32), timeout_s=30.0
        )
        assert engine.entered.wait(10.0)  # worker is mid-dispatch
        batch_futs = [
            sched.submit(
                np.full((1, OBS_DIM), 200.0 + i, np.float32),
                timeout_s=30.0,
                slo_class="batch",
            )
            for i in range(3)
        ]
        # Queue full of batch work: interactive is still admitted —
        # newest batch requests yield, newest-first.
        inter_futs = [
            sched.submit(
                np.full((1, OBS_DIM), 100.0 + i, np.float32),
                timeout_s=30.0,
            )
            for i in range(2)
        ]
        preempted = [f for f in batch_futs if f.done()]
        assert len(preempted) == 2
        for f in (batch_futs[2], batch_futs[1]):
            assert isinstance(f.exception(0), BackpressureError)
        assert f.exception(0).retry_after_s >= 0.0
        assert sched.metrics.preempted_total == 2
        engine.release.set()
        blocker.result(30.0)
        for f in inter_futs:
            f.result(30.0)
        batch_futs[0].result(30.0)
    # The surviving batch request (200) dispatched AFTER both
    # interactive requests despite enqueueing first.
    assert engine.order[0] == 99
    assert engine.order[1:3] == [100, 101]
    assert engine.order[3] == 200


def test_building_a_sharded_engine_never_invalidates_a_warmed_engine():
    """Construction-order pin: a replicated engine warmed BEFORE the
    process's first mesh-sharded engine exists must keep serving without
    retraces after one is built. jax config values key the jit cache, so
    nothing the sharded stack imports lazily (``parallel.mesh``, ...) may
    change a config value at import. Run in a fresh interpreter: this
    suite has already imported everything, which would mask the
    ordering."""
    import subprocess
    import sys

    code = """
import numpy as np
from marl_distributedformation_tpu.compat.policy import LoadedPolicy
from marl_distributedformation_tpu.models import MLPActorCritic
from marl_distributedformation_tpu.serving import (
    BucketedPolicyEngine, ShardedPolicyEngine,
)
import jax, jax.numpy as jnp

model = MLPActorCritic(act_dim=2)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
policy = LoadedPolicy(dict(variables))
replicated = BucketedPolicyEngine(policy, buckets=(8,))
obs = np.ones((4, 8), np.float32)
replicated.act(obs)  # warm: the rung's one budgeted trace

from marl_distributedformation_tpu.parallel.mesh import make_mesh
sharded = ShardedPolicyEngine(policy, make_mesh({"dp": 2}), buckets=(8,))
sharded.act(obs)

replicated.act(obs)  # would RetraceError if the build flipped config
assert replicated.compile_counts() == {8: 1}, replicated.compile_counts()
print("OK")
"""
    env = {
        **__import__("os").environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
        cwd=str(__import__("pathlib").Path(__file__).parent.parent),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_autotuner_zeroes_the_dedicated_lanes_window():
    """A routing floor that fills the slice's smallest rung on arrival
    earns window 0 for that lane (nothing to coalesce — waiting is pure
    latency); a floor below the rung (partial-rung requests pad up)
    keeps the global window."""
    trace = synthetic_trace(
        2.0, 200.0, seed=3, size_mix=((1, 0.5), (8, 0.3), (512, 0.2))
    )
    filled = autotune_ladder(
        trace, p95_target_ms=50.0, mesh_divisor=2, sharded_min_rows=512
    )
    assert filled.sharded_buckets and min(filled.sharded_buckets) == 512
    assert filled.sharded_window_ms == 0.0
    partial = autotune_ladder(
        trace, p95_target_ms=50.0, mesh_divisor=2, sharded_min_rows=100
    )
    assert partial.sharded_buckets and min(partial.sharded_buckets) > 100
    assert partial.sharded_window_ms == partial.window_ms > 0.0


def test_router_gives_the_sharded_lane_its_own_window():
    """ShardedSpec.window_ms overrides the fleet window for the slice's
    scheduler only; None inherits."""
    policy = _make_policy()
    spec = ShardedSpec(
        axis_sizes={"dp": 2}, buckets=(64,), min_rows=64, window_ms=0.0
    )
    with FleetRouter(
        policy, num_replicas=1, buckets=(1, 64), window_ms=2.0,
        sharded=spec,
    ) as router:
        by_kind = {r.kind: r for r in router.replicas}
        assert by_kind["sharded"].scheduler.window_s == 0.0
        assert by_kind["replicated"].scheduler.window_s == 0.002
