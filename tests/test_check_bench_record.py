"""The bench-record evidence gate (scripts/check_bench_record.py)."""

from __future__ import annotations

import pathlib

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_check_bench_record_gates():
    """The shared evidence gate (scripts/check_bench_record.py) rejects
    non-TPU/error/degraded records and missing fields, passes clean ones."""
    import sys

    sys.path.insert(0, str(REPO / "scripts"))
    try:
        from check_bench_record import check
    finally:
        sys.path.pop(0)

    clean = {
        "metric": "m", "platform": "tpu", "value": 1.0,
        "knn_impl": "pallas", "knn_env_steps_per_sec": 5.0,
    }
    assert check(clean, ["value", "knn_env_steps_per_sec"],
                 ["knn_impl=pallas"]) == []
    assert check({**clean, "platform": "gpu"}, [], [])
    assert check({**clean, "platform": "cpu"}, [], [])
    assert check({**clean, "error": "watchdog"}, [], [])
    assert check({**clean, "notes": "train phase skipped: deadline"}, [], [])
    assert check({**clean, "notes": "knn phase failed: X"}, [], [])
    assert check(clean, ["train_env_steps_per_sec"], [])  # absent field
    assert check({**clean, "value": 0.0}, ["value"], [])  # zero rate
    assert check(clean, [], ["knn_impl=xla"])  # impl mismatch
    # Obs tracing fields (bench phase 8), validated whenever present:
    # overhead must be a finite number; the span breakdown must be a
    # numeric stage dict whose sum stays within the latency + tolerance.
    assert check({**clean, "tracing_overhead_pct": 1.7}, [], []) == []
    assert check({**clean, "tracing_overhead_pct": -0.4}, [], []) == []
    assert check({**clean, "tracing_overhead_pct": float("inf")}, [], [])
    assert check({**clean, "tracing_overhead_pct": "fast"}, [], [])
    pipeline_ok = {
        **clean,
        "promotion_latency_s_p50": 2.0, "promotion_latency_s_p95": 3.0,
        "gate_eval_steps_per_sec": 100.0, "pipeline_gate_compiles": 1,
    }
    breakdown = {
        "stream_poll_s": 1.0, "gate_eval_s": 0.8, "publish_s": 0.01,
        "barrier_commit_s": 0.15, "first_serve_s": 0.04,
    }
    assert check(
        {**pipeline_ok, "promotion_span_breakdown": breakdown}, [], []
    ) == []
    assert check(  # stages sum past p95 + tolerance: double counting
        {**pipeline_ok,
         "promotion_span_breakdown": {**breakdown, "stream_poll_s": 9.0}},
        [], [],
    )
    # deferred_wait_s is p50'd over ONLY deferred promotions — a few
    # long defers among many fast promotions may dwarf the all-promotion
    # latency p95 on a healthy run, so it stays out of the sum check.
    assert check(
        {**pipeline_ok,
         "promotion_span_breakdown": {**breakdown, "deferred_wait_s": 30.0}},
        [], [],
    ) == []
    assert check(
        {**pipeline_ok, "promotion_span_breakdown": {}}, [], []
    )
    assert check(
        {**pipeline_ok,
         "promotion_span_breakdown": {"gate_eval_s": "slow"}},
        [], [],
    )
    assert check(
        {**pipeline_ok,
         "promotion_span_breakdown": {"gate_eval_s": -1.0}},
        [], [],
    )
    # SLO serving fields (bench phase 9), validated whenever the
    # req/s-at-SLO headline is present: positive rate and 512-rung
    # percentiles, finite bf16 delta (negative legitimate on CPU),
    # budget-1 compile receipts.
    slo_ok = {
        **clean,
        "serving_req_per_sec_at_p95_slo": 462.0,
        "serving_sharded_512_p95_ms": 27.7,
        "serving_replicated_512_p95_ms": 57.3,
        "serving_bf16_speedup_pct": -20.0,
        "serving_slo_max_compiles_per_rung": 1,
    }
    assert check(slo_ok, [], []) == []
    assert check({**slo_ok, "serving_req_per_sec_at_p95_slo": 0.0}, [], [])
    assert check({**slo_ok, "serving_sharded_512_p95_ms": 0.0}, [], [])
    assert check(
        {**slo_ok, "serving_bf16_speedup_pct": float("nan")}, [], []
    )
    assert check(
        {**slo_ok, "serving_slo_max_compiles_per_rung": 2}, [], []
    )
    # Adversarial-robustness fields (bench phase 10), validated whenever
    # the search throughput is present: positive rate, budget-1 search
    # compiles, finite worst-case gap (negative legitimate — bench-sized
    # training makes the curriculum payoff directional).
    adv_ok = {
        **clean,
        "adversarial_candidates_per_sec": 42.0,
        "adversarial_search_compiles": 1,
        "worst_case_return_gap_pct": 5.2,
    }
    assert check(adv_ok, [], []) == []
    assert check({**adv_ok, "worst_case_return_gap_pct": -3.0}, [], []) == []
    assert check({**adv_ok, "adversarial_candidates_per_sec": 0.0}, [], [])
    assert check({**adv_ok, "adversarial_search_compiles": 2}, [], [])
    assert check(
        {**adv_ok, "worst_case_return_gap_pct": float("nan")}, [], []
    )
    assert check(
        {**adv_ok, "worst_case_return_gap_pct": "better"}, [], []
    )
    # BENCH_SKIP_* sentinel: "skipped" in a rate field is structurally
    # absent (no SLO validation fires), but --require rejects it with
    # the explicit not-run reason instead of a generic type error.
    skipped = {**clean, "serving_req_per_sec_at_p95_slo": "skipped"}
    assert check(skipped, [], []) == []
    problems = check(skipped, ["serving_req_per_sec_at_p95_slo"], [])
    assert problems and "explicitly skipped" in problems[0]
    adv_skipped = {
        **clean,
        "adversarial_candidates_per_sec": "skipped",
        "adversarial_search_compiles": "skipped",
        "worst_case_return_gap_pct": "skipped",
    }
    assert check(adv_skipped, [], []) == []
    # Live-metrics-plane fields (bench phase 11), validated whenever
    # present: finite telemetry overhead (negative legitimate — noise
    # around zero is the expected result), positive sentinel poll rate,
    # "skipped" sentinels structurally absent.
    tel_ok = {
        **clean,
        "telemetry_overhead_pct": -0.1,
        "sentinel_checks_per_sec": 87488.7,
    }
    assert check(tel_ok, [], []) == []
    assert check({**tel_ok, "telemetry_overhead_pct": float("nan")}, [], [])
    assert check({**tel_ok, "telemetry_overhead_pct": "cheap"}, [], [])
    assert check({**tel_ok, "sentinel_checks_per_sec": 0.0}, [], [])
    assert check({**tel_ok, "sentinel_checks_per_sec": "many"}, [], [])
    assert check(
        {
            **clean,
            "telemetry_overhead_pct": "skipped",
            "sentinel_checks_per_sec": "skipped",
        },
        [], [],
    ) == []
    # Chaos-plane fields (bench phase 12), validated whenever present:
    # violations must be exactly 0, MTTR finite and > 0, the
    # disabled-plane overhead finite and under the 5% bar (negative is
    # legitimate — noise around zero), "skipped" sentinels honored.
    chaos_ok = {
        **clean,
        "chaos_invariant_violations": 0,
        "chaos_mttr_s": 0.8,
        "fault_plane_overhead_pct": -0.2,
    }
    assert check(chaos_ok, [], []) == []
    assert check({**chaos_ok, "chaos_invariant_violations": 1}, [], [])
    assert check({**chaos_ok, "chaos_invariant_violations": "none"}, [], [])
    assert check({**chaos_ok, "chaos_mttr_s": 0.0}, [], [])
    assert check({**chaos_ok, "chaos_mttr_s": float("inf")}, [], [])
    assert check({**chaos_ok, "chaos_mttr_s": "fast"}, [], [])
    assert check({**chaos_ok, "fault_plane_overhead_pct": 7.5}, [], [])
    assert check(
        {**chaos_ok, "fault_plane_overhead_pct": float("nan")}, [], []
    )
    assert check(
        {
            **clean,
            "chaos_invariant_violations": "skipped",
            "chaos_mttr_s": "skipped",
            "fault_plane_overhead_pct": "skipped",
        },
        [], [],
    ) == []
    # Program-ledger fields (bench phase 13), validated whenever
    # present: enabled-ledger overhead finite and under the 5% bar
    # (negative legitimate — noise around zero), a census with at
    # least one program, finite non-negative total compile seconds,
    # "skipped" sentinels structurally absent.
    ledger_ok = {
        **clean,
        "ledger_overhead_pct": 0.8,
        "ledger_program_count": 11,
        "ledger_compile_seconds_total": 42.7,
    }
    assert check(ledger_ok, [], []) == []
    assert check({**ledger_ok, "ledger_overhead_pct": -0.3}, [], []) == []
    assert check({**ledger_ok, "ledger_overhead_pct": 6.1}, [], [])
    assert check(
        {**ledger_ok, "ledger_overhead_pct": float("inf")}, [], []
    )
    assert check({**ledger_ok, "ledger_overhead_pct": "cheap"}, [], [])
    assert check({**ledger_ok, "ledger_program_count": 0}, [], [])
    assert check({**ledger_ok, "ledger_program_count": "many"}, [], [])
    assert check(
        {**ledger_ok, "ledger_compile_seconds_total": -2.0}, [], []
    )
    assert check(
        {**ledger_ok, "ledger_compile_seconds_total": float("nan")},
        [], [],
    )
    assert check(
        {
            **clean,
            "ledger_overhead_pct": "skipped",
            "ledger_program_count": "skipped",
            "ledger_compile_seconds_total": "skipped",
        },
        [], [],
    ) == []
    # Mesh-tier fields (bench phase 14), validated whenever present:
    # throughput finite > 0, swap latency percentiles finite > 0 and
    # ordered, failover-lost EXACTLY 0, per-host compile receipts at
    # most 1, "skipped" sentinels honored.
    mesh_ok = {
        **clean,
        "mesh_req_per_sec": 412.0,
        "mesh_global_swap_latency_s_p50": 0.03,
        "mesh_global_swap_latency_s_p95": 0.09,
        "mesh_failover_lost_requests": 0,
        "mesh_host_compile_receipts_max": 1.0,
    }
    assert check(mesh_ok, [], []) == []
    assert check({**mesh_ok, "mesh_req_per_sec": 0.0}, [], [])
    assert check({**mesh_ok, "mesh_req_per_sec": "fast"}, [], [])
    assert check(
        {**mesh_ok, "mesh_global_swap_latency_s_p50": 0.0}, [], []
    )
    assert check(
        {**mesh_ok, "mesh_global_swap_latency_s_p95": float("inf")},
        [], [],
    )
    assert check(  # percentile order violated
        {
            **mesh_ok,
            "mesh_global_swap_latency_s_p50": 0.2,
            "mesh_global_swap_latency_s_p95": 0.1,
        },
        [], [],
    )
    assert check({**mesh_ok, "mesh_failover_lost_requests": 1}, [], [])
    assert check(
        {**mesh_ok, "mesh_failover_lost_requests": "none"}, [], []
    )
    assert check({**mesh_ok, "mesh_step_violations": 0}, [], []) == []
    assert check({**mesh_ok, "mesh_step_violations": 2}, [], [])
    assert check(
        {**mesh_ok, "mesh_host_compile_receipts_max": 2.0}, [], []
    )
    assert check(
        {
            **clean,
            "mesh_req_per_sec": "skipped",
            "mesh_global_swap_latency_s_p50": "skipped",
            "mesh_global_swap_latency_s_p95": "skipped",
            "mesh_failover_lost_requests": "skipped",
        },
        [], [],
    ) == []
    # Train-lane recovery fields (bench phase 15), validated whenever
    # present: health-word overhead finite under the 5% bar (negative
    # legitimate — interleave noise), recovery MTTR finite > 0, the
    # drill's divergence count >= 1 (the bench injects a bomb; zero
    # means the detector is broken), "skipped" sentinels honored.
    recovery_ok = {
        **clean,
        "health_overhead_pct": 0.7,
        "recovery_mttr_s": 0.21,
        "train_divergence_events": 1,
    }
    assert check(recovery_ok, [], []) == []
    assert check(
        {**recovery_ok, "health_overhead_pct": -0.2}, [], []
    ) == []
    assert check({**recovery_ok, "health_overhead_pct": 6.2}, [], [])
    assert check(
        {**recovery_ok, "health_overhead_pct": float("nan")}, [], []
    )
    assert check({**recovery_ok, "health_overhead_pct": "cheap"}, [], [])
    assert check({**recovery_ok, "recovery_mttr_s": 0.0}, [], [])
    assert check(
        {**recovery_ok, "recovery_mttr_s": float("inf")}, [], []
    )
    assert check({**recovery_ok, "recovery_mttr_s": "fast"}, [], [])
    assert check({**recovery_ok, "train_divergence_events": 0}, [], [])
    assert check(
        {**recovery_ok, "train_divergence_events": "some"}, [], []
    )
    assert check(
        {
            **clean,
            "health_overhead_pct": "skipped",
            "recovery_mttr_s": "skipped",
            "train_divergence_events": "skipped",
        },
        [], [],
    ) == []
    # graftlint wall (bench phase 16), validated whenever present:
    # finite positive and under the static ceiling (the engine's
    # package-global analyses must not go super-linear).
    assert check({**clean, "graftlint_wall_s": 4.7}, [], []) == []
    assert check({**clean, "graftlint_wall_s": 0.0}, [], [])
    assert check({**clean, "graftlint_wall_s": -1.0}, [], [])
    assert check({**clean, "graftlint_wall_s": float("nan")}, [], [])
    assert check({**clean, "graftlint_wall_s": float("inf")}, [], [])
    assert check({**clean, "graftlint_wall_s": 500.0}, [], [])
    assert check({**clean, "graftlint_wall_s": "slow"}, [], [])
    assert check({**clean, "graftlint_wall_s": "skipped"}, [], []) == []
    # Registered-env ladder fields (bench phase 1d), validated whenever
    # present: both per-env rates finite positive AND recorded together
    # (a lone rate means the ladder died mid-loop), obstacle overhead a
    # finite number in [0, 100], "skipped" sentinels honored.
    envs_ok = {
        **clean,
        "env_steps_per_sec_formation": 1.6e6,
        "env_steps_per_sec_pursuit_evasion": 1.5e6,
        "obstacle_overhead_pct": 12.3,
    }
    assert check(envs_ok, [], []) == []
    assert check({**envs_ok, "env_steps_per_sec_formation": 0.0}, [], [])
    assert check(
        {**envs_ok, "env_steps_per_sec_pursuit_evasion": "fast"}, [], []
    )
    lone = dict(envs_ok)
    del lone["env_steps_per_sec_pursuit_evasion"]
    assert check(lone, [], [])  # ladder died mid-loop
    assert check({**envs_ok, "obstacle_overhead_pct": -3.0}, [], [])
    assert check({**envs_ok, "obstacle_overhead_pct": 101.0}, [], [])
    assert check(
        {**envs_ok, "obstacle_overhead_pct": float("nan")}, [], []
    )
    assert check({**envs_ok, "obstacle_overhead_pct": "cheap"}, [], [])
    assert check(
        {
            **clean,
            "env_steps_per_sec_formation": "skipped",
            "env_steps_per_sec_pursuit_evasion": "skipped",
            "obstacle_overhead_pct": "skipped",
        },
        [], [],
    ) == []
    # Multi-tenant serving fields (serving/tenancy), validated whenever
    # present: isolation ratio finite >= 1 beside per-tenant rates,
    # every lane rate finite positive, per-lane step monotonicity
    # violations exactly 0, shared_rung_compiles EXACTLY 1 per
    # (arch, rung) — 0 = never warmed, 2+ = a lane retraced instead of
    # sharing the executable.
    tenancy_ok = {
        **clean,
        "tenant_isolation_p95_ratio": 1.4,
        "model_formation-a__requests_per_sec": 120.0,
        "model_formation-b__requests_per_sec": 115.0,
        "model_pursuit__requests_per_sec": 98.0,
        "model_formation-a__step_monotonic_violations": 0,
        "shared_rung_compiles": {
            "MLPActorCritic_h8x8_obs6_act2:rung1": 1,
            "MLPActorCritic_h8x8_obs6_act2:rung8": 1,
            "GNNActorCritic_h8x8_obs9_act2:rung1": 1,
        },
    }
    assert check(tenancy_ok, [], []) == []
    assert check(
        {**tenancy_ok, "tenant_isolation_p95_ratio": 0.3}, [], []
    )
    assert check(
        {**tenancy_ok, "tenant_isolation_p95_ratio": float("inf")}, [], []
    )
    assert check(
        {**tenancy_ok, "tenant_isolation_p95_ratio": "isolated"}, [], []
    )
    assert check(  # ratio with no lane rates beside it
        {**clean, "tenant_isolation_p95_ratio": 1.1}, [], []
    )
    assert check(
        {**tenancy_ok, "model_pursuit__requests_per_sec": 0.0}, [], []
    )
    assert check(
        {**tenancy_ok, "model_pursuit__requests_per_sec": "fast"}, [], []
    )
    assert check(
        {**tenancy_ok, "model_formation-a__step_monotonic_violations": 2},
        [], [],
    )
    assert check({**tenancy_ok, "shared_rung_compiles": {}}, [], [])
    assert check(
        {**tenancy_ok, "shared_rung_compiles": "one each"}, [], []
    )
    bad_shared = dict(tenancy_ok["shared_rung_compiles"])
    bad_shared["MLPActorCritic_h8x8_obs6_act2:rung1"] = 2  # retrace
    assert check(
        {**tenancy_ok, "shared_rung_compiles": bad_shared}, [], []
    )
    bad_shared["MLPActorCritic_h8x8_obs6_act2:rung1"] = 0  # never warmed
    assert check(
        {**tenancy_ok, "shared_rung_compiles": bad_shared}, [], []
    )
    # Skipped sentinels honored across the tenancy fields.
    assert check(
        {
            **clean,
            "tenant_isolation_p95_ratio": "skipped",
            "model_formation-a__requests_per_sec": "skipped",
            "shared_rung_compiles": "skipped",
        },
        [], [],
    ) == []
    # Elastic-capacity fields (serving/elastic, bench phase "elastic"),
    # validated whenever present: both storm-half rates finite
    # positive, the re-split pause bounded in (0, 250] ms beside a
    # committed re-split, prewarm compiles >= 1 beside a zero census
    # diff (every compile attributed to prewarm, never the request
    # path), budget-1 receipts per rung.
    elastic_ok = {
        **clean,
        "serving_req_per_sec_at_p95_slo_elastic": 1440.0,
        "serving_req_per_sec_at_p95_slo_static": 141.2,
        "elastic_resplit_pause_ms": 0.049,
        "elastic_resplits_committed": 2,
        "elastic_prewarm_compiles": 7,
        "elastic_storm_new_programs": 0,
        "elastic_max_compiles_per_rung": 1,
    }
    assert check(elastic_ok, [], []) == []
    assert check(
        {**elastic_ok, "serving_req_per_sec_at_p95_slo_elastic": 0.0},
        [], [],
    )
    assert check(
        {
            **elastic_ok,
            "serving_req_per_sec_at_p95_slo_static": float("nan"),
        },
        [], [],
    )
    assert check(
        {**elastic_ok, "serving_req_per_sec_at_p95_slo_elastic": "fast"},
        [], [],
    )
    assert check({**elastic_ok, "elastic_resplit_pause_ms": 0.0}, [], [])
    assert check(
        {**elastic_ok, "elastic_resplit_pause_ms": 900.0}, [], []
    )
    assert check(
        {**elastic_ok, "elastic_resplit_pause_ms": "quick"}, [], []
    )
    assert check(  # pause with nothing committed beside it
        {**elastic_ok, "elastic_resplits_committed": 0}, [], []
    )
    assert check({**elastic_ok, "elastic_prewarm_compiles": 0}, [], [])
    assert check(  # a compile leaked onto the measured storm path
        {**elastic_ok, "elastic_storm_new_programs": 3}, [], []
    )
    assert check(  # a rung retraced after warm-up
        {**elastic_ok, "elastic_max_compiles_per_rung": 2}, [], []
    )
    # Skipped sentinels honored across the elastic fields.
    assert check(
        {
            **clean,
            "serving_req_per_sec_at_p95_slo_elastic": "skipped",
            "serving_req_per_sec_at_p95_slo_static": "skipped",
            "elastic_resplit_pause_ms": "skipped",
            "elastic_prewarm_compiles": "skipped",
        },
        [], [],
    ) == []


def test_load_record_takes_the_last_metric_line(tmp_path):
    """bench.py prints log lines, then exactly one JSON record."""
    import json
    import sys

    import pytest

    sys.path.insert(0, str(REPO / "scripts"))
    try:
        from check_bench_record import load_record
    finally:
        sys.path.pop(0)

    src = tmp_path / "bench.out"
    src.write_text(
        "[bench] device: platform=tpu\n"
        + json.dumps({"not": "a record"}) + "\n"
        + json.dumps({"metric": "m", "platform": "tpu", "value": 2.0}) + "\n"
    )
    assert load_record(src)["value"] == 2.0
    src.write_text("no json here\n")
    with pytest.raises(SystemExit):
        load_record(src)
