"""Multi-tenant serving contract (tier-1, multi-device CPU): named
model lanes over ONE fleet.

The acceptance pins from the tenancy ISSUE live here, on the
8-virtual-device CPU mesh tests/conftest.py provisions:

- two same-arch formation lanes + one pursuit_evasion lane serve from
  ONE ``TenantFleet``; a batch storm on lane A leaves lane B's
  interactive traffic unrejected and per-lane step-monotonic;
- the ledger census shows shared rung executables — <= 1 compile per
  (arch, rung): same-arch lanes ride one set of compiled rungs
  (params are traced inputs), the distinct arch pays exactly its own
  budget-1 compile;
- a mid-storm coordinated swap of ONE lane commits (its served step
  advances, monotonically in completion order) without pausing any
  other lane's dispatch;
- admission is per-lane: one lane's full queue quotes ITS Retry-After
  while another lane's requests are still admitted;
- the HTTP frontend speaks ``model_id`` end to end — stamped on every
  act response, 400 with a did-you-mean for unknown lanes.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.compat.policy import (  # noqa: E402
    LoadedPolicy,
)
from marl_distributedformation_tpu.models import MLPActorCritic  # noqa: E402
from marl_distributedformation_tpu.serving import (  # noqa: E402
    BackpressureError,
)
from marl_distributedformation_tpu.serving.fleet import (  # noqa: E402
    FleetFrontend,
)
from marl_distributedformation_tpu.serving.tenancy import (  # noqa: E402
    TenantDirectory,
    TenantSpec,
    TenantFleet,
    run_tenant_smoke,
    tenant_fleet_from_directory,
)
from marl_distributedformation_tpu.utils.checkpoint import (  # noqa: E402
    save_checkpoint,
)

OBS_DIM = 8  # both registered envs' default rows are 8-wide
HIDDEN = (8, 8)


def _make_policy(seed=0, hidden=HIDDEN, obs_dim=OBS_DIM):
    model = MLPActorCritic(act_dim=2, hidden=hidden)
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, obs_dim)))
    return LoadedPolicy(dict(variables), model_kwargs={"hidden": hidden})


def _write_ckpt(log_dir, step, policy):
    return save_checkpoint(
        log_dir,
        step,
        {
            "policy": type(policy.model).__name__,
            "params": policy.params,
            "num_timesteps": step,
        },
    )


def _obs(n, seed=0):
    return (
        np.random.default_rng(seed)
        .standard_normal((n, OBS_DIM))
        .astype(np.float32)
    )


def _directory(tmp_path=None):
    """Two same-arch formation lanes + one distinct-arch pursuit lane.
    With a tmp_path, each lane gets its own promoted/ dir + seed ckpt."""
    specs = [
        TenantSpec(model_id="formation-a", env="formation", hidden=HIDDEN),
        TenantSpec(model_id="formation-b", env="formation", hidden=HIDDEN),
        TenantSpec(
            model_id="pursuit", env="pursuit_evasion", hidden=(16, 16)
        ),
    ]
    if tmp_path is None:
        return TenantDirectory(specs)
    out = []
    for i, spec in enumerate(specs):
        d = tmp_path / spec.model_id / "promoted"
        _write_ckpt(d, 100 * (i + 1), _make_policy(i, hidden=spec.hidden))
        out.append(
            TenantSpec(
                **{
                    **{
                        f.name: getattr(spec, f.name)
                        for f in spec.__dataclass_fields__.values()
                    },
                    "promoted_dir": d,
                }
            )
        )
    return TenantDirectory(out)


# ---------------------------------------------------------------------------
# Directory
# ---------------------------------------------------------------------------


def test_directory_validates_lane_declarations():
    # model_id grammar: it becomes a Prometheus label value and the
    # model_{id}__{metric} snapshot key, so "__" and junk are rejected.
    for bad in ("", "a__b", "-leading", "sp ace", "semi;colon"):
        with pytest.raises(ValueError, match="model_id"):
            TenantSpec(model_id=bad)
    with pytest.raises(ValueError, match="slo_class"):
        TenantSpec(model_id="a", slo_class="platinum")
    with pytest.raises(ValueError, match="policy"):
        TenantSpec(model_id="a", policy="TransformerXXL")
    # Misspelled env fails at DECLARATION time with the registry's
    # did-you-mean, not at first request.
    with pytest.raises(ValueError, match="did you mean 'formation'"):
        TenantSpec(model_id="a", env="fromation")
    d = TenantDirectory([TenantSpec(model_id="a")])
    with pytest.raises(ValueError, match="duplicate"):
        d.add(TenantSpec(model_id="a"))


def test_directory_lookup_and_arch_grouping():
    d = _directory()
    assert list(d) == ["formation-a", "formation-b", "pursuit"]
    with pytest.raises(KeyError, match="formation-a"):
        d.get("formation_a")  # did-you-mean names the close lane
    groups = d.arch_groups()
    assert len(groups) == 2  # two formation lanes share one signature
    sizes = sorted(len(specs) for specs in groups.values())
    assert sizes == [1, 2]
    (pursuit_arch,) = [
        arch
        for arch, specs in groups.items()
        if specs[0].model_id == "pursuit"
    ]
    assert "16x16" in pursuit_arch and "obs8" in pursuit_arch


def test_fleet_construction_is_fail_fast():
    d = _directory()
    policies = {
        "formation-a": _make_policy(0),
        "formation-b": _make_policy(1),
        "pursuit": _make_policy(2, hidden=(16, 16)),
    }
    with pytest.raises(ValueError, match="no seed policy"):
        TenantFleet(d, {k: policies[k] for k in ("formation-a", "pursuit")})
    with pytest.raises(ValueError, match="undeclared"):
        TenantFleet(d, {**policies, "ghost": _make_policy(3)})
    # A lane declaring the shared arch whose actual param tree differs
    # cannot ride the group's compiled rungs — caught at construction,
    # not as a shape crash inside a rung at first dispatch.
    with pytest.raises(ValueError, match="cannot share"):
        TenantFleet(
            d, {**policies, "formation-b": _make_policy(1, hidden=(4, 4))}
        )


# ---------------------------------------------------------------------------
# Per-lane admission
# ---------------------------------------------------------------------------


def test_admission_is_per_lane():
    """Fill lane A's admission queue; lane A's next request is rejected
    with a lane-A Retry-After while lane B is still admitted."""
    d = TenantDirectory(
        [
            TenantSpec(model_id="lane-a", hidden=HIDDEN),
            TenantSpec(model_id="lane-b", hidden=HIDDEN),
        ]
    )
    fleet = TenantFleet(
        d,
        {"lane-a": _make_policy(0), "lane-b": _make_policy(0)},
        num_replicas=1,
        buckets=(1,),
        window_ms=0.0,
        tenant_max_queue=1,
        probe_interval_s=60.0,
    )
    fleet.warmup()
    (replica,) = fleet.replicas
    orig = replica.engine.act

    def slow_act(*args, **kwargs):
        time.sleep(0.3)
        return orig(*args, **kwargs)

    replica.engine.act = slow_act
    with fleet:
        in_flight = fleet.submit(_obs(1, seed=0), model_id="lane-a")
        time.sleep(0.05)  # worker picks it up and blocks in slow_act
        queued = fleet.submit(_obs(1, seed=1), model_id="lane-a")
        with pytest.raises(BackpressureError) as exc:
            fleet.submit(_obs(1, seed=2), model_id="lane-a")
        assert exc.value.retry_after_s > 0.0
        # Lane B's queue is untouched: still admitted, still served.
        other = fleet.submit(_obs(1, seed=3), model_id="lane-b")
        for fut in (in_flight, queued, other):
            assert fut.result(timeout=30).actions.shape == (1, 2)
        snap = fleet.snapshot()
        assert snap["model_lane-a__rejected_total"] == 1.0
        assert snap["model_lane-b__rejected_total"] == 0.0
        # model_id is required on a tenant fleet, and stamped on results.
        with pytest.raises(ValueError, match="model_id"):
            fleet.submit(_obs(1, seed=4))
        res = fleet.submit(_obs(1, seed=5), model_id="lane-b").result(
            timeout=30
        )
        assert res.model_id == "lane-b"


# ---------------------------------------------------------------------------
# The acceptance e2e: isolation + shared executables + mid-storm swap
# ---------------------------------------------------------------------------


def test_tenant_storm_isolation_shared_rungs_and_midstorm_swap(tmp_path):
    """Two same-arch formation lanes + one pursuit lane from ONE fleet:
    a batch storm on formation-a leaves the quiet lanes unrejected and
    step-monotonic; mid-storm, formation-a's coordinator commits a new
    checkpoint (its step advances monotonically) without pausing the
    other lanes; and the compile census shows <= 1 compile per
    (arch, rung) — the executable-sharing receipt."""
    d = _directory(tmp_path)
    fleet = tenant_fleet_from_directory(
        d,
        num_replicas=2,
        buckets=(1, 8),
        watch=False,  # the swap below is driven by hand, mid-storm
    )
    coord = fleet.coordinators["formation-a"]
    swap = {"committed": False}

    def mid_storm():
        _write_ckpt(
            d.get("formation-a").promoted_dir, 150, _make_policy(7)
        )
        swap["committed"] = coord.refresh()

    with fleet:
        report = run_tenant_smoke(
            fleet,
            sizes=(1, 3, 8),
            duration_s=2.0,
            clients_per_lane=2,
            storm_lane="formation-a",
            storm_clients=3,
            mid_storm=mid_storm,
            mid_storm_at_s=0.2,
        )

    assert swap["committed"], "mid-storm swap of formation-a must commit"
    assert coord.last_commit["model_id"] == "formation-a"
    for mid in ("formation-a", "formation-b", "pursuit"):
        assert report[f"model_{mid}__requests_ok"] > 0, report
        assert report[f"model_{mid}__step_monotonic_violations"] == 0.0
    # The quiet lanes never saw the storm: zero rejections, steps flat.
    for mid, step in (("formation-b", 200.0), ("pursuit", 300.0)):
        assert report[f"model_{mid}__rejected"] == 0.0
        assert report[f"model_{mid}__step_min"] == step
        assert report[f"model_{mid}__step_max"] == step
    # The swapped lane's step advanced 100 -> 150, monotonically (the
    # violations pin above covers completion order).
    assert report["model_formation-a__step_min"] == 100.0
    assert report["model_formation-a__step_max"] == 150.0
    assert report["tenant_isolation_p95_ratio"] >= 1.0
    assert np.isfinite(report["tenant_isolation_p95_ratio"])
    # Executable sharing: <= 1 compile per (arch, rung) across BOTH
    # arch groups — two formation lanes rode one set of rungs, and
    # pursuit paid exactly its own.
    shared = report["shared_rung_compiles"]
    assert len(shared) == 4  # 2 arch groups x 2 rungs
    assert all(count == 1 for count in shared.values()), shared


# ---------------------------------------------------------------------------
# HTTP frontend over a tenant fleet
# ---------------------------------------------------------------------------


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url + "/v1/act",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def test_frontend_speaks_model_id_end_to_end():
    d = TenantDirectory(
        [
            TenantSpec(model_id="lane-a", hidden=HIDDEN),
            TenantSpec(model_id="lane-b", hidden=HIDDEN),
        ]
    )
    policies = {"lane-a": _make_policy(0), "lane-b": _make_policy(1)}
    fleet = TenantFleet(
        d,
        policies,
        steps={"lane-a": 11, "lane-b": 22},
        num_replicas=2,
        buckets=(1, 8),
    )
    fleet.warmup()
    obs = _obs(3, seed=9)
    with fleet, FleetFrontend(fleet, port=0) as frontend:
        for mid, step in (("lane-a", 11), ("lane-b", 22)):
            body = _post(
                frontend.url, {"obs": obs.tolist(), "model_id": mid}
            )
            ref, _ = policies[mid].predict(obs, deterministic=True)
            np.testing.assert_allclose(
                np.asarray(body["actions"], np.float32), ref,
                rtol=1e-5, atol=1e-6,
            )
            assert body["model_id"] == mid
            assert body["model_step"] == step
        # Distinct lanes really answered with distinct params.
        a, _ = policies["lane-a"].predict(obs, deterministic=True)
        b, _ = policies["lane-b"].predict(obs, deterministic=True)
        assert not np.allclose(a, b)
        # Missing model_id on a tenant fleet -> 400 naming the lanes;
        # unknown lane -> 400 with the did-you-mean hint.
        for payload, needle in (
            ({"obs": obs.tolist()}, "model_id is required"),
            ({"obs": obs.tolist(), "model_id": "lane_a"}, "did you mean"),
        ):
            try:
                _post(frontend.url, payload)
                raise AssertionError("expected 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert needle in json.loads(e.read())["error"]
        # Health exposes per-lane steps, each monotonic on its own.
        health = json.loads(
            urllib.request.urlopen(
                frontend.url + "/v1/health", timeout=10
            ).read()
        )
        assert health["model_steps"] == {"lane-a": 11, "lane-b": 22}
        assert health["model_step"] == 22
        # The metrics scrape folds lanes into model-labeled families.
        req = urllib.request.Request(
            frontend.url + "/v1/metrics",
            headers={"Accept": "text/plain"},
        )
        text = urllib.request.urlopen(req, timeout=10).read().decode()
        assert 'marl_model_step{model="lane-a"} 11.0' in text
        assert 'marl_model_step{model="lane-b"} 22.0' in text
