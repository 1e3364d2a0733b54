"""Fused Pallas k-NN kernel vs the XLA reference path.

Runs the kernel in interpret mode (CPU, conftest.py) and checks it
reproduces ``ops.knn.knn``'s selection, ordering, masking, and self-loop
semantics exactly. On real TPU hardware the same kernel compiles natively
(``impl="pallas"``); these tests pin its semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.env.formation import (
    compute_obs,
    reset_batch,
    step_batch,
)
from marl_distributedformation_tpu.ops import knn, knn_batch
from marl_distributedformation_tpu.ops.knn_pallas import knn_batch_pallas


def _xla_batch(points, k, valid=None):
    if valid is None:
        return jax.vmap(lambda p: knn(p, k))(points)
    return jax.vmap(lambda p, v: knn(p, k, v))(points, valid)


def _assert_matches(pallas_out, xla_out):
    idx_p, off_p, dist_p = pallas_out
    idx_x, off_x, dist_x = xla_out
    np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_x))
    np.testing.assert_allclose(
        np.asarray(off_p), np.asarray(off_x), rtol=1e-5, atol=1e-5
    )
    # Both sides now compute direct coordinate differences (the round-3
    # precision fix removed the |a|^2+|b|^2-2ab expansion from the XLA
    # path); the loose atol predates that fix and is kept for headroom.
    np.testing.assert_allclose(
        np.asarray(dist_p), np.asarray(dist_x), rtol=1e-3, atol=2e-2
    )


@pytest.mark.parametrize(
    "m,n,k", [(4, 100, 8), (3, 10, 3), (2, 130, 4), (1, 5, 2)]
)
def test_matches_xla_path(m, n, k):
    pts = jax.random.uniform(
        jax.random.PRNGKey(m * 1000 + n), (m, n, 2), minval=0.0, maxval=400.0
    )
    _assert_matches(
        knn_batch_pallas(pts, k, interpret=True), _xla_batch(pts, k)
    )


def test_matches_xla_path_with_valid_mask():
    m, n, k = 4, 20, 5
    pts = jax.random.uniform(
        jax.random.PRNGKey(7), (m, n, 2), minval=0.0, maxval=400.0
    )
    # Mix of rows with plenty of neighbors and rows short enough (<= k
    # valid agents) to force self-loop degradation.
    n_valid = jnp.array([20, 12, 5, 3])
    valid = jnp.arange(n)[None, :] < n_valid[:, None]
    _assert_matches(
        knn_batch_pallas(pts, k, valid=valid, interpret=True),
        _xla_batch(pts, k, valid=valid),
    )


def test_ascending_distance_order():
    pts = jax.random.uniform(jax.random.PRNGKey(3), (2, 50, 2)) * 100.0
    _, _, dists = knn_batch_pallas(pts, 6, interpret=True)
    d = np.asarray(dists)
    assert (np.diff(d, axis=-1) >= -1e-6).all()


def test_vmem_guard_rejects_oversized_n():
    from marl_distributedformation_tpu.ops.knn_pallas import fits_vmem

    assert fits_vmem(512) and not fits_vmem(1000)
    pts = jnp.zeros((1, 1000, 2))
    with pytest.raises(ValueError, match="VMEM"):
        knn_batch_pallas(pts, 4, interpret=True)
    # auto dispatch must quietly take the XLA path instead of exploding
    idx, _, _ = knn_batch(
        jax.random.uniform(jax.random.PRNGKey(0), (1, 1000, 2)), 4,
        impl="auto",
    )
    assert idx.shape == (1, 1000, 4)


def test_knn_batch_dispatch():
    pts = jax.random.uniform(jax.random.PRNGKey(11), (2, 30, 2)) * 50.0
    _assert_matches(
        knn_batch(pts, 4, impl="pallas_interpret"),
        knn_batch(pts, 4, impl="xla"),
    )
    with pytest.raises(AssertionError):
        knn_batch(pts, 4, impl="bogus")


@pytest.mark.slow
def test_step_batch_obs_identical_across_impls():
    """The full env step must produce identical knn observations whether the
    neighbor search runs through XLA or the Pallas kernel."""
    base = EnvParams(num_agents=16, obs_mode="knn", knn_k=4)
    key = jax.random.PRNGKey(0)
    state = reset_batch(key, base, 6)
    vel = (
        jax.random.uniform(jax.random.PRNGKey(1), (6, 16, 2)) * 2.0 - 1.0
    ) * base.max_speed

    outs = {}
    for impl in ("xla", "pallas_interpret"):
        params = base.replace(knn_impl=impl)
        next_state, tr = step_batch(state, vel, params)
        outs[impl] = (np.asarray(tr.obs), np.asarray(tr.reward))
    np.testing.assert_allclose(
        outs["xla"][0], outs["pallas_interpret"][0], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(outs["xla"][1], outs["pallas_interpret"][1])


def test_reset_obs_batch_path():
    """Batched compute_obs (ndim == 3) agrees with the per-formation path."""
    params = EnvParams(num_agents=12, obs_mode="knn", knn_k=3)
    state = reset_batch(jax.random.PRNGKey(5), params, 4)
    batched = compute_obs(state.agents, state.goal, params)
    single = jnp.stack(
        [
            compute_obs(state.agents[i], state.goal[i], params)
            for i in range(4)
        ]
    )
    np.testing.assert_allclose(
        np.asarray(batched), np.asarray(single), rtol=1e-6, atol=1e-6
    )


@pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="compiled-mode Pallas needs a real TPU backend — run "
    "`MDF_TPU_TESTS=1 pytest` (conftest opt-out) or "
    "`python tests/tpu_compiled_parity.py` / `python chip_smoke.py` on "
    "hardware",
)
def test_compiled_pallas_parity_on_tpu():
    """All three hardware legs: the north-star shape (fused, block_m=8),
    the mid-N sublane regime (fused, block_m=2 — the Mosaic (8, 128) rule
    regression gate for the singleton-axis plane layout), and the chunked
    big-N kernel. Interpret mode (the CPU tests above) does not exercise
    Mosaic lowering; this does. Single source of truth for the assertions:
    tests/tpu_compiled_parity.py."""
    from tpu_compiled_parity import run_parity, run_parity_big, run_parity_mid

    run_parity()
    run_parity_mid()
    run_parity_big()


def test_auto_dispatch_consults_spmd_guard(monkeypatch):
    """With the backend pinned to 'tpu', the auto dispatch must pick xla for
    partitioner-controlled batches and pallas for local ones — guarding the
    round-1 ADVICE-high regression at the dispatch level."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import importlib

    # ops/__init__ rebinds the name `knn` to the function, so attribute-style
    # module imports resolve to it; go through the module registry instead.
    knn_mod = importlib.import_module(
        "marl_distributedformation_tpu.ops.knn"
    )
    from marl_distributedformation_tpu.parallel import make_mesh

    monkeypatch.setattr(
        knn_mod.jax, "default_backend", lambda: "tpu"
    )
    pts = jnp.zeros((16, 12, 2))
    assert knn_mod._resolve_auto_impl(pts) == "pallas"
    mesh = make_mesh({"dp": 8})
    pts_dp = jax.device_put(pts, NamedSharding(mesh, P("dp")))
    assert knn_mod._resolve_auto_impl(pts_dp) == "xla"
    seen = []
    jax.jit(
        lambda p: seen.append(knn_mod._resolve_auto_impl(p)) or p
    )(pts_dp)
    assert seen[-1] == "xla"
    # Over the fused kernel's VMEM budget -> the chunked streaming kernel
    # (round 3); the SPMD guard still applies to it.
    big = jnp.zeros((16, 4096, 2))
    assert knn_mod._resolve_auto_impl(big) == "pallas_big"
    big_dp = jax.device_put(big, NamedSharding(mesh, P("dp")))
    assert knn_mod._resolve_auto_impl(big_dp) == "xla"


def test_xla_knn_precision():
    """Regression pin for the round-2 TPU correctness bug (VERDICT.md r2
    Weak #1): pairwise_sq_dists must NOT lower to a matmul. The old
    |a|^2+|b|^2-2a.b expansion ran the cross term through dot_general,
    which TPUs execute at bf16 input precision by default — at coordinate
    scale ~400 that corrupted 33% of neighbor indices on the chip. The
    direct broadcast form has no dot at all, so the bug class is
    structurally excluded; additionally check f64-level accuracy at the
    world-coordinate scale where the old form lost precision even in f32.
    """
    from marl_distributedformation_tpu.ops.knn import pairwise_sq_dists

    pts = jnp.asarray(
        np.random.default_rng(0).uniform(0, 400, (100, 2)), jnp.float32
    )
    jaxpr = jax.make_jaxpr(pairwise_sq_dists)(pts)
    prims = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
    assert "dot_general" not in prims, (
        "pairwise_sq_dists lowered to a matmul — on TPU this runs at bf16 "
        "input precision and corrupts the neighbor graph at world scale"
    )

    d2 = np.asarray(pairwise_sq_dists(pts), np.float64)
    p64 = np.asarray(pts, np.float64)
    ref = ((p64[:, None, :] - p64[None, :, :]) ** 2).sum(-1)
    ref[np.diag_indices(100)] += 1e12
    off_diag = ~np.eye(100, dtype=bool)
    np.testing.assert_allclose(
        d2[off_diag], ref[off_diag], rtol=1e-5, atol=1e-2
    )


class TestChunkedBigKernel:
    """knn_batch_pallas_big: the streaming kernel for N past the fused
    kernel's VMEM cliff. Interpret mode with small tiles exercises the
    multi-chunk / multi-row-block merge paths on CPU."""

    def _run(self, m, n, k, block_r=128, chunk_c=128, valid=None, seed=0):
        from marl_distributedformation_tpu.ops.knn_pallas import (
            knn_batch_pallas_big,
        )

        pts = jnp.asarray(
            np.random.default_rng(seed).uniform(0, 400, (m, n, 2)),
            jnp.float32,
        )
        got = knn_batch_pallas_big(
            pts, k, valid, block_r=block_r, chunk_c=chunk_c, interpret=True
        )
        want = knn_batch(pts, k, valid, impl="xla")
        return got, want

    @pytest.mark.parametrize(
        "m,n,k,block_r,chunk_c",
        [
            # Fast split keeps one multi-chunk and one spill case; the
            # heavier interpret-mode shapes are slow-marked (full suite +
            # the hardware gate tests/tpu_compiled_parity.py cover them).
            (3, 300, 4, 128, 128),   # 3 chunks, 3 row blocks, ragged N
            pytest.param(
                2, 700, 4, 128, 256, marks=pytest.mark.slow
            ),                       # past the fused kernel's cliff
            (1, 129, 3, 128, 128),   # barely spills into chunk 2
            pytest.param(
                4, 256, 5, 128, 128, marks=pytest.mark.slow
            ),                       # k > 4
        ],
    )
    def test_matches_xla(self, m, n, k, block_r, chunk_c):
        (gi, go, gd), (wi, wo, wd) = self._run(
            m, n, k, block_r=block_r, chunk_c=chunk_c
        )
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_allclose(
            np.asarray(gd), np.asarray(wd), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(go), np.asarray(wo), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.slow
    def test_valid_mask_and_self_loops(self):
        """Invalid points are never selected; short rows degrade to
        self-loops exactly like ops.knn.knn's valid path."""
        rng = np.random.default_rng(5)
        valid = jnp.asarray(rng.random((3, 300)) > 0.5)
        (gi, go, gd), (wi, wo, wd) = self._run(3, 300, 4, valid=valid)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_allclose(
            np.asarray(gd), np.asarray(wd), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.slow
    def test_tie_breaking_matches_top_k(self):
        """Duplicate coordinates force distance ties; selection must match
        lax.top_k's stable lower-index preference bit-for-bit."""
        from marl_distributedformation_tpu.ops.knn_pallas import (
            knn_batch_pallas_big,
        )

        base = np.random.default_rng(9).uniform(0, 400, (2, 40, 2))
        pts = np.tile(base, (1, 8, 1))  # every point duplicated 8x -> 320
        pts = jnp.asarray(pts, jnp.float32)
        gi, _, gd = knn_batch_pallas_big(
            pts, 4, block_r=128, chunk_c=128, interpret=True
        )
        wi, _, wd = knn_batch(pts, 4, impl="xla")
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_allclose(
            np.asarray(gd), np.asarray(wd), rtol=1e-6, atol=1e-6
        )

    def test_auto_dispatch_selects_big_kernel(self, monkeypatch):
        import importlib

        knn_mod = importlib.import_module(
            "marl_distributedformation_tpu.ops.knn"
        )
        monkeypatch.setattr(knn_mod.jax, "default_backend", lambda: "tpu")
        assert knn_mod._resolve_auto_impl(jnp.zeros((4, 100, 2))) == "pallas"
        assert (
            knn_mod._resolve_auto_impl(jnp.zeros((4, 641, 2)))
            == "pallas_big"
        )
        assert (
            knn_mod._resolve_auto_impl(jnp.zeros((4, 4096, 2)))
            == "pallas_big"
        )
        # Past the compile-time cap (static chunk unroll), auto falls back.
        assert (
            knn_mod._resolve_auto_impl(jnp.zeros((1, 20000, 2))) == "xla"
        )


    @pytest.mark.slow
    def test_displaced_tie_keeps_top_k_order(self):
        """Regression for the bubble-insert tie bug: a best list holding
        two equal-distance neighbors (lower column first) must keep that
        order when a CLOSER candidate from a later chunk displaces the
        list — a strict '<' insert would trap the displaced lower-column
        element behind its equal."""
        from marl_distributedformation_tpu.ops.knn_pallas import (
            knn_batch_pallas_big,
        )

        n = 300
        pts = np.full((1, n, 2), 1e4, np.float32)
        pts[0, 0] = (0.0, 0.0)       # query
        pts[0, 5] = (10.0, 0.0)      # tie A (dist 10), chunk 0
        pts[0, 9] = (0.0, 10.0)      # tie B (dist 10), chunk 0
        pts[0, 200] = (1.0, 0.0)     # closer, chunk 1 -> displaces
        pts = jnp.asarray(pts)
        gi, _, gd = knn_batch_pallas_big(
            pts, 3, block_r=128, chunk_c=128, interpret=True
        )
        wi, _, wd = knn_batch(pts, 3, impl="xla")
        assert wi[0, 0].tolist() == [200, 5, 9]  # top_k stable order
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_allclose(
            np.asarray(gd), np.asarray(wd), rtol=1e-6, atol=1e-6
        )
