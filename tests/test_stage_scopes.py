"""The names the program gives its stages (``utils.profiling.DEVICE_SCOPES``,
``KERNEL_NAMES``, ``HOST_SPANS``) reach where the measurement reads them:
the compiled iteration's ``op_name`` metadata, the Pallas kernels' names,
the profiler's host plane, and the benchmark's per-layer readers."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness, host_spans
from marl_distributedformation_tpu.algo import PPOConfig
from marl_distributedformation_tpu.env import EnvParams
from marl_distributedformation_tpu.models import GNNActorCritic, TrunkActorCritic
from marl_distributedformation_tpu.models.trunk import load_trunk_arch
from marl_distributedformation_tpu.ops.knn_pallas import (
    knn_batch_pallas,
    knn_batch_pallas_big,
)
from marl_distributedformation_tpu.ops.sparse_attention import sparse_flash_attention
from marl_distributedformation_tpu.train import TrainConfig, Trainer
from marl_distributedformation_tpu.utils.profiling import (
    DEVICE_SCOPES,
    HOST_SPANS,
    KERNEL_NAMES,
)

ROOT = Path(__file__).resolve().parents[1]
# The scope each stage is opened under; None at the iteration's top level.
PARENTS = {
    "rollout": (None,),
    "policy": ("rollout",),
    "env_step": ("rollout",),
    "gae": (None,),
    "ppo_update": (None,),
    "epoch_shuffle": ("ppo_update",),
    "minibatch_gather": ("ppo_update",),
    "row_pack": ("minibatch_gather",),
    "subrow_pick": ("minibatch_gather",),
    "loss_and_grad": ("ppo_update",),
    "optimizer_step": ("ppo_update",),
    "neighbor_gather": ("policy", "loss_and_grad"),
    "trunk_attention": ("policy", "loss_and_grad"),
    "trunk_indexer": ("policy", "loss_and_grad"),
    "trunk_moe": ("policy", "loss_and_grad"),
    "router": ("trunk_moe",),
    "routed_experts": ("trunk_moe",),
    "shared_expert": ("trunk_moe",),
    "trunk_gated_attention": ("policy", "loss_and_grad"),
    "trunk_kda": ("policy", "loss_and_grad"),
    "kda_recurrence": ("trunk_kda",),
    "trunk_mla": ("policy", "loss_and_grad"),
    "mla_softmax": ("trunk_mla",),
    # read before each sublayer; written where the sublayer's part is added:
    # under the mixer's stage, the dense layer's, the expert layer's two
    "trunk_residual": (
        "policy", "loss_and_grad", "trunk_mla", "dense_ffn", "trunk_moe", "shared_expert",
    ),
    "hc_sinkhorn": ("trunk_residual",),
    "dense_ffn": ("policy", "loss_and_grad"),
    "trunk_conv": ("policy", "loss_and_grad"),
    "conv_taps": ("trunk_conv",),
    "trunk_gqa": ("policy", "loss_and_grad"),
}
# In a sparse_gqa trunk, what of these has no tangent (the selection's
# counting passes, the routing) jax hoists out of the differentiated function,
# and the hoisted layer loop loses ``loss_and_grad`` from its path; of the
# hybrid's layers (each kind one jitted function, traced once) the softmax
# pairs' causal masks, the routing and the KDA chunks' masks are; of a latent
# attention layer's, the pairs' masks, the routing, and the loops of the dense
# layer's blocks and of the residual path's reads; of a trunk of gated
# convolutions, the same but for the residual path, and the convolution's
# zero padding and the slices of its taps.
HOISTED = {
    "trunk": ("trunk_indexer", "trunk_moe", "routed_experts"),
    "trunk_hybrid": ("trunk_gated_attention", "trunk_moe", "trunk_kda"),
    "trunk_mla_hc": ("trunk_mla", "trunk_moe", "trunk_residual", "dense_ffn"),
    "trunk_lfm2": ("trunk_conv", "trunk_gqa", "trunk_moe", "dense_ffn"),
}
GNN_ONLY = ("neighbor_gather",)
# what only a sparse_gqa layer opens, what every trunk layer opens, and what
# only the hybrid's layers open (models/trunk.py MIXERS)
SPARSE_ONLY = ("trunk_attention", "trunk_indexer")
HYBRID_ONLY = ("trunk_gated_attention", "trunk_kda", "kda_recurrence")
# what only a trunk of latent attention under the hyper residual opens
MLA_ONLY = ("trunk_mla", "mla_softmax", "trunk_residual", "hc_sinkhorn")
# what only a trunk of gated short convolutions and dense RoPE GQA opens, and
# what it shares with the hyper trunk alone
LFM2_ONLY = ("trunk_conv", "conv_taps", "trunk_gqa")
DENSE_LAYER = ("dense_ffn",)
TRUNK_ONLY = (
    SPARSE_ONLY + ("trunk_moe", "router", "routed_experts", "shared_expert")
    + HYBRID_ONLY + MLA_ONLY + LFM2_ONLY + DENSE_LAYER
)
# Rows of 13 floats pack, eight to a 128-lane row of the table, so the
# sub-row is picked; a formation's rows (8 agents x 21 floats) are over one
# vreg's lanes and keep the gather a leaf.
MLP_ONLY = ("row_pack", "subrow_pick")


POLICIES = ("mlp", "gnn", "trunk", "trunk_hybrid", "trunk_mla_hc", "trunk_lfm2")


def _tiny_trainer(policy, tmp_path, **config):
    config = {
        "num_formations": 4, "name": "scopes", "checkpoint": False,
        "log_dir": str(tmp_path / "logs"), **config,
    }
    if policy == "mlp":
        return Trainer(
            EnvParams(num_agents=3),
            ppo=PPOConfig(n_steps=4, batch_size=24, n_epochs=2),
            config=TrainConfig(**config),
        )
    if policy == "gnn":
        agents, model = 8, GNNActorCritic(k=3, rounds=2)
    else:  # 16 agents: the second block of 8 queries sees more than topk 8
        name = {
            "trunk": "tiny", "trunk_hybrid": "tiny-hybrid", "trunk_mla_hc": "tiny-mla-hc",
            "trunk_lfm2": "tiny-lfm2",
        }[policy]
        agents, model = 16, TrunkActorCritic(arch=load_trunk_arch(name), k=3)
    return Trainer(
        EnvParams(num_agents=agents, obs_mode="knn", knn_k=3),
        ppo=PPOConfig(n_steps=4, batch_size=4 * agents, n_epochs=2),
        model=model,
        config=TrainConfig(**config),
    )


@pytest.fixture(scope="module")
def compiled_text(tmp_path_factory):
    """policy -> the compiled tiny training iteration's HLO text."""
    texts = {}
    for policy in POLICIES:
        trainer = _tiny_trainer(policy, tmp_path_factory.mktemp(policy))
        texts[policy] = trainer._iteration.lower(
            trainer.train_state, trainer.env_state, trainer.obs, trainer.key
        ).compile().as_text()
    return texts


@pytest.fixture(scope="module")
def op_paths(compiled_text):
    """policy -> every ``op_name`` of the compiled tiny training iteration,
    split into its ``/``-separated parts."""
    paths = {}
    for policy, text in compiled_text.items():
        # A reducer's own body (``to_apply``) carries a path cut at its
        # head; a trace shows the instruction that calls it, whose path is
        # whole and starts at the jitted program.
        paths[policy] = [
            name.split("/")
            for name in set(re.findall(r'op_name="(jit\([^"]*)"', text))
        ]
    return paths


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("scope", DEVICE_SCOPES)
def test_scope_is_an_exact_path_part_under_its_parent(op_paths, scope, policy):
    assert set(PARENTS) == set(DEVICE_SCOPES)
    found = set()
    for parts in op_paths[policy]:
        if scope in parts:
            above = [p for p in parts[: parts.index(scope)] if p in DEVICE_SCOPES]
            found.add(above[-1] if above else None)
    absent = {
        "mlp": GNN_ONLY + TRUNK_ONLY,
        "gnn": MLP_ONLY + TRUNK_ONLY,
        "trunk": MLP_ONLY + GNN_ONLY + ("shared_expert",) + HYBRID_ONLY + MLA_ONLY
        + LFM2_ONLY + DENSE_LAYER,
        "trunk_hybrid": MLP_ONLY + GNN_ONLY + SPARSE_ONLY + MLA_ONLY + LFM2_ONLY + DENSE_LAYER,
        "trunk_mla_hc": MLP_ONLY + GNN_ONLY + SPARSE_ONLY + HYBRID_ONLY + LFM2_ONLY,
        "trunk_lfm2": MLP_ONLY + GNN_ONLY + SPARSE_ONLY + ("shared_expert",) + HYBRID_ONLY
        + MLA_ONLY,
    }
    if scope in absent[policy]:
        assert not found
        return
    expected = set(PARENTS[scope])
    if scope in HOISTED.get(policy, ()):
        expected.add("ppo_update" if scope != "routed_experts" else "trunk_moe")
    assert found == expected, (scope, policy, found)


def _minibatch_gathers(text):
    """The ``gather`` instructions under ``minibatch_gather``."""
    return [
        line for line in text.splitlines()
        if re.search(r"= \S+ gather\(", line)
        and re.search(r'op_name="[^"]*/minibatch_gather/', line)
    ]


@pytest.mark.parametrize(
    "policy,gathers",
    [
        ("mlp", 1), ("gnn", 5), ("trunk", 5), ("trunk_hybrid", 5), ("trunk_mla_hc", 5),
        ("trunk_lfm2", 5),
    ],
)
def test_a_minibatch_is_one_gather_where_rows_pack(compiled_text, policy, gathers):
    """Packed rows are looked up once a minibatch; a leaf at a time (five
    leaves) where they are not."""
    found = _minibatch_gathers(compiled_text[policy])
    assert len(found) == gathers, found


def test_the_one_gather_fetches_a_128_lane_row_an_index(compiled_text):
    """The packed table's physical row is one (8,128) tile's lanes: the mlp
    iteration's only gather under ``minibatch_gather`` fetches a ``(1, 128)``
    slice an index, and the fold to the sub-row's 16 lanes is a ``dot``
    under ``subrow_pick``."""
    (gather,) = _minibatch_gathers(compiled_text["mlp"])
    # 24 rows a minibatch; the CPU's compiler keeps the slice's unit axis
    assert re.search(r"= f32\[24,(1,)?128\]\S* gather\(", gather), gather
    assert "slice_sizes={1,128}" in gather, gather
    under = _opcodes_under(compiled_text["mlp"], "subrow_pick")
    assert under and any("dot" in ops for ops in under.values()), under
    assert all(above.endswith("/minibatch_gather") for above in under), under


def _opcodes_under(text, scope):
    """``/``-joined path above ``scope`` -> the opcodes of the instructions
    whose ``op_name`` has ``scope`` as a path part."""
    found = {}
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        opcode = re.search(r"= \S+ ([\w-]+)\(", line)
        if name and opcode and scope in name.group(1).split("/"):
            above = name.group(1).split("/" + scope + "/")[0]
            found.setdefault(above, set()).add(opcode.group(1))
    return found


def test_neighbor_gather_is_a_product_in_rollout_and_update(compiled_text):
    """The tiny GNN trainer has 8 agents, within one MXU tile as gnn100's
    100 are: in the compiled iteration every ``neighbor_gather`` (the
    rollout's steps, its bootstrap value, the update's forward pass and
    its transpose) holds a ``dot`` and neither ``gather`` nor ``scatter``,
    and the program has no scatter at all left to read as no stage."""
    under = _opcodes_under(compiled_text["gnn"], "neighbor_gather")
    stages = {
        stage: [ops for above, ops in under.items() if stage in above.split("/")]
        for stage in ("rollout", "loss_and_grad")
    }
    assert all(stages.values()), under
    for ops in under.values():
        assert "dot" in ops and not ops & {"gather", "scatter"}, under
    assert any("transpose" in above for above in under), under
    assert not re.search(r"= \S+ scatter\(", compiled_text["gnn"])


@pytest.mark.parametrize("agents,product", [(100, True), (128, True), (129, False)])
def test_neighbor_gather_picks_its_path_by_the_node_axis(agents, product):
    """``GNNActorCritic.apply`` and its gradient alone, lowered at gnn100's
    k=4 and compiled: with N <= 128 the four one-hot products (two rounds,
    forward and transposed; the model's only batched ``dot``s) each
    carry ``neighbor_gather`` in their ``op_name`` (the transposed ones
    under ``transpose(...)`` too), and no ``gather`` or ``scatter`` is left
    anywhere; past 128 the gather and its scatter-add stand as they were."""
    model = GNNActorCritic(k=4, rounds=2)
    obs = jax.ShapeDtypeStruct(
        (2, agents, EnvParams(num_agents=agents, obs_mode="knn", knn_k=4).obs_dim),
        jnp.float32,
    )
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), obs)

    def loss(variables, obs):
        mean, _, value = model.apply(variables, obs)
        return mean.sum() + value.sum()

    text = jax.jit(jax.grad(loss)).lower(variables, obs).compile().as_text()
    lines = text.splitlines()
    under = _opcodes_under(text, "neighbor_gather")
    forward = [ops for above, ops in under.items() if "transpose" not in above]
    transposed = [ops for above, ops in under.items() if "transpose" in above]
    assert forward and transposed, under
    if not product:
        assert all("gather" in ops and "dot" not in ops for ops in forward), under
        assert all("scatter" in ops and "dot" not in ops for ops in transposed), under
        return
    assert not [line for line in lines if re.search(r"= \S+ (gather|scatter)\(", line)]
    # the only batched products of the model: a node table a formation
    products = [
        line for line in lines
        if re.search(r"= \S+ dot\(", line) and "lhs_batch_dims={0}" in line
    ]
    assert len(products) == 4, products
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in products]
    assert all("/neighbor_gather/" in name for name in names), names
    assert sum("transpose" in name for name in names) == 2, names


def test_no_name_is_a_primitive_or_helper_of_jax():
    names = DEVICE_SCOPES + KERNEL_NAMES + HOST_SPANS
    assert len(set(names)) == len(names)
    # what jax itself writes into op_name on the update's path
    assert not set(names) & {"gather", "sort", "shuffle", "scatter-add", "while", "body"}
    # knn_roofline matches on ``knn``, trunk_attention_roofline on
    # ``sparse_flash``: each kernel is read by one of them
    assert all(("knn" in name) != ("sparse_flash" in name) for name in KERNEL_NAMES)


@pytest.mark.parametrize(
    "name,kernel,n",
    [
        ("knn_fused", lambda p: knn_batch_pallas(p, 4), 100),
        ("knn_streaming", lambda p: knn_batch_pallas_big(p, 4), 600),
    ],
)
def test_pallas_call_carries_its_name(name, kernel, n):
    """Lowered for the TPU from here (no chip, no TPU library): the Mosaic
    call's ``kernel_name`` is what names the instruction in a trace."""
    assert name in KERNEL_NAMES
    text = (
        jax.jit(kernel)
        .trace(jax.ShapeDtypeStruct((8, n, 2), jnp.float32))
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "(\w+)"', text) == [name]


def test_the_attention_kernels_carry_their_names():
    """Lowered for the TPU from here: the sparse attention alone is its
    forward kernel; its gradient adds the backward kernel."""
    s, heads, group, d = 256, 1, 8, 128
    shapes = (
        jax.ShapeDtypeStruct((s, heads, group, d), jnp.float32),
        jax.ShapeDtypeStruct((s, heads, d), jnp.float32),
        jax.ShapeDtypeStruct((s, heads, d), jnp.float32),
        jax.ShapeDtypeStruct((s, s), jnp.bool_),
    )

    def names(fn):
        text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        return sorted(re.findall(r'kernel_name = "(\w+)"', text))

    loss = lambda *args: sparse_flash_attention(*args).sum()  # noqa: E731
    assert names(sparse_flash_attention) == ["sparse_flash_fwd"]
    assert names(jax.grad(loss, argnums=(0, 1, 2))) == ["sparse_flash_bwd", "sparse_flash_fwd"]
    assert {"sparse_flash_fwd", "sparse_flash_bwd"} <= set(KERNEL_NAMES)


def test_host_spans_one_dispatch_a_chunk_and_one_drain(tmp_path):
    """Two chunks dispatched under the profiler, then a whole fused run:
    the annotations are on the profiler's host plane, one ``train_dispatch``
    a chunk with an increasing ``step_num``, one ``train_drain`` a chunk
    drained, and the benchmark's helper reads exactly those names."""
    trainer = _tiny_trainer("mlp", tmp_path, fused_chunk=1)
    jax.block_until_ready(trainer.run_chunk())  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for _ in range(2):
            jax.block_until_ready(trainer.run_chunk())
    finally:
        jax.profiler.stop_trace()
    from benchmarks import trace

    xplane = trace.find_xplane(tmp_path / "trace")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    steps = sorted(
        dict(ev.stats)["step_num"]
        for plane in data.planes
        if plane.name == host_spans.HOST_PLANE
        for line in plane.lines
        for ev in line.events
        if ev.name == "train_dispatch"
    )
    assert steps == [1, 2]
    spans = host_spans.read_host_spans(xplane, HOST_SPANS)
    assert set(spans) == {"train_dispatch"}
    assert [args["step_num"] for _, _, args in spans["train_dispatch"]] == [1, 2]
    assert all(end > start for start, end, _ in spans["train_dispatch"])

    trainer = _tiny_trainer(
        "mlp", tmp_path, fused_chunk=1, total_timesteps=3 * 4 * 4 * 3,
        log_dir=str(tmp_path / "fused"),
    )
    jax.profiler.start_trace(str(tmp_path / "trace_fused"))
    try:
        trainer.train()
    finally:
        jax.profiler.stop_trace()
    spans = host_spans.read_host_spans(
        trace.find_xplane(tmp_path / "trace_fused"), HOST_SPANS
    )
    assert [a["step_num"] for _, _, a in spans["train_dispatch"]] == [0, 1, 2]
    assert [a["chunk"] for _, _, a in spans["train_drain"]] == [0, 1, 2]


# ----------------------------------------------------------------------
# The per-layer readers this adds, on a context made by hand
# ----------------------------------------------------------------------

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SCOPE_READERS = {
    "update_shuffle_ms": "epoch_shuffle",
    "update_gather_ms": "minibatch_gather",
    "update_grad_ms": "loss_and_grad",
    "update_optimizer_ms": "optimizer_step",
    "env_step_ms": "env_step",
    "policy_forward_ms": "policy",
    "knn_ms": "knn_fused",
    "knn_streaming_ms": "knn_streaming",
    "gnn_neighbor_ms": "neighbor_gather",
    "trunk_attention_ms": "trunk_attention",
    "trunk_indexer_ms": "trunk_indexer",
    "trunk_moe_ms": "trunk_moe",
    "trunk_kda_ms": "trunk_kda",
    "trunk_kda_recurrence_ms": "kda_recurrence",
    "trunk_gated_attention_ms": "trunk_gated_attention",
    "trunk_routed_experts_ms": "routed_experts",
    "trunk_router_ms": "router",
    "trunk_shared_expert_ms": "shared_expert",
    "trunk_mla_ms": "trunk_mla",
    "trunk_mla_softmax_ms": "mla_softmax",
    "trunk_hyper_residual_ms": "trunk_residual",
    "trunk_hyper_sinkhorn_ms": "hc_sinkhorn",
    "trunk_dense_ffn_ms": "dense_ffn",
    "trunk_conv_ms": "trunk_conv",
    "trunk_conv_taps_ms": "conv_taps",
    "trunk_gqa_ms": "trunk_gqa",
}


def _context(scope_s, iterations=2):
    return {"trace": {"scope_s": scope_s}, "iterations": iterations}


def _reader(metric):
    return harness.load_reader(ROOT / BENCH["paths"][0], metric)


@pytest.mark.parametrize("metric,scope", sorted(SCOPE_READERS.items()))
def test_scope_reader_reads_its_scope_or_nothing(metric, scope):
    assert scope in DEVICE_SCOPES + KERNEL_NAMES
    read = _reader(metric)
    assert read(_context({"ppo_update": 3.0, "rollout": 1.0})) is None
    assert read(_context({scope: 0.5, "ppo_update": 3.0})) == pytest.approx(250.0)


def test_unattributed_is_the_update_less_its_stages():
    read = _reader("update_unattributed_ms")
    stages = {"epoch_shuffle": 1.0, "minibatch_gather": 6.0, "loss_and_grad": 2.0,
              "optimizer_step": 0.5}
    value = read(_context({"ppo_update": 10.0, "rollout": 4.0, **stages}))
    assert value == pytest.approx(250.0)
    by_stage = [
        _reader(m)(_context({"ppo_update": 10.0, **stages}))
        for m, s in SCOPE_READERS.items() if s in stages
    ]
    assert sum(by_stage) + value == pytest.approx(1e3 * 10.0 / 2)
    # one stage the program lacks counts as nothing; no stage at all, or
    # no update, is nothing to split
    assert read(_context({"ppo_update": 10.0, "minibatch_gather": 6.0})) == pytest.approx(2000.0)
    assert read(_context({"ppo_update": 10.0})) is None
    assert read(_context({"minibatch_gather": 6.0})) is None


def test_attention_roofline_reads_the_kernels_or_nothing():
    """The FLOP attention over the selected keys requires an iteration of
    Keye's cell (2.79e13), over the seconds of the
    ``sparse_flash`` kernels' events alone; nothing where they are absent
    (the blocked XLA path) or where the policy selects no keys."""
    from benchmarks import costs_attention

    read = _reader("trunk_attention_roofline")
    cell = harness.load_cell("keye-vl2-a3b-ep8-s8k-train-m2", ROOT)
    work = costs_attention.selected_attention_flops_per_iteration(cell.config, cell.job)
    assert work == pytest.approx(2.79e13, rel=2e-3)
    knn = {"rollout/env_step/knn_streaming/pallas_call::custom-call.3": (12.0, 0.02)}
    kernels = {
        **knn,
        "policy/trunk_attention/sparse_flash_fwd/pallas_call::custom-call.1": (60.0, 0.9),
        "transpose/trunk_attention/sparse_flash_bwd/pallas_call::custom-call.2": (16.0, 1.1),
    }
    context = {
        "cell": cell, "iterations": 4, "peaks": {"bf16_flops_per_s": 197e12},
        "trace": {"kernel_s": kernels},
    }
    assert read(context) == pytest.approx(100.0 * 4 * work / 2.0 / 197e12)
    assert read({**context, "trace": {"kernel_s": knn}}) is None
    assert read({**context, "trace": {}}) is None
    mlp5 = harness.load_cell("mlp5-train-m262k", ROOT)
    assert read({**context, "cell": mlp5}) is None


def test_dispatch_enqueue_reads_the_mean_span_or_nothing(tmp_path, monkeypatch):
    read = _reader("dispatch_enqueue_ms")
    cell = harness.load_cell("mlp5-train-m262k", ROOT)
    cell.bench_dir = tmp_path / "benchmarks"  # no trace was written here
    assert read({"cell": cell}) is None
    spans = {"train_dispatch": [(0.0, 1e6, {"step_num": 3}), (5e6, 8e6, {"step_num": 4})]}
    monkeypatch.setattr(host_spans, "host_spans", lambda cell: spans)
    assert _reader("dispatch_enqueue_ms")({"cell": cell}) == pytest.approx(2.0)


def test_new_entries_name_their_cells_and_layers():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    added = list(SCOPE_READERS) + ["update_unattributed_ms", "dispatch_enqueue_ms"]
    layers = {m["layer"] for m in BENCH["per_layer"][:8]}
    for name in added:
        entry = per_layer[name]
        assert (entry["unit"], entry["better"], entry["moves"]) == (
            "ms", "lower", "agent_steps_per_s")
        assert entry["layer"] in layers
    for name in ("knn_ms", "gnn_neighbor_ms"):
        assert per_layer[name]["workloads"] == ["gnn100-train-m8k"]
    mlp5 = [m["name"] for m in harness.load_cell("mlp5-train-m262k", ROOT).per_layer]
    assert "knn_ms" not in mlp5 and "gnn_neighbor_ms" not in mlp5
    assert "update_gather_ms" in mlp5 and "dispatch_enqueue_ms" in mlp5
