"""A transformer trunk over the agents of a swarm as its tokens.

``policy=trunk trunk=<name>`` (train.py) reads the architecture from
``cfg/trunk/<name>.yaml``: a published decoder block under its published
key names, plus what of it this chip holds (``layers_held``,
``experts_held``, ``expert_share``, ``head_share``). A sequence is one swarm
at one time step in ring-slot order, the mask is causal over the agent
index, and a minibatch row is a whole swarm-step (``per_formation``).

A layer is a token mixer, then RMSNorm and a routed expert layer that is
told which experts it holds, routes over all of them and computes the part
its own give, with no token dropped (plus the shared expert, where the
model has one: whole on every chip); the leading ``first_k_dense_replace``
layers of a model that has them end in a dense SwiGLU instead. How a
sublayer reads what the layers carry and writes its part back is the
residual path's (``RESIDUALS``): a plain sum, or hyper-connections over
``hc_mult`` streams. The mixers (``MIXERS``; equations in
``benchmarks/reference/policy_trunk.py``, ``policy_trunk_hybrid.py`` and
``policy_trunk_mla_hc.py``, which the tests hold this module to):

- ``sparse_gqa`` (Keye-VL-2.0's): grouped-query attention with q/k head
  norms and RoPE over the keys a learned indexer selects (its ``topk``
  largest index scores among the keys a query can see);
- ``gated_gqa`` (Solar-Open2's layers ``gqa_layers``): dense causal
  grouped-query attention, no RoPE, no q/k norm, a sigmoid output gate;
- ``kda`` (its other layers): Kimi Delta Attention, short causal
  convolutions, a per-channel log-decay gate, the delta rule with beta in
  (0, 2) run in chunks over the agent axis (``models/kda.py``), a gated
  head norm. The state runs over the agents of one swarm-step inside one
  forward pass; nothing is carried over time;
- ``mla`` (Xing4.0's): multi-head latent attention as training computes
  it, queries and keys and values through low-rank halves with a norm
  between, a rotary part under YaRN that all heads' keys share,
  uncompressed keys of ``qk_nope_head_dim + qk_rope_head_dim`` and values of
  ``v_head_dim`` in ``gated_gqa``'s softmax.

Of a mixer's heads the chip may hold a share (``head_share``: share i of n
holds ``heads / n`` query heads with their key heads): the projections'
widths follow the heads held and ``o @ wo`` is the partial sum it is.

How it is computed here:

- layers run under one ``lax.scan`` over the periods of the layer pattern
  (a trunk of one kind: over its layers; leading dense layers ahead of it,
  each on its own), a swarm at a time with
  ``jax.checkpoint`` a layer; attention works by blocks of ``q_chunk_size``
  queries against the keys the block can see, each block recomputed in the
  backward pass, so the ``heads x S x S`` scores never exist whole
  (``gated_gqa``: by pairs of a query block and a tile of keys under the
  diagonal, all of one shape, a block's tiles put together afterwards);
- a mixer's projections that read the normalised input are one product
  with one parameter leaf, the matrices side by side (``w_in`` of
  ``gated_gqa`` and ``kda``, ``s_in`` of the shared expert);
- the selection needs no sort: a query's ``topk``-th largest index score
  is found by bisection on the float's bits (32 counting passes), ties go
  to the lower index by a running count, and the result is a mask on the
  block's scores. The thresholds are what the layer's checkpoint keeps, so
  the recomputation selects nothing twice. The group whose queries see at
  most ``topk`` keys selects everything and skips it;
- precision: float32 state and activations; products in three bf16 passes
  (jax's ``high``), the delta rule's state update among them; both
  selections (the indexer's ``qI . kI`` and the router's logits) and the
  observation embedding at float32 ``highest``: fewer passes there re-rank
  keys around rank ``topk`` and experts around rank
  ``num_experts_per_tok``;
- the expert layer runs each held expert over the swarm and masks in the
  tokens routed to it: dense under the routing's mask, not dispatched
  (``expert_layer``). The indexer gets no gradient (the selection is
  indices).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import yaml
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from marl_distributedformation_tpu.models.common import (
    PolicyHead,
    PooledValueHead,
)
from marl_distributedformation_tpu.models.kda import chunked_delta_rule, short_conv

Array = jax.Array

HIGHEST = jax.lax.Precision.HIGHEST
# What a layer's checkpoint keeps besides its input (see select_keys).
_KEPT = ("trunk_select_threshold", "trunk_select_ties")
# Query blocks in one key tile of the gated GQA mixer (``_causal_tiles``).
_GQA_TILE_BLOCKS = 4
# Tokens of a leading dense layer's SwiGLU at a time (``dense_ffn``).
_DENSE_TOKENS = 1024
# Every counter a layer can sow; a trunk sows those its layers have.
COUNTERS = (
    "moe_held_share", "moe_load_max_over_mean", "indexer_selected_mean",
    "kda_log_decay_mean", "kda_beta_mean",
    "hc_res_offdiag_mean", "hc_sinkhorn_row_err",
)
# How a counter's values over tokens, sublayers and layers become one: a
# largest value for these, a mean for the others.
_OVER = {"hc_sinkhorn_row_err": jnp.max}


@dataclasses.dataclass(frozen=True)
class TrunkArch:
    """The architecture file's content (hashable: a flax attribute). Head
    and expert counts are the published ones; ``*_held`` and the shares say
    what of them this chip holds."""

    name: str
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    q_chunk_size: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    layers_held: int
    experts_held: int
    expert_share: Tuple[int, int]  # (this chip's share, of how many)
    head_share: Tuple[int, int] = (0, 1)
    # the held layers' mixers, in order (keys of MIXERS)
    layer_kinds: Tuple[str, ...] = ()
    shared_expert_size: int = 0  # 0: the model has no shared expert
    # sparse_gqa
    rope_theta: float = 0.0
    indexer_num_heads: int = 0
    indexer_head_dim: int = 0
    topk: int = 0
    # kda
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_size: int = 0
    kda_chunk_size: int = 0
    # mla
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Tuple[Tuple[str, float], ...] = ()  # YaRN's numbers, by key
    # the first ``dense_layers`` held layers end in a dense SwiGLU of
    # ``intermediate_size``, the others in the expert layer
    dense_layers: int = 0
    intermediate_size: int = 0
    # the router: its scores, a selection bias that does not enter the
    # weights (``noaux_tc``), the weights' scale
    scoring_func: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # the residual path (a key of RESIDUALS) and the hyper-connections' numbers
    residual: str = "plain"
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_res_clamp: Tuple[float, float] = (0.0, 0.0)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that the held layers after the leading
        dense ones repeat."""
        kinds = self.layer_kinds[self.dense_layers :]
        return next(
            kinds[:p] for p in range(1, len(kinds) + 1)
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
        )

    def held(self, heads: int) -> int:
        """How many of ``heads`` published heads this chip holds."""
        return heads // self.head_share[1]

    @classmethod
    def from_dict(cls, name: str, data: dict) -> "TrunkArch":
        """Three shapes of file: Keye-VL-2.0's (``sa_config``, ``num_experts``;
        every layer ``sparse_gqa``), Solar-Open2's (``gqa_layers``,
        ``linear_attn_config``, ``n_routed_experts``, ``n_shared_experts``) and
        Xing4.0's (``kv_lora_rank``, ``hc_mult``, ``first_k_dense_replace``,
        ``scoring_func``, ``routed_scaling_factor``; every layer ``mla``, the
        residual path ``hyper``, held from layer ``first_layer_held`` on)."""
        sa = data.get("sa_config") or {}
        kda = data.get("linear_attn_config") or {}
        yarn = data.get("rope_scaling") or {}
        hybrid = "gqa_layers" in data
        mla = "kv_lora_rank" in data
        layers = int(data["layers_held"])
        first = int(data.get("first_layer_held", 0))
        share, count = (int(v) for v in data["expert_share"])
        head, head_count = (int(v) for v in data.get("head_share", (0, 1)))
        # what only Xing4.0's shape of file states, and its reference computes
        own = dict(
            q_lora_rank=data["q_lora_rank"],
            kv_lora_rank=data["kv_lora_rank"],
            qk_nope_head_dim=data["qk_nope_head_dim"],
            qk_rope_head_dim=data["qk_rope_head_dim"],
            v_head_dim=data["v_head_dim"],
            rope_scaling=tuple(sorted(
                (key, value) for key, value in yarn.items() if key != "type"
            )),
            dense_layers=min(max(int(data["first_k_dense_replace"]) - first, 0), layers),
            intermediate_size=data["intermediate_size"],
            scoring_func=data.get("scoring_func", "softmax"),
            router_bias=data.get("topk_method") == "noaux_tc",
            routed_scaling_factor=float(data.get("routed_scaling_factor", 1)),
            residual="hyper",
            hc_mult=int(data["hc_mult"]),
            hc_sinkhorn_iters=int(data["hc_sinkhorn_iters"]),
            hc_eps=float(data["hc_eps"]),
            hc_res_clamp=(
                float(data["mhc_h_res_clamp_min"]), float(data["mhc_h_res_clamp_max"])
            ),
        ) if mla else {}
        arch = cls(
            name=name,
            hidden_size=data["hidden_size"],
            num_attention_heads=data["num_attention_heads"],
            num_key_value_heads=data["num_key_value_heads"],
            head_dim=data["qk_nope_head_dim"] + data["qk_rope_head_dim"]
            if mla else data["head_dim"],
            rms_norm_eps=data["rms_norm_eps"],
            q_chunk_size=(sa if "q_chunk_size" in sa else data)["q_chunk_size"],
            num_experts=data["n_routed_experts" if hybrid or mla else "num_experts"],
            num_experts_per_tok=data["num_experts_per_tok"],
            moe_intermediate_size=data["moe_intermediate_size"],
            norm_topk_prob=data["norm_topk_prob"],
            layers_held=layers,
            experts_held=data["experts_held"],
            expert_share=(share, count),
            head_share=(head, head_count),
            layer_kinds=tuple(
                "mla" if mla
                else ("gated_gqa" if i in data["gqa_layers"] else "kda") if hybrid
                else "sparse_gqa"
                for i in range(layers)
            ),
            shared_expert_size=data["moe_intermediate_size"]
            * int(data.get("n_shared_experts", 0)),
            rope_theta=0.0 if hybrid else data["rope_theta"],
            indexer_num_heads=sa.get("indexer_num_heads", 0),
            indexer_head_dim=sa.get("indexer_head_dim", 0),
            topk=sa.get("topk", 0),
            kda_num_heads=kda.get("num_heads", 0),
            kda_head_dim=kda.get("head_dim", 0),
            kda_conv_size=kda.get("short_conv_kernel_size", 0),
            kda_chunk_size=data.get("kda_chunk_size", 0),
            **own,
        )
        unsupported = {
            "hidden_act": data.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(data.get("attention_bias", False)),
            "indexer_num_kv_heads": int(sa.get("indexer_num_kv_heads", 1)) != 1,
            "num_key_value_heads": arch.num_attention_heads
            % arch.num_key_value_heads != 0,
            "expert_share": not 0 <= share < count
            or arch.experts_held * count != arch.num_experts,
            "layers_held": not 1 <= arch.layers_held
            <= int(data["num_hidden_layers"]) - first or first < 0,
            "head_share": not 0 <= head < head_count
            or arch.num_key_value_heads % head_count != 0
            or arch.kda_num_heads % head_count != 0,
            "sa_config": not hybrid and not mla and not sa,
        }
        if hybrid:  # what the two mixers and this shape's reference do not compute
            unsupported.update({
                "use_rope": bool(data.get("use_rope", False)),
                "use_gqa_gate": not data.get("use_gqa_gate", False),
                "kda_use_full_proj": bool(data.get("kda_use_full_proj", False)),
                "kda_allow_neg_eigval": not data.get("kda_allow_neg_eigval", False),
                "first_k_dense_replace": int(data.get("first_k_dense_replace", 0)) != 0,
                "routed_scaling_factor": data.get("routed_scaling_factor", 1) != 1,
                "linear_attn_config": kda.get("num_kv_heads") is not None
                or (
                    "kda" in arch.layer_kinds
                    and not (arch.kda_num_heads and arch.kda_chunk_size > 0)
                ),
            })
        if mla:  # what the latent attention, the router and the streams do not compute
            unsupported.update({
                "n_group": int(data.get("n_group", 1)) != 1,
                "topk_group": int(data.get("topk_group", 1)) != 1,
                "rope_scaling": yarn.get("type") != "yarn"
                or yarn.get("mscale") != yarn.get("mscale_all_dim"),
                "num_nextn_predict_layers": int(data.get("num_nextn_predict_layers", 0)) != 0,
                "scoring_func": arch.scoring_func not in ("softmax", "sigmoid"),
                "topk_method": data.get("topk_method", "greedy") not in ("greedy", "noaux_tc"),
                "moe_layer_freq": int(data.get("moe_layer_freq", 1)) != 1,
                "num_attention_heads": arch.num_attention_heads != arch.num_key_value_heads,
                "hc_mult": arch.hc_mult < 1,
                "hc_sinkhorn_iters": arch.hc_sinkhorn_iters < 1,
            })
        bad = [key for key, wrong in unsupported.items() if wrong]
        if bad:
            raise ValueError(
                f"trunk architecture {name!r}: unsupported or inconsistent {bad}"
            )
        return arch


def load_trunk_arch(name: str) -> TrunkArch:
    from marl_distributedformation_tpu.utils.config import repo_root

    path = repo_root() / "cfg" / "trunk" / f"{name}.yaml"
    if not path.exists():
        known = sorted(p.stem for p in path.parent.glob("*.yaml"))
        raise ValueError(f"no trunk architecture {name!r}; cfg/trunk has {known}")
    return TrunkArch.from_dict(name, yaml.safe_load(path.read_text()))


def _rms(x: Array, g: Array, eps: float) -> Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x: Array, scale: Array, bias: Array, eps: float) -> Array:
    centred = x - x.mean(-1, keepdims=True)
    var = jnp.mean(centred * centred, -1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x: Array, theta: float) -> Array:
    """Rotate-half RoPE on ``x (S, ..., d)`` at positions 0..S-1 (the
    published ``mrope_section`` with its three position axes equal)."""
    d = x.shape[-1]
    return _rotate(x, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def _rotate(x: Array, inv_freq: Array) -> Array:
    """``x (S, ..., d)`` turned by ``inv_freq (d / 2)`` times its position."""
    s, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1).reshape(
        s, *([1] * (x.ndim - 2)), d
    )
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


# ----------------------------------------------------------------------
# The indexer's selection
# ----------------------------------------------------------------------


def index_scores(qi: Array, ki: Array, w: Array) -> Array:
    """``I (T, S)`` for queries ``qi (T, heads, d)``, the one key head
    ``ki (S, d)`` and per-query head weights ``w (T, heads)``; the product
    at float32 ``highest``."""
    heads, d = qi.shape[-2], qi.shape[-1]
    per_head = jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki, precision=HIGHEST))
    return (heads**-0.5 * d**-0.5) * (w[..., None] * per_head).sum(1)


def _ordered_bits(x: Array) -> Array:
    """float32 -> uint32 with the floats' order; every float maps above 0,
    which is left for keys a query cannot see."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(keys: Array, k: int) -> Array:
    """The ``k``-th largest of each row of ``keys (..., S) uint32``, bit
    by bit from the top: the largest value that ``k`` entries reach."""

    def refine(i, found):
        candidate = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = (keys >= candidate[..., None]).sum(-1)
        return jnp.where(reach >= k, candidate, found)

    return jax.lax.fori_loop(
        0, 32, refine, jnp.zeros(keys.shape[:-1], jnp.uint32)
    )


def select_keys(index: Array, visible: Array, topk: int) -> Array:
    """The mask of each row's ``min(visible, topk)`` largest ``index``
    entries among the ``visible`` ones, ties toward the lower index: what
    ``jax.lax.top_k`` selects, without its sort."""
    keys = jnp.where(visible, _ordered_bits(index), jnp.uint32(0))
    threshold = checkpoint_name(_kth_largest(keys, topk), _KEPT[0])
    above = keys > threshold[..., None]
    ties_wanted = checkpoint_name(topk - above.sum(-1), _KEPT[1])
    tied = keys == threshold[..., None]
    first_ties = jnp.cumsum(tied, axis=-1) <= ties_wanted[..., None]
    return (above | (tied & first_ties)) & visible


def _select_block(qi, ki, w, first, topk: int, select_all: bool) -> Array:
    """Key mask ``(T, S)`` for queries ``first .. first + T`` against keys
    ``0 .. S``. With ``select_all`` (no query of the group sees more than
    ``topk`` keys) it is the causal mask and no score is computed."""
    t, s = qi.shape[0], ki.shape[0]
    visible = jnp.arange(s)[None, :] <= (first + jnp.arange(t))[:, None]
    if select_all:
        return visible
    return select_keys(index_scores(qi, ki, w), visible, topk)


def _query_groups(s: int, chunk: int, topk: int):
    """``(start, end, chunk)`` of the groups a swarm's queries are worked
    in: blocks of ``chunk`` queries, grouped so that the blocks of a group
    share one key length ``end`` (a multiple of ``topk``: one loop body a
    group to compile, at the price of keys no query of an early block can
    see). A swarm the chunk does not divide is one block."""
    if s % chunk:
        return [(0, s, s)]
    step = max(chunk, topk - topk % chunk)
    return [(start, min(start + step, s), chunk) for start in range(0, s, step)]


# ----------------------------------------------------------------------
# Attention over the selected keys, a block of queries at a time
# ----------------------------------------------------------------------


@jax.checkpoint
def _attend_block(q: Array, k: Array, v: Array, mask: Array) -> Array:
    """``q (T, kv, group, d)``, ``k, v (S, kv, d)``, ``mask (T, S)`` ->
    ``(T, kv * group * d)``. Recomputed in the backward pass."""
    scores = jnp.einsum("tgad,sgd->gats", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gats,sgd->tgad", p, v).reshape(q.shape[0], -1)


# ----------------------------------------------------------------------
# The expert layer
# ----------------------------------------------------------------------


def route(
    h2: Array, router: Array, top: int, normalise: bool, scoring: str = "softmax",
    bias: Optional[Array] = None, scale: float = 1.0,
):
    """Each token's ``top`` experts of all the router's and the weights
    they combine with; the logits at float32 ``highest``. ``scoring`` makes
    the scores of the logits (``softmax`` over the experts, or ``sigmoid``
    an expert); ``bias`` is added to them for the selection alone (it gets no
    gradient: the selection is indices); ``scale`` multiplies the weights."""
    logits = jnp.einsum("sh,he->se", h2, router, precision=HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        r_top, e_top = jax.lax.top_k(scores, top)
    else:
        _, e_top = jax.lax.top_k(scores + bias, top)
        r_top = jnp.take_along_axis(scores, e_top, axis=-1)
    c = r_top / r_top.sum(-1, keepdims=True) if normalise else r_top
    return e_top, c if scale == 1.0 else scale * c


def expert_layer(
    h2: Array, e_top: Array, c: Array, w_gate: Array, w_up: Array, w_down: Array,
    share: Tuple[int, int],
) -> Tuple[Array, Dict[str, Array]]:
    """What the held experts add for one swarm ``h2 (S, H)``: experts
    ``share[0] * held ... + held`` of the router's, weights ``(held, H,
    F)`` and ``(held, F, H)``. Each held expert in turn computes the
    swarm and a mask keeps the tokens routed to it, so no token is
    dropped and the time does not follow the routing; every other
    expert's part is left out. This is not sparse dispatch: it spends
    ``held`` times the products the routing requires. A layout without
    dropped tokens whose time is the same whatever the routing needs room
    for every token at every held expert, which is this one; the sorted,
    grouped product comes with the ``ep`` axis and its exchange (ROADMAP
    R3; PERF.md section 6, PR 27, has both layouts' readings)."""
    held = w_gate.shape[0]
    ids = share[0] * held + jnp.arange(held)

    @jax.checkpoint  # an expert's (S, F) activations are not kept for 16
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.where(e_top == e, c, 0.0).sum(-1)  # 0: not routed to e
        y = jax.nn.silu(h2 @ gate) * (h2 @ up)
        return out + weight[:, None] * (y @ down), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2), (ids, w_gate, w_up, w_down)
    )
    sizes = (e_top[..., None] == ids).sum((0, 1))  # assignments an expert
    counters = {
        "moe_held_share": sizes.sum() / e_top.size,
        "moe_load_max_over_mean": sizes.max() / jnp.maximum(sizes.mean(), 1e-9),
    }
    return out, counters


def shared_expert(h2: Array, w_in: Array, down: Array) -> Array:
    """The shared expert's part for one swarm: every token, ungated; every
    chip that shares the layer computes it alike. ``w_in`` is ``[gate |
    up]`` side by side."""
    gate, up = jnp.split(h2 @ w_in, 2, axis=1)
    return (jax.nn.silu(gate) * up) @ down


def dense_ffn(h2: Array, w_in: Array, down: Array) -> Array:
    """A leading dense layer's SwiGLU for one swarm, ``_DENSE_TOKENS`` tokens
    at a time and each block again in the backward pass: a swarm's ``(S, 2
    intermediate_size)`` products at once are the largest buffers of the
    layer (1.1 GiB at 8,192 x 9,216) at the point where its memory peaks."""
    s = h2.shape[0]
    block = _DENSE_TOKENS if s % _DENSE_TOKENS == 0 else s
    out = jax.lax.map(
        jax.checkpoint(lambda rows: shared_expert(rows, w_in, down)),
        h2.reshape(s // block, block, -1),
    )
    return out.reshape(s, -1)


# ----------------------------------------------------------------------
# The mixers
# ----------------------------------------------------------------------
# A mixer's ``shapes(arch)`` names its parameters (initialiser, shape of one
# layer); its ``mix(x, lp, arch, collect)`` takes what the residual path
# hands one swarm's sublayer, ``x (S, hidden)`` before the norm, and returns
# its part ``(S, hidden)`` (the layer adds it: ``RESIDUALS``), its counters
# summed over the swarm's tokens (the layer divides by S) and, with
# ``collect``, what it selected.


def _sliced_normal(key, shape, dtype=jnp.float32):
    # a layer's (and an expert's) slice at a time: the TPU compiles
    # one draw of 10^8 numbers in 17 s, 64 of 10^6 in under one
    lead = len(shape) - 2
    draw = lambda k: nn.initializers.normal(0.02)(k, shape[lead:], dtype)  # noqa: E731
    slices = jax.random.split(key, math.prod(shape[:lead]))
    return jax.lax.map(draw, slices).reshape(shape)


def _rbg_normal(key, shape, dtype=jnp.float32):
    # XLA's own generator (``impl="rbg"``), one draw whatever the shape:
    # ``_sliced_normal`` is a loop to compile a matrix, and a hybrid trunk has
    # three times the matrices (``sparse_gqa`` keeps the draw it has had)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    rbg = jax.random.wrap_key_data(jnp.resize(key, (4,)), impl="rbg")
    return nn.initializers.normal(0.02)(rbg, shape, dtype)


def _sparse_gqa_shapes(a: TrunkArch):
    h, hd = a.hidden_size, a.head_dim
    nq, nkv = a.held(a.num_attention_heads), a.held(a.num_key_value_heads)
    ni, di = a.indexer_num_heads, a.indexer_head_dim
    return {
        "attn_norm": (nn.initializers.ones, (h,)),
        "wq": (_sliced_normal, (h, nq * hd)),
        "wk": (_sliced_normal, (h, nkv * hd)),
        "wv": (_sliced_normal, (h, nkv * hd)),
        "wo": (_sliced_normal, (nq * hd, h)),
        "q_norm": (nn.initializers.ones, (hd,)),
        "k_norm": (nn.initializers.ones, (hd,)),
        "idx_wq": (_sliced_normal, (h, ni * di)),
        "idx_wk": (_sliced_normal, (h, di)),
        "idx_w": (_sliced_normal, (h, ni)),
        "idx_k_scale": (nn.initializers.ones, (di,)),
        "idx_k_bias": (nn.initializers.zeros, (di,)),
    }


def _sparse_gqa(x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool):
    s = x.shape[0]
    eps, theta = arch.rms_norm_eps, arch.rope_theta
    nq, nkv = arch.held(arch.num_attention_heads), arch.held(arch.num_key_value_heads)
    hd, ni, di = arch.head_dim, arch.indexer_num_heads, arch.indexer_head_dim

    with jax.named_scope("trunk_attention"):
        h = _rms(x, lp["attn_norm"], eps)
        q = (h @ lp["wq"]).reshape(s, nkv, nq // nkv, hd)
        q = _rope(_rms(q, lp["q_norm"], eps), theta)
        k = _rope(_rms((h @ lp["wk"]).reshape(s, nkv, hd), lp["k_norm"], eps), theta)
        v = (h @ lp["wv"]).reshape(s, nkv, hd)
    with jax.named_scope("trunk_indexer"):
        qi = _rope((h @ lp["idx_wq"]).reshape(s, ni, di), theta)
        ki = _rope(
            _layer_norm(h @ lp["idx_wk"], lp["idx_k_scale"], lp["idx_k_bias"], eps),
            theta,
        )
        w = h @ lp["idx_w"]

    outs, masks, selected = [], [], 0.0
    for start, end, chunk in _query_groups(s, arch.q_chunk_size, arch.topk):

        def block(i, start=start, end=end, chunk=chunk):
            """Queries ``start + i * chunk ...`` against keys ``0 .. end``."""
            first = start + i * chunk
            rows = lambda a: jax.lax.dynamic_slice_in_dim(a, first, chunk)  # noqa: E731
            with jax.named_scope("trunk_indexer"):
                mask = _select_block(
                    rows(qi), ki[:end], rows(w), first, arch.topk, end <= arch.topk
                )
            with jax.named_scope("trunk_attention"):
                out = _attend_block(rows(q), k[:end], v[:end], mask)
            return out, (mask if collect else mask.sum())

        out, found = jax.lax.map(block, jnp.arange((end - start) // chunk))
        outs.append(out.reshape(end - start, -1))
        if collect:
            masks.append(
                jnp.pad(found.reshape(end - start, end), ((0, 0), (0, s - end)))
            )
        selected = selected + found.sum()
    with jax.named_scope("trunk_attention"):
        part = jnp.concatenate(outs) @ lp["wo"]
    collected = {"selected_keys": jnp.concatenate(masks)} if collect else {}
    return part, {"indexer_selected_mean": selected}, collected


def _gated_gqa_shapes(a: TrunkArch):
    h, hd = a.hidden_size, a.head_dim
    nq, nkv = a.held(a.num_attention_heads), a.held(a.num_key_value_heads)
    return {
        "attn_norm": (nn.initializers.ones, (h,)),
        # everything that reads the normalised input, side by side:
        # [wq | wk | wv | wg], widths nq, nkv, nkv, nq heads
        "w_in": (_rbg_normal, (h, 2 * (nq + nkv) * hd)),
        "wo": (_rbg_normal, (nq * hd, h)),
    }


@jax.checkpoint
def _attend_tile(q: Array, k: Array, v: Array, mask: Array):
    """One tile of a softmax over more keys than it sees: ``q (T, kv, group,
    d)``, ``k, v (L, kv, d)``, ``mask (T, L)`` with a key in every row ->
    the tile's largest score ``m (kv, group, T)``, its weights' sum ``l`` and
    their product with ``v``, ``(T, kv, group, d)``, both relative to ``m``.
    Recomputed in the backward pass."""
    scores = jnp.einsum("tgad,sgd->gats", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(mask, scores, -jnp.inf)
    m = scores.max(-1)
    p = jnp.exp(scores - m[..., None])
    return jnp.einsum("gats,sgd->tgad", p, v), m, p.sum(-1)


def _causal_tiles(s: int, chunk: int):
    """``(chunk, tile, pairs)``: the (query block, key tile) pairs a causal
    mask leaves something of, for blocks of ``chunk`` queries and tiles of
    ``tile`` keys (``_GQA_TILE_BLOCKS`` blocks long where that divides the
    swarm). A swarm the chunk does not divide is one pair."""
    if s % chunk:
        return s, s, [(0, 0)]
    tile = chunk * _GQA_TILE_BLOCKS
    tile = tile if s % tile == 0 else chunk
    return chunk, tile, [
        (i, j) for i in range(s // chunk) for j in range(i * chunk // tile + 1)
    ]


def _causal_softmax(q: Array, k: Array, v: Array, chunk_size: int, whole_pairs: bool):
    """Dense causal attention without the keys the mask hides, for ``q (S, kv,
    group, d)``, ``k (S, kv, d)`` and ``v (S, kv, dv)`` -> ``(S, kv * group *
    dv)``; the keys' width and the values' may differ. One loop over the
    (query block, key tile) pairs under the diagonal, every pair of one shape
    (so one loop body in each pass), and a block's tiles put together by their
    largest scores and weight sums, as a softmax over all of them. With
    ``whole_pairs`` a pair takes its slices again in the backward pass: where
    every query head has a key head of its own a tile of keys and values is
    too large to keep for each pair."""
    s = q.shape[0]
    chunk, tile, pairs = _causal_tiles(s, chunk_size)
    block_of = jnp.array([i for i, _ in pairs])

    def pair(ij):
        first, start = ij[0] * chunk, ij[1] * tile
        visible = (
            (start + jnp.arange(tile))[None, :] <= (first + jnp.arange(chunk))[:, None]
        )
        return _attend_tile(
            jax.lax.dynamic_slice_in_dim(q, first, chunk),
            jax.lax.dynamic_slice_in_dim(k, start, tile),
            jax.lax.dynamic_slice_in_dim(v, start, tile),
            visible,
        )

    o, m, l = jax.lax.map(  # (pairs, ...)
        jax.checkpoint(pair) if whole_pairs else pair, jnp.array(pairs)
    )
    blocks = s // chunk
    largest = jax.ops.segment_max(m, block_of, blocks, indices_are_sorted=True)
    weight = jnp.exp(m - largest[block_of])  # (pairs, kv, group, T)
    total = jax.ops.segment_sum(l * weight, block_of, blocks, indices_are_sorted=True)
    summed = jax.ops.segment_sum(
        o * jnp.moveaxis(weight, -1, 1)[..., None], block_of, blocks,
        indices_are_sorted=True,
    )  # (blocks, T, kv, group, dv)
    return (summed / jnp.moveaxis(total, -1, 1)[..., None]).reshape(s, -1)


def _gated_gqa(x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool):
    s, hd = x.shape[0], arch.head_dim
    nq, nkv = arch.held(arch.num_attention_heads), arch.held(arch.num_key_value_heads)
    with jax.named_scope("trunk_gated_attention"):
        h = _rms(x, lp["attn_norm"], arch.rms_norm_eps)
        q, k, v, gate = jnp.split(
            h @ lp["w_in"], (nq * hd, (nq + nkv) * hd, (nq + 2 * nkv) * hd), 1
        )
        q = q.reshape(s, nkv, nq // nkv, hd)
        k, v = k.reshape(s, nkv, hd), v.reshape(s, nkv, hd)
        attended = _causal_softmax(q, k, v, arch.q_chunk_size, whole_pairs=False)
        part = (attended * jax.nn.sigmoid(gate)) @ lp["wo"]
    return part, {}, {}


def _log_uniform_decay(key, shape, dtype=jnp.float32):
    """``A_log``: log of U(1, 16), a value a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias(key, shape, dtype=jnp.float32):
    """A bias whose softplus is U(0.001, 0.1)."""
    dt = jax.random.uniform(key, shape, dtype, 0.001, 0.1)
    return dt + jnp.log(-jnp.expm1(-dt))


def _kda_shapes(a: TrunkArch):
    h, d, heads = a.hidden_size, a.kda_head_dim, a.held(a.kda_num_heads)
    width = heads * d
    return {
        "attn_norm": (nn.initializers.ones, (h,)),
        # what reads the normalised input, side by side: [wq | wk | wv | f_a |
        # g_a] (the two gates' low-rank first halves). ``w_beta`` stays a leaf
        # of its own: with its ``heads`` columns the width is no multiple of
        # 128 lanes, and the compiled update then copies the leaf, its moments
        # and its gradient into another layout every step
        "w_in": (_rbg_normal, (h, 3 * width + 2 * d)),
        "w_beta": (_rbg_normal, (h, heads)),
        "conv": (_rbg_normal, (a.kda_conv_size, 3 * width)),  # of q, k, v
        "f_b": (_rbg_normal, (d, width)),  # the decay gate's second half
        "dt_bias": (_dt_bias, (width,)),
        "A_log": (_log_uniform_decay, (heads,)),
        "g_b": (_rbg_normal, (d, width)),  # the output gate's second half
        "o_norm": (nn.initializers.ones, (d,)),
        "wo": (_rbg_normal, (width, h)),
    }


def _l2norm(x: Array) -> Array:
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _kda(x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool):
    s, d = x.shape[0], arch.kda_head_dim
    width = lp["wo"].shape[0]
    with jax.named_scope("trunk_kda"):
        h = _rms(x, lp["attn_norm"], arch.rms_norm_eps)
        qkv, f, g = jnp.split(h @ lp["w_in"], (3 * width, 3 * width + d), 1)
        q, k, v = jnp.moveaxis(
            jax.nn.silu(short_conv(qkv, lp["conv"])).reshape(s, 3, -1, d), 1, 0
        )
        q = _l2norm(q) * d**-0.5
        k = _l2norm(k)
        log_decay = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
            (f @ lp["f_b"] + lp["dt_bias"]).reshape(s, -1, d)
        )
        beta = 2.0 * jax.nn.sigmoid(h @ lp["w_beta"])
        with jax.named_scope("kda_recurrence"):
            o = chunked_delta_rule(q, k, v, log_decay, beta, arch.kda_chunk_size)
        gate = jax.nn.sigmoid(g @ lp["g_b"]).reshape(s, -1, d)
        o = _rms(o, lp["o_norm"], arch.rms_norm_eps) * gate
        part = o.reshape(s, -1) @ lp["wo"]
    sums = {
        "kda_log_decay_mean": log_decay.mean((1, 2)).sum(),
        "kda_beta_mean": beta.mean(1).sum(),
    }
    return part, sums, {}


def _mla_shapes(a: TrunkArch):
    h, heads = a.hidden_size, a.held(a.num_attention_heads)
    return {
        "attn_norm": (nn.initializers.ones, (h,)),
        # the low-rank first halves are whole on every chip, the second
        # halves' widths follow the heads held
        "wq_a": (_rbg_normal, (h, a.q_lora_rank)),
        "q_a_norm": (nn.initializers.ones, (a.q_lora_rank,)),
        # a head [q_nope | q_rope]
        "wq_b": (_rbg_normal, (a.q_lora_rank, heads * a.head_dim)),
        "wkv_a": (_rbg_normal, (h, a.kv_lora_rank + a.qk_rope_head_dim)),  # [ckv | k_rope]
        "kv_a_norm": (nn.initializers.ones, (a.kv_lora_rank,)),
        # a head [k_nope | v]
        "wkv_b": (
            _rbg_normal, (a.kv_lora_rank, heads * (a.qk_nope_head_dim + a.v_head_dim))
        ),
        "wo": (_rbg_normal, (heads * a.v_head_dim, h)),
    }


def _yarn(a: TrunkArch) -> Tuple[Array, float]:
    """The rotary part's ``qk_rope_head_dim / 2`` frequencies under YaRN
    (pairs that turn more than ``beta_fast`` times over the original context
    keep theirs, those that turn fewer than ``beta_slow`` times are slowed by
    ``factor``, a ramp between), and what YaRN multiplies the softmax's scale
    by: ``mscale`` squared (cos and sin stay unscaled where ``mscale`` equals
    ``mscale_all_dim``, which ``from_dict`` holds the file to)."""
    d, yarn = a.qk_rope_head_dim, dict(a.rope_scaling)

    def pair_of(turns):
        span = yarn["original_max_position_embeddings"] / (2 * math.pi * turns)
        return d * math.log(span) / (2 * math.log(a.rope_theta))

    low = max(math.floor(pair_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_of(yarn["beta_slow"])), d - 1)
    pairs = jnp.arange(d // 2, dtype=jnp.float32)
    inv_freq = a.rope_theta ** (-2.0 * pairs / d)
    kept = 1.0 - jnp.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    mscale = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    return inv_freq * kept + inv_freq / yarn["factor"] * (1.0 - kept), mscale * mscale


def _mla(x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool):
    """Multi-head latent attention as training computes it: queries, keys
    and values through their low-rank halves, a rotary part that all heads'
    keys share, uncompressed keys and values in ``gated_gqa``'s softmax (no
    absorbed products, no cache)."""
    s, eps = x.shape[0], arch.rms_norm_eps
    heads, d_nope = arch.held(arch.num_attention_heads), arch.qk_nope_head_dim
    inv_freq, mscale2 = _yarn(arch)
    with jax.named_scope("trunk_mla"):
        h = _rms(x, lp["attn_norm"], eps)
        q = _rms(h @ lp["wq_a"], lp["q_a_norm"], eps) @ lp["wq_b"]
        ckv, k_rope = jnp.split(h @ lp["wkv_a"], (arch.kv_lora_rank,), 1)
        kv = _rms(ckv, lp["kv_a_norm"], eps) @ lp["wkv_b"]
        q_nope, q_rope = jnp.split(q.reshape(s, heads, -1), (d_nope,), 2)
        k_nope, v = jnp.split(kv.reshape(s, heads, -1), (d_nope,), 2)
        # the tile scales its scores by d^-0.5; YaRN's part rides on the queries
        q = mscale2 * jnp.concatenate([q_nope, _rotate(q_rope, inv_freq)], -1)
        k_rope = jnp.broadcast_to(
            _rotate(k_rope, inv_freq)[:, None, :], (s, heads, arch.qk_rope_head_dim)
        )
        k = jnp.concatenate([k_nope, k_rope], -1)
        with jax.named_scope("mla_softmax"):
            attended = _causal_softmax(
                q[:, :, None, :], k, v, arch.q_chunk_size, whole_pairs=True
            )
        part = attended @ lp["wo"]
    return part, {}, {}


class Mixer(NamedTuple):
    shapes: Callable[[TrunkArch], Dict[str, tuple]]
    mix: Callable
    normal: Callable  # the draw of the layer's matrices
    scope: str  # the stage its part is added under


MIXERS = {
    "sparse_gqa": Mixer(_sparse_gqa_shapes, _sparse_gqa, _sliced_normal, "trunk_attention"),
    "gated_gqa": Mixer(_gated_gqa_shapes, _gated_gqa, _rbg_normal, "trunk_gated_attention"),
    "kda": Mixer(_kda_shapes, _kda, _rbg_normal, "trunk_kda"),
    "mla": Mixer(_mla_shapes, _mla, _rbg_normal, "trunk_mla"),
}


# ----------------------------------------------------------------------
# The residual path
# ----------------------------------------------------------------------
# What a layer carries from sublayer to sublayer, and how a sublayer reads
# it and writes to it. ``shapes(arch, normal)`` names the leaves a layer
# holds for it; ``spread(x, arch)`` makes what the layers carry of the
# embedding ``(..., S, hidden)`` and ``gather`` what the heads read of it;
# ``read(x, lp, sublayer, arch)`` gives a swarm's sublayer (``attn`` or
# ``ffn``) its input ``(S, hidden)``, what its parts are added to, how, and
# counters; ``add(base, part, how)`` adds a part ``(S, hidden)``.
#
# ``plain``: one stream and a sum, ``x = x + F(norm(x))``.
# ``hyper``: ``n = hc_mult`` streams ``X``, a tuple of ``(S, hidden)``, under
# manifold-constrained hyper-connections (equations in
# ``benchmarks/reference/policy_trunk_mla_hc.py``): a sublayer reads the
# mixture ``h_pre . X`` and writes ``X' = H_res X + h_post (outer) y``, with
# ``h_pre``, ``h_post`` and the doubly stochastic ``H_res`` made per token
# from the streams themselves. A stream is an array of its own: stacked
# ``(n, S, hidden)`` they are one buffer four times the size of every other,
# which the compiler cannot place in what is left beside the parameters, and
# ``(S, n, hidden)`` pads a token's ``n`` rows to a tile of 8. The
# coefficients have the tokens on the lanes, ``(n, S)`` and ``(n, n, S)``.


def _hc_bias(key, shape, dtype=jnp.float32):
    """``[b_pre | b_post | b_res]``: N(0, 1), and 2 more on ``b_res``'s
    diagonal: ``H_res`` lies near the identity and visibly off it."""
    n = math.isqrt(shape[-1] + 1) - 1  # the width is n (n + 2)
    diagonal = jnp.concatenate([jnp.zeros(2 * n, dtype), jnp.eye(n, dtype=dtype).reshape(-1)])
    return jax.random.normal(key, shape, dtype) + 2.0 * diagonal


def _hyper_shapes(a: TrunkArch, normal: Callable):
    n = a.hc_mult
    shapes = {}
    for sublayer in ("attn", "ffn"):
        shapes.update({
            # [phi_pre | phi_post | phi_res], a row a number of vec(X[t])
            f"hc_{sublayer}_phi": (normal, (n * a.hidden_size, n * (n + 2))),
            f"hc_{sublayer}_alpha": (nn.initializers.constant(0.01), (3,)),
            f"hc_{sublayer}_b": (_hc_bias, (n * (n + 2),)),
        })
    return shapes


def _hyper_read(xs: Tuple[Array, ...], lp: Dict[str, Array], sublayer: str, arch: TrunkArch):
    n, hidden = arch.hc_mult, arch.hidden_size
    with jax.named_scope("trunk_residual"):
        phi = lp[f"hc_{sublayer}_phi"].reshape(n, hidden, -1)
        alpha, b = lp[f"hc_{sublayer}_alpha"], lp[f"hc_{sublayer}_b"][:, None]
        # u phi for u = rms(vec(X[t])): the product on the streams as they
        # are, then the token's scale
        square = sum((x * x).sum(-1) for x in xs) / (n * hidden)
        scale = jax.lax.rsqrt(square + arch.rms_norm_eps)[:, None]
        pre, post, res = jnp.split(
            (sum(x @ phi[i] for i, x in enumerate(xs)) * scale).T, (n, 2 * n), 0
        )
        b_pre, b_post, b_res = jnp.split(b, (n, 2 * n), 0)
        h_pre = jax.nn.sigmoid(alpha[0] * pre + b_pre)  # (n, S)
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * post + b_post)
        with jax.named_scope("hc_sinkhorn"):
            h_res = jnp.exp(
                jnp.clip(alpha[2] * res + b_res, *arch.hc_res_clamp)
            ).reshape(n, n, -1)  # [row, column, token]
            def step(_, h_res):  # columns, then rows
                h_res = h_res / (h_res.sum(0, keepdims=True) + arch.hc_eps)
                return h_res / (h_res.sum(1, keepdims=True) + arch.hc_eps)

            # a loop and not its steps written out: 14,000 of the compiled
            # program's 39,000 instructions and 16 s of a cold run otherwise
            # (PERF.md section 6, PR 35); the gradient goes through all of them
            h_res = jax.lax.fori_loop(0, arch.hc_sinkhorn_iters, step, h_res)
        mixed = sum(h_pre[i][:, None] * x for i, x in enumerate(xs))
        base = tuple(
            sum(h_res[i, j][:, None] * x for j, x in enumerate(xs)) for i in range(n)
        )
        diagonal = sum(h_res[i, i] for i in range(n))
        counters = {
            "hc_res_offdiag_mean": 1.0 - diagonal.mean() / n,
            "hc_sinkhorn_row_err": jnp.abs(h_res.sum(1) - 1.0).max(),
        }
    return mixed, base, h_post, counters


def _hyper_add(base: Tuple[Array, ...], part: Array, h_post: Array):
    with jax.named_scope("trunk_residual"):
        return tuple(x + h_post[i][:, None] * part for i, x in enumerate(base))


class Residual(NamedTuple):
    shapes: Callable
    spread: Callable
    read: Callable
    add: Callable
    gather: Callable


RESIDUALS = {
    "plain": Residual(
        shapes=lambda arch, normal: {},
        spread=lambda x, arch: x,
        read=lambda x, lp, sublayer, arch: (x, x, None, {}),
        add=lambda base, part, how: base + part,
        gather=lambda x: x,
    ),
    "hyper": Residual(
        shapes=_hyper_shapes,
        spread=lambda x, arch: (x,) * arch.hc_mult,  # every stream starts as e_t
        read=_hyper_read,
        add=_hyper_add,
        gather=sum,
    ),
}


# ----------------------------------------------------------------------
# The layer and the module
# ----------------------------------------------------------------------


def _moe_shapes(a: TrunkArch, normal: Callable):
    h, f = a.hidden_size, a.moe_intermediate_size
    shapes = {
        "moe_norm": (nn.initializers.ones, (h,)),
        "router": (normal, (h, a.num_experts)),
        **({"router_bias": (nn.initializers.zeros, (a.num_experts,))} if a.router_bias else {}),
        "w_gate": (normal, (a.experts_held, h, f)),
        "w_up": (normal, (a.experts_held, h, f)),
        "w_down": (normal, (a.experts_held, f, h)),
    }
    if a.shared_expert_size:
        shapes.update({
            "s_in": (normal, (h, 2 * a.shared_expert_size)),  # [gate | up]
            "s_down": (normal, (a.shared_expert_size, h)),
        })
    return shapes


def _dense_shapes(a: TrunkArch, normal: Callable):
    h, f = a.hidden_size, a.intermediate_size
    return {
        "dense_norm": (nn.initializers.ones, (h,)),
        "d_in": (normal, (h, 2 * f)),  # [gate | up]
        "d_down": (normal, (f, h)),
    }


def trunk_layer(
    x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool = False,
    kind: str = "sparse_gqa",
):
    """One decoder layer of mixer ``kind`` on one swarm, ``x (S, hidden)`` or
    what the residual path carries in its place; also its counters. The
    feed-forward sublayer is the dense SwiGLU where ``lp`` holds one and the
    expert layer otherwise. ``collect`` adds what was selected to the
    counters, the key mask ``(S, S)`` of a ``sparse_gqa`` layer and the
    experts ``(S, top)``: what a small swarm's test compares."""
    s = jax.tree_util.tree_leaves(x)[0].shape[-2]
    mixer, residual = MIXERS[kind], RESIDUALS[arch.residual]
    h, x, how, seen = residual.read(x, lp, "attn", arch)
    part, sums, collected = mixer.mix(h, lp, arch, collect)
    with jax.named_scope(mixer.scope):
        x = residual.add(x, part, how)

    h, x, how, seen_ffn = residual.read(x, lp, "ffn", arch)
    counters, e_top = {}, None
    if "d_in" in lp:  # a leading dense layer
        with jax.named_scope("dense_ffn"):
            h2 = _rms(h, lp["dense_norm"], arch.rms_norm_eps)
            x = residual.add(x, dense_ffn(h2, lp["d_in"], lp["d_down"]), how)
    else:
        with jax.named_scope("trunk_moe"):
            with jax.named_scope("router"):
                h2 = _rms(h, lp["moe_norm"], arch.rms_norm_eps)
                e_top, c = route(
                    h2, lp["router"], arch.num_experts_per_tok, arch.norm_topk_prob,
                    arch.scoring_func, lp.get("router_bias"), arch.routed_scaling_factor,
                )
            with jax.named_scope("routed_experts"):
                added, counters = expert_layer(
                    h2, e_top, c, lp["w_gate"], lp["w_up"], lp["w_down"],
                    arch.expert_share,
                )
            x = residual.add(x, added, how)
            if "s_in" in lp:  # the model has a shared expert
                with jax.named_scope("shared_expert"):
                    x = residual.add(x, shared_expert(h2, lp["s_in"], lp["s_down"]), how)
    for name, total in sums.items():
        counters[name] = total / s
    for name, value in seen.items():  # of the layer's two sublayers
        counters[name] = _OVER.get(name, jnp.mean)(jnp.stack([value, seen_ffn[name]]))
    if collect:
        counters.update(collected)
        if e_top is not None:
            counters["selected_experts"] = e_top
    return x, counters


def period_names(period: Tuple[str, ...]) -> Tuple[str, ...]:
    """The parameter groups of a period's layers: ``0_gated_gqa``, ``1_kda``..."""
    return tuple(f"{i}_{kind}" for i, kind in enumerate(period))


def _one_draw(key, shapes: Dict[str, tuple]) -> Dict[str, tuple]:
    """``shapes`` (name -> (initialiser, shape)) with every ``_rbg_normal``
    matrix carved, in order, out of one draw: a draw is a program for the
    TPU to compile, a quarter of a second each and forty of them a hybrid
    trunk, and what is drawn is the same distribution."""
    drawn = {name: shape for name, (init, shape) in shapes.items() if init is _rbg_normal}
    sizes = [math.prod(shape) for shape in drawn.values()]
    parts = jnp.split(
        _rbg_normal(key, (sum(sizes),)), list(itertools.accumulate(sizes))[:-1]
    )
    carved = {
        name: part.reshape(shape) for (name, shape), part in zip(drawn.items(), parts)
    }
    return {
        name: ((lambda *_, name=name: carved[name]) if name in carved else init, shape)
        for name, (init, shape) in shapes.items()
    }


def _stack(module: nn.Module, arch: TrunkArch, kind: str, count: Optional[int]):
    """The parameters of ``count`` layers of ``kind`` (mixer, what the
    residual path holds, then the expert layer) as ``module``'s own, stacked
    on a leading axis; without a ``count``, of one leading dense layer as it
    is."""
    mixer = MIXERS[kind]
    feed_forward = _dense_shapes if count is None else _moe_shapes
    shapes = {
        name: (init, (*(() if count is None else (count,)), *shape))
        for name, (init, shape) in {
            **mixer.shapes(arch),
            **RESIDUALS[arch.residual].shapes(arch, mixer.normal),
            **feed_forward(arch, mixer.normal),
        }.items()
    }
    # (not for ``sparse_gqa``: taking a key here would move its draws)
    if mixer.normal is _rbg_normal and module.is_initializing():
        shapes = _one_draw(module.make_rng("params"), shapes)
    return {name: module.param(name, init, shape) for name, (init, shape) in shapes.items()}


class _LayerStack(nn.Module):
    """One layer of the pattern's period, stacked over the periods; or,
    without ``periods``, one leading dense layer."""

    arch: TrunkArch
    kind: str
    periods: Optional[int]

    @nn.compact
    def __call__(self) -> Dict[str, Array]:
        return _stack(self, self.arch, self.kind, self.periods)


# A hybrid trunk's layer is traced and lowered once a kind, not once a layer
# and a pass: the rollout's, the bootstrap's and the update's forward passes
# over the three ``kda`` layers are one jitted function's one trace.
_traced_once = jax.jit(trunk_layer, static_argnames=("arch", "collect", "kind"))


class TrunkLayers(nn.Module):
    """The held layers' parameters and the scan over them: first the leading
    dense layers, each a group ``dense<i>_<kind>/<name>`` run on its own, then
    the periods of the layer pattern. A trunk of one kind holds ``<name>
    (layers, ...)`` and scans over its layers; one whose
    layers differ holds ``<i>_<kind>/<name> (periods, ...)`` for the ``i``-th
    layer of its pattern's period and scans over the periods, the body
    running a period's layers in order. Stacked by position and not by
    kind: a scan over a run of one kind would compile the kind once a pass
    (20 s of a cold run), but a loop's body copies its layer out of the
    stack, and the stack's gradient in, in every pass: 3.8% of the rate and
    1.27 GiB at Solar-Open2's widths (PERF.md section 6, PR 33)."""

    arch: TrunkArch

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, Dict[str, Array]]:
        a = self.arch
        leading, period = a.layer_kinds[: a.dense_layers], a.period
        periods = (a.layers_held - len(leading)) // len(period)
        uniform = len(period) == 1
        residual = RESIDUALS[a.residual]
        layer = trunk_layer if uniform and not leading else _traced_once
        by_layer = {}  # counter -> its values, a held layer (or the scan's) each

        def note(counters):
            for counter, value in counters.items():
                by_layer.setdefault(counter, []).append(value)

        def run_layer(x, lp, kind=period[0]):
            # a swarm at a time, so that what a layer holds at once does
            # not grow with the batch
            swarm = jax.checkpoint(
                functools.partial(layer, lp=lp, arch=a, kind=kind),
                policy=jax.checkpoint_policies.save_only_these_names(*_KEPT),
            )
            return jax.lax.map(swarm, x)

        def run_period(x, pp):
            found = {}
            for name, kind in zip(period_names(period), period):
                x, counters = run_layer(x, pp[name], kind)
                for counter, value in counters.items():
                    found.setdefault(counter, []).append(value)
            return x, {  # the mean over the period's layers that have it
                name: jnp.stack(values).mean(0) for name, values in found.items()
            }

        x = residual.spread(x, a)
        for i, kind in enumerate(leading):
            x, counters = run_layer(
                x, _LayerStack(a, kind, None, name=f"dense{i}_{kind}")(), kind
            )
            note(counters)
        if uniform:
            stacked = _stack(self, a, period[0], periods)
        else:
            stacked = {
                name: _LayerStack(a, kind, periods, name=name)()
                for name, kind in zip(period_names(period), period)
            }
        x, counters = jax.lax.scan(run_layer if uniform else run_period, x, stacked)
        note(counters)
        return residual.gather(x), {
            name: _over_layers(name, values) for name, values in by_layer.items()
        }


def _over_layers(name: str, values) -> Array:
    """A counter's values by layer and swarm as one number."""
    flat = values[0] if len(values) == 1 else jnp.concatenate(
        [value.reshape(-1) for value in values]
    )
    return _OVER.get(name, jnp.mean)(flat)


class TrunkActorCritic(nn.Module):
    """``__call__(obs)`` takes ``obs (..., N, obs_dim)`` in the k-NN
    layout and returns per-agent ``(action_mean, log_std, value)``. The
    trunk reads the geometric floats (own position, k offsets, k
    distances, relative goal), not the neighbour indices at the row's end.
    """

    arch: TrunkArch
    k: int
    act_dim: int = 2
    goal_in_obs: bool = True
    log_std_init: float = 0.0
    per_formation: bool = True  # trainer flag: minibatch whole formations

    @nn.compact
    def __call__(
        self, obs: Array, mask: Optional[Array] = None
    ) -> Tuple[Array, Array, Array]:
        if mask is not None:
            raise ValueError("the trunk has no path for padded formations")
        features = 2 + 3 * self.k + (2 if self.goal_in_obs else 0)
        lead, s = obs.shape[:-2], obs.shape[-2]
        # Products in three bf16 passes (``high``), in the backward pass
        # too (a transpose keeps its product's precision). At the backend's
        # default, one pass, the program is as far from the float32
        # reference as that reference computed in bfloat16 is, and nothing
        # could tell a sound run from one in a lower precision.
        with jax.default_matmul_precision("high"):
            # at ``highest``: the embedding stands for a table lookup, which
            # is exact, and agents differ by small parts of their positions
            x = nn.Dense(
                self.arch.hidden_size,
                kernel_init=nn.initializers.normal(0.02),
                precision=HIGHEST,
                name="embed",
            )(obs[..., :features].reshape(-1, s, features))
            x, counters = TrunkLayers(self.arch, name="layers")(x)
            final_norm = self.param(
                "final_norm", nn.initializers.ones, (self.arch.hidden_size,)
            )
            x = _rms(x, final_norm, self.arch.rms_norm_eps)
            mean = PolicyHead(self.act_dim, (), name="actor")(x)
            value = PooledValueHead((), name="critic")(x)
        if not self.is_initializing():  # init would hand them back as state
            for name, value_ in counters.items():
                self.sow("counters", name, value_)
        log_std = self.param(
            "log_std",
            nn.initializers.constant(self.log_std_init),
            (self.act_dim,),
        )
        return (
            mean.reshape(*lead, s, self.act_dim),
            log_std,
            value.reshape(*lead, s),
        )

    def forward_counters(self, params, obs: Array) -> Dict[str, Array]:
        """The counters one forward pass sows (those of ``COUNTERS`` this
        trunk's layers have), as scalars: the share of assignments that fall
        on held experts, the held experts' largest load over their mean;
        the mean number of keys a query selects (``sparse_gqa``); the mean
        log-decay a step and the mean beta (``kda``: how far the state
        reaches and how hard it is overwritten). Read on demand: it is a
        forward pass of its own, so the training iteration does not make
        it."""
        _, sown = self.apply(params, obs, mutable=["counters"])
        return {
            name: sown["counters"][name][0]
            for name in COUNTERS if name in sown["counters"]
        }
