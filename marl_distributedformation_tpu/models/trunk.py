"""A transformer trunk over the agents of a swarm as its tokens.

``policy=trunk trunk=<name>`` (train.py) reads the architecture from
``cfg/trunk/<name>.yaml``: a published decoder block under its published
key names, plus what of it this chip holds (``layers_held``,
``experts_held``, ``expert_share``). A sequence is one swarm at one time
step in ring-slot order, the mask is causal over the agent index, and a
minibatch row is a whole swarm-step (``per_formation``).

The block (equations in ``benchmarks/reference/policy_trunk.py``, which
the tests hold this module to): RMSNorm, grouped-query attention with q/k
head norms and RoPE over the keys a learned indexer selects (its ``topk``
largest index scores among the keys a query can see), RMSNorm, a routed
expert layer that is told which experts it holds, routes over all of them
and computes the part its own give, with no token dropped.

How it is computed here:

- layers run under one ``lax.scan``, a swarm at a time with
  ``jax.checkpoint`` a layer; attention works by blocks of ``q_chunk_size`` queries against the keys
  the block can see, each block recomputed in the backward pass, so the
  ``heads x S x S`` scores never exist whole;
- the selection needs no sort: a query's ``topk``-th largest index score
  is found by bisection on the float's bits (32 counting passes), ties go
  to the lower index by a running count, and the result is a mask on the
  block's scores. The thresholds are what the layer's checkpoint keeps, so
  the recomputation selects nothing twice. The group whose queries see at
  most ``topk`` keys selects everything and skips it;
- precision: float32 state and activations; products in three bf16 passes
  (jax's ``high``); both selections (the indexer's ``qI . kI`` and the
  router's logits) and the observation embedding at float32 ``highest``:
  fewer passes there re-rank keys around rank ``topk`` and experts around
  rank ``num_experts_per_tok``;
- the expert layer runs each held expert over the swarm and masks in the
  tokens routed to it: dense under the routing's mask, not dispatched
  (``expert_layer``). The indexer gets no gradient (the selection is
  indices).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import yaml
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from marl_distributedformation_tpu.models.common import (
    PolicyHead,
    PooledValueHead,
)

Array = jax.Array

HIGHEST = jax.lax.Precision.HIGHEST
# What a layer's checkpoint keeps besides its input (see select_keys).
_KEPT = ("trunk_select_threshold", "trunk_select_ties")
COUNTERS = ("moe_held_share", "moe_load_max_over_mean", "indexer_selected_mean")


@dataclasses.dataclass(frozen=True)
class TrunkArch:
    """The architecture file's content (hashable: a flax attribute)."""

    name: str
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    q_chunk_size: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    layers_held: int
    experts_held: int
    expert_share: Tuple[int, int]  # (this chip's share, of how many)

    @classmethod
    def from_dict(cls, name: str, data: dict) -> "TrunkArch":
        sa = data["sa_config"]
        share, count = (int(v) for v in data["expert_share"])
        arch = cls(
            name=name,
            expert_share=(share, count),
            **{
                field.name: (sa if field.name in sa else data)[field.name]
                for field in dataclasses.fields(cls)
                if field.name not in ("name", "expert_share")
            },
        )
        unsupported = {
            "hidden_act": data.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(data.get("attention_bias", False)),
            "indexer_num_kv_heads": int(sa.get("indexer_num_kv_heads", 1)) != 1,
            "num_key_value_heads": arch.num_attention_heads
            % arch.num_key_value_heads != 0,
            "expert_share": not 0 <= share < count
            or arch.experts_held * count != arch.num_experts,
            "layers_held": not 1 <= arch.layers_held <= int(data["num_hidden_layers"]),
        }
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
    s, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
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


def route(h2: Array, router: Array, top: int, normalise: bool):
    """Each token's ``top`` experts of all the router's and the weights
    they combine with; the logits at float32 ``highest``."""
    logits = jnp.einsum("sh,he->se", h2, router, precision=HIGHEST)
    r_top, e_top = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top)
    c = r_top / r_top.sum(-1, keepdims=True) if normalise else r_top
    return e_top, c


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


# ----------------------------------------------------------------------
# The layer and the module
# ----------------------------------------------------------------------


def trunk_layer(
    x: Array, lp: Dict[str, Array], arch: TrunkArch, collect: bool = False
):
    """One decoder layer on one swarm ``x (S, hidden)``; also its
    counters. ``collect`` adds what was selected to them, the key mask
    ``(S, S)`` and the experts ``(S, top)``: what a small swarm's test
    compares."""
    s = x.shape[0]
    eps, theta = arch.rms_norm_eps, arch.rope_theta
    nq, nkv, hd = arch.num_attention_heads, arch.num_key_value_heads, arch.head_dim
    ni, di = arch.indexer_num_heads, arch.indexer_head_dim

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
        x = x + jnp.concatenate(outs) @ lp["wo"]

    with jax.named_scope("trunk_moe"):
        h2 = _rms(x, lp["moe_norm"], eps)
        e_top, c = route(
            h2, lp["router"], arch.num_experts_per_tok, arch.norm_topk_prob
        )
        added, counters = expert_layer(
            h2, e_top, c, lp["w_gate"], lp["w_up"], lp["w_down"], arch.expert_share
        )
        x = x + added
    counters["indexer_selected_mean"] = selected / s
    if collect:
        counters["selected_keys"] = jnp.concatenate(masks)
        counters["selected_experts"] = e_top
    return x, counters


class TrunkLayers(nn.Module):
    """The held layers' parameters, stacked on a leading layer axis, and
    the scan over them."""

    arch: TrunkArch

    @nn.compact
    def __call__(self, x: Array) -> Tuple[Array, Dict[str, Array]]:
        a = self.arch
        h, hd, f = a.hidden_size, a.head_dim, a.moe_intermediate_size
        nq, nkv = a.num_attention_heads, a.num_key_value_heads
        ni, di = a.indexer_num_heads, a.indexer_head_dim

        def normal(key, shape, dtype=jnp.float32):
            # a layer's (and an expert's) slice at a time: the TPU compiles
            # one draw of 10^8 numbers in 17 s, 64 of 10^6 in under one
            lead = len(shape) - 2
            draw = lambda k: nn.initializers.normal(0.02)(k, shape[lead:], dtype)  # noqa: E731
            slices = jax.random.split(key, math.prod(shape[:lead]))
            return jax.lax.map(draw, slices).reshape(shape)

        shapes = {
            "attn_norm": (nn.initializers.ones, (h,)),
            "wq": (normal, (h, nq * hd)),
            "wk": (normal, (h, nkv * hd)),
            "wv": (normal, (h, nkv * hd)),
            "wo": (normal, (nq * hd, h)),
            "q_norm": (nn.initializers.ones, (hd,)),
            "k_norm": (nn.initializers.ones, (hd,)),
            "idx_wq": (normal, (h, ni * di)),
            "idx_wk": (normal, (h, di)),
            "idx_w": (normal, (h, ni)),
            "idx_k_scale": (nn.initializers.ones, (di,)),
            "idx_k_bias": (nn.initializers.zeros, (di,)),
            "moe_norm": (nn.initializers.ones, (h,)),
            "router": (normal, (h, a.num_experts)),
            "w_gate": (normal, (a.experts_held, h, f)),
            "w_up": (normal, (a.experts_held, h, f)),
            "w_down": (normal, (a.experts_held, f, h)),
        }
        stacked = {
            name: self.param(name, init, (a.layers_held, *shape))
            for name, (init, shape) in shapes.items()
        }

        def layer(x, lp):
            # a swarm at a time, so that what a layer holds at once does
            # not grow with the batch
            swarm = jax.checkpoint(
                functools.partial(trunk_layer, lp=lp, arch=a),
                policy=jax.checkpoint_policies.save_only_these_names(*_KEPT),
            )
            return jax.lax.map(swarm, x)

        x, counters = jax.lax.scan(layer, x, stacked)
        return x, {name: value.mean() for name, value in counters.items()}


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
        """The counters one forward pass sows (``COUNTERS``), as scalars:
        the share of assignments that fall on held experts, the held
        experts' largest load over their mean, the mean number of keys a
        query selects. Read on demand: it is a forward pass of its own, so
        the training iteration does not make it."""
        _, sown = self.apply(params, obs, mutable=["counters"])
        return {name: sown["counters"][name][0] for name in COUNTERS}
