"""Plain reference of the trunk policy: the decoder block of
Keye-VL-2.0-30B-A3B (language model of
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json)
over the agents of one swarm as its tokens. A sequence is a swarm in
ring-slot order at one time step, the mask is causal over the agent index,
and every layer is: RMSNorm, grouped-query attention (q/k head norms, RoPE)
over the keys a learned indexer selects, RMSNorm, a top-k routed expert
layer of which this chip holds a share.

Straight ``jax.numpy``: ``jax.lax.top_k`` for both selections (ties go to
the lower index), a ``-inf`` mask and a dense softmax, a loop over the held
experts with a mask. Its one concession to size is that a layer (one
``scan`` over them) works a swarm at a time and by query blocks, each
under ``jax.checkpoint``, so that
a swarm of 8,192 fits beside its float32 state (every block against all
the swarm's keys, masked: one loop body to compile). Parameters sit in the
tree the program's policy reads. Imports jax and the reference's own
helpers only.

Departures from the published model, each also in the configuration file:
- depth ``layers_held`` of ``num_hidden_layers``; ``experts_held`` of the
  ``num_experts`` routed experts, ids ``share * experts_held ...`` for
  share ``expert_share[0]`` of ``expert_share[1]``: the router keeps its
  published width and top-k, and what the absent experts would add is
  left out;
- no vocabulary: tokens are continuous observations, so the embedding
  table is a dense layer on the k-NN observation's geometric floats and
  the output head is the system's Gaussian policy head and pooled value
  head; the vision tower is left out (no images);
- ``mrope_section`` with all three position axes equal to the agent index
  is plain 1-D RoPE, which is what is written here;
- assumed (not in the published config): q/k head RMSNorm; the indexer's
  form (per-head ReLU scores weighted per query, one shared key head under
  LayerNorm, RoPE on its queries and key, scale ``heads^-0.5 * dim^-0.5``),
  without its Hadamard transform and FP8 quantisation; initialiser
  normal(0, 0.02), norms at 1;
- the indexer is run and not trained: the selection is indices, so its
  leaves get exactly zero gradient, and the PPO loss has no alignment or
  router balance term.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .policy_mlp import _affine, _dense

PER_FORMATION = True  # a minibatch row is a whole swarm-step (N tokens)

INIT_STD = 0.02


def _features(env) -> int:
    """The geometric floats of the k-NN observation: own position, k
    offsets, k distances, relative goal. The k neighbour indices at the
    end of the row are not fed to the trunk."""
    return 2 + 3 * env["knn_k"] + (2 if env["goal_in_obs"] else 0)


def init(key, policy, env, act_dim=2):
    h, hd = policy["hidden_size"], policy["head_dim"]
    nq, nkv = policy["num_attention_heads"], policy["num_key_value_heads"]
    sa = policy["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    layers, held = policy["layers_held"], policy["experts_held"]
    f = policy["moe_intermediate_size"]
    keys = iter(jax.random.split(key, 14))

    def normal(*shape, lead=1):
        """Drawn a slice of the ``lead`` leading axes at a time: a TPU
        compiles one draw of 10^8 numbers in 17 s, 64 draws of 10^6 in
        under one."""
        slices = jax.random.split(next(keys), math.prod(shape[:lead]))
        draw = lambda k: INIT_STD * jax.random.normal(k, shape[lead:], jnp.float32)  # noqa: E731
        return jax.lax.map(draw, slices).reshape(shape)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    stack = {
        "attn_norm": ones(layers, h),
        "wq": normal(layers, h, nq * hd),
        "wk": normal(layers, h, nkv * hd),
        "wv": normal(layers, h, nkv * hd),
        "wo": normal(layers, nq * hd, h),
        "q_norm": ones(layers, hd),
        "k_norm": ones(layers, hd),
        "idx_wq": normal(layers, h, ni * di),
        "idx_wk": normal(layers, h, di),
        "idx_w": normal(layers, h, ni),
        "idx_k_scale": ones(layers, di),
        "idx_k_bias": jnp.zeros((layers, di), jnp.float32),
        "moe_norm": ones(layers, h),
        "router": normal(layers, h, policy["num_experts"]),
        "w_gate": normal(layers, held, h, f, lead=2),
        "w_up": normal(layers, held, h, f, lead=2),
        "w_down": normal(layers, held, f, h, lead=2),
    }
    return {
        "params": {
            "embed": {
                "kernel": normal(_features(env), h, lead=0),
                "bias": jnp.zeros((h,), jnp.float32),
            },
            "layers": stack,
            "final_norm": ones(h),
            "actor": {"pi_head": _dense(next(keys), h, act_dim, 0.01)},
            "critic": {"vf_head": _dense(next(keys), 2 * h, 1, 1.0)},
            "log_std": jnp.full((act_dim,), policy["log_std_init"], jnp.float32),
        }
    }


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g.astype(x.dtype)


def _layer_norm(x, scale, bias, eps):
    centred = x - x.mean(-1, keepdims=True)
    var = (centred * centred).mean(-1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * scale.astype(x.dtype) + bias.astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE on ``x (S, ..., d)`` at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    angle = angle.reshape(s, *([1] * (x.ndim - 2)), d)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angle).astype(x.dtype) + rotated * jnp.sin(angle).astype(x.dtype)


def index_scores(qi, ki, w):
    """``I[t, s]`` for queries ``qi (T, heads, d)``, the one key head
    ``ki (S, d)`` and the per-query head weights ``w (T, heads)``."""
    heads, d = qi.shape[-2], qi.shape[-1]
    per_head = jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki))
    return (heads**-0.5 * d**-0.5) * (w[:, :, None] * per_head).sum(1)


def selected_keys(index, visible, topk):
    """The mask ``(T, S)`` of the ``min(visible keys, topk)`` largest
    entries of each row of ``index`` among the ``visible`` ones, ties
    toward the lower index (``jax.lax.top_k``'s rule): everything above
    the ``topk``-th largest value that a sort finds, and of the entries
    equal to it the first few by index that fill the set. (A scatter of
    ``top_k``'s indices says the same and is what the tests hold this to;
    a TPU takes 13 ns an index for one, 0.2 s a swarm and layer.)"""
    masked = jnp.where(visible, index, -jnp.inf)
    kth = jax.lax.top_k(masked, min(topk, index.shape[-1]))[0][:, -1:]
    above, tied = masked > kth, masked == kth
    wanted = topk - above.sum(-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= wanted))) & visible


def _attention_block(q, k, v, qi, ki, w, start, topk):
    """Queries ``start .. start + T`` of one swarm against keys ``0 ..
    S``, of which they can see ``0 .. start + T``. ``q (T, nq, d)``,
    ``k, v (S, nkv, d)``."""
    t, nq, d = q.shape
    s, nkv = k.shape[0], k.shape[1]
    visible = jnp.arange(s)[None, :] <= (start + jnp.arange(t))[:, None]
    mask = selected_keys(
        jax.lax.stop_gradient(index_scores(qi, ki, w)), visible, topk
    )
    group = nq // nkv  # head a reads key group a // group
    scores = jnp.einsum(
        "tad,sad->ats", q, jnp.repeat(k, group, axis=1)
    ) / jnp.sqrt(jnp.asarray(d, q.dtype))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ats,sad->tad", p, jnp.repeat(v, group, axis=1))
    return out.reshape(t, nq * d), mask


def route(h2, router, top, normalise):
    """Each token's ``top`` experts of all the router's, and the weights
    ``c`` they are combined with."""
    r = jax.nn.softmax(h2 @ router.astype(h2.dtype), axis=-1)
    r_top, e_top = jax.lax.top_k(r, top)
    c = r_top / r_top.sum(-1, keepdims=True) if normalise else r_top
    return e_top, c


def expert_layer(h2, e_top, c, w_gate, w_up, w_down, held_ids):
    """What the experts ``held_ids`` (weights in that order) add for the
    tokens routed to them; every other expert's part is left out. Each
    held expert computes every token, and a mask keeps its own."""

    def add_expert(out, held):
        e, gate, up, down = held
        weight = ((e_top == e) * c).sum(-1)  # 0 where the token is not e's
        y = jax.nn.silu(h2 @ gate.astype(h2.dtype)) * (h2 @ up.astype(h2.dtype))
        return out + weight[:, None] * (y @ down.astype(h2.dtype)), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2),
        (jnp.asarray(list(held_ids)), w_gate, w_up, w_down),
    )
    return out


def held_experts(policy):
    share, _ = policy["expert_share"]
    held = policy["experts_held"]
    return range(share * held, (share + 1) * held)


def layer(x, lp, policy, collect=False):
    """One decoder layer on one swarm ``x (S, hidden)``. ``collect`` also
    returns what was selected: the key mask ``(S, S)`` and the experts
    ``(S, top)``; only a small swarm can afford that."""
    s = x.shape[0]
    eps, theta = policy["rms_norm_eps"], policy["rope_theta"]
    nq, nkv, hd = (
        policy["num_attention_heads"], policy["num_key_value_heads"], policy["head_dim"]
    )
    sa = policy["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731

    h = _rms(x, lp["attn_norm"], eps)
    q = _rope(_rms((h @ cast("wq")).reshape(s, nq, hd), lp["q_norm"], eps), theta)
    k = _rope(_rms((h @ cast("wk")).reshape(s, nkv, hd), lp["k_norm"], eps), theta)
    v = (h @ cast("wv")).reshape(s, nkv, hd)
    qi = _rope((h @ cast("idx_wq")).reshape(s, ni, di), theta)
    ki = _rope(
        _layer_norm(h @ cast("idx_wk"), lp["idx_k_scale"], lp["idx_k_bias"], eps), theta
    )
    w = h @ cast("idx_w")

    chunk = sa["q_chunk_size"] if s % sa["q_chunk_size"] == 0 else s

    def block(start):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk)  # noqa: E731
        out, mask = _attention_block(
            rows(q), k, v, rows(qi), ki, rows(w), start, sa["topk"]
        )
        return (out, mask) if collect else out

    attended = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, chunk))
    if collect:
        attended, masks = attended
        masks = masks.reshape(s, s)
    x = x + attended.reshape(s, nq * hd) @ cast("wo")

    h2 = _rms(x, lp["moe_norm"], eps)
    e_top, c = route(
        h2, lp["router"], policy["num_experts_per_tok"], policy["norm_topk_prob"]
    )
    x = x + expert_layer(
        h2, e_top, c, lp["w_gate"], lp["w_up"], lp["w_down"], held_experts(policy)
    )
    if collect:
        return x, (masks, e_top)
    return x


def apply(params, policy, env, obs, dtype=jnp.float32, collect=False):
    """``(mean, log_std, value)`` for ``obs (..., N, obs_dim)`` in the
    k-NN layout; ``dtype`` is the precision the trunk computes in (the
    control lowers it). ``collect`` appends each layer's selections."""
    p = params["params"]
    lead, s = obs.shape[:-2], obs.shape[-2]
    feats = obs[..., : _features(env)].reshape(-1, s, _features(env)).astype(dtype)
    x = _affine(p["embed"], feats)

    def run_layer(x, lp):
        swarm_layer = lambda one: layer(one, lp, policy, collect)  # noqa: E731
        if not collect:
            swarm_layer = jax.checkpoint(swarm_layer)
        out = jax.lax.map(swarm_layer, x)  # a swarm at a time
        return out if collect else (out, None)

    x, selections = jax.lax.scan(run_layer, x, p["layers"])
    x = _rms(x, p["final_norm"], policy["rms_norm_eps"])
    mean = _affine(p["actor"]["pi_head"], x).astype(jnp.float32)
    pooled = jnp.broadcast_to(x.mean(axis=-2, keepdims=True), x.shape)
    value = _affine(p["critic"]["vf_head"], jnp.concatenate([x, pooled], -1))
    value = value.astype(jnp.float32)[..., 0]
    result = (mean.reshape(*lead, s, -1), p["log_std"], value.reshape(*lead, s))
    if collect:  # [(key mask, experts)] by layer
        return (*result, list(zip(*selections)))
    return result


def mean_selected(s, topk):
    """Mean ``|Sel(t)|`` over the queries of a swarm of ``s``."""
    return sum(min(t + 1, topk) for t in range(s)) / s


def forward_flops_per_agent(policy, env, act_dim=2):
    """The work the equations require for one token's forward pass, not
    what an implementation spends: multiply-adds x2 of the projections,
    the index scores over the keys a query can see, attention over the
    keys it selects, the router, and the held experts' expected share of
    the ``num_experts_per_tok`` assignments."""
    h, hd = policy["hidden_size"], policy["head_dim"]
    nq, nkv = policy["num_attention_heads"], policy["num_key_value_heads"]
    sa = policy["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    s = env["num_agents_per_formation"]
    attention = 2 * (2 * h * nq * hd + 2 * h * nkv * hd)
    indexer = 2 * (h * ni * di + h * di + h * ni)
    scores = (s + 1) / 2 * (2 * ni * di + 2 * ni)
    selected = mean_selected(s, sa["topk"]) * 4 * nq * hd
    router = 2 * h * policy["num_experts"]
    experts = (
        policy["num_experts_per_tok"] * policy["experts_held"] / policy["num_experts"]
        * 2 * 3 * h * policy["moe_intermediate_size"]
    )
    per_layer = attention + indexer + scores + selected + router + experts
    heads = 2 * _features(env) * h + 2 * h * act_dim + 2 * 2 * h
    return policy["layers_held"] * per_layer + heads
