"""Plain reference of a trunk policy whose residual path is not a sum: the
decoder block of Xing4.0-29B-A4B
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type: xing4_0``) over the agents of one swarm as its tokens. A
sequence is a swarm in ring-slot order at one time step, causal over the
agent index. Every layer is multi-head latent attention, then a feed-forward
sublayer (a dense SwiGLU in the leading ``first_k_dense_replace`` layers, a
routed expert layer with a shared expert after them), and both sublayers
read and write ``n = hc_mult`` residual streams through manifold-constrained
hyper-connections (DeepSeek's mHC, arXiv 2512.24880).

With ``X[t] (n, hidden)`` the streams of token ``t``, ``rms(x, g)`` RMSNorm
with ``rms_norm_eps``:

- Streams. ``X_0[t] = (e_t, ..., e_t)``, ``e_t`` the observation embedding;
  after the last held layer ``x_t = sum_i X[t, i]``, then the final norm and
  the heads.
- A sublayer ``F`` with its norm ``g`` and its own ``phi``, ``alpha``, ``b``.
  ``u = rms(vec(X[t]), 1)`` over all ``n hidden`` numbers; ``P = alpha_pre
  (u phi_pre) + b_pre`` (n), ``Q = alpha_post (u phi_post) + b_post`` (n),
  ``R = alpha_res mat(u phi_res) + b_res`` (n x n, row-major); ``h_pre =
  sigmoid(P)``, ``h_post = 2 sigmoid(Q)``, ``M_0 = exp(clip(R,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))``, then ``hc_sinkhorn_iters``
  times: each column divided by (its sum + ``hc_eps``), then each row by (its
  sum + ``hc_eps``); ``H_res`` is the last ``M``. ``y = F(rms(h_pre . X[t],
  g))`` and ``X'[t] = H_res X[t] + h_post (outer) y``.
- MLA. ``cq = rms(h Wqa, gq)``; ``[q_nope | q_rope] = cq Wqb`` a head;
  ``[ckv | k_rope] = h Wkva``, ``ckv = rms(ckv, gkv)``; ``[k_nope | v] = ckv
  Wkvb`` a head; ``k_rope`` is one for all heads; rotate-half RoPE on
  ``q_rope`` and ``k_rope`` at position ``t`` with YaRN's frequencies
  (``yarn_inv_freq``), cos and sin unscaled (``mscale = mscale_all_dim``);
  scores ``(q_nope . k_nope + q_rope . k_rope) (d_nope + d_rope)^-0.5 (0.1
  mscale_all_dim ln factor + 1)^2``, causal softmax, ``y = concat_heads(softmax
  v) Wo``. Keys and values uncompressed, as training computes them: no
  absorbed products, no cache.
- Dense layer. ``y = (silu(h Wg) * (h Wu)) Wd``.
- Expert layer. ``s = sigmoid(h Wr)`` over all routed experts; chosen = the
  ``num_experts_per_tok`` largest of ``s + bias`` (``noaux_tc``: the bias
  selects and does not weigh; zero at the draw, zero gradient); ``c =
  routed_scaling_factor s_chosen / sum(s_chosen)``; the held experts' SwiGLU
  parts under those weights plus the shared expert for every token, ungated.

Straight ``jax.numpy``: the Sinkhorn is a plain ``lax.fori_loop`` (written out
as a Python loop its 20 steps, twice a layer and four times a pass, are 25 s
of a cold run's tracing and compiling), the stream mixing is
sums over the ``n`` streams, the softmax is dense under a ``-inf`` mask by
query blocks, the held experts are a loop with a mask; what is done to a
token alone is done ``TOKEN_BLOCK`` tokens at a time (``by_tokens``) and a
layer under ``jax.checkpoint``, so that a swarm of 8,192 fits beside its
float32 state. The streams of a token are kept side by side, ``vec(X[t]) (n
hidden)``. Imports jax and the reference's own helpers only.

Parameters sit in the tree the program's policy reads: ``layers/dense<i>_mla/
<name>`` for the ``i``-th held dense layer, ``layers/<name> (expert layers,
...)`` stacked for the expert layers. Side by side in one leaf: ``hc_*_phi =
[phi_pre | phi_post | phi_res]``, ``hc_*_b`` likewise, ``hc_*_alpha =
(alpha_pre, alpha_post, alpha_res)``; ``wq_b`` a head ``[q_nope | q_rope]``,
``wkv_a = [ckv | k_rope]``, ``wkv_b`` a head ``[k_nope | v]``; ``d_in = [Wg |
Wu]``, ``s_in = [Sg | Su]``.

Departures from the published model, each also in the configuration file:
- depth: layers ``first_layer_held ... + layers_held`` of
  ``num_hidden_layers``; ``experts_held`` of ``n_routed_experts`` (ids
  ``share * experts_held ...``): the router keeps its published width, top-k
  and scale, what the absent experts would add is left out, the shared
  expert is whole; of the heads the share ``head_share[0]`` of
  ``head_share[1]`` (``o Wo`` is the held heads' partial sum; the low-rank
  first halves and ``k_rope`` are whole on every chip);
- no vocabulary and no multi-token-prediction layer: tokens are continuous
  observations, the embedding is a dense layer on the k-NN observation's
  geometric floats, the heads are the system's;
- assumed (not in the published config): the hyper-connections' form as
  above (columns first; where the clamp and ``hc_eps`` enter; ``u`` without
  a learned scale; streams start equal and are summed at the end); rotate-half
  RoPE; ``alpha`` 0.01 each; ``b_pre``, ``b_post`` drawn N(0, 1), ``b_res``
  ``2 I + N(0, 1)`` (``H_res`` then lies visibly off the identity, so a
  Sinkhorn left out changes every number compared); matrices normal(0,
  0.02), norms at 1;
- not trained: no router balance loss, and the selection bias is never
  moved (``noaux_tc`` moves it outside the gradient; nothing does here).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .policy_mlp import _affine, _dense
from .policy_trunk import _features, _rms, held_experts
from .policy_trunk_hybrid import by_tokens

PER_FORMATION = True  # a minibatch row is a whole swarm-step (N tokens)

INIT_STD = 0.02
HC_ALPHA = 0.01
HC_RES_DIAGONAL = 2.0
QUERY_BLOCK = 256  # queries of the softmax at a time


def held_heads(policy):
    return policy["num_attention_heads"] // policy["head_share"][1]


def dense_held(policy):
    """How many of the held layers are leading dense ones."""
    first = policy["first_layer_held"]
    return min(max(policy["first_k_dense_replace"] - first, 0), policy["layers_held"])


def dense_names(policy):
    return [f"dense{i}_mla" for i in range(dense_held(policy))]


def init(key, policy, env, act_dim=2):
    h, n = policy["hidden_size"], policy["hc_mult"]
    nh = held_heads(policy)
    rq, rkv = policy["q_lora_rank"], policy["kv_lora_rank"]
    d_nope, d_rope, d_v = (
        policy["qk_nope_head_dim"], policy["qk_rope_head_dim"], policy["v_head_dim"]
    )
    held, f = policy["experts_held"], policy["moe_intermediate_size"]
    fs, fd = f * policy["n_shared_experts"], policy["intermediate_size"]
    experts = policy["layers_held"] - dense_held(policy)
    # XLA's own generator, one draw a matrix (policy_trunk_hybrid.py's reason)
    keys = iter(jax.random.split(
        jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg"),
        32 * (dense_held(policy) + 2),
    ))

    def normal(*shape):
        return INIT_STD * jax.random.normal(next(keys), shape, jnp.float32)

    def layer(lead, dense):
        ones = lambda *shape: jnp.ones((*lead, *shape), jnp.float32)  # noqa: E731
        mat = lambda *shape: normal(*lead, *shape)  # noqa: E731

        def hyper(name):
            noise = jax.random.normal(next(keys), (*lead, n * (n + 2)), jnp.float32)
            eye = jnp.concatenate([jnp.zeros(2 * n), jnp.eye(n).reshape(-1)])
            return {
                f"hc_{name}_phi": mat(n * h, n * (n + 2)),  # [pre | post | res]
                f"hc_{name}_alpha": HC_ALPHA * ones(3),
                f"hc_{name}_b": noise + HC_RES_DIAGONAL * eye,
            }

        mixer = {
            "attn_norm": ones(h),
            "wq_a": mat(h, rq),
            "q_a_norm": ones(rq),
            "wq_b": mat(rq, nh * (d_nope + d_rope)),  # a head [q_nope | q_rope]
            "wkv_a": mat(h, rkv + d_rope),  # [ckv | k_rope]
            "kv_a_norm": ones(rkv),
            "wkv_b": mat(rkv, nh * (d_nope + d_v)),  # a head [k_nope | v]
            "wo": mat(nh * d_v, h),
        }
        if dense:
            ffn = {
                "dense_norm": ones(h),
                "d_in": mat(h, 2 * fd),  # [Wg | Wu]
                "d_down": mat(fd, h),
            }
        else:
            ffn = {
                "moe_norm": ones(h),
                "router": mat(h, policy["n_routed_experts"]),
                "router_bias": jnp.zeros((*lead, policy["n_routed_experts"]), jnp.float32),
                "w_gate": mat(held, h, f), "w_up": mat(held, h, f), "w_down": mat(held, f, h),
                "s_in": mat(h, 2 * fs),  # [Sg | Su]
                "s_down": mat(fs, h),
            }
        return {**mixer, **hyper("attn"), **hyper("ffn"), **ffn}

    return {
        "params": {
            "embed": {
                "kernel": normal(_features(env), h),
                "bias": jnp.zeros((h,), jnp.float32),
            },
            "layers": {
                **{name: layer((), True) for name in dense_names(policy)},
                **layer((experts,), False),
            },
            "final_norm": jnp.ones((h,), jnp.float32),
            "actor": {"pi_head": _dense(next(keys), h, act_dim, 0.01)},
            "critic": {"vf_head": _dense(next(keys), 2 * h, 1, 1.0)},
            "log_std": jnp.full((act_dim,), policy["log_std_init"], jnp.float32),
        }
    }


# ----------------------------------------------------------------------
# Hyper-connections
# ----------------------------------------------------------------------


def sinkhorn(m, iters, eps):
    """``m (..., n, n)`` positive -> nearly doubly stochastic: ``iters`` times
    the columns, then the rows, each divided by (its sum + ``eps``)."""
    def step(_, m):
        m = m / (m.sum(-2, keepdims=True) + eps)
        return m / (m.sum(-1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, m)


def hyper_coefficients(xs, lp, name, policy):
    """``h_pre (T, n)``, ``h_post (T, n)``, ``H_res (T, n, n)`` of sublayer
    ``name`` for a block of tokens' streams ``xs (T, n hidden)``."""
    n = policy["hc_mult"]
    cast = lambda leaf: lp[f"hc_{name}_{leaf}"].astype(xs.dtype)  # noqa: E731
    u = xs / jnp.sqrt((xs * xs).mean(-1, keepdims=True) + policy["rms_norm_eps"])
    alpha, b = cast("alpha"), cast("b")
    pre, post, res = jnp.split(u @ cast("phi"), (n, 2 * n), -1)
    b_pre, b_post, b_res = jnp.split(b, (n, 2 * n))
    res = (alpha[2] * res + b_res).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(res, policy["mhc_h_res_clamp_min"], policy["mhc_h_res_clamp_max"]))
    return (
        jax.nn.sigmoid(alpha[0] * pre + b_pre),
        2.0 * jax.nn.sigmoid(alpha[1] * post + b_post),
        sinkhorn(m, policy["hc_sinkhorn_iters"], policy["hc_eps"]),
    )


def _streams(xs, n):
    return jnp.split(xs, n, -1)  # n of (T, hidden)


def hyper_sublayer(xs, lp, name, f, policy):
    """``X' = H_res X + h_post (outer) f(h_pre . X)`` for one swarm's streams
    ``xs (S, n hidden)``; ``f`` takes the sublayer's input before its norm and
    may mix tokens."""
    n = policy["hc_mult"]

    def read(xs):
        h_pre, h_post, h_res = hyper_coefficients(xs, lp, name, policy)
        mixed = sum(h_pre[:, i, None] * x for i, x in enumerate(_streams(xs, n)))
        return mixed, h_post, h_res

    def write(xs, y, h_post, h_res):
        old = _streams(xs, n)
        return jnp.concatenate([
            sum(h_res[:, i, j, None] * old[j] for j in range(n)) + h_post[:, i, None] * y
            for i in range(n)
        ], -1)

    mixed, h_post, h_res = by_tokens(read, xs)
    return by_tokens(write, xs, f(mixed), h_post, h_res)


# ----------------------------------------------------------------------
# Latent attention
# ----------------------------------------------------------------------


def yarn_correction_range(policy):
    """``(low, high)``: the rotary pairs below ``low`` keep their frequency,
    those from ``high`` on are slowed by ``factor``."""
    d, rs = policy["qk_rope_head_dim"], policy["rope_scaling"]

    def pair_of(rotations):
        return (
            d * math.log(rs["original_max_position_embeddings"] / (2 * math.pi * rotations))
            / (2 * math.log(policy["rope_theta"]))
        )

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), d - 1)
    return low, high


def yarn_inv_freq(policy):
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN."""
    d, rs = policy["qk_rope_head_dim"], policy["rope_scaling"]
    low, high = yarn_correction_range(policy)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = policy["rope_theta"] ** (-2.0 * i / d)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * keep + f / rs["factor"] * (1.0 - keep)


def softmax_scale(policy):
    d = policy["qk_nope_head_dim"] + policy["qk_rope_head_dim"]
    rs = policy["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return d**-0.5 * mscale * mscale


def _rope(x, inv_freq):
    """Rotate-half RoPE on ``x (S, ..., d)`` at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], -1).reshape(s, *([1] * (x.ndim - 2)), d)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(angle).astype(x.dtype) + rotated * jnp.sin(angle).astype(x.dtype)


def mla_mixer(x, lp, policy):
    """What the held latent-attention heads add for one swarm ``x (S,
    hidden)``, the sublayer's input before its norm."""
    s, eps = x.shape[0], policy["rms_norm_eps"]
    rkv = policy["kv_lora_rank"]
    d_nope, d_rope, d_v = (
        policy["qk_nope_head_dim"], policy["qk_rope_head_dim"], policy["v_head_dim"]
    )
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731

    def project(x):
        h = _rms(x, lp["attn_norm"], eps)
        q = _rms(h @ cast("wq_a"), lp["q_a_norm"], eps) @ cast("wq_b")
        ckv, k_rope = jnp.split(h @ cast("wkv_a"), (rkv,), -1)
        return q, _rms(ckv, lp["kv_a_norm"], eps) @ cast("wkv_b"), k_rope

    q, kv, k_rope = by_tokens(project, x)
    q_nope, q_rope = jnp.split(q.reshape(s, -1, d_nope + d_rope), (d_nope,), -1)
    k_nope, v = jnp.split(kv.reshape(s, -1, d_nope + d_v), (d_nope,), -1)
    inv_freq = yarn_inv_freq(policy)
    q = jnp.concatenate([q_nope, _rope(q_rope, inv_freq)], -1)
    k_rope = jnp.broadcast_to(
        _rope(k_rope, inv_freq)[:, None, :], (s, k_nope.shape[1], d_rope)
    )
    k = jnp.concatenate([k_nope, k_rope], -1)
    scale = jnp.asarray(softmax_scale(policy), x.dtype)
    chunk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, chunk)
        visible = jnp.arange(s)[None, :] <= (start + jnp.arange(chunk))[:, None]
        scores = jnp.einsum("tad,sad->ats", rows, k) * scale
        p = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("ats,sad->tad", p, v).reshape(chunk, -1)

    attended = jax.lax.map(block, jnp.arange(0, s, chunk)).reshape(s, -1)
    return by_tokens(lambda o: o @ cast("wo"), attended)


# ----------------------------------------------------------------------
# Feed-forward sublayers
# ----------------------------------------------------------------------


def _swiglu(h, w_in, down):
    gate, up = jnp.split(h @ w_in, 2, -1)
    return (jax.nn.silu(gate) * up) @ down


def dense_ffn(x, lp, policy):
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    return by_tokens(
        lambda x: _swiglu(
            _rms(x, lp["dense_norm"], policy["rms_norm_eps"]), cast("d_in"), cast("d_down")
        ),
        x,
    )


def route(h2, router, bias, policy):
    """Each token's ``num_experts_per_tok`` experts of all the router's and
    the weights they combine with."""
    if policy["scoring_func"] != "sigmoid" or policy["n_group"] != 1:
        raise ValueError("this reference routes by ungrouped sigmoid scores")
    scores = jax.nn.sigmoid(h2 @ router.astype(h2.dtype))
    _, e_top = jax.lax.top_k(scores + bias.astype(h2.dtype), policy["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, e_top, axis=-1)
    if policy["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return e_top, jnp.asarray(policy["routed_scaling_factor"], h2.dtype) * chosen


def expert_part(x, lp, policy, held_ids=None):
    """``(routed, shared)``: what the experts ``held_ids`` (the
    configuration's share unless given; weights in that order) add for one
    swarm, each over every token under a mask that keeps its own, and the
    shared expert's part."""
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    held_ids = held_experts(policy) if held_ids is None else held_ids

    def routed_and_shared(x):
        h2 = _rms(x, lp["moe_norm"], policy["rms_norm_eps"])
        e_top, c = route(h2, lp["router"], lp["router_bias"], policy)
        return h2, e_top, c, _swiglu(h2, cast("s_in"), cast("s_down"))

    h2, e_top, c, shared = by_tokens(routed_and_shared, x)

    def add_expert(out, held):
        e, gate, up, down = (a if a.ndim == 0 else a.astype(x.dtype) for a in held)

        @jax.checkpoint  # an expert's (S, F) activations are not kept for 8
        def tokens(h2, e_top, c):
            weight = ((e_top == e) * c).sum(-1)  # 0 where the token is not e's
            return weight[:, None] * ((jax.nn.silu(h2 @ gate) * (h2 @ up)) @ down)

        return out + by_tokens(tokens, h2, e_top, c), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2),
        (jnp.asarray(list(held_ids)), lp["w_gate"], lp["w_up"], lp["w_down"]),
    )
    return routed, shared


def layer(xs, lp, policy):
    """One decoder layer on one swarm's streams ``xs (S, n hidden)``: dense
    where ``lp`` holds a dense layer's leaves."""
    # each sublayer's inside is made again in the backward pass: what a latent
    # attention over 8,192 tokens keeps is what fills the chip
    mixer = jax.checkpoint(lambda x: mla_mixer(x, lp, policy))
    if "d_in" in lp:
        ffn = jax.checkpoint(lambda x: dense_ffn(x, lp, policy))
    else:
        ffn = jax.checkpoint(lambda x: sum(expert_part(x, lp, policy)))
    xs = hyper_sublayer(xs, lp, "attn", mixer, policy)
    return hyper_sublayer(xs, lp, "ffn", ffn, policy)


def apply(params, policy, env, obs, dtype=jnp.float32):
    """``(mean, log_std, value)`` for ``obs (..., N, obs_dim)`` in the k-NN
    layout; ``dtype`` is the precision the trunk computes in (the control
    lowers it)."""
    p = params["params"]
    lead, s = obs.shape[:-2], obs.shape[-2]
    n = policy["hc_mult"]
    feats = obs[..., : _features(env)].reshape(-1, s, _features(env)).astype(dtype)
    embedded = _affine(p["embed"], feats)
    xs = jnp.concatenate([embedded] * n, -1)  # every stream starts as e_t

    def run_layer(xs, lp):
        swarm = jax.checkpoint(lambda one: layer(one, lp, policy))
        return jax.lax.map(swarm, xs), None  # a swarm at a time

    for name in dense_names(policy):
        xs, _ = run_layer(xs, p["layers"][name])
    stacked = {k: v for k, v in p["layers"].items() if not isinstance(v, dict)}
    xs, _ = jax.lax.scan(run_layer, xs, stacked)
    x = sum(jnp.split(xs, n, -1))
    x = _rms(x, p["final_norm"], policy["rms_norm_eps"])
    mean = _affine(p["actor"]["pi_head"], x).astype(jnp.float32)
    pooled = jnp.broadcast_to(x.mean(axis=-2, keepdims=True), x.shape)
    value = _affine(p["critic"]["vf_head"], jnp.concatenate([x, pooled], -1))
    value = value.astype(jnp.float32)[..., 0]
    return mean.reshape(*lead, s, -1), p["log_std"], value.reshape(*lead, s)


def forward_flops_per_agent(policy, env, act_dim=2):
    """The work the equations require for one token's forward pass, not what
    an implementation spends: multiply-adds x2 of the latent attention's five
    projections, dense causal attention over the keys a query can see (scores
    over ``d_nope + d_rope``, the product with ``v`` over ``d_v``), a
    hyper-connection's coefficient product, Sinkhorn (a division and an
    addition an entry, twice a step) and three stream mixings a sublayer, the
    dense SwiGLU, the router, the held experts' expected share of the
    ``num_experts_per_tok`` assignments, and the shared expert."""
    h, n = policy["hidden_size"], policy["hc_mult"]
    nh = held_heads(policy)
    rq, rkv = policy["q_lora_rank"], policy["kv_lora_rank"]
    d_nope, d_rope, d_v = (
        policy["qk_nope_head_dim"], policy["qk_rope_head_dim"], policy["v_head_dim"]
    )
    s = env["num_agents_per_formation"]
    f = policy["moe_intermediate_size"]
    mla = (
        2 * (h * rq + rq * nh * (d_nope + d_rope) + h * (rkv + d_rope)
             + rkv * nh * (d_nope + d_v) + nh * d_v * h)
        + (s + 1) / 2 * 2 * nh * (d_nope + d_rope + d_v)
    )
    hyper = (
        2 * n * h * n * (n + 2)  # u phi
        + 4 * n * n * policy["hc_sinkhorn_iters"]
        + 2 * n * h + 2 * n * n * h + 2 * n * h  # h_pre . X, H_res X, h_post (outer) y
    )
    dense = 2 * 3 * h * policy["intermediate_size"]
    experts = (
        2 * h * policy["n_routed_experts"]
        + policy["num_experts_per_tok"] * policy["experts_held"]
        / policy["n_routed_experts"] * 2 * 3 * h * f
        + 2 * 3 * h * f * policy["n_shared_experts"]
    )
    layers, leading = policy["layers_held"], dense_held(policy)
    heads = 2 * _features(env) * h + 2 * h * act_dim + 2 * 2 * h
    return (
        layers * (mla + 2 * hyper) + leading * dense + (layers - leading) * experts + heads
    )
