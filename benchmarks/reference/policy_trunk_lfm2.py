"""Plain reference of a trunk policy of gated short convolutions: the
decoder block of LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json,
``model_type: lfm2_moe``) over the agents of one swarm as its tokens. A
sequence is a swarm in ring-slot order at one time step and every mixer is
causal over the agent index. ``layer_types`` says which layers convolve
(``conv``) and which attend (``full_attention``); the leading
``num_dense_layers`` end in a dense SwiGLU, the others in a routed expert
layer with no shared expert.

With ``x (S, hidden)`` one swarm, ``rms(x, g)`` RMSNorm with ``norm_eps``, ``h
= rms(x, g_mixer)``:

- Conv layer. ``[b | c | u] = h W_in`` (the published ``in_proj(x).chunk(3)``:
  B, C, x), ``z_t = b_t * u_t``, ``y_t = c_t * sum_(j=0..K-1) w_j * z_(t-K+1+j)``
  for ``K = conv_L_cache`` taps a channel (depthwise, causal, no bias: the last
  tap multiplies the current token, ``z`` before the first token is 0),
  ``x = x + y W_out``.
- Attention layer. ``q_i = rope(rms(h Wq_i, g_q))``, ``k_g = rope(rms(h Wk_g,
  g_k))``, ``v_g = h Wv_g`` for heads of ``hidden / num_attention_heads``, the
  norms over a head; head ``i`` reads key head ``i // (heads / kv_heads)``;
  causal softmax of ``q k^T d^-0.5``; ``x = x + concat_heads(softmax v) Wo``.
  RoPE rotate-half over the whole head at position ``t``, ``theta =
  rope_parameters.rope_theta``.
- Dense layer. ``x = x + (silu(h2 Wg) * (h2 Wu)) Wd``, ``h2 = rms(x, g_dense)``.
- Expert layer. ``h2 = rms(x, g_moe)``; ``s = sigmoid(h2 Wr)`` over all
  ``num_experts``; chosen = the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``: ``b`` selects and does not weigh; zero at the draw, zero
  gradient); ``c = routed_scaling_factor s_chosen / (sum(s_chosen) + 1e-6)``
  (``norm_topk_prob``); ``x = x + `` the held experts' SwiGLU parts under those
  weights.
- After the last held layer ``rms(x, g_final)`` (the published
  ``embedding_norm``), then the heads.

Straight ``jax.numpy``: the convolution is a sum of shifted products, the
softmax is dense under a ``-inf`` mask by query blocks, the held experts are
a loop with a mask; what is done to a token alone is done ``TOKEN_BLOCK``
tokens at a time (``by_tokens``), a run of layers of one kind is one
``lax.scan`` whose step picks its layer's parameters (as
``policy_trunk_hybrid.py`` does, for the TPU compiler's sake), and a layer
works a swarm at a time under ``jax.checkpoint``, so that a swarm of 8,192
fits beside its float32 state. Imports jax and the reference's own helpers
only.

Parameters sit in the tree the program's policy reads: ``layers/dense<i>_<kind>
/<name>`` for the ``i``-th held dense layer and ``layers/<i>_<kind>/<name>
(periods, ...)`` for the ``i``-th layer of the held pattern's period, kinds
``conv`` and ``gqa`` (``full_attention``). Side by side in one leaf: ``w_in =
[B | C | x]`` of a conv layer, ``w_in = [Wq | Wk | Wv]`` of an attention
layer, ``d_in = [Wg | Wu]``.

Departures from the published model, each also in the configuration file:
- depth: layers ``first_layer_held ... + layers_held`` of
  ``num_hidden_layers``; ``experts_held`` of ``num_experts`` (ids ``share *
  experts_held ...``): the router keeps its published width, top-k and scale,
  and what the absent experts would add is left out;
- no vocabulary: tokens are continuous observations, the embedding is a dense
  layer on the k-NN observation's geometric floats, the heads are the
  system's;
- assumed (not in the published config): sigmoid router scores; the
  renormalisation's ``1e-6``; ``intermediate_size`` and
  ``moe_intermediate_size`` used as given; q/k norms RMSNorm with a learned
  scale at ``norm_eps``; rotate-half RoPE; matrices and taps normal(0, 0.02),
  norms at 1;
- not trained: no router balance loss, and the selection bias is never moved
  (``use_expert_bias`` moves it outside the gradient; nothing does here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .policy_mlp import _affine, _dense
from .policy_trunk import _features, _rms, _rope, held_experts
from .policy_trunk_hybrid import by_tokens, short_conv
from .policy_trunk_mla_hc import _swiglu

PER_FORMATION = True  # a minibatch row is a whole swarm-step (N tokens)

INIT_STD = 0.02
TOPK_NORM_EPS = 1e-6
QUERY_BLOCK = 256  # queries of the softmax at a time
KINDS = {"conv": "conv", "full_attention": "gqa"}  # layer_types -> mixer


def head_dim(policy):
    return policy["hidden_size"] // policy["num_attention_heads"]


def dense_held(policy):
    """How many of the held layers are leading dense ones."""
    first = policy["first_layer_held"]
    return min(max(policy["num_dense_layers"] - first, 0), policy["layers_held"])


def held_kinds(policy):
    first = policy["first_layer_held"]
    return [KINDS[t] for t in policy["layer_types"][first : first + policy["layers_held"]]]


def dense_names(policy):
    kinds = held_kinds(policy)[: dense_held(policy)]
    return [(f"dense{i}_{kind}", kind) for i, kind in enumerate(kinds)]


def runs(policy):
    """``(periods, [(kind, [names]), ...])``: the held layers after the dense
    ones as periods of runs of one kind, ``names`` the parameter groups of the
    run's layers."""
    kinds = held_kinds(policy)[dense_held(policy) :]
    size = next(
        p for p in range(1, len(kinds) + 1)
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
    )
    found = []
    for i, kind in enumerate(kinds[:size]):
        if not found or found[-1][0] != kind:
            found.append((kind, []))
        found[-1][1].append(f"{i}_{kind}")
    return len(kinds) // size, found


def init(key, policy, env, act_dim=2):
    h, hd = policy["hidden_size"], head_dim(policy)
    nq, nkv = policy["num_attention_heads"], policy["num_key_value_heads"]
    held, f = policy["experts_held"], policy["moe_intermediate_size"]
    periods, period = runs(policy)
    names = [(name, kind) for kind, group in period for name in group]
    # XLA's own generator, one draw a matrix (policy_trunk_hybrid.py's reason)
    keys = iter(jax.random.split(
        jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg"),
        8 * (len(names) + dense_held(policy) + 1),
    ))

    def normal(*shape):
        return INIT_STD * jax.random.normal(next(keys), shape, jnp.float32)

    def layer(lead, kind, dense):
        ones = lambda *shape: jnp.ones((*lead, *shape), jnp.float32)  # noqa: E731
        mat = lambda *shape: normal(*lead, *shape)  # noqa: E731
        if kind == "conv":
            mixer = {
                "attn_norm": ones(h),
                "w_in": mat(h, 3 * h),  # [B | C | x]
                "conv": mat(policy["conv_L_cache"], h),
                "wo": mat(h, h),
            }
        else:
            mixer = {
                "attn_norm": ones(h),
                "w_in": mat(h, (nq + 2 * nkv) * hd),  # [Wq | Wk | Wv]
                "q_norm": ones(hd),
                "k_norm": ones(hd),
                "wo": mat(nq * hd, h),
            }
        if dense:
            fd = policy["intermediate_size"]
            ffn = {"dense_norm": ones(h), "d_in": mat(h, 2 * fd), "d_down": mat(fd, h)}
        else:
            ffn = {
                "moe_norm": ones(h),
                "router": mat(h, policy["num_experts"]),
                "router_bias": jnp.zeros((*lead, policy["num_experts"]), jnp.float32),
                "w_gate": mat(held, h, f), "w_up": mat(held, h, f), "w_down": mat(held, f, h),
            }
        return {**mixer, **ffn}

    return {
        "params": {
            "embed": {
                "kernel": normal(_features(env), h),
                "bias": jnp.zeros((h,), jnp.float32),
            },
            "layers": {
                **{name: layer((), kind, True) for name, kind in dense_names(policy)},
                **{name: layer((periods,), kind, False) for name, kind in names},
            },
            "final_norm": jnp.ones((h,), jnp.float32),
            "actor": {"pi_head": _dense(next(keys), h, act_dim, 0.01)},
            "critic": {"vf_head": _dense(next(keys), 2 * h, 1, 1.0)},
            "log_std": jnp.full((act_dim,), policy["log_std_init"], jnp.float32),
        }
    }


def conv_mixer(x, lp, policy):
    """What a gated short convolution adds to one swarm ``x (S, hidden)``."""
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    b, c, u = by_tokens(
        lambda x: jnp.split(_rms(x, lp["attn_norm"], policy["norm_eps"]) @ cast("w_in"), 3, -1),
        x,
    )
    y = c * short_conv(b * u, lp["conv"])
    return by_tokens(lambda y: y @ cast("wo"), y)


def gqa_mixer(x, lp, policy):
    """What the attention heads add to one swarm ``x (S, hidden)``."""
    s, hd, eps = x.shape[0], head_dim(policy), policy["norm_eps"]
    nq, nkv = policy["num_attention_heads"], policy["num_key_value_heads"]
    theta = policy["rope_parameters"]["rope_theta"]
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731

    def project(x):
        q, k, v = jnp.split(
            _rms(x, lp["attn_norm"], eps) @ cast("w_in"), (nq * hd, (nq + nkv) * hd), -1
        )
        q = _rms(q.reshape(-1, nq, hd), lp["q_norm"], eps)
        k = _rms(k.reshape(-1, nkv, hd), lp["k_norm"], eps)
        return q, k, v.reshape(-1, nkv, hd)

    q, k, v = by_tokens(project, x)
    q, k = _rope(q, theta), _rope(k, theta)
    group = nq // nkv  # head a reads key head a // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    chunk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, chunk)
        visible = jnp.arange(s)[None, :] <= (start + jnp.arange(chunk))[:, None]
        scores = jnp.einsum("tad,sad->ats", rows, k) / jnp.sqrt(jnp.asarray(hd, x.dtype))
        p = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("ats,sad->tad", p, v).reshape(chunk, -1)

    attended = jax.lax.map(block, jnp.arange(0, s, chunk)).reshape(s, -1)
    return by_tokens(lambda o: o @ cast("wo"), attended)


MIXERS = {"conv": conv_mixer, "gqa": gqa_mixer}


def dense_ffn(x, lp, policy):
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    return by_tokens(
        lambda x: _swiglu(
            _rms(x, lp["dense_norm"], policy["norm_eps"]), cast("d_in"), cast("d_down")
        ),
        x,
    )


def route(h2, router, bias, policy):
    """Each token's ``num_experts_per_tok`` experts of all the router's and
    the weights they combine with."""
    scores = jax.nn.sigmoid(h2 @ router.astype(h2.dtype))
    _, e_top = jax.lax.top_k(scores + bias.astype(h2.dtype), policy["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, e_top, axis=-1)
    if policy["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + TOPK_NORM_EPS)
    return e_top, jnp.asarray(policy["routed_scaling_factor"], h2.dtype) * chosen


def expert_part(x, lp, policy, held_ids=None):
    """What the experts ``held_ids`` (the configuration's share unless given;
    weights in that order) add for one swarm, each over every token under a
    mask that keeps its own."""
    held_ids = held_experts(policy) if held_ids is None else held_ids

    def routed(x):
        h2 = _rms(x, lp["moe_norm"], policy["norm_eps"])
        return (h2, *route(h2, lp["router"], lp["router_bias"], policy))

    h2, e_top, c = by_tokens(routed, x)

    def add_expert(out, held):
        e, gate, up, down = (a if a.ndim == 0 else a.astype(x.dtype) for a in held)

        @jax.checkpoint  # an expert's (S, F) activations are not kept for 8
        def tokens(h2, e_top, c):
            weight = ((e_top == e) * c).sum(-1)  # 0 where the token is not e's
            return weight[:, None] * ((jax.nn.silu(h2 @ gate) * (h2 @ up)) @ down)

        return out + by_tokens(tokens, h2, e_top, c), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2),
        (jnp.asarray(list(held_ids)), lp["w_gate"], lp["w_up"], lp["w_down"]),
    )
    return out


def layer(x, lp, kind, policy):
    """One decoder layer of mixer ``kind`` on one swarm ``x (S, hidden)``:
    dense where ``lp`` holds a dense layer's leaves."""
    x = x + MIXERS[kind](x, lp, policy)
    return x + (dense_ffn if "d_in" in lp else expert_part)(x, lp, policy)


def apply(params, policy, env, obs, dtype=jnp.float32):
    """``(mean, log_std, value)`` for ``obs (..., N, obs_dim)`` in the k-NN
    layout; ``dtype`` is the precision the trunk computes in (the control
    lowers it)."""
    p = params["params"]
    lead, s = obs.shape[:-2], obs.shape[-2]
    feats = obs[..., : _features(env)].reshape(-1, s, _features(env)).astype(dtype)
    x = _affine(p["embed"], feats)

    def run_layer(x, lp, kind):
        swarm = jax.checkpoint(lambda one: layer(one, lp, kind, policy))
        return jax.lax.map(swarm, x)  # a swarm at a time

    for name, kind in dense_names(policy):
        x = run_layer(x, p["layers"][name], kind)
    periods, period = runs(policy)
    for at in range(periods):
        for kind, names in period:
            of_run = [
                jax.tree_util.tree_map(lambda a: a[at], p["layers"][name])
                for name in names
            ]

            def one_layer(x, i, kind=kind, of_run=of_run):
                lp = jax.tree_util.tree_map(
                    lambda *leaves: jax.lax.select_n(i, *leaves), *of_run
                )  # the run's i-th layer
                return run_layer(x, lp, kind), None

            x, _ = jax.lax.scan(one_layer, x, jnp.arange(len(names)))
    x = _rms(x, p["final_norm"], policy["norm_eps"])
    mean = _affine(p["actor"]["pi_head"], x).astype(jnp.float32)
    pooled = jnp.broadcast_to(x.mean(axis=-2, keepdims=True), x.shape)
    value = _affine(p["critic"]["vf_head"], jnp.concatenate([x, pooled], -1))
    value = value.astype(jnp.float32)[..., 0]
    return mean.reshape(*lead, s, -1), p["log_std"], value.reshape(*lead, s)


def forward_flops_per_agent(policy, env, act_dim=2):
    """The work the equations require for one token's forward pass, not what
    an implementation spends: multiply-adds x2 of the projections; a conv
    layer's gate ``b u``, its taps (a multiply-add each) and gate ``c``;
    dense causal attention over the keys a query can see (scores and their
    product with ``v``); the dense SwiGLU; the router; the held experts'
    expected share of the ``num_experts_per_tok`` assignments (routed top-k,
    not the dense-under-mask work the program spends)."""
    h, hd = policy["hidden_size"], head_dim(policy)
    nq, nkv = policy["num_attention_heads"], policy["num_key_value_heads"]
    s = env["num_agents_per_formation"]
    conv = 2 * (3 * h * h + h * h) + (2 * policy["conv_L_cache"] + 2) * h
    gqa = (
        2 * (h * (nq + 2 * nkv) * hd + nq * hd * h)
        + (s + 1) / 2 * 4 * nq * hd  # scores and their product with v
    )
    dense = 2 * 3 * h * policy["intermediate_size"]
    experts = (
        2 * h * policy["num_experts"]
        + policy["num_experts_per_tok"] * policy["experts_held"]
        / policy["num_experts"] * 2 * 3 * h * policy["moe_intermediate_size"]
    )
    kinds, leading = held_kinds(policy), dense_held(policy)
    mixers = kinds.count("conv") * conv + kinds.count("gqa") * gqa
    heads = 2 * _features(env) * h + 2 * h * act_dim + 2 * 2 * h
    return mixers + leading * dense + (len(kinds) - leading) * experts + heads
