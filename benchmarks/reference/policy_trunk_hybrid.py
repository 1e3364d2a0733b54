"""Plain reference of a trunk policy whose layers differ: the decoder period
of Solar-Open2-250B
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type: solar_open2``) over the agents of one swarm as its tokens. A
sequence is a swarm in ring-slot order at one time step and every mixer is
causal over the agent index. Layers ``gqa_layers`` are gated softmax
attention, the others Kimi Delta Attention (arXiv 2510.26692); every layer
ends in a routed expert layer with a shared expert.

With ``x (S, hidden)`` one swarm, ``h = RMSNorm(x)``, ``H`` heads held of
size ``d``:

- KDA layer. ``q = l2norm(silu(conv(h Wq)))``, ``k = l2norm(silu(conv(h
  Wk)))``, ``v = silu(conv(h Wv))``, split into heads; ``conv`` is a
  depthwise causal convolution over the agent axis (kernel 4, no bias); ``q``
  is scaled by ``d^-0.5``. Log-decay per head and channel ``g_t = -exp(A_log)
  * softplus((h_t F_a) F_b + dt_bias)``, ``alpha_t = exp(g_t)``; ``beta_t =
  2 sigmoid(h_t w_beta)`` per head. Per head a state ``S (d, d)`` from zero:
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``. ``y_t = [RMSNorm_head(o_t) * sigmoid((h_t G_a) G_b)]
  Wo``, the norm over ``d`` with one learned scale; ``x = x + y``.
- Gated GQA layer. ``q = h Wq``, ``k = h Wk``, ``v = h Wv``; no RoPE, no q/k
  norm; causal softmax of ``q k^T d^-0.5``; ``y = [attn * sigmoid(h Wg)] Wo``.
- Expert layer. ``h2 = RMSNorm(x)``; softmax over all routed experts, the
  top ``num_experts_per_tok`` renormalised; the held experts' SwiGLU parts
  under those weights plus the shared expert ``(silu(h2 Sg) * (h2 Su)) Sd``
  for every token, ungated.

Straight ``jax.numpy``: the delta rule is run token by token (one
``lax.scan`` over the agent axis, in blocks under ``jax.checkpoint`` so
that a swarm of 8,192 fits beside its float32 state), the convolution is
four shifted products, the softmax is dense under a ``-inf`` mask by query
blocks, the held experts are a loop with a mask. Imports jax and the
reference's own helpers only.

Parameters sit in the tree the program's policy reads (the harness places
this file's seeded state into the trainer): ``layers/<i>_<kind>/<name>`` for
the ``i``-th layer of the pattern's period (``0_gated_gqa``, ``1_kda``,
``2_kda``, ``3_kda``), stacked ``(periods, ...)``. The matrices that read one
input are one leaf, side by side, and a column of the product is the column
of its own matrix's product: ``w_in = [Wq | Wk | Wv | F_a | G_a]`` and ``conv
= [conv_q | conv_k | conv_v]`` in a KDA layer (``w_beta``, a column a head, is
a leaf of its own: with it the width would be no multiple of 128 lanes),
``w_in = [Wq | Wk | Wv | Wg]`` in a GQA layer, ``s_in = [Sg | Su]`` for the
shared expert.

Three things are arranged for the TPU compiler's sake and change no equation
(``PERF.md`` section 6, PRs 32-33, has what each one saves). Its time for a
float32 product at ``highest`` (six bfloat16 passes) grows with the product's
rows and is paid for every product in the text, so (1) what is done to a
token alone (norms, projections, gates, router, experts) is done
``TOKEN_BLOCK`` tokens at a time (``by_tokens``), and (2) a run of layers of
one kind is one ``lax.scan`` whose step picks its layer's parameters out of
the run's (``lax.select_n`` on the step's number: the parameters picked are
the layer's own, to the bit), so the kind's text is there once a pass. (3)
The initialiser's numbers come from XLA's own generator (``impl="rbg"``), one
draw a matrix: threefry draws of this many numbers want a loop a matrix.

Departures from the published model, each also in the configuration file:
- depth ``layers_held`` of ``num_hidden_layers`` (whole periods);
  ``experts_held`` of the ``n_routed_experts``, ids ``share * experts_held
  ...`` for share ``expert_share[0]`` of ``expert_share[1]``: the router
  keeps its published width and top-k, what the absent experts would add is
  left out, the shared expert is whole;
- of every mixer's heads the share ``head_share[0]`` of ``head_share[1]``:
  ``num_attention_heads / n`` query heads with ``num_key_value_heads / n``
  key heads, ``linear_attn_config.num_heads / n`` KDA heads. The parameters
  handed in have the held heads' widths; ``o Wo`` is the partial sum of
  those heads and what the absent heads would add is left out;
- no vocabulary: tokens are continuous observations, so the embedding
  table is a dense layer on the k-NN observation's geometric floats and the
  output head is the system's Gaussian policy head and pooled value head;
- assumed (not in the published config): the KDA layer's form as above
  (Kimi Linear's), ``kda_use_full_proj: false`` read as the low-rank ``F_a
  F_b`` and ``G_a G_b`` of rank ``head_dim``; l2norm's eps 1e-6; the GQA
  gate elementwise, of width ``heads x head_dim``, before ``Wo``; router
  scoring softmax; shared expert's width ``moe_intermediate_size x
  n_shared_experts``; initialiser normal(0, 0.02) (convolutions too), norms
  at 1, ``A_log`` the log of U(1, 16) a head, ``dt_bias`` the inverse
  softplus of U(0.001, 0.1) a channel;
- not trained: no router balance loss (the config gives no coefficient).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .policy_mlp import _affine, _dense
from .policy_trunk import _features, _rms, held_experts, route

PER_FORMATION = True  # a minibatch row is a whole swarm-step (N tokens)

INIT_STD = 0.02
L2_EPS = 1e-6
SCAN_BLOCK = 64  # tokens of the delta rule under one checkpoint
QUERY_BLOCK = 512  # queries of the softmax at a time
TOKEN_BLOCK = 256  # tokens of a per-token product at a time


def layer_kinds(policy):
    return [
        "gated_gqa" if i in policy["gqa_layers"] else "kda"
        for i in range(policy["layers_held"])
    ]


def _period(kinds):
    return next(
        p for p in range(1, len(kinds) + 1)
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
    )


def runs(policy):
    """``(periods, [(kind, [names]), ...])``: the held layers as periods of
    runs of one kind, ``names`` the parameter groups of the run's layers."""
    kinds = layer_kinds(policy)
    period = kinds[: _period(kinds)]
    found = []
    for i, kind in enumerate(period):
        if not found or found[-1][0] != kind:
            found.append((kind, []))
        found[-1][1].append(f"{i}_{kind}")
    return len(kinds) // len(period), found


def _heads(policy):
    """Held ``(query heads, key heads, KDA heads)``."""
    n = policy["head_share"][1]
    return (
        policy["num_attention_heads"] // n,
        policy["num_key_value_heads"] // n,
        policy["linear_attn_config"]["num_heads"] // n,
    )


def init(key, policy, env, act_dim=2):
    h, hd = policy["hidden_size"], policy["head_dim"]
    nq, nkv, nl = _heads(policy)
    lin = policy["linear_attn_config"]
    d, taps = lin["head_dim"], lin["short_conv_kernel_size"]
    held, f = policy["experts_held"], policy["moe_intermediate_size"]
    fs = f * policy["n_shared_experts"]
    periods, period = runs(policy)
    names = [(name, kind) for kind, group in period for name in group]
    # XLA's own generator, one draw a matrix (the docstring's third point)
    keys = iter(jax.random.split(
        jax.random.wrap_key_data(jnp.concatenate([key, key]), impl="rbg"),
        16 * (len(names) + 1),
    ))

    def normal(*shape):
        return INIT_STD * jax.random.normal(next(keys), shape, jnp.float32)

    def stack(kind):
        n = (periods,)
        ones = lambda *shape: jnp.ones((*n, *shape), jnp.float32)  # noqa: E731
        mat = lambda *shape: normal(*n, *shape)  # noqa: E731
        if kind == "gated_gqa":
            mixer = {
                "attn_norm": ones(h),
                "w_in": mat(h, 2 * (nq + nkv) * hd),  # [Wq | Wk | Wv | Wg]
                "wo": mat(nq * hd, h),
            }
        else:
            dt = jax.random.uniform(next(keys), (*n, nl * d), jnp.float32, 0.001, 0.1)
            mixer = {
                "attn_norm": ones(h),
                "w_in": mat(h, 3 * nl * d + 2 * d),  # [Wq | Wk | Wv | F_a | G_a]
                "w_beta": mat(h, nl),
                "conv": mat(taps, 3 * nl * d),  # [conv_q | conv_k | conv_v]
                "f_b": mat(d, nl * d),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "A_log": jnp.log(
                    jax.random.uniform(next(keys), (*n, nl), jnp.float32, 1.0, 16.0)
                ),
                "g_b": mat(d, nl * d),
                "o_norm": ones(d),
                "wo": mat(nl * d, h),
            }
        return {
            **mixer,
            "moe_norm": ones(h),
            "router": mat(h, policy["n_routed_experts"]),
            "w_gate": mat(held, h, f), "w_up": mat(held, h, f), "w_down": mat(held, f, h),
            "s_in": mat(h, 2 * fs),  # [Sg | Su]
            "s_down": mat(fs, h),
        }

    return {
        "params": {
            "embed": {
                "kernel": normal(_features(env), h),
                "bias": jnp.zeros((h,), jnp.float32),
            },
            "layers": {name: stack(kind) for name, kind in names},
            "final_norm": jnp.ones((h,), jnp.float32),
            "actor": {"pi_head": _dense(next(keys), h, act_dim, 0.01)},
            "critic": {"vf_head": _dense(next(keys), 2 * h, 1, 1.0)},
            "log_std": jnp.full((act_dim,), policy["log_std_init"], jnp.float32),
        }
    }


def short_conv(x, w):
    """``y_t = sum_j w_j x_(t - K + 1 + j)`` for ``x (S, C)``, taps ``w (K,
    C)``: one product a tap with ``x`` shifted down the agent axis."""
    taps, s = w.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate([jnp.zeros_like(x[:back]), x[: s - back]])
        out = out + w[j].astype(x.dtype) * shifted
    return out


def _l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta):
    """``o (S, H, d)`` of the recurrence, a token at a time: ``q, k, v,
    alpha (S, H, d)``, ``beta (S, H)``, the state ``(H, d, d)`` from zero."""
    s, heads, d = q.shape
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def token(state, x):
        q_t, k_t, v_t, alpha_t, beta_t = x
        state = alpha_t[:, :, None] * state  # Diag(alpha) S
        seen = (k_t[:, :, None] * state).sum(1)  # (Diag(alpha) S)^T k
        state = state + beta_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]
        return state, (q_t[:, :, None] * state).sum(1)  # S^T q

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(s // block, block, *a.shape[1:]), (q, k, v, alpha, beta)
    )
    _, out = jax.lax.scan(tokens, jnp.zeros((heads, d, d), q.dtype), blocks)
    return out.reshape(s, heads, d)


def by_tokens(f, *xs):
    """``f(*xs)`` for ``xs (S, ...)``, ``TOKEN_BLOCK`` tokens at a time:
    what ``f`` makes of a token must not depend on the other tokens."""
    s = xs[0].shape[0]
    block = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    blocks = tuple(a.reshape(s // block, block, *a.shape[1:]) for a in xs)
    out = jax.lax.map(lambda b: f(*b), blocks)
    return jax.tree_util.tree_map(lambda a: a.reshape(s, *a.shape[2:]), out)


def kda_mixer(x, lp, policy):
    """What the held KDA heads add to one swarm ``x (S, hidden)``."""
    s, d = x.shape[0], policy["linear_attn_config"]["head_dim"]
    eps = policy["rms_norm_eps"]
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    width = lp["wo"].shape[0]  # held heads x d
    ends = (width, 2 * width, 3 * width, 3 * width + d)

    def project(x):  # h [Wq Wk Wv F_a G_a], then the gates' second halves
        h = _rms(x, lp["attn_norm"], eps)
        q, k, v, f, g = jnp.split(h @ cast("w_in"), ends, -1)
        return q, k, v, f @ cast("f_b"), g @ cast("g_b"), h @ cast("w_beta")

    q, k, v, f, g, b = by_tokens(project, x)
    conv_q, conv_k, conv_v = jnp.split(lp["conv"], 3, -1)

    def heads_of(a, taps):
        return jax.nn.silu(short_conv(a, taps)).reshape(s, -1, d)

    q = _l2norm(heads_of(q, conv_q)) / jnp.sqrt(jnp.asarray(d, x.dtype))
    k = _l2norm(heads_of(k, conv_k))
    v = heads_of(v, conv_v)
    dt = jax.nn.softplus(f + cast("dt_bias"))
    log_decay = -jnp.exp(cast("A_log"))[None, :, None] * dt.reshape(s, -1, d)
    beta = 2.0 * jax.nn.sigmoid(b)
    o = delta_rule(q, k, v, jnp.exp(log_decay), beta)
    gate = jax.nn.sigmoid(g).reshape(s, -1, d)
    return by_tokens(
        lambda o, gate: (_rms(o, lp["o_norm"], eps) * gate).reshape(o.shape[0], -1)
        @ cast("wo"),
        o, gate,
    )


def gated_gqa_mixer(x, lp, policy):
    """What the held softmax heads add to one swarm ``x (S, hidden)``."""
    s, hd = x.shape[0], policy["head_dim"]
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    nq, nkv, _ = _heads(policy)
    ends = (nq * hd, (nq + nkv) * hd, (nq + 2 * nkv) * hd)
    q, k, v, g = by_tokens(  # h [Wq Wk Wv Wg]
        lambda x: jnp.split(
            _rms(x, lp["attn_norm"], policy["rms_norm_eps"]) @ cast("w_in"), ends, -1
        ),
        x,
    )
    q, k, v = (a.reshape(s, -1, hd) for a in (q, k, v))
    group = q.shape[1] // k.shape[1]  # head a reads key head a // group
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
    return by_tokens(
        lambda attended, g: (attended * jax.nn.sigmoid(g)) @ cast("wo"), attended, g
    )


MIXERS = {"kda": kda_mixer, "gated_gqa": gated_gqa_mixer}


def expert_part(x, lp, policy, held_ids=None):
    """What the expert layer adds to one swarm: the experts ``held_ids`` (the
    configuration's share unless given; weights in that order), each over
    every token under a mask that keeps its own, and the shared expert."""
    cast = lambda name: lp[name].astype(x.dtype)  # noqa: E731
    held_ids = held_experts(policy) if held_ids is None else held_ids

    def routed_and_shared(x):
        h2 = _rms(x, lp["moe_norm"], policy["rms_norm_eps"])
        e_top, c = route(
            h2, lp["router"], policy["num_experts_per_tok"], policy["norm_topk_prob"]
        )
        gate, up = jnp.split(h2 @ cast("s_in"), 2, -1)  # h2 [Sg Su]
        shared = (jax.nn.silu(gate) * up) @ cast("s_down")
        return h2, e_top, c, shared

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


def layer(x, lp, kind, policy):
    """One decoder layer of mixer ``kind`` on one swarm ``x (S, hidden)``."""
    x = x + MIXERS[kind](x, lp, policy)
    routed, shared = expert_part(x, lp, policy)
    return x + routed + shared


def apply(params, policy, env, obs, dtype=jnp.float32):
    """``(mean, log_std, value)`` for ``obs (..., N, obs_dim)`` in the k-NN
    layout; ``dtype`` is the precision the trunk computes in (the control
    lowers it)."""
    p = params["params"]
    lead, s = obs.shape[:-2], obs.shape[-2]
    feats = obs[..., : _features(env)].reshape(-1, s, _features(env)).astype(dtype)
    x = _affine(p["embed"], feats)
    periods, period = runs(policy)
    for at in range(periods):
        for kind, names in period:
            of_run = [
                jax.tree_util.tree_map(lambda a: a[at], p["layers"][name])
                for name in names
            ]

            def one_layer(x, i, kind=kind, of_run=of_run):
                @jax.checkpoint  # keeps its input, not the parameters picked
                def swarm(one):
                    lp = jax.tree_util.tree_map(
                        lambda *leaves: jax.lax.select_n(i, *leaves), *of_run
                    )  # the run's i-th layer
                    return layer(one, lp, kind, policy)

                return jax.lax.map(swarm, x), None  # a swarm at a time

            x, _ = jax.lax.scan(one_layer, x, jnp.arange(len(names)))
    x = _rms(x, p["final_norm"], policy["rms_norm_eps"])
    mean = _affine(p["actor"]["pi_head"], x).astype(jnp.float32)
    pooled = jnp.broadcast_to(x.mean(axis=-2, keepdims=True), x.shape)
    value = _affine(p["critic"]["vf_head"], jnp.concatenate([x, pooled], -1))
    value = value.astype(jnp.float32)[..., 0]
    return mean.reshape(*lead, s, -1), p["log_std"], value.reshape(*lead, s)


def forward_flops_per_agent(policy, env, act_dim=2):
    """The work the equations require for one token's forward pass, not what
    an implementation spends: multiply-adds x2 of the projections and gates,
    the convolutions, the delta rule's four passes over a head's state
    (decay, read, rank-one write, query), dense causal attention over the
    keys a query can see, the router, the held experts' expected share of
    the ``num_experts_per_tok`` assignments, and the shared expert."""
    h, hd = policy["hidden_size"], policy["head_dim"]
    nq, nkv, nl = _heads(policy)
    lin = policy["linear_attn_config"]
    d, taps = lin["head_dim"], lin["short_conv_kernel_size"]
    s = env["num_agents_per_formation"]
    f = policy["moe_intermediate_size"]
    kda = (
        2 * 4 * h * nl * d  # wq, wk, wv, wo
        + 2 * 2 * (h * d + d * nl * d)  # the two low-rank gates
        + 2 * h * nl  # beta
        + 2 * 3 * taps * nl * d  # convolutions
        + 7 * nl * d * d  # decay d^2; read, write and query 2 d^2 each
    )
    gqa = (
        2 * (3 * h * nq * hd + 2 * h * nkv * hd)  # wq, wg, wo; wk, wv
        + (s + 1) / 2 * 4 * nq * hd  # scores and their product with v
    )
    experts = (
        2 * h * policy["n_routed_experts"]
        + policy["num_experts_per_tok"] * policy["experts_held"]
        / policy["n_routed_experts"] * 2 * 3 * h * f
        + 2 * 3 * h * f * policy["n_shared_experts"]
    )
    kinds = layer_kinds(policy)
    mixers = kinds.count("kda") * kda + kinds.count("gated_gqa") * gqa
    heads = 2 * _features(env) * h + 2 * h * act_dim + 2 * 2 * h
    return mixers + len(kinds) * experts + heads
