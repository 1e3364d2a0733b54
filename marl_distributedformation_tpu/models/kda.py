"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv 2510.26692), computed in chunks over the sequence axis.

Per head, with a state ``S (d, d)`` that starts at zero, a log-decay
``g_t (d,) <= 0``, ``alpha_t = exp(g_t)`` and ``beta_t`` in (0, 2):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``benchmarks/reference/policy_trunk_hybrid.py`` runs that token by token.
Here a chunk of ``C`` tokens is worked at once (the WY / UT form). With
``G_t`` the log-decay cumulated from the chunk's start and ``S_0`` the state
it starts from, ``S_t = Diag(e^G_t) S_0 + sum_(i<=t) Diag(e^(G_t - G_i))
k_i u_i^T`` for pseudo-values ``u`` that solve one unit lower-triangular
system a chunk, ``(I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0)``,
``A_ti = sum_c k_tc k_ic e^(G_tc - G_ic)`` for ``i < t``. The system is
linear in ``S_0``, so both of its right-hand sides are solved for every
chunk at once, ahead of the ``lax.scan`` that carries the state from chunk
to chunk with four small products a step.

Every exponent of a decay difference is non-positive: ``A`` and its twin
for the queries are summed channel by channel from ``e^(G_t - G_i)``, ``i <=
t``, never from ``e^G_t`` times ``e^-G_i``. At a log-decay of -1.6 a step
(the stated initialisation reaches it) a chunk of 64 cumulates -102, and
``e^102`` is past float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# Chunks whose pairwise terms ``(heads, C, C, d)`` are made at one time.
_CHUNKS_AT_ONCE = 8


def short_conv(x: Array, w: Array) -> Array:
    """Depthwise causal convolution over axis 0, no bias: ``x (S, C)``, taps
    ``w (K, C)``, ``y_t = sum_j w_j x_(t - K + 1 + j)`` (the last tap
    multiplies the current token; what precedes the first token is zero)."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[j : j + s] for j in range(taps))


def _within_chunk(chunk):
    """Everything of one chunk that does not need the state it starts from.
    ``q, k, v, g (heads, C, d)``, ``beta (heads, C)``."""
    q, k, v, g, beta = chunk
    c, d = q.shape[1], q.shape[2]
    cum = jnp.cumsum(g, axis=1)  # G_t
    t = jnp.arange(c)
    seen = t[:, None] >= t[None, :]  # i <= t
    gap = jnp.where(
        seen[..., None], cum[:, :, None, :] - cum[:, None, :, :], 0.0
    )  # G_t - G_i <= 0, (heads, C, C, d)
    k_decayed = k[:, None, :, :] * jnp.exp(gap)
    a = (k[:, :, None, :] * k_decayed).sum(-1)
    b = jnp.where(seen, (q[:, :, None, :] * k_decayed).sum(-1), 0.0)
    system = jnp.eye(c) + beta[..., None] * jnp.where(
        t[:, None] > t[None, :], a, 0.0
    )
    solved = jax.scipy.linalg.solve_triangular(
        system,
        beta[..., None] * jnp.concatenate([k * jnp.exp(cum), v], -1),
        lower=True,
        unit_diagonal=True,
    )
    whole = cum[:, -1:, :]  # the chunk's cumulated log-decay
    return {
        "w": solved[..., :d],  # U = u - w S_0
        "u": solved[..., d:],
        "b": b,  # o = (q e^G) S_0 + b U
        "q": q * jnp.exp(cum),
        "k": k * jnp.exp(whole - cum),  # S_C = e^G_C S_0 + (k e^(G_C - G))^T U
        "decay": jnp.exp(whole[:, 0, :]),
    }


def _across_chunks(state: Array, part):
    """One chunk's outputs and the state it leaves; ``state (heads, d, d)``."""
    u = part["u"] - part["w"] @ state
    out = part["q"] @ state + part["b"] @ u
    state = part["decay"][..., None] * state + jnp.swapaxes(part["k"], 1, 2) @ u
    return state, out


def chunked_delta_rule(
    q: Array, k: Array, v: Array, g: Array, beta: Array, chunk: int
) -> Array:
    """``o (S, heads, d)`` of the recurrence above for ``q, k, v, g (S,
    heads, d)`` and ``beta (S, heads)``. A swarm the chunk does not divide
    is padded with tokens that leave the state as it is (``k = 0``, ``beta
    = 0``, ``g = 0``). The backward pass recomputes a chunk and keeps the
    states between chunks only."""
    s, heads, d = q.shape
    n = -(-s // chunk)

    def chunks(a):  # (S, heads, ...) -> (n, heads, C, ...)
        a = jnp.pad(a, ((0, n * chunk - s),) + ((0, 0),) * (a.ndim - 1))
        return jnp.swapaxes(a.reshape(n, chunk, *a.shape[1:]), 1, 2)

    parts = jax.lax.map(
        jax.checkpoint(_within_chunk),
        tuple(chunks(a) for a in (q, k, v, g, beta)),
        batch_size=min(n, _CHUNKS_AT_ONCE),
    )
    _, out = jax.lax.scan(
        jax.checkpoint(_across_chunks), jnp.zeros((heads, d, d), q.dtype), parts
    )
    return jnp.swapaxes(out, 1, 2).reshape(n * chunk, heads, d)[:s]
