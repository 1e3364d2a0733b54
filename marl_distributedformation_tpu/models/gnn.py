"""Graph-network actor-critic over the k-NN observation graph.

BASELINE.json config 4: "100-agent swarm with k-nearest-neighbor obs graph
+ GNN policy" — new capability beyond the reference (whose policy is a
per-agent MLP over a fixed ring view, vectorized_env.py:126; SURVEY.md §5
"long-context" note). Design:

- Nodes are agents; edges are each agent's ``k`` nearest neighbors, carried
  inside the observation produced by ``env.formation.compute_obs_knn``
  (offsets, distances, and neighbor indices — indices exact in float32).
- ``rounds`` of message passing: fetch neighbor embeddings
  (``gather_nodes``: where the node axis fits one MXU tile, N <= 128, an
  exact product of a one-hot matrix with the node table, forward and
  transposed, because the chip serves a ``take_along_axis`` of 64-float
  rows an index at a time and its transpose as a sorted scatter-add;
  larger swarms keep the ``take_along_axis``, whose cost grows as N where
  the product's grows as N^2), compute
  edge messages from [h_i, h_j, edge_feats] with a shared MLP (batched
  matmuls on the MXU — no per-edge loop), mean-aggregate, GRU-free residual
  update. An agent's action therefore depends on its ``rounds``-hop
  neighborhood — a learned communication radius, decentralized-executable
  by running the same stack on each agent's local subgraph.
- Critic is centralized CTDE-style: masked mean-pool of final node
  embeddings appended to each node before the value head.

Everything is static-shaped: (N, k) gathers, (N, k, F) edge batches —
``vmap`` over M formations turns the whole swarm forward pass into a few
large MXU matmuls.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from marl_distributedformation_tpu.models.common import (
    PolicyHead,
    PooledValueHead,
    hidden_init,
)

Array = jax.Array


def parse_knn_obs(
    obs: Array, k: int, goal_in_obs: bool = True
) -> Tuple[Array, Array, Array]:
    """Split a ``compute_obs_knn`` layout into (node_feats, edge_feats, idx).

    ``obs``: ``(..., N, 2 + 3k [+2] + k)``. Returns node features
    ``(..., N, 2 [+2])`` (own pos, rel goal), edge features ``(..., N, k, 3)``
    (offset, dist), and int32 neighbor indices ``(..., N, k)``.
    """
    own = obs[..., :2]
    offsets = obs[..., 2 : 2 + 2 * k]
    dists = obs[..., 2 + 2 * k : 2 + 3 * k]
    node_parts = [own]
    if goal_in_obs:
        node_parts.append(obs[..., 2 + 3 * k : 4 + 3 * k])
    idx = obs[..., -k:].astype(jnp.int32)
    edge = jnp.concatenate(
        [
            offsets.reshape(*offsets.shape[:-1], k, 2),
            dists[..., None],
        ],
        axis=-1,
    )
    return jnp.concatenate(node_parts, axis=-1), edge, idx


# The widest node axis whose neighbor fetch is a one-hot product: one MXU
# tile's contraction. The product's work grows as N^2 where the gather's
# grows as N; alone on a v5e it still won at N=640 (PERF.md section 6, PR
# 28), but no benchmark cell runs a swarm past 128 to place the crossing.
ONEHOT_MAX_NODES = 128


def neighbor_onehot(idx: Array) -> Optional[Array]:
    """``idx (..., N, k)`` -> boolean ``(..., N*k, N)``, row ``i*k + j`` set
    at column ``idx[..., i, j]``; None where ``N > ONEHOT_MAX_NODES``. A
    comparison of integers: it has no tangent."""
    n, k = idx.shape[-2], idx.shape[-1]
    if n > ONEHOT_MAX_NODES:
        return None
    flat = idx.reshape(*idx.shape[:-2], n * k, 1)
    return flat == jnp.arange(n, dtype=idx.dtype)


def gather_nodes(
    h: Array, idx: Array, onehot: Optional[Array] = None
) -> Array:
    """``h (..., N, E)``, ``idx (..., N, k)`` -> neighbor embeddings
    ``(..., N, k, E)``, the rows of ``h`` unrounded. The static shape picks
    the path: where ``N <= ONEHOT_MAX_NODES`` a product of
    ``neighbor_onehot(idx)`` (pass it where several calls share ``idx``)
    with ``h``, exact for float32 and narrower, whose transpose is the
    transposed product; past that one flat ``take_along_axis`` on the node
    axis. Indices lie in ``[0, N)`` by construction (``compute_obs_knn``,
    self-loops for padded agents). Outside it the paths differ: the
    product gives a row of zeros, ``take_along_axis`` wraps a negative
    index and fills NaN past ``N``; and a non-finite entry of ``h`` makes
    its whole column of the product NaN (``0 * inf``).
    """
    n, k = idx.shape[-2], idx.shape[-1]
    if onehot is None:
        onehot = neighbor_onehot(idx)
    if onehot is not None:
        # HIGHEST: the backend multiplies float32 in bfloat16 parts, and a
        # row of one 1.0 among zeros then hands h's parts back whole, where
        # the default's single pass would round h (and, transposed, its
        # cotangents) to 8 bits.
        flat = jnp.einsum(
            "...rn,...ne->...re",
            onehot.astype(h.dtype),
            h,
            precision=jax.lax.Precision.HIGHEST,
        )
    else:
        flat = jnp.take_along_axis(
            h, idx.reshape(*idx.shape[:-2], n * k, 1), axis=-2
        )
    return flat.reshape(*idx.shape[:-2], n, k, h.shape[-1])


class GNNActorCritic(nn.Module):
    """Message-passing actor-critic for k-NN swarm observations.

    ``__call__(obs, mask=None)`` takes ``obs (..., N, obs_dim)`` in the
    ``compute_obs_knn`` layout and returns per-agent
    ``(action_mean, log_std, value)``. ``mask (..., N)`` marks valid agents
    in padded (heterogeneous) formations: messages from padded neighbors are
    zeroed, padded agents are excluded from the critic pool, and their
    values are 0.
    """

    k: int
    act_dim: int = 2
    embed_dim: int = 64
    msg_dim: int = 64
    rounds: int = 2
    hidden: Sequence[int] = (64,)
    goal_in_obs: bool = True
    log_std_init: float = 0.0
    per_formation: bool = True  # trainer flag: minibatch whole formations

    @nn.compact
    def __call__(
        self, obs: Array, mask: Optional[Array] = None
    ) -> Tuple[Array, Array, Array]:
        node, edge, idx = parse_knn_obs(obs, self.k, self.goal_in_obs)

        h = nn.tanh(
            nn.Dense(self.embed_dim, kernel_init=hidden_init, name="embed")(
                node
            )
        )
        with jax.named_scope("neighbor_gather"):
            onehot = neighbor_onehot(idx)  # once: idx is every round's
        for r in range(self.rounds):
            with jax.named_scope("neighbor_gather"):
                h_nb = gather_nodes(h, idx, onehot)  # (..., N, k, E)
            h_self = jnp.broadcast_to(
                h[..., :, None, :], h_nb.shape
            )
            msg_in = jnp.concatenate([h_self, h_nb, edge], axis=-1)
            msg = nn.tanh(
                nn.Dense(
                    self.msg_dim, kernel_init=hidden_init, name=f"msg_{r}"
                )(msg_in)
            )
            if mask is not None:
                with jax.named_scope("neighbor_gather"):
                    nb_valid = gather_nodes(
                        mask.astype(msg.dtype)[..., None], idx, onehot
                    )  # (..., N, k, 1)
                msg = msg * nb_valid
                agg = msg.sum(axis=-2) / jnp.maximum(
                    nb_valid.sum(axis=-2), 1.0
                )
            else:
                agg = msg.mean(axis=-2)
            upd = nn.tanh(
                nn.Dense(
                    self.embed_dim, kernel_init=hidden_init, name=f"upd_{r}"
                )(jnp.concatenate([h, agg, node], axis=-1))
            )
            h = h + upd  # residual: round r refines round r-1

        # Actor head: local (r-hop) information only.
        mean = PolicyHead(self.act_dim, self.hidden, name="actor")(h)

        # Critic: CTDE pooled global context.
        value = PooledValueHead(self.hidden, name="critic")(h, mask)

        log_std = self.param(
            "log_std",
            nn.initializers.constant(self.log_std_init),
            (self.act_dim,),
        )
        return mean, log_std, value
