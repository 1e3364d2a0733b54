"""Proximal Policy Optimization: clipped surrogate, minibatch epochs.

In-repo replacement for the SB3 ``PPO`` the reference imports
(vectorized_env.py:115,126-131; SURVEY.md §2.2). Hyperparameter defaults are
the SB3 defaults overridden exactly as the reference overrides them
(``n_steps=10``, ``learning_rate=1e-3``, ``ent_coef=0.01``); everything else
(gamma, lambda, clip, epochs, batch size, vf coef, grad clip, Adam eps)
matches SB3's defaults so the ≤1% return-parity gate is meaningful.

Known deliberate deviation: when the rollout size is not divisible by
``batch_size``, the remainder transitions are dropped from each epoch's
shuffled pass (SB3 runs a final smaller minibatch). Static shapes keep the
whole update one XLA program; with default sizes the remainder is zero.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from flax.training.train_state import TrainState

from marl_distributedformation_tpu.models import distributions

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    """Static PPO hyperparameters (hashable; safe to close over in jit)."""

    n_steps: int = 10  # reference vectorized_env.py:128
    learning_rate: float = 1e-3  # vectorized_env.py:130
    ent_coef: float = 0.01  # vectorized_env.py:131
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_range: float = 0.2
    clip_range_vf: Optional[float] = None  # SB3 default: no value clipping
    n_epochs: int = 10
    batch_size: int = 64
    vf_coef: float = 0.5
    max_grad_norm: float = 0.5
    adam_eps: float = 1e-5  # SB3 ActorCriticPolicy optimizer default
    normalize_advantage: bool = True
    log_std_init: float = 0.0  # parity: the reference's -2 is a no-op (Q5)
    # Entropy-coefficient decay (beyond SB3, which only schedules lr/clip):
    # when ``ent_coef_final`` is set, the effective coefficient interpolates
    # linearly from ``ent_coef`` to ``ent_coef_final`` over the run, keyed
    # on the optimizer step already carried in ``TrainState.step`` — so it
    # threads through vmapped populations, scan-fused dispatch, and
    # checkpoint resume with zero extra state. Motivation: a constant
    # entropy bonus can leave a policy RELYING on its action noise (the
    # hetero5 artifact holds ring spacing only through noise — its mode
    # action collapses, docs/acceptance/hetero5/). NB measured caveat:
    # annealing removes the pressure to KEEP noise, but adds none to
    # move its function into the mean — in the hetero5 budget the noise
    # equilibrium was self-sustaining (entropy barely moved with the
    # bonus at 5e-4), so evaluate as-trained (eval_deterministic=false)
    # remains the honest measure for such policies. ``total_iterations``
    # (the decay horizon, in iterations) is filled by the trainer shell.
    ent_coef_final: Optional[float] = None
    # Scheduled action-noise decay (the round-4 lesson, VERDICT r4
    # next-#1): annealing the entropy BONUS alone removes the pressure to
    # keep noise but adds none to move its function into the mean — the
    # hetero5 policy's noise-as-spacing equilibrium was self-sustaining.
    # ``log_std_final`` adds that missing pressure as a PROJECTION: after
    # every optimizer step the learned ``log_std`` parameter is clamped
    # to a ceiling that decays linearly from ``log_std_init`` to
    # ``log_std_final`` over the run (same optimizer-step progress as the
    # entropy schedule). A projection rather than a loss term because the
    # clipped-Adam optimizer takes ~unit-scaled steps: any pull term
    # moves log_std at most ``learning_rate`` per minibatch step, far too
    # slow to traverse nats within a normal run's horizon. Clamping the
    # PARAMETER (not the effective value) keeps rollout, loss,
    # checkpoint, and eval consistent: the saved policy actually IS the
    # narrow-noise policy, so ``deterministic=True`` eval stops
    # misrepresenting it. The policy may still learn a log_std BELOW the
    # ceiling; the schedule only forbids hiding behavior in noise.
    # ``log_std_decay_start`` holds the ceiling at ``log_std_init`` until
    # that fraction of the run, then decays linearly to ``log_std_final``
    # over the remainder — full exploration while behavior is learned,
    # noise squeezed out in the home stretch (measured: an all-run decay
    # starves late curriculum stages of exploration).
    log_std_final: Optional[float] = None
    log_std_decay_start: float = 0.0
    total_iterations: int = 0

    def make_optimizer(
        self, inject_lr: bool = False
    ) -> optax.GradientTransformation:
        """The training optimizer (SB3's clipped Adam). ``inject_lr=True``
        wraps adam in ``optax.inject_hyperparams`` so the learning rate
        lives in the OPTIMIZER STATE — one shared transform can then serve
        a vmapped population with per-member rates (train/sweep.py).
        Single source of truth for the chain: both variants must stay
        structurally identical apart from the inject wrapper."""
        adam = (
            optax.inject_hyperparams(optax.adam)(
                learning_rate=self.learning_rate, eps=self.adam_eps
            )
            if inject_lr
            else optax.adam(self.learning_rate, eps=self.adam_eps)
        )
        return optax.chain(
            optax.clip_by_global_norm(self.max_grad_norm), adam
        )


@struct.dataclass
class MinibatchData:
    obs: Array  # (b, obs_dim)
    actions: Array  # (b, act_dim)
    old_log_probs: Array  # (b,)
    advantages: Array  # (b,)
    returns: Array  # (b,)
    weights: Array = None  # (b,) optional per-transition loss weights —
    #   heterogeneous (padded) formations put weight 0 on padded agents
    #   (env/hetero.py); None means uniform weights (homogeneous path).
    mask: Array = None  # (b, N) optional agent-validity mask forwarded to
    #   per-formation models (CTDE/GNN) so padded agents are excluded from
    #   the pooled critic; None for agent-factored models or homogeneous
    #   batches. Distinct from ``weights``: the mask shapes the MODEL's
    #   forward pass, weights shape the LOSS reduction.
    rows_sharding: Any = struct.field(pytree_node=False, default=None)
    #   Static, set on the flat rollout data ``ppo_update`` is handed: the
    #   layout of an epoch's (num_minibatches, batch_size) row indices over
    #   a mesh (``parallel.minibatch_sharding``), or None: every device
    #   takes each minibatch whole. It rides on the data, not in
    #   ``ppo_update``'s signature, so whatever stands in for
    #   ``ppo_update(train_state, data, key, config)`` keeps the layout.


def _leaf_name(entry) -> Optional[str]:
    """Name of a tree-path entry (DictKey .key / GetAttrKey .name) — the
    single definition shared by the log_std structure check and the
    projection clamp so the two can't drift."""
    return getattr(entry, "key", getattr(entry, "name", None))


def _wmean(x: Array, weights: Array) -> Array:
    """Weighted mean; with ``weights=None`` falls back to a plain mean."""
    if weights is None:
        return x.mean()
    w = weights.reshape(x.shape if x.ndim else ())
    return (x * w).sum() / jnp.maximum(w.sum(), 1e-8)


# One (8,128) tile's lanes: the packed table's physical row. Rows at most
# this wide pack, as many to a physical row as their power-of-two width fits.
_PACK_MAX_WIDTH = 128


def _pack_rows(
    data: MinibatchData,
) -> Optional[Tuple[Callable[[Array], Array], Callable[[Array], MinibatchData]]]:
    """Pack ``data``'s leaves into one float32 table 128 lanes wide, so a
    minibatch's rows are looked up once instead of once a leaf, and an index
    touches one (8,128) tile (the TPU pays a narrow-row gather per index and
    per tile the row touches, not per byte).

    A packed row of ``width`` floats takes ``sub`` lanes, the next power of
    two; ``g = 128 // sub`` of them share a physical row, ``P = ceil(total /
    g)`` physical rows in all. Logical row ``j`` lies in physical row ``j %
    P``, lanes ``(j // P) * sub`` onwards: strided groups, so the table is
    the plain transpose of a ``(128, P)`` array and ``total`` stays on the
    lanes while it is built (eight consecutive rows a physical row would go
    through a ``(total, sub)`` array lane-padded to 128).

    Returns ``(lookup, unpack)`` with ``unpack(lookup(idx))`` equal to
    ``tree_map(lambda x: x[idx], data)`` for ``idx`` in ``[0, total)``, or
    ``None`` where the rows do not pack: a leaf that is not float32, or rows
    wider than 128 floats (per-formation rows are already wide contiguous
    blocks). Where ``g > 1``, ``lookup`` picks the sub-row with a lane mask
    and a product against a constant 0/1 matrix at ``Precision.HIGHEST``
    (scope ``subrow_pick``): bit-equal for finite values, except that
    ``-0.0`` comes back ``+0.0``; a non-finite entry makes every float of
    its own logical row NaN or infinite, and of no other."""
    leaves, treedef = jax.tree_util.tree_flatten(data)
    shapes = [x.shape[1:] for x in leaves]
    widths = [math.prod(shape) for shape in shapes]
    width = sum(widths)
    if width > _PACK_MAX_WIDTH or any(x.dtype != jnp.float32 for x in leaves):
        return None
    total = leaves[0].shape[0]
    sub = 1 << (width - 1).bit_length()
    groups = _PACK_MAX_WIDTH // sub
    phys = -(-total // groups)
    columns = jnp.concatenate(
        [x.reshape(total, w).T for x, w in zip(leaves, widths)], axis=0
    )
    columns = jnp.pad(columns, ((0, sub - width), (0, groups * phys - total)))
    table = (
        columns.reshape(sub, groups, phys)
        .transpose(1, 0, 2)
        .reshape(_PACK_MAX_WIDTH, phys)
        .T
    )
    lane = np.arange(_PACK_MAX_WIDTH)
    lane_group = lane // sub
    fold = (lane[:, None] % sub == np.arange(sub)).astype(np.float32)

    def lookup(idx: Array) -> Array:
        rows = table[idx % phys]
        if groups == 1:
            return rows
        with jax.named_scope("subrow_pick"):
            mine = (idx // phys)[:, None] == lane_group
            return jnp.dot(
                jnp.where(mine, rows, 0.0),
                fold,
                precision=jax.lax.Precision.HIGHEST,
            )

    splits = list(itertools.accumulate(widths))

    def unpack(rows: Array) -> MinibatchData:
        # the last split is the pad up to ``sub`` lanes
        return treedef.unflatten(
            col.reshape(-1, *shape)
            for col, shape in zip(jnp.split(rows, splits, axis=1), shapes)
        )

    return lookup, unpack


def ppo_loss(
    nn_params: Any,
    apply_fn,
    mb: MinibatchData,
    config: PPOConfig,
    ent_coef: Optional[Array] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """Clipped-surrogate PPO loss on one minibatch (SB3 semantics).

    ``ent_coef`` overrides ``config.ent_coef`` with a traced scalar when
    the entropy coefficient is scheduled (``config.ent_coef_final``)."""
    if mb.mask is not None:
        mean, log_std, values = apply_fn(nn_params, mb.obs, mb.mask)
    else:
        mean, log_std, values = apply_fn(nn_params, mb.obs)
    log_probs = distributions.log_prob(mb.actions, mean, log_std)
    ent = distributions.entropy(log_std)

    w = mb.weights
    advantages = mb.advantages
    if config.normalize_advantage:
        # SB3 normalizes per minibatch with torch's unbiased std. With
        # weights, moments run over the weighted (active) transitions only.
        if w is None:
            advantages = (advantages - advantages.mean()) / (
                advantages.std(ddof=1) + 1e-8
            )
        else:
            wa = w.reshape(advantages.shape)
            n_active = jnp.maximum(wa.sum(), 2.0)
            adv_mean = (advantages * wa).sum() / n_active
            adv_var = (((advantages - adv_mean) ** 2) * wa).sum() / (
                n_active - 1.0
            )
            advantages = (advantages - adv_mean) / (jnp.sqrt(adv_var) + 1e-8)

    ratio = jnp.exp(log_probs - mb.old_log_probs)
    unclipped = advantages * ratio
    clipped = advantages * jnp.clip(
        ratio, 1.0 - config.clip_range, 1.0 + config.clip_range
    )
    policy_loss = -_wmean(jnp.minimum(unclipped, clipped), w)

    if config.clip_range_vf is not None:
        # SB3's value clipping: predictions move at most clip_range_vf
        # from the rollout-time values. Those old values need no extra
        # plumbing — GAE's identity returns = advantages + values means
        # old_values = returns - advantages (both raw in the minibatch;
        # normalization above works on a local copy).
        old_values = mb.returns - mb.advantages
        values = old_values + jnp.clip(
            values - old_values,
            -config.clip_range_vf,
            config.clip_range_vf,
        )
    value_loss = _wmean((mb.returns - values) ** 2, w)
    entropy_loss = -ent  # state-independent Gaussian: scalar

    effective_ent_coef = (
        config.ent_coef if ent_coef is None else ent_coef
    )
    loss = (
        policy_loss
        + effective_ent_coef * entropy_loss
        + config.vf_coef * value_loss
    )
    metrics = {
        "loss": loss,
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": ent,
        "approx_kl": _wmean(mb.old_log_probs - log_probs, w),
        "clip_fraction": _wmean(
            (jnp.abs(ratio - 1.0) > config.clip_range).astype(jnp.float32), w
        ),
    }
    return loss, metrics


def minibatch_shape(config: PPOConfig, total: int) -> Tuple[int, int]:
    """``(num_minibatches, batch_size)`` of an update over ``total`` rows."""
    # Clamp for rollouts smaller than batch_size (e.g. num_formation=1):
    # train on one full-rollout minibatch instead of crashing.
    batch_size = min(config.batch_size, total)
    return total // batch_size, batch_size


def ppo_update(
    train_state: TrainState,
    data: MinibatchData,
    key: Array,
    config: PPOConfig,
) -> Tuple[TrainState, Dict[str, Array]]:
    """Run ``n_epochs`` of shuffled minibatch SGD over flattened rollout data.

    ``data`` leaves are flat ``(total, ...)`` with ``total = T * M * N``
    agent-transitions — each agent is its own "environment", the reference's
    parameter-sharing trick (vectorized_env.py:32). Narrow float32 rows are
    packed into one table so a minibatch is one gather (``_pack_rows``).

    ``data.rows_sharding`` lays an epoch's ``(num_minibatches,
    batch_size)`` index array out over a mesh: each device then looks up
    and differentiates its share of every minibatch's rows, and the
    partitioner all-reduces the gradient, the advantage moments and the
    metrics. The permutation and the table stay whole on every device, so
    the minibatches are the same sets of rows as without it.
    """
    total = data.obs.shape[0]
    num_minibatches, batch_size = minibatch_shape(config, total)
    used = num_minibatches * batch_size

    ent_decay = config.ent_coef_final is not None
    std_decay = config.log_std_final is not None
    decay = ent_decay or std_decay
    if decay:
        assert config.total_iterations > 0, (
            "ent_coef_final/log_std_final require total_iterations > 0 "
            "(the trainer shell fills it; constructing PPOConfig by "
            "hand, pass the planned iteration count)"
        )
    if std_decay:
        # Structure check up front: the projection below is path-keyed on
        # the leaf name, so a model without a "log_std" parameter would
        # silently make the schedule a no-op.
        leaf_names = {
            _leaf_name(p[-1])
            for p, _ in jax.tree_util.tree_flatten_with_path(
                train_state.params
            )[0]
        }
        assert "log_std" in leaf_names, (
            "log_std_final requires a 'log_std' parameter leaf; "
            f"model params have {sorted(map(str, leaf_names))}"
        )
        assert 0.0 <= config.log_std_decay_start < 1.0, (
            "log_std_decay_start is the fraction of the run to hold the "
            "ceiling before decaying; it must be in [0, 1) — at >= 1 the "
            f"decay would silently never run (got "
            f"{config.log_std_decay_start})"
        )
    if decay:
        # Linear schedule on the optimizer step the TrainState already
        # carries — resumes, vmapped populations, and fused dispatch all
        # inherit the right position for free.
        # ASSUMES a constant rollout size across the run: the horizon is
        # derived from THIS call's num_minibatches, while ts.step
        # accumulated under every earlier call's count. All trainer
        # shells keep rollout shape fixed (hetero pads to N_max), so the
        # two agree; a variable-shape caller would miscalibrate the
        # anneal and must fill total_iterations in minibatch-steps
        # itself.
        expected_total = (
            config.total_iterations * config.n_epochs * num_minibatches
        )

    grad_fn = jax.value_and_grad(ppo_loss, has_aux=True)

    # Decided at trace time from what ``data`` holds; ``row_pack`` in a
    # trace says the job's rows packed (docs/profiling.md).
    with jax.named_scope("minibatch_gather"), jax.named_scope("row_pack"):
        packed = _pack_rows(data)

    def minibatch_step(ts: TrainState, idx: Array):
        with jax.named_scope("minibatch_gather"):
            if packed is None:
                mb = jax.tree_util.tree_map(lambda x: x[idx], data)
            else:
                lookup, unpack = packed
                mb = unpack(lookup(idx))
        ent_coef = None
        if decay:
            # Two-limb float split of the integer step: a straight
            # float32(step) collapses consecutive steps past 2^24 (#
            # reachable at parity batch_size=64 with large M), stalling
            # the anneal near the horizon. hi < 2^24 for any int32 step
            # and lo < 4096 are both exact in float32, so progress stays
            # strictly monotone in step.
            hi = jnp.asarray(ts.step // 4096, jnp.float32)
            lo = jnp.asarray(ts.step % 4096, jnp.float32)
            progress = jnp.clip(
                hi * (4096.0 / expected_total) + lo / expected_total,
                0.0,
                1.0,
            )
            if ent_decay:
                ent_coef = config.ent_coef + progress * (
                    config.ent_coef_final - config.ent_coef
                )
            if std_decay:
                start = config.log_std_decay_start
                sprog = jnp.clip(
                    (progress - start) / max(1.0 - start, 1e-8), 0.0, 1.0
                )
                log_std_ceiling = config.log_std_init + sprog * (
                    config.log_std_final - config.log_std_init
                )
        with jax.named_scope("loss_and_grad"):
            (_, metrics), grads = grad_fn(
                ts.params, ts.apply_fn, mb, config, ent_coef
            )
            # Raw (pre-clip) global gradient norm: the divergence
            # diagnostic the train lane's health word bounds
            # (train/recovery.py) — the optimizer chain clips at
            # max_grad_norm, so the clipped norm would saturate at 0.5
            # and hide every explosion.
            metrics["grad_norm"] = optax.global_norm(grads)
        if ent_decay:
            metrics["ent_coef"] = ent_coef
        with jax.named_scope("optimizer_step"):
            ts = ts.apply_gradients(grads=grads)
            if std_decay:
                # Project the log_std parameter under the decayed ceiling
                # — every model family names its state-independent noise
                # parameter "log_std" (models/mlp.py, ctde.py, gnn.py);
                # the path-keyed clamp composes with vmapped populations
                # (leaves gain a member axis, the name does not change).
                metrics["log_std_ceiling"] = log_std_ceiling

                def clamp(path, leaf):
                    if _leaf_name(path[-1]) == "log_std":
                        return jnp.minimum(leaf, log_std_ceiling)
                    return leaf

                ts = ts.replace(
                    params=jax.tree_util.tree_map_with_path(
                        clamp, ts.params
                    )
                )
        return ts, metrics

    def epoch_step(ts: TrainState, epoch_key: Array):
        with jax.named_scope("epoch_shuffle"):
            perm = jax.random.permutation(epoch_key, total)[:used]
            idx = perm.reshape(num_minibatches, batch_size)
            if data.rows_sharding is not None:
                idx = jax.lax.with_sharding_constraint(
                    idx, data.rows_sharding
                )
        ts, metrics = jax.lax.scan(minibatch_step, ts, idx)
        return ts, jax.tree_util.tree_map(lambda m: m.mean(), metrics)

    epoch_keys = jax.random.split(key, config.n_epochs)
    train_state, metrics = jax.lax.scan(epoch_step, train_state, epoch_keys)
    return train_state, jax.tree_util.tree_map(lambda m: m.mean(), metrics)
