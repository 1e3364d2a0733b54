"""PPO training algorithm: GAE, rollouts, clipped-surrogate updates."""

from marl_distributedformation_tpu.algo.gae import compute_gae  # noqa: F401
from marl_distributedformation_tpu.algo.ppo import (  # noqa: F401
    MinibatchData,
    PPOConfig,
    minibatch_shape,
    ppo_loss,
    ppo_update,
)
from marl_distributedformation_tpu.algo.rollout import (  # noqa: F401
    RolloutBatch,
    collect_rollout,
)
