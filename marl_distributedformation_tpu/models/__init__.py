"""Policy/value networks (flax) and action distributions."""

from marl_distributedformation_tpu.models.mlp import MLPActorCritic  # noqa: F401
from marl_distributedformation_tpu.models.ctde import CTDEActorCritic  # noqa: F401
from marl_distributedformation_tpu.models.gnn import GNNActorCritic  # noqa: F401
from marl_distributedformation_tpu.models.trunk import TrunkActorCritic  # noqa: F401
from marl_distributedformation_tpu.models import distributions  # noqa: F401
