"""Training substrate: step builder, trainer loop, SCISPACE checkpointing."""

from .checkpoint import CheckpointManager
from .step import build_train_step, init_state, init_state_abstract, shard_state, state_shardings
from .telemetry import TELEMETRY
from .trainer import FaultInjector, NodeFailure, Trainer, TrainerConfig

__all__ = [
    "CheckpointManager",
    "build_train_step",
    "init_state",
    "init_state_abstract",
    "shard_state",
    "state_shardings",
    "TELEMETRY",
    "FaultInjector",
    "NodeFailure",
    "Trainer",
    "TrainerConfig",
]
