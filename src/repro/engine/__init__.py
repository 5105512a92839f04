from .spmd import AXIS, EngineConfig, SPMDEngine, stack_epoch_batches
from .sequential import SequentialReference
from .stacking import (StackedBlocks, build_stacked_gat_blocks,
                       build_stacked_split_vjp_blocks,
                       build_stacked_vjp_blocks, stack_pytrees)

__all__ = [
    "AXIS", "EngineConfig", "SPMDEngine", "SequentialReference",
    "StackedBlocks", "build_stacked_vjp_blocks", "build_stacked_gat_blocks",
    "build_stacked_split_vjp_blocks", "stack_pytrees",
    "stack_epoch_batches", "make_engine",
]


def make_engine(model, loss_fn, optimizer, pg, hp=None, config=None):
    """Mode-dispatching factory: sequential -> SequentialReference, anything
    else -> SPMDEngine (which resolves auto/spmd/stacked itself)."""
    from ..core.gp.trainer import GPHyperParams

    hp = hp or GPHyperParams()
    config = config or EngineConfig()
    if config.mode == "sequential":
        return SequentialReference(model, loss_fn, optimizer, pg, hp, config)
    return SPMDEngine(model, loss_fn, optimizer, pg, hp, config)
