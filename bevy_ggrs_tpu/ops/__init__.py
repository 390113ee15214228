"""Pallas TPU kernels for the framework's hot ops.

Two device-side cost centers dominate the rollback loop (survey §3.4-3.6):

- the per-save order-insensitive world checksum (reference
  ``/root/reference/src/world_snapshot.rs:72-75,123-125``) — a streaming
  integer hash over every registered component word of every slot, executed
  once per simulated frame and once per speculative branch;
- entity-coupled model dynamics, here the boids O(N²) pairwise interaction
  (BASELINE.md config 4), where materializing [N, N] intermediates in HBM is
  the bandwidth trap.

Both get hand-blocked Pallas kernels that stream HBM exactly once per input.
Kernels run compiled on TPU and in interpreter mode elsewhere (the CPU test
mesh), selected automatically.
"""

from bevy_ggrs_tpu.ops.checksum import checksum_pallas, install_pallas_checksum
from bevy_ggrs_tpu.ops.neighbor import (
    GridConfig,
    PairKernel,
    bin_entities,
    default_grid_config,
    grid_stats,
    interact,
    resolve_mode,
    set_default_interaction_mode,
)

__all__ = [
    "GridConfig",
    "PairKernel",
    "bin_entities",
    "checksum_pallas",
    "default_grid_config",
    "grid_stats",
    "install_pallas_checksum",
    "interact",
    "resolve_mode",
    "set_default_interaction_mode",
]
