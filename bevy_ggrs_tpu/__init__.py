"""bevy_ggrs_tpu — a TPU-native rollback-simulation framework.

A from-scratch rebuild of the capabilities of `bevy_ggrs` (the Bevy plugin for
the GGRS P2P rollback-netcode library, reference at
`/root/reference/src/lib.rs`), designed TPU-first:

- Rollback-registered game state lives as an SoA pytree of device arrays in
  HBM (``state.WorldState``) instead of reflection-cloned ECS components
  (reference ``src/world_snapshot.rs:51-56``).
- The snapshot ring buffer (reference ``src/ggrs_stage.rs:89``) is a stacked,
  device-resident pytree; save/load are `dynamic_update_slice` index ops, not
  deep copies.
- Misprediction resimulation (reference ``src/ggrs_stage.rs:259-269``'s serial
  request loop) is a fused `lax.scan` over frames, optionally `vmap`-ed over
  speculative input branches and `pjit`-sharded across a device mesh.
- The GGRS session protocol (P2P / SyncTest / Spectator), input prediction,
  input delay, and the save/load/advance request contract are reimplemented
  from scratch in `session/`; peer transport is non-blocking UDP or an
  in-memory loopback in `transport/`.
"""

from bevy_ggrs_tpu.state import (
    TypeRegistry,
    ComponentDef,
    ResourceDef,
    WorldState,
    HostWorld,
    SnapshotRing,
    init_state,
    ring_init,
    ring_save,
    ring_load,
    ring_frame_at,
    checksum,
    combine64,
    to_host,
)

# Heavier layers import on demand to keep `import bevy_ggrs_tpu` light:
#   bevy_ggrs_tpu.app          — GGRSPlugin / RollbackApp / GGRSStage
#   bevy_ggrs_tpu.runner       — RollbackRunner (request-burst executor)
#   bevy_ggrs_tpu.spec_runner  — SpeculativeRollbackRunner (recovery-as-select)
#   bevy_ggrs_tpu.session      — P2P / SyncTest / Spectator + builder
#   bevy_ggrs_tpu.transport    — UDP + deterministic loopback
#   bevy_ggrs_tpu.parallel     — branch/entity sharding, multihost, executor
#   bevy_ggrs_tpu.ops          — Pallas TPU kernels (checksum, pairwise)
#   bevy_ggrs_tpu.utils        — metrics, persistence (checkpoint/resume)

__version__ = "0.5.0"
