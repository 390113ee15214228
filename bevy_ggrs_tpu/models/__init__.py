"""Example game models (the reference's ``examples/`` analog): each model
provides a registry, a setup/spawn routine, and a rollback schedule of pure
systems.

- ``box_game`` — reference-parity example (per-entity arithmetic)
- ``boids`` — entity-coupled O(N²) flocking (VPU / Pallas showcase)
- ``neural_bots`` — MLP-policy agents (MXU showcase: batched inference
  inside the rollback domain, weights as rollback state)
- ``projectiles`` — dynamic entity lifecycle (in-step spawn/despawn with a
  device-resident rollback-id allocator)
- ``particles`` — upstream's particle stress test: a hundred births and a
  hundred deaths a frame (the shared claim's select form)
"""

from bevy_ggrs_tpu.models import boids, box_game, neural_bots, particles, projectiles
