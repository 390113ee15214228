"""Device-mesh sharding of speculative rollouts.

The reference scales by *replication* — every peer simulates the full world,
kept consistent by determinism (survey §2.3 point 2). The TPU-native scale
axis is different: the speculative branch batch is sharded across chips of a
``jax.sharding.Mesh`` and the confirmed branch is gathered back — XLA
inserts the collectives; they ride ICI.

Two mesh axes are used by the framework:

- ``"branch"`` — data-parallel analog: candidate input branches split across
  devices; zero cross-device traffic during the rollout, one gather at
  confirm time.
- ``"entity"`` — tensor-parallel analog for models whose systems couple
  entities (e.g. the all-pairs boids forces in
  :mod:`bevy_ggrs_tpu.models.boids`): the entity axis of the world state is
  split, and coupled systems ``psum``/all-gather over it inside the step.

Sessions never see any of this: the :class:`~bevy_ggrs_tpu.parallel.
speculate.SpeculativeExecutor` takes an optional mesh and lays out its
branch-stacked pytrees with :func:`shard_branch_axis`.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def branch_mesh(
    devices: Optional[Sequence] = None,
    entity_shards: int = 1,
    branch_axis: str = "branch",
    entity_axis: str = "entity",
) -> Mesh:
    """A ``[branch, entity]`` mesh over ``devices`` (default: all).

    ``entity_shards`` devices along the entity (model-parallel) axis; the
    rest along the branch (data-parallel) axis.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % entity_shards:
        raise ValueError(f"{n} devices not divisible by entity_shards={entity_shards}")
    arr = np.array(devices).reshape(n // entity_shards, entity_shards)
    return Mesh(arr, (branch_axis, entity_axis))


def shard_branch_axis(tree, mesh: Mesh, branch_axis: str = "branch"):
    """Place every leaf's leading (branch) axis over ``mesh``'s branch axis,
    replicating all other dims. Leaves without a leading branch axis are
    replicated by the caller's jit; this helper is for branch-stacked
    pytrees (states[B], rings[B], bits[B, F, ...])."""
    sharding = NamedSharding(mesh, P(branch_axis))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def branch_pspec(mesh: Mesh, branch_axis: str = "branch") -> NamedSharding:
    return NamedSharding(mesh, P(branch_axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Entity-axis (model-parallel analog) sharding of world-state pytrees
# ---------------------------------------------------------------------------


def world_pspecs(state, entity_axis: Optional[str] = None):
    """PartitionSpec tree for a :class:`~bevy_ggrs_tpu.state.WorldState`:
    every leaf with a leading ``capacity`` axis is split over
    ``entity_axis`` (or replicated when None); resources replicate.

    With these annotations, coupled systems (e.g. the boids all-pairs
    forces) need no manual collectives: GSPMD propagates the sharding
    through the [N, N] interaction and inserts the all-gathers/reductions
    itself — the scaling-book recipe (annotate, compile, profile).
    """
    cap = state.capacity

    def spec(x):
        if (
            entity_axis is not None
            and hasattr(x, "ndim")
            and x.ndim >= 1
            and x.shape[0] == cap
        ):
            return P(entity_axis)
        return P()

    return jax.tree_util.tree_map(spec, state)


def prepend_axes(specs_tree, *axes):
    """Prefix every PartitionSpec in the tree with ``axes`` (e.g. a leading
    ring-depth ``None`` or a ``"branch"`` batch axis)."""
    return jax.tree_util.tree_map(
        lambda s: P(*axes, *s), specs_tree, is_leaf=lambda s: isinstance(s, P)
    )


def to_named(specs_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def shard_world(state, mesh: Mesh, entity_axis: str = "entity"):
    """Lay a world state out with its entity (capacity) axis split over the
    mesh's entity axis."""
    return jax.tree_util.tree_map(
        jax.device_put, state, to_named(world_pspecs(state, entity_axis), mesh)
    )


def world_and_ring_shardings(
    state_template, mesh: Mesh, entity_axis: str, prefix: tuple = ()
):
    """The (world, snapshot-ring) sharding pair every executor needs:
    world leaves split on ``entity_axis``, ring leaves gain a replicated
    depth axis, and ``prefix`` names any leading batch axes (the
    speculative executor passes ``(branch_axis,)``; the serial executor
    none). Shared so the recipe can't drift between the two paths."""
    from bevy_ggrs_tpu.state import SnapshotRing

    sspec = world_pspecs(state_template, entity_axis)
    state_s = to_named(prepend_axes(sspec, *prefix), mesh)
    ring_s = SnapshotRing(
        states=to_named(prepend_axes(sspec, *prefix, None), mesh),
        frames=NamedSharding(mesh, P(*prefix)),
        checksums=NamedSharding(mesh, P(*prefix)),
    )
    return state_s, ring_s
