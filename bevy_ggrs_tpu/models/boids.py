"""Boids flocking: the entity-count scaling model (BASELINE.md config 4).

Unlike box_game (`/root/reference/examples/box_game/box_game.rs`) whose
entities are independent given inputs, boids couple ALL entities through the
classic separation/alignment/cohesion rules — an O(N²) pairwise interaction
per frame. That makes it:

- the entity-count stress model (1k+ rollback-tagged entities, each with
  Transform+Velocity, per BASELINE.md config 4), and
- the model-parallel showcase: the pairwise force matrix shards over the
  mesh's ``entity`` axis (each shard computes its rows against an
  all-gathered position set — the TP analog), composing with branch-axis
  data parallelism.

Players steer flock "leaders" with the same u8 input bitmask as box_game, so
the full session machinery (prediction, rollback, checksums) applies
unchanged.

Determinism note: all reductions are fixed-order ``sum`` over a static
entity axis — bit-reproducible under XLA on a given platform, which is what
the SyncTest harness checks.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.ops import neighbor
from bevy_ggrs_tpu.schedule import InputSpec, PlayerInputs, Schedule
from bevy_ggrs_tpu.state import HostWorld, TypeRegistry, WorldState

INPUT_UP = 1 << 0
INPUT_DOWN = 1 << 1
INPUT_LEFT = 1 << 2
INPUT_RIGHT = 1 << 3

# 4 steering bits -> value universe 0..15 for speculation branch trees.
INPUT_SPEC = InputSpec(shape=(), dtype=jnp.uint8, values=tuple(range(16)))

# Flocking parameters (2D plane).
NEIGHBOR_RADIUS = 1.0
SEPARATION_RADIUS = 0.35
# np scalars, not jnp: importing this module must not execute a JAX op
# (backend selection may not have happened yet — e.g. the multichip dryrun
# rebuilds a virtual CPU mesh before touching any model).
W_SEPARATION = np.float32(0.08)
W_ALIGNMENT = np.float32(0.05)
W_COHESION = np.float32(0.03)
W_LEADER = np.float32(0.06)
LEADER_STEER = np.float32(0.02)
MAX_SPEED = np.float32(0.08)
MIN_SPEED = np.float32(0.02)
WORLD_HALF = np.float32(8.0)


def make_registry() -> TypeRegistry:
    reg = TypeRegistry()
    reg.register_component("position", shape=(2,), dtype=jnp.float32)
    reg.register_component("velocity", shape=(2,), dtype=jnp.float32)
    # Leader boids carry the player handle steering them; -1 = flock member.
    reg.register_component("leader_handle", shape=(), dtype=jnp.int32, default=-1)
    reg.register_resource("frame_count", jnp.uint32(0))
    return reg


def make_world(
    num_boids: int,
    num_players: int,
    capacity: Optional[int] = None,
    seed: int = 0,
) -> HostWorld:
    """``num_boids`` flock members on a deterministic spawn spiral; the
    first ``num_players`` of them are player-steered leaders."""
    capacity = num_boids if capacity is None else capacity
    world = HostWorld(make_registry(), capacity)
    rng = np.random.RandomState(seed)
    for i in range(num_boids):
        ang = i * 2.399963  # golden-angle spiral: deterministic, spread out
        rad = 0.15 * math.sqrt(i + 1)
        vel = rng.uniform(-0.03, 0.03, size=2).astype(np.float32)
        world.spawn(
            {
                "position": np.array(
                    [rad * math.cos(ang), rad * math.sin(ang)], dtype=np.float32
                ),
                "velocity": vel,
                "leader_handle": np.int32(i if i < num_players else -1),
            },
            rollback_id=i,
        )
    return world


def _kernel_params() -> dict:
    """The five flocking constants every MXU kernel call shares —
    built in one place (read at call time, not import time) so the
    sharded and unsharded paths can never silently diverge on a tuning
    change, which would void the allclose-across-paths contract."""
    return dict(
        neighbor_radius=float(NEIGHBOR_RADIUS),
        separation_radius=float(SEPARATION_RADIUS),
        w_separation=float(W_SEPARATION),
        w_alignment=float(W_ALIGNMENT),
        w_cohesion=float(W_COHESION),
    )


def flock_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """One flocking step: O(N²) pairwise separation/alignment/cohesion
    forces + leader steering from player inputs, then clamped integration.

    The pairwise part is a dense [N, N] interaction — on TPU this is MXU/VPU
    work that a sharded variant splits by rows over the ``entity`` mesh axis
    (see ``bevy_ggrs_tpu.parallel.sharding.world_pspecs``).
    """
    return _flock_step(state, inputs, _pairwise_forces)


def flock_system_mxu(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """`flock_system` with the pairwise reductions carried by the MXU
    (:func:`bevy_ggrs_tpu.ops.pairwise.pairwise_force_rows_mxu2`): the
    neighborhood sums become feature-major bf16 matmuls with f32
    accumulation (operands split into bf16 terms: two of everything, a
    third of the positions the separation sum cancels), while d2 and the
    membership masks stay f32 so borderline pairs classify identically on
    all paths. Measured on the v5e through the normal path (`boids1k.wan`:
    1,024 boids, 128 branches x 8 frames a tick, two programs a tick since
    PR 32; my chip run of 2026-10-02, PR 49, `PERF.md` section 6): a tick
    6.4 ms of device time, 5.6 ms of it this kernel (7.7 and 7.0 until
    PR 49 walked the pair block in strips; 15.6 ms a tick with the sums
    reduced on the VPU, PR 31: that kernel went in PR 57); under
    `[64] x [8]` in a served dispatch
    24.4 of 27.2 ms; one step within 5e-6 of a plain float32 NumPy
    reference (before PR 31's two repairs: 4.4e-5, more for a close
    pair). At N >= 4096 the square all-vs-all
    shape dispatches to the symmetry-halved triangle kernel
    (:func:`~bevy_ggrs_tpu.ops.pairwise.pairwise_force_square_mxu_tri`;
    not measured on this chip); below that the
    block grid is too small to amortize the triangle's col-side work.
    The session caveat of every force path: allclose across paths,
    bitwise only within one — and the two MXU shapes are themselves
    distinct float paths, chosen statically by N, so every executable at
    a given world size uses exactly one."""
    from bevy_ggrs_tpu.ops.pairwise import (
        pairwise_force_rows_mxu2,
        pairwise_force_square_mxu_tri,
    )

    params = _kernel_params()

    def forces(pos, vel, active):
        if pos.shape[0] >= 4096:  # static shape: one kernel per executable
            return pairwise_force_square_mxu_tri(pos, vel, active, **params)
        return pairwise_force_rows_mxu2(
            pos, vel, pos, vel, active, active, **params
        )

    return _flock_step(state, inputs, forces)


def _flock_step(state: WorldState, inputs: PlayerInputs, pairwise_fn) -> WorldState:
    pos = state.components["position"]  # [N, 2]
    vel = state.components["velocity"]
    leader = state.components["leader_handle"]
    active = (state.alive & state.present["position"]).astype(jnp.float32)  # [N]

    force = pairwise_fn(pos, vel, active)

    # Leader steering (player inputs), box_game-style exclusive keys.
    bits = inputs.for_handles(leader).astype(jnp.uint32)
    is_leader = (leader >= 0) & state.alive
    steer_x = (
        ((bits & INPUT_RIGHT) != 0).astype(jnp.float32)
        - ((bits & INPUT_LEFT) != 0).astype(jnp.float32)
    )
    steer_y = (
        ((bits & INPUT_DOWN) != 0).astype(jnp.float32)
        - ((bits & INPUT_UP) != 0).astype(jnp.float32)
    )
    steer = jnp.stack([steer_x, steer_y], axis=1) * LEADER_STEER
    force = force + jnp.where(is_leader[:, None], steer, 0.0)

    new_vel = vel + force
    # Speed clamp to [MIN_SPEED, MAX_SPEED].
    speed = jnp.sqrt(jnp.sum(new_vel * new_vel, axis=1, keepdims=True))
    speed_safe = jnp.maximum(speed, jnp.float32(1e-6))
    clamped = jnp.clip(speed_safe, MIN_SPEED, MAX_SPEED)
    new_vel = new_vel * (clamped / speed_safe)

    new_pos = pos + new_vel
    # Toroidal wrap keeps the flock bounded without wall dynamics.
    new_pos = jnp.where(new_pos > WORLD_HALF, new_pos - 2 * WORLD_HALF, new_pos)
    new_pos = jnp.where(new_pos < -WORLD_HALF, new_pos + 2 * WORLD_HALF, new_pos)

    sel = (state.alive & state.present["position"] & state.present["velocity"])[
        :, None
    ]
    return state.replace(
        components={
            **state.components,
            "position": jnp.where(sel, new_pos, pos),
            "velocity": jnp.where(sel, new_vel, vel),
        }
    )


def _pairwise_forces(
    pos: jnp.ndarray, vel: jnp.ndarray, active: jnp.ndarray
) -> jnp.ndarray:
    """Dense all-pairs flocking forces for rows [N] against columns [N].

    Factored out so the entity-sharded variant can compute row blocks
    against the full (all-gathered) column set.
    """
    from bevy_ggrs_tpu.obs.trace import device_scope
    from bevy_ggrs_tpu.ops.pairwise import FORCE

    with device_scope(FORCE):
        return pairwise_force_rows(pos, vel, pos, vel, active, active)


def pairwise_force_rows(
    row_pos: jnp.ndarray,  # [R, 2] — the rows this shard owns
    row_vel: jnp.ndarray,  # [R, 2]
    all_pos: jnp.ndarray,  # [N, 2] — every boid (gathered)
    all_vel: jnp.ndarray,  # [N, 2]
    row_active: jnp.ndarray,  # float[R]
    all_active: jnp.ndarray,  # float[N]
) -> jnp.ndarray:
    """Separation/alignment/cohesion force on each row boid from all boids.

    Self-interaction is annihilated by the distance-zero mask on separation
    and by excluding d≈0 from the neighborhood.
    """
    diff = row_pos[:, None, :] - all_pos[None, :, :]  # [R, N, 2]
    d2 = jnp.sum(diff * diff, axis=2)  # [R, N]

    both = row_active[:, None] * all_active[None, :]
    is_self = d2 < jnp.float32(1e-10)
    # Neighborhood membership on d² (identical float values to the Pallas
    # kernel's masks, so borderline pairs classify the same on both paths);
    # 1/d via one rsqrt — no sqrt/divide on the [R, N] inner tensors.
    neigh = (
        both
        * (d2 < jnp.float32(NEIGHBOR_RADIUS) ** 2).astype(jnp.float32)
        * (1.0 - is_self.astype(jnp.float32))
    )  # [R, N]
    n_neigh = jnp.sum(neigh, axis=1, keepdims=True)  # [R, 1]
    n_safe = jnp.maximum(n_neigh, jnp.float32(1.0))

    # Separation: push away from too-close neighbors, 1/d weighted.
    inv_d = jax.lax.rsqrt(jnp.maximum(d2, jnp.float32(1e-12)))
    close = neigh * (d2 < jnp.float32(SEPARATION_RADIUS) ** 2).astype(jnp.float32)
    sep = jnp.sum(diff * inv_d[:, :, None] * close[:, :, None], axis=1)

    # Alignment: match neighborhood mean velocity.
    mean_vel = jnp.sum(all_vel[None, :, :] * neigh[:, :, None], axis=1) / n_safe
    align = jnp.where(n_neigh > 0, mean_vel - row_vel, 0.0)

    # Cohesion: steer toward neighborhood centroid.
    mean_pos = jnp.sum(all_pos[None, :, :] * neigh[:, :, None], axis=1) / n_safe
    coh = jnp.where(n_neigh > 0, mean_pos - row_pos, 0.0)

    force = W_SEPARATION * sep + W_ALIGNMENT * align + W_COHESION * coh
    return force * row_active[:, None]


# ---------------------------------------------------------------------------
# Grid mode: the same flocking rules over the spatial-binning neighbor grid
# (ops/neighbor.py) — O(N·(9K+S)) instead of O(N²). Dense and grid modes are
# allclose, not bitwise (different summation association); a session picks
# one mode, and within grid mode serial/fused/sharded executables are
# bitwise-equal to each other (tests/test_neighbor.py).
# ---------------------------------------------------------------------------


def _flock_accumulate(dx, dy, d2, row, col):
    """Per-pair flocking terms, mask-for-mask identical to
    :func:`pairwise_force_rows` (same f32 d² thresholds, same d≈0
    self-exclusion — borderline pairs classify the same in both modes)."""
    both = row["active"] * col["active"]
    is_self = (d2 < jnp.float32(1e-10)).astype(jnp.float32)
    neigh = (
        both
        * (d2 < jnp.float32(NEIGHBOR_RADIUS) ** 2).astype(jnp.float32)
        * (1.0 - is_self)
    )
    inv_d = jax.lax.rsqrt(jnp.maximum(d2, jnp.float32(1e-12)))
    close = neigh * (d2 < jnp.float32(SEPARATION_RADIUS) ** 2).astype(
        jnp.float32
    )
    w = inv_d * close
    return (
        neigh,                 # neighbor count
        dx * w, dy * w,        # separation (1/d-weighted push-away)
        col["vx"] * neigh, col["vy"] * neigh,  # alignment sums
        col["px"] * neigh, col["py"] * neigh,  # cohesion sums
    )


def _flock_combine(sums, row):
    n, sx, sy, svx, svy, spx, spy = sums
    n_safe = jnp.maximum(n, jnp.float32(1.0))
    has = (n > 0).astype(jnp.float32)
    fx = (
        W_SEPARATION * sx
        + W_ALIGNMENT * (svx / n_safe - row["vx"]) * has
        + W_COHESION * (spx / n_safe - row["px"]) * has
    )
    fy = (
        W_SEPARATION * sy
        + W_ALIGNMENT * (svy / n_safe - row["vy"]) * has
        + W_COHESION * (spy / n_safe - row["py"]) * has
    )
    return (fx * row["active"], fy * row["active"])


FLOCK_PAIR_KERNEL = neighbor.PairKernel(
    radius=float(NEIGHBOR_RADIUS),
    out_dim=2,
    n_terms=7,
    accumulate=_flock_accumulate,
    combine=_flock_combine,
    row_feats=("vx", "vy"),
    col_feats=("vx", "vy"),
)


def grid_config(num_boids: int) -> neighbor.GridConfig:
    """The boids neighbor grid: cell edge = NEIGHBOR_RADIUS over the
    ±WORLD_HALF torus (spawn-spiral positions beyond the torus just alias
    mod G — false candidates the radius mask rejects)."""
    return neighbor.default_grid_config(
        num_boids, float(NEIGHBOR_RADIUS), float(WORLD_HALF)
    )


def _grid_forces(pos, vel, active, impl):
    return neighbor.interact(
        pos, active, FLOCK_PAIR_KERNEL,
        feats={"vx": vel[:, 0], "vy": vel[:, 1]},
        mode="grid", config=grid_config(pos.shape[0]), impl=impl,
    )


def flock_system_grid(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """`flock_system` over the neighbor grid, per-cell compute in XLA
    (GSPMD-friendly; also the interpret-mode reference for the cell
    kernel)."""
    return _flock_step(
        state, inputs, lambda p, v, a: _grid_forces(p, v, a, "xla")
    )


def flock_system_grid_pallas(
    state: WorldState, inputs: PlayerInputs
) -> WorldState:
    """`flock_system` over the neighbor grid with the per-cell compute in
    the Pallas cell-gather kernel (:mod:`bevy_ggrs_tpu.ops.cell_gather`) —
    the single-chip 32k/64k path."""
    return _flock_step(
        state, inputs, lambda p, v, a: _grid_forces(p, v, a, "pallas")
    )


def increase_frame_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    del inputs
    return state.replace(
        resources={
            **state.resources,
            "frame_count": state.resources["frame_count"] + jnp.uint32(1),
        }
    )


def make_sharded_flock_system(mesh, entity_axis: str = "entity",
                              kernel: str = "mxu",
                              mode: Optional[str] = None):
    """A flock system whose Pallas kernel PARTITIONS over the mesh's entity
    axis via ``shard_map`` (round-2 verdict weak #7: GSPMD cannot partition
    a custom call, so under plain jit the Pallas kernels ran replicated —
    only the XLA path scaled). Each device all-gathers the column set
    (positions/velocities ride ICI once per step) and runs the kernel on
    its own row block — the row-subset contract the kernels already expose
    for exactly this (``pairwise_force_rows*(row_*, all_*)``).

    Works in BOTH executors: the entity-sharded serial session (1D entity
    mesh, dryrun §4) and the vmapped SpeculativeExecutor on a 2D
    branch×entity mesh (shard_map under vmap — bitwise-equal to the
    unsharded kernel, `tests/test_boids.py::TestShardMapSpeculative`)."""
    from jax.sharding import PartitionSpec as P

    from bevy_ggrs_tpu.ops.pairwise import pairwise_force_rows_mxu2

    if kernel != "mxu":
        # "xla" needs no shard_map: GSPMD partitions ``make_schedule()``.
        raise ValueError(
            f"unknown sharded boids force kernel {kernel!r} (accepted: 'mxu')"
        )
    params = _kernel_params()

    def per_shard(p, v, a):  # p: [N/k, 2] — this shard's rows
        all_p = jax.lax.all_gather(p, entity_axis, axis=0, tiled=True)
        all_v = jax.lax.all_gather(v, entity_axis, axis=0, tiled=True)
        all_a = jax.lax.all_gather(a, entity_axis, axis=0, tiled=True)
        return pairwise_force_rows_mxu2(
            p, v, all_p, all_v, a, all_a, **params
        )

    n_shards = mesh.shape[entity_axis]

    def per_shard_grid(p, v, a):
        # Grid mode partitions by CELLS, not rows: every shard runs the
        # identical replicated binning on the gathered set (bitwise-equal
        # inputs -> bitwise-equal tables), computes slot forces for its
        # contiguous cell slice, and all-gathers the slot-force tensor —
        # an exact concatenation, so the scatter consumes bit-identical
        # values to the unsharded path (a psum would not be: float
        # reduction can re-associate). Spill + scatter are replicated.
        all_p = jax.lax.all_gather(p, entity_axis, axis=0, tiled=True)
        all_v = jax.lax.all_gather(v, entity_axis, axis=0, tiled=True)
        all_a = jax.lax.all_gather(a, entity_axis, axis=0, tiled=True)
        n = all_p.shape[0]
        cfg = grid_config(n)
        if cfg.num_cells % n_shards:
            raise ValueError(
                f"{cfg.num_cells} grid cells do not shard over "
                f"{n_shards} devices"
            )
        grid, cand, padded = neighbor.build_grid_tables(
            all_p, all_a, cfg,
            feats={"vx": all_v[:, 0], "vy": all_v[:, 1]},
        )
        cells_per = cfg.num_cells // n_shards
        idx = jax.lax.axis_index(entity_axis)
        slots_sl = jax.lax.dynamic_slice_in_dim(
            grid.slots, idx * cells_per, cells_per, 0
        )
        cand_sl = jax.lax.dynamic_slice_in_dim(
            cand, idx * cells_per, cells_per, 0
        )
        slot_f = neighbor.slot_forces(
            FLOCK_PAIR_KERNEL, slots_sl, cand_sl, padded
        )
        slot_full = jax.lax.all_gather(
            slot_f, entity_axis, axis=0, tiled=True
        )
        spill_f = neighbor.spill_forces(FLOCK_PAIR_KERNEL, grid.spill, padded)
        out = neighbor.scatter_forces(
            n, grid.slots, grid.spill, slot_full, spill_f
        )
        return jax.lax.dynamic_slice_in_dim(out, idx * p.shape[0],
                                            p.shape[0], 0)

    def _shard(fn):
        # check_vma=False: the per-shard bodies place their own
        # collectives, which the replication inference would reject.
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(entity_axis, None), P(entity_axis, None), P(entity_axis)
            ),
            out_specs=P(entity_axis, None),
            check_vma=False,
        )

    sharded_force = _shard(per_shard)
    sharded_grid_force = _shard(per_shard_grid)

    def system(state: WorldState, inputs: PlayerInputs) -> WorldState:
        n = state.components["position"].shape[0]
        resolved = neighbor.resolve_mode(mode, n)
        fn = sharded_grid_force if resolved == "grid" else sharded_force
        return _flock_step(state, inputs, fn)

    return system


def make_sharded_schedule(mesh, entity_axis: str = "entity",
                          kernel: str = "mxu",
                          mode: Optional[str] = None) -> Schedule:
    return Schedule([
        make_sharded_flock_system(mesh, entity_axis, kernel, mode=mode),
        increase_frame_system,
    ])


_KERNELS = {
    "xla": flock_system,
    "mxu": flock_system_mxu,
}


def make_schedule(kernel: str = "xla", mode: Optional[str] = None) -> Schedule:
    """``kernel``: "xla" (the serial reference, GSPMD-partitionable) or
    "mxu" (the Pallas kernel, matmul reductions); any other name raises a
    ``ValueError``. On the v5e at 1,024 boids, 128 branches x 8 frames a
    fused tick (my chip runs, PR 31 and PR 49; `PERF.md` section 6): "mxu"
    7.5 then 6.4 ms a tick, and "xla" fails the warm-up attestation there
    (its vmapped rollout is not bitwise its serial burst), so a session on
    it runs without speculation.

    ``mode`` selects the interaction structure: "dense" (the O(N²)
    paths above), "grid" (the O(N·k) neighbor grid — "mxu" routes its
    per-cell compute through the cell-gather kernel, "xla" stays pure
    XLA), or "auto" (grid at N >= neighbor grid
    threshold). ``None`` keeps the legacy dense default. Resolution
    happens at trace time via :func:`bevy_ggrs_tpu.ops.neighbor.
    resolve_mode` — the ``GGRS_FORCE_MODE`` env var and the
    ``SessionBuilder.with_interaction_mode`` session default override
    ``None``/"auto" (never an explicit "dense"/"grid")."""
    if kernel not in _KERNELS:
        raise ValueError(
            f"unknown boids force kernel {kernel!r} "
            f"(accepted: {', '.join(map(repr, _KERNELS))})"
        )
    dense_system = _KERNELS[kernel]
    grid_system = (
        flock_system_grid_pallas if kernel == "mxu" else flock_system_grid
    )

    def flock(state: WorldState, inputs: PlayerInputs) -> WorldState:
        n = state.components["position"].shape[0]
        resolved = neighbor.resolve_mode(mode, n)
        return (grid_system if resolved == "grid" else dense_system)(
            state, inputs
        )

    return Schedule([flock, increase_frame_system])
