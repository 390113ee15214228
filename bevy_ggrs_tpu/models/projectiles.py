"""Projectiles: dynamic entity lifecycle driven from inside game systems.

The reference's restore path handles entities created or destroyed during
mispredicted frames — find-or-spawn by rollback id plus despawn of live
entities absent from the snapshot (``/root/reference/src/world_snapshot.rs:
140-151,190-193``) — and users mint ids for mid-game spawns through
``RollbackIdProvider`` (``/root/reference/src/lib.rs:59-75``). box_game and
boids never exercise that: their entity sets are fixed at setup. This model
makes spawn/despawn the gameplay itself, so rollback across entity-set
changes is what SyncTest/P2P certify:

- each player steers a TURRET (like a box_game cube, 2D);
- the FIRE bit spawns a PROJECTILE entity *inside the jitted step* — a
  vectorized claim of free capacity slots with a fresh rollback id from a
  device-resident allocator;
- projectiles fly straight, expire after ``PROJ_TTL`` frames, leave the
  arena, or hit an opposing turret (scoring a point) — all three release
  the slot (despawn) inside the step.

TPU-native design notes:

- Spawn is a masked select: firing players are ranked with a cumulative
  sum and matched rank-for-rank to free slots; every free slot reads its own
  ordinal off the free-slot prefix sum and takes the shot of that rank, and
  a shot whose rank finds no free slot is dropped when capacity is
  exhausted — no data-dependent shapes and nothing indexed, so the step
  stays one fused XLA program under ``lax.scan``/``vmap``. The claim is the
  shared helper ``ops/lifecycle.py`` ``claim_rows`` (``models/particles.py``
  is its other caller, with a hundred births a frame), which says why it is
  a select and not a scatter.
- The rollback-id allocator is a REGISTERED RESOURCE (``next_rollback_id``):
  rolling back rewinds the allocator with everything else, so a respawned
  projectile gets the same id on resimulation — the id-stability contract of
  ``Rollback { id }`` (``src/lib.rs:40-55``) without host round trips.
  Device-minted ids start at ``DEVICE_ID_BASE`` so they never collide with
  host-side ``RollbackIdProvider`` ids (which count up from 0).
- All math is float32 add/mul/compare with a fixed operation order —
  bit-reproducible per platform, so speculative (vmapped) and serial
  executions agree bitwise. This is no longer a docstring claim: the
  framework machine-checks it at warmup
  (``spec_runner.attest_speculation_safety``) and ``tests/
  test_attestation.py`` runs this model through the speculative runner,
  including FIRE-press misprediction hits enabled by ``INPUT_SPEC.values``.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.ops import lifecycle, neighbor
from bevy_ggrs_tpu.schedule import InputSpec, PlayerInputs, Schedule
from bevy_ggrs_tpu.state import DEVICE_ID_BASE, HostWorld, TypeRegistry, WorldState

INPUT_UP = 1 << 0
INPUT_DOWN = 1 << 1
INPUT_LEFT = 1 << 2
INPUT_RIGHT = 1 << 3
INPUT_FIRE = 1 << 4

# 4 movement bits + FIRE (1<<4) -> value universe 0..31: without declaring
# it, speculation's structured tree could never enumerate a fire press
# (round-2 verdict: the default 0..15 tree made projectile speculation
# silently useless).
INPUT_SPEC = InputSpec(shape=(), dtype=jnp.uint8, values=tuple(range(32)))

KIND_TURRET = 0
KIND_PROJECTILE = 1

TURRET_SPEED = np.float32(0.06)
PROJ_SPEED = np.float32(0.25)
PROJ_TTL = 48  # frames a projectile lives
FIRE_COOLDOWN = 6  # frames between shots per player
HIT_RADIUS = np.float32(0.35)
ARENA_HALF = np.float32(4.0)

MAX_PLAYERS = 8
# Device-minted rollback ids live above every host-minted id (canonical
# boundary: state.DEVICE_ID_BASE, enforced by the host-side allocators).


def make_registry() -> TypeRegistry:
    reg = TypeRegistry()
    reg.register_component("position", shape=(2,), dtype=jnp.float32)
    reg.register_component("velocity", shape=(2,), dtype=jnp.float32)
    # Facing direction a fired projectile inherits; updated by movement.
    reg.register_component("aim", shape=(2,), dtype=jnp.float32)
    reg.register_component("kind", shape=(), dtype=jnp.int32, default=KIND_TURRET)
    reg.register_component("owner", shape=(), dtype=jnp.int32, default=-1)
    reg.register_component("ttl", shape=(), dtype=jnp.int32, default=0)
    reg.register_resource("frame_count", jnp.uint32(0))
    # The in-step rollback-id allocator (see module docstring).
    reg.register_resource("next_rollback_id", jnp.int32(DEVICE_ID_BASE))
    reg.register_resource("fire_cooldown", np.zeros((MAX_PLAYERS,), np.int32))
    reg.register_resource("score", np.zeros((MAX_PLAYERS,), np.int32))
    return reg


def make_world(
    num_players: int, capacity: int = 64, registry: Optional[TypeRegistry] = None
) -> HostWorld:
    """Turrets on a circle; all remaining capacity is projectile headroom."""
    if not 1 <= num_players <= MAX_PLAYERS:
        raise ValueError(f"num_players must be 1..{MAX_PLAYERS}")
    world = HostWorld(registry or make_registry(), capacity)
    r = float(ARENA_HALF) * 0.5
    for handle in range(num_players):
        ang = 2.0 * np.pi * handle / num_players
        world.spawn(
            {
                "position": np.array(
                    [r * np.cos(ang), r * np.sin(ang)], dtype=np.float32
                ),
                "velocity": np.zeros(2, np.float32),
                "aim": np.array([1.0, 0.0], np.float32),
                "kind": KIND_TURRET,
                "owner": handle,
                "ttl": 0,
            },
            rollback_id=handle,
        )
    return world


# ---------------------------------------------------------------------------
# Systems
# ---------------------------------------------------------------------------


def _input_dirs(inputs: PlayerInputs) -> jnp.ndarray:
    """[P, 2] move/aim direction per player from the bitmask."""
    bits = inputs.bits.astype(jnp.uint32)
    dx = (
        ((bits & INPUT_RIGHT) != 0).astype(jnp.float32)
        - ((bits & INPUT_LEFT) != 0).astype(jnp.float32)
    )
    dy = (
        ((bits & INPUT_UP) != 0).astype(jnp.float32)
        - ((bits & INPUT_DOWN) != 0).astype(jnp.float32)
    )
    return jnp.stack([dx, dy], axis=1)


def move_turret_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """Turrets translate by their player's direction keys and re-aim when a
    direction is held (box_game movement flattened to 2D, ``box_game.rs:
    154-203``)."""
    pos = state.components["position"]
    aim = state.components["aim"]
    kind = state.components["kind"]
    owner = state.components["owner"]

    dirs = _input_dirs(inputs)  # [P, 2]
    safe = jnp.clip(owner, 0, inputs.num_players - 1)
    d = dirs[safe]  # [cap, 2]

    is_turret = (
        state.alive
        & state.present["position"]
        & (kind == KIND_TURRET)
        & (owner >= 0)
    )
    sel = is_turret[:, None]
    new_pos = jnp.clip(pos + d * TURRET_SPEED, -ARENA_HALF, ARENA_HALF)
    moved = jnp.any(d != 0.0, axis=1, keepdims=True)
    new_aim = jnp.where(moved, d, aim)
    return state.replace(
        components={
            **state.components,
            "position": jnp.where(sel, new_pos, pos),
            "aim": jnp.where(sel, new_aim, aim),
        }
    )


def fire_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """Spawn one projectile per firing player — entity creation INSIDE the
    jitted step (the capability ``world_snapshot.rs:140-151`` restores
    across rollbacks).

    Claim rule (deterministic, shape-static): firing players ranked by
    handle take free slots in ascending slot order; when fewer free slots
    than firers remain, the highest-ranked firers' shots fizzle (the
    shared claim, :func:`bevy_ggrs_tpu.ops.lifecycle.claim_rows`, drops
    them). A fizzled shot still restarts its player's cooldown.
    """
    num_players = inputs.num_players
    bits = inputs.bits.astype(jnp.uint32)
    cooldown = state.resources["fire_cooldown"]

    # Which players fire this frame: FIRE held, cooldown elapsed, and their
    # turret alive (dead turrets can't shoot; turrets are immortal here but
    # the mask keeps the rule total).
    kind = state.components["kind"]
    owner = state.components["owner"]
    is_turret = state.alive & (kind == KIND_TURRET) & (owner >= 0)
    # Per-player turret slot: argmax of the one-hot (owner==p & turret).
    p_range = jnp.arange(num_players)
    turret_one_hot = is_turret[None, :] & (owner[None, :] == p_range[:, None])
    turret_slot = jnp.argmax(turret_one_hot, axis=1)  # [P]
    has_turret = jnp.any(turret_one_hot, axis=1)

    firing = (
        ((bits & INPUT_FIRE) != 0)
        & (cooldown[:num_players] <= 0)
        & has_turret
    )  # [P]

    # Rank firers (0-based among firing players, by handle order) and match
    # them to free slots in ascending slot order: the shared claim
    # (``ops/lifecycle.py``), each leaf given as a table by player.
    claim = lifecycle.claim_rows(state.alive, firing)

    next_id = state.resources["next_rollback_id"]
    tpos = state.components["position"][turret_slot]  # [P, 2]
    taim = state.components["aim"][turret_slot]  # [P, 2]
    # Normalize aim so diagonal shots aren't faster (fixed op order).
    norm = jnp.sqrt(jnp.sum(taim * taim, axis=1, keepdims=True))
    aim_unit = taim / jnp.maximum(norm, jnp.float32(1e-6))

    alive = claim.put(state.alive, True)
    rollback_id = claim.put(state.rollback_id, next_id + claim.rank)
    comps = dict(state.components)
    pres = dict(state.present)
    born = {
        "position": tpos,
        "velocity": aim_unit * PROJ_SPEED,
        "aim": aim_unit,
        "kind": KIND_PROJECTILE,
        "owner": p_range,
        "ttl": PROJ_TTL,
    }
    # Mark present ONLY the components written here: a user registry may
    # carry extra components, and flagging them present would expose the
    # slot's previous occupant's stale values to systems and the checksum.
    for name, values in born.items():
        comps[name] = claim.put(comps[name], values)
        pres[name] = claim.put(pres[name], True)

    # Every firing player restarts their cooldown — a fizzled (capacity-
    # dropped) shot still counts as having pulled the trigger.
    cd_now = jnp.where(
        firing, jnp.int32(FIRE_COOLDOWN), cooldown[:num_players]
    )
    cooldown = cooldown.at[:num_players].set(cd_now)

    return state.replace(
        alive=alive,
        rollback_id=rollback_id,
        components=comps,
        present=pres,
        resources={
            **state.resources,
            "next_rollback_id": next_id + claim.placed,
            "fire_cooldown": cooldown,
        },
    )


def _hit_accumulate(dx, dy, d2, row, col):
    """Projectile-row vs turret-col hit indicator. Every factor is a 0/1
    f32, so the candidate-axis sums are exact integers — dense and grid
    modes agree BITWISE on the resulting hit booleans (unlike float force
    sums, summation order cannot matter)."""
    del dx, dy
    return (
        row["is_proj"]
        * col["is_turret"]
        * (row["owner"] != col["owner"]).astype(jnp.float32)
        * (d2 < HIT_RADIUS * HIT_RADIUS).astype(jnp.float32),
    )


def _hit_combine(sums, row):
    return (sums[0] * row["is_proj"],)


HIT_PAIR_KERNEL = neighbor.PairKernel(
    radius=float(HIT_RADIUS),
    out_dim=1,
    n_terms=1,
    accumulate=_hit_accumulate,
    combine=_hit_combine,
    row_feats=("owner", "is_proj"),
    col_feats=("owner", "is_turret"),
)


def projectile_system(
    state: WorldState, inputs: PlayerInputs, *, mode: Optional[str] = None
) -> WorldState:
    """Fly, age, collide, expire — entity DESTRUCTION inside the jitted step
    (the despawn side of ``world_snapshot.rs:190-193``).

    A projectile despawns when its ttl runs out, it leaves the arena, or it
    passes within ``HIT_RADIUS`` of an opposing turret (which scores its
    owner a point).

    The hit test runs through :func:`bevy_ggrs_tpu.ops.neighbor.interact`
    (``mode`` as in boids ``make_schedule``): the dense path reproduces
    the original [cap, cap] broadcast bitwise, and because the interaction
    terms are pure 0/1 indicators the grid path's hit booleans are bitwise
    identical to dense too — the model's despawn/respawn machinery is
    mode-invariant, which ``tests/test_neighbor.py`` checks step-for-step.
    """
    del inputs
    pos = state.components["position"]
    vel = state.components["velocity"]
    kind = state.components["kind"]
    owner = state.components["owner"]
    ttl = state.components["ttl"]

    is_proj = state.alive & (kind == KIND_PROJECTILE)
    is_turret = state.alive & (kind == KIND_TURRET) & (owner >= 0)

    new_pos = jnp.where(is_proj[:, None], pos + vel, pos)
    new_ttl = jnp.where(is_proj, ttl - 1, ttl)

    # Pairwise projectile-vs-turret hits on the moved positions.
    hit_count = neighbor.interact(
        new_pos,
        state.alive,
        HIT_PAIR_KERNEL,
        feats={
            "owner": owner.astype(jnp.float32),
            "is_proj": is_proj.astype(jnp.float32),
            "is_turret": is_turret.astype(jnp.float32),
        },
        mode=mode,
        world_half=float(ARENA_HALF),
    )[:, 0]
    proj_hit = hit_count > jnp.float32(0.0)

    # Score: one point per hit projectile to its owner (a projectile grazing
    # two turrets in the same frame still scores once).
    score = state.resources["score"]
    safe_owner = jnp.clip(owner, 0, MAX_PLAYERS - 1)
    score = score.at[safe_owner].add(proj_hit.astype(jnp.int32))

    out = jnp.any(jnp.abs(new_pos) > ARENA_HALF, axis=1)
    gone = is_proj & ((new_ttl <= 0) | out | proj_hit)

    alive = state.alive & ~gone
    rollback_id = jnp.where(gone, -1, state.rollback_id)
    pres = {n: p & ~gone for n, p in state.present.items()}
    return state.replace(
        alive=alive,
        rollback_id=rollback_id,
        components={**state.components, "position": new_pos, "ttl": new_ttl},
        present=pres,
        resources={**state.resources, "score": score},
    )


def cooldown_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    del inputs
    cd = state.resources["fire_cooldown"]
    return state.replace(
        resources={
            **state.resources,
            "fire_cooldown": jnp.maximum(cd - 1, 0),
        }
    )


def increase_frame_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    del inputs
    return state.replace(
        resources={
            **state.resources,
            "frame_count": state.resources["frame_count"] + jnp.uint32(1),
        }
    )


def make_schedule(mode: Optional[str] = None) -> Schedule:
    """``mode``: interaction mode for the hit test ("dense" | "grid" |
    "auto"; ``None`` = legacy dense unless ``GGRS_FORCE_MODE`` or the
    SessionBuilder default overrides — see
    :func:`bevy_ggrs_tpu.ops.neighbor.resolve_mode`)."""

    def projectiles(state: WorldState, inputs: PlayerInputs) -> WorldState:
        return projectile_system(state, inputs, mode=mode)

    return Schedule([
        move_turret_system,
        fire_system,
        projectiles,
        cooldown_system,
        increase_frame_system,
    ])
