"""Particles: a world whose entities are born and die every frame.

Upstream's stress test of snapshot and restore under entity churn
(gschup/bevy_ggrs ``examples/stress_tests/particles.rs``; command-line
defaults ``--rate 100 --fps 60 --max-prediction 8 --check-distance 2``): a
SyncTest session in which every frame spawns ``rate`` particles, each with a
velocity and a time-to-live drawn at random, gravity integrates them, and a
particle is despawned when its time-to-live runs out. It works the one part
of the reference's restore contract that fixed-population titles never
touch, at a deployment's size: find-or-spawn by rollback id and despawn of
live entities absent from the snapshot (``/root/reference/src/
world_snapshot.rs:140-151,190-193``) with ids minted mid-game
(``/root/reference/src/lib.rs:59-75``). ``models/projectiles.py`` has the
same lifecycle at one birth a player; here the births are a hundred a
frame and a third of the rows turn over every second.

The rules (``benchmark/reference/particles_np.py`` restates them in NumPy):

- components ``position`` f32[2], ``velocity`` f32[2], ``ttl`` i32;
  resources ``frame_count`` u32, ``next_rollback_id`` i32 (the in-step
  allocator of ``projectiles``: rollback-registered, ids from
  ``DEVICE_ID_BASE``), ``emitter_position`` f32[P, 2], ``match_seed`` u32,
  ``spawn_fizzled`` i32;
- each frame, in this order: (1) the emitters move; (2) ``rate`` particles
  are born; (3) every live particle integrates, ``velocity += gravity *
  dt`` then ``position += velocity * dt`` with ``dt`` = 1/60; (4) ``ttl -=
  1`` and a particle whose ``ttl`` reaches 0 is despawned (row freed, id
  -1, ``present`` cleared); (5) ``frame_count += 1``;
- birth ``k`` of a frame (k = 0 .. rate - 1) gets the id
  ``next_rollback_id + k``, belongs to emitter ``k mod P``, is born at that
  emitter's position, and draws its ``ttl`` (uniform over ``TTL_MIN ..
  TTL_MIN + TTL_SPAN - 1`` = 60 .. 89 frames) and its velocity (uniform in
  the square ``+-SPEED``) from a counter-based integer hash of
  (``match_seed``, id): :func:`draws`;
- births take free rows in ascending row order, rank for rank
  (``ops/lifecycle.py`` ``claim_rows``, shared with ``projectiles``: dense
  selects, no scatter). A birth that finds no free row fizzles
  and is counted in ``spawn_fizzled``; ``CAPACITY`` = 9,216 = 72 x 128 rows
  holds the largest population the rules allow (100 x 89 = 8,900), so the
  count stays 0.

Two departures from upstream, both on purpose:

- upstream keeps a stateful random generator as a rollback resource. A hash
  of the id rewinds with the allocator (a particle re-born on
  resimulation gets the same id, hence the same draws), gives the same
  distribution, needs no sequential draw under ``vmap``, and is bit-exact
  in NumPy (integers, and floats made from 24-bit integers by exact
  operations);
- upstream's test reads no input. Here each of the P players steers an
  emitter with the direction keys (``projectiles``' turret rule: 0.06 a
  frame, clipped to +-4), so that a slot's speculative branches are
  different worlds and a SyncTest replay exercises the input path. The
  births, the deaths and the ids do not depend on input; where a particle
  is born does.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.ops import lifecycle
from bevy_ggrs_tpu.schedule import InputSpec, PlayerInputs, Schedule
from bevy_ggrs_tpu.state import DEVICE_ID_BASE, HostWorld, TypeRegistry, WorldState

INPUT_UP = 1 << 0
INPUT_DOWN = 1 << 1
INPUT_LEFT = 1 << 2
INPUT_RIGHT = 1 << 3

# Four direction bits -> the default value universe 0..15.
INPUT_SPEC = InputSpec(shape=(), dtype=jnp.uint8)

RATE = 100  # births a frame (upstream's --rate)
CAPACITY = 9216  # 72 x 128 rows: above 100 births x 89 frames
TTL_MIN = 60  # frames
TTL_SPAN = 30  # ttl uniform over TTL_MIN .. TTL_MIN + TTL_SPAN - 1
DT = np.float32(1.0 / 60.0)
GRAVITY = np.float32(-9.8)  # on y, a second squared
# What a frame adds to a velocity: one float32 constant, so that no machine
# has a product to contract or to round its own way.
GRAVITY_DT = np.asarray([0.0, GRAVITY * DT], np.float32)
SPEED = np.float32(2.0)  # birth velocity uniform in [-SPEED, SPEED)^2
EMITTER_SPEED = np.float32(0.06)  # a frame, as projectiles' turrets
ARENA_HALF = np.float32(4.0)
EMITTER_RING = 2.0  # the emitters spawn on a circle of this radius

MAX_PLAYERS = 8

_GOLDEN = np.uint32(0x9E3779B1)
# One stream of the hash a draw: ttl, velocity x, velocity y.
_STREAMS = tuple(
    np.uint32((n * 0x7F4A7C15) & 0xFFFFFFFF) for n in (1, 2, 3)
)


def _fmix(h):
    """murmur3's 32-bit finaliser: a bijection of uint32 that avalanches."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def draws(match_seed, ids):
    """What the particle with rollback id ``ids`` (int32, any shape) draws
    in the match seeded ``match_seed`` (uint32): ``(ttl int32[...],
    velocity float32[..., 2])``. uint32 arithmetic (wrapping) and exact
    float32 operations on 24-bit integers only: the plain reference's NumPy
    gives the same bits."""
    base = _fmix(match_seed ^ (ids.astype(jnp.uint32) * _GOLDEN))
    h_ttl, h_vx, h_vy = (_fmix(base + stream) for stream in _STREAMS)
    # The high 16 bits scaled into 0 .. TTL_SPAN - 1 (no integer division).
    ttl = TTL_MIN + (
        ((h_ttl >> np.uint32(16)) * np.uint32(TTL_SPAN)) >> np.uint32(16)
    ).astype(jnp.int32)

    def unit(h):  # 24 bits -> [-SPEED, SPEED), every step exact
        u = (h >> np.uint32(8)).astype(jnp.float32) * np.float32(2.0 ** -24)
        return u * (np.float32(2.0) * SPEED) - SPEED

    return ttl, jnp.stack([unit(h_vx), unit(h_vy)], axis=-1)


def make_registry(num_players: int) -> TypeRegistry:
    reg = TypeRegistry()
    reg.register_component("position", shape=(2,), dtype=jnp.float32)
    reg.register_component("velocity", shape=(2,), dtype=jnp.float32)
    reg.register_component("ttl", shape=(), dtype=jnp.int32, default=0)
    reg.register_resource("frame_count", jnp.uint32(0))
    # The in-step rollback-id allocator (see models/projectiles.py).
    reg.register_resource("next_rollback_id", jnp.int32(DEVICE_ID_BASE))
    reg.register_resource(
        "emitter_position", np.zeros((num_players, 2), np.float32)
    )
    reg.register_resource("match_seed", jnp.uint32(0))
    reg.register_resource("spawn_fizzled", jnp.int32(0))
    return reg


def emitter_spawn(num_players: int) -> np.ndarray:
    """float32[P, 2]: the emitters' spawn points, on a circle."""
    ang = 2.0 * np.pi * np.arange(num_players) / num_players
    return np.stack(
        [EMITTER_RING * np.cos(ang), EMITTER_RING * np.sin(ang)], axis=1
    ).astype(np.float32)


def make_world(
    num_players: int, capacity: int = CAPACITY, match_seed: int = 0
) -> HostWorld:
    """An empty world (every row is headroom for particles) with the
    emitters on their spawn points. ``match_seed`` is what parts one match's
    draws from another's (:func:`with_match_seed` for a committed state)."""
    if not 1 <= num_players <= MAX_PLAYERS:
        raise ValueError(f"num_players must be 1..{MAX_PLAYERS}")
    world = HostWorld(make_registry(num_players), capacity)
    world.set_resource("emitter_position", emitter_spawn(num_players))
    world.set_resource("match_seed", np.uint32(match_seed))
    return world


def with_match_seed(state: WorldState, match_seed: int) -> WorldState:
    """``state`` (a committed spawn world) for another match: the same
    leaves but the seed's."""
    return state.replace(resources={
        **state.resources, "match_seed": jnp.uint32(match_seed),
    })


# ---------------------------------------------------------------------------
# Systems
# ---------------------------------------------------------------------------


def move_emitter_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """Each player's emitter translates by the direction keys."""
    bits = inputs.bits.astype(jnp.uint32)
    held = lambda mask: ((bits & mask) != 0).astype(jnp.float32)  # noqa: E731
    dirs = jnp.stack(
        [held(INPUT_RIGHT) - held(INPUT_LEFT), held(INPUT_UP) - held(INPUT_DOWN)],
        axis=1,
    )  # [P, 2]
    emitter = state.resources["emitter_position"]
    return state.replace(resources={
        **state.resources,
        "emitter_position": jnp.clip(
            emitter + dirs * EMITTER_SPEED, -ARENA_HALF, ARENA_HALF
        ),
    })


def spawn_system(
    state: WorldState, inputs: PlayerInputs, *, rate: int = RATE
) -> WorldState:
    """``rate`` births a frame: entity creation inside the jitted step.
    Every value a birth writes is a function of its ordinal among the
    frame's births, which is what keeps the claim free of the births' count
    (``ops/lifecycle.py``)."""
    del inputs
    claim = lifecycle.claim_rows(state.alive, jnp.ones((rate,), jnp.bool_))
    next_id = state.resources["next_rollback_id"]
    seed = state.resources["match_seed"]
    emitter = state.resources["emitter_position"]
    players = emitter.shape[0]

    def born_at(ordinal):
        """The position of emitter ``ordinal mod P``, by a select over the
        P emitters (an index would batch into a gather; ``schedule.py``)."""
        owner = jnp.remainder(ordinal, players)[..., None]
        out = jnp.broadcast_to(emitter[players - 1], ordinal.shape + (2,))
        for p in range(players - 2, -1, -1):
            out = jnp.where(owner == p, emitter[p], out)
        return out

    born = {
        "position": born_at,
        "velocity": lambda o: draws(seed, next_id + o)[1],
        "ttl": lambda o: draws(seed, next_id + o)[0],
    }
    comps = dict(state.components)
    pres = dict(state.present)
    # Present ONLY what is written here (see projectiles.fire_system).
    for name, values in born.items():
        comps[name] = claim.put(comps[name], values)
        pres[name] = claim.put(pres[name], True)
    return state.replace(
        alive=claim.put(state.alive, True),
        rollback_id=claim.put(state.rollback_id, lambda o: next_id + o),
        components=comps,
        present=pres,
        resources={
            **state.resources,
            "next_rollback_id": next_id + claim.placed,
            "spawn_fizzled": (
                state.resources["spawn_fizzled"] + (rate - claim.placed)
            ),
        },
    )


def integrate_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """Gravity on every live particle; a free row keeps its last bits."""
    del inputs
    pos = state.components["position"]
    vel = state.components["velocity"]
    live = state.alive[:, None]
    new_vel = vel + GRAVITY_DT
    return state.replace(components={
        **state.components,
        "velocity": jnp.where(live, new_vel, vel),
        "position": jnp.where(live, pos + new_vel * DT, pos),
    })


def expire_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    """``ttl -= 1``; a particle at 0 is despawned: entity destruction inside
    the jitted step (the despawn side of ``world_snapshot.rs:190-193``)."""
    del inputs
    ttl = state.components["ttl"]
    new_ttl = jnp.where(state.alive, ttl - 1, ttl)
    gone = state.alive & (new_ttl <= 0)
    return state.replace(
        alive=state.alive & ~gone,
        rollback_id=jnp.where(gone, -1, state.rollback_id),
        components={**state.components, "ttl": new_ttl},
        present={n: p & ~gone for n, p in state.present.items()},
    )


def increase_frame_system(state: WorldState, inputs: PlayerInputs) -> WorldState:
    del inputs
    return state.replace(resources={
        **state.resources,
        "frame_count": state.resources["frame_count"] + jnp.uint32(1),
    })


def make_schedule(rate: int = RATE) -> Schedule:
    """The five systems in the rules' order; ``rate`` births a frame."""

    def spawn(state: WorldState, inputs: PlayerInputs) -> WorldState:
        return spawn_system(state, inputs, rate=rate)

    return Schedule([
        move_emitter_system,
        spawn,
        integrate_system,
        expire_system,
        increase_frame_system,
    ])
