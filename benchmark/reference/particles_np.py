"""The particle stress test in plain float32 NumPy: the benchmark's yardstick
for the title ``particles``.

Written from the rules (the program's ``models/particles.py`` states them in
its docstring; upstream's ``examples/stress_tests/particles.rs`` is where
they come from). It imports nothing of the program and owns its constants:
a later PR may change the program, never this file.

The rules, a frame, in this order:

1. each player's emitter moves by the direction keys (UP=1 DOWN=2 LEFT=4
   RIGHT=8: ``EMITTER_SPEED`` a frame a held axis, clipped to
   ``+-ARENA_HALF``);
2. ``rate`` particles are born. Birth ``k`` gets the id ``next_id + k``,
   is born at the position of emitter ``k mod P``, and draws its ``ttl``
   (uniform over 60..89) and its velocity (uniform in ``[-SPEED, SPEED)^2``)
   from an integer hash of (the match's seed, the id): ``draws``. A world
   holds ``capacity`` particles: births past it fizzle, in birth order from
   the last, are counted, and mint no id;
3. every live particle integrates: ``velocity += GRAVITY_DT``, ``position
   += velocity * DT``;
4. ``ttl -= 1``; a particle whose ``ttl`` reaches 0 dies;
5. ``frame_count += 1``.

**How the world is kept** (this file's own choice; the program keeps
entity rows and claims free ones): a particle lives 89 frames at most, so
the particles that can be alive after frame ``f`` were born in frames ``f -
88 .. f``, and a table ``[M, 89, rate]`` whose row ``f mod 89`` holds the
births of frame ``f`` holds them all, whatever row of its own world the
program gave each. Identity is the rollback id, and the comparison with the
program is by id. ``M`` matches are stepped at once.

``precision="bfloat16"`` is the control of the ``correct`` decision: the
same rules with position and velocity rounded through bfloat16 after every
frame, the next precision below the float32 the configuration states. The
lifecycle (ids, ttl, counts) is integers and does not change with it.
"""

from __future__ import annotations

import numpy as np

INPUT_UP, INPUT_DOWN, INPUT_LEFT, INPUT_RIGHT = 1, 2, 4, 8

RATE = 100
CAPACITY = 9216
TTL_MIN = 60
TTL_SPAN = 30
TTL_MAX = TTL_MIN + TTL_SPAN - 1                  # 89
DT = np.float32(1.0 / 60.0)
GRAVITY = np.float32(-9.8)
GRAVITY_DT = np.asarray([0.0, GRAVITY * DT], np.float32)
SPEED = np.float32(2.0)
EMITTER_SPEED = np.float32(0.06)
ARENA_HALF = np.float32(4.0)
EMITTER_RING = 2.0
ID_BASE = 1 << 20               # ids minted inside a step count up from here


def round_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16 -> float32, on the host."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def _fmix(h: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser on a uint32 array (wrapping)."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def draws(seed, ids):
    """(ttl int32[...], velocity float32[..., 2]) of the particles ``ids``
    (integers, any shape) in matches seeded ``seed`` (broadcast against
    ``ids``). uint32 arithmetic; the floats are 24-bit integers scaled by
    powers of two and shifted, every step exact in float32."""
    ids = np.asarray(ids).astype(np.uint32)
    seed = np.asarray(seed).astype(np.uint32)
    base = _fmix(seed ^ (ids * np.uint32(0x9E3779B1)))
    h_ttl, h_vx, h_vy = (
        _fmix(base + np.uint32((n * 0x7F4A7C15) & 0xFFFFFFFF))
        for n in (1, 2, 3))
    ttl = TTL_MIN + (((h_ttl >> np.uint32(16)) * np.uint32(TTL_SPAN))
                     >> np.uint32(16)).astype(np.int32)

    def unit(h):
        u = (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)
        return u * (np.float32(2.0) * SPEED) - SPEED

    return ttl, np.stack([unit(h_vx), unit(h_vy)], axis=-1)


def emitter_spawn(num_players: int) -> np.ndarray:
    """float32[P, 2]: the emitters' spawn points, on a circle."""
    ang = 2.0 * np.pi * np.arange(num_players) / num_players
    return np.stack([EMITTER_RING * np.cos(ang), EMITTER_RING * np.sin(ang)],
                    axis=1).astype(np.float32)


def spawn(seeds, num_players: int, rate: int = RATE) -> dict:
    """The empty worlds of ``len(seeds)`` matches, emitters on their spawn
    points. A world is a dict of arrays with a leading match axis."""
    seeds = np.asarray(seeds).astype(np.uint32)
    m = seeds.shape[0]
    table = (m, TTL_MAX, rate)
    return {
        "seed": seeds,
        "alive": np.zeros(table, bool),
        "id": np.full(table, -1, np.int32),
        "ttl": np.zeros(table, np.int32),
        "position": np.zeros(table + (2,), np.float32),
        "velocity": np.zeros(table + (2,), np.float32),
        "emitter": np.broadcast_to(emitter_spawn(num_players),
                                   (m, num_players, 2)).copy(),
        "next_id": np.full((m,), ID_BASE, np.int32),
        "fizzled": np.zeros((m,), np.int32),
        "frame_count": np.zeros((m,), np.uint32),
    }


def step(world: dict, bits, precision: str = "float32",
         capacity: int = CAPACITY) -> dict:
    """One frame of every match. ``bits`` is ``uint8[M, P]``."""
    inp = np.asarray(bits).astype(np.uint32)
    held = lambda mask: ((inp & mask) != 0).astype(np.float32)  # noqa: E731
    dirs = np.stack([held(INPUT_RIGHT) - held(INPUT_LEFT),
                     held(INPUT_UP) - held(INPUT_DOWN)], axis=-1)
    emitter = np.clip(world["emitter"] + dirs * EMITTER_SPEED,
                      -ARENA_HALF, ARENA_HALF).astype(np.float32)
    m, _, rate = world["alive"].shape
    players = emitter.shape[1]

    # Births: the row of this frame, which last held the births of 89 frames
    # ago, all dead by now.
    row = int(world["frame_count"][0]) % TTL_MAX
    if not np.all(world["frame_count"] == world["frame_count"][0]):
        raise ValueError("matches of one call share their frame")
    alive = world["alive"].copy()
    ids, ttl = world["id"].copy(), world["ttl"].copy()
    pos, vel = world["position"].copy(), world["velocity"].copy()
    if alive[:, row].any():
        raise AssertionError("a particle outlived TTL_MAX frames")
    room = capacity - alive.reshape(m, -1).sum(axis=1)
    placed = np.minimum(rate, room).astype(np.int32)            # [M]
    k = np.arange(rate)
    born = k[None, :] < placed[:, None]                         # [M, rate]
    new_id = world["next_id"][:, None] + k[None, :].astype(np.int32)
    new_ttl, new_vel = draws(world["seed"][:, None], new_id)
    alive[:, row] = born
    ids[:, row] = np.where(born, new_id, -1)
    ttl[:, row] = np.where(born, new_ttl, 0)
    vel[:, row] = new_vel
    pos[:, row] = emitter[:, k % players]

    # Integrate and age the living.
    live = alive[..., None]
    vel = np.where(live, vel + GRAVITY_DT, vel)
    pos = np.where(live, pos + vel * DT, pos)
    if precision == "bfloat16":
        pos, vel = round_bfloat16(pos), round_bfloat16(vel)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    ttl = np.where(alive, ttl - 1, ttl)
    gone = alive & (ttl <= 0)
    return {
        "seed": world["seed"],
        "alive": alive & ~gone,
        "id": np.where(gone, -1, ids),
        "ttl": ttl,
        "position": pos,
        "velocity": vel,
        "emitter": emitter,
        "next_id": world["next_id"] + placed,
        "fizzled": world["fizzled"] + (rate - placed),
        "frame_count": world["frame_count"] + np.uint32(1),
    }


def replay_worlds(bits, frames, seeds, precision: str = "float32",
                  rate: int = RATE, capacity: int = CAPACITY) -> dict:
    """The world of every match after its own number of frames, from spawn.

    ``bits`` is ``uint8[M, P, F]``, ``frames`` is ``int[M]`` with
    ``frames[m] <= F``, ``seeds`` is ``uint32[M]``. Matches are stepped
    together and each is kept as it stood after ``frames[m]`` frames."""
    bits = np.asarray(bits, np.uint8)
    frames = np.asarray(frames, np.int64)
    m, players, f_max = bits.shape
    if frames.shape != (m,) or (frames.size and int(frames.max()) > f_max):
        raise ValueError("frames does not fit the input table")
    world = spawn(seeds, players, rate)
    kept = {k: v.copy() for k, v in world.items()}
    for f in range(int(frames.max()) if frames.size else 0):
        world = step(world, bits[:, :, f], precision, capacity)
        done = frames == f + 1
        if done.any():
            for key, value in world.items():
                kept[key][done] = value[done]
    return kept


def replay(bits, frames, precision: str = "float32", rate: int = RATE,
           capacity: int = CAPACITY):
    """``replay_worlds`` with match ``m`` seeded ``m``, as the three arrays
    the manifest's tests compare between precisions: (position, velocity,
    frame_count), the tables flattened to ``[M, 89 * rate, 2]`` and zero
    where no particle lives (the lifecycle is the same at every precision,
    so the same entries are live)."""
    bits = np.asarray(bits, np.uint8)
    world = replay_worlds(bits, frames, np.arange(bits.shape[0]), precision,
                          rate, capacity)
    live = world["alive"].reshape(bits.shape[0], -1, 1)
    flat = lambda x: np.where(live, x.reshape(live.shape[0], -1, 2), 0.0)  # noqa: E731
    return (flat(world["position"]).astype(np.float32),
            flat(world["velocity"]).astype(np.float32), world["frame_count"])


def by_id(world: dict, match: int):
    """(ids, ttl, position, velocity) of one match's live particles, sorted
    by id: what the comparison with the program matches on."""
    live = world["alive"][match].reshape(-1)
    ids = world["id"][match].reshape(-1)[live]
    order = np.argsort(ids, kind="stable")
    pick = lambda x, *tail: x[match].reshape((-1,) + tail)[live][order]  # noqa: E731
    return (ids[order], pick(world["ttl"]), pick(world["position"], 2),
            pick(world["velocity"], 2))
