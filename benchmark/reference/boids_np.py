"""Boids flocking in plain float32 NumPy: the benchmark's yardstick for the
title ``boids``.

One simulated frame of the flocking title, written from the rules'
definition (Reynolds' separation / alignment / cohesion over two radii,
player-steered leaders, a speed clamp, a toroidal world, a frame counter).
The program's ``models/boids.py`` (``_flock_step``, ``_pairwise_forces``,
``pairwise_force_rows``) is the description it follows, and the constants
and the spawn spiral are that file's, copied: no upstream title fixes them.
It imports nothing of the program and owns its constants: a later PR may
change the program, never this file.

Departures from the program's step, each on purpose:

- every row is a live boid (the deployments spawn ``num_entities`` boids in
  a world of that capacity), so the program's ``alive`` / ``present`` masks
  have no counterpart here;
- sums over neighbours are NumPy's (pairwise, float32), not the program's
  order: float association differs, so the two agree to rounding and not
  bit for bit, and the limits of a configuration are set from readings;
- ``1 / sqrt(d2)`` where the program takes ``rsqrt`` with a clamp at 1e-12
  that no counted pair reaches (a pair closer than 1e-5 is "self").

A flocking step is not continuous: each pair passes two radius tests
(``d2 < r**2``), and a position one rounding away flips one. ``undecided``
marks the boids with such a pair, from float64 distances of its own: the
``correct`` decision leaves them out of the float comparison and counts
them.

Vectorised over matches: ``position`` and ``velocity`` are ``float32[M, N,
2]`` (M matches, N boids), ``bits`` is ``uint8[M, P]`` (UP=1, DOWN=2, LEFT=4,
RIGHT=8); boid ``h < P`` is the leader that player ``h`` steers.

``DEFAULT_ENTITIES`` is a small world on purpose: the manifest's tests call
``replay`` with no size (16 matches x 150 frames, three times a
configuration) and must take seconds; the benchmark's loop passes the
configuration's own ``num_entities``.

``precision="bfloat16"`` is the control of the ``correct`` decision: the same
rules with position and velocity rounded through bfloat16 after every
frame, the next precision below the float32 the configurations state.
"""

from __future__ import annotations

import math

import numpy as np

INPUT_UP, INPUT_DOWN, INPUT_LEFT, INPUT_RIGHT = 1, 2, 4, 8

DEFAULT_ENTITIES = 64

NEIGHBOR_RADIUS = 1.0
SEPARATION_RADIUS = 0.35
NEIGHBOR_R2 = np.float32(NEIGHBOR_RADIUS) ** 2
SEPARATION_R2 = np.float32(SEPARATION_RADIUS) ** 2
SELF_D2 = np.float32(1e-10)          # closer than this is the boid itself
W_SEPARATION = np.float32(0.08)
W_ALIGNMENT = np.float32(0.05)
W_COHESION = np.float32(0.03)
LEADER_STEER = np.float32(0.02)
MAX_SPEED = np.float32(0.08)
MIN_SPEED = np.float32(0.02)
WORLD_HALF = np.float32(8.0)
SPAWN_SEED = 0


def round_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16 -> float32, on the host."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def spawn(num_matches: int, num_players: int,
          num_entities: int = DEFAULT_ENTITIES):
    """Boids on a golden-angle spiral (boid i at radius 0.15 sqrt(i + 1)),
    velocities uniform in +-0.03 from a fixed seed; the same in every
    match. ``num_players`` only says which boids are leaders (``step``)."""
    del num_players
    p = np.zeros((num_entities, 2), np.float32)
    for i in range(num_entities):
        ang = i * 2.399963
        rad = 0.15 * math.sqrt(i + 1)
        p[i] = [rad * math.cos(ang), rad * math.sin(ang)]
    v = np.random.RandomState(SPAWN_SEED).uniform(
        -0.03, 0.03, size=(num_entities, 2)).astype(np.float32)
    shape = (num_matches, num_entities, 2)
    return (np.broadcast_to(p, shape).copy(), np.broadcast_to(v, shape).copy())


def _pair_d2(position, dtype):
    p = np.asarray(position, dtype)
    diff = p[..., :, None, :] - p[..., None, :, :]           # [.., N, N, 2]
    return diff, diff[..., 0] * diff[..., 0] + diff[..., 1] * diff[..., 1]


def forces(position, velocity):
    """Separation (unit vectors away from each boid nearer than the
    separation radius), alignment (towards the neighbourhood's mean
    velocity) and cohesion (towards its centroid), weighted and summed."""
    p = np.asarray(position, np.float32)
    v = np.asarray(velocity, np.float32)
    diff, d2 = _pair_d2(p, np.float32)
    neigh = ((d2 < NEIGHBOR_R2) & ~(d2 < SELF_D2)).astype(np.float32)
    close = neigh * (d2 < SEPARATION_R2).astype(np.float32)
    inv_d = (np.float32(1.0)
             / np.sqrt(np.maximum(d2, np.float32(1e-12)))).astype(np.float32)
    sep = (diff * (inv_d * close)[..., None]).sum(axis=-2, dtype=np.float32)
    count = neigh.sum(axis=-1, keepdims=True, dtype=np.float32)
    safe = np.maximum(count, np.float32(1.0))
    has = count > 0
    mean_v = np.matmul(neigh, v).astype(np.float32) / safe
    mean_p = np.matmul(neigh, p).astype(np.float32) / safe
    align = np.where(has, mean_v - v, np.float32(0.0))
    coh = np.where(has, mean_p - p, np.float32(0.0))
    return (W_SEPARATION * sep + W_ALIGNMENT * align
            + W_COHESION * coh).astype(np.float32)


def step(position, velocity, bits, precision: str = "float32"):
    """One frame for every match; returns new (position, velocity)."""
    p = np.asarray(position, np.float32)
    v = np.asarray(velocity, np.float32)
    inp = np.asarray(bits).astype(np.uint32)                 # [M, P]
    players = inp.shape[-1]
    force = forces(p, v)
    steer_x = (((inp & INPUT_RIGHT) != 0).astype(np.float32)
               - ((inp & INPUT_LEFT) != 0).astype(np.float32))
    steer_y = (((inp & INPUT_DOWN) != 0).astype(np.float32)
               - ((inp & INPUT_UP) != 0).astype(np.float32))
    force[..., :players, 0] += steer_x * LEADER_STEER
    force[..., :players, 1] += steer_y * LEADER_STEER

    nv = v + force
    speed = np.sqrt((nv * nv).sum(axis=-1, keepdims=True, dtype=np.float32))
    speed = np.maximum(speed, np.float32(1e-6))
    nv = (nv * (np.clip(speed, MIN_SPEED, MAX_SPEED) / speed)).astype(
        np.float32)
    npos = p + nv
    two = np.float32(2.0) * WORLD_HALF
    npos = np.where(npos > WORLD_HALF, npos - two, npos)
    npos = np.where(npos < -WORLD_HALF, npos + two, npos).astype(np.float32)
    if precision == "bfloat16":
        npos, nv = round_bfloat16(npos), round_bfloat16(nv)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return npos, nv


def undecided(position, margin: float) -> np.ndarray:
    """bool[..., N]: the boids one of whose pairs lies, by distances taken
    in float64, within the relative ``margin`` of the neighbour radius or
    of the separation radius. For those a float32 step may put the pair on
    either side of the test, and no reference can say which is right."""
    _, d2 = _pair_d2(position, np.float64)
    d = np.sqrt(d2)
    near = np.zeros(d.shape, bool)
    for r2 in (NEIGHBOR_R2, SEPARATION_R2):
        r = math.sqrt(float(r2))
        near |= np.abs(d - r) <= margin * r
    return near.any(axis=-1)


def torus_gap(a, b) -> np.ndarray:
    """|a - b| of positions on the wrapped world, in float64: two steps that
    land a rounding apart on either side of the edge are that rounding
    apart, not a world's width."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return np.minimum(d, 2.0 * float(WORLD_HALF) - d)


def replay(bits, frames, precision: str = "float32",
           num_entities: int = DEFAULT_ENTITIES):
    """State of every match after its own number of frames, from spawn.

    ``bits`` is ``uint8[M, P, F]`` (input of match m, player p, frame f),
    ``frames`` is ``int[M]`` with ``frames[m] <= F``: match m applies the
    inputs of frames ``0 .. frames[m]-1``. Returns (position, velocity,
    frame_count). Two float32 machines part for good within some hundred
    frames of this (see ``undecided``): fit for comparing this file with
    itself at another precision, not with the program."""
    bits = np.asarray(bits, np.uint8)
    frames = np.asarray(frames, np.int64)
    m, players, f_max = bits.shape
    if frames.shape != (m,) or (frames.size and int(frames.max()) > f_max):
        raise ValueError("frames does not fit the input table")
    p, v = spawn(m, players, num_entities)
    for f in range(int(frames.max()) if frames.size else 0):
        live = (frames > f)[:, None, None]
        np_, nv = step(p, v, bits[:, :, f], precision)
        p = np.where(live, np_, p)
        v = np.where(live, nv, v)
    return p, v, frames.astype(np.uint32)
