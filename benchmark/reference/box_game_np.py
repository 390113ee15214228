"""box_game in plain float32 NumPy: the benchmark's own yardstick.

One simulated frame of upstream's ``examples/box_game/box_game.rs``
(``move_cube_system`` then ``increase_frame_system``), written from the
upstream example's rules, in the order of operations of the program's
``models/box_game.py:step_np`` so that an exact float32 machine reproduces
it bit for bit. It imports nothing of the program and owns its constants:
a later PR may change the program, never this file.

Vectorised over matches: ``translation`` and ``velocity`` are
``float32[M, P, 3]`` (M matches, P player cubes), ``bits`` is ``uint8[M, P]``
(UP=1, DOWN=2, LEFT=4, RIGHT=8). Every cube is a live player cube, which is
what both deployments of the benchmark spawn.

``precision="bfloat16"`` is the control of the ``correct`` decision: the same
rules with translation and velocity rounded through bfloat16 after every
frame — the next precision below the float32 that the configurations state.
"""

from __future__ import annotations

import math

import numpy as np

INPUT_UP, INPUT_DOWN, INPUT_LEFT, INPUT_RIGHT = 1, 2, 4, 8

MOVEMENT_SPEED = np.float32(0.005)
MAX_SPEED = np.float32(0.05)
FRICTION = np.float32(0.9)
PLANE_SIZE = 5.0
CUBE_SIZE = 0.2
HALF = np.float32((PLANE_SIZE - CUBE_SIZE) * 0.5)


def round_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16 -> float32, on the host."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def spawn(num_matches: int, num_players: int):
    """Players on a circle of radius PLANE_SIZE/4 at height CUBE_SIZE/2
    (``setup_system``), at rest."""
    r = PLANE_SIZE / 4.0
    t = np.zeros((num_players, 3), np.float32)
    for handle in range(num_players):
        rot = handle / num_players * 2.0 * math.pi
        t[handle] = [r * math.cos(rot), CUBE_SIZE / 2.0, r * math.sin(rot)]
    translation = np.broadcast_to(t, (num_matches, num_players, 3)).copy()
    return translation, np.zeros_like(translation)


def step(translation, velocity, bits, precision: str = "float32"):
    """One frame for every match; returns new (translation, velocity)."""
    t = np.asarray(translation, np.float32)
    v = np.asarray(velocity, np.float32)
    inp = np.asarray(bits).astype(np.uint32)
    up = (inp & INPUT_UP) != 0
    down = (inp & INPUT_DOWN) != 0
    left = (inp & INPUT_LEFT) != 0
    right = (inp & INPUT_RIGHT) != 0

    vx, vy, vz = v[..., 0], v[..., 1], v[..., 2]
    vz = np.where(up & ~down, vz - MOVEMENT_SPEED, vz)
    vz = np.where(down & ~up, vz + MOVEMENT_SPEED, vz)
    vz = np.where(~up & ~down, vz * FRICTION, vz)
    vx = np.where(left & ~right, vx - MOVEMENT_SPEED, vx)
    vx = np.where(right & ~left, vx + MOVEMENT_SPEED, vx)
    vx = np.where(~left & ~right, vx * FRICTION, vx)
    vy = vy * FRICTION

    mag = np.sqrt(vx * vx + vy * vy + vz * vz).astype(np.float32)
    over = mag > MAX_SPEED
    factor = np.where(over, MAX_SPEED / np.where(over, mag, np.float32(1.0)),
                      np.float32(1.0)).astype(np.float32)
    vx, vy, vz = vx * factor, vy * factor, vz * factor

    tx = np.minimum(np.maximum(t[..., 0] + vx, -HALF), HALF)
    ty = t[..., 1] + vy
    tz = np.minimum(np.maximum(t[..., 2] + vz, -HALF), HALF)
    new_t = np.stack([tx, ty, tz], axis=-1).astype(np.float32)
    new_v = np.stack([vx, vy, vz], axis=-1).astype(np.float32)
    if precision == "bfloat16":
        new_t, new_v = round_bfloat16(new_t), round_bfloat16(new_v)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return new_t, new_v


def replay(bits, frames, precision: str = "float32"):
    """State of every match after its own number of frames.

    ``bits`` is ``uint8[M, P, F]`` (input of match m, player p, frame f),
    ``frames`` is ``int[M]`` with ``frames[m] <= F``: match m applies the
    inputs of frames ``0 .. frames[m]-1``. Returns (translation, velocity,
    frame_count)."""
    bits = np.asarray(bits, np.uint8)
    frames = np.asarray(frames, np.int64)
    m, p, f_max = bits.shape
    if frames.shape != (m,) or (frames.size and int(frames.max()) > f_max):
        raise ValueError("frames does not fit the input table")
    t, v = spawn(m, p)
    for f in range(int(frames.max()) if frames.size else 0):
        live = (frames > f)[:, None, None]
        nt, nv = step(t, v, bits[:, :, f], precision)
        t = np.where(live, nt, t)
        v = np.where(live, nv, v)
    return t, v, frames.astype(np.uint32)
