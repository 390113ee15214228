"""box_game as the program offers it: the glue between a configuration's
``title`` and the program's public API. The drivers know no title by name;
they ask this module for the schedule, the world and the readback.

``CONTROLS`` are schedules that are *meant* to be wrong, built from the
program's public ``Schedule`` API by this file alone (the program gets no
switch): the lower-precision control of the ``correct`` decision, and a
broken step for the test that must see ``correct`` come out false.
"""

from __future__ import annotations

import numpy as np

REFERENCE = "box_game_np"


def _round_bf16_system(state, inputs):
    """translation and velocity through bfloat16 after the move.
    ``reduce_precision``, not a convert round trip: XLA:TPU folds the
    latter away (PERF.md, PR 21)."""
    from jax import lax

    del inputs
    comps = dict(state.components)
    for name in ("translation", "velocity"):
        comps[name] = lax.reduce_precision(comps[name], exponent_bits=8,
                                           mantissa_bits=7)
    return state.replace(components=comps)


def _freeze_last_player_system(state, inputs):
    """A part of the batch left out: the cube of the highest handle is put
    back on its spawn point every frame."""
    import jax.numpy as jnp

    from benchmark.reference import box_game_np as ref

    handle = state.components["player_handle"]
    last = inputs.num_players - 1
    spawn_t, _ = ref.spawn(1, inputs.num_players)
    sel = (handle == last)[:, None]
    comps = dict(state.components)
    comps["translation"] = jnp.where(
        sel, jnp.asarray(spawn_t[0, last]), comps["translation"])
    comps["velocity"] = jnp.where(sel, 0.0, comps["velocity"])
    return state.replace(components=comps)


CONTROLS = {
    "bf16_state": _round_bf16_system,
    "freeze_last_player": _freeze_last_player_system,
}


def input_spec():
    from bevy_ggrs_tpu.models import box_game

    return box_game.INPUT_SPEC


def make_schedule(control=None):
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.schedule import Schedule

    if control is None:
        return box_game.make_schedule()
    return Schedule([box_game.move_cube_system, CONTROLS[control],
                     box_game.increase_frame_system])


def make_world(num_players: int):
    from bevy_ggrs_tpu.models import box_game

    return box_game.make_world(num_players).commit()


def build_plugin(num_players: int, control=None):
    """A ``GGRSPlugin`` wired as examples/box_game_common.py wires one,
    without the input system (the driver gives it)."""
    import jax.numpy as jnp

    from bevy_ggrs_tpu.app import GGRSPlugin
    from bevy_ggrs_tpu.models import box_game

    def setup(world, app):
        box_game.spawn_players(
            world, num_players, next_id=app.rollback_id_provider.next_id
        )

    return (
        GGRSPlugin(box_game.INPUT_SPEC)
        .register_rollback_component("translation", shape=(3,),
                                     dtype=jnp.float32)
        .register_rollback_component("velocity", shape=(3,),
                                     dtype=jnp.float32)
        .register_rollback_component("player_handle", dtype=jnp.int32,
                                     default=-1)
        .register_rollback_resource("frame_count", jnp.uint32(0))
        .with_rollback_schedule(make_schedule(control))
        .with_num_players(num_players)
        .with_world_capacity(16)
        .with_setup_system(setup)
    )


def readback(state, num_players: int):
    """What the reference compares, from a ``WorldState`` whose leaves may
    carry leading batch axes: (translation, velocity) as
    ``float32[..., P, 3]`` ordered by player handle, and ``frame_count``."""
    t = np.asarray(state.components["translation"])
    v = np.asarray(state.components["velocity"])
    handle = np.asarray(state.components["player_handle"])
    order = np.argsort(np.where(handle >= 0, handle, 1 << 30), axis=-1,
                       kind="stable")[..., :num_players]
    take = lambda a: np.take_along_axis(a, order[..., None], axis=-2)
    return take(t), take(v), np.asarray(state.resources["frame_count"])
