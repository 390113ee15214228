"""Boids as the program offers it: the glue between a configuration's
``title`` and the program's public API (``models/boids.py``).

The size of the world and the force path are the configuration's
(``settings.num_entities``, ``settings.force_kernel``), which the accepted
hook ``build_plugin(num_players, control)`` cannot carry: a loop kind that
runs this title asks ``configured(settings)`` for a title bound to them and
uses that in its place (``drivers/p2p_pair_world.py``).

``CONTROLS`` are schedules that are *meant* to be wrong, built from the
program's public ``Schedule`` API by this file alone (the program gets no
switch): the lower-precision control of the ``correct`` decision, and a
broken step for the test that must see ``correct`` come out false.
"""

from __future__ import annotations

import numpy as np

REFERENCE = "boids_np"


def _round_bf16_system(state, inputs):
    """position and velocity through bfloat16 after the flocking step.
    ``reduce_precision``, not a convert round trip: XLA:TPU folds the
    latter away (PERF.md, PR 21)."""
    from jax import lax

    del inputs
    comps = dict(state.components)
    for name in ("position", "velocity"):
        comps[name] = lax.reduce_precision(comps[name], exponent_bits=8,
                                           mantissa_bits=7)
    return state.replace(components=comps)


def _freeze_last_player_system(state, inputs):
    """A part of the world left out: the leader of the highest handle is
    put back on its spawn point, at its spawn velocity, every frame."""
    import jax.numpy as jnp

    from benchmark.reference import boids_np as ref

    last = inputs.num_players - 1
    spawn_p, spawn_v = ref.spawn(1, inputs.num_players, last + 1)
    sel = (state.components["leader_handle"] == last)[:, None]
    comps = dict(state.components)
    comps["position"] = jnp.where(sel, jnp.asarray(spawn_p[0, last]),
                                  comps["position"])
    comps["velocity"] = jnp.where(sel, jnp.asarray(spawn_v[0, last]),
                                  comps["velocity"])
    return state.replace(components=comps)


CONTROLS = {
    "bf16_state": _round_bf16_system,
    "freeze_last_player": _freeze_last_player_system,
}


def input_spec():
    from bevy_ggrs_tpu.models import boids

    return boids.INPUT_SPEC


def make_schedule(control=None, force_kernel: str = "xla"):
    from bevy_ggrs_tpu.models import boids
    from bevy_ggrs_tpu.schedule import Schedule

    schedule = boids.make_schedule(kernel=force_kernel)
    if control is None:
        return schedule
    flock, count = schedule.systems
    return Schedule([flock, CONTROLS[control], count])


def make_world(num_players: int, num_entities: int):
    from bevy_ggrs_tpu.models import boids

    return boids.make_world(num_entities, num_players).commit()


def build_plugin(num_players: int, control=None, num_entities: int = 64,
                 force_kernel: str = "xla"):
    """A ``GGRSPlugin`` wired as examples/model_zoo_synctest.py wires the
    model, through the app's own registration calls, without the input
    system (the driver gives it)."""
    import jax.numpy as jnp

    from bevy_ggrs_tpu.app import GGRSPlugin
    from bevy_ggrs_tpu.models import boids

    def setup(world, app):
        spawned = {
            name: np.asarray(leaf) for name, leaf in
            make_world(num_players, num_entities).components.items()}
        for row in range(num_entities):
            world.spawn({name: leaf[row] for name, leaf in spawned.items()},
                        rollback_id=app.rollback_id_provider.next_id())

    return (
        GGRSPlugin(boids.INPUT_SPEC)
        .register_rollback_component("position", shape=(2,),
                                     dtype=jnp.float32)
        .register_rollback_component("velocity", shape=(2,),
                                     dtype=jnp.float32)
        .register_rollback_component("leader_handle", dtype=jnp.int32,
                                     default=-1)
        .register_rollback_resource("frame_count", jnp.uint32(0))
        .with_rollback_schedule(make_schedule(control, force_kernel))
        .with_num_players(num_players)
        .with_world_capacity(num_entities)
        .with_setup_system(setup)
    )


def readback(state, num_players: int):
    """What the reference compares, from a ``WorldState`` whose leaves may
    carry leading batch axes: (position, velocity) as ``float32[..., N, 2]``
    in row order (boid i is row i; the leaders are rows 0..P-1), and
    ``frame_count``."""
    del num_players
    return (np.asarray(state.components["position"]),
            np.asarray(state.components["velocity"]),
            np.asarray(state.resources["frame_count"]))


class _Configured:
    """This module with ``build_plugin`` bound to a configuration's
    ``settings``: what a driver's ``ctx.title`` is for a sized world."""

    REFERENCE = REFERENCE
    CONTROLS = CONTROLS
    input_spec = staticmethod(input_spec)
    readback = staticmethod(readback)

    def __init__(self, settings: dict):
        self.num_entities = int(settings["num_entities"])
        self.force_kernel = str(settings["force_kernel"])
        if self.force_kernel == "mxu":
            _require_three_term_mxu()

    def build_plugin(self, num_players: int, control=None):
        return build_plugin(num_players, control, self.num_entities,
                            self.force_kernel)


def _require_three_term_mxu() -> None:
    """A program older than PR 31 cannot run a float32 configuration through
    its MXU path: its separation sum kept 16 bits of a position and read
    4.4e-5 a step on the chip, over the limit a float32 step is held to
    (PERF.md section 6, PR 31). It is refused here, in seconds and by name,
    rather than after a minute's run to ``correct: false``."""
    from bevy_ggrs_tpu.ops import pairwise

    if not hasattr(pairwise, "SEP_ROWS"):
        raise SystemExit(
            "benchmark/titles/boids.py: this program's MXU force path keeps "
            "two bfloat16 terms of a position (ops/pairwise.py has no "
            "SEP_ROWS); force_kernel 'mxu' at float32 needs the third "
            "(PR 31). Nothing was run.")


def configured(settings: dict) -> _Configured:
    return _Configured(settings)
