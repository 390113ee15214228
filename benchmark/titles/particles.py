"""The particle stress test as the program offers it: the glue between a
configuration's ``title`` and the program's public API
(``models/particles.py``).

The births a frame and the rows of a world are the configuration's
(``settings.rate``, ``settings.world_capacity``), which the accepted hooks
``make_schedule(control)`` / ``make_world(players)`` cannot carry: loop kind
``match_server_churn`` asks ``configured(settings)`` for a title bound to
them. A served title only: no ``build_plugin`` (no client cell runs it).

``CONTROLS`` are schedules that are *meant* to be wrong, built from the
program's public ``Schedule`` API by this file alone (the program gets no
switch): the lower-precision control of the ``correct`` decision, and a
broken step for the test that must see ``correct`` come out false.
"""

from __future__ import annotations

import numpy as np

REFERENCE = "particles_np"


def _round_bf16_system(state, inputs):
    """position and velocity through bfloat16 after the frame's integration.
    ``reduce_precision``, not a convert round trip: XLA:TPU folds the
    latter away (PERF.md, PR 21). The lifecycle is integers: untouched."""
    from jax import lax

    del inputs
    comps = dict(state.components)
    for name in ("position", "velocity"):
        comps[name] = lax.reduce_precision(comps[name], exponent_bits=8,
                                           mantissa_bits=7)
    return state.replace(components=comps)


def _freeze_last_player_system(state, inputs):
    """A part of the world left out: the emitter of the highest handle is
    put back on its spawn point every frame, so its particles are born
    where the player never steered it from."""
    import jax.numpy as jnp

    from benchmark.reference import particles_np as ref

    last = inputs.num_players - 1
    emitter = state.resources["emitter_position"]
    home = jnp.asarray(ref.emitter_spawn(inputs.num_players)[last])
    return state.replace(resources={
        **state.resources, "emitter_position": emitter.at[last].set(home)})


CONTROLS = {
    "bf16_state": _round_bf16_system,
    "freeze_last_player": _freeze_last_player_system,
}


def input_spec():
    from bevy_ggrs_tpu.models import particles

    return particles.INPUT_SPEC


def for_match(world, match_seed: int):
    """``world`` (what ``make_world`` gave) for the match seeded
    ``match_seed``: the same device buffers but the seed's."""
    from bevy_ggrs_tpu.models import particles

    return particles.with_match_seed(world, match_seed)


def readback(state, num_players: int):
    """What the comparisons read, from a ``WorldState`` whose leaves may
    carry leading batch axes, as NumPy arrays: ``(position, velocity,
    frame_count, world)``. The first three are what ``match_server.check``
    indexes (rows in the program's order, dead rows included: they mean
    nothing by themselves); ``world`` is the dict the comparison by id
    reads: ``alive``, ``id``, ``ttl``, ``position``, ``velocity``
    (``[..., capacity(, 2)]``) and the resources ``next_id``, ``fizzled``,
    ``frame_count``, ``emitter``."""
    del num_players
    comp, res = state.components, state.resources
    world = {
        "alive": np.asarray(state.alive),
        "id": np.asarray(state.rollback_id),
        "ttl": np.asarray(comp["ttl"]),
        "position": np.asarray(comp["position"]),
        "velocity": np.asarray(comp["velocity"]),
        "next_id": np.asarray(res["next_rollback_id"]),
        "fizzled": np.asarray(res["spawn_fizzled"]),
        "frame_count": np.asarray(res["frame_count"]),
        "emitter": np.asarray(res["emitter_position"]),
    }
    return world["position"], world["velocity"], world["frame_count"], world


class _Configured:
    """This module's hooks bound to a configuration's ``settings``: what
    ``match_server`` asks of ``ctx.title``."""

    REFERENCE = REFERENCE
    CONTROLS = CONTROLS
    input_spec = staticmethod(input_spec)
    readback = staticmethod(readback)
    for_match = staticmethod(for_match)

    def __init__(self, settings: dict):
        self.rate = int(settings["rate"])
        self.world_capacity = int(settings["world_capacity"])

    def make_schedule(self, control=None):
        from bevy_ggrs_tpu.models import particles
        from bevy_ggrs_tpu.schedule import Schedule

        schedule = particles.make_schedule(self.rate)
        if control is None:
            return schedule
        *rules, count = schedule.systems
        return Schedule([*rules, CONTROLS[control], count])

    def make_world(self, num_players: int):
        from bevy_ggrs_tpu.models import particles

        return particles.make_world(
            num_players, self.world_capacity).commit()


def configured(settings: dict) -> _Configured:
    """Refuses, by name and before anything is built, a program that has
    no such title (the parent of PR 44)."""
    try:
        from bevy_ggrs_tpu.models import particles  # noqa: F401
    except ImportError:
        raise SystemExit(
            "benchmark/titles/particles.py: this program has no title "
            "'particles' (bevy_ggrs_tpu/models/particles.py, PR 44). "
            "Nothing was run.") from None
    return _Configured(settings)
