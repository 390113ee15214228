"""Step engine: pure-function systems composed into a rollback schedule.

TPU-native replacement for the reference's user-owned Bevy ``Schedule`` that
``GGRSStage`` runs once per simulated frame (``/root/reference/src/
ggrs_stage.rs:301-306``: insert ``PlayerInputs`` resource → ``schedule.
run_once(world)`` → remove resource). Here the schedule is a composition of
pure ``(WorldState, PlayerInputs) -> WorldState`` functions, so one simulated
frame is a single traced function XLA can fuse end to end — and ``lax.scan``
over it is a whole resimulation burst (see :mod:`bevy_ggrs_tpu.rollout`).

The reference runs systems on a thread pool (``SystemStage::parallel()``,
``examples/box_game/box_game_p2p.rs:74``); the TPU analog is XLA op-level
fusion inside the compiled step, so systems compose sequentially here and
the compiler extracts the parallelism.

Inputs are positional per player, mirroring the ``PlayerInputs<T>`` resource
(``ggrs_stage.rs:60-75``): ``inputs.bits[p]`` is player ``p``'s payload, and
a system whose entities carry a player handle reads each entity's input with
:meth:`PlayerInputs.for_handles`, the reference's ``inputs[p.handle].0``
(``examples/box_game/box_game.rs:159``) for a whole handle column at once.
Indexing ``inputs.bits[handles]`` gives the same values, but the sessions
vmap a step over branches and the server over slots as well, and jax batches
that index into an XLA ``gather`` which the TPU runs an entity at a time
(a fifth of the served 1,024-boid dispatch, ``PERF.md`` section 6, PR 38);
the helper is a select over the few players and stays dense under any number
of batch axes. Each input carries an ``InputStatus`` (confirmed / predicted /
disconnected — ggrs ``InputStatus`` consumed at ``ggrs_stage.rs:61``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from flax import struct

from bevy_ggrs_tpu.state import WorldState

# ggrs::InputStatus analog (per player, per frame).
CONFIRMED = 0
PREDICTED = 1
DISCONNECTED = 2


@dataclasses.dataclass(frozen=True)
class InputSpec:
    """Shape/dtype of one player's input for one frame.

    The reference requires ``Config::Input: Pod`` (a flat byte struct,
    ``examples/box_game/box_game.rs:34-38``); here the input is a fixed-shape
    integer array. Default matches box_game's single ``u8`` bitmask.

    ``values`` optionally declares the model's input-value universe (e.g.
    ``range(16)`` for a 4-bit bitmask, ``range(32)`` when a FIRE bit
    exists). Speculation's structured branch trees enumerate candidate
    futures from this set — a model whose spec omits it falls back to the
    4-bit default and can never speculatively hit a change in higher bits.
    """

    shape: Tuple[int, ...] = ()
    dtype: Any = jnp.uint8
    values: Optional[Tuple[int, ...]] = None

    def zeros(self, num_players: int) -> jnp.ndarray:
        return jnp.zeros((num_players,) + self.shape, dtype=self.dtype)

    def zeros_np(self, num_players: int) -> np.ndarray:
        return np.zeros((num_players,) + self.shape,
                        dtype=np.dtype(jnp.dtype(self.dtype).name))


@struct.dataclass
class PlayerInputs:
    """Confirmed-or-predicted inputs for ALL players for one simulated frame.

    Mirrors ``PlayerInputs<T>(Vec<(T::Input, InputStatus)>)``
    (``src/ggrs_stage.rs:60-75``). ``bits[p]`` is player ``p``'s input payload;
    ``status[p]`` is CONFIRMED / PREDICTED / DISCONNECTED.
    """

    bits: jnp.ndarray  # [num_players, *input_shape]
    status: jnp.ndarray  # int32[num_players]

    @property
    def num_players(self) -> int:
        return self.status.shape[0]

    def for_handles(self, handles: jnp.ndarray) -> jnp.ndarray:
        """Each entity's input by its player handle: ``int32[N]`` handles in,
        ``bits.dtype[N, *input_shape]`` out, bit for bit
        ``bits[clip(handles, 0, P - 1)]`` (a handle under 0 reads player 0,
        one past the last reads the last; the caller masks entities without
        a player). A select over the P players and not an index: it moves
        the same integers, and no batch axis turns it into a ``gather``."""
        last = self.num_players - 1
        safe = jnp.clip(handles, 0, last)
        # The mask broadcasts over the trailing axes of a vector input.
        safe = safe.reshape(safe.shape + (1,) * (self.bits.ndim - 1))
        out = jnp.broadcast_to(
            self.bits[last], handles.shape + self.bits.shape[1:]
        )
        for p in range(last - 1, -1, -1):
            out = jnp.where(safe == p, self.bits[p], out)
        return out


def make_inputs(bits, status=None) -> PlayerInputs:
    bits = jnp.asarray(bits)
    if status is None:
        status = jnp.zeros((bits.shape[0],), dtype=jnp.int32)
    return PlayerInputs(bits=bits, status=jnp.asarray(status, dtype=jnp.int32))


# A system is a pure function advancing the registered world slice by one
# frame given this frame's inputs. The reference analog is one Bevy system in
# the user's rollback schedule (e.g. move_cube_system, box_game.rs:154-203).
System = Callable[[WorldState, PlayerInputs], WorldState]


class Schedule:
    """An ordered composition of systems = one simulated frame.

    ``schedule(state, inputs)`` is pure and jit-safe; the session drivers scan
    it over frames and vmap it over speculative branches.
    """

    def __init__(self, systems: Sequence[System] = ()):
        self._systems = list(systems)

    def add_system(self, system: System) -> "Schedule":
        self._systems.append(system)
        return self

    @property
    def systems(self) -> Tuple[System, ...]:
        return tuple(self._systems)

    def __call__(self, state: WorldState, inputs: PlayerInputs) -> WorldState:
        # The title's own rules, as one device scope: the only scope that
        # holds others (a title's ``claim``, the force kernel's own).
        # (``obs`` imports the sessions, which import this module.)
        from bevy_ggrs_tpu.obs.trace import device_scope

        with device_scope("schedule"):
            for system in self._systems:
                state = system(state, inputs)
        return state
