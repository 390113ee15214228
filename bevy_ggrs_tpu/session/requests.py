"""The Save/Load/Advance request protocol.

``advance_frame()`` on every session flavor returns an ordered list of these;
the driver MUST execute them in order (`/root/reference/src/ggrs_stage.rs:
259-269`). The driver may fuse a ``[Load?, (Save, Advance)*]`` run into one
device rollout (see :class:`bevy_ggrs_tpu.rollout.RolloutExecutor`) — the
observable semantics are identical to serial execution.

Request invariants (the compatibility contract, survey §7 "hard parts"):
- ``SaveGameState.frame`` always equals the driver's current frame
  (`ggrs_stage.rs:277`'s ``assert_eq!``): saves are labeled pre-advance.
- ``AdvanceFrame`` increments the driver frame by one (`ggrs_stage.rs:305`).
- ``LoadGameState.frame`` targets a frame still in the ring (within
  ``max_prediction`` of current — guaranteed by the protocol).

A session's list is always ONE such run, and the session builds it from a
:class:`Segment`: the run as the two arrays the queue set's ``advance``
returned. ``advance_frame()`` is ``advance_segment().requests()``; a hosting
loop that executes many matches a dispatch (``serve/batch.py``) takes the
segment itself and never makes the request objects.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class SaveGameState:
    """Snapshot the current world, labeled ``frame``; report the checksum
    back to the session via ``session.report_checksum(frame, cs)`` (the
    ``GameStateCell::save(frame, None, Some(checksum))`` analog,
    `ggrs_stage.rs:282-283`)."""

    frame: int


@dataclasses.dataclass(frozen=True)
class LoadGameState:
    """Roll back: restore the world saved as ``frame`` and set the driver
    frame to it (`ggrs_stage.rs:290-299`)."""

    frame: int


@dataclasses.dataclass(frozen=True)
class RestoreGameState:
    """Adopt an externally supplied world (supervisor state transfer, not
    the ring): set the driver frame to ``frame``, replace the device state
    with ``state``, and re-seed the snapshot ring from it. Outside the
    reference's request vocabulary — ggrs stops at DesyncDetected; this is
    the repair path (docs/chaos.md). Unlike ``LoadGameState`` there is no
    within-``max_prediction`` bound: the adopted frame replaces history
    rather than rewinding into it."""

    frame: int
    state: object  # WorldState pytree (host or device arrays)


@dataclasses.dataclass(frozen=True)
class AdvanceFrame:
    """Run one simulated frame with these per-player inputs
    (`ggrs_stage.rs:301-306`). ``bits[p]`` payload, ``status[p]`` ∈
    {CONFIRMED, PREDICTED, DISCONNECTED}."""

    bits: np.ndarray  # [num_players, *input_shape]
    status: np.ndarray  # int32[num_players]

    def __post_init__(self):
        object.__setattr__(self, "bits", np.asarray(self.bits))
        object.__setattr__(
            self, "status", np.asarray(self.status, dtype=np.int32)
        )


class SegmentError(ValueError):
    """A request list that is not ``[Load?, (Save, Advance)*]`` runs with
    the saves labeled contiguously, so no :class:`Segment` says it.
    ``reason``: ``restore_request``, ``unsupported_request`` or
    ``non_canonical_burst`` (the batched core's ``SlotFault`` reasons)."""

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason


class Segment:
    """One Load-delimited run of requests as arrays: ``[Load(load)]`` when
    ``load`` is not None, then ``(Save(start + i), Advance(bits[i],
    status[i]))`` for each of the ``n`` rows. ``bits [n, P, *input_shape]``
    and ``status int32[n, P]`` are what ``NativeQueueSet.advance`` /
    ``PyQueueSet.advance`` returned: the segment owns them, and whoever is
    handed the segment may keep views of them. A session's segment is
    canonical by construction (``start`` is the frame loaded, or the
    driver's current frame)."""

    __slots__ = ("load", "start", "bits", "status")

    def __init__(
        self, load: Optional[int], start: int, bits: np.ndarray,
        status: np.ndarray,
    ):
        self.load = load
        self.start = start
        self.bits = bits
        self.status = status

    @property
    def n(self) -> int:
        """Frames the segment advances."""
        return len(self.bits)

    def requests(self) -> List[object]:
        """The request list of this segment: what ``advance_frame()``
        returns."""
        out: List[object] = (
            [] if self.load is None else [LoadGameState(self.load)]
        )
        bits, status, start = self.bits, self.status, self.start
        for i in range(len(bits)):
            out.append(SaveGameState(start + i))
            out.append(AdvanceFrame(bits=bits[i], status=status[i]))
        return out

    @classmethod
    def from_requests(cls, requests) -> List["Segment"]:
        """Cut a request list at its Loads, one :class:`Segment` a run;
        raises :class:`SegmentError` for a request other than the three, a
        save without its advance (or the reverse) and saves not labeled
        contiguously. ``start`` is the first save's label (the load's
        frame for a bare ``[Load]``, None for an empty run with nothing to
        say it): whether that IS the frame the driver stands at is the
        driver's check."""
        runs: List[tuple] = []
        load: Optional[int] = None
        steps: List[object] = []
        for req in requests:
            if isinstance(req, LoadGameState):
                if steps or load is not None:
                    runs.append((load, steps))
                load, steps = req.frame, []
            elif isinstance(req, (SaveGameState, AdvanceFrame)):
                steps.append(req)
            else:
                raise SegmentError(
                    "restore_request"
                    if any(isinstance(r, RestoreGameState) for r in requests)
                    else "unsupported_request",
                    f"unknown request {req!r}",
                )
        if steps or load is not None:
            runs.append((load, steps))
        out = []
        for load, steps in runs:
            saves, advs = steps[0::2], steps[1::2]
            paired = len(saves) == len(advs) and all(
                isinstance(s, SaveGameState) and isinstance(a, AdvanceFrame)
                for s, a in zip(saves, advs)
            )
            start = saves[0].frame if paired and saves else load
            if not paired or any(
                s.frame != start + i for i, s in enumerate(saves)
            ):
                raise SegmentError(
                    "non_canonical_burst",
                    f"{len(steps)} requests after Load({load})",
                )
            if advs:
                bits = np.stack([a.bits for a in advs])
                status = np.stack([a.status for a in advs])
            else:
                bits = status = np.zeros((0, 0), np.int32)
            out.append(cls(load, start, bits, status))
        return out
