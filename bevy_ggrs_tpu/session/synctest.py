"""SyncTestSession: the determinism harness.

All players are local. Once ``check_distance`` frames of history exist,
every ``advance_frame`` first emits a forced rollback ``check_distance``
frames deep, resimulates up to the present with the *same* stored inputs,
and only then takes the new frame's own (save, advance) step: upstream
ggrs's order (``SyncTestSession::advance_frame`` in
``src/sessions/sync_test_session.rs``: ``adjust_gamestate(current_frame -
check_distance)``, then ``save_current_state()`` + ``AdvanceFrame``). A
frame is therefore ONE Load-delimited request list,

    [Load(f - d), (Save(g), Advance(g)) for g in f - d .. f]

the list a ``P2PSession`` emits on a rollback, and one dispatch of the
driver. When the driver re-saves each resimulated frame, the session
compares the new checksum against the one recorded on the frame's first
save; any mismatch raises :class:`MismatchedChecksum` — the
simulate-vs-resimulate property check the reference runs continuously
(`/root/reference/examples/box_game/box_game_synctest.rs:27-38`; driven by
`src/ggrs_stage.rs:163-193`). Every frame is saved ``check_distance + 1``
times (at ticks ``g .. g + d``, each from a snapshot one frame newer than
the last) and compared ``check_distance`` times; the last of them is the
re-save of the snapshot just loaded, so ``check_distance`` 1 holds a
snapshot to its own checksum and nothing else (as upstream's, which saves
each frame once there): a non-deterministic step needs 2, the default.

What the order does NOT catch: the live state is loaded over before it is
saved, so a live state changed between two ticks at a frame >=
``check_distance`` never enters a checksum. Non-determinism of the step
does, within ``check_distance`` ticks; resident state is the attestation
sweep's to guard (``serve/server.py`` ``_attest_sweep``,
``RollbackRunner.attest_and_repair``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bevy_ggrs_tpu.schedule import InputSpec
from bevy_ggrs_tpu.session.common import (
    InvalidRequest,
    MismatchedChecksum,
    SessionState,
    restore_spans,
    serialize_spans,
)
from bevy_ggrs_tpu.native.core import make_queue_set
from bevy_ggrs_tpu.session.requests import Segment


class SyncTestSession:
    def __init__(
        self,
        num_players: int,
        input_spec: InputSpec = InputSpec(),
        check_distance: int = 2,
        max_prediction: int = 8,
        input_delay: int = 0,
    ):
        if check_distance > max_prediction:
            raise InvalidRequest(
                f"check_distance {check_distance} exceeds max_prediction "
                f"{max_prediction}"
            )
        self.num_players = int(num_players)
        self.input_spec = input_spec
        self.check_distance = int(check_distance)
        self.max_prediction = int(max_prediction)
        self.current_frame = 0
        zero = input_spec.zeros_np(1)[0]
        self._zero = zero
        self._qset = make_queue_set(
            zero, [input_delay] * num_players, window=self.max_prediction + 1
        )
        self._queues = self._qset.queues
        self._handles = list(range(self.num_players))
        self._pending: Dict[int, np.ndarray] = {}
        self._checksums: Dict[int, int] = {}

    # -- API parity with the stage driver's session usage ------------------

    def current_state(self) -> SessionState:
        return SessionState.RUNNING  # synctest never synchronizes

    def local_player_handles(self) -> List[int]:
        return list(range(self.num_players))

    def add_local_input(self, handle: int, bits) -> None:
        """Collect this frame's input for ``handle``
        (`ggrs_stage.rs:186`)."""
        if not 0 <= handle < self.num_players:
            raise InvalidRequest(f"invalid player handle {handle}")
        self._pending[handle] = np.asarray(
            bits, dtype=self._zero.dtype
        ).reshape(self._zero.shape)

    def advance_frame(self) -> List[object]:
        """Emit the request list for one simulated frame: the forced
        rollback + resimulation once history allows, then the frame's own
        step, as one Load-delimited list (:meth:`advance_segment`'s)."""
        return self.advance_segment().requests()

    def advance_segment(self) -> Segment:
        """One simulated frame as a :class:`Segment`: what a hosting loop
        takes in place of the request list."""
        if set(self._pending) != set(range(self.num_players)):
            missing = set(range(self.num_players)) - set(self._pending)
            raise InvalidRequest(f"missing local input for handles {sorted(missing)}")
        frame = self.current_frame
        resim = self.check_distance > 0 and frame >= self.check_distance
        # One call into the queue set: the local inputs, the frames of the
        # forced rollback and this one gathered (all players are local and
        # fed each frame, so every status reads CONFIRMED), and the GC of
        # inputs older than the deepest future rollback.
        horizon = frame - self.check_distance
        start, _load, bits, status, _stored, _confirmed, _last = (
            self._qset.advance(
                None, frame, self._handles,
                [self._pending[h] for h in self._handles],
                self.max_prediction, horizon if resim else frame, horizon,
            )
        )
        self._pending.clear()
        self.current_frame = frame + 1
        for f in [f for f in self._checksums if f < horizon]:
            del self._checksums[f]
        # ``start`` is ``frame`` before history allows a rollback: then the
        # segment is the frame's own (save, advance) alone, nothing loaded.
        return Segment(start if resim else None, start, bits, status)

    # -- checkpoint / resume -----------------------------------------------

    def state_dict(self) -> Dict:
        """JSON-serializable resumable state: frame counter plus the input
        and checksum history inside the forced-rollback window. Everything
        older is already GC'd (see :meth:`advance_frame`), so this is the
        complete session state. Inputs are captured PER QUEUE through each
        queue's own confirmed horizon — with ``input_delay`` > 0 that
        horizon runs ``delay`` frames past ``current_frame`` (in-flight
        delayed inputs), which a frame-window capture would drop."""
        inputs = serialize_spans(
            self._queues, max(0, self.current_frame - self.check_distance - 1)
        )
        return {
            "current_frame": self.current_frame,
            "inputs": inputs,
            "checksums": {str(f): int(c) for f, c in self._checksums.items()},
        }

    def load_state_dict(self, sd: Dict) -> None:
        """Restore :meth:`state_dict` output into a freshly constructed
        session (same num_players / input_spec / check_distance /
        input_delay). Inputs are re-inserted verbatim through the no-delay
        path (delay was already applied before capture), so the next forced
        rollback resimulates with exactly the original inputs."""
        self.current_frame = int(sd["current_frame"])
        zero = self.input_spec.zeros_np(1)[0]
        restore_spans(
            self._queues, sd["inputs"], self.current_frame,
            zero.dtype, zero.shape,
        )
        self._checksums = {int(f): int(c) for f, c in sd["checksums"].items()}
        self._pending.clear()

    def report_checksum(self, frame: int, checksum: int) -> None:
        """The ``GameStateCell::save`` analog (`ggrs_stage.rs:282-283`): the
        driver reports each saved frame's checksum; a resimulated frame that
        hashes differently than its original save is a desync."""
        self.report_checksums(frame, (checksum,))

    def report_checksums(self, first_frame: int, checksums) -> None:
        """The checksums of frames ``first_frame ..`` saved in a row (a
        segment's), compared in order as so many :meth:`report_checksum`
        calls: raises at the first frame that differs."""
        seen = self._checksums
        for frame, checksum in enumerate(checksums, first_frame):
            checksum = int(checksum)
            prev = seen.setdefault(frame, checksum)
            if prev != checksum:
                raise MismatchedChecksum(frame, prev, checksum)
