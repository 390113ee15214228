"""ctypes bindings for the native session data-plane (+ Python fallback).

The session layer constructs its per-player input queues and its
misprediction tracker through :func:`make_queue_set` / :func:`make_tracker`.
By default those build the C++ core (``session_core.cpp``) and return thin
ctypes wrappers whose surface is identical to the pure-Python
:class:`~bevy_ggrs_tpu.session.input_queue.InputQueue` / tracker logic they
replace — sessions are agnostic. Set ``BEVY_GGRS_TPU_NATIVE=0`` to force the
Python path (parity tests run both).

A session's frame crosses into the core twice at most: a queue set's
:meth:`advance` is all of ``advance_frame()``'s work on the queues and the
tracker, :meth:`ingest` all of one ``InputMsg``'s, and both hand back the
confirmed frame and every queue's last confirmed frame, so the session
keeps them as fields. The Python plane's two methods are the sequence of
primitives they replaced, the reference the native plane is held to bitwise
(``tests/test_session_coarse_calls.py``).
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

NULL_FRAME = -1  # matches bevy_ggrs_tpu.session.common (not imported here:
# the session package imports this module at load time)

_INT32_MAX = 2**31 - 1

# Disconnect-frame sentinel meaning "this player never disconnected". The
# value is a three-way contract: session_core.cpp compares
# `frame >= disc_frames[h]` against INT32_MAX, make_tracker/gather default-fill
# with it, and the p2p session passes it for connected players.
NEVER_DISCONNECTED = _INT32_MAX


def _invalid_request(msg: str) -> Exception:
    from bevy_ggrs_tpu.session.common import InvalidRequest

    return InvalidRequest(msg)

_lib = None


def _load():
    """The native core, or None when the Python plane was asked for. A
    build or load failure raises: the Python plane is the parity
    reference, several times slower, and never a silent substitute."""
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("BEVY_GGRS_TPU_NATIVE", "1").lower() in ("0", "false"):
        return None
    # CI alias: the spec-runner suite runs twice, native and forced-Python
    # (GGRS_NO_NATIVE=1), to keep both paths green.
    if os.environ.get("GGRS_NO_NATIVE", "0").lower() in ("1", "true"):
        return None
    from bevy_ggrs_tpu.native.build import ensure_core_built

    lib = ctypes.CDLL(ensure_core_built())
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.ggrs_qs_new.argtypes = [ctypes.c_int, ctypes.c_int, u8p, i32p]
    lib.ggrs_qs_new.restype = ctypes.c_void_p
    lib.ggrs_qs_free.argtypes = [ctypes.c_void_p]
    lib.ggrs_qs_free.restype = None
    lib.ggrs_qs_last_confirmed.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ggrs_qs_last_confirmed.restype = ctypes.c_int32
    lib.ggrs_qs_delay.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ggrs_qs_delay.restype = ctypes.c_int
    lib.ggrs_qs_add_input.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, u8p]
    lib.ggrs_qs_add_input.restype = ctypes.c_int32
    lib.ggrs_qs_add_local.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, u8p]
    lib.ggrs_qs_add_local.restype = ctypes.c_int32
    lib.ggrs_qs_confirmed.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, u8p]
    lib.ggrs_qs_confirmed.restype = ctypes.c_int
    lib.ggrs_qs_input.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, u8p]
    lib.ggrs_qs_input.restype = ctypes.c_int
    lib.ggrs_qs_confirmed_span.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, ctypes.c_int32,
        u8p, u8p]
    lib.ggrs_qs_confirmed_span.restype = None
    lib.ggrs_qs_discard_before.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.ggrs_qs_discard_before.restype = None
    lib.ggrs_qs_reset.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                  ctypes.c_int32, u8p]
    lib.ggrs_qs_reset.restype = None
    lib.ggrs_qs_last_input.argtypes = [ctypes.c_void_p, ctypes.c_int, u8p]
    lib.ggrs_qs_last_input.restype = None
    lib.ggrs_qs_min_confirmed.argtypes = [ctypes.c_void_p, u8p]
    lib.ggrs_qs_min_confirmed.restype = ctypes.c_int32
    lib.ggrs_qs_gather.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, i32p, u8p, i32p]
    lib.ggrs_qs_gather.restype = ctypes.c_int
    lib.ggrs_rt_new.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.ggrs_rt_new.restype = ctypes.c_void_p
    lib.ggrs_rt_free.argtypes = [ctypes.c_void_p]
    lib.ggrs_rt_free.restype = None
    lib.ggrs_rt_record_used.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, u8p, i32p]
    lib.ggrs_rt_record_used.restype = None
    lib.ggrs_rt_note_confirmed.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, u8p]
    lib.ggrs_rt_note_confirmed.restype = None
    lib.ggrs_rt_first_incorrect.argtypes = [ctypes.c_void_p]
    lib.ggrs_rt_first_incorrect.restype = ctypes.c_int32
    lib.ggrs_rt_clear_first_incorrect.argtypes = [ctypes.c_void_p]
    lib.ggrs_rt_clear_first_incorrect.restype = None
    lib.ggrs_rt_get_used.argtypes = [ctypes.c_void_p, ctypes.c_int32, u8p, i32p]
    lib.ggrs_rt_get_used.restype = ctypes.c_int
    lib.ggrs_rt_discard_before.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.ggrs_rt_discard_before.restype = None
    # The coarse calls take their buffers as addresses (integers kept by
    # the queue set that owns the buffers), never a pointer cast a call.
    vp = ctypes.c_void_p
    lib.ggrs_qs_advance.argtypes = [
        vp, vp, ctypes.c_int32, ctypes.c_int32, vp, vp, vp, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, vp, vp, vp, vp, vp]
    lib.ggrs_qs_advance.restype = ctypes.c_int
    lib.ggrs_qs_ingest.argtypes = [
        vp, vp, ctypes.c_int, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_char_p, ctypes.c_int64, vp, vp]
    lib.ggrs_qs_ingest.restype = None
    lib.ggrs_native_calls.argtypes = []
    lib.ggrs_native_calls.restype = ctypes.c_uint64
    i64p = ctypes.POINTER(ctypes.c_int64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.ggrs_sb_new.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, i64p, ctypes.c_int, u8p]
    lib.ggrs_sb_new.restype = ctypes.c_void_p
    lib.ggrs_sb_free.argtypes = [ctypes.c_void_p]
    lib.ggrs_sb_free.restype = None
    lib.ggrs_sb_log_set.argtypes = [ctypes.c_void_p, ctypes.c_int32, u8p]
    lib.ggrs_sb_log_set.restype = None
    lib.ggrs_sb_log_del.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.ggrs_sb_log_del.restype = None
    lib.ggrs_sb_log_clear.argtypes = [ctypes.c_void_p]
    lib.ggrs_sb_log_clear.restype = None
    lib.ggrs_sb_seed.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint64, u8p, u8p, u8p,
        ctypes.c_int32]
    lib.ggrs_sb_seed.restype = None
    lib.ggrs_sb_clear_seed.argtypes = [ctypes.c_void_p]
    lib.ggrs_sb_clear_seed.restype = None
    lib.ggrs_sb_build.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32, u8p, u8p,
        ctypes.c_int, ctypes.c_uint64, u8p, u64p]
    lib.ggrs_sb_build.restype = ctypes.c_int
    lib.ggrs_sb_match.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_int32, ctypes.c_int32, u8p,
        ctypes.c_int32, ctypes.c_int32, i32p, i32p]
    lib.ggrs_sb_match.restype = ctypes.c_int
    lib.ggrs_match_prefix.argtypes = [
        u8p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, u8p,
        ctypes.c_int32, i32p, i32p]
    lib.ggrs_match_prefix.restype = None
    vpp = ctypes.POINTER(ctypes.c_void_p)
    lib.ggrs_batch_stage.argtypes = [
        vpp, ctypes.c_int32, ctypes.c_int32, u8p, i32p, i32p, u8p, u8p,
        vpp, i32p, i32p, ctypes.c_int32, i32p, i32p, u8p, i32p, i64p,
        ctypes.c_int32, ctypes.c_int32, i32p]
    lib.ggrs_batch_stage.restype = ctypes.c_int
    lib.ggrs_batch_build.argtypes = [
        vpp, ctypes.c_int32, u8p, u8p, vpp, i32p, vpp, u8p, u8p, u8p,
        u8p, u8p, u8p, ctypes.c_uint64, ctypes.c_int32, u8p, u64p]
    lib.ggrs_batch_build.restype = ctypes.c_int
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def native_calls() -> Optional[int]:
    """How many ``ggrs_qs_*`` / ``ggrs_rt_*`` entry points this process has
    called (counted in the core; nothing a call in Python), or None on the
    Python plane. The served frame samples it around its session loop."""
    lib = _load()
    return None if lib is None else int(lib.ggrs_native_calls())


def _u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _frontier(qset) -> Tuple[int, List[int]]:
    """A queue set's ``(confirmed frame, [each queue's last confirmed
    frame])`` by the primitives: for the few places that change who is
    connected (the coarse calls return it themselves)."""
    return (
        qset.min_confirmed(qset.disc == NEVER_DISCONNECTED),
        [q.last_confirmed_frame for q in qset.queues],
    )


# ---------------------------------------------------------------------------
# Native queue set
# ---------------------------------------------------------------------------


class _NativeQueueView:
    """InputQueue-compatible view over one player's native queue."""

    __slots__ = ("_qs", "_h")

    def __init__(self, qs: "NativeQueueSet", handle: int):
        self._qs = qs
        self._h = handle

    @property
    def delay(self) -> int:
        return int(_lib.ggrs_qs_delay(self._qs._ptr, self._h))

    @property
    def last_confirmed_frame(self) -> int:
        return int(_lib.ggrs_qs_last_confirmed(self._qs._ptr, self._h))

    def reset(self, next_frame: int, last_input=None) -> None:
        if last_input is None:
            _lib.ggrs_qs_reset(self._qs._ptr, self._h, int(next_frame), None)
        else:
            _lib.ggrs_qs_reset(
                self._qs._ptr, self._h, int(next_frame),
                _u8p(self._qs._in(last_input)),
            )

    @property
    def last_input(self) -> np.ndarray:
        """The repeat-last prediction source (for checkpointing)."""
        flat = self._qs._out_flat(1)
        _lib.ggrs_qs_last_input(self._qs._ptr, self._h, _u8p(flat))
        return self._qs._decode_one(flat)

    def add_input(self, frame: int, bits) -> Optional[int]:
        got = int(
            _lib.ggrs_qs_add_input(
                self._qs._ptr, self._h, int(frame), _u8p(self._qs._in(bits))
            )
        )
        if got == -2:
            raise _invalid_request(
                f"non-contiguous input: got frame {frame}, expected "
                f"{self.last_confirmed_frame + 1}"
            )
        return None if got == -1 else got

    def add_local_input(self, frame: int, bits) -> int:
        return int(
            _lib.ggrs_qs_add_local(
                self._qs._ptr, self._h, int(frame), _u8p(self._qs._in(bits))
            )
        )

    def confirmed(self, frame: int) -> Optional[np.ndarray]:
        flat = self._qs._out_flat(1)
        if _lib.ggrs_qs_confirmed(self._qs._ptr, self._h, int(frame), _u8p(flat)):
            return self._qs._decode_one(flat)
        return None

    def confirmed_span(self, lo: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Confirmed inputs for frames ``lo .. lo+n-1`` in ONE native call:
        ``(values[n, *shape], mask[n])`` — unconfirmed slots are zeros with
        mask False. The speculative runner's per-tick bulk query."""
        flat = np.zeros(n * self._qs._nbytes, dtype=np.uint8)
        mask = np.zeros(n, dtype=np.uint8)
        _lib.ggrs_qs_confirmed_span(
            self._qs._ptr, self._h, int(lo), int(n), _u8p(flat), _u8p(mask)
        )
        values = flat.view(self._qs._dtype).reshape((n,) + self._qs._shape)
        return values, mask.astype(bool)

    def input(self, frame: int) -> Tuple[np.ndarray, bool]:
        flat = self._qs._out_flat(1)
        got = int(_lib.ggrs_qs_input(self._qs._ptr, self._h, int(frame), _u8p(flat)))
        if got < 0:
            raise _invalid_request(f"input for frame {frame} was discarded")
        return self._qs._decode_one(flat), bool(got)

    def discard_before(self, frame: int) -> None:
        # Per-queue discard is only used via the set-level call in sessions;
        # native discards the whole set at once (same horizon for all).
        self._qs.discard_before(frame)


class NativeQueueSet:
    def __init__(self, zero: np.ndarray, delays: Sequence[int], window: int = 0):
        # NB: np.ascontiguousarray would promote 0-d inputs to 1-d and
        # corrupt the spec shape; reshape(-1) for the byte view instead.
        zero = np.asarray(zero)
        self._dtype = zero.dtype
        self._shape = zero.shape
        self._nbytes = zero.nbytes
        self._num_players = len(delays)
        self._delays = [int(d) for d in delays]
        d = np.asarray(self._delays, dtype=np.int32)
        self._ptr = _lib.ggrs_qs_new(
            self._num_players,
            self._nbytes,
            _u8p(zero.reshape(-1).view(np.uint8)),
            _i32p(d),
        )
        self.queues: List[_NativeQueueView] = [
            _NativeQueueView(self, h) for h in range(self._num_players)
        ]
        # The coarse calls' buffers, allocated here at their largest size
        # and never again; `_at` keeps each one's address, which is how it
        # crosses. `disc` is the session's to write: the frame each player
        # disconnected at, NEVER_DISCONNECTED while connected.
        P, shape = self._num_players, self._shape
        self._window = int(window)
        self._slots = max(self._delays, default=0) + 1
        self.disc = np.full((P,), NEVER_DISCONNECTED, dtype=np.int32)
        self._handles: List[int] = []
        self._buf = {
            "disc": self.disc,
            "handles": np.zeros((P,), np.int32),
            "local_in": np.zeros((P,) + shape, self._dtype),
            "local_out": np.zeros((P, self._slots) + shape, self._dtype),
            "local_mask": np.zeros((P, self._slots), np.uint8),
            "bits": np.zeros((self._window, P) + shape, self._dtype),
            "status": np.zeros((self._window, P), np.int32),
            "ints": np.zeros((4 + P,), np.int32),
        }
        self._at = {k: v.ctypes.data for k, v in self._buf.items()}

    def _in(self, bits) -> np.ndarray:
        arr = np.asarray(bits, dtype=self._dtype).reshape(self._shape)
        return np.ascontiguousarray(arr.reshape(-1)).view(np.uint8)

    def _out_flat(self, n: int) -> np.ndarray:
        return np.empty(n * self._nbytes, dtype=np.uint8)

    def _decode_one(self, flat: np.ndarray) -> np.ndarray:
        return flat.view(self._dtype).reshape(self._shape)

    def discard_before(self, frame: int) -> None:
        _lib.ggrs_qs_discard_before(self._ptr, int(frame))

    def min_confirmed(self, connected=None) -> int:
        if connected is None:
            mask = np.ones(self._num_players, dtype=np.uint8)
        else:
            mask = np.ascontiguousarray(np.asarray(connected, dtype=np.uint8))
        return int(_lib.ggrs_qs_min_confirmed(self._ptr, _u8p(mask)))

    def gather(
        self, frame: int, disc_frames: Optional[Sequence[int]] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused per-frame input assembly: ``(bits[P, *shape], status[P])``."""
        P = self._num_players
        flat = self._out_flat(P)
        status = np.empty((P,), dtype=np.int32)
        if disc_frames is None:
            disc = np.full((P,), _INT32_MAX, dtype=np.int32)
        else:
            disc = np.ascontiguousarray(np.asarray(disc_frames, dtype=np.int32))
        rc = _lib.ggrs_qs_gather(
            self._ptr, int(frame), _i32p(disc), _u8p(flat), _i32p(status)
        )
        if rc != 0:
            raise _invalid_request(f"input for frame {frame} was discarded")
        bits = flat.view(self._dtype).reshape((P,) + self._shape)
        return bits, status

    frontier = _frontier

    def advance(
        self, tracker, frame: int, local_handles: Sequence[int],
        local_bits: Sequence[np.ndarray], max_prediction: int,
        resim_from: int, gc_cap: int, echo_locals: bool = False,
    ):
        """One frame's advance in one call (``ggrs_qs_advance``): add
        ``local_bits[i]`` for ``local_handles[i]`` at ``frame``; pick the
        segment to simulate (from the tracker's first incorrect frame,
        clamped to ``frame - max_prediction``, when ``tracker`` has one, else
        from ``resim_from``); gather every frame of it (and record it as
        used); clear the tracker's mark; discard history before
        ``min(confirmed frame, gc_cap)``.

        Returns ``(start, load, bits[n, P, ...], status[n, P], stored,
        confirmed, last_confirmed)``: the segment's first frame, the frame
        to load (NULL_FRAME: none), its inputs, with ``echo_locals`` each
        local handle's stored ``(frame, bits)`` for the frames its endpoints
        are sent, and the frontier. ``bits``, ``status`` and the stored
        inputs are COPIES of the bound buffers: a caller may keep them."""
        if max(frame - resim_from, max_prediction) >= self._window:
            raise _invalid_request(
                f"a segment from frame {min(resim_from, frame - max_prediction)}"
                f" to {frame} is more than the {self._window} frames this "
                f"queue set's buffers were bound for"
            )
        if local_handles != self._handles:
            self._handles = list(local_handles)
            self._buf["handles"][: len(self._handles)] = self._handles
        local_in = self._buf["local_in"]
        n_local = len(local_bits)
        for i in range(n_local):
            local_in[i] = local_bits[i]
        at = self._at
        rc = _lib.ggrs_qs_advance(
            self._ptr, None if tracker is None else tracker._ptr, frame,
            n_local, at["handles"], at["local_in"], at["disc"],
            max_prediction, resim_from, gc_cap, self._slots,
            at["local_out"] if echo_locals else None, at["local_mask"],
            at["bits"], at["status"], at["ints"],
        )
        ints = self._buf["ints"].tolist()
        if rc != 0:
            raise _invalid_request(f"input for frame {ints[0]} was discarded")
        start, n, load, confirmed = ints[:4]
        stored = []
        if echo_locals:
            out = self._buf["local_out"][:n_local].copy()
            mask = self._buf["local_mask"][:n_local].tolist()
            for i, h in enumerate(self._handles):
                stored.append([
                    (frame + s, out[i, s, ...])
                    for s in range(self._delays[h] + 1) if mask[i][s]
                ])
        return (
            start, load, self._buf["bits"][:n].copy(),
            self._buf["status"][:n].copy(), stored, confirmed, ints[4:],
        )

    def ingest(
        self, tracker, handle: int, start_frame: int, num: int,
        payload: bytes,
    ) -> Tuple[int, bool, int, List[int]]:
        """One ``InputMsg``'s span in one call (``ggrs_qs_ingest``): frames
        at or under ``handle``'s last confirmed frame are skipped, the
        contiguous new ones added and noted against ``tracker``, a gap stops
        the span. Returns ``(frames skipped, stopped at a gap, confirmed,
        last_confirmed)``."""
        _lib.ggrs_qs_ingest(
            self._ptr, None if tracker is None else tracker._ptr, handle,
            start_frame, num, payload, len(payload), self._at["disc"],
            self._at["ints"],
        )
        ints = self._buf["ints"].tolist()
        return ints[0], bool(ints[1]), ints[2], ints[3:3 + self._num_players]

    def __del__(self):
        try:
            if self._ptr:
                _lib.ggrs_qs_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Python fallback queue set
# ---------------------------------------------------------------------------


class PyQueueSet:
    def __init__(self, zero: np.ndarray, delays: Sequence[int]):
        from bevy_ggrs_tpu.session.input_queue import InputQueue

        zero = np.asarray(zero)
        self._zero = zero
        self._num_players = len(delays)
        self.queues = [InputQueue(zero, int(d)) for d in delays]
        self.disc = np.full(
            (self._num_players,), NEVER_DISCONNECTED, dtype=np.int32
        )

    def discard_before(self, frame: int) -> None:
        for q in self.queues:
            q.discard_before(frame)

    def min_confirmed(self, connected=None) -> int:
        frames = [
            q.last_confirmed_frame
            for h, q in enumerate(self.queues)
            if connected is None or connected[h]
        ]
        return min(frames) if frames else NULL_FRAME

    def gather(
        self, frame: int, disc_frames: Optional[Sequence[int]] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        from bevy_ggrs_tpu.schedule import CONFIRMED, DISCONNECTED, PREDICTED

        P = self._num_players
        bits = np.empty((P,) + self._zero.shape, self._zero.dtype)
        status = np.empty((P,), np.int32)
        for h, q in enumerate(self.queues):
            b, is_confirmed = q.input(frame)
            bits[h] = b
            if disc_frames is not None and frame >= disc_frames[h]:
                status[h] = DISCONNECTED
            else:
                status[h] = CONFIRMED if is_confirmed else PREDICTED
        return bits, status

    frontier = _frontier

    def advance(
        self, tracker, frame: int, local_handles: Sequence[int],
        local_bits: Sequence[np.ndarray], max_prediction: int,
        resim_from: int, gc_cap: int, echo_locals: bool = False,
    ):
        """:meth:`NativeQueueSet.advance` as the sequence of primitives a
        session used to make, one call a step: the reference."""
        stored = []
        for h, b in zip(local_handles, local_bits):
            q = self.queues[h]
            target = q.add_local_input(frame, b)
            if echo_locals:
                echoed = [(f, q.confirmed(f)) for f in range(frame, target + 1)]
                stored.append([e for e in echoed if e[1] is not None])
        load, start = NULL_FRAME, min(resim_from, frame)
        if tracker is not None and tracker.first_incorrect != NULL_FRAME:
            load = max(tracker.first_incorrect, frame - max_prediction)
            start = min(load, frame)
        n = frame - start + 1
        bits = np.empty((n, self._num_players) + self._zero.shape,
                        self._zero.dtype)
        status = np.empty((n, self._num_players), np.int32)
        for i in range(n):
            if tracker is not None and i == n - 1:
                tracker.clear_first_incorrect()
            bits[i], status[i] = self.gather(start + i, self.disc)
            if tracker is not None:
                tracker.record_used(start + i, bits[i], status[i])
        frontier = self.frontier()  # a discard does not move it
        horizon = min(frontier[0], gc_cap)
        self.discard_before(horizon)
        if tracker is not None:
            tracker.discard_before(horizon)
        return (start, load, bits, status, stored) + frontier

    def ingest(
        self, tracker, handle: int, start_frame: int, num: int,
        payload: bytes,
    ) -> Tuple[int, bool, int, List[int]]:
        """:meth:`NativeQueueSet.ingest` by the primitives: the reference."""
        q = self.queues[handle]
        nbytes = self._zero.nbytes
        redundant, gap = 0, False
        for i in range(min(num, len(payload) // nbytes if nbytes else 0)):
            frame = start_frame + i
            if frame != q.last_confirmed_frame + 1:
                if frame <= q.last_confirmed_frame:
                    redundant += 1
                    continue  # redundant resend
                gap = True
                break  # gap (loss beyond span): wait for the next resend
            q.add_input(frame, np.frombuffer(
                payload, self._zero.dtype, self._zero.size, i * nbytes
            ).reshape(self._zero.shape))
            if tracker is not None:
                tracker.note_confirmed(handle, frame, q.confirmed(frame))
        return (redundant, gap) + self.frontier()


# ---------------------------------------------------------------------------
# Trackers
# ---------------------------------------------------------------------------


class NativeTracker:
    def __init__(self, num_players: int, zero: np.ndarray):
        zero = np.asarray(zero)
        self._P = int(num_players)
        self._dtype = zero.dtype
        self._shape = zero.shape
        self._nbytes = zero.nbytes
        self._ptr = _lib.ggrs_rt_new(self._P, self._nbytes)

    def _in_one(self, bits) -> np.ndarray:
        arr = np.asarray(bits, dtype=self._dtype).reshape(self._shape)
        return np.ascontiguousarray(arr.reshape(-1)).view(np.uint8)

    def record_used(self, frame: int, bits: np.ndarray, status: np.ndarray) -> None:
        b = np.asarray(bits, dtype=self._dtype).reshape((self._P,) + self._shape)
        s = np.ascontiguousarray(np.asarray(status, dtype=np.int32))
        _lib.ggrs_rt_record_used(
            self._ptr, int(frame),
            _u8p(np.ascontiguousarray(b.reshape(-1)).view(np.uint8)), _i32p(s)
        )

    def note_confirmed(self, handle: int, frame: int, bits) -> None:
        _lib.ggrs_rt_note_confirmed(
            self._ptr, int(handle), int(frame), _u8p(self._in_one(bits))
        )

    @property
    def first_incorrect(self) -> int:
        return int(_lib.ggrs_rt_first_incorrect(self._ptr))

    def clear_first_incorrect(self) -> None:
        _lib.ggrs_rt_clear_first_incorrect(self._ptr)

    def get_used(self, frame: int):
        flat = np.empty(self._P * self._nbytes, dtype=np.uint8)
        status = np.empty((self._P,), dtype=np.int32)
        got = _lib.ggrs_rt_get_used(self._ptr, int(frame), _u8p(flat), _i32p(status))
        if not got:
            return None
        return flat.view(self._dtype).reshape((self._P,) + self._shape), status

    def discard_before(self, frame: int) -> None:
        _lib.ggrs_rt_discard_before(self._ptr, int(frame))

    def __del__(self):
        try:
            if self._ptr:
                _lib.ggrs_rt_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


class PyTracker:
    def __init__(self, num_players: int, zero: np.ndarray):
        from bevy_ggrs_tpu.schedule import CONFIRMED

        self._P = int(num_players)
        self._confirmed = CONFIRMED
        self._used = {}
        self._first_incorrect = NULL_FRAME

    def record_used(self, frame: int, bits: np.ndarray, status: np.ndarray) -> None:
        self._used[int(frame)] = (np.array(bits, copy=True), np.array(status, copy=True))

    def note_confirmed(self, handle: int, frame: int, bits) -> None:
        used = self._used.get(int(frame))
        if used is None:
            return
        used_bits, used_status = used
        if used_status[handle] != self._confirmed and not np.array_equal(
            used_bits[handle], np.asarray(bits, dtype=used_bits.dtype)
        ):
            if self._first_incorrect == NULL_FRAME or frame < self._first_incorrect:
                self._first_incorrect = int(frame)

    @property
    def first_incorrect(self) -> int:
        return self._first_incorrect

    def clear_first_incorrect(self) -> None:
        self._first_incorrect = NULL_FRAME

    def get_used(self, frame: int):
        return self._used.get(int(frame))

    def discard_before(self, frame: int) -> None:
        for f in [f for f in self._used if f < frame]:
            del self._used[f]


# ---------------------------------------------------------------------------
# Factories
# ---------------------------------------------------------------------------


def make_queue_set(zero: np.ndarray, delays: Sequence[int], window: int = 0):
    """``window``: the most frames one :meth:`advance` may gather
    (``max_prediction + 1``); 0 for a set driven by the primitives alone."""
    if available():
        return NativeQueueSet(np.asarray(zero), delays, window)
    return PyQueueSet(np.asarray(zero), delays)


def make_tracker(num_players: int, zero: np.ndarray):
    if available():
        return NativeTracker(num_players, np.asarray(zero))
    return PyTracker(num_players, np.asarray(zero))
