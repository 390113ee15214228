"""Per-peer connection state machine (the ggrs UdpProtocol analog).

One :class:`PeerEndpoint` per remote address. Owns the sync handshake
(nonce-echo roundtrips before the session reports Running —
`/root/reference/src/ggrs_stage.rs:202,244` gates on that), pending-output
input spans with redundant resend until acked, ping measurement via
quality report/reply, frame-advantage exchange for time sync, keepalives,
and disconnect detection with the interrupt/resume event pair the reference
examples print (`examples/box_game/box_game_p2p.rs:107-111`).

All timing flows through an explicit ``now`` (seconds) so the loopback
transport's virtual clock drives everything deterministically in tests.
"""

from __future__ import annotations

import enum
from bisect import bisect_left, bisect_right
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.session.common import (
    EventKind,
    NetworkStats,
    SessionEvent,
    NULL_FRAME,
)
from bevy_ggrs_tpu.utils.metrics import null_metrics

NUM_SYNC_ROUNDTRIPS = 5
SYNC_RETRY_INTERVAL = 0.2
# Unanswered sync requests back off exponentially (base interval doubling per
# failure) up to this cap, with 0-25% jitter so two peers restarting together
# don't stay phase-locked. Progress (any SyncReply) resets the backoff.
SYNC_RETRY_MAX = 5.0
QUALITY_REPORT_INTERVAL = 0.2
KEEP_ALIVE_INTERVAL = 0.2
# (Checksum-exchange cadence is session config: P2PSession.desync_interval,
# set via SessionBuilder.with_desync_detection — the endpoint just carries
# whatever reports the session hands it.)
DEFAULT_DISCONNECT_TIMEOUT = 2.0
DEFAULT_DISCONNECT_NOTIFY_START = 0.5
# Mismatched-version datagrams from one peer before VERSION_MISMATCH fires
# (small enough to trigger well inside the sync retry window, large enough
# that one stray/spoofed datagram doesn't raise a false alarm).
VERSION_MISMATCH_THRESHOLD = 5
# Mismatched config digests (SyncRequest/SyncReply, v4) before a
# CONFIG_MISMATCH event fires. Lower than the version threshold: these
# arrive inside well-formed same-version handshake datagrams, so two
# consistent sightings already rule out a stray spoof.
CONFIG_MISMATCH_THRESHOLD = 2
# Max frames per InputMsg: keeps the wire span well under the uint16 field
# and one MTU even for late-joining spectators catching up on long history.
MAX_INPUT_SPAN = 120


class PeerState(enum.Enum):
    SYNCHRONIZING = "synchronizing"
    RUNNING = "running"
    DISCONNECTED = "disconnected"


class UnackedInputs:
    """One handle's unacked inputs, kept as the bytes they go out as:
    ``rows`` holds ``size`` bytes a frame in the order of ``frames``
    (ascending), so a datagram's payload is a slice of it and an input is
    serialised once, when it is queued. ``len()`` is the frames held."""

    __slots__ = ("frames", "rows", "size")

    def __init__(self, size: int):
        self.frames: List[int] = []
        self.rows = bytearray()
        self.size = size

    def __len__(self) -> int:
        return len(self.frames)

    def put(self, frame: int, row: bytes) -> None:
        if len(row) != self.size:
            raise ValueError(
                f"an input of {len(row)} bytes among inputs of {self.size}"
            )
        frames = self.frames
        if not frames or frame > frames[-1]:
            # The steady state: the next frame (after a gap, a later one).
            frames.append(frame)
            self.rows += row
            return
        # A refill below the start, a relay behind what is held, an
        # overwrite: the row goes where its frame sorts.
        i = bisect_left(frames, frame)
        at = i * self.size
        if frames[i] == frame:
            self.rows[at : at + self.size] = row
        else:
            frames.insert(i, frame)
            self.rows[at:at] = row

    def drop_first(self, n: int) -> None:
        del self.frames[:n]
        del self.rows[: n * self.size]


class PeerEndpoint:
    def __init__(
        self,
        addr,
        rng: np.random.RandomState,
        disconnect_timeout: float = DEFAULT_DISCONNECT_TIMEOUT,
        disconnect_notify_start: float = DEFAULT_DISCONNECT_NOTIFY_START,
        metrics=None,
        config_digest: int = 0,
    ):
        self.addr = addr
        # Session-config digest advertised in (and checked against) every
        # sync handshake leg: the input-predictor weight content hash, 0 =
        # prediction off. See on_message for the refusal semantics.
        self.config_digest = int(config_digest) & 0xFFFFFFFFFFFFFFFF
        self.state = PeerState.SYNCHRONIZING
        self.metrics = metrics if metrics is not None else null_metrics
        self._rng = rng
        self.disconnect_timeout = disconnect_timeout
        self.disconnect_notify_start = disconnect_notify_start

        self._sync_remaining = NUM_SYNC_ROUNDTRIPS
        self._sync_nonce: Optional[int] = None
        self._last_sync_sent = -1e9
        self._sync_failures = 0  # unanswered sync sends (drives backoff)
        # True on endpoints the session re-created to chase a dead peer
        # (reconnect_peer): lets advance_frame skip queuing inputs to a peer
        # that may never come back, and marks the eventual SYNCHRONIZED as a
        # rejoin rather than a first join.
        self.reconnecting = False

        # Outgoing input spans, per local handle (unacked).
        self._pending_output: Dict[int, UnackedInputs] = {}
        # Highest frame actually TRANSMITTED per handle: bounds acceptable
        # acks (a peer cannot have received what we never sent).
        self._max_sent: Dict[int, int] = {}
        # Latest ack VALUE the peer claimed per handle (unclamped — see
        # _ack/refill_range for the ack-corruption healing loop).
        self._last_ack_rx: Dict[int, int] = {}
        # Handles we relay on behalf of a disconnected peer: the generic
        # piggybacked ack in InputMsg covers only the sender's OWN handles,
        # so relayed handles are trimmed exclusively by explicit InputAcks.
        self._relay_handles: set = set()

        self._last_recv = 0.0
        self._last_send = -1e9
        self._last_quality_sent = -1e9
        self._interrupted = False

        self.ping_ms = 0.0
        self.remote_frame = NULL_FRAME
        self.remote_advantage = 0  # peer's own advantage estimate, in frames
        self.bytes_sent = 0
        self._send_window: List[Tuple[float, int]] = []  # (time, nbytes)

        self.outbox: List[bytes] = []
        self.events: List[SessionEvent] = []

        # Remote checksum reports for desync detection: frame -> checksum.
        self.remote_checksums: Dict[int, int] = {}

        # Supervisor-bound control messages (StateRequest / StateChunk):
        # the session drains these into its own control inbox each poll.
        self.control_inbox: List[proto.Message] = []

        # Version-skew accounting (the datagrams themselves are dropped).
        self.version_mismatches = 0
        self._version_mismatch_reported = False
        # v5 data-plane CRC drops: corrupt datagrams detected by the
        # trailer check. Dropped like loss (redundant spans re-deliver);
        # counted so wire corruption is a visible rate, not silent.
        self.data_crc_drops = 0
        # Config-digest skew accounting (handshake legs refused, typed).
        self.config_mismatches = 0
        self._config_mismatch_reported = False

    # ------------------------------------------------------------------

    def _emit(self, kind: EventKind, data=None) -> None:
        self.events.append(SessionEvent(kind, addr=self.addr, data=data))

    def _send(self, msg: proto.Message, now: float) -> None:
        self._send_bytes(proto.encode(msg), now)

    def _send_bytes(self, data: bytes, now: float) -> None:
        self.metrics.count("datagrams_out")
        self.outbox.append(data)
        self.bytes_sent += len(data)
        self._send_window.append((now, len(data)))
        if len(self._send_window) > 4096:  # bound even if stats() never runs
            self._send_window = [
                (t, n) for t, n in self._send_window if now - t <= 2.0
            ]
        self._last_send = now

    # ------------------------------------------------------------------

    def poll(self, now: float, local_frame: int, local_advantage: int) -> None:
        """Drive timers: sync retries, quality reports, keepalives,
        disconnect detection."""
        if self.state == PeerState.SYNCHRONIZING:
            interval = min(
                SYNC_RETRY_INTERVAL * (2.0 ** self._sync_failures),
                SYNC_RETRY_MAX,
            ) * (1.0 + 0.25 * float(self._rng.random_sample()))
            if now - self._last_sync_sent >= interval:
                if self._last_sync_sent > -1e9:
                    self._sync_failures += 1  # previous request went unanswered
                self._sync_nonce = int(self._rng.randint(0, 2**31))
                self._send(
                    proto.SyncRequest(self._sync_nonce, self.config_digest),
                    now,
                )
                self._last_sync_sent = now
            return
        if self.state == PeerState.DISCONNECTED:
            return

        idle = now - self._last_recv
        if idle > self.disconnect_timeout:
            self.state = PeerState.DISCONNECTED
            self._pending_output.clear()  # nothing will ever ack these
            self._emit(EventKind.DISCONNECTED)
            return
        if idle > self.disconnect_notify_start and not self._interrupted:
            self._interrupted = True
            self._emit(
                EventKind.NETWORK_INTERRUPTED,
                data={"disconnect_timeout": self.disconnect_timeout},
            )

        if now - self._last_quality_sent >= QUALITY_REPORT_INTERVAL:
            self._send(
                proto.QualityReport(int(now * 1000) & 0xFFFFFFFF, local_advantage),
                now,
            )
            self._last_quality_sent = now
        if now - self._last_send >= KEEP_ALIVE_INTERVAL:
            self._send(proto.KeepAlive(), now)

    # ------------------------------------------------------------------

    def on_message(
        self,
        msg: proto.Message,
        now: float,
        on_inputs: Callable[[proto.InputMsg], None],
    ) -> None:
        if isinstance(msg, proto.InputMsg):
            self.on_input(now, msg.ack_frame, msg.sender_frame, msg.advantage)
            on_inputs(msg)
            return
        self._note_received(now)
        if isinstance(msg, proto.SyncRequest):
            # Typed refusal on config skew: no reply — the mismatched
            # peer's handshake can never complete against us (and ours
            # never completes against it, see the SyncReply leg), so
            # neither side reaches RUNNING with divergent predictor
            # weights. The event names both digests for the operator.
            if msg.config_digest != self.config_digest:
                self.note_config_mismatch(msg.config_digest)
                return
            self._send(proto.SyncReply(msg.nonce, self.config_digest), now)
        elif isinstance(msg, proto.SyncReply):
            if msg.config_digest != self.config_digest:
                self.note_config_mismatch(msg.config_digest)
                return
            if (
                self.state == PeerState.SYNCHRONIZING
                and msg.nonce == self._sync_nonce
            ):
                self._sync_remaining -= 1
                self._last_sync_sent = -1e9  # send next roundtrip immediately
                self._sync_failures = 0  # progress: reset the backoff
                if self._sync_remaining <= 0:
                    self.state = PeerState.RUNNING
                    self._last_recv = now
                    self._emit(EventKind.SYNCHRONIZED)
                else:
                    self._emit(
                        EventKind.SYNCHRONIZING,
                        data={
                            "count": NUM_SYNC_ROUNDTRIPS - self._sync_remaining,
                            "total": NUM_SYNC_ROUNDTRIPS,
                        },
                    )
        elif isinstance(msg, proto.InputAck):
            self._ack(msg.handle, msg.ack_frame)
        elif isinstance(msg, proto.QualityReport):
            self.remote_advantage = msg.frame_advantage
            self._send(proto.QualityReply(msg.send_time_ms), now)
        elif isinstance(msg, proto.QualityReply):
            rtt = (int(now * 1000) & 0xFFFFFFFF) - msg.pong_time_ms
            if rtt >= 0:
                self.ping_ms = 0.8 * self.ping_ms + 0.2 * rtt if self.ping_ms else rtt
        elif isinstance(msg, proto.ChecksumReport):
            self.metrics.count("checksum_reports_rx")
            self.remote_checksums[msg.frame] = msg.checksum
            if len(self.remote_checksums) > 64:
                for f in sorted(self.remote_checksums)[:-64]:
                    del self.remote_checksums[f]
        elif isinstance(msg, (proto.StateRequest, proto.StateChunk)):
            # Recovery traffic is the supervisor's business, not the
            # endpoint's: park it for the session to drain.
            self.control_inbox.append(msg)
            if len(self.control_inbox) > 256:  # bound if nothing drains
                del self.control_inbox[:-256]
        # KeepAlive: nothing beyond the last_recv bump.

    def _note_received(self, now: float) -> None:
        self._last_recv = now
        if self._interrupted and self.state == PeerState.RUNNING:
            self._interrupted = False
            self._emit(EventKind.NETWORK_RESUMED)

    def on_input(
        self, now: float, ack_frame: int, sender_frame: int, advantage: int
    ) -> None:
        """What an ``InputMsg`` means to the endpoint, from its fields (the
        span itself is the session's): a poll that parsed the datagram in
        place calls this, ``on_message`` calls it for a decoded one."""
        self._note_received(now)
        # Latest claim, NOT a running max: a single corrupted
        # sender_frame would poison a max() forever (wedging timesync
        # and catch-up heuristics on a bogus huge frame), while under
        # plain reordering the dip lasts one datagram. Negative claims
        # are impossible (frames start at 0) and would flip the local
        # advantage past the int16 wire field, so drop those outright;
        # a bogus *positive* claim only zeroes the advantage until the
        # next genuine message overwrites it.
        if sender_frame >= 0:
            self.remote_frame = sender_frame
        self.remote_advantage = advantage
        relayed = self._relay_handles
        for h in self._pending_output:
            if h not in relayed:
                self._ack(h, ack_frame)

    def note_undecodable(self, data: bytes) -> None:
        """Called with a datagram ``decode`` rejected: if it was OUR magic at
        a different version (vs plain garbage), count it toward the skew
        alarm; if it was a v5 data-plane frame whose crc32 trailer failed,
        count it as a detected wire-corruption drop."""
        if proto.crc_mismatch(data):
            self.data_crc_drops += 1
            self.metrics.count("data_crc_drops")
            return
        skew = proto.version_mismatch(data)
        if skew is not None:
            self.note_version_mismatch(skew)

    def note_version_mismatch(self, peer_version: int) -> None:
        """Count a dropped mixed-version datagram from this peer; after
        VERSION_MISMATCH_THRESHOLD of them, emit one VERSION_MISMATCH event
        so a version-skewed peer surfaces instead of stalling sync forever
        (the datagrams stay dropped — there is no cross-version parse).

        The event only fires while the peer is failing to progress: still
        SYNCHRONIZING (the state a version-skewed peer is stuck in at
        session start), or RUNNING but interrupted (no valid traffic past
        the notify threshold — the mid-session shape, e.g. a peer that
        restarted on an upgraded binary). Datagram source addresses are
        spoofable (plain UDP, no origin auth), so an off-path attacker who
        knows a peer's addr:port could replay skewed headers; while the
        real peer is RUNNING healthily those can only be noise, and gating
        on progress silences that false alarm (round-3 advice #4).
        Counting continues either way (``network_stats`` exposes it)."""
        self.version_mismatches += 1
        stalled = (
            self.state is PeerState.SYNCHRONIZING or self._interrupted
        )
        if (
            not self._version_mismatch_reported
            and stalled
            and self.version_mismatches >= VERSION_MISMATCH_THRESHOLD
        ):
            self._version_mismatch_reported = True
            self._emit(
                EventKind.VERSION_MISMATCH,
                data={
                    "peer_version": peer_version,
                    "local_version": proto.VERSION,
                    "count": self.version_mismatches,
                },
            )

    def note_config_mismatch(self, peer_digest: int) -> None:
        """Count a refused handshake leg whose config digest disagreed
        with ours; after CONFIG_MISMATCH_THRESHOLD of them, emit one
        CONFIG_MISMATCH event. Unlike version skew there is no progress
        gate: mismatched digests arrive in datagrams we fully parsed at
        our own protocol version, and the refusal itself is what keeps
        the peer stalled — the operator needs the signal immediately."""
        self.config_mismatches += 1
        self.metrics.count("config_mismatch_datagrams")
        if (
            not self._config_mismatch_reported
            and self.config_mismatches >= CONFIG_MISMATCH_THRESHOLD
        ):
            self._config_mismatch_reported = True
            self._emit(
                EventKind.CONFIG_MISMATCH,
                data={
                    "local_digest": self.config_digest,
                    "peer_digest": int(peer_digest) & 0xFFFFFFFFFFFFFFFF,
                    "count": self.config_mismatches,
                },
            )

    def _ack(self, handle: int, ack_frame: int) -> None:
        pending = self._pending_output.get(handle)
        if pending is None:
            return
        # Latest CLAIMED frontier, unclamped: a corrupted (lying-high) ack
        # trims pending below, but the next genuine ack then lands under
        # the trimmed buffer and refill_range() re-queues the lost frames
        # from session history (self-healing against ack corruption).
        self._last_ack_rx[handle] = ack_frame
        # A peer cannot legitimately ack frames we never TRANSMITTED: a
        # lying ack-ahead (buggy peer or source spoof) would otherwise trim
        # input history before its first send and permanently stall the
        # session. Clamp to the transmitted frontier.
        ack_frame = min(ack_frame, self._max_sent.get(handle, -1))
        frames = pending.frames
        if frames and frames[0] <= ack_frame:
            pending.drop_first(bisect_right(frames, ack_frame))

    # ------------------------------------------------------------------

    def queue_input(
        self, handle: int, frame: int, bits: np.ndarray, relay: bool = False
    ) -> None:
        self.queue_row(handle, frame, np.asarray(bits).tobytes(), relay)

    def queue_row(
        self, handle: int, frame: int, row: bytes, relay: bool = False
    ) -> None:
        """:meth:`queue_input` for an input already serialised (a session
        that queues one frame to several peers makes its bytes once)."""
        pending = self._pending_output.get(handle)
        if pending is None:
            pending = self._pending_output[handle] = UnackedInputs(len(row))
        pending.put(frame, row)
        if relay:
            self._relay_handles.add(handle)
        if self.state != PeerState.RUNNING and len(pending) > MAX_INPUT_SPAN:
            # A handshaking (reconnect) endpoint has no acks flowing, so
            # its buffer would grow as long as the peer stays away. Keep
            # only the newest span's worth: a rejoiner that far behind
            # restores the older history from a state transfer anyway.
            drop = len(pending) - MAX_INPUT_SPAN
            self.metrics.count("input_queue_drops", drop)
            pending.drop_first(drop)

    def refill_range(self, handle: int) -> Optional[Tuple[int, int]]:
        """``(start, end)`` of frames the peer still claims to need but
        that are no longer pending — the wake of a corrupted lying-high
        ack that trimmed them before the peer received them. The session
        re-queues them from its own input history; None when healthy."""
        pending = self._pending_output.get(handle)
        claimed = self._last_ack_rx.get(handle)
        if pending is None or claimed is None:
            return None
        nxt = (
            pending.frames[0] if pending
            else self._max_sent.get(handle, -1) + 1
        )
        if claimed + 1 < nxt:
            return claimed + 1, nxt
        return None

    def send_pending_inputs(
        self, now: float, local_frame: int, local_advantage: int, ack_frame: int
    ) -> None:
        """One InputMsg per local handle carrying every unacked frame —
        the redundancy that makes the protocol loss-tolerant without
        retransmit timers."""
        if self.state != PeerState.RUNNING:
            return
        for handle, pending in self._pending_output.items():
            frames = pending.frames
            held = len(frames)
            if not held:
                continue
            # A datagram is a header, a slice of the rows (all of them but
            # through a long silence) and the trailer: a frame's bytes were
            # made when it was queued. As the frames are packed, so they
            # are named: the first one's number and a count, whatever gaps
            # a relay left between them.
            for i in range(0, held, MAX_INPUT_SPAN):
                num = min(MAX_INPUT_SPAN, held - i)
                rows = pending.rows
                if num != held:
                    rows = rows[i * pending.size : (i + num) * pending.size]
                self._send_bytes(
                    proto.encode_input(
                        handle, frames[i], num, rows,
                        ack_frame, local_frame, local_advantage,
                    ),
                    now,
                )
            if frames[-1] > self._max_sent.get(handle, -1):
                self._max_sent[handle] = frames[-1]

    def force_disconnect(self) -> None:
        """Voluntary disconnect: same state transition + pending clear as
        the idle-timeout path."""
        if self.state != PeerState.DISCONNECTED:
            self.state = PeerState.DISCONNECTED
            self._pending_output.clear()
            self._emit(EventKind.DISCONNECTED)

    def send_input_ack(self, handle: int, ack_frame: int, now: float) -> None:
        self._send(proto.InputAck(handle, ack_frame), now)

    def send_checksum(self, frame: int, checksum: int, now: float) -> None:
        self._send(proto.ChecksumReport(frame, checksum), now)

    # ------------------------------------------------------------------

    def stats(self, now: float, local_frame: int) -> NetworkStats:
        window = [(t, n) for t, n in self._send_window if now - t <= 2.0]
        self._send_window = window
        kbps = sum(n for _, n in window) * 8 / 1000.0 / max(
            min(2.0, now - window[0][0]) if window else 1.0, 1e-3
        )
        return NetworkStats(
            ping_ms=self.ping_ms,
            send_queue_len=max(
                (len(p) for p in self._pending_output.values()), default=0
            ),
            kbps_sent=kbps,
            local_frames_behind=self.remote_frame - local_frame,
            remote_frames_behind=self.remote_advantage,
        )
