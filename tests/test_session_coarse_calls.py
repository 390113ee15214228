"""The session/core boundary is two coarse calls: parity of the planes.

A session crosses into its queue set + tracker at most twice a frame:
``advance`` (all of ``advance_frame()``'s work on them) and ``ingest`` (all
of one ``InputMsg``'s). Three implementations must agree bitwise on seeded
random schedules:

- the native plane's two calls (``ggrs_qs_advance`` / ``ggrs_qs_ingest``),
- the Python plane's (``PyQueueSet.advance`` / ``.ingest``),
- the sequence of primitives a session made before there were coarse calls,
  written here as the parent's ``_advance_frame`` / ``_on_remote_inputs``
  made them, one call a step, on a native queue set.

Compared after every step: the segment (frames, bits, status, the load
frame), the echoed local inputs, ``first_incorrect``, every queue's last
confirmed frame, the cached confirmed frame against a fresh
``min_confirmed``, and the counts a session derives (redundant frames, span
gaps, mispredictions, withheld frames). A returned ``bits`` array is also
held unchanged across the next two calls: the buffers a native queue set
fills are bound once, what it hands out is a copy.
"""

import json
import os

import numpy as np
import pytest

from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.schedule import InputSpec
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
    SyncTestSession,
)
from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.session.common import InvalidRequest
from bevy_ggrs_tpu.session.requests import AdvanceFrame, LoadGameState, SaveGameState
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

pytestmark = pytest.mark.skipif(
    not ncore.available(), reason="native session core did not build"
)

NULL_FRAME = ncore.NULL_FRAME
NEVER = ncore.NEVER_DISCONNECTED
MAXPRED = 8
FPS_DT = 1.0 / 60.0

SPECS = {
    "u8": ((), np.uint8),
    "u16x2": ((2,), np.uint16),
    "f32x3": ((3,), np.float32),
}


def draw(rng, shape, dtype):
    if np.issubdtype(dtype, np.floating):
        return rng.randint(-3, 4, size=shape).astype(dtype) / dtype(4)
    return rng.randint(0, 5, size=shape).astype(dtype)


# ---------------------------------------------------------------------------
# The three implementations behind one face


class Coarse:
    """A queue set + tracker driven through the two coarse calls."""

    def __init__(self, native, zero, delays, window):
        P = len(delays)
        if native:
            self.qs = ncore.NativeQueueSet(zero, delays, window)
            self.tr = ncore.NativeTracker(P, zero)
        else:
            self.qs = ncore.PyQueueSet(zero, delays)
            self.tr = ncore.PyTracker(P, zero)

    def set_disc(self, disc):
        self.qs.disc[:] = disc
        return self.qs.frontier()

    def advance(self, frame, handles, bits, gc_floor):
        return self.qs.advance(
            self.tr, frame, handles, bits, MAXPRED, frame,
            min(frame - 2 * MAXPRED, gc_floor), echo_locals=True,
        )

    def ingest(self, handle, start, num, payload):
        return self.qs.ingest(self.tr, handle, start, num, payload)


class Primitives:
    """The parent's session code, call by call (``session/p2p.py`` at
    13aea45: ``_advance_frame``, ``_advance_request``, ``_gc``,
    ``_on_remote_inputs``), returning what the coarse calls return."""

    def __init__(self, zero, delays):
        self.zero = zero
        self.P = len(delays)
        self.qs = ncore.NativeQueueSet(zero, delays)
        self.tr = ncore.NativeTracker(self.P, zero)
        self.disc = [NEVER] * self.P

    def frontier(self):
        return (
            self.qs.min_confirmed([d == NEVER for d in self.disc]),
            [q.last_confirmed_frame for q in self.qs.queues],
        )

    def set_disc(self, disc):
        self.disc = [int(d) for d in disc]
        return self.frontier()

    def _request(self, frame):
        bits, status = self.qs.gather(frame, self.disc)
        self.tr.record_used(frame, bits, status)
        return bits, status

    def advance(self, frame, handles, bits, gc_floor):
        stored = []
        for h, b in zip(handles, bits):
            q = self.qs.queues[h]
            target = q.add_local_input(frame, b)
            echoed = []
            for f in range(max(0, target - (q.delay or 0)), target + 1):
                got = q.confirmed(f)
                if got is not None:
                    echoed.append((f, got))
            stored.append(echoed)
        rows = []
        start, load = frame, NULL_FRAME
        rollback_to = self.tr.first_incorrect
        if rollback_to != NULL_FRAME:
            floor = frame - MAXPRED
            if rollback_to < floor:
                rollback_to = floor
            load = start = rollback_to
            for f in range(rollback_to, frame):
                rows.append(self._request(f))
            self.tr.clear_first_incorrect()
        rows.append(self._request(frame))
        confirmed, _ = self.frontier()
        horizon = min(confirmed, frame + 1 - 2 * MAXPRED - 1, gc_floor)
        self.qs.discard_before(horizon)
        self.tr.discard_before(horizon)
        return (
            start, load, np.stack([b for b, _ in rows]),
            np.stack([s for _, s in rows]), stored,
        ) + self.frontier()

    def ingest(self, handle, start, num, payload):
        q = self.qs.queues[handle]
        msg = proto.InputMsg(handle, start, payload, num, 0, 0, 0)
        redundant, gap = 0, False
        for frame, bits in proto.unpack_input_span(
            msg, np.dtype(self.zero.dtype), self.zero.shape
        ):
            if frame != q.last_confirmed_frame + 1:
                if frame <= q.last_confirmed_frame:
                    redundant += 1
                    continue
                gap = True
                break
            q.add_input(frame, bits)
            self.tr.note_confirmed(handle, frame, q.confirmed(frame))
        return (redundant, gap) + self.frontier()


def canon(x):
    """A result as plain data: arrays by dtype, shape and bytes."""
    if isinstance(x, np.ndarray):
        return (str(x.dtype), x.shape, x.tobytes())
    if isinstance(x, (list, tuple)):
        return [canon(v) for v in x]
    if isinstance(x, (bool, np.bool_)):
        return bool(x)
    if isinstance(x, (int, np.integer)):
        return int(x)
    return x


# ---------------------------------------------------------------------------
# Seeded random schedules, as a session's life makes them

SCENARIOS = {
    # remote lag (frames), stall chance, gap chance, past-span chance,
    # whether a remote player disconnects and is relayed later
    "steady": dict(lag=(1, 3), stall=0.0, gap=0.05, past=0.05, disconnect=False),
    "window": dict(lag=(5, 8), stall=0.02, gap=0.05, past=0.05, disconnect=False),
    "lossy": dict(lag=(1, 6), stall=0.01, gap=0.35, past=0.25, disconnect=False),
    "disconnect": dict(lag=(2, 5), stall=0.0, gap=0.1, past=0.1, disconnect=True),
}


def run_schedule(players, spec, delay, scenario, seed, frames=220):
    shape, dtype = SPECS[spec]
    zero = np.zeros(shape, dtype)
    cfg = SCENARIOS[scenario]
    rng = np.random.RandomState(seed)
    local, remotes = [0], list(range(1, players))
    delays = [delay if h in local else 0 for h in range(players)]
    impls = [
        Coarse(True, zero, delays, MAXPRED + 1),
        Coarse(False, zero, delays, MAXPRED + 1),
        Primitives(zero, delays),
    ]

    # The remote players' true inputs, changing often enough to mispredict.
    true = {h: [] for h in remotes}
    for h in remotes:
        cur = draw(rng, shape, dtype)
        for _ in range(frames + 64):
            if rng.rand() < 0.4:
                cur = draw(rng, shape, dtype)
            true[h].append(cur)

    disc = [NEVER] * players
    dead, dead_at, relay_from = None, None, None
    if cfg["disconnect"]:
        dead, dead_at = remotes[-1], 40
        relay_from = dead_at + 2 * MAXPRED - 3
    confirmed, last = NULL_FRAME, [NULL_FRAME] * players
    counts = dict(redundant=0, gaps=0, mispredictions=0, withheld=0,
                  clamped=0, depth1=0, at_window=0, disconnected_rows=0)
    kept = []  # (array a caller kept, its bytes when it was returned)
    frame = 0
    stalled = {h: 0 for h in remotes}

    def same(results, what):
        a, b, c = (canon(r) for r in results)
        assert a == b, f"{what}: native != python plane"
        assert a == c, f"{what}: coarse calls != the parent's primitives"
        for impl in impls:
            qs = impl.qs
            mask = [d == NEVER for d in disc]
            assert results[0][-2] == qs.min_confirmed(mask), what
            assert list(results[0][-1]) == [
                q.last_confirmed_frame for q in qs.queues
            ], what
        assert len({impl.tr.first_incorrect for impl in impls}) == 1, what
        for arr, was in kept:
            assert arr.tobytes() == was, f"{what}: a returned array changed"

    def keep(arr):
        kept.append((arr, arr.tobytes()))
        del kept[:-6]  # three arrays a call: two calls back

    for tick in range(frames):
        # Remote input spans, as the wire delivers them.
        for h in remotes:
            if h == dead and (relay_from is None or frame < relay_from):
                if frame >= dead_at:
                    continue
            if stalled[h]:
                stalled[h] -= 1
                continue
            if rng.rand() < cfg["stall"]:
                stalled[h] = MAXPRED + 3  # long enough to withhold frames
                continue
            have = max(0, frame - rng.randint(cfg["lag"][0], cfg["lag"][1] + 1))
            roll = rng.rand()
            if roll < cfg["past"] and last[h] >= 3:
                start = max(0, last[h] - rng.randint(2, 8))
                num = min(rng.randint(1, 4), last[h] - start + 1)  # wholly past
            elif roll < cfg["past"] + cfg["gap"]:
                start = last[h] + 1 + rng.randint(1, 4)  # a gap
                num = rng.randint(1, 5)
            else:
                start = max(0, last[h] + 1 - rng.randint(0, 7))  # redundant prefix
                num = max(0, have - start + 1)
            num = min(num, 12)
            payload = b"".join(
                np.ascontiguousarray(true[h][start + i]).tobytes()
                for i in range(num)
            )
            if rng.rand() < 0.1:
                payload = payload[:-1]  # a truncated datagram body
            results = [im.ingest(h, start, num, payload) for im in impls]
            same(results, f"ingest h={h} start={start} num={num} @tick {tick}")
            redundant, gap, confirmed, last = results[0]
            counts["redundant"] += redundant
            counts["gaps"] += bool(gap)

        if dead is not None and frame == dead_at and disc[dead] == NEVER:
            disc[dead] = frame
            results = [im.set_disc(disc) for im in impls]
            assert canon(results[0]) == canon(results[1]) == canon(results[2])
            confirmed, last = results[0]

        # Back-pressure, where the session tests it: on the cached frame.
        if frame - confirmed > MAXPRED:
            counts["withheld"] += 1
            continue
        bits = [draw(rng, shape, dtype)]
        results = [im.advance(frame, local, bits, NEVER) for im in impls]
        same(results, f"advance frame={frame} @tick {tick}")
        start, load, seg_bits, seg_status, stored, confirmed, last = results[0]
        assert len(seg_bits) == frame - start + 1 <= MAXPRED + 1
        if load != NULL_FRAME:
            counts["mispredictions"] += 1
            counts["depth1"] += frame - load == 1
            counts["at_window"] += frame - load == MAXPRED
            counts["clamped"] += load == frame - MAXPRED
        counts["disconnected_rows"] += int((seg_status == 2).sum())
        assert stored[0][-1][0] == frame + delay
        keep(seg_bits)
        keep(seg_status)
        keep(stored[0][-1][1])
        frame += 1
    counts["frames"] = frame
    return counts


@pytest.mark.parametrize("scenario", list(SCENARIOS))
@pytest.mark.parametrize("delay", [0, 2])
@pytest.mark.parametrize("spec", list(SPECS))
@pytest.mark.parametrize("players", [2, 4])
def test_three_implementations_agree(players, spec, delay, scenario):
    seed = sum(ord(c) for c in f"{players}{spec}{delay}{scenario}")
    counts = run_schedule(players, spec, delay, scenario, seed)
    # The schedule reached what it was written to reach.
    assert counts["frames"] > 60
    assert counts["redundant"] > 0
    assert counts["mispredictions"] > 0
    if scenario == "window":
        assert counts["withheld"] > 0
    if scenario == "lossy":
        assert counts["gaps"] > 0
    if scenario == "disconnect":
        # Relayed inputs contradict frames frozen deeper than the ring
        # reaches (the floor clamp), and a rollback's segment holds frames
        # on both sides of the disconnect frame.
        assert counts["clamped"] > 0
        assert counts["disconnected_rows"] > 0


def test_rollback_depths_are_all_reached():
    """Over the scenarios' seeds: a rollback one frame deep, one exactly
    at the prediction window, one past it (clamped to the floor)."""
    total = dict(depth1=0, at_window=0, clamped=0)
    for scenario in ("steady", "window", "disconnect"):
        for seed in range(3):
            counts = run_schedule(2, "u8", 0, scenario, 1000 + seed)
            for k in total:
                total[k] += counts[k]
    assert all(total.values()), total


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_discarded_frame_raises_where_it_did(native):
    """A segment that reaches behind the discard horizon is an
    InvalidRequest naming the frame, from both planes' ``advance``."""
    zero = np.zeros((), np.uint8)
    impl = Coarse(native, zero, [0, 0], 40)
    for f in range(30):
        impl.qs.queues[1].add_input(f, np.uint8(f % 5))
        impl.advance(f, [0], [np.uint8(1)], NEVER)
    # History before frame 13 is gone (30 - 2 * MAXPRED - 1); ask for it.
    with pytest.raises(InvalidRequest, match="frame 5 was discarded"):
        impl.qs.advance(None, 30, [0], [np.uint8(1)], MAXPRED, 5, 0)


def test_a_segment_longer_than_the_bound_buffers_is_refused():
    """The native buffers are bound once, ``window`` frames long: a call
    that could gather more is refused before anything crosses."""
    zero = np.zeros((), np.uint8)
    qs = ncore.NativeQueueSet(zero, [0, 0], MAXPRED + 1)
    with pytest.raises(InvalidRequest, match="bound for"):
        qs.advance(None, 20, [0], [np.uint8(1)], MAXPRED, 20 - MAXPRED - 1, 0)
    with pytest.raises(InvalidRequest, match="bound for"):
        qs.advance(None, 20, [0], [np.uint8(1)], MAXPRED + 1, 20, 0)
    assert qs.queues[0].last_confirmed_frame == NULL_FRAME  # nothing was added


# ---------------------------------------------------------------------------
# SyncTest: the same call for its check_distance + 1 gathers


def parent_synctest_requests(qs, frame, check_distance, bits):
    """``SyncTestSession.advance_frame`` at 13aea45, on primitives."""
    P = len(qs.queues)
    for h, q in enumerate(qs.queues):
        q.add_local_input(frame, bits[h])

    def request(f):
        got, _ = qs.gather(f)
        return AdvanceFrame(bits=got, status=np.zeros((P,), np.int32))

    requests = [SaveGameState(frame), request(frame)]
    if check_distance > 0 and frame >= check_distance:
        requests.append(LoadGameState(frame - check_distance))
        for f in range(frame - check_distance, frame + 1):
            requests += [SaveGameState(f), request(f)]
    qs.discard_before(frame + 1 - check_distance - 1)
    return requests


def canon_requests(requests):
    out = []
    for r in requests:
        if isinstance(r, AdvanceFrame):
            out.append(("advance", canon(r.bits), canon(r.status)))
        else:
            out.append((type(r).__name__, r.frame))
    return out


@pytest.mark.parametrize("check_distance", [0, 2, MAXPRED])
@pytest.mark.parametrize("delay", [0, 2])
@pytest.mark.parametrize("spec", list(SPECS))
@pytest.mark.parametrize("players", [2, 4])
def test_synctest_requests_are_the_parents(
    players, spec, delay, check_distance, monkeypatch
):
    shape, dtype = SPECS[spec]
    input_spec = InputSpec(shape=shape, dtype=dtype)
    rng = np.random.RandomState(players * 100 + delay * 10 + check_distance)
    native = SyncTestSession(
        players, input_spec, check_distance, MAXPRED, delay)
    monkeypatch.setattr(ncore, "available", lambda: False)
    python = SyncTestSession(
        players, input_spec, check_distance, MAXPRED, delay)
    monkeypatch.undo()
    assert isinstance(native._qset, ncore.NativeQueueSet)
    assert isinstance(python._qset, ncore.PyQueueSet)
    parent = ncore.NativeQueueSet(np.zeros(shape, dtype), [delay] * players)
    kept = []
    for frame in range(40):
        bits = [draw(rng, shape, dtype) for _ in range(players)]
        if frame == 20:  # a checkpoint in the middle
            for i, s in enumerate((native, python)):
                sd = s.state_dict()
                fresh = SyncTestSession(
                    players, input_spec, check_distance, MAXPRED, delay)
                if i == 1:
                    monkeypatch.setattr(ncore, "available", lambda: False)
                    fresh = SyncTestSession(
                        players, input_spec, check_distance, MAXPRED, delay)
                    monkeypatch.undo()
                fresh.load_state_dict(sd)
                if i == 0:
                    native = fresh
                else:
                    python = fresh
        lists = []
        for s in (native, python):
            for h in range(players):
                s.add_local_input(h, bits[h])
            lists.append(s.advance_frame())
        want = canon_requests(
            parent_synctest_requests(parent, frame, check_distance, bits))
        if ("LoadGameState", frame - check_distance) in want:
            # PR 34, upstream's order: the forced rollback comes first and
            # steps the new frame itself, so the parent's leading (Save,
            # Advance) of that frame goes and the rest is the parent's.
            assert want[2] == ("LoadGameState", frame - check_distance)
            assert want[:2] == want[-2:]
            want = want[2:]
        assert canon_requests(lists[0]) == want, frame
        assert canon_requests(lists[1]) == want, frame
        for arr, was in kept:
            assert arr.tobytes() == was, "a kept AdvanceFrame.bits changed"
        kept += [
            (r.bits, r.bits.tobytes()) for r in lists[0]
            if isinstance(r, AdvanceFrame)
        ]
        del kept[:-2 * (check_distance + 2)]


# ---------------------------------------------------------------------------
# Sessions over the wire: the two planes send the same bytes


class RecordingNetwork(LoopbackNetwork):
    """Keeps every datagram sent: (source, destination, bytes), in order."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.wire = []

    def _send(self, src, dst, msg):
        self.wire.append((src, dst, bytes(msg)))
        super()._send(src, dst, msg)


def run_pair(
    python_plane, monkeypatch, frames=300, players=2, delay=0,
    spec="u8", resume_at=None, seed=11,
):
    """A seeded ``P2PSession`` group over a WAN-like loopback (``wan``'s
    numbers: latency 2 f, jitter 1 f, loss 3 %), sessions only: each peer
    reports a checksum that is a function of the frame alone, so ballots
    are exchanged and compared and never disagree. Returns everything a
    peer or the wire could observe."""
    from bevy_ggrs_tpu.utils.metrics import Metrics

    shape, dtype = SPECS[spec]
    if python_plane:
        monkeypatch.setattr(ncore, "available", lambda: False)
    net = RecordingNetwork(
        latency=2 * FPS_DT, jitter=FPS_DT, loss=0.03, seed=seed)
    clock = lambda: net.now  # noqa: E731

    def build(me, sock=None):
        b = (
            SessionBuilder(InputSpec(shape=shape, dtype=dtype))
            .with_num_players(players)
            .with_max_prediction_window(MAXPRED)
            .with_input_delay(delay)
        )
        for h in range(players):
            b.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)), h)
        metrics = Metrics()
        sock = sock if sock is not None else net.socket(("peer", me))
        return b.start_p2p_session(sock, clock=clock, metrics=metrics), sock

    sessions, socks = zip(*[build(me) for me in range(players)])
    sessions = list(sessions)
    assert isinstance(
        sessions[0]._qset,
        ncore.PyQueueSet if python_plane else ncore.NativeQueueSet,
    )
    rng = np.random.RandomState(seed)
    held = [draw(rng, shape, dtype) for _ in range(players)]
    log = dict(requests=[], events=[], votes=[], withheld=0, cached=[])
    for i in range(frames):
        net.advance(FPS_DT)
        if resume_at is not None and i == resume_at:
            # Peer 0 is restored from its own checkpoint on a fresh session
            # (same socket binding), as a crashed host would be.
            sd = sessions[0].state_dict()
            sessions[0], _ = build(0, sock=socks[0])
            sessions[0].load_state_dict(sd)
        for me, s in enumerate(sessions):
            s.poll_remote_clients()
            log["events"] += [(me, e.kind, e.addr, repr(e.data)) for e in s.events()]
            for f in sorted(s._checksum_votes):
                log["votes"].append((me, f, sorted(s.checksum_votes(f).items())))
            # The cached frontier is the queues' own.
            fresh = s._qset.min_confirmed(
                [h not in s._disconnected for h in range(players)])
            assert s.confirmed_frame() == fresh
            assert s._last_confirmed == [
                q.last_confirmed_frame for q in s._queues]
            if s.current_state() != SessionState.RUNNING:
                continue
            if rng.rand() < 0.3:
                held[me] = draw(rng, shape, dtype)
            s.add_local_input(me, held[me])
            try:
                requests = s.advance_frame()
            except PredictionThreshold:
                log["withheld"] += 1
                continue
            log["requests"].append((me, canon_requests(requests)))
            for r in requests:
                if isinstance(r, SaveGameState) and s.wants_checksum(r.frame):
                    s.report_checksum(r.frame, (r.frame * 2654435761) & 0xFFFFFFFF)
    log["wire"] = net.wire
    log["counters"] = [dict(s.metrics.counters) for s in sessions]
    log["frames"] = [s.current_frame for s in sessions]
    log["first_incorrect"] = [s._tracker.first_incorrect for s in sessions]
    if python_plane:
        monkeypatch.undo()
    return log


WIRE_CASES = [
    dict(),
    dict(players=4, spec="u16x2"),
    dict(delay=2, spec="f32x3"),
    dict(resume_at=150),
    dict(resume_at=150, delay=2, players=4),
]


@pytest.mark.parametrize(
    "case", WIRE_CASES,
    ids=["-".join(f"{k}{v}" for k, v in c.items()) or "wan" for c in WIRE_CASES],
)
def test_wire_events_and_counters_equal_between_planes(case, monkeypatch):
    nat = run_pair(False, monkeypatch, **case)
    py = run_pair(True, monkeypatch, **case)
    # The run did what a WAN does to a session.
    assert min(nat["frames"]) > 200
    for name in ("mispredictions", "input_frames_redundant", "checksum_ballots"):
        assert sum(c.get(name, 0) for c in nat["counters"]) > 0, name
    assert not any(c.get("desyncs_flagged") for c in nat["counters"])
    # Every datagram: bytes, order, destination.
    assert len(nat["wire"]) == len(py["wire"])
    assert nat["wire"] == py["wire"]
    for key in ("requests", "events", "votes", "withheld", "counters",
                "frames", "first_incorrect"):
        assert nat[key] == py[key], key


def test_native_call_counter_counts_entry_points():
    """``native_calls()`` moves by one for every ``ggrs_qs_*`` /
    ``ggrs_rt_*`` entry point called, and a SyncTest frame is one call."""
    zero = np.zeros((), np.uint8)
    qs = ncore.NativeQueueSet(zero, [0, 0], MAXPRED + 1)
    tr = ncore.NativeTracker(2, zero)
    n0 = ncore.native_calls()
    qs.queues[1].add_input(0, np.uint8(1))
    assert ncore.native_calls() == n0 + 1
    qs.advance(tr, 0, [0], [np.uint8(2)], MAXPRED, 0, -16, echo_locals=True)
    qs.ingest(tr, 1, 0, 2, b"\x01\x02")
    assert ncore.native_calls() == n0 + 3
    _ = tr.first_incorrect
    assert ncore.native_calls() == n0 + 4

    s = SyncTestSession(2, InputSpec(), check_distance=2)
    for frame in range(5):
        s.add_local_input(0, np.uint8(frame))
        s.add_local_input(1, np.uint8(frame))
        n0 = ncore.native_calls()
        s.advance_frame()
        assert ncore.native_calls() == n0 + 1


def test_python_plane_reports_no_counter(monkeypatch):
    monkeypatch.setattr(ncore, "_load", lambda: None)
    assert ncore.native_calls() is None


# ---------------------------------------------------------------------------
# The input exchange, built and parsed in place: the wire is the parent's
#
# A RUNNING endpoint keeps its unacked inputs as the bytes they go out as and
# a poll parses an incoming ``T_INPUT`` datagram where it lies. Two seeded
# lobbies live through everything that touches that store (loss, a cut link
# longer than ``MAX_INPUT_SPAN``, a lying-high ack and the refill that heals
# it, a disconnect with relay, a reconnect with the supervisor's gap fill, a
# peer that never comes back, a resume from a checkpoint, a spectator that
# joins late) and are held, on both planes, to a recording made on the code
# before the store changed (``tests/input_exchange_gate.json``, written by
# ``write_gate_recording()`` from a checkout of that commit with this file
# laid over it): every datagram, event, frontier and ``network_stats``.

GATE_FILE = os.path.join(os.path.dirname(__file__), "input_exchange_gate.json")
GATE_EVERY = 30  # ticks between two samples of the frontier and the stats
GATE_CLEAN = 30  # ticks without loss: the lobby forms, then the WAN sets in
# Addresses of whole numbers: a session makes its endpoints in the order of a
# set of addresses, and only such a set's order is the same in every process.
WATCHER = (1, 0)


def peer(handle):
    return (0, handle)


GATES = {
    # A duel with a spectator on peer 0, whose acks are cut for longer than
    # MAX_INPUT_SPAN frames; a lying-high ack; peer 0 resumed from its own
    # checkpoint.
    "duel": dict(
        players=2, spec="u8", delay=0, window=MAXPRED, ticks=420, seed=23,
        timeout=4.0, cut=[(WATCHER, peer(0), 100, 260),
                          (peer(1), peer(0), 60, 72)],
        lie=(300, 1, 0), resume=340, spectator_at=0,
    ),
    # An eight-player lobby (window 12, input delay 2) with a late
    # spectator: peer 6 dies and rejoins from peer 0's checkpoint, peer 7
    # dies for good while the survivors' re-armed endpoints buffer for it.
    "lobby": dict(
        players=8, spec="u16x2", delay=2, window=12, ticks=470, seed=29,
        timeout=1.0, cut=[], lie=(50, 3, 2), resume=None, spectator_at=150,
        dies=[(6, 80, 190), (7, 260, None)],
    ),
}


class GateNetwork(RecordingNetwork):
    """Keeps every datagram sent, then drops those a cut link carries."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.cut = set()

    def _send(self, src, dst, msg):
        self.wire.append((src, dst, bytes(msg)))
        if (src, dst) not in self.cut:
            LoopbackNetwork._send(self, src, dst, msg)


def run_gate(name, python_plane, monkeypatch):
    """One gate scenario, sessions only. Returns the recording (plain data,
    as the JSON file holds it) and the wire."""
    import hashlib

    from bevy_ggrs_tpu.session.common import EventKind, NotSynchronized
    from bevy_ggrs_tpu.utils.metrics import Metrics

    cfg = GATES[name]
    players, delay, window = cfg["players"], cfg["delay"], cfg["window"]
    shape, dtype = SPECS[cfg["spec"]]
    input_spec = InputSpec(shape=shape, dtype=dtype)
    if python_plane:
        monkeypatch.setattr(ncore, "available", lambda: False)
    net = GateNetwork(latency=2 * FPS_DT, jitter=FPS_DT, seed=cfg["seed"])
    clock = lambda: net.now  # noqa: E731

    def builder():
        return (
            SessionBuilder(input_spec)
            .with_num_players(players)
            .with_max_prediction_window(window)
            .with_input_delay(delay)
            .with_disconnect_timeout(cfg["timeout"])
        )

    def build(me, sock=None):
        b = builder()
        for h in range(players):
            b.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(peer(h)), h)
        if me == 0:
            b.add_player(PlayerType.spectator(WATCHER), players + 1)
        sock = sock if sock is not None else net.socket(peer(me))
        return b.start_p2p_session(sock, clock=clock, metrics=Metrics()), sock

    sessions, socks = (list(x) for x in zip(*[build(me) for me in range(players)]))
    assert isinstance(
        sessions[0]._qset,
        ncore.PyQueueSet if python_plane else ncore.NativeQueueSet,
    )
    spectator = None
    rng = np.random.RandomState(cfg["seed"])
    held = [draw(rng, shape, dtype) for _ in range(players)]
    away = set()
    dies = {me: (at, back) for me, at, back in cfg.get("dies", [])}
    rec = dict(events=[], frontier=[], stats=[], wire=[], refills=0,
               spectator_frames=0, withheld=0)
    requests = hashlib.sha256()
    wire_hash, hashed = hashlib.sha256(), 0

    def rejoin(me, donor):
        """A restarted peer adopts the donor's session checkpoint and fills
        its own queue with its frozen last input up to the donor's frame,
        as ``SessionSupervisor`` does once the donor's state has landed
        (``supervisor.py`` ``_adopt``), here without a runner."""
        socks[me].receive_all()  # what piled up while the process was gone
        fresh, _ = build(me, sock=socks[me])
        fresh.load_state_dict(sessions[donor].state_dict())
        for h in fresh.local_handles:
            fresh._disconnected.pop(h, None)
            q = fresh._queues[h]
            frozen = np.asarray(q.last_input).copy()
            for f in range(q.last_confirmed_frame + 1, fresh.current_frame):
                q.add_input(f, frozen)
                fresh._tracker.note_confirmed(h, f, frozen)
                for addr in set(fresh._handle_addr.values()):
                    fresh._endpoints[addr].queue_input(h, f, frozen)
        fresh._refresh_frontier()
        return fresh

    for tick in range(cfg["ticks"]):
        net.advance(FPS_DT)
        net.loss = 0.03 if tick >= GATE_CLEAN else 0.0
        net.cut = {(s, d) for s, d, t0, t1 in cfg["cut"] if t0 <= tick < t1}
        for me, (at, back) in dies.items():
            if tick == at:
                away.add(me)
            if tick == back:
                away.discard(me)
                sessions[me] = rejoin(me, donor=0)
        if cfg["lie"] and tick == cfg["lie"][0]:
            _, liar, victim = cfg["lie"]
            net._send(peer(liar), peer(victim), proto.encode(
                proto.InputMsg(liar, 0, b"", 0, 1_000_000,
                               sessions[liar].current_frame, 0)))
        if cfg["resume"] is not None and tick == cfg["resume"]:
            sd = sessions[0].state_dict()
            sessions[0], _ = build(0, sock=socks[0])
            sessions[0].load_state_dict(sd)
        if tick == cfg["spectator_at"]:
            spectator = builder().start_spectator_session(
                peer(0), net.socket(WATCHER), clock=clock)
        for me, s in enumerate(sessions):
            if me in away:
                continue
            s.poll_remote_clients()
            for e in s.events():
                rec["events"].append(
                    [tick, me, e.kind.value, repr(e.addr), repr(e.data)])
                if e.kind == EventKind.DISCONNECTED and e.addr != WATCHER:
                    s.reconnect_peer(e.addr)  # the supervisor's re-arm
            if s.current_state() != SessionState.RUNNING:
                continue
            rec["refills"] += any(
                ep.refill_range(me) is not None
                for ep in s._endpoints.values())
            if rng.rand() < 0.3:
                held[me] = draw(rng, shape, dtype)
            s.add_local_input(me, held[me])
            try:
                got = s.advance_frame()
            except PredictionThreshold:
                rec["withheld"] += 1
                continue
            requests.update(repr((me, canon_requests(got))).encode())
            for r in got:
                if isinstance(r, SaveGameState) and s.wants_checksum(r.frame):
                    s.report_checksum(r.frame, (r.frame * 2654435761) & 0xFFFFFFFF)
        if spectator is not None:
            spectator.poll_remote_clients()
            for e in spectator.events():
                rec["events"].append(
                    [tick, "spec", e.kind.value, repr(e.addr), repr(e.data)])
            try:
                rec["spectator_frames"] += len(spectator.advance_frame())
            except (PredictionThreshold, NotSynchronized):
                pass
        if tick % GATE_EVERY == GATE_EVERY - 1 or tick == cfg["ticks"] - 1:
            for src, dst, data in net.wire[hashed:]:
                wire_hash.update(repr((src, dst, data)).encode())
            hashed = len(net.wire)
            rec["wire"].append([tick, hashed, wire_hash.hexdigest()[:16]])
            for me, s in enumerate(sessions):
                rec["frontier"].append([
                    tick, me, s.current_frame, s._confirmed,
                    list(s._last_confirmed), sorted(s._disconnected.items()),
                ])
                for h in s.remote_player_handles():
                    st = s.network_stats(h)
                    rec["stats"].append([
                        tick, me, h, st.ping_ms, st.send_queue_len,
                        st.kbps_sent, st.local_frames_behind,
                        st.remote_frames_behind,
                    ])
    rec["requests"] = requests.hexdigest()[:16]
    rec["counters"] = [
        {k: v for k, v in sorted(s.metrics.counters.items())}
        for s in sessions
    ]
    if python_plane:
        monkeypatch.undo()
    # Through JSON, as the file holds it (tuples become lists).
    return json.loads(json.dumps(rec)), net.wire


def write_gate_recording():
    """Make ``tests/input_exchange_gate.json`` from the code of the checkout
    this runs in (see the section's head)."""
    mp = pytest.MonkeyPatch()
    out = {name: run_gate(name, False, mp)[0] for name in GATES}
    with open(GATE_FILE, "w") as f:
        json.dump(out, f, sort_keys=True, separators=(",", ":"))
        f.write("\n")


@pytest.mark.parametrize("plane", ["native", "python"])
@pytest.mark.parametrize("name", list(GATES))
def test_input_exchange_is_the_parents_byte_for_byte(name, plane, monkeypatch):
    from bevy_ggrs_tpu.session.endpoint import MAX_INPUT_SPAN

    rec, wire = run_gate(name, plane == "python", monkeypatch)
    with open(GATE_FILE) as f:
        want = json.load(f)[name]
    # Every input datagram either end sent is what ``proto.encode`` makes of
    # the same fields.
    spans, relayed = [], 0
    for src, dst, data in wire:
        if data[2] != proto.T_INPUT:
            continue
        msg = proto.decode(data)
        assert isinstance(msg, proto.InputMsg), (src, dst, data)
        assert proto.encode(msg) == data, (src, dst, msg)
        spans.append(msg.num)
        relayed += WATCHER not in (src, dst) and src != peer(msg.handle)
    # The run reached what it was written to reach.
    assert len(spans) > 1000
    assert max(spans) == MAX_INPUT_SPAN  # a pending set in two datagrams
    assert rec["refills"] > 0 and rec["spectator_frames"] > 100
    kinds = {e[2] for e in rec["events"]}
    if name == "duel":
        assert {"network_interrupted", "network_resumed"} <= kinds
    else:
        assert relayed > 0
        assert {"disconnected", "player_rejoined"} <= kinds
        assert sum(c.get("input_queue_drops", 0) for c in rec["counters"]) > 0
    # The recording. A count the parent did not keep is not held to it.
    new = {"datagrams_in_direct"}
    got_counters = [
        {k: v for k, v in c.items() if k not in new} for c in rec["counters"]
    ]
    assert got_counters == want["counters"]
    assert rec["wire"] == want["wire"]  # every datagram, in order
    for key in ("events", "frontier", "stats", "requests", "refills",
                "spectator_frames", "withheld"):
        assert rec[key] == want[key], key


class ParentsPending:
    """An endpoint's unacked inputs and its input datagrams as the code
    before PR 56 kept and made them (``session/endpoint.py`` at 078939d:
    a dict of arrays a handle, sorted and serialised again every send)."""

    def __init__(self):
        self.pending, self.max_sent, self.last_ack, self.relayed = {}, {}, {}, set()
        self.drops = 0

    def queue(self, handle, frame, bits, relay, running):
        from bevy_ggrs_tpu.session.endpoint import MAX_INPUT_SPAN

        pending = self.pending.setdefault(handle, {})
        pending[frame] = np.asarray(bits)
        if relay:
            self.relayed.add(handle)
        if not running and len(pending) > MAX_INPUT_SPAN:
            drop = sorted(pending)[: len(pending) - MAX_INPUT_SPAN]
            self.drops += len(drop)
            for f in drop:
                del pending[f]

    def ack(self, handle, ack_frame):
        pending = self.pending.get(handle)
        if pending is None:
            return
        self.last_ack[handle] = ack_frame
        ack_frame = min(ack_frame, self.max_sent.get(handle, -1))
        for f in [f for f in pending if f <= ack_frame]:
            del pending[f]

    def ack_own(self, ack_frame):
        for h in list(self.pending):
            if h not in self.relayed:
                self.ack(h, ack_frame)

    def refill_range(self, handle):
        pending, claimed = self.pending.get(handle), self.last_ack.get(handle)
        if pending is None or claimed is None:
            return None
        nxt = min(pending) if pending else self.max_sent.get(handle, -1) + 1
        return (claimed + 1, nxt) if claimed + 1 < nxt else None

    def send(self, local_frame, advantage, ack_frame):
        from bevy_ggrs_tpu.session.endpoint import MAX_INPUT_SPAN

        out = []
        for handle, pending in self.pending.items():
            frames = sorted(pending)
            for i in range(0, len(frames), MAX_INPUT_SPAN):
                chunk = frames[i : i + MAX_INPUT_SPAN]
                payload = b"".join(
                    np.ascontiguousarray(pending[f]).tobytes() for f in chunk)
                out.append(proto.encode(proto.InputMsg(
                    handle, chunk[0], payload, len(chunk), ack_frame,
                    local_frame, advantage)))
                self.max_sent[handle] = max(
                    self.max_sent.get(handle, -1), chunk[-1])
        return out


@pytest.mark.parametrize("spec", list(SPECS))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_unacked_store_sends_what_the_parents_dict_sent(seed, spec):
    """The store of rows against the parent's dict of arrays, one random
    operation at a time: appends, refills below the start, overwrites,
    gaps, relayed handles, honest and lying acks by both message kinds,
    the trim of a handshaking endpoint, a disconnect's clear."""
    from bevy_ggrs_tpu.session.endpoint import (
        MAX_INPUT_SPAN, PeerEndpoint, PeerState)
    from bevy_ggrs_tpu.utils.metrics import Metrics

    shape, dtype = SPECS[spec]
    rng = np.random.RandomState(seed)
    metrics = Metrics()
    ep = PeerEndpoint((0, 1), np.random.RandomState(0), metrics=metrics)
    ep.state = PeerState.RUNNING
    model = ParentsPending()
    nxt = {h: 0 for h in range(3)}  # the next frame a handle would queue
    seen = dict(below=0, overwrite=0, gap=0, chunked=0, refill=0)
    for step in range(3000):
        running = ep.state == PeerState.RUNNING
        op = rng.rand()
        h = int(rng.choice(3, p=[0.25, 0.25, 0.5]))
        if op < 0.45:
            held = sorted(model.pending.get(h, {}))
            roll = rng.rand()
            if roll < 0.8 or not held:
                frame = nxt[h]
                if rng.rand() < 0.05:
                    frame += int(rng.randint(1, 4))
                    seen["gap"] += 1
                nxt[h] = frame + 1
            elif roll < 0.9:
                frame = held[int(rng.randint(0, len(held)))]
                seen["overwrite"] += 1
            else:
                frame = max(0, held[0] - int(rng.randint(1, 5)))
                seen["below"] += frame < held[0]
            bits = draw(rng, shape, dtype)
            relay = h == 2
            ep.queue_input(h, frame, bits, relay=relay)
            model.queue(h, frame, bits, relay, running)
        elif op < 0.65:
            top = model.max_sent.get(h, -1)
            ack = int(rng.randint(-1, top + 2)) if rng.rand() < 0.9 else top + 500
            if rng.rand() < 0.5:
                ep.on_input(0.0, ack, step, 0)
                model.ack_own(ack)
            elif h != 2 or rng.rand() < 0.1:
                # The relayed handle is acked rarely: its rows pile up past
                # one datagram's span.
                ep.on_message(proto.InputAck(h, ack), 0.0, None)
                model.ack(h, ack)
        elif op < 0.97:
            adv = int(rng.randint(-3, 4))
            ep.send_pending_inputs(0.0, step, adv, step - 2)
            want = model.send(step, adv, step - 2) if running else []
            assert ep.outbox == want, step
            seen["chunked"] += len(want) > len(
                [p for p in model.pending.values() if p])
            ep.outbox.clear()
        elif op < 0.98:
            # A handshaking endpoint buffers and trims, ~20 steps a spell.
            ep.state = PeerState.SYNCHRONIZING
        elif op < 0.9807 and running:
            ep.force_disconnect()
            model.pending.clear()
            ep.state = PeerState.RUNNING  # the next test case, same endpoint
        if not running and rng.rand() < 0.05:
            ep.state = PeerState.RUNNING
        for handle in range(3):
            assert ep.refill_range(handle) == model.refill_range(handle), step
            seen["refill"] += model.refill_range(handle) is not None
        assert ep._max_sent == model.max_sent, step
        assert ep._last_ack_rx == model.last_ack, step
        assert {h: len(p) for h, p in ep._pending_output.items()} == {
            h: len(p) for h, p in model.pending.items()}, step
        assert ep.stats(0.0, step).send_queue_len == max(
            (len(p) for p in model.pending.values()), default=0)
    assert metrics.counters.get("input_queue_drops", 0) == model.drops
    assert all(seen.values()), seen
    assert model.drops > 0
