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
