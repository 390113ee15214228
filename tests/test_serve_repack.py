"""The re-pack at a served frame's end, and the handle that follows a match.

Off-peak, ``MatchServer`` moves the survivors of its emptiest hot stagger
group into the free slots of the others (``serve/server.py`` ``_repack``),
so that the emptied group skips its dispatch from the next frame on:

- every match still advances once a served frame through the moves, and is
  bitwise a serial ``RollbackRunner`` after them (state, frame, ring frames,
  ring checksums), on box_game and on the entity-coupled boids title;
- the ``MatchHandle`` the caller was given says where the match lives now,
  and one rebuilt from ``(group, slot)`` finds what lives there now;
- a full server, and holes that do not add up to a group, move nothing;
- a recovery lane, a reserved slot and a queued admission are left alone
  and hold their group's drain back;
- nothing compiles after ``warmup()``.
"""

import numpy as np
import pytest

from benchmark.drivers.common import tree_equal
from bevy_ggrs_tpu.models import box_game, boids
from bevy_ggrs_tpu.obs.slo import SlotSLO
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.serve import MatchHandle, MatchServer, SlotHealth
from bevy_ggrs_tpu.serve.faults import ServerCheckpointer
from bevy_ggrs_tpu.session import SessionBuilder
from bevy_ggrs_tpu.utils import xla_cache
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_serve_boids import MASKS, WINDOW, P


class Title:
    """One title at toy size: what a server, a session, a match's inputs
    and its serial oracle are made of."""

    def __init__(self, name: str):
        self.name = name
        if name == "box_game":
            self.spec = box_game.INPUT_SPEC
            self.schedule = box_game.make_schedule()
            self.world = box_game.make_world(P).commit()
            self.values = np.arange(16, dtype=np.uint8)
        else:
            self.spec = boids.INPUT_SPEC
            self.schedule = boids.make_schedule(kernel="xla")
            self.world = boids.make_world(64, P).commit()
            self.values = MASKS

    def server(self, capacity=8, groups=2, **kw) -> MatchServer:
        kw.setdefault("metrics", Metrics())
        server = MatchServer(
            self.schedule, self.world, WINDOW, P, self.spec,
            capacity=capacity, stagger_groups=groups, num_branches=4,
            spec_frames=WINDOW, **kw)
        server.warmup()
        return server

    def session(self):
        return (SessionBuilder(self.spec).with_num_players(P)
                .with_max_prediction_window(WINDOW).with_check_distance(2)
                .start_synctest_session())

    def feed(self, k: int):
        table = np.random.RandomState(100 + k).choice(
            self.values, size=(P, 256))
        return lambda frame, handle: table[handle, frame]

    def assert_serial(self, server, handle, k: int) -> None:
        """The match at ``handle`` is bitwise a fresh serial runner fed
        match ``k``'s inputs for as many frames (the benchmark driver's own
        comparison)."""
        core = server.groups[handle.group]
        frames = core.slots[handle.slot].frame
        session, feed = self.session(), self.feed(k)
        oracle = RollbackRunner(
            self.schedule, self.world, WINDOW, P, self.spec)
        for _ in range(frames):
            for p in session.local_player_handles():
                session.add_local_input(p, feed(session.current_frame, p))
            oracle.handle_requests(session.advance_frame(), session)
        assert oracle.frame == frames
        assert tree_equal(core.slot_state(handle.slot), oracle.state)
        assert np.array_equal(np.asarray(core.rings.frames)[handle.slot],
                              np.asarray(oracle.ring.frames))
        assert np.array_equal(np.asarray(core.rings.checksums)[handle.slot],
                              np.asarray(oracle.ring.checksums))


@pytest.fixture(scope="module", params=["box_game", "boids"])
def title(request):
    return Title(request.param)


@pytest.fixture(scope="module")
def box():
    return Title("box_game")


def evening(title, server, keep):
    """Fill the server, then retire all but ``keep`` (match numbers, in
    admission order: ``slots_per_group`` a group). Returns ``{k: handle}``
    of the survivors."""
    handles = [server.add_match(title.session(), title.feed(k))
               for k in range(server.capacity)]
    for k, h in enumerate(handles):
        if k not in keep:
            server.retire_match(h)
    return {k: handles[k] for k in sorted(keep)}


def frame_of(server, handle) -> int:
    return server.groups[handle.group].slots[handle.slot].frame


def repacked(server) -> int:
    return int(server.metrics.counters.get("matches_repacked", 0))


def hot_groups(server) -> list:
    return list(server.metrics.series["serve_hot_groups"])


# ---------------------------------------------------------------------------
# (a), (e), (f): the survivors end in the fewest groups
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("admit_budget,drain_frames", [(4, 1), (1, 2)])
def test_survivors_end_in_one_group(title, admit_budget, drain_frames):
    server = title.server(admit_budget=admit_budget)
    live = evening(title, server, keep={0, 1, 4, 5})
    assert [tuple(h) for h in live.values()] == [
        (0, 0), (0, 1), (1, 0), (1, 1)]
    compiles = xla_cache.compile_counters()["backend_compiles"]
    frames = 0
    for _ in range(drain_frames):
        server.run_frame()
        frames += 1
        # Every match advances once a served frame through the moves.
        assert [frame_of(server, h) for h in live.values()] == [frames] * 4
    assert repacked(server) == 2 == server.matches_repacked_total
    assert server.groups[1].active_count == 0
    # The handles the caller kept say where the matches live now.
    assert [tuple(h) for h in live.values()] == [
        (0, 0), (0, 1), (0, 2), (0, 3)]
    for _ in range(10):
        server.run_frame()
        frames += 1
        assert [frame_of(server, h) for h in live.values()] == [frames] * 4
    assert hot_groups(server) == [2.0] * drain_frames + [1.0] * 10
    assert repacked(server) == 2 and not server.repacked
    assert server.groups[1].ticks_total == drain_frames
    assert xla_cache.compile_counters()["backend_compiles"] == compiles
    assert server.faults_total == 0 and server.evictions_total == 0
    for k, h in live.items():
        title.assert_serial(server, h, k)


def test_three_groups_drain_one_after_another(box):
    """12 slots in 3 groups, 1 survivor a group: the highest group of the
    fewest drains first, and the frame after the next one goes."""
    server = box.server(capacity=12, groups=3)
    live = evening(box, server, keep={0, 4, 8})
    for _ in range(6):
        server.run_frame()
    assert hot_groups(server) == [3.0, 2.0, 1.0, 1.0, 1.0, 1.0]
    assert [tuple(h) for h in live.values()] == [(0, 0), (0, 2), (0, 1)]
    for k, h in live.items():
        assert frame_of(server, h) == 6
        box.assert_serial(server, h, k)


# ---------------------------------------------------------------------------
# (b): no churn
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("keep", [
    set(range(8)),          # a full server
    {0, 1, 2, 4, 5},        # 3 + 2 of 4 + 4: the holes are no group
    {0, 1, 2, 3},           # one hot group already
], ids=["full", "holes_add_up_to_no_group", "one_hot_group"])
def test_nothing_moves(box, keep):
    server = box.server()
    live = evening(box, server, keep=keep)
    before = [tuple(h) for h in live.values()]
    for _ in range(5):
        server.run_frame()
    assert repacked(server) == 0 and server.matches_repacked_total == 0
    assert [tuple(h) for h in live.values()] == before
    assert len(set(hot_groups(server))) == 1
    assert "serve_repack_ms" not in server.metrics.series


# ---------------------------------------------------------------------------
# (c): what never moves
# ---------------------------------------------------------------------------


def test_recovery_lane_holds_its_group_back(box):
    """A match on a recovery lane is not on the batch: its group is the
    one with the fewest, and is not drained while the lane holds its slot;
    once the match is readmitted and healthy, the drain runs."""
    server = box.server()
    live = evening(box, server, keep={0, 1, 4, 5})
    sick = live[4]
    server._fault(sick, server._matches[sick], "test")
    assert sick in server._lanes
    while sick in server._lanes:
        server.run_frame()
        assert server.frames_served < 64
        if sick in server._lanes:
            assert repacked(server) == 0
            assert tuple(live[5]) == (1, 1)
    assert server.health_of(sick) is SlotHealth.HEALTHY
    for _ in range(2):
        server.run_frame()
    assert repacked(server) == 2
    assert {h.group for h in live.values()} == {0}
    for k, h in live.items():
        box.assert_serial(server, h, k)


def test_reserved_slot_holds_its_group_back(box):
    server = box.server()
    live = evening(box, server, keep={0, 1, 4, 5})
    server._reserved[1].add(3)      # the source group's
    server.run_frame()
    assert repacked(server) == 0
    server._reserved[1].clear()
    server._reserved[0].update({2, 3})   # the target's: no room left
    server.run_frame()
    assert repacked(server) == 0
    server._reserved[0].discard(3)       # room for one of two: no drain
    server.run_frame()
    assert repacked(server) == 0
    server._reserved[0].clear()
    server.run_frame()
    assert repacked(server) == 2
    assert [frame_of(server, h) for h in live.values()] == [4] * 4


def test_queued_admission_holds_its_group_back(box):
    """A match in the admit queue holds a slot of the group that would be
    drained. The frame that admits it moves nothing (the match has not
    ridden a dispatch; while one still waits, the admissions have used the
    budget up); it moves with its group the frame after."""
    server = box.server(admit_budget=2)
    handles = [server.add_match(box.session(), box.feed(k))
               for k in range(6)]
    build = lambda: box.world  # noqa: E731 -- a lazy build: the slow drain
    ahead = server.enqueue_match(box.session(), box.feed(8), build)
    late = server.enqueue_match(box.session(), box.feed(7), build)
    assert tuple(ahead) == (1, 2) and tuple(late) == (1, 3)
    for k in (0, 1, 4):             # group 0 keeps 2 and 3, group 1 keeps 5
        server.retire_match(handles[k])
    server.retire_match(MatchHandle(1, 2))  # an abandon ahead of admission
    assert [entry[0] for entry in server._admit_queue] == [late]
    live = {2: handles[2], 3: handles[3], 5: handles[5], 7: late}
    server.run_frame()
    assert late in server._pending_first and repacked(server) == 0
    assert tuple(late) == (1, 3) and tuple(handles[5]) == (1, 1)
    server.run_frame()              # rides its first dispatch, then moves
    assert late not in server._pending_first
    assert repacked(server) == 2 and server.groups[1].active_count == 0
    assert tuple(handles[5]) == (0, 0) and tuple(late) == (0, 1)
    for _ in range(4):
        server.run_frame()
    assert [frame_of(server, h) for h in live.values()] == [6, 6, 6, 5]
    for k, h in live.items():
        box.assert_serial(server, h, k)


def test_unhealthy_match_holds_its_group_back(box):
    server = box.server()
    live = evening(box, server, keep={0, 1, 4, 5})
    fsm = server._matches[live[5]].fsm
    fsm.to(SlotHealth.DEGRADED, reason="test")
    fsm.clear = lambda: None        # a good tick would clear it at once
    server.run_frame()
    assert repacked(server) == 0
    del fsm.clear
    server.run_frame()              # ... as this one does
    assert server.health_of(live[5]) is SlotHealth.HEALTHY
    assert repacked(server) == 2


# ---------------------------------------------------------------------------
# (d): identity and location
# ---------------------------------------------------------------------------


def test_rebuilt_handle_finds_what_lives_there_now(box):
    server = box.server()
    live = evening(box, server, keep={0, 1, 4, 5})
    kept = live[4]
    assert kept == kept and kept != MatchHandle(1, 0)   # identity, not place
    assert {kept: "mine"}[kept] == "mine"
    server.run_frame()
    assert tuple(kept) == (0, 2)
    assert {kept: "mine"}[kept] == "mine"               # the hash is its own
    assert server._matches[kept].fsm.slot == 2
    # A handle rebuilt from the new place finds the match; the old place
    # holds nothing.
    assert server.health_of(MatchHandle(0, 2)) is SlotHealth.HEALTHY
    assert server.health_of((0, 2)) is SlotHealth.HEALTHY
    with pytest.raises(KeyError):
        server.health_of(MatchHandle(1, 0))
    with pytest.raises(RuntimeError):
        server.suspend_match(MatchHandle(1, 0))
    server.retire_match((1, 0))                          # nothing: no-op
    assert server.slots_active == 4
    # Suspended through a rebuilt handle, resumed under the kept one: the
    # kept handle is the match's again.
    session = server._matches[kept].session
    ticket = server.suspend_match(MatchHandle(0, 2))
    assert kept not in server._matches and server.slots_active == 3
    again = server.resume_match(session, box.feed(4), ticket, handle=kept)
    assert again is kept and tuple(kept) == (0, 2)
    record = server.resume_match(
        box.session(), box.feed(9),
        server.suspend_match(live[5]), handle=(1, 1))
    assert tuple(record) == (1, 1) and record is not live[5]
    server.retire_match(MatchHandle(1, 1))
    assert record not in server._matches
    for _ in range(3):
        server.run_frame()
    assert frame_of(server, kept) == 4
    box.assert_serial(server, kept, 4)
    snap = {tuple(r["handle"]): r["frame"] for r in server.snapshot_matches()}
    assert snap == {(0, 0): 4, (0, 1): 4, (0, 2): 4}


def test_slo_history_follows_the_match(box):
    server = box.server()
    live = evening(box, server, keep={0, 1, 4, 5})
    old = server._flat_slot(live[4])
    server.run_frame()
    new = server._flat_slot(live[4])
    assert (old, new) == (4, 2)
    assert old not in server.slo._slots and new in server.slo._slots
    assert len(server.slo._slots[new].bad["deadline"]) == 1
    server.run_frame()
    assert len(server.slo._slots[new].bad["deadline"]) == 2


def test_slot_slo_move():
    slo = SlotSLO(metrics=Metrics())
    slo.observe_tick(3, deadline_ok=False)
    slo.observe_tick(5, deadline_ok=True)
    window = slo._slots[3]
    slo.move(3, 5)
    assert slo._slots == {5: window}
    slo.move(7, 5)          # nothing at the old place: the new one is bare
    assert slo._slots == {}


def test_checkpoint_taken_on_a_moving_frame_names_the_new_places(
        box, tmp_path):
    """A checkpoint names a match by its place, so the frame that moves
    matches saves one: a restart from the newest restores every survivor
    where its handle says, bitwise."""
    ckpt = str(tmp_path / "ckpt")
    server = box.server(checkpoint_dir=ckpt, checkpoint_interval=1000)
    live = evening(box, server, keep={0, 1, 4, 5})
    server.run_frame()
    assert repacked(server) == 2 and server.checkpointer.saves_total == 1
    server.run_frame()
    assert server.checkpointer.saves_total == 1
    fresh = box.server()
    restored = ServerCheckpointer(ckpt).restore(
        fresh,
        {tuple(h): {"session": box.session(), "local_inputs": box.feed(k)}
         for k, h in live.items()},
    )
    assert sorted(tuple(h) for h in restored) == sorted(
        tuple(h) for h in live.values())
    fresh.run_frame()
    for k, h in live.items():
        assert frame_of(fresh, h) == 2 == frame_of(server, h)
        box.assert_serial(fresh, h, k)
