"""The tick's plan (``fused.py``): one definition, three callers.

``match_pending`` -> ``plan_tick`` -> ``account_rollback`` decide what a
lane's tick is, write its ``TickInts`` scalars and count its rollback. The
table below runs them with no device: every kind of tick, its row, its
outcome and its counter deltas. The last test drives the same scripted
rollbacks through the singleton runner and both served dispatch paths and
holds the three rows equal.
"""

import types

import numpy as np
import pytest

from bevy_ggrs_tpu import fused
from bevy_ggrs_tpu.fused import (
    TickInts,
    account_rollback,
    match_pending,
    plan_rollout,
    plan_tick,
)
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.obs.ledger import SpeculationLedger
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.session.requests import AdvanceFrame
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_batched_sessions import rollback_requests, step_requests

P, MF, DEPTH = 2, 6, 5
# The pending rollout: 3 branches x 4 frames from frame 10. Branch 0 repeats
# (1, 1); branch 1 changes player 0 to 2 at frame 11; branch 2 to 3 at 12.
ANCHOR, F = 10, 4
RES = np.ones((3, F, P), np.uint8)
RES[1, 1:, 0] = 2
RES[2, 2:, 0] = 3
LOG = {10: np.array([1, 1], np.uint8)}  # as-used, before the rollback


def steps_of(rows):
    status = np.zeros(P, np.int32)
    return [
        types.SimpleNamespace(adv=AdvanceFrame(np.array(r, np.uint8), status))
        for r in rows
    ]


# name: (frame, load_frame, corrected inputs | steady step count, pending?,
#        log, next anchor, spec_on)
#   -> (row, plan's (n_commit, missed, spec_active), outcome, counter deltas)
# row = (branch, absorb_first, absorb_n, prev_anchor, prev_total, do_load,
#        load_frame, start_frame, n_burst, from_live, spec_anchor)
CASES = {
    "steady": (
        (13, None, 1, True, LOG, 12, True),
        ((0, 0, 0, 10, 4, 0, 0, 13, 1, 0, 12), (0, False, True), None, {}),
    ),
    "full_hit": (
        (13, 11, [(2, 1), (2, 1)], True, LOG, 13, True),
        ((1, 11, 2, 10, 4, 0, 0, 13, 0, 1, 13), (2, False, True), "full",
         {"rollbacks": 1, "rollback_frames_recovered": 2, "spec_hits": 1}),
    ),
    "partial_hit": (  # the tail resimulates, with no load
        (13, 11, [(2, 1), (5, 1)], True, LOG, 13, True),
        ((1, 11, 1, 10, 4, 0, 0, 12, 1, 1, 13), (1, False, True), "partial",
         {"rollbacks": 1, "rollback_frames_recovered": 1,
          "spec_partial_hits": 1, "rollback_frames": 1}),
    ),
    "miss": (  # every branch follows the log's frame 10 and no replayed frame
        (13, 11, [(7, 7), (7, 7)], True, LOG, 13, True),
        ((0, 11, 0, 10, 4, 1, 11, 11, 2, 1, 13), (0, True, True), "miss",
         {"rollbacks": 1, "spec_misses": 1, "rollback_frames": 2}),
    ),
    "log_gap": (  # frame 10 was never logged: no branch can be asked
        (13, 11, [(2, 1), (2, 1)], True, {}, 13, True),
        ((0, 11, 0, 10, 4, 1, 11, 11, 2, 1, 13), (0, False, True),
         "unmatched", {"rollbacks": 1, "rollback_frames": 2}),
    ),
    "no_pending": (
        (13, 11, [(2, 1), (2, 1)], False, LOG, 12, True),
        ((0, 11, 0, 0, 0, 1, 11, 11, 2, 0, 12), (0, False, True),
         "unmatched", {"rollbacks": 1, "rollback_frames": 2}),
    ),
    "load_before_anchor": (
        (11, 9, [(1, 1), (1, 1)], True, LOG, 11, True),
        ((0, 9, 0, 10, 4, 1, 9, 9, 2, 1, 11), (0, False, True),
         "unmatched", {"rollbacks": 1, "rollback_frames": 2}),
    ),
    "anchor_past_frontier": (  # all confirmed: rollout from the live frontier
        (13, None, 1, True, LOG, 15, True),
        ((0, 0, 0, 10, 4, 0, 0, 13, 1, 1, 14), (0, False, False), None, {}),
    ),
    "anchor_aged_out": (  # 14 - DEPTH: the ring no longer holds it
        (13, None, 1, True, LOG, 9, True),
        ((0, 0, 0, 10, 4, 0, 0, 13, 1, 1, 14), (0, False, False), None, {}),
    ),
    "speculation_off": (
        (13, 11, [(2, 1), (2, 1)], False, LOG, 13, False),
        ((0, 11, 0, 0, 0, 1, 11, 11, 2, 1, 13), (0, False, False),
         "unmatched", {"rollbacks": 1, "rollback_frames": 2}),
    ),
}


def make_owner():
    return types.SimpleNamespace(
        metrics=Metrics(), ledger=SpeculationLedger(), rollbacks_total=0,
        rollback_frames_total=0, rollback_frames_recovered_total=0,
        spec_hits=0, spec_partial_hits=0, spec_misses=0,
    )


@pytest.mark.parametrize("slot", [None, 3], ids=["singleton", "served"])
@pytest.mark.parametrize("name", list(CASES))
def test_plan_row_outcome_and_counters(name, slot):
    (frame, load_frame, inputs, pending, log, anchor, spec_on), want = (
        CASES[name]
    )
    want_row, (want_commit, want_missed, want_active), want_outcome, want_d = (
        want
    )
    steps = steps_of([(0, 0)] * inputs if load_frame is None else inputs)
    res_anchor = ANCHOR if pending else None
    matched = match_pending(
        None, log, RES if pending else None, res_anchor, F, load_frame, steps
    )
    ints = TickInts.zeros(MF, P, (4,))
    ints[:] = -7  # every scalar is written, whatever the row held
    plan = plan_tick(
        ints[2], frame, load_frame, len(steps), res_anchor, F, matched,
        anchor, DEPTH, spec_on,
    )
    assert tuple(ints[2, :TickInts.STATUS]) == want_row
    assert (ints[[0, 1, 3]] == -7).all() and (ints[2, TickInts.STATUS:] == -7).all()
    branch, n_commit, missed, burst_load, burst_start, n_tail = plan[:6]
    assert (n_commit, missed, plan[6]) == (want_commit, want_missed, want_active)
    # the returned plan says what the row says
    T = TickInts
    assert (branch, n_commit, burst_start, n_tail, plan[8], plan[7]) == tuple(
        ints[2, [T.BRANCH, T.ABSORB_N, T.START_FRAME, T.N_BURST,
                 T.SPEC_FROM_LIVE, T.SPEC_ANCHOR]]
    )
    assert burst_load == (load_frame if ints[2, T.DO_LOAD] else None)

    owner = make_owner()
    outcome = None
    if load_frame is not None:
        outcome = account_rollback(
            owner, load_frame, len(steps), branch, n_commit, missed,
            (1, load_frame), slot=slot,
        )
    assert outcome == want_outcome
    # the served core also counts these three by match
    labeled = {
        owner.metrics._key(k, {"match_slot": slot}): v
        for k, v in want_d.items()
        if slot is not None and k in ("rollbacks", "spec_hits", "spec_misses")
    }
    assert dict(owner.metrics.counters) == {**want_d, **labeled}
    assert (
        owner.rollbacks_total, owner.rollback_frames_recovered_total,
        owner.rollback_frames_total, owner.spec_hits,
        owner.spec_partial_hits, owner.spec_misses,
    ) == tuple(
        want_d.get(k, 0) for k in (
            "rollbacks", "rollback_frames_recovered", "rollback_frames",
            "spec_hits", "spec_partial_hits", "spec_misses")
    )
    entries = list(owner.ledger.entries)
    if want_outcome is None:
        assert not entries and "rollback_depth" not in owner.metrics.series
        return
    assert owner.metrics.series["rollback_depth"] == [len(steps)]
    (entry,) = entries
    assert entry["outcome"] == want_outcome
    assert entry["depth"] == len(steps) == (
        entry["frames_recovered"] + entry["frames_resimulated"]
    )
    assert entry["frames_recovered"] == n_commit
    assert entry.get("branch") == (branch if n_commit else None)
    assert entry.get("slot") == slot and entry["load_frame"] == load_frame
    assert (entry["blame_player"], entry["blame_frame"]) == (1, load_frame)


@pytest.mark.parametrize(
    "res_anchor,want_row",
    [
        # the pending rollout again, from its ring snapshot
        (10, (0, 0, 0, 0, 0, 0, 0, 13, 0, 0, 10)),
        # pending from the frame the lane is still at: from the live state
        (13, (0, 0, 0, 0, 0, 0, 0, 13, 0, 1, 13)),
        # nothing pending: a discarded rollout from the live frontier
        (None, (0, 0, 0, 0, 0, 0, 0, 13, 0, 1, 13)),
    ],
    ids=["pending", "pending_live", "none"],
)
def test_noop_lane_is_a_plan(res_anchor, want_row):
    row = TickInts.zeros(MF, P)
    row[:] = -7
    plan = plan_rollout(row, 13, res_anchor, DEPTH)
    assert tuple(row[:TickInts.STATUS]) == want_row
    assert plan[:3] == (0, 0, False) and plan[5] == 0


# -- the three callers ---------------------------------------------------

MAXPRED, BRANCHES, SPEC = 4, 8, 3


def script():
    """Steady ticks, a stalled frontier, then the recovery tick. The
    corrected inputs miss the structured tree (random), follow one of its
    branches for a while (player 1 back to its value before last, from
    the load frame on: the tree's best-ranked change; then player 0
    changes too) or all the way. A full hit comes last: from there on the
    singleton, which keeps its rollout after an absorb-only commit, and
    the server, which rolls out again, plan from different pending
    rollouts by design."""
    rng = np.random.RandomState(3)
    ticks, frame = [], 0
    for kind, depth in (
        ("random", 2), ("two_changes", 2), ("one_change", 3), ("random", 1),
        ("two_changes", 4), ("one_change", 2), ("same", 2),
    ):
        older = rng.randint(0, 16, size=P)
        pred = (older + 1 + rng.randint(0, 15, size=P)) % 16  # != older
        for bits in (older, pred):
            ticks.append((step_requests(frame, bits), frame))
            frame += 1
        frontier = frame - 1
        for d in range(depth):
            ticks.append((step_requests(frame + d, pred), frontier))
        frame += depth
        corrected = [pred.copy() for _ in range(depth)]
        if kind == "random":
            corrected = [(pred + 1 + rng.randint(0, 15, size=P)) % 16
                         for _ in range(depth)]
        elif kind != "same":
            for t in range(depth):
                corrected[t][1] = older[1]
                if kind == "two_changes" and t:
                    corrected[t][0] = older[0]
        reqs = rollback_requests(frame - depth, corrected)
        ticks.append((reqs + step_requests(frame, corrected[-1]), frame))
        frame += 1
    return ticks


def record_plans(monkeypatch, module, sink):
    """Every ``plan_tick`` call ``module`` makes for a lane with work:
    its row, as written, and its plan."""
    def recording(row, frame, load_frame, n_steps, *rest):
        plan = fused.plan_tick(row, frame, load_frame, n_steps, *rest)
        if n_steps:
            sink.append((np.array(row[:TickInts.STATUS]), plan))
        return plan

    monkeypatch.setattr(module, "plan_tick", recording)


@pytest.mark.skipif(
    not ncore.available(), reason="native session core did not build"
)
def test_three_callers_write_the_same_rows(monkeypatch):
    from bevy_ggrs_tpu import spec_runner
    from bevy_ggrs_tpu.serve import batch

    ticks = script()
    rows = {"singleton": [], "python": [], "native": []}

    record_plans(monkeypatch, spec_runner, rows["singleton"])
    runner = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=MAXPRED, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=BRANCHES, spec_frames=SPEC,
    )
    runner.warmup()
    for reqs, confirmed in ticks:
        runner.tick(reqs, confirmed, None)

    for path in ("python", "native"):
        record_plans(monkeypatch, batch, rows[path])
        core = BatchedSessionCore(
            box_game.make_schedule(), box_game.make_world(P).commit(),
            MAXPRED, P, box_game.INPUT_SPEC, num_slots=3,
            num_branches=BRANCHES, spec_frames=SPEC, predictor=False,
        )
        if path == "python":
            core._plane = None  # the GGRS_NO_NATIVE=1 route
        assert (core._plane is not None) == (path == "native")
        core.warmup()
        core.admit()
        lane = core.admit()  # lane 1 lives, lanes 0 and 2 never tick
        for reqs, confirmed in ticks:
            core.tick({lane: (reqs, confirmed, None)})
        assert core.slots[lane].frame == runner.frame

    # every tick made one plan in each caller, up to the first full hit
    outcomes = [
        ("full" if not p[5] else "partial") if p[1] else
        ("miss" if p[2] else "steady_or_unmatched")
        for _, p in rows["singleton"]
    ]
    upto = outcomes.index("full") + 1
    assert {"miss", "partial", "full"} <= set(outcomes[:upto])
    assert len(ticks) == len(outcomes) == len(rows["python"]) == len(rows["native"])
    for k in range(upto):
        want_row, want_plan = rows["singleton"][k]
        for path in ("python", "native"):
            got_row, got_plan = rows[path][k]
            assert np.array_equal(got_row, want_row), (path, k, got_row, want_row)
            assert got_plan == want_plan, (path, k)
