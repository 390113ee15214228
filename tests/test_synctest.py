"""SyncTestSession end-to-end: the minimum slice of the survey's build plan
(§7 step 3) — box_game running under forced rollbacks with checksum
comparison every frame, driven through the real request protocol and the
fused device executor.

Reference behavior: `examples/box_game/box_game_synctest.rs:27-38` +
`src/ggrs_stage.rs:163-193`.
"""

import contextlib

import numpy as np
import pytest

from bevy_ggrs_tpu import checksum, combine64
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import make_inputs
from bevy_ggrs_tpu.session import (
    InvalidRequest,
    MismatchedChecksum,
    SyncTestSession,
)
from bevy_ggrs_tpu.session.requests import AdvanceFrame, LoadGameState, SaveGameState


def make(num_players=2, check_distance=2, input_delay=0, max_prediction=8,
         session_cls=SyncTestSession):
    session = session_cls(
        num_players,
        box_game.INPUT_SPEC,
        check_distance=check_distance,
        max_prediction=max_prediction,
        input_delay=input_delay,
    )
    runner = RollbackRunner(
        box_game.make_schedule(),
        box_game.make_world(num_players).commit(),
        max_prediction=max_prediction,
        num_players=num_players,
        input_spec=box_game.INPUT_SPEC,
    )
    return session, runner


def tick(session, runner, bits):
    for h in range(session.num_players):
        session.add_local_input(h, bits[h])
    runner.handle_requests(session.advance_frame(), session)


def test_request_shape_before_and_after_check_distance():
    session, _ = make(check_distance=2)
    for h in range(2):
        session.add_local_input(h, np.uint8(0))
    reqs = session.advance_frame()
    # Frame 0: no history yet → plain [Save, Advance].
    assert [type(r) for r in reqs] == [SaveGameState, AdvanceFrame]
    for _ in range(2):
        for h in range(2):
            session.add_local_input(h, np.uint8(0))
        reqs = session.advance_frame()
    # Frame 2: forced rollback 2 deep FIRST, the frame's own step last
    # (upstream ggrs's order): Load(0), then 3 (Save, Advance) pairs for
    # frames 0..2, frame 2 stepped once.
    kinds = [type(r) for r in reqs]
    assert kinds == [LoadGameState] + [SaveGameState, AdvanceFrame] * 3
    assert reqs[0].frame == 0
    assert [r.frame for r in reqs[1::2]] == [0, 1, 2]


class CountingSession(SyncTestSession):
    """Counts what ``report_checksum`` is told, frame by frame."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.reported = {}

    def report_checksum(self, frame, checksum):
        self.reported[frame] = self.reported.get(frame, 0) + 1
        super().report_checksum(frame, checksum)


DISTANCES = [1, 2, 4, 8]


@pytest.mark.parametrize("d", DISTANCES)
def test_every_list_is_one_segment(d):
    """One Load-delimited list a frame, whatever the check distance: the
    frame's own step alone before history allows a rollback, ``d + 1``
    steps from ``Load(f - d)`` after."""
    session, _ = make(check_distance=d)
    for f in range(3 * d + 2):
        for h in range(2):
            session.add_local_input(h, np.uint8(f % 16))
        reqs = session.advance_frame()
        segs = RollbackRunner._segment(None, reqs)
        assert len(segs) == 1
        load, steps = segs[0]
        if f < d:
            assert load is None and len(steps) == 1
        else:
            assert load == f - d and len(steps) == d + 1
        start = f if load is None else load
        assert [st.save_frame for st in steps] == list(
            range(start, start + len(steps)))
        assert all(st.adv is not None for st in steps)


@pytest.mark.parametrize("d", DISTANCES)
def test_every_frame_is_reported_d_plus_one_times(d):
    """Over 40 seeded frames every frame old enough is saved ``d + 1``
    times (its first save and ``d`` comparisons, upstream's count), and
    the run ends on the straight-line state."""
    session, runner = make(check_distance=d, session_cls=CountingSession)
    sched = box_game.make_schedule()
    oracle = box_game.make_world(2).commit()
    rng = np.random.RandomState(d)
    for _ in range(40):
        bits = rng.randint(0, 16, size=2).astype(np.uint8)
        tick(session, runner, bits)
        oracle = sched(oracle, make_inputs(bits))
    for f in range(40):
        # Frame f is saved first at tick f and again at every later tick
        # that rolls back to f or before it: ticks f + 1 .. f + d, of which
        # the first d - 1 load nothing yet and the run stops after tick 39.
        again = range(max(f + 1, d), min(f + d, 39) + 1)
        assert session.reported[f] == 1 + len(again), f
        if d <= f <= 39 - d:
            assert session.reported[f] == d + 1
    assert runner.frame == 40
    assert combine64(checksum(runner.state)) == combine64(checksum(oracle))


@pytest.mark.parametrize("d", DISTANCES)
def test_tampered_snapshot_is_caught_at_its_load(d):
    """A snapshot that no longer is what was saved (the ring row the next
    tick loads, changed behind the session's back) is re-saved right after
    its load and hashes differently: caught in that tick, whatever the
    check distance. (With ``verify_restores`` on, the runner's own restore
    guard repairs the row first: that is the SDC path, not the harness.)"""
    session, runner = make(check_distance=d)
    runner.verify_restores = False
    for i in range(d + 3):
        tick(session, runner, np.asarray([i % 16, (i + 5) % 16], np.uint8))
    ring = runner.ring
    target = runner.frame - d
    row = target % ring.depth
    runner.ring = ring.replace(states=ring.states.replace(components={
        **ring.states.components,
        "translation": ring.states.components["translation"].at[row].add(
            0.001),
    }))
    with pytest.raises(MismatchedChecksum) as e:
        tick(session, runner, np.zeros(2, np.uint8))
    assert e.value.frame == target


@pytest.mark.parametrize("d, caught", [(1, False), (2, True), (4, True),
                                       (8, True)])
def test_nondeterministic_step_is_caught_within_d_plus_one_ticks(d, caught):
    """The step itself made non-deterministic at a frame >= ``d``: a
    system reads host state the snapshots do not hold, and that state
    changes between two ticks. The first resimulation after the change
    re-saves a frame that hashed differently on its first pass.

    ``check_distance`` 1 is PINNED as blind to it, as upstream's is: its
    one re-save of a frame is of the snapshot just loaded (the test above
    is all it compares), every resimulated state is saved for the first
    time. The builder's default is 2."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    from bevy_ggrs_tpu.schedule import Schedule

    host = {"drift": 0.0}

    def leaky_system(state, inputs):
        del inputs
        drift = io_callback(
            lambda: np.float32(host["drift"]),
            jax.ShapeDtypeStruct((), jnp.float32),
        )
        comps = dict(state.components)
        comps["translation"] = comps["translation"] + drift
        return state.replace(components=comps)

    session, _ = make(check_distance=d)
    runner = RollbackRunner(
        Schedule([box_game.move_cube_system, leaky_system,
                  box_game.increase_frame_system]),
        box_game.make_world(2).commit(),
        max_prediction=8, num_players=2, input_spec=box_game.INPUT_SPEC,
    )
    for i in range(d + 3):  # past frame d: every tick rolls back
        tick(session, runner, np.asarray([i % 16, (i + 5) % 16], np.uint8))
    host["drift"] = 0.001
    with (pytest.raises(MismatchedChecksum) if caught
          else contextlib.nullcontext()):
        for _ in range(d + 1):
            tick(session, runner, np.zeros(2, np.uint8))
    assert (runner.frame < 2 * d + 4) == caught


def test_live_state_tampered_between_ticks_is_loaded_over():
    """PINS a consequence of upstream's order (rollback first, own step
    last): at a frame >= ``check_distance`` the live state is loaded over
    before anything saves it, so a live state changed BETWEEN two ticks
    never enters a checksum and the run ends on the straight-line state.
    (Before frame ``check_distance`` the list is [Save, Advance] and the
    tamper IS saved and caught: ``test_synctest_detects_nondeterminism``.)
    What the harness exists for is a non-deterministic STEP (the test
    above); resident state is guarded by the attestation sweep
    (``RollbackRunner.attest_and_repair``, ``MatchServer._attest_sweep``;
    tests/test_integrity.py)."""
    session, runner = make(num_players=2, check_distance=2)
    sched = box_game.make_schedule()
    oracle = box_game.make_world(2).commit()
    rng = np.random.RandomState(7)
    for i in range(12):
        if i == 6:
            runner.state = runner.state.replace(components={
                **runner.state.components,
                "translation": runner.state.components["translation"] + 0.5,
            })
        bits = rng.randint(0, 16, size=2).astype(np.uint8)
        tick(session, runner, bits)
        oracle = sched(oracle, make_inputs(bits))
    assert runner.frame == 12
    assert combine64(checksum(runner.state)) == combine64(checksum(oracle))


def test_synctest_deterministic_game_runs_clean():
    session, runner = make(num_players=2, check_distance=3)
    rng = np.random.RandomState(0)
    for _ in range(30):
        tick(session, runner, rng.randint(0, 16, size=2).astype(np.uint8))
    assert runner.frame == 30
    assert runner.rollbacks_total > 0  # forced rollbacks actually happened
    assert int(runner.state.resources["frame_count"]) == 30


def test_synctest_matches_straightline_simulation():
    """After N frames with rollbacks forced every frame, state must equal a
    straight single-pass simulation of the same inputs."""
    session, runner = make(num_players=2, check_distance=4)
    sched = box_game.make_schedule()
    oracle = box_game.make_world(2).commit()
    rng = np.random.RandomState(1)
    for _ in range(20):
        bits = rng.randint(0, 16, size=2).astype(np.uint8)
        tick(session, runner, bits)
        oracle = sched(oracle, make_inputs(bits))
    assert combine64(checksum(runner.state)) == combine64(checksum(oracle))


def test_synctest_detects_nondeterminism():
    """State mutated outside the rollback domain (bypassing the snapshot
    ring) must trip MismatchedChecksum on a later resimulation — the desync
    class the harness exists to catch (reference
    `examples/README.md:13-18`)."""
    session, runner = make(num_players=2, check_distance=2)
    tick(session, runner, np.zeros(2, np.uint8))
    # Out-of-band tamper: live state drifts, ring snapshots don't know.
    runner.state = runner.state.replace(
        components={
            **runner.state.components,
            "translation": runner.state.components["translation"] + 0.001,
        }
    )
    with pytest.raises(MismatchedChecksum):
        for _ in range(5):
            tick(session, runner, np.zeros(2, np.uint8))


def test_input_delay_shifts_effect():
    """With input_delay=2, an input issued at frame f takes effect at f+2
    (`with_input_delay`, box_game_p2p.rs:37)."""
    session, runner = make(num_players=1, check_distance=0, input_delay=2)
    tick(session, runner, np.array([box_game.INPUT_RIGHT], np.uint8))
    v_after_f0 = runner.world()["components"]["velocity"][0]
    assert v_after_f0[0] == 0.0  # delayed input not yet in effect
    tick(session, runner, np.zeros(1, np.uint8))
    tick(session, runner, np.zeros(1, np.uint8))
    v_after_f2 = runner.world()["components"]["velocity"][0]
    assert v_after_f2[0] > 0.0  # now it landed


def test_missing_input_rejected():
    session, _ = make(num_players=2)
    session.add_local_input(0, np.uint8(0))
    with pytest.raises(InvalidRequest):
        session.advance_frame()


def test_check_distance_beyond_prediction_rejected():
    with pytest.raises(InvalidRequest):
        SyncTestSession(2, box_game.INPUT_SPEC, check_distance=9, max_prediction=8)


def test_deep_prediction_window():
    """The temporal axis at 4x the reference's example depth: a 32-frame
    prediction window with 30-deep forced rollbacks every frame (the
    'long-context' analog, survey §5 — the frame axis is a lax.scan, so
    depth costs compile-time shape only, not host round trips)."""
    session, runner = make(check_distance=30, max_prediction=32)
    sched = box_game.make_schedule()
    oracle = box_game.make_world(2).commit()
    for i in range(40):
        bits = np.asarray([(i + h) % 16 for h in range(2)], np.uint8)
        tick(session, runner, bits)
        oracle = sched(oracle, make_inputs(bits))
    assert runner.frame == 40
    assert runner.rollback_frames_total >= 30 * 9  # deep resims really ran
    # And the deeply-resimulated state equals straight-line simulation.
    assert combine64(checksum(runner.state)) == combine64(checksum(oracle))
