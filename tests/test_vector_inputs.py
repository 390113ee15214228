"""Structured speculation trees for NON-SCALAR inputs (round-2 weak #4).

A twin-stick-style test model carries a vector input per player —
``[move_bitmask, throttle_level]`` as ``uint8[2]`` — exercising the
generalized single-change tree: each branch changes one player's one FIELD
to one candidate value at one frame and holds, so a throttle-change
misprediction is recoverable as a branch commit exactly like a scalar
bitmask press. The sticky random sampler's measured hit rate on such
changes was 0 (ROUND_NOTES r1); these tests pin the structured tree's to
hits."""

import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import InputSpec, PlayerInputs, Schedule
from bevy_ggrs_tpu.session.requests import AdvanceFrame, LoadGameState, SaveGameState
from bevy_ggrs_tpu.spec_runner import (
    SpeculativeRollbackRunner,
    attest_speculation_safety,
)
from bevy_ggrs_tpu.state import HostWorld, TypeRegistry

INPUT_UP, INPUT_DOWN, INPUT_LEFT, INPUT_RIGHT = 1, 2, 4, 8
# Field 0: movement bitmask 0..15; field 1: throttle 0..15.
INPUT_SPEC = InputSpec(shape=(2,), dtype=jnp.uint8, values=tuple(range(16)))
P = 2


def make_registry():
    reg = TypeRegistry()
    reg.register_component("position", shape=(2,), dtype=jnp.float32)
    reg.register_component("owner", shape=(), dtype=jnp.int32, default=-1)
    reg.register_resource("frame_count", jnp.uint32(0))
    return reg


def make_world():
    world = HostWorld(make_registry(), 4)
    for h in range(P):
        world.spawn(
            {"position": np.array([float(h), 0.0], np.float32), "owner": h},
            rollback_id=h,
        )
    return world


def move_system(state, inputs: PlayerInputs):
    """Integer-graded movement: direction from field 0's bitmask, speed
    scaled by field 1's throttle level. f32 add/mul with fixed order —
    bit-reproducible, so speculation attests safe."""
    owner = state.components["owner"]
    pos = state.components["position"]
    safe = jnp.clip(owner, 0, inputs.num_players - 1)
    bits = inputs.bits[safe, 0].astype(jnp.uint32)
    throttle = inputs.bits[safe, 1].astype(jnp.float32)
    dx = (
        ((bits & INPUT_RIGHT) != 0).astype(jnp.float32)
        - ((bits & INPUT_LEFT) != 0).astype(jnp.float32)
    )
    dy = (
        ((bits & INPUT_UP) != 0).astype(jnp.float32)
        - ((bits & INPUT_DOWN) != 0).astype(jnp.float32)
    )
    step = jnp.stack([dx, dy], axis=1) * (
        jnp.float32(0.01) * (jnp.float32(1.0) + throttle)[:, None]
    )
    sel = (state.alive & (owner >= 0))[:, None]
    return state.replace(
        components={
            **state.components,
            "position": jnp.where(sel, pos + step, pos),
        }
    )


def frame_system(state, inputs):
    del inputs
    return state.replace(
        resources={
            **state.resources,
            "frame_count": state.resources["frame_count"] + jnp.uint32(1),
        }
    )


def make_schedule():
    return Schedule([move_system, frame_system])


def adv(vec):
    return AdvanceFrame(
        bits=np.asarray(vec, np.uint8), status=np.zeros(P, np.int32)
    )


def step_requests(frame, vec):
    return [SaveGameState(frame), adv(vec)]


def rollback_requests(load, corrected):
    reqs = [LoadGameState(load)]
    for t, vec in enumerate(corrected):
        reqs += [SaveGameState(load + t), adv(vec)]
    return reqs


class Log:
    def __init__(self):
        self.seen = {}

    def report_checksum(self, frame, cs):
        self.seen[frame] = int(cs)


def make_runners(num_branches=128, spec_frames=4):
    serial = RollbackRunner(
        make_schedule(), make_world().commit(),
        max_prediction=8, num_players=P, input_spec=INPUT_SPEC,
    )
    spec = SpeculativeRollbackRunner(
        make_schedule(), make_world().commit(),
        max_prediction=8, num_players=P, input_spec=INPUT_SPEC,
        num_branches=num_branches, spec_frames=spec_frames,
    )
    return serial, spec


def test_vector_model_attests_safe():
    _, spec = make_runners(num_branches=8)
    assert attest_speculation_safety(spec).ok


def test_single_field_change_is_a_spec_hit():
    """Player 1 changes ONLY the throttle field (field 1) at the anchor;
    the structured tree enumerates that single-field change, so the
    rollback burst commits a precomputed branch."""
    serial, spec = make_runners()
    logs = (Log(), Log())
    base = np.zeros((P, 2), np.uint8)
    base[:, 0] = INPUT_RIGHT  # both players holding right, throttle 0
    for f in range(3):
        serial.handle_requests(step_requests(f, base), logs[0])
        spec.handle_requests(step_requests(f, base), logs[1])
    spec.speculate(2)  # anchor 3
    for f in (3, 4):
        serial.handle_requests(step_requests(f, base), logs[0])
        spec.handle_requests(step_requests(f, base), logs[1])
    # Truth: player 1 pushed throttle to 5 at frame 3 and held.
    changed = base.copy()
    changed[1, 1] = 5
    corrected = [changed, changed]
    serial.handle_requests(rollback_requests(3, corrected), logs[0])
    spec.handle_requests(rollback_requests(3, corrected), logs[1])

    assert spec.spec_hits == 1 and spec.spec_misses == 0
    assert serial.frame == spec.frame
    assert logs[0].seen == logs[1].seen  # bitwise agreement with serial


def test_two_field_change_falls_back_serial():
    """A simultaneous two-field change is outside the single-change tree:
    must be a MISS that falls back to (bit-identical) serial replay — the
    correctness contract is unconditional, only the hit rate varies."""
    serial, spec = make_runners()
    logs = (Log(), Log())
    base = np.zeros((P, 2), np.uint8)
    for f in range(3):
        serial.handle_requests(step_requests(f, base), logs[0])
        spec.handle_requests(step_requests(f, base), logs[1])
    spec.speculate(2)
    for f in (3, 4):
        serial.handle_requests(step_requests(f, base), logs[0])
        spec.handle_requests(step_requests(f, base), logs[1])
    changed = base.copy()
    changed[1] = [INPUT_UP, 7]  # move AND throttle changed together
    corrected = [changed, changed]
    serial.handle_requests(rollback_requests(3, corrected), logs[0])
    spec.handle_requests(rollback_requests(3, corrected), logs[1])

    assert spec.spec_hits == 0 and spec.spec_misses == 1
    assert serial.frame == spec.frame
    assert logs[0].seen == logs[1].seen


def test_structured_tree_enumerates_fields_scalar_compatible():
    """Direct tree inspection: every non-base branch differs from base in
    exactly one (player, field) suffix; scalar models keep their old tree
    shape (ndindex(()) degenerates to one field)."""
    _, spec = make_runners(num_branches=64, spec_frames=3)
    last = np.zeros((P, 2), np.uint8)
    known = np.zeros((3, P, 2), np.uint8)
    mask = np.zeros((3, P), bool)
    tree = spec.tree.structured_bits(spec._input_log, last, known, mask)
    assert tree.shape == (64, 3, P, 2)
    base = tree[0]
    for b in range(1, 64):
        diff = tree[b] != base
        changed = np.argwhere(diff)
        assert len(changed) > 0
        # All diffs share one (player, field) and form a frame suffix.
        players = {(p, f) for _, p, f in changed}
        assert len(players) == 1
        frames = sorted({t for t, _, _ in changed})
        assert frames == list(range(frames[0], 3))


def test_vector_speculation_live_session_equivalence_and_hits():
    """Full two-peer loopback P2P with the twin-stick vector model: the
    speculating peer's confirmed checksum stream must equal the all-serial
    universe's, and the structured single-field tree must land real hits
    against scripted single-field input changes."""
    from bevy_ggrs_tpu.session import (
        PlayerType,
        PredictionThreshold,
        SessionBuilder,
        SessionState,
    )
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    FPS_DT = 1.0 / 60.0

    def scripted_vec(handle, frame):
        """One FIELD changes at a time, every 4 frames: move bitmask cycles
        on even periods, throttle steps on odd — the misprediction shape
        the single-change tree enumerates."""
        vec = np.zeros(2, np.uint8)
        period = frame // 4
        vec[0] = [INPUT_UP, INPUT_RIGHT, 0, INPUT_DOWN][(period // 2 + handle) % 4]
        # Throttle steps only on odd periods (held through even ones), so
        # each period boundary changes at most one field.
        vec[1] = (period + period % 2 + handle) % 4
        return vec

    def drive(speculate):
        net = LoopbackNetwork(latency=2.5 * FPS_DT, seed=31)
        peers = []
        for me in range(P):
            sock = net.socket(("peer", me))
            builder = (
                SessionBuilder(INPUT_SPEC)
                .with_num_players(P)
                .with_max_prediction_window(8)
            )
            for h in range(P):
                builder.add_player(
                    PlayerType.local() if h == me
                    else PlayerType.remote(("peer", h)),
                    h,
                )
            session = builder.start_p2p_session(sock, clock=lambda: net.now)
            if me == 0 and speculate:
                runner = SpeculativeRollbackRunner(
                    make_schedule(), make_world().commit(),
                    max_prediction=8, num_players=P, input_spec=INPUT_SPEC,
                    num_branches=128, spec_frames=8, seed=5,
                )
            else:
                runner = RollbackRunner(
                    make_schedule(), make_world().commit(),
                    max_prediction=8, num_players=P, input_spec=INPUT_SPEC,
                )
            peers.append((session, runner))
        for _ in range(70):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(
                        h, scripted_vec(h, session.current_frame)
                    )
                try:
                    requests = session.advance_frame()
                except PredictionThreshold:
                    continue
                runner.handle_requests(requests, session)
                if isinstance(runner, SpeculativeRollbackRunner):
                    runner.speculate(session.confirmed_frame(), session)
        return peers

    spec_peers = drive(True)
    serial_peers = drive(False)

    from tests.test_p2p import common_confirmed_checksums

    f1, cs1 = common_confirmed_checksums(spec_peers)
    f2, cs2 = common_confirmed_checksums(serial_peers)
    assert f1 and f1 == f2
    assert all(a == b for a, b in cs1)
    assert cs1 == cs2  # speculation invisible in the vector universe too
    spec_runner = spec_peers[0][1]
    assert spec_runner.rollbacks_total > 0
    # The structured single-field tree recovers real mispredictions live.
    assert spec_runner.spec_hits + spec_runner.spec_partial_hits > 0


def test_periodic_extrapolation_per_field_vector_inputs():
    """Per-(player, FIELD) period detection: field 0 cycles with period 4,
    field 1 holds constant — the extrapolated base must continue field 0's
    cycle exactly while leaving field 1 on repeat-last, independently per
    player (players offset in phase)."""
    spec = SpeculativeRollbackRunner(
        make_schedule(), make_world().commit(),
        max_prediction=8, num_players=P, input_spec=INPUT_SPEC,
        num_branches=16, spec_frames=8,
    )
    cycle = [1, 2, 4, 8]

    def field0(h, f):
        return cycle[(f + h) % 4]

    for f in range(40):
        spec._input_log[f] = np.array(
            [[field0(h, f), 7] for h in range(P)], np.uint8
        )
    anchor = 40
    last = spec._input_log[anchor - 1]
    known = np.zeros((8, P, 2), np.uint8)
    mask = np.zeros((8, P), bool)
    tree = spec.tree.structured_bits(
        spec._input_log, last, known, mask, anchor
    )
    truth = np.array(
        [[[field0(h, anchor + t), 7] for h in range(P)] for t in range(8)],
        np.uint8,
    )
    # Branch 0 = forward-fill (field 0 stuck on its last value).
    assert np.array_equal(tree[0], np.broadcast_to(last, (8, P, 2)))
    assert not np.array_equal(tree[0], truth)
    # Branch 1 = the true per-field periodic continuation.
    assert np.array_equal(tree[1], truth), (tree[1], truth)
