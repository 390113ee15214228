"""SpeculativeRollbackRunner: recovery-as-select must be invisible.

Two layers: request-level unit tests crafting exact rollback bursts against
a hand-built branch tensor (hit, miss, partial-span, anchor-offset cases),
asserting bitwise equality with the serial runner and correct hit/miss
accounting; and a full two-peer loopback session where one peer speculates
— confirmed checksum streams must match the all-serial universe exactly.
"""

import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.session.requests import AdvanceFrame, LoadGameState, SaveGameState
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.state import combine64, checksum

P = 2
MAXPRED = 8


def fixed_sampler(tensor):
    """A sampler that always returns ``tensor`` ([B, F, P] uint8)."""
    t = jnp.asarray(tensor)

    def sample(key, last_bits, num_branches, num_frames):
        assert t.shape[0] == num_branches and t.shape[1] == num_frames
        return t
    return sample


def make_runners(sampler=None, num_branches=4, spec_frames=4, **kw):
    serial = RollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=MAXPRED, num_players=P, input_spec=box_game.INPUT_SPEC,
    )
    spec = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=MAXPRED, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=num_branches, sampler=sampler, spec_frames=spec_frames,
        **kw,
    )
    return serial, spec


def adv(bits):
    return AdvanceFrame(
        bits=np.asarray(bits, np.uint8), status=np.zeros(P, np.int32)
    )


def step_requests(frame, bits):
    return [SaveGameState(frame), adv(bits)]


def rollback_requests(load, corrected):
    """[Load, (Save, Adv)×k] replaying ``corrected`` from frame ``load``."""
    reqs = [LoadGameState(load)]
    for t, bits in enumerate(corrected):
        reqs += [SaveGameState(load + t), adv(bits)]
    return reqs


class ChecksumLog:
    def __init__(self):
        self.seen = {}

    def report_checksum(self, frame, cs):
        self.seen[frame] = int(cs)


def run_both(serial, spec, script):
    """Apply the same request script to both runners (spec speculates when
    the script says so); returns their checksum logs."""
    logs = (ChecksumLog(), ChecksumLog())
    for item in script:
        if item[0] == "reqs":
            serial.handle_requests(item[1], logs[0])
            spec.handle_requests(item[1], logs[1])
        elif item[0] == "speculate":
            spec.speculate(item[1])
    assert serial.frame == spec.frame
    assert combine64(checksum(serial.state)) == combine64(checksum(spec.state))
    assert logs[0].seen == logs[1].seen
    return logs


def test_full_span_hit():
    # Frames 0..2 advance normally; speculate from anchor 3 (confirmed=2);
    # frames 3,4 advance (predicted); rollback Load(3) replays corrected
    # inputs that branch 2 of the tensor predicts exactly.
    corrected = np.array([[[1, 4]], [[1, 8]], [[1, 2]]], np.uint8).reshape(3, P)
    tensor = np.zeros((4, 4, P), np.uint8)
    tensor[2, :3] = corrected
    tensor[2, 3] = [9, 9]  # unused tail frame of the rollout
    serial, spec = make_runners(fixed_sampler(tensor), 4, 4)

    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(3)]
    script.append(("speculate", 2))
    script.append(("reqs", step_requests(3, [3, 4])))
    script.append(("reqs", step_requests(4, [4, 5])))
    script.append(("reqs", rollback_requests(3, list(corrected))))
    run_both(serial, spec, script)
    assert spec.spec_hits == 1 and spec.spec_misses == 0


def test_miss_falls_back_serial():
    tensor = np.full((4, 4, P), 13, np.uint8)  # never matches
    serial, spec = make_runners(fixed_sampler(tensor), 4, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(3)]
    script.append(("speculate", 2))
    script.append(("reqs", step_requests(3, [3, 4])))
    script.append(("reqs", rollback_requests(3, [[5, 6], [6, 7]])))
    run_both(serial, spec, script)
    assert spec.spec_hits == 0 and spec.spec_misses == 1


def test_partial_span_hit_load_after_anchor():
    # Anchor 2 but rollback loads at 4: the branch must ALSO match the
    # as-used inputs for frames 2..3 for its trajectory to be valid.
    used = {2: [2, 3], 3: [3, 4]}
    corrected = [[11, 1], [12, 2]]
    tensor = np.zeros((2, 4, P), np.uint8)
    tensor[1, 0] = used[2]
    tensor[1, 1] = used[3]
    tensor[1, 2] = corrected[0]
    tensor[1, 3] = corrected[1]
    serial, spec = make_runners(fixed_sampler(tensor), 2, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(2)]
    script.append(("speculate", 1))  # anchor = 2
    for f in (2, 3, 4):
        script.append(("reqs", step_requests(f, used.get(f, [4, 5]))))
    script.append(("reqs", rollback_requests(4, corrected)))
    run_both(serial, spec, script)
    assert spec.spec_hits == 1


def test_trajectory_mismatch_before_load_is_a_miss():
    # Branch matches the corrected span but NOT the as-used frame between
    # anchor and load — committing it would adopt a wrong trajectory, so it
    # must miss.
    corrected = [[11, 1]]
    tensor = np.zeros((2, 4, P), np.uint8)
    tensor[1, 0] = [99, 99]  # contradicts as-used inputs of frame 2
    tensor[1, 1] = corrected[0]
    serial, spec = make_runners(fixed_sampler(tensor), 2, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(2)]
    script.append(("speculate", 1))  # anchor = 2
    script.append(("reqs", step_requests(2, [2, 3])))
    script.append(("reqs", step_requests(3, [3, 4])))
    script.append(("reqs", rollback_requests(3, corrected)))
    run_both(serial, spec, script)
    assert spec.spec_hits == 0 and spec.spec_misses == 1


def test_hit_through_rollout_end_uses_final_state():
    # Replay consumes the rollout's entire span: the committed state must be
    # the rollout's final state, not a ring slot.
    corrected = np.array([[5, 1], [6, 2], [7, 3], [8, 4]], np.uint8)
    tensor = np.zeros((2, 4, P), np.uint8)
    tensor[0] = corrected
    serial, spec = make_runners(fixed_sampler(tensor), 2, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(3)]
    script.append(("speculate", 2))  # anchor = 3, rollout covers 3..6
    for f in (3, 4, 5, 6):
        script.append(("reqs", step_requests(f, [f, f + 1])))
    script.append(("reqs", rollback_requests(3, list(corrected))))
    run_both(serial, spec, script)
    assert spec.spec_hits == 1


def test_partial_prefix_commit_resimulates_only_tail():
    # Branch matches the first 2 of 3 corrected frames: those 2 commit from
    # the rollout, only the third is resimulated — still bitwise equal.
    corrected = [[11, 1], [12, 2], [13, 3]]
    tensor = np.zeros((2, 4, P), np.uint8)
    tensor[1, 0] = corrected[0]
    tensor[1, 1] = corrected[1]
    tensor[1, 2] = [99, 99]  # diverges at the third replayed frame
    serial, spec = make_runners(fixed_sampler(tensor), 2, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(3)]
    script.append(("speculate", 2))  # anchor = 3
    script.append(("reqs", step_requests(3, [3, 4])))
    script.append(("reqs", step_requests(4, [4, 5])))
    script.append(("reqs", rollback_requests(3, corrected)))
    run_both(serial, spec, script)
    assert spec.spec_partial_hits == 1 and spec.spec_hits == 0
    assert spec.rollback_frames_recovered_total == 2
    assert spec.rollback_frames_total == 1  # only the tail frame re-ran


def test_sampler_path_with_session_pinning():
    """Custom sampler + a session exposing confirmed_input: pinning must
    produce a writable tensor (regression: read-only device-array view)
    and pinned slots must override the sampler across all branches."""
    class FakeSession:
        def confirmed_input(self, handle, frame):
            if frame <= 4:  # frames 3..4 confirmed for everyone
                return np.uint8(7 + handle)
            return None

    tensor = np.full((4, 4, P), 13, np.uint8)
    _, spec = make_runners(fixed_sampler(tensor), 4, 4)
    script = [("reqs", step_requests(f, [f, f + 1])) for f in range(3)]
    for item in script:
        spec.handle_requests(item[1], ChecksumLog())
    spec.speculate(2, FakeSession())  # anchor 3, span 3..6
    bits = np.asarray(spec._result.branch_bits)
    assert (bits[:, 0] == [7, 8]).all()  # frame 3 pinned
    assert (bits[:, 1] == [7, 8]).all()  # frame 4 pinned
    # Branch 0 is the session's forward-fill prediction: after the confirmed
    # mid-span change the unknown suffix repeats the LAST KNOWN value, not
    # the anchor-1 input (and not the sampler's variation).
    assert (bits[0, 2] == [7, 8]).all()
    # Other branches spend capacity on sampler variations of the unknowns.
    assert (bits[1:, 2] == 13).all()


def test_structured_base_forward_fills_known_changes():
    """A confirmed input change inside the span must carry forward into the
    unknown suffix (the session predicts repeat-LAST-CONFIRMED, not
    repeat-anchor-input) — otherwise branch 0 diverges from the session's
    own prediction."""
    _, spec = make_runners(None, num_branches=8, spec_frames=4)
    last = np.array([1, 2], np.uint8)
    known = np.zeros((4, P), np.uint8)
    known_mask = np.zeros((4, P), bool)
    known[0, 0] = 9  # player 0 confirmed a change to 9 at span frame 0
    known_mask[0, 0] = True
    bits = spec.tree.structured_bits(spec._input_log, last, known, known_mask)
    # Branch 0: player 0 holds the NEW confirmed value through the suffix;
    # player 1 repeats its anchor input.
    assert bits[0, :, 0].tolist() == [9, 9, 9, 9]
    assert bits[0, :, 1].tolist() == [2, 2, 2, 2]
    # Change branches never alter the pinned slot.
    assert (bits[:, 0, 0] == 9).all()


def _candidates_loop_oracle(spec, last):
    """Independent straight-Python candidate ranking: recent distinct
    as-used values (newest first), then press/release toggles of
    recently-changed bits, then the declared universe."""
    nP = spec.num_players
    shape = spec.input_spec.shape
    n_field = int(np.prod(shape, dtype=np.int64)) if shape else 1
    dtype = spec.input_spec.zeros_np(1).dtype
    lastf = np.asarray(last).reshape(nP, n_field)
    frames = sorted(spec._input_log)[-32:]
    rows = {}
    for h in range(nP):
        for k in range(n_field):
            seq = [
                int(np.asarray(spec._input_log[f]).reshape(nP, n_field)[h, k])
                for f in frames
            ]
            recent = []
            for v in reversed(seq):
                if v not in recent:
                    recent.append(v)
            toggles = []
            if np.issubdtype(dtype, np.integer):
                changed = 0
                for a, b in zip(seq, seq[1:]):
                    changed |= a ^ b
                top = max((int(v) for v in spec._branch_values), default=0)
                all_bits, bit = [], 1
                while bit <= max(changed, top):
                    all_bits.append(bit)
                    bit <<= 1
                for b in [x for x in all_bits if changed & x] + [
                    x for x in all_bits if not (changed & x)
                ]:
                    toggles.append(int(lastf[h, k]) ^ b)
            allowed = {int(v) for v in spec._branch_values}
            row = []
            for v in recent + toggles + [int(v) for v in spec._branch_values]:
                if v not in row and v in allowed:
                    row.append(v)
            rows[h, k] = row
    return rows


def _structured_bits_loop_oracle(spec, last, known, known_mask):
    """Straight-Python enumeration oracle for the vectorized builder:
    (candidate-rank, frame, player, field)-major over the history-ranked
    candidate rows, skipping pinned slots, rank padding, and values equal
    to the base prediction."""
    from bevy_ggrs_tpu.branch_tree import forward_fill

    F, P_, B = spec.spec_frames, spec.num_players, spec.num_branches
    shape = spec.input_spec.shape
    base = forward_fill(last, known, known_mask)
    out = np.broadcast_to(base, (B, F, P_) + shape).copy()
    rows = _candidates_loop_oracle(spec, last)
    max_r = max(len(r) for r in rows.values())
    b = 1
    frames_idx = np.arange(F)
    for r in range(max_r):
        for t in range(F):
            for h in range(P_):
                if known_mask[t, h]:
                    continue
                suffix = (frames_idx >= t) & ~known_mask[:, h]
                for k, field in enumerate(np.ndindex(shape)) if shape else [
                    (0, ())
                ]:
                    row = rows[h, k]
                    if r >= len(row):
                        continue
                    v = row[r]
                    if b >= B:
                        return out
                    if v == base[(t, h) + field]:
                        continue
                    out[(b,) + (suffix, h) + field] = v
                    b += 1
    return out


def test_structured_bits_vectorized_matches_loop_oracle():
    """The vectorized tree builder (round-3 verdict weak #5: the Python
    O(B·F) loop cost milliseconds per tick at the stress shape) must
    reproduce the loop enumeration bit-for-bit, including at the stress
    shape P=8, F=12, B=1024 — with and without input history driving the
    candidate ranking."""
    rng = np.random.RandomState(5)
    # Pinned predictor-OFF: the loop oracle models the heuristic
    # candidate ranking (seeded-tree parity lives in test_predictor.py).
    cases = [
        (4, 4, P, make_runners(None, 4, 4, predictor=False)[1]),
        (96, 4, P, None),
    ]
    for B, F, nP, spec in cases + [(1024, 12, 8, None)]:
        if spec is None:
            spec = SpeculativeRollbackRunner(
                box_game.make_schedule(),
                box_game.make_world(nP).commit(),
                max_prediction=12, num_players=nP,
                input_spec=box_game.INPUT_SPEC,
                num_branches=B, spec_frames=F, predictor=False,
            )
        last = rng.randint(0, 16, (nP,)).astype(np.uint8)
        known = rng.randint(0, 16, (F, nP)).astype(np.uint8)
        mask = rng.rand(F, nP) < 0.4
        got = spec.tree.structured_bits(spec._input_log, last, known, mask)
        want = _structured_bits_loop_oracle(spec, last, known, mask)
        assert np.array_equal(got, want), (B, F, nP)
        # With as-used history: recency + toggle ranking kicks in.
        for f in range(6):
            spec._input_log[f] = rng.randint(0, 16, (nP,)).astype(np.uint8)
        got = spec.tree.structured_bits(spec._input_log, last, known, mask)
        want = _structured_bits_loop_oracle(spec, last, known, mask)
        assert np.array_equal(got, want), ("hist", B, F, nP)
        spec._input_log.clear()
    # Degenerate: everything pinned -> every branch is the base prediction.
    spec = make_runners(None, 4, 4)[1]
    last = np.array([1, 2], np.uint8)
    known = np.full((4, P), 5, np.uint8)
    mask = np.ones((4, P), bool)
    bits = spec.tree.structured_bits(spec._input_log, last, known, mask)
    assert (bits == bits[0]).all()


def test_candidate_ranking_prioritizes_recent_and_toggles():
    """Projectiles' live failure mode (round-4 verdict item 2): a player
    alternating UP <-> UP|FIRE in a 32-value universe. The candidate row
    must lead with the recent working set, so the FIRE transition is
    covered at EVERY frame by a small tree."""
    from bevy_ggrs_tpu.models import projectiles

    # Pinned predictor-OFF: this asserts the HEURISTIC ranking's shape
    # (a learned ranking is free to order the row differently).
    spec = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=8, num_players=2,
        input_spec=projectiles.INPUT_SPEC, num_branches=64,
        predictor=False,
    )
    UP, FIRE = projectiles.INPUT_UP, projectiles.INPUT_FIRE
    # Irregular (APERIODIC) fire tapping: the periodic extrapolator must
    # not trigger, leaving coverage to the transition-ranked tree.
    pattern = [0, 0, 0, 1, 1, 0, 0, 0, 0, 1, 1, 1]
    for f, fire in enumerate(pattern):
        bits = np.array([UP | (FIRE if fire else 0), 0], np.uint8)
        spec._input_log[f] = bits
    last = np.array([UP, 0], np.uint8)
    C, valid = spec.tree.candidate_values(spec._input_log, last)
    row0 = [int(v) for v in C[0, 0][valid[0, 0]]]
    # Player 0's top candidates are its two recent values; UP|FIRE (the
    # transition from last=UP) ranks in the top two.
    assert (UP | FIRE) in row0[:2]
    # The tree therefore covers the FIRE press at every unknown frame:
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    tree = spec.tree.structured_bits(spec._input_log, last, known, mask)
    for t in range(8):
        wanted = np.broadcast_to(last, (8, 2)).copy()
        wanted[t:, 0] = UP | FIRE
        assert any(
            np.array_equal(tree[b], wanted) for b in range(64)
        ), f"FIRE press at frame {t} not enumerated"


def test_confirmed_span_bulk_query_matches_getter():
    """P2PSession.confirmed_span (one call per player per tick) must agree
    with the per-frame confirmed_input getter on both queue backends —
    it is what BranchTree.known_inputs pins branches with."""
    from tests.test_p2p import FPS_DT, make_pair, scripted_input
    from bevy_ggrs_tpu.session import PredictionThreshold, SessionState
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    net = LoopbackNetwork(latency=2 * FPS_DT, seed=3)
    peers = make_pair(net)
    for _ in range(40):
        net.advance(FPS_DT)
        for session, runner in peers:
            session.poll_remote_clients()
            if session.current_state() != SessionState.RUNNING:
                continue
            for h in session.local_player_handles():
                session.add_local_input(
                    h, scripted_input(h, session.current_frame)
                )
            try:
                runner.handle_requests(session.advance_frame(), session)
            except PredictionThreshold:
                continue
    session, _ = peers[0]
    anchor = session.confirmed_frame() - 3
    for h in range(P):
        vals, mask = session.confirmed_span(h, anchor, 8)
        assert mask.any() and not mask.all()  # straddles the frontier
        for i in range(8):
            got = session.confirmed_input(h, anchor + i)
            assert mask[i] == (got is not None)
            if got is not None:
                assert np.array_equal(vals[i], got)


def test_loopback_session_equivalence():
    """Full P2P run: peer 0 speculating must produce exactly the checksum
    stream of the all-serial universe (hits or not)."""
    from tests.test_p2p import (
        FPS_DT, common_confirmed_checksums, make_pair, scripted_input,
    )
    from bevy_ggrs_tpu.session import PredictionThreshold, SessionState
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    def drive_universe(speculate: bool):
        net = LoopbackNetwork(latency=2.5 * FPS_DT, seed=11)
        peers = make_pair(net, max_prediction=8)
        if speculate:
            session0, _ = peers[0]
            spec_runner = SpeculativeRollbackRunner(
                box_game.make_schedule(), box_game.make_world(2).commit(),
                max_prediction=8, num_players=2,
                input_spec=box_game.INPUT_SPEC,
                num_branches=16, spec_frames=8, seed=3,
            )
            peers[0] = (session0, spec_runner)
        for _ in range(60):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(
                        h, scripted_input(h, session.current_frame)
                    )
                try:
                    requests = session.advance_frame()
                except PredictionThreshold:
                    continue
                runner.handle_requests(requests, session)
                if isinstance(runner, SpeculativeRollbackRunner):
                    runner.speculate(session.confirmed_frame())
        return peers

    serial_peers = drive_universe(False)
    spec_peers = drive_universe(True)
    f1, cs1 = common_confirmed_checksums(serial_peers)
    f2, cs2 = common_confirmed_checksums(spec_peers)
    assert f1 and f1 == f2
    # Within each universe both peers agree; across universes identical.
    assert all(a == b for a, b in cs1)
    assert all(a == b for a, b in cs2)
    assert cs1 == cs2
    spec_runner = spec_peers[0][1]
    assert spec_runner.rollbacks_total > 0  # rollbacks actually happened


def test_meshed_live_speculation_equivalent_and_distributed():
    """A SpeculativeRollbackRunner built with a mesh lays the branch axis
    over it for LIVE speculation (not just the standalone executor) and
    keeps the world entity-sharded — and the P2P outcome is bitwise the
    unmeshed universe's."""
    import jax

    from bevy_ggrs_tpu.parallel.sharding import branch_mesh
    from tests.test_p2p import (
        FPS_DT, common_confirmed_checksums, make_pair, scripted_input,
    )
    from bevy_ggrs_tpu.session import PredictionThreshold, SessionState
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    if len(jax.devices()) < 2:
        import pytest

        pytest.skip("needs a multi-device mesh")

    def drive(mesh):
        net = LoopbackNetwork(latency=2.5 * FPS_DT, seed=21)
        peers = make_pair(net, max_prediction=8)
        session0, _ = peers[0]
        spec = SpeculativeRollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=8, num_players=2, input_spec=box_game.INPUT_SPEC,
            num_branches=16, spec_frames=8, seed=3, mesh=mesh,
        )
        peers[0] = (session0, spec)
        for _ in range(50):
            net.advance(FPS_DT)
            for session, runner in peers:
                session.poll_remote_clients()
                if session.current_state() != SessionState.RUNNING:
                    continue
                for h in session.local_player_handles():
                    session.add_local_input(
                        h, scripted_input(h, session.current_frame)
                    )
                try:
                    requests = session.advance_frame()
                except PredictionThreshold:
                    continue
                runner.handle_requests(requests, session)
                if isinstance(runner, SpeculativeRollbackRunner):
                    runner.speculate(session.confirmed_frame(), session)
        return peers, spec

    mesh = branch_mesh()  # all devices on the branch axis
    meshed_peers, meshed_spec = drive(mesh)
    plain_peers, _ = drive(None)

    # Live rollouts really were distributed over the mesh.
    assert meshed_spec._result is not None
    leaf = meshed_spec._result.checksums
    assert not leaf.sharding.is_fully_replicated
    assert meshed_spec.rollbacks_total > 0

    f1, cs1 = common_confirmed_checksums(meshed_peers)
    f2, cs2 = common_confirmed_checksums(plain_peers)
    assert f1 and f1 == f2 and cs1 == cs2


def test_speculate_dedups_identical_redispatch():
    """Ticks where the confirmed frontier hasn't moved and no new inputs
    confirmed inside the span must NOT re-dispatch the (identical) rollout;
    anything that changes the prediction inputs must."""

    class FakeSession:
        def __init__(self):
            self.inputs = {}

        def confirmed_input(self, handle, frame):
            return self.inputs.get((handle, frame))

    _, spec = make_runners(num_branches=4, spec_frames=4)
    session = FakeSession()
    # Advance to frame 4 so a past anchor exists.
    for f in range(4):
        spec.handle_requests(step_requests(f, [f, f + 1]), None)

    spec.speculate(1, session)  # anchor 2 < frame 4: dedup eligible
    first = spec._result
    assert first is not None and spec.spec_dispatches_skipped == 0
    spec.speculate(1, session)  # identical tick -> skipped
    assert spec.spec_dispatches_skipped == 1
    assert spec._result is first
    # A newly confirmed input inside the span changes the signature.
    session.inputs[(1, 3)] = np.uint8(9)
    spec.speculate(1, session)
    assert spec.spec_dispatches_skipped == 1
    assert spec._result is not first
    # Frontier advance changes the anchor -> re-dispatch.
    second = spec._result
    spec.speculate(2, session)
    assert spec._result is not second
    # Live-state anchor (anchor == frame) never dedups: state moves.
    spec.speculate(3, session)
    live1 = spec._result
    spec.speculate(3, session)
    assert spec._result is not live1


def test_restore_invalidates_speculative_transients(tmp_path):
    """A checkpoint restore replaces ring/state/frame from outside the
    request protocol; the pending rollout, its dedup signature, and the
    as-used input log describe the pre-restore world and must be dropped
    (code-review r3: the dedup otherwise serves a pre-restore rollout
    indefinitely)."""
    from bevy_ggrs_tpu.utils.persistence import restore_runner, save_runner

    _, spec = make_runners(num_branches=4, spec_frames=4)
    for f in range(3):
        spec.handle_requests(step_requests(f, [f, f + 1]), None)
    path = str(tmp_path / "ck.npz")
    save_runner(path, spec)
    spec.handle_requests(step_requests(3, [3, 4]), None)
    spec.speculate(2)
    assert spec._result is not None and spec._input_log

    restore_runner(path, spec)
    assert spec._result is None
    assert spec._spec_sig is None
    assert not spec._input_log
    assert spec.frame == 3


def test_random_sampler_path_never_dedups():
    """Each sampler dispatch draws fresh Monte Carlo branches — skipping a
    'same-signature' tick would collapse the compounding hit probability,
    so the dedup must bypass sampler-based runners entirely."""
    from bevy_ggrs_tpu.parallel.speculate import bitmask_sampler

    _, spec = make_runners(num_branches=4, spec_frames=4)
    spec._sampler = bitmask_sampler()
    for f in range(4):
        spec.handle_requests(step_requests(f, [f, f + 1]), None)
    spec.speculate(1)
    first = spec._result
    spec.speculate(1)
    assert spec._result is not first  # fresh draw, no skip
    assert spec.spec_dispatches_skipped == 0


def test_periodic_extrapolation_covers_multi_player_cycles():
    """Two remote players cycling keys every 3 frames (the projectiles
    live workload): repeat-last mispredicts every boundary and a span
    contains boundaries from BOTH players — unreachable for single-change
    branches. The periodic extrapolation base must predict both players'
    continuations exactly, so branch 1 matches the true future."""
    spec = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=8, num_players=2,
        input_spec=box_game.INPUT_SPEC, num_branches=16, spec_frames=8,
    )
    keys = [1, 2, 4, 0]

    def scripted(h, f):
        return keys[(f // 3 + h) % 4]

    for f in range(40):
        spec._input_log[f] = np.array(
            [scripted(0, f), scripted(1, f)], np.uint8
        )
    anchor = 40
    last = spec._input_log[anchor - 1]
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    tree = spec.tree.structured_bits(
        spec._input_log, last, known, mask, anchor
    )
    truth = np.array(
        [[scripted(h, anchor + t) for h in range(2)] for t in range(8)],
        np.uint8,
    )
    # Branch 0 stays the session's forward-fill prediction...
    assert np.array_equal(tree[0], np.broadcast_to(last, (8, 2)))
    # ...and branch 1 IS the true periodic future for both players.
    assert np.array_equal(tree[1], truth), (tree[1], truth)


def test_extrapolation_falls_back_without_periodicity():
    """Aperiodic history must leave the tree identical to the plain
    forward-fill single-change enumeration (no wasted branch 1)."""
    rng = np.random.RandomState(9)
    spec = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=8, num_players=2,
        input_spec=box_game.INPUT_SPEC, num_branches=16, spec_frames=8,
    )
    for f in range(40):
        spec._input_log[f] = rng.randint(0, 16, (2,)).astype(np.uint8)
    last = spec._input_log[39]
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    tree = spec.tree.structured_bits(spec._input_log, last, known, mask, 40)
    base = np.broadcast_to(last, (8, 2))
    assert np.array_equal(tree[0], base)
    assert not np.array_equal(tree[1], tree[0])  # a real change branch
