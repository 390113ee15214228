"""Batched multi-session serving: bitwise parity + churn contracts.

The session-axis core (`serve/batch.py`) must produce byte-identical
per-slot state vs serial singleton runs — with heterogeneous rollback
depths across slots, spec-ON branch trees, and admit/retire mid-run — and
match churn must never recompile the batched executable.

Hit COUNTERS may differ from the singleton (the batch re-dispatches full
hits and never dedup-skips); committed state, ring contents and checksum
reports must not.
"""

import ast
import inspect
import textwrap

import numpy as np
import pytest

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.obs.trace import SpanTracer
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.serve.server import MatchServer
from bevy_ggrs_tpu.session.builder import SessionBuilder
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    SaveGameState,
)
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.state import checksum, combine64
from bevy_ggrs_tpu.utils import xla_cache

P = 2
MAXPRED = 4
BRANCHES = 8
SPEC_FRAMES = 3


def adv(bits):
    return AdvanceFrame(
        bits=np.asarray(bits, np.uint8), status=np.zeros(P, np.int32)
    )


def step_requests(frame, bits):
    return [SaveGameState(frame), adv(bits)]


def rollback_requests(load, corrected):
    reqs = [LoadGameState(load)]
    for t, bits in enumerate(corrected):
        reqs += [SaveGameState(load + t), adv(bits)]
    return reqs


def make_script(seed, depth, cycles):
    """A (requests, confirmed_frame) tick script with a rollback of
    ``depth`` frames per cycle: steady confirmed ticks, then ``depth``
    predicted ticks (repeat-last), then the canonical recovery tick. Each
    slot gets a different seed AND a different depth — the heterogeneous
    shape the batch must absorb in one dispatch."""
    rng = np.random.RandomState(seed)
    script = []
    frame = 0
    for _ in range(cycles):
        for _ in range(3):  # confirmed steady ticks
            bits = rng.randint(0, 16, size=P)
            script.append((step_requests(frame, bits), frame))
            frame += 1
        frontier = frame - 1
        pred = rng.randint(0, 16, size=P)  # the stalled prediction
        for d in range(depth):  # predicted ticks, frontier stalled
            script.append((step_requests(frame + d, pred), frontier))
        frame += depth
        # Recovery: corrected history for the predicted span + one new
        # confirmed frame, in one request list.
        corrected = [
            (pred if rng.rand() < 0.5 else rng.randint(0, 16, size=P))
            for _ in range(depth)
        ]
        new_bits = rng.randint(0, 16, size=P)
        reqs = rollback_requests(frame - depth, corrected)
        reqs += step_requests(frame, new_bits)
        script.append((reqs, frame))
        frame += 1
    return script


def make_core(num_slots=4, **kw):
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC, num_slots=num_slots,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES, **kw,
    )
    core.warmup()
    return core


def make_singleton(spec=True, **kw):
    if spec:
        r = SpeculativeRollbackRunner(
            box_game.make_schedule(), box_game.make_world(P).commit(),
            max_prediction=MAXPRED, num_players=P,
            input_spec=box_game.INPUT_SPEC,
            num_branches=BRANCHES, spec_frames=SPEC_FRAMES, **kw,
        )
    else:
        r = RollbackRunner(
            box_game.make_schedule(), box_game.make_world(P).commit(),
            max_prediction=MAXPRED, num_players=P,
            input_spec=box_game.INPUT_SPEC,
        )
    r.warmup()
    return r


def assert_slot_equals_runner(core, slot, runner):
    assert core.slots[slot].frame == runner.frame
    assert combine64(checksum(core.slot_state(slot))) == combine64(
        checksum(runner.state)
    )
    assert np.array_equal(
        np.asarray(core.rings.frames)[slot], np.asarray(runner.ring.frames)
    )
    assert np.array_equal(
        np.asarray(core.rings.checksums)[slot],
        np.asarray(runner.ring.checksums),
    )


def drive(core, scripts):
    """Run per-slot scripts through the core, slot-heterogeneous lengths
    allowed (shorter scripts' slots idle as no-op lanes)."""
    for t in range(max(len(s) for s in scripts.values())):
        work = {
            slot: (script[t][0], script[t][1], None)
            for slot, script in scripts.items()
            if t < len(script)
        }
        core.tick(work)


def test_parity_heterogeneous_rollback_depths():
    """Four slots, rollback depths 1..4 with distinct input streams, vs
    BOTH a spec-ON singleton tick() run and a plain serial RollbackRunner
    replay — bitwise state/ring parity for every slot."""
    core = make_core(num_slots=4)
    slots = [core.admit() for _ in range(4)]
    scripts = {
        s: make_script(seed=100 + s, depth=1 + s, cycles=3) for s in slots
    }
    drive(core, scripts)
    for s in slots:
        spec = make_singleton(spec=True)
        for reqs, confirmed in scripts[s]:
            spec.tick(reqs, confirmed, None)
        assert_slot_equals_runner(core, s, spec)
        serial = make_singleton(spec=False)
        for reqs, _ in scripts[s]:
            serial.handle_requests(reqs, None)
        assert core.slots[s].frame == serial.frame
        assert combine64(checksum(core.slot_state(s))) == combine64(
            checksum(serial.state)
        )


def test_parity_spec_branches_commit():
    """A script shaped for the structured tree (one player deviates, the
    other holds) must produce speculative commits in the batch AND stay
    bitwise-equal to the singleton — state parity must hold through the
    absorb path, not just the serial-burst path.

    Pinned predictor-OFF: the deviation below was crafted to land inside
    the HEURISTIC ranking's branch budget, which a learned ranking is
    free to order differently (predictor-ON absorb coverage lives in
    tests/test_predictor.py's session suite)."""
    core = make_core(num_slots=2, predictor=False)
    slot = core.admit()
    script = [(step_requests(f, [f % 4, (f + 1) % 4]), f) for f in range(3)]
    script.append((step_requests(3, [2, 3]), 2))
    script.append((step_requests(4, [2, 3]), 2))
    reqs = rollback_requests(3, [[1, 3], [1, 3]])
    reqs += step_requests(5, [1, 3])
    script.append((reqs, 5))
    drive(core, {slot: script})
    assert core.spec_hits >= 1  # the absorb path actually exercised
    spec = make_singleton(spec=True, predictor=False)
    for r, confirmed in script:
        spec.tick(r, confirmed, None)
    assert_slot_equals_runner(core, slot, spec)


def test_parity_with_admit_retire_mid_run():
    """Slot churn mid-run: a retired slot's row is dead weight, a
    readmitted slot starts fresh — neither may perturb surviving slots'
    trajectories (no-op lanes are semantically inert)."""
    core = make_core(num_slots=3)
    s0, s1 = core.admit(), core.admit()
    sc0 = make_script(seed=7, depth=2, cycles=4)
    sc1 = make_script(seed=8, depth=3, cycles=4)
    half = len(sc1) // 2
    drive(core, {s0: sc0[:half], s1: sc1[:half]})
    core.retire(s0)
    s2 = core.admit()  # fresh match joins mid-run
    sc2 = make_script(seed=9, depth=1, cycles=2)
    drive(core, {s1: sc1[half:], s2: sc2})
    # s1 ran its full script across the churn; s2 ran sc2 from scratch.
    for slot, script in ((s1, sc1), (s2, sc2)):
        spec = make_singleton(spec=True)
        for reqs, confirmed in script:
            spec.tick(reqs, confirmed, None)
        assert_slot_equals_runner(core, slot, spec)


def test_admit_retire_zero_recompiles():
    """After warmup, any amount of match churn leaves the compiled-variant
    count and the backend-compile counter untouched (traced slot indices +
    fixed batch shape: the no-recompile acceptance contract)."""
    assert xla_cache.install_compile_listeners()
    core = make_core(num_slots=4)
    s = core.admit()
    drive(core, {s: make_script(seed=1, depth=2, cycles=1)})
    cache0 = core._exec.cache_size()
    base = xla_cache.compile_counters()["backend_compiles"]
    for k in range(3):
        core.retire(s)
        s = core.admit()
        s2 = core.admit()
        drive(core, {
            s: make_script(seed=20 + k, depth=1 + k, cycles=1),
            s2: make_script(seed=30 + k, depth=2, cycles=1),
        })
        core.retire(s2)
    assert xla_cache.compile_counters()["backend_compiles"] == base
    assert core._exec.cache_size() == cache0 == 1


def test_dispatch_enters_one_context():
    """The device dispatch is timed by ONE instrument: ``_finish_dispatch``
    enters span ``serve_dispatch`` and nothing beside it (no second clock
    hooked into the hot path), once a dispatch."""
    fn = ast.parse(
        textwrap.dedent(inspect.getsource(BatchedSessionCore._finish_dispatch))
    )
    entered = [
        ast.unparse(item.context_expr)
        for node in ast.walk(fn) if isinstance(node, ast.With)
        for item in node.items
    ]
    assert entered == ["self.span('serve_dispatch')"]

    tracer = SpanTracer()
    core = make_core(num_slots=2, tracer=tracer)
    assert not hasattr(core, "attribution")
    slot = core.admit()
    before = tracer.summary().get("serve_dispatch", {}).get("count", 0)
    dispatched = core.device_dispatches_total
    drive(core, {slot: make_script(seed=3, depth=2, cycles=1)})
    assert core.device_dispatches_total > dispatched
    assert (
        tracer.summary()["serve_dispatch"]["count"] - before
        == core.device_dispatches_total - dispatched
    )


def test_checksum_reports_match_serial():
    """Deferred per-slot checksum reports must deliver the same
    (frame -> checksum) map a serial synchronous run reports."""

    class Log:
        def __init__(self):
            self.seen = {}

        def wants_checksum(self, frame):
            return True

        def report_checksum(self, frame, cs):
            self.seen[frame] = int(cs)

    core = make_core(num_slots=2)
    slot = core.admit()
    script = make_script(seed=5, depth=2, cycles=2)
    log = Log()
    for reqs, confirmed in script:
        core.tick({slot: (reqs, confirmed, log)})
    core.flush_reports()
    oracle = make_singleton(spec=False)
    olog = Log()
    for reqs, _ in script:
        oracle.handle_requests(reqs, olog)
    for f, cs in olog.seen.items():
        assert log.seen[f] == cs, f


def test_session_axis_env_is_bitwise(monkeypatch):
    """GGRS_SESSION_AXIS conformance mode: the singleton runner computed
    through the vmapped session-axis program (broadcast + slice slot 0)
    must be bitwise-identical to the plain singleton."""
    script = make_script(seed=3, depth=3, cycles=2)
    plain = make_singleton(spec=True)
    for reqs, confirmed in script:
        plain.tick(reqs, confirmed, None)
    monkeypatch.setenv("GGRS_SESSION_AXIS", "3")
    axised = make_singleton(spec=True)
    assert axised._fused.session_axis == 3
    for reqs, confirmed in script:
        axised.tick(reqs, confirmed, None)
    assert plain.frame == axised.frame
    assert combine64(checksum(plain.state)) == combine64(
        checksum(axised.state)
    )
    assert np.array_equal(
        np.asarray(plain.ring.checksums), np.asarray(axised.ring.checksums)
    )
    assert (plain.spec_hits, plain.spec_misses) == (
        axised.spec_hits, axised.spec_misses
    )


def test_match_server_synctest_end_to_end():
    """MatchServer driving synctest sessions (which self-verify via their
    forced-rollback checksum compare): matches advance in lockstep,
    occupancy gauges track churn, and per-slot metrics export with the
    match_slot label."""
    from bevy_ggrs_tpu.obs.prom import export_prometheus
    from bevy_ggrs_tpu.obs.recorder import FlightRecorder
    from bevy_ggrs_tpu.utils.metrics import Metrics

    metrics = Metrics()
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC,
        capacity=4, stagger_groups=2, num_branches=BRANCHES,
        spec_frames=SPEC_FRAMES, metrics=metrics,
    )
    server.warmup()

    def make_session():
        return (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(P)
            .with_max_prediction_window(MAXPRED)
            .with_check_distance(2)
            .start_synctest_session()
        )

    def inputs_for(seed):
        def f(frame, handle):
            return np.uint8((frame * 3 + handle * 5 + seed) % 16)

        return f

    handles = [
        server.add_match(make_session(), inputs_for(k)) for k in range(3)
    ]
    for _ in range(12):
        server.run_frame()
    assert server.slots_active == 3 and server.slots_free == 1
    for h in handles:
        assert server.groups[h.group].slots[h.slot].frame == 12
    server.retire_match(handles[0])
    assert server.slots_active == 2
    for _ in range(4):
        server.run_frame()
    rec = FlightRecorder()
    r = rec.capture(server=server)
    assert r.slots_active == 2 and r.slots_free == 2
    assert r.stagger_jitter_ms is not None
    text = export_prometheus(metrics)
    assert 'match_slot="' in text
    assert "ggrs_frames_served_total" in text


def test_non_standard_burst_rejected():
    from bevy_ggrs_tpu.serve.faults import SlotFault

    core = make_core(num_slots=2)
    slot = core.admit()
    with pytest.raises(SlotFault) as exc:
        core.tick({slot: ([adv([1, 2])], 0, None)})  # advance without save
    assert exc.value.slot == slot
    assert exc.value.reason == "non_canonical_burst"
