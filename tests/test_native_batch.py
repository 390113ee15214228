"""Batched-native data plane: bitwise parity vs the per-slot Python path.

The :class:`~bevy_ggrs_tpu.native.spec.NativeBatchPlane` consolidates the
whole per-slot host loop — as-used log appends, in-flight tree matches,
predictor window gathers, branch-tree builds and no-op tree re-use —
into two C calls per dispatch (``serve/batch.py::_dispatch_native``).
The committed device state is a function of the arrays these calls
produce, so the plane must be BITWISE identical to the per-slot path it
replaces (`_dispatch_python`, the ``GGRS_NO_NATIVE=1`` route): same jit
argument tensors, same branch trees, same predictor windows, same
committed state/rings — across heterogeneous rollback depths, predictor
ON and OFF, and admit/retire churn (which must also never recompile).

The in-process A/B here pins ``_plane = None`` on one core, which is
exactly the router's ``GGRS_NO_NATIVE=1`` fallback; CI additionally runs
this whole file under ``GGRS_NO_NATIVE=1`` so the pure-Python leg stays
exercised end to end.

Also covered: the MatchServer slot-template pool — a template-admitted
match must be indistinguishable (bitwise) from a cold-admitted one.
"""

import numpy as np
import pytest

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.serve.server import MatchServer
from bevy_ggrs_tpu.session.builder import SessionBuilder
from bevy_ggrs_tpu.state import checksum, combine64
from bevy_ggrs_tpu.utils import xla_cache
from tests.test_batched_sessions import drive, make_script

P = 2
MAXPRED = 4
BRANCHES = 8
SPEC_FRAMES = 3

native = pytest.mark.skipif(
    not ncore.available(), reason="native session core did not build"
)


def make_core(num_slots=4, plane=True, **kw):
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC, num_slots=num_slots,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES, **kw,
    )
    if not plane:
        # Exactly the GGRS_NO_NATIVE=1 router fallback: _dispatch routes
        # to _dispatch_python when the plane is absent.
        core._plane = None
    core.warmup()
    return core


def capture_jit_args(core):
    """Record a deep copy of every dispatch's 15 jit argument arrays —
    the complete host->device contract (branch selectors, absorb
    metadata, staged bits/statuses, phase masks, branch trees)."""
    captured = []
    orig = core._finish_dispatch

    def wrapper(jit_args, post, reports):
        captured.append(tuple(np.array(a, copy=True) for a in jit_args))
        return orig(jit_args, post, reports)

    core._finish_dispatch = wrapper
    return captured


def assert_cores_bitwise_equal(nat, py, cap_n, cap_p):
    assert len(cap_n) == len(cap_p) > 0
    for d, (an, ap) in enumerate(zip(cap_n, cap_p)):
        for j, (x, y) in enumerate(zip(an, ap)):
            assert np.array_equal(x, y), (
                f"dispatch {d}: jit arg {j} diverges"
            )
    for s in nat.slots:
        assert s.frame == py.slots[s.index].frame
        if s.active:
            assert combine64(checksum(nat.slot_state(s.index))) == combine64(
                checksum(py.slot_state(s.index))
            )
    assert np.array_equal(
        np.asarray(nat.rings.frames), np.asarray(py.rings.frames)
    )
    assert np.array_equal(
        np.asarray(nat.rings.checksums), np.asarray(py.rings.checksums)
    )
    assert (nat.spec_hits, nat.spec_partial_hits, nat.spec_misses) == (
        py.spec_hits, py.spec_partial_hits, py.spec_misses
    )


def heterogeneous_scripts(rng, slots, cycles=3):
    """Distinct seed AND rollback depth per slot, plus one slot with a
    shorter script so the no-op lane (tree re-use copy path) runs."""
    scripts = {}
    for k, s in enumerate(slots):
        depth = 1 + (k % MAXPRED)
        c = cycles - 1 if k == len(slots) - 1 else cycles
        scripts[s] = make_script(
            seed=int(rng.randint(1 << 30)), depth=depth, cycles=c
        )
    return scripts


@native
@pytest.mark.parametrize("trial", [0, 1])
def test_parity_predictor_off(trial):
    """Property-based A/B: randomized heterogeneous-depth scripts through
    the plane vs the per-slot path — every jit argument tensor (including
    the [S,B,F] branch trees) and all committed state bitwise equal."""
    from bevy_ggrs_tpu.utils.metrics import Metrics

    rng = np.random.RandomState(1000 + trial)
    mn, mp = Metrics(), Metrics()
    nat = make_core(plane=True, predictor=False, metrics=mn)
    py = make_core(plane=False, predictor=False, metrics=mp)
    assert nat._plane is not None and py._plane is None
    cap_n, cap_p = capture_jit_args(nat), capture_jit_args(py)
    slots = [nat.admit() for _ in range(4)]
    for _ in range(4):
        py.admit()
    scripts = heterogeneous_scripts(rng, slots)
    drive(nat, scripts)
    drive(py, scripts)
    assert_cores_bitwise_equal(nat, py, cap_n, cap_p)
    assert nat.native_batch_calls > 0
    assert py.native_batch_calls == 0
    assert sum(mn.series["native_batch_ms"]) > 0.0
    # Satellite counters: the consolidated call is attributable.
    assert mn.counters["native_batch_calls"] == nat.native_batch_calls
    assert len(mn.series["native_batch_ms"]) > 0
    assert "native_batch_calls" not in mp.counters
    # The host-work decomposition stays a real measured split on BOTH
    # paths (not a dead column): the build sub-span is the batched build
    # call's wall time, arg assembly the rest of the staging loop.
    for m in (mn, mp):
        assert len(m.series["serve_branch_build_ms"]) > 0
        assert len(m.series["serve_arg_assembly_ms"]) > 0
    assert sum(mn.series["serve_branch_build_ms"]) > 0.0


@native
def test_parity_predictor_on_trees_and_windows():
    """Predictor ON: the plane's batched window gather + seed staging
    must reproduce the Python path's per-slot
    ``predictor.window_indices`` + ``render_seed`` route bitwise — any
    divergence flips candidate order and shows up in the seeded branch
    trees the jit args carry."""
    rng = np.random.RandomState(77)
    nat = make_core(plane=True, predictor=True)
    if nat._predictor is None:
        pytest.skip("default predictor artifact does not bind box_game")
    py = make_core(plane=False, predictor=True)
    assert nat._plane is not None and py._plane is None
    cap_n, cap_p = capture_jit_args(nat), capture_jit_args(py)
    slots = [nat.admit() for _ in range(4)]
    for _ in range(4):
        py.admit()
    scripts = heterogeneous_scripts(rng, slots)
    drive(nat, scripts)
    drive(py, scripts)
    assert_cores_bitwise_equal(nat, py, cap_n, cap_p)
    assert nat.predictor_rank_dispatches > 0
    assert py.predictor_rank_dispatches > 0
    # Direct window check: the last dispatch's gathered [W, P] universe
    # indices for every ranked slot must equal the Python oracle
    # recomputed from the same log at the same anchor.
    plane = nat._plane
    checked = 0
    for s in nat.slots:
        if not s.active or not plane.win_mask[s.index]:
            continue
        want = nat._predictor.window_indices(
            s.input_log, int(plane.win_anchors[s.index]), P
        )
        assert np.array_equal(plane.wins[s.index], want), s.index
        checked += 1
    assert checked > 0


@native
def test_churn_zero_recompiles_on_plane():
    """Admit/retire churn through the batched-native dispatch leaves the
    backend-compile counter and the executor cache untouched — the plane
    stages into persistent [S, ...] SoA buffers and fresh-per-dispatch
    jit args, never shape-specialized per occupancy."""
    assert xla_cache.install_compile_listeners()
    core = make_core(plane=True, predictor=False)
    s = core.admit()
    drive(core, {s: make_script(seed=1, depth=2, cycles=1)})
    calls0 = core.native_batch_calls
    cache0 = core._exec.cache_size()
    base = xla_cache.compile_counters()["backend_compiles"]
    for k in range(3):
        core.retire(s)
        s = core.admit()
        s2 = core.admit()
        drive(core, {
            s: make_script(seed=40 + k, depth=1 + k, cycles=1),
            s2: make_script(seed=50 + k, depth=2, cycles=1),
        })
        core.retire(s2)
    assert xla_cache.compile_counters()["backend_compiles"] == base
    assert core._exec.cache_size() == cache0 == 1
    assert core.native_batch_calls > calls0


# ---------------------------------------------------------------------------
# SyncTest groups: one Load-delimited list a frame, one dispatch a tick
# ---------------------------------------------------------------------------

PLANES = [
    pytest.param("native", marks=native),
    # The whole GGRS_NO_NATIVE=1 route: sessions on PyQueueSet, the core
    # on _dispatch_python.
    "python",
]


def _synctest_tick(core, sessions, frame):
    work = {}
    for slot, session in sessions.items():
        for h in range(P):
            session.add_local_input(h, _inputs_for(slot)(frame, h))
        work[slot] = (session.advance_frame(), None, session)
    core.tick(work)


@pytest.mark.parametrize("plane", PLANES)
def test_synctest_group_is_one_dispatch_a_tick(plane, monkeypatch):
    """A group of SyncTest matches past frame ``check_distance`` makes
    exactly one device dispatch a ``BatchedSessionCore.tick`` (the forced
    rollback and the frame's own step are one canonical segment), series
    ``serve_rounds`` reads 1, the matches' own checksum compares stay
    silent, and the batched executable is still the one of warm-up."""
    from bevy_ggrs_tpu.utils.metrics import Metrics

    if plane == "python":
        monkeypatch.setattr(ncore, "available", lambda: False)
    metrics = Metrics()
    core = make_core(num_slots=4, metrics=metrics)
    assert (core._plane is not None) == (plane == "native")
    sessions = {core.admit(): _make_session() for _ in range(3)}
    for f in range(12):
        d0, t0 = core.device_dispatches_total, core.ticks_total
        _synctest_tick(core, sessions, f)
        assert core.ticks_total - t0 == 1
        assert core.device_dispatches_total - d0 == 1, f
    core.flush_reports()  # the last tick's compares
    assert metrics.series["serve_rounds"] == [1] * 12
    assert all(core.slots[s].frame == 12 for s in sessions)
    assert core.rollbacks_total == 3 * 10  # every match, every frame >= 2
    assert core._exec.cache_size() == 1


@pytest.mark.parametrize("plane", PLANES)
def test_two_loads_in_a_list_still_run_as_two_rounds(plane, monkeypatch):
    """The general path stays: a list that does hold two ``Load``s (none
    of this repo's sessions emits one since PR 34) is two segments, two
    rounds, two dispatches, beside a one-segment slot that idles in the
    second round; the result is what two ticks would have given."""
    from bevy_ggrs_tpu.utils.metrics import Metrics
    from tests.test_batched_sessions import (
        rollback_requests,
        step_requests,
    )

    if plane == "python":
        monkeypatch.setattr(ncore, "available", lambda: False)
    metrics = Metrics()
    twice, once = make_core(num_slots=2, metrics=metrics), make_core(
        num_slots=2)
    rng = np.random.RandomState(3)
    bits = [rng.randint(0, 16, size=P) for _ in range(4)]
    for core in (twice, once):
        a, b = core.admit(), core.admit()
        for f in range(3):
            core.tick({s: (step_requests(f, bits[f]), None, None)
                       for s in (a, b)})
    first = rollback_requests(1, bits[1:3])  # Load(1), frames 1, 2
    second = rollback_requests(2, bits[2:3]) + step_requests(3, bits[3])
    d0 = twice.device_dispatches_total
    twice.tick({a: (first + second, None, None),
                b: (step_requests(3, bits[3]), None, None)})
    assert twice.device_dispatches_total - d0 == 2
    assert metrics.series["serve_rounds"][-1] == 2
    once.tick({a: (first, None, None),
               b: (step_requests(3, bits[3]), None, None)})
    once.tick({a: (second, None, None)})
    for s in (a, b):
        assert twice.slots[s].frame == once.slots[s].frame == 4
        assert combine64(checksum(twice.slot_state(s))) == combine64(
            checksum(once.slot_state(s)))
    assert np.array_equal(
        np.asarray(twice.rings.checksums), np.asarray(once.rings.checksums))


# ---------------------------------------------------------------------------
# Slot template pool: pre-warmed admission is bitwise-invisible
# ---------------------------------------------------------------------------


def _make_server():
    srv = MatchServer(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC,
        capacity=2, stagger_groups=1, num_branches=BRANCHES,
        spec_frames=SPEC_FRAMES,
    )
    srv.warmup()
    return srv


def _make_session():
    return (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(P)
        .with_max_prediction_window(MAXPRED)
        .with_check_distance(2)
        .start_synctest_session()
    )


def _inputs_for(seed):
    def f(frame, handle):
        return np.uint8((frame * 3 + handle * 5 + seed) % 16)

    return f


def test_template_pool_is_codec_identity():
    """The pool's decoded state must be flat-byte identical to the live
    template, and its ring identical to a cold ``ring_init`` — the
    witness that template admission cannot perturb anything."""
    import jax

    from bevy_ggrs_tpu.state import ring_init

    srv = _make_server()
    assert srv._slot_templates
    tpl_ring, tpl_state = srv._slot_templates[0]
    core = srv.groups[0]
    for x, y in zip(
        jax.tree_util.tree_leaves(tpl_state),
        jax.tree_util.tree_leaves(core._template),
    ):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    cold = ring_init(core._template, core.ring_depth)
    assert np.array_equal(
        np.asarray(tpl_ring.frames), np.asarray(cold.frames)
    )
    assert np.array_equal(
        np.asarray(tpl_ring.checksums), np.asarray(cold.checksums)
    )
    for x, y in zip(
        jax.tree_util.tree_leaves(tpl_ring.states),
        jax.tree_util.tree_leaves(cold.states),
    ):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_template_admission_bitwise_continuity():
    """A match admitted through the pre-warmed template pool must run
    bitwise identical to one cold-admitted on a pool-less server: same
    per-frame state checksums, same ring contents, zero desyncs (the
    synctest sessions self-verify every frame)."""
    warm, cold = _make_server(), _make_server()
    assert warm._slot_templates
    cold._slot_templates = []  # force the per-joiner ring_init path
    hw = warm.add_match(_make_session(), _inputs_for(3))
    hc = cold.add_match(_make_session(), _inputs_for(3))
    assert warm.templates_admitted == 1
    assert cold.templates_admitted == 0
    for _ in range(20):
        warm.run_frame()
        cold.run_frame()
    cw, cc = warm.groups[hw.group], cold.groups[hc.group]
    assert cw.slots[hw.slot].frame == cc.slots[hc.slot].frame == 20
    assert combine64(checksum(cw.slot_state(hw.slot))) == combine64(
        checksum(cc.slot_state(hc.slot))
    )
    assert np.array_equal(
        np.asarray(cw.rings.frames)[hw.slot],
        np.asarray(cc.rings.frames)[hc.slot],
    )
    assert np.array_equal(
        np.asarray(cw.rings.checksums)[hw.slot],
        np.asarray(cc.rings.checksums)[hc.slot],
    )
    # Queued admissions ride the template pool too (the recycled entry
    # means churn never drains it) — and a pooled admission drains at
    # the TOP of the frame, so it ticks on the very frame that drains
    # it (5 run_frames -> frame 5, not 4).
    warm.retire_match(hw)
    h2 = warm.enqueue_match(_make_session(), _inputs_for(5))
    warm.run_frame()
    assert warm.templates_admitted == 2
    assert len(warm._slot_templates) == warm.admit_budget * len(warm.groups)
    for _ in range(4):
        warm.run_frame()
    assert warm.groups[h2.group].slots[h2.slot].frame == 5
