"""Rollout engine tests: fused save/advance bursts, rollback restore,
padding-mask no-ops, and equivalence with serial execution.

Contract under test: one `RolloutExecutor.run` call must be observably
identical to the reference's serial request loop
(`/root/reference/src/ggrs_stage.rs:259-306`) executing
[Load?, (Save, Advance)*] one request at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import checksum, combine64, ring_init, ring_load, ring_save
from bevy_ggrs_tpu.branch_tree import (
    BranchTree,
    distinct_prefixes,
    rollout_world_steps,
)
from bevy_ggrs_tpu.fused import LANE_AXIS
from bevy_ggrs_tpu.models import boids, box_game, particles
from bevy_ggrs_tpu.parallel.speculate import SpeculativeExecutor
from bevy_ggrs_tpu.rollout import (
    RolloutExecutor,
    advance_n,
    prefix_classes,
    rollout_branches,
    rollout_form,
    share_width,
)
from bevy_ggrs_tpu.schedule import (
    PREDICTED,
    InputSpec,
    Schedule,
    make_inputs,
)
from bevy_ggrs_tpu.state import (
    ONCE,
    STEPS,
    HostWorld,
    TypeRegistry,
    branch_rows_shaped,
)
from tests.test_lane_uniform_ring import SPECIAL, assert_bits_equal


def setup(num_players=2, depth=8, max_frames=9):
    state = box_game.make_world(num_players).commit()
    sched = box_game.make_schedule()
    ring = ring_init(state, depth)
    ex = RolloutExecutor(sched, max_frames)
    return state, sched, ring, ex


def serial_reference(sched, ring, state, start_frame, bits_seq):
    """The reference's serial loop: per frame, ring_save then schedule."""
    css = []
    frame = start_frame
    for bits in bits_seq:
        ring, cs = ring_save(ring, state, frame)
        state = sched(state, make_inputs(bits))
        css.append(combine64(cs))
        frame += 1
    return ring, state, css


def rand_bits(rng, n, players):
    return rng.randint(0, 16, size=(n, players)).astype(np.uint8)


def test_burst_equals_serial():
    state, sched, ring, ex = setup()
    rng = np.random.RandomState(11)
    bits = rand_bits(rng, 5, 2)
    status = np.zeros((5, 2), np.int32)

    r1, s1, cs1 = ex.run(ring, state, 0, bits, status, n_frames=5)
    r2, s2, cs2 = serial_reference(sched, ring, state, 0, bits)

    assert [combine64(c) for c in np.asarray(cs1)[:5]] == cs2
    assert combine64(checksum(s1)) == combine64(checksum(s2))
    np.testing.assert_array_equal(np.asarray(r1.frames), np.asarray(r2.frames))
    for f in range(5):
        np.testing.assert_array_equal(
            np.asarray(ring_load(r1, f).components["translation"]),
            np.asarray(ring_load(r2, f).components["translation"]),
        )


def test_padding_steps_are_noops():
    state, sched, ring, ex = setup(max_frames=9)
    bits = np.zeros((2, 2), np.uint8)
    status = np.zeros((2, 2), np.int32)
    r, s, cs = ex.run(ring, state, 0, bits, status, n_frames=2)
    # Only frames 0 and 1 saved; padding produced zero checksums and no writes.
    assert int(r.frames[0]) == 0 and int(r.frames[1]) == 1
    assert int(r.frames[2]) == -1
    assert all(combine64(c) == 0 for c in np.asarray(cs)[2:])
    assert int(s.resources["frame_count"]) == 2


def test_rollback_load_then_resimulate():
    """Save frames 0..4 advancing with inputs A; then roll back to frame 2 and
    resimulate with inputs B — must equal plain advance of A[:2]+B from
    scratch (the misprediction-recovery semantics, survey §3.3)."""
    state, sched, ring, ex = setup()
    rng = np.random.RandomState(5)
    A = rand_bits(rng, 5, 2)
    B = rand_bits(rng, 3, 2)
    status5 = np.zeros((5, 2), np.int32)
    status3 = np.zeros((3, 2), np.int32)

    ring1, mispredicted, _ = ex.run(ring, state, 0, A, status5, n_frames=5)
    ring2, corrected, cs = ex.run(
        ring1, mispredicted, 5, B, status3, n_frames=3, load_frame=2
    )

    # Oracle: run A[0:2] then B from the initial state.
    oracle = state
    for bits in list(A[:2]) + list(B):
        oracle = sched(oracle, make_inputs(bits))
    assert combine64(checksum(corrected)) == combine64(checksum(oracle))
    assert int(corrected.resources["frame_count"]) == 5
    # Re-saved frames 2..4 must now hold the corrected timeline.
    resaved = ring_load(ring2, 3)
    oracle3 = state
    for bits in list(A[:2]) + [B[0]]:
        oracle3 = sched(oracle3, make_inputs(bits))
    assert combine64(checksum(resaved)) == combine64(checksum(oracle3))


def test_resimulation_checksums_match_original_when_inputs_agree():
    """SyncTest property at the rollout level: rollback + resimulate with the
    SAME inputs reproduces identical per-frame checksums."""
    state, sched, ring, ex = setup()
    rng = np.random.RandomState(42)
    bits = rand_bits(rng, 6, 2)
    status = np.zeros((6, 2), np.int32)
    ring1, s1, cs_orig = ex.run(ring, state, 0, bits, status, n_frames=6)
    ring2, s2, cs_resim = ex.run(
        ring1, s1, 6, bits[2:], status[2:], n_frames=4, load_frame=2
    )
    np.testing.assert_array_equal(np.asarray(cs_resim)[:4], np.asarray(cs_orig)[2:6])
    assert combine64(checksum(s1)) == combine64(checksum(s2))


def test_burst_too_long_raises():
    state, sched, ring, ex = setup(max_frames=4)
    bits = np.zeros((5, 2), np.uint8)
    try:
        ex.run(ring, state, 0, bits, np.zeros((5, 2), np.int32), n_frames=5)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


def test_advance_n_matches_schedule_loop():
    state, sched, ring, ex = setup()
    rng = np.random.RandomState(9)
    bits = rand_bits(rng, 7, 2)
    out = advance_n(sched, state, jnp.asarray(bits))
    oracle = state
    for b in bits:
        oracle = sched(oracle, make_inputs(b))
    assert combine64(checksum(out)) == combine64(checksum(oracle))


# ---------------------------------------------------------------------------
# The shared rollout (PR 58): a level steps each DISTINCT input prefix of the
# tree once (``rollout.py`` ``share_width`` / ``prefix_classes`` /
# ``_rollout_shared``) and returns the plain ``vmap``'s bits.
# ---------------------------------------------------------------------------

SB, SF, SP = 8, 6, 2  # a shared tree: branches, frames, players
S_STATUS = jnp.full((SF, SP), PREDICTED, jnp.int32)
S_INPUTS = np.zeros((SP,), np.uint8)
S_SPEC = InputSpec(shape=(), dtype=jnp.uint8, values=tuple(range(16)))


def _herd_system(state, inputs):
    """Two large leaves follow the inputs; ``mass``, where the world has
    it, is a large leaf no input reaches (``ONCE``)."""
    push = jnp.sum(inputs.bits.astype(jnp.float32) * jnp.arange(1, SP + 1))
    vel = state.components["vel"] * jnp.float32(0.5) + push
    new = {"pos": state.components["pos"] + jnp.pad(vel, ((0, 0), (0, 1))),
           "vel": vel}
    if "mass" in state.components:
        new["mass"] = state.components["mass"] * jnp.float32(1.5) + 1
    return state.replace(
        components=new,
        resources={"tick": state.resources["tick"] + 1},
    )


@functools.lru_cache(maxsize=None)
def herd(once: bool):
    """``(schedule, state, form)`` of a world whose rows are 4-6 KiB."""
    reg = TypeRegistry()
    reg.register_component("pos", shape=(3,))
    reg.register_component("vel", shape=(2,))
    if once:
        reg.register_component("mass", shape=(2,))
    reg.register_resource("tick", jnp.int32(0))
    world = HostWorld(reg, 512)
    for i in range(500):
        world.spawn({"pos": [i, -i, 0.5], "vel": [0.25 * i, -1.0],
                     **({"mass": [i, 2.0]} if once else {})}, rollback_id=i)
    state = world.commit()
    state = state.replace(components={
        k: jnp.asarray(np.resize(SPECIAL, v.shape).view(np.float32))
        for k, v in state.components.items()})
    schedule = Schedule([_herd_system])
    form = rollout_form(schedule, state, S_INPUTS)
    kinds = [k for k in jax.tree_util.tree_leaves(form) if k in (STEPS, ONCE)]
    assert kinds.count(ONCE) == int(once) and kinds.count(STEPS) == 2
    assert share_width(form, state, SB) == 1
    return schedule, state, form


def _tree(**kw):
    return BranchTree(S_SPEC, SP, SB, SF, tuple(range(16)), **kw)


def _structured(log, last, pinned=()):
    known = np.zeros((SF, SP), np.uint8)
    mask = np.zeros((SF, SP), bool)
    for t, h, v in pinned:
        known[t, h], mask[t, h] = v, True
    anchor = max(log, default=-1) + 1
    return _tree().structured_bits(
        log, np.asarray(last, np.uint8), known, mask, anchor=anchor)


def _periodic_log():
    return {f: np.array([(1, 2, 4)[f % 3], 8], np.uint8) for f in range(24)}


TREES = {
    # every branch a copy of branch 0 up to its one change
    "repeat_last": lambda: _structured({}, [3, 5]),
    # a rhythm in the log: the single changes hang off branch 1
    "periodic": lambda: _structured(_periodic_log(), [4, 8]),
    # the server's own seat known for three frames: nothing changes there
    "pinned": lambda: _structured(
        {}, [3, 5], [(0, 0, 3), (1, 0, 7), (2, 0, 7)]),
    "identical": lambda: np.broadcast_to(
        np.array([3, 5], np.uint8), (SB, SF, SP)).copy(),
    "differ_at_0": lambda: np.stack([
        np.full((SF, SP), b, np.uint8) for b in range(SB)]),
    "random": lambda: np.random.default_rng(5).integers(
        0, 3, size=(SB, SF, SP)).astype(np.uint8),
}
# the distinct (branch, frame) prefixes of each, counted by hand or by
# ``len(set(...))`` below; the default tree's stay well under B x F
DISTINCT = {"identical": SF, "differ_at_0": SB * SF}


def _count_by_sets(bits):
    return [len({bits[b, :f + 1].tobytes() for b in range(bits.shape[0])})
            for f in range(bits.shape[1])]


@pytest.mark.parametrize("tree", list(TREES))
def test_the_hosts_count_of_distinct_prefixes_is_the_devices(tree):
    bits = TREES[tree]()
    want = _count_by_sets(bits)
    assert distinct_prefixes(bits).tolist() == want
    order, slot, n = jax.jit(prefix_classes)(jnp.asarray(bits))
    assert np.asarray(n).tolist() == want
    if tree in DISTINCT:
        assert sum(want) == DISTINCT[tree]
    if tree in ("repeat_last", "periodic", "pinned"):
        assert SF < sum(want) < SB * SF     # the tree shares, and branches
        assert want == sorted(want)         # a level never has fewer
    if tree == "periodic":                  # ... and hang off branch 1
        assert not np.array_equal(bits[0], bits[1])
        first = np.asarray(slot)[0]
        assert first[1] != first[0]
        assert (first == first[1]).sum() > (first == first[0]).sum()
    # every branch's class is led by the lowest branch with its prefix
    order, slot = np.asarray(order), np.asarray(slot)
    for f in range(SF):
        for b in range(SB):
            lead = order[f, slot[f, b]]
            assert lead <= b and np.array_equal(
                bits[lead, :f + 1], bits[b, :f + 1])
            assert slot[f, b] < want[f]
    # the world-steps a lane runs, by widths
    for width in (1, 2, 4):
        steps, fill = rollout_world_steps(bits, width)
        assert steps == sum(-(-n // width) * width for n in want)
        assert fill == sum(want) / steps
    assert rollout_world_steps(bits, None) == (SB * SF, None)


def test_the_hosts_count_takes_lanes_and_payloads():
    lanes = np.stack([TREES[t]() for t in ("repeat_last", "identical",
                                           "differ_at_0", "pinned")])
    own = distinct_prefixes(lanes, lead=1)
    assert own.tolist() == [_count_by_sets(x) for x in lanes]
    steps, fill = rollout_world_steps(lanes, 1, lead=1)
    assert steps == int(own.max(axis=0).sum()) == SB * SF
    assert fill == own.sum() / (4 * steps)
    # bit patterns, not values: -0.0 is another input than 0.0
    stick = np.zeros((2, 3, SP, 2), np.float32)
    assert distinct_prefixes(stick).tolist() == [1, 1, 1]
    stick[1, 1, 0, 1] = -0.0
    assert distinct_prefixes(stick).tolist() == [1, 2, 2]
    _, _, n = prefix_classes(jnp.asarray(stick))
    assert np.asarray(n).tolist() == [1, 2, 2]


def test_shared_rollout_of_a_world_no_input_reaches():
    """Every leaf without a branch axis in the loop: the branches' rows
    are one world's, broadcast where the form asks for ``[B, ...]``."""
    _, state, _ = herd(True)
    deaf = Schedule([lambda st, inputs: _herd_system(
        st, inputs.replace(bits=jnp.zeros_like(inputs.bits)))])
    form = rollout_form(deaf, state, None)    # blind: large leaves keep B
    bits = jnp.asarray(TREES["repeat_last"]())
    got = jax.jit(lambda st, bb: rollout_branches(
        deaf, st, jnp.int32(3), bb, S_STATUS, form))(state, bits)
    assert_bits_equal(_shaped(got, form, state), _plain(deaf, state, 3, bits))
    seen = rollout_form(deaf, state, S_INPUTS)
    assert STEPS not in jax.tree_util.tree_leaves(seen)
    got = jax.jit(lambda st, bb: rollout_branches(
        deaf, st, jnp.int32(3), bb, S_STATUS, seen))(state, bits)
    assert_bits_equal(_shaped(got, seen, state), _plain(deaf, state, 3, bits))


def _plain(schedule, state, anchor, bits):
    return jax.jit(lambda st, a, bb: rollout_branches(
        schedule, st, a, bb, S_STATUS))(state, anchor, bits)


def _shaped(got, form, state):
    rings, states, cs = got
    rings, states = branch_rows_shaped(rings, states, form, state, SB)
    return rings, states, cs


@pytest.mark.parametrize("once", [False, True], ids=["steps", "once"])
@pytest.mark.parametrize("tree", list(TREES))
def test_shared_rollout_returns_the_plain_vmaps_bits(tree, once):
    schedule, state, form = herd(once)
    bits = jnp.asarray(TREES[tree]())
    shared = jax.jit(lambda st, a, bb: rollout_branches(
        schedule, st, a, bb, S_STATUS, form))
    got = shared(state, jnp.int32(41), bits)
    # the carried form: a large leaf [F, B, n], without B where no input
    # reaches it, its final state likewise
    for kind, x, end in zip(*map(jax.tree_util.tree_leaves,
                                 (form, got[0].states, got[1]))):
        if kind == STEPS:
            assert x.shape[:2] == (SF, SB) and x.ndim == 3
        if kind == ONCE:
            assert x.shape[0] == SF and x.ndim == 2 and end.ndim == 2
    assert_bits_equal(
        _shaped(got, form, state), _plain(schedule, state, 41, bits))


@pytest.mark.parametrize("once", [False, True], ids=["steps", "once"])
def test_shared_rollout_under_the_slot_vmap_runs_the_deepest_lanes_trips(once):
    schedule, state, form = herd(once)
    names = list(TREES)
    bits = jnp.asarray(np.stack([TREES[t]() for t in names]))
    anchors = jnp.asarray(7 + 5 * np.arange(len(names)), jnp.int32)
    states = jax.tree_util.tree_map(
        lambda x: jnp.stack([
            jnp.roll(x, i, 0) if x.ndim else x + i
            for i in range(len(names))]),
        state)
    served = lambda st, a, bb: jax.vmap(    # noqa: E731
        lambda s, x, b: rollout_branches(
            schedule, s, x, b, S_STATUS, form, LANE_AXIS),
        axis_name=LANE_AXIS)(st, a, bb)
    got = jax.jit(served)(states, anchors, bits)
    for i in range(len(names)):
        lane = jax.tree_util.tree_map(lambda x: x[i], (states, got))
        assert_bits_equal(
            _shaped(lane[1], form, state),
            _plain(schedule, lane[0], anchors[i], bits[i]))
    # ONE trip count a level for all lanes: the loop's predicate is not
    # batched, so no lane's carry is selected a step beside the one select
    # a leaf that hands the branches their classes' results
    jaxpr = jax.make_jaxpr(served)(states, anchors, bits).jaxpr
    loops = _eqns(jaxpr, "while")
    assert len(loops) == 1
    body = loops[0].params["body_jaxpr"].jaxpr
    carried = [v.aval.shape for v in body.outvars if v.aval.ndim > 2]
    assert (len(names), SB, 512, 3) in carried
    assert len([e for e in _eqns(body, "select_n")
                if e.outvars[0].aval.shape in carried]) == len(carried)
    assert not _eqns(loops[0].params["cond_jaxpr"].jaxpr, "reduce_or")


def _eqns(jaxpr, name):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


def test_the_predicate_keeps_small_and_very_large_rows_on_the_scan():
    P2 = 2
    box = box_game.make_world(P2).commit()
    assert rollout_form(box_game.make_schedule(), box, S_INPUTS) is None
    assert share_width(None, box, 8) is None
    assert share_width(None, box, 1024) is None
    # the churning title at its real width: rows of a quarter megabyte
    churn = jax.eval_shape(lambda: particles.make_world(P2, 8192, 7).commit())
    kinds = jax.tree_util.tree_map(lambda x: STEPS, churn)
    assert share_width(kinds, churn, 8) is None
    # the flock at its: 8 KB leaves, 1 a step at 8 branches, 16 at 128
    flock = jax.eval_shape(lambda: boids.make_world(1024, P2).commit())
    kinds = jax.tree_util.tree_map(lambda x: STEPS, flock)
    assert share_width(kinds, flock, 8) == 1
    assert share_width(kinds, flock, 128) == 16
    assert share_width(kinds, flock, 12) == 1
    assert share_width(kinds, flock, 20) == 2
    # and the programs say so: a level's loop where it engages, none else
    schedule, state, form = herd(False)
    bits = jnp.zeros((SB, SF, SP), jnp.uint8)
    roll = lambda sched, form: jax.make_jaxpr(    # noqa: E731
        lambda st, bb: rollout_branches(
            sched, st, jnp.int32(0), bb, S_STATUS, form))
    assert len(_eqns(roll(schedule, form)(state, bits).jaxpr, "while")) == 1
    assert not _eqns(roll(schedule, None)(state, bits).jaxpr, "while")
    assert not _eqns(
        roll(box_game.make_schedule(), None)(box, bits).jaxpr, "while")
    small = particles.make_world(P2, 4096, 7).commit()    # 32 KiB rows
    small_form = rollout_form(particles.make_schedule(7), small, S_INPUTS)
    assert small_form is not None
    assert not _eqns(
        roll(particles.make_schedule(7), small_form)(small, bits).jaxpr,
        "while")


def test_the_mesh_path_keeps_the_plain_vmap():
    """``parallel/speculate.py`` hands no form: a branch axis laid over
    devices cannot loop over its classes."""
    schedule, state, _ = herd(False)
    bits = jnp.zeros((SB, SF, SP), jnp.uint8)
    jaxpr = jax.make_jaxpr(functools.partial(
        SpeculativeExecutor._run_impl, schedule))(
        state, jnp.int32(0), bits, S_STATUS).jaxpr
    assert not _eqns(jaxpr, "while") and len(_eqns(jaxpr, "scan")) == 1
