"""The absorb phase runs as many copy steps as the deepest lane commits, not
its padded length, and reads the matched branch only where somebody commits
(``fused.py`` ``absorb_branch_frames(n_run=...)`` / ``_absorb_impl``,
``rollout.py`` ``deepest_lane``).

- The trimmed loop equals the full-length scan (kept here as the reference)
  bit for bit (ring, state, ``checksums[max_steps]``) for every ``absorb_n``
  in ``0..burst_frames``, for box_game and a title whose rows ride flat, the
  replay that consumes the whole rollout included.
- Under ``jax.vmap`` with lanes asking different counts the group runs the
  deepest lane's and every lane's outputs are its own; an idle lane beside
  absorbing ones; the whole batched tick, the split tick's front and the
  absorb-only program against the parent's phase 1 (the ``[B]``-wide read,
  the full scan, the ring's ``keep`` select).
- Structure: the batched tick's absorb phase stands inside one conditional
  on one scalar; the side that commits holds the ``[B]``-wide read and a
  ``while`` that compares two scalars, the other side computes nothing;
  one executable serves every ``absorb_n``.
- Counters: ``absorb_steps_total`` / ``absorb_step_slots_total`` stay 0 in
  a SyncTest group and add ``num_slots x`` the deepest hit elsewhere, alike
  on both host paths.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.fused import (
    LANE_AXIS,
    FusedTickExecutor,
    TickInts,
    absorb_branch_frames,
)
from bevy_ggrs_tpu.models import box_game, particles
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.rollout import deepest_lane
from bevy_ggrs_tpu.serve import MatchServer
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore, BatchedTickExecutor
from bevy_ggrs_tpu.session import SessionBuilder
from bevy_ggrs_tpu.utils.metrics import Metrics
from bevy_ggrs_tpu.state import (
    FLAT_ROW_BYTES,
    ring_put,
    ring_row_read,
    ring_step_load,
)
from tests import test_packed_tick as packed
from tests.test_batched_sessions import rollback_requests, step_requests
from tests.test_burst_trim import (
    PLANES,
    _python_plane,
    assert_scalar_trip_count,
    equations,
    whiles,
)
from tests.test_lane_uniform_ring import (
    assert_bits_equal,
    i32,
    lane,
    random_like,
    random_ring,
    stack,
)

P = 2
MF = 10          # max_prediction 8 + 2, as the served tick pads
DEPTH = 12
TITLES = ["box_game", "particles"]


@functools.lru_cache(maxsize=None)
def world(name):
    if name == "box_game":
        return box_game.make_world(P).commit()
    state = particles.make_world(P, 1152, 5).commit()    # rows ride flat
    assert state.components["position"].nbytes >= FLAT_ROW_BYTES
    return state


# ---------------------------------------------------------------------------
# The parent's phase 1, as it stood: the reference
# ---------------------------------------------------------------------------


def full_absorb(main_ring, spec_ring, spec_states, first_frame, n_frames,
                anchor, total_spec, max_steps, n_run=None):
    """``absorb_branch_frames`` as a scan over all ``max_steps`` padded
    steps, ``valid`` deciding (``n_run`` is not read)."""

    def body(ring, t):
        f = first_frame + t
        valid = t < n_frames
        saved, cs = ring_step_load(spec_ring, f, anchor)
        ring = ring_put(ring, saved, f, cs, valid)
        return ring, jnp.where(valid, cs, jnp.uint32(0))

    main_ring, checksums = jax.lax.scan(
        body, main_ring, jnp.arange(max_steps, dtype=jnp.int32))
    end = first_frame + n_frames
    from_ring, _ = ring_step_load(spec_ring, end, anchor)
    state = jax.tree_util.tree_map(
        lambda a, b: jnp.where(end < anchor + total_spec, a, b),
        from_ring, spec_states)
    return main_ring, state, checksums


def full_absorb_impl(burst_frames, ring, prev_rings, prev_states, branch,
                     absorb_first, absorb_n, prev_anchor, prev_total,
                     lane_axis=None, form=None):
    """``_absorb_impl`` with the matched branch read whoever commits, the
    full scan, and the ring selected back where nobody did (over
    ``[B, F, *row]`` trees: the parent knew no other form)."""
    assert form is None
    sel = lambda x: ring_row_read(x, branch)       # noqa: E731
    ring_a, state, cs = full_absorb(
        ring, jax.tree_util.tree_map(sel, prev_rings),
        jax.tree_util.tree_map(sel, prev_states), absorb_first, absorb_n,
        prev_anchor, prev_total, max_steps=burst_frames)
    ring = jax.tree_util.tree_map(
        lambda a, b: jnp.where(absorb_n > 0, a, b), ring_a, ring)
    return ring, state, cs


def parents_program(monkeypatch):
    """From here on ``_tick_impl`` / ``_front_impl`` / ``PackedTick.absorb``
    trace the parent's phase 1."""
    monkeypatch.setattr(
        FusedTickExecutor, "_absorb_impl", staticmethod(full_absorb_impl))


# ---------------------------------------------------------------------------
# Values: one lane
# ---------------------------------------------------------------------------

trimmed = jax.jit(lambda *a: absorb_branch_frames(
    *a, max_steps=MF, n_run=a[4]))
full = jax.jit(lambda *a: full_absorb(*a, max_steps=MF))


def absorb_args(name, seed, n, d=0, total=MF):
    """A random main ring, a random branch ring of ``total`` rows from an
    anchor the seed draws, and a replay of ``n`` frames from ``d`` frames
    into the rollout."""
    rng = np.random.default_rng(seed)
    state = world(name)
    anchor = int(rng.integers(3, 2000))
    return (random_ring(rng, state, DEPTH), random_ring(rng, state, total),
            random_like(rng, state), i32(anchor + d), i32(n), i32(anchor),
            i32(total))


@pytest.mark.parametrize("n", range(MF + 1))
@pytest.mark.parametrize("name", TITLES)
def test_trimmed_absorb_is_the_full_scan(name, n):
    """``n == MF`` consumes the whole rollout: the state is its final one."""
    args = absorb_args(name, 100 + n, n)
    want = full(*args)
    assert_bits_equal(trimmed(*args), want)
    ring, state, cs = want
    assert not np.asarray(cs)[n:].any()         # the steps past n did nothing
    if n == 0:
        assert_bits_equal(ring, args[0])
    if n == MF:
        assert_bits_equal(state, args[2])


# (frames into the rollout, frames replayed, the rollout's length)
OFFSETS = [(1, 2, 4), (3, 1, 4), (2, 2, 4), (0, 4, 4), (4, 4, 8), (7, 1, 8),
           (5, 0, 8)]


@pytest.mark.parametrize("d,n,total", OFFSETS,
                         ids=lambda v: str(v))
@pytest.mark.parametrize("name", TITLES)
def test_trimmed_absorb_from_inside_a_shorter_rollout(name, d, n, total):
    """A rollout shorter than the burst, a replay that starts inside it;
    ``d + n == total`` ends on the rollout's final state."""
    args = absorb_args(name, 10 * d + n, n, d, total)
    want = full(*args)
    assert_bits_equal(trimmed(*args), want)
    if d + n == total:
        assert_bits_equal(want[1], args[2])


# ---------------------------------------------------------------------------
# Under the lane vmap
# ---------------------------------------------------------------------------

LANE_COUNTS = [
    (3, 0, MF, 1),      # an idle lane, a full one
    (0, 0, 0, 0),       # nobody commits: the loop runs no step
    (2, 2, 2, 2),
    (0, 0, 7, 0),       # one deep hit among lanes that have none
    (1, 4, 0, 2),
    (MF, MF, MF, MF),
]


lanes = jax.jit(jax.vmap(
    lambda *a: absorb_branch_frames(
        *a, max_steps=MF, n_run=deepest_lane(a[4], LANE_AXIS)),
    axis_name=LANE_AXIS))


@pytest.mark.parametrize("counts", LANE_COUNTS,
                         ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("name", TITLES)
def test_lanes_run_the_deepest_and_keep_their_own(name, counts):
    per_lane = [absorb_args(name, 10 * i + n, n) for i, n in enumerate(counts)]
    got = lanes(*stack(per_lane))
    assert_bits_equal(got, stack([full(*a) for a in per_lane]))
    # one scalar for the dispatch: the deepest lane's count
    deepest = jax.vmap(
        lambda n: deepest_lane(n, LANE_AXIS), axis_name=LANE_AXIS,
        out_axes=None)(i32(counts))
    assert deepest.shape == () and int(deepest) == max(counts)


#            miss        partial hit  hit         no-op
LANE_PLANS = {
    "mixed": ([0, 2, 3, 0], [4, 2, 0, 0], [True, False, False, False]),
    "nobody_commits": ([0, 0, 0, 0], [4, 1, 1, 0], [True, False, False, False]),
    "all_full_hits": ([3, 1, 2, 3], [0, 0, 0, 0], [False] * 4),
    "one_deep_hit": ([0, 0, 3, 0], [1, 1, 0, 0], [False] * 4),
}


def lane_arguments(rng, plan):
    """``test_packed_tick.lane_arguments`` with the lanes' commits, bursts
    and loads of ``LANE_PLANS[plan]``."""
    absorb_n, n_burst, do_load = LANE_PLANS[plan]
    trees, ints, bits, bb = packed.lane_arguments(rng)
    T = TickInts
    ints[:, T.ABSORB_N] = absorb_n
    ints[:, T.N_BURST] = n_burst
    ints[:, T.DO_LOAD] = do_load
    ints[:, T.START_FRAME] = ints[:, T.ABSORB_FIRST] + np.array(absorb_n)
    return trees, ints, bits, bb


@pytest.mark.parametrize("plan", sorted(LANE_PLANS))
def test_batched_tick_is_the_parents_program(plan, monkeypatch):
    """The [S]-vmapped packed tick and the split tick's front program
    against ``_tick_impl`` over the parent's phase 1, lane by lane."""
    rng = np.random.default_rng(len(plan))
    sched = box_game.make_schedule()
    trees, ints, bits, bb = lane_arguments(rng, plan)
    batched = BatchedTickExecutor(
        sched, packed.LANES, packed.BURST, packed.BRANCHES, packed.SPEC)
    got = batched.run(batched.pack(*trees), ints, bits, bb)
    single = FusedTickExecutor(
        sched, packed.BURST, packed.BRANCHES, packed.SPEC)
    single.build_front()
    front = []
    for i in range(packed.LANES):
        n = int(ints[i, TickInts.N_BURST])
        front.append(single.run_front(
            single.pack(*lane(trees, i)), ints[i].copy(), bits[i, :n],
            TickInts.status(ints[i], packed.BURST, P)[:n], bb[i]))
    parents_program(monkeypatch)
    want = [packed.direct_tick(sched, lane(trees, i), ints[i], bits[i], bb[i])
            for i in range(packed.LANES)]
    packed.assert_tick_equal(batched, got, stack(want))
    for (carry, state, (absorb_cs, burst_cs)), w in zip(front, want):
        assert_bits_equal(single.unpack(carry)[:2], w[:2])
        assert_bits_equal((state, absorb_cs, burst_cs), (w[1], w[2], w[3]))


def program_trees(name, seed, branches, spec):
    rng = np.random.default_rng(seed)
    state = world(name)
    return (
        random_ring(rng, state, DEPTH), random_like(rng, state),
        stack([random_ring(rng, state, spec) for _ in range(branches)]),
        stack([random_like(rng, state) for _ in range(branches)]),
    )


@pytest.mark.parametrize("session_axis", [0, 3], ids=["single", "axis3"])
@pytest.mark.parametrize("name", TITLES)
def test_absorb_only_program_is_the_parents(name, session_axis, monkeypatch):
    """``commit_absorb`` (a full hit's program) for every depth, alone and
    through the ``GGRS_SESSION_AXIS`` wrap, on one executable."""
    B, F = 3, 4
    sched = (box_game.make_schedule() if name == "box_game"
             else particles.make_schedule(12))
    trees = program_trees(name, 5, B, F)
    ex = FusedTickExecutor(sched, MF, B, F, session_axis=session_axis)
    carry = ex.pack(*trees)
    cases = [(b, d, n) for b in (0, B - 1) for d, n in
             ((0, 0), (0, 1), (1, 2), (0, F), (2, 2))]
    got = [ex.commit_absorb(carry, b, 40 + d, n, 40, F) for b, d, n in cases]
    assert ex._absorb._cache_size() == 1
    parent = jax.jit(functools.partial(full_absorb_impl, MF))
    ring, _, prev_rings, prev_states = trees
    for (b, d, n), (carry_out, state, cs) in zip(cases, got):
        want = parent(ring, prev_rings, prev_states, i32(b), i32(40 + d),
                      i32(n), i32(40), i32(F))
        out = ex.unpack(carry_out)
        assert_bits_equal((out[0], cs), (want[0], want[2]))
        if n:       # an empty absorb's state is meaningless on both sides
            assert_bits_equal(state, want[1])
        assert_bits_equal(out[1], state)
        assert_bits_equal(out[2:], trees[2:])   # the rollout stays carried


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def conds(jaxpr):
    return equations(jaxpr, "cond")


def test_batched_absorb_stands_inside_one_scalar_conditional():
    """``_absorb_impl`` under the slot ``vmap``: the previous rollout's
    ``[S, B, ...]`` leaves are operands of ONE equation, a conditional on an
    unbatched predicate out of which nothing with a branch axis comes. Its
    one side holds the ``[B]``-wide read and the copy loop, whose trip count
    is one scalar; its other side computes nothing: the ring goes through
    and zeros are made."""
    S, B, F = 4, 7, 5       # no two of S, B, F, DEPTH alike, none a row's axis
    trees = stack([program_trees("box_game", i, B, F) for i in range(S)])
    ring, _, prev_rings, prev_states = trees
    fn = jax.vmap(
        functools.partial(
            FusedTickExecutor._absorb_impl, MF, lane_axis=LANE_AXIS),
        axis_name=LANE_AXIS)
    scalars = [i32([0, 6, 1, 0]), i32([6, 7, 8, 9]), i32([0, 3, 1, 0]),
               i32([6, 7, 8, 9]), i32([F] * S)]
    jaxpr = jax.make_jaxpr(fn)(ring, prev_rings, prev_states, *scalars).jaxpr
    n_ring = len(jax.tree_util.tree_leaves(ring))
    n_prev = len(jax.tree_util.tree_leaves((prev_rings, prev_states)))
    branch_leaves = jaxpr.invars[n_ring:n_ring + n_prev]
    assert all(v.aval.shape[:2] == (S, B) for v in branch_leaves)
    ids = set(map(id, branch_leaves))
    readers = [e for e in jaxpr.eqns if ids & set(map(id, e.invars))]
    (cond,) = readers
    assert cond.primitive.name == "cond"
    assert cond.invars[0].aval.shape == ()          # one predicate a dispatch
    out_shapes = [v.aval.shape for v in cond.outvars]
    assert out_shapes and all(
        s[:1] == (S,) and B not in s[1:] for s in out_shapes)
    # the count came from a reduction over the lanes, in the program, and
    # nothing else of the phase runs outside the conditional
    assert "pmax[axes=(0,)" in str(jaxpr)
    assert not [e for e in jaxpr.eqns if e.primitive.name in (
        "while", "scan", "select_n", "dynamic_slice", "dynamic_update_slice")]
    skip, commit = (b.jaxpr for b in cond.params["branches"])
    assert {e.primitive.name for e in skip.eqns} <= {"broadcast_in_dim"}
    (loop,) = whiles(commit)
    assert_scalar_trip_count(loop, S)
    assert not [v for v in loop.invars if v.aval.shape[:2] == (S, B)]
    # the same absorb with a count a lane: the form this must not take
    bad = jax.make_jaxpr(jax.vmap(
        functools.partial(FusedTickExecutor._absorb_impl, MF)
    ))(ring, prev_rings, prev_states, *scalars).jaxpr
    assert not conds(bad)       # a batched predicate runs both sides
    (bad_loop,) = whiles(bad)
    assert [v.aval.shape for v in
            bad_loop.params["cond_jaxpr"].jaxpr.outvars] == [(S,)]


def test_batched_tick_has_one_conditional_and_it_is_the_absorbs():
    sched = box_game.make_schedule()
    trees, ints, bits, bb = packed.lane_arguments(np.random.default_rng(0))
    batched = BatchedTickExecutor(
        sched, packed.LANES, packed.BURST, packed.BRANCHES, packed.SPEC)
    jaxpr = jax.make_jaxpr(batched._fn)(
        batched.pack(*trees), ints, bits, bb).jaxpr
    (cond,) = conds(jaxpr)
    assert cond.invars[0].aval.shape == ()
    S, B = packed.LANES, packed.BRANCHES
    assert any(v.aval.shape[:2] == (S, B) for v in cond.invars[1:])
    assert not any(v.aval.shape[:2] == (S, B) for v in cond.outvars)
    absorb_loop, burst_loop = whiles(jaxpr)
    for loop in (absorb_loop, burst_loop):
        assert_scalar_trip_count(loop, S)


def test_one_executable_for_every_absorb_depth():
    sched = box_game.make_schedule()
    rng = np.random.default_rng(3)
    B, F = packed.BRANCHES, packed.SPEC
    single = FusedTickExecutor(sched, packed.BURST, B, F)
    trees, ints, bits, bb = packed.lane_arguments(rng)
    carry = single.pack(*lane(trees, 0))
    for n in (0, 2, F, 1, 0):
        row = ints[0].copy()
        row[TickInts.ABSORB_N] = n
        carry, _, _ = single.run(
            carry, row, bits[0, :4], TickInts.status(row, packed.BURST, P)[:4],
            bb[0])
    assert single.cache_size() == 1
    batched = BatchedTickExecutor(sched, packed.LANES, packed.BURST, B, F)
    carry = batched.pack(*trees)
    for counts in ((0, 0, 0, 0), (F, 0, 1, 0), (2, 2, 2, 2), (0, 0, 0, 0)):
        rows = ints.copy()
        rows[:, TickInts.ABSORB_N] = counts
        carry, _, _ = batched.run(carry, rows, bits, bb)
    assert batched.cache_size() == 1


# ---------------------------------------------------------------------------
# The counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plane", PLANES)
def test_synctest_group_never_absorbs(plane):
    matches, groups, frames = 8, 2, 12
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(P).commit(), 8, P,
        box_game.INPUT_SPEC, capacity=matches, stagger_groups=groups,
        num_branches=4, spec_frames=8, metrics=Metrics())
    _python_plane(server.groups, plane)
    server.warmup()
    for k in range(matches):
        session = (SessionBuilder(box_game.INPUT_SPEC).with_num_players(P)
                   .with_max_prediction_window(8).with_check_distance(2)
                   .start_synctest_session())
        server.add_match(session, lambda frame, h, k=k: (frame + h + k) % 16)
    for _ in range(frames):
        server.run_frame()
    assert server.faults_total == 0
    for core in server.groups:
        assert [s.frame for s in core.slots] == [frames] * core.num_slots
        assert core.rollbacks_total > 0         # it rolls back every frame
        assert core.absorb_steps_total == 0
        assert core.absorb_step_slots_total == 0
        assert "absorb_steps_total" not in core.metrics.counters
        assert "absorb_step_slots_total" not in core.metrics.counters


def hit_run(plane):
    """A group of four lanes, one empty: three tick alike until one of
    them corrects two predicted frames to inputs a branch of its rollout
    holds (``tests/test_batched_sessions.py``'s script for the structured
    tree) while its neighbours take the frame's own step. Returns the core
    and the counters' samples around that dispatch."""
    S = 4
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(), 4, P,
        box_game.INPUT_SPEC, num_slots=S, num_branches=8, spec_frames=3,
        predictor=False, metrics=Metrics())
    _python_plane([core], plane)
    core.warmup()
    live = [core.admit() for _ in range(S - 1)]
    script = [(step_requests(f, [f % 4, (f + 1) % 4]), f) for f in range(3)]
    script += [(step_requests(f, [2, 3]), 2) for f in (3, 4)]
    for reqs, confirmed in script:
        core.tick({i: (reqs, confirmed, None) for i in live})
    read = lambda: (core.absorb_steps_total,                    # noqa: E731
                    core.absorb_step_slots_total,
                    core.rollback_frames_recovered_total)
    before = read()
    hit = rollback_requests(3, [[1, 3], [1, 3]]) + step_requests(5, [1, 3])
    work = {i: (step_requests(5, [2, 3]), 2, None) for i in live[1:]}
    work[live[0]] = (hit, 5, None)
    core.tick(work)
    return core, before, read()


@pytest.mark.parametrize("plane", PLANES)
def test_group_pays_its_deepest_hit(plane):
    core, before, after = hit_run(plane)
    assert before == (0, 0, 0)          # nobody had hit: both counts at 0
    steps, slots, recovered = after
    assert core.spec_hits + core.spec_partial_hits == 1
    assert steps == recovered >= 2      # the one lane's commit
    # every lane of that dispatch ran the deepest lane's copy steps
    assert slots == core.num_slots * steps
    assert core.metrics.counters["absorb_steps_total"] == steps
    assert core.metrics.counters["absorb_step_slots_total"] == slots
    assert core._exec.cache_size() == 1
    assert [core.slots[i].frame for i in range(3)] == [6, 6, 6]


@pytest.mark.skipif(not ncore.available(),
                    reason="native session core did not build")
def test_the_two_planes_count_alike():
    (a, _, after_a), (b, _, after_b) = hit_run("native"), hit_run("python")
    assert after_a == after_b and after_a[0] > 0
    assert_bits_equal(a.states, b.states)
