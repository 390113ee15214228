"""The serial burst runs as many steps as its masks ask for, not its padded
length (``rollout.py`` ``live_steps`` / ``rollout_burst(n_run=...)``).

- The trimmed loop equals the full-length masked loop bit for bit (ring,
  state, checksums) for every burst length ``0..max_frames``, for box_game
  and boids (the XLA force), for a spectator's burst (advance, no save) and
  for a caller's masks with holes in them.
- Under ``jax.vmap`` with lanes asking different counts the group runs the
  deepest lane's and every lane's outputs are its own; an idle lane beside
  live ones; the whole batched tick against the full-length program.
- Structure: the batched tick's ``while``s (the absorb's copy loop and the
  burst) have an unbatched predicate (two scalars compared, no reduction
  over lanes, so no per-lane select of the carry), and one executable
  serves every burst length.
- Counters: ``burst_step_slots_total`` adds ``num_slots x`` the deepest
  lane's burst, alike on both host paths.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import fused
from bevy_ggrs_tpu.fused import LANE_AXIS, FusedTickExecutor, TickInts
from bevy_ggrs_tpu.models import boids, box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.rollout import RolloutExecutor, live_steps, rollout_burst
from bevy_ggrs_tpu.serve import MatchServer
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore, BatchedTickExecutor
from bevy_ggrs_tpu.session import SessionBuilder
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    SaveGameState,
)
from bevy_ggrs_tpu.state import ring_init
from tests import test_packed_tick as packed
from tests.test_lane_uniform_ring import assert_bits_equal, lane, stack

P = 2
MF = 10          # max_prediction 8 + 2, as the served tick pads
DEPTH = 12
TITLES = ["box_game", "boids"]
PLANES = [
    pytest.param("native", marks=pytest.mark.skipif(
        not ncore.available(), reason="native session core did not build")),
    "python",
]


@functools.lru_cache(maxsize=None)
def title(name):
    """``(schedule, jitted full-length loop, jitted trimmed loop)`` of a
    title: all ``MF`` padded steps with the masks deciding, and the live
    prefix alone."""
    sched = (box_game.make_schedule() if name == "box_game"
             else boids.make_schedule(kernel="xla"))
    full = jax.jit(lambda *a: rollout_burst(sched, *a, n_run=jnp.int32(MF)))
    trimmed = jax.jit(lambda *a: rollout_burst(
        sched, *a, n_run=live_steps(a[-2], a[-1])))
    return sched, full, trimmed


def burst_args(name, seed, save, adv):
    """A world a few frames in, a ring that already holds them, and a
    padded burst whose inputs past the masks are NOT zero (padding must not
    be read)."""
    sched = title(name)[0]
    rng = np.random.default_rng(seed)
    state = (box_game.make_world(P) if name == "box_game"
             else boids.make_world(24, P)).commit()
    ring = ring_init(state, DEPTH)
    bits = rng.integers(0, 16, size=(MF, P)).astype(np.uint8)
    status = rng.integers(0, 3, size=(MF, P)).astype(np.int32)
    start = int(rng.integers(3, 40))
    for f in range(start - 3, start):
        ring, state, _ = rollout_burst(
            sched, ring, state, f, bits[:1], status[:1],
            jnp.ones(1, bool), jnp.ones(1, bool), n_run=1)
    return (ring, state, jnp.int32(start), jnp.asarray(bits),
            jnp.asarray(status), jnp.asarray(save, bool),
            jnp.asarray(adv, bool))


def prefix(n):
    return np.arange(MF) < n


@pytest.mark.parametrize("n", range(MF + 1))
@pytest.mark.parametrize("name", TITLES)
def test_trimmed_burst_is_the_full_scan(name, n):
    _, full, trimmed = title(name)
    args = burst_args(name, 100 + n, prefix(n), prefix(n))
    assert int(live_steps(args[-2], args[-1])) == n
    want = full(*args)
    assert_bits_equal(trimmed(*args), want)
    # the steps past n did nothing on either side
    ring, state, cs = want
    assert not np.asarray(cs)[n:].any()
    assert int(np.asarray(ring.frames).max()) == int(args[2]) + n - 1


MASKS = {
    # a spectator never saves
    "spectator_3": (prefix(0), prefix(3)),
    "spectator_full": (prefix(0), prefix(MF)),
    # a caller's masks (``RolloutExecutor.run`` takes them) are no prefix
    "holes": (np.arange(MF) % 2 == 0, prefix(7)),
    "late_start": (np.arange(MF) == 4, np.arange(MF) == 6),
    "save_only_last": (np.arange(MF) == MF - 1, prefix(0)),
}


@pytest.mark.parametrize("masks", sorted(MASKS))
@pytest.mark.parametrize("name", TITLES)
def test_trimmed_burst_reads_any_masks(name, masks):
    _, full, trimmed = title(name)
    save, adv = MASKS[masks]
    args = burst_args(name, 7, save, adv)
    last = int(np.flatnonzero(save | adv).max()) + 1
    assert int(live_steps(args[-2], args[-1])) == last
    assert_bits_equal(trimmed(*args), full(*args))


@pytest.mark.parametrize("masks", ["spectator_3", "holes"])
def test_rollout_executor_takes_a_callers_masks(masks):
    """The serial runner's program, through ``run()``'s own padding."""
    sched, full, _ = title("box_game")
    save, adv = MASKS[masks]
    ring, state, start, bits, status, _, _ = burst_args(
        "box_game", 3, save, adv)
    ex = RolloutExecutor(sched, MF)
    n = 7
    got = ex.run(ring, state, int(start), np.asarray(bits)[:n],
                 np.asarray(status)[:n], n, save_mask=save[:n],
                 adv_mask=adv[:n])
    padded = lambda x: jnp.asarray(x).at[n:].set(0)
    want = full(ring, state, start, padded(bits), padded(status),
                jnp.asarray(save & prefix(n)), jnp.asarray(adv & prefix(n)))
    assert_bits_equal(got, want)


# ---------------------------------------------------------------------------
# Under the lane vmap
# ---------------------------------------------------------------------------

LANE_COUNTS = [
    (3, 0, MF, 1),      # an idle lane, a full one
    (0, 0, 0, 0),       # nobody asks: the loop runs no step
    (1, 1, 1, 1),       # the steady tick
    (3, 3, 3, 3),       # SyncTest at check distance 2
    (9, 1, 1, 1),       # one lane behind a bursty link
    (0, 5, 0, 2),
]


@functools.lru_cache(maxsize=None)
def lanes_fn(name):
    sched = title(name)[0]
    return jax.jit(jax.vmap(
        lambda *a: rollout_burst(
            sched, *a, n_run=live_steps(a[-2], a[-1], LANE_AXIS)),
        axis_name=LANE_AXIS))


@pytest.mark.parametrize("counts", LANE_COUNTS,
                         ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("name", TITLES)
def test_lanes_run_the_deepest_and_keep_their_own(name, counts):
    _, full, _ = title(name)
    per_lane = [burst_args(name, 10 * i + n, prefix(n), prefix(n))
                for i, n in enumerate(counts)]
    got = lanes_fn(name)(*stack(per_lane))
    assert_bits_equal(got, stack([full(*a) for a in per_lane]))
    # one scalar for the dispatch: the deepest lane's count
    deepest = jax.vmap(
        lambda s, a: live_steps(s, a, LANE_AXIS), axis_name=LANE_AXIS,
        out_axes=None)(*stack([a[-2:] for a in per_lane]))
    assert deepest.shape == () and int(deepest) == max(counts)


@pytest.mark.parametrize("seed", [0, 1])
def test_batched_tick_is_the_parents_program(seed, monkeypatch):
    """The [S]-vmapped packed tick (a miss, a partial hit, a full hit and an
    idle lane: bursts of 4, 2, 0, 0 steps) against ``_tick_impl`` with the
    burst at its full padded length."""
    rng = np.random.default_rng(seed)
    sched = box_game.make_schedule()
    trees, ints, bits, bb = packed.lane_arguments(rng)
    batched = BatchedTickExecutor(
        sched, packed.LANES, packed.BURST, packed.BRANCHES, packed.SPEC)
    got = batched.run(batched.pack(*trees), ints, bits, bb)
    single = FusedTickExecutor(
        sched, packed.BURST, packed.BRANCHES, packed.SPEC)
    front = []
    single.build_front()
    for i in range(packed.LANES):
        n = int(ints[i, TickInts.N_BURST])
        front.append(single.run_front(
            single.pack(*lane(trees, i)), ints[i].copy(), bits[i, :n],
            TickInts.status(ints[i], packed.BURST, P)[:n], bb[i]))
    monkeypatch.setattr(
        fused, "live_steps", lambda save, *a: jnp.int32(save.shape[0]))
    want = [packed.direct_tick(sched, lane(trees, i), ints[i], bits[i], bb[i])
            for i in range(packed.LANES)]
    packed.assert_tick_equal(batched, got, stack(want))
    # the split tick's front program: the same ring, state and checksums
    for (carry, state, (absorb_cs, burst_cs)), w in zip(front, want):
        assert_bits_equal(single.unpack(carry)[:2], w[:2])
        assert_bits_equal((state, absorb_cs, burst_cs), (w[1], w[2], w[3]))


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def equations(jaxpr, primitive):
    """Every equation of a jaxpr with that primitive, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(equations(sub, primitive))
    return found


def whiles(jaxpr):
    return equations(jaxpr, "while")


def assert_scalar_trip_count(loop, lanes):
    """``i < n_run`` on two scalars. A trip count that differed per lane
    would make the predicate ``[S]``: the loop would then run while any
    lane asks and select its whole carry by lane."""
    cond = loop.params["cond_jaxpr"].jaxpr
    (lt,) = cond.eqns
    assert lt.primitive.name == "lt"
    assert [v.aval.shape for v in lt.invars] == [(), ()]
    assert [v.aval.shape for v in cond.outvars] == [()]
    body = loop.params["body_jaxpr"].jaxpr
    batched_carry = [v for v in body.outvars if v.aval.shape[:1] == (lanes,)]
    assert len(batched_carry) > 10      # rings, (state) and checksums by lane


def test_batched_burst_loop_has_one_scalar_trip_count():
    sched = box_game.make_schedule()
    trees, ints, bits, bb = packed.lane_arguments(np.random.default_rng(0))
    batched = BatchedTickExecutor(
        sched, packed.LANES, packed.BURST, packed.BRANCHES, packed.SPEC)
    carry = batched.pack(*trees)
    jaxpr = jax.make_jaxpr(batched._fn)(carry, ints, bits, bb).jaxpr
    loops = whiles(jaxpr)
    # the rollout is a ``scan``: the two ``while``s are the absorb's copy
    # loop (``tests/test_absorb_trim.py``) and, behind it, the burst
    assert len(loops) == 2
    S = packed.LANES
    for loop in loops:
        assert_scalar_trip_count(loop, S)
    # the count itself came from a reduction over the lanes, in the program
    flat = str(jaxpr)
    assert "reduce_max" in flat
    # the same burst with a count a lane: the form this must not take
    per_lane = jax.make_jaxpr(jax.vmap(
        lambda *a: rollout_burst(sched, *a, n_run=live_steps(a[-2], a[-1]))
    ))(*stack([burst_args("box_game", i, prefix(i), prefix(i))
               for i in range(S)])).jaxpr
    (bad,) = whiles(per_lane)
    assert [v.aval.shape for v in bad.params["cond_jaxpr"].jaxpr.outvars] == [
        (S,)]


@pytest.mark.parametrize("name", TITLES)
def test_one_executable_for_every_burst_length(name):
    sched = title(name)[0]
    state = (box_game.make_world(P) if name == "box_game"
             else boids.make_world(24, P)).commit()
    B, F = 2, 3
    single = FusedTickExecutor(sched, MF, B, F)
    ring = ring_init(state, DEPTH)
    spec_rings = stack([ring_init(state, F)] * B)
    carry = single.pack(ring, state, spec_rings, stack([state] * B))
    bb = np.zeros((B, F, P), np.uint8)
    frame = 0
    for n in (0, 1, 3, MF, 2):
        ints = TickInts.zeros(MF, P)
        fused.plan_tick(ints, frame, None, n, None, 0, None, frame + n, DEPTH)
        carry, _, _ = single.run(
            carry, ints, np.ones((n, P), np.uint8), np.zeros((n, P), np.int32),
            bb)
        frame += n
    assert int(single.unpack(carry)[1].resources["frame_count"]) == frame
    assert single.cache_size() == 1
    S = 3
    batched = BatchedTickExecutor(sched, S, MF, B, F)
    carry = batched.pack(*stack([
        (ring, state, spec_rings, stack([state] * B))] * S))
    frames = [0] * S
    for counts in ((0, 0, 0), (1, 1, 1), (MF, 0, 2), (3, 3, 3)):
        ints = TickInts.zeros(MF, P, (S,))
        for i, n in enumerate(counts):
            fused.plan_tick(
                ints[i], frames[i], None, n, None, 0, None, frames[i] + n,
                DEPTH)
            frames[i] += n
        carry, states, _ = batched.run(
            carry, ints, np.ones((S, MF, P), np.uint8),
            np.zeros((S, B, F, P), np.uint8))
    assert list(np.asarray(states.resources["frame_count"])) == frames
    assert batched.cache_size() == 1


# ---------------------------------------------------------------------------
# The counters
# ---------------------------------------------------------------------------


def _python_plane(cores, plane):
    if plane == "python":
        for core in cores:
            core._plane = None  # the GGRS_NO_NATIVE=1 route of _dispatch


def _steps(frame, first):
    """Request list of a lane at ``frame`` that resimulates from ``first``
    (``first == frame``: the frame's own step alone)."""
    requests = [] if first == frame else [LoadGameState(first)]
    for f in range(first, frame + 1):
        bits = np.array([f % 16, (f * 3) % 16], np.uint8)
        requests += [SaveGameState(f), AdvanceFrame(bits, np.zeros(P, np.int32))]
    return requests


@pytest.mark.parametrize("plane", PLANES)
def test_uniform_synctest_group_fills_its_burst(plane):
    matches, groups, frames = 8, 2, 12
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(P).commit(), 8, P,
        box_game.INPUT_SPEC, capacity=matches, stagger_groups=groups,
        num_branches=4, spec_frames=8)
    _python_plane(server.groups, plane)
    server.warmup()
    # warm-up's dispatch ticked no lane: it ran no burst step
    assert [g.burst_step_slots_total for g in server.groups] == [0] * groups
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
        # every lane of a dispatch asked alike (1, 1, then 3 a frame), and
        # the dispatch ran exactly that
        assert core.burst_steps_total == core.num_slots * (2 + 3 * (frames - 2))
        assert core.burst_step_slots_total == core.burst_steps_total
        # one dispatch a frame (and warm-up's, for the group that made it)
        assert frames <= core.device_dispatches_total <= frames + 1


@pytest.mark.parametrize("deep", [8, 3])
@pytest.mark.parametrize("plane", PLANES)
def test_group_pays_its_deepest_lane(plane, deep):
    S, window = 4, 8
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=window, num_players=P,
        input_spec=box_game.INPUT_SPEC, num_slots=S, num_branches=4)
    _python_plane([core], plane)
    core.warmup()
    live = [core.admit() for _ in range(S - 1)]   # one slot stays empty
    for frame in range(window):
        core.tick({i: (_steps(frame, frame), None, None) for i in live})
    steps0, slots0 = core.burst_steps_total, core.burst_step_slots_total
    assert steps0 == window * len(live)
    assert slots0 == window * S     # a step a dispatch, the empty lane's too
    # one lane rolls back ``deep`` frames' worth of steps, the rest take
    # the frame's own
    work = {i: (_steps(window, window), None, None) for i in live[1:]}
    work[live[0]] = (_steps(window, window - deep + 1), None, None)
    core.tick(work)
    assert core.burst_steps_total - steps0 == deep + len(live) - 1
    assert core.burst_step_slots_total - slots0 == S * deep
    assert core._exec.cache_size() == 1
    assert [core.slots[i].frame for i in live] == [window + 1] * len(live)
