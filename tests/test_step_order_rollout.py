"""A speculative rollout's branch ring stands in STEP order (``rollout.py``
``rollout_steps``, ``state.py`` ``ring_of_steps`` / ``ring_step_load``):
row ``t`` is the state that entered frame ``anchor + t``, written at the
loop's own counter, and the absorb finds a frame at ``frame - anchor``.

Values: the rollout against ``F`` serial ``ring_save`` + schedule steps, row
by row and bit for bit (NaN payloads, ``-0.0``, ``inf``), alone and under
``vmap(S)`` x ``vmap(B)`` with another anchor in every lane;
``absorb_branch_frames`` over every ``(anchor % F, first_frame - anchor,
n_frames)`` of a small ``F`` against the serial replay, per lane and
vmapped; the whole tick absorbing a prefix of the previous rollout (all of
it in one lane: the state then comes from the rollout's end, not from a
row) and stepping on in its burst.

Structure: in the lowered ``[S]``-vmapped tick of box_game and of
``particles`` at a small size every branch-ring leaf leaves the rollout's
loop through a ``dynamic_update_slice`` and no loop of the program selects
over a leaf of that size; the count ``ring_row_lowerings["step"]`` says so.

The CARRIED form (PR 51; ``state.py``, "A ROLLOUT's branch ring is not a
ring"): between dispatches a large row stays what the rollout's scan wrote,
step-major and flat, and has no branch axis where jax's trace of the
rollout found none (``rollout.py`` ``rollout_form``). Values: for
``particles`` at 4,096 rows (seven of its eight large leaves without a
branch axis), a boids-like world (every large leaf with one) and box_game
(no large leaf: the form unchanged), alone and under the slot ``vmap`` with
another anchor a lane, the whole tick, the split tick's front and the
absorb-only program return the parent's bits (the twin whose
``rollout_form`` answers None carries ``[B, F, *row]`` trees, as the
parent did), the carry unpacked included; ``absorb_branch_frames`` over
every ``(anchor % F, first_frame - anchor, n_frames)`` reading the carried
rows against the serial replay. Structure: the rollout loop's buffers ARE
the program's outputs (no operation of the jaxpr uses them). Counter: what
a warmed core reports.
"""

import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import fused
from bevy_ggrs_tpu.fused import (
    LANE_AXIS,
    FusedTickExecutor,
    TickInts,
    absorb_branch_frames,
)
from bevy_ggrs_tpu.models import box_game, particles
from bevy_ggrs_tpu.rollout import (
    deepest_lane,
    rollout_branches,
    rollout_form,
    rollout_steps,
)
from bevy_ggrs_tpu.schedule import PREDICTED, PlayerInputs, Schedule
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore, BatchedTickExecutor
from bevy_ggrs_tpu.state import (
    FLAT_ROW_BYTES,
    ONCE,
    SHAPED,
    STEPS,
    HostWorld,
    SnapshotRing,
    TypeRegistry,
    branch_rows_gathered,
    branch_rows_of,
    ring_init,
    ring_put,
    ring_save,
    ring_step_load,
)
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_lane_uniform_ring import (
    BRANCHES,
    BURST,
    DEPTH,
    LANES,
    LONG,
    P,
    SPEC,
    SPECIAL,
    assert_bits_equal,
    i32,
    lane,
    plain_schedule,
    plain_world,
    random_like,
    random_ring,
    stack,
    tick_args,
)

F = SPEC
STATUS = jnp.full((F, P), PREDICTED, jnp.int32)


def special_world(roll=0):
    """``plain_world`` with every float a pattern arithmetic would spoil."""
    state = plain_world()
    pos = np.resize(SPECIAL, state.components["pos"].shape)
    return state.replace(components={"pos": jnp.asarray(np.roll(pos, roll))})


@functools.lru_cache(maxsize=None)
def _serial_fns():
    sched = plain_schedule()
    step = jax.jit(lambda st, b, s: sched(st, PlayerInputs(bits=b, status=s)))
    return step, jax.jit(ring_save)


def serial_rollout(state, anchor, bits):
    """``F`` SaveGameState / AdvanceFrame pairs, one request at a time:
    the states that entered ``anchor .. anchor + F`` (the last one is the
    final state), the checksums of the first ``F``, and the ring a serial
    runner would hold (``frame % F``)."""
    step, save = _serial_fns()
    ring = ring_init(state, F)
    entered, cs = [state], []
    for t in range(F):
        ring, c = save(ring, entered[-1], jnp.int32(anchor + t))
        cs.append(c)
        entered.append(step(entered[-1], bits[t], STATUS[t]))
    return entered, jnp.stack(cs), ring


def _rollout(state, anchor, bits):
    return rollout_steps(plain_schedule(), state, anchor, bits, STATUS)


def random_bits(rng, *lead):
    return jnp.asarray(rng.integers(0, 16, size=lead + (F, P)), jnp.uint8)


# ---------------------------------------------------------------------------
# Values: the rollout
# ---------------------------------------------------------------------------


def assert_rollout_is_serial(got, state, anchor, bits):
    ring, final, cs = got
    entered, want_cs, serial_ring = serial_rollout(state, anchor, bits)
    np.testing.assert_array_equal(
        np.asarray(ring.frames), anchor + np.arange(F))
    for t in range(F):
        assert_bits_equal(lane(ring.states, t), entered[t])
        # the same rows a serial runner's ring holds, permuted
        assert_bits_equal(lane(ring.states, t),
                          lane(serial_ring.states, (anchor + t) % F))
    assert_bits_equal(ring.checksums, want_cs)
    assert_bits_equal(cs, want_cs)
    assert_bits_equal(final, entered[F])


@pytest.mark.parametrize("anchor", [0, 1, 2, 7, 3 * 10 ** 6 + 1, 2 ** 30 + 5])
def test_rollout_rows_are_the_serial_saves_in_step_order(anchor):
    rng = np.random.default_rng(anchor % 1000)
    state, bits = special_world(), random_bits(rng)
    got = jax.jit(_rollout)(state, jnp.int32(anchor), bits)
    assert_rollout_is_serial(got, state, anchor, bits)


@pytest.mark.parametrize("anchors", [
    [0, 3, 6, 9],           # every lane a multiple of F
    [4, 5, 9, 2 ** 30 + 3],     # rotations 1, 2, 0, 1
    [7, 7, 8, 8],
], ids=lambda a: "-".join(map(str, a)))
def test_rollout_under_slot_and_branch_vmaps_matches_serial(anchors):
    rng = np.random.default_rng(sum(anchors) % 1000)
    states = stack([special_world(i) for i in range(LANES)])
    bits = random_bits(rng, LANES, BRANCHES)
    served = jax.jit(jax.vmap(
        lambda st, a, bb: jax.vmap(lambda b: _rollout(st, a, b))(bb)))
    got = served(states, i32(anchors), bits)
    for s, b in itertools.product(range(LANES), range(BRANCHES)):
        assert_rollout_is_serial(
            lane(lane(got, s), b), lane(states, s), anchors[s], bits[s, b])


# ---------------------------------------------------------------------------
# Values: the absorb, every rotation x offset x depth
# ---------------------------------------------------------------------------


def _absorb(ring, spec_ring, spec_state, first, n, anchor, total,
            lane_axis=None):
    return absorb_branch_frames(
        ring, spec_ring, spec_state, first, n, anchor, total, max_steps=BURST,
        n_run=deepest_lane(n, lane_axis))


# (anchor % F, first_frame - anchor, n_frames): every replay that stays
# inside the rollout, the one that consumes it whole included.
ABSORBS = [(r, d, n) for r in range(F) for d in range(F)
           for n in range(F - d + 1)]


@functools.lru_cache(maxsize=None)
def absorb_case(r, d, n):
    """One lane: a real rollout from ``anchor``, a random main ring, and
    what a serial replay of frames ``first .. first + n - 1`` leaves."""
    anchor = 7 * F + r
    rng = np.random.default_rng(100 * r + 10 * d + n)
    state, bits = special_world(r + d), random_bits(rng)
    spec_ring, spec_state, _ = jax.jit(_rollout)(
        state, jnp.int32(anchor), bits)
    entered, cs, _ = serial_rollout(state, anchor, bits)
    main = random_ring(rng, state, DEPTH)
    want_ring, put = main, jax.jit(ring_put)
    for t in range(d, d + n):
        want_ring = put(want_ring, entered[t], jnp.int32(anchor + t), cs[t])
    want_cs = np.zeros((BURST, 2), np.uint32)
    want_cs[:n] = np.asarray(cs)[d:d + n]
    args = (main, spec_ring, spec_state,
            i32(anchor + d), i32(n), i32(anchor), i32(F))
    return args, (want_ring, entered[d + n], jnp.asarray(want_cs))


def assert_absorbed(got, want, n):
    assert_bits_equal(got[0], want[0])
    assert_bits_equal(got[2], want[2])
    if n:       # the state of an empty absorb is selected away by its caller
        assert_bits_equal(got[1], want[1])


@pytest.mark.parametrize("r,d,n", ABSORBS)
def test_absorb_finds_every_frame_of_the_rollout(r, d, n):
    args, want = absorb_case(r, d, n)
    assert_absorbed(jax.jit(_absorb)(*args), want, n)


# The copy loop's trip count under a ``vmap``: a count a lane (the loop's
# predicate batched), or the deepest lane's, one scalar, as the served tick
# asks for it (``rollout.py`` ``deepest_lane``).
TRIP_COUNTS = [None, LANE_AXIS]


def _absorb_lanes(lane_axis):
    return jax.jit(jax.vmap(
        functools.partial(_absorb, lane_axis=lane_axis), axis_name=LANE_AXIS))


@pytest.mark.parametrize("lane_axis", TRIP_COUNTS, ids=["per_lane", "deepest"])
@pytest.mark.parametrize("r", range(F))
def test_absorb_vmapped_over_every_offset_and_depth(r, lane_axis):
    cases = [c for c in ABSORBS if c[0] == r]
    args = stack([absorb_case(*c)[0] for c in cases])
    got = _absorb_lanes(lane_axis)(*args)
    for i, c in enumerate(cases):
        assert_absorbed(lane(got, i), absorb_case(*c)[1], c[2])


@pytest.mark.parametrize("lane_axis", TRIP_COUNTS, ids=["per_lane", "deepest"])
def test_absorb_vmapped_with_another_rotation_in_every_lane(lane_axis):
    cases = [(0, 0, F), (1, 1, 2), (2, 0, 0), (1, 2, 1)]
    args = stack([absorb_case(*c)[0] for c in cases])
    got = _absorb_lanes(lane_axis)(*args)
    for i, c in enumerate(cases):
        assert_absorbed(lane(got, i), absorb_case(*c)[1], c[2])


def test_step_load_reads_the_frame_not_the_row():
    state, bits = special_world(), random_bits(np.random.default_rng(3))
    anchor = 11     # 11 % F == 2: frame % F names another row than the step
    ring, _, cs = jax.jit(_rollout)(state, jnp.int32(anchor), bits)
    entered, _, _ = serial_rollout(state, anchor, bits)
    for t in range(F):
        got, got_cs = jax.jit(ring_step_load)(
            ring, jnp.int32(anchor + t), jnp.int32(anchor))
        assert_bits_equal(got, entered[t])
        assert_bits_equal(got_cs, cs[t])


# ---------------------------------------------------------------------------
# Values: the whole tick, a partial absorb and then a burst
# ---------------------------------------------------------------------------

# (anchor, matched branch, frames absorbed, burst steps behind them); the
# last lane absorbs the whole rollout: its burst starts from the rollout's
# final state.
TICK_LANES = [(21, 0, 1, 2), (22, 3, 2, 1), (23, 1, 1, BURST), (25, 2, F, 2)]


def tick_lane(anchor, branch, n, m, seed):
    """One lane's ``_tick_impl`` arguments and the serial replay's answer:
    the previous rollout has ``BRANCHES`` branches from ``anchor``; the
    confirmed history follows ``branch`` for ``n`` frames and then its own
    inputs for ``m`` more."""
    rng = np.random.default_rng(seed)
    state = special_world(seed)
    branch_bits = random_bits(rng, BRANCHES)
    prev_rings, prev_states, _ = jax.jit(jax.vmap(
        lambda b: _rollout(state, jnp.int32(anchor), b)))(branch_bits)
    burst = np.zeros((BURST, P), np.uint8)
    burst[:m] = rng.integers(0, 16, size=(m, P))
    mask = jnp.asarray(np.arange(BURST) < m)
    next_bits = random_bits(rng, BRANCHES)
    main = random_ring(rng, state, DEPTH)
    args = (
        main, state, prev_rings, prev_states, i32(branch),
        i32(anchor), i32(n), i32(anchor), i32(F),
        jnp.asarray(False), i32(0), i32(anchor + n),
        jnp.asarray(burst), jnp.zeros((BURST, P), jnp.int32), mask, mask,
        jnp.asarray(True), i32(anchor + n + m), next_bits,
    )
    # the serial replay: n + m (save, advance) pairs from the anchor
    step, save = _serial_fns()
    history = list(np.asarray(branch_bits[branch])[:n]) + list(burst[:m])
    ring, live, cs = main, state, []
    for t, b in enumerate(history):
        ring, c = save(ring, live, jnp.int32(anchor + t))
        cs.append(np.asarray(c))
        live = step(live, jnp.asarray(b), jnp.zeros(P, jnp.int32))
    pad = lambda rows: jnp.asarray(np.concatenate(     # noqa: E731
        [np.reshape(rows, (-1, 2)),
         np.zeros((BURST - len(rows), 2))]).astype(np.uint32))
    return args, (ring, live, pad(cs[:n]), pad(cs[n:]), next_bits)


def _tick(*args, lane_axis=None):
    return FusedTickExecutor._tick_impl(
        plain_schedule(), BURST, *args, STATUS, lane_axis=lane_axis)


def assert_tick_is_serial(got, want, anchor, n, m):
    ring, live, absorb_cs, burst_cs, spec_rings, spec_states, spec_cs = got
    want_ring, want_live, want_absorb, want_burst, next_bits = want
    assert_bits_equal((ring, live, absorb_cs, burst_cs),
                      (want_ring, want_live, want_absorb, want_burst))
    for b in range(BRANCHES):       # the next rollout, from the new frontier
        assert_rollout_is_serial(
            lane((spec_rings, spec_states, spec_cs), b), want_live,
            anchor + n + m, next_bits[b])


@pytest.mark.parametrize("case", range(len(TICK_LANES)))
def test_partial_absorb_then_burst_equals_the_serial_replay(case):
    anchor, _, n, m = TICK_LANES[case]
    args, want = tick_lane(*TICK_LANES[case], seed=case)
    assert_tick_is_serial(jax.jit(_tick)(*args), want, anchor, n, m)


@pytest.mark.parametrize("lane_axis", TRIP_COUNTS, ids=["per_lane", "deepest"])
def test_partial_absorb_then_burst_vmapped_over_the_slots(lane_axis):
    lanes = [tick_lane(*c, seed=i) for i, c in enumerate(TICK_LANES)]
    tick = functools.partial(_tick, lane_axis=lane_axis)
    got = jax.jit(jax.vmap(tick, axis_name=LANE_AXIS))(
        *stack([a for a, _ in lanes]))
    for i, (anchor, _, n, m) in enumerate(TICK_LANES):
        assert_tick_is_serial(lane(got, i), lanes[i][1], anchor, n, m)


# ---------------------------------------------------------------------------
# Structure: the lowered [S]-vmapped tick
# ---------------------------------------------------------------------------


def _title(name):
    if name == "box_game":
        return box_game.make_schedule(), box_game.make_world(P).commit()
    world = particles.make_world(P, 1152, 5).commit()    # rows ride flat
    assert world.components["position"].nbytes >= FLAT_ROW_BYTES
    return particles.make_schedule(12), world


def _loop_bodies(jaxpr):
    """The body of every ``scan`` / ``while`` of a jaxpr, nested included."""
    found = []
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name in ("scan", "while"):
            found += subs
        for sub in subs:
            found += _loop_bodies(sub)
    return found


def _selects(jaxpr):
    found = [e for e in jaxpr.eqns if e.primitive.name == "select_n"]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _selects(sub)
    return found


@pytest.mark.parametrize("name", ["box_game", "particles"])
def test_served_rollout_writes_each_branch_ring_row_once(name):
    schedule, state = _title(name)
    ex = BatchedTickExecutor(schedule, LANES, BURST, BRANCHES, SPEC)
    args = tick_args(ex, state, LANES, BURST, BRANCHES, SPEC)
    leaves = jax.tree_util.tree_leaves(state)
    # a branch-ring leaf, [S, B, F, ...], as it is and with its row flat
    lead = (LANES, BRANCHES, SPEC)
    ring_leaves = {lead + x.shape for x in leaves} | {
        lead + (int(x.size),) for x in leaves} | {lead + (2,)}

    # No loop of the program selects over such a leaf: the form the
    # per-lane ``frame % F`` write took (the whole ring a step).
    jaxpr = jax.make_jaxpr(ex._fn)(*args).jaxpr
    assert not [
        e for body in _loop_bodies(jaxpr) for e in _selects(body)
        if e.outvars[0].aval.shape in ring_leaves
    ]
    # Every leaf's rows, the checksums too, leave the rollout's loop through
    # a dynamic_update_slice at the loop's counter: [F, S, B, ...] stacked.
    text = ex._fn.lower(*args).as_text()
    # (a leaf no branch's inputs reach stays one row a slot: [F, S, ...]).
    stacked = re.findall(
        r"stablehlo\.dynamic_update_slice.*-> tensor<%dx%dx" % (SPEC, LANES),
        text)
    assert len(stacked) == len(leaves) + 1
    # ... and the count says which form the executable holds.
    traced = ex.traced_ring_rows()
    assert traced["step"] == len(leaves)
    assert ("flat" in traced) == (name == "particles")     # the burst's ring


# ---------------------------------------------------------------------------
# The carried form (PR 51)
# ---------------------------------------------------------------------------

INPUTS = np.zeros((P,), np.uint8)  # one frame's input rows


def _flock_system(state, inputs):
    """Every large leaf follows the inputs, as a flock follows its leaders."""
    push = jnp.sum(inputs.bits.astype(jnp.float32))
    vel = state.components["vel"] * jnp.float32(0.5) + push
    pos = state.components["pos"]
    return state.replace(components={
        "pos": pos + jnp.pad(vel, ((0, 0), (0, 1))), "vel": vel})


@functools.lru_cache(maxsize=None)
def carried_title(name):
    """``(schedule, state, kinds of its large leaves)``."""
    if name == "box_game":
        return box_game.make_schedule(), box_game.make_world(P).commit(), []
    if name == "particles":  # bool[4096] is a large row: eight large leaves
        state = particles.make_world(P, 4096, 7).commit()
        return particles.make_schedule(7), state, [STEPS] + [ONCE] * 7
    reg = TypeRegistry()
    reg.register_component("pos", shape=(3,))
    reg.register_component("vel", shape=(2,))
    world = HostWorld(reg, 512)
    for i in range(500):
        world.spawn({"pos": [i, -i, 0.5], "vel": [0.25 * i, -1.0]},
                    rollback_id=i)
    state = world.commit()
    special = lambda x: jnp.asarray(    # noqa: E731
        np.resize(SPECIAL, x.shape).view(np.float32))
    return Schedule([_flock_system]), state.replace(components={
        k: special(v) for k, v in state.components.items()}), [STEPS] * 2


TITLES = ["particles", "flock", "box_game"]


def parents_form(monkeypatch):
    """From here on a carry is bound to ``[B, F, *row]`` trees throughout:
    the parent's program (its rows shaped back behind the loop, every leaf
    broadcast over the branches)."""
    monkeypatch.setattr(fused, "rollout_form", lambda *a: None)


@pytest.mark.parametrize("name", TITLES)
def test_the_trace_finds_the_leaves_without_a_branch_axis(name):
    schedule, state, kinds = carried_title(name)
    form = rollout_form(schedule, state, INPUTS)
    if not kinds:
        assert form is None     # no row of 4 KiB: the shaped form throughout
        return
    found = [k for k in jax.tree_util.tree_leaves(form) if k != SHAPED]
    assert sorted(found) == sorted(kinds)
    if name == "particles":     # only the emitter reads an input
        assert form.components["position"] == STEPS
    # without the inputs' shape nothing can be traced: every leaf keeps B
    blind = jax.tree_util.tree_leaves(rollout_form(schedule, state, None))
    assert [k for k in blind if k != SHAPED] == [STEPS] * len(kinds)


def _two_ticks(ex, state, lanes, plans, seed):
    """A rollout from each lane's anchor, then a tick that absorbs
    ``plans[lane] = (branch, d, n, m)``: ``n`` frames of ``branch`` from
    frame ``anchor + d`` and ``m`` burst steps behind them. Returns what
    both dispatches returned, the carries unpacked."""
    rng = np.random.default_rng(seed)
    lead = (lanes,) if lanes else ()
    anchors = 20 + 3 * np.arange(max(lanes, 1))
    carry, ints, bits, bb = tick_args(
        ex, state, lanes, BURST, BRANCHES, SPEC)
    bb = rng.integers(0, 16, size=bb.shape).astype(np.uint8)
    rows = ints.reshape(-1, ints.shape[-1])
    for row, a in zip(rows, anchors):   # nothing but a rollout from ``a``
        row[TickInts.START_FRAME] = row[TickInts.SPEC_ANCHOR] = a
        row[TickInts.SPEC_FROM_LIVE] = 1
    run = (lambda c, i, b, s: ex.run(c, i, b, s)) if lanes else (
        lambda c, i, b, s: ex.run(c, i, b[:0], np.zeros((0, P), np.int32), s))
    first = run(carry, ints, bits, bb)
    ints2 = np.zeros_like(ints)
    bits2 = np.zeros_like(bits)
    rows = ints2.reshape(-1, ints.shape[-1])
    burst = bits2.reshape((-1,) + bits.shape[-2:])
    for i, (row, a, (branch, d, n, m)) in enumerate(zip(rows, anchors, plans)):
        T = TickInts
        row[[T.BRANCH, T.ABSORB_FIRST, T.ABSORB_N, T.PREV_ANCHOR,
             T.PREV_TOTAL]] = branch, a + d, n, a, SPEC
        row[T.START_FRAME], row[T.N_BURST] = a + d + n, m
        row[T.SPEC_FROM_LIVE], row[T.SPEC_ANCHOR] = 1, a + d + n + m
        burst[i, :m] = rng.integers(0, 16, size=(m, P))
    if lanes:
        second = ex.run(first[0], ints2, bits2, bb[..., ::-1, :, :].copy())
    else:
        m = plans[0][3]
        second = ex.run(first[0], ints2, bits2[:m],
                        np.zeros((m, P), np.int32), bb[::-1].copy())
    opened = lambda out: (ex.unpack(out[0]), out[1], ex.cs_host(out[2]))  # noqa
    return opened(first), opened(second), (first[0], ints2, bits2)


# (branch, first_frame - anchor, frames absorbed, burst steps); nobody
# absorbs in the first: the conditional's other side.
PLANS = {
    "nobody": [(0, 0, 0, 1), (0, 0, 0, 2), (0, 0, 0, 0), (0, 0, 0, 1)],
    "some": [(3, 0, 2, 1), (0, 0, 0, 2), (1, 1, 2, 0), (2, 0, SPEC, 2)],
}


@pytest.mark.parametrize("plan", list(PLANS))
@pytest.mark.parametrize("lanes", [0, LANES], ids=["alone", "lanes"])
@pytest.mark.parametrize("name", TITLES)
def test_carried_tick_returns_the_parents_bits(name, lanes, plan, monkeypatch):
    schedule, state, kinds = carried_title(name)
    make = (lambda: BatchedTickExecutor(
        schedule, LANES, BURST, BRANCHES, SPEC, inputs=INPUTS)) if lanes else (
        lambda: FusedTickExecutor(
            schedule, BURST, BRANCHES, SPEC, inputs=INPUTS))
    plans = PLANS[plan] if lanes else PLANS[plan][-1:]
    ex = make()
    got = _two_ticks(ex, state, lanes, plans, seed=len(name))
    form = ex.packed.form
    assert sorted(k for k in jax.tree_util.tree_leaves(form)
                  if k != SHAPED) == sorted(kinds)
    # the carry holds a carried leaf as the scan wrote it: steps first, the
    # slots behind them, the branches where the trace found any
    at = (1,) * len(kinds)
    assert tuple(a for a in ex.packed.carry.axes if a) == at
    steps_first = [x.shape for x, a in zip(got[2][0], ex.packed.carry.axes)
                   if a]
    lead = (SPEC, lanes) if lanes else (SPEC,)
    assert all(s[:len(lead)] == lead for s in steps_first)
    assert sum(len(s) == len(lead) + 1 for s in steps_first) == kinds.count(
        ONCE)
    parents_form(monkeypatch)
    parent = make()
    want = _two_ticks(parent, state, lanes, plans, seed=len(name))
    assert parent.packed.form is None
    assert_bits_equal(got[0], want[0])
    assert_bits_equal(got[1], want[1])


@pytest.mark.parametrize("name", ["particles", "flock"])
def test_session_axis_mode_reads_the_carried_rows(name):
    """``GGRS_SESSION_AXIS``'s copies of one session (every argument
    broadcast in FRONT: the carried rows' lanes are then not where the
    gather wants them, and are moved) against the plain singleton."""
    schedule, state, _ = carried_title(name)
    make = lambda **kw: FusedTickExecutor(    # noqa: E731
        schedule, BURST, BRANCHES, SPEC, inputs=INPUTS, **kw)
    got = _two_ticks(make(session_axis=3), state, 0, [(2, 0, 2, 1)], seed=3)
    want = _two_ticks(make(), state, 0, [(2, 0, 2, 1)], seed=3)
    assert_bits_equal(got[:2], want[:2])


@pytest.mark.parametrize("name", ["particles", "flock"])
def test_front_and_absorb_programs_return_the_parents_bits(name, monkeypatch):
    schedule, state, _ = carried_title(name)

    def programs():
        ex = FusedTickExecutor(schedule, BURST, BRANCHES, SPEC, inputs=INPUTS)
        ex.build_front()
        _, _, (carry, ints, bits) = _two_ticks(
            ex, state, 0, [(2, 1, 2, 1)], seed=5)
        front = ex.run_front(
            carry, ints.copy(), bits[:1], np.zeros((1, P), np.int32),
            np.zeros((BRANCHES, SPEC, P), np.uint8))
        absorb = ex.commit_absorb(carry, 1, 21, 2, 20, SPEC)
        return (
            (ex.unpack(front[0]), front[1], front[2]),
            (ex.unpack(absorb[0]), absorb[1], absorb[2]),
        )

    got = programs()
    parents_form(monkeypatch)
    assert_bits_equal(got, programs())


@functools.lru_cache(maxsize=None)
def _carried_absorb(name):
    schedule, state, _ = carried_title(name)
    form = rollout_form(schedule, state, INPUTS)
    step = jax.jit(lambda st, b, s: schedule(st, PlayerInputs(bits=b, status=s)))
    roll = jax.jit(lambda st, a, bb: rollout_branches(
        schedule, st, a, bb, STATUS, form))
    absorb = jax.jit(lambda ring, rings, states, branch, *a: _absorb(
        ring, *branch_rows_of(rings, states, branch, form), *a))
    return state, form, step, roll, absorb


@pytest.mark.parametrize("r,d,n", ABSORBS)
def test_absorb_reads_every_frame_of_the_carried_rows(r, d, n):
    """The absorb over the rows as the rollout carries them (flat, a leaf
    no branch reaches without its branch axis), against the serial replay
    of the matched branch."""
    state, form, step, roll, absorb = _carried_absorb(
        "particles" if (r + d + n) % 2 else "flock")
    anchor, branch = 7 * F + r, (r + d + n) % BRANCHES
    rng = np.random.default_rng(100 * r + 10 * d + n)
    bits = random_bits(rng, BRANCHES)
    rings, states, cs = roll(state, jnp.int32(anchor), bits)
    entered = [state]
    for t in range(F):
        entered.append(step(entered[-1], bits[branch, t], STATUS[t]))
    main = random_ring(rng, state, DEPTH)
    want_ring, put = main, jax.jit(ring_put)
    for t in range(d, d + n):
        want_ring = put(
            want_ring, entered[t], jnp.int32(anchor + t), cs[branch, t])
    want_cs = np.zeros((BURST, 2), np.uint32)
    want_cs[:n] = np.asarray(cs)[branch, d:d + n]
    got = absorb(main, rings, states, i32(branch), i32(anchor + d), i32(n),
                 i32(anchor), i32(F))
    assert_absorbed(got, (want_ring, entered[d + n], jnp.asarray(want_cs)), n)


@pytest.mark.parametrize("branch,d,n", [(1, 0, F), (2, 1, 2), (3, 0, 1)])
def test_absorb_commits_the_serial_rows_of_a_branch_that_shared_its_steps(
        branch, d, n):
    """A tree as the default builder makes them: every branch the base up
    to the frame of its one change, so the flock's rollout (``rollout.py``
    ``share_width``: rows of 4 KiB and more) stepped ONE world for the
    frames branches share. What the absorb commits of such a branch, across
    the frame where it left the base, is the serial replay of its own
    inputs."""
    state, form, step, roll, absorb = _carried_absorb("flock")
    schedule, _, _ = carried_title("flock")
    from bevy_ggrs_tpu.rollout import prefix_classes, share_width
    assert share_width(form, state, BRANCHES) == 1
    anchor = 7 * F + 1
    base = np.tile(np.array([3, 5], np.uint8), (F, 1))
    bits = np.stack([base] * BRANCHES)
    bits[1, F - 1:, 0] = 9      # leaves the base at the last frame
    bits[2, 1:, 1] = 4          # ... at frame 1
    bits[3, 0:, 0] = 1          # ... at frame 0
    _, _, classes = prefix_classes(jnp.asarray(bits))
    assert np.asarray(classes).tolist() == [2, 3, 4][:F]
    bits = jnp.asarray(bits)
    rings, states, cs = roll(state, jnp.int32(anchor), bits)
    entered = [state]
    for t in range(F):
        entered.append(step(entered[-1], bits[branch, t], STATUS[t]))
    rng = np.random.default_rng(branch)
    main = random_ring(rng, state, DEPTH)
    want_ring, put = main, jax.jit(ring_put)
    for t in range(d, d + n):
        want_ring = put(
            want_ring, entered[t], jnp.int32(anchor + t), cs[branch, t])
    want_cs = np.zeros((BURST, 2), np.uint32)
    want_cs[:n] = np.asarray(cs)[branch, d:d + n]
    got = absorb(main, rings, states, i32(branch), i32(anchor + d), i32(n),
                 i32(anchor), i32(F))
    assert_absorbed(got, (want_ring, entered[d + n], jnp.asarray(want_cs)), n)
    # and its checksums are the serial saves' own
    save = jax.jit(ring_save)
    for t in range(F):
        _, c = save(main, entered[t], jnp.int32(anchor + t))
        assert_bits_equal(cs[branch, t], c)


@pytest.mark.parametrize("branches", [BRANCHES, LONG], ids=["chain", "one_hot"])
def test_a_lane_reads_its_rows_out_of_the_gathered_buffer(branches):
    """``branch_rows_of`` under the slot ``vmap`` (every lane's carried
    rows gathered, the lane's column read where it lies) against the
    dynamic slices one lane alone takes; a wide rollout reads through the
    one-hot pass."""
    rng = np.random.default_rng(branches)
    n = FLAT_ROW_BYTES // 4
    form = plain_world().replace(components={"pos": STEPS}, resources={
        "tick": ONCE}, alive=SHAPED, rollback_id=SHAPED, present={"pos": SHAPED})
    rand = lambda *shape: random_like(     # noqa: E731
        rng, jnp.zeros(shape, jnp.float32))
    small = lambda *tail: rand(LANES, branches, *tail)    # noqa: E731
    rings = SnapshotRing(
        states=form.replace(
            components={"pos": rand(SPEC, LANES, branches, n)},
            resources={"tick": rand(SPEC, LANES, n)},
            alive=small(SPEC, 4), rollback_id=small(SPEC, 4),
            present={"pos": small(SPEC, 4)}),
        frames=small(SPEC), checksums=small(SPEC, 2))
    states = form.replace(
        components={"pos": small(n)}, resources={"tick": rand(LANES, n)},
        alive=small(4), rollback_id=small(4), present={"pos": small(4)})
    picks = i32(rng.integers(0, branches, size=LANES))
    axes = jax.tree_util.tree_map(lambda k: 0 if k == SHAPED else 1, form)
    ring_axes = SnapshotRing(states=axes, frames=0, checksums=0)

    def lanes(rings, states, branch):
        rings = branch_rows_gathered(rings, form, LANE_AXIS)
        return branch_rows_of(rings, states, branch, form, LANE_AXIS)

    got = jax.jit(jax.vmap(
        lanes, in_axes=(ring_axes, 0, 0), axis_name=LANE_AXIS))(
        rings, states, picks)
    alone = jax.jit(lambda r, s, b: branch_rows_of(r, s, b, form))
    for i in range(LANES):
        mine = jax.tree_util.tree_map(
            lambda a, x: x[i] if a == 0 else x[:, i], ring_axes, rings)
        assert_bits_equal(lane(got, i), alone(mine, lane(states, i), picks[i]))


def _scans(jaxpr, length):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append((jaxpr, eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scans(sub, length)
    return found


@pytest.mark.parametrize("name", ["particles", "flock"])
def test_the_rollout_loops_buffers_are_the_programs_outputs(name):
    """No transposition, reshape or broadcast (no operation at all) stands
    between the rollout's loop and the outputs of the lowered
    ``[S]``-vmapped tick for a leaf carried as written; and no operation of
    the program but the absorb's conditional reads the carried leaves it
    is handed."""
    schedule, state, kinds = carried_title(name)
    ex = BatchedTickExecutor(
        schedule, LANES, BURST, BRANCHES, SPEC, inputs=INPUTS)
    args = tick_args(ex, state, LANES, BURST, BRANCHES, SPEC)
    outer = jax.make_jaxpr(ex._fn)(*args).jaxpr
    assert [e.primitive.name for e in outer.eqns] == ["jit"]
    tick = outer.eqns[0].params["jaxpr"].jaxpr
    rollout, = [e for j, e in _scans(tick, SPEC) if j is tick]
    rows = [v for v in rollout.outvars
            if v.aval.shape[:2] == (SPEC, LANES) and v.aval.ndim > 2
            and v.aval.shape[-1] * v.aval.dtype.itemsize >= FLAT_ROW_BYTES]
    assert len(rows) == len(kinds)
    assert sum(v.aval.ndim == 3 for v in rows) == kinds.count(ONCE)
    used = {id(v) for e in tick.eqns for v in e.invars}
    for v in rows:
        assert id(v) not in used and v in tick.outvars
    # ... and the carried leaves it takes in go to the conditional alone.
    carried_in = [v for v, a in zip(tick.invars, ex.packed.carry.axes) if a]
    assert len(carried_in) == len(kinds)
    readers = {e.primitive.name for e in tick.eqns
               for v in e.invars if any(v is c for c in carried_in)}
    assert readers == {"cond"}
    traced = ex.traced_ring_rows()
    assert traced["carried"] == len(kinds)
    assert traced.get("carried_once", 0) == kinds.count(ONCE)


@pytest.mark.parametrize("name", ["particles", "box_game"])
def test_a_warmed_core_reports_what_it_carries_as_written(name):
    schedule, state, kinds = carried_title(name)
    spec = particles.INPUT_SPEC if name == "particles" else box_game.INPUT_SPEC
    metrics = Metrics()
    core = BatchedSessionCore(
        schedule, state, 4, P, spec, num_slots=2, num_branches=BRANCHES,
        spec_frames=SPEC, metrics=metrics)
    core.warmup()
    count = lambda kind: metrics.counters.get(    # noqa: E731
        'ring_row_lowering{kind="%s"}' % kind, 0)
    assert count("step") == len(jax.tree_util.tree_leaves(state))
    assert count("carried") == len(kinds)
    assert count("carried_once") == kinds.count(ONCE)
    # off the serving loop the rollout is [S, B, F, *row] trees all the same
    rows = core.prev_rings.states
    for x, ref in zip(jax.tree_util.tree_leaves(rows),
                      jax.tree_util.tree_leaves(state)):
        assert x.shape == (2, BRANCHES, SPEC) + ref.shape
    assert metrics.series["serve_carry_bytes"][0] == sum(
        x.nbytes for x in core._carry)
