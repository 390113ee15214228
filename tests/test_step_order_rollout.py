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
"""

import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.fused import (
    LANE_AXIS,
    FusedTickExecutor,
    absorb_branch_frames,
)
from bevy_ggrs_tpu.models import box_game, particles
from bevy_ggrs_tpu.rollout import deepest_lane, rollout_steps
from bevy_ggrs_tpu.schedule import PREDICTED, PlayerInputs
from bevy_ggrs_tpu.serve.batch import BatchedTickExecutor
from bevy_ggrs_tpu.state import (
    FLAT_ROW_BYTES,
    ring_init,
    ring_put,
    ring_save,
    ring_step_load,
)
from tests.test_lane_uniform_ring import (
    BRANCHES,
    BURST,
    DEPTH,
    LANES,
    P,
    SPEC,
    SPECIAL,
    assert_bits_equal,
    i32,
    lane,
    plain_schedule,
    plain_world,
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
