"""The tick's call carries its trees packed (``state.py`` ``PackCodec``,
``fused.py`` ``PackedTick``): a few flat arrays cross the jit boundary
instead of one buffer a leaf.

Codec: ``unpack(pack(t))`` is ``t`` bit for bit on random bit patterns, for
the state, ring and branch-stacked trees of all five models, with and
without a leading ``[S]`` axis. Parity: one packed tick and one packed
absorb equal ``FusedTickExecutor._tick_impl`` / ``_absorb_impl`` called
directly, for the singleton and the ``[S]``-vmapped executor, over miss,
partial-hit, hit and no-op lanes. Readers: after ``warmup()`` a served
frame, ``slot_state``, ``slot_ring``, ``attest`` and an ``extract`` /
``admit`` pair build no executable. Counter: ``tick_io_buffers``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.fused import FusedTickExecutor, TickInts
from bevy_ggrs_tpu.models import boids, box_game, neural_bots, projectiles
from bevy_ggrs_tpu.schedule import PREDICTED
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore, BatchedTickExecutor
from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.state import PackCodec, ring_init
from bevy_ggrs_tpu.utils import xla_cache
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_lane_uniform_ring import (
    assert_bits_equal,
    lane,
    random_like,
    random_ring,
    stack,
)

P = 2
LANES = 4
DEPTH = 5
SPEC = 3
BRANCHES = 4
BURST = 6

MODELS = {
    "box_game": lambda: box_game.make_world(2).commit(),
    "box_game_8p": lambda: box_game.make_world(8).commit(),
    "boids": lambda: boids.make_world(24, 2).commit(),
    "neural_bots": lambda: neural_bots.make_world(8, 2).commit(),
    "projectiles": lambda: projectiles.make_world(2, capacity=16).commit(),
}


def carried_trees(rng, state, lead=()):
    """Random (ring, state, branch rings, branch states), as the carry holds
    them, every leaf with the leading axes ``lead``."""
    def one():
        return (
            random_ring(rng, state, DEPTH),
            random_like(rng, state),
            stack([random_ring(rng, state, SPEC) for _ in range(BRANCHES)]),
            stack([random_like(rng, state) for _ in range(BRANCHES)]),
        )

    if not lead:
        return one()
    return stack([carried_trees(rng, state, lead[1:]) for _ in range(lead[0])])


# ---------------------------------------------------------------------------
# The codec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("lead", [(), (3,)], ids=["single", "stacked"])
def test_pack_unpack_is_bitwise(model, lead):
    rng = np.random.default_rng(7)
    state = MODELS[model]()
    template = carried_trees(rng, state)
    codec = PackCodec(template)
    trees = carried_trees(rng, state, lead)
    packed = jax.jit(codec.pack)(trees)
    dtypes = {jnp.dtype(x.dtype) for x in jax.tree_util.tree_leaves(template)}
    assert jnp.dtype(bool) in dtypes and jnp.dtype(jnp.uint32) in dtypes
    # one flat array a dtype, the leading axes kept
    assert len(packed) == codec.num_buffers == len(dtypes)
    assert all(a.shape[:-1] == lead and a.ndim == len(lead) + 1 for a in packed)
    assert_bits_equal(jax.jit(codec.unpack)(packed), trees)
    # the host-side read of a packed result is the same unpack
    assert_bits_equal(
        codec.unpack(tuple(np.asarray(a) for a in packed)), trees
    )
    # a vmap over the leading axis sees the unstacked form
    if lead:
        again = jax.jit(jax.vmap(lambda c: codec.pack(codec.unpack(c))))(packed)
        assert_bits_equal(again, packed)


def test_large_leaves_and_the_mesh_form_keep_their_buffers():
    state = MODELS["boids"]()
    template = (ring_init(state, DEPTH), state)
    n = len(jax.tree_util.tree_leaves(template))
    per_leaf = PackCodec(template, own_buffer_bytes=0)
    assert per_leaf.num_buffers == n
    packed = per_leaf.pack(template)
    assert all(a is b for a, b in
               zip(packed, jax.tree_util.tree_leaves(template)))
    assert_bits_equal(per_leaf.unpack(packed), template)
    # the size rule counts every copy carried: 24 boids x 2 floats x DEPTH
    # rows is 960 bytes a slot, over the limit from 2 slots on
    mixed = PackCodec(template, copies=2, own_buffer_bytes=1024)
    small = PackCodec(template, copies=1, own_buffer_bytes=1024)
    assert small.num_buffers < mixed.num_buffers < n
    rng = np.random.default_rng(3)
    trees = random_like(rng, template)
    assert_bits_equal(mixed.unpack(jax.jit(mixed.pack)(trees)), trees)


def test_pack_refuses_another_tree():
    state = MODELS["box_game"]()
    codec = PackCodec((state,))
    with pytest.raises(ValueError):
        codec.pack((ring_init(state, DEPTH),))
    with pytest.raises(ValueError):
        codec.pack((jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), state),))
    with pytest.raises(ValueError):
        codec.unpack(codec.pack((state,))[:-1])


# ---------------------------------------------------------------------------
# Parity with _tick_impl / _absorb_impl called directly
# ---------------------------------------------------------------------------

#            miss        partial hit  hit         no-op
ABSORB_N = [0, 2, 3, 0]
N_BURST = [4, 2, 0, 0]
DO_LOAD = [True, False, False, False]


def lane_arguments(rng):
    """Per-lane trees and host arguments: a rollback that misses, one that
    absorbs 2 frames and resimulates 2, a full hit, an idle lane."""
    state = box_game.make_world(P).commit()
    trees = carried_trees(rng, state, (LANES,))
    base = rng.integers(5, 1000, size=LANES)
    ints = np.zeros((LANES, TickInts.STATUS + BURST * P), np.int32)
    T = TickInts
    ints[:, T.BRANCH] = rng.integers(0, BRANCHES, size=LANES)
    ints[:, T.ABSORB_FIRST] = base
    ints[:, T.ABSORB_N] = ABSORB_N
    ints[:, T.PREV_ANCHOR] = base - np.array([0, 1, 0, 0])
    ints[:, T.PREV_TOTAL] = SPEC
    ints[:, T.DO_LOAD] = DO_LOAD
    ints[:, T.LOAD_FRAME] = base - 2
    ints[:, T.START_FRAME] = base + np.array(ABSORB_N)
    ints[:, T.N_BURST] = N_BURST
    ints[:, T.SPEC_FROM_LIVE] = [1, 0, 1, 1]
    ints[:, T.SPEC_ANCHOR] = base - 1
    ints[:, T.STATUS:] = rng.integers(0, 3, size=(LANES, BURST * P))
    bits = rng.integers(0, 16, size=(LANES, BURST, P)).astype(np.uint8)
    bb = rng.integers(0, 16, size=(LANES, BRANCHES, SPEC, P)).astype(np.uint8)
    return trees, ints, bits, bb


def direct_tick(sched, trees, ints, bits, bb):
    """``_tick_impl`` itself on one lane's unpacked arguments."""
    T = TickInts
    mask = jnp.asarray(np.arange(BURST) < ints[T.N_BURST])
    tick = jax.jit(functools.partial(
        FusedTickExecutor._tick_impl, sched, BURST))
    return tick(
        *trees, *(jnp.int32(ints[k]) for k in (
            T.BRANCH, T.ABSORB_FIRST, T.ABSORB_N, T.PREV_ANCHOR, T.PREV_TOTAL)),
        jnp.asarray(bool(ints[T.DO_LOAD])), jnp.int32(ints[T.LOAD_FRAME]),
        jnp.int32(ints[T.START_FRAME]), jnp.asarray(bits),
        jnp.asarray(ints[T.STATUS:].reshape(BURST, P)), mask, mask,
        jnp.asarray(bool(ints[T.SPEC_FROM_LIVE])),
        jnp.int32(ints[T.SPEC_ANCHOR]), jnp.asarray(bb),
        jnp.full((SPEC, P), PREDICTED, jnp.int32),
    )


def assert_tick_equal(ex, got, want):
    carry, state, cs = got
    ring, live, absorb_cs, burst_cs, spec_rings, spec_states, spec_cs = want
    assert_bits_equal(ex.unpack(carry), (ring, live, spec_rings, spec_states))
    assert_bits_equal(state, live)
    assert_bits_equal(ex.cs_host(cs), (absorb_cs, burst_cs, spec_cs))


@pytest.mark.parametrize("seed", [0, 1])
def test_packed_tick_equals_tick_impl(seed):
    rng = np.random.default_rng(seed)
    sched = box_game.make_schedule()
    trees, ints, bits, bb = lane_arguments(rng)
    want = [
        direct_tick(sched, lane(trees, i), ints[i], bits[i], bb[i])
        for i in range(LANES)
    ]
    # the [S]-vmapped executor: all four lanes in one call
    batched = BatchedTickExecutor(sched, LANES, BURST, BRANCHES, SPEC)
    got = batched.run(batched.pack(*trees), ints, bits, bb)
    assert_tick_equal(batched, got, stack(want))
    assert batched.io.last <= 25
    # the singleton executor, a lane a call, through its own staging
    single = FusedTickExecutor(sched, BURST, BRANCHES, SPEC)
    T = TickInts
    for i in range(LANES):
        n = int(ints[i, T.N_BURST])
        got = single.run(
            single.pack(*lane(trees, i)), ints[i].copy(), bits[i, :n],
            TickInts.status(ints[i], BURST, P)[:n], bb[i],
        )
        # beyond n_burst the padded steps are masked: zero them on both sides
        pad = ints[i].copy()
        pad[T.STATUS + n * P:] = 0
        padded_bits = bits[i].copy()
        padded_bits[n:] = 0
        assert_tick_equal(
            single, got,
            direct_tick(sched, lane(trees, i), pad, padded_bits, bb[i]),
        )
        assert single.io.last <= 30
    assert single.cache_size() == 1


@pytest.mark.parametrize("session_axis", [0, LANES])
def test_packed_absorb_equals_absorb_impl(session_axis):
    rng = np.random.default_rng(5)
    sched = box_game.make_schedule()
    trees, ints, _, _ = lane_arguments(rng)
    ex = FusedTickExecutor(
        sched, BURST, BRANCHES, SPEC, session_axis=session_axis
    )
    absorb = jax.jit(functools.partial(FusedTickExecutor._absorb_impl, BURST))
    T = TickInts
    for i in range(LANES):
        ring, state, prev_rings, prev_states = lane(trees, i)
        five = [int(ints[i, k]) for k in (
            T.BRANCH, T.ABSORB_FIRST, T.ABSORB_N, T.PREV_ANCHOR, T.PREV_TOTAL)]
        carry, got_state, got_cs = ex.commit_absorb(
            ex.pack(ring, state, prev_rings, prev_states), *five
        )
        want_ring, want_state, want_cs = absorb(
            ring, prev_rings, prev_states, *(jnp.int32(v) for v in five)
        )
        assert_bits_equal(
            ex.unpack(carry), (want_ring, want_state, prev_rings, prev_states)
        )
        assert_bits_equal(got_state, want_state)
        assert_bits_equal(got_cs, want_cs)


# ---------------------------------------------------------------------------
# Readers off the hot path build nothing after warm-up
# ---------------------------------------------------------------------------


def _requests(frame):
    bits = np.array([frame % 16, (frame * 3) % 16], np.uint8)
    return [SaveGameState(frame),
            AdvanceFrame(bits, np.zeros(P, np.int32))]


def test_warm_core_builds_nothing_for_frames_readers_and_churn():
    metrics = Metrics()
    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=4, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_slots=LANES, num_branches=BRANCHES, metrics=metrics,
    )
    core.warmup()
    for _ in range(3):
        core.admit()
    core.tick({i: (_requests(0), None, None) for i in range(3)})
    jax.block_until_ready(core.states)
    before = xla_cache.compile_counters()["backend_compiles"]
    for frame in range(1, 4):  # served frames
        core.tick({i: (_requests(frame), None, None) for i in range(3)})
    state, ring = core.slot_state(1), core.slot_ring(1)
    assert int(np.asarray(ring.frames).max()) == 3
    assert core.attest() == {}
    ticket = core.extract(2)  # a drain ...
    slot = core.admit(ticket=ticket)  # ... and the readmission
    core.tick({i: (_requests(4), None, None) for i in (0, 1, slot)})
    assert_bits_equal(core.slot_ring(1).states.components,
                      lane(core.rings, 1).states.components)
    jax.block_until_ready((core.states, state))
    assert xla_cache.compile_counters()["backend_compiles"] == before
    assert core._exec.cache_size() == 1
    # the readers' one unpack is kept until the next dispatch replaces it
    assert core.rings is core.rings
    kept = core.prev_rings
    core.tick({0: (_requests(5), None, None)})
    assert core.prev_rings is not kept
    series = metrics.series["tick_io_buffers"]
    assert series and max(series) <= 25


def test_runner_counts_its_buffers_and_the_mesh_keeps_a_buffer_a_leaf():
    metrics = Metrics()
    r = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=4, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=BRANCHES, metrics=metrics,
    )
    r.warmup()
    for frame in range(3):
        r.tick(_requests(frame), confirmed_frame=frame - 1)
    assert r.frame == 3
    series = metrics.series["tick_io_buffers"]
    assert len(series) >= 3 and max(series) <= 30
    assert r._fused.cache_size() == 1
    # reading the ring goes through one unpack, kept while the carry stands
    assert r.ring is r.ring and int(np.asarray(r.ring.frames).max()) == 2
    # a mesh lays every leaf out by itself: nothing is packed
    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh = jax.sharding.Mesh(devices, ("branch", "entity"))
    m = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=4, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=BRANCHES, metrics=Metrics(), mesh=mesh, attest=False,
    )
    m.warmup()
    m.tick(_requests(0), confirmed_frame=-1)
    leaves = len(jax.tree_util.tree_leaves(
        (m.ring, m.state, m._result.rings, m._result.states)))
    assert len(m._packed_carry()) == leaves == 40
    assert m.metrics.series["tick_io_buffers"][-1] == (
        leaves + 3 + leaves + len(jax.tree_util.tree_leaves(m.state)) + 3
    )
    assert_bits_equal(m.state, r_state_after_one_tick())


def r_state_after_one_tick():
    r = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=4, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=BRANCHES, attest=False,
    )
    r.warmup()
    r.tick(_requests(0), confirmed_frame=-1)
    return r.state


def test_the_trace_still_knows_the_programs_by_their_names():
    """``benchmark/layer_metrics/tick_program_ms.*`` find the tick by the
    name jit gives it: the client's two programs are anonymous, the
    server's is ``_tick_impl``."""
    sched = box_game.make_schedule()
    trees, ints, bits, bb = lane_arguments(np.random.default_rng(0))
    name = lambda lowered: lowered.as_text().splitlines()[0].split()[1]
    batched = BatchedTickExecutor(sched, LANES, BURST, BRANCHES, SPEC)
    carry = batched.pack(*trees)
    assert name(batched._fn.lower(carry, ints, bits, bb)) == "@jit__tick_impl"
    single = FusedTickExecutor(sched, BURST, BRANCHES, SPEC)
    carry = single.pack(*lane(trees, 0))
    assert name(single._fn.lower(carry, ints[0], bits[0], bb[0])) == "@jit__unknown"
    assert name(single._absorb.lower(carry, ints[0, :TickInts.ABSORB])) == "@jit__unknown"
