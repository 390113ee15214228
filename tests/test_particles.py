"""The particle stress test (``models/particles.py``): a world whose entities
are born and die every frame, and the free-row claim it shares with
``projectiles`` (``ops/lifecycle.py``).

- the title against the plain NumPy reference at a small size: who lives,
  each one's ``ttl`` and the allocator exactly, by rollback id; positions
  and velocities within a float32 step's rounding;
- a world too small for its births counts the fizzled ones, as the
  reference does;
- a rollback across births and deaths re-mints the same ids;
- the serial runner, the speculative runner and ``MatchServer``'s
  ``[S]``-vmapped batched tick agree bit for bit, each match from a seed of
  its own; the speculation attests;
- the claim against a plain NumPy statement of its rule in every leaf and
  every way a title gives a birth's values, at 1 to 40 births, under
  ``vmap`` and not; a spawning step traced under ``[S] x [B]`` holds no
  ``scatter`` (``ops/lifecycle.py`` says what one costs there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.common import tree_equal
from benchmark.reference import particles_np as ref
from bevy_ggrs_tpu.models import particles as pt
from bevy_ggrs_tpu.ops import lifecycle
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import make_inputs
from bevy_ggrs_tpu.serve.server import MatchServer
from bevy_ggrs_tpu.session import SessionBuilder
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    SaveGameState,
)
from bevy_ggrs_tpu.spec_runner import (
    SpeculativeRollbackRunner,
    attest_speculation_safety,
)
from bevy_ggrs_tpu.state import DEVICE_ID_BASE, checksum, combine64

P = 2
WINDOW = 8
MASKS = np.asarray([0, 1, 2, 4, 5, 6, 8, 9, 10], np.uint8)


def _table(seed, matches, frames):
    return np.random.RandomState(seed).choice(MASKS, size=(matches, P, frames))


def _run(rate, capacity, seed, bits):
    """The program's state after ``bits[P, F]``, a jitted step a frame."""
    step = jax.jit(pt.make_schedule(rate))
    state = pt.make_world(P, capacity, match_seed=seed).commit()
    for f in range(bits.shape[1]):
        state = step(state, make_inputs(bits[:, f]))
    return state


def _by_id(state):
    """(ids, ttl, position, velocity) of the live rows, sorted by id."""
    rows = np.flatnonzero(np.asarray(state.alive))
    ids = np.asarray(state.rollback_id)[rows]
    order = np.argsort(ids, kind="stable")
    comp = {k: np.asarray(v)[rows][order] for k, v in state.components.items()}
    return ids[order], comp["ttl"], comp["position"], comp["velocity"]


# ---------------------------------------------------------------------------
# The title against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rate,capacity,fizzles", [
    (6, 640, False),        # 6 x 89 = 534 rows at most: nothing fizzles
    (12, 1152, False),      # 12 x 89 = 1,068 rows at most
    (12, 256, True),        # a world too small: births fizzle, and count
])
def test_title_against_the_plain_reference(rate, capacity, fizzles):
    frames, seed = 200, 0x9E3779B9
    bits = _table(3, 1, frames)
    state = _run(rate, capacity, seed, bits[0])
    world = ref.replay_worlds(bits, [frames], [seed], rate=rate,
                              capacity=capacity)
    ids, ttl, pos, vel = _by_id(state)
    want_ids, want_ttl, want_p, want_v = ref.by_id(world, 0)
    # Integers decide who lives: exact, by id and not by row.
    assert np.array_equal(ids, want_ids) and np.array_equal(ttl, want_ttl)
    assert int(state.resources["next_rollback_id"]) == int(world["next_id"][0])
    assert int(state.resources["frame_count"]) == frames
    fizzled = int(state.resources["spawn_fizzled"])
    assert fizzled == int(world["fizzled"][0]) and (fizzled > 0) == fizzles
    assert ids.size == np.unique(ids).size and ids.min() >= DEVICE_ID_BASE
    if not fizzles:     # rate x (mean ttl - 1) live at a frame's end
        assert abs(ids.size - rate * 73.5) < rate * 6
        assert int(world["next_id"][0]) == DEVICE_ID_BASE + rate * frames
    else:
        assert ids.size <= capacity
    # Floats: a particle lives 89 frames at most; XLA:CPU may contract the
    # multiply-add of the position update (15 ulp of a position near 10).
    assert np.abs(pos.astype(np.float64) - want_p).max() <= 1e-4
    assert np.abs(vel.astype(np.float64) - want_v).max() <= 1e-5
    assert np.array_equal(np.asarray(state.resources["emitter_position"]),
                          world["emitter"][0])
    # Free rows: id -1, nothing present; live rows: all three present.
    alive = np.asarray(state.alive)
    assert (np.asarray(state.rollback_id)[~alive] == -1).all()
    for flags in state.present.values():
        assert np.array_equal(np.asarray(flags), alive)


# ---------------------------------------------------------------------------
# Rollback across births and deaths
# ---------------------------------------------------------------------------


def _burst(load, frames_bits):
    reqs = [] if load is None else [LoadGameState(frame=load)]
    for f, bits in frames_bits:
        reqs.append(SaveGameState(frame=f))
        reqs.append(AdvanceFrame(bits=np.asarray(bits, np.uint8),
                                 status=np.zeros(P, np.int32)))
    return reqs


def test_rollback_across_births_and_deaths_remints_the_same_ids():
    rate, capacity = 5, 512
    runner = RollbackRunner(
        pt.make_schedule(rate), pt.make_world(P, capacity, 77).commit(),
        WINDOW, P, pt.INPUT_SPEC)
    bits = _table(5, 1, 80)[0]
    frames = [(f, bits[:, f]) for f in range(72)]
    for i in range(0, len(frames), 8):      # past the first deaths (60)
        runner.handle_requests(_burst(None, frames[i:i + 8]))
    first = runner.state
    cs_first = combine64(checksum(first))
    ids, ttl, pos, _ = _by_id(first)
    assert ids.min() > DEVICE_ID_BASE      # the oldest have died

    # Roll back six frames and resimulate with OTHER inputs: the same
    # particles are born and die (ids, ttl), somewhere else.
    other = [(f, (bits[:, f] ^ 0x5) & 0xA) for f in range(66, 72)]
    runner.handle_requests(_burst(66, other))
    ids2, ttl2, pos2, _ = _by_id(runner.state)
    assert np.array_equal(ids2, ids) and np.array_equal(ttl2, ttl)
    assert not np.array_equal(pos2, pos)
    assert (int(runner.state.resources["next_rollback_id"])
            == DEVICE_ID_BASE + rate * 72)

    # And again with the first inputs: the first trajectory, bit for bit.
    runner.handle_requests(_burst(66, frames[66:72]))
    assert combine64(checksum(runner.state)) == cs_first
    assert tree_equal(runner.state, first)


# ---------------------------------------------------------------------------
# Serial runner, speculative runner, batched tick
# ---------------------------------------------------------------------------


def _session():
    return (SessionBuilder(pt.INPUT_SPEC).with_num_players(P)
            .with_max_prediction_window(WINDOW).with_check_distance(2)
            .start_synctest_session())


def _replayed(runner, feed, frames):
    session = _session()
    for _ in range(frames):
        for p in session.local_player_handles():
            session.add_local_input(p, feed(session.current_frame, p))
        runner.handle_requests(session.advance_frame(), session)
    return runner


def test_serial_speculative_and_batched_tick_agree_bit_for_bit():
    rate, capacity, matches, frames = 9, 896, 4, 100
    schedule = pt.make_schedule(rate)
    world = pt.make_world(P, capacity).commit()
    server = MatchServer(
        schedule, world, WINDOW, P, pt.INPUT_SPEC, capacity=matches,
        stagger_groups=2, num_branches=4, spec_frames=WINDOW)
    server.warmup()
    table = _table(7, matches, frames)
    feed = lambda k: lambda frame, handle: table[k, handle, frame]  # noqa
    seeds = [11, 22, 33, 44]
    handles = [
        server.add_match(_session(), feed(k),
                         initial_state=pt.with_match_seed(world, seeds[k]))
        for k in range(matches)]
    for _ in range(frames):
        server.run_frame()
    assert server.faults_total == 0 and server.evictions_total == 0

    populations = set()
    for k, h in enumerate(handles):
        core = server.groups[h.group]
        assert core.slots[h.slot].frame == frames
        mine = pt.with_match_seed(world, seeds[k])
        serial = _replayed(
            RollbackRunner(schedule, mine, WINDOW, P, pt.INPUT_SPEC),
            feed(k), frames)
        spec = SpeculativeRollbackRunner(
            schedule, mine, max_prediction=WINDOW, num_players=P,
            input_spec=pt.INPUT_SPEC, num_branches=4, spec_frames=WINDOW)
        spec.warmup()
        assert spec.speculation_enabled and spec.attestation.ok
        _replayed(spec, feed(k), frames)
        for other in (core.slot_state(h.slot), spec.state):
            assert tree_equal(other, serial.state)
        ring = core.slot_ring(h.slot)
        assert np.array_equal(np.asarray(ring.frames),
                              np.asarray(serial.ring.frames))
        assert np.array_equal(np.asarray(ring.checksums),
                              np.asarray(serial.ring.checksums))
        populations.add(tuple(_by_id(serial.state)[1]))
    # A seed a match: the four worlds turn over differently.
    assert len(populations) == matches


def test_speculation_attests():
    runner = SpeculativeRollbackRunner(
        pt.make_schedule(12), pt.make_world(P, 1152, 5).commit(),
        max_prediction=WINDOW, num_players=P, input_spec=pt.INPUT_SPEC,
        num_branches=8, spec_frames=4)
    report = attest_speculation_safety(runner)
    assert report.ok and report.branches_checked >= 1


# ---------------------------------------------------------------------------
# The claim
# ---------------------------------------------------------------------------


def _written(claim, leaves, table, next_id):
    """Every way a title writes a birth: one value, a table by candidate
    birth, a function of the ordinal."""
    flags, ids, vectors = leaves
    return (
        claim.put(flags, True),
        claim.put(ids, lambda o: next_id + o),
        claim.put(ids, next_id + claim.rank),
        claim.put(vectors, table),
        claim.put(vectors, lambda o: jnp.stack(
            [o.astype(jnp.float32), -o.astype(jnp.float32)], axis=-1)),
        claim.placed,
    )


def _written_np(alive, wants, leaves, table, next_id):
    """The rule of ``ops/lifecycle.py``'s docstring, a birth at a time."""
    flags, ids, vectors = (np.array(x) for x in leaves)
    by_rank, by_table, by_fn = ids.copy(), vectors.copy(), vectors.copy()
    free = np.flatnonzero(~alive)
    placed = 0
    for k in np.flatnonzero(wants):     # birth order
        if placed == free.size:
            break                       # this birth and the rest fizzle
        row = free[placed]
        flags[row] = True
        by_rank[row] = next_id + placed
        by_table[row] = table[k]
        by_fn[row] = (placed, -placed)
        placed += 1
    return flags, by_rank, by_rank, by_table, by_fn, np.int32(placed)


@pytest.mark.parametrize("births", [1, 8, 9, 40])
@pytest.mark.parametrize("free", [0, 3, 200])
def test_the_claim_against_its_rule_in_every_leaf(births, free):
    cap, lanes = 256, 3
    rng = np.random.RandomState(births * 1000 + free)
    alive = np.ones((lanes, cap), bool)
    for lane in range(lanes):
        alive[lane, rng.choice(cap, size=free, replace=False)] = False
    wants = rng.uniform(size=(lanes, births)) < 0.7
    wants[0] = True
    leaves = (jnp.zeros((lanes, cap), bool),
              jnp.asarray(rng.randint(0, 99, (lanes, cap)), jnp.int32),
              jnp.asarray(rng.uniform(size=(lanes, cap, 2)), jnp.float32))
    table = jnp.asarray(rng.uniform(size=(lanes, births, 2)), jnp.float32)
    next_id = jnp.asarray([100, 200, 300], jnp.int32)

    def claimed(alive, wants, leaves, table, next_id):
        return _written(lifecycle.claim_rows(alive, wants), leaves, table,
                        next_id)

    args = (jnp.asarray(alive), jnp.asarray(wants), leaves, table, next_id)
    batched = jax.jit(jax.vmap(claimed))(*args)
    assert np.array_equal(np.asarray(batched[-1]),
                          np.minimum(wants.sum(axis=1), free))
    for lane in range(lanes):
        one = jax.tree_util.tree_map(lambda x: x[lane], args)
        want = _written_np(*jax.tree_util.tree_map(np.asarray, one))
        # Without the batch axis and with it: the rule, bit for bit.
        for got in (claimed(*one),
                    jax.tree_util.tree_map(lambda x: x[lane], batched)):
            for g, w in zip(got, want):
                assert np.asarray(g).dtype == w.dtype
                assert np.array_equal(np.asarray(g), w)


def _primitives(jaxpr):
    """The primitive names of a jaxpr and of every jaxpr inside it."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub)
    return names


@pytest.mark.parametrize("values", ["by_ordinal", "by_table"])
def test_births_hold_no_scatter_under_the_served_axes(values):
    if values == "by_ordinal":      # particles' whole spawn system
        spawn = lambda s, i: pt.spawn_system(s, i, rate=12)  # noqa: E731
        args = (pt.make_world(P, 1152, 5).commit(),
                make_inputs(np.zeros(P, np.uint8)))
    else:       # what projectiles.fire_system gives: a table by player
        def spawn(alive, wants, leaf, table):
            claim = lifecycle.claim_rows(alive, wants)
            return claim.put(leaf, table), claim.put(alive, True)
        args = (jnp.zeros(64, bool), jnp.ones(8, bool),
                jnp.zeros((64, 2), jnp.float32),
                jnp.ones((8, 2), jnp.float32))
    lead = (3, 4)       # [S] x [B]
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, lead + x.shape), args)
    names = _primitives(
        jax.make_jaxpr(jax.vmap(jax.vmap(spawn)))(*stacked).jaxpr)
    assert "select_n" in names
    assert not [n for n in names if n.startswith("scatter")]
    assert "sort" not in names and "while" not in names     # no searchsorted
    if values == "by_ordinal":      # nothing indexed at all
        assert "gather" not in names
