"""The entity-coupled title under ``MatchServer`` (PR 37).

- ``MatchServer`` hosting SyncTest matches of boids-64 in 2 stagger groups:
  every slot bitwise the serial ``RollbackRunner`` (state, ring frames, ring
  checksums), no fault, and every step the rings still hold within the plain
  NumPy reference's step of the program's own state before it; over the XLA
  force and one Pallas force (the MXU kernel, interpreted here).
- The force under the server's two batch axes, ``[S]`` outside the
  rollout's ``[B]``, equal to per-world calls.
- The third ring lowering (``state.py`` ``FLAT_ROW_BYTES``: a burst carries a
  large row flat, largest axis last): equal bit for bit to the shaped form
  and to a plain scatter, on both sides of the threshold, under ``vmap`` and
  not; and counted where it is traced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.common import tree_equal
from benchmark.reference import boids_np as ref
from bevy_ggrs_tpu import state as state_mod
from bevy_ggrs_tpu.models import boids
from bevy_ggrs_tpu.rollout import rollout_burst
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import Schedule
from bevy_ggrs_tpu.serve.server import MatchServer
from bevy_ggrs_tpu.session import SessionBuilder
from bevy_ggrs_tpu.state import (
    FLAT_ROW_BYTES,
    HostWorld,
    TypeRegistry,
    ring_init,
    ring_put,
    ring_rows_flat,
    ring_rows_shaped,
)
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_lane_uniform_ring import assert_bits_equal, random_like

P = 2
WINDOW = 8
MASKS = np.asarray([0, 1, 2, 4, 5, 6, 8, 9, 10], np.uint8)
# boids_1k_client's limits, which boids_1k_server256 takes as they stand.
LIMIT_T, LIMIT_V, MARGIN = 4e-4, 8e-5, 1e-5


def _session():
    return (SessionBuilder(boids.INPUT_SPEC).with_num_players(P)
            .with_max_prediction_window(WINDOW).with_check_distance(2)
            .start_synctest_session())


# ---------------------------------------------------------------------------
# MatchServer hosts the title
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel,frames,branches", [
    ("xla", 40, 8),
    ("mxu", 12, 2),     # the Pallas kernel runs interpreted: one small case
])
def test_match_server_hosts_boids(kernel, frames, branches):
    n, matches = 64, 8
    schedule = boids.make_schedule(kernel=kernel)
    world = boids.make_world(n, P).commit()
    metrics = Metrics()
    server = MatchServer(
        schedule, world, WINDOW, P, boids.INPUT_SPEC, capacity=matches,
        stagger_groups=2, num_branches=branches, spec_frames=WINDOW,
        metrics=metrics)
    server.warmup()
    table = np.random.RandomState(7).choice(MASKS, size=(matches, P, frames))
    feed = lambda k: lambda frame, handle: table[k, handle, frame]  # noqa
    handles = [server.add_match(_session(), feed(k)) for k in range(matches)]
    for _ in range(frames):
        server.run_frame()
    assert server.faults_total == 0 and server.evictions_total == 0
    assert server.slots_quarantined + server.slots_recovering == 0
    # boids-64's rows are under the threshold: its bursts stay shaped.
    assert metrics.counters['ring_row_lowering{kind="shaped"}'] > 0
    assert 'ring_row_lowering{kind="flat"}' not in metrics.counters
    # ... and its rollouts write every leaf of the branch ring in step order.
    assert metrics.counters['ring_row_lowering{kind="step"}'] == len(
        jax.tree_util.tree_leaves(world))
    assert metrics.series["serve_carry_bytes"] and all(
        v > 0 for v in metrics.series["serve_carry_bytes"])
    assert set(metrics.series["tick_stage_bytes"]) == {
        float(sum(a.nbytes for a in server.groups[0]._host_args()))}
    # ... each branch every frame (rows under 4 KiB: ``rollout.py``
    # ``share_width``), and the sink hears that constant a dispatch.
    assert set(metrics.series["serve_rollout_steps"]) == {branches * WINDOW}
    assert len(metrics.series["serve_rollout_steps"]) == len(
        metrics.series["tick_stage_bytes"])
    assert "serve_rollout_fill_share" not in metrics.series

    steps = 0
    for k, h in enumerate(handles):
        core = server.groups[h.group]
        assert core.slots[h.slot].frame == frames
        # Bitwise the serial singleton fed the same inputs.
        session, oracle = _session(), RollbackRunner(
            schedule, world, WINDOW, P, boids.INPUT_SPEC)
        for _ in range(frames):
            for p in session.local_player_handles():
                session.add_local_input(p, feed(k)(session.current_frame, p))
            oracle.handle_requests(session.advance_frame(), session)
        assert tree_equal(core.slot_state(h.slot), oracle.state)
        ring = core.slot_ring(h.slot)
        assert np.array_equal(np.asarray(ring.frames),
                              np.asarray(oracle.ring.frames))
        assert np.array_equal(np.asarray(ring.checksums),
                              np.asarray(oracle.ring.checksums))
        # Every held step against the plain reference, from the program's
        # own state before it.
        held = {int(f): row for row, f in enumerate(np.asarray(ring.frames))
                if f >= 0}
        pos = np.asarray(ring.states.components["position"])
        vel = np.asarray(ring.states.components["velocity"])
        for f in sorted(f for f in held if f + 1 in held):
            a, b = held[f], held[f + 1]
            want_p, want_v = ref.step(pos[a][None], vel[a][None],
                                      table[k][None, :, f])
            decided = ~ref.undecided(pos[a], MARGIN)
            gap_t = ref.torus_gap(pos[b], want_p[0]).max(axis=-1)
            gap_v = np.abs(vel[b].astype(np.float64) - want_v[0]).max(axis=-1)
            assert gap_t[decided].max() <= LIMIT_T
            assert gap_v[decided].max() <= LIMIT_V
            assert decided.mean() > 0.9
            steps += 1
    assert steps >= matches * (WINDOW - 1)


def test_match_server_hosts_a_flock_whose_rollout_shares_its_steps():
    """512 boids make rows of 4 KiB: the served rollout steps each distinct
    input prefix of a lane's tree once (``rollout.py`` ``share_width``), the
    deepest lane's count a level for every lane. The matches stay bitwise
    the serial singleton, and a listening sink is told the world-steps a
    dispatch ran and the share of them that were a lane's own."""
    n, matches, frames, branches = 512, 4, 14, 8
    schedule = boids.make_schedule()
    world = boids.make_world(n, P).commit()
    metrics = Metrics()
    server = MatchServer(
        schedule, world, WINDOW, P, boids.INPUT_SPEC, capacity=matches,
        stagger_groups=1, num_branches=branches, spec_frames=WINDOW,
        metrics=metrics)
    server.warmup()
    core = server.groups[0]
    assert core._exec.packed.share_width == 1
    table = np.random.RandomState(11).choice(MASKS, size=(matches, P, frames))
    feed = lambda k: lambda frame, handle: table[k, handle, frame]  # noqa
    handles = [server.add_match(_session(), feed(k)) for k in range(matches)]
    for _ in range(frames):
        server.run_frame()
    assert server.faults_total == 0 and server.evictions_total == 0
    for k, h in enumerate(handles):
        assert core.slots[h.slot].frame == frames
        session, oracle = _session(), RollbackRunner(
            schedule, world, WINDOW, P, boids.INPUT_SPEC)
        for _ in range(frames):
            for p in session.local_player_handles():
                session.add_local_input(p, feed(k)(session.current_frame, p))
            oracle.handle_requests(session.advance_frame(), session)
        assert tree_equal(core.slot_state(h.slot), oracle.state)
        ring = core.slot_ring(h.slot)
        assert np.array_equal(np.asarray(ring.checksums),
                              np.asarray(oracle.ring.checksums))
    steps = metrics.series["serve_rollout_steps"]
    fill = metrics.series["serve_rollout_fill_share"]
    assert len(steps) == len(fill) == len(metrics.series["tick_stage_bytes"])
    # both players free from the anchor: 3 + 5 + 7 + 8 x 5 of 8 x 8
    assert WINDOW <= min(steps) and max(steps) <= branches * WINDOW
    assert sorted(steps)[len(steps) // 2] == 55
    assert all(0 < v <= 100 for v in fill)


# ---------------------------------------------------------------------------
# The force under two batch axes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["xla", "mxu"])
def test_force_under_two_batch_axes_equals_per_world_calls(kernel):
    n, slots, branches = 64, 3, 2
    rng = np.random.RandomState(11)
    pos = rng.uniform(-2, 2, size=(slots, branches, n, 2)).astype(np.float32)
    vel = rng.uniform(-0.08, 0.08, size=pos.shape).astype(np.float32)
    active = (rng.uniform(size=(slots, branches, n)) > 0.1).astype(np.float32)
    if kernel == "xla":
        force = boids._pairwise_forces
    else:
        from bevy_ggrs_tpu.ops.pairwise import pairwise_force_rows_mxu2

        def force(p, v, a):
            return pairwise_force_rows_mxu2(
                p, v, p, v, a, a, **boids._kernel_params())

    both = jax.jit(jax.vmap(jax.vmap(force)))(pos, vel, active)
    one = jax.jit(force)
    for s in range(slots):
        for b in range(branches):
            np.testing.assert_array_equal(
                np.asarray(both[s, b]),
                np.asarray(one(pos[s, b], vel[s, b], active[s, b])))


# ---------------------------------------------------------------------------
# The third ring lowering: large rows ride a burst flat
# ---------------------------------------------------------------------------

DEPTH = 5


def _world(rows: int):
    reg = TypeRegistry()
    reg.register_component("position", shape=(2,), dtype=jnp.float32)
    reg.register_component("tag", shape=(), dtype=jnp.int32)
    reg.register_resource("tick", jnp.int32(0))
    world = HostWorld(reg, rows)
    for i in range(rows):
        world.spawn({"position": [i, -i], "tag": i}, rollback_id=i)
    return world.commit()


# position is [rows, 2] float32: 8 bytes a row of the world.
@pytest.mark.parametrize("rows,flat", [
    (FLAT_ROW_BYTES // 8 - 1, False),
    (FLAT_ROW_BYTES // 8, True),
    (FLAT_ROW_BYTES // 4, True),
])
@pytest.mark.parametrize("lanes", [0, 3])
def test_flat_rows_equal_shaped_rows_and_a_plain_scatter(rows, flat, lanes):
    rng = np.random.default_rng(rows + lanes)
    state = _world(rows)
    lead = (lanes,) if lanes else ()
    stacked = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.broadcast_to(x, lead + x.shape), tree)
    ring = random_like(rng, stacked(ring_init(state, DEPTH)))
    new = random_like(rng, stacked(state))
    frame = jnp.asarray(rng.integers(0, 4 * DEPTH, size=lead), jnp.int32)
    cs = jnp.asarray(rng.integers(0, 2**32, size=lead + (2,)), jnp.uint32)
    valid = jnp.asarray(rng.integers(0, 2, size=lead).astype(bool))

    def shaped(ring, new, frame, cs, valid):
        return ring_put(ring, new, frame, cs, valid)

    def through_flat(ring, new, frame, cs, valid):
        carried = ring_rows_flat(ring)
        kept = carried.states.components["position"].ndim == 2
        assert kept == flat         # [depth, rows * 2] against [depth, rows, 2]
        assert carried.states.components["tag"].ndim == 2   # 1-D rows: as is
        return ring_rows_shaped(
            ring_put(carried, new, frame, cs, valid), ring)

    wrap = (lambda f: jax.jit(jax.vmap(f))) if lanes else jax.jit
    args = (ring, new, frame, cs, valid)
    got, want = wrap(through_flat)(*args), wrap(shaped)(*args)
    assert_bits_equal(got, want)

    # A plain scatter, lane by lane, on the host.
    def scatter(x, row):
        x, row = np.array(x), np.asarray(row)
        x, row = (x, row) if lanes else (x[None], row[None])
        for i in range(x.shape[0]):
            if np.asarray(valid).reshape(-1)[i]:
                x[i, int(np.asarray(frame).reshape(-1)[i]) % DEPTH] = row[i]
        return x if lanes else x[0]

    assert_bits_equal(
        got.states, jax.tree_util.tree_map(scatter, ring.states, new))


def _drift(state, inputs):
    push = jnp.sum(inputs.bits.astype(jnp.float32))
    return state.replace(
        components={**state.components,
                    "position": state.components["position"] * 0.5 + push},
        resources={"tick": state.resources["tick"] + 1})


@pytest.mark.parametrize("lanes", [0, 3])
def test_a_burst_with_flat_rows_is_bitwise_the_shaped_burst(lanes, monkeypatch):
    rows, frames = FLAT_ROW_BYTES // 8, DEPTH + 2
    rng = np.random.default_rng(5)
    state = _world(rows)
    lead = (lanes,) if lanes else ()
    stacked = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.broadcast_to(x, lead + x.shape), tree)
    ring, state = stacked(ring_init(state, DEPTH)), stacked(state)
    start = jnp.asarray(rng.integers(0, 50, size=lead), jnp.int32)
    bits = jnp.asarray(rng.integers(0, 16, size=lead + (frames, P)), jnp.uint8)
    status = jnp.zeros(lead + (frames, P), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=lead + (frames,)).astype(bool))

    def burst(*args):
        return rollout_burst(Schedule([_drift]), *args, n_run=frames)

    wrap = (lambda f: jax.jit(jax.vmap(f))) if lanes else jax.jit
    args = (ring, state, start, bits, status, mask, mask)
    before = dict(state_mod.ring_row_lowerings)
    flat = wrap(burst)(*args)
    traced = {k: v - before[k]
              for k, v in state_mod.ring_row_lowerings.items()}
    # (a burst hands no rollout on: none of the rollout's kinds)
    assert {k: n for k, n in traced.items() if n} == {"flat": 1, "shaped": len(
        jax.tree_util.tree_leaves(ring.states)) - 1}
    monkeypatch.setattr(state_mod, "FLAT_ROW_BYTES", 1 << 40)
    shaped = wrap(burst)(*args)
    assert_bits_equal(flat, shaped)
