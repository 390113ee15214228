"""The benchmark's plain NumPy flocking reference against the program's
three dense force paths, one step at a time at a small size (CPU), and the
unsharded kernels under the branch ``vmap`` against the serial burst."""

import numpy as np
import pytest

from benchmark.reference import boids_np as ref
from benchmark.titles import boids as title

MASKS = [0, 1, 2, 4, 5, 6, 8, 9, 10]
MARGIN = 1e-5

# One step from identical float32 state. The radius tests see the same
# float32 d2 on every path, so outside the undecided margin the masks agree
# and only the sums' association differs: a few ulp of a position up to 8
# (4.8e-7 each) on the float32 paths. The MXU path multiplies bfloat16
# terms: a weight keeps 16 bits (2**-17 of each unit vector it sums) and the
# matmul form of the separation sum cancels numbers of size |p| * w with w
# capped at CLOSE_W = 200 (2e-8 * 8 * 200 * the weight 0.08 = 2.6e-6): a few
# 1e-6 whatever the flock, so it gets its own tolerance, two decades under
# what a bfloat16 state gives (3.9e-3 from positions in 1..2).
TOLERANCE = {"xla": 2e-6, "mxu": 2e-5}


def _state(n, seed):
    """A flock denser than spawn, random headings: every boid has
    neighbours inside both radii."""
    rng = np.random.RandomState(seed)
    side = 0.18 * np.sqrt(n)
    pos = rng.uniform(-side, side, size=(n, 2)).astype(np.float32)
    ang = rng.uniform(0, 2 * np.pi, size=n)
    speed = rng.uniform(0.02, 0.08, size=n)
    vel = (speed[:, None] * np.stack([np.cos(ang), np.sin(ang)], 1)).astype(
        np.float32)
    bits = rng.choice(np.asarray(MASKS, np.uint8), size=2)
    return pos, vel, bits


def _program_step(kernel, pos, vel, bits, control=None):
    import jax

    from bevy_ggrs_tpu.schedule import make_inputs

    world = title.make_world(2, pos.shape[0])
    world = world.replace(components={
        **world.components, "position": jax.numpy.asarray(pos),
        "velocity": jax.numpy.asarray(vel)})
    out = jax.jit(title.make_schedule(control, kernel))(
        world, make_inputs(bits))
    return title.readback(out, 2)


def test_reference_imports_nothing_of_the_program():
    with open(ref.__file__, encoding="utf-8") as f:
        src = f.read()
    assert "import jax" not in src and "from jax" not in src
    assert "import bevy_ggrs_tpu" not in src and "from bevy" not in src


def test_reference_spawn_is_the_programs_world():
    world = title.make_world(2, 96)
    p, v = ref.spawn(1, 2, 96)
    assert np.array_equal(p[0], np.asarray(world.components["position"]))
    assert np.array_equal(v[0], np.asarray(world.components["velocity"]))
    handles = np.asarray(world.components["leader_handle"])
    assert list(handles[:3]) == [0, 1, -1]


@pytest.mark.parametrize("kernel", ["xla", "mxu"])
@pytest.mark.parametrize("n", [64, 256])
def test_one_step_of_each_dense_path_against_the_reference(kernel, n):
    for seed in (11, 12):
        pos, vel, bits = _state(n, seed)
        got_p, got_v, count = _program_step(kernel, pos, vel, bits)
        want_p, want_v = ref.step(pos[None], vel[None], bits[None])
        decided = ~ref.undecided(pos, MARGIN)
        assert decided.mean() > 0.9 and int(count) == 1
        assert ref.torus_gap(got_p, want_p[0])[decided].max() <= TOLERANCE[
            kernel]
        assert np.abs(got_v - want_v[0])[decided].max() <= TOLERANCE[kernel]
        # The leaders felt their keys: without them the reference differs.
        if bits.any():
            idle_p, _ = ref.step(pos[None], vel[None], np.zeros((1, 2)))
            assert np.abs(idle_p[0, :2] - want_p[0, :2]).max() > 1e-4


# Where the close pairs sit among the MXU kernel's 128-row strips of a
# 256-row block (``ops/pairwise.py`` ``STRIP_ROWS``): in the first strip, in
# the second, one boid in each (each boid's row then lies in another strip
# than its partner's), and a pair in each strip.
PAIR_ROWS = {"first_strip": [(40, 41)], "second_strip": [(200, 201)],
             "straddling": [(100, 230)], "a_pair_a_strip": [(40, 41), (200, 201)]}
# The same four places among 200 boids (padded to 256 rows and columns).
PADDED_PAIR_ROWS = {"first_strip": [(17, 18)], "second_strip": [(150, 151)],
                    "straddling": [(17, 150)],
                    "a_pair_a_strip": [(17, 18), (150, 151)]}


@pytest.mark.parametrize("kernel", ["xla", "mxu"])
@pytest.mark.parametrize("distance", [3e-3, 1e-4])
@pytest.mark.parametrize("where", list(PAIR_ROWS))
def test_a_close_pair_far_from_the_origin_keeps_float32(kernel, distance,
                                                        where):
    """The matmul form of the separation sum, rpx * sum(w) - sum(w * cpx),
    cancels numbers of size |p| / d: before PR 31 the MXU path read 3e-4 for
    a pair 1e-4 apart at the world's edge. What a weight has above CLOSE_W
    is summed as differences, in the strip that holds the pair's row."""
    pos, vel, bits = _state(256, 31)
    corner = np.asarray([7.9, -7.7], np.float32)
    for k, (i, j) in enumerate(PAIR_ROWS[where]):
        pos[i] = corner * np.asarray([1.0, (-1.0) ** k], np.float32)
        pos[j] = pos[i] + np.asarray([distance, 0.0], np.float32)
    got_p, got_v, _ = _program_step(kernel, pos, vel, bits)
    want_p, want_v = ref.step(pos[None], vel[None], bits[None])
    decided = ~ref.undecided(pos, MARGIN)
    assert decided[np.ravel(PAIR_ROWS[where])].all()
    assert ref.torus_gap(got_p, want_p[0])[decided].max() <= TOLERANCE[kernel]
    assert np.abs(got_v - want_v[0])[decided].max() <= TOLERANCE[kernel]


@pytest.mark.parametrize("kernel", ["mxu"])
@pytest.mark.parametrize("where", list(PADDED_PAIR_ROWS))
def test_a_padded_column_is_nobodys_close_neighbour(kernel, where):
    """200 boids pad to 256 columns, which sit at the origin with activity
    0: a boid 1e-3 from the origin must not feel them, in the matmuls (zero
    features) or in the close pairs' differenced sums (the mask; a live
    boid 2e-3 away makes those sums run for its strip)."""
    rows = PADDED_PAIR_ROWS[where]
    pos, vel, bits = _state(200, 41)
    for k, (i, j) in enumerate(rows):
        pos[i] = np.asarray([1e-3, 4e-3 * k], np.float32)
        pos[j] = pos[i] + np.asarray([0.0, 2e-3], np.float32)
    got_p, got_v, _ = _program_step(kernel, pos, vel, bits)
    want_p, want_v = ref.step(pos[None], vel[None], bits[None])
    decided = ~ref.undecided(pos, MARGIN)
    assert decided[rows[0][0]]
    assert np.abs(got_v - want_v[0])[decided].max() <= TOLERANCE[kernel]


@pytest.mark.parametrize("radius", [ref.NEIGHBOR_RADIUS,
                                    ref.SEPARATION_RADIUS])
def test_a_pair_on_a_radius_is_undecided_and_nothing_else_is(radius):
    pos, vel, bits = _state(64, 5)
    pos = pos * np.float32(3.0)                  # sparse: few pairs at all
    pos[7] = pos[3] + np.asarray([radius, 0.0], np.float32)
    flagged = ref.undecided(pos, MARGIN)
    assert flagged[3] and flagged[7]
    others = np.delete(np.arange(64), [3, 7])
    # No other pair of this seed lies within 1e-5 of a radius.
    assert not flagged[others].any()
    # Moved clear of the radius by a hundred margins, the pair is decided.
    pos[7] = pos[3] + np.asarray([radius * (1 + 100 * MARGIN), 0.0],
                                 np.float32)
    assert not ref.undecided(pos, MARGIN)[[3, 7]].any()
    # One float32 step either side of the radius flips the pair's test, and
    # the boid's force with it: what the margin leaves out.
    near = pos.copy()
    near[7] = near[3] + np.asarray([radius * (1 - 1e-6), 0.0], np.float32)
    far = pos.copy()
    far[7] = far[3] + np.asarray([radius * (1 + 1e-6), 0.0], np.float32)
    f_near, f_far = ref.forces(near, vel), ref.forces(far, vel)
    assert np.abs(f_near[3] - f_far[3]).max() > 1e-4


@pytest.mark.parametrize("kernel", ["xla", "mxu"])
def test_bfloat16_state_fails_every_tolerance(kernel):
    pos, vel, bits = _state(256, 21)
    got_p, got_v, _ = _program_step(kernel, pos, vel, bits, "bf16_state")
    assert np.array_equal(ref.round_bfloat16(got_p), got_p)
    want_p, want_v = ref.step(pos[None], vel[None], bits[None])
    decided = ~ref.undecided(pos, MARGIN)
    assert ref.torus_gap(got_p, want_p[0])[decided].max() > 10 * max(
        TOLERANCE.values())
    # The reference's own control rounds the same way.
    ctl_p, ctl_v = ref.step(pos[None], vel[None], bits[None],
                            precision="bfloat16")
    assert ref.torus_gap(got_p, ctl_p[0])[decided].max() <= 2 ** -7


def test_torus_gap_wraps_at_the_edge():
    a = np.asarray([[7.99999, 0.0]], np.float32)
    b = np.asarray([[-7.99999, 0.0]], np.float32)
    assert ref.torus_gap(a, b).max() < 1e-4
    assert ref.torus_gap(a, a * 0).max() == pytest.approx(7.99999, rel=1e-6)


@pytest.mark.parametrize("kernel", ["mxu"])
def test_unsharded_kernel_under_the_branch_vmap_is_the_serial_burst(kernel):
    """What warm-up attests on the chip, at a small size on the CPU: the
    ``[B]``-vmapped rollout through the Pallas kernel is bitwise the
    serial burst, every branch, structured tree included."""
    from bevy_ggrs_tpu.models import boids
    from bevy_ggrs_tpu.spec_runner import (
        SpeculativeRollbackRunner, attest_speculation_safety,
    )

    runner = SpeculativeRollbackRunner(
        boids.make_schedule(kernel=kernel),
        boids.make_world(64, 2).commit(), max_prediction=8, num_players=2,
        input_spec=boids.INPUT_SPEC, num_branches=4, spec_frames=4)
    report = attest_speculation_safety(runner)
    assert report.ok and report.scanned_branches == 4
    assert report.structured_checked
