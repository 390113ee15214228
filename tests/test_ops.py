"""Pallas kernel parity tests (interpreter mode on the CPU test mesh).

The checksum kernel must agree BITWISE with the XLA path (same integer ops,
same order); the pairwise-force kernel must be allclose to the XLA path and
bitwise self-deterministic (the SyncTest property).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import state as state_lib
from bevy_ggrs_tpu.models import boids, box_game
from bevy_ggrs_tpu.ops.checksum import checksum_pallas, install_pallas_checksum
from bevy_ggrs_tpu.schedule import make_inputs
from bevy_ggrs_tpu.state import (
    TypeRegistry,
    HostWorld,
    checksum,
    combine64,
    ring_init,
    ring_save,
)


def test_checksum_pallas_bitwise_box_game():
    state = box_game.make_world(2).commit()
    assert combine64(checksum_pallas(state)) == combine64(checksum(state))


def test_checksum_pallas_bitwise_boids():
    state = boids.make_world(64, 2).commit()
    assert combine64(checksum_pallas(state)) == combine64(checksum(state))


def test_checksum_pallas_sees_despawn_and_presence():
    w = box_game.make_world(4, capacity=8)
    base = w.commit()
    w.despawn(1)
    fewer = w.commit()
    assert combine64(checksum_pallas(base)) == combine64(checksum(base))
    assert combine64(checksum_pallas(fewer)) == combine64(checksum(fewer))
    assert combine64(checksum_pallas(base)) != combine64(checksum_pallas(fewer))


def test_checksum_pallas_large_component_scan_path():
    # >64 words per slot exercises the fori_loop branch of the kernel.
    reg = TypeRegistry()
    reg.register_component("grid", shape=(10, 10), dtype=jnp.float32)
    reg.register_component("tag", shape=(), dtype=jnp.int32)
    w = HostWorld(reg, 16)
    rng = np.random.RandomState(3)
    for i in range(12):
        w.spawn(
            {"grid": rng.randn(10, 10).astype(np.float32), "tag": np.int32(i)},
            rollback_id=i,
        )
    state = w.commit()
    assert combine64(checksum_pallas(state)) == combine64(checksum(state))


def test_checksum_pallas_vmap_branch_axis():
    state = box_game.make_world(2).commit()
    moved = state.replace(
        components={
            **state.components,
            "translation": state.components["translation"] + 1.0,
        }
    )
    stacked = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]), state, moved
    )
    cs = jax.vmap(checksum_pallas)(stacked)
    assert combine64(cs[0]) == combine64(checksum(state))
    assert combine64(cs[1]) == combine64(checksum(moved))


def test_install_pallas_checksum_ring_save():
    state = box_game.make_world(2).commit()
    ring = ring_init(state, 4)
    try:
        install_pallas_checksum(True)
        _, cs = ring_save(ring, state, 0)
    finally:
        install_pallas_checksum(False)
    assert combine64(cs) == combine64(checksum(state))


def _random_flock(n, seed=0, inactive_every=None):
    rng = np.random.RandomState(seed)
    pos = rng.uniform(-2, 2, size=(n, 2)).astype(np.float32)
    vel = rng.uniform(-0.05, 0.05, size=(n, 2)).astype(np.float32)
    active = np.ones((n,), dtype=np.float32)
    if inactive_every:
        active[::inactive_every] = 0.0
    return jnp.asarray(pos), jnp.asarray(vel), jnp.asarray(active)


_KPARAMS = dict(
    neighbor_radius=float(boids.NEIGHBOR_RADIUS),
    separation_radius=float(boids.SEPARATION_RADIUS),
    w_separation=float(boids.W_SEPARATION),
    w_alignment=float(boids.W_ALIGNMENT),
    w_cohesion=float(boids.W_COHESION),
)


# ---------------------------------------------------------------------------
# MXU kernel variant (feature-major matmul reductions)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 200, 300])
def test_pairwise_mxu_matches_xla(n):
    """bf16 hi/lo-split matmul reductions: ~4e-4 relative to the force
    scale vs the f32 paths (documented tolerance — the masks themselves are
    f32-exact, so no discrete neighbor flips, only summation rounding)."""
    from bevy_ggrs_tpu.ops.pairwise import pairwise_force_rows_mxu2

    pos, vel, active = _random_flock(n, seed=n, inactive_every=7)
    got = pairwise_force_rows_mxu2(
        pos, vel, pos, vel, active, active, col_block=128, **_KPARAMS
    )
    want = boids.pairwise_force_rows(pos, vel, pos, vel, active, active)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=max(1e-3 * scale, 1e-6)
    )
    assert not np.any(np.asarray(got)[::7])  # inactive rows exactly zero


def test_pairwise_mxu_row_subset_and_vmap():
    from bevy_ggrs_tpu.ops.pairwise import pairwise_force_rows_mxu2

    pos, vel, active = _random_flock(128, seed=5)
    got = pairwise_force_rows_mxu2(
        pos[32:64], vel[32:64], pos, vel, active[32:64], active,
        col_block=128, **_KPARAMS,
    )
    want = boids.pairwise_force_rows(
        pos[32:64], vel[32:64], pos, vel, active[32:64], active
    )
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=max(1e-3 * scale, 1e-6)
    )

    batches = [_random_flock(96, seed=s) for s in range(2)]
    bp = jnp.stack([b[0] for b in batches])
    bv = jnp.stack([b[1] for b in batches])
    ba = jnp.stack([b[2] for b in batches])

    def one(p, v, a):
        return pairwise_force_rows_mxu2(
            p, v, p, v, a, a, col_block=128, **_KPARAMS
        )

    got = jax.vmap(one)(bp, bv, ba)
    for i in range(2):
        want = boids.pairwise_force_rows(
            bp[i], bv[i], bp[i], bv[i], ba[i], ba[i]
        )
        scale = np.abs(np.asarray(want)).max()
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want), atol=max(1e-3 * scale, 1e-6)
        )


def _flock_with_close_pairs(n, seed):
    """A random flock with three pairs closer than 1 / CLOSE_W (3e-4 to
    2e-3), spread over the rows, and one inactive boid."""
    pos, vel, active = (np.asarray(x).copy() for x in _random_flock(n, seed))
    for k, gap in enumerate((3e-4, 1e-3, 2e-3)):
        i, j = (k * n) // 3 + 5, n - 7 - 11 * k
        pos[j] = pos[i] + np.float32([gap, 0.0])
    active[n // 2 + 1] = 0.0
    return jnp.asarray(pos), jnp.asarray(vel), jnp.asarray(active)


# (boids, rows the call owns, col_block, batch axes): the 1,024-boid world
# at a size the interpreter takes in a second, the sharded contract (R < N),
# boid counts that pad rows and columns, two column steps, the two vmaps.
_STRIP_CASES = {
    "square_four_strips": (512, (0, 512), 1024, 0),
    "square_two_strips": (256, (0, 256), 1024, 0),
    "row_subset_of_a_shard": (512, (128, 384), 1024, 0),
    "row_subset_one_strip": (512, (384, 512), 1024, 0),
    "rows_and_columns_pad": (300, (0, 300), 1024, 0),
    "rows_pad_two_column_steps": (200, (0, 200), 128, 0),
    "two_column_steps": (512, (0, 512), 256, 0),
    "under_one_vmap": (256, (0, 256), 1024, 1),
    "under_two_vmaps": (256, (0, 256), 1024, 2),
}


@pytest.mark.parametrize("case", list(_STRIP_CASES))
def test_pairwise_mxu_strip_height_changes_no_bit(case):
    """The pair block is walked in strips of ``STRIP_ROWS`` rows (PR 49):
    a row's sums contract the same columns in the same order whichever
    strip it rides in, and the close pairs' sums are taken where a strip
    holds one, so the strip height changes no bit of any force. Compared
    with the whole block as one strip (the kernel's form before PR 49)."""
    from bevy_ggrs_tpu.ops.pairwise import (
        STRIP_ROWS, pairwise_force_rows_mxu2,
    )

    n, (r0, r1), col_block, batch = _STRIP_CASES[case]
    worlds = [_flock_with_close_pairs(n, seed=n + k) for k in range(2 ** batch)]
    args = [jnp.stack(x).reshape((2,) * batch + x[0].shape)
            for x in zip(*worlds)]

    def force(strip_rows):
        def one(p, v, a):
            return pairwise_force_rows_mxu2(
                p[r0:r1], v[r0:r1], p, v, a[r0:r1], a, col_block=col_block,
                strip_rows=strip_rows, **_KPARAMS,
            )

        for _ in range(batch):
            one = jax.vmap(one)
        return np.asarray(one(*args))

    whole, strips = force(1024), force(STRIP_ROWS)
    assert np.array_equal(whole, strips)
    assert np.abs(whole).max() > 0.05  # a close pair's force, not zeros


@pytest.mark.parametrize("n,blk", [(256, 128), (300, 128), (512, 128)])
def test_pairwise_tri_matches_xla(n, blk):
    """Triangle kernel (symmetry-halved mask work): same tolerance class
    as the general MXU kernel; the small block forces a multi-block grid
    so diagonal, off-diagonal, predicated-off, and padded blocks all
    execute. n=300 exercises column padding inside the triangle."""
    from bevy_ggrs_tpu.ops.pairwise import pairwise_force_square_mxu_tri

    pos, vel, active = _random_flock(n, seed=n, inactive_every=7)
    got = pairwise_force_square_mxu_tri(
        pos, vel, active, block=blk, **_KPARAMS
    )
    want = boids.pairwise_force_rows(pos, vel, pos, vel, active, active)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=max(1e-3 * scale, 1e-6)
    )
    assert not np.any(np.asarray(got)[::7])  # inactive rows exactly zero


def test_pairwise_tri_vmap_and_determinism():
    """The speculative executor runs kernels under vmap: the triangle's
    full-width col-side scratch and predicated grid must batch correctly,
    and repeated runs must be bitwise identical (SyncTest property)."""
    from bevy_ggrs_tpu.ops.pairwise import pairwise_force_square_mxu_tri

    batches = [_random_flock(256, seed=s) for s in range(2)]
    bp = jnp.stack([b[0] for b in batches])
    bv = jnp.stack([b[1] for b in batches])
    ba = jnp.stack([b[2] for b in batches])

    def one(p, v, a):
        return pairwise_force_square_mxu_tri(p, v, a, block=128, **_KPARAMS)

    got = jax.vmap(one)(bp, bv, ba)
    for i in range(2):
        want = boids.pairwise_force_rows(
            bp[i], bv[i], bp[i], bv[i], ba[i], ba[i]
        )
        scale = np.abs(np.asarray(want)).max()
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want),
            atol=max(1e-3 * scale, 1e-6),
        )
    again = jax.vmap(one)(bp, bv, ba)
    assert np.array_equal(np.asarray(got), np.asarray(again))


def test_flock_mxu_step_close_and_deterministic():
    state = boids.make_world(200, 2).commit()
    inputs = make_inputs(jnp.asarray([boids.INPUT_RIGHT, 0], dtype=jnp.uint8))
    xla_step = boids.make_schedule(kernel="xla")
    mxu_step = boids.make_schedule(kernel="mxu")
    a = xla_step(state, inputs)
    b = mxu_step(state, inputs)
    np.testing.assert_allclose(
        np.asarray(a.components["position"]),
        np.asarray(b.components["position"]),
        atol=1e-4,
    )
    # Bitwise self-determinism (what SyncTest checks within one path).
    b2 = mxu_step(state, inputs)
    assert combine64(checksum(b)) == combine64(checksum(b2))
