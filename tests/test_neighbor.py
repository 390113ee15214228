"""Neighbor-grid subsystem (ops/neighbor.py + ops/cell_gather.py).

Five claims under test, matching the module's determinism contract:

1. Binning is bitwise-reproducible and *specified*: a pure-NumPy oracle
   twin reproduces slots/spill/occupancy/drop counters exactly (integer
   equality), including the overflow and drop regimes.
2. Grid-mode forces agree with the dense path within float tolerance
   (different summation association — allclose, never bitwise), for both
   the XLA and the Pallas cell-gather per-cell implementations.
3. Interactions whose pair terms are 0/1 indicators (the projectile hit
   test) agree with dense BITWISE — whole-state equality across an
   80-step spawn/despawn episode, and under SyncTest forced rollbacks
   (despawn/respawn masking mid-rollback).
4. Within grid mode the serial, fused-speculative (attestation) and
   entity-sharded executables are bitwise-equal to each other.
5. Mode resolution precedence: explicit > GGRS_FORCE_MODE env >
   SessionBuilder default > auto-threshold > legacy dense.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.models import boids
from bevy_ggrs_tpu.models import projectiles as pj
from bevy_ggrs_tpu.ops import neighbor
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.schedule import make_inputs
from bevy_ggrs_tpu.session import SyncTestSession


@pytest.fixture(autouse=True)
def _clear_session_default():
    yield
    neighbor.set_default_interaction_mode(None)


def oracle_bin(pos, active, cfg):
    """NumPy twin of neighbor.bin_entities — same float ops (f32 multiply,
    floor, int32 mod), same stable order, pure host code."""
    n = pos.shape[0]
    g, c = cfg.grid_dim, cfg.num_cells
    k, s = cfg.cell_capacity, cfg.spill_capacity
    inv = np.float32(1.0 / cfg.cell_size)
    ix = np.floor(pos[:, 0].astype(np.float32) * inv).astype(np.int32) % g
    iy = np.floor(pos[:, 1].astype(np.float32) * inv).astype(np.int32) % g
    cell = np.where(active.astype(bool), iy * g + ix, c).astype(np.int32)
    order = np.argsort(cell, kind="stable").astype(np.int32)
    sc = cell[order]
    rank = np.arange(n) - np.searchsorted(sc, sc, side="left")
    slots = np.full((c, k), n, np.int32)
    slotted = (sc < c) & (rank < k)
    slots[sc[slotted], rank[slotted]] = order[slotted]
    over = (sc < c) & (rank >= k)
    ov = order[over]
    spill = np.full(s, n, np.int32)
    spill[: min(len(ov), s)] = ov[:s]
    occ = np.bincount(sc[sc < c], minlength=c)[:c].astype(np.int32)
    n_spilled = int(over.sum())
    return slots, spill, cell, occ, n_spilled, max(n_spilled - s, 0)


def rand_world(n, seed=0, spread=8.0):
    rng = np.random.RandomState(seed)
    pos = rng.uniform(-spread, spread, size=(n, 2)).astype(np.float32)
    vel = rng.uniform(-0.05, 0.05, size=(n, 2)).astype(np.float32)
    active = np.ones(n, bool)
    active[rng.choice(n, size=n // 8, replace=False)] = False
    return pos, vel, active


def assert_matches_oracle(pos, active, cfg):
    g = neighbor.bin_entities(jnp.asarray(pos), jnp.asarray(active), cfg)
    slots, spill, cell, occ, n_spilled, n_dropped = oracle_bin(
        pos, active, cfg
    )
    np.testing.assert_array_equal(np.asarray(g.slots), slots)
    np.testing.assert_array_equal(np.asarray(g.spill), spill)
    np.testing.assert_array_equal(np.asarray(g.cell_of), cell)
    np.testing.assert_array_equal(np.asarray(g.occupancy), occ)
    assert int(g.n_spilled) == n_spilled
    assert int(g.n_dropped) == n_dropped


class TestBinning:
    def test_matches_numpy_oracle(self):
        pos, _, active = rand_world(700, seed=3)
        assert_matches_oracle(pos, active, boids.grid_config(700))

    def test_oracle_parity_beyond_world_bounds(self):
        """Spawn-spiral positions exceed ±WORLD_HALF at scale; binning must
        stay well-defined (mod-wrap aliasing) and oracle-exact there."""
        rng = np.random.RandomState(9)
        pos = rng.uniform(-60, 60, size=(900, 2)).astype(np.float32)
        active = rng.rand(900) > 0.2
        assert_matches_oracle(pos, active, boids.grid_config(900))

    def test_oracle_parity_under_overflow_and_drop(self):
        """Clustered world: cells overflow into spill, spill overflows into
        counted drops — the oracle reproduces both regimes exactly."""
        rng = np.random.RandomState(5)
        pos = (rng.uniform(-0.4, 0.4, size=(64, 2))).astype(np.float32)
        active = np.ones(64, bool)
        cfg = neighbor.GridConfig(
            cell_size=1.0, grid_dim=4, cell_capacity=4, spill_capacity=8
        )
        g = neighbor.bin_entities(jnp.asarray(pos), jnp.asarray(active), cfg)
        assert int(g.n_spilled) > 8 and int(g.n_dropped) > 0
        assert_matches_oracle(pos, active, cfg)

    def test_inactive_entities_reach_neither_slots_nor_spill(self):
        pos, _, active = rand_world(300, seed=7)
        cfg = boids.grid_config(300)
        g = neighbor.bin_entities(jnp.asarray(pos), jnp.asarray(active), cfg)
        slots = np.asarray(g.slots)
        members = set(slots[slots < 300].tolist())
        spill = np.asarray(g.spill)
        members |= set(spill[spill < 300].tolist())
        assert members == set(np.where(active)[0].tolist())
        assert np.all(np.asarray(g.cell_of)[~active] == cfg.num_cells)

    def test_default_config_shapes(self):
        cfg = boids.grid_config(32768)
        assert cfg.grid_dim == 16  # pow2 covering the ±8 torus at s=1
        assert cfg.cell_capacity % 8 == 0
        assert cfg.padded_cols % 128 == 0
        with pytest.raises(ValueError):
            neighbor.GridConfig(
                cell_size=1.0, grid_dim=2, cell_capacity=4, spill_capacity=4
            )

    def test_cell_size_below_radius_rejected(self):
        pos, vel, active = rand_world(64)
        cfg = neighbor.GridConfig(
            cell_size=0.5, grid_dim=16, cell_capacity=8, spill_capacity=8
        )
        with pytest.raises(ValueError, match="radius"):
            neighbor.interact(
                jnp.asarray(pos), jnp.asarray(active),
                boids.FLOCK_PAIR_KERNEL,
                {"vx": jnp.asarray(vel[:, 0]), "vy": jnp.asarray(vel[:, 1])},
                mode="grid", config=cfg,
            )

    def test_grid_stats_keys(self):
        pos, _, active = rand_world(500)
        stats = neighbor.grid_stats(pos, active, boids.grid_config(500))
        for key in ("occupancy_mean", "occupancy_max", "spill_rate",
                    "dropped", "slot_utilization"):
            assert key in stats
        assert stats["dropped"] == 0


class TestForces:
    def _forces(self, pos, vel, active, **kw):
        return neighbor.interact(
            jnp.asarray(pos), jnp.asarray(active), boids.FLOCK_PAIR_KERNEL,
            {"vx": jnp.asarray(vel[:, 0]), "vy": jnp.asarray(vel[:, 1])},
            **kw,
        )

    def test_dense_matches_legacy_reference(self):
        """The PairKernel dense path must reproduce pairwise_force_rows —
        same terms, same masks — to float tolerance."""
        pos, vel, active = rand_world(400, seed=1)
        af = active.astype(np.float32)
        ref = boids.pairwise_force_rows(
            jnp.asarray(pos), jnp.asarray(vel), jnp.asarray(pos),
            jnp.asarray(vel), jnp.asarray(af), jnp.asarray(af),
        )
        got = self._forces(pos, vel, active, mode="dense")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_grid_matches_dense(self, impl):
        pos, vel, active = rand_world(1500, seed=2)
        cfg = boids.grid_config(1500)
        dense = self._forces(pos, vel, active, mode="dense")
        grid, g = self._forces(pos, vel, active, mode="grid", config=cfg,
                               impl=impl, return_grid=True)
        assert int(g.n_dropped) == 0
        np.testing.assert_allclose(np.asarray(grid), np.asarray(dense),
                                   atol=1e-5)
        assert np.all(np.asarray(grid)[~active] == 0.0)

    def test_spill_fallback_preserves_forces(self):
        """Overflowed cells degrade to the dense [S, N] pass, not to wrong
        values: a clustered world with most entities spilled still matches
        dense."""
        rng = np.random.RandomState(11)
        pos = rng.uniform(-0.45, 0.45, size=(48, 2)).astype(np.float32)
        vel = rng.uniform(-0.05, 0.05, size=(48, 2)).astype(np.float32)
        active = np.ones(48, bool)
        cfg = neighbor.GridConfig(
            cell_size=1.0, grid_dim=4, cell_capacity=4, spill_capacity=48
        )
        dense = self._forces(pos, vel, active, mode="dense")
        grid, g = self._forces(pos, vel, active, mode="grid", config=cfg,
                               return_grid=True)
        assert int(g.n_spilled) > 0 and int(g.n_dropped) == 0
        np.testing.assert_allclose(np.asarray(grid), np.asarray(dense),
                                   atol=1e-5)

    def test_dropped_entities_get_zero_force(self):
        rng = np.random.RandomState(13)
        pos = rng.uniform(-0.45, 0.45, size=(48, 2)).astype(np.float32)
        vel = rng.uniform(-0.05, 0.05, size=(48, 2)).astype(np.float32)
        active = np.ones(48, bool)
        cfg = neighbor.GridConfig(
            cell_size=1.0, grid_dim=4, cell_capacity=4, spill_capacity=4
        )
        grid, g = self._forces(pos, vel, active, mode="grid", config=cfg,
                               return_grid=True)
        assert int(g.n_dropped) > 0
        slots = np.asarray(g.slots)
        placed = set(slots[slots < 48].tolist())
        spill = np.asarray(g.spill)
        placed |= set(spill[spill < 48].tolist())
        dropped = sorted(set(range(48)) - placed)
        assert len(dropped) == int(g.n_dropped)
        np.testing.assert_array_equal(np.asarray(grid)[dropped], 0.0)


class TestProjectilesBitwise:
    def test_dense_vs_grid_bitwise_over_lifecycle(self):
        """0/1 indicator sums are exact in f32, so the hit decision — and
        therefore the whole spawn/despawn state evolution — is bitwise
        mode-invariant."""
        state = pj.make_world(2, capacity=64).commit()
        sched_d = pj.make_schedule(mode="dense")
        sched_g = pj.make_schedule(mode="grid")

        @functools.partial(jax.jit, static_argnums=1)
        def step(s, sched, bits):
            return sched(s, make_inputs(bits))

        rng = np.random.RandomState(1)
        s_d = s_g = state
        saw_projectile = False
        for _ in range(80):
            bits = jnp.asarray(rng.randint(0, 32, size=2), jnp.uint8)
            s_d = step(s_d, sched_d, bits)
            s_g = step(s_g, sched_g, bits)
            saw_projectile = saw_projectile or bool(
                np.asarray(s_d.alive).sum() > 2
            )
        assert saw_projectile
        for a, b in zip(jax.tree_util.tree_leaves(s_d),
                        jax.tree_util.tree_leaves(s_g)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(s_d.resources["score"]).sum() > 0

    def test_synctest_despawn_respawn_under_forced_rollbacks_grid(self):
        """Grid-mode despawn/respawn masking mid-rollback: SyncTest
        resimulates every frame from check_distance back, so rolled-back
        spawns/despawns must rebin identically or the checksum trips."""
        session = SyncTestSession(
            2, pj.INPUT_SPEC, check_distance=5, max_prediction=8
        )
        runner = RollbackRunner(
            pj.make_schedule(mode="grid"),
            pj.make_world(2, capacity=32).commit(),
            max_prediction=8,
            num_players=2,
            input_spec=pj.INPUT_SPEC,
        )
        saw_projectile = False
        for frame in range(60):  # raises MismatchedChecksum on any desync
            for h in range(2):
                bits = pj.INPUT_FIRE if (frame + h) % 3 == 0 else (
                    pj.INPUT_RIGHT if h == 0 else pj.INPUT_UP
                )
                session.add_local_input(h, np.uint8(bits))
            runner.handle_requests(session.advance_frame(), session)
            host_alive = np.asarray(runner.state.alive)
            saw_projectile = saw_projectile or host_alive.sum() > 2
        assert runner.frame == 60
        assert saw_projectile


class TestCrossExecutable:
    def test_serial_vs_sharded_grid_bitwise(self):
        """Grid-mode twin of tests/test_sharded_midscale.py: the cell-slice
        sharded path (all-gathered slot-force concat, no float psum) must
        match the unsharded grid executable bitwise."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        from bevy_ggrs_tpu.parallel.sharding import branch_mesh, shard_world
        from bevy_ggrs_tpu.rollout import advance_n
        from bevy_ggrs_tpu.state import checksum, combine64

        sched = boids.make_schedule(kernel="xla", mode="grid")
        state = boids.make_world(4096, 2).commit()
        bits = jnp.asarray(np.array([[1, 2], [4, 8], [0, 3]], np.uint8))

        plain = advance_n(sched, state, bits)
        mesh = branch_mesh(entity_shards=8)
        sharded = advance_n(sched, shard_world(state, mesh, "entity"), bits)

        assert combine64(checksum(plain)) == combine64(checksum(sharded))
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(sharded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_sharded_grid_system_bitwise(self):
        """make_sharded_flock_system(mode="grid") — replicated binning +
        per-shard cell slices — matches the serial grid system bitwise."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:8]), ("entity",))
        state = boids.make_world(4096, 2).commit()
        serial = boids.make_schedule(kernel="xla", mode="grid")
        # Grid mode partitions the XLA cell compute: ``kernel`` names the
        # DENSE force and is not read here.
        shard = boids.make_sharded_schedule(mesh, "entity", mode="grid")

        @functools.partial(jax.jit, static_argnums=1)
        def step(s, sched, bits):
            return sched(s, make_inputs(bits))

        s1 = s2 = state
        for f in range(3):
            bits = jnp.asarray([f % 16, (f * 7) % 16], jnp.uint8)
            s1 = step(s1, serial, bits)
            s2 = step(s2, shard, bits)
        for name in ("position", "velocity"):
            np.testing.assert_array_equal(
                np.asarray(s1.components[name]),
                np.asarray(s2.components[name]),
            )

    @pytest.mark.parametrize(
        "make", [
            lambda: (boids.make_schedule(kernel="xla", mode="grid"),
                     boids.make_world(256, 2).commit(), boids.INPUT_SPEC),
            lambda: (pj.make_schedule(mode="grid"),
                     pj.make_world(2, capacity=32).commit(), pj.INPUT_SPEC),
        ],
        ids=["boids_grid", "projectiles_grid"],
    )
    def test_attestation_holds_in_grid_mode(self, make):
        """Serial-burst vs vmapped-speculative bitwise equality (the
        attestation machinery) with the binning inside the step."""
        from bevy_ggrs_tpu.spec_runner import (
            SpeculativeRollbackRunner,
            attest_speculation_safety,
        )

        sched, state, spec = make()
        runner = SpeculativeRollbackRunner(
            sched, state, max_prediction=8, num_players=2,
            input_spec=spec, num_branches=8, spec_frames=4,
        )
        report = attest_speculation_safety(runner)
        assert report.ok


class TestModeResolution:
    def test_explicit_always_wins(self, monkeypatch):
        monkeypatch.setenv("GGRS_FORCE_MODE", "grid")
        assert neighbor.resolve_mode("dense", 10**6) == "dense"
        monkeypatch.setenv("GGRS_FORCE_MODE", "dense")
        assert neighbor.resolve_mode("grid", 4) == "grid"

    def test_env_overrides_auto_and_legacy_default(self, monkeypatch):
        monkeypatch.setenv("GGRS_FORCE_MODE", "grid")
        assert neighbor.resolve_mode(None, 4) == "grid"
        assert neighbor.resolve_mode("auto", 4) == "grid"
        monkeypatch.delenv("GGRS_FORCE_MODE")
        assert neighbor.resolve_mode(None, 10**6) == "dense"

    def test_auto_threshold(self, monkeypatch):
        monkeypatch.delenv("GGRS_FORCE_MODE", raising=False)
        t = neighbor.GRID_AUTO_THRESHOLD
        assert neighbor.resolve_mode("auto", t - 1) == "dense"
        assert neighbor.resolve_mode("auto", t) == "grid"

    def test_session_builder_default(self, monkeypatch):
        monkeypatch.delenv("GGRS_FORCE_MODE", raising=False)
        from bevy_ggrs_tpu.session import SessionBuilder

        SessionBuilder().with_interaction_mode("grid")
        assert neighbor.resolve_mode(None, 4) == "grid"
        # env still outranks the session default for non-explicit modes
        monkeypatch.setenv("GGRS_FORCE_MODE", "dense")
        assert neighbor.resolve_mode(None, 4) == "dense"
        neighbor.set_default_interaction_mode(None)
        monkeypatch.delenv("GGRS_FORCE_MODE")
        assert neighbor.resolve_mode(None, 4) == "dense"

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError):
            neighbor.resolve_mode("sparse", 4)
        with pytest.raises(ValueError):
            neighbor.set_default_interaction_mode("sparse")
