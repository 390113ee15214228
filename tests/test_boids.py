"""Boids model: entity-coupled dynamics, determinism, entity-axis sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.models import boids
from bevy_ggrs_tpu.parallel.sharding import branch_mesh, shard_branch_axis, shard_world
from bevy_ggrs_tpu.parallel.speculate import SpeculativeExecutor
from bevy_ggrs_tpu.rollout import advance_n
from bevy_ggrs_tpu.schedule import make_inputs
from bevy_ggrs_tpu.state import combine64, checksum


def make_state(n=64, players=2, seed=0):
    return boids.make_world(n, players, seed=seed).commit()


class TestFlocking:
    def test_speed_clamp_and_bounds(self):
        state = make_state(48)
        sched = boids.make_schedule()
        inputs = make_inputs(np.zeros(2, np.uint8))
        for _ in range(5):
            state = sched(state, inputs)
        v = np.asarray(state.components["velocity"])
        speed = np.sqrt((v * v).sum(axis=1))
        assert (speed <= float(boids.MAX_SPEED) + 1e-5).all()
        assert (speed >= float(boids.MIN_SPEED) - 1e-5).all()
        p = np.asarray(state.components["position"])
        assert (np.abs(p) <= float(boids.WORLD_HALF) + 1e-4).all()

    def test_leaders_respond_to_input(self):
        state = make_state(16, players=1)
        sched = boids.make_schedule()
        right = make_inputs(np.array([boids.INPUT_RIGHT], np.uint8))
        s1 = sched(state, right)
        # Leader (slot 0) accelerated +x relative to no-input run.
        s0 = sched(state, make_inputs(np.zeros(1, np.uint8)))
        dv = float(s1.components["velocity"][0, 0] - s0.components["velocity"][0, 0])
        assert dv > 0

    def test_bitwise_deterministic(self):
        state = make_state(64)
        bits = jnp.asarray(
            np.random.RandomState(1).randint(0, 16, (10, 2), dtype=np.uint8)
        )
        a = advance_n(boids.make_schedule(), state, bits)
        b = advance_n(boids.make_schedule(), state, bits)
        np.testing.assert_array_equal(
            np.asarray(a.components["position"]), np.asarray(b.components["position"])
        )
        assert combine64(checksum(a)) == combine64(checksum(b))


class TestBoidsSyncTest:
    def test_rollback_resim_is_bit_identical(self):
        """The determinism harness on an entity-coupled model: forced
        rollback + resimulation must reproduce checksums exactly."""
        from bevy_ggrs_tpu.models import boids as bd
        from bevy_ggrs_tpu.runner import RollbackRunner
        from bevy_ggrs_tpu.session import SessionBuilder

        session = (
            SessionBuilder(bd.INPUT_SPEC)
            .with_num_players(2)
            .with_check_distance(3)
            .start_synctest_session()
        )
        runner = RollbackRunner(
            bd.make_schedule(), make_state(48), 8, 2, bd.INPUT_SPEC
        )
        rng = np.random.RandomState(7)
        for _ in range(12):  # raises MismatchedChecksum on any divergence
            for h in range(2):
                session.add_local_input(h, np.uint8(rng.randint(0, 16)))
            runner.handle_requests(session.advance_frame(), session)
        assert runner.rollbacks_total > 0


class TestEntitySharding:
    def test_2d_mesh_speculative_close_to_unsharded(self):
        """branch x entity mesh: numerics match the unsharded run to float
        tolerance (cross-device reduction order may differ, so this is
        allclose, not bitwise — bitwise holds within a fixed topology)."""
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = branch_mesh(entity_shards=2)  # 4 x 2 over 8 virtual devices
        n_branch = 8
        frames = 3
        state = make_state(32)
        bits = jnp.asarray(
            np.random.RandomState(5).randint(
                0, 16, (n_branch, frames, 2), dtype=np.uint8
            )
        )
        plain = SpeculativeExecutor(boids.make_schedule(), n_branch, frames)
        r_plain = plain.run(state, 0, bits)

        sharded = SpeculativeExecutor(
            boids.make_schedule(),
            n_branch,
            frames,
            mesh=mesh,
            entity_axis="entity",
            state_template=state,
        )
        r_shard = sharded.run(
            shard_world(state, mesh), 0, shard_branch_axis(bits, mesh)
        )
        np.testing.assert_allclose(
            np.asarray(r_plain.states.components["position"]),
            np.asarray(r_shard.states.components["position"]),
            rtol=1e-5,
            atol=1e-6,
        )

    def test_2d_mesh_reproducible_within_topology(self):
        """Same mesh, same inputs → bitwise-identical checksums (the
        determinism contract peers must share a topology for)."""
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device mesh")
        mesh = branch_mesh(entity_shards=2)
        state = make_state(32)
        bits = jnp.asarray(
            np.random.RandomState(6).randint(0, 16, (8, 3, 2), dtype=np.uint8)
        )
        ex = SpeculativeExecutor(
            boids.make_schedule(), 8, 3, mesh=mesh,
            entity_axis="entity", state_template=state,
        )
        r1 = ex.run(shard_world(state, mesh), 0, shard_branch_axis(bits, mesh))
        r2 = ex.run(shard_world(state, mesh), 0, shard_branch_axis(bits, mesh))
        np.testing.assert_array_equal(
            np.asarray(r1.checksums), np.asarray(r2.checksums)
        )


class TestShardMapKernel:
    """Round-2 weak #7: Pallas kernels ran replicated under GSPMD (a custom
    call cannot be partitioned). make_sharded_flock_system wraps them in
    shard_map: each device runs the kernel on its own row block against an
    all-gathered column set. Row blocks are independent in the kernel's
    accumulation, and the gathered column order is the global order, so the
    sharded run must match the unsharded kernel BITWISE."""

    def _run_session(self, schedule, mesh):
        from bevy_ggrs_tpu.runner import RollbackRunner
        from bevy_ggrs_tpu.session import SyncTestSession

        session = SyncTestSession(2, boids.INPUT_SPEC, check_distance=3,
                                  max_prediction=6)
        runner = RollbackRunner(
            schedule, boids.make_world(64, 2).commit(),
            max_prediction=6, num_players=2, input_spec=boids.INPUT_SPEC,
            mesh=mesh,
        )
        rng = np.random.RandomState(9)
        cs = []
        for _ in range(15):
            for h in range(2):
                session.add_local_input(h, np.uint8(rng.randint(0, 16)))
            runner.handle_requests(session.advance_frame(), session)
            cs.append(combine64(checksum(runner.state)))
        return cs

    @pytest.mark.parametrize("kernel", ["mxu"])
    def test_sharded_kernel_bitwise_vs_unsharded(self, kernel):
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device mesh")
        from bevy_ggrs_tpu.parallel.sharding import branch_mesh

        mesh = branch_mesh(entity_shards=len(jax.devices()))
        sharded = self._run_session(
            boids.make_sharded_schedule(mesh, kernel=kernel), mesh
        )
        plain = self._run_session(boids.make_schedule(kernel=kernel), None)
        assert sharded == plain

    def test_sharded_kernel_state_distributed(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device mesh")
        from bevy_ggrs_tpu.parallel.sharding import branch_mesh
        from bevy_ggrs_tpu.runner import RollbackRunner
        from bevy_ggrs_tpu.session import SyncTestSession

        mesh = branch_mesh(entity_shards=len(jax.devices()))
        runner = RollbackRunner(
            boids.make_sharded_schedule(mesh), boids.make_world(64, 2).commit(),
            max_prediction=6, num_players=2, input_spec=boids.INPUT_SPEC,
            mesh=mesh,
        )
        session = SyncTestSession(2, boids.INPUT_SPEC, check_distance=3,
                                  max_prediction=6)
        for _ in range(8):
            for h in range(2):
                session.add_local_input(h, np.uint8(0))
            runner.handle_requests(session.advance_frame(), session)
        assert not runner.state.components["position"].sharding.is_fully_replicated


class TestShardMapSpeculative:
    def test_sharded_kernel_under_vmapped_branches_bitwise(self):
        """The full composition: shard_map-partitioned MXU kernel inside
        the vmapped SpeculativeExecutor on a 2D branch x entity mesh —
        checksum streams bitwise-equal to the unsharded mxu rollout."""
        if len(jax.devices()) < 4:
            pytest.skip("needs a 2D mesh")
        mesh = branch_mesh(entity_shards=2)
        state = boids.make_world(64, 2).commit()
        # Branch count sized to the mesh's branch axis (divisibility).
        B, F = 2 * (len(jax.devices()) // 2), 3
        bits = np.random.RandomState(0).randint(0, 16, (B, F, 2), np.uint8)

        ex = SpeculativeExecutor(
            boids.make_sharded_schedule(mesh, kernel="mxu"), B, F,
            mesh=mesh, entity_axis="entity", state_template=state,
        )
        res = ex.run(
            shard_world(state, mesh), 0,
            shard_branch_axis(jnp.asarray(bits), mesh),
        )

        ex_plain = SpeculativeExecutor(boids.make_schedule(kernel="mxu"), B, F)
        res_plain = ex_plain.run(state, 0, jnp.asarray(bits))
        assert np.array_equal(
            np.asarray(res.checksums), np.asarray(res_plain.checksums)
        )
        # The branch states really are distributed on both axes.
        pos = res.states.components["position"]
        assert not pos.sharding.is_fully_replicated
