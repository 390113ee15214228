"""App layer: GGRSPlugin builder + GGRSStage fixed-timestep driver."""

import jax
import numpy as np
import pytest

from bevy_ggrs_tpu.app import GGRSPlugin, RollbackApp, SessionType
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.session import MismatchedChecksum, SessionBuilder, PlayerType
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

import jax.numpy as jnp


def constant_input(key):
    return lambda handle, app: np.uint8(key)


def scripted(handle, app):
    keys = [box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0]
    frame = app.session.current_frame
    return np.uint8(keys[(frame // 3 + handle) % len(keys)])


def build_box_app(num_players=2, fps=60, input_fn=None, max_prediction=8,
                  clock=None, speculation=0, mesh=None, metrics=None):
    def setup(world, app):
        box_game.spawn_players(
            world, num_players, next_id=app.rollback_id_provider.next_id
        )

    plugin = (
        GGRSPlugin(box_game.INPUT_SPEC)
        .with_update_frequency(fps)
        .with_input_system(input_fn or constant_input(box_game.INPUT_UP))
        .register_rollback_component("translation", shape=(3,), dtype=jnp.float32)
        .register_rollback_component("velocity", shape=(3,), dtype=jnp.float32)
        .register_rollback_component("player_handle", dtype=jnp.int32, default=-1)
        .register_rollback_resource("frame_count", jnp.uint32(0))
        .with_rollback_schedule(box_game.make_schedule())
        .with_num_players(num_players)
        .with_max_prediction_window(max_prediction)
        .with_world_capacity(16)
        .with_setup_system(setup)
    )
    if clock is not None:
        plugin.with_clock(clock)
    if speculation:
        plugin.with_speculation(speculation)
    if mesh is not None:
        plugin.with_mesh(mesh)
    if metrics is not None:
        plugin.with_metrics(metrics)
    return plugin.build()


class TestBuilder:
    def test_requires_input_system(self):
        with pytest.raises(ValueError, match="input system"):
            GGRSPlugin().build()

    def test_setup_spawns_players(self):
        app = build_box_app(num_players=3)
        world = app.world()
        assert int(world["alive"].sum()) == 3
        assert sorted(world["rollback_id"][world["alive"]]) == [0, 1, 2]


class TestFixedTimestep:
    def test_accumulator_runs_zero_to_k_steps(self):
        app = build_box_app(fps=60)
        session = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_check_distance(0)
            .start_synctest_session()
        )
        app.insert_session(session, SessionType.SYNC_TEST)
        dt = 1.0 / 60.0
        assert app.update(now=0.0) == 0  # first call only sets last_time
        assert app.update(now=0.5 * dt) == 0  # not enough accumulated
        assert app.update(now=1.6 * dt) == 1
        assert app.update(now=4.6 * dt) == 3  # catches up with 3 steps
        assert app.frame == 4

    def test_run_slow_stretches_period(self):
        app = build_box_app(fps=60)
        app.stage.run_slow = True
        session = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_check_distance(0)
            .start_synctest_session()
        )
        app.insert_session(session, SessionType.SYNC_TEST)
        dt = 1.0 / 60.0
        app.update(now=0.0)
        # 1.05 normal periods < 1.1 stretched periods: no step yet.
        # SyncTest never sets run_slow, so it stays at the forced value.
        assert app.update(now=1.05 * dt) == 0
        assert app.update(now=1.2 * dt) == 1

    def test_reset_on_session_removal(self):
        app = build_box_app()
        session = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .start_synctest_session()
        )
        app.insert_session(session, SessionType.SYNC_TEST)
        app.run_for(5, dt=1.0 / 60.0)
        assert app.stage.accumulator >= 0.0 and app.stage.last_time is not None
        app.remove_session()
        app.update(now=99.0)
        assert app.stage.last_time is None  # reset (`ggrs_stage.rs:155-161`)


class TestSyncTestApp:
    def test_synctest_green(self):
        app = build_box_app(input_fn=scripted)
        session = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_check_distance(4)
            .start_synctest_session()
        )
        app.insert_session(session, SessionType.SYNC_TEST)
        app.run_for(30, dt=1.0 / 60.0)  # raises MismatchedChecksum on desync
        # First update only arms the clock, so 30 render frames yield ~29
        # sim steps (modulo float accumulation).
        assert app.frame >= 27
        assert app.stage.runner.rollbacks_total > 0


class TestP2PApp:
    def _run_two_apps(self, speculation=0, mesh=None):
        net = LoopbackNetwork(latency=2 / 60.0)
        apps = []
        for me in range(2):
            clock = lambda: net.now
            app = build_box_app(input_fn=scripted, clock=clock,
                                max_prediction=8,
                                speculation=speculation if me == 0 else 0,
                                mesh=mesh)
            builder = (
                SessionBuilder(box_game.INPUT_SPEC)
                .with_num_players(2)
                .with_max_prediction_window(8)
            )
            for h in range(2):
                builder.add_player(
                    PlayerType.local() if h == me else PlayerType.remote(("peer", h)),
                    h,
                )
            session = builder.start_p2p_session(
                net.socket(("peer", me)), clock=clock
            )
            app.insert_session(session, SessionType.P2P)
            apps.append(app)

        dt = 1.0 / 60.0
        for i in range(90):
            net.advance(dt)
            for app in apps:
                app.update(now=net.now)

        a, b = apps
        assert a.frame > 40 and b.frame > 40
        assert a.stage.runner.rollbacks_total > 0
        sa, sb = a.session, b.session
        upto = min(sa.confirmed_frame(), sb.confirmed_frame())
        common = [
            f for f in sa._local_checksums
            if f <= upto and f in sb._local_checksums
        ]
        # Lazy checksum reporting: only desync-interval frames are synced
        # to the host and stored (wants_checksum) — all of them must agree.
        assert sa.desync_interval == min(16, sa.max_prediction)  # auto
        assert len(common) >= 2
        assert all(f % sa.desync_interval == 0 for f in common)
        assert all(sa._local_checksums[f] == sb._local_checksums[f] for f in common)
        return apps

    def test_two_apps_over_loopback(self):
        self._run_two_apps()

    def test_two_apps_with_speculation_stay_consistent(self):
        """GGRSStage wiring of with_speculation: app A speculates (stage
        calls runner.speculate with the session each tick), app B runs
        serial — the interval checksums must still agree bitwise, and the
        speculative runner must actually engage."""
        apps = self._run_two_apps(speculation=16)
        runner = apps[0].stage.runner
        assert hasattr(runner, "spec_hits")
        assert runner.rollbacks_total > 0
        # The structured tree + pinning should recover at least something
        # over 90 frames of every-3-frame input changes at 2-frame latency.
        assert runner.spec_hits + runner.spec_partial_hits > 0


class TestMeshedApp:
    def test_with_mesh_shards_session_and_speculation(self):
        """GGRSPlugin.with_mesh threads the mesh through GGRSStage into the
        runner: world entity-sharded, live speculative rollouts branch-
        sharded — and the meshed pair stays bitwise-consistent end to end
        (same helper and assertions as the unmeshed P2P tests)."""
        from bevy_ggrs_tpu.parallel.sharding import branch_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs a 2D mesh")
        mesh = branch_mesh(entity_shards=2)  # branches x entity
        apps = TestP2PApp()._run_two_apps(speculation=8, mesh=mesh)
        runner = apps[0].stage.runner
        assert not runner.state.components[
            "translation"
        ].sharding.is_fully_replicated
        # Live speculation really ran sharded over the mesh's branch axis.
        assert runner._result is not None
        assert not runner._result.checksums.sharding.is_fully_replicated
