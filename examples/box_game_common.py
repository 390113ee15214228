"""Shared wiring for the box_game examples.

Mirrors the reference's shared example module
(`/root/reference/examples/box_game/box_game.rs`): plugin construction with
rollback type registrations, the setup system spawning one rollback-tagged
cube per player, an input system, and the event/stat printing systems the
p2p/spectator binaries install outside the rollback schedule
(`box_game_p2p.rs:107-129`).

Headless: instead of a keyboard, the input system is a deterministic script
(change direction every few frames) or seeded-random stream — the framework
path exercised is identical.
"""

from __future__ import annotations

import argparse
from typing import Optional


def force_platform(platform: Optional[str]) -> None:
    """Select the JAX platform BEFORE first backend use and say which one
    the example runs on. ``None`` is JAX's own choice (the accelerator
    where there is one); a named platform that is not there is an error —
    never a quiet run somewhere else."""
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    dev = jax.devices()[0]
    print(f"[platform] {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}")


import numpy as np  # noqa: E402


def build_app(num_players: int, max_prediction: int, fps: int, input_fn,
              clock=None, speculation: int = 0, metrics=None):
    from bevy_ggrs_tpu.app import GGRSPlugin
    from bevy_ggrs_tpu.models import box_game
    import jax.numpy as jnp

    def setup(world, app):
        # One cube per player on the spawn circle, tagged with a unique
        # rollback id (`box_game.rs:106-130` + RollbackIdProvider).
        box_game.spawn_players(
            world, num_players, next_id=app.rollback_id_provider.next_id
        )

    plugin = (
        GGRSPlugin(box_game.INPUT_SPEC)
        .with_update_frequency(fps)
        .with_input_system(input_fn)
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
    if metrics is not None:
        plugin.with_metrics(metrics)
    return plugin.build()


def scripted_input(handle: int, app) -> np.uint8:
    """Deterministic movement: cycle through UP/RIGHT/DOWN/idle, offset per
    player, switching every 3 simulated frames."""
    from bevy_ggrs_tpu.models import box_game

    keys = [box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0]
    frame = app.session.current_frame if app.session is not None else 0
    return np.uint8(keys[(frame // 3 + handle) % len(keys)])


def print_events_system(app) -> None:
    """`print_events_system` analog (`box_game_p2p.rs:107-111`), upgraded:
    a desync event immediately prints the per-component checksum breakdown
    of the CURRENT state so both sides can diff and name the diverging
    registered type (divergence is non-determinism — it persists, so the
    live state localizes it even after the exact frame left the ring)."""
    from bevy_ggrs_tpu.session.common import EventKind

    for event in app.events:
        print(f"[event] {event.kind.value} addr={event.addr} data={event.data}")
        if event.kind == EventKind.DESYNC_DETECTED:
            # Prefer the ring snapshot of the exact divergent frame (both
            # peers then hash the SAME frame, so only diverging types
            # differ); fall back to the live state when the slot rotated
            # out — divergence persists, but frame-dependent parts will
            # then differ too.
            frame = (event.data or {}).get("frame")
            parts = None
            if frame is not None:
                parts = app.stage.runner.diagnose_frame(frame)
            which = f"frame {frame} snapshot"
            if parts is None:
                from bevy_ggrs_tpu.state import checksum_breakdown

                parts = checksum_breakdown(app.stage.runner.state)
                which = "live state (divergent frame left the ring)"
            print(f"[desync diagnosis] per-part checksums of {which} "
                  "(diff against the other peer's):")
            for name, cs in sorted(parts.items()):
                print(f"  {name}: {cs:#018x}")
    app.events.clear()


def make_stats_system(interval_frames: int = 60):
    """`print_network_stats_system` analog (`box_game_p2p.rs:113-129`)."""
    last = [-1]

    def system(app) -> None:
        f = app.frame
        if f // interval_frames == last[0] or f % interval_frames:
            return
        last[0] = f // interval_frames
        session = app.session
        if session is None or not hasattr(session, "network_stats"):
            return
        if hasattr(session, "remote_player_handles"):
            for h in session.remote_player_handles():
                try:
                    s = session.network_stats(h)
                    print(
                        f"[stats] frame={f} player={h} ping={s.ping_ms:.1f}ms "
                        f"kbps={s.kbps_sent:.1f} queue={s.send_queue_len}"
                    )
                except Exception:
                    pass
        else:
            s = session.network_stats()
            print(
                f"[stats] frame={f} host ping={s.ping_ms:.1f}ms "
                f"kbps={s.kbps_sent:.1f}"
            )

    return system


def print_world(app, label: str) -> None:
    world = app.world()
    t = world["components"]["translation"]
    alive = world["alive"]
    fc = int(world["resources"]["frame_count"])
    print(f"[{label}] frame_count={fc}")
    for i in range(len(alive)):
        if alive[i]:
            print(
                f"  cube {int(world['components']['player_handle'][i])}: "
                f"({t[i][0]:+.3f}, {t[i][1]:+.3f}, {t[i][2]:+.3f})"
            )


def add_common_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--frames", type=int, default=300,
                        help="render frames to run (headless bound)")
    parser.add_argument("--fps", type=int, default=60)
    parser.add_argument("--platform", choices=["cpu", "tpu"], default=None,
                        help="JAX platform to pin (default: JAX's own "
                             "choice); fails if it is not available. A "
                             "chip serves one process: peers sharing a "
                             "machine pass --platform cpu")
    parser.add_argument("--profile", metavar="DIR", default=None,
                        help="capture a JAX/XLA profiler trace of the run "
                             "into DIR (view with TensorBoard)")
    parser.add_argument("--report-metrics", action="store_true",
                        help="collect per-phase timings + rollback-depth "
                             "histograms and print the summary at exit")


class Instruments:
    """Wires --profile / --report-metrics into an app run.

    Usage::

        inst = Instruments(args)
        app = build_app(..., metrics=inst.metrics)
        with inst:
            ... run loop ...
        inst.finish()   # prints the metrics report when enabled
    """

    def __init__(self, args):
        from bevy_ggrs_tpu.utils.metrics import Metrics

        self.profile_dir = getattr(args, "profile", None)
        self.metrics = Metrics() if getattr(args, "report_metrics", False) else None

    def __enter__(self):
        if self.profile_dir:
            import jax

            jax.profiler.start_trace(self.profile_dir)
        return self

    def __exit__(self, *exc):
        if self.profile_dir:
            import jax

            jax.profiler.stop_trace()
            print(f"[profile] trace written to {self.profile_dir}")
        return False

    def finish(self) -> None:
        if self.metrics is not None:
            print("[metrics]")
            print(self.metrics.report())
