#!/usr/bin/env python3
"""The quickest proof that the system still starts, and is right, on the chip.

    python3 chip_smoke.py            # one TPU chip, upstream's sizes

One process holds the chip (``jax.devices()[0]`` — four chips are ROADMAP
R7's business) from start to finish, spawns nothing that needs it, and
drives the two main paths through the entry points a user calls:

- what a player's client runs: two box_game P2P peers built with
  ``GGRSPlugin`` / ``SessionBuilder`` at the ggrs defaults (2 players,
  window 8, 60 fps) over a seeded lossy loopback, speculating 256 branches
  x 8 frames (the ``BASELINE.json`` headline shape);
- what an operator runs: one ``MatchServer`` at capacity 256 hosting 256
  SyncTest matches (every frame a forced rollback and a checksum compare).

Both are held to fresh serial singleton oracles, bitwise. Around them: who
we are running as (identity), whether ``block_until_ready`` can be trusted
as a timer here (timer honesty), and every Pallas kernel, compiled by
Mosaic, against its XLA reference at the sizes the repository claims.

Each phase prints one JSON line (platform, device kind and count, versions,
set-up seconds apart from run seconds, executables compiled vs served from
the persistent cache). A phase that raises is reported with its traceback
and the remaining phases still run, so one chip call shows every failure;
any failed phase makes the exit code 1 and withholds the pass line. The
last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits 2 at once and prints no result.
``--rehearse`` runs every phase at a toy size on whatever backend there is
(kernels interpreted off-TPU), labels each line with that platform, and
never prints the pass line: it is for the CPU tests, not a measurement.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time
import traceback

import numpy as np


@dataclasses.dataclass(frozen=True)
class Size:
    # timer honesty: one long rollout
    timer_boids: int
    timer_frames: int
    timer_reps: int
    # singleton live pair
    pair_branches: int
    pair_frames: int
    # served path
    serve_capacity: int
    serve_groups: int
    serve_frames: int
    serve_churn: int
    # kernels
    checksum_branches: int
    checksum_boids: int
    spec_boids: int
    spec_branches: int
    spec_frames: int
    tri_ns: tuple
    tri_block: int
    grid_n: int
    sync_boids: int
    sync_distance: int
    sync_frames: int
    # in-place ring write: lanes, and the (rows of 128, dtype) of each leaf
    ring_lanes: int
    ring_leaves: tuple


# Upstream's sizes: ggrs SessionBuilder defaults, BASELINE.json configs
# HL and 4, the serve tier at the capacity docs/serving.md quotes, and the
# entity counts BASELINE.md claims for each kernel.
FULL = Size(
    timer_boids=16384, timer_frames=8, timer_reps=7,
    pair_branches=256, pair_frames=600,
    serve_capacity=256, serve_groups=4, serve_frames=300, serve_churn=16,
    checksum_branches=256, checksum_boids=1024,
    spec_boids=1024, spec_branches=128, spec_frames=8,
    tri_ns=(4096, 16384), tri_block=1024, grid_n=32768,
    sync_boids=1024, sync_distance=7, sync_frames=120,
    # particles' position / velocity and ttl / rollback_id under 64 slots
    ring_lanes=64, ring_leaves=((144, "float32"), (72, "int32")),
)
# Same phases, toy sizes: the Pallas interpreter is ~100x slower than
# Mosaic, and this has to fit inside the tier-1 test budget.
REHEARSAL = Size(
    timer_boids=128, timer_frames=2, timer_reps=3,
    pair_branches=8, pair_frames=60,
    serve_capacity=8, serve_groups=2, serve_frames=24, serve_churn=2,
    checksum_branches=4, checksum_boids=64,
    spec_boids=64, spec_branches=4, spec_frames=2,
    tri_ns=(256,), tri_block=128, grid_n=512,
    sync_boids=64, sync_distance=3, sync_frames=12,
    ring_lanes=4, ring_leaves=((64, "float32"), (64, "int32")),
)

WINDOW = 8  # ggrs SessionBuilder default max prediction
FPS = 60
PLAYERS = 2
DT = 1.0 / FPS


class Failed(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def _tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


class Compiles:
    """Executables obtained since construction, split by where they came
    from (utils/xla_cache.py): ``built`` is every jit cache miss,
    ``cache_misses`` the ones the backend really compiled."""

    def __init__(self):
        from bevy_ggrs_tpu.utils import xla_cache

        self._cache = xla_cache
        self._base = xla_cache.compile_counters()
        self._events = len(xla_cache.compile_events())

    def delta(self) -> dict:
        now = self._cache.compile_counters()
        return {
            "built": now["backend_compiles"] - self._base["backend_compiles"],
            "cache_hits": now["cache_hits"] - self._base["cache_hits"],
            "cache_misses": now["cache_misses"] - self._base["cache_misses"],
        }

    def slowest(self, n: int = 3) -> list:
        """The executables that took longest to obtain, and how: the big
        programs (fused tick, batched tick) and whether the persistent
        cache served them."""
        events = self._cache.compile_events()[self._events:]
        return [
            {"name": e["fingerprint"], "seconds": round(e["ms"] / 1e3, 3),
             "cache": e["cache"]}
            for e in sorted(events, key=lambda ev: -ev["ms"])[:n]
        ]


# ---------------------------------------------------------------------------
# identity
# ---------------------------------------------------------------------------


def phase_identity(size: Size, ident: dict) -> dict:
    from bevy_ggrs_tpu.native import build, core
    from bevy_ggrs_tpu.ops.interpret import pallas_interpret
    from bevy_ggrs_tpu.utils import xla_cache

    lib = build.lib_path(build.CORE_SRC)
    reused = os.path.exists(lib)
    t0 = time.perf_counter()
    require(core.available(), "native data plane is not loaded "
            "(GGRS_NO_NATIVE / BEVY_GGRS_TPU_NATIVE set?)")
    require(os.path.exists(lib), f"native core loaded but {lib} is absent")
    interpret = pallas_interpret()
    require(interpret == (ident["platform"] != "tpu"),
            f"Pallas interpret mode resolved to {interpret} on "
            f"{ident['platform']}")
    return {
        "native_core": os.path.basename(lib),
        "native_core_built_this_run": not reused,
        "native_core_seconds": round(time.perf_counter() - t0, 3),
        "cache_dir": xla_cache.ensure_persistent_compilation_cache(),
        "cache_dir_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        "pallas_interpret": interpret,
    }


# ---------------------------------------------------------------------------
# timer honesty
# ---------------------------------------------------------------------------


def phase_timer_honesty(size: Size, ident: dict) -> dict:
    """Wall time of one known-long program ending in block_until_ready
    against the same ending in a value-forcing host read: the former
    was once observed returning early, and every host-clock metric of
    the benchmark needs to know whether it does here."""
    import jax
    import jax.numpy as jnp

    from bevy_ggrs_tpu.models import boids
    from bevy_ggrs_tpu.parallel.speculate import (
        SpeculativeExecutor,
        enumerate_branches,
    )

    t0 = time.perf_counter()
    compiles = Compiles()
    ex = SpeculativeExecutor(
        boids.make_schedule(kernel="mxu"), 1, size.timer_frames
    )
    state = boids.make_world(size.timer_boids, PLAYERS).commit()
    bits = enumerate_branches(
        jax.random.PRNGKey(ident["seed"]), jnp.zeros((PLAYERS,), jnp.uint8),
        1, size.timer_frames,
    )

    def forced(res) -> int:
        return int(np.asarray(jnp.sum(res.checksums.astype(jnp.uint32))))

    forced(ex.run(state, 0, bits))  # compile + warm (both programs)
    jax.block_until_ready(ex.run(state, 0, bits).checksums)
    setup_s = time.perf_counter() - t0

    t_run = time.perf_counter()
    blocked_ms, forced_ms = [], []
    for _ in range(size.timer_reps):
        t = time.perf_counter()
        jax.block_until_ready(ex.run(state, 0, bits).checksums)
        blocked_ms.append((time.perf_counter() - t) * 1e3)
        t = time.perf_counter()
        forced(ex.run(state, 0, bits))
        forced_ms.append((time.perf_counter() - t) * 1e3)
    blocked, read = float(np.median(blocked_ms)), float(np.median(forced_ms))
    out = {
        "program": f"boids_{size.timer_boids}_{size.timer_frames}f_x_1b",
        "blocked_ms": blocked,
        "forced_read_ms": read,
        "blocked_over_forced": blocked / read,
        "block_until_ready_honest": blocked >= 0.5 * read,
        "reps": size.timer_reps,
        "setup_s": round(setup_s, 3),
        "run_s": round(time.perf_counter() - t_run, 3),
        "setup_compiles": compiles.delta(),
    }
    require(out["block_until_ready_honest"],
            f"block_until_ready returned in {blocked:.3f} ms, under half "
            f"the {read:.3f} ms a value-forcing read takes")
    return out


# ---------------------------------------------------------------------------
# singleton live pair
# ---------------------------------------------------------------------------


def _scripted_keys():
    from bevy_ggrs_tpu.models import box_game

    return [box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0]


def _scripted_bits(frame: int, handle: int) -> np.uint8:
    keys = _scripted_keys()
    return np.uint8(keys[(frame // 3 + handle) % len(keys)])


def _box_app(speculation: int, clock=None):
    """A box_game app exactly as examples/box_game_common.py builds one."""
    import jax.numpy as jnp

    from bevy_ggrs_tpu.app import GGRSPlugin
    from bevy_ggrs_tpu.models import box_game

    def setup(world, app):
        box_game.spawn_players(
            world, PLAYERS, next_id=app.rollback_id_provider.next_id
        )

    def input_system(handle, app):
        return _scripted_bits(app.session.current_frame, handle)

    plugin = (
        GGRSPlugin(box_game.INPUT_SPEC)
        .with_update_frequency(FPS)
        .with_input_system(input_system)
        .register_rollback_component("translation", shape=(3,),
                                     dtype=jnp.float32)
        .register_rollback_component("velocity", shape=(3,),
                                     dtype=jnp.float32)
        .register_rollback_component("player_handle", dtype=jnp.int32,
                                     default=-1)
        .register_rollback_resource("frame_count", jnp.uint32(0))
        .with_rollback_schedule(box_game.make_schedule())
        .with_num_players(PLAYERS)
        .with_max_prediction_window(WINDOW)
        .with_world_capacity(16)
        .with_setup_system(setup)
    )
    if clock is not None:
        plugin.with_clock(clock)
    if speculation:
        plugin.with_speculation(speculation)
    return plugin.build()


def phase_singleton_pair(size: Size, ident: dict) -> dict:
    from bevy_ggrs_tpu.app import SessionType
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.schedule import CONFIRMED
    from bevy_ggrs_tpu.session import PlayerType, SessionBuilder
    from bevy_ggrs_tpu.session.common import EventKind
    from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
    from bevy_ggrs_tpu.state import checksum, combine64, ring_load
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
    from bevy_ggrs_tpu.utils.metrics import Metrics

    t0 = time.perf_counter()
    compiles = Compiles()
    # The loopback profile of ``benchmark/traffic/wan.json``: latency
    # 2 frames, jitter 1, loss 3 %; seed 5.
    net = LoopbackNetwork(latency=2 * DT, jitter=1 * DT, loss=0.03, seed=5)
    clock = lambda: net.now  # noqa: E731
    metrics = Metrics()
    apps = []
    for me in range(2):
        # Peer 0 speculates; peer 1 resimulates serially, so every checksum
        # the two exchange also compares the speculating executable
        # against the serial one.
        app = _box_app(size.pair_branches if me == 0 else 0, clock)
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(PLAYERS)
            .with_max_prediction_window(WINDOW)
            .with_fps(FPS)
        )
        for h in range(PLAYERS):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(("peer", h)),
                h,
            )
        session = builder.start_p2p_session(
            net.socket(("peer", me)), clock=clock,
            metrics=metrics if me == 0 else None,
        )
        app.insert_session(session, SessionType.P2P)
        apps.append(app)
    oracle = _box_app(0).stage.runner  # fresh, serial, warmed
    a, b = apps
    runner = a.stage.runner
    attestation = runner.attestation
    require(attestation is not None and attestation.ok,
            f"box_game speculation failed attestation: {attestation}")
    require(runner.speculation_enabled, "speculation was switched off")
    require((runner.num_branches, runner.spec_frames)
            == (size.pair_branches, WINDOW),
            "speculation shape is not the one asked for")
    setup_s = time.perf_counter() - t0
    setup_compiles = compiles.delta()
    slowest = compiles.slowest()

    t_run = time.perf_counter()
    compiles = Compiles()
    ticks = 0
    while min(a.frame, b.frame) < size.pair_frames:
        require(ticks < 4 * size.pair_frames,
                f"peers stalled at frames {a.frame}/{b.frame}")
        net.advance(DT)
        for app in apps:
            app.update(now=net.now)
        ticks += 1
    # Let every input in flight land, then give peer 0 one more step: it
    # applies any correction still pending, after which its snapshot of
    # frame ``confirmed + 1`` rests on confirmed inputs only.
    for _ in range(4 * FPS):
        net.advance(DT)
        for app in apps:
            flush = getattr(app.stage.runner, "flush_reports", None)
            if flush is not None:
                flush(app.session)
            app.session.poll_remote_clients(net.now)
            app.events.extend(app.session.events())
        if a.session.confirmed_frame() >= min(a.frame, b.frame) - 1:
            break
    a.stage.last_time = net.now  # exactly one step, however long the drain
    net.advance(DT)
    a.update(now=net.now)
    run_s = time.perf_counter() - t_run
    run_compiles = compiles.delta()

    desyncs = sum(
        1 for app in apps for ev in app.events
        if ev.kind == EventKind.DESYNC_DETECTED
    )
    upto = a.session.confirmed_frame() + 1
    ring_frames = np.asarray(runner.ring.frames)
    require(upto in ring_frames,
            f"frame {upto} is no longer in peer 0's ring {ring_frames}")

    # Serial replay of the confirmed inputs (input delay 0: the confirmed
    # input of a frame is the script's value for that frame).
    status = np.full((PLAYERS,), CONFIRMED, np.int32)
    for f in range(upto):
        bits = np.asarray([_scripted_bits(f, h) for h in range(PLAYERS)],
                          np.uint8)
        oracle.handle_requests(
            [SaveGameState(f), AdvanceFrame(bits=bits, status=status)]
        )
    live_state = ring_load(runner.ring, upto)
    state_bitwise = _tree_equal(live_state, oracle.state)
    live_cs = {int(f): combine64(c) for f, c in
               zip(ring_frames, np.asarray(runner.ring.checksums))}
    oracle_cs = {int(f): combine64(c) for f, c in
                 zip(np.asarray(oracle.ring.frames),
                     np.asarray(oracle.ring.checksums))}
    oracle_cs[upto] = combine64(checksum(oracle.state))
    shared = sorted(f for f in live_cs if 0 <= f <= upto and f in oracle_cs)
    ring_bitwise = bool(shared) and all(
        live_cs[f] == oracle_cs[f] for f in shared
    )

    out = {
        "shape": f"box_game_{PLAYERS}p_w{WINDOW}_{size.pair_branches}b_x_"
                 f"{runner.spec_frames}f",
        "frames": [a.frame, b.frame],
        "ticks": ticks,
        "rollbacks": runner.rollbacks_total,
        "resimulated_frames": runner.rollback_frames_total,
        "spec_hits": runner.spec_hits,
        "spec_partial_hits": runner.spec_partial_hits,
        "spec_misses": runner.spec_misses,
        "frames_recovered": runner.rollback_frames_recovered_total,
        "attestation": dataclasses.asdict(attestation),
        "speculation_enabled": runner.speculation_enabled,
        "desync_events": desyncs,
        "checksums_compared": int(metrics.counters.get("checksum_ballots", 0)),
        "oracle_frame": upto,
        "oracle_state_bitwise": state_bitwise,
        "oracle_ring_frames_compared": len(shared),
        "oracle_ring_bitwise": ring_bitwise,
        "datagrams_sent": net.sent,
        "datagrams_dropped": net.dropped,
        "setup_s": round(setup_s, 3),
        "run_s": round(run_s, 3),
        "setup_compiles": setup_compiles,
        "setup_slowest_executables": slowest,
        "compiles_after_warmup": run_compiles["built"],
    }
    require(min(a.frame, b.frame) >= size.pair_frames, "peers fell short")
    require(runner.rollbacks_total > 0, "no rollback happened")
    require(runner.spec_hits + runner.spec_partial_hits > 0,
            "no speculative commit happened")
    require(desyncs == 0, f"{desyncs} desync events")
    require(out["checksums_compared"] > 0, "peers compared no checksum")
    require(state_bitwise, f"peer 0's frame {upto} differs from the serial "
            "replay of its confirmed inputs")
    require(ring_bitwise, "ring checksums differ from the serial replay: "
            f"live {live_cs} oracle {oracle_cs}")
    require(run_compiles["built"] == 0,
            f"{run_compiles['built']} executables built after warmup()")
    return out


# ---------------------------------------------------------------------------
# served path
# ---------------------------------------------------------------------------


def phase_served(size: Size, ident: dict) -> dict:
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.runner import RollbackRunner
    from bevy_ggrs_tpu.serve.server import MatchServer
    from bevy_ggrs_tpu.session import SessionBuilder
    from bevy_ggrs_tpu.utils.metrics import Metrics

    t0 = time.perf_counter()
    compiles = Compiles()
    check_distance = 2
    schedule = box_game.make_schedule()
    initial = box_game.make_world(PLAYERS).commit()
    metrics = Metrics()
    server = MatchServer(
        schedule, initial, WINDOW, PLAYERS, box_game.INPUT_SPEC,
        capacity=size.serve_capacity, stagger_groups=size.serve_groups,
        num_branches=8, spec_frames=8, metrics=metrics,
    )
    server.warmup()

    def make_session():
        return (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(PLAYERS)
            .with_max_prediction_window(WINDOW)
            .with_check_distance(check_distance)
            .start_synctest_session()
        )

    rng = np.random.RandomState(ident["seed"])
    offsets = rng.randint(0, 16, size=size.serve_capacity + size.serve_churn)

    def inputs_for(k):
        def f(frame, handle):
            return np.uint8((frame * 3 + handle * 5 + int(offsets[k])) % 16)

        return f

    handles = [
        server.add_match(make_session(), inputs_for(k))
        for k in range(size.serve_capacity)
    ]
    # The serial oracles' executable compiles here, not in the run window.
    oracle_probe = RollbackRunner(schedule, initial, WINDOW, PLAYERS,
                                  box_game.INPUT_SPEC)
    oracle_probe.warmup()
    setup_s = time.perf_counter() - t0
    setup_compiles = compiles.delta()
    slowest = compiles.slowest()

    t_run = time.perf_counter()
    compiles = Compiles()
    for _ in range(size.serve_frames):
        server.run_frame()
    # Churn: retire some, admit as many new ones through the queued path.
    retired = handles[:size.serve_churn]
    for h in retired:
        server.retire_match(h)
    joined = [
        server.enqueue_match(make_session(),
                             inputs_for(size.serve_capacity + i))
        for i in range(size.serve_churn)
    ]
    churn_frames = 0
    while server.admissions_completed < size.serve_capacity + size.serve_churn:
        require(churn_frames < 10 * size.serve_churn + 10,
                "queued admissions never completed")
        server.run_frame()
        churn_frames += 1
    tail = 2 * WINDOW
    for _ in range(tail):
        server.run_frame()
    churn_frames += tail
    run_s = time.perf_counter() - t_run
    churn_recompiles = compiles.delta()["built"]

    def slot_frame(h):
        return server.groups[h.group].slots[h.slot].frame

    survivors = handles[size.serve_churn:]
    total = size.serve_frames + churn_frames
    frames_ok = all(slot_frame(h) == total for h in survivors)
    joined_frames = [slot_frame(h) for h in joined]

    # Bitwise parity of sampled slots against fresh serial singletons —
    # the only thing that says the [S, B]-vmapped executable agrees with
    # the serial one on this backend (serve/batch.py attests nothing).
    sample = {
        len(handles) - 1: handles[-1],
        (len(handles) + size.serve_churn) // 2:
            handles[(len(handles) + size.serve_churn) // 2],
        size.serve_churn: handles[size.serve_churn],
        size.serve_capacity: joined[0],
    }
    mismatched = []
    for k, h in sorted(sample.items()):
        session = make_session()
        oracle = RollbackRunner(schedule, initial, WINDOW, PLAYERS,
                                box_game.INPUT_SPEC)
        feed = inputs_for(k)
        for _ in range(slot_frame(h)):
            for p in session.local_player_handles():
                session.add_local_input(p, feed(session.current_frame, p))
            oracle.handle_requests(session.advance_frame(), session)
        core = server.groups[h.group]
        same = (
            slot_frame(h) == oracle.frame
            and _tree_equal(core.slot_state(h.slot), oracle.state)
            and np.array_equal(np.asarray(core.rings.frames)[h.slot],
                               np.asarray(oracle.ring.frames))
            and np.array_equal(np.asarray(core.rings.checksums)[h.slot],
                               np.asarray(oracle.ring.checksums))
        )
        if not same:
            mismatched.append(k)

    out = {
        "shape": f"box_game_S{server.capacity}_G{size.serve_groups}_w"
                 f"{WINDOW}_8b_x_8f_synctest_d{check_distance}",
        "matches": len(handles),
        "frames_served": server.frames_served,
        "survivor_frames_each": total,
        "all_survivors_advanced": frames_ok,
        "readmitted": len(joined),
        "readmitted_frames": [min(joined_frames), max(joined_frames)],
        "admissions_completed": server.admissions_completed,
        "slot_faults": server.faults_total,
        "quarantined": server.slots_quarantined + server.slots_recovering,
        "evictions": server.evictions_total,
        "oracle_slots": sorted(sample),
        "oracle_mismatches": mismatched,
        "churn_recompiles": churn_recompiles,
        "setup_s": round(setup_s, 3),
        "run_s": round(run_s, 3),
        "run_frame_ms_mean": 1e3 * run_s / (size.serve_frames + churn_frames),
        # The server's own host-clock timers over the run: a group's whole
        # tick, the enqueue of its batched program, and the one
        # device->host wait (the deferred checksum read).
        "host_timers_ms": {
            name: {"count": len(vals), "total": sum(vals),
                   "median": float(np.median(vals))}
            for name in ("serve_tick_ms", "serve_dispatch_ms",
                         "checksum_sync_ms")
            if (vals := metrics.series.get(name))
        },
        "setup_compiles": setup_compiles,
        "setup_slowest_executables": slowest,
    }
    require(frames_ok, "a match did not advance every frame")
    require(min(joined_frames) >= tail, "a re-admitted match did not run")
    require(server.faults_total == 0 and out["quarantined"] == 0
            and server.evictions_total == 0,
            "slot faults / quarantines / evictions happened (a SyncTest "
            "mismatch faults its slot)")
    require(not mismatched,
            f"slots {mismatched} differ from their serial oracles")
    require(churn_recompiles == 0,
            f"{churn_recompiles} executables built after warmup()")
    return out


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _flock(n: int):
    """The model's own world at ``n`` boids (``boids.make_world``: the
    state every boids bench config starts from), every seventh boid
    switched off as tests/test_ops.py does. Not a uniform random cloud:
    the MXU kernels carry separation as ``p_i * sum(w) - sum(w * p_j)``,
    whose cancellation error grows with 1/d, and only a flock that has
    never felt its own separation force has pairs at d ~ 1e-3."""
    import jax.numpy as jnp

    from bevy_ggrs_tpu.models import boids

    state = boids.make_world(n, PLAYERS).commit()
    active = np.ones((n,), np.float32)
    active[::7] = 0.0
    return (state.components["position"], state.components["velocity"],
            jnp.asarray(active))


def _dense_reference(pos, vel, active, rows: int = 2048):
    """The XLA all-pairs force, row block by row block (the [R, N, 2]
    intermediates of a 16k flock do not fit in one piece)."""
    import jax

    from bevy_ggrs_tpu.models import boids

    block = jax.jit(boids.pairwise_force_rows)
    n = pos.shape[0]
    return np.concatenate([
        np.asarray(block(pos[r:r + rows], vel[r:r + rows], pos, vel,
                         active[r:r + rows], active))
        for r in range(0, n, rows)
    ])


def _uses_mosaic(fn, *args) -> bool:
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _kernel_checks(size: Size, ident: dict):
    """(name, thunk) per kernel; each thunk returns a dict and raises
    Failed when its kernel and its reference disagree."""
    import jax
    import jax.numpy as jnp

    from bevy_ggrs_tpu.models import boids, box_game
    from bevy_ggrs_tpu.ops.checksum import checksum_pallas
    from bevy_ggrs_tpu.ops.pairwise import (
        pairwise_force_rows_mxu2,
        pairwise_force_square_mxu_tri,
    )
    from bevy_ggrs_tpu.state import checksum

    mosaic = ident["platform"] == "tpu"
    params = boids._kernel_params()
    seed = ident["seed"]

    def compiled(fn, *args) -> dict:
        got = _uses_mosaic(fn, *args)
        require(got == mosaic,
                f"lowered with{'' if got else 'out'} a Mosaic custom call "
                f"on {ident['platform']}")
        return {"mosaic": got}

    def close(got, want, atol, label) -> dict:
        got, want = np.asarray(got), np.asarray(want)
        require(got.shape == want.shape, f"{label}: shape {got.shape}")
        require(bool(np.isfinite(got).all()), f"{label}: not finite")
        err = float(np.abs(got - want).max())
        require(err <= atol, f"{label}: max |err| {err:.3e} > atol {atol:.3e}")
        return {"max_abs_err": err, "atol": atol}

    def checksum_box_game():
        b = size.checksum_branches
        base = box_game.make_world(PLAYERS).commit()
        shift = jnp.arange(b, dtype=jnp.float32)[:, None, None] * 0.25
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (b,) + x.shape), base
        )
        stacked = stacked.replace(components={
            **stacked.components,
            "translation": stacked.components["translation"] + shift,
        })
        fn = jax.vmap(checksum_pallas)
        out = compiled(fn, stacked)
        got = np.asarray(jax.jit(fn)(stacked))
        want = np.asarray(jax.jit(jax.vmap(checksum))(stacked))
        require(len({tuple(r) for r in want}) == b, "reference collides")
        require(np.array_equal(got, want), "checksum differs from XLA")
        return {**out, "branches": b, "bitwise": True}

    def checksum_boids():
        state = boids.make_world(size.checksum_boids, PLAYERS).commit()
        out = compiled(checksum_pallas, state)
        got = np.asarray(jax.jit(checksum_pallas)(state))
        require(np.array_equal(got, np.asarray(jax.jit(checksum)(state))),
                "checksum differs from XLA")
        return {**out, "boids": size.checksum_boids, "bitwise": True}

    def rows_mxu():
        pos, vel, act = _flock(size.spec_boids)
        fn = lambda p, v, a: pairwise_force_rows_mxu2(  # noqa: E731
            p, v, p, v, a, a, **params)
        out = compiled(fn, pos, vel, act)
        want = _dense_reference(pos, vel, act)
        atol = max(1e-3 * float(np.abs(want).max()), 1e-6)
        out.update(close(fn(pos, vel, act), want, atol, "rows_mxu2"))
        return {**out, "n": size.spec_boids}

    def spec_mxu():
        # BASELINE.json config 4: the MXU kernel under the speculative
        # executor, and the runner's own verdict on it.
        from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner

        runner = SpeculativeRollbackRunner(
            boids.make_schedule(kernel="mxu"),
            boids.make_world(size.spec_boids, PLAYERS).commit(),
            max_prediction=max(WINDOW, size.spec_frames),
            num_players=PLAYERS, input_spec=boids.INPUT_SPEC,
            num_branches=size.spec_branches, spec_frames=size.spec_frames,
        )
        runner.warmup()
        att = runner.attestation
        require(att is not None and att.ok and runner.speculation_enabled,
                f"boids-mxu speculation failed attestation: {att}")
        return {"shape": f"boids_{size.spec_boids}_{size.spec_frames}f_x_"
                         f"{size.spec_branches}b_mxu",
                "attestation": dataclasses.asdict(att)}

    def tri(n):
        def check():
            pos, vel, act = _flock(n)
            fn = lambda p, v, a: pairwise_force_square_mxu_tri(  # noqa: E731
                p, v, a, block=size.tri_block, **params)
            out = compiled(fn, pos, vel, act)
            want = _dense_reference(pos, vel, act)
            atol = max(1e-3 * float(np.abs(want).max()), 1e-6)
            got = fn(pos, vel, act)
            out.update(close(got, want, atol, f"tri_{n}"))
            require(np.array_equal(np.asarray(got),
                                   np.asarray(fn(pos, vel, act))),
                    "two runs differ")
            return {**out, "n": n}

        return check

    def grid():
        # The neighbour grid through the model's entry point, per-cell
        # compute in the Pallas cell kernel vs in XLA (both grid mode).
        from bevy_ggrs_tpu.schedule import make_inputs

        from bevy_ggrs_tpu.ops import neighbor

        n = size.grid_n
        cfg = boids.grid_config(n)
        state = boids.make_world(n, PLAYERS).commit()
        inputs = make_inputs(jnp.asarray([boids.INPUT_RIGHT, 0], jnp.uint8))
        pallas_step = boids.make_schedule(kernel="mxu", mode="grid")
        out = compiled(pallas_step, state, inputs)
        got = jax.jit(pallas_step)(state, inputs)
        want = jax.jit(boids.make_schedule(kernel="xla", mode="grid"))(
            state, inputs)
        for name in ("velocity", "position"):
            out[name] = close(got.components[name], want.components[name],
                              1e-5, f"grid {name}")
        moved = float(np.abs(
            np.asarray(got.components["velocity"])
            - np.asarray(state.components["velocity"])).max())
        require(moved > 0.0, "the step changed no velocity")

        # The force itself (the step's clamp and add round most of a
        # kernel's error away), at tests/test_neighbor.py's tolerance:
        # against the same grid in XLA, and against all pairs.
        pos, vel = state.components["position"], state.components["velocity"]
        active = state.alive & state.present["position"]

        def force(impl):
            return jax.jit(lambda p, v, a: neighbor.interact(
                p, a, boids.FLOCK_PAIR_KERNEL,
                feats={"vx": v[:, 0], "vy": v[:, 1]},
                mode="grid", config=cfg, impl=impl))(pos, vel, active)

        kernel_force = force("pallas")
        out["force_vs_xla_grid"] = close(
            kernel_force, force("xla"), 1e-5, "grid force vs XLA grid")
        out["force_vs_dense"] = close(
            kernel_force,
            _dense_reference(pos, vel, active.astype(jnp.float32)),
            1e-5, "grid force vs all pairs")
        return {**out, "n": n, "cells": cfg.num_cells,
                "cell_capacity": cfg.cell_capacity,
                "padded_cols": cfg.padded_cols}

    def synctest_mxu():
        from bevy_ggrs_tpu.runner import RollbackRunner
        from bevy_ggrs_tpu.session import SessionBuilder

        window = max(WINDOW, size.sync_distance)
        session = (
            SessionBuilder(boids.INPUT_SPEC)
            .with_num_players(PLAYERS)
            .with_max_prediction_window(window)
            .with_check_distance(size.sync_distance)
            .start_synctest_session()
        )
        runner = RollbackRunner(
            boids.make_schedule(kernel="mxu"),
            boids.make_world(size.sync_boids, PLAYERS).commit(),
            max_prediction=window, num_players=PLAYERS,
            input_spec=boids.INPUT_SPEC,
        )
        rng = np.random.RandomState(seed)
        for _ in range(size.sync_frames):
            for h in range(PLAYERS):
                session.add_local_input(h, np.uint8(rng.randint(0, 16)))
            # A mismatch raises MismatchedChecksum out of here.
            runner.handle_requests(session.advance_frame(), session)
        require(runner.rollbacks_total
                == size.sync_frames - size.sync_distance,
                f"{runner.rollbacks_total} forced rollbacks")
        return {"boids": size.sync_boids,
                "check_distance": size.sync_distance,
                "frames": runner.frame,
                "rollbacks": runner.rollbacks_total, "mismatches": 0}

    def ring_write():
        """``state.ring_row_write`` under a ``vmap`` that batches index and
        mask, on rings whose rows are whole lane tiles (the in-place copy
        of ``ops/ring_write.py``), against the select over the same ring
        with its rows flat: bit for bit, three saves in a loop."""
        from bevy_ggrs_tpu.state import (
            in_place_writes, ring_row_read, ring_row_write,
        )

        depth, lanes = WINDOW + 1, size.ring_lanes
        rng = np.random.RandomState(seed)
        slot = jnp.asarray(rng.randint(-depth, 2 * depth, lanes), jnp.int32)
        valid = jnp.asarray(rng.randint(0, 3, lanes) > 0)  # a mixed mask
        out = {"lanes": lanes, "saving_lanes": int(valid.sum()), "leaves": []}

        def burst(ring, rows, slot, valid):
            def step(t, ring):
                return ring_row_write(ring, ring_row_read(rows, t),
                                      slot + t, valid)

            return jax.lax.fori_loop(0, 3, step, ring)

        for r, dtype in size.ring_leaves:
            bits = lambda *shape: jnp.asarray(rng.randint(   # noqa: E731
                0, 2 ** 32, size=shape, dtype=np.uint64
            ).astype(np.uint32).view(dtype))
            ring, rows = bits(lanes, depth, r, 128), bits(lanes, 3, r, 128)
            args = (ring, rows, slot, valid)
            flat = (ring.reshape(lanes, depth, -1),
                    rows.reshape(lanes, 3, -1), slot, valid)
            visits = in_place_writes[0]
            got = compiled(jax.vmap(burst), *args)
            require(in_place_writes[0] > visits,
                    "the tiled ring's write is not the in-place copy")
            require(not _uses_mosaic(jax.vmap(burst), *flat),
                    "the flat ring's write is no select")
            same = np.array_equal(
                np.asarray(jax.jit(jax.vmap(burst))(*args)).view(np.uint32),
                np.asarray(jax.jit(jax.vmap(burst))(*flat)).view(
                    np.uint32).reshape(ring.shape))
            require(same, f"{dtype}[{lanes}, {depth}, {r}, 128]: the copy "
                          "differs from the select")
            out["leaves"].append({"shape": list(ring.shape), "dtype": dtype,
                                  "bitwise": same, **got})
        out["mosaic"] = all(leaf["mosaic"] for leaf in out["leaves"])
        return out

    return [
        ("ring_write_in_place", ring_write),
        ("checksum_box_game_vmapped", checksum_box_game),
        ("checksum_boids", checksum_boids),
        ("pairwise_rows_mxu2", rows_mxu),
        ("spec_executor_mxu", spec_mxu),
        *[(f"pairwise_tri_{n}", tri(n)) for n in size.tri_ns],
        ("grid_cell_kernel", grid),
        ("synctest_boids_mxu", synctest_mxu),
    ]


def phase_kernels(size: Size, ident: dict) -> dict:
    results, failed = {}, []
    for name, check in _kernel_checks(size, ident):
        t0 = time.perf_counter()
        compiles = Compiles()
        try:
            res = {"ok": True, **check()}
        except Exception as exc:  # reported below; the phase fails
            traceback.print_exc()
            res = {"ok": False, "error": f"{type(exc).__name__}: {exc}"[:600]}
            failed.append(name)
        res["seconds"] = round(time.perf_counter() - t0, 3)
        res["compiles"] = compiles.delta()
        results[name] = res
    return {"ok": not failed, "failed": failed, "kernels": results}


# ---------------------------------------------------------------------------


PHASES = [
    ("identity", phase_identity),
    ("timer_honesty", phase_timer_honesty),
    ("singleton_pair", phase_singleton_pair),
    ("served", phase_served),
    ("kernels", phase_kernels),
]


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes on any backend; never passes")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed for the generated inputs and flocks")
    args = parser.parse_args(argv)

    import jax

    import bevy_ggrs_tpu
    from bevy_ggrs_tpu.utils import xla_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    xla_cache.ensure_persistent_compilation_cache()
    xla_cache.install_compile_listeners()
    ident = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "versions": {
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "jaxlib": _version("jaxlib"),
            "libtpu": _version("libtpu"),
            "numpy": np.__version__,
            "flax": _version("flax"),
            "bevy_ggrs_tpu": bevy_ggrs_tpu.__version__,
        },
        "size": "rehearsal" if args.rehearse else "full",
        "seed": args.seed,
    }
    size = REHEARSAL if args.rehearse else FULL

    t_all = time.perf_counter()
    failed = []
    for name, phase in PHASES:
        t0 = time.perf_counter()
        try:
            result = {"ok": True, **phase(size, ident)}
        except Exception as exc:  # every phase reports; the run then fails
            traceback.print_exc()
            result = {"ok": False,
                      "error": f"{type(exc).__name__}: {exc}"[:2000]}
        if not result["ok"]:
            failed.append(name)
        result["seconds"] = round(time.perf_counter() - t0, 3)
        print(json.dumps({"phase": name, **ident, **result}), flush=True)

    total = round(time.perf_counter() - t_all, 3)
    if failed:
        print(f"chip_smoke: FAILED phases {failed} after {total} s",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "platform": dev.platform,
                          "seconds": total}))
        return 0
    print(json.dumps({"phase": "summary", "seconds": total,
                      "compile_cache": xla_cache.compile_counters()}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
