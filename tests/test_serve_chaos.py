"""MatchServer under chaos: P2P matches served from batch slots while the
network misbehaves and the server process itself is killed and restarted.

Three layers:

- :class:`ServerKillRestart` plan plumbing — generation, JSON roundtrip,
  seed-replayability (the serve-tier failure script is one artifact).
- A non-slow smoke: a small server hosting peer-0 of real P2P matches over
  the loopback transport is kill -9'd mid-match and restarted from its
  periodic checkpoint; every match rejoins through the supervisor's
  crash-restart path and converges bitwise with its surviving peer.
- The slow acceptance soak (S=16): loss/reorder/duplicate/corrupt windows,
  an asymmetric partition, one external-peer kill/restart AND one server
  kill/restart — zero desyncs, bounded recovery, no evictions, and one
  match's full confirmed-input log replayed serially from scratch must
  reproduce the recorded checksums bitwise.

KillRestart-family directives are executed at the HARNESS level (a socket
can't kill a process) — the same contract as tests/test_chaos_soak.py.
"""

import os

import numpy as np
import pytest

from bevy_ggrs_tpu.chaos import (
    ChaosPlan,
    ChaosSocket,
    CheckpointCorrupt,
    Corrupt,
    Duplicate,
    KillRestart,
    LossBurst,
    Partition,
    Reorder,
    ServerKillRestart,
    SnapshotCorrupt,
)
from bevy_ggrs_tpu import integrity
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.obs import (
    FlightRecorder,
    ProvenanceLog,
    SidecarSocket,
    SpanTracer,
    SpeculationLedger,
    frame_flows,
    merge_traces,
)
from bevy_ggrs_tpu.relay import RelayServer, RelaySocket, peer_addr
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.serve import MatchServer, SlotHealth
from bevy_ggrs_tpu.session import (
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session.requests import AdvanceFrame, SaveGameState
from bevy_ggrs_tpu.session.supervisor import Health, SessionSupervisor
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_p2p import FPS_DT, scripted_input
from tests.test_supervisor import settled_checksums

MAX_PRED = 8
BRANCHES = 8
SPEC_FRAMES = 3


# ---------------------------------------------------------------------------
# ServerKillRestart: plan plumbing
# ---------------------------------------------------------------------------


def test_server_kill_restart_generated_and_replayable():
    peers = (("peer", 0), ("peer", 1))
    plan = ChaosPlan.generate(
        41, 30.0, peers, kill_restart=True, relay=("relay", 0),
        match_server=("srv", 0),
    )
    skrs = plan.server_kill_restarts()
    assert len(skrs) == 1
    (skr,) = skrs
    assert skr.server == ("srv", 0)
    # Late in the run, layered onto the network-fault windows.
    assert 0.55 * 30.0 <= skr.at <= 0.75 * 30.0
    assert skr.down_for > 0
    assert plan.horizon() >= skr.at + skr.down_for
    # Same arguments -> the identical plan, always (seed replay).
    again = ChaosPlan.generate(
        41, 30.0, peers, kill_restart=True, relay=("relay", 0),
        match_server=("srv", 0),
    )
    assert again == plan
    # Leaving the server out never perturbs the rest of the schedule.
    without = ChaosPlan.generate(
        41, 30.0, peers, kill_restart=True, relay=("relay", 0)
    )
    assert without.directives == plan.directives[:-1]


def test_server_kill_restart_json_roundtrip():
    plan = ChaosPlan(
        7,
        (
            LossBurst(1.0, 2.0, 0.2),
            ServerKillRestart(5.0, ("srv", 3), 1.5),
            KillRestart(3.0, ("ext", 0), 1.0),
        ),
    )
    back = ChaosPlan.from_json(plan.to_json())
    assert back == plan  # tuple addresses normalized back from JSON lists
    assert back.server_kill_restarts()[0].server == ("srv", 3)


# ---------------------------------------------------------------------------
# Served-P2P harness
# ---------------------------------------------------------------------------


def server_inputs(frame, handle):
    return scripted_input(handle, frame)


def build_server(ckpt_dir, capacity, groups, net, metrics, tracer=None,
                 ledger=None):
    server = MatchServer(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        MAX_PRED, 2, box_game.INPUT_SPEC,
        capacity=capacity, stagger_groups=groups,
        num_branches=BRANCHES, spec_frames=SPEC_FRAMES,
        metrics=metrics, clock=lambda: net.now, tracer=tracer,
        checkpoint_dir=ckpt_dir, checkpoint_interval=120,
        ledger=ledger,
        # A tight attestation cadence (every 4 frames vs the ring's depth
        # of MAX_PRED+1 rows) so harness-injected SnapshotCorrupt bit
        # flips are caught while the corrupt row is still resident.
        attest_interval=4,
    )
    server.warmup()
    return server


def make_host_session(net, m, tap=None):
    """The server-side session of match ``m``: local player 0 at
    ("srv", m), remote player 1 at ("ext", m). ``tap`` (optional) wraps
    the raw socket in a passive provenance sidecar — all host sessions
    share one "server" log, matching the server tracer's process row."""
    builder = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(2)
        .with_max_prediction_window(MAX_PRED)
        .with_disconnect_timeout(1.0)
    )
    builder.add_player(PlayerType.local(), 0)
    builder.add_player(PlayerType.remote(("ext", m)), 1)
    sock = net.socket(("srv", m))
    if tap is not None:
        sock = tap(sock, "server", 500)
    return builder.start_p2p_session(sock, clock=lambda: net.now)


def make_ext_peer(net, m, plan=None, tap=None):
    """The external peer of match ``m``: its own supervised singleton stack
    (session + RollbackRunner + SessionSupervisor), chaos-wrapped. The
    provenance ``tap`` goes on the RAW socket, below the ChaosSocket, so
    it records what actually crossed the wire (drops included)."""
    builder = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(2)
        .with_max_prediction_window(MAX_PRED)
        .with_disconnect_timeout(1.0)
    )
    builder.add_player(PlayerType.remote(("srv", m)), 0)
    builder.add_player(PlayerType.local(), 1)
    sock = net.socket(("ext", m))
    if tap is not None:
        sock = tap(sock, f"ext{m}", 600 + m)
    session = builder.start_p2p_session(sock, clock=lambda: net.now)
    if plan is not None:
        session.socket = ChaosSocket(
            session.socket, plan, clock=lambda: net.now, addr=("ext", m)
        )
    runner = RollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=MAX_PRED, num_players=2,
        input_spec=box_game.INPUT_SPEC,
    )
    metrics = Metrics()
    sup = SessionSupervisor(session, runner, metrics=metrics)
    return (session, runner, sup, metrics)


def ext_step(net, peer, canon=None):
    """One external-peer drive iteration (the supervisor drive contract),
    optionally recording the canonical per-frame (bits, status) — rollback
    corrections overwrite predictions, so ``canon`` converges to the
    as-executed confirmed input log."""
    session, runner, sup, _ = peer
    session.poll_remote_clients()
    sup.tick(net.now)
    if session.current_state() != SessionState.RUNNING:
        return
    if not sup.should_advance():
        return
    for _ in range(1 + min(sup.frames_behind(), 4)):
        for h in session.local_player_handles():
            session.add_local_input(
                h, sup.input_for(h, scripted_input(h, session.current_frame))
            )
        try:
            requests = session.advance_frame()
        except PredictionThreshold:
            break
        if canon is not None:
            f = None
            for r in requests:
                if isinstance(r, SaveGameState):
                    f = r.frame
                elif isinstance(r, AdvanceFrame) and f is not None:
                    canon[f] = (
                        np.array(r.bits, copy=True),
                        np.array(r.status, copy=True),
                    )
                    f = None
        runner.handle_requests(requests, session)


def run_served_soak(
    plan, n_matches, n_iters, capacity, groups, ckpt_dir, canon_match=None
):
    """Drive ``n_matches`` served-P2P matches under ``plan``, executing
    peer KillRestart and ServerKillRestart directives at the harness level.
    Returns (server, ext peers, handle map, restore frame, canon log,
    faults, server metrics)."""
    net = LoopbackNetwork()
    metrics = Metrics()
    obs_dir = os.environ.get("GGRS_OBS_DIR")
    # When GGRS_OBS_DIR is set the soak also captures the fleet-trace
    # artifact set — a server SpanTracer plus passive provenance sidecars
    # on every raw socket — without changing the soak's topology (the
    # sidecars transmit nothing; see tests/test_telemetry_determinism.py).
    # Logs live HERE (not in the peers) so kill/restart cycles append to
    # one continuous per-component timeline.
    tracer = (
        SpanTracer(clock=lambda: net.now, pid=500, process_name="server")
        if obs_dir else None
    )
    prov = {}

    def tap(sock, component, pid):
        log = prov.get(component)
        if log is None:
            log = prov[component] = ProvenanceLog(
                component, pid=pid, clock=lambda: net.now
            )
        return SidecarSocket(sock, log)

    tap = tap if obs_dir else None
    # One server-lifetime speculation ledger: passed through kill/restart
    # rebuilds (like the tracer) so blame/economics stay one timeline.
    ledger = (
        SpeculationLedger(component="spec-ledger", pid=501)
        if obs_dir else None
    )
    server = build_server(ckpt_dir, capacity, groups, net, metrics, tracer,
                          ledger)
    ext = {m: make_ext_peer(net, m, plan, tap) for m in range(n_matches)}
    handle_of = {
        m: server.add_match(make_host_session(net, m, tap), server_inputs)
        for m in range(n_matches)
    }
    canon = {} if canon_match is not None else None
    kills = [
        {"at": k.at, "until": k.at + k.down_for, "me": k.peer[1],
         "killed": False, "done": False}
        for k in plan.kill_restarts()
    ]
    skrs = [
        {"at": k.at, "until": k.at + k.down_for,
         "killed": False, "done": False}
        for k in plan.server_kill_restarts()
    ]
    # StateFault directives run at the harness level too (a socket can't
    # reach device memory): SnapshotCorrupt flips one checksum-covered bit
    # in the target match's on-device ring row, CheckpointCorrupt flips a
    # bit in the newest on-disk server checkpoint. Both are seeded from
    # the plan so the injection is replayable.
    sdc_rng = np.random.RandomState(plan.seed ^ 0x5DC)
    snaps = [{"at": d.at, "target": d.target, "done": False}
             for d in plan.snapshot_corrupts()]
    ckcs = [{"at": d.at, "done": False}
            for d in plan.checkpoint_corrupts()]

    def inject_snapshot(d):
        if server is None:
            return False
        m = d["target"][1] if d["target"] is not None else 0
        h = handle_of.get(m)
        if h is None or h in server._lanes:
            return False
        core = server.groups[h.group]
        s = core.slots[h.slot]
        if not s.active:
            return False
        frames_h = np.asarray(core.rings.frames)[h.slot]
        # A mid-depth resident row: old enough that the save already
        # settled, young enough to survive until the next attest sweep.
        rows = np.flatnonzero(
            (frames_h >= 0) & (frames_h <= s.frame - 3)
            & (frames_h >= s.frame - 5)
        )
        if rows.size == 0:
            return False
        row = int(rows[0])
        core.rings, info = integrity.flip_ring_bit(
            core.rings, row, sdc_rng, slot=h.slot
        )
        faults.append((net.now, "snapshot_corrupt", info))
        return True
    recorders = (
        {"server": FlightRecorder(),
         **{m: FlightRecorder() for m in ext}}
        if obs_dir else {}
    )
    faults = []
    restore_frame = None
    for _ in range(n_iters):
        net.advance(FPS_DT)
        for k in kills:
            if not k["killed"] and net.now >= k["at"]:
                victim = ext.pop(k["me"])
                faults.extend(victim[0].socket.faults)
                victim[0].socket.close()
                k["killed"] = True
            elif k["killed"] and not k["done"] and net.now >= k["until"]:
                m = k["me"]
                fresh = make_ext_peer(net, m, plan, tap)
                fresh[2].begin_rejoin(("srv", m))
                ext[m] = fresh
                k["done"] = True
        for k in skrs:
            if not k["killed"] and net.now >= k["at"]:
                # kill -9: no flush, no farewell — sockets just go dark.
                # Harvest the dying host sessions' CRC-drop counts into the
                # (restart-surviving) Metrics first: chaos corruption is
                # tx-side on the ext sockets, so the server end is where
                # the v5 trailer check catches it.
                for match in server._matches.values():
                    for ep in match.session._endpoints.values():
                        if ep.data_crc_drops:
                            metrics.count("data_crc_drops", ep.data_crc_drops)
                    match.session.socket.close()
                server = None
                k["killed"] = True
            elif k["killed"] and not k["done"] and net.now >= k["until"]:
                server = build_server(ckpt_dir, capacity, groups, net,
                                      metrics, tracer, ledger)
                attachments = {
                    (h.group, h.slot): {
                        "session": make_host_session(net, m, tap),
                        "local_inputs": server_inputs,
                        "donor": ("ext", m),
                    }
                    for m, h in handle_of.items()
                }
                restored = server.checkpointer.restore(server, attachments)
                assert {(h.group, h.slot) for h in restored} == set(
                    attachments
                )
                # A handle is its match's identity on ONE server: the
                # rebuilt server hands out its own.
                at = {(h.group, h.slot): h for h in restored}
                handle_of.update(
                    (m, at[h.group, h.slot]) for m, h in handle_of.items()
                )
                restore_frame = max(
                    p[0].current_frame for p in ext.values()
                )
                k["done"] = True
        for d in snaps:
            if not d["done"] and net.now >= d["at"]:
                d["done"] = inject_snapshot(d)
        for d in ckcs:
            if not d["done"] and net.now >= d["at"]:
                ckpts = sorted(
                    f for f in os.listdir(ckpt_dir)
                    if f.startswith("server_ckpt_") and f.endswith(".npz")
                )
                if ckpts:
                    newest = max(
                        ckpts, key=lambda f: int(f[len("server_ckpt_"):-4])
                    )
                    info = integrity.flip_file_bit(
                        os.path.join(ckpt_dir, newest), sdc_rng
                    )
                    if info is not None:
                        faults.append((net.now, "checkpoint_corrupt", info))
                        d["done"] = True
        if server is not None:
            server.run_frame()
            if recorders:
                recorders["server"].capture(server=server, now=net.now)
        for m, peer in ext.items():
            ext_step(net, peer, canon if m == canon_match else None)
            if recorders:
                recorders[m].capture(
                    session=peer[0], runner=peer[1], supervisor=peer[2],
                    now=net.now,
                )
    for peer in ext.values():
        faults.extend(peer[0].socket.faults)
    if obs_dir:
        os.makedirs(obs_dir, exist_ok=True)
        for name, rec in recorders.items():
            rec.export_jsonl(
                os.path.join(obs_dir, f"serve_soak_{name}_frames.jsonl")
            )
        prov_paths = []
        for comp, log in prov.items():
            p = os.path.join(obs_dir, f"serve_soak_{comp}_provenance.jsonl")
            log.export_jsonl(p)
            prov_paths.append(p)
        trace_paths = []
        if server is not None:
            arts = server.export_telemetry(obs_dir, prefix="serve_soak")
            if arts and "trace" in arts:
                trace_paths.append(arts["trace"])
        if ledger is not None and "server" in prov:
            # Blamed-input flow arrows: re-emit each blamed entry keyed
            # by its causal rx input datagram so the merged trace draws
            # sender-tx -> server-rx -> spec_resim across process tracks.
            p = os.path.join(obs_dir, "serve_soak_spec_provenance.jsonl")
            if ledger.export_provenance(p, prov["server"]):
                prov_paths.append(p)
        merge_traces(
            trace_paths, prov_paths,
            path=os.path.join(obs_dir, "serve_soak_merged_trace.json"),
        )
    assert all(k["done"] for k in kills + skrs)
    return server, ext, handle_of, restore_frame, canon, faults, metrics


def assert_match_converged(server, handle, ext_peer, after_frame):
    """Server-side and external session agree bitwise on every settled
    checksum past ``after_frame``."""
    host = server._matches[handle].session
    assert host.current_state() == SessionState.RUNNING
    frames, rows = settled_checksums([host, ext_peer[0]])
    tail = [(f, r) for f, r in zip(frames, rows) if f > after_frame]
    assert len(tail) >= 2, f"match {handle}: no settled tail past {after_frame}"
    for f, row in tail:
        assert row[0] == row[1], f"match {handle} frame {f} diverged: {row}"


# ---------------------------------------------------------------------------
# Non-slow smoke: server kill -> checkpoint restart -> bitwise rejoin
# ---------------------------------------------------------------------------

SMOKE_PLAN = ChaosPlan(
    909,
    (
        LossBurst(1.0, 2.0, 0.2),
        Duplicate(1.5, 2.5, 0.2),
        ServerKillRestart(3.0, "server", 1.5),
    ),
)


def test_server_crash_restart_smoke(tmp_path):
    server, ext, handle_of, restore_frame, _, faults, metrics = (
        run_served_soak(
            SMOKE_PLAN, n_matches=2, n_iters=480, capacity=2, groups=1,
            ckpt_dir=str(tmp_path),
        )
    )
    assert server is not None and restore_frame is not None
    # Every match made it back onto the batch path, healthy.
    assert server.slots_active == 2 and not server._lanes
    for m, h in handle_of.items():
        assert server.health_of(h) is SlotHealth.HEALTHY
        assert_match_converged(server, h, ext[m], restore_frame)
        assert ext[m][2].health in (Health.HEALTHY, Health.DEGRADED)
    assert server.readmissions_total >= 2  # both rejoined via lanes
    assert server.evictions_total == 0
    assert server.cache_size() == 1
    assert any(k == "loss" for _, k, _ in faults)


def test_soak_exports_fleet_trace_artifacts(tmp_path, monkeypatch):
    """GGRS_OBS_DIR turns the soak into an artifact producer: flight
    recorder frames, per-component provenance logs, the server telemetry
    set (trace/metrics/SLO/HTML report), and one merged Perfetto trace —
    continuous across the server kill/restart."""
    import json

    obs = tmp_path / "obs"
    monkeypatch.setenv("GGRS_OBS_DIR", str(obs))
    run_served_soak(
        SMOKE_PLAN, n_matches=2, n_iters=330, capacity=2, groups=1,
        ckpt_dir=str(tmp_path / "ckpt"),
    )
    for f in (
        "serve_soak_server_frames.jsonl",
        "serve_soak_server_provenance.jsonl",
        "serve_soak_ext0_provenance.jsonl",
        "serve_soak_ext1_provenance.jsonl",
        "serve_soak_trace.json",
        "serve_soak_metrics.prom",
        "serve_soak_slo.json",
        "serve_soak_report.html",
        "serve_soak_spec_ledger.jsonl",
        "serve_soak_merged_trace.json",
    ):
        p = obs / f
        assert p.exists() and p.stat().st_size > 0, f"missing artifact {f}"
    with open(obs / "serve_soak_merged_trace.json") as f:
        merged = json.load(f)
    events = merged["traceEvents"]
    # Server span track AND all three wire tracks landed in one trace,
    # with cross-process flow arrows stitched between them.
    tracks = {
        ev["args"]["name"]
        for ev in events
        if ev.get("ph") == "M" and ev["name"] == "thread_name"
    }
    assert {"wire:server", "wire:ext0", "wire:ext1"} <= tracks
    assert "server" in tracks  # the tracer's serve-loop track
    flow_pids = {}
    for ev in events:
        if ev.get("cat") == "flow":
            flow_pids.setdefault(ev["id"], set()).add(ev["pid"])
    assert any(len(p) >= 2 for p in flow_pids.values())
    # The provenance timeline is continuous across the server restart:
    # records exist both before the kill (t=3.0) and after (t=4.5).
    kill_us, back_us = int(3.0e6), int(4.5e6)
    stamps = []
    with open(obs / "serve_soak_server_provenance.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            if "meta" not in rec:
                stamps.append(rec["ts_us"])
    assert min(stamps) < kill_us and max(stamps) > back_us


# ---------------------------------------------------------------------------
# Acceptance: one frame's provenance spans peer / relay / server tracks
# ---------------------------------------------------------------------------


def test_served_relay_trace_spans_three_component_tracks(tmp_path):
    """A match whose server-hosted replica talks to its external peer
    THROUGH the relay tier, with passive sidecars on all three raw
    sockets: the merged trace carries wire tracks for peer, relay and
    server, and one input frame's flow chain crosses all three —
    tx at the originator, rx+tx at the relay, rx at the terminal."""
    net = LoopbackNetwork()
    logs = {}

    def tap(sock, component, pid):
        log = logs[component] = ProvenanceLog(
            component, pid=pid, clock=lambda: net.now
        )
        return SidecarSocket(sock, log)

    relay_tracer = SpanTracer(
        clock=lambda: net.now, pid=100, process_name="relay"
    )
    relay = RelayServer(
        tap(net.socket(("relay", 0)), "relay", 100),
        clock=lambda: net.now, tracer=relay_tracer,
    )

    def relay_session(me, component, pid):
        rsock = RelaySocket(
            tap(net.socket(("peer", me)), component, pid),
            [("relay", 0)], session_id=1, peer_id=me,
            clock=lambda: net.now,
        )
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_max_prediction_window(MAX_PRED)
            .with_disconnect_timeout(1.0)
        )
        for h in range(2):
            builder.add_player(
                PlayerType.local() if h == me
                else PlayerType.remote(peer_addr(h)), h,
            )
        return builder.start_p2p_session(rsock, clock=lambda: net.now)

    tracer = SpanTracer(clock=lambda: net.now, pid=500,
                        process_name="server")
    server = build_server(
        str(tmp_path / "ckpt"), 1, 1, net, Metrics(), tracer
    )
    server.add_match(relay_session(0, "server", 500), server_inputs)
    ext_sess = relay_session(1, "ext", 600)
    ext_runner = RollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=MAX_PRED, num_players=2,
        input_spec=box_game.INPUT_SPEC,
    )
    for _ in range(300):
        net.advance(FPS_DT)
        relay.pump(net.now)
        server.run_frame()
        ext_sess.poll_remote_clients()
        if ext_sess.current_state() != SessionState.RUNNING:
            continue
        for h in ext_sess.local_player_handles():
            ext_sess.add_local_input(
                h, scripted_input(h, ext_sess.current_frame)
            )
        try:
            ext_runner.handle_requests(ext_sess.advance_frame(), ext_sess)
        except PredictionThreshold:
            pass
    assert ext_sess.current_frame >= 150  # the match actually ran

    obs = tmp_path / "obs"
    os.makedirs(obs)
    prov_paths = []
    for comp, log in logs.items():
        p = str(obs / f"{comp}_provenance.jsonl")
        log.export_jsonl(p)
        prov_paths.append(p)
    relay_trace = str(obs / "relay_trace.json")
    relay_tracer.export_perfetto(relay_trace)
    arts = server.export_telemetry(str(obs), prefix="served_relay")
    merged = merge_traces(
        [arts["trace"], relay_trace], prov_paths,
        path=str(obs / "merged_trace.json"),
    )

    # Three component wire tracks plus both span tracers, one timeline.
    tracks = {
        ev["args"]["name"]
        for ev in merged["traceEvents"]
        if ev.get("ph") == "M" and ev["name"] == "thread_name"
    }
    assert {"wire:server", "wire:relay", "wire:ext"} <= tracks
    # Flow arrows cross at least three distinct merged processes.
    flow_pids = {}
    for ev in merged["traceEvents"]:
        if ev.get("cat") == "flow":
            flow_pids.setdefault(ev["id"], set()).add(ev["pid"])
    assert any(len(p) >= 3 for p in flow_pids.values())

    # One frame's provenance, followed end to end: originator tx ->
    # relay rx -> relay tx -> terminal rx, identical flow key throughout.
    spanning = None
    for frame in range(40, 90):
        for chain in frame_flows(prov_paths, frame).values():
            if {"server", "relay", "ext"} <= {c for c, _ in chain}:
                spanning = chain
                break
        if spanning:
            break
    assert spanning is not None, "no input frame crossed all three tracks"
    comps = [c for c, _ in spanning]
    dirs = [r["dir"] for _, r in spanning]
    assert comps[0] in ("server", "ext") and dirs[0] == "tx"
    assert comps[-1] in ("server", "ext") and dirs[-1] == "rx"
    i = comps.index("relay")
    assert comps[i:i + 2] == ["relay", "relay"]
    assert dirs[i:i + 2] == ["rx", "tx"]  # the relay forwarded verbatim


# ---------------------------------------------------------------------------
# The slow acceptance soak: S=16 under full chaos
# ---------------------------------------------------------------------------

# Corrupt windows are allowed everywhere since protocol v5: every
# data-plane frame (inputs included) carries a crc32 trailer, so a
# bit-flipped datagram never decodes — it is dropped and counted
# (``data_crc_drops``), indistinguishable from loss, which rollback
# already absorbs. The StateFault family rides along: one snapshot-ring
# bit flip on the batch (self-healed bitwise by the attestation sweep,
# quarantine-free) and one checkpoint-file bit flip while the server is
# down (the restore falls back to the next-newest clean checkpoint).
SOAK_PLAN = ChaosPlan(
    2025,
    (
        LossBurst(2.0, 4.0, 0.2),
        LossBurst(8.0, 10.0, 0.25),
        Reorder(3.0, 6.0, 0.2, delay=0.05),
        Duplicate(5.0, 7.0, 0.3),
        Corrupt(2.5, 9.5, 0.05),
        Partition(6.0, 6.5, src=("ext", 3)),
        KillRestart(4.0, ("ext", 0), 1.5),
        ServerKillRestart(11.0, "server", 1.5),
        SnapshotCorrupt(7.6, ("ext", 1)),
        CheckpointCorrupt(12.0, "server"),
    ),
)


@pytest.mark.slow
def test_serve_chaos_soak_s16(tmp_path):
    n = 16
    server, ext, handle_of, restore_frame, canon, faults, metrics = (
        run_served_soak(
            SOAK_PLAN, n_matches=n, n_iters=990, capacity=n, groups=4,
            ckpt_dir=str(tmp_path), canon_match=1,
        )
    )
    assert server is not None

    # Converged: every match back on the batch, both replicas RUNNING.
    assert server.slots_active == n and not server._lanes
    assert server.evictions_total == 0
    for m, h in handle_of.items():
        assert server.health_of(h) is SlotHealth.HEALTHY
        assert_match_converged(server, h, ext[m], restore_frame)

    # Zero desyncs, anywhere: the chaos was all network-level and every
    # replica's checksum votes stayed unanimous.
    for m, peer in ext.items():
        assert peer[3].counters["desyncs_detected"] == 0
        assert peer[2].health in (Health.HEALTHY, Health.DEGRADED)
    assert metrics.counters["desyncs_detected"] == 0

    # The killed external peer came back through a donor state transfer
    # served from the live batch slot (the facade donor path).
    assert ext[0][3].counters["recoveries"] >= 1
    assert metrics.counters["reconnects_initiated"] >= 1

    # Server crash-restart: every match rejoined through a recovery lane,
    # within the documented recovery bound, and churn never recompiled.
    assert server.readmissions_total >= n
    recoveries = [
        v for k, s in metrics.series.items()
        if k.startswith("slot_recovery_frames") for v in s
    ]
    assert all(v <= 600 for v in recoveries)
    assert server.cache_size() == 1

    # The plan actually injected chaos of every scripted kind — including
    # wire corruption and both StateFault flavors.
    kinds = {k for _, k, _ in faults}
    assert {
        "loss", "reorder", "duplicate", "corrupt", "partition",
        "snapshot_corrupt", "checkpoint_corrupt",
    } <= kinds

    # v5 data-plane integrity: corrupted datagrams were dropped-and-counted
    # at the endpoints (never decoded), on both sides of the wire.
    drops = sum(
        ep.data_crc_drops
        for peer in ext.values()
        for ep in peer[0]._endpoints.values()
    ) + sum(
        ep.data_crc_drops
        for m in server._matches.values()
        for ep in m.session._endpoints.values()
    ) + int(metrics.counters.get("data_crc_drops", 0))
    assert drops > 0

    # The snapshot bit flip was detected by the attestation sweep and
    # repaired bitwise, in place, quarantine-free — no fault escalation,
    # and the serial replay below proves the repaired match's checksums
    # are exactly what an uninterrupted run would have produced.
    assert metrics.counters["sdc_detected"] >= 1
    assert metrics.counters["sdc_repaired"] >= 1
    assert (metrics.counters["sdc_repaired_bitwise"]
            == metrics.counters["sdc_repaired"])
    assert metrics.counters.get("sdc_unrepairable", 0) == 0

    # The corrupted newest checkpoint was refused by the digest-guarded
    # loader; the restart restored from the next-newest clean one.
    assert server.checkpointer.load_fallbacks >= 1

    # Independent serial replay: rebuild match 1's trajectory from nothing
    # but its canonical confirmed-input log; the reported checksums must
    # be bitwise identical to what the live (batched, chaos-ridden,
    # crash-restarted) match recorded.
    sess = ext[1][0]
    upto = min(sess.confirmed_frame(), max(canon))
    assert upto > 600  # the log actually covers the match

    class Log:
        def __init__(self):
            self.seen = {}

        def wants_checksum(self, frame):
            return True

        def report_checksum(self, frame, cs):
            self.seen[frame] = int(cs)

    replay = RollbackRunner(
        box_game.make_schedule(), box_game.make_world(2).commit(),
        max_prediction=MAX_PRED, num_players=2,
        input_spec=box_game.INPUT_SPEC,
    )
    log = Log()
    for f in range(upto + 1):
        bits, status = canon[f]
        replay.handle_requests(
            [SaveGameState(f), AdvanceFrame(bits=bits, status=status)], log
        )
    # The session prunes its checksum map to a few exchange intervals
    # behind confirmed, so only the tail survives — which is still a full
    # end-to-end proof: the checksum at frame ~900 depends bitwise on
    # every one of the ~900 frames (and both restarts) before it.
    recorded = {
        f: cs for f, cs in sess._local_checksums.items() if f <= upto
    }
    assert len(recorded) >= 3
    for f, cs in recorded.items():
        assert log.seen[f] == cs, f"serial replay diverged at frame {f}"
