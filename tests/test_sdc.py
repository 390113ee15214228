"""Data-plane integrity (protocol v5) + silent-state-corruption (SDC)
attestation and rollback-powered self-healing.

Four layers, mirroring the subsystem's trust chain:

- **Wire**: every data-plane frame type (1-8) carries a crc32 trailer.
  The property suite proves a truncated, bit-flipped, or garbage-trailed
  datagram NEVER decodes as a data-plane message — it is dropped and
  counted (``data_crc_drops``), indistinguishable from loss, which
  rollback already absorbs. Stale-version (v4) frames are refused as
  version skew, never mis-counted as corruption and never desynced.
- **Memory**: ``integrity.attest_ring`` recomputes every occupied
  snapshot-ring row's two-lane digest against its save-time value, so a
  flipped bit in device memory is detected within one attestation
  interval — singleton runner and stacked ``[S, depth]`` serve rings
  alike (one vmapped pass).
- **Repair**: ``RollbackRunner.attest_and_repair`` /
  ``BatchedSessionCore.repair_slot`` restore the deepest digest-clean
  snapshot and resimulate from the as-used input log. The repair must
  land *bitwise* (equal to an uninterrupted serial replay), recompile
  nothing, and leave batch siblings untouched; an unrepairable ring
  raises a typed ``StateFault(reason="sdc")`` that the supervisor
  escalates to the donor-transfer rung (docs/serving.md#self-healing).
- **Disk**: a bit-flipped server checkpoint is refused by the
  digest-guarded loader as a typed ``ValueError`` and
  ``ServerCheckpointer.restore`` falls back to the next-newest clean
  file (counted in ``load_fallbacks``).
"""

import zlib

import numpy as np
import pytest

from bevy_ggrs_tpu import integrity
from bevy_ggrs_tpu.chaos import (
    ChaosPlan,
    ChaosSocket,
    CheckpointCorrupt,
    Corrupt,
    SnapshotCorrupt,
)
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.session import EventKind, SessionState
from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.session.endpoint import (
    VERSION_MISMATCH_THRESHOLD,
    PeerEndpoint,
)
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    SaveGameState,
)
from bevy_ggrs_tpu.session.supervisor import Health
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils import xla_cache
from tests.test_protocol_fuzz import _valid_messages
from tests.test_supervisor import (
    MAX_PRED,
    make_supervised,
    settled_checksums,
    sup_step,
)

DATA_PLANE_CLASSES = (
    proto.SyncRequest,
    proto.SyncReply,
    proto.InputMsg,
    proto.InputAck,
    proto.QualityReport,
    proto.QualityReply,
    proto.KeepAlive,
    proto.ChecksumReport,
)


# ---------------------------------------------------------------------------
# Wire: the v5 crc32 trailer property suite
# ---------------------------------------------------------------------------


def test_every_data_plane_frame_carries_crc_trailer():
    for msg in _valid_messages():
        wire = proto.encode(msg)
        assert wire[2] in proto.DATA_PLANE_TYPES
        (trailer,) = proto._CRC.unpack_from(wire, len(wire) - 4)
        assert trailer == (zlib.crc32(wire[:-4]) & 0xFFFFFFFF)
        assert proto.decode(wire) == msg  # the trailer round-trips


def test_control_plane_frames_not_enveloped():
    # Types 9+ carry their own per-chunk crc/digest; they get no trailer
    # and never count toward crc_mismatch.
    wire = proto.encode(proto.StateRequest(nonce=7, kind=proto.STATE_KIND_RING))
    assert wire[2] not in proto.DATA_PLANE_TYPES
    assert not proto.crc_mismatch(wire)
    assert proto.decode(wire) == proto.StateRequest(7, proto.STATE_KIND_RING)


def test_single_bit_flip_never_decodes_as_data_plane():
    """Exhaustive: EVERY single-bit flip of EVERY data-plane frame either
    fails to decode or (type-byte flips that land on an unenveloped
    control type) decodes as a non-data-plane message the session input
    path ignores. No flip ever injects a wrong input/ack/checksum."""
    for msg in _valid_messages():
        wire = proto.encode(msg)
        for bit in range(len(wire) * 8):
            flipped = bytearray(wire)
            flipped[bit // 8] ^= 1 << (bit % 8)
            got = proto.decode(bytes(flipped))
            assert not isinstance(got, DATA_PLANE_CLASSES), (
                msg, bit, got,
            )


def test_truncation_and_trailing_garbage_never_decode():
    for msg in _valid_messages():
        wire = proto.encode(msg)
        for cut in range(len(wire)):
            assert proto.decode(wire[:cut]) is None, (msg, cut)
        for garbage in (b"\x00", b"\xff" * 3, wire[-4:]):
            assert proto.decode(wire + garbage) is None, (msg, garbage)
            # ...and the drop is attributed to corruption, not version skew.
            assert proto.crc_mismatch(wire + garbage)


def test_crc_valid_but_stale_version_refused_as_skew():
    """A frame whose bytes are internally consistent but carry the v4
    version byte (the frozen-deploy peer) is refused by the version gate
    BEFORE the crc check: decode None, version_mismatch says 4, and
    crc_mismatch stays False so the drop is counted as skew — the typed
    refusal, never a desync and never a corruption stat."""
    for msg in _valid_messages():
        wire = proto.encode(msg)
        stale = bytes([wire[0], 4, wire[2]]) + wire[3:-4]  # v4: no trailer
        assert proto.decode(stale) is None
        assert proto.version_mismatch(stale) == 4
        assert not proto.crc_mismatch(stale)


def test_endpoint_drops_and_counts_corruption_separately_from_skew():
    ep = PeerEndpoint(("peer", 1), np.random.RandomState(0))
    wire = bytearray(proto.encode(proto.KeepAlive()))
    wire[-1] ^= 0x40  # break the trailer
    ep.note_undecodable(bytes(wire))
    assert ep.data_crc_drops == 1
    assert ep.version_mismatches == 0

    good = proto.encode(proto.SyncRequest(3))
    stale = bytes([good[0], 4, good[2]]) + good[3:-4]
    ep.note_undecodable(stale)
    assert ep.data_crc_drops == 1
    assert ep.version_mismatches == 1


def test_v4_peer_handshake_gets_typed_refusal():
    """A still-SYNCHRONIZING endpoint fed v4 datagrams emits one
    VERSION_MISMATCH event after the threshold — the session surfaces the
    skewed peer instead of stalling sync forever."""
    ep = PeerEndpoint(("peer", 1), np.random.RandomState(0))
    good = proto.encode(proto.SyncRequest(3))
    stale = bytes([good[0], 4, good[2]]) + good[3:-4]
    for _ in range(VERSION_MISMATCH_THRESHOLD):
        ep.note_undecodable(stale)
    kinds = [e.kind for e in ep.events]
    assert kinds.count(EventKind.VERSION_MISMATCH) == 1
    assert ep.version_mismatches == VERSION_MISMATCH_THRESHOLD


def test_p2p_pair_corrupt_window_drops_counted_zero_desyncs():
    """The P2P-pair acceptance drill: a real two-peer match under an
    aggressive Corrupt window converges bitwise with zero desyncs — every
    flipped datagram was dropped-and-counted at the receiving endpoint,
    then re-delivered by the redundant input spans."""
    net = LoopbackNetwork()
    plan = ChaosPlan(77, (Corrupt(0.3, 4.0, 0.10),))
    peers = [make_supervised(net, 2, me) for me in range(2)]
    for me, peer in enumerate(peers):
        peer[0].socket = ChaosSocket(
            peer[0].socket, plan, clock=lambda: net.now, addr=("peer", me)
        )
    for _ in range(330):
        net.advance(1.0 / 60.0)
        for peer in peers:
            sup_step(net, peer, lambda h, f: np.uint8((f // 3 + h) % 4))
    sessions = [p[0] for p in peers]
    for s, _, sup, m in peers:
        assert s.current_state() == SessionState.RUNNING
        assert sup.health in (Health.HEALTHY, Health.DEGRADED)
        assert m.counters.get("desyncs_detected", 0) == 0
    drops = sum(
        ep.data_crc_drops for s in sessions for ep in s._endpoints.values()
    )
    assert drops > 0
    assert sum(len(p[0].socket.faults) for p in peers) > 0
    frames, rows = settled_checksums(sessions)
    assert len(frames) >= 3
    for f, row in zip(frames, rows):
        assert row[0] == row[1], f"frame {f} diverged: {row}"


# ---------------------------------------------------------------------------
# Memory + repair: singleton runner
# ---------------------------------------------------------------------------

N_PLAYERS = 2


def mk_runner():
    r = RollbackRunner(
        box_game.make_schedule(),
        box_game.make_world(N_PLAYERS).commit(),
        max_prediction=MAX_PRED,
        num_players=N_PLAYERS,
        input_spec=box_game.INPUT_SPEC,
    )
    r.warmup()
    return r


def bits_for(f):
    z = box_game.INPUT_SPEC.zeros_np(N_PLAYERS)
    return np.stack(
        [box_game.INPUT_SPEC.zeros_np(1)[0] + ((f + h) % 3)
         for h in range(N_PLAYERS)]
    ).astype(z.dtype)


def advance(runner, frames, start=None):
    start = runner.frame if start is None else start
    for f in range(start, start + frames):
        runner.handle_requests(
            [SaveGameState(f),
             AdvanceFrame(bits_for(f), np.zeros(N_PLAYERS, np.int32))]
        )


def occupied_frames(ring):
    return sorted(int(f) for f in np.asarray(ring.frames).ravel() if f >= 0)


def test_clean_ring_attests_clean():
    runner = mk_runner()
    advance(runner, 24)
    assert not integrity.attest_ring(runner.ring).any()
    assert runner.attest_and_repair() == {
        "corrupt_frames": [], "repaired": 0, "repair_frames": 0,
        "bitwise": None, "first_corrupt_field": None,
    }
    assert runner.state_faults == []


def test_flip_detected_and_repaired_bitwise_no_recompile():
    runner, serial = mk_runner(), mk_runner()
    advance(runner, 30, start=0)
    advance(serial, 30, start=0)

    rng = np.random.RandomState(7)
    target = occupied_frames(runner.ring)[3]
    runner.ring, info = integrity.flip_ring_bit(
        runner.ring, target % runner.ring.depth, rng
    )
    assert integrity.attest_ring(runner.ring).any()

    xla_cache.install_compile_listeners()
    c0 = xla_cache.compile_counters()["backend_compiles"]
    report = runner.attest_and_repair()
    c1 = xla_cache.compile_counters()["backend_compiles"]

    assert report["corrupt_frames"] == [target]
    assert report["bitwise"] is True
    assert report["first_corrupt_field"] == info["field"]
    assert c1 - c0 == 0, "repair must reuse the warmed executable"
    assert not integrity.attest_ring(runner.ring).any()
    assert runner.sdc_detected_total == 1
    assert runner.sdc_repaired_total == 1
    assert [r["reason"] for r in runner.state_faults] == ["sdc"]
    assert runner.state_faults[0]["repaired"] is True

    # Bitwise witness: live state AND every ring row equal an
    # uninterrupted serial replay of the same inputs.
    import jax

    a = np.asarray(integrity._state_digest(runner.state))
    b = np.asarray(integrity._state_digest(serial.state))
    assert (a == b).all()
    for la, lb in zip(
        jax.tree_util.tree_leaves(runner.ring.states),
        jax.tree_util.tree_leaves(serial.ring.states),
    ):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_restore_path_verifies_rows_and_self_heals():
    """A rollback that targets a corrupt ring row must NOT silently
    resimulate from garbage: the restore-path guard attests, self-heals,
    and only then replays — the final state is bitwise what a clean run
    produces, with the incident on the typed fault log."""
    runner, serial = mk_runner(), mk_runner()
    advance(runner, 30)
    advance(serial, 30)

    rng = np.random.RandomState(9)
    back = occupied_frames(runner.ring)[4]
    runner.ring, _ = integrity.flip_ring_bit(
        runner.ring, back % runner.ring.depth, rng
    )
    top = runner.frame
    reqs = [LoadGameState(back)]
    for f in range(back, top):
        reqs += [SaveGameState(f),
                 AdvanceFrame(bits_for(f), np.zeros(N_PLAYERS, np.int32))]
    runner.handle_requests(reqs)

    assert not integrity.attest_ring(runner.ring).any()
    assert len(runner.state_faults) == 1
    assert runner.state_faults[0]["repaired"] is True
    assert runner.state_faults[0]["bitwise"] is True
    a = np.asarray(integrity._state_digest(runner.state))
    b = np.asarray(integrity._state_digest(serial.state))
    assert (a == b).all()


def test_unrepairable_ring_raises_typed_fault():
    runner = mk_runner()
    advance(runner, 12)
    rng = np.random.RandomState(5)
    for f in occupied_frames(runner.ring):
        runner.ring, _ = integrity.flip_ring_bit(
            runner.ring, f % runner.ring.depth, rng
        )
    with pytest.raises(integrity.StateFault) as exc:
        runner.attest_and_repair()
    assert exc.value.reason == "sdc"
    assert exc.value.frames  # names the corrupt frames
    rec = runner.state_faults[-1]
    assert rec["reason"] == "sdc" and rec["repaired"] is False


# ---------------------------------------------------------------------------
# Memory + repair: batched serve rings
# ---------------------------------------------------------------------------


def make_batch():
    from tests.test_batched_sessions import make_core, make_script

    core = make_core(num_slots=4)
    for _ in range(3):
        core.admit()
    scripts = {i: make_script(100 + i, depth=2 + (i % 2), cycles=6)
               for i in range(3)}
    n = min(len(v) for v in scripts.values())
    for t in range(n):
        core.tick({i: (scripts[i][t][0], scripts[i][t][1], None)
                   for i in range(3)})
    return core


def test_batched_attest_detects_exact_slots_and_repairs_bitwise():
    core = make_batch()
    assert core.attest() == {}

    pre = np.asarray(integrity._states_digests(core.states)).copy()
    rng = np.random.RandomState(11)
    frames_h = np.asarray(core.rings.frames)
    injected = {}
    for slot, nrows in ((1, 2), (2, 1)):
        occ = sorted(int(f) for f in frames_h[slot] if f >= 0)
        for tf in occ[1:1 + nrows]:
            core.rings, _ = integrity.flip_ring_bit(
                core.rings, tf % core.ring_depth, rng, slot=slot
            )
            injected.setdefault(slot, []).append(tf)

    detected = core.attest()
    assert detected == injected  # exact slots, exact frames

    xla_cache.install_compile_listeners()
    c0 = xla_cache.compile_counters()["backend_compiles"]
    for slot, bad in detected.items():
        rep = core.repair_slot(slot, bad)
        assert rep["bitwise"] is True
        assert rep["repaired"] == len(bad)
    c1 = xla_cache.compile_counters()["backend_compiles"]
    assert c1 - c0 == 0

    assert core.attest() == {}
    post = np.asarray(integrity._states_digests(core.states))
    # Repaired slots land bitwise AND siblings were never touched.
    assert (pre == post).all()


def test_batched_unrepairable_slot_faults_with_slot_index():
    core = make_batch()
    rng = np.random.RandomState(13)
    frames_h = np.asarray(core.rings.frames)[0]
    for f in (int(x) for x in frames_h if x >= 0):
        core.rings, _ = integrity.flip_ring_bit(
            core.rings, f % core.ring_depth, rng, slot=0
        )
    detected = core.attest()
    with pytest.raises(integrity.StateFault) as exc:
        core.repair_slot(0, detected[0])
    assert exc.value.reason == "sdc"
    assert exc.value.slot == 0


def test_match_server_sweep_heals_every_injection_in_place():
    """The served lifecycle, by count: sweep-aligned single-bit ring flips
    into a ``MatchServer``'s live matches are ALL detected by its periodic
    attestation sweep and ALL repaired bitwise in place; no match is
    quarantined or evicted and nothing compiles after steady state."""
    from bevy_ggrs_tpu.serve import SlotHealth
    from bevy_ggrs_tpu.utils.metrics import Metrics
    from tests.test_serve_faults import inputs_for, make_server, make_synctest

    S, ATTEST, TARGET = 8, 4, 4
    metrics = Metrics()
    server = make_server(metrics=metrics, capacity=S, attest_interval=ATTEST)
    handles = [
        server.add_match(make_synctest(), inputs_for(m)) for m in range(S)
    ]
    rng = np.random.RandomState(0x5DC)

    def inject(handle):
        # The row of frame - 3: under the SyncTest reload depth (check
        # distance 2), so it is never loaded before the sweep reads it,
        # and resident until this frame's sweep (the ring holds 5 rows).
        core = server.groups[handle.group]
        slot = core.slots[handle.slot]
        rows = np.flatnonzero(
            np.asarray(core.rings.frames)[handle.slot] == slot.frame - 3
        )
        if not slot.active or rows.size == 0:
            return False
        core.rings, _ = integrity.flip_ring_bit(
            core.rings, int(rows[0]), rng, slot=handle.slot
        )
        return True

    assert xla_cache.install_compile_listeners()
    injected = 0
    for t in range(60):
        if t == 12:
            built = xla_cache.compile_counters()["backend_compiles"]
        if (
            t >= 16 and injected < TARGET
            and server.frames_served % ATTEST == 0
            and inject(handles[(injected * 3) % S])
        ):
            injected += 1
        server.run_frame()
    assert injected == TARGET
    c = metrics.counters
    assert c["sdc_detected"] == injected
    assert c["sdc_repaired"] == c["sdc_repaired_bitwise"] == injected
    assert c.get("sdc_unrepairable", 0) == 0
    # One resimulation span a repair, from the deepest clean snapshot.
    spans = metrics.series["sdc_repair_frames"]
    assert len(spans) == injected and min(spans) >= 1
    assert server.evictions_total == 0
    assert all(server.health_of(h) is SlotHealth.HEALTHY for h in handles)
    assert xla_cache.compile_counters()["backend_compiles"] == built


# ---------------------------------------------------------------------------
# Disk: checkpoint corruption -> typed refusal -> newest-clean fallback
# ---------------------------------------------------------------------------


def test_corrupt_checkpoint_refused_and_restore_falls_back(tmp_path):
    from tests.test_serve_faults import inputs_for, make_server, make_synctest
    from bevy_ggrs_tpu.serve.faults import load_checkpoint_matches

    ckpt = str(tmp_path / "ckpts")
    server = make_server(checkpoint_dir=ckpt, checkpoint_interval=6,
                         checkpoint_keep=3)
    handles = [server.add_match(make_synctest(), inputs_for(k))
               for k in (11, 12)]
    for _ in range(12):
        server.run_frame()
    assert server.checkpointer.saves_total == 2
    newest = server.checkpointer.latest()
    del server

    info = integrity.flip_file_bit(newest, np.random.RandomState(3))
    assert info is not None

    # The guarded loader refuses the flipped file as a typed ValueError
    # (never an unpickling crash, never a plausible impostor state).
    revived = make_server(checkpoint_dir=ckpt, checkpoint_interval=6,
                          checkpoint_keep=3)
    with pytest.raises(ValueError, match="corrupt server checkpoint"):
        load_checkpoint_matches(newest, revived.state_codec())

    # restore() with no explicit path skips it and restores every match
    # from the next-newest clean checkpoint (frame 6, not 12).
    attachments = {
        (h.group, h.slot): {"session": make_synctest(),
                            "local_inputs": inputs_for(k)}
        for h, k in zip(handles, (11, 12))
    }
    restored = revived.checkpointer.restore(revived, attachments)
    assert {(h.group, h.slot) for h in restored} == set(attachments)
    assert revived.checkpointer.load_fallbacks == 1
    for h in handles:
        assert revived.groups[h.group].slots[h.slot].frame == 6

    # An explicitly named corrupt path NEVER falls back silently.
    with pytest.raises(ValueError, match="corrupt server checkpoint"):
        make_server(checkpoint_dir=ckpt).checkpointer.restore(
            make_server(checkpoint_dir=ckpt), attachments, path=newest
        )


# ---------------------------------------------------------------------------
# Supervisor: periodic attestation, typed events, donor escalation
# ---------------------------------------------------------------------------


def drive_pair(net, peers, n, events=None):
    for _ in range(n):
        net.advance(1.0 / 60.0)
        for peer in peers:
            sup_step(
                net, peer, lambda h, f: np.uint8((f // 3 + h) % 4),
                events=events,
            )


def test_supervisor_attests_heals_in_place_and_emits_typed_event():
    net = LoopbackNetwork()
    peers = [make_supervised(net, 2, me) for me in range(2)]
    for _, _, sup, _ in peers:
        sup.attest_interval = 4  # tight cadence for the drill
    drive_pair(net, peers, 60)
    session, runner, sup, metrics = peers[0]
    assert session.current_state() == SessionState.RUNNING

    rng = np.random.RandomState(21)
    occ = occupied_frames(runner.ring)
    target = occ[len(occ) // 2]
    runner.ring, _ = integrity.flip_ring_bit(
        runner.ring, target % runner.ring.depth, rng
    )

    events = []
    drive_pair(net, peers, 90, events=events)

    sdc = [e for e in events if e.kind == EventKind.STATE_FAULT]
    assert len(sdc) >= 1
    assert sdc[0].data["reason"] == "sdc"
    assert sdc[0].data["repaired"] is True
    assert sdc[0].data["bitwise"] is True
    assert metrics.counters["sdc_faults"] >= 1
    # Quarantine-free: the repair landed bitwise, so the timeline provably
    # never diverged — no desync, no health excursion, checksums agree.
    assert sup.health is Health.HEALTHY
    assert metrics.counters.get("quarantines", 0) == 0
    assert metrics.counters.get("desyncs_detected", 0) == 0
    frames, rows = settled_checksums([peers[0][0], peers[1][0]])
    assert frames and all(r[0] == r[1] for r in rows)


def test_supervisor_escalates_unrepairable_to_donor_transfer():
    net = LoopbackNetwork()
    peers = [make_supervised(net, 2, me) for me in range(2)]
    for _, _, sup, _ in peers:
        sup.attest_interval = 4
    drive_pair(net, peers, 60)
    session, runner, sup, metrics = peers[0]

    rng = np.random.RandomState(23)
    for f in occupied_frames(runner.ring):
        runner.ring, _ = integrity.flip_ring_bit(
            runner.ring, f % runner.ring.depth, rng
        )

    events = []
    drive_pair(net, peers, 240, events=events)

    # Rung 2 of the ladder: local repair impossible -> quarantine ->
    # digest-verified donor snapshot -> replay forward -> healthy again.
    assert metrics.counters["sdc_escalations"] >= 1
    assert metrics.counters["recoveries"] >= 1
    sdc = [e for e in events if e.kind == EventKind.STATE_FAULT]
    assert any(e.data["repaired"] is False for e in sdc)
    assert sup.health in (Health.HEALTHY, Health.DEGRADED)
    assert session.current_state() == SessionState.RUNNING
    frames, rows = settled_checksums([peers[0][0], peers[1][0]])
    assert frames and all(r[0] == r[1] for r in rows)


# ---------------------------------------------------------------------------
# ChaosPlan: the StateFault directive family
# ---------------------------------------------------------------------------


def test_sdc_family_drawn_last_keeps_old_plans_byte_identical():
    peers = (("peer", 0), ("peer", 1))
    base = ChaosPlan.generate(
        31, 30.0, peers, kill_restart=True, match_server=("srv", 0)
    )
    with_sdc = ChaosPlan.generate(
        31, 30.0, peers, kill_restart=True, match_server=("srv", 0), sdc=True
    )
    # Every pre-existing draw is untouched; the sdc family is appended.
    assert with_sdc.directives[: len(base.directives)] == base.directives
    snaps = with_sdc.snapshot_corrupts()
    assert len(snaps) == 2
    assert all(0.2 * 30.0 <= d.at <= 0.7 * 30.0 for d in snaps)
    assert all(d.target in peers for d in snaps)
    ckcs = with_sdc.checkpoint_corrupts()
    assert len(ckcs) == 1 and ckcs[0].target == ("srv", 0)
    assert 0.6 * 30.0 <= ckcs[0].at <= 0.85 * 30.0
    # Seed-replayable like every other family.
    assert with_sdc == ChaosPlan.generate(
        31, 30.0, peers, kill_restart=True, match_server=("srv", 0), sdc=True
    )


def test_sdc_directives_json_roundtrip_and_horizon():
    plan = ChaosPlan(
        5,
        (
            Corrupt(1.0, 2.0, 0.05),
            SnapshotCorrupt(3.0, ("peer", 1)),
            CheckpointCorrupt(4.5, "server"),
        ),
    )
    back = ChaosPlan.from_json(plan.to_json())
    assert back == plan
    assert back.snapshot_corrupts()[0].target == ("peer", 1)
    assert back.checkpoint_corrupts()[0].target == "server"
    assert plan.horizon() >= 4.5
