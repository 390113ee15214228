"""Relay tree: tiered spectator fan-out (relay/tree.py).

Covers the whole tree surface: depth-2 bitwise exactness at every leaf
against the authoritative ring (the tier link feeds raw datagrams, so
exactness is structural), the shared-keyframe cache (N cold joins in one
interval cost ONE upstream encode; stream-epoch invalidation; corrupt
cached entries refused by digest and rebuilt), chain-aware warm resume
across a relay swap (zero keyframe bytes on the wire — the satellite
fix), KEYFRAME_ONLY parent propagation (children re-seed, no silent
chain break), the mid-tier kill soak (re-home ladder, zero desync,
bounded resume), relay-tier autopilot elasticity (spawn -> fan-out ->
drain -> retire, ledger replays bit-identically), RelayTreeKill plan
stability (drawn LAST; old seeds stay byte-identical), and a subprocess
relay tier over real UDP.
"""

import json
import os
import zlib

import numpy as np
import pytest

from bevy_ggrs_tpu.chaos import (
    ChaosPlan,
    ChaosSocket,
    LossBurst,
    Partition,
    RelayTreeKill,
    Reorder,
)
from bevy_ggrs_tpu.fleet.autopilot import (
    RelayAutopilot,
    RelayAutopilotConfig,
    RelayObservation,
    RelayPolicy,
    RelaySample,
    verify_relay_ledger,
)
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.relay import (
    RelayServer,
    StateCodec,
    StatePublisher,
    StreamSpectator,
    payload_digest,
)
from bevy_ggrs_tpu.relay.server import MODE_FULL, MODE_KEYFRAME
from bevy_ggrs_tpu.relay.stream import CHUNK_PAYLOAD
from bevy_ggrs_tpu.relay.tree import ProcRelayTier, RelayTree
from bevy_ggrs_tpu.runner import RollbackRunner
from bevy_ggrs_tpu.session import EventKind, SessionState
from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.session.common import NULL_FRAME
from bevy_ggrs_tpu.session.requests import AdvanceFrame
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_p2p import FPS_DT, scripted_input
from tests.test_relay import FakeSocket, make_relay_peer
from tests.test_supervisor import MAX_PRED, settled_checksums, sup_step

SESSION = 7
ROOT = ("relay", 0)


def _kf_raws(frame, data):
    """Hand-craft a chunked StreamKeyframe exactly as StatePublisher
    would ship it (same chunking, crc, digest)."""
    digest = payload_digest(data)
    chunks = [
        data[i : i + CHUNK_PAYLOAD]
        for i in range(0, len(data), CHUNK_PAYLOAD)
    ] or [b""]
    return [
        proto.encode(
            proto.StreamKeyframe(
                frame, seq, len(chunks),
                zlib.crc32(p) & 0xFFFFFFFF, digest, p,
            )
        )
        for seq, p in enumerate(chunks)
    ]


def _tree_fixture(
    net,
    mids=2,
    leaf_under=None,
    server_kwargs=None,
    socket_factory=None,
    max_depth=2,
):
    """Root + ``mids`` tier-1 relays (+ optionally one tier-2 leaf
    under ``leaf_under``) with per-relay Metrics, 2 relay-peers through
    the root, and a publisher on peer 0."""
    tree = RelayTree(
        socket_factory if socket_factory is not None else net.socket,
        session_id=SESSION,
        clock=lambda: net.now,
        max_depth=max_depth,
        metrics_factory=lambda addr: Metrics(),
        server_kwargs=server_kwargs or {},
    )
    tree.add_relay(addr=ROOT)
    mid_nodes = [tree.add_relay(parent=ROOT) for _ in range(mids)]
    leaf_node = (
        tree.add_relay(parent=leaf_under) if leaf_under is not None else None
    )
    a = make_relay_peer(net, 2, 0, [ROOT])
    b = make_relay_peer(net, 2, 1, [ROOT])
    pub = StatePublisher(
        a[0], a[1], socket=a[0].socket, keyframe_interval=10,
        max_frames_per_publish=1,
    )
    return tree, mid_nodes, leaf_node, (a, b), pub


def _make_spec(net, addr, relays, codec, **kw):
    kw.setdefault("session_id", SESSION)
    kw.setdefault("window", 8)
    kw.setdefault("clock", lambda: net.now)
    kw.setdefault("resub_timeout", 0.6)
    return StreamSpectator(net.socket(addr), relays=relays, codec=codec, **kw)


# ---------------------------------------------------------------------------
# Depth-2 bitwise exactness at every leaf
# ---------------------------------------------------------------------------


class TestRelayTreeExactness:
    def test_depth2_streams_bitwise_exact_at_every_leaf(self):
        """Acceptance: root -> mid -> leaf relays, spectators at every
        tier. Every frame every spectator reconstructs equals the
        authoritative ring state bitwise, and the final frame matches an
        independent serial replay of the scripted inputs."""
        net = LoopbackNetwork()
        tree, mid_nodes, leaf_node, peers, pub = _tree_fixture(
            net, mids=2, leaf_under=None,
        )
        mid0, mid1 = mid_nodes
        leaf_node = tree.add_relay(parent=mid0.addr)
        assert leaf_node.tier == 2 and tree.depth() == 2

        codec = StateCodec.for_state(box_game.make_world(2).commit())
        specs = [
            _make_spec(net, ("spec", i), [addr], codec, max_apply_per_poll=1)
            for i, addr in enumerate(
                [mid0.addr, mid1.addr, leaf_node.addr]
            )
        ]
        authoritative = {}
        checked = [0, 0, 0]

        def drain(spec, i):
            while True:
                prev = spec.current_frame
                spec.poll(net.now)
                if spec.current_frame == prev:
                    return
                f = spec.current_frame
                if f in authoritative:
                    assert spec.state_bytes == authoritative[f], (
                        f"spec {i} diverged at frame {f}"
                    )
                    checked[i] += 1

        for _ in range(300):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for peer in peers:
                sup_step(net, peer, scripted_input)
            before = pub.published_frames
            pub.publish(net.now)
            if pub.published_frames > before:
                authoritative[pub._prev_frame] = pub._prev
            for i, spec in enumerate(specs):
                drain(spec, i)

        # Drain: peers stop advancing; the stream flushes down the tree.
        for _ in range(40):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for session, _, _, _ in peers:
                session.poll_remote_clients()
            pub.publish(net.now)
            for i, spec in enumerate(specs):
                drain(spec, i)

        assert len(authoritative) >= 150
        for i, spec in enumerate(specs):
            assert spec.current_frame == pub._prev_frame, f"spec {i} lagged"
            assert spec.state_bytes == pub._prev
            assert checked[i] >= 150
            assert spec.deltas_applied >= 100  # rode the chain, not keyframes
        # The tree is caught up: no tier holds residual lag after drain.
        assert all(lag == 0 for lag in tree.tier_lag().values())

        # Independent serial replay anchor: exact w.r.t. the true
        # trajectory, not just the publisher's own ring.
        F = specs[2].current_frame
        ref = RollbackRunner(
            box_game.make_schedule(),
            box_game.make_world(2).commit(),
            max_prediction=MAX_PRED,
            num_players=2,
            input_spec=box_game.INPUT_SPEC,
        )
        for f in range(F):
            bits = np.stack([scripted_input(h, f) for h in range(2)])
            ref.handle_requests(
                [AdvanceFrame(bits=bits, status=np.zeros(2, np.int32))]
            )
        assert codec.encode(ref.world()) == specs[2].state_bytes

    def test_live_fanout_lag_per_tier_and_tree_wide_keyframe_cache(self):
        """The tiered fan-out's bounds, by count, WHILE the match runs:
        root -> 2 mids -> 4 leaves, three spectators cold-joining each
        leaf in one burst and three witnesses on the root. A tier adds
        at most 2 frames of lag (the leaves' p99 over the witnesses',
        split across the tiers crossed; no link ever trails its parent
        by more), each leaf encodes its keyframe once and serves the
        other joiners from the shared cache, and every spectator drains
        to the publisher's exact bytes."""
        net = LoopbackNetwork()
        tree, mids, _, peers, pub = _tree_fixture(net, mids=2)
        leaves = [
            tree.add_relay(parent=mid.addr) for mid in mids for _ in range(2)
        ]
        assert tree.depth() == 2
        codec = StateCodec.for_state(box_game.make_world(2).commit())
        specs = [
            _make_spec(net, ("spec", i), [leaves[i % 4].addr], codec)
            for i in range(12)
        ]
        witnesses = [
            _make_spec(net, ("wit", i), [ROOT], codec) for i in range(3)
        ]
        everyone = specs + witnesses
        WARM, SETTLE, FRAMES = 60, 60, 240
        lag, root_lag, link_lag = [], [], []
        for tick in range(FRAMES):
            net.advance(FPS_DT)
            for peer in peers:
                sup_step(net, peer, scripted_input)
            pub.publish(net.now)
            # Pump after publish: a deployed tree pumps far faster than
            # the frame loop, and pumping first would add one whole frame
            # of the harness's own quantization to every tier sample.
            tree.pump(net.now)
            if tick < WARM:
                continue
            for spec in everyone:  # tick WARM is the cold-join burst
                spec.poll(net.now)
            if tick >= WARM + SETTLE:
                head = pub._prev_frame
                lag += [max(0, head - s.current_frame) for s in specs]
                root_lag += [
                    max(0, head - w.current_frame) for w in witnesses
                ]
                link_lag += list(tree.tier_lag().values())
        assert pub.published_frames > 150
        added = (
            np.percentile(lag, 99) - np.percentile(root_lag, 99)
        ) / tree.depth()
        assert np.percentile(root_lag, 99) <= 2
        assert added <= 2
        assert max(link_lag) <= 2

        rows = {r["relay"]: r for r in tree.topology_rows()}
        for leaf in leaves:
            row = rows[repr(leaf.addr)]
            assert row["subscribers"] == 3
            assert row["cache_misses"] == 1 and row["cache_hits"] >= 2

        for _ in range(60):  # the match is over: the head is fixed
            net.advance(FPS_DT)
            tree.pump(net.now)
            for session, _, _, _ in peers:
                session.poll_remote_clients()
            pub.publish(net.now)
            for spec in everyone:
                spec.poll(net.now)
        for spec in everyone:
            assert spec.current_frame == pub._prev_frame
            assert spec.state_bytes == pub._prev

    def test_topology_rows_and_report_section(self):
        """topology_rows feeds the ops report's tree section."""
        net = LoopbackNetwork()
        tree, mid_nodes, _, _, _ = _tree_fixture(net, mids=2)
        tree.add_relay(parent=mid_nodes[0].addr)
        rows = tree.topology_rows()
        assert len(rows) == 4
        assert [r["tier"] for r in rows] == [0, 1, 1, 2]
        assert rows[0]["parent"] == "" and rows[3]["alive"]
        from bevy_ggrs_tpu.obs.report import build_report

        html = build_report(relay_tree=rows, title="tree test")
        assert "Relay tree" in html and "tier 2" in html
        # Empty trees render a placeholder, not a broken table.
        assert "no relay-tree members" in build_report(relay_tree=[])


# ---------------------------------------------------------------------------
# Shared-keyframe cache
# ---------------------------------------------------------------------------


def _relay_with_stream(data=b"\x55" * 2600, frame=40, **kw):
    """RelayServer + an ingested chunked keyframe (no match needed)."""
    sock = FakeSocket(addr=("relay", 9))
    relay = RelayServer(sock, clock=lambda: 0.0, metrics=Metrics(), **kw)
    for raw in _kf_raws(frame, data):
        assert relay.ingest(SESSION, raw)
    return relay, sock, payload_digest(data)


def _cold_join(relay, addr, now=0.0):
    relay.socket.inbox.append(
        (addr, proto.encode(proto.Subscribe(SESSION, NULL_FRAME, 8)))
    )
    relay.pump(now)


class TestSharedKeyframeCache:
    def test_n_cold_joins_one_upstream_encode(self):
        """Satellite acceptance: N cold joins inside one keyframe
        interval cost exactly ONE upstream encode (the periodic publish
        that produced the keyframe) — the relay re-serves it from the
        content-addressed cache, never asking upstream again."""
        net = LoopbackNetwork()
        relay = RelayServer(
            net.socket(ROOT), clock=lambda: net.now, metrics=Metrics(),
        )
        a = make_relay_peer(net, 2, 0, [ROOT])
        b = make_relay_peer(net, 2, 1, [ROOT])
        pub = StatePublisher(
            a[0], a[1], socket=a[0].socket, keyframe_interval=10,
        )
        encodes = [0]
        for _ in range(140):
            net.advance(FPS_DT)
            relay.pump(net.now)
            for peer in (a, b):
                sup_step(net, peer, scripted_input)
            pub.publish(net.now)
            if pub.codec is not None and not hasattr(pub.codec, "_counted"):
                orig = pub.codec.encode

                def counting_encode(state, _orig=orig):
                    encodes[0] += 1
                    return _orig(state)

                pub.codec.encode = counting_encode
                pub.codec._counted = True
        assert pub.published_frames > 60
        assert relay.stream_latest_keyframe(SESSION) is not None

        # Freeze the match: from here, any upstream encode would be
        # join-driven — the witness the cache must keep at zero.
        codec = StateCodec.for_state(box_game.make_world(2).commit())
        n = 6
        specs = [
            _make_spec(net, ("cold", i), [ROOT], codec) for i in range(n)
        ]
        encodes_before = encodes[0]
        for _ in range(30):
            net.advance(FPS_DT)
            relay.pump(net.now)
            for session, _, _, _ in (a, b):
                session.poll_remote_clients()
            for spec in specs:
                spec.poll(net.now)

        assert encodes[0] == encodes_before  # ONE-encode witness
        for spec in specs:
            assert spec.state_bytes is not None
            assert spec.current_frame >= relay.stream_latest_keyframe(SESSION)
        c = relay.metrics.counters
        assert c["keyframe_cache_misses"] == 1  # first serve populates
        assert c["keyframe_cache_hits"] >= n - 1  # the rest are cache hits
        assert relay.keyframe_cache.hits >= n - 1

    def test_cache_invalidated_on_stream_epoch_change(self):
        relay, sock, digest = _relay_with_stream()
        _cold_join(relay, ("s", 0))
        assert len(relay.keyframe_cache) == 1 and digest in relay.keyframe_cache
        relay.reset_stream(SESSION)
        assert len(relay.keyframe_cache) == 0
        assert relay.metrics.counters["fanout_stream_resets"] == 1
        # A fresh stream instance repopulates cleanly.
        new = b"\xaa" * 2600
        for raw in _kf_raws(50, new):
            relay.ingest(SESSION, raw)
        _cold_join(relay, ("s", 1))
        assert payload_digest(new) in relay.keyframe_cache
        assert digest not in relay.keyframe_cache

    def test_corrupt_cached_entry_refused_by_digest_and_refetched(self):
        relay, sock, digest = _relay_with_stream()
        _cold_join(relay, ("s", 0))  # miss + populate
        assert relay.metrics.counters["keyframe_cache_misses"] == 1
        # Flip a byte inside the cached raw datagram: the next lookup
        # must refuse it (per-chunk crc / digest), purge, and rebuild
        # from the intact stream buffer.
        entry = relay.keyframe_cache._entries[digest]
        raw0 = bytearray(entry["chunks"][0])
        raw0[-1] ^= 0xFF
        entry["chunks"][0] = bytes(raw0)
        sent_before = len(sock.sent)
        _cold_join(relay, ("s", 1))
        assert relay.keyframe_cache.corrupt == 1
        assert relay.metrics.counters["keyframe_cache_corrupt"] == 1
        # The join was still served — with the CORRECT bytes.
        served = [
            proto.decode(d) for d, addr in sock.sent[sent_before:]
            if addr == ("s", 1)
        ]
        kfs = [m for m in served if isinstance(m, proto.StreamKeyframe)]
        assert kfs and payload_digest(
            b"".join(m.payload for m in sorted(kfs, key=lambda m: m.seq))
        ) == digest
        # And the cache healed: the rebuilt entry validates again.
        assert relay.keyframe_cache.lookup(digest) is not None
        assert relay.keyframe_cache.corrupt == 1  # no new corruption

    def test_cache_capacity_fifo_eviction(self):
        from bevy_ggrs_tpu.relay.server import KeyframeCache

        cache = KeyframeCache(capacity=2)
        for i, data in enumerate([b"a" * 40, b"b" * 40, b"c" * 40]):
            cache.put(payload_digest(data), i, _kf_raws(i, data))
        assert len(cache) == 2
        assert payload_digest(b"a" * 40) not in cache
        assert cache.lookup(payload_digest(b"c" * 40)) is not None


# ---------------------------------------------------------------------------
# Chain-aware warm resume (the relay-swap keyframe fix)
# ---------------------------------------------------------------------------


class TestWarmFailoverResume:
    def test_warm_swap_costs_zero_keyframe_bytes(self):
        """Satellite fix pin: a spectator bounces mid0 -> mid1 -> mid0.
        While it is away, mid0's stale entry degrades to KEYFRAME_ONLY;
        on return its delta chain is still contiguous, so the resume
        must promote straight back to FULL — bytes-on-wire shows ZERO
        keyframe bytes after the swap settles."""
        net = LoopbackNetwork()
        tree, (mid0, mid1), _, peers, pub = _tree_fixture(net, mids=2)
        codec = StateCodec.for_state(box_game.make_world(2).commit())
        spec_metrics = Metrics()
        spec = _make_spec(
            net, ("spec", 0), [mid0.addr], codec, metrics=spec_metrics,
        )

        def run(ticks):
            for _ in range(ticks):
                net.advance(FPS_DT)
                tree.pump(net.now)
                for peer in peers:
                    sup_step(net, peer, scripted_input)
                pub.publish(net.now)
                spec.poll(net.now)

        run(140)  # warm up on mid0
        assert spec.state_bytes is not None
        assert mid0.server.subscriber_mode(("spec", 0)) == MODE_FULL

        spec.retarget([mid1.addr])  # swap away; mid0 entry goes stale
        run(35)
        assert spec.frames_behind() <= 8  # warm on mid1 too
        # The stale mid0 entry degraded while the spectator was away —
        # exactly the rung the chain-aware resume must clear.
        assert mid0.server.subscriber_mode(("spec", 0)) == MODE_KEYFRAME

        spec.retarget([mid0.addr])  # swap back
        # One tick flushes the in-flight keyframe spam the stale entry
        # sent BEFORE the re-subscribe landed; everything after this
        # snapshot is post-resume traffic — the bytes being pinned.
        run(1)
        kf_bytes = spec_metrics.counters["stream_keyframe_bytes_received"]
        delta_bytes = spec_metrics.counters["stream_delta_bytes_received"]
        run(45)
        assert spec_metrics.counters["stream_keyframe_bytes_received"] == \
            kf_bytes, "warm swap-back re-requested a keyframe"
        assert spec_metrics.counters["stream_delta_bytes_received"] > \
            delta_bytes  # the chain kept flowing
        assert mid0.server.metrics.counters["fanout_resumed_warm"] >= 1
        assert mid0.server.subscriber_mode(("spec", 0)) == MODE_FULL

        # And the resumed stream is still bitwise exact.
        for _ in range(30):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for session, _, _, _ in peers:
                session.poll_remote_clients()
            pub.publish(net.now)
            spec.poll(net.now)
        assert spec.current_frame == pub._prev_frame
        assert spec.state_bytes == pub._prev


# ---------------------------------------------------------------------------
# KEYFRAME_ONLY parent propagation
# ---------------------------------------------------------------------------


class TestKeyframeOnlyParentPropagation:
    def test_degraded_parent_does_not_break_child_chains(self):
        """An ack partition on the uplink degrades the ROOT's view of
        the tier link to KEYFRAME_ONLY. The child keeps ingesting the
        keyframes, its own subscribers re-seed from them (epoch-style),
        and after the heal both ladders recover to FULL — bitwise
        throughout."""
        net = LoopbackNetwork()
        uplink_addr = ((("relay", 1)), "uplink")
        plan = ChaosPlan(31, (Partition(1.5, 2.5, src=uplink_addr),))

        def factory(addr):
            sock = net.socket(addr)
            if addr == uplink_addr:
                return ChaosSocket(
                    sock, plan, clock=lambda: net.now, addr=addr
                )
            return sock

        tree, (mid0,), _, peers, pub = _tree_fixture(
            net, mids=1, socket_factory=factory,
            server_kwargs=dict(degrade_after=8, shed_after=5.0),
        )
        codec = StateCodec.for_state(box_game.make_world(2).commit())
        spec = _make_spec(net, ("spec", 0), [mid0.addr], codec)
        root_srv = tree.node(ROOT).server

        link_modes, kf_in_window = set(), [0]
        for _ in range(260):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for peer in peers:
                sup_step(net, peer, scripted_input)
            pub.publish(net.now)
            before = spec.keyframes_applied
            spec.poll(net.now)
            if 1.5 < net.now < 2.5:
                m = root_srv.subscriber_mode(uplink_addr)
                if m is not None:
                    link_modes.add(m)
                kf_in_window[0] += spec.keyframes_applied - before

        # The root degraded the LINK, not just a spectator...
        assert MODE_KEYFRAME in link_modes
        assert root_srv.metrics.counters["fanout_degraded"] >= 1
        # ...and the child's subscriber survived ON keyframes that the
        # link kept ingesting (no silent chain break).
        assert kf_in_window[0] >= 1
        assert mid0.server.metrics.counters["fanout_degraded"] >= 1

        # Post-heal: both tiers recovered and the leaf converges.
        for _ in range(40):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for session, _, _, _ in peers:
                session.poll_remote_clients()
            pub.publish(net.now)
            spec.poll(net.now)
        assert root_srv.subscriber_mode(uplink_addr) == MODE_FULL
        assert root_srv.metrics.counters["fanout_recovered"] >= 1
        assert spec.current_frame == pub._prev_frame
        assert spec.state_bytes == pub._prev


# ---------------------------------------------------------------------------
# Mid-tier kill soak: re-home ladder under loss/reorder
# ---------------------------------------------------------------------------


class TestRelayTreeKillSoak:
    def test_midtier_kill_rehomes_zero_desync_bounded_resume(self):
        """Acceptance soak: a scripted RelayTreeKill takes out mid0
        (which owns a tier-2 child relay and direct spectators) under
        spectator loss + reorder. The orphaned child re-homes to the
        sibling (ladder rung 1), spectators re-home client-side with
        their cursors, a replacement relay spawns after the window —
        zero desync, every spectator resumes within 8 frames, bitwise
        exact at the end."""
        net = LoopbackNetwork()
        tree, (mid0, mid1), leaf, peers, pub = _tree_fixture(
            net, mids=2, server_kwargs=dict(shed_after=5.0),
        )
        leaf = tree.add_relay(parent=mid0.addr)
        plan = ChaosPlan(91, (
            Reorder(1.0, 2.2, 0.2, delay=0.03),
            RelayTreeKill(3.0, mid0.addr, 0.5),
        ))
        spec_plan = ChaosPlan(92, (LossBurst(1.2, 2.4, 0.25),))
        kill = plan.relay_tree_kills()[0]
        assert kill.relay == mid0.addr

        codec = StateCodec.for_state(box_game.make_world(2).commit())
        specs = []
        for i, target in enumerate([mid0.addr, leaf.addr, mid1.addr]):
            inner = net.socket(("spec", i))
            sock = ChaosSocket(
                inner, spec_plan, clock=lambda: net.now, addr=("spec", i)
            )
            specs.append(StreamSpectator(
                sock, relays=[target], session_id=SESSION, window=8,
                codec=codec, clock=lambda: net.now, resub_timeout=0.6,
                metrics=Metrics(),
            ))

        killed = respawned = False
        rehomed = []
        events = []
        for _ in range(int(6.5 / FPS_DT)):
            net.advance(FPS_DT)
            if not killed and net.now >= kill.at:
                rehomed = tree.kill(mid0.addr)
                killed = True
                # Client-side re-home: the dead relay's spectators move
                # to where their subtree went (the ladder target).
                specs[0].retarget([mid1.addr], now=net.now)
            if killed and not respawned and net.now >= kill.at + kill.down_for:
                assert tree.spawn_relay()  # elastic replacement
                respawned = True
            tree.pump(net.now)
            for peer in peers:
                sup_step(net, peer, scripted_input, events)
            pub.publish(net.now)
            for spec in specs:
                spec.poll(net.now)

        # Drain to the stream head.
        for _ in range(30):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for session, _, _, _ in peers:
                session.poll_remote_clients()
            pub.publish(net.now)
            for spec in specs:
                spec.poll(net.now)

        # CI forensics land BEFORE the assertions (ops report includes
        # the tree topology section).
        obs_dir = os.environ.get("GGRS_OBS_DIR")
        if obs_dir:
            os.makedirs(obs_dir, exist_ok=True)
            from bevy_ggrs_tpu.obs.report import build_report

            build_report(
                os.path.join(obs_dir, "relay_tree_soak.html"),
                title="relay tree kill soak",
                relay_tree=tree.topology_rows(),
                notes=f"plan seed 91; kill at {kill.at}s",
            )
            with open(os.path.join(obs_dir, "relay_tree_soak.json"), "w") as f:
                json.dump({
                    "plan": json.loads(plan.to_json()),
                    "tree_events": [
                        {k: repr(v) for k, v in e.items()}
                        for e in tree.events
                    ],
                    "spectators": [
                        {"frame": s.current_frame,
                         "behind": s.frames_behind(),
                         "keyframe_bytes": s.metrics.counters[
                             "stream_keyframe_bytes_received"],
                         } for s in specs
                    ],
                }, f, indent=2)

        # --- topology: the ladder re-homed the orphaned subtree -------
        assert killed and respawned
        assert rehomed == [leaf.addr]
        assert leaf.parent == mid1.addr and leaf.tier == 2
        assert leaf.link.retargets == 1
        kinds = [e["event"] for e in tree.events]
        assert "kill" in kinds and "rehome" in kinds and kinds.count("spawn") == 5

        # --- match plane: untouched by the fan-out tier death ---------
        assert not any(e.kind == EventKind.DESYNC_DETECTED for e in events)
        assert not any(e.kind == EventKind.DISCONNECTED for e in events)
        for session, _, _, _ in peers:
            assert session.current_state() == SessionState.RUNNING
        frames, rows = settled_checksums([p[0] for p in peers])
        assert len(frames) >= 3
        for f, row in zip(frames, rows):
            assert len(set(row)) == 1, f"frame {f} desynced"

        # --- spectators: bounded resume, bitwise exact ----------------
        RESUME_BOUND = 8  # frames — THE acceptance bound
        for i, spec in enumerate(specs):
            assert spec.state_bytes is not None
            assert spec.frames_behind() <= RESUME_BOUND, (
                f"spec {i} is {spec.frames_behind()} frames behind"
            )
            assert spec.current_frame == pub._prev_frame
            assert spec.state_bytes == pub._prev, f"spec {i} diverged"


# ---------------------------------------------------------------------------
# Relay-tier autopilot elasticity
# ---------------------------------------------------------------------------


def _sample(rid, tier=1, parent=0, subs=0, cap=4, alive=True, draining=False):
    return RelaySample(
        relay_id=rid, tier=tier, parent_id=parent, subscribers=subs,
        capacity=cap, alive=alive, draining=draining,
    )


class TestRelayPolicy:
    def test_scale_up_needs_confirm_streak(self):
        pol = RelayPolicy(RelayAutopilotConfig(confirm_beats=3))
        obs = lambda t: RelayObservation(t, {1: _sample(1, subs=4)})
        assert pol.decide(obs(0)) == []
        assert pol.decide(obs(1)) == []
        acts = pol.decide(obs(2))
        assert [a.kind for a in acts] == ["relay_spawn"]

    def test_orphan_rehomes_to_closest_live_tier_once(self):
        pol = RelayPolicy()
        relays = {
            1: _sample(1, tier=1, parent=0, subs=1),
            2: _sample(2, tier=2, parent=9, subs=1, alive=False),  # orphan
        }
        acts = pol.decide(RelayObservation(0, relays))
        assert [a.kind for a in acts] == ["relay_rehome"]
        assert acts[0].server_id == 2 and acts[0].dst_id == 1
        # One action per orphan per episode.
        assert pol.decide(RelayObservation(1, relays)) == []

    def test_rehome_refused_once_when_no_target(self):
        pol = RelayPolicy()
        relays = {2: _sample(2, tier=1, parent=9, subs=1, alive=False)}
        acts = pol.decide(RelayObservation(0, relays))
        assert [a.kind for a in acts] == ["refuse"]
        assert pol.decide(RelayObservation(1, relays)) == []

    def test_drain_retire_scale_down_arc(self):
        cfg = RelayAutopilotConfig(
            confirm_beats=1, cooldown_scale_ticks=0, min_relays=1,
        )
        pol = RelayPolicy(cfg)
        two_idle = {
            1: _sample(1, subs=0), 2: _sample(2, subs=0),
        }
        acts = pol.decide(RelayObservation(0, two_idle))
        assert [a.kind for a in acts] == ["relay_drain"]
        assert acts[0].server_id == 2  # emptiest; newest id on ties
        draining = {
            1: _sample(1, subs=0, draining=True), 2: _sample(2, subs=0),
        }
        acts = pol.decide(RelayObservation(1, draining))
        assert [a.kind for a in acts] == ["relay_retire"]


class TestRelayAutopilotArc:
    def _drive(self, net, tree, peers, pub, pilot, subs, ticks, t0=0):
        for t in range(t0, t0 + ticks):
            net.advance(FPS_DT)
            tree.pump(net.now)
            for peer in peers:
                sup_step(net, peer, scripted_input)
            pub.publish(net.now)
            for s in subs:
                s.poll(net.now)
            pilot.step(t)
        return t0 + ticks

    def test_spawn_fanout_drain_arc_replays_identically(self, tmp_path):
        """The whole elastic arc against a REAL in-process tree: load
        pushes fill over the high watermark -> spawn; load leaves ->
        drain -> retire; and the JSONL ledger replays bit-identically
        through a fresh policy (the determinism contract)."""
        net = LoopbackNetwork()
        tree, (mid0,), _, peers, pub = _tree_fixture(
            net, mids=1, max_depth=1,
            server_kwargs=dict(shed_after=0.4),
        )
        tree.fanout_capacity = 2
        pilot = RelayAutopilot(
            tree,
            RelayAutopilotConfig(
                high_watermark=0.8, low_watermark=0.4, confirm_beats=3,
                cooldown_scale_ticks=10, min_relays=1, max_relays=3,
            ),
            metrics=Metrics(),
        )
        codec = StateCodec.for_state(box_game.make_world(2).commit())
        specs = [
            _make_spec(net, ("load", i), [mid0.addr], codec)
            for i in range(2)
        ]
        t = self._drive(net, tree, peers, pub, pilot, specs, 80)
        assert pilot.counts.get("relay_spawn", 0) >= 1  # fill 1.0 >= 0.8
        assert len(tree.live_relays()) >= 3  # root + mid0 + spawned

        # Load leaves: subscribers stop polling, shed after 0.4s, fill
        # drops to zero -> drain the emptiest -> retire it once empty.
        t = self._drive(net, tree, peers, pub, pilot, [], 120, t0=t)
        assert pilot.counts.get("relay_drain", 0) >= 1
        assert pilot.counts.get("relay_retire", 0) >= 1
        assert len([
            a for a in tree.live_relays() if a != ROOT
        ]) < 2 + pilot.counts["relay_spawn"]

        # The arc is a replayable artifact.
        path = str(tmp_path / "relay_ledger.jsonl")
        n = pilot.export_jsonl(path)
        assert n == t
        ok, ticks = verify_relay_ledger(path)
        assert ok and ticks == t
        kinds = {a.kind for a in pilot.actions}
        assert {"relay_spawn", "relay_drain", "relay_retire"} <= kinds

    def test_ledger_divergence_detected(self, tmp_path):
        tree_like = _ScriptedRelayFleet([
            {1: _sample(1, subs=4)} for _ in range(4)
        ])
        pilot = RelayAutopilot(
            tree_like, RelayAutopilotConfig(confirm_beats=2),
        )
        for t in range(4):
            pilot.step(t)
        path = str(tmp_path / "tampered.jsonl")
        pilot.export_jsonl(path)
        lines = open(path).read().splitlines()
        rec = json.loads(lines[2])
        rec["actions"] = []  # erase the recorded spawn
        lines[2] = json.dumps(rec)
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        ok, _ = verify_relay_ledger(path)
        assert not ok

    def test_cli_routes_relay_ledgers(self, tmp_path):
        from bevy_ggrs_tpu.fleet.autopilot import _ledger_kind, _main

        tree_like = _ScriptedRelayFleet([
            {1: _sample(1, subs=4)} for _ in range(3)
        ])
        pilot = RelayAutopilot(
            tree_like, RelayAutopilotConfig(confirm_beats=2),
        )
        for t in range(3):
            pilot.step(t)
        path = str(tmp_path / "relay.jsonl")
        pilot.export_jsonl(path)
        recs = [json.loads(line) for line in open(path)]
        assert _ledger_kind(recs) == "relay"
        assert _main([path]) == 0


class _ScriptedRelayFleet:
    """Adapter returning scripted samples; executors always succeed."""

    def __init__(self, script):
        self.script = list(script)
        self.i = 0

    def relay_samples(self):
        s = self.script[min(self.i, len(self.script) - 1)]
        self.i += 1
        return dict(s)

    def spawn_relay(self):
        return True

    def drain_relay(self, rid):
        return True

    def retire_relay(self, rid):
        return True

    def rehome(self, rid, dst):
        return True


# ---------------------------------------------------------------------------
# Plan stability (satellite: RelayTreeKill drawn LAST)
# ---------------------------------------------------------------------------


class TestRelayTreePlanStability:
    def test_relay_tree_kill_drawn_last_prefix_byte_stable(self):
        """Adding the relay_tree domain must append exactly one
        RelayTreeKill AFTER every existing draw: a seed's pre-tree plan
        stays byte-identical (the pinned replay-artifact contract)."""
        kw = dict(
            peers=(("peer", 0), ("peer", 1)), kill_restart=True,
            relay=("relay", 0), fleet=(1, 2), fleet_matches=3,
            elastic=True, control=True, sdc=True,
        )
        base = ChaosPlan.generate(40, 9.0, **kw)
        tree = ChaosPlan.generate(
            40, 9.0, relay_tree=(("relay", 1), ("relay", 2)), **kw
        )
        assert tree.directives[: len(base.directives)] == base.directives
        extra = tree.directives[len(base.directives):]
        assert len(extra) == 1 and isinstance(extra[0], RelayTreeKill)
        assert extra[0].relay in (("relay", 1), ("relay", 2))
        assert base.to_json() == ChaosPlan.generate(40, 9.0, **kw).to_json()

    def test_relay_tree_kill_json_roundtrip_and_horizon(self):
        plan = ChaosPlan.generate(
            41, 8.0, peers=(("peer", 0),),
            relay_tree=(("relay", 1),),
        )
        kills = plan.relay_tree_kills()
        assert len(kills) == 1 and kills[0].relay == ("relay", 1)
        back = ChaosPlan.from_json(plan.to_json())
        assert back == plan
        assert back.relay_tree_kills()[0].relay == ("relay", 1)
        assert plan.horizon() >= kills[0].at + kills[0].down_for
        # Hand-built plans roundtrip too (address tuple normalization).
        manual = ChaosPlan(5, (RelayTreeKill(1.0, ("relay", 3), 0.25),))
        assert ChaosPlan.from_json(manual.to_json()) == manual


# ---------------------------------------------------------------------------
# Subprocess relay tier over real UDP
# ---------------------------------------------------------------------------


class TestProcRelayTier:
    def test_subprocess_relay_streams_and_drains(self, tmp_path):
        """One subprocess relay child under an in-process root, real UDP
        both hops: the child's TierLink subscribes up, a UDP spectator
        subscribes down, and the injected stream arrives bitwise. Then
        the drain command flips the child's status beat."""
        import time

        from bevy_ggrs_tpu.transport.udp import UdpSocket

        use_native = os.environ.get("GGRS_NO_NATIVE", "") != "1"
        root_sock = UdpSocket(0, host="127.0.0.1", use_native=use_native)
        root = RelayServer(root_sock, metrics=Metrics())
        state = bytes(range(256)) * 12  # 3 chunks
        for raw in _kf_raws(30, state):
            root.ingest(0, raw)

        tier = ProcRelayTier(
            ("127.0.0.1", root_sock.local_port()),
            base_config={"status_interval_s": 0.1},
            stderr_dir=str(tmp_path),
        )
        try:
            rid = tier.spawn_relay(timeout=60.0)
            assert rid is not None, "child never reported ready"
            child_addr = tier.addr_of(rid)
            spec_sock = UdpSocket(0, host="127.0.0.1", use_native=use_native)
            spec = StreamSpectator(
                spec_sock, relays=[child_addr], session_id=0,
                resub_timeout=2.0,
            )
            deadline = time.monotonic() + 30.0
            while spec.state_bytes is None and time.monotonic() < deadline:
                root.pump()
                spec.poll()
                time.sleep(0.01)
            assert spec.state_bytes == state  # bitwise through 2 UDP hops
            assert spec.current_frame == 30

            samples = tier.relay_samples()
            assert rid in samples and samples[rid].alive
            assert tier.drain_relay(rid)
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                tier.poll()
                if tier.relay_samples()[rid].draining:
                    break
                time.sleep(0.05)
            assert tier.relay_samples()[rid].draining
            spec_sock.close()
        finally:
            tier.close()
            root.close()
        assert [e["event"] for e in tier.events][:2] == ["spawn", "drain"]
