"""Learned input prediction (predict/): the determinism contract.

Four layers, each with its own witness:

- **Artifact** — canonical bytes (no container metadata), a content hash
  stable across saves, processes, and platforms, and typed refusal of
  foreign/truncated/trailing bytes.
- **Handshake** — the resolved predictor's content hash is the session
  config digest; a digest-mismatched peer pair never synchronizes and
  surfaces one typed ``CONFIG_MISMATCH`` event per endpoint (never a
  desync).
- **Trees** — predictor-seeded branch trees are bitwise identical
  between the native C++ builder and the pure-Python fallback, keep
  branch 0 repeat-last, and change the dedup signature; the batched
  session-axis ranker matches the host rollout element-for-element.
- **Sessions** — a predictor-ON peer pair is wire-bitwise invisible
  (identical non-handshake datagrams and confirmed checksums vs the
  predictor-OFF run of the same script), and predictor OFF is bitwise
  identical to an unconfigured runner.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from bevy_ggrs_tpu.branch_tree import BranchTree
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.native import spec as native_spec
from bevy_ggrs_tpu.predict import (
    DEFAULT_ARTIFACT,
    InputPredictor,
    PredictorWeights,
    load_artifact,
    load_default,
    resolve_predictor,
    resolve_predictor_config,
    save_artifact,
)
from bevy_ggrs_tpu.schedule import InputSpec
from bevy_ggrs_tpu.session import (
    EventKind,
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from bevy_ggrs_tpu.session import protocol as proto
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

from tests.test_p2p import (
    FPS_DT,
    common_confirmed_checksums,
    scripted_input,
)

UNIVERSE = list(range(16))
MAXPRED = 8


# --------------------------------------------------------------------------
# Artifact determinism
# --------------------------------------------------------------------------


class TestArtifact:
    def test_canonical_bytes_roundtrip(self, tmp_path):
        w = load_default()
        data = w.to_bytes()
        # Committed artifact == canonical bytes of its own weights: the
        # file carries nothing (timestamps, container metadata) beyond
        # the canonical string.
        with open(DEFAULT_ARTIFACT, "rb") as f:
            assert f.read() == data
        # save -> load -> save is byte-stable.
        p1, p2 = str(tmp_path / "a.ggrspred"), str(tmp_path / "b.ggrspred")
        save_artifact(w, p1)
        save_artifact(load_artifact(p1), p2)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read() == data

    def test_content_hash_stable_across_processes(self):
        """The wire digest must not depend on process state (hash
        randomization, import order, caches) — re-derive it in a fresh
        interpreter and compare."""
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-c",
             "from bevy_ggrs_tpu.predict import load_default;"
             "print(load_default().content_hash)"],
            capture_output=True, text=True, env=env, check=True,
        )
        assert int(out.stdout.strip()) == load_default().content_hash

    def test_hash_tracks_weight_bytes(self, tmp_path):
        w = load_default()
        w1 = np.array(w.w1, copy=True)
        w1[0, 0] = np.int8(int(w1[0, 0]) ^ 1)
        perturbed = PredictorWeights(
            w.weight_version, w.window, w.value_slots, w.phase_mod,
            w.hidden, w.shift, w1, w.b1, w.w2, w.b2,
        )
        assert perturbed.content_hash != w.content_hash
        p = str(tmp_path / "p.ggrspred")
        save_artifact(perturbed, p)
        assert load_artifact(p).content_hash == perturbed.content_hash

    def test_typed_refusal_of_bad_bytes(self, tmp_path):
        data = load_default().to_bytes()
        with pytest.raises(ValueError, match="not a GGRSPRED"):
            PredictorWeights.from_bytes(b"XXXXXXXX" + data[8:])
        with pytest.raises(ValueError, match="truncated"):
            PredictorWeights.from_bytes(data[:-4])
        with pytest.raises(ValueError, match="trailing"):
            PredictorWeights.from_bytes(data + b"\x00")

    def test_resolve_config_env_semantics(self, monkeypatch):
        monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
        assert resolve_predictor_config(None) is None
        for off in ("0", "off", "false"):
            monkeypatch.setenv("GGRS_PREDICTOR", off)
            assert resolve_predictor_config(None) is None
        monkeypatch.setenv("GGRS_PREDICTOR", "1")
        ip = resolve_predictor_config(None)
        assert isinstance(ip, InputPredictor)
        assert ip.content_hash == load_default().content_hash
        # False forces OFF even when the env says on.
        assert resolve_predictor_config(False) is None
        monkeypatch.setenv("GGRS_PREDICTOR", DEFAULT_ARTIFACT)
        assert (resolve_predictor_config(None).content_hash
                == load_default().content_hash)
        with pytest.raises(TypeError):
            resolve_predictor_config(3.14)


# --------------------------------------------------------------------------
# Handshake refusal
# --------------------------------------------------------------------------


def _p2p_builder(me, predictor):
    builder = (
        SessionBuilder(box_game.INPUT_SPEC)
        .with_num_players(2)
        .with_max_prediction_window(MAXPRED)
    )
    if predictor is not None:
        builder.with_input_predictor(predictor)
    for h in range(2):
        if h == me:
            builder.add_player(PlayerType.local(), h)
        else:
            builder.add_player(PlayerType.remote(("peer", h)), h)
    return builder


class TestHandshake:
    def test_digest_mismatch_is_typed_refusal(self, monkeypatch):
        """ON host vs OFF peer: neither synchronizes, both surface one
        CONFIG_MISMATCH event carrying the two digests — no desync, no
        progress."""
        monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
        net = LoopbackNetwork()
        sessions = [
            _p2p_builder(0, True).start_p2p_session(
                net.socket(("peer", 0)), clock=lambda: net.now
            ),
            _p2p_builder(1, False).start_p2p_session(
                net.socket(("peer", 1)), clock=lambda: net.now
            ),
        ]
        events = []
        for _ in range(120):
            net.advance(FPS_DT)
            for s in sessions:
                s.poll_remote_clients()
                events.extend(s.events())
        for s in sessions:
            assert s.current_state() != SessionState.RUNNING
        mismatches = [e for e in events
                      if e.kind == EventKind.CONFIG_MISMATCH]
        assert mismatches, "refusal never surfaced as a typed event"
        digest = load_default().content_hash
        for e in mismatches:
            assert {e.data["local_digest"], e.data["peer_digest"]} == {
                0, digest,
            }
        assert not any(e.kind == EventKind.DESYNC_DETECTED for e in events)

    def test_matching_digests_synchronize(self, monkeypatch):
        monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
        net = LoopbackNetwork()
        sessions = [
            _p2p_builder(me, True).start_p2p_session(
                net.socket(("peer", me)), clock=lambda: net.now
            )
            for me in range(2)
        ]
        for _ in range(30):
            net.advance(FPS_DT)
            for s in sessions:
                s.poll_remote_clients()
                s.events()
        assert all(
            s.current_state() == SessionState.RUNNING for s in sessions
        )

    def test_builder_digest_resolution(self, monkeypatch):
        monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
        b = SessionBuilder(box_game.INPUT_SPEC)
        assert b._config_digest() == 0
        b.with_input_predictor(True)
        assert b._config_digest() == load_default().content_hash
        b.with_input_predictor(False)
        assert b._config_digest() == 0
        with pytest.raises((TypeError, OSError, ValueError)):
            b.with_input_predictor("/nonexistent/weights.ggrspred")


# --------------------------------------------------------------------------
# Seeded branch trees: native vs Python, batched vs host
# --------------------------------------------------------------------------


@pytest.mark.skipif(not ncore.available(), reason="native core unavailable")
def test_seeded_tree_native_python_parity():
    """Randomized: predictor-seeded trees agree bitwise between builders,
    the seed changes the dedup signature, the seeded signature dedup-skips,
    and branch 0 stays literal repeat-last."""
    rng = np.random.RandomState(7)
    bound_cache = {}
    for trial in range(12):
        players = int(rng.choice([2, 4]))
        frames = int(rng.choice([4, 8]))
        branches = int(rng.choice([8, 64]))
        spec = InputSpec(shape=(), dtype=np.uint8, values=tuple(UNIVERSE))
        tree = BranchTree(spec, players, branches, frames, UNIVERSE)
        log = {}
        nat = native_spec.make_spec_builder(
            spec, players, branches, frames, UNIVERSE
        )
        assert nat is not None
        if players not in bound_cache:
            bound_cache[players] = InputPredictor(load_default()).bind(
                UNIVERSE, np.uint8, 1
            )
        bound = bound_cache[players]
        keys = [1, 8, 2, 0]
        n_log = int(rng.randint(0, 24))
        for f in range(n_log):
            row = np.array(
                [keys[(f // 3 + h) % 4] for h in range(players)],
                dtype=np.uint8,
            )
            if rng.rand() < 0.1:
                row = rng.randint(0, 16, size=players).astype(np.uint8)
            log[f] = row
            nat.log_set(f, row)
        anchor = n_log
        last = log.get(anchor - 1)
        if last is None:
            last = spec.zeros_np(players)
        known = np.zeros((frames, players), dtype=np.uint8)
        mask = np.zeros((frames, players), dtype=bool)
        for p in range(players):
            k = rng.randint(0, frames)
            mask[:k, p] = True
            known[:k, p] = rng.randint(0, 16, size=k)

        seed = bound.seed(log, anchor, frames, players)
        py_off = tree.structured_bits(
            log, np.asarray(last), known, mask, anchor
        )
        nb_off, sig_off = nat.build(anchor, None, known, mask, False, None)
        assert np.array_equal(py_off, nb_off)

        py_on = dataclasses.replace(tree, predictor=bound).structured_bits(
            log, np.asarray(last), known, mask, anchor
        )
        nat.seed(anchor, seed)
        nb_on, sig_on = nat.build(anchor, None, known, mask, False, None)
        assert np.array_equal(py_on, nb_on)
        if n_log > 0:
            assert sig_on != sig_off  # the seed is part of tree identity
        # Seeded dedup skip: same seed + same signature -> no rebuild.
        nat.seed(anchor, seed)
        nb2, sig2 = nat.build(anchor, None, known, mask, True, sig_on)
        assert nb2 is None and sig2 == sig_on
        # Branch 0 repeat-last survives seeding, in both builders.
        assert np.array_equal(py_on[0], py_off[0])
        assert np.array_equal(nb_on[0], py_off[0])


def test_batched_ranker_matches_host_rollout():
    from bevy_ggrs_tpu.predict.batch import BatchedRanker

    bound = InputPredictor(load_default()).bind(UNIVERSE, np.uint8, 1)
    frames, S, P = 6, 5, 2
    ranker = BatchedRanker(bound, frames)
    rng = np.random.RandomState(11)
    wins = rng.randint(-1, len(UNIVERSE), size=(S, bound.weights.window, P))
    wins = wins.astype(np.int32)
    anchors = rng.randint(0, 200, size=S).astype(np.int32)
    traj, order = ranker.rank(wins, anchors)
    V = len(UNIVERSE)
    for s in range(S):
        htraj, hlogits = bound.rollout(wins[s], int(anchors[s]), frames)
        horder = np.argsort(
            -hlogits[:, :V], axis=1, kind="stable"
        ).astype(np.int32)
        assert np.array_equal(traj[s], htraj)
        assert np.array_equal(order[s], horder)
        # The rendered seeds agree too (shared render_seed path).
        assert (bound.render_seed(traj[s], order[s]).fold_bytes()
                == bound.render_seed(htraj, horder).fold_bytes())


def test_ledger_policy_registry_has_learned():
    from bevy_ggrs_tpu.obs import ledger

    assert set(ledger.POLICIES) >= {"current", "repeat_last", "learned"}
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "spec_baseline.json")) as f:
        base = json.load(f)
    assert set(base["policies"]) >= {"current", "repeat_last", "learned"}
    for name, cfg in base["configs"].items():
        pol = cfg["policies"]
        # The committed acceptance: learned strictly above repeat-last
        # everywhere, and at least matching the live heuristic.
        assert pol["learned"]["full_hit_rate"] > (
            pol["repeat_last"]["full_hit_rate"]
        ), name
        assert pol["learned"]["full_hit_rate"] >= (
            pol["current"]["full_hit_rate"]
        ), name


# --------------------------------------------------------------------------
# Live sessions: wire invisibility + OFF identity
# --------------------------------------------------------------------------


class _RecordingSocket:
    def __init__(self, inner, tape):
        self._inner = inner
        self.tape = tape
        self.addr = inner.addr

    def send_to(self, msg, addr):
        self.tape.append(bytes(msg))
        self._inner.send_to(msg, addr)

    def receive_all(self):
        return self._inner.receive_all()

    def close(self):
        self._inner.close()


def _run_spec_pair(predictor, iters=180, latency=1.5 * FPS_DT):
    """A full predictor-configured P2P run: two spec runners, scripted
    inputs, injected latency (real rollbacks), every sent datagram
    taped. Returns (peers, tapes, events)."""
    net = LoopbackNetwork(latency=latency, seed=5)
    peers, tapes = [], []
    for me in range(2):
        tape = []
        sock = _RecordingSocket(net.socket(("peer", me)), tape)
        session = _p2p_builder(me, predictor).start_p2p_session(
            sock, clock=lambda: net.now
        )
        runner = SpeculativeRollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=MAXPRED, num_players=2,
            input_spec=box_game.INPUT_SPEC, num_branches=16, spec_frames=4,
            predictor=predictor,
        )
        peers.append((session, runner))
        tapes.append(tape)
    events = []
    for _ in range(iters):
        net.advance(FPS_DT)
        for session, runner in peers:
            session.poll_remote_clients()
            events.extend(session.events())
            if session.current_state() != SessionState.RUNNING:
                continue
            for h in session.local_player_handles():
                session.add_local_input(
                    h, scripted_input(h, session.current_frame)
                )
            try:
                requests = session.advance_frame()
            except PredictionThreshold:
                continue
            runner.handle_requests(requests, session)
            runner.speculate(session.confirmed_frame(), session)
    return peers, tapes, events


def _split_sync(tape):
    sync, rest = [], []
    for msg in tape:
        decoded = proto.decode(msg)
        if isinstance(decoded, (proto.SyncRequest, proto.SyncReply)):
            sync.append(msg)
        else:
            rest.append(msg)
    return sync, rest


@pytest.mark.slow
def test_predictor_on_wire_invisible(monkeypatch):
    """The whole point of the determinism contract: a predictor-ON pair's
    traffic is byte-identical to the OFF pair's outside the handshake
    digest, trajectories agree bitwise across ON/OFF AND across peers,
    and no desync fires — speculation internals never reach the wire."""
    monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
    on_peers, on_tapes, on_events = _run_spec_pair(True)
    off_peers, off_tapes, off_events = _run_spec_pair(False)
    for events in (on_events, off_events):
        assert not any(
            e.kind in (EventKind.DESYNC_DETECTED, EventKind.CONFIG_MISMATCH)
            for e in events
        )
    # The predictor actually ran in the ON pair.
    for _, runner in on_peers:
        assert runner._predictor is not None
        assert runner.predictor_rank_builds > 0
    for _, runner in off_peers:
        assert runner._predictor is None
    # Wire invisibility: everything but the sync handshake is
    # byte-identical in order; the handshake differs only by carrying a
    # different digest (same message count).
    for on_tape, off_tape in zip(on_tapes, off_tapes):
        on_sync, on_rest = _split_sync(on_tape)
        off_sync, off_rest = _split_sync(off_tape)
        assert on_rest == off_rest
        assert len(on_sync) == len(off_sync)
    # Bitwise trajectories: peers agree with each other and across runs.
    frames_on, pairs_on = common_confirmed_checksums(on_peers)
    frames_off, pairs_off = common_confirmed_checksums(off_peers)
    assert frames_on and all(a == b for a, b in pairs_on)
    assert frames_off and all(a == b for a, b in pairs_off)
    common = sorted(set(frames_on) & set(frames_off))
    assert common
    cs_on = dict(zip(frames_on, (a for a, _ in pairs_on)))
    cs_off = dict(zip(frames_off, (a for a, _ in pairs_off)))
    assert all(cs_on[f] == cs_off[f] for f in common)


def test_predictor_on_pair_full_hits_hold_the_repeat_last_floor(monkeypatch):
    """A learned ranking must never make live speculation worse than the
    zero-parameter baseline: a predictor-ON pair under real rollbacks
    ranks with the predictor, commits whole rollbacks speculatively, and
    its full-hit rate stands at or above the best ``repeat_last`` rate of
    the committed counterfactual table (``spec_baseline.json``)."""
    import json

    monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "spec_baseline.json")) as f:
        floor = max(
            cfg["policies"]["repeat_last"]["full_hit_rate"]
            for cfg in json.load(f)["configs"].values()
        )
    peers, _, events = _run_spec_pair(True, iters=120)
    assert not any(e.kind == EventKind.DESYNC_DETECTED for e in events)
    for _, runner in peers:
        assert runner._predictor is not None
        assert runner.predictor_rank_builds > 0
        assert runner.rollbacks_total > 0
        assert runner.spec_hits > 0
        assert runner.spec_hits / runner.rollbacks_total >= floor


def test_predictor_off_identical_to_unconfigured(monkeypatch):
    """predictor=False and a plain unconfigured runner run the same
    script to bitwise-identical state — the OFF path has zero behavioral
    surface (the pre-PR identity witness backing the CI matrix's OFF
    legs)."""
    monkeypatch.delenv("GGRS_PREDICTOR", raising=False)
    from bevy_ggrs_tpu.state import checksum, combine64

    def run(**kw):
        r = SpeculativeRollbackRunner(
            box_game.make_schedule(), box_game.make_world(2).commit(),
            max_prediction=4, num_players=2,
            input_spec=box_game.INPUT_SPEC, num_branches=8, spec_frames=3,
            **kw,
        )
        r.warmup()
        from bevy_ggrs_tpu.session.requests import (
            AdvanceFrame, LoadGameState, SaveGameState,
        )

        frame = 0
        for cycle in range(4):
            for _ in range(3):
                bits = np.array(
                    [scripted_input(h, frame) for h in range(2)], np.uint8
                )
                r.tick(
                    [SaveGameState(frame),
                     AdvanceFrame(bits=bits,
                                  status=np.zeros(2, np.int32))],
                    frame, None,
                )
                frame += 1
            # A depth-2 rollback per cycle.
            reqs = [LoadGameState(frame - 2)]
            for f in range(frame - 2, frame + 1):
                bits = np.array(
                    [scripted_input(h, f) ^ (1 if f < frame else 0)
                     for h in range(2)], np.uint8,
                )
                reqs += [SaveGameState(f),
                         AdvanceFrame(bits=bits,
                                      status=np.zeros(2, np.int32))]
            r.tick(reqs, frame, None)
            frame += 1
        return r

    plain, off = run(), run(predictor=False)
    assert plain._predictor is None and off._predictor is None
    assert plain.frame == off.frame
    assert combine64(checksum(plain.state)) == combine64(
        checksum(off.state)
    )
    assert np.array_equal(
        np.asarray(plain.ring.checksums), np.asarray(off.ring.checksums)
    )
