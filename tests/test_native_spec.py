"""Parity tests: native branch-tree builder/matcher vs the Python path.

The native speculation core (builder + matcher in
``native/session_core.cpp``, bound by ``native/spec.py``) must be
BITWISE-identical to the pure-Python path it replaces: same branch
tensors, same dedup-skip decisions, same (branch, depth) matches — the
runner commits device state based on these, so "close" is not a grade.
These tests drive both through randomized logs, rollback corrections,
malformed histories, and a full loopback session, mirroring the
``test_native_core.py`` discipline for the queue/tracker data plane.
"""

import numpy as np
import pytest

from bevy_ggrs_tpu.branch_tree import BranchTree
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.native import spec as native_spec
from bevy_ggrs_tpu.parallel.speculate import _match_branch_numpy, match_branch
from bevy_ggrs_tpu.schedule import InputSpec
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner

native = pytest.mark.skipif(
    not ncore.available(), reason="native session core did not build"
)


_DTYPES = [np.uint8, np.int8, np.uint16, np.int16, np.int32, np.int64]


def _rand_payload(rng, dtype, shape, small=True):
    info = np.iinfo(dtype)
    if small:
        lo, hi = (0, 32) if info.min == 0 else (-16, 16)
    else:
        # Wide draws exercise the int64 normalization (sign extension,
        # truncation) the native comparisons run through.
        lo = max(info.min, -(2 ** 31))
        hi = min(int(info.max), 2 ** 31 - 1)
    return rng.randint(lo, hi + 1, size=shape).astype(dtype)


def _rand_case(rng):
    players = int(rng.choice([2, 4, 8]))
    dtype = np.dtype(_DTYPES[rng.randint(len(_DTYPES))])
    shape = () if rng.rand() < 0.7 else (2,)
    frames = int(rng.choice([4, 8, 12]))
    branches = int(rng.choice([1, 8, 64]))
    n_uni = int(rng.choice([0, 4, 16]))
    values = tuple(
        int(v) for v in np.unique(_rand_payload(
            rng, dtype, (n_uni,), small=bool(rng.rand() < 0.8)
        ))
    ) if n_uni else ()
    spec = InputSpec(shape=shape, dtype=dtype)
    # The Python side of every parity check: the tree the native core
    # replaces, over a plain dict log.
    tree = BranchTree(spec, players, branches, frames, values)
    nat = native_spec.make_spec_builder(spec, players, branches, frames,
                                        values)
    assert nat is not None
    return spec, tree, {}, nat


def _fill_log(rng, tree, log, nat, lo, hi, gap_p=0.1, periodic=False):
    spec = tree.input_spec
    P = tree.num_players
    base = [
        _rand_payload(rng, spec.zeros_np(P).dtype, (P,) + spec.shape)
        for _ in range(max(1, rng.randint(1, 5)))
    ]
    for f in range(lo, hi):
        if rng.rand() < gap_p:
            continue
        bits = (
            base[f % len(base)] if periodic
            else _rand_payload(rng, spec.zeros_np(P).dtype,
                               (P,) + spec.shape,
                               small=bool(rng.rand() < 0.9))
        )
        log[f] = bits
        nat.log_set(f, bits)


def _rand_known(rng, tree):
    F, P = tree.spec_frames, tree.num_players
    zeros = tree.input_spec.zeros_np(P)
    known = np.broadcast_to(zeros, (F,) + zeros.shape).copy()
    mask = rng.rand(F, P) < rng.choice([0.0, 0.2, 0.6])
    vals = _rand_payload(rng, zeros.dtype, (F,) + zeros.shape)
    known[mask] = vals[mask]
    return known, mask


def _py_bits(tree, log, known, mask, anchor):
    last = log.get(anchor - 1)
    if last is None:
        last = tree.input_spec.zeros_np(tree.num_players)
    return tree.structured_bits(log, np.asarray(last), known, mask, anchor)


@native
def test_build_parity_randomized():
    rng = np.random.RandomState(11)
    for trial in range(50):
        spec, tree, log, nat = _rand_case(rng)
        hi = int(rng.randint(1, 60))
        _fill_log(rng, tree, log, nat, max(0, hi - 50), hi,
                  periodic=bool(rng.rand() < 0.3))
        # Anchors inside, at, and beyond the logged range.
        anchor = int(rng.randint(0, hi + 10))
        known, mask = _rand_known(rng, tree)
        got, _sig = nat.build(anchor, None, known, mask, False, None)
        want = _py_bits(tree, log, known, mask, anchor)
        assert got.dtype == want.dtype and got.shape == want.shape, trial
        assert np.array_equal(got, want), (
            trial, spec, tree.num_players, anchor
        )


@native
def test_build_parity_after_rollback_corrections():
    """Rollback corrections rewrite and DELETE log entries; the mirror and
    the ranking/extrapolation must track exactly."""
    rng = np.random.RandomState(12)
    for trial in range(20):
        spec, tree, log, nat = _rand_case(rng)
        _fill_log(rng, tree, log, nat, 0, 40, gap_p=0.0, periodic=True)
        for _ in range(rng.randint(1, 8)):  # corrections + evictions
            f = int(rng.randint(0, 40))
            if rng.rand() < 0.5 and f in log:
                del log[f]
                nat.log_del(f)
            else:
                bits = _rand_payload(
                    rng, spec.zeros_np(tree.num_players).dtype,
                    (tree.num_players,) + spec.shape,
                )
                log[f] = bits
                nat.log_set(f, bits)
        anchor = int(rng.randint(30, 45))
        known, mask = _rand_known(rng, tree)
        got, _ = nat.build(anchor, None, known, mask, False, None)
        want = _py_bits(tree, log, known, mask, anchor)
        assert np.array_equal(got, want), trial


@native
def test_build_malformed_history_fuzz():
    """Degenerate shapes the tick path can reach: empty log, empty
    universe, B=1, single-entry log, anchor far past the log."""
    rng = np.random.RandomState(13)
    spec = InputSpec()
    for players, branches, frames in [(2, 1, 4), (2, 8, 8), (4, 64, 8)]:
        for log_frames, anchor in [
            ([], 0), ([], 100), ([5], 6), ([5], 50),
            (list(range(10)), 3),  # anchor INSIDE the logged range
        ]:
            for values in [(), tuple(range(16))]:
                tree = BranchTree(spec, players, branches, frames, values)
                log = {}
                nat = native_spec.make_spec_builder(
                    spec, players, branches, frames, values
                )
                for f in log_frames:
                    bits = _rand_payload(rng, np.dtype(np.uint8),
                                         (players,))
                    log[f] = bits
                    nat.log_set(f, bits)
                known, mask = _rand_known(rng, tree)
                got, _ = nat.build(anchor, None, known, mask, False, None)
                want = _py_bits(tree, log, known, mask, anchor)
                assert np.array_equal(got, want), (
                    players, branches, log_frames, anchor, values
                )


@native
def test_unsupported_dtypes_fall_back():
    # uint64 breaks the int64 normalization's injectivity; floats are
    # outside the byte-comparable contract entirely.
    for dtype in (np.uint64, np.float32):
        assert native_spec.make_spec_builder(
            InputSpec(dtype=dtype), 2, 8, 8, (1, 2)
        ) is None


@native
def test_dedup_signature_equivalence_classes():
    """The native FNV signature must induce the same skip decisions as the
    Python tuple: identical state skips, any input to the build changing
    (log contents, anchor, known set) rebuilds."""
    rng = np.random.RandomState(14)
    spec, tree, log, nat = _rand_case(rng)
    _fill_log(rng, tree, log, nat, 0, 30, gap_p=0.0)
    anchor = 30
    known, mask = _rand_known(rng, tree)
    bits, sig = nat.build(anchor, None, known, mask, False, None)
    assert bits is not None
    # Same state, allow_skip: the native dedup-skip fires.
    again, sig2 = nat.build(anchor, None, known, mask, True, sig)
    assert again is None and sig2 == sig
    # Same state, skip not allowed (rollback tick): full build, same sig.
    forced, sig3 = nat.build(anchor, None, known, mask, False, sig)
    assert forced is not None and sig3 == sig
    # A log mutation inside the fingerprint window changes the signature.
    bump = log[29] ^ np.ones_like(log[29])
    nat.log_set(29, bump)
    rebuilt, sig4 = nat.build(anchor, None, known, mask, True, sig)
    assert rebuilt is not None and sig4 != sig
    # A different anchor changes it too.
    _, sig5 = nat.build(anchor + 1, None, known, mask, True, sig4)
    assert sig5 not in (sig, sig4)


@native
def test_match_parity_randomized():
    """Native corrected-history match vs the Python needed-assembly +
    match_branch, including the log-gap -> no-match contract."""
    rng = np.random.RandomState(15)
    for trial in range(40):
        spec, tree, log, nat = _rand_case(rng)
        F, P = tree.spec_frames, tree.num_players
        _fill_log(rng, tree, log, nat, 0, 30, gap_p=0.15)
        anchor = int(rng.randint(0, 25))
        known, mask = _rand_known(rng, tree)
        bits, _ = nat.build(anchor, None, known, mask, False, None)
        pre = int(rng.randint(0, F))
        load_frame = anchor + pre
        n_steps = int(rng.randint(1, F + 2))
        dtype = spec.zeros_np(P).dtype
        steps = np.stack([
            # Bias toward replaying a branch row so full hits occur.
            np.asarray(bits[rng.randint(bits.shape[0]), min(pre + t, F - 1)])
            if rng.rand() < 0.5
            else _rand_payload(rng, dtype, (P,) + spec.shape)
            for t in range(n_steps)
        ])
        got = nat.match(np.asarray(bits), anchor, load_frame, steps, F)
        needed, gap = [], False
        for f in range(anchor, load_frame):
            entry = log.get(f)
            if entry is None:
                gap = True
                break
            needed.append(entry)
        if gap:
            assert got is None, trial
            continue
        needed.extend(steps)
        needed_arr = np.stack(needed)[:F] if needed else np.zeros(
            (0, P) + spec.shape, dtype
        )
        want = match_branch(np.asarray(bits), needed_arr)
        assert got == want, (trial, anchor, pre, n_steps)


@native
def test_match_prefix_parity_randomized():
    rng = np.random.RandomState(16)
    for trial in range(60):
        B = int(rng.choice([1, 4, 64]))
        F = int(rng.choice([4, 8]))
        P = int(rng.choice([2, 4]))
        shape = () if rng.rand() < 0.7 else (3,)
        dtype = np.dtype(_DTYPES[rng.randint(len(_DTYPES))])
        bb = _rand_payload(rng, dtype, (B, F, P) + shape,
                           small=bool(rng.rand() < 0.5))
        k = int(rng.randint(1, F + 1))
        if rng.rand() < 0.5:  # force a (possibly tied) full hit
            cb = bb[rng.randint(B), :k].copy()
        else:
            cb = _rand_payload(rng, dtype, (k, P) + shape)
        got = native_spec.match_prefix(bb, cb)
        assert got is not None
        assert got == _match_branch_numpy(bb, cb, k), trial
        # The public entry agrees with both.
        assert match_branch(bb, cb) == got


@native
def test_mirrored_log_tracks_dict_semantics():
    """MirroredLog is the runner's _input_log: every dict mutation path the
    base runner uses must both behave like dict AND keep the native mirror
    build-identical to a Python oracle over a plain dict."""
    rng = np.random.RandomState(17)
    spec = InputSpec()
    tree = BranchTree(spec, 2, 8, 8, tuple(range(16)))
    nat = native_spec.make_spec_builder(spec, 2, 8, 8, tuple(range(16)))
    log = native_spec.MirroredLog(nat)
    shadow = {}

    def check(step):
        assert dict(log) == shadow, step
        known, mask = _rand_known(rng, tree)
        anchor = max(shadow, default=0) + 1
        got, _ = nat.build(anchor, None, known, mask, False, None)
        want = _py_bits(tree, dict(shadow), known, mask, anchor)
        assert np.array_equal(got, want), step

    for step in range(60):
        op = rng.randint(0, 6)
        f = int(rng.randint(0, 20))
        bits = _rand_payload(rng, np.dtype(np.uint8), (2,))
        if op == 0:
            log[f] = bits
            shadow[f] = bits
        elif op == 1 and f in shadow:
            del log[f]
            del shadow[f]
        elif op == 2 and shadow:
            assert log.pop(f, None) is not None or f not in shadow
            shadow.pop(f, None)
        elif op == 3:
            log.setdefault(f, bits)
            shadow.setdefault(f, bits)
        elif op == 4:
            upd = {f: bits, f + 1: bits}
            log.update(upd)
            shadow.update(upd)
        elif op == 5 and rng.rand() < 0.15:
            log.clear()
            shadow.clear()
        if step % 10 == 9:
            check(step)
    check("final")


@native
def test_qset_in_process_parity():
    """When the session's queue set is native, the build reads the
    confirmed frontier in-process; tensor AND signature must equal the
    host-roundtrip (known/mask arrays) form, which itself equals the
    Python oracle through session.confirmed_span."""

    class FakeSession:
        def __init__(self, qset):
            self._qset = qset

        def confirmed_span(self, handle, lo, n):
            return self._qset.queues[handle].confirmed_span(lo, n)

    rng = np.random.RandomState(18)
    for shape, dtype in [((), np.uint8), ((2,), np.int16)]:
        spec = InputSpec(shape=shape, dtype=dtype)
        P, B, F = 2, 16, 8
        values = tuple(range(8))
        tree, log = BranchTree(spec, P, B, F, values), {}
        nat = native_spec.make_spec_builder(spec, P, B, F, values)
        qset = ncore.NativeQueueSet(np.zeros(shape, dtype), [0] * P)
        session = FakeSession(qset)
        for f in range(12):
            for h in range(P):
                if f < 10 or h == 0:  # player 1's frontier trails
                    qset.queues[h].add_local_input(
                        f, _rand_payload(rng, np.dtype(dtype), shape)
                    )
        _fill_log(rng, tree, log, nat, 0, 10, gap_p=0.0)
        for anchor in (0, 5, 9, 11, 14):
            qs_ptr = nat.qset_ptr(session)
            assert qs_ptr is not None
            got, sig_q = nat.build(anchor, qs_ptr, None, None, False, None)
            known, mask = tree.known_inputs(session, anchor)
            host, sig_h = nat.build(anchor, None, known, mask, False, None)
            want = _py_bits(tree, log, known, mask, anchor)
            assert sig_q == sig_h, anchor
            assert np.array_equal(got, host), anchor
            assert np.array_equal(got, want), anchor


@native
def test_qset_ptr_gated_on_confirmed_span():
    """Sessions without a confirmed_span getter (synctest, spectator) hide
    their queues from Python's _known_inputs — the native path must not
    read them either, or it would pin inputs Python leaves free."""

    class NoSpanSession:
        def __init__(self, qset):
            self._qset = qset

    nat = native_spec.make_spec_builder(InputSpec(), 2, 8, 8, (1, 2))
    qset = ncore.NativeQueueSet(np.zeros((), np.uint8), [0, 0])
    assert nat.qset_ptr(NoSpanSession(qset)) is None


def _run_session(frames, speculate_native, monkeypatch):
    """A deterministic 2-peer loopback box_game run; returns the final
    state checksum plus every speculation/rollback counter."""
    if not speculate_native:
        monkeypatch.setattr(
            "bevy_ggrs_tpu.native.spec.make_spec_builder",
            lambda *a, **k: None,
        )
        monkeypatch.setattr(
            "bevy_ggrs_tpu.native.spec.match_prefix",
            lambda *a, **k: None,
        )
    else:
        monkeypatch.undo()
    # Both runs must pay attestation identically: the verdict is memoized
    # module-globally, so whichever run goes first computes it (two extra
    # rollout dispatches) while the second hits the cache — a dispatch-count
    # gap that has nothing to do with native/python parity.
    import bevy_ggrs_tpu.spec_runner as _sr

    monkeypatch.setattr(_sr, "_ATTEST_MEMO", {})
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.runner import RollbackRunner
    from bevy_ggrs_tpu.session import (
        PlayerType, PredictionThreshold, SessionBuilder, SessionState,
    )
    from bevy_ggrs_tpu.state import checksum, combine64
    from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork

    net = LoopbackNetwork(latency=2 / 60, jitter=1 / 60, loss=0.03, seed=5)
    keys = [box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0]
    peers = []
    for me in range(2):
        builder = (
            SessionBuilder(box_game.INPUT_SPEC)
            .with_num_players(2)
            .with_max_prediction_window(8)
        )
        for h in range(2):
            if h == me:
                builder.add_player(PlayerType.local(), h)
            else:
                builder.add_player(PlayerType.remote(("peer", h)), h)
        session = builder.start_p2p_session(
            net.socket(("peer", me)), clock=lambda: net.now
        )
        if me == 0:
            runner = SpeculativeRollbackRunner(
                box_game.make_schedule(), box_game.make_world(2).commit(),
                max_prediction=8, num_players=2,
                input_spec=box_game.INPUT_SPEC, num_branches=16,
            )
            assert (runner._native is not None) == speculate_native
        else:
            runner = RollbackRunner(
                box_game.make_schedule(), box_game.make_world(2).commit(),
                max_prediction=8, num_players=2,
                input_spec=box_game.INPUT_SPEC,
            )
        runner.warmup()
        peers.append((session, runner))
    for tick in range(frames):
        net.advance(1 / 60)
        for me, (session, runner) in enumerate(peers):
            flush = getattr(runner, "flush_reports", None)
            if flush is not None:
                flush(session)
            session.poll_remote_clients()
            list(session.events())
            if session.current_state() != SessionState.RUNNING:
                continue
            for h in session.local_player_handles():
                session.add_local_input(
                    h,
                    np.uint8(keys[(session.current_frame // 3 + h) % 4]),
                )
            try:
                requests = session.advance_frame()
            except PredictionThreshold:
                continue
            tick_fn = getattr(runner, "tick", None)
            if tick_fn is not None:
                tick_fn(requests, session.confirmed_frame(), session)
            else:
                runner.handle_requests(requests, session)
    runner0 = peers[0][1]
    return {
        "checksum": int(combine64(np.asarray(checksum(runner0.state)))),
        "frame": runner0.frame,
        "spec_hits": runner0.spec_hits,
        "spec_partial_hits": runner0.spec_partial_hits,
        "spec_misses": runner0.spec_misses,
        "spec_dispatches_skipped": runner0.spec_dispatches_skipped,
        "rollbacks_total": runner0.rollbacks_total,
        "rollback_frames_recovered":
            runner0.rollback_frames_recovered_total,
        "dispatches": runner0.device_dispatches_total,
    }


@native
def test_end_to_end_session_parity(monkeypatch):
    """The acceptance gate end to end: a deterministic loopback session
    must produce the SAME world checksum, frame count, and every
    speculation counter whether the tick path is native or pure Python —
    the two implementations are indistinguishable from outside."""
    got_native = _run_session(150, True, monkeypatch)
    got_python = _run_session(150, False, monkeypatch)
    assert got_native == got_python
    assert got_native["spec_hits"] > 0  # speculation actually exercised
