"""The tick as two programs (``spec_runner.py`` ``_decide_split``,
``fused.py`` ``PackedTick.front``): where the rollout outlasts one more call,
absorb + burst go out first and the rollout behind them, so that
``runner.state`` is readable before the rollout runs.

Parity: a split runner and a fused runner fed the same request lists (steady
ticks, full hits, partial hits, misses) hold the same bits after every tick:
the packed carry (main ring, live state, branch rings and states), all three
checksum parts, the reports handed to the session, every counter; the series
``tick_programs`` reads 2 / 1 as the tick was split / absorb-only, and no
executable is built after warm-up. The decision: a pure function of the two
times warm-up takes; injected times pick each path; a front program that
disagrees with the fused burst leaves the runner fused and is counted; a
split tick's ``runner.state`` is the front program's output object; mesh and
session-axis runners never measure.
"""

import jax
import numpy as np
import pytest

from bevy_ggrs_tpu import spec_runner
from bevy_ggrs_tpu.fused import FusedTickExecutor, split_pays
from bevy_ggrs_tpu.models import boids, box_game
from bevy_ggrs_tpu.obs.ledger import SpeculationLedger
from bevy_ggrs_tpu.session.requests import (
    AdvanceFrame,
    LoadGameState,
    SaveGameState,
)
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.utils import xla_cache
from bevy_ggrs_tpu.utils.metrics import Metrics

P = 2
MAXPRED = 8
SPLITS = (9.0, 1.0)  # one rollout dispatch, one call without: 8 ms > 1 ms
FUSES = (1.2, 1.0)  # 0.2 ms < 1 ms
KEYS = (0, 1, 4)  # the masks a player alternates among

TITLES = {
    "box_game": lambda: (
        box_game.make_schedule(), box_game.make_world(P).commit(),
        box_game.INPUT_SPEC,
    ),
    # The MXU force kernel, interpreted here: the path boids1k.wan splits.
    "boids64_mxu": lambda: (
        boids.make_schedule(kernel="mxu"), boids.make_world(64, P).commit(),
        boids.INPUT_SPEC,
    ),
}


def inject(monkeypatch, *times):
    """Warm-up's clock reads these, in the order it takes them; a read
    beyond them fails the test."""
    reads = iter(times)
    monkeypatch.setattr(
        spec_runner, "_blocking_ms", lambda call, reps=3: next(reads)
    )


def make_runner(monkeypatch, title, times, **kwargs):
    schedule, state, input_spec = TITLES[title]()
    inject(monkeypatch, *times)
    opts = dict(num_branches=8, spec_frames=4, metrics=Metrics(),
                ledger=SpeculationLedger())
    opts.update(kwargs)
    r = SpeculativeRollbackRunner(
        schedule, state, max_prediction=MAXPRED, num_players=P,
        input_spec=input_spec, **opts,
    )
    r.warmup()
    return r


class Peer:
    """What a P2P session is to the runner over a run of ticks: the request
    lists (a remote player whose inputs arrive late, predicted by
    repeat-last and corrected by a rollback to the first wrong frame), the
    inputs already confirmed inside a rollout's span, and the sink of the
    checksum reports."""

    def __init__(self, seed: int, ticks: int):
        rng = np.random.default_rng(seed)
        # Held keys: a player keeps a mask for a few frames, then draws
        # another. Mostly predictable, so that hits, partial hits and
        # misses all occur.
        self.true = np.zeros((ticks, P), np.uint8)
        for p in range(P):
            f = 0
            while f < ticks:
                hold = int(rng.integers(2, 9))
                self.true[f:f + hold, p] = rng.choice(KEYS)
                f += hold
        lag = np.clip(np.cumsum(rng.integers(-1, 2, ticks)) % 6, 1, 5)
        self.confirmed = np.maximum.accumulate(np.arange(ticks) - lag)
        self.used = np.zeros((ticks, P), np.uint8)
        self.known = -1  # the remote player's inputs are known up to here
        self.reports = {}

    def _inputs(self, frame: int) -> np.ndarray:
        bits = self.true[frame].copy()
        if frame > self.known:  # repeat the last confirmed remote input
            bits[1] = self.true[self.known, 1] if self.known >= 0 else 0
        return bits

    def tick(self, frame: int):
        """``(requests, confirmed_frame)`` of the tick that advances
        ``frame``."""
        before, self.known = self.known, int(self.confirmed[frame])
        wrong = [g for g in range(max(before + 1, 0), min(self.known + 1, frame))
                 if self.used[g, 1] != self.true[g, 1]]
        start = wrong[0] if wrong else frame
        requests = [LoadGameState(start)] if wrong else []
        for g in range(start, frame + 1):
            self.used[g] = self._inputs(g)
            requests += [SaveGameState(g), AdvanceFrame(
                bits=self.used[g].copy(), status=np.zeros(P, np.int32))]
        return requests, self.known

    # -- the session's side of the runner's interface --------------------

    def confirmed_input(self, handle: int, frame: int):
        local = handle == 0 and frame <= self._frame
        if frame < len(self.true) and (local or frame <= self.known):
            return self.true[frame, handle]
        return None

    def report_checksum(self, frame: int, cs: int) -> None:
        self.reports.setdefault(frame, []).append(int(cs))

    def drive(self, runner, frame: int):
        self._frame = frame
        requests, confirmed = self.tick(frame)
        runner.tick(requests, confirmed, self)


def first_tick(runner) -> None:
    runner.tick([SaveGameState(0), AdvanceFrame(
        bits=np.zeros(P, np.uint8), status=np.zeros(P, np.int32))], -1)


def same_bits(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb)
    )


def spy(obj, name, calls):
    """Record every return value of ``obj.name`` in ``calls``."""
    inner = getattr(obj, name)

    def wrapped(*args, **kwargs):
        out = inner(*args, **kwargs)
        calls.append(out)
        return out

    setattr(obj, name, wrapped)



def test_the_split_is_decided_on_a_tree_as_a_tick_builds_them(monkeypatch):
    """Warm-up times the rollout it decides from on the default tree's own
    shape, every player free from the anchor, not on the all-alike tensor
    it compiles with: a rollout that shares its steps (``rollout.py``
    ``share_width``) runs one world a frame for that one and would look
    cheaper than any tick's (boids1k.wan stopped splitting and its frame
    rose 3.9 -> 6.2 ms: ``PERF.md`` section 6, PR 58)."""
    inject(monkeypatch, *SPLITS)
    timed = []
    decide = spec_runner.SpeculativeRollbackRunner._decide_split
    monkeypatch.setattr(
        spec_runner.SpeculativeRollbackRunner, "_decide_split",
        lambda self, bits: (timed.append(np.array(bits)), decide(self, bits)))
    schedule, state, input_spec = TITLES["box_game"]()
    r = SpeculativeRollbackRunner(
        schedule, state, max_prediction=MAXPRED, num_players=P,
        input_spec=input_spec, num_branches=8, spec_frames=4)
    r.warmup()
    tree, = timed
    assert tree.shape[:3] == (8, 4, P) and r._split
    # the single changes of a tree with nothing pinned: more distinct
    # prefixes than frames, fewer than branches x frames
    from bevy_ggrs_tpu.branch_tree import distinct_prefixes
    assert 4 < int(distinct_prefixes(tree).sum()) < 8 * 4


@pytest.mark.parametrize("title,ticks", [("box_game", 300),
                                         ("boids64_mxu", 160)])
def test_split_and_fused_runners_hold_the_same_bits(monkeypatch, title, ticks):
    split = make_runner(monkeypatch, title, SPLITS)
    fused = make_runner(monkeypatch, title, FUSES)
    assert split._split and not fused._split
    assert fused._fused._front is None  # a fused runner builds nothing new
    fronts, wholes, absorbs, fused_absorbs = [], [], [], []
    spy(split._fused, "run_front", fronts)
    spy(split._fused, "commit_absorb", absorbs)
    spy(fused._fused, "run", wholes)
    spy(fused._fused, "commit_absorb", fused_absorbs)
    depths = set()  # of the full hits, and of the partial ones
    peers = Peer(11, ticks), Peer(11, ticks)
    built = xla_cache.compile_counters()["backend_compiles"]
    # from here on (the first runner of a process attests, the second finds
    # the verdict cached: their warm-ups' rollouts differ)
    rollouts = [(r.ledger.rollouts_dispatched, r.ledger.spec_frames_dispatched)
                for r in (split, fused)]
    shapes = {"split": 0, "absorb": 0, "other": 0}
    for frame in range(ticks):
        n_front, n_whole, n_absorb = len(fronts), len(wholes), len(absorbs)
        for runner, peer in zip((split, fused), peers):
            peer.drive(runner, frame)
        assert split.frame == fused.frame == frame + 1
        # main ring, live state, branch rings and states: the packed carry
        assert same_bits(split._packed_carry(), fused._packed_carry())
        assert same_bits(split.state, fused.state)
        assert peers[0].reports == peers[1].reports
        programs = split.metrics.series["tick_programs"][-1]
        assert fused.metrics.series["tick_programs"][-1] <= programs
        if len(fronts) > n_front:
            # A split tick: the front program's checksums are the fused
            # tick's first two parts, the program behind it made the third.
            assert programs == 2 and len(wholes) == n_whole + 1
            absorb_cs, burst_cs, spec_cs = fused._fused.cs_host(wholes[-1][2])
            assert same_bits(fronts[-1][2], (absorb_cs, burst_cs))
            assert same_bits(
                split._fused.cs_host(split._spec_cs)[2], spec_cs)
            assert split.state is fronts[-1][1]
            shapes["split"] += 1
            # The front's copy loop ran the rows it reports and no more.
            depths.add(("front", int(np.asarray(absorb_cs).any(axis=1).sum())))
        elif len(absorbs) > n_absorb:
            assert programs == 1  # a full hit: the absorb-only program
            shapes["absorb"] += 1
            # Both runners took it, to the same carry, state and checksums;
            # the copy loop ran the hit's depth: the rows behind it are 0.
            assert same_bits(absorbs[-1], fused_absorbs[-1])
            rows = np.asarray(absorbs[-1][2]).any(axis=1)
            depth = int(rows.sum())
            assert depth > 0 and rows[:depth].all()
            depths.add(("absorb", depth))
        else:
            shapes["other"] += 1  # a tick both runners took serially
    for name in ("rollbacks_total", "spec_hits", "spec_partial_hits",
                 "spec_misses", "rollback_frames_recovered_total",
                 "rollback_frames_total", "spec_dispatches_skipped"):
        assert getattr(split, name) == getattr(fused, name), name
    since = [(r.ledger.rollouts_dispatched - n, r.ledger.spec_frames_dispatched - f)
             for r, (n, f) in zip((split, fused), rollouts)]
    assert since[0] == since[1] and since[0][0] >= shapes["split"]
    assert split.ledger.outcome_counts == fused.ledger.outcome_counts
    # the run saw every kind of tick the issue names
    assert min(split.spec_hits, split.spec_partial_hits, split.spec_misses) > 0
    assert shapes["split"] > ticks // 2 and shapes["absorb"] > 0
    # hits of more than one depth, no commit at all among the split ticks,
    # and one executable each for all of them
    assert len({d for kind, d in depths if kind == "absorb"}) > 1
    assert ("front", 0) in depths and len(depths) > 3
    assert split._fused._absorb._cache_size() == 1
    assert split._fused._front._cache_size() == 1
    for runner, peer in zip((split, fused), peers):
        runner.flush_reports(peer)
    assert peers[0].reports == peers[1].reports and peers[0].reports
    assert xla_cache.compile_counters()["backend_compiles"] == built
    # one sample a dispatch: two for a split tick
    assert len(split.metrics.series["tick_io_buffers"]) >= (
        len(fused.metrics.series["tick_io_buffers"]) + shapes["split"])


@pytest.mark.parametrize("rollout_ms,call_ms,splits", [
    (7.4, 1.5, True),      # boids at 1,024 x 128 x 8
    (0.18, 1.3, False),    # box_game at 256 x 8
    (1.5001, 1.5, True),
    (1.5, 1.5, False),     # on the line: the one program stays
    (1.4999, 1.5, False),
    (-0.05, 0.9, False),   # a difference of two times can come out negative
])
def test_the_rule_is_a_pure_function_of_two_times(rollout_ms, call_ms, splits):
    assert split_pays(rollout_ms, call_ms) is splits


@pytest.mark.parametrize("times,splits", [(SPLITS, True), (FUSES, False)])
def test_warmup_picks_the_path_from_the_times_it_takes(
        monkeypatch, times, splits):
    r = make_runner(monkeypatch, "box_game", times)
    assert r._split is splits
    # the gauges: the second time, and the first less the second
    assert r.extra_call_ms == times[1]
    assert r.rollout_device_ms == pytest.approx(times[0] - times[1])
    assert r.metrics.series["extra_call_ms"] == [r.extra_call_ms]
    assert r.metrics.series["rollout_device_ms"] == [r.rollout_device_ms]
    assert (r._fused._front is not None) is splits
    assert "tick_split_refused" not in r.metrics.counters
    before = r.device_dispatches_total
    first_tick(r)
    assert r.device_dispatches_total - before == (2 if splits else 1)
    assert r.metrics.series["tick_programs"] == [2.0 if splits else 1.0]


def test_a_front_program_that_disagrees_leaves_the_runner_fused(monkeypatch):
    real = FusedTickExecutor.run_front

    def off_by_a_bit(self, *args):
        carry, state, (absorb_cs, burst_cs) = real(self, *args)
        return carry, state, (absorb_cs, burst_cs ^ np.uint32(1))

    monkeypatch.setattr(FusedTickExecutor, "run_front", off_by_a_bit)
    r = make_runner(monkeypatch, "box_game", SPLITS)
    assert not r._split and r.speculation_enabled
    assert r.metrics.counters["tick_split_refused"] == 1
    first_tick(r)
    assert r.metrics.series["tick_programs"] == [1.0]


def test_a_split_ticks_state_does_not_wait_for_the_rollout(monkeypatch):
    class NeverReady:
        def block_until_ready(self):
            raise AssertionError("the state waited for the rollout program")

    r = make_runner(monkeypatch, "box_game", SPLITS)
    fronts = []
    spy(r._fused, "run_front", fronts)
    run = r._fused.run

    def rollout_that_never_ends(*args):
        carry, _, cs = run(*args)
        return carry, NeverReady(), cs

    r._fused.run = rollout_that_never_ends
    first_tick(r)
    assert len(fronts) == 1 and r.state is fronts[0][1]
    jax.block_until_ready(r.state)
    with pytest.raises(AssertionError):
        jax.block_until_ready(NeverReady())  # the stub does bite


@pytest.mark.parametrize("kind", ["mesh", "session_axis"])
def test_mesh_and_session_axis_runners_never_split(monkeypatch, kind):
    kwargs = {"attest": False}
    if kind == "mesh":
        devices = np.array(jax.devices()[:4]).reshape(2, 2)
        kwargs["mesh"] = jax.sharding.Mesh(devices, ("branch", "entity"))
    else:
        monkeypatch.setenv("GGRS_SESSION_AXIS", "3")
    # no time is taken: a read of the injected clock would raise
    r = make_runner(monkeypatch, "box_game", (), num_branches=4, **kwargs)
    assert not r._split and r._fused._front is None
    assert r.rollout_device_ms is None and r.extra_call_ms is None
    assert "rollout_device_ms" not in r.metrics.series
    before = r.device_dispatches_total
    first_tick(r)
    assert r.device_dispatches_total - before == 1
