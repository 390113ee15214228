"""Loop kind ``match_server_p2p`` and the burst generator: what the new
cell's numbers rest on. CPU, toy size; never a device number."""

import json
import time

import numpy as np
import pytest

from benchmark import run
from benchmark.bursts import LossBursts
from benchmark.drivers import match_server_p2p

CELL = "server256.wan"


def _bursts():
    return run.load_cell(CELL)[3]["bursts"]


def _run(control=None, seconds=1.0, traffic=None):
    overrides = run.load_toy(CELL)
    overrides["traffic"] = run._merged(overrides["traffic"], traffic or {})
    lines = []
    rc, result = run.run_cell(CELL, 2**31 + 29, seconds, False,
                              control=control, require_tpu=False,
                              overrides=overrides, emit=lines.append)
    assert rc == 0
    info = [json.loads(x) for x in lines[:-1]]
    return result, next(i for i in info if i["info"] == "run"), info


@pytest.fixture(scope="module")
def long_bursts():
    """A toy run whose every burst is 12 frames long (longer than the
    window of 8) and some 80 frames apart, with far ends slowed to 20 ms a
    served frame."""
    inner = match_server_p2p.Driver._far_ends

    def slow(self):
        t = time.perf_counter()
        inner(self)
        time.sleep(0.02)
        return time.perf_counter() - t

    match_server_p2p.Driver._far_ends = slow
    try:
        t = time.perf_counter()
        out = _run(traffic={"bursts": {
            "length_frames": [12, 12], "gap_mean_frames": 80, "block": 4}})
        return out + (time.perf_counter() - t,)
    finally:
        match_server_p2p.Driver._far_ends = inner


def test_attempted_is_advanced_plus_withheld_plus_failed(long_bursts):
    result, info, _, _ = long_bursts
    s = info["scalars"]
    assert result["correct"] is True
    assert s["frames_withheld"] > 0
    assert result["attempted"] == s["frames_served"] * s["live_matches"]
    assert result["attempted"] == (s["match_frames"] + s["frames_withheld"]
                                   + result["failed"])
    # A withheld frame is neither a match-frame nor a failure.
    assert result["failed"] == 0
    assert s["match_frames"] < result["attempted"]
    assert s["count.frames_withheld"] == s["frames_withheld"]
    assert s["count.match_frames_attempted"] == result["attempted"]
    assert 0 < s["count.burst_steps_total"] < s["count.burst_step_slots_total"]
    assert s["count.checksum_ballots"] > 0 and s["count.desync_events"] == 0


def test_far_end_time_is_out_of_the_window(long_bursts):
    result, info, lines, wall_s = long_bursts
    s = info["scalars"]
    # 20 ms of far end a served frame, none of it in the window: the window
    # is the second asked for, the far ends' time is beside it.
    assert s["far_end_s"] >= 0.02 * s["frames_served"]
    frames = next(i for i in lines if i["info"] == "series"
                  and i["name"] == "serve_frame_ms")
    assert frames["count"] == s["frames_served"]
    # The window closes with the served frame that passes the second.
    assert 1.0 <= info["window_s"] < 1.05 + 2e-3 * frames["max"]
    # The window and the far ends' time are disjoint stretches of the run.
    assert wall_s > info["window_s"] + s["far_end_s"]
    assert frames["count"] * frames["ladder"]["5"] <= 1e3 * info["window_s"]
    rate = result["metrics"]["match_frames_per_s"]["value"]
    assert rate == pytest.approx(s["match_frames"] / info["window_s"])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 29])
def test_every_seed_draws_the_same_bursts_in_another_order(seed):
    params = _bursts()
    gen = LossBursts(seed, params)
    lo, hi = params["length_frames"]
    assert sorted(set(gen.lengths)) == list(range(lo, hi + 1))
    assert len(gen.lengths) == len(gen.gaps) == params["block"]
    # The mid-quantiles of an exponential distribution leave out its far
    # tail: a block's mean gap is a little under the mix's.
    assert 0.9 * params["gap_mean_frames"] < gen.gaps.mean() \
        <= params["gap_mean_frames"]
    want = (sorted(gen.gaps), sorted(gen.lengths))
    blocks = [gen._block(link, b) for link in range(6) for b in range(3)]
    for gaps, lengths in blocks:
        assert (sorted(gaps), sorted(lengths)) == want
    assert len({tuple(g) for g, _ in blocks}) == len(blocks)
    other = LossBursts(seed + 1, params)
    assert (sorted(other.gaps), sorted(other.lengths)) == want
    assert other.schedule(0) != gen.schedule(0)
    assert gen.schedule(0) != gen.schedule(1)
    assert gen.schedule(3) == LossBursts(seed, params).schedule(3)


def test_a_links_schedule_is_its_blocks_from_a_phase_of_its_own():
    params = dict(_bursts(), horizon_frames=3 * 3347)
    gen = LossBursts(5, params)
    lo, hi = params["length_frames"]
    for link in range(4):
        sched = gen.schedule(link)
        starts = np.asarray([a for a, _ in sched])
        ends = np.asarray([b for _, b in sched])
        lengths = ends - starts
        assert starts[0] >= 0 and starts[-1] < params["horizon_frames"]
        assert np.all(starts[1:] > ends[:-1])
        # Whole bursts are the block's lengths; only the first may be cut
        # by the phase.
        assert set(lengths[1:]) <= set(range(lo, hi + 1))
        gaps = starts[1:] - ends[:-1]
        assert set(gaps) <= set(gen.gaps)
        # Three blocks and the parts a phase cuts off: every length twice a
        # block.
        for length in range(lo, hi + 1):
            assert 4 <= (lengths == length).sum() <= 8
    first = [gen.schedule(link)[0][0] for link in range(16)]
    assert len(set(first)) > 8


def test_a_plan_is_the_programs_own_loss_bursts_in_virtual_seconds():
    from bevy_ggrs_tpu.chaos import LossBurst

    gen = LossBursts(9, _bursts())
    dt = 1.0 / 60.0
    plan = gen.plan(2, dt)
    sched = gen.schedule(2)
    assert len(plan.directives) == len(sched)
    clock = 0.0
    lost = set()
    for frame in range(1, sched[1][1] + 3):
        clock += dt        # as the network's clock moves: frame by frame
        if plan.active(LossBurst, clock):
            lost.add(frame)
    want = {f for a, b in sched[:2] for f in range(max(a, 1), b)}
    assert lost == want
    assert all(isinstance(d, LossBurst) and d.rate == 1.0
               for d in plan.directives)
    assert gen.plan(2, dt) == plan and gen.plan(3, dt) != plan


def test_unknown_generator_and_uneven_block_are_refused():
    with pytest.raises(ValueError):
        LossBursts(1, dict(_bursts(), kind="poisson"))
    with pytest.raises(ValueError):
        LossBursts(1, dict(_bursts(), block=10))


def test_reference_at_the_confirmed_frame_catches_bf16_state():
    result, info, lines = _run(control="bf16_state", seconds=2.0)
    compares = {i["name"]: i for i in lines if i["info"] == "compare"}
    failed = {n for n, c in compares.items() if not c["ok"]}
    assert result["correct"] is False
    # Both ends round alike, so every guarantee holds, the serial replay
    # of the confirmed inputs included: the plain reference alone fails.
    assert failed and all(n.startswith("reference.") for n in failed)
    assert compares["guarantee.sampled_matches_differ_from_serial_replay"][
        "value"] == 0
    assert compares["reference.frame_count_gap"]["value"] == 0
    s = info["scalars"]
    # Every live match was compared, each at its own confirmed frame.
    assert s["checked_matches"] == s["live_matches"]
    lo, hi = s["checked_frames_each"]
    assert 0 < lo <= hi <= s["virtual_frames"]
