"""The turnover plan (``benchmark/turnover.py``) and loop kind
``match_server_p2p_turnover`` at its toy size on the CPU: counts and the
``correct`` decision, never a time. The manifest-driven tests rehearse the
cell end to end, traced, under ``bf16_state`` and ``freeze_last_player``;
here are the plan's own rules, the lifecycle's books, and the control that
only this loop kind has.
"""

import importlib
import json
import os

import pytest

from benchmark import run
from benchmark.readers.common import Results
from benchmark.turnover import GAME_OVER, SILENT_DROP, Turnover

CELL = "server256.turnover"
NEW_METRICS = {
    "admission_ms.p50.serve": "program_span",
    "admission_admit_ms.p50.serve": "program_span",
    "admit_program_ms.serve": "device_trace",
    "sync_frames.p95.serve": "program_counter",
    "disconnect_wait_frames.p50.serve": "program_counter",
    "not_running_slot_share.serve": "program_counter",
    "turnover_per_frame.serve": "program_counter",
    "operator_ms.serve": "host_clock",
}


def _mix():
    return run.load_cell(CELL)[3]


def _plan(seed, seats=256):
    return Turnover(seed, seats, _mix()["turnover"])


# -- the plan ----------------------------------------------------------------


def test_the_plan_is_a_function_of_the_seed_alone():
    a, b, other = _plan(2**31 + 5), _plan(2**31 + 5), _plan(7)
    waits = {m: 122 for m in range(a.matches)}
    assert a.ledger(waits, 600) == b.ledger(waits, 600)
    assert a.rank_of.tolist() != other.rank_of.tolist()
    assert sorted(a.rank_of.tolist()) == list(range(256))
    # Which seat ends when differs; every life is the same life.
    assert a.ledger(waits, 600) != other.ledger(waits, 600)
    for seat in (0, 17, 255):
        rank = int(a.rank_of[seat])
        twin = int(other.rank_of.tolist().index(rank))
        for g in range(3):
            mine, theirs = a.life(seat, g), other.life(twin, g)
            assert (mine.kind, mine.length) == (theirs.kind, theirs.length)
            assert mine.match == g * 256 + seat


@pytest.mark.parametrize("frame", [3, 100, 300, 510, 700])
def test_every_seed_ends_the_same_matches_by_a_served_frame(frame):
    def ended_by(plan):
        waits = {m: 122 for m in range(plan.matches)}
        return sum(last >= 0 for *_, last in plan.ledger(waits, frame))

    counts = {ended_by(_plan(seed)) for seed in (1, 2, 2**31 + 17)}
    assert len(counts) == 1
    ended = counts.pop()
    # ~0.26 a served frame, evenly from the first frames on (a drop is
    # retired two seconds after it ends).
    assert 0.19 * frame <= ended <= 0.30 * frame + 1


def test_lives_and_kinds_go_by_mid_quantile():
    plan = _plan(11)
    lengths = plan.lengths
    assert len(lengths) == 64 and lengths.min() >= 300
    assert lengths.max() <= 2700
    assert 850 <= sorted(lengths)[32] <= 950          # the median, 15 s
    residuals = plan.residuals.tolist()
    assert residuals == sorted(residuals) and residuals[0] == 2
    assert residuals[1] - residuals[0] == 4           # ~ mean / 256
    kinds = [plan.life(seat, 0).kind for seat in range(256)]
    assert kinds.count(SILENT_DROP) == 64 and kinds.count(GAME_OVER) == 192
    # The window's first end is a game-over in served frame 2.
    first = min(range(256), key=plan.first_end)
    assert plan.first_end(first) == 2
    assert plan.life(first, 0).kind == GAME_OVER
    with pytest.raises(ValueError, match="generations"):
        plan.life(0, plan.generations)
    for bad in ({"kind": "poisson"}, {"end_kinds": {"rage_quit": 1.0}},
                {"end_kinds": {"game_over": 0.7, "silent_drop": 0.2}}):
        with pytest.raises(ValueError):
            Turnover(1, 8, dict(_mix()["turnover"], **bad))


def test_the_ledger_follows_a_seat_down_its_generations():
    plan = _plan(3)
    seat = next(s for s in range(256) if plan.life(s, 0).kind == SILENT_DROP
                and plan.first_end(s) < 200)
    end, first_tenant = plan.first_end(seat), plan.life(seat, 0)
    second = plan.life(seat, 1)
    rows = lambda waits, upto: [  # noqa: E731
        r for r in plan.ledger(waits, upto) if r[1] == seat]
    # Nobody knows yet when the server lets go of a drop: the chain stops.
    assert rows({}, 4000) == [(first_tenant.match, seat, -1, -1)]
    waited = {first_tenant.match: 123}
    assert rows(waited, end + 122) == [(first_tenant.match, seat, -1, -1)]
    assert rows(waited, end + 123) == [
        (first_tenant.match, seat, -1, end + 123)]
    asked = end + 124                   # the served frame after
    assert rows(waited, asked)[1] == (second.match, seat, asked, -1)
    if second.kind == GAME_OVER:
        assert rows(waited, asked + second.length)[1] == (
            second.match, seat, asked, asked + second.length)


# -- the loop kind at its toy size -----------------------------------------


def _run(seed, seconds, control=None, trace=False):
    lines = []
    rc, result = run.run_cell(
        CELL, seed, seconds, trace, control=control, require_tpu=False,
        overrides=run.load_toy(CELL), emit=lines.append)
    assert rc == 0
    info = [json.loads(x) for x in lines[:-1]]
    scalars = next(i for i in info if i["info"] == "run")["scalars"]
    failed = {i["name"] for i in info
              if i["info"] == "compare" and not i["ok"]}
    return result, scalars, failed


@pytest.fixture(scope="module")
def sound():
    return _run(2**31 + 29, 1.5)


def test_the_toy_runs_ledger_is_the_plans(sound):
    result, scalars, failed = sound
    assert result["correct"] is True and not failed
    assert result["compared"]["guarantee.ledger_rows_differ_from_plan"] == {
        "value": 0, "limit": 0}
    # Matches ended both ways and were replaced inside the window.
    assert scalars["matches_ended"] >= 6
    assert 1 <= scalars["matches_dropped"] < scalars["matches_ended"]
    assert scalars["matches_admitted"] in (scalars["matches_ended"],
                                           scalars["matches_ended"] - 1)
    assert scalars["ledger_rows"] == 8 + scalars["matches_admitted"]
    assert scalars["count.matches_retired_total"] == scalars["matches_ended"]
    # Every drop was reported 2 s after the server last heard its player.
    assert scalars["disconnect_waits"] and all(
        120 <= w <= 132 for w in scalars["disconnect_waits"])
    # Every result read at a retirement was held against the reference,
    # with the matches live at the end; a second tenant bitwise.
    assert scalars["checked_results"] == scalars["matches_ended"]
    assert scalars["checked_matches"] >= scalars["checked_results"] + 4
    assert scalars["successors_compared"] >= 1
    # The books: what was attempted either advanced or was withheld.
    assert result["failed"] == 0
    assert result["attempted"] == (scalars["match_frames"]
                                   + scalars["frames_withheld"])
    assert scalars["count.slot_frames_syncing_total"] > 0
    assert scalars["count.slot_frames_stalled_total"] >= 80
    assert (scalars["count.slot_frames_total"]
            == 8 * scalars["frames_served"])


def test_a_drop_left_unretired_is_not_correct():
    result, scalars, failed = _run(2**31 + 29, 2.5, control="leave_one_drop")
    # The operator saw the drop and let it sit: the slot is never asked for
    # again, and the ledger the plan makes of the same waits says so. The
    # server did nothing wrong, and the states are sound.
    assert result["correct"] is False
    assert failed == {"guarantee.ledger_rows_differ_from_plan"}
    assert scalars["matches_dropped"] >= 1


def test_a_program_that_hands_out_no_events_is_refused_at_once(monkeypatch):
    from bevy_ggrs_tpu.serve.server import MatchServer

    monkeypatch.delattr(MatchServer, "drain_events")
    with pytest.raises(RuntimeError, match="drain_events"):
        _run(1, 1.0)


# -- the manifest's entries -------------------------------------------------


def _read(name, results):
    with open(os.path.join(run.HERE, "layer_metrics", name + ".json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    return importlib.import_module(
        f"benchmark.readers.{spec['kind']}").read(spec, results)


def test_the_new_metric_files_load_and_read():
    manifest = run._load(os.path.join(run.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, source in NEW_METRICS.items():
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "match_frames_per_s"
        assert entries[name]["source"] == source
    results = Results(
        window_s=1.0, series={"operator_ms": [0.1, 0.3, 0.2]}, scalars={},
        counters={"slot_frames_syncing_total": 30,
                  "slot_frames_stalled_total": 20, "slot_frames_total": 1000,
                  "admissions_completed": 13, "frames_served": 50},
        program_series={"admission_ms": [900.0, 1100.0, 1000.0],
                        "admission_admit_ms": [0.5, 0.7, 0.6],
                        "sync_frames": [25] * 19 + [61],
                        "disconnect_wait_frames": [90, 91, 90]})
    assert _read("admission_ms.p50.serve", results) == 1000.0
    assert _read("admission_admit_ms.p50.serve", results) == 0.6
    assert 25 < _read("sync_frames.p95.serve", results) <= 61
    assert _read("disconnect_wait_frames.p50.serve", results) == 90
    assert _read("not_running_slot_share.serve", results) == 5.0
    assert _read("turnover_per_frame.serve", results) == 0.26
    assert _read("operator_ms.serve", results) == 0.2
    # A program without the series or the counters (the parent): nothing,
    # and nothing raised; no trace, no device time.
    bare = Results(window_s=1.0, series={}, scalars={}, counters={},
                   program_series={})
    assert all(_read(name, bare) is None for name in NEW_METRICS)


def test_the_cell_and_its_bypass_share_everything_but_the_turnover():
    _, cell, config, mix = run.load_cell(CELL)
    _, _, bypass, bypass_mix = run.load_cell("server256.wan")
    assert cell["chips"] == 1 and config["driver"] == "match_server_p2p_turnover"
    assert {k: v for k, v in config["settings"].items()
            if k != "disconnect_notify_start_s"} == bypass["settings"]
    assert config["settings"]["disconnect_notify_start_s"] == 0.5
    assert config["limits"].keys() == bypass["limits"].keys()
    for name, spec in config["limits"].items():
        assert spec["limit"] == bypass["limits"][name]["limit"]
    assert config["reduced"] == bypass["reduced"]
    for key in ("loop", "occupancy", "network", "bursts", "inputs",
                "inputs_horizon_frames", "sync_frames_limit", "warmup_frames",
                "sample_slots", "trace_window_s", "traced_run_s"):
        assert mix[key] == bypass_mix[key], key
    # Every reading of the bypass that no accepted test pins to its cells.
    manifest = run._load(os.path.join(run.ROOT, "BENCHMARK.json"))
    without = {m["name"] for m in manifest["per_layer"]
               if "server256.wan" in m.get("workloads", ())
               and CELL not in m["workloads"]}
    assert without == {
        "dispatch_rounds.serve", "launch_lag_ms.serve", "call_tail_ms.serve",
        "poll_endpoints.serve", "branch_build_ms.serve",
        "spec_miss_share.serve", "spec_partial_hit_share.serve"}
