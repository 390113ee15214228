"""Reader ``trace_phase`` (PR 50): a device trace's operation times joined
with the program's own phase map (``utils.xla_cache.executable_phases``):
device time by phase and by device scope, a dispatch of the batched tick."""

import json
import os

import pytest

from benchmark import run
from benchmark.readers import trace_phase
from benchmark.readers.common import Results
from benchmark.reduce import trace as rt
from bevy_ggrs_tpu.utils import xla_cache

ROOT = run.ROOT
SIX = ["server256.synctest", "server256.quarter", "boids256.synctest",
       "boids256.quarter", "boids256.wan", "particles.synctest"]
# name -> (by, scope, cells)
METRICS = {
    "phase_absorb_ms.serve": ("phase", "absorb", SIX),
    "phase_burst_ms.serve": ("phase", "burst", SIX),
    "phase_rollout_ms.serve": ("phase", "rollout", SIX),
    "phase_codec_ms.serve": ("phase", "carry_codec", SIX),
    "phase_rest_ms.serve": ("rest", None, SIX),
    "scope_schedule_ms.serve": ("scope", "schedule", SIX),
    "scope_ring_write_ms.serve": ("scope", "ring_write", SIX),
    "scope_ring_read_ms.serve": ("scope", "ring_read", SIX),
    "scope_checksum_ms.serve": ("scope", "checksum", SIX),
    "scope_row_layout_ms.serve": ("scope", "row_layout", SIX),
    # Something to read in ``particles.synctest`` alone (0 elsewhere: no
    # births), listed like the others: an accepted test
    # (test_benchmark_particles.py) holds that cell to two metrics that
    # ``boids256.synctest`` lacks.
    "scope_claim_ms.serve": ("scope", "claim", SIX),
}
PHASES = ["phase_absorb_ms.serve", "phase_burst_ms.serve",
          "phase_rollout_ms.serve", "phase_codec_ms.serve",
          "phase_rest_ms.serve"]

# The map of a made-up tick: instruction name -> scopes, outermost first.
TICK = "batched_tick_S64_B8_F8"


def _record(ops, unscoped=0):
    return {"ops": dict(ops), "inherited": 0, "unscoped": unscoped}


OPS = {
    "conditional.1": ("absorb",),
    "while.9": ("burst",),
    "fusion.1": ("burst", "ring_write"),
    "fusion.2": ("burst", "checksum"),
    "fusion.3": ("burst", "schedule", "claim"),
    "while.10": ("rollout",),
    "fusion.4": ("rollout", "schedule"),
    "pairwise_force.1": ("rollout", "schedule", "pairwise_force"),
    "fusion.5": ("rollout", "row_layout"),
    "fusion.6": ("rollout", "ring_read"),
    "fusion.7": ("carry_codec",),
}
# Seconds over the traced stretch, two dispatches; ``copy.8`` and
# ``slice-start.2`` are the compiler's own, in no phase.
SELF_S = {
    "conditional.1": 0.0002,
    "while.9": 0.0001,
    "while.9/fusion.1": 0.0020,
    "while.9/fusion.2": 0.0010,
    "while.9/fusion.3": 0.0006,
    "while.10": 0.0003,
    "while.10/fusion.4": 0.0040,
    "while.10/pairwise_force.1": 0.0100,
    "while.10/fusion.6": 0.0008,
    "fusion.5": 0.0030,
    "fusion.7": 0.0016,
    "copy.8": 0.0012,
    "slice-start.2": 0.0002,
}
PROGRAMS = [("jit__tick_impl(7)", 1.0, 1.014),
            ("jit__tick_impl(7)", 3.0, 3.014)]
N = len(PROGRAMS)


def _trace(self_s=SELF_S, programs=PROGRAMS, devices=1):
    spans = [(rt.WINDOW_SPAN, 0.0, 10.0)]
    return rt.Trace(
        spans=spans,
        modules={d: list(programs) for d in range(devices)},
        blocks={}, op_self_s={d: dict(self_s) for d in range(devices)},
        threads=[spans])


def _results(trace):
    return Results(window_s=10.0, series={}, scalars={}, counters={},
                   program_series={}, trace=trace,
                   trace_window=rt.window_of(trace) if trace else None)


def _read(trace, by, scope=None):
    spec = {"kind": "trace_phase", "program": "batched_tick",
            "per_program": "^jit__tick_impl", "by": by}
    if scope:
        spec["scope"] = scope
    return trace_phase.read(spec, _results(trace))


@pytest.fixture
def captured(monkeypatch):
    """The program's phase maps as if a warm-up had captured ``OPS``."""
    maps = {TICK: _record(OPS, unscoped=2)}
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", maps)
    return maps


def test_phase_scope_and_rest_sums_a_dispatch(captured):
    tr = _trace()
    ms = lambda *keys: 1e3 * sum(SELF_S[k] for k in keys) / N  # noqa: E731
    assert _read(tr, "phase", "absorb") == pytest.approx(ms("conditional.1"))
    assert _read(tr, "phase", "burst") == pytest.approx(ms(
        "while.9", "while.9/fusion.1", "while.9/fusion.2", "while.9/fusion.3"))
    assert _read(tr, "phase", "rollout") == pytest.approx(ms(
        "while.10", "while.10/fusion.4", "while.10/pairwise_force.1",
        "while.10/fusion.6", "fusion.5"))
    assert _read(tr, "phase", "carry_codec") == pytest.approx(ms("fusion.7"))
    # A scope is read at any depth, in whichever phase.
    assert _read(tr, "scope", "schedule") == pytest.approx(ms(
        "while.9/fusion.3", "while.10/fusion.4", "while.10/pairwise_force.1"))
    assert _read(tr, "scope", "claim") == pytest.approx(ms("while.9/fusion.3"))
    assert _read(tr, "scope", "ring_write") == pytest.approx(
        ms("while.9/fusion.1"))
    assert _read(tr, "scope", "row_layout") == pytest.approx(ms("fusion.5"))
    # The rest: the program's own time less every operation with a phase
    # (here the two unscoped operations and the gaps between operations).
    program_ms = 1e3 * sum(e - s for _, s, e in PROGRAMS) / N
    with_phase = 1e3 * sum(v for k, v in SELF_S.items()
                           if k.rsplit("/", 1)[-1] in OPS) / N
    assert _read(tr, "rest") == pytest.approx(program_ms - with_phase)
    assert _read(tr, "rest") > ms("copy.8", "slice-start.2")


def test_the_phases_and_the_rest_add_up_to_the_program(captured):
    tr = _trace()
    parts = [_read(tr, "phase", p)
             for p in ("absorb", "burst", "rollout", "carry_codec")]
    program_ms = 1e3 * sum(e - s for _, s, e in PROGRAMS) / N
    assert sum(parts) + _read(tr, "rest") == pytest.approx(program_ms)


def test_several_devices_are_averaged(captured):
    one, two = _trace(devices=1), _trace(devices=2)
    for by, scope in (("phase", "burst"), ("scope", "schedule"),
                      ("rest", None)):
        assert _read(two, by, scope) == pytest.approx(_read(one, by, scope))


def test_a_scope_no_operation_lies_under_reads_zero_not_nothing(captured):
    # The map has phases, the title simply has no such work (a box_game
    # tick flattens no row): an honest 0, which sums and compares.
    captured[TICK]["ops"].pop("fusion.5")
    assert _read(_trace(), "scope", "row_layout") == 0.0


@pytest.mark.parametrize("case", ["no capture", "empty map", "other program",
                                  "program without the map"])
def test_nothing_to_read_is_none_never_zero(case, monkeypatch):
    ops = dict(OPS)
    if case == "no capture":
        maps = {}
    elif case == "empty map":        # compiled from a tree without scopes
        maps = {TICK: _record({}, unscoped=40)}
    elif case == "other program":    # only another executable was captured
        maps = {"fused_tick_B128_F8": _record(ops)}
    else:
        maps = {TICK: _record(ops)}
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", maps)
    if case == "program without the map":   # a parent commit's xla_cache
        monkeypatch.delattr(xla_cache, "executable_phases")
    for by, scope in (("phase", "burst"), ("scope", "schedule"),
                      ("rest", None)):
        assert _read(_trace(), by, scope) is None


def test_a_trace_without_the_program_or_without_operations_is_none(captured):
    others = [("jit__unknown(3)", 1.0, 1.014)]
    assert _read(_trace(programs=others), "phase", "burst") is None
    assert _read(_trace(self_s={}), "phase", "burst") is None
    assert _read(None, "phase", "burst") is None
    # Programs that ran outside the window do not count as executions.
    late = [("jit__tick_impl(7)", 11.0, 11.014)]
    assert _read(_trace(programs=late), "rest") is None


def test_an_unknown_by_is_refused(captured):
    with pytest.raises(ValueError, match="unknown 'by'"):
        _read(_trace(), "share", "burst")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_entry_and_its_data_file(name):
    by, scope, cells = METRICS[name]
    manifest = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device programs",
        "moves": "match_frames_per_s", "workloads": cells}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["kind"] == "trace_phase"
    assert spec["program"] == "batched_tick"
    assert spec["per_program"] == "^jit__tick_impl"
    assert (spec["by"], spec.get("scope")) == (by, scope)
    assert "fusion counts whole" in spec["what"]
    # Every cell that lists it reports the end-to-end metric it moves.
    moved = {m["name"]: m for m in manifest["end_to_end"]}[entry["moves"]]
    assert set(cells) <= set(moved.get("workloads", cells))


@pytest.mark.parametrize("cell", SIX)
def test_a_cells_line_holds_them_and_they_close(cell, captured):
    """Through ``run.read_metrics`` as a traced run does it: every
    ``phase_*`` / ``scope_*`` metric the cell lists is on the line, and the
    four phases and the rest add up to ``tick_program_ms.serve``."""
    manifest = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    names = set(METRICS) | {"tick_program_ms.serve"}
    entries = [m for m in run.metric_entries(manifest, "per_layer", cell)
               if m["name"] in names]
    line = run.read_metrics(entries, "layer_metrics", _results(_trace()))
    expected = {n for n, (_, _, cells) in METRICS.items() if cell in cells}
    assert set(line) == expected | {"tick_program_ms.serve"}
    total = sum(line[n]["value"] for n in PHASES)
    assert total == pytest.approx(line["tick_program_ms.serve"]["value"])
    assert all(line[n]["unit"] == "ms" for n in expected)


def test_the_two_box_game_wan_cells_do_not_list_them():
    manifest = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in ("server256.wan", "hosted8.wan", "client.wan"):
        listed = {m["name"] for m in
                  run.metric_entries(manifest, "per_layer", cell)}
        assert not listed & set(METRICS)
