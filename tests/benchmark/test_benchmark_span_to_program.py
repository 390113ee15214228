"""Reader ``span_to_program`` (PR 35): a jitted call's span split at the
start of the device program it launched, on the one clock of the trace."""

import gzip
import os

import pytest

from benchmark import run
from benchmark.readers import span_to_program
from benchmark.readers.common import Results
from benchmark.reduce import trace as rt

ROOT = run.ROOT
CALL = "ggrs/serve_dispatch"
SERVER_CELLS = ["server256.synctest", "server256.quarter", "server256.wan"]
CLIENT_CELLS = ["client.wan", "client.lan", "boids1k.wan", "lobby8.wan"]
METRICS = {
    "launch_lag_ms.serve": (CALL, "launch_lag", SERVER_CELLS),
    "call_tail_ms.serve": (CALL, "call_tail", SERVER_CELLS),
    "launch_lag_ms.client": ("ggrs/tick_enqueue", "launch_lag", CLIENT_CELLS),
    "call_tail_ms.client": ("ggrs/tick_enqueue", "call_tail", CLIENT_CELLS),
}


def _trace(calls, programs, other_thread=()):
    """A hand-made trace: the window 0..10 s on one thread with ``calls``
    (instances of the span), ``programs`` on device 0."""
    main = [(rt.WINDOW_SPAN, 0.0, 10.0), ("bench/run_frame", 0.0, 10.0)]
    main += [(CALL, s, e) for s, e in calls]
    threads = [sorted(main, key=lambda e: e[1])]
    if other_thread:
        threads.append([(CALL, s, e) for s, e in other_thread])
    return rt.Trace(
        spans=sorted(sum(threads, []), key=lambda e: e[1]),
        modules={0: [(name, s, e) for name, s, e in programs]},
        blocks={}, op_self_s={}, threads=threads)


def _read(trace, part, **spec):
    results = Results(window_s=10.0, series={}, scalars={}, counters={},
                      program_series={}, trace=trace,
                      trace_window=rt.window_of(trace) if trace else None)
    return span_to_program.read(
        {"kind": "span_to_program", "span": CALL, "part": part, **spec},
        results)


def test_a_call_that_returns_after_and_one_that_returns_before_the_start():
    # First call: the program starts 0.2 s in, the call returns 0.3 s
    # later. Second: the call returns 0.1 s BEFORE the device starts.
    calls = [(1.0, 1.5), (3.0, 3.4)]
    programs = [("jit__tick_impl(1)", 1.2, 1.9),
                ("jit__tick_impl(1)", 3.5, 4.0)]
    pairs = span_to_program.launches(_trace(calls, programs), CALL,
                                     (0.0, 10.0))
    assert pairs == [pytest.approx((0.2, 0.3)), pytest.approx((0.5, -0.1))]
    # By construction: the two parts of an instance are its duration.
    for (lag, tail), (s, e) in zip(pairs, calls):
        assert lag + tail == pytest.approx(e - s)


def test_two_calls_in_one_tick_each_take_their_own_program():
    # A split tick: the front's call and the rollout's, back to back; the
    # second call's program is not the first's, though both follow both.
    calls = [(1.0, 1.2), (1.25, 1.5)]
    programs = [("jit__unknown(7)", 1.1, 1.3), ("jit__unknown(9)", 1.4, 9.0)]
    tr = _trace(calls, programs)
    assert span_to_program.launches(tr, CALL, (0.0, 10.0)) == [
        pytest.approx((0.1, 0.1)), pytest.approx((0.15, 0.1))]
    assert _read(tr, "launch_lag", reduce="mean") == pytest.approx(125.0)
    assert _read(tr, "call_tail") == pytest.approx(100.0)   # ms, the median


def test_a_span_with_no_program_is_left_out():
    # The second call launched nothing before the third began; the fourth
    # is cut off by the window's end; a call on another thread is not the
    # window's.
    calls = [(1.0, 1.5), (2.0, 2.5), (3.0, 3.5), (9.8, 10.5)]
    programs = [("jit__tick_impl(1)", 1.1, 1.3),
                ("jit__tick_impl(1)", 3.2, 3.3)]
    tr = _trace(calls, programs, other_thread=[(5.0, 5.5)])
    tr.modules[0].append(("jit__tick_impl(1)", 5.1, 5.2))
    assert span_to_program.launches(tr, CALL, (0.0, 10.0)) == [
        pytest.approx((0.1, 0.4)), pytest.approx((0.2, 0.3))]


def test_a_pattern_keeps_other_programs_out():
    calls = [(1.0, 1.5)]
    programs = [("jit__row_digest(3)", 1.05, 1.1),
                ("jit__tick_impl(1)", 1.3, 1.9)]
    tr = _trace(calls, programs)
    assert _read(tr, "launch_lag") == pytest.approx(50.0)
    assert _read(tr, "launch_lag", pattern="^jit__tick_impl") == (
        pytest.approx(300.0))


def test_nothing_to_read_is_nothing_not_an_error():
    # No trace (an untraced run), no such span (a parent commit), no
    # device plane, a window without an instance: None, never a raise.
    assert _read(None, "launch_lag") is None
    assert _read(_trace([], [("jit__tick_impl(1)", 1.0, 2.0)]),
                 "launch_lag") is None
    no_device = _trace([(1.0, 1.5)], [])
    no_device.modules = {}
    assert _read(no_device, "call_tail") is None
    with pytest.raises(ValueError):
        _read(_trace([(1.0, 1.5)], []), "duration")


def test_the_recorded_trace_has_no_such_span_and_reads_nothing():
    """``client_wan_12ticks`` was recorded before the program wrote
    ``ggrs/`` spans (PR 24): what a parent without the span gives."""
    with gzip.open(os.path.join(ROOT, "benchmark", "testdata",
                                "client_wan_12ticks.xplane.pb.gz")) as f:
        tr = rt.load(f.read())
    assert not any(n == "ggrs/tick_enqueue" for n, _, _ in tr.spans)
    results = Results(window_s=0.2, series={}, scalars={}, counters={},
                      program_series={}, trace=tr,
                      trace_window=rt.window_of(tr))
    spec = run._load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "launch_lag_ms.client.json"))
    assert span_to_program.read(spec, results) is None
    assert run.read_metrics([{"name": "launch_lag_ms.client", "unit": "ms"}],
                            "layer_metrics", results) == {}
    # With the benchmark's own span as the call, the same trace splits:
    # every update() launched a program, and the parts add up.
    pairs = span_to_program.launches(tr, "bench/update", rt.window_of(tr),
                                     "^jit__unknown")
    updates = [(s, e) for n, s, e in tr.spans if n == "bench/update"
               and s >= rt.window_of(tr)[0] and e <= rt.window_of(tr)[1]]
    assert len(pairs) == len(updates) > 5
    for (lag, tail), (s, e) in zip(pairs, updates):
        assert lag > 0 and lag + tail == pytest.approx(e - s)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_files_and_the_manifest_agree(name):
    span, part, cells = METRICS[name]
    spec = run._load(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))
    assert (spec["kind"], spec["span"], spec["part"]) == (
        "span_to_program", span, part)
    manifest = run._load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    assert entry["workloads"] == cells
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    assert entry["moves"] == (
        "match_frames_per_s" if name.endswith(".serve") else "frame_ms.p50")
