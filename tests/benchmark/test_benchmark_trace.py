"""The reduction from a profiler trace to numbers, on the small trace
recorded on the v5e (12 ticks of ``client.wan``, cut from a 5 s traced run
of PR 23) and on hand-made events."""

import gzip
import os

import pytest

from benchmark.reduce import trace as rt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "benchmark", "testdata",
                        "client_wan_12ticks.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED) as src:
        path.write_bytes(src.read())
    return rt.load(str(path))


def test_recorded_trace_is_small():
    assert os.path.getsize(RECORDED) < 512 * 1024


def test_recorded_window_and_spans(recorded):
    lo, hi = rt.window_of(recorded)
    assert hi - lo == pytest.approx(0.2, abs=1e-9)
    names = [n for n, _, _ in recorded.spans]
    for span in ("bench/pacer_sleep", "bench/update", "bench/readable",
                 "bench/far_end"):
        assert names.count(span) == 12
    assert names.count(rt.WINDOW_SPAN) == 1
    # Recorded before the program had spans of its own (PR 24): one thread,
    # the benchmark's spans only, and they do not nest.
    assert len(recorded.threads) == 1
    assert sorted(recorded.threads[0], key=lambda e: e[1]) == recorded.spans
    assert all(n.startswith("bench/") for n in names)
    assert recorded.dropped_at is None
    assert list(recorded.modules) == [0] and len(recorded.modules[0]) == 72


def test_recorded_busy_and_idle(recorded):
    win = rt.window_of(recorded)
    busy = rt.busy_seconds(recorded, win)
    assert busy == pytest.approx(0.0032591670, rel=1e-6)
    idle_share = 1 - busy / (win[1] - win[0])
    assert 0.98 < idle_share < 0.99      # a paced client leaves the chip idle
    gaps = dict(rt.idle_gaps(recorded, win))
    # Idle plus busy is the window, to the nanosecond.
    assert sum(gaps.values()) + busy == pytest.approx(0.2, abs=1e-8)
    assert max(gaps, key=gaps.get) == "bench/pacer_sleep"
    assert gaps["bench/pacer_sleep"] == pytest.approx(0.08231418, rel=1e-6)
    assert gaps["bench/far_end"] == pytest.approx(0.052379735, rel=1e-6)


def test_recorded_program_time_by_pattern_and_host_span(recorded):
    win = rt.window_of(recorded)
    total, n = rt.program_time(recorded, "jit__unknown", win)
    assert n == 24 and total == pytest.approx(0.003363987, rel=1e-6)
    client, n_client = rt.program_time(
        recorded, "jit__unknown", win, ["bench/update", "bench/readable"])
    far, n_far = rt.program_time(recorded, "jit__unknown", win,
                                 ["bench/far_end"])
    assert (n_client, n_far) == (12, 12)
    assert client + far == pytest.approx(total, rel=1e-9)
    assert client == pytest.approx(0.002218159, rel=1e-6)
    assert rt.program_time(recorded, "no_such_program", win) == (0.0, 0)
    # A window that ends early leaves out what does not fit whole.
    half = (win[0], win[0] + 0.1)
    assert rt.program_time(recorded, "jit__unknown", half)[1] == 12


def test_recorded_top_ops_are_self_times(recorded):
    top = rt.top_ops(recorded, 10)
    assert [name for name, _ in top[:3]] == ["while.18", "while.5", "while.19"]
    assert any("/" in name for name, _ in top)       # loop bodies are nested
    total_self = sum(sum(v.values()) for v in recorded.op_self_s.values())
    busy = rt.busy_seconds(recorded, (0.0, 1e9))
    # Sibling operations overlap a little (a copy in flight beside a fusion).
    assert total_self == pytest.approx(busy, rel=0.05)


def test_short_name_cuts_the_hlo_text():
    text = "%while.137 = (s32[]{:T(128)}, pred[64,9,16]{0,2,1}) while(...)"
    assert rt.short_name(text) == "while.137"
    assert rt.short_name("fusion.3") == "fusion.3"


def test_reduce_ops_nesting_blocks_and_self_time():
    us = 1e-6
    events = [
        ("%while.1 = (...)", 0.0, 100 * us),
        ("%fusion.2 = f32[]", 10 * us, 30 * us),
        ("%fusion.3 = f32[]", 40 * us, 90 * us),
        ("%copy.4 = f32[]", 105 * us, 110 * us),     # 5 us gap: same block
        ("%fusion.5 = f32[]", 200 * us, 210 * us),   # 90 us gap: new block
    ]
    blocks, self_s = rt.reduce_ops(events)
    assert len(blocks) == 2
    assert blocks[0][0] == 0.0 and blocks[0][1] == pytest.approx(110 * us)
    assert blocks[0][2] == pytest.approx(105 * us)   # busy inside the block
    assert blocks[1] == pytest.approx((200 * us, 210 * us, 10 * us))
    assert self_s["while.1"] == pytest.approx(30 * us)
    assert self_s["while.1/fusion.2"] == pytest.approx(20 * us)
    assert self_s["while.1/fusion.3"] == pytest.approx(50 * us)
    assert self_s["copy.4"] == pytest.approx(5 * us)


def _hand_made(dropped_at=None):
    blocks, self_s = rt.reduce_ops([
        ("%a = x", 1.0, 1.5), ("%b = x", 2.0, 2.25), ("%c = x", 3.5, 3.75),
    ])
    return rt.Trace(
        spans=[(rt.WINDOW_SPAN, 0.5, 4.5), ("bench/run_frame", 0.5, 2.1),
               ("bench/final_wait", 2.1, 4.5)],
        modules={0: [("jit__tick_impl(1)", 1.0, 1.5),
                     ("jit__tick_impl(1)", 2.0, 2.25),
                     ("jit__tick_impl(1)", 3.5, 3.75)]},
        blocks={0: blocks}, op_self_s={0: self_s}, dropped_at=dropped_at)


def test_hand_made_gaps_are_charged_to_host_spans():
    tr = _hand_made()
    win = rt.window_of(tr)
    assert win == (0.5, 4.5)
    assert rt.busy_seconds(tr, win) == pytest.approx(1.0)
    gaps = dict(rt.idle_gaps(tr, win))
    assert gaps["bench/run_frame"] == pytest.approx(0.5 + 0.5)
    assert gaps["bench/final_wait"] == pytest.approx(1.25 + 0.75)
    assert rt.program_time(tr, "tick_impl", win) == (pytest.approx(1.0), 3)

    # The program's spans nest inside the benchmark's: each gap goes to the
    # innermost span open at that instant, and only what no narrower span
    # covers stays with its parent.
    tr.spans += [("ggrs/serve_tick", 0.6, 2.0),
                 ("ggrs/serve_sessions", 0.6, 1.2),
                 ("ggrs/serve_dispatch", 1.2, 1.9),
                 ("ggrs/serve_native_batch", 1.2, 1.4)]
    nested = dict(rt.idle_gaps(tr, win))
    assert nested == pytest.approx({
        "bench/run_frame": 0.1,             # 0.5-0.6, before the group's tick
        "ggrs/serve_sessions": 0.4,         # 0.6-1.0; busy from 1.0 to 1.5
        "ggrs/serve_dispatch": 0.4,         # 1.5-1.9
        "ggrs/serve_tick": 0.1,             # 1.9-2.0: its self time
        "bench/final_wait": 1.25 + 0.75,
    })
    assert "ggrs/serve_native_batch" not in nested   # the device was busy
    assert sum(nested.values()) == pytest.approx(sum(gaps.values()))
    # What does not read host spans reads what it read.
    assert rt.window_of(tr) == win
    assert rt.busy_seconds(tr, win) == pytest.approx(1.0)
    assert rt.program_time(tr, "tick_impl", win) == (pytest.approx(1.0), 3)
    assert rt.program_time(tr, "tick_impl", win, ["bench/run_frame"]) == (
        pytest.approx(0.75), 2)


def _threaded():
    tr = _hand_made()
    other = [("ggrs/gc_pause", 0.5, 4.5)]           # a thread of its own
    tr.threads = [other, list(tr.spans)]
    tr.spans = sorted(tr.spans + other, key=lambda e: e[1])
    return tr


def test_gaps_are_charged_on_the_thread_that_holds_the_window():
    tr = _threaded()
    assert rt.window_thread(tr) is tr.threads[1]
    gaps = dict(rt.idle_gaps(tr, rt.window_of(tr)))
    assert "ggrs/gc_pause" not in gaps
    assert gaps["bench/run_frame"] == pytest.approx(1.0)


def test_nest_gives_parents_self_times_and_innermost_segments():
    instances, segments = rt.nest([
        ("bench/update", 0.0, 10.0), ("ggrs/stage_update", 1.0, 9.0),
        ("ggrs/poll", 1.0, 2.0), ("ggrs/tick_dispatch", 3.0, 8.0),
        ("ggrs/tick_enqueue", 4.0, 8.5),    # outlives its parent: cut at 8
        ("bench/readable", 12.0, 13.0),
    ])
    by_name = {i[0]: i for i in instances}
    assert by_name["ggrs/poll"][1:] == ("ggrs/stage_update", 1.0, 1.0)
    assert by_name["ggrs/tick_enqueue"][1:] == ("ggrs/tick_dispatch", 4.0, 4.0)
    assert by_name["ggrs/tick_dispatch"][1:] == ("ggrs/stage_update", 5.0, 1.0)
    assert by_name["ggrs/stage_update"][1:] == ("bench/update", 8.0, 2.0)
    assert by_name["bench/update"][1:] == (None, 10.0, 2.0)
    assert segments == [
        (0.0, 1.0, "bench/update"), (1.0, 2.0, "ggrs/poll"),
        (2.0, 3.0, "ggrs/stage_update"), (3.0, 4.0, "ggrs/tick_dispatch"),
        (4.0, 8.0, "ggrs/tick_enqueue"), (8.0, 9.0, "ggrs/stage_update"),
        (9.0, 10.0, "bench/update"), (10.0, 12.0, None),
        (12.0, 13.0, "bench/readable"),
    ]
    assert rt.nest([]) == ([], [])


def test_the_tools_innermost_is_the_benchmarks(recorded):
    """tools/trace_spans.py still carries the reduction this one was moved
    from (a benchmark PR may not edit it): until it imports this one, the
    two agree on every set of spans here."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "trace_spans.py")
    if not os.path.isfile(path):
        return
    spec = importlib.util.spec_from_file_location("trace_spans_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    nested = _hand_made().spans + [("ggrs/serve_tick", 0.6, 2.0),
                                   ("ggrs/serve_sessions", 0.6, 1.2)]
    for spans in (nested, recorded.spans, recorded.threads[0], []):
        assert tool.nest(list(spans)) == rt.nest(list(spans))


def test_dropped_trace_buffers_cut_the_window():
    tr = _hand_made(dropped_at=3.0)
    win = rt.window_of(tr)
    assert win == (0.5, 3.0)
    assert rt.busy_seconds(tr, win) == pytest.approx(0.75)
    assert rt.program_time(tr, "tick_impl", win)[1] == 2


def test_a_trace_without_the_window_span_is_refused():
    tr = _hand_made()
    tr.spans = [s for s in tr.spans if s[0] != rt.WINDOW_SPAN]
    with pytest.raises(ValueError):
        rt.window_of(tr)
