#!/usr/bin/env python3
"""When is peer 0's state readable if the rollout runs BEHIND the burst, in a
program of its own? (ISSUE 32, step 0; PERF.md section 6, PR 32.)

    python3 tools/split_probe.py [--cells boids1k.wan,client.wan]
                                 [--seconds 8] [--seed 2440000101]

For each cell (the benchmark's own pair of peers: ``benchmark/drivers``,
two peers on loopback, 60 Hz open loop) it measures, in one process:

1. ``fused``: the cell's own window (``Driver.window``): due time of a tick
   to ``block_until_ready(runner.state)``, the median over the ticks, with
   every tick on the one fused program; ``split``: the same window as a
   runner that chose two programs at warm-up ticks by itself.
2. ``pair``: the same loop with peer 0's ``tick`` replaced by the pair the
   runner falls back to, ``handle_requests(requests)`` then
   ``speculate(confirmed_frame)``, timed as a split tick would be felt: the
   state ``s`` that ``handle_requests`` left (the serial executor's output)
   is kept, ``speculate`` only enqueues, then ``block_until_ready(s)``. Also
   how long after ``s`` the rollout's own outputs became ready
   (``runner.state`` after ``speculate`` is the rollout program's
   pass-through): the readiness that decouples, or does not.
3. the two times the runner's warm-up decides from (on a runner that has
   them: gauges ``rollout_device_ms`` / ``extra_call_ms``), and the same two
   taken here by hand: the blocking wall time of one rollout dispatch and of
   one absorb-only call that commits nothing, median and least of 9.

Prints one JSON object a cell and writes them to
``chiprun_out/split_probe/<cell>.json``. Needs the TPU for times that mean
anything (``chiprun -- python3 tools/split_probe.py``); on the CPU it runs
the same code at the cell's toy size (``--toy``).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def build(cell: str, seed: int, toy: bool):
    from benchmark import run
    from benchmark.drivers.common import Context

    overrides = run.load_toy(cell) if toy else None
    _, _, config, traffic = run.load_cell(cell, overrides)
    title = importlib.import_module(f"benchmark.titles.{config['title']}")
    ctx = Context(
        config=config, traffic=traffic, seed=seed, trace=False, control=None,
        title=title, annotate=lambda name: contextlib.nullcontext(),
        reference=importlib.import_module(
            f"benchmark.reference.{title.REFERENCE}"),
    )
    driver = importlib.import_module(
        f"benchmark.drivers.{config['driver']}").Driver(ctx)
    driver.setup()
    return driver


def pair_window(driver, seconds: float) -> dict:
    """``Driver.window``'s loop with peer 0 on the fall-back pair."""
    import jax

    from benchmark.drivers.common import wait_until

    a, b, net, runner = driver.a, driver.b, driver.net, driver.runner
    held = {}

    def pair_tick(requests, confirmed_frame, session=None):
        runner.ticks_total += 1
        runner.flush_reports(session)
        runner.handle_requests(requests, session)
        held["s"] = runner.state
        runner.speculate(confirmed_frame, session)

    runner.tick = pair_tick
    state_ms, rollout_after_ms, update_ms = [], [], []
    dt = driver.dt
    t0 = time.perf_counter()
    k = 0
    try:
        while k * dt < seconds:
            due = t0 + k * dt
            wait_until(due)
            start = time.perf_counter()
            net.advance(dt)
            held.clear()
            a.update(now=net.now)
            returned = time.perf_counter()
            if "s" in held:
                jax.block_until_ready(held["s"])
                ready = time.perf_counter()
                jax.block_until_ready(runner.state)
                state_ms.append((ready - due) * 1e3)
                rollout_after_ms.append((time.perf_counter() - ready) * 1e3)
                update_ms.append((returned - start) * 1e3)
            b.update(now=net.now)
            k += 1
    finally:
        del runner.tick
    jax.block_until_ready((runner.state, b.stage.runner.state))
    med = statistics.median
    return {
        "ticks": len(state_ms),
        "state_ready_ms.p50": med(state_ms),
        "update_ms.p50": med(update_ms),
        "rollout_ready_after_state_ms.p50": med(rollout_after_ms),
    }


def warmup_times(runner, reps: int = 9) -> dict:
    """One rollout dispatch, and one call that carries the same carry and
    no rollout, each blocked on."""
    import jax

    from bevy_ggrs_tpu.fused import TickInts, plan_rollout

    fused = runner._fused
    bits = runner._result.branch_bits if runner._result is not None else None
    if bits is None:
        raise RuntimeError("no pending rollout to take the branch tensor from")

    def rollout():
        ints = TickInts.zeros(fused.burst_frames, runner.num_players)
        plan_rollout(ints, runner.frame, runner.frame, runner._ring_depth)
        return fused.run(runner._packed_carry(), ints, (), (), bits)

    def absorb():
        return fused.commit_absorb(
            runner._packed_carry(), 0, 0, 0, 0, runner.spec_frames)

    out = {}
    for name, call in (("rollout_call_ms", rollout), ("absorb_call_ms", absorb)):
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(call())
            samples.append((time.perf_counter() - t0) * 1e3)
        out[name] = {"p50": statistics.median(samples), "min": min(samples)}
    return out


def probe(cell: str, seconds: float, seed: int, toy: bool) -> dict:
    import jax

    driver = build(cell, seed, toy)
    runner = driver.runner
    out = {
        "cell": cell, "seed": seed, "seconds": seconds, "toy": toy,
        "device": jax.devices()[0].device_kind,
        "gauges": {k: getattr(runner, k, None)
                   for k in ("rollout_device_ms", "extra_call_ms")},
        "runner_splits": getattr(runner, "_split", None),
    }
    # The cell's own window on the one fused program, and (a runner that
    # chose to split) as the runner ticks by itself.
    for name, split in (("fused", False), ("split", True)):
        if split and not out["runner_splits"]:
            continue
        if out["runner_splits"] is not None:
            runner._split = split
        series = driver.series["frame_ms"]
        del series[:]
        driver.window(seconds)
        out[name] = {"frame_ms.p50": statistics.median(series),
                     "ticks": len(series)}
    out["by_hand"] = warmup_times(runner)
    out["pair"] = pair_window(driver, seconds)
    desyncs = sum(
        1 for app in driver.apps for ev in app.events
        if getattr(ev.kind, "name", "") == "DESYNC_DETECTED")
    out["desync_events"] = desyncs
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", default="boids1k.wan,client.wan")
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--seed", type=int, default=2440000101)
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args(argv)
    from bevy_ggrs_tpu.utils import xla_cache

    xla_cache.ensure_persistent_compilation_cache()
    out_dir = os.path.join(ROOT, "chiprun_out", "split_probe")
    os.makedirs(out_dir, exist_ok=True)
    for i, cell in enumerate(args.cells.split(",")):
        result = probe(cell, args.seconds, args.seed + i, args.toy)
        with open(os.path.join(out_dir, cell + ".json"), "w",
                  encoding="utf-8") as f:
            json.dump(result, f, indent=1)
        print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
