#!/usr/bin/env python3
"""What the tick's jitted call costs by its SIGNATURE (ROADMAP S8, step 0).

    python3 tools/enqueue_probe.py [--calls 300] [--branches 256] [--slots 64]

Times, host clock around the call only (median of ``--calls`` after a warm-up,
the outputs blocked on outside the timed part, each call fed the previous
call's outputs as the tick loop does):

(a) the client's own warmed fused tick (``FusedTickExecutor.run``: staging and
    the jitted call) and the server's batched tick (span ``serve_dispatch`` of
    an idle ``_dispatch``), as the tree has them, with the buffers that call
    hands over and gets back (in + out);
(b) a jitted identity-like body (``x + 1`` a leaf) with the flattened
    signature and shapes the PARENT of PR 28 gave that call (56 in / 43 out on
    box_game: one buffer a leaf);
(c) the same bytes packed: one device array a dtype for the carried trees,
    the host arguments as three NumPy arrays, the live state a leaf each and
    one checksum array out;
(d) one array in, one out;
(e) variant (c) with its host bytes split over 1, 3 and 15 NumPy arrays.

So (b) - (c) is what the buffers cost and (d) what a launch costs whatever it
carries. Prints one table and writes ``chiprun_out/enqueue_probe.json``. On
the CPU the times are counts of jax's own argument handling, not chip times.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.state import SnapshotRing, ring_init

P, MAXPRED = 2, 8


def bump(x):
    return ~x if x.dtype == jnp.bool_ else x + jnp.ones((), x.dtype)


def timed(call, feed, args, calls: int, warm: int = 20):
    """Median / p90 ms of ``call(*args)`` alone; ``feed(out, args)`` makes the
    next call's arguments from this call's outputs."""
    samples = []
    for k in range(warm + calls):
        t0 = time.perf_counter()
        out = call(*args)
        dt = time.perf_counter() - t0
        jax.block_until_ready(out)
        if k >= warm:
            samples.append(dt * 1e3)
        args = feed(out, args)
    q = statistics.quantiles(samples, n=10)
    return {"p50": statistics.median(samples), "p10": q[0], "p90": q[-1]}


def stacked(tree, prefix):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, prefix + x.shape), tree
    )


def old_signature(S: int, B: int):
    """(device trees, host arrays, output leaves' shapes) of the tick call as
    the parent of PR 28 made it: ``S == 0`` the client's, else the server's
    ``[S]``-stacked one."""
    state = box_game.make_world(P).commit()
    ring = ring_init(state, MAXPRED + 1)
    F, MF = MAXPRED, MAXPRED + 2
    pre = (S,) if S else ()
    spec_ring = SnapshotRing(
        states=stacked(state, pre + (B, F)),
        frames=jnp.full(pre + (B, F), -1, jnp.int32),
        checksums=jnp.zeros(pre + (B, F, 2), jnp.uint32),
    )
    trees = (
        stacked(ring, pre), stacked(state, pre), spec_ring,
        stacked(state, pre + (B,)),
    )
    zeros = box_game.INPUT_SPEC.zeros_np(P)
    i32 = lambda: np.zeros(pre, np.int32)
    host = {
        "scalars": [i32() for _ in range(8)]
        + [np.zeros(pre, bool), np.zeros(pre, bool)],
        "bits": np.zeros(pre + (MF,) + zeros.shape, zeros.dtype),
        "status": np.zeros(pre + (MF, P), np.int32),
        "masks": [np.zeros(pre + (MF,), bool), np.zeros(pre + (MF,), bool)],
        "branch_bits": np.zeros(pre + (B, F) + zeros.shape, zeros.dtype),
        "spec_status": np.zeros((F, P), np.int32),
    }
    cs = [
        jnp.zeros(pre + (MF, 2), jnp.uint32), jnp.zeros(pre + (MF, 2), jnp.uint32),
        jnp.zeros(pre + (B, F, 2), jnp.uint32),
    ]
    return trees, host, cs


def variant_b(trees, host, cs, host_as_numpy: bool):
    """One buffer a leaf: 56 in / 43 out on box_game."""
    flat_host = (
        host["scalars"] + [host["bits"], host["status"]] + host["masks"]
        + [host["branch_bits"]]
    )
    if not host_as_numpy:
        # The client caches its scalars, masks and spec_status on the device
        # and passes three NumPy tensors.
        keep = {id(host["bits"]), id(host["status"]), id(host["branch_bits"])}
        flat_host = [h if id(h) in keep else jnp.asarray(h) for h in flat_host]
    flat_host.append(jnp.asarray(host["spec_status"]))  # cached on both paths

    @jax.jit
    def fn(trees, flat_host, cs):
        k = flat_host[0].astype(jnp.uint32).reshape(-1)[0]
        return (
            jax.tree_util.tree_map(bump, trees),
            [c + k for c in cs],
        )

    n_in = len(jax.tree_util.tree_leaves((trees, flat_host)))
    n_out = len(jax.tree_util.tree_leaves((trees, cs)))
    call = lambda tr: fn(tr, flat_host, cs)
    feed = lambda out, args: (out[0],)
    return call, feed, (trees,), n_in, n_out


def pack_by_dtype(leaves):
    groups = {}
    for x in leaves:
        groups.setdefault(jnp.dtype(x.dtype).name, []).append(x.reshape(-1))
    return {k: jnp.concatenate(v) for k, v in sorted(groups.items())}


def variant_c(trees, host, cs, n_host: int):
    """The carried trees one array a dtype, the live state a leaf each, the
    host bytes in ``n_host`` NumPy arrays, one checksum array out."""
    ring, state, prev_rings, prev_states = trees
    carry = pack_by_dtype(jax.tree_util.tree_leaves((ring, prev_rings, prev_states)))
    host_bytes = np.concatenate([
        np.asarray(h).reshape(-1).view(np.uint8)
        for h in host["scalars"] + [host["bits"], host["status"],
                                    host["branch_bits"]]
    ])
    host_arrays = [np.ascontiguousarray(c) for c in np.array_split(host_bytes, n_host)]
    cs_all = jnp.concatenate([c.reshape(-1) for c in cs])

    @jax.jit
    def fn(carry, state, host_arrays, cs_all):
        k = host_arrays[0][0].astype(jnp.uint32)
        return (
            {n: bump(x) for n, x in carry.items()},
            jax.tree_util.tree_map(bump, state),
            cs_all + k,
        )

    n_in = len(jax.tree_util.tree_leaves((carry, state, host_arrays)))
    n_out = len(jax.tree_util.tree_leaves((carry, state))) + 1

    def call(carry, state):
        # Fresh host arrays a call, as a tick's are.
        return fn(carry, state, [h.copy() for h in host_arrays], cs_all)

    feed = lambda out, args: (out[0], out[1])
    return call, feed, (carry, state), n_in, n_out


def variant_d():
    fn = jax.jit(bump)
    return fn, (lambda out, args: (out,)), (jnp.zeros((1024,), jnp.int32),), 1, 1


def real_client(B: int, calls: int):
    from bevy_ggrs_tpu.fused import TickInts, plan_tick
    from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner

    r = SpeculativeRollbackRunner(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        max_prediction=MAXPRED, num_players=P, input_spec=box_game.INPUT_SPEC,
        num_branches=B, spec_frames=MAXPRED,
    )
    r.warmup()
    zeros = box_game.INPUT_SPEC.zeros_np(P)
    bb = np.zeros((B, MAXPRED) + zeros.shape, zeros.dtype)
    r._dispatch_rollout(r.frame, bb)
    carry, frame = [r._packed_carry()], [r.frame]

    def call():
        # A steady tick: one (save, advance) step, a fresh rollout.
        f = frame[0]
        ints = TickInts.zeros(r._fused.burst_frames, P)
        plan_tick(ints, f, None, 1, f, MAXPRED, None, f + 1, r._ring_depth)
        return r._fused.run(
            carry[0], ints, zeros[None], np.zeros((1, P), np.int32), bb.copy()
        )

    def feed(out, args):
        carry[0] = out[0]
        frame[0] += 1
        return ()

    t = timed(call, feed, (), calls)
    return r._fused.io.last, t


def real_server(S: int, calls: int):
    from bevy_ggrs_tpu.serve.batch import BatchedSessionCore

    core = BatchedSessionCore(
        box_game.make_schedule(), box_game.make_world(P).commit(),
        MAXPRED, P, box_game.INPUT_SPEC, num_slots=S, num_branches=8,
    )
    core.warmup()
    for _ in range(S):
        core.admit()
    # The whole of an idle dispatch (host loop included) is not the call; the
    # span `serve_dispatch` is. Read it from the core's own series.
    from bevy_ggrs_tpu.utils.metrics import Metrics

    core._set_sinks(Metrics(), None)
    for k in range(20 + calls):
        core._dispatch({})
        jax.block_until_ready(core.states)
    s = core.metrics.series["serve_dispatch_ms"][20:]
    q = statistics.quantiles(s, n=10)
    return core._exec.io.last, {
        "p50": statistics.median(s), "p10": q[0], "p90": q[-1]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--branches", type=int, default=256)
    ap.add_argument("--slots", type=int, default=64)
    a = ap.parse_args()
    dev = jax.devices()[0]
    rows = []

    def row(name, n_in, n_out, t):
        rows.append({"variant": name, "in": n_in, "out": n_out, **t})
        print(f"{name:<44} {n_in!s:>4} in {n_out!s:>4} out  "
              f"p50 {t['p50']:.3f}  p10 {t['p10']:.3f}  p90 {t['p90']:.3f} ms",
              flush=True)

    for label, S, B in (("client", 0, a.branches), ("server", a.slots, 8)):
        sig = old_signature(S, B)
        for name, v in (
            (f"(b) {label}: a buffer a leaf, host as the tick passes it",
             variant_b(*sig, host_as_numpy=bool(S))),
            (f"(c) {label}: packed, 3 host arrays", variant_c(*sig, n_host=3)),
            (f"(e) {label}: packed, 1 host array", variant_c(*sig, n_host=1)),
            (f"(e) {label}: packed, 15 host arrays", variant_c(*sig, n_host=15)),
        ):
            call, feed, args, n_in, n_out = v
            row(name, n_in, n_out, timed(call, feed, args, a.calls))
    call, feed, args, n_in, n_out = variant_d()
    row("(d) one array in, one out", n_in, n_out, timed(call, feed, args, a.calls))
    n, t = real_client(a.branches, a.calls)
    row("(a) client: FusedTickExecutor.run (stage + call)", n, "", t)
    n, t = real_server(a.slots, a.calls)
    row("(a) server: span serve_dispatch of an idle dispatch", n, "", t)

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls": a.calls, "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "enqueue_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["device"]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
