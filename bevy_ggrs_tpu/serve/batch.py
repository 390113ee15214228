"""The session axis: S independent matches advanced by ONE fused dispatch.

"Millions of users" is ~100k concurrent 2-8 player matches, not one giant
world — and a per-session singleton pays its own dispatch, its own compile
cache and its own slice of the 16.7 ms frame for every one of them. This
module applies the Podracer/Anakin batched-environments shape (PAPERS.md,
arXiv 2104.06272) to rollback sessions: the fused tick program
(:meth:`~bevy_ggrs_tpu.fused.FusedTickExecutor._tick_impl` — absorb +
serial burst + B-branch speculative rollout, every phase gated by traced
scalars) vmaps over a leading slot axis, so one compiled executable
advances S matches — each with its OWN frame counter, rollback depth and
branch tree — per dispatch.

One rule keeps that vmap dense: **an index that differs per lane never
goes into a dynamic slice under the slot vmap.** Every slot has its own
frame, so ``frame % depth`` and the matched ``branch`` are per-lane
indices, and jax batches a ``dynamic_update_slice`` / ``dynamic_slice``
whose index is batched into a ``scatter`` / ``gather``. The TPU compiler
expands each into a loop over the S slots of a few tiny ops: 27 such
loops inside the tick's three scans, 46.0 ms a dispatch at S=64 x B=8 x
F=8 against 0.19 ms for the unvmapped twin with twice the lanes
(``PERF.md`` section 6, PR 25). So the ring-row and branch accesses
(``state.py`` ``ring_row_write`` / ``ring_row_read``) carry a batching
rule: where the index has the batch axis they lower to a select over the
small static axis (bit-moving, so the bitwise guarantees hold; measured
0.84 ms a dispatch), and where it has not (the singleton, the
rollout's vmap over branches) they stay the in-place dynamic slice.
Nothing here chooses: vmapping the index is what makes the choice. A
title's own per-lane lookups are the title's, and the same disease: an
entity's input read as ``inputs.bits[handle]`` becomes a ``gather`` of one
serial step an entity under the slot axis (9.7 of the served 1,024-boid
dispatch's 48 ms until ``boids`` read it through
``PlayerInputs.for_handles``, the same select over the small static
axis; ``PERF.md`` section 6, PR 38; box_game's 16 rows still index).
The price is ``depth`` x the row's bytes a save instead of
one row: nothing for box_game's rows, 1.3 % of the served 1,024-boid
dispatch since a burst carries large rows flat (``PERF.md`` section 6,
PR 37).

Design rules that make the batch shape static (one executable, ever):

- **Fixed capacity, padding + no-op masks.** The batch always carries S
  slots. A slot with no work this dispatch runs with every phase no-op'd
  (``absorb_n=0``, all burst masks False, ``do_load=False``) — the traced
  gates that already pad heterogeneous burst depths in the singleton
  program are exactly what makes an idle slot free of semantic effect.
- **Admit/retire without recompiles.** Admission writes a fresh singleton
  (ring, state) into a slot row via ``dynamic_update_index_in_dim`` with a
  TRACED slot index — one jitted write program covers every slot.
  Retirement is host-only bookkeeping (the stale rows are dead weight
  until readmission). ``utils.xla_cache.compile_counters()`` is the
  observable this contract is asserted against.
- **No-op slots REPLAY their previous rollout.** The batched program
  returns full ``[S, B, ...]`` speculative buffers which wholesale replace
  the previous ones — so a slot that is not ticking must re-dispatch its
  previous (anchor, from-live, branch tensor) rollout to keep its pending
  branches valid. The recompute is bitwise-identical (same executable,
  same anchor state — the slot's ring/state rows are untouched by its own
  no-op phases), so the replacement is a no-op for that slot's data.
- **Full hits re-dispatch.** The singleton runner's absorb-only fast path
  and dedup-skip are latency optimizations for a session that owns the
  whole chip; in a batch the program runs anyway, so a full hit is simply
  absorb + empty tail + a fresh rollout. Hit/skip COUNTERS therefore
  differ from a serial singleton run — committed state does not: commits
  only ever absorb branch frames whose inputs matched the corrected
  history exactly, computed by the attested executable. The parity suite
  (tests/test_batched_sessions.py) compares state bytes, frames and ring
  contents, which is the contract that matters.

What a lane's tick IS is decided in ``fused.py``, by the functions the
singleton's ``tick()`` calls: ``match_pending`` (which branch of the
pending rollout a rollback follows; the native plane stages the same match
for all lanes in one C call and hands its answer over in the same form),
``plan_tick`` (the commit, the burst geometry, the next rollout: the one
writer of a lane's :class:`~bevy_ggrs_tpu.fused.TickInts` scalars, a no-op
lane's included) and, once the dispatch is made, ``account_rollback`` (the
counters, the outcome, the ledger entry). Both host paths here only stage a
lane's inputs for them and its burst rows behind them.

Per-slot tree building is the singleton's: the native builder is
instantiated per slot (it owns a per-match C++ input-log mirror) and the
pure-Python fallback is the ONE :class:`~bevy_ggrs_tpu.branch_tree.
BranchTree` the core holds for all its slots (they share the
configuration; the log it is handed is the slot's), the class the
singleton runner builds its own from (``tests/test_branch_tree.py`` holds
the two to the same bits).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.branch_tree import BranchTree, rollout_world_steps
from bevy_ggrs_tpu.fused import (
    LANE_AXIS,
    IoBuffers,
    PackedTick,
    TickInts,
    account_rollback,
    match_pending,
    plan_rollout,
    plan_tick,
    spec_in_window,
    wanted_rows,
)
from bevy_ggrs_tpu.native import spec as native_spec
from bevy_ggrs_tpu.obs.ledger import rollback_blame
from bevy_ggrs_tpu.obs.trace import NULL_SPAN, Instrumented, null_tracer
from bevy_ggrs_tpu.predict.batch import BatchedRanker
from bevy_ggrs_tpu.predict.model import resolve_predictor
from bevy_ggrs_tpu.schedule import Schedule
from bevy_ggrs_tpu.serve.faults import SlotFault, SlotTicket
from bevy_ggrs_tpu.session.requests import Segment, SegmentError
from bevy_ggrs_tpu.state import (
    SnapshotRing,
    WorldState,
    combine64_rows,
    ring_init,
    ring_row_lowerings,
)
from bevy_ggrs_tpu.utils.metrics import null_metrics


class BatchedTickExecutor:
    """The fused tick vmapped over a leading ``[S]`` slot axis, plus the
    traced-index admit program. One instance = one compiled executable;
    every :class:`BatchedSessionCore` of the same model family (and every
    stagger group of a :class:`~bevy_ggrs_tpu.serve.server.MatchServer`)
    should share it."""

    def __init__(
        self,
        schedule: Schedule,
        num_slots: int,
        burst_frames: int,
        num_branches: int,
        spec_frames: int,
        inputs=None,
    ):
        self.schedule = schedule
        self.num_slots = int(num_slots)
        self.burst_frames = int(burst_frames)
        self.num_branches = int(num_branches)
        self.spec_frames = int(spec_frames)
        # The singleton's packed tick (``fused.py`` :class:`PackedTick`:
        # carry in, carry + live states + one checksum array out), every
        # argument carrying the slot axis: in front, but for the buffers
        # of the carry that are a rollout's rows as its loop wrote them,
        # which have it behind the steps (``packed.carry.axes``). A leaf
        # keeps a buffer of its own by the size of all ``num_slots`` copies
        # of it. ``inputs`` is one frame's ``[P, *input_shape]`` rows
        # (shape and dtype; :class:`PackedTick`).
        self.packed = PackedTick(
            schedule, self.burst_frames, self.num_branches,
            self.spec_frames, copies=self.num_slots, lane_axis=LANE_AXIS,
            inputs=inputs,
        )
        # The device trace knows this program as ``jit__tick_impl`` (the
        # benchmark's ``tick_program_ms.serve`` finds it by that name), as
        # when ``_tick_impl`` itself was vmapped here.
        def _tick_impl(carry, ints, bits, branch_bits):
            axes = self.packed.carry.axes
            return jax.vmap(
                self.packed.tick, in_axes=(axes, 0, 0, 0),
                out_axes=(axes, 0, 0), axis_name=LANE_AXIS,
            )(carry, ints, bits, branch_bits)

        self._fn = jax.jit(_tick_impl)
        self._admit = jax.jit(self._admit_impl)
        self._pack = jax.jit(self.packed.pack)
        self._unpack = jax.jit(self.packed.unpack)
        self._unpack_main = jax.jit(self.packed.unpack_main)
        self._row = jax.jit(self._row_impl)
        # ``io.last``: the series ``tick_io_buffers`` of the cores that
        # share this executor.
        self.io = IoBuffers()
        # Ring leaves traced flat / shaped so far in this process
        # (``state.py`` ``FLAT_ROW_BYTES``): what this executable's first
        # call adds is its own (:meth:`traced_ring_rows`).
        self._ring_rows0: Optional[Dict[str, int]] = dict(ring_row_lowerings)
        # Cost-observatory hook: when armed, the NEXT dispatch prices the
        # compiled program (cost_analysis/memory_analysis) and keeps its
        # phase map (which compiled operation lies under which device
        # scope) in utils.xla_cache under this name. Arm it before warmup:
        # the AOT lowering and its compile then land before any churn
        # counter is snapshotted.
        self._cost_name: Optional[str] = None
        self._captured_name: Optional[str] = None

    def enable_cost_capture(self, name: str) -> None:
        self._cost_name = str(name)

    def cost(self) -> dict:
        """The captured cost record for this executable ({} until a
        dispatch ran with capture armed, or when the backend exposes no
        cost/memory analysis)."""
        if self._captured_name is None:
            return {}
        from bevy_ggrs_tpu.utils import xla_cache

        return xla_cache.executable_costs().get(self._captured_name, {})

    def _admit_impl(self, carry, slot, new_ring, new_state):
        # The codec's own trees: the previous rollout goes through as it
        # is carried, untouched.
        codec = self.packed.carry
        rings, states, prev_rings, prev_states = codec.unpack(carry)
        write = lambda stacked, row: jax.tree_util.tree_map(
            lambda R, r: jax.lax.dynamic_update_index_in_dim(R, r, slot, 0),
            stacked, row,
        )
        states = write(states, new_state)
        carry = codec.pack(
            (write(rings, new_ring), states, prev_rings, prev_states)
        )
        return carry, states

    def admit(self, carry, slot: int, new_ring, new_state):
        """Write a fresh singleton (ring, state) into slot row ``slot`` of
        the carry; returns ``(carry, states)``. The index is traced — one
        compile covers every slot, which is what makes match churn
        recompile-free."""
        return self._admit(carry, np.int32(slot), new_ring, new_state)

    @staticmethod
    def _row_impl(stacked, slot):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, slot, 0, keepdims=False),
            stacked,
        )

    def row(self, stacked, slot: int):
        """Slot row ``slot`` of an ``[S]``-stacked tree (traced index: one
        compile a tree structure, whatever the slot)."""
        return self._row(stacked, np.int32(slot))

    def pack(self, rings, states, prev_rings, prev_states):
        """The carry of the four ``[S]``-stacked trees (one dispatch)."""
        self.packed.bind((rings, states, prev_rings, prev_states), lead=1)
        return self._pack(rings, states, prev_rings, prev_states)

    def unpack(self, carry):
        """``(rings, states, prev_rings, prev_states)`` of a carry (one
        dispatch): for readers off the serving loop. The previous rollout
        comes as ``[S, B, F, *row]`` trees, written out for the reader
        (1.2 GB a group of the churning title, whose carry is half of
        that): who wants the rings alone asks :meth:`unpack_main`."""
        return self._unpack(carry)

    def unpack_main(self, carry):
        """``(rings, states)`` of a carry (one dispatch)."""
        return self._unpack_main(carry)

    def cs_host(self, cs):
        """A tick's checksum output on the host: ``(absorb_cs, burst_cs,
        spec_cs)`` as NumPy arrays with the leading ``[S]`` axis."""
        return self.packed.cs_host(cs)

    def traced_ring_rows(self) -> Dict[str, int]:
        """``{kind: ring leaves}`` the batched tick was traced with, by the
        form its bursts carry them in ("flat" / "shaped") and its rollout
        hands them on in ("step"; "carried" / "carried_once": ``state.py``
        ``ring_row_lowerings``); told once, to
        the core whose warm-up traced the program ({} to every other)."""
        before, self._ring_rows0 = self._ring_rows0, None
        if before is None:
            return {}
        return {
            kind: n - before[kind]
            for kind, n in ring_row_lowerings.items() if n > before[kind]
        }

    def cache_size(self) -> int:
        """Compiled-variant count of the batched tick program (-1 when the
        jit internals don't expose it). 1 after warmup, and it must STAY 1
        through any amount of match churn."""
        probe = getattr(self._fn, "_cache_size", None)
        return int(probe()) if probe is not None else -1

    def run(self, carry, ints, bits, branch_bits):
        """Dispatch one batched tick on the carry. The host arguments are
        three fresh NumPy arrays (jit's C++ path transfers them while it
        shards the arguments): ``ints [S, TickInts.STATUS + burst_frames *
        P]``, a row a slot of every scalar of :class:`~bevy_ggrs_tpu.fused.
        TickInts` followed by the slot's padded status rows; ``bits [S,
        burst_frames, P, ...]``; ``branch_bits [S, B, F, P, ...]``. The
        burst masks come from ``N_BURST`` inside the program. Returns
        ``(carry, states, cs)``: the next carry, the live states as an
        ``[S]``-stacked ``WorldState`` and the checksums (:meth:`cs_host`),
        device-resident."""
        full_args = (carry, ints, bits, branch_bits)
        if self._cost_name is not None:
            from bevy_ggrs_tpu.utils import xla_cache

            name, self._cost_name = self._cost_name, None
            xla_cache.record_executable_cost(name, self._fn, *full_args)
            self._captured_name = name
        return self.io.count(
            "tick", full_args, self._fn(*full_args), host=full_args[1:]
        )


class _Slot:
    """Host-side record of one batch slot: match identity, frame counter,
    per-slot input log / native builder, and the metadata of the pending
    rollout living in row ``index`` of the core's prev buffers."""

    __slots__ = (
        "index", "active", "frame", "spec_on", "native", "input_log",
        "res_anchor", "res_bits",
    )

    def __init__(self, index: int):
        self.index = index
        self.active = False
        self.frame = 0
        self.spec_on = True
        self.native = None
        self.input_log: dict = {}
        self.res_anchor: Optional[int] = None
        self.res_bits: Optional[np.ndarray] = None


class BatchedSessionCore(Instrumented):
    """S fixed-capacity match slots over stacked device state, advanced by
    one :class:`BatchedTickExecutor` dispatch per tick round.

    The per-slot request protocol matches the singleton runner's canonical
    tick: each slot submits one ``[Load?, (Save, Advance)*]`` segment per
    round with saves labeled contiguously, as the
    :class:`~bevy_ggrs_tpu.session.requests.Segment` its session made
    (``advance_segment()``) or as the request list, which :meth:`tick`
    converts once at entry; everything behind that entry pass reads
    segments. ``RestoreGameState`` and non-standard bursts
    raise a typed :class:`~bevy_ggrs_tpu.serve.faults.SlotFault` naming
    the offending slot — BEFORE any slot's host or device state is touched
    (every segment of every slot is checked ahead of the apply loop), so
    the server can drop the faulted slot, re-tick the rest, and drain the
    match to a singleton recovery lane via :meth:`extract`.

    Determinism-per-slot: every slot's committed trajectory is computed by
    the same vmapped executable regardless of what other slots are doing
    (phase gates are per-slot; lanes never interact), so a slot's state
    stream is bitwise-reproducible by a serial replay of its own inputs —
    the guarantee docs/serving.md specifies and
    tests/test_batched_sessions.py asserts.
    """

    def __init__(
        self,
        schedule: Schedule,
        initial_state: WorldState,
        max_prediction: int,
        num_players: int,
        input_spec,
        num_slots: int,
        num_branches: int = 8,
        spec_frames: Optional[int] = None,
        branch_values=None,
        metrics=None,
        tracer=None,
        executor: Optional[BatchedTickExecutor] = None,
        report_checksums: bool = True,
        timeseries=None,
        ledger=None,
        predictor=None,
    ):
        from bevy_ggrs_tpu.obs.ledger import null_ledger
        from bevy_ggrs_tpu.obs.timeseries import null_timeseries

        self._set_sinks(metrics, tracer)
        self.timeseries = (
            timeseries if timeseries is not None else null_timeseries
        )
        # Per-rollback causal accounting (obs/ledger.py). A MatchServer
        # passes a scoped view so entries carry fleet-unique flat slot
        # ids; entries here label the local match_slot.
        self.ledger = ledger if ledger is not None else null_ledger
        # The host-work spans arm only when someone is listening (a sink,
        # or the rolling timeseries): the clock reads would otherwise tax
        # the loop for nothing (the telemetry-off determinism guard stays
        # exact).
        self._span_always = bool(self.timeseries.enabled)
        self.schedule = schedule
        self.num_players = int(num_players)
        self.input_spec = input_spec
        self.max_prediction = int(max_prediction)
        self.num_slots = int(num_slots)
        self.spec_frames = int(spec_frames or max_prediction)
        self.num_branches = int(num_branches)
        self.report_checksums = bool(report_checksums)
        if branch_values is not None:
            self._branch_values = list(branch_values)
        elif getattr(input_spec, "values", None):
            self._branch_values = list(input_spec.values)
        else:
            self._branch_values = list(range(16))
        # Ring/burst sizing mirrors RollbackRunner: depth = max_prediction
        # + 1 slack, burst padded to max_prediction + 2.
        self.ring_depth = self.max_prediction + 1
        self.burst_frames = self.max_prediction + 2
        if executor is not None:
            if executor.num_slots != self.num_slots:
                raise ValueError(
                    f"shared executor has {executor.num_slots} slots, core "
                    f"wants {self.num_slots}"
                )
            self._exec = executor
        else:
            self._exec = BatchedTickExecutor(
                schedule, self.num_slots, self.burst_frames,
                self.num_branches, self.spec_frames,
                inputs=input_spec.zeros_np(self.num_players),
            )
        S, B, F = self.num_slots, self.num_branches, self.spec_frames
        # ONE capture of the compiled tick, armed by an operator
        # (GGRS_XLA_COST=1) or by a real sink (somebody is tracing): the
        # warmup dispatch prices the batched tick (flops / bytes /
        # hbm_peak_bytes) and keeps its phase map (utils.xla_cache
        # ``executable_phases``: device time by phase, once joined with a
        # device trace). Opt-in because the AOT lowering re-traces the
        # program (seconds at large S) and its compile, keyed with this
        # tree's scopes, is a real one the first time: a core with both
        # sinks null never lowers twice.
        if (
            os.environ.get("GGRS_XLA_COST", "").lower()
            not in ("", "0", "false")
            or self.metrics is not null_metrics
            or self.tracer is not null_tracer
        ):
            self._exec.enable_cost_capture(
                f"batched_tick_S{S}_B{B}_F{F}"
            )
        self._template = jax.tree_util.tree_map(jnp.asarray, initial_state)
        # What a committed frame copies: one ring row, a state's bytes.
        self.row_bytes = sum(
            int(x.nbytes) for x in jax.tree_util.tree_leaves(self._template)
        )
        bcast = lambda prefix: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x.reshape((1,) * len(prefix) + x.shape), prefix + x.shape
            ),
            self._template,
        )
        rings = SnapshotRing(
            states=bcast((S, self.ring_depth)),
            frames=jnp.full((S, self.ring_depth), -1, dtype=jnp.int32),
            checksums=jnp.zeros((S, self.ring_depth, 2), dtype=jnp.uint32),
        )
        # Previous rollout buffers, wholesale-replaced every dispatch.
        # Placeholder contents are never read: a slot's absorb phase only
        # selects its row when that slot has pending-rollout metadata.
        prev_rings = SnapshotRing(
            states=bcast((S, B, F)),
            frames=jnp.full((S, B, F), -1, dtype=jnp.int32),
            checksums=jnp.zeros((S, B, F, 2), dtype=jnp.uint32),
        )
        # Between dispatches the four stacked trees live as the executor's
        # packed carry; the live states are also kept as the ``[S]``
        # ``WorldState`` every tick returns. ``rings`` / ``prev_rings`` /
        # ``prev_states`` are read through one unpack (``_unpacked``).
        self._states = bcast((S,))
        self._carry = self._exec.pack(
            rings, self._states, prev_rings, bcast((S, B))
        )
        self._trees: Optional[tuple] = None
        self.slots = [_Slot(i) for i in range(S)]
        self._pending_reports: List[Tuple[object, List[tuple]]] = []
        zero = input_spec.zeros_np(self.num_players)
        self._zero = np.asarray(zero)
        self._zero_bb = np.zeros(
            (B, F) + self._zero.shape, self._zero.dtype
        )
        # Shared all-unknown (known, mask) for sessionless slots: the
        # builders only read these, and allocating them per slot per tick
        # was a measured chunk of the S=256 host budget.
        self._known0 = np.broadcast_to(
            self._zero, (F,) + self._zero.shape
        ).copy()
        self._mask0 = np.zeros((F, self.num_players), dtype=bool)
        # Learned input predictor (predict/): one BOUND predictor shared
        # by every slot (weights are per-deployment, not per-match), with
        # a batched ranker so ONE vmapped int8 forward ranks candidates
        # for all predictor-eligible slots per dispatch. ``predictor=
        # None`` consults GGRS_PREDICTOR; binding falls back to None (and
        # the heuristic ranking) when the weights don't fit this input
        # geometry — exactly the singleton runner's resolution.
        shape = tuple(getattr(input_spec, "shape", ()) or ())
        n_field = int(np.prod(shape, dtype=np.int64)) if shape else 1
        self._predictor = resolve_predictor(
            predictor, self._branch_values, self._zero.dtype, n_field,
        )
        self._ranker = (
            BatchedRanker(self._predictor, self.spec_frames)
            if self._predictor is not None else None
        )
        # One tree for every slot: the slots share this configuration,
        # and the log a build reads is the slot's own.
        self._tree = BranchTree(
            self.input_spec, self.num_players, B, F, self._branch_values,
            self._predictor,
        )
        # Native batched data plane (native/spec.NativeBatchPlane): the
        # whole per-slot host loop — as-used log appends, in-flight tree
        # matches, predictor window gather, branch-tree builds and no-op
        # tree re-use — consolidated into TWO C calls per dispatch.
        # ``GGRS_NO_NATIVE=1`` / unsupported dtypes keep the per-slot
        # path (bitwise identical, tests/test_native_batch.py).
        self._plane = native_spec.make_batch_plane(
            self.input_spec, self.num_players, S, B, F,
            self.burst_frames, self._predictor,
        )
        self.native_batch_calls = 0
        # Aggregate counters (per-slot views go through labeled metrics).
        self.ticks_total = 0
        self.device_dispatches_total = 0
        # Burst steps of the batched tick, exact: every dispatch runs, for
        # each of its ``num_slots`` lanes, as many steps as its deepest
        # lane's burst is long (``burst_step_slots_total``; the program
        # takes that maximum itself, ``rollout.py`` ``live_steps``);
        # ``burst_steps_total`` is how many of them a lane's request list
        # asked for (its AdvanceFrames). The ratio is the share of the
        # tick that is not padding.
        self.burst_steps_total = 0
        self.burst_step_slots_total = 0
        # The absorb phase's pair, counted alike (``fused.py``
        # ``_absorb_impl`` runs the deepest lane's ``ABSORB_N`` copy steps
        # for every lane): frames the lanes committed from their rollouts
        # (``absorb_steps_total``, = ``rollback_frames_recovered_total``)
        # and lane-steps run. Both stay 0 in a group that never hits.
        self.absorb_steps_total = 0
        self.absorb_step_slots_total = 0
        self.spec_hits = 0
        self.spec_partial_hits = 0
        self.spec_misses = 0
        self.rollbacks_total = 0
        self.rollback_frames_total = 0
        self.rollback_frames_recovered_total = 0
        self.last_predictor_rank_ms = 0.0
        self.predictor_rank_ms_total = 0.0
        self.predictor_rank_dispatches = 0

    # -- lifecycle ------------------------------------------------------

    @property
    def active_count(self) -> int:
        return sum(1 for s in self.slots if s.active)

    def free_slots(self) -> List[int]:
        return [s.index for s in self.slots if not s.active]

    # -- the stacked trees ----------------------------------------------

    def _unpacked(self) -> tuple:
        """``(rings, states, prev_rings, prev_states)`` as trees: ONE
        unpack of the carry (a dispatch, off the serving loop), kept until
        the next dispatch replaces the carry."""
        if self._trees is None or len(self._trees) == 2:
            self._trees = self._exec.unpack(self._carry)
        return self._trees

    def _main_rings(self) -> SnapshotRing:
        """The main rings alone (``_trees`` then holds ``(rings,
        states)``): the previous rollout stays as it is carried."""
        if self._trees is None:
            self._trees = self._exec.unpack_main(self._carry)
        return self._trees[0]

    def _replace(self, index: int, tree) -> None:
        trees = list(self._unpacked())
        trees[index] = tree
        self._carry = self._exec.pack(*trees)
        self._states, self._trees = trees[1], None

    rings = property(
        lambda self: self._main_rings(),
        lambda self, tree: self._replace(0, tree),
    )
    # Every tick returns the live states: reading them never dispatches.
    states = property(
        lambda self: self._states,
        lambda self, tree: self._replace(1, tree),
    )
    prev_rings = property(
        lambda self: self._unpacked()[2],
        lambda self, tree: self._replace(2, tree),
    )
    prev_states = property(
        lambda self: self._unpacked()[3],
        lambda self, tree: self._replace(3, tree),
    )

    def _admit_row(self, slot: int, ring, state) -> None:
        self._carry, self._states = self._exec.admit(
            self._carry, slot, ring, state
        )
        self._trees = None

    def warmup(self) -> None:
        """Compile the batched tick, the admit program and the readers'
        programs (unpack, slot row) before serving — from here on, match
        churn, ``slot_state`` / ``slot_ring``, ``attest`` and ``extract``
        must not trigger a compile (the acceptance contract checked
        against ``compile_counters()``)."""
        self._dispatch({})
        self._observe_warmup()
        self._exec.unpack(self._carry)  # ``prev_rings``' reader compiles here
        # Identity write: row 0 written back onto itself compiles the
        # admit program without disturbing any occupant.
        self._admit_row(0, self.slot_ring(0), self.slot_state(0))
        if self._ranker is not None:
            self._ranker.warmup(self.num_slots, self.num_players)
        from bevy_ggrs_tpu import integrity

        # SDC attestation digests (integrity.attest/repair_slot) must not
        # compile on the serving path either.
        integrity.warm(self.rings, states=self.states)

    def admit(
        self,
        initial_state: Optional[WorldState] = None,
        slot: Optional[int] = None,
        spec_on: bool = True,
        ticket: Optional[SlotTicket] = None,
        template: Optional[tuple] = None,
    ) -> int:
        """Place a match into a free slot and return the slot number.

        Fresh admission writes ``ring_init(state)`` + ``state`` on device
        at a traced index. Passing ``ticket`` instead READMITS a drained
        match mid-trajectory: the ticket's whole ring and live state go
        through the SAME traced-index admit program (identical shapes —
        singleton rings share the ``max_prediction + 1`` depth — so zero
        recompiles), the frame counter resumes where the ticket left off,
        and the fresh per-slot input log / native builder is seeded from
        the ticket's log tail so the next speculation round builds from
        the same history a singleton would."""
        if slot is None:
            free = self.free_slots()
            if not free:
                raise RuntimeError("no free match slots")
            slot = free[0]
        s = self.slots[slot]
        if s.active:
            raise RuntimeError(f"slot {slot} is occupied")
        if ticket is not None:
            depth = int(ticket.ring.frames.shape[0])
            if depth != self.ring_depth:
                raise ValueError(
                    f"ticket ring depth {depth} != core depth "
                    f"{self.ring_depth} (mismatched max_prediction)"
                )
            new_ring = ticket.ring
            state = jax.tree_util.tree_map(jnp.asarray, ticket.state)
        elif template is not None and initial_state is None:
            # Pre-warmed admission (MatchServer's slot template pool): a
            # codec-round-tripped (ring, state) pair built once at
            # warmup. Bitwise identical to the cold path below — the
            # codec decode reproduces the template flat-byte exact, and
            # ring_init is deterministic — so template-admitted and
            # cold-admitted matches are indistinguishable
            # (tests/test_native_batch.py pins this).
            new_ring, state = template
        else:
            state = (
                self._template if initial_state is None
                else jax.tree_util.tree_map(jnp.asarray, initial_state)
            )
            new_ring = ring_init(state, self.ring_depth)
        self._admit_row(slot, new_ring, state)
        s.active = True
        s.frame = 0 if ticket is None else int(ticket.frame)
        s.spec_on = bool(spec_on if ticket is None else ticket.spec_on)
        s.res_anchor = None
        s.res_bits = None
        s.native = native_spec.make_spec_builder(
            self.input_spec, self.num_players, self.num_branches,
            self.spec_frames, self._branch_values,
        )
        s.input_log = (
            native_spec.MirroredLog(s.native) if s.native is not None else {}
        )
        if self._plane is not None:
            self._plane.set_builder(slot, s.native)
        if ticket is not None and ticket.input_log:
            # MirroredLog.update forwards into the native builder's C++
            # mirror, so readmitted slots rank/fingerprint from the same
            # history either way.
            s.input_log.update(ticket.input_log)
        return slot

    def retire(self, slot: int) -> None:
        """Free a slot. Host-only: the device rows become dead weight until
        readmission overwrites them — retirement never dispatches, so churn
        cost is O(1) bookkeeping."""
        s = self.slots[slot]
        if not s.active:
            return
        # Reports already queued for this slot's session must survive the
        # retire (they carry their own session refs) — flush now.
        self.flush_reports()
        if self._plane is not None:
            self._plane.set_builder(slot, None)
            self._plane.set_res(slot, None)
            self._plane.set_qs(slot, None)
        s.active = False
        s.native = None
        s.input_log = {}
        s.res_anchor = None
        s.res_bits = None

    def slot_state(self, slot: int) -> WorldState:
        """Device view of one slot's live state (e.g. for handing a match
        back to a singleton runner, or for parity checks)."""
        return self._exec.row(self.states, slot)

    def slot_ring(self, slot: int) -> SnapshotRing:
        return self._exec.row(self.rings, slot)

    def extract(self, slot: int) -> SlotTicket:
        """Drain a slot: snapshot its full trajectory state into a
        :class:`SlotTicket` (device views are snapshots — later dispatches
        never mutate them) and retire the slot. The ticket seeds a
        singleton recovery runner (``faults.adopt_ticket``) and later
        readmits via ``admit(ticket=...)``, bitwise-continuous."""
        s = self.slots[slot]
        if not s.active:
            raise RuntimeError(f"slot {slot} is not active")
        ticket = SlotTicket(
            frame=int(s.frame),
            state=self.slot_state(slot),
            ring=self.slot_ring(slot),
            input_log=dict(s.input_log),
            spec_on=bool(s.spec_on),
        )
        self.retire(slot)
        return ticket

    # -- ticking --------------------------------------------------------

    def _check_segment(self, slot: int, frame: int, seg: Segment) -> int:
        """Canonical-shape check for one segment of a slot standing at
        ``frame``, BEFORE anything mutates: raises :class:`SlotFault`
        instead of half-applying a round. Returns the frame the slot would
        reach."""
        n = len(seg.bits)
        if n == 0 or seg.start != (frame if seg.load is None else seg.load):
            raise SlotFault(slot, "non_canonical_burst", frame)
        if n > self.burst_frames:
            raise SlotFault(slot, "burst_overflow", frame)
        return seg.start + n

    def _enter(self, work: Dict[int, tuple]) -> Tuple[Dict[int, list], int]:
        """:meth:`tick`'s entry pass: every slot's work item as checked
        segments, ``{slot: [(segment, confirmed, session), ...]}``, and how
        many items arrived as a session-made :class:`Segment`. A request
        list is converted HERE, once (``Segment.from_requests``: cut at its
        Loads, one segment a dispatch round); nothing has been written when
        this raises."""
        per_slot: Dict[int, list] = {}
        direct = 0
        for slot, (item, confirmed, session) in work.items():
            s = self.slots[slot]
            if not s.active:
                raise RuntimeError(f"slot {slot} is not active")
            frame = s.frame
            if isinstance(item, Segment):
                direct += 1
                segs = (item,)
            else:
                try:
                    segs = Segment.from_requests(item)
                except SegmentError as e:
                    raise SlotFault(slot, e.reason, frame, cause=e) from e
            for seg in segs:
                frame = self._check_segment(slot, frame, seg)
            per_slot[slot] = [(seg, confirmed, session) for seg in segs]
        return per_slot, direct

    def tick(self, work: Dict[int, tuple]) -> None:
        """Advance every slot named in ``work`` — ``{slot: (segment or
        request list, confirmed_frame, session)}`` (``confirmed_frame=None``
        means fully confirmed; ``session`` may be None) — in as few batched
        dispatches as the deepest work item needs (one per Load-delimited
        segment; every session emits one, so normally one: series
        ``serve_rounds``). Series ``serve_segment_direct_share``: the share
        of the items that came as a session-made segment.

        Fault atomicity: every slot's every segment (all rounds) is
        checked up front (:meth:`_enter`), so a :class:`SlotFault` escaping
        this method guarantees NO slot's state — host or device — changed.
        The caller may drop the named slot from ``work`` and call again."""
        self.ticks_total += 1
        self.flush_reports()
        # The entry pass of the whole group, one span (no span a slot).
        with self.span("serve_segment", slots=len(work)):
            per_slot, direct = self._enter(work)
        rounds = max([1, *map(len, per_slot.values())])
        for r in range(rounds):
            batch = {
                slot: segs[r] for slot, segs in per_slot.items()
                if r < len(segs)
            }
            with self.span("serve_round", round=r, slots=len(batch)):
                self._dispatch(batch)
        # The dispatch rounds this group tick ran: 1 when every item was
        # one Load-delimited segment (every session's, SyncTest included).
        self.metrics.observe("serve_rounds", rounds)
        self.timeseries.observe("serve_rounds", rounds)
        if work:
            self.metrics.observe(
                "serve_segment_direct_share", direct / len(work)
            )

    def flush_reports(self) -> None:
        """Deliver deferred checksum reports (the only device->host sync
        in the serving loop, off the producing dispatch's critical path):
        the read is span ``checksum_sync``, the fold to 64 bits (once an
        array, of the reporting slots' rows, never the rollout's) and the
        sessions' ``report_checksums`` calls, one a segment part, span
        ``serve_report_delivery``. A
        session without ``report_checksums`` is told row by row
        (``report_checksum``, the rows it wants)."""
        if not self._pending_reports:
            return
        pending, self._pending_reports = self._pending_reports, []
        # An entry names a dispatch's checksum output, which part of it
        # (0 absorb, 1 burst) and the slot's first ``n`` rows there; one
        # read a dispatch.
        with self.span("checksum_sync") as sp_sync:
            read: Dict[int, tuple] = {}
            for entry in pending:
                cs = entry[0]
                if id(cs) not in read:
                    read[id(cs)] = self._exec.cs_host(cs)
        # Every entry holds rows (the post pass appends no empty one);
        # they are counted only for a span somebody reads.
        n_rows = (
            sum(entry[4] for entry in pending)
            if sp_sync is not NULL_SPAN else 0
        )
        with self.span("serve_report_delivery", rows=n_rows):
            # One fold an array, of the rows of the slots that have an
            # entry there; the entries are served in the order they came.
            slots_of: Dict[tuple, list] = {}
            for cs, part, slot, _first, _n, _session in pending:
                slots_of.setdefault((id(cs), part), []).append(slot)
            folded = {
                key: iter(combine64_rows(read[key[0]][key[1]][slots]).tolist())
                for key, slots in slots_of.items()
            }
            for cs, part, _slot, first, n, session in pending:
                values = next(folded[id(cs), part])[:n]
                report = getattr(session, "report_checksums", None)
                if report is not None:
                    report(first, values)
                else:
                    for t, frame in wanted_rows(session, first, n):
                        session.report_checksum(frame, values[t])

    def _record_predictor_rank(self, rank_ms: float) -> None:
        self.last_predictor_rank_ms = rank_ms
        self.predictor_rank_ms_total += rank_ms
        self.predictor_rank_dispatches += 1
        self.metrics.observe("predictor_rank_ms", rank_ms)
        self.timeseries.observe("predictor_rank_ms", rank_ms)

    def _build_branches(self, s: _Slot, anchor: int, end: int, session,
                        seed=None):
        """The next rollout's branch tensor for one slot — the singleton
        builder, verbatim (native when available, else the core's
        :class:`BranchTree` over this slot's log). ``seed`` is this slot's
        slice of the batched predictor ranking (None when the predictor is
        off or did not rank this slot: the tree then asks it)."""
        if s.native is not None:
            if seed is not None:
                s.native.seed(anchor, seed)
            qs_ptr = s.native.qset_ptr(session)
            if qs_ptr is not None:
                known = known_mask = None
            elif session is None:
                known, known_mask = self._known0, self._mask0
            else:
                known, known_mask = self._tree.known_inputs(session, anchor)
            bits, _sig = s.native.build(
                anchor, qs_ptr, known, known_mask, False, None
            )
            return bits
        last = s.input_log.get(anchor - 1)
        if last is None:
            last = self._zero
        if session is None:
            known, known_mask = self._known0, self._mask0
        else:
            known, known_mask = self._tree.known_inputs(session, anchor)
        return self._tree.structured_bits(
            s.input_log, np.asarray(last), known, known_mask, anchor,
            seed=seed,
        )

    def _dispatch(self, batch: Dict[int, tuple]) -> None:
        """One vmapped dispatch: slots in ``batch`` run their segment,
        every other slot no-ops (and, if it has a pending rollout, replays
        it bitwise so the wholesale prev-buffer swap preserves it).

        The host work is staged by the native batch plane when it loaded
        (ONE C call for the per-slot work, :meth:`_dispatch_native`) or by
        the per-slot Python loop (:meth:`_dispatch_python`) — bitwise
        identical paths, property-tested in tests/test_native_batch.py.
        Either returns the three host arrays and the post pass's rows, and
        the device call is made from here: the staging frame is gone by
        then, so the first call's trace does not run deeper for its locals
        (``PERF.md`` §6, PR 29: 5 s of warm-up hung on that); the post
        pass (:meth:`_post_dispatch`) runs once the call's frame is gone
        too.

        ``batch`` is ``{slot: (segment, confirmed, session)}``, every
        segment already checked (:meth:`_check_segment`: by :meth:`tick`'s
        entry pass, or by the direct caller that built it,
        :meth:`repair_slot`), so nothing here raises a :class:`SlotFault`:
        a sibling slot's next-tick output is bitwise unaffected by another
        slot faulting."""
        stage = (
            self._dispatch_native if self._plane is not None
            else self._dispatch_python
        )
        self._post_dispatch(*self._finish_dispatch(*stage(batch)))

    def _dispatch_python(self, batch: Dict[int, tuple]) -> tuple:
        """The per-slot host loop (the ``GGRS_NO_NATIVE=1`` reference
        path): log writes, branch matches, window gather and tree builds
        all run per slot in Python. Returns :meth:`_finish_dispatch`'s
        arguments."""
        S, B, F, MF = (
            self.num_slots, self.num_branches, self.spec_frames,
            self.burst_frames,
        )
        P = self.num_players
        # post[slot] -> state updates applied after the dispatch succeeds
        post: Dict[int, tuple] = {}
        reports: List[tuple] = []

        # The host work before the call is one span; the predictor ranking
        # and the tree builds are its children, and ``serve_arg_assembly_ms``
        # (the series) is its self time: the pre-pass, the three fresh
        # arrays, log writes, matches, array fills.
        with self.span(
            "serve_arg_assembly", series=False, slots=len(batch)
        ) as sp_loop:
            ints_a, bits_a, bb_a = self._host_args()
            status_a = TickInts.status(ints_a, MF, P)
            # Pass 1 — as-used log writes + anchor geometry for every batched
            # slot, hoisted ahead of the build loop so the batched predictor
            # ranking sees all post-write windows in ONE vmapped call.
            geom: Dict[int, tuple] = {}
            for i, (seg, confirmed, _session) in batch.items():
                s = self.slots[i]
                start = seg.start
                end = start + len(seg.bits)
                anchor = end if confirmed is None else confirmed + 1
                # As-used log BEFORE match/build (forward-fill reads anchor-1,
                # which this very burst may advance): views of the segment's
                # rows, which nobody else writes.
                for t, row in enumerate(seg.bits):
                    s.input_log[start + t] = row
                spec_active = s.spec_on and spec_in_window(
                    anchor, end, self.ring_depth
                )
                geom[i] = (start, end, anchor, spec_active)
            seeds: Dict[int, object] = {}
            if self._ranker is not None:
                eligible = [i for i in batch if geom[i][3]]
                if eligible:
                    with self.timed_span(
                        "serve_predictor_rank", slots=len(eligible)
                    ) as sp_rank:
                        W = self._predictor.weights.window
                        wins = np.full((S, W, P), -1, dtype=np.int32)
                        anchors = np.zeros(S, dtype=np.int32)
                        for i in eligible:
                            anchors[i] = geom[i][2]
                            wins[i] = self._predictor.window_indices(
                                self.slots[i].input_log, geom[i][2], P
                            )
                        traj_idx, order = self._ranker.rank(wins, anchors)
                        for i in eligible:
                            seeds[i] = self._predictor.render_seed(
                                traj_idx[i], order[i]
                            )
                    self._record_predictor_rank(sp_rank.ms)
            # Every spec-active slot's next branch tree, in ONE span (a
            # served frame has hundreds of slots: no span per slot). A
            # build reads only the slot's as-used log (written in pass 1)
            # and its session's confirmed inputs — nothing the commit
            # decisions below change.
            trees: Dict[int, np.ndarray] = {}
            to_build = [
                s for s in self.slots if s.index in batch and geom[s.index][3]
            ]
            with self.span(
                "serve_branch_build", series=False, slots=len(to_build)
            ) as sp_build:
                for s in to_build:
                    i = s.index
                    _start, end, anchor, _active = geom[i]
                    trees[i] = self._build_branches(
                        s, anchor, end, batch[i][2], seeds.get(i)
                    )
            for s in self.slots:
                i = s.index
                if i not in batch:
                    # No-op lane: every phase gated off; replay the pending
                    # rollout (if any) so the prev-buffer swap keeps it valid.
                    plan_rollout(
                        ints_a[i], s.frame, s.res_anchor, self.ring_depth
                    )
                    if s.res_anchor is not None:
                        bb_a[i] = s.res_bits
                    continue
                seg, _confirmed, session = batch[i]
                _start, end, anchor, spec_active = geom[i]
                load_frame, n_steps = seg.load, len(seg.bits)
                # The plan (host-side, zero device syncs).
                matched = match_pending(
                    s.native, s.input_log, s.res_bits, s.res_anchor, F,
                    load_frame, seg.bits,
                )
                plan = plan_tick(
                    ints_a[i], s.frame, load_frame, n_steps, s.res_anchor,
                    F, matched, anchor, self.ring_depth, s.spec_on,
                )
                n_commit, n_tail = plan[1], plan[5]
                if n_tail:
                    bits_a[i, :n_tail] = seg.bits[n_commit:]
                    status_a[i, :n_tail] = seg.status[n_commit:]
                # An inactive lane's rollout (from the live frontier) is
                # discarded: its row stays zeros. bb is per-call fresh from
                # both builders, so storing it for the replay/match path
                # needs no defensive copy.
                bb = trees[i] if spec_active else None
                if bb is not None:
                    bb_a[i] = bb
                blame = rollback_blame(
                    self.ledger, matched, s.res_bits, s.res_anchor,
                    load_frame, seg.bits,
                )
                post[i] = (
                    end, load_frame, n_steps, session, bb, plan, blame,
                )

        if sp_loop is not NULL_SPAN:
            self._observe_assembly(sp_loop.self_ms, sp_build.ms)

        return (ints_a, bits_a, bb_a), post, reports

    def _observe_assembly(
        self, self_ms: float, build_ms: float,
        stage_ms: Optional[float] = None,
    ) -> None:
        """The host loop's split, one sample a dispatch, the same keys in
        ``Metrics`` and the rolling ``timeseries``: ``serve_branch_build_ms``
        (the tree builds), ``native_batch_ms`` (the native plane's two
        calls, stage + build) and ``serve_arg_assembly_ms``, the SELF time
        of span ``serve_arg_assembly``: what its children (stage, build,
        ranking) leave of the loop, so that the series add up to the span."""
        for sink in (self.metrics, self.timeseries):
            sink.observe("serve_branch_build_ms", build_ms)
            sink.observe("serve_arg_assembly_ms", self_ms)
            if stage_ms is not None:
                sink.observe("native_batch_ms", stage_ms + build_ms)

    def _observe_warmup(self) -> None:
        """What the first dispatch fixed for good: the bytes of this
        group's carried device state (one sample of ``serve_carry_bytes``:
        states, rings, prev_states, prev_rings as the packed carry) and the
        form the program's bursts carry their ring rows in (the labelled
        count ``ring_row_lowering``). Its own frame ON PURPOSE: ``warmup``'s
        locals lie under the first call's trace (``PERF.md`` section 7)."""
        self.metrics.observe(
            "serve_carry_bytes", sum(int(x.nbytes) for x in self._carry)
        )
        for kind, n in self._exec.traced_ring_rows().items():
            self.metrics.count(
                "ring_row_lowering", n, labels={"kind": kind}
            )

    def _host_args(self) -> tuple:
        """The three host arrays of one dispatch (``BatchedTickExecutor.
        run``), zeroed: ``plan_tick`` writes every lane's scalars. Fresh per
        dispatch (NOT reused buffers): the previous dispatch's
        ``branch_bits`` rows live on as the slots' in-flight trees
        (``res_bits`` views) until the post pass replaces them, and the jit
        argument transfer may still read all three asynchronously."""
        S, B, F, MF = (
            self.num_slots, self.num_branches, self.spec_frames,
            self.burst_frames,
        )
        return (
            TickInts.zeros(MF, self.num_players, (S,)),
            np.zeros((S, MF) + self._zero.shape, self._zero.dtype),
            np.zeros((S, B, F) + self._zero.shape, self._zero.dtype),
        )

    def _finish_dispatch(
        self, jit_args: tuple, post: Dict[int, tuple],
        reports: List[tuple],
    ) -> tuple:
        """The device dispatch shared by both host paths (per-slot Python
        loop and native batch plane): run the batched tick. Returns
        :meth:`_post_dispatch`'s arguments. Few locals ON PURPOSE: the
        first call's trace runs under this frame (``PERF.md`` §7)."""
        self.device_dispatches_total += 1
        self._count_lane_steps(jit_args[0], jit_args[2])
        with self.span("serve_dispatch"):
            self._carry, self._states, cs = self._exec.run(
                self._carry, *jit_args
            )
        self._trees = None
        self.metrics.observe("tick_io_buffers", self._exec.io.last)
        self.metrics.observe("tick_stage_bytes", self._exec.io.staged_bytes)
        return cs, post, reports

    def _count_lane_steps(
        self, ints: np.ndarray, branch_bits: np.ndarray
    ) -> None:
        """What the dispatch's loops run for ``ints`` (the
        :class:`TickInts` rows it is handed) and ``branch_bits`` (its
        trees): ``num_slots x`` the deepest lane's burst, and the same of
        its absorb. Both depths are series, a sample a dispatch
        (``serve_burst_depth``; ``serve_absorb_depth``, 0 included), and so
        are the bytes the lanes commit (``serve_absorb_commit_bytes``:
        their frames x ``row_bytes``, also the count
        ``absorb_commit_bytes_total``). While a sink listens, also the
        world-steps a lane's rollout runs (``serve_rollout_steps``: the
        deepest lane's distinct input prefixes level by level where the
        rollout shares its steps, ``B x F`` where it does not) and the
        share of them that are a lane's own
        (``serve_rollout_fill_share``, %, where it shares)."""
        self.metrics.count("serve_dispatches_total")
        burst = int(ints[:, TickInts.N_BURST].max())
        self.burst_step_slots_total += self.num_slots * burst
        self.metrics.observe("serve_burst_depth", burst)
        commits = ints[:, TickInts.ABSORB_N]
        depth = int(commits.max())
        committed = int(commits.sum()) * self.row_bytes
        self.metrics.observe("serve_absorb_depth", depth)
        self.metrics.observe("serve_absorb_commit_bytes", committed)
        if depth:
            steps = self.num_slots * depth
            self.absorb_step_slots_total += steps
            self.metrics.count("absorb_step_slots_total", steps)
            self.metrics.count("absorb_commit_bytes_total", committed)
        if self.metrics is not null_metrics:
            steps, fill = rollout_world_steps(
                branch_bits, self._exec.packed.share_width, lead=1
            )
            self.metrics.observe("serve_rollout_steps", steps)
            if fill is not None:
                self.metrics.observe("serve_rollout_fill_share", 100 * fill)

    def _post_dispatch(
        self, cs, post: Dict[int, tuple], reports: List[tuple]
    ) -> None:
        """The post-dispatch bookkeeping of one round (span ``serve_post``,
        one for the whole round): apply each ticked lane's plan: frame
        counter, rollout metadata, the rollback's accounting
        (``fused.account_rollback``) and, for each part of the dispatch's
        checksum output ``cs`` the lane's session wants a frame of, ONE
        deferred entry ``(cs, part, slot, first frame, frames, session)``. ``post[slot]`` is ``(end,
        load_frame, n_steps, session, the lane's next in-flight tree or
        None, its plan, its blame)``."""
        with self.span("serve_post", slots=len(post)):
            for i, (
                end, load_frame, n_steps, session, res_bits, plan, blame,
            ) in post.items():
                (
                    branch, n_commit, missed, _, burst_start, n_tail,
                    spec_active, spec_anchor, _,
                ) = plan
                s = self.slots[i]
                s.frame = end
                if spec_active:
                    s.res_anchor, s.res_bits = spec_anchor, res_bits
                    # A fresh rollout dispatched for this slot: B×F
                    # speculative device frames. (No-op lane replays are NOT
                    # charged — they are an artifact of the wholesale
                    # prev-buffer swap, not new speculative intent.)
                    self.ledger.record_rollout(
                        self.num_branches * self.spec_frames, slot=i
                    )
                else:
                    s.res_anchor, s.res_bits = None, None
                self.burst_steps_total += n_steps
                if n_commit:
                    self.absorb_steps_total += n_commit
                    self.metrics.count("absorb_steps_total", n_commit)
                self.metrics.count("frames_advanced", n_steps)
                self.metrics.count(
                    "frames_advanced", n_steps, labels={"match_slot": i}
                )
                if load_frame is not None:
                    account_rollback(
                        self, load_frame, n_steps, branch, n_commit, missed,
                        blame, slot=i,
                    )
                if session is not None and self.report_checksums:
                    wants = getattr(session, "wants_checksum", None)
                    for part, first, n in (
                        (0, load_frame, n_commit), (1, burst_start, n_tail)
                    ):
                        if n and (
                            wants is None
                            or any(map(wants, range(first, first + n)))
                        ):
                            reports.append((cs, part, i, first, n, session))
                self._gc_log(s)
            self._pending_reports.extend(reports)

    def _dispatch_native(self, batch: Dict[int, tuple]) -> tuple:
        """One vmapped dispatch's per-slot host loop consolidated
        into the two batch-plane calls: ``ggrs_batch_stage`` lands every
        slot's as-used log rows, in-flight tree match and predictor
        window gather in ONE C call before the commit decisions, and
        ``ggrs_batch_build`` runs every seeded tree build plus the no-op
        lanes' tree re-use copies straight into the dispatch's jit
        argument buffer. Bitwise identical to :meth:`_dispatch_python`
        (the C side loops over the same per-slot primitives), and returns
        the same."""
        plane = self._plane
        S, B, F, MF = (
            self.num_slots, self.num_branches, self.spec_frames,
            self.burst_frames,
        )
        P = self.num_players
        post: Dict[int, tuple] = {}
        reports: List[tuple] = []

        # One span over the host work before the call (the pre-pass and
        # the three fresh arrays included); the two C calls and the
        # predictor ranking are its children. ``native_batch_ms`` is the
        # two calls' sum, ``serve_branch_build_ms`` the build call and
        # ``serve_arg_assembly_ms`` the span's self time: what stage, build
        # and ranking leave of it (:meth:`_observe_assembly`).
        with self.span(
            "serve_arg_assembly", series=False, slots=len(batch)
        ) as sp_loop:
            ints_a, bits_a, bb_a = self._host_args()
            status_a = TickInts.status(ints_a, MF, P)
            plane.reset_masks()
            # Pass 1 — SoA staging for ggrs_batch_stage: step bits/status
            # (the segment's two arrays, a slice assignment each), anchor
            # geometry, match inputs, window-gather requests. The
            # Python-side dict update (views of the segment's rows) bypasses
            # MirroredLog's per-row ctypes forward — the stage call lands
            # the same rows in the native mirror (in per-slot log -> match
            # -> gather order, mirroring the Python pass structure).
            geom: Dict[int, tuple] = {}
            for i, (seg, confirmed, _session) in batch.items():
                s = self.slots[i]
                load_frame, start, bits = seg.load, seg.start, seg.bits
                n_steps = len(bits)
                end = start + n_steps
                anchor = end if confirmed is None else confirmed + 1
                plane.log_mask[i] = 1
                plane.starts[i] = start
                plane.n_steps[i] = n_steps
                plane.steps[i, :n_steps] = bits
                plane.status[i, :n_steps] = seg.status
                dict.update(s.input_log, zip(range(start, end), bits))
                if (
                    load_frame is not None
                    and s.res_anchor is not None
                    and load_frame >= s.res_anchor
                ):
                    plane.match_mask[i] = 1
                    plane.res_anchors[i] = s.res_anchor
                    plane.load_frames[i] = load_frame
                    plane.set_res(i, s.res_bits)
                spec_active = s.spec_on and spec_in_window(
                    anchor, end, self.ring_depth
                )
                if self._ranker is not None and spec_active:
                    plane.win_mask[i] = 1
                    plane.win_anchors[i] = anchor
                geom[i] = (start, end, anchor, spec_active)
            with self.span(
                "serve_native_batch", series=False, call="stage",
                slots=len(batch),
            ) as sp_stage:
                plane.stage(F)
            self.native_batch_calls += 1
            self.metrics.count("native_batch_calls")
            if self._ranker is not None:
                eligible = [i for i in batch if geom[i][3]]
                if eligible:
                    with self.timed_span(
                        "serve_predictor_rank", slots=len(eligible)
                    ) as sp_rank:
                        anchors = np.zeros(S, dtype=np.int32)
                        el = np.asarray(eligible, dtype=np.intp)
                        anchors[el] = plane.win_anchors[el]
                        # Stale non-eligible window rows are fine: the ranker
                        # is a vmapped lane-independent forward, and only the
                        # eligible rows' outputs are consumed.
                        traj_idx, order = self._ranker.rank(
                            plane.wins, anchors
                        )
                        # render_seed vectorized over the eligible rows — the
                        # same universe gather + dtype cast per slot; the
                        # shared all-ones valid plane lives in the batch plane.
                        uni = self._predictor.universe
                        plane.seed_traj[el] = uni[traj_idx[el]]
                        plane.seed_cand[el] = uni[order[el]]
                        plane.seed_mask[el] = 1
                    self._record_predictor_rank(sp_rank.ms)
            # Pass 2 — commit decisions from the staged match results, then
            # build-call staging (anchors, known inputs, no-op copies) and
            # the per-slot scalar fills for the jit arguments.
            dirty_known: List[int] = []
            n_build = 0
            for s in self.slots:
                i = s.index
                if i not in batch:
                    plan_rollout(
                        ints_a[i], s.frame, s.res_anchor, self.ring_depth
                    )
                    if s.res_anchor is not None:
                        plane.copy_mask[i] = 1
                        plane.set_res(i, s.res_bits)
                    continue
                seg, _confirmed, session = batch[i]
                _start, end, anchor, spec_active = geom[i]
                load_frame, n_steps = seg.load, len(seg.bits)
                # The staged match, as ``match_pending`` answers: -1 = a
                # gap in the as-used log.
                matched = None
                if plane.match_mask[i] and plane.out_branch[i] >= 0:
                    matched = (
                        int(plane.out_branch[i]), int(plane.out_depth[i])
                    )
                plan = plan_tick(
                    ints_a[i], s.frame, load_frame, n_steps, s.res_anchor, F,
                    matched, anchor, self.ring_depth, s.spec_on,
                )
                n_commit, n_tail = plan[1], plan[5]
                if n_tail:
                    bits_a[i, :n_tail] = plane.steps[i, n_commit:n_steps]
                    status_a[i, :n_tail] = plane.status[i, n_commit:n_steps]
                blame = rollback_blame(
                    self.ledger, matched, s.res_bits, s.res_anchor,
                    load_frame, seg.bits,
                )
                # The slot's next in-flight tree is its bb_a row, written by
                # the build call below — the view is stored now, the bytes
                # land before the device dispatch reads them.
                post[i] = (
                    end, load_frame, n_steps, session,
                    bb_a[i] if spec_active else None, plan, blame,
                )
                if spec_active:
                    n_build += 1
                    plane.build_mask[i] = 1
                    plane.anchors[i] = anchor
                    qs_ptr = (
                        s.native.qset_ptr(session) if session is not None
                        else None
                    )
                    plane.set_qs(i, qs_ptr)
                    if qs_ptr is None and session is not None and (
                        getattr(session, "confirmed_span", None) is not None
                        or getattr(session, "confirmed_input", None)
                        is not None
                    ):
                        # Sessions with a confirmed-inputs surface but no
                        # native queue set: the Python bulk query fills this
                        # slot's known rows (re-zeroed after the build).
                        known, kmask = self._tree.known_inputs(
                            session, anchor
                        )
                        plane.known[i] = known
                        plane.kmask[i] = kmask
                        dirty_known.append(i)
            with self.span(
                "serve_native_batch", series=False, call="build",
                slots=n_build,
            ) as sp_build:
                plane.build(bb_a)
            self.native_batch_calls += 1
            self.metrics.count("native_batch_calls")
            for i in dirty_known:
                plane.known[i] = 0
                plane.kmask[i] = 0

        if sp_loop is not NULL_SPAN:
            self._observe_assembly(
                sp_loop.self_ms, sp_build.ms, sp_stage.ms
            )

        return (ints_a, bits_a, bb_a), post, reports

    def _gc_log(self, s: _Slot) -> None:
        horizon = s.frame - self.ring_depth - 64
        for f in [f for f in s.input_log if f < horizon]:
            del s.input_log[f]

    # -- SDC attestation + repair (bevy_ggrs_tpu.integrity) -------------

    def attest(self) -> Dict[int, List[int]]:
        """Attest every active slot's ring rows in ONE vmapped digest pass
        over the ``[S, depth]`` axes (amortized over the batch exactly like
        the checksum stream). Returns ``{slot: sorted corrupt frames}`` —
        empty when every occupied row still hashes to its save-time
        digest."""
        from bevy_ggrs_tpu import integrity

        mask = integrity.attest_ring(self.rings)  # [S, depth] host bools
        out: Dict[int, List[int]] = {}
        if not mask.any():
            return out
        frames_h = np.asarray(self.rings.frames)
        for s in self.slots:
            if not s.active:
                continue  # dead rows: stale until readmission overwrites
            rows = np.flatnonzero(mask[s.index])
            if rows.size:
                bad = sorted(int(f) for f in frames_h[s.index][rows])
                out[s.index] = bad
                self.metrics.count("sdc_detected", len(bad))
                self.metrics.count(
                    "sdc_detected", len(bad), labels={"match_slot": s.index}
                )
        return out

    def repair_slot(self, slot: int, corrupt: List[int],
                    session=None) -> dict:
        """Self-heal one slot's corrupt ring rows by rollback
        resimulation: one canonical burst (Load deepest-clean base, then
        (Save, Advance) per frame from the slot's as-used input log)
        through the ordinary batched dispatch — every occupied row sits
        within ``ring_depth`` of the live frame, so the whole span fits one
        burst and the repair costs exactly one no-recompile dispatch.
        Sibling slots ride the no-op lane, bitwise untouched. Statuses
        resimulate as zeros: committed states are functions of the input
        BITS alone (the batched/singleton parity contract), so the rewrite
        is bitwise. Raises :class:`~bevy_ggrs_tpu.integrity.StateFault`
        when no clean base exists or the log has gaps — the caller
        escalates (MatchServer drains the slot to a recovery lane /
        checkpoint)."""
        from bevy_ggrs_tpu import integrity

        s = self.slots[slot]
        if not s.active:
            raise RuntimeError(f"slot {slot} is not active")
        corrupt = sorted(int(f) for f in corrupt)
        frames_h = np.asarray(self.rings.frames)[slot]
        cset = set(corrupt)

        def _fail(detail: str):
            self.metrics.count("sdc_unrepairable")
            raise integrity.StateFault("sdc", corrupt, slot=slot,
                                       detail=detail)

        if corrupt[-1] >= s.frame:
            _fail(f"corrupt row at frame {corrupt[-1]} >= live frame "
                  f"{s.frame} — resimulation cannot reach it")
        clean_below = sorted(
            int(f) for f in frames_h[frames_h >= 0]
            if int(f) < corrupt[0] and int(f) not in cset
        )
        if not clean_below:
            _fail("no digest-clean snapshot below the corrupt rows")
        base = clean_below[-1]
        rows = []
        for f in range(base, s.frame):
            bits = s.input_log.get(f)
            if bits is None:
                _fail(f"as-used input log does not cover frame {f}")
            rows.append(bits)
        replay = Segment(
            base, base, np.stack(rows),
            np.zeros((len(rows), self.num_players), np.int32),
        )
        self._check_segment(slot, s.frame, replay)
        row = corrupt[0] % self.ring_depth
        before = integrity.host_row(self.rings, row, slot=slot)
        pre_live = np.asarray(integrity._states_digests(self.states))[slot]
        # Pending branches were rolled out from pre-repair buffers; drop
        # them so the dispatch skips branch-match and rolls fresh ones.
        s.res_anchor, s.res_bits = None, None
        with self.span("sdc_repair", slot=slot, frames=replay.n):
            self._dispatch({slot: (replay, None, session)})
        post_live = np.asarray(integrity._states_digests(self.states))[slot]
        after = integrity.host_row(self.rings, row, slot=slot)
        post_mask = integrity.attest_ring(self.rings)[slot]
        report = {
            "slot": slot,
            "corrupt_frames": corrupt,
            "repaired": len(corrupt),
            "repair_frames": replay.n,
            "bitwise": bool(
                (pre_live == post_live).all() and not post_mask.any()
            ),
            "first_corrupt_field": integrity.first_corrupt_field(
                before, after
            ),
        }
        self.metrics.count("sdc_repaired", len(corrupt))
        if report["bitwise"]:
            self.metrics.count("sdc_repaired_bitwise", len(corrupt))
        self.metrics.observe("sdc_repair_frames", replay.n)
        return report
