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
title's own per-lane lookups (box_game's ``inputs.bits[handle]``) are
the title's. The price is ``depth`` x the row's bytes a save instead of
one row, nothing for box_game's rows; no served large world has been
measured (``PERF.md`` section 7).

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

Host-side per-slot speculation (branch build, match, input log) reuses the
singleton implementation verbatim: the native builder is instantiated per
slot (it owns a per-match C++ input-log mirror) and the pure-Python
fallback borrows :class:`~bevy_ggrs_tpu.spec_runner.
SpeculativeRollbackRunner`'s tree-builder methods unbound through
:class:`_SlotSpecShim` — bit-identical trees by construction, no forked
logic to drift.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.fused import FusedTickExecutor, _i32_cached
from bevy_ggrs_tpu.native import spec as native_spec
from bevy_ggrs_tpu.obs.ledger import blame_divergence
from bevy_ggrs_tpu.obs.trace import NULL_SPAN, Instrumented
from bevy_ggrs_tpu.parallel.speculate import match_branch
from bevy_ggrs_tpu.predict.batch import BatchedRanker
from bevy_ggrs_tpu.predict.model import resolve_predictor
from bevy_ggrs_tpu.runner import RollbackRunner, _Step
from bevy_ggrs_tpu.schedule import PREDICTED, Schedule
from bevy_ggrs_tpu.serve.faults import SlotFault, SlotTicket
from bevy_ggrs_tpu.session.requests import AdvanceFrame, RestoreGameState
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner
from bevy_ggrs_tpu.state import SnapshotRing, WorldState, combine64, ring_init


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
    ):
        self.schedule = schedule
        self.num_slots = int(num_slots)
        self.burst_frames = int(burst_frames)
        self.num_branches = int(num_branches)
        self.spec_frames = int(spec_frames)
        tick = functools.partial(
            FusedTickExecutor._tick_impl, schedule, self.burst_frames,
            self.spec_frames,
        )
        # 20 args; spec_status (the shared all-PREDICTED [F, P] constant)
        # broadcasts, everything else carries the slot axis.
        self._fn = jax.jit(jax.vmap(tick, in_axes=(0,) * 19 + (None,)))
        self._admit = jax.jit(self._admit_impl)
        self._spec_status = None
        # Cost-observatory hook: when armed, the NEXT dispatch prices the
        # compiled program (cost_analysis/memory_analysis) into
        # utils.xla_cache under this name. Arm it before warmup — the AOT
        # lowering's backend compile is then a cache hit of the warmup
        # compile and lands before any churn counter is snapshotted.
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

    @staticmethod
    def _admit_impl(rings, states, slot, new_ring, new_state):
        write = lambda stacked, row: jax.tree_util.tree_map(
            lambda R, r: jax.lax.dynamic_update_index_in_dim(R, r, slot, 0),
            stacked, row,
        )
        return write(rings, new_ring), write(states, new_state)

    def admit(self, rings, states, slot: int, new_ring, new_state):
        """Write a fresh singleton (ring, state) into slot row ``slot`` of
        the stacked trees. The index is traced — one compile covers every
        slot, which is what makes match churn recompile-free."""
        return self._admit(
            rings, states, _i32_cached(slot), new_ring, new_state
        )

    def cache_size(self) -> int:
        """Compiled-variant count of the batched tick program (-1 when the
        jit internals don't expose it). 1 after warmup, and it must STAY 1
        through any amount of match churn."""
        probe = getattr(self._fn, "_cache_size", None)
        return int(probe()) if probe is not None else -1

    def run(
        self,
        rings, states, prev_rings, prev_states,
        branch, absorb_first, absorb_n, prev_anchor, prev_total,
        do_load, load_frame, start_frame,
        bits, status, save_mask, adv_mask,
        spec_from_live, spec_anchor, branch_bits,
    ):
        """Dispatch one batched tick. Scalar args are host ``[S]`` arrays,
        tensors ``[S, ...]`` (all plain NumPy — jit's C++ fast path
        transfers them during argument sharding); trees are the stacked
        device pytrees. Returns the full 7-tuple, device-resident."""
        if self._spec_status is None:
            P = np.shape(branch_bits)[3]
            self._spec_status = jnp.full(
                (self.spec_frames, P), PREDICTED, dtype=jnp.int32
            )
        full_args = (
            rings, states, prev_rings, prev_states,
            branch, absorb_first, absorb_n, prev_anchor, prev_total,
            do_load, load_frame, start_frame,
            bits, status, save_mask, adv_mask,
            spec_from_live, spec_anchor, branch_bits, self._spec_status,
        )
        if self._cost_name is not None:
            from bevy_ggrs_tpu.utils import xla_cache

            name, self._cost_name = self._cost_name, None
            xla_cache.record_executable_cost(name, self._fn, *full_args)
            self._captured_name = name
        return self._fn(*full_args)


class _SlotSpecShim:
    """Adapter exposing exactly the attributes the singleton runner's
    branch-tree methods read, so they can run UNBOUND against a per-slot
    input log. Any drift between batched and singleton trees is therefore
    impossible short of editing the singleton itself."""

    _structured_bits = SpeculativeRollbackRunner._structured_bits
    _candidate_values = SpeculativeRollbackRunner._candidate_values
    _extrapolate_base = SpeculativeRollbackRunner._extrapolate_base
    _history_fingerprint = SpeculativeRollbackRunner._history_fingerprint
    _known_inputs = SpeculativeRollbackRunner._known_inputs

    def __init__(
        self, input_spec, num_players, num_branches, spec_frames,
        branch_values, input_log,
    ):
        self.input_spec = input_spec
        self.num_players = num_players
        self.num_branches = num_branches
        self.spec_frames = spec_frames
        self._branch_values = branch_values
        self._input_log = input_log


class _Slot:
    """Host-side record of one batch slot: match identity, frame counter,
    per-slot input log / native builder, and the metadata of the pending
    rollout living in row ``index`` of the core's prev buffers."""

    __slots__ = (
        "index", "active", "frame", "spec_on", "native", "input_log",
        "shim", "res_anchor", "res_bits", "res_from_live",
    )

    def __init__(self, index: int):
        self.index = index
        self.active = False
        self.frame = 0
        self.spec_on = True
        self.native = None
        self.input_log: dict = {}
        self.shim: Optional[_SlotSpecShim] = None
        self.res_anchor: Optional[int] = None
        self.res_bits: Optional[np.ndarray] = None
        self.res_from_live = True


class BatchedSessionCore(Instrumented):
    """S fixed-capacity match slots over stacked device state, advanced by
    one :class:`BatchedTickExecutor` dispatch per tick round.

    The per-slot request protocol matches the singleton runner's canonical
    tick: each slot submits one ``[Load?, (Save, Advance)*]`` segment per
    round with saves labeled contiguously (the session layer produces
    exactly this shape). ``RestoreGameState`` and non-standard bursts
    raise a typed :class:`~bevy_ggrs_tpu.serve.faults.SlotFault` naming
    the offending slot — BEFORE any slot's host or device state is touched
    (every segment of every slot is validated ahead of the apply loop), so
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
            )
        S, B, F = self.num_slots, self.num_branches, self.spec_frames
        # Cost observatory opt-in (GGRS_XLA_COST=1): the warmup dispatch
        # prices the batched tick (flops / bytes / hbm_peak_bytes) into
        # utils.xla_cache. Opt-in because the AOT lowering re-traces the
        # program — its backend compile is a persistent-cache hit, but
        # the trace itself costs seconds at large S.
        if os.environ.get("GGRS_XLA_COST", "").lower() not in (
            "", "0", "false"
        ):
            self._exec.enable_cost_capture(
                f"batched_tick_S{S}_B{B}_F{F}"
            )
        self._template = jax.tree_util.tree_map(jnp.asarray, initial_state)
        bcast = lambda prefix: jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x.reshape((1,) * len(prefix) + x.shape), prefix + x.shape
            ),
            self._template,
        )
        self.states = bcast((S,))
        self.rings = SnapshotRing(
            states=bcast((S, self.ring_depth)),
            frames=jnp.full((S, self.ring_depth), -1, dtype=jnp.int32),
            checksums=jnp.zeros((S, self.ring_depth, 2), dtype=jnp.uint32),
        )
        # Previous rollout buffers, wholesale-replaced every dispatch.
        # Placeholder contents are never read: a slot's absorb phase only
        # selects its row when that slot has pending-rollout metadata.
        self.prev_states = bcast((S, B))
        self.prev_rings = SnapshotRing(
            states=bcast((S, B, F)),
            frames=jnp.full((S, B, F), -1, dtype=jnp.int32),
            checksums=jnp.zeros((S, B, F, 2), dtype=jnp.uint32),
        )
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
        self.native_batch_ms_total = 0.0
        # Optional AttributionProbe (obs/attribution.py): when a bench
        # attaches one, the executor call is timed as a nested
        # device_wait so backends whose dispatch blocks on the in-flight
        # computation (XLA:CPU admits one) don't get device execution
        # billed as host work in the probe's enclosing host window.
        self.attribution = None
        # Aggregate counters (per-slot views go through labeled metrics).
        self.ticks_total = 0
        self.device_dispatches_total = 0
        # Scan steps of the batched tick, exact: every dispatch runs
        # ``burst_frames`` static steps for each of its ``num_slots`` lanes
        # (``burst_step_slots_total``); ``burst_steps_total`` is how many
        # of them a lane's request list asked for (its AdvanceFrames). The
        # ratio is the share of the tick that is not padding.
        self.burst_steps_total = 0
        self.burst_step_slots_total = 0
        self.spec_hits = 0
        self.spec_partial_hits = 0
        self.spec_misses = 0
        self.rollbacks_total = 0
        self.rollback_frames_total = 0
        self.rollback_frames_recovered_total = 0
        # Last dispatch's measured host-work split (docs/serving.md
        # "Front door"): the known per-slot Python-loop budget, decomposed
        # so the ROADMAP's native-argument-assembly item has a baseline.
        self.last_branch_build_ms = 0.0
        self.last_arg_assembly_ms = 0.0
        self.last_predictor_rank_ms = 0.0
        self.predictor_rank_ms_total = 0.0
        self.predictor_rank_dispatches = 0

    # -- lifecycle ------------------------------------------------------

    @property
    def active_count(self) -> int:
        return sum(1 for s in self.slots if s.active)

    def free_slots(self) -> List[int]:
        return [s.index for s in self.slots if not s.active]

    def warmup(self) -> None:
        """Compile the batched tick AND the admit program before serving —
        from here on, match churn must not trigger a compile (the
        acceptance contract checked against ``compile_counters()``)."""
        self._dispatch({})
        row = lambda tree: jax.tree_util.tree_map(lambda x: x[0], tree)
        # Identity write: row 0 written back onto itself compiles the
        # admit program without disturbing any occupant.
        self.rings, self.states = self._exec.admit(
            self.rings, self.states, 0, row(self.rings), row(self.states)
        )
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
        self.rings, self.states = self._exec.admit(
            self.rings, self.states, slot, new_ring, state,
        )
        s.active = True
        s.frame = 0 if ticket is None else int(ticket.frame)
        s.spec_on = bool(spec_on if ticket is None else ticket.spec_on)
        s.res_anchor = None
        s.res_bits = None
        s.res_from_live = True
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
        s.shim = _SlotSpecShim(
            self.input_spec, self.num_players, self.num_branches,
            self.spec_frames, self._branch_values, s.input_log,
        )
        if self._predictor is not None:
            # The borrowed _structured_bits picks this up via getattr;
            # per-dispatch seeds land in _seed_memo (see _dispatch).
            s.shim._predictor = self._predictor
        self.metrics.count(
            "matches_admitted" if ticket is None else "matches_readmitted"
        )
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
        s.shim = None
        s.res_anchor = None
        s.res_bits = None
        self.metrics.count("matches_retired")

    def slot_state(self, slot: int) -> WorldState:
        """Device view of one slot's live state (e.g. for handing a match
        back to a singleton runner, or for parity checks)."""
        return jax.tree_util.tree_map(lambda x: x[slot], self.states)

    def slot_ring(self, slot: int) -> SnapshotRing:
        return jax.tree_util.tree_map(lambda x: x[slot], self.rings)

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

    def _validate_segment(
        self, slot: int, frame: int, load_frame: Optional[int], steps
    ) -> int:
        """Canonical-shape check for one segment, BEFORE anything mutates:
        raises :class:`SlotFault` instead of half-applying a round.
        Returns the frame the slot would reach."""
        start = frame if load_frame is None else load_frame
        if not steps or any(
            st.adv is None or st.save_frame != start + t
            for t, st in enumerate(steps)
        ):
            raise SlotFault(slot, "non_canonical_burst", frame)
        if len(steps) > self.burst_frames:
            raise SlotFault(slot, "burst_overflow", frame)
        return start + len(steps)

    def tick(self, work: Dict[int, tuple]) -> None:
        """Advance every slot named in ``work`` — ``{slot: (requests,
        confirmed_frame, session)}`` (``confirmed_frame=None`` means fully
        confirmed; ``session`` may be None) — in as few batched dispatches
        as the deepest request list needs (one per Load-delimited segment;
        the session layer emits single-segment lists, so normally one).

        Fault atomicity: every slot's every segment (all rounds) is
        validated up front, so a :class:`SlotFault` escaping this method
        guarantees NO slot's state — host or device — changed. The caller
        may drop the named slot from ``work`` and call again."""
        self.ticks_total += 1
        self.flush_reports()
        per_slot: Dict[int, List[tuple]] = {}
        rounds = 1
        for slot, (requests, confirmed, session) in work.items():
            if not self.slots[slot].active:
                raise RuntimeError(f"slot {slot} is not active")
            frame = self.slots[slot].frame
            try:
                segs = RollbackRunner._segment(None, requests)
            except TypeError as e:
                reason = (
                    "restore_request"
                    if any(isinstance(r, RestoreGameState) for r in requests)
                    else "unsupported_request"
                )
                raise SlotFault(slot, reason, frame, cause=e) from e
            for load, steps in segs:
                frame = self._validate_segment(slot, frame, load, steps)
            per_slot[slot] = [
                (load, steps, confirmed, session) for load, steps in segs
            ]
            rounds = max(rounds, len(segs))
        for r in range(rounds):
            batch = {
                slot: segs[r] for slot, segs in per_slot.items()
                if r < len(segs)
            }
            with self.span("serve_round", round=r, slots=len(batch)):
                self._dispatch(batch)

    def flush_reports(self) -> None:
        """Deliver deferred checksum reports (the only device->host sync
        in the serving loop, off the producing dispatch's critical path)."""
        if not self._pending_reports:
            return
        pending, self._pending_reports = self._pending_reports, []
        with self.span("checksum_sync"):
            host = [(np.asarray(arr), rows) for arr, rows in pending]
        for cs_host, rows in host:
            for slot, t, frame, session in rows:
                session.report_checksum(frame, combine64(cs_host[slot, t]))

    def _record_predictor_rank(self, rank_ms: float) -> None:
        self.last_predictor_rank_ms = rank_ms
        self.predictor_rank_ms_total += rank_ms
        self.predictor_rank_dispatches += 1
        self.metrics.observe("predictor_rank_ms", rank_ms)
        self.timeseries.observe("predictor_rank_ms", rank_ms)

    def _build_branches(self, s: _Slot, anchor: int, end: int, session,
                        seed=None):
        """The next rollout's branch tensor for one slot — the singleton
        builder, verbatim (native when available, else the borrowed
        structured tree). ``seed`` is this slot's slice of the batched
        predictor ranking (None when the predictor is off)."""
        if s.native is not None:
            if seed is not None:
                s.native.seed(anchor, seed)
            qs_ptr = s.native.qset_ptr(session)
            if qs_ptr is not None:
                known = known_mask = None
            elif session is None:
                known, known_mask = self._known0, self._mask0
            else:
                known, known_mask = s.shim._known_inputs(anchor, session)
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
            known, known_mask = s.shim._known_inputs(anchor, session)
        if getattr(s.shim, "_predictor", None) is not None:
            # Fresh per-call memo: a stale one (same anchor, pre-burst
            # window) must never leak into this build.
            s.shim._seed_memo = (anchor, seed) if seed is not None else None
        return s.shim._structured_bits(
            np.asarray(last), known, known_mask, anchor
        )

    def _dispatch(self, batch: Dict[int, tuple]) -> None:
        """One vmapped dispatch: slots in ``batch`` run their segment,
        every other slot no-ops (and, if it has a pending rollout, replays
        it bitwise so the wholesale prev-buffer swap preserves it).

        Routes to the native batch plane when it loaded (ONE C call for
        the per-slot host work, :meth:`_dispatch_native`) or the per-slot
        Python loop (:meth:`_dispatch_python`) — bitwise identical paths,
        property-tested in tests/test_native_batch.py.

        Atomic on fault: segments are re-validated in a pre-pass (direct
        callers may bypass :meth:`tick`), so a raise can only happen before
        the first input-log write or device dispatch — a sibling slot's
        next-tick output is bitwise unaffected by another slot faulting."""
        if self._plane is not None:
            return self._dispatch_native(batch)
        return self._dispatch_python(batch)

    def _dispatch_python(self, batch: Dict[int, tuple]) -> None:
        """The per-slot host loop (the ``GGRS_NO_NATIVE=1`` reference
        path): log writes, branch matches, window gather and tree builds
        all run per slot in Python."""
        S, B, F, MF = (
            self.num_slots, self.num_branches, self.spec_frames,
            self.burst_frames,
        )
        P = self.num_players
        for i, (load_frame, steps, _confirmed, _session) in batch.items():
            self._validate_segment(i, self.slots[i].frame, load_frame, steps)
        i32 = lambda: np.zeros(S, np.int32)
        branch_a, absorb_first_a, absorb_n_a = i32(), i32(), i32()
        prev_anchor_a, prev_total_a = i32(), i32()
        load_frame_a, start_frame_a, spec_anchor_a = i32(), i32(), i32()
        do_load_a = np.zeros(S, bool)
        from_live_a = np.ones(S, bool)
        save_mask_a = np.zeros((S, MF), bool)
        adv_mask_a = np.zeros((S, MF), bool)
        bits_a = np.zeros((S, MF) + self._zero.shape, self._zero.dtype)
        status_a = np.zeros((S, MF, P), np.int32)
        bb_a = np.zeros((S, B, F) + self._zero.shape, self._zero.dtype)
        # post[slot] -> state updates applied after the dispatch succeeds
        post: Dict[int, tuple] = {}
        reports: List[tuple] = []

        # The per-slot loop is one span; the predictor ranking and the
        # tree builds are its children, and ``serve_arg_assembly`` (the
        # series) is its self time: log writes, matches, array fills.
        sp_rank = NULL_SPAN
        with self.span(
            "serve_arg_assembly", series=False, slots=len(batch)
        ) as sp_loop:
            # Pass 1 — as-used log writes + anchor geometry for every batched
            # slot, hoisted ahead of the build loop so the batched predictor
            # ranking sees all post-write windows in ONE vmapped call.
            geom: Dict[int, tuple] = {}
            for i, (load_frame, steps, confirmed, _session) in batch.items():
                s = self.slots[i]
                start = s.frame if load_frame is None else load_frame
                end = start + len(steps)
                anchor = end if confirmed is None else confirmed + 1
                # As-used log BEFORE match/build (forward-fill reads anchor-1,
                # which this very burst may advance).
                for t, st in enumerate(steps):
                    s.input_log[start + t] = np.asarray(st.adv.bits)
                spec_active = (
                    s.spec_on and anchor <= end
                    and anchor > end - self.ring_depth
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
                        s, anchor, end, batch[i][3], seeds.get(i)
                    )
            for s in self.slots:
                i = s.index
                if i not in batch:
                    # No-op lane: every phase gated off; replay the pending
                    # rollout (if any) so the prev-buffer swap keeps it valid.
                    start_frame_a[i] = s.frame
                    if s.res_anchor is not None:
                        spec_anchor_a[i] = s.res_anchor
                        from_live_a[i] = s.res_from_live
                        bb_a[i] = s.res_bits
                    else:
                        spec_anchor_a[i] = s.frame
                    continue
                requests_seg = batch[i]
                load_frame, steps, confirmed, session = requests_seg
                start, end, anchor, spec_active = geom[i]
                n_steps = len(steps)
                # Branch-commit decision (host-side, zero device syncs).
                absorb_branch, n_commit = 0, 0
                missed = False
                blame_player = blame_frame = None
                if (
                    load_frame is not None
                    and s.res_anchor is not None
                    and load_frame >= s.res_anchor
                ):
                    steps_arr = np.stack(
                        [np.asarray(st.adv.bits) for st in steps]
                    )
                    matched = None
                    if s.native is not None:
                        matched = s.native.match(
                            s.res_bits, s.res_anchor, load_frame, steps_arr, F
                        )
                    else:
                        needed = []
                        complete = True
                        for f in range(s.res_anchor, load_frame):
                            got = s.input_log.get(f)
                            if got is None:
                                complete = False
                                break
                            needed.append(got)
                        if complete:
                            needed.extend(steps_arr)
                            matched = match_branch(
                                s.res_bits, np.stack(needed)[:F]
                            )
                    if matched is not None:
                        br, depth = matched
                        nc = min(depth - (load_frame - s.res_anchor), n_steps)
                        if nc > 0:
                            absorb_branch, n_commit = int(br), int(nc)
                        else:
                            missed = True
                            self.spec_misses += 1
                            self.metrics.count("spec_misses")
                            self.metrics.count(
                                "spec_misses", labels={"match_slot": i}
                            )
                        if self.ledger.enabled:
                            # Blame: first corrected input diverging from the
                            # branch-0 prediction rows (pure NumPy on the
                            # host-resident branch tensor).
                            pre = load_frame - s.res_anchor
                            k = min(n_steps, F - pre)
                            if k > 0:
                                div = blame_divergence(
                                    np.asarray(s.res_bits)[0][pre:pre + k],
                                    steps_arr[:k],
                                )
                                if div is not None:
                                    blame_player = div[1]
                                    blame_frame = load_frame + div[0]
                # The next rollout. Speculation is active only when the anchor
                # lies inside the post-burst ring window (precomputed in pass
                # 1); otherwise the lane still computes a (discarded) rollout
                # from the live frontier.
                if spec_active:
                    bb = trees[i]
                    spec_anchor, from_live = anchor, (anchor == end)
                else:
                    bb = self._zero_bb
                    spec_anchor, from_live = end, True
                # Burst assembly: after a partial commit only the unmatched
                # tail resimulates, absorb having positioned the state.
                tail = steps[n_commit:]
                if n_commit > 0:
                    burst_load, burst_start = None, load_frame + n_commit
                else:
                    burst_load, burst_start = load_frame, start
                branch_a[i] = absorb_branch
                absorb_first_a[i] = load_frame if load_frame is not None else 0
                absorb_n_a[i] = n_commit
                prev_anchor_a[i] = s.res_anchor or 0
                prev_total_a[i] = F if s.res_anchor is not None else 0
                do_load_a[i] = burst_load is not None
                load_frame_a[i] = burst_load if burst_load is not None else 0
                start_frame_a[i] = burst_start
                n_tail = len(tail)
                save_mask_a[i, :n_tail] = True
                adv_mask_a[i, :n_tail] = True
                for t, st in enumerate(tail):
                    bits_a[i, t] = np.asarray(st.adv.bits)
                    status_a[i, t] = np.asarray(st.adv.status, np.int32)
                spec_anchor_a[i] = spec_anchor
                from_live_a[i] = from_live
                bb_a[i] = bb
                # bb is per-call fresh from both builders, so storing it for
                # the replay/match path needs no defensive copy.
                post[i] = (
                    end, spec_active, anchor if spec_active else None,
                    bb if spec_active else None,
                    from_live, load_frame, n_commit, n_steps, burst_start,
                    n_tail, session, missed, blame_player, blame_frame,
                )

        if sp_loop is not NULL_SPAN:
            bb_ms = sp_build.ms
            arg_ms = max(0.0, sp_loop.ms - bb_ms - sp_rank.ms)
            self.last_branch_build_ms = bb_ms
            self.last_arg_assembly_ms = arg_ms
            self.metrics.observe("serve_branch_build", bb_ms)
            self.metrics.observe("serve_arg_assembly", arg_ms)
            self.timeseries.observe("serve_branch_build_ms", bb_ms)
            self.timeseries.observe("serve_arg_assembly_ms", arg_ms)

        self._finish_dispatch(
            (branch_a, absorb_first_a, absorb_n_a, prev_anchor_a,
             prev_total_a, do_load_a, load_frame_a, start_frame_a,
             bits_a, status_a, save_mask_a, adv_mask_a,
             from_live_a, spec_anchor_a, bb_a),
            post, reports,
        )

    def _finish_dispatch(
        self, jit_args: tuple, post: Dict[int, tuple],
        reports: List[tuple],
    ) -> None:
        """The device dispatch + post-dispatch bookkeeping shared by both
        host paths (per-slot Python loop and native batch plane): run the
        batched tick, then apply frame counters, rollout metadata,
        hit/miss counters, ledger entries and deferred checksum rows."""
        branch_a = jit_args[0]
        self.device_dispatches_total += 1
        self.burst_step_slots_total += self.num_slots * self.burst_frames
        dev = (
            self.attribution.device_wait()
            if self.attribution is not None
            else contextlib.nullcontext()
        )
        with self.span("serve_dispatch"), dev:
            (
                self.rings, self.states, absorb_cs, burst_cs,
                self.prev_rings, self.prev_states, _spec_cs,
            ) = self._exec.run(
                self.rings, self.states, self.prev_rings, self.prev_states,
                *jit_args,
            )

        for i, (
            end, spec_active, res_anchor, res_bits, from_live, load_frame,
            n_commit, n_steps, burst_start, n_tail, session, missed,
            blame_player, blame_frame,
        ) in post.items():
            s = self.slots[i]
            s.frame = end
            if spec_active:
                s.res_anchor, s.res_bits = res_anchor, res_bits
                s.res_from_live = from_live
                # A fresh rollout dispatched for this slot: B×F
                # speculative device frames. (No-op lane replays are NOT
                # charged — they are an artifact of the wholesale
                # prev-buffer swap, not new speculative intent.)
                self.ledger.record_rollout(
                    self.num_branches * self.spec_frames, slot=i
                )
            else:
                s.res_anchor, s.res_bits = None, None
            lab = {"match_slot": i}
            self.burst_steps_total += n_steps
            self.metrics.count("frames_advanced", n_steps)
            self.metrics.count("frames_advanced", n_steps, labels=lab)
            if load_frame is not None:
                self.rollbacks_total += 1
                self.metrics.count("rollbacks")
                self.metrics.count("rollbacks", labels=lab)
                self.metrics.observe("rollback_depth", n_steps)
                outcome = (
                    ("full" if n_commit == n_steps else "partial")
                    if n_commit > 0
                    else ("miss" if missed else "unmatched")
                )
                self.ledger.record(
                    outcome, depth=n_steps, frames_recovered=n_commit,
                    frames_resimulated=n_steps - n_commit,
                    branch=branch_a[i] if n_commit > 0 else None,
                    rank=branch_a[i] if n_commit > 0 else None,
                    blame_player=blame_player, blame_frame=blame_frame,
                    slot=i, load_frame=load_frame,
                )
                if n_commit > 0:
                    self.rollback_frames_recovered_total += n_commit
                    self.metrics.count("rollback_frames_recovered", n_commit)
                    if n_commit == n_steps:
                        self.spec_hits += 1
                        self.metrics.count("spec_hits")
                        self.metrics.count("spec_hits", labels=lab)
                    else:
                        self.spec_partial_hits += 1
                        self.metrics.count("spec_partial_hits")
                        self.rollback_frames_total += n_tail
                        self.metrics.count("rollback_frames", n_tail)
                else:
                    self.rollback_frames_total += n_steps
                    self.metrics.count("rollback_frames", n_steps)
            if session is not None and self.report_checksums:
                wants = getattr(session, "wants_checksum", None)
                rows_a = [
                    (i, t, load_frame + t) for t in range(n_commit)
                    if wants is None or wants(load_frame + t)
                ]
                rows_b = [
                    (i, t, burst_start + t) for t in range(n_tail)
                    if wants is None or wants(burst_start + t)
                ]
                if rows_a:
                    reports.append(
                        (absorb_cs, [r + (session,) for r in rows_a])
                    )
                if rows_b:
                    reports.append(
                        (burst_cs, [r + (session,) for r in rows_b])
                    )
            self._gc_log(s)
        self._pending_reports.extend(reports)

    def _dispatch_native(self, batch: Dict[int, tuple]) -> None:
        """One vmapped dispatch with the per-slot host loop consolidated
        into the two batch-plane calls: ``ggrs_batch_stage`` lands every
        slot's as-used log rows, in-flight tree match and predictor
        window gather in ONE C call before the commit decisions, and
        ``ggrs_batch_build`` runs every seeded tree build plus the no-op
        lanes' tree re-use copies straight into the dispatch's jit
        argument buffer. Bitwise identical to :meth:`_dispatch_python`
        (the C side loops over the same per-slot primitives)."""
        plane = self._plane
        S, B, F, MF = (
            self.num_slots, self.num_branches, self.spec_frames,
            self.burst_frames,
        )
        P = self.num_players
        for i, (load_frame, steps, _confirmed, _session) in batch.items():
            self._validate_segment(i, self.slots[i].frame, load_frame, steps)
        i32 = lambda: np.zeros(S, np.int32)
        branch_a, absorb_first_a, absorb_n_a = i32(), i32(), i32()
        prev_anchor_a, prev_total_a = i32(), i32()
        load_frame_a, start_frame_a, spec_anchor_a = i32(), i32(), i32()
        do_load_a = np.zeros(S, bool)
        from_live_a = np.ones(S, bool)
        save_mask_a = np.zeros((S, MF), bool)
        adv_mask_a = np.zeros((S, MF), bool)
        bits_a = np.zeros((S, MF) + self._zero.shape, self._zero.dtype)
        status_a = np.zeros((S, MF, P), np.int32)
        # Fresh per dispatch (NOT a reused plane buffer): the previous
        # dispatch's rows live on as the slots' in-flight trees
        # (res_bits views) until the post pass replaces them, and the jit
        # argument transfer may still read them asynchronously.
        bb_a = np.zeros((S, B, F) + self._zero.shape, self._zero.dtype)
        post: Dict[int, tuple] = {}
        reports: List[tuple] = []

        # One span over the host loop; the two C calls and the predictor
        # ranking are its children. ``native_batch_ms`` is the two calls'
        # sum, ``serve_branch_build`` the build call, ``serve_arg_assembly``
        # the loop's time outside build and ranking.
        sp_rank = NULL_SPAN
        with self.span(
            "serve_arg_assembly", series=False, slots=len(batch)
        ) as sp_loop:
            plane.reset_masks()
            # Pass 1 — SoA staging for ggrs_batch_stage: step bits/status,
            # anchor geometry, match inputs, window-gather requests. The
            # Python-side dict update bypasses MirroredLog's per-row ctypes
            # forward — the stage call lands the same rows in the native
            # mirror (in per-slot log -> match -> gather order, mirroring
            # the Python pass structure).
            geom: Dict[int, tuple] = {}
            for i, (load_frame, steps, confirmed, _session) in batch.items():
                s = self.slots[i]
                start = s.frame if load_frame is None else load_frame
                end = start + len(steps)
                anchor = end if confirmed is None else confirmed + 1
                plane.log_mask[i] = 1
                plane.starts[i] = start
                plane.n_steps[i] = len(steps)
                for t, st in enumerate(steps):
                    arr = np.asarray(st.adv.bits)
                    dict.__setitem__(s.input_log, start + t, arr)
                    plane.steps[i, t] = arr
                    plane.status[i, t] = np.asarray(st.adv.status, np.int32)
                if (
                    load_frame is not None
                    and s.res_anchor is not None
                    and load_frame >= s.res_anchor
                ):
                    plane.match_mask[i] = 1
                    plane.res_anchors[i] = s.res_anchor
                    plane.load_frames[i] = load_frame
                    plane.set_res(i, s.res_bits)
                spec_active = (
                    s.spec_on and anchor <= end
                    and anchor > end - self.ring_depth
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
                    start_frame_a[i] = s.frame
                    if s.res_anchor is not None:
                        spec_anchor_a[i] = s.res_anchor
                        from_live_a[i] = s.res_from_live
                        plane.copy_mask[i] = 1
                        plane.set_res(i, s.res_bits)
                    else:
                        spec_anchor_a[i] = s.frame
                    continue
                load_frame, steps, confirmed, session = batch[i]
                start, end, anchor, spec_active = geom[i]
                n_steps = len(steps)
                absorb_branch, n_commit = 0, 0
                missed = False
                blame_player = blame_frame = None
                if plane.match_mask[i]:
                    br = int(plane.out_branch[i])
                    if br >= 0:  # -1 = as-used log gap (the Python no-match)
                        depth = int(plane.out_depth[i])
                        nc = min(depth - (load_frame - s.res_anchor), n_steps)
                        if nc > 0:
                            absorb_branch, n_commit = br, int(nc)
                        else:
                            missed = True
                            self.spec_misses += 1
                            self.metrics.count("spec_misses")
                            self.metrics.count(
                                "spec_misses", labels={"match_slot": i}
                            )
                        if self.ledger.enabled:
                            pre = load_frame - s.res_anchor
                            k = min(n_steps, F - pre)
                            if k > 0:
                                div = blame_divergence(
                                    np.asarray(s.res_bits)[0][pre:pre + k],
                                    plane.steps[i, :k],
                                )
                                if div is not None:
                                    blame_player = div[1]
                                    blame_frame = load_frame + div[0]
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
                        known, kmask = s.shim._known_inputs(anchor, session)
                        plane.known[i] = known
                        plane.kmask[i] = kmask
                        dirty_known.append(i)
                    spec_anchor, from_live = anchor, (anchor == end)
                else:
                    spec_anchor, from_live = end, True
                if n_commit > 0:
                    burst_load, burst_start = None, load_frame + n_commit
                else:
                    burst_load, burst_start = load_frame, start
                branch_a[i] = absorb_branch
                absorb_first_a[i] = load_frame if load_frame is not None else 0
                absorb_n_a[i] = n_commit
                prev_anchor_a[i] = s.res_anchor or 0
                prev_total_a[i] = F if s.res_anchor is not None else 0
                do_load_a[i] = burst_load is not None
                load_frame_a[i] = burst_load if burst_load is not None else 0
                start_frame_a[i] = burst_start
                n_tail = n_steps - n_commit
                save_mask_a[i, :n_tail] = True
                adv_mask_a[i, :n_tail] = True
                if n_tail:
                    bits_a[i, :n_tail] = plane.steps[i, n_commit:n_steps]
                    status_a[i, :n_tail] = plane.status[i, n_commit:n_steps]
                spec_anchor_a[i] = spec_anchor
                from_live_a[i] = from_live
                # The slot's next in-flight tree is its bb_a row, written by
                # the build call below — the view is stored now, the bytes
                # land before the device dispatch reads them.
                post[i] = (
                    end, spec_active, anchor if spec_active else None,
                    bb_a[i] if spec_active else None,
                    from_live, load_frame, n_commit, n_steps, burst_start,
                    n_tail, session, missed, blame_player, blame_frame,
                )
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
            bb_ms = sp_build.ms
            nb_ms = sp_stage.ms + bb_ms
            arg_ms = max(0.0, sp_loop.ms - bb_ms - sp_rank.ms)
            self.last_branch_build_ms = bb_ms
            self.last_arg_assembly_ms = arg_ms
            self.native_batch_ms_total += nb_ms
            self.metrics.observe("serve_branch_build", bb_ms)
            self.metrics.observe("serve_arg_assembly", arg_ms)
            self.metrics.observe("native_batch_ms", nb_ms)
            self.timeseries.observe("serve_branch_build_ms", bb_ms)
            self.timeseries.observe("serve_arg_assembly_ms", arg_ms)
            self.timeseries.observe("native_batch_ms", nb_ms)

        self._finish_dispatch(
            (branch_a, absorb_first_a, absorb_n_a, prev_anchor_a,
             prev_total_a, do_load_a, load_frame_a, start_frame_a,
             bits_a, status_a, save_mask_a, adv_mask_a,
             from_live_a, spec_anchor_a, bb_a),
            post, reports,
        )

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
        steps = []
        for f in range(base, s.frame):
            bits = s.input_log.get(f)
            if bits is None:
                _fail(f"as-used input log does not cover frame {f}")
            steps.append(_Step(
                save_frame=f,
                adv=AdvanceFrame(bits, np.zeros(self.num_players, np.int32)),
            ))
        row = corrupt[0] % self.ring_depth
        before = integrity.host_row(self.rings, row, slot=slot)
        pre_live = np.asarray(integrity._states_digests(self.states))[slot]
        # Pending branches were rolled out from pre-repair buffers; drop
        # them so the dispatch skips branch-match and rolls fresh ones.
        s.res_anchor, s.res_bits = None, None
        with self.span("sdc_repair", slot=slot, frames=len(steps)):
            self._dispatch({slot: (base, steps, None, session)})
        post_live = np.asarray(integrity._states_digests(self.states))[slot]
        after = integrity.host_row(self.rings, row, slot=slot)
        post_mask = integrity.attest_ring(self.rings)[slot]
        report = {
            "slot": slot,
            "corrupt_frames": corrupt,
            "repaired": len(corrupt),
            "repair_frames": len(steps),
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
        self.metrics.observe("sdc_repair_frames", len(steps))
        return report
