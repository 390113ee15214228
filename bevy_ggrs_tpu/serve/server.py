"""MatchServer: the host loop that turns batch slots into served matches.

One server = one model family (one schedule, one input spec, one compiled
batched executable) serving up to ``capacity`` concurrent matches. The
slots are partitioned into ``stagger_groups`` groups that dispatch at
evenly spaced offsets across the 16.7 ms frame: with G groups only S/G
matches' host work (input collection, branch build, argument assembly)
lands on any one instant, flattening the dispatch burst a single
all-slots tick would concentrate at frame start. All groups share ONE
:class:`~bevy_ggrs_tpu.serve.batch.BatchedTickExecutor` — the program is
compiled once, and the persistent XLA cache
(:func:`~bevy_ggrs_tpu.utils.xla_cache.ensure_persistent_compilation_cache`)
makes even that compile a disk read for every process after the first.

Session contract (duck-typed, getattr-guarded — SyncTestSession, P2P and
spectator sessions all fit):

- ``local_player_handles()`` + ``add_local_input(handle, bits)`` — fed
  from the match's ``local_inputs(frame, handle)`` callback each frame;
- ``advance_frame() -> [requests]`` — the canonical request list; a
  session whose class also has ``advance_segment()`` (``SyncTestSession``,
  ``P2PSession``) is asked for that instead: the same run as one
  :class:`~bevy_ggrs_tpu.session.requests.Segment`, arrays in place of
  request objects (a wrapper around such a session, or an instance whose
  ``advance_frame`` was replaced, is asked through ``advance_frame()``);
- ``confirmed_frame()`` (optional) — the speculation anchor; absent means
  fully confirmed every frame (synctest);
- ``poll_remote_clients()`` (optional) — pumped before input collection;
  one that also takes ``parts=`` (P2P and spectator sessions) is handed
  the group's four-slot list while a sink listens, for the poll's receive
  and send seconds and, from a session that counts them, the datagrams it
  received and those parsed in place (series ``serve_poll_direct_share``),
  and asked for ``num_endpoints`` (the remote endpoints a poll pumps:
  series ``serve_endpoints_polled``);
- ``report_checksum(frame, checksum)`` / ``wants_checksum(frame)``
  (optional) — fed from the core's deferred checksum reports, a segment's
  in one ``report_checksums(first_frame, checksums)`` call where the
  session has it;
- ``checksum_votes`` + ``drain_control`` (optional) — their presence
  marks a supervisable P2P session: the server wraps it in a
  :class:`~bevy_ggrs_tpu.session.supervisor.SessionSupervisor` whose
  runner is a facade over the live batch slot, so desync ballots and
  donor-side state serving work while the match is batched.

Fault domains (docs/serving.md "Failure domains"): each match carries a
:class:`~bevy_ggrs_tpu.serve.faults.SlotHealthFSM`. A session that raises,
blows its per-tick watchdog budget ``strike_limit`` times, or trips the
batched core's canonical-burst contract is fenced at the group boundary —
its slot drains to a singleton :class:`~bevy_ggrs_tpu.serve.faults.
RecoveryLane` (all lanes share ONE warmed rollout executable, so the
compile-counter delta through any amount of fault churn stays 0), the
other S−1 lanes dispatch on time, and the match readmits at its reserved
slot index once the lane reports clean — bitwise-continuous with its
pre-fault trajectory. A ``checkpoint_dir`` arms periodic whole-server
checkpoints (:class:`~bevy_ggrs_tpu.serve.faults.ServerCheckpointer`) for
kill -9 crash-restart.

Observability: every group dispatch runs under a ``serve_tick`` span and
per-slot counters carry a ``match_slot`` label; ``slots_active``,
``slots_free``, ``slots_quarantined``, ``slots_recovering`` and
``last_stagger_jitter_ms`` are live gauges the FlightRecorder's
``capture(server=...)`` columns snapshot, and every fault/readmit emits
``slot_fault``/``slot_recover`` tracer instants.
"""

from __future__ import annotations

import inspect
import time
from typing import Callable, Dict, List, Optional, Tuple

from bevy_ggrs_tpu.native.core import native_calls
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore, BatchedTickExecutor
from bevy_ggrs_tpu.obs.trace import (
    NULL_SPAN,
    Instrumented,
    attach_process_events,
    detach_process_events,
)
from bevy_ggrs_tpu.serve.faults import (
    RecoveryLane,
    ServerCheckpointer,
    SlotFault,
    SlotHealth,
    SlotHealthFSM,
    SlotTicket,
    _SlotRunnerFacade,
    adopt_ticket,
)
from bevy_ggrs_tpu.session.common import (
    EventKind,
    PredictionThreshold,
    SessionEvent,
    SessionState,
)
from bevy_ggrs_tpu.session.requests import AdvanceFrame, Segment


class MatchHandle:
    """The handle of one served match: what ``add_match``, ``enqueue_match``,
    ``resume_match`` and ``adopt_rejoin`` return, and the match's handle for
    as long as the server holds it. ``group`` and ``slot`` say where the
    match lives NOW: the server rewrites them in the step that moves the
    match (the re-pack at a frame's end), so read them at the time of use
    and keep no copy. ``==`` and ``hash`` follow IDENTITY, never location:
    a dict keyed on handles stays sound through a move, and two handles of
    one place are equal only if they are one object. A handle rebuilt from
    a record (``MatchHandle(g, s)``, or the bare tuple) names whatever
    lives at ``(g, s)`` when the server is asked: every method that takes
    a handle resolves it by location (docs/serving.md "Handles")."""

    __slots__ = ("group", "slot")

    def __init__(self, group: int, slot: int):
        self.group = int(group)
        self.slot = int(slot)

    def __iter__(self):
        return iter((self.group, self.slot))

    def __repr__(self) -> str:
        return f"MatchHandle(group={self.group}, slot={self.slot})"


class _Match:
    __slots__ = (
        "session", "local_inputs", "fsm", "supervisor", "spec_on",
        "events", "interrupted_at",
    )

    def __init__(self, session, local_inputs, fsm, supervisor, spec_on):
        self.session = session
        self.local_inputs = local_inputs
        self.fsm = fsm
        self.supervisor = supervisor
        self.spec_on = spec_on
        # Who drains the session's events: its supervisor's tick where it
        # has one, else the session's own ``events()`` (None: a session
        # without events, SyncTest).
        self.events = (
            None if supervisor is not None
            else getattr(session, "events", None)
        )
        # The served frame that handed out NETWORK_INTERRUPTED for a peer
        # that has not been heard since (None: every peer is heard).
        self.interrupted_at: Optional[int] = None


def _supervisable(session) -> bool:
    """P2P-shaped sessions (desync ballots + control channel) get a
    SessionSupervisor; synctest/spectator sessions do not."""
    return hasattr(session, "checksum_votes") and hasattr(
        session, "drain_control"
    )


def _advance(session):
    """The session's work for this frame: the :class:`Segment` of
    ``advance_segment()`` where its CLASS has one, else the request list
    of ``advance_frame()``. Asked of the class, and only while the
    instance still has the class's ``advance_frame``: a delegating wrapper
    (``__getattr__``) or a replaced ``advance_frame`` stands in front of
    the list, and is asked for it."""
    segment = getattr(type(session), "advance_segment", None)
    if segment is None or "advance_frame" in getattr(session, "__dict__", ()):
        return session.advance_frame()
    return segment(session)


def _advances(item) -> int:
    """Frames a work item advances: a segment's rows, a list's
    ``AdvanceFrame``s."""
    if isinstance(item, Segment):
        return len(item.bits)
    return sum(1 for r in item if isinstance(r, AdvanceFrame))


def _requests(item) -> List[object]:
    """A work item as the request list a recovery lane's runner takes."""
    return item.requests() if isinstance(item, Segment) else item


_TAKES_PARTS: Dict[type, bool] = {}


def _poll_takes_parts(session) -> bool:
    """Whether the session's ``poll_remote_clients`` has the ``parts``
    parameter (``P2PSession``'s and ``SpectatorSession``'s do). The
    contract is a bare ``poll_remote_clients()``: a session written to it
    is polled as ever and timed from outside, its poll on neither side.
    Asked only while a sink listens; looked up once a session type."""
    kind = type(session)
    takes = _TAKES_PARTS.get(kind)
    if takes is None:
        try:
            takes = "parts" in inspect.signature(
                kind.poll_remote_clients
            ).parameters
        except (AttributeError, TypeError, ValueError):
            takes = False
        _TAKES_PARTS[kind] = takes
    return takes


class MatchServer(Instrumented):
    def __init__(
        self,
        schedule,
        initial_state,
        max_prediction: int,
        num_players: int,
        input_spec,
        capacity: int = 64,
        stagger_groups: int = 4,
        num_branches: int = 8,
        spec_frames: Optional[int] = None,
        branch_values=None,
        frame_ms: float = 1000.0 / 60.0,
        metrics=None,
        tracer=None,
        clock=time.perf_counter,
        report_checksums: bool = True,
        watchdog_budget_ms: Optional[float] = None,
        watchdog_strike_limit: int = 3,
        recovery_deadline_frames: int = 900,
        lane_error_limit: int = 8,
        checkpoint_dir: Optional[str] = None,
        checkpoint_interval: int = 120,
        checkpoint_keep: int = 3,
        slo_config=None,
        slo_export_interval: int = 32,
        trace_dir: Optional[str] = None,
        server_id: int = 0,
        fleet_socket=None,
        fleet_addr=None,
        heartbeat_interval: int = 8,
        timeseries=None,
        admit_budget: int = 4,
        admission_slo_ms: Optional[float] = None,
        ledger=None,
        attest_interval: Optional[int] = 64,
        profiler=None,
    ):
        from bevy_ggrs_tpu.obs.ledger import null_ledger
        from bevy_ggrs_tpu.obs.profiler import null_profiler
        from bevy_ggrs_tpu.obs.slo import SlotSLO, WindowSLO
        from bevy_ggrs_tpu.obs.timeseries import null_timeseries
        from bevy_ggrs_tpu.utils.xla_cache import (
            ensure_persistent_compilation_cache,
            install_compile_listeners,
        )

        ensure_persistent_compilation_cache()
        install_compile_listeners()
        self._set_sinks(metrics, tracer)
        # Sampling host profiler (obs/profiler.py): reads the serving
        # thread's stacks from its own thread — wire-inert by
        # construction. The server does not start/stop it (the soak
        # harness owns the window); it only exports its artifacts.
        self.profiler = profiler if profiler is not None else null_profiler
        self.timeseries = (
            timeseries if timeseries is not None else null_timeseries
        )
        # ONE server-level speculation ledger; each slot group writes
        # through a scoped view so entries carry the server-wide flat
        # slot id (group * per_group + slot — the SLO/metrics key).
        self.ledger = ledger if ledger is not None else null_ledger
        self._ledger_seq = 0  # run_frame's incremental tail() watermark
        self.frame_ms = float(frame_ms)
        self._clock = clock
        # Watchdog: a session's host work (poll + inputs + advance) gets
        # two frame budgets before a miss counts as a strike — generous
        # enough for GC hiccups, tight enough that a hung session is
        # fenced within strike_limit frames.
        self.watchdog_budget_ms = (
            2.0 * self.frame_ms
            if watchdog_budget_ms is None
            else float(watchdog_budget_ms)
        )
        self.watchdog_strike_limit = int(watchdog_strike_limit)
        self.recovery_deadline_frames = int(recovery_deadline_frames)
        self.lane_error_limit = int(lane_error_limit)
        G = max(1, int(stagger_groups))
        per_group = -(-int(capacity) // G)  # ceil: capacity is a floor
        self.capacity = per_group * G
        self._exec = BatchedTickExecutor(
            schedule, per_group, int(max_prediction) + 2, int(num_branches),
            int(spec_frames or max_prediction),
            inputs=input_spec.zeros_np(int(num_players)),
        )
        self.groups: List[BatchedSessionCore] = [
            BatchedSessionCore(
                schedule, initial_state, max_prediction, num_players,
                input_spec, per_group, num_branches=num_branches,
                spec_frames=spec_frames, branch_values=branch_values,
                metrics=self.metrics, tracer=self.tracer,
                executor=self._exec, report_checksums=report_checksums,
                timeseries=self.timeseries,
                ledger=self.ledger.scoped(g * per_group),
            )
            for g in range(G)
        ]
        # Lane-runner construction parameters (recovery lanes are built
        # on demand; they all share one warmed rollout executable so the
        # drain -> recover -> readmit cycle never compiles).
        from bevy_ggrs_tpu.rollout import RolloutExecutor

        self._schedule = schedule
        self._max_prediction = int(max_prediction)
        self._num_players = int(num_players)
        self._input_spec = input_spec
        self._report_checksums = bool(report_checksums)
        self._template = self.groups[0]._template
        self._recovery_exec = RolloutExecutor(
            schedule, self._max_prediction + 2, state_template=self._template
        )
        self._codec = None
        self._matches: Dict[MatchHandle, _Match] = {}
        self._lanes: Dict[MatchHandle, RecoveryLane] = {}
        # Where each held handle lives now: a registered match (batched or
        # on a lane) or a queued admission. The maps above are keyed on the
        # handle OBJECT (its hash is its identity, so a move re-keys
        # nothing there); this one is keyed on location and re-keyed in the
        # step that moves a match (``_move``). A handle rebuilt from a
        # record finds its match through it (``_held``).
        self._at: Dict[Tuple[int, int], MatchHandle] = {}
        self._reserved: Dict[int, set] = {g: set() for g in range(G)}
        self.checkpointer = (
            ServerCheckpointer(
                checkpoint_dir, checkpoint_interval, checkpoint_keep
            )
            if checkpoint_dir is not None
            else None
        )
        self.frames_served = 0
        # Match-frames withheld by a session's back-pressure
        # (PredictionThreshold): not advanced, not a fault.
        self.frames_withheld_total = 0
        self.faults_total = 0
        self.readmissions_total = 0
        # The re-pack (``_repack``): matches moved since the server was
        # built, and the handles the last served frame moved (a fleet child
        # tells its parent where they live now).
        self.matches_repacked_total = 0
        self.repacked: List[MatchHandle] = []
        self.evictions_total = 0
        # What the hosted sessions reported in the last served frame, each
        # event with the handle of its match (``drain_events``), and the
        # lifecycle's counts: matches retired, events handed out, and the
        # slot-frames in which a hosted match attempted nothing because its
        # session was still synchronising, or withheld its frame while a
        # peer of it was silent past the notify threshold.
        self._match_events: List[Tuple[MatchHandle, SessionEvent]] = []
        self.matches_retired_total = 0
        self.match_events_delivered_total = 0
        self.slot_frames_syncing_total = 0
        self.slot_frames_stalled_total = 0
        self.last_recovery_frames: Optional[int] = None
        self.last_stagger_jitter_ms: Optional[float] = None
        # Slot SLO engine (obs/slo.py): per-tick samples reduce to
        # burn-rate levels every slo_export_interval frames, exported
        # through the labeled metrics path and fed to each slot's FSM.
        self._per_group = per_group
        self.slo = SlotSLO(config=slo_config, metrics=self.metrics)
        self.slo_export_interval = max(1, int(slo_export_interval))
        self.slo_levels: Dict[int, str] = {}
        self.trace_dir = trace_dir
        # Admission queue: enqueue_match reserves the slot immediately and
        # returns the handle, but the expensive part of a join (session
        # warm, initial-state build, device admit) drains AFTER every
        # group has dispatched — a slow join costs the joiner latency,
        # never a sibling group its deadline. admit_budget bounds drains
        # per frame so an arrival storm cannot own the inter-frame gap.
        self.admit_budget = max(1, int(admit_budget))
        self._admit_queue: List[tuple] = []
        self._pending_first: Dict[MatchHandle, object] = {}
        # The served frame a queued arrival was enqueued in, until its
        # first dispatch (series ``sync_frames``).
        self._enqueued_at: Dict[MatchHandle, int] = {}
        self.admissions_completed = 0
        # Slot template pool (filled by warmup): codec-round-tripped
        # (ring, state) pairs a fresh admission reuses instead of
        # re-deriving ring_init(template) per joiner — the migration
        # warmup trick extended to the front door. Entries are immutable
        # device arrays (the admit program copies them into slot rows),
        # so consuming one recycles it and the pool never drains.
        self._slot_templates: List[tuple] = []
        self.templates_admitted = 0
        # Server-scope SLOs over the online time-series windows (the
        # signals the front-door knee detector and the balancer read).
        self.admission_slo_ms = (
            2.0 * self.frame_ms
            if admission_slo_ms is None
            else float(admission_slo_ms)
        )
        objectives = {
            "admission": (
                "admission_ms", self.admission_slo_ms, 0.99,
            ),
            "frame_deadline": ("frame_ms", self.frame_ms, 0.99),
        }
        if self.ledger.enabled:
            # spec_spill is 0.0 for a fully-absorbed rollback and 1.0
            # otherwise (WindowSLO counts samples ABOVE threshold as
            # bad): the objective is 75% of rollbacks fully absorbed.
            objectives["spec_spill"] = ("spec_spill", 0.5, 0.75)
        self.window_slo = WindowSLO(
            self.timeseries,
            objectives,
            config=slo_config,
            metrics=self.metrics,
        )
        self.front_door_levels: Dict[str, str] = {}
        # Fleet membership: with a socket + balancer address configured,
        # the server emits a FleetHeartbeat every heartbeat_interval served
        # frames — the balancer's liveness signal (missed beats past its
        # timeout mean THIS server is dead and its matches fail over).
        self.server_id = int(server_id)
        self.fleet_socket = fleet_socket
        self.fleet_addr = fleet_addr
        self.heartbeat_interval = max(1, int(heartbeat_interval))
        self.heartbeats_sent = 0
        # SDC attestation cadence in served frames (None disables): every
        # interval, one vmapped digest pass per group re-verifies all ring
        # rows; mismatches self-heal in place via repair_slot, escalating
        # unrepairable slots to the recovery-lane / checkpoint ladder
        # (docs/serving.md#self-healing). Detection latency <= interval.
        self.attest_interval = (
            None if attest_interval is None else max(1, int(attest_interval))
        )
        self.sdc_repairs_total = 0
        attach_process_events(self)

    def close(self) -> None:
        """Stop receiving ``gc_pause`` / ``compile`` events."""
        detach_process_events(self)

    def _flat_slot(self, handle: MatchHandle) -> int:
        """Server-wide slot id (group-qualified) — the SLO/metrics key.
        Distinct from ``handle.slot``, which repeats across groups."""
        return handle.group * self._per_group + handle.slot

    def _held(self, handle) -> Optional[MatchHandle]:
        """The handle this server gave whatever lives at ``handle``'s
        (group, slot) NOW — ``handle`` itself when it is the one the
        caller was given, the match's own for a handle rebuilt from a
        record or a bare ``(group, slot)`` tuple; None when nothing lives
        there."""
        return self._at.get(tuple(handle))

    # -- gauges ---------------------------------------------------------

    @property
    def slots_active(self) -> int:
        """Matches currently served: batched slots + recovery lanes."""
        return sum(g.active_count for g in self.groups) + len(self._lanes)

    @property
    def slots_free(self) -> int:
        reserved = sum(len(r) for r in self._reserved.values())
        return (
            self.capacity
            - sum(g.active_count for g in self.groups)
            - reserved
        )

    @property
    def slots_quarantined(self) -> int:
        return sum(
            1
            for m in self._matches.values()
            if m.fsm.state is SlotHealth.QUARANTINED
        )

    @property
    def slots_recovering(self) -> int:
        return sum(
            1
            for m in self._matches.values()
            if m.fsm.state is SlotHealth.RECOVERING
        )

    def cache_size(self) -> int:
        return self._exec.cache_size()

    def heartbeat(self):
        """The liveness + load beacon a :class:`~bevy_ggrs_tpu.fleet.
        FleetBalancer` consumes — also readable in-process for balancers
        colocated with their servers."""
        from bevy_ggrs_tpu.session.protocol import FleetHeartbeat

        spec_hit_permille = spec_waste_permille = 0
        if self.ledger.enabled:
            s = self.ledger.summary()
            spec_hit_permille = int(
                round(1000.0 * s["spec_full_hit_rate"])
            )
            spec_waste_permille = int(
                round(1000.0 * s["spec_waste_ratio"])
            )
        return FleetHeartbeat(
            server_id=self.server_id,
            frames_served=self.frames_served,
            slots_active=self.slots_active,
            slots_free=self.slots_free,
            quarantined=self.slots_quarantined + self.slots_recovering,
            pages=sum(
                1 for lvl in self.slo_levels.values() if lvl == "page"
            ),
            spec_hit_permille=spec_hit_permille,
            spec_waste_permille=spec_waste_permille,
            # Monotonic send counter (1-based on the wire): the balancer
            # refuses to let a beat whose seq it already advanced past
            # refresh liveness, so chaos reorder can't fake freshness.
            beat_seq=self.heartbeats_sent + 1,
        )

    def free_slot_handles(self) -> List[MatchHandle]:
        """Every admittable (group, slot), busiest group with room first
        (pack-first, same policy as :meth:`_pick_slot` — fewest hot
        groups, fewest fixed-cost dispatch programs) — the fleet
        balancer's stagger-aware placement domain. Reserved slots
        (recovering matches) are never offered."""
        order = sorted(
            range(len(self.groups)),
            key=lambda g: (len(self._free_unreserved(g)), g),
        )
        return [
            MatchHandle(g, s)
            for g in order
            for s in self._free_unreserved(g)
        ]

    def health_of(self, handle: MatchHandle) -> SlotHealth:
        return self._matches[self._held(handle)].fsm.state

    def state_codec(self):
        """The server's StateCodec (relay-tier flat-byte layout), built
        lazily from the world template — checkpoints and parity checks
        share one deterministic encoding."""
        if self._codec is None:
            from bevy_ggrs_tpu.relay.delta import StateCodec
            from bevy_ggrs_tpu.state import to_host

            self._codec = StateCodec(to_host(self._template))
        return self._codec

    # -- lifecycle ------------------------------------------------------

    def warmup(self) -> None:
        """Compile the shared batched tick + admit programs (one dispatch
        through group 0 covers every group — they share the executor) AND
        the shared recovery-lane rollout executable, so the drain ->
        recover -> readmit cycle is recompile-free from here on.

        Also round-trips one template ticket through the checkpoint/
        migration blob codec: landing a migrated-in match is steady state
        for a fleet destination, and the decode-side device re-upload
        programs are shape-specialized and process-local, so without this
        the FIRST landing would retrace (a churn_recompiles violation).

        The decoded record seeds the **slot template pool**: fresh
        admissions (``initial_state=None``) reuse its pre-built
        ``(ring_init(state), state)`` pair instead of re-deriving it per
        joiner, so the per-admission device-upload prep amortizes to ~0.
        The codec round-trip is the bitwise witness — the decoded state
        is flat-byte identical to the live template, so a template-
        admitted match is indistinguishable from a cold-admitted one
        (tests/test_native_batch.py pins this)."""
        self.groups[0].warmup()
        lane = self._make_lane_runner()
        lane.warmup()
        from .faults import pack_match_record, unpack_match_record

        codec = self.state_codec()
        rec = unpack_match_record(
            codec,
            pack_match_record(
                codec,
                {
                    "handle": None,
                    "kind": "synctest",
                    "frame": 0,
                    "state": lane.state,
                    "ring": lane.ring,
                    "input_log": {},
                    "spec_on": True,
                    "session_state": None,
                },
            ),
        )
        import jax

        from bevy_ggrs_tpu.state import ring_init

        tpl_state = jax.tree_util.tree_map(
            jax.numpy.asarray, rec["ticket"].state
        )
        tpl_ring = ring_init(tpl_state, self.groups[0].ring_depth)
        jax.block_until_ready(tpl_ring.frames)
        # One entry per drain slot per group: every admission a single
        # frame can complete finds a template waiting. All entries share
        # the same immutable arrays — the pool is bookkeeping, not copies.
        self._slot_templates = [
            (tpl_ring, tpl_state)
            for _ in range(self.admit_budget * len(self.groups))
        ]

    def _make_lane_runner(self):
        from bevy_ggrs_tpu.runner import RollbackRunner

        runner = RollbackRunner(
            self._schedule, self._template, self._max_prediction,
            self._num_players, self._input_spec,
            report_checksums=self._report_checksums,
            metrics=self.metrics, tracer=self.tracer,
        )
        runner.executor = self._recovery_exec
        runner._input_log = {}
        return runner

    def _free_unreserved(self, group: int) -> List[int]:
        reserved = self._reserved[group]
        return [
            i
            for i in self.groups[group].free_slots()
            if i not in reserved
        ]

    def _register(
        self,
        handle: MatchHandle,
        session,
        local_inputs,
        spec_on: bool,
        initial: SlotHealth = SlotHealth.HEALTHY,
        supervisor=None,
    ) -> _Match:
        fsm = SlotHealthFSM(
            handle.slot,
            metrics=self.metrics,
            tracer=self.tracer,
            strike_limit=self.watchdog_strike_limit,
            initial=initial,
        )
        if supervisor is None and _supervisable(session):
            from bevy_ggrs_tpu.session.supervisor import SessionSupervisor

            supervisor = SessionSupervisor(
                session,
                _SlotRunnerFacade(self.groups[handle.group], handle.slot),
                metrics=self.metrics,
                tracer=self.tracer,
                clock=self._clock,
            )
        m = _Match(session, local_inputs, fsm, supervisor, bool(spec_on))
        self._matches[handle] = m
        self._at[tuple(handle)] = handle
        return m

    def _drop(self, handle: MatchHandle) -> None:
        """Forget a match that left (retired, suspended, evicted): its
        record, its place and its SLO history. The handle keeps the last
        location it had."""
        self._matches.pop(handle, None)
        self._pending_first.pop(handle, None)
        self._enqueued_at.pop(handle, None)
        if self._at.get(tuple(handle)) is handle:
            del self._at[tuple(handle)]
        self._vacate_slo(handle)

    def _pick_slot(self) -> MatchHandle:
        # Pack-first: the busiest group that still has room. A group's
        # vmapped tick program costs the same at one live slot as at
        # full occupancy, so the number of HOT groups — not the number
        # of live matches — sets the per-frame device bill; packing
        # keeps it minimal at partial occupancy. The least-loaded
        # spread this replaces existed to balance the per-slot Python
        # host loop across groups, and the batched native plane made
        # that cost flat in occupancy.
        candidates = [
            g for g in range(len(self.groups))
            if self._free_unreserved(g)
        ]
        if not candidates:
            raise RuntimeError("server at capacity")
        group = min(
            candidates,
            key=lambda g: (len(self._free_unreserved(g)), g),
        )
        return MatchHandle(group, self._free_unreserved(group)[0])

    def add_match(
        self,
        session,
        local_inputs: Optional[Callable[[int, int], object]] = None,
        initial_state=None,
        spec_on: bool = True,
        trace=None,
    ) -> MatchHandle:
        """Admit a match synchronously: its session + a ``local_inputs
        (frame, handle) -> bits`` callback feeding the session's local
        handles each frame. Slots balance across stagger groups
        (least-loaded first); slots reserved for recovering matches are
        never handed out. ``trace`` (an :class:`~bevy_ggrs_tpu.serve.
        admission.AdmissionTrace`) gets the slot_warm/admit stages and
        first-frame completion recorded against it."""
        handle = self._pick_slot()
        self._admit_at(
            handle, session, local_inputs, initial_state, spec_on, trace
        )
        return handle

    def enqueue_match(
        self,
        session,
        local_inputs: Optional[Callable[[int, int], object]] = None,
        initial_state=None,
        spec_on: bool = True,
        trace=None,
    ) -> MatchHandle:
        """Admit a match OFF the frame-critical path: the slot is
        reserved and the handle returned now, but session warm +
        initial-state build + device admit run at the end of a
        :meth:`run_frame` (after every group dispatched), bounded by
        ``admit_budget`` per frame. ``initial_state`` may be a zero-arg
        callable — the lazy-build hook that keeps an expensive world
        construction off sibling groups' deadlines."""
        handle = self._pick_slot()
        self._reserved[handle.group].add(handle.slot)
        self._at[tuple(handle)] = handle
        if trace is not None:
            trace.begin("first_frame")
        self._admit_queue.append(
            (handle, session, local_inputs, initial_state, spec_on, trace)
        )
        self._enqueued_at[handle] = self.frames_served
        self.metrics.count("admissions_queued")
        return handle

    def _admit_at(
        self, handle, session, local_inputs, initial_state, spec_on, trace
    ) -> None:
        """The expensive half of admission, shared by the synchronous
        path and the queue drain: build the slot's initial state
        (resolving a lazy callable), device-admit, register the match."""
        core = self.groups[handle.group]
        if trace is not None:
            trace.begin("slot_warm")
        if callable(initial_state):
            initial_state = initial_state()
        template = None
        if initial_state is None and self._slot_templates:
            # Pre-warmed path: pop a codec-round-tripped template and
            # recycle it (device-immutable — admit copies, never
            # mutates), so slot_warm is a pool pop instead of a
            # per-joiner ring build.
            template = self._slot_templates.pop()
            self._slot_templates.append(template)
            self.templates_admitted += 1
            self.metrics.count("template_admissions")
        m = None
        try:
            if trace is not None:
                trace.end("slot_warm")
                trace.begin("admit")
            core.admit(
                initial_state=initial_state,
                slot=handle.slot,
                spec_on=spec_on,
                template=template,
            )
            m = self._register(handle, session, local_inputs, spec_on)
        finally:
            if trace is not None and trace.is_open("admit"):
                trace.end("admit")
            if m is not None:
                # Pending even without a trace: admissions_completed and
                # the admission_ms series count EVERY admission.
                self._pending_first[handle] = trace
                if trace is not None and not trace.is_open("first_frame"):
                    trace.begin("first_frame")

    def retire_match(self, handle: MatchHandle) -> None:
        """Free the match's slot and forget the match: its session, its
        supervisor (whatever that re-armed after a ``DISCONNECTED``: nobody
        polls the session again), its health and its SLO history. Events
        of it that nobody drained yet stay in :meth:`drain_events`' list
        under the handle, which keeps the last location it had."""
        handle = self._held(handle)
        if handle is None:
            return  # nothing lives there (retired already)
        self.matches_retired_total += 1
        self.metrics.count("matches_retired")
        # A match retired while still in the admit queue (an abandon that
        # beat its own admission) just releases its reservation.
        for i, pending in enumerate(self._admit_queue):
            if pending[0] is handle:
                del self._admit_queue[i]
                self._reserved[handle.group].discard(handle.slot)
                del self._at[tuple(handle)]
                self._enqueued_at.pop(handle, None)
                trace = pending[5]
                if trace is not None:
                    trace.finish()
                return
        lane = self._lanes.pop(handle, None)
        if lane is not None:
            self._reserved[handle.group].discard(handle.slot)
        else:
            self.groups[handle.group].retire(handle.slot)
        self._drop(handle)

    def suspend_match(self, handle: MatchHandle) -> SlotTicket:
        """Voluntary drain: extract the match's full trajectory state as a
        :class:`SlotTicket` and free its slot. The SAME match (same
        session, same frame counters) can later :meth:`resume_match` —
        possibly into a different slot or a different server — and
        continue bitwise. Not valid while the match is on a recovery
        lane."""
        group, slot = handle
        handle = self._held(handle)
        if handle in self._lanes:
            raise RuntimeError(
                f"match {handle} is on a recovery lane; wait for "
                "readmission or retire it"
            )
        ticket = self.groups[group].extract(slot)
        if handle is not None:
            self._drop(handle)
        return ticket

    def _vacate_slo(self, handle: MatchHandle) -> None:
        """Slot SLO history is per-tenancy: a vacated slot's frozen
        window must not keep the server paging (or damn its next
        tenant), so drop it with the match."""
        flat = self._flat_slot(handle)
        self.slo.forget(flat)
        self.slo_levels.pop(flat, None)

    def resume_match(
        self,
        session,
        local_inputs: Optional[Callable[[int, int], object]] = None,
        ticket: Optional[SlotTicket] = None,
        handle=None,
    ) -> MatchHandle:
        """Readmit a suspended (or checkpoint-restored) match from its
        ticket, mid-trajectory. ``handle`` pins the exact (group, slot) —
        crash-restart re-seeds every match where it lived — and a
        :class:`MatchHandle` given there (the one the match had when it
        was suspended) is the match's handle again, so what the caller
        kept stays valid."""
        if ticket is None:
            raise ValueError("resume_match requires a ticket")
        given = handle if isinstance(handle, MatchHandle) else None
        if handle is not None:
            group, slot = handle
            if slot in self._reserved[group]:
                raise RuntimeError(f"slot {handle} is reserved")
        else:
            group = max(
                range(len(self.groups)),
                key=lambda g: (len(self._free_unreserved(g)), -g),
            )
            free = self._free_unreserved(group)
            if not free:
                raise RuntimeError("server at capacity")
            slot = free[0]
        core = self.groups[group]
        slot = core.admit(slot=slot, ticket=ticket)
        handle = given if given is not None else MatchHandle(group, slot)
        handle.group, handle.slot = group, slot
        self._register(handle, session, local_inputs, ticket.spec_on)
        return handle

    def adopt_rejoin(
        self,
        handle,
        session,
        local_inputs: Optional[Callable[[int, int], object]] = None,
        donor=None,
    ) -> MatchHandle:
        """Crash-restart path for a P2P match: reserve its slot and start
        a RECOVERING lane whose supervisor adopts a full checkpoint from
        ``donor`` (the surviving peer) via :meth:`~bevy_ggrs_tpu.session.
        supervisor.SessionSupervisor.begin_rejoin`. The match readmits at
        the reserved slot once caught up and out of its frozen-input
        window."""
        from bevy_ggrs_tpu.session.supervisor import SessionSupervisor

        if not isinstance(handle, MatchHandle):
            handle = MatchHandle(*handle)
        if self.groups[handle.group].slots[handle.slot].active:
            raise RuntimeError(f"slot {handle} is occupied")
        runner = self._make_lane_runner()
        supervisor = SessionSupervisor(
            session, runner, metrics=self.metrics, tracer=self.tracer,
            clock=self._clock,
        )
        if donor is not None:
            supervisor.begin_rejoin(donor)
        m = self._register(
            handle, session, local_inputs, True,
            initial=SlotHealth.RECOVERING, supervisor=supervisor,
        )
        self._reserved[handle.group].add(handle.slot)
        self._lanes[handle] = RecoveryLane(
            handle, session, runner, supervisor=supervisor,
            local_inputs=local_inputs, fault_frame=None,
        )
        return handle

    def _finish_admission(self, handle: MatchHandle, trace) -> None:
        """The arrival's terminal stage: its slot just rode a successful
        group dispatch. Closes the trace and feeds the admission series
        the window SLO + knee detector read. ``trace`` may be None
        (untraced admissions still count)."""
        self.admissions_completed += 1
        self.metrics.count("admissions_completed")
        since = self._enqueued_at.pop(handle, None)
        if since is not None:
            # Enqueue to the first dispatch (the frame the session turned
            # RUNNING in), in served frames: the queue and the handshake.
            self.metrics.observe("sync_frames", self.frames_served - since)
        if trace is None:
            return
        if trace.is_open("first_frame"):
            trace.end("first_frame")
        trace.finish(server_id=self.server_id, handle=handle)
        total = trace.total_ms
        self.metrics.observe("admission_ms", total)
        self.timeseries.observe("admission_ms", total)
        for stage, ms in trace.durations.items():
            self.metrics.observe(f"admission_{stage}_ms", ms)
            self.timeseries.observe(f"admission_{stage}_ms", ms)

    # -- what the hosted sessions report -----------------------------------

    def _keep_events(self, handle: MatchHandle, m: _Match, events) -> None:
        """File a match's events of this served frame under its handle,
        and keep the one clock the lifecycle's series needs: the served
        frame a peer was reported silent in (``NETWORK_INTERRUPTED``),
        until it is heard again or disconnected (series
        ``disconnect_wait_frames``: from that report to ``DISCONNECTED``).
        ``WAIT_RECOMMENDATION`` is not kept: it advises the loop that
        drives a session to skip frames, that loop is this server's, and a
        healthy server gets one from four matches in ten every frame
        (kept a frame, they outlive the young collections they used to die
        in)."""
        keep = self._match_events
        for ev in events:
            kind = ev.kind
            if kind is EventKind.WAIT_RECOMMENDATION:
                continue
            keep.append((handle, ev))
            if kind is EventKind.NETWORK_INTERRUPTED:
                m.interrupted_at = self.frames_served
            elif kind is EventKind.NETWORK_RESUMED:
                m.interrupted_at = None
            elif kind is EventKind.DISCONNECTED:
                if m.interrupted_at is not None:
                    self.metrics.observe(
                        "disconnect_wait_frames",
                        self.frames_served - m.interrupted_at,
                    )
                m.interrupted_at = None

    def drain_events(self) -> List[Tuple[MatchHandle, SessionEvent]]:
        """What the hosted sessions reported in the last served frame, each
        :class:`~bevy_ggrs_tpu.session.common.SessionEvent` with the handle
        of its match, in the order the frame met them: the events a
        match's :class:`~bevy_ggrs_tpu.session.supervisor.SessionSupervisor`
        drained (the handshake's progress, ``NETWORK_INTERRUPTED``,
        ``NETWORK_RESUMED``, ``DISCONNECTED``, the supervisor's own; not
        ``WAIT_RECOMMENDATION``, which is advice to the loop that drives
        the session: the server's), or ``session.events()`` of a hosted
        session without one. Call it
        after :meth:`run_frame`; each event is handed out once, and the
        next served frame drops what nobody took (the server holds one
        frame of them). The policy is the caller's: a match whose only
        remote player is ``DISCONNECTED`` goes on with that player's inputs
        frozen until :meth:`retire_match` (docs/serving.md "A match
        ends")."""
        with self.span("serve_match_events", events=len(self._match_events)):
            out, self._match_events = self._match_events, []
            if out:
                self.match_events_delivered_total += len(out)
                self.metrics.count("match_events_delivered", len(out))
        return out

    # -- re-packing ------------------------------------------------------

    def _move(self, handle: MatchHandle, m: _Match, group: int, slot: int):
        """Move a batched match to the free ``(group, slot)``, between two
        frames: the ``extract`` -> ``admit(ticket=...)`` pair migration
        rests on (``extract`` flushes the deferred checksum reports before
        the slot is vacated, as ``retire`` does), with every book re-keyed
        in this one step. The match keeps its ``_Match`` record — session,
        ``local_inputs``, health FSM and supervisor, whose runner facade
        is re-pointed — and the handle it was given, rewritten."""
        old_flat = self._flat_slot(handle)
        ticket = self.groups[handle.group].extract(handle.slot)
        core = self.groups[group]
        core.admit(slot=slot, ticket=ticket)
        del self._at[tuple(handle)]
        handle.group, handle.slot = group, slot
        self._at[group, slot] = handle
        flat = self._flat_slot(handle)
        self.slo.move(old_flat, flat)
        level = self.slo_levels.pop(old_flat, None)
        if level is not None:
            self.slo_levels[flat] = level
        m.fsm.slot = slot
        if m.supervisor is not None:
            m.supervisor.retarget(_SlotRunnerFacade(core, slot))

    def _repack(self, by_group, budget: int) -> List[MatchHandle]:
        """Off-peak, empty a stagger group so that the next frame skips its
        dispatch: while the batched matches fit in fewer groups than are
        hot (``by_group``: the groups this frame ticked), move up to
        ``budget`` matches — what the frame's admissions left of
        ``admit_budget`` — out of the hot group with the fewest (ties: the
        highest index) into the other hot groups' free, unreserved slots,
        busiest first as :meth:`_pick_slot`. A drain starts, and goes on,
        only while ALL the source's matches fit there, so it ends in an
        empty group and holes that do not add up to a group move nothing.
        Never moved, and a source holding one is not drained this frame: a
        reserved slot (a recovery lane's, a queued admission's), a match
        that has not ridden its first dispatch, one whose health is not
        HEALTHY. In a full server the first comparison is all that runs.
        Returns the handles of the matches moved."""
        live = sum(map(len, by_group.values()))
        if budget <= 0 or -(-live // self._per_group) >= len(by_group):
            return []
        counts = {g: core.active_count for g, core in enumerate(self.groups)}
        hot = [g for g, n in counts.items() if n]
        if len(hot) < 2:
            return []
        src = min(hot, key=lambda g: (counts[g], -g))
        movers = [
            (h, m) for h, m in self._matches.items() if h.group == src
        ]
        if self._reserved[src] or any(
            h in self._pending_first or m.fsm.state is not SlotHealth.HEALTHY
            for h, m in movers
        ):
            return []
        free = {g: self._free_unreserved(g) for g in hot if g != src}
        if sum(map(len, free.values())) < len(movers):
            return []
        movers = movers[:budget]
        with self.span("serve_repack", group=src, matches=len(movers)):
            for handle, m in movers:
                dst = min(
                    (g for g in free if free[g]),
                    key=lambda g: (len(free[g]), g),
                )
                self._move(handle, m, dst, free[dst].pop(0))
        self.metrics.count("matches_repacked", len(movers))
        self.matches_repacked_total += len(movers)
        return [handle for handle, _m in movers]

    # -- fault containment ----------------------------------------------

    def _fault(
        self,
        handle: MatchHandle,
        m: _Match,
        reason: str,
        cause: Optional[BaseException] = None,
        pending: Optional[Tuple[List[object], object]] = None,
    ) -> None:
        """Fence a sick match off the batch: quarantine its FSM, extract
        its slot into a ticket (reserving the slot index for readmission),
        and stand up a recovery lane seeded from it. The ``pending``
        request list the faulting tick dropped replays on the lane's
        singleton runner first — the escape hatch for request shapes the
        batch can't express (RestoreGameState, non-canonical bursts)."""
        core = self.groups[handle.group]
        frame = core.slots[handle.slot].frame
        m.fsm.to(SlotHealth.QUARANTINED, reason=reason, frame=frame)
        self.faults_total += 1
        self.metrics.count("slot_faults")
        self.metrics.count(
            "slot_faults",
            labels={"match_slot": handle.slot, "reason": reason},
        )
        self.tracer.instant(
            "slot_fault",
            group=handle.group,
            slot=handle.slot,
            reason=reason,
            frame=frame,
            cause=repr(cause) if cause is not None else "",
        )
        ticket = core.extract(handle.slot)
        self._reserved[handle.group].add(handle.slot)
        runner = self._make_lane_runner()
        adopt_ticket(runner, ticket)
        if m.supervisor is not None:
            m.supervisor.retarget(runner)
        self._lanes[handle] = RecoveryLane(
            handle, m.session, runner, supervisor=m.supervisor,
            local_inputs=m.local_inputs, pending=pending, fault_frame=frame,
        )

    def _readmit(self, handle: MatchHandle, lane: RecoveryLane) -> None:
        m = self._matches[handle]
        core = self.groups[handle.group]
        ticket = lane.ticket(spec_on=m.spec_on)
        core.admit(slot=handle.slot, ticket=ticket)
        self._reserved[handle.group].discard(handle.slot)
        del self._lanes[handle]
        if m.supervisor is not None:
            m.supervisor.retarget(_SlotRunnerFacade(core, handle.slot))
        m.fsm.to(SlotHealth.HEALTHY)
        self.readmissions_total += 1
        self.metrics.count("slot_readmissions")
        recovery = (
            None
            if lane.fault_frame is None
            else ticket.frame - lane.fault_frame
        )
        if recovery is not None:
            self.last_recovery_frames = recovery
            self.metrics.observe("slot_recovery_frames", recovery)
        self.tracer.instant(
            "slot_recover",
            group=handle.group,
            slot=handle.slot,
            frame=ticket.frame,
            recovery_frames=-1 if recovery is None else recovery,
        )

    def _evict(self, handle: MatchHandle, lane: RecoveryLane) -> None:
        m = self._matches[handle]
        m.fsm.to(SlotHealth.EVICTED, reason="recovery_deadline")
        del self._lanes[handle]
        self._reserved[handle.group].discard(handle.slot)
        self._drop(handle)
        self.evictions_total += 1
        self.metrics.count("slot_evictions")
        self.metrics.count(
            "slot_evictions", labels={"match_slot": handle.slot}
        )
        self.tracer.instant(
            "slot_evict",
            group=handle.group,
            slot=handle.slot,
            errors=lane.errors,
            last_error=repr(lane.last_error),
        )

    # -- SDC attestation (bevy_ggrs_tpu.integrity) ----------------------

    def _attest_sweep(self) -> None:
        """One silent-corruption sweep over every group and recovery lane:
        recompute all ring-row digests (one vmapped pass per group),
        self-heal mismatched slots in place via ``repair_slot`` (one
        no-recompile dispatch each, siblings untouched), and escalate
        anything unrepairable down the ladder — batched slot -> recovery
        lane (``_fault(reason="sdc")``), lane -> the eviction/checkpoint
        rung. A repair that lands bitwise keeps the match on the batch:
        quarantine-free."""
        from bevy_ggrs_tpu.integrity import StateFault

        for g, core in enumerate(self.groups):
            with self.span("attest", group=g):
                detected = core.attest()
            for slot, bad in detected.items():
                handle = self._at.get((g, slot))
                m = self._matches.get(handle)
                if m is None or handle in self._lanes:
                    continue
                try:
                    rep = core.repair_slot(slot, bad)
                except StateFault as e:
                    self._fault(handle, m, "sdc", cause=e)
                    continue
                self.sdc_repairs_total += 1
                self.tracer.instant(
                    "sdc_repair", group=g, slot=slot,
                    frames=rep["repair_frames"], bitwise=rep["bitwise"],
                    field=rep["first_corrupt_field"] or "",
                )
                if not rep["bitwise"]:
                    # Dispatched but did not land bitwise: the slot's
                    # timeline can no longer be trusted on the batch.
                    self._fault(handle, m, "sdc_nonbitwise")
        for handle, lane in list(self._lanes.items()):
            runner = lane.runner
            attest = getattr(runner, "attest_and_repair", None)
            if attest is None:
                continue
            try:
                with self.span(
                    "attest", group=handle.group, slot=handle.slot
                ):
                    attest()
            except StateFault as e:
                # Lane state unrepairable locally: strike the lane's error
                # ladder — persistent corruption rides it to eviction, and
                # the fleet checkpoint rung re-seats the match.
                lane.errors += 1
                lane.last_error = e
            for rec in runner.state_faults:
                self.tracer.instant(
                    "sdc_fault", group=handle.group, slot=handle.slot,
                    repaired=bool(rec.get("repaired")),
                    bitwise=bool(rec.get("bitwise")),
                    field=rec.get("field") or "",
                )
            runner.state_faults.clear()

    # -- crash-restart checkpoints --------------------------------------

    def snapshot_matches(self) -> List[Dict]:
        """Uniform per-match state records for the checkpointer: batched
        slots read their device rows, recovering matches read their lane
        runner — both carry frame, world state, full ring, and the as-used
        input-log tail."""
        out: List[Dict] = []
        for handle, m in self._matches.items():
            lane = self._lanes.get(handle)
            if lane is not None:
                r = lane.runner
                state, ring, frame = r.state, r.ring, int(r.frame)
                log = dict(r._input_log or {})
            else:
                core = self.groups[handle.group]
                s = core.slots[handle.slot]
                state = core.slot_state(handle.slot)
                ring = core.slot_ring(handle.slot)
                frame, log = int(s.frame), dict(s.input_log)
            session_state = None
            kind = "p2p"
            if m.supervisor is None:
                sd = getattr(m.session, "state_dict", None)
                if sd is not None:
                    session_state = sd()
                    kind = "synctest"
            out.append(
                {
                    "handle": handle,
                    "kind": kind,
                    "frame": frame,
                    "state": state,
                    "ring": ring,
                    "input_log": log,
                    "spec_on": m.spec_on,
                    "session_state": session_state,
                }
            )
        return out

    # -- the frame loop -------------------------------------------------

    def run_frame(self) -> None:
        """Serve one 60 Hz frame: each stagger group collects its matches'
        inputs, advances their sessions, and dispatches one batched tick —
        at its offset within the frame. The loop itself never sleeps (the
        caller owns pacing, as everywhere in this codebase); the jitter
        gauge records how far each group's dispatch drifted from its ideal
        offset given the work that preceded it.

        Fault containment: any match whose host work raises or blows the
        watchdog budget is fenced BEFORE the group dispatch; a
        :class:`SlotFault` from the dispatch itself (pre-mutation, so
        sibling slots are untouched) drops that slot and re-ticks the
        rest. Recovery lanes step after the groups, readmitting or
        evicting as they resolve.

        All of it is span ``serve_frame``; what the spans under it
        (``serve_tick``, ``checksum_sync``, ``serve_report_delivery``,
        ``admit_*``, ``serve_repack``, ``attest``, ``lane_step``) leave of
        it is the series ``serve_frame_other_ms``: the frame's own
        bookkeeping. Off-peak the admissions are followed by the re-pack
        (:meth:`_repack`)."""
        with self.span(
            "serve_frame", frame=self.frames_served, groups=len(self.groups)
        ) as sp_frame:
            self._serve_frame()
        if sp_frame is not NULL_SPAN:
            self.metrics.observe("serve_frame_other_ms", sp_frame.self_ms)

    def _serve_frame(self) -> None:
        t_wall = time.perf_counter()
        if self._match_events:
            self._match_events.clear()  # kept one served frame, not taken
        # Fast-path admission drain, TOP of frame: a pre-warmed joiner
        # (initial_state None with a slot template pooled) costs ~a
        # template pop + one small device-admit program, so it drains
        # BEFORE the group loop and rides THIS frame's dispatch —
        # first_frame loses a whole serve-frame of queue wait. Strictly
        # FIFO: the scan stops at the first admission that needs a real
        # state build, so nothing ever overtakes a slow joiner. Those
        # slow/lazy builds keep the after-dispatch drain below (a slow
        # join costs the joiner latency, never a sibling group its
        # deadline). One admit_budget bounds both drains per frame.
        admit_budget_left = self.admit_budget
        while (
            admit_budget_left > 0
            and self._admit_queue
            and self._admit_queue[0][3] is None
            and self._slot_templates
        ):
            handle, session, local_inputs, initial_state, spec_on, trace = (
                self._admit_queue.pop(0)
            )
            self._reserved[handle.group].discard(handle.slot)
            admit_budget_left -= 1
            with self.span(
                "admit_fast", group=handle.group, slot=handle.slot
            ):
                self._admit_at(
                    handle, session, local_inputs, initial_state, spec_on,
                    trace,
                )
        t0 = self._clock()
        worst_jitter = 0.0
        dispatched = 0
        by_group: Dict[int, Dict[int, Tuple[MatchHandle, _Match]]] = {}
        for handle, m in self._matches.items():
            if handle in self._lanes:
                continue  # draining/recovering: not on the batch path
            by_group.setdefault(handle.group, {})[handle.slot] = (handle, m)
        for g, core in enumerate(self.groups):
            matches = by_group.get(g)
            if not matches:
                continue
            # Deliver last tick's deferred checksum reports BEFORE any
            # session polls: a rollback's corrected re-report must land
            # before the session can send that frame's checksum to peers,
            # or a settled-but-stale value leaks out as a false desync.
            core.flush_reports()
            ideal_off = g * self.frame_ms / len(self.groups)
            actual_off = (self._clock() - t0) * 1000.0
            jitter = actual_off - ideal_off
            worst_jitter = max(worst_jitter, abs(jitter))
            self.metrics.observe("stagger_jitter", jitter)
            with self.span(
                "serve_tick", group=g, frame=self.frames_served,
                matches=len(matches),
            ) as sp_tick:
                work = {}
                # The session layer of the group: one span a group tick
                # (supervisor, poll, local inputs, advance_frame and the
                # SLO sample of every match; 64 matches a group at S=256,
                # so no span per match).
                with self.span(
                    "serve_sessions", group=g, matches=len(matches)
                ) as sp_sessions:
                    # What happens once a match is summed a group tick,
                    # one sample a series, written as the span closes
                    # (``_observe_session_sums``): the clock is read at the
                    # five boundaries of a match only while a sink listens,
                    # and the time stays inside ``ggrs/serve_sessions``.
                    timed = sp_sessions is not NULL_SPAN
                    clock = time.perf_counter
                    sup_s = poll_s = inputs_s = adv_s = slo_s = 0.0
                    # The polls' receive and send seconds, the datagrams
                    # they received and those parsed in place: the session
                    # adds them in for the caller that asks (``parts``).
                    poll_parts = [0.0, 0.0, 0, 0] if timed else None
                    # The remote endpoints those polls pumped: series
                    # ``serve_endpoints_polled`` (one far end a hosted duel,
                    # P - 1 a hosted lobby).
                    endpoints = 0
                    # The loop's crossings into the native session core, a
                    # live match: series ``serve_session_native_calls``
                    # (the core counts; None on the Python plane).
                    calls_0 = native_calls() if timed else None
                    for slot, (handle, m) in matches.items():
                        session = m.session
                        if timed:
                            t_a = clock()
                        t_m = self._clock()
                        try:
                            sup = m.supervisor
                            if sup is not None:
                                events = sup.tick(t_m)
                                if events:
                                    self._keep_events(handle, m, events)
                                if not sup.should_advance():
                                    # Lost a desync ballot (or mid-rejoin):
                                    # the state transfer needs a real runner.
                                    self._fault(
                                        handle, m, "supervisor_quarantine"
                                    )
                                    continue
                            elif m.events is not None:
                                events = m.events()
                                if events:
                                    self._keep_events(handle, m, events)
                            if timed:
                                t_b = clock()
                                sup_s += t_b - t_a
                                t_a = t_b
                            poll = getattr(
                                session, "poll_remote_clients", None
                            )
                            if poll is not None:
                                if timed:
                                    if _poll_takes_parts(session):
                                        poll(parts=poll_parts)
                                        endpoints += session.num_endpoints
                                    else:
                                        poll()
                                    t_b = clock()
                                    poll_s += t_b - t_a
                                    t_a = t_b
                                else:
                                    poll()
                            cur = getattr(session, "current_state", None)
                            if (
                                cur is not None
                                and cur() != SessionState.RUNNING
                            ):
                                # Still synchronizing: no work yet.
                                self.slot_frames_syncing_total += 1
                                continue
                            frame = core.slots[slot].frame
                            if m.local_inputs is not None:
                                for h in session.local_player_handles():
                                    bits = m.local_inputs(frame, h)
                                    if sup is not None:
                                        bits = sup.input_for(h, bits)
                                    session.add_local_input(h, bits)
                            if timed:
                                t_b = clock()
                                inputs_s += t_b - t_a
                                t_a = t_b
                            item = _advance(session)
                            conf = getattr(session, "confirmed_frame", None)
                            confirmed = conf() if conf is not None else None
                            if timed:
                                t_b = clock()
                                adv_s += t_b - t_a
                                t_a = t_b
                        except PredictionThreshold:
                            # Back-pressure, not a fault: a withheld frame.
                            # Counted, and sampled by the SLO as any tick
                            # (its own host time, nothing rolled back).
                            self.frames_withheld_total += 1
                            self.metrics.count("frames_withheld")
                            if m.interrupted_at is not None:
                                # Waiting out a silent peer, not a burst.
                                self.slot_frames_stalled_total += 1
                            self.slo.observe_tick(
                                self._flat_slot(handle),
                                deadline_ok=(self._clock() - t_m) * 1000.0
                                <= self.watchdog_budget_ms,
                                rollback_depth=0,
                            )
                            continue
                        except SlotFault as f:
                            self._fault(handle, m, f.reason, cause=f)
                            continue
                        except Exception as e:
                            self._fault(handle, m, "session_error", cause=e)
                            continue
                        elapsed_ms = (self._clock() - t_m) * 1000.0
                        # SLO sample: deadline hit + rollback depth (every
                        # frame past the first of a canonical burst is a
                        # resimulated frame).
                        depth = max(0, _advances(item) - 1)
                        self.slo.observe_tick(
                            self._flat_slot(handle),
                            deadline_ok=elapsed_ms <= self.watchdog_budget_ms,
                            rollback_depth=depth,
                        )
                        if elapsed_ms > self.watchdog_budget_ms:
                            if m.fsm.strike(frame):
                                # Deadline expiry: the requests are already in
                                # hand — they ride to the lane so session and
                                # runner frame counters stay converged.
                                self._fault(
                                    handle, m, "watchdog_timeout",
                                    pending=(_requests(item), session),
                                )
                                continue
                        else:
                            m.fsm.clear()
                        work[slot] = (item, confirmed, session)
                        if timed:
                            slo_s += clock() - t_a
                    if calls_0 is not None:
                        self.metrics.observe(
                            "serve_session_native_calls",
                            (native_calls() - calls_0) / len(matches),
                        )
                if timed:
                    self.metrics.observe("serve_endpoints_polled", endpoints)
                    self._observe_session_sums(sp_sessions.ms, poll_parts, {
                        "serve_supervisor_ms": sup_s,
                        "serve_poll_ms": poll_s,
                        "serve_local_inputs_ms": inputs_s,
                        "serve_advance_ms": adv_s,
                        "serve_slo_ms": slo_s,
                    })
                while work:
                    try:
                        core.tick(work)
                        dispatched += 1
                        break
                    except SlotFault as f:
                        item, _conf, session = work.pop(f.slot)
                        handle, m = matches[f.slot]
                        self._fault(
                            handle, m, f.reason,
                            cause=f, pending=(_requests(item), session),
                        )
                # Any slot that just rode its first successful dispatch
                # completes its admission trace: first_frame_served.
                if work and self._pending_first:
                    for slot in work:
                        h = matches[slot][0]
                        if h in self._pending_first:
                            self._finish_admission(
                                h, self._pending_first.pop(h)
                            )
            if sp_tick is not NULL_SPAN:
                # What ``serve_sessions``, ``serve_segment`` and the rounds
                # leave of the group's tick.
                self.metrics.observe("serve_tick_other_ms", sp_tick.self_ms)
        # Slow-path admission drain: immediately AFTER every group issued
        # its dispatch — the tick programs are still in flight on device
        # (dispatch is async), so a joiner's session warm + state build
        # + device-admit enqueue overlaps dispatch N instead of
        # serializing behind the attest/lane sweeps (which block on
        # device results). A slow join still costs the joiner latency,
        # never a sibling group its deadline. Shares the frame's
        # admit_budget with the fast-path drain at the top of the frame.
        # Freshly admitted slots are attest-safe before their first
        # dispatch: their ring frames are all -1 and attest_ring masks
        # unoccupied rows.
        for _ in range(min(admit_budget_left, len(self._admit_queue))):
            handle, session, local_inputs, initial_state, spec_on, trace = (
                self._admit_queue.pop(0)
            )
            self._reserved[handle.group].discard(handle.slot)
            admit_budget_left -= 1
            with self.span(
                "admit_drain", group=handle.group, slot=handle.slot
            ):
                self._admit_at(
                    handle, session, local_inputs, initial_state, spec_on,
                    trace,
                )
        # The groups that dispatched this frame, and the re-pack that makes
        # them fewer: admissions first, with what is left of their budget.
        self.metrics.observe("serve_hot_groups", dispatched)
        self.repacked = self._repack(by_group, admit_budget_left)
        # Periodic SDC attestation sweep, off the hot path like the lanes:
        # detection within attest_interval frames, self-healing in place.
        if (
            self.attest_interval is not None
            and self.frames_served % self.attest_interval == 0
        ):
            self._attest_sweep()
        # Recovery lanes: off the hot path, after every group dispatched.
        now = self._clock()
        # Group head frames — a lane's recovery debt is how far it trails
        # the most-advanced batched slot of its group.
        heads: Dict[int, int] = {}
        for g, core in enumerate(self.groups):
            frames = [
                s.frame for s in core.slots if getattr(s, "active", False)
            ]
            if frames:
                heads[g] = max(frames)
        for handle, lane in list(self._lanes.items()):
            m = self._matches.get(handle)
            if m is None:
                continue
            with self.span(
                "lane_step", group=handle.group, slot=handle.slot
            ):
                lane.step(now)
            if m.fsm.state is SlotHealth.QUARANTINED and lane.advancing:
                m.fsm.to(SlotHealth.RECOVERING)
            debt = max(
                0,
                heads.get(handle.group, int(lane.runner.frame))
                - int(lane.runner.frame),
            )
            self.slo.observe_tick(
                self._flat_slot(handle),
                deadline_ok=True,  # lanes are off the deadline path
                recovery_debt=debt,
                quarantined=m.fsm.state is SlotHealth.QUARANTINED,
            )
            if lane.ready and m.fsm.state is SlotHealth.RECOVERING:
                self._readmit(handle, lane)
            elif (
                lane.frames_stepped > self.recovery_deadline_frames
                or lane.errors > self.lane_error_limit
            ):
                self._evict(handle, lane)
        self.last_stagger_jitter_ms = worst_jitter
        self.frames_served += 1
        self.metrics.count("frames_served")
        if self.timeseries.enabled:
            # perf_counter, not self._clock: frame cost is real host work
            # even when the serving loop runs on a virtual clock.
            self.timeseries.observe(
                "frame_ms", (time.perf_counter() - t_wall) * 1000.0
            )
            self.timeseries.observe("stagger_jitter_ms", worst_jitter)
            self.timeseries.observe("slots_active", self.slots_active)
            self.timeseries.observe(
                "admit_queue_depth", len(self._admit_queue)
            )
            if self.ledger.enabled:
                # Incremental ledger drain into the live windows: one
                # spec_spill sample per rollback (0 = fully absorbed —
                # the WindowSLO objective), per-player blame streams,
                # and the hit-rank distribution.
                for e in self.ledger.tail(self._ledger_seq):
                    self._ledger_seq = e["seq"] + 1
                    self.timeseries.observe(
                        "spec_spill",
                        0.0 if e["outcome"] == "full" else 1.0,
                    )
                    if e.get("rank") is not None:
                        self.timeseries.observe(
                            "spec_hit_rank", float(e["rank"])
                        )
                    bp = e.get("blame_player")
                    if bp is not None:
                        self.timeseries.observe(f"spec_blame_p{bp}", 1.0)
                disp = self.ledger.spec_frames_dispatched
                if disp:
                    self.timeseries.observe(
                        "spec_waste_ratio",
                        max(
                            0.0,
                            1.0
                            - self.ledger.frames_recovered_total / disp,
                        ),
                    )
        if self.frames_served % self.slo_export_interval == 0:
            self.slo_levels = self.slo.export()
            for handle, m in self._matches.items():
                lvl = self.slo_levels.get(self._flat_slot(handle))
                if lvl is not None:
                    m.fsm.slo_signal(lvl, frame=self.frames_served)
            if self.timeseries.enabled:
                self.front_door_levels = self.window_slo.export()
        if (
            self.fleet_socket is not None
            and self.fleet_addr is not None
            and self.frames_served % self.heartbeat_interval == 0
        ):
            from bevy_ggrs_tpu.session import protocol as _proto

            self.fleet_socket.send_to(
                _proto.encode(self.heartbeat()), self.fleet_addr
            )
            self.heartbeats_sent += 1
            self.metrics.count("fleet_heartbeats_sent")
        if self.checkpointer is not None:
            if self.repacked:
                # A checkpoint names a match by where it lives: one taken
                # before a move would restore it under a place it left.
                self.checkpointer.save(self)
            else:
                self.checkpointer.maybe_save(self)

    def _observe_session_sums(
        self, span_ms: float, poll_parts: List[float],
        sums_s: Dict[str, float],
    ) -> None:
        """One sample a group tick of each sum of span ``serve_sessions``
        (the seconds one kind of work took over every match of the group,
        under the series key given), of the polls' two sides, of the share
        of the datagrams they received that were parsed in place
        (``serve_poll_direct_share``, where any arrived), and of what
        the sums leave of the span (``serve_sessions_other_ms``: the loop
        itself, a match that left it early)."""
        observe = self.metrics.observe
        for key, seconds in sums_s.items():
            observe(key, seconds * 1000.0)
        observe("serve_poll_recv_ms", poll_parts[0] * 1000.0)
        observe("serve_poll_send_ms", poll_parts[1] * 1000.0)
        if poll_parts[2]:
            observe(
                "serve_poll_direct_share",
                100.0 * poll_parts[3] / poll_parts[2],
            )
        observe(
            "serve_sessions_other_ms",
            span_ms - sum(sums_s.values()) * 1000.0,
        )

    # -- telemetry export -----------------------------------------------

    def export_telemetry(
        self, directory: Optional[str] = None, prefix: str = "server"
    ) -> Optional[Dict[str, str]]:
        """Dump the server's telemetry set under ``directory`` (default:
        the ``trace_dir`` it was built with): Perfetto trace (when the
        tracer is enabled), Prometheus exposition, SLO snapshot JSON, and
        the self-contained HTML ops report. Returns {artifact: path}, or
        None when no directory is configured."""
        import json as _json
        import os as _os

        from bevy_ggrs_tpu.obs.prom import export_prometheus
        from bevy_ggrs_tpu.obs.report import build_report

        directory = directory if directory is not None else self.trace_dir
        if directory is None:
            return None
        _os.makedirs(directory, exist_ok=True)
        out: Dict[str, str] = {}
        if getattr(self.tracer, "enabled", False):
            p = _os.path.join(directory, f"{prefix}_trace.json")
            self.tracer.export_perfetto(p)
            out["trace"] = p
        if getattr(self.profiler, "enabled", False):
            p = _os.path.join(directory, f"{prefix}_profile.folded")
            self.profiler.export_folded(p)
            out["profile_folded"] = p
            p = _os.path.join(directory, f"{prefix}_profile_counters.json")
            self.profiler.export_perfetto(p)
            out["profile_counters"] = p
            p = _os.path.join(directory, f"{prefix}_profile.json")
            self.profiler.export_report_json(p)
            out["profile"] = p
        p = _os.path.join(directory, f"{prefix}_metrics.prom")
        export_prometheus(
            self.metrics,
            path=p,
            timeseries=(
                self.timeseries if self.timeseries.enabled else None
            ),
            ledger=self.ledger if self.ledger.enabled else None,
        )
        out["metrics"] = p
        if self.ledger.enabled:
            p = _os.path.join(directory, f"{prefix}_spec_ledger.jsonl")
            self.ledger.export_jsonl(p)
            out["spec_ledger"] = p
        p = _os.path.join(directory, f"{prefix}_slo.json")
        with open(p, "w") as f:
            _json.dump(self.slo.snapshot(), f, indent=2)
        out["slo"] = p
        if self.timeseries.enabled:
            p = _os.path.join(directory, f"{prefix}_front_door_slo.json")
            with open(p, "w") as f:
                _json.dump(self.window_slo.snapshot(), f, indent=2)
            out["front_door_slo"] = p
        p = _os.path.join(directory, f"{prefix}_report.html")
        build_report(
            p,
            title=f"{prefix} ops report",
            slo=self.slo,
            tracers={prefix: self.tracer},
            metrics=self.metrics,
            timeseries=(
                self.timeseries if self.timeseries.enabled else None
            ),
            ledger=self.ledger if self.ledger.enabled else None,
            profile=(
                self.profiler
                if getattr(self.profiler, "enabled", False) else None
            ),
            notes=(
                f"frames_served={self.frames_served} "
                f"faults={self.faults_total} "
                f"readmissions={self.readmissions_total} "
                f"evictions={self.evictions_total}"
            ),
        )
        out["report"] = p
        return out
