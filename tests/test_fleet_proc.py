"""Subprocess fleet contracts: the autopilot's claims proven against
real process boundaries — separate JAX runtimes, real UDP datagrams,
real SIGKILL.

- Smoke (tier-1): one supervised child boots warm from the shared XLA
  disk cache, admits matches over the stdin/stdout control plane, beats
  over real UDP, refuses admissions while draining, and shuts down
  gracefully.
- Elastic soak (slow): TrafficPlan-driven arrivals onto an
  autopilot-managed subprocess fleet. One full elasticity arc: high
  watermark -> scale-up to N=3; armed burn window on one child -> SLO
  pages -> preemptive migrations land while the source's watchdog fence
  count is still ZERO; traffic drop -> low watermark ->
  drain-pack-retire. Zero matches lost, zero faults/evictions (synctest
  check-distance makes any desync a fault), zero post-steady-state
  recompiles fleet-wide, and the autopilot ledger replays IDENTICAL
  offline.
- Crash (slow): SIGKILL a child mid-serve; heartbeat silence past the
  timeout marks it dead; the parent re-packs its on-disk checkpoint and
  ships every match to the survivor over the ordinary migration wire.
"""

import os
import time

import pytest

from bevy_ggrs_tpu.fleet.autopilot import (
    AutopilotConfig,
    FleetAutopilot,
    verify_ledger,
)
from bevy_ggrs_tpu.fleet.proc import ProcFleet
from bevy_ggrs_tpu.fleet.traffic import TrafficPlan

BASE = {
    "fps": 0,  # free-run: soak wall time is compute-bound, not paced
    "heartbeat_interval": 8,
    "status_interval": 20,
    "checkpoint_interval": 40,
}


def pump_until(fleet, pred, timeout=60.0, tick=None, msg=""):
    deadline = time.time() + timeout
    while time.time() < deadline:
        fleet.pump()
        if tick is not None:
            tick()
        if pred():
            return
        time.sleep(0.03)
    pytest.fail(f"timed out waiting for: {msg or pred}")


def match_frames(fleet, sid):
    st = fleet.members[sid].status or {}
    return {int(k): v for k, v in st.get("matches", {}).items()}


# ---------------------------------------------------------------------------
# Tier-1 smoke: one child, full control-plane lifecycle
# ---------------------------------------------------------------------------


def test_subprocess_server_lifecycle(tmp_path):
    fleet = ProcFleet(str(tmp_path), base_config=BASE)
    try:
        sid = fleet.spawn_server(wait_ready=True)
        m = fleet.members[sid]
        assert m.mig_addr is not None and m.info is not None
        assert fleet.scale_up_s and fleet.scale_up_s[0] > 0
        # Admissions over the control plane; real heartbeats carry the
        # occupancy back.
        assert fleet.admit(11) == sid
        assert fleet.admit(12) == sid
        pump_until(
            fleet,
            lambda: match_frames(fleet, sid).get(11, 0) > 20
            and fleet.members[sid].info.slots_active == 2,
            msg="admitted matches serving",
        )
        assert 11 in fleet.handles and 12 in fleet.handles
        st = fleet.members[sid].status
        assert st["faults"] == 0 and st["evictions"] == 0
        assert st["quarantined"] == 0
        # Draining: the child refuses new admissions; the parent unbooks.
        assert fleet.set_draining(sid)
        fleet.members[sid].process.send(cmd="admit", match=13)
        pump_until(
            fleet,
            lambda: fleet.admissions_rejected >= 1,
            msg="draining child refuses admission",
        )
        assert 13 not in fleet.placements()
        rows = {r["server_id"]: r for r in fleet.fleet_rows()}
        assert rows[sid]["draining"] is True and rows[sid]["matches"] == 2
    finally:
        fleet.close()
    assert not fleet.members[0].process.alive()


def test_parent_book_follows_a_repacked_match(tmp_path):
    """The child's server re-packs its survivors at a frame's end; the
    child reports each move (event ``moved``), so the parent's ``(group,
    slot)`` book, which finds a match's checkpoint record, follows."""
    fleet = ProcFleet(str(tmp_path), base_config=BASE)
    try:
        sid = fleet.spawn_server(wait_ready=True)
        for mid in (21, 22, 23, 24):
            assert fleet.admit(mid) == sid
        pump_until(fleet, lambda: len(fleet.handles) == 4,
                   msg="four matches placed")
        assert [fleet.handles[m] for m in (21, 22, 23, 24)] == [
            (0, 0), (0, 1), (1, 0), (1, 1)]
        # One survivor a group: the second group's moves into the first.
        assert fleet.retire_match(22) and fleet.retire_match(24)
        pump_until(fleet, lambda: fleet.handles.get(23) == (0, 1),
                   msg="the moved match's place reaches the parent")
        assert fleet.handles == {21: (0, 0), 23: (0, 1)}
        before = match_frames(fleet, sid).get(23, 0)
        pump_until(
            fleet,
            lambda: match_frames(fleet, sid).get(23, 0) > before + 20
            and fleet.members[sid].info.slots_active == 2,
            msg="the moved match keeps serving",
        )
        st = fleet.members[sid].status
        assert st["faults"] == 0 and st["evictions"] == 0
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# The elastic autopilot soak
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_elastic_autopilot_soak(tmp_path):
    obs_root = os.environ.get("GGRS_OBS_DIR")
    obs_dir = os.path.join(obs_root or str(tmp_path), "fleet_proc_soak")
    os.makedirs(obs_dir, exist_ok=True)
    # Generous heartbeat timeout: a child blocks its loop for the
    # session-jit load on its FIRST admission, and a false death here
    # would trigger a failover mid-soak (the end-of-soak failovers==0
    # assert would catch it, confusingly).
    fleet = ProcFleet(
        str(tmp_path / "fleet"),
        base_config=BASE,
        heartbeat_timeout=8.0,
        obs_dir=obs_dir,
    )
    cfg = AutopilotConfig(
        high_watermark=0.8,
        low_watermark=0.3,
        confirm_beats=3,
        preempt_confirm=2,
        preempt_batch=1,
        cooldown_scale_ticks=40,
        cooldown_preempt_ticks=20,
        min_servers=2,
        max_servers=4,
    )
    ap = FleetAutopilot(fleet, config=cfg)
    tickbox = {"t": 0}

    def tick():
        ap.step(tickbox["t"])
        tickbox["t"] += 1
        for dead in fleet.check():
            fleet.failover(dead, preferred=ap.backups)

    try:
        for _ in range(2):
            fleet.spawn_server(wait_ready=True)
        assert sorted(fleet.members) == [0, 1]

        # Phase 1 — TrafficPlan arrivals (compressed onto ~4s of wall
        # time) push occupancy over the high watermark (7 of 8 slots):
        # the policy must scale up to N=3. Heartbeat-lagged placement
        # can bounce an admission off a just-filled server
        # (admit_failed unbooks it), so reconcile until every arrival
        # is genuinely admitted somewhere.
        plan = TrafficPlan.generate(
            seed=23, duration=10.0, match_rate=3.0, num_players=2
        )
        arrivals = plan.arrivals()[:7]
        assert len(arrivals) == 7
        t0 = time.time()
        horizon = max(a.at for a in arrivals) or 1.0
        pending = list(arrivals)
        while pending:
            fleet.pump()
            tick()
            elapsed = (time.time() - t0) * (horizon / 4.0)
            while pending and pending[0].at <= elapsed:
                fleet.admit(pending.pop(0).match_id)
            time.sleep(0.03)

        def all_admitted():
            missing = [
                a.match_id
                for a in arrivals
                if a.match_id not in fleet.handles
            ]
            for mid in missing:
                if mid not in fleet.book:
                    fleet.admit(mid)
            return not missing

        pump_until(
            fleet, all_admitted, timeout=60, tick=tick,
            msg="all arrivals admitted",
        )
        pump_until(
            fleet,
            lambda: len(fleet.samples()) == 3,
            timeout=120,
            tick=tick,
            msg="autopilot scale-up to N=3",
        )
        assert ap.counts.get("scale_up", 0) >= 1
        new_sid = max(fleet.members)
        assert new_sid == 2
        assert len(fleet.scale_up_s) == 3

        # Phase 1b — steady state: warm the new server's serving path
        # with real matches, then re-baseline every child's compile
        # counter. Everything after this point must be recompile-free.
        for mid in (100, 101):
            assert fleet.admit(mid, new_sid) == new_sid
        pump_until(
            fleet,
            lambda: match_frames(fleet, new_sid).get(100, 0) > 20,
            tick=tick,
            msg="new server serving admitted matches",
        )
        for m in fleet.members.values():
            m.process.send(cmd="rebase_compiles")

        # Phase 2 — burn window on server 0: SLO pages, the watchdog
        # never fences (1-in-3 misses are never consecutive), and the
        # autopilot evacuates matches BEFORE any fence could land.
        donor = 0
        hosted = [mid for mid, s in fleet.placements().items() if s == donor]
        assert hosted, "traffic should have landed matches on server 0"
        fleet.members[donor].process.send(
            cmd="hiccup", every=3, ms=60.0, frames=400
        )
        migrated_before = fleet.migrations_completed
        pump_until(
            fleet,
            lambda: any(
                e["event"] == "migrated" and e["src"] == donor
                for e in fleet.events
            ),
            timeout=120,
            tick=tick,
            msg="burn-triggered preemptive migration completing",
        )
        assert ap.counts.get("preempt_migrate", 0) >= 1
        # The policy acted on observed pages...
        assert any(
            rec["observation"]["servers"].get(str(donor), {}).get("pages", 0)
            >= 1
            for rec in ap.ledger
        )
        # ...and the preemption landed while the source was still
        # clean: zero watchdog fences, zero quarantined slots.
        assert fleet.members[donor].info.quarantined == 0
        st = fleet.members[donor].status
        assert st["faults"] == 0 and st["evictions"] == 0
        assert fleet.migrations_completed > migrated_before
        assert fleet.matches_lost == 0

        # Let the burn window close so pages clear before scale-down.
        pump_until(
            fleet,
            lambda: fleet.members[donor].info.pages == 0,
            timeout=120,
            tick=tick,
            msg="pages clearing after burn window",
        )

        # Phase 3 — traffic drop. First guarantee every server hosts at
        # least one match (preemption may have fully evacuated the
        # donor), so whichever member the policy drains must PACK
        # before it can retire. Then abandon everything else:
        # occupancy falls under the low watermark and the policy
        # drain-pack-retires the emptiest member.
        keep = {}
        for mid, sid in sorted(fleet.placements().items()):
            keep.setdefault(sid, mid)
        for sid in sorted(fleet.samples()):
            if sid not in keep:
                assert fleet.admit(200 + sid, sid) == sid
                keep[sid] = 200 + sid
        pump_until(
            fleet,
            lambda: all(m in fleet.handles for m in keep.values()),
            tick=tick,
            msg="fill-in admissions serving",
        )
        for mid in sorted(fleet.placements()):
            if mid not in keep.values():
                assert fleet.retire_match(mid)
        pump_until(
            fleet,
            lambda: any(e["event"] == "retired" for e in fleet.events),
            timeout=120,
            tick=tick,
            msg="drain-pack-retire completing",
        )
        assert ap.counts.get("scale_down", 0) >= 1
        assert ap.counts.get("pack_migrate", 0) >= 1
        assert ap.counts.get("retire", 0) >= 1
        victim = next(
            e["server"] for e in fleet.events if e["event"] == "retired"
        )
        pump_until(
            fleet,
            lambda: not fleet.members[victim].process.alive(),
            tick=tick,
            msg="retired child exiting",
        )
        assert len(fleet.samples()) == 2
        # Every surviving match kept serving through the whole arc.
        assert fleet.matches_lost == 0
        assert fleet.failovers == 0  # no false heartbeat deaths either
        survivors = set(fleet.placements().values())
        assert victim not in survivors
        assert all(fleet.members[s].alive for s in survivors)

        # Fleet-wide churn gate: zero recompiles since steady state —
        # every migration landed in the destination's warm jit cache.
        frames_before = {
            sid: (m.status or {}).get("frames", 0)
            for sid, m in fleet.members.items()
            if m.process.alive()
        }
        pump_until(
            fleet,
            lambda: all(
                (fleet.members[sid].status or {}).get("frames", 0)
                > frames_before[sid]
                for sid in frames_before
            ),
            tick=tick,
            msg="fresh post-arc status from survivors",
        )
        for sid, m in fleet.members.items():
            if m.process.alive() and m.status is not None:
                assert m.status["compiles"] == 0, (
                    f"server {sid} recompiled after steady state"
                )
                assert m.status["faults"] == 0
                assert m.status["evictions"] == 0

        # The decision ledger replays IDENTICAL offline.
        ledger_path = os.path.join(obs_dir, "autopilot_ledger.jsonl")
        ap.export_jsonl(ledger_path)
        ok, ticks = verify_ledger(ledger_path)
        assert ok and ticks == len(ap.ledger)
    finally:
        fleet.close()

    # Post-shutdown: every child exported telemetry; one merged
    # cross-process fleet timeline.
    merged_path = os.path.join(obs_dir, "fleet_proc_merged_trace.json")
    merged = fleet.merge_observability(merged_path)
    assert merged is not None and os.path.exists(merged_path)
    pids = {
        ev.get("pid")
        for ev in merged.get("traceEvents", [])
        if ev.get("ph") != "M"
    }
    assert len(pids) >= 2, "merged timeline must span multiple processes"
    ledgers = [
        f for f in os.listdir(obs_dir) if f.endswith("_spec_ledger.jsonl")
    ]
    assert ledgers, "per-server speculation ledgers exported"


# ---------------------------------------------------------------------------
# Crash: SIGKILL -> heartbeat timeout -> checkpoint failover
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sigkill_heartbeat_timeout_failover(tmp_path):
    fleet = ProcFleet(
        str(tmp_path), base_config=BASE, heartbeat_timeout=2.0
    )
    try:
        a = fleet.spawn_server(wait_ready=True)
        b = fleet.spawn_server(wait_ready=True)
        mids = [31, 32, 33]
        for mid in mids:
            assert fleet.admit(mid, a) == a
        pump_until(
            fleet,
            lambda: all(
                match_frames(fleet, a).get(m, 0) > 0 for m in mids
            ),
            msg="matches serving on the doomed server",
        )
        # Outlive two checkpoint intervals past the last admission so
        # the on-disk fleet checkpoint covers every match.
        base_frames = (fleet.members[a].status or {}).get("frames", 0)
        pump_until(
            fleet,
            lambda: (fleet.members[a].status or {}).get("frames", 0)
            > base_frames + 2 * BASE["checkpoint_interval"],
            msg="checkpoint coverage",
        )
        frames_at_kill = match_frames(fleet, a)

        fleet.members[a].process.kill()
        t0 = time.time()
        dead = []

        def detect():
            dead.extend(fleet.check())
            return bool(dead)

        pump_until(
            fleet, detect, timeout=15,
            msg="heartbeat-timeout death detection",
        )
        detect_s = time.time() - t0
        assert dead == [a]
        assert detect_s < fleet.heartbeat_timeout + 5.0

        initiated = fleet.failover(a, preferred={m: b for m in mids})
        assert sorted(m for m, _ in initiated) == mids
        assert all(dst == b for _, dst in initiated)
        pump_until(
            fleet,
            lambda: fleet.matches_recovered + fleet.matches_lost
            >= len(mids),
            msg="failover transfers settling",
        )
        assert fleet.matches_lost == 0
        assert fleet.matches_recovered == len(mids)
        assert all(fleet.book[m] == b for m in mids)
        # Recovered matches resume from the checkpoint (at or before the
        # kill frame) and keep serving past it; synctest check-distance
        # would fault any desync in the restored state.
        pump_until(
            fleet,
            lambda: all(
                match_frames(fleet, b).get(m, 0)
                > frames_at_kill.get(m, 0)
                for m in mids
            ),
            msg="recovered matches outrunning their kill frame",
        )
        st = fleet.members[b].status
        assert st["faults"] == 0 and st["evictions"] == 0
    finally:
        fleet.close()
