"""TrafficPlan + Matchmaker contracts (fleet/traffic.py):

- Seeded determinism: same (seed, rates) -> the same plan, every time.
- JSON roundtrip: to_json -> from_json is identity, and the re-serialized
  text is byte-identical (the replay artifact a bench run commits).
- RNG-stream discipline: arrivals draw LAST, so sweeping ``match_rate``
  (the saturation ladder's knob) leaves the spectate/abandon schedules a
  seed produces byte-identical; per-match attributes come from derived
  per-match streams and can't perturb any schedule.
- The Matchmaker applies a plan open-loop against a real fleet: every
  admitted arrival's :class:`AdmissionTrace` completes all five stages,
  abandons retire live matches, and a full fleet drops (never retries)
  arrivals — the drop is the saturation signal.
"""

import numpy as np
import pytest

from bevy_ggrs_tpu.fleet import (
    FleetBalancer,
    MatchAbandon,
    MatchArrival,
    Matchmaker,
    SpectatorSubscribe,
    TrafficPlan,
)
from bevy_ggrs_tpu.serve import ADMISSION_STAGES
from bevy_ggrs_tpu.transport.loopback import LoopbackNetwork
from bevy_ggrs_tpu.utils.metrics import Metrics
from tests.test_serve_faults import inputs_for, make_server, make_synctest

FPS_DT = 1.0 / 60.0

GEN = dict(duration=10.0, match_rate=1.5, spectate_rate=0.8,
           abandon_rate=0.4, num_players=2)


# ---------------------------------------------------------------------------
# Plan generation: determinism + serialization
# ---------------------------------------------------------------------------


def test_generate_is_seed_deterministic():
    a = TrafficPlan.generate(seed=11, **GEN)
    b = TrafficPlan.generate(seed=11, **GEN)
    assert a == b
    c = TrafficPlan.generate(seed=12, **GEN)
    assert a != c


def test_json_roundtrip_is_identity_and_byte_stable():
    plan = TrafficPlan.generate(seed=5, **GEN)
    text = plan.to_json()
    back = TrafficPlan.from_json(text)
    assert back == plan
    assert back.to_json() == text  # byte-identical replay artifact
    # Tuples survive the trip (join_delays is the list-normalized field).
    arr = back.arrivals()[0]
    assert isinstance(arr.join_delays, tuple)


def test_arrivals_draw_last_so_rate_sweeps_keep_other_streams():
    """The ladder's whole premise: stepping match_rate must not reshuffle
    the spectate/abandon schedules a seed produces."""
    lo = TrafficPlan.generate(seed=23, **{**GEN, "match_rate": 0.5})
    hi = TrafficPlan.generate(seed=23, **{**GEN, "match_rate": 6.0})
    assert lo.spectates() == hi.spectates()
    assert lo.abandons() == hi.abandons()
    assert len(hi.arrivals()) > len(lo.arrivals())


def test_per_match_draws_never_touch_the_main_stream():
    """Changing per-match shape (num_players) must leave every event
    *time* identical — join delays come from derived per-match RNGs."""
    p2 = TrafficPlan.generate(seed=31, **{**GEN, "num_players": 2})
    p4 = TrafficPlan.generate(seed=31, **{**GEN, "num_players": 4})
    assert [a.at for a in p2.arrivals()] == [a.at for a in p4.arrivals()]
    assert p2.spectates() == p4.spectates()
    assert p2.abandons() == p4.abandons()
    assert all(len(a.join_delays) == 4 for a in p4.arrivals())


def test_poisson_rates_are_calibrated():
    plan = TrafficPlan.generate(
        seed=3, duration=400.0, match_rate=2.0, spectate_rate=1.0,
    )
    n = len(plan.arrivals())
    assert 600 <= n <= 1000  # 2.0/s * 400 s = 800 expected
    assert all(0.0 <= a.at < 400.0 for a in plan.arrivals())


def test_zero_rates_and_horizon():
    plan = TrafficPlan.generate(seed=1, duration=5.0, match_rate=0.0)
    assert plan.events == ()
    assert plan.horizon() == 0.0
    plan = TrafficPlan.generate(seed=1, duration=5.0, match_rate=3.0)
    assert plan.horizon() >= max(a.at for a in plan.arrivals())


# ---------------------------------------------------------------------------
# Matchmaker: open-loop application against a live fleet
# ---------------------------------------------------------------------------


def make_traffic_fleet(net, servers=2, **server_kw):
    bal = FleetBalancer(metrics=Metrics())
    out = []
    for k in range(servers):
        srv = make_server(
            clock=lambda: net.now, server_id=k, metrics=Metrics(),
            **server_kw,
        )
        bal.register(k, srv)
        out.append(srv)
    return bal, out


def run_traffic(net, mm, servers, frames):
    for _ in range(frames):
        net.advance(FPS_DT)
        mm.pump(net.now)
        for srv in servers:
            srv.run_frame()


def test_matchmaker_admits_with_complete_stage_traces():
    net = LoopbackNetwork()
    bal, servers = make_traffic_fleet(net)
    plan = TrafficPlan.generate(
        seed=3, duration=1.5, match_rate=4.0, spectate_rate=2.0,
        abandon_rate=1.0,
    )
    mm = Matchmaker(
        bal, plan,
        make_session=lambda a: make_synctest(),
        make_inputs=lambda a: inputs_for(a.input_seed % 32),
        clock=lambda: net.now, metrics=Metrics(),
    )
    run_traffic(net, mm, servers, 200)
    assert mm.drained
    assert mm.arrivals_seen == len(plan.arrivals())
    assert mm.admissions_started > 0
    assert mm.admissions_rejected == 0
    # Every admission that survived to serving has all five stages.
    served = [
        t for mid, t in mm.traces.items() if mid in mm.live
    ]
    assert served
    for t in served:
        assert t.complete, t.snapshot()
        assert set(t.durations) == set(ADMISSION_STAGES)
        assert t.server_id in (0, 1)
    # The trace is born at matchmaking COMPLETION: the plan's join-delay
    # wait is open-loop schedule, not admission latency. On the virtual
    # clock, matchmake (session/input assembly inside one pump) is
    # instantaneous, regardless of how long the arrival waited.
    for mid, t in mm.traces.items():
        if t.complete:
            assert t.durations["matchmake"] <= 1e-6
    # Abandons retired real matches; placements were cleaned up.
    assert mm.abandons_applied > 0
    for mid in mm.live:
        assert mid in bal.placements
    assert len(bal.placements) == len(mm.live)


def test_matchmaker_replay_is_deterministic():
    """Same plan, same fleet shape -> identical admission/placement
    history (the replayability contract chaos plans established)."""

    def run():
        net = LoopbackNetwork()
        bal, servers = make_traffic_fleet(net)
        plan = TrafficPlan.generate(
            seed=9, duration=1.2, match_rate=5.0, abandon_rate=1.0,
        )
        mm = Matchmaker(
            bal, plan,
            make_session=lambda a: make_synctest(),
            make_inputs=lambda a: inputs_for(a.input_seed % 32),
            clock=lambda: net.now, metrics=Metrics(),
        )
        run_traffic(net, mm, servers, 150)
        return (
            sorted(mm.live.items()),
            mm.admissions_started,
            mm.abandons_applied,
            sorted(
                (mid, tuple(sorted(t.durations)))
                for mid, t in mm.traces.items()
            ),
        )

    assert run() == run()


def test_full_fleet_drops_arrivals_open_loop():
    """Open-loop saturation: a fleet with zero free slots drops the
    arrival (counted), never blocks or retries — the drop rate IS the
    measurement."""
    net = LoopbackNetwork()
    bal, servers = make_traffic_fleet(net, servers=1, capacity=2)
    for m in range(2):
        bal.place_match(1000 + m, make_synctest(), inputs_for(m))
    plan = TrafficPlan.generate(seed=4, duration=0.5, match_rate=10.0)
    mm = Matchmaker(
        bal, plan,
        make_session=lambda a: make_synctest(),
        make_inputs=lambda a: inputs_for(a.input_seed % 32),
        clock=lambda: net.now, metrics=Metrics(),
    )
    run_traffic(net, mm, servers, 60)
    assert mm.drained
    assert mm.admissions_started == 0
    assert mm.admissions_rejected == len(plan.arrivals()) > 0
    assert mm.metrics.counters["traffic_admissions_rejected"] == (
        mm.admissions_rejected
    )
    # Rejected traces are finished (closed), not complete (no stages).
    for t in mm.traces.values():
        assert t.t_done is not None


def test_admission_churn_faults_no_slot_and_compiles_nothing():
    """A healthy front door, by count: once the admission path has run
    once on every (server, group), an open-loop plan of queued admissions
    and abandons raises no slot fault, rejects nobody and obtains no
    executable."""
    from bevy_ggrs_tpu.utils import xla_cache

    assert xla_cache.install_compile_listeners()
    net = LoopbackNetwork()
    bal, servers = make_traffic_fleet(net, capacity=8)

    def serve(frames):
        for _ in range(frames):
            net.advance(FPS_DT)
            for srv in servers:
                srv.run_frame()

    # Steady state: enqueue -> drain -> first dispatch -> retire, on
    # every group of every server.
    warm = [
        (k, g) for k, srv in enumerate(servers)
        for g in range(len(srv.groups))
    ]
    for wid, (k, _) in enumerate(warm, 100_000):
        bal.place_match(
            wid, make_synctest(), inputs_for(wid), server_id=k, queue=True,
        )
    serve(8)
    for wid in range(100_000, 100_000 + len(warm)):
        pl = bal.placements.pop(wid)
        servers[pl.server_id].retire_match(pl.handle)
    serve(4)
    built = xla_cache.compile_counters()["backend_compiles"]

    plan = TrafficPlan.generate(
        seed=9000, duration=2.0, match_rate=5.0, abandon_rate=1.5,
        num_players=2, max_join_delay=0.05,
    )
    mm = Matchmaker(
        bal, plan,
        make_session=lambda a: make_synctest(),
        make_inputs=lambda a: inputs_for(a.input_seed % 64),
        clock=lambda: net.now, metrics=Metrics(),
    )
    run_traffic(net, mm, servers, 180)
    assert mm.drained and mm.admissions_rejected == 0
    assert mm.abandons_applied > 0
    assert sum(s.admissions_completed for s in servers) >= 6 + len(warm)
    assert all(
        s.metrics.counters.get("slot_faults", 0) == 0 for s in servers
    )
    assert xla_cache.compile_counters()["backend_compiles"] == built


def test_spectators_resolve_against_live_matches():
    net = LoopbackNetwork()
    bal, servers = make_traffic_fleet(net)
    events = (
        MatchArrival(0.01, 0, 2, 7, (0.0, 0.0)),
        MatchArrival(0.02, 1, 2, 8, (0.0, 0.0)),
        SpectatorSubscribe(0.30, 0.0),   # -> lowest live id
        SpectatorSubscribe(0.31, 0.99),  # -> highest live id
        MatchAbandon(0.50, 0.0),         # retires lowest live id
    )
    mm = Matchmaker(
        bal,
        TrafficPlan(1, events),
        make_session=lambda a: make_synctest(),
        make_inputs=lambda a: inputs_for(a.input_seed % 32),
        clock=lambda: net.now, metrics=Metrics(),
    )
    run_traffic(net, mm, servers, 60)
    assert mm.spectates_applied == 2
    # Both spectates resolved (0.0 -> match 0, 0.99 -> match 1); the
    # abandon then retired match 0 and unsubscribed its viewers.
    assert mm.spectators == {1: 1}
    assert sorted(mm.live) == [1]
    assert mm.abandons_applied == 1
    # The retired match's server slot was actually freed.
    assert sum(s.slots_active for s in servers) == 1
