"""Speculation ledger: per-rollback causal accounting for the branch tree.

The speculative runner's aggregate counters (``spec_hits`` /
``spec_partial_hits`` / ``spec_misses``) say *whether* speculation pays,
never *why* it fails. The ledger records one causal entry per rollback:

- **blame** — which player's input at which frame diverged from the
  branch-0 prediction (derived from the corrected-history diff the prefix
  matcher already computes — no extra device sync);
- **rank** — which branch matched. The structured tree enumerates
  candidates rank-major (every slot's best candidate before any slot's
  second, ``branch_tree.BranchTree.structured_bits``), so the matched
  branch index
  IS the candidate rank — the signal a learned ranking policy trains
  against;
- **economics** — frames recovered vs resimulated per rollback, and
  speculative device frames dispatched vs committed across the run (the
  **waste ratio**: every rollout computes B×F frames of which at most F
  ever commit).

Outcome taxonomy, reconciled 1:1 against the legacy counters
(test-enforced in ``tests/test_spec_ledger.py``):

- ``full``      — the whole recovery burst absorbed (== ``spec_hits``);
- ``partial``   — a prefix absorbed, the tail resimulated
  (== ``spec_partial_hits``);
- ``miss``      — a branch match was attempted and no branch covered the
  corrected history (== ``spec_misses``); the rollback resimulated
  serially;
- ``unmatched`` — a rollback with no match attempt at all (no pending
  rollout, anchor out of window, as-used log gap, non-canonical burst,
  speculation disabled, restore-path recovery). Every rollback is exactly
  one entry: ``full + partial + miss + unmatched == rollbacks_total``.

Telemetry discipline matches the rest of ``obs/``: the ``null_ledger``
singleton keeps every call site unconditional, a ledger ON changes no
wire byte and no RNG draw (witnessed in
``tests/test_telemetry_determinism.py``), and the whole set stays inside
the established ≤5 %-of-frame-budget overhead at S=256.

The module also ships the **counterfactual ranking harness**
(:func:`replay_baseline` / ``python -m bevy_ggrs_tpu.obs.ledger replay``):
a canonical input log is fed back through the branch builder under
alternative ranking policies and scored offline — hit-rate, hit-rank,
waste — producing the frozen ``spec_baseline.json`` table the ROADMAP's
learned input predictor must beat. Model/JAX imports are lazy (CLI-only)
so this module stays import-light for the runner hot path.
"""

from __future__ import annotations

import dataclasses
import json
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bevy_ggrs_tpu.branch_tree import BranchTree, forward_fill

#: Entry outcomes, in reconciliation order (see module docstring).
OUTCOMES: Tuple[str, ...] = ("full", "partial", "miss", "unmatched")


def blame_divergence(predicted, corrected) -> Optional[Tuple[int, int]]:
    """First ``(frame_offset, player)`` at which ``corrected`` diverges
    from the branch-0 ``predicted`` rows (both ``[k, P, *payload]``),
    scanning frame-major then player — the earliest mispredicted input is
    the causal one (everything after it resimulated *because* of it).
    ``None`` when the rows agree (the rollback was caused by pre-span
    history or a session-level prediction the rollout never saw)."""
    pred = np.asarray(predicted)
    corr = np.asarray(corrected)
    k = min(int(pred.shape[0]), int(corr.shape[0]))
    if k <= 0:
        return None
    P = int(corr.shape[1])
    diff = (
        pred[:k].reshape(k, P, -1) != corr[:k].reshape(k, P, -1)
    ).any(axis=2)
    if not diff.any():
        return None
    j, p = np.unravel_index(int(np.argmax(diff)), diff.shape)
    return int(j), int(p)


def rollback_blame(
    ledger, matched, branch_bits, anchor: int, load_frame: int, steps
):
    """``(blame_player, blame_frame)`` for ``ledger``'s entry of a rollback
    to ``load_frame`` whose corrected ``steps`` (each ``.adv.bits [P,
    ...]``; or a segment's ``bits [n, P, ...]`` as it is) were ``matched``
    against the rollout ``branch_bits [B, F, P, ...]`` from frame
    ``anchor``: :func:`blame_divergence` against branch 0
    over the frames both cover, the frame made absolute. Pure NumPy on the
    host-resident branch tensor. ``(None, None)`` when the ledger is off
    (nothing is gathered), when no branch was asked (``matched is None``)
    or when branch 0 agreed."""
    if matched is None or not ledger.enabled:
        return None, None
    b0 = np.asarray(branch_bits)[0]
    if not isinstance(steps, np.ndarray):
        steps = [np.asarray(s.adv.bits) for s in steps]
    hit = blame_divergence(b0[load_frame - anchor:], steps)
    return (None, None) if hit is None else (hit[1], load_frame + hit[0])


class SpeculationLedger:
    """Bounded per-rollback entry ring + persistent aggregate totals.

    Entries are plain dicts (JSONL-exportable as-is) on a ``deque`` of
    ``capacity``; the aggregates (outcome counts, blame histogram, rank
    histogram, frame economics) survive ring eviction so ``summary()``
    covers the whole run. ``seq`` is monotonic — consumers that poll
    (``MatchServer.run_frame`` feeding TimeSeries) read only new entries
    via :meth:`tail`.
    """

    enabled = True

    def __init__(
        self,
        capacity: int = 65536,
        clock=time.perf_counter,
        component: str = "spec",
        pid: int = 0,
        wall_t0: Optional[float] = None,
    ):
        self.capacity = int(capacity)
        self.component = component
        self.pid = int(pid)
        self.wall_t0 = time.time() if wall_t0 is None else float(wall_t0)
        self._clock = clock
        self._origin = clock()
        self.entries: deque = deque(maxlen=self.capacity)
        self._seq = 0
        # Persistent aggregates (survive ring eviction).
        self.outcome_counts: Counter = Counter()
        self.frames_recovered_total = 0
        self.frames_resimulated_total = 0
        self.rollouts_dispatched = 0
        self.spec_frames_dispatched = 0
        self.blame_counts: Counter = Counter()  # player -> entries blamed
        self.rank_hist: Counter = Counter()  # branch rank -> hit count

    # -- writers ---------------------------------------------------------

    def _now_us(self) -> int:
        return int((self._clock() - self._origin) * 1e6)

    def record(
        self,
        outcome: str,
        *,
        depth: int = 0,
        frames_recovered: int = 0,
        frames_resimulated: int = 0,
        branch: Optional[int] = None,
        rank: Optional[int] = None,
        blame_player: Optional[int] = None,
        blame_frame: Optional[int] = None,
        slot: Optional[int] = None,
        load_frame: Optional[int] = None,
    ) -> None:
        """One causal entry per rollback. ``depth`` is the rollback span
        (frames between the load frame and the live frontier);
        ``frames_recovered + frames_resimulated == depth`` always."""
        entry = {
            "seq": self._seq,
            "ts_us": self._now_us(),
            "outcome": outcome,
            "depth": int(depth),
            "frames_recovered": int(frames_recovered),
            "frames_resimulated": int(frames_resimulated),
        }
        if branch is not None:
            entry["branch"] = int(branch)
        if rank is not None:
            entry["rank"] = int(rank)
        if blame_player is not None:
            entry["blame_player"] = int(blame_player)
            self.blame_counts[int(blame_player)] += 1
        if blame_frame is not None:
            entry["blame_frame"] = int(blame_frame)
        if slot is not None:
            entry["slot"] = int(slot)
        if load_frame is not None:
            entry["load_frame"] = int(load_frame)
        self._seq += 1
        self.entries.append(entry)
        self.outcome_counts[outcome] += 1
        self.frames_recovered_total += int(frames_recovered)
        self.frames_resimulated_total += int(frames_resimulated)
        if rank is not None and outcome in ("full", "partial"):
            self.rank_hist[int(rank)] += 1

    def record_rollout(self, frames: int, slot: Optional[int] = None) -> None:
        """One speculative rollout dispatched: ``frames`` = B×F device
        frames of branch compute (of which at most F can ever commit)."""
        self.rollouts_dispatched += 1
        self.spec_frames_dispatched += int(frames)

    # -- readers ---------------------------------------------------------

    @property
    def rollbacks(self) -> int:
        return sum(self.outcome_counts[o] for o in OUTCOMES)

    def tail(self, since_seq: int) -> List[dict]:
        """Entries with ``seq >= since_seq``, oldest first — the polling
        consumer's incremental read (pass the last seen ``seq + 1``)."""
        if not self.entries or self.entries[-1]["seq"] < since_seq:
            return []
        return [e for e in self.entries if e["seq"] >= since_seq]

    def _rank_percentile(self, q: float) -> float:
        total = sum(self.rank_hist.values())
        if total == 0:
            return 0.0
        target = max(1, int(np.ceil(q * total)))  # nearest-rank
        cum = 0
        for rank in sorted(self.rank_hist):
            cum += self.rank_hist[rank]
            if cum >= target:
                return float(rank)
        return float(max(self.rank_hist))

    def summary(self) -> Dict[str, float]:
        """The whole-run view: hit rate, hit-rank
        percentiles, waste ratio, and blame concentration."""
        rb = self.rollbacks
        blamed = sum(self.blame_counts.values())
        dispatched = self.spec_frames_dispatched
        committed = self.frames_recovered_total
        return {
            "rollbacks": rb,
            "spec_full": self.outcome_counts["full"],
            "spec_partial": self.outcome_counts["partial"],
            "spec_miss": self.outcome_counts["miss"],
            "spec_unmatched": self.outcome_counts["unmatched"],
            "spec_full_hit_rate": (
                self.outcome_counts["full"] / rb if rb else 0.0
            ),
            "spec_hit_rank_p50": self._rank_percentile(0.5),
            "spec_hit_rank_p99": self._rank_percentile(0.99),
            "spec_waste_ratio": (
                max(0.0, 1.0 - committed / dispatched) if dispatched else 0.0
            ),
            "blame_top_player_share": (
                max(self.blame_counts.values()) / blamed if blamed else 0.0
            ),
            "frames_recovered_total": committed,
            "frames_resimulated_total": self.frames_resimulated_total,
            "rollouts_dispatched": self.rollouts_dispatched,
            "spec_frames_dispatched": dispatched,
        }

    def blame_shares(self) -> Dict[int, float]:
        """player -> share of blamed rollbacks (empty until one blames)."""
        total = sum(self.blame_counts.values())
        if not total:
            return {}
        return {
            p: c / total for p, c in sorted(self.blame_counts.items())
        }

    def scoped(self, slot_base: int) -> "_ScopedLedger":
        """A lightweight writer view that offsets every entry's ``slot``
        by ``slot_base`` into this ledger — how ``MatchServer`` gives each
        slot group a per-``match_slot`` namespace over ONE server-level
        ledger (flat slot = group × per_group + slot)."""
        return _ScopedLedger(self, int(slot_base))

    def clear(self) -> None:
        self.entries.clear()
        self.outcome_counts.clear()
        self.blame_counts.clear()
        self.rank_hist.clear()
        self.frames_recovered_total = 0
        self.frames_resimulated_total = 0
        self.rollouts_dispatched = 0
        self.spec_frames_dispatched = 0

    # -- exports ---------------------------------------------------------

    def export_jsonl(self, path: str) -> None:
        """Entry ring as JSON lines, first line a meta header — the
        failure-forensics artifact the chaos soaks drop next to the
        provenance logs."""
        with open(path, "w") as f:
            f.write(json.dumps({"meta": {
                "component": self.component, "pid": self.pid,
                "wall_t0": self.wall_t0, "summary": self.summary(),
            }}) + "\n")
            for e in self.entries:
                f.write(json.dumps(e) + "\n")

    def export_provenance(self, path: str, provenance_records) -> int:
        """Blamed entries as a provenance-format JSONL so
        ``obs.merge.merge_traces`` draws a flow arrow from the blamed
        input datagram to the resim/absorb burst it caused.

        Each blamed entry resolves the ``flow_key`` of the LAST rx input
        datagram (from the local :class:`~bevy_ggrs_tpu.obs.provenance.
        ProvenanceLog`'s records) whose start frame is ≤ the blamed frame
        — the packet that delivered the misprediction — and re-emits it
        as an rx ``spec_resim`` record under this ledger's component.
        The merge's causal ordering makes the ledger hop terminal (an
        rx-only owner), so the chain reads sender-tx → peer-rx →
        spec-resim across process tracks. Returns the records written."""
        records = getattr(provenance_records, "records", provenance_records)
        if callable(records):  # ProvenanceLog.records() is a method
            records = records()
        rx_inputs = [
            r for r in records
            if r.get("dir") == "rx" and r.get("type") == "input"
            and r.get("frame") is not None
        ]
        written = 0
        with open(path, "w") as f:
            f.write(json.dumps({"meta": {
                "component": self.component, "pid": self.pid,
                "wall_t0": self.wall_t0,
            }}) + "\n")
            for e in self.entries:
                bf = e.get("blame_frame")
                if bf is None:
                    continue
                cands = [r for r in rx_inputs if r["frame"] <= bf]
                if not cands:
                    continue
                src = max(cands, key=lambda r: (r["frame"], r["ts_us"]))
                rec = {
                    # Strictly after the source rx so the merged flow
                    # terminates here even across clock-origin skew.
                    "ts_us": max(e["ts_us"], src["ts_us"] + 1),
                    "dir": "rx",
                    "key": src["key"],
                    "len": 0,
                    "type": "spec_resim",
                    "frame": bf,
                    "blame_player": e.get("blame_player"),
                    "outcome": e["outcome"],
                    "depth": e["depth"],
                }
                if "slot" in e:
                    rec["slot"] = e["slot"]
                f.write(json.dumps(rec) + "\n")
                written += 1
        return written


class _ScopedLedger:
    """Per-slot-group writer view over a parent ledger (see
    :meth:`SpeculationLedger.scoped`). Only the write surface — readers
    go through the parent, which owns the totals."""

    __slots__ = ("parent", "slot_base")

    def __init__(self, parent: SpeculationLedger, slot_base: int):
        self.parent = parent
        self.slot_base = slot_base

    @property
    def enabled(self) -> bool:
        return self.parent.enabled

    def record(self, outcome: str, *, slot: Optional[int] = None, **kw) -> None:
        self.parent.record(
            outcome,
            slot=self.slot_base + (slot or 0),
            **kw,
        )

    def record_rollout(self, frames: int, slot: Optional[int] = None) -> None:
        self.parent.record_rollout(
            frames, slot=self.slot_base + (slot or 0)
        )


class _NullLedger:
    """Disabled ledger: writers are no-ops, readers are empty — call
    sites stay unconditional (the ``null_metrics`` pattern). ``enabled``
    is False so blame computation (the only non-trivial host work) is
    skipped entirely at the match sites."""

    enabled = False
    entries: Tuple[dict, ...] = ()
    rollbacks = 0
    frames_recovered_total = 0
    frames_resimulated_total = 0
    rollouts_dispatched = 0
    spec_frames_dispatched = 0

    def record(self, outcome: str, **kw) -> None:
        pass

    def record_rollout(self, frames: int, slot: Optional[int] = None) -> None:
        pass

    def tail(self, since_seq: int) -> List[dict]:
        return []

    def summary(self) -> Dict[str, float]:
        return {}

    def blame_shares(self) -> Dict[int, float]:
        return {}

    def scoped(self, slot_base: int) -> "_NullLedger":
        return self

    def clear(self) -> None:
        pass

    def export_jsonl(self, path: str) -> None:
        pass

    def export_provenance(self, path: str, provenance_records) -> int:
        return 0


null_ledger = _NullLedger()


# ----------------------------------------------------------------------
# Counterfactual ranking harness (offline, host-only).
# ----------------------------------------------------------------------

#: Pluggable ranking-policy registry. A policy is registered under a
#: name as a FACTORY: it receives the configuration's
#: :class:`~bevy_ggrs_tpu.branch_tree.BranchTree` (the class the live
#: runner and every served slot build their trees with: no world, no
#: schedule, no executor) and the canonical input log, a ``dict`` the
#: harness grows a frame at a time (every frame below ``anchor`` is in
#: it when ``anchor`` is asked for), and returns the per-anchor callable
#: ``fn(anchor, last, known, mask) -> (bits, n_branches)``. Built-ins:
#:
#: - ``current``     — the production structured tree (history-ranked
#:   candidates + periodic extrapolation, through the native builder
#:   when it loads);
#: - ``repeat_last`` — the single-branch forward-fill ablation: the
#:   reference engine's whole prediction policy, and the floor any
#:   learned ranker must clear;
#: - ``learned``     — the ``predict/`` tier: the committed int8 MLP
#:   artifact seeding the same structured tree.
#:
#: Future rankers call :func:`register_policy` instead of editing the
#: harness.
#: factory(tree, log) -> fn(anchor, last, known, mask) -> (bits, n_branches)
PolicyFactory = Callable[..., Callable]

POLICY_REGISTRY: Dict[str, PolicyFactory] = {}


def register_policy(name: str):
    """Decorator: ``@register_policy("mine")`` over a policy factory."""

    def deco(factory):
        POLICY_REGISTRY[name] = factory
        return factory

    return deco


def _replay_configs() -> Dict[str, dict]:
    """The live paced pairs' model configs plus the structurally-hard
    8p/B=1024 spectator config — the exact configurations the ROADMAP's
    learned-predictor success metric is defined over. Input scripts are
    the canonical key cycles (`keys[(frame // 3 + handle) % len(keys)]`)."""
    from bevy_ggrs_tpu.models import boids, box_game, projectiles

    box_keys = [
        box_game.INPUT_UP, box_game.INPUT_RIGHT, box_game.INPUT_DOWN, 0,
    ]
    return {
        "box_game": dict(
            input_spec=box_game.INPUT_SPEC, players=2, branches=64,
            spec_frames=8, keys=box_keys,
        ),
        "boids": dict(
            input_spec=boids.INPUT_SPEC, players=2, branches=16,
            spec_frames=8,
            keys=[boids.INPUT_UP, boids.INPUT_RIGHT, boids.INPUT_DOWN, 0],
        ),
        "projectiles": dict(
            input_spec=projectiles.INPUT_SPEC, players=4, branches=64,
            spec_frames=8,
            keys=[
                projectiles.INPUT_UP, projectiles.INPUT_FIRE,
                projectiles.INPUT_RIGHT, 0,
            ],
        ),
        "neural_bots": dict(
            input_spec=_neural_bots_spec(), players=2, branches=32,
            spec_frames=8, keys=[1, 2, 4, 0],
        ),
        "box_game_8p_B1024": dict(
            input_spec=box_game.INPUT_SPEC, players=8, branches=1024,
            spec_frames=12, keys=box_keys,
        ),
    }


def _neural_bots_spec():
    from bevy_ggrs_tpu.models import neural_bots

    return neural_bots.INPUT_SPEC


def _branch_values_for(input_spec) -> list:
    # The runner ctor's default universe resolution.
    if getattr(input_spec, "values", None):
        return list(input_spec.values)
    return list(range(16))


# -- built-in ranking policies -----------------------------------------


@register_policy("current")
def _policy_current(tree: BranchTree, log: dict):
    """The production structured tree, native builder when it loads."""
    from bevy_ggrs_tpu.native import spec as native_spec

    native = native_spec.make_spec_builder(
        tree.input_spec, tree.num_players, tree.num_branches,
        tree.spec_frames, tree.branch_values,
    )

    mirrored = 0  # the native builder ranks from its own mirror of ``log``

    def fn(anchor, last, known, mask):
        nonlocal mirrored
        if native is not None:
            for f in range(mirrored, anchor):
                native.log_set(f, log[f])
            mirrored = anchor
            bits, _ = native.build(anchor, None, known, mask, False, None)
        else:
            bits = tree.structured_bits(
                log, np.asarray(last), known, mask, anchor
            )
        return np.asarray(bits), tree.num_branches

    return fn


@register_policy("repeat_last")
def _policy_repeat_last(tree: BranchTree, log: dict):
    """The single forward-fill branch — the reference engine's whole
    prediction policy, and the learned ranker's floor."""

    def fn(anchor, last, known, mask):
        base = forward_fill(np.asarray(last), known, mask)
        return np.broadcast_to(base, (1,) + base.shape).copy(), 1

    return fn


@register_policy("learned")
def _policy_learned(tree: BranchTree, log: dict):
    """The ``predict/`` tier: the committed int8 MLP artifact bound to
    this config's universe, seeding the same structured tree the live
    path builds (branch 0 stays repeat-last inside ``structured_bits``)."""
    from bevy_ggrs_tpu.predict import InputPredictor, load_default

    spec = tree.input_spec
    n_field = 1
    if getattr(spec, "shape", ()):
        n_field = int(np.prod(spec.shape, dtype=np.int64))
    bound = InputPredictor(load_default()).bind(
        tree.branch_values, spec.zeros_np(1).dtype, n_field
    )
    if bound is None:
        raise ValueError(
            "learned policy: predictor does not apply to this config "
            f"(n_field={n_field}, universe={len(tree.branch_values)})"
        )
    seeded_tree = dataclasses.replace(tree, predictor=bound)

    def fn(anchor, last, known, mask):
        bits = seeded_tree.structured_bits(
            log, np.asarray(last), known, mask, anchor
        )
        return np.asarray(bits), tree.num_branches

    return fn


#: Registration-ordered policy names; the CLI default scores them all.
POLICIES: Tuple[str, ...] = tuple(POLICY_REGISTRY)


def replay_config(
    name: str, cfg: dict, frames: int, policies=POLICIES,
) -> Dict[str, dict]:
    """Score each ranking policy over ``frames`` anchors of the canonical
    scripted input log for one model config. Pure host work: branch
    tensors are built and prefix-matched against the scripted truth; no
    device rollout runs (waste here is the dispatch-side B×F accounting,
    identical to what the live ledger records per rollout)."""
    from bevy_ggrs_tpu.parallel.speculate import match_branch

    spec = cfg["input_spec"]
    P, B, F = cfg["players"], cfg["branches"], cfg["spec_frames"]
    keys = cfg["keys"]
    values = _branch_values_for(spec)
    zeros = spec.zeros_np(P)
    dtype = spec.zeros_np(1).dtype

    def frame_input(f: int) -> np.ndarray:
        row = zeros.copy()
        for h in range(P):
            row[h] = np.asarray(keys[(f // 3 + h) % len(keys)], dtype)
        return row

    # The span is scored as pure prediction (no pinned known inputs):
    # identical known-input pinning would shift every policy equally, and
    # the unpinned tree is what separates ranking policies.
    known = np.broadcast_to(zeros, (F,) + zeros.shape).copy()
    mask = np.zeros((F, P), dtype=bool)

    out: Dict[str, dict] = {}
    for policy in policies:
        factory = POLICY_REGISTRY.get(policy)
        if factory is None:
            raise ValueError(
                f"unknown ranking policy {policy!r} "
                f"(registered: {', '.join(POLICY_REGISTRY)})"
            )
        log: dict = {}
        policy_fn = factory(BranchTree(spec, P, B, F, values), log)
        ledger = SpeculationLedger(capacity=frames + 1)
        full_hits = 0
        anchors = 0
        # Warm 16 frames of history before the first anchor so the
        # recency ranking and period detector see a real log.
        log[0] = frame_input(0)
        for a in range(1, max(2, frames - F)):
            last = log[a - 1]
            bits, n_branches = policy_fn(a, last, known, mask)
            truth = np.stack([frame_input(a + t) for t in range(F)])
            branch, depth = match_branch(np.asarray(bits), truth)
            branch, depth = int(branch), int(depth)
            anchors += 1
            ledger.record_rollout(n_branches * F)
            blame = blame_divergence(np.asarray(bits)[0], truth)
            outcome = "full" if depth == F else (
                "partial" if depth > 0 else "miss"
            )
            if depth == F:
                full_hits += 1
            ledger.record(
                outcome, depth=F, frames_recovered=depth,
                frames_resimulated=F - depth,
                branch=branch if depth > 0 else None,
                rank=branch if depth > 0 else None,
                blame_player=None if blame is None else blame[1],
                blame_frame=None if blame is None else a + blame[0],
                load_frame=a,
            )
            log[a] = frame_input(a)
        s = ledger.summary()
        out[policy] = {
            "anchors": anchors,
            "full_hits": full_hits,
            "full_hit_rate": round(full_hits / anchors, 4) if anchors else 0.0,
            "hit_rank_p50": s["spec_hit_rank_p50"],
            "hit_rank_p99": s["spec_hit_rank_p99"],
            "waste_ratio": round(s["spec_waste_ratio"], 4),
            "blame_top_player_share": round(
                s["blame_top_player_share"], 4
            ),
            "mean_commit_depth": round(
                s["frames_recovered_total"] / anchors, 3
            ) if anchors else 0.0,
        }
    return out


def replay_baseline(
    frames: int = 240,
    configs: Optional[List[str]] = None,
    policies=POLICIES,
) -> dict:
    """The frozen prediction-quality baseline: every config × policy
    scored over the same canonical input log. This is the table
    (``spec_baseline.json``) a learned ranking policy must beat — see
    the ROADMAP's learned-input-prediction item."""
    all_cfgs = _replay_configs()
    names = configs or list(all_cfgs)
    table = {
        "generated_by": "python -m bevy_ggrs_tpu.obs.ledger replay",
        "frames_per_config": int(frames),
        "policies": list(policies),
        "configs": {},
    }
    for name in names:
        cfg = all_cfgs[name]
        table["configs"][name] = {
            "players": cfg["players"],
            "branches": cfg["branches"],
            "spec_frames": cfg["spec_frames"],
            "policies": replay_config(name, cfg, frames, policies),
        }
    return table


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m bevy_ggrs_tpu.obs.ledger",
        description="Speculation-ledger offline tools.",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser(
        "replay",
        help="score branch-ranking policies over the canonical input "
             "log and write the spec_baseline.json table",
    )
    rp.add_argument("--frames", type=int, default=240,
                    help="anchors scored per config (default 240)")
    rp.add_argument("--configs", default=None,
                    help="comma-separated config subset (default: all)")
    rp.add_argument("--policies", default=",".join(POLICIES),
                    help="comma-separated policy subset")
    rp.add_argument("--out", default="spec_baseline.json",
                    help="output table path (default spec_baseline.json)")
    args = ap.parse_args(argv)

    if args.cmd == "replay":
        table = replay_baseline(
            frames=args.frames,
            configs=args.configs.split(",") if args.configs else None,
            policies=tuple(args.policies.split(",")),
        )
        with open(args.out, "w") as f:
            json.dump(table, f, indent=2)
            f.write("\n")
        for name, cfg in table["configs"].items():
            for policy, row in cfg["policies"].items():
                print(
                    f"{name:>20} {policy:>12}: "
                    f"hit_rate={row['full_hit_rate']:.3f} "
                    f"rank_p50={row['hit_rank_p50']:.0f} "
                    f"waste={row['waste_ratio']:.3f}"
                )
        print(f"baseline table -> {args.out}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
