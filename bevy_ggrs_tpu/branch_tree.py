"""The branch tree: which input futures a speculative rollout covers.

One decision with one home. A rollout simulates ``num_branches`` candidate
futures of ``spec_frames`` frames from the confirmed frontier; a rollback
is recovered without resimulation when one of them IS the corrected
history. :class:`BranchTree` builds that ``[B, F, P, ...]`` tensor from a
match's as-used input log: branch 0 is the session's own repeat-last
prediction (so the engine strictly contains the reference's policy),
branch 1 the periodic extrapolation of the log when it has a rhythm, and
every further branch one player changing one control at one frame, the
likeliest change first.

Everyone who builds a tree builds it here: the singleton runner
(``spec_runner.py``), every slot of a :class:`~bevy_ggrs_tpu.serve.batch.
BatchedSessionCore`, the counterfactual replay harness
(``obs/ledger.py``), and the tests that hold the native builder
(``native/spec.py``, ``session_core.cpp``) to it bit for bit. A change of
the tree's shape lands here and in the native builder, nowhere else.

The tree holds NO state. It is a function of its configuration (the
fields) and its arguments: the input log is an argument (a ``dict`` frame
-> as-used bits, or a ``native.spec.MirroredLog``), a learned predictor
(``predict/``) is a field, and that predictor's ranking for one anchor is
the ``seed`` argument. This module imports NumPy and ``zlib`` and nothing
of the runner, the serving tier, the sessions or ``obs``.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Optional, Sequence

import numpy as np


def forward_fill(
    last: np.ndarray, known: np.ndarray, known_mask: np.ndarray
) -> np.ndarray:
    """The session's actual prediction for a rollout span: per player, start
    from the anchor-1 input and forward-fill the latest confirmed value into
    unknown frames (a confirmed change inside the span keeps predicting the
    NEW value afterwards, exactly like the repeat-last queues). Resuming the
    anchor-1 input after a pinned prefix would diverge from the session's
    prediction and force two-change branches no tree enumerates.

    ``last[P, ...]``, ``known[F, P, ...]``, ``known_mask[F, P]`` — payload
    dims beyond ``[F, P]`` are handled (vector inputs).
    """
    extra = known.ndim - 2
    mask = known_mask.reshape(known_mask.shape + (1,) * extra)
    base = np.empty_like(known)
    carry = np.array(last, copy=True)
    for t in range(known.shape[0]):
        carry = np.where(mask[t], known[t], carry)
        base[t] = carry
    return base


def distinct_prefixes(branch_bits: np.ndarray, lead: int = 0) -> np.ndarray:
    """How many DISTINCT input prefixes each level of a tree holds:
    ``int[*lead, F]`` of ``branch_bits[*lead, B, F, P, ...]``, level ``f``
    counting the branches no lower branch equals on frames ``0..f`` (bit
    patterns). What a rollout that steps each prefix once has to step
    (``rollout.py`` ``prefix_classes`` is the device's rule, and a test
    holds the two equal); the default tree's single changes share their
    base's prefix up to the frame they change."""
    bits = np.ascontiguousarray(branch_bits)
    head = bits.shape[:lead + 2]
    raw = bits.view(np.uint8).reshape(head + (-1,))
    differ = (raw[..., :, None, :, :] != raw[..., None, :, :, :]).any(-1)
    same = ~np.logical_or.accumulate(differ, axis=-1)  # [*lead, B, B, F]
    lower = np.tri(head[-2], k=-1, dtype=bool)[..., None]
    return (~(same & lower).any(axis=-2)).sum(axis=-2)


def rollout_world_steps(
    branch_bits: np.ndarray, width: Optional[int], lead: int = 0
) -> tuple:
    """``(steps, fill)`` of one dispatch's rollout over the trees
    ``branch_bits[*lead, B, F, P, ...]``: the world-steps a lane runs (a
    level ``width`` at a time as often as the deepest lane's distinct
    prefixes ask for: ``rollout.py`` ``_rollout_shared``) and the share of
    the lanes' steps that are a lane's own distinct prefixes. ``width``
    None: a rollout that steps every branch every frame, ``B x F`` and no
    share."""
    if width is None:
        return int(np.prod(branch_bits.shape[lead:lead + 2])), None
    own = distinct_prefixes(branch_bits, lead)
    deepest = own.reshape(-1, own.shape[-1]).max(axis=0)
    steps = int((-(-deepest // width) * width).sum())
    return steps, float(own.sum()) / (own[..., 0].size * steps)


@dataclasses.dataclass(frozen=True)
class BranchTree:
    """The default (structured) branch tree of one session configuration.

    ``branch_values`` is the declared universe of input values the tree's
    own perturbations stay inside (what the warm-up attestation samples);
    ``predictor`` is a BOUND learned predictor (``predict.model``) or
    None for the heuristic ranking. Every method is a pure function of
    the fields and its arguments; ``log`` is the match's as-used input
    log, frame -> ``bits[P, ...]``."""

    input_spec: Any
    num_players: int
    num_branches: int
    spec_frames: int
    branch_values: Sequence
    predictor: Any = None

    def known_inputs(self, session, anchor: int):
        """(known[F, P, ...], mask[F, P]) of inputs already confirmed inside
        the rollout span. Prefers the session's bulk ``confirmed_span``
        (one call — one FFI round trip on the native queue — per player)
        over the per-(frame, player) ``confirmed_input`` getter loop whose
        O(F x P) Python/ctypes cost was the measured per-tick dispatch
        overhead (round-3 verdict weak #5)."""
        F, P = self.spec_frames, self.num_players
        zeros = self.input_spec.zeros_np(P)
        known = np.broadcast_to(zeros, (F,) + zeros.shape).copy()
        mask = np.zeros((F, P), dtype=bool)
        span = getattr(session, "confirmed_span", None)
        if span is not None:
            for h in range(P):
                vals, m = span(h, anchor, F)
                if m.any():
                    known[m, h] = vals[m]
                    mask[:, h] = m
            return known, mask
        getter = getattr(session, "confirmed_input", None)
        if getter is None:
            return known, mask
        for t in range(F):
            for h in range(P):
                got = getter(h, anchor + t)
                if got is not None:
                    known[t, h] = np.asarray(got)
                    mask[t, h] = True
        return known, mask

    def candidate_values(self, log, last: np.ndarray):
        """History-ranked candidate matrix ``(C[P, n_field, R], valid[P,
        n_field, R])`` for the structured tree: per player/field, the
        values most likely to be the misprediction, best-first.

        Ranking (round-4 verdict item 2 — the uniform value sweep spent
        64 branches covering frame-0 changes of a 32-value universe and
        hit 10% live on projectiles):

        1. values this player RECENTLY used (from the as-used input log,
           most recent first) — players alternate among a tiny working set
           (hold-to-move masks, FIRE toggles), so the actual correction is
           almost always a recent value;
        2. single-button press/release TRANSITIONS (integer payloads):
           ``last ^ bit`` for every bit of the universe, recently-toggling
           bits first — the canonical one-button misprediction, ranked
           ahead of multi-bit universe combos even when that exact mask
           has never been used (a brand-new session's first FIRE press
           must be coverable);
        3. the declared universe, in order, as the exhaustive tail.

        ``valid`` masks padding (rows are ragged before padding)."""
        P = self.num_players
        shape = self.input_spec.shape
        n_field = int(np.prod(shape, dtype=np.int64)) if shape else 1
        dtype = self.input_spec.zeros_np(1).dtype
        universe = np.asarray(self.branch_values, dtype=dtype).reshape(-1)
        lastf = np.asarray(last).reshape(P, n_field)
        frames = sorted(log)[-32:]
        hist = (
            np.stack([
                np.asarray(log[f]).reshape(P, n_field)
                for f in frames
            ])
            if frames else np.zeros((0, P, n_field), dtype)
        )
        integer = np.issubdtype(dtype, np.integer)
        rows = []
        max_r = 0
        for h in range(P):
            for k in range(n_field):
                seq = hist[::-1, h, k]  # newest first
                if seq.size:
                    _, first = np.unique(seq, return_index=True)
                    recent = list(seq[np.sort(first)])
                else:
                    recent = []
                toggles = []
                if integer:
                    changed = (
                        int(np.bitwise_or.reduce(
                            np.bitwise_xor(seq[1:], seq[:-1])
                        ))
                        if seq.size >= 2 else 0
                    )
                    top = int(max((int(v) for v in universe), default=0))
                    limit = max(changed, top)
                    all_bits = []
                    bit = 1
                    while bit <= limit:
                        all_bits.append(bit)
                        bit <<= 1
                    ordered = (
                        [b for b in all_bits if changed & b]
                        + [b for b in all_bits if not (changed & b)]
                    )
                    toggles = [
                        dtype.type(int(lastf[h, k]) ^ b) for b in ordered
                    ]
                # Candidates are CLAMPED to the declared universe: the
                # warmup attestation samples exactly `_branch_values`, so
                # a tree must never enumerate a value class attestation
                # never replayed through the serial executable. (Received
                # out-of-contract values still appear in the branch-0
                # base — unavoidable for any prediction policy — but the
                # tree's own perturbations stay in-contract.)
                allowed = {
                    v.item() if hasattr(v, "item") else v for v in universe
                }
                row, seen = [], set()
                for v in [*recent, *toggles, *universe]:
                    key = v.item() if hasattr(v, "item") else v
                    if key not in seen and key in allowed:
                        seen.add(key)
                        row.append(v)
                rows.append(row)
                max_r = max(max_r, len(row))
        C = np.zeros((P, n_field, max_r), dtype)
        valid = np.zeros((P, n_field, max_r), bool)
        for i, row in enumerate(rows):
            h, k = divmod(i, n_field)
            C[h, k, : len(row)] = row
            valid[h, k, : len(row)] = True
        return C, valid

    def history_fingerprint(self, log, anchor: int) -> tuple:
        """Digest of everything the structured branch tree reads from the
        input log: the max logged frame (the recency ranking in
        :meth:`candidate_values` keys on the latest 32 logged frames) and
        a hash of the contiguous ≤48-frame window ending at ``anchor - 1``
        (the periodic-extrapolation input). The dedup signatures fold this
        in so a SHIFTED history window — same (anchor, last, known) but new
        log contents — can't pin a stale branch tree."""
        L = anchor - 1
        start = L
        while start - 1 in log and L - (start - 1) < 48:
            start -= 1
        digest = 0
        for f in range(start, L + 1):
            got = log.get(f)
            if got is not None:
                digest = zlib.crc32(
                    np.ascontiguousarray(got).tobytes(), digest
                )
        return (max(log, default=-1), start, digest)

    def extrapolate_base(
        self, log, base: np.ndarray, known: np.ndarray, known_mask: np.ndarray,
        anchor: int,
    ) -> Optional[np.ndarray]:
        """Per-(player, field) PERIODIC extrapolation of the as-used input
        history — the loop-predictor analog for inputs. Rhythmic play
        (autorepeat fire, strafe tapping, the benches' key cycles) makes a
        player's stream exactly periodic; repeat-last then mispredicts at
        every period boundary, and with several remote players a rollback
        span contains boundaries from MORE than one of them — a shape no
        single-change tree covers (the round-4 projectiles 10% live hit
        rate). Detection: smallest p in 2..16 with ``seq[p:] == seq[:-p]``
        over a contiguous ≤48-frame window ending at the anchor; the
        prediction for future frame g is the logged value at ``g - p``
        (phase-aligned by construction). Returns the extrapolated base
        with known slots re-pinned, or None when no player/field has a
        (non-constant) period."""
        F, P = self.spec_frames, self.num_players
        shape = self.input_spec.shape
        n_field = int(np.prod(shape, dtype=np.int64)) if shape else 1
        L = anchor - 1  # last frozen history frame
        start = L
        while start - 1 in log and L - (start - 1) < 48:
            start -= 1
        if L not in log or L - start + 1 < 8:
            return None
        frames = range(start, L + 1)
        hist = np.stack([
            np.asarray(log[f]).reshape(P, n_field)
            for f in frames
        ])  # [W, P, K]
        predf = base.reshape(F, P, n_field).copy()
        universe = np.asarray(self.branch_values, dtype=hist.dtype).reshape(-1)
        found = False
        for h in range(P):
            for k in range(n_field):
                seq = hist[:, h, k]
                # Extrapolation REPLAYS history values as predictions, so a
                # history containing out-of-contract values (outside the
                # declared `_branch_values` universe the warmup attestation
                # sampled) would smuggle them into branch bases. Skip the
                # (player, field): repeat-last keeps the unavoidable
                # branch-0 exposure and nothing more.
                if universe.size and not np.isin(seq, universe).all():
                    continue
                n = seq.shape[0]
                period = 0
                for p in range(2, min(16, n // 2) + 1):
                    if np.array_equal(seq[p:], seq[:-p]):
                        period = p
                        break
                if not period or (seq[-period:] == seq[-1]).all():
                    continue  # aperiodic, or constant (= repeat-last)
                found = True
                for t in range(F):
                    off = (anchor + t) - L
                    g0 = (anchor + t) - period * (-(-off // period))
                    predf[t, h, k] = hist[g0 - start, h, k]
        if not found:
            return None
        knownf = np.asarray(known).reshape(F, P, n_field)
        predf = np.where(known_mask[:, :, None], knownf, predf)
        return predf.reshape(base.shape)

    def structured_bits(
        self, log, last: np.ndarray, known: np.ndarray, known_mask: np.ndarray,
        anchor: Optional[int] = None, seed=None,
    ) -> np.ndarray:
        """The default branch tree: branch 0 is the session's own
        prediction (known inputs pinned, unknowns repeat-last); every
        further branch changes ONE player's unknown suffix — for vector
        payloads, one FIELD of it — to one candidate value starting at one
        frame, the shape of a real misprediction (one player pressed or
        released one control at one frame and held). Fields beyond the
        changed one keep the prediction, matching how independent controls
        (stick axis, button) mispredict one at a time.

        Enumeration order is (candidate-rank, frame, player, field)-major
        over the history-ranked candidate matrix (:meth:`candidate_
        values`): every player/frame slot gets its BEST candidate before
        any slot gets its second — so a B-branch tree covers the likely
        transition (e.g. projectiles' FIRE toggle) at EVERY frame of the
        span instead of exhausting the budget on improbable values at
        frame 0 (round-4 verdict item 2; the old (frame, value)-major
        sweep hit 10% live on projectiles' 32-value universe)."""
        F, P, B = self.spec_frames, self.num_players, self.num_branches
        shape = self.input_spec.shape  # per-player payload dims, () scalar
        base = forward_fill(last, known, known_mask)  # [F, P, *shape]
        if B <= 1 or not self.branch_values:
            return np.broadcast_to(base, (B, F, P) + shape).copy()
        if anchor is None:
            anchor = max(log, default=0) + 1
        # Detected input periodicity replaces repeat-last as the BASE the
        # tree perturbs: branch 1 is the extrapolated pattern itself (all
        # players continue their rhythms — covers multi-player period
        # boundaries in one branch), and the single-change branches model
        # one player DEVIATING from the pattern. Branch 0 stays the
        # session's literal forward-fill prediction (the engine must
        # strictly contain the reference's repeat-last policy).
        # A bound learned predictor (predict/) replaces BOTH the
        # periodic extrapolator (its autoregressive trajectory becomes
        # the effective base) and the recency/toggle candidate ranking
        # (its first-step logits order the universe). ``seed`` is the
        # caller's ranking for this anchor (the same tick's
        # signature-fold seed, or a slot's slice of a batched ranking);
        # None asks the predictor. Branch 0 below stays the literal
        # forward-fill prediction regardless — recovery is never worse
        # than repeat-last.
        seeded = None
        if self.predictor is not None:
            seeded = (
                seed if seed is not None
                else self.predictor.seed(log, anchor, F, P)
            )
        if seeded is not None:
            knownf = np.asarray(known).reshape(F, P, -1)
            trajf = seeded.traj.reshape(F, P, -1).astype(
                base.dtype, copy=True
            )
            trajf = np.where(known_mask[:, :, None], knownf, trajf)
            pred = trajf.reshape(base.shape)
            if np.array_equal(pred, base):
                pred = None
        else:
            pred = self.extrapolate_base(log, base, known, known_mask, anchor)
        eff_base = base if pred is None else pred
        out = np.broadcast_to(eff_base, (B, F, P) + shape).copy()
        out[0] = base
        start_b = 1
        if pred is not None and not np.array_equal(pred, base):
            start_b = 2  # out[1] is already the unperturbed extrapolation
        # Fully vectorized selection (the Python t/h/field/value loop was
        # O(B·F) per tick — milliseconds at the 1024-branch stress shape,
        # round-3 verdict weak #5). Eligibility E[r, t, h, field]: the
        # slot is not pinned, the rank is not padding, and the candidate
        # differs from the base prediction; flattening E in C order gives
        # the rank-major enumeration, and the first B-start_b eligible
        # entries become branches start_b..B-1.
        if seeded is not None:
            C, cvalid = seeded.cand, seeded.valid  # [P, K, R]
        else:
            C, cvalid = self.candidate_values(log, last)  # [P, K, R]
        n_field = C.shape[1]
        basef = eff_base.reshape(F, P, n_field)
        free = ~known_mask  # [F, P]
        cv = C.transpose(2, 0, 1)  # [R, P, K]
        elig = (
            free[None, :, :, None]
            & cvalid.transpose(2, 0, 1)[:, None, :, :]
            & (cv[:, None, :, :] != basef[None, :, :, :])
        )  # [R, F, P, K]
        idx = np.flatnonzero(elig.reshape(-1))[: B - start_b]
        if idx.size == 0:
            return out
        r_i, t_i, h_i, k_i = np.unravel_index(idx, elig.shape)
        # Each selected branch writes its value over the change player's
        # unpinned suffix (frames >= t that are not known for that player).
        suffix = (
            (np.arange(F)[None, :] >= t_i[:, None]) & free[:, h_i].T
        )  # [n_sel, F]
        bb, ff = np.nonzero(suffix)
        outf = out.reshape(B, F, P, n_field)
        outf[start_b + bb, ff, h_i[bb], k_i[bb]] = C[h_i[bb], k_i[bb], r_i[bb]]
        return out
