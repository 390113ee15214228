"""Matches that end and begin, drawn from the seed: the plan of a server's
turnover, and the plain reference of its lifecycle.

A traffic mix names the parameters (``benchmark/traffic/<mix>.json``,
``turnover``); this file turns them and ``--seed`` into who sits where,
how long each match lasts and how it ends. Plain Python and NumPy: it
imports nothing of the program, and a loop kind holds the program's
lifecycle (retirements, admissions, the slot each arrival was given)
against ``ledger()``.

A server of ``seats`` slots is full when the window opens, and stays full:
every seat holds a chain of matches, generation 0 the one that is live at
the window's first served frame, generation ``g + 1`` the arrival that is
asked for the served frame after generation ``g`` was retired. Match
``g * seats + seat`` is generation ``g`` of ``seat``; its inputs and its
link's bursts are drawn from that id.

As ``inputs.HeldKeys`` does for holds, every seed gets the same lives in
another order. A block is the ``block`` mid-quantiles of the clipped
log-normal ``match_length_frames`` (virtual frames from the served frame
that enqueued the match to the one that ends it). The live matches'
remaining frames are the ``seats`` mid-quantiles of that length's
stationary residual life (density ``(1 - F(x)) / mean``: what an observer
who walks in on a renewal process finds), the smallest moved to
``first_end_frame``; rank ``j`` of them belongs to the seat the seed's
permutation gives it. Generation ``g >= 1`` of rank ``j`` lasts
``lengths[(29 j + 17 g + 7) % block]`` frames, and ends by the kind at
position ``((j + 3 g) % period + 0.5) / period`` of the cumulated
``end_kinds``. So two seeds differ in WHICH seat ends when (and in every
input and burst), never in how many matches end by a given served frame or
how they end.

Frames are served frames of the window, the first 0. A ``game_over`` is
retired in the served frame it ends in. A ``silent_drop``'s far end ticks
last in the frame it ends in; the server retires it when it reports the
player disconnected, ``waits[match]`` served frames later: the one thing
of the lifecycle the seed does not decide (the network does, inside the
bounds the configuration's guarantee states), so ``ledger()`` takes the
waits the run observed.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

GAME_OVER = "game_over"
SILENT_DROP = "silent_drop"
KINDS = (GAME_OVER, SILENT_DROP)


class Life(NamedTuple):
    """One match of the plan. ``length`` is None for generation 0, whose
    ``end`` is its residual life; every later ``end`` follows from when the
    predecessor was retired."""

    match: int
    seat: int
    generation: int
    kind: str
    length: Optional[int]


class Turnover:
    def __init__(self, seed: int, seats: int, params: dict):
        if params.get("kind") != "residual_life":
            raise ValueError(f"unknown turnover plan {params.get('kind')!r}")
        self.seed, self.seats = int(seed), int(seats)
        length = params["match_length_frames"]
        lo, hi = (int(x) for x in length["clip"])
        n = int(params["block"])
        self.generations = int(params["generations"])
        if not 1 <= lo <= hi or n < 1 or self.seats < 1 \
                or self.generations < 2:
            raise ValueError("bad match lengths, block, seats or generations")
        z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        self.lengths = np.clip(np.rint(np.exp(
            math.log(float(length["median"]))
            + float(length["sigma"]) * np.asarray(z))), lo, hi
        ).astype(np.int64)
        shares = [float(params["end_kinds"].get(k, 0.0)) for k in KINDS]
        if set(params["end_kinds"]) - set(KINDS) \
                or abs(sum(shares) - 1.0) > 1e-9:
            raise ValueError("end_kinds are shares of game_over / silent_drop")
        self.period = next(
            (p for p in range(1, 65)
             if all(abs(s * p - round(s * p)) < 1e-9 for s in shares)), 0)
        if not self.period:
            raise ValueError("end_kinds are not whole parts of 64 or fewer")
        self._cum = np.cumsum(shares)
        self.residuals = self._residuals(int(params["first_end_frame"]))
        # rank_of[seat]: which of the residual lives the seat's match has.
        self.rank_of = np.random.Generator(
            np.random.PCG64([self.seed, 0x7EA7])).permutation(self.seats)

    def _residuals(self, first_end: int) -> np.ndarray:
        """The ``seats`` mid-quantiles of the stationary residual life of a
        length drawn from the block: the inverse of
        ``G(x) = sum_i min(x, L_i) / sum_i L_i``, which is linear between
        two lengths of the block."""
        lengths = np.sort(self.lengths).astype(np.float64)
        total = lengths.sum()
        knots = np.concatenate([[0.0], lengths])
        # G at each knot: the lengths under it whole, the others up to it.
        under = np.concatenate([[0.0], np.cumsum(lengths)])
        g = (under + knots * (len(lengths) - np.arange(len(knots)))) / total
        q = (np.arange(self.seats) + 0.5) / self.seats
        out = np.rint(np.interp(q, g, knots)).astype(np.int64)
        out[0] = first_end
        return np.maximum(out, 0)

    # -- one match -------------------------------------------------------

    def kind(self, rank: int, generation: int) -> str:
        u = ((rank + 3 * generation) % self.period + 0.5) / self.period
        return KINDS[int(np.searchsorted(self._cum, u, side="right"))]

    def life(self, seat: int, generation: int) -> Life:
        if not (0 <= seat < self.seats
                and 0 <= generation < self.generations):
            raise ValueError(
                f"the plan holds {self.generations} generations of "
                f"{self.seats} seats: no match ({seat}, {generation})")
        rank = int(self.rank_of[seat])
        length = None if generation == 0 else int(self.lengths[
            (29 * rank + 17 * generation + 7) % len(self.lengths)])
        return Life(generation * self.seats + seat, seat, generation,
                    self.kind(rank, generation), length)

    def first_end(self, seat: int) -> int:
        """The served frame generation 0 of ``seat`` ends in."""
        return int(self.residuals[self.rank_of[seat]])

    @property
    def matches(self) -> int:
        """Match ids are ``0 .. matches - 1``."""
        return self.seats * self.generations

    # -- the lifecycle, replayed -------------------------------------------

    def chain(self, seat: int, waits: Dict[int, int]
              ) -> Iterator[Tuple[Life, int, int, Optional[int]]]:
        """``(life, first, end, retired)`` down a seat's generations:
        ``first`` the frame the match was asked for (-1: before the
        window), ``end`` the frame it ends in, ``retired`` the frame the
        server lets go of it (None for a drop without a wait: nobody knows
        yet, and the chain stops there)."""
        first, end = -1, self.first_end(seat)
        for g in range(self.generations):
            life = self.life(seat, g)
            if g:
                end = first + life.length
            retired = end
            if life.kind == SILENT_DROP:
                wait = waits.get(life.match)
                retired = None if wait is None else end + int(wait)
            yield life, first, end, retired
            if retired is None:
                return
            first = retired + 1

    def ledger(self, waits: Dict[int, int], upto: int
               ) -> List[Tuple[int, int, int, int]]:
        """``(match, seat, first frame, last frame)`` of every match asked
        for up to served frame ``upto``, by match id: ``first`` -1 for the
        matches the window found live, ``last`` the frame of the
        retirement, -1 for a match that is still held at ``upto``."""
        rows = []
        for seat in range(self.seats):
            for life, first, _end, retired in self.chain(seat, waits):
                if first > upto:
                    break
                last = retired if retired is not None and retired <= upto \
                    else -1
                rows.append((life.match, seat, first, last))
        return sorted(rows)
