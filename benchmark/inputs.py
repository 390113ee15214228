"""The one input generator: held keys, drawn from the seed.

A traffic mix names the parameters (``benchmark/traffic/<mix>.json``,
``inputs``); this file turns them and ``--seed`` into what every player of
every match presses on every frame. A player holds a key state for a
discretised log-normal number of frames and then draws the next state
uniformly from the masks allowed (for box_game the 9 of 16 with no opposing
pair). Streams are independent per (match, handle) and do not depend on how
far the table has been extended, so the same seed gives the same inputs
whatever the system's frame rate.

Every seed gets the same set of hold lengths in another order: a block of 64
holds is the 64 mid-quantiles of the clipped log-normal, permuted per stream
from the seed. So every stream changes keys equally often over a block (about
1050 frames at the parameters of the first mixes) and two seeds differ in
when the changes fall and what they change to, not in how much work they are.
"""

from __future__ import annotations

import statistics

import numpy as np

_BLOCK = 64  # holds drawn per stream per block


class HeldKeys:
    """``table(frames)`` is ``uint8[matches, players, >=frames]``;
    ``bits(match, frame, handle)`` reads one entry, extending on demand."""

    def __init__(self, seed: int, matches: int, players: int, params: dict):
        if params.get("kind") != "held_keys":
            raise ValueError(f"unknown input generator {params.get('kind')!r}")
        self.seed = int(seed)
        self.matches = int(matches)
        self.players = int(players)
        self.median = float(params["hold_median_frames"])
        self.sigma = float(params["hold_sigma"])
        self.lo, self.hi = (int(x) for x in params["hold_clip_frames"])
        self.masks = np.asarray(params["masks"], np.uint8)
        if self.lo < 1 or self.hi < self.lo or self.masks.size == 0:
            raise ValueError("bad hold clip or empty mask set")
        z = [statistics.NormalDist().inv_cdf((i + 0.5) / _BLOCK)
             for i in range(_BLOCK)]
        self._holds = np.clip(
            np.rint(np.exp(np.log(self.median) + self.sigma * np.asarray(z))),
            self.lo, self.hi).astype(np.int64)
        n = self.matches * self.players
        self._tab = np.zeros((n, 0), np.uint8)
        self._blocks = 0

    def _block(self, b: int) -> np.ndarray:
        n = self.matches * self.players
        holds_rng = np.random.Generator(np.random.PCG64([self.seed, b, 0]))
        state_rng = np.random.Generator(np.random.PCG64([self.seed, b, 1]))
        holds = holds_rng.permuted(np.tile(self._holds, (n, 1)), axis=1)
        states = self.masks[state_rng.integers(0, self.masks.size,
                                               (n, _BLOCK))]
        # Every stream's block covers the same frames: the set's sum.
        return np.repeat(states.reshape(-1), holds.reshape(-1)).reshape(n, -1)

    def table(self, frames: int) -> np.ndarray:
        while self._tab.shape[1] < frames:
            self._tab = np.concatenate(
                [self._tab, self._block(self._blocks)], axis=1
            )
            self._blocks += 1
        return self._tab.reshape(self.matches, self.players, -1)

    def bits(self, match: int, frame: int, handle: int) -> np.uint8:
        if frame >= self._tab.shape[1]:
            self.table(frame + 1)
        return self._tab[match * self.players + handle, frame]


def network_seed(seed: int) -> int:
    """The loopback network's RNG seed (numpy RandomState: 32 bits)."""
    return (int(seed) * 2654435761 + 0x9E3779B9) % (2**32)
