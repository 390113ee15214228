"""Loss in bursts, drawn from the seed: one ``ChaosPlan`` a link.

A traffic mix names the parameters (``benchmark/traffic/<mix>.json``,
``bursts``); this file turns them and ``--seed`` into the program's own
fault script (``bevy_ggrs_tpu.chaos.ChaosPlan`` of ``LossBurst``), which the
driver hands to a ``ChaosSocket`` on a far end's socket: while a burst
lasts, every datagram that far end sends is dropped.

As ``inputs.HeldKeys`` does for holds, every seed gets the same bursts in
another order: a block is ``block`` bursts whose lengths are the whole
frames ``length_frames[0] .. length_frames[1]`` in equal numbers and whose
gaps (end of one burst to the start of the next) are the ``block``
mid-quantiles of an exponential distribution of mean ``gap_mean_frames``.
Each link permutes a block's lengths and its gaps from (seed, link, block)
and starts at a phase of its own inside the first block. So two seeds, and
two links of one seed, differ in when the bursts fall, not in how many
frames are lost.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np


class LossBursts:
    """``schedule(link)`` is that link's ``[(start_frame, end_frame)]`` up
    to ``horizon_frames``; ``plan(link, dt)`` the same as a ``ChaosPlan``
    in seconds of the virtual clock."""

    def __init__(self, seed: int, params: dict):
        if params.get("kind") != "loss_bursts":
            raise ValueError(f"unknown burst generator {params.get('kind')!r}")
        self.seed = int(seed)
        self.rate = float(params["rate"])
        self.horizon = int(params["horizon_frames"])
        lo, hi = (int(x) for x in params["length_frames"])
        n = int(params["block"])
        mean = float(params["gap_mean_frames"])
        if lo < 1 or hi < lo or n < 1 or n % (hi - lo + 1) or mean <= 0:
            raise ValueError("bad burst lengths, block or gap")
        self.lengths = np.repeat(np.arange(lo, hi + 1), n // (hi - lo + 1))
        self.gaps = np.maximum(1, np.rint(np.asarray(
            [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        ))).astype(np.int64)
        self.block_frames = int(self.lengths.sum() + self.gaps.sum())

    def _block(self, link: int, b: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.Generator(np.random.PCG64([self.seed, link, b]))
        return rng.permutation(self.gaps), rng.permutation(self.lengths)

    def schedule(self, link: int) -> List[Tuple[int, int]]:
        phase = np.random.Generator(
            np.random.PCG64([self.seed, link, 0xB0257])
        ).integers(0, self.block_frames)
        out: List[Tuple[int, int]] = []
        t, b = -int(phase), 0
        while t < self.horizon:
            for gap, length in zip(*self._block(link, b)):
                start = t + int(gap)
                t = start + int(length)
                if t > 0 and start < self.horizon:
                    out.append((max(start, 0), t))
            b += 1
        return out

    def plan(self, link: int, dt: float):
        from bevy_ggrs_tpu.chaos import ChaosPlan, LossBurst

        return ChaosPlan(
            (self.seed * 1000003 + link) & 0x7FFFFFFF,
            # Half a frame early: a clock that adds dt frame by frame and
            # a product a * dt differ in the last bit, and the burst has to
            # hold frames a .. b - 1 whichever way that falls.
            tuple(LossBurst((a - 0.5) * dt, (b - 0.5) * dt, self.rate)
                  for a, b in self.schedule(link)),
        )
