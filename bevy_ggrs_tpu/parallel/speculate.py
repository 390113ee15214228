"""Speculative branch batching: the serial replay loop, turned into a batch axis.

The reference predicts remote inputs with exactly ONE hypothesis —
repeat-last-input — and pays a serial ``max_prediction``-deep replay when it
is wrong (`/root/reference/src/ggrs_stage.rs:259-269`; GGPO prediction policy
per survey §2.2). On TPU the marginal cost of more hypotheses is ~zero:
``vmap`` the fused rollout over B candidate input branches, shard the branch
axis across the device mesh, and when real inputs arrive pick the branch
whose prefix matches — misprediction recovery becomes a *select*, not a
resimulation.

Pipeline:

1. :func:`enumerate_branches` — build the candidate input tensor
   ``bits[B, F, P, …]``. Branch 0 is always the reference's own policy
   (repeat last confirmed input), so the speculative engine strictly
   dominates the reference: its prediction is one of ours.
2. :class:`SpeculativeExecutor` — one jitted device call rolls every branch
   forward F frames from the same start state, ring-saving each frame
   per-branch and streaming per-branch-per-frame checksums.
3. :func:`match_branch` — host-side: longest-prefix match of confirmed
   inputs against the branch tensor.
4. :meth:`SpeculativeExecutor.commit` — gather the matched branch's
   ring/state (one cross-device gather when sharded) and merge its saved
   frames into the session's main snapshot ring.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.obs.trace import Instrumented
from bevy_ggrs_tpu.schedule import PREDICTED, Schedule
from bevy_ggrs_tpu.state import SnapshotRing, WorldState
from bevy_ggrs_tpu.rollout import rollout_branches

# A branch sampler maps (key, last_bits[P, …], B, F) -> bits[B, F, P, …]:
# the Monte Carlo input tree (survey §7 "branch selection policy").
BranchSampler = Callable[[jax.Array, jnp.ndarray, int, int], jnp.ndarray]


def repeat_last_sampler(key, last_bits, num_branches: int, num_frames: int):
    """Every branch repeats the last input — degenerate tree, reference
    parity (all branches identical; useful as a baseline)."""
    del key
    return jnp.broadcast_to(
        last_bits[None, None], (num_branches, num_frames) + last_bits.shape
    )


def bitmask_sampler(
    num_bits: int = 4, keep_prob: float = 0.5
) -> BranchSampler:
    """Monte Carlo tree over ``u8``-bitmask inputs (box_game-style).

    Per branch/frame/player: with ``keep_prob`` keep the previous frame's
    input (players hold keys across frames far more often than not), else
    draw a uniform random mask over the low ``num_bits`` bits. Branch 0 is
    pinned to repeat-last so the engine always contains the reference's
    prediction.
    """

    def sample(key, last_bits, num_branches: int, num_frames: int):
        kk, km = jax.random.split(key)
        shape = (num_branches, num_frames) + last_bits.shape
        keep = jax.random.bernoulli(kk, keep_prob, shape)
        rand = jax.random.randint(km, shape, 0, 1 << num_bits, dtype=jnp.int32)

        def scan_frame(prev, xs):
            k, r = xs  # [B, P...]
            cur = jnp.where(k, prev, r.astype(last_bits.dtype))
            return cur, cur

        init = jnp.broadcast_to(last_bits, (num_branches,) + last_bits.shape)
        _, bits = jax.lax.scan(
            scan_frame, init, (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(rand, 1, 0))
        )
        bits = jnp.moveaxis(bits, 0, 1)  # [B, F, P, …]
        base = jnp.broadcast_to(
            last_bits[None, None], (1, num_frames) + last_bits.shape
        ).astype(last_bits.dtype)
        return jnp.concatenate([base, bits[1:]], axis=0)

    return sample


def enumerate_branches(
    key,
    last_bits,
    num_branches: int,
    num_frames: int,
    sampler: Optional[BranchSampler] = None,
) -> jnp.ndarray:
    """Candidate input tensor ``[B, F, P, …]``; branch 0 = repeat-last."""
    last_bits = jnp.asarray(last_bits)
    if sampler is None:
        sampler = repeat_last_sampler
    return sampler(key, last_bits, num_branches, num_frames)


def match_branch(
    branch_bits: np.ndarray, confirmed_bits: np.ndarray
) -> Tuple[int, int]:
    """Longest-prefix match: which branch predicted the confirmed inputs?

    ``branch_bits[B, F, P, …]`` vs ``confirmed_bits[K, P, …]`` (K ≤ F
    confirmed frames). Returns ``(branch, depth)``: the branch agreeing with
    the most leading confirmed frames, and how many frames agree. A full
    match (``depth == K``) means the session can reuse that branch's states
    outright; a partial match still skips ``depth`` frames of resimulation.
    Ties break toward branch 0 (the repeat-last baseline).

    Byte-comparable (integer/bool) tensors take the native prefix matcher
    (one ctypes call, no ``[B, K, …]`` comparison tensor); anything else —
    or a core that didn't load — keeps the NumPy path. Both are
    bitwise-identical (tests/test_native_spec.py).
    """
    bb = np.asarray(branch_bits)
    cb = np.asarray(confirmed_bits)
    k = cb.shape[0]
    if k == 0:
        return 0, 0
    from bevy_ggrs_tpu.native import spec as native_spec

    got = native_spec.match_prefix(bb, cb)
    if got is not None:
        return got
    return _match_branch_numpy(bb, cb, k)


def _match_branch_numpy(
    bb: np.ndarray, cb: np.ndarray, k: int
) -> Tuple[int, int]:
    """Pure-NumPy :func:`match_branch` body (native-parity oracle)."""
    eq = bb[:, :k].reshape(bb.shape[0], k, -1) == cb.reshape(1, k, -1)
    frame_ok = eq.all(axis=2)  # [B, K]
    # Depth of agreement = leading run of True per branch.
    depth = np.where(
        frame_ok.all(axis=1), k, frame_ok.argmin(axis=1)
    )
    best = int(depth.argmax())  # argmax ties break low → branch 0
    return best, int(depth[best])


@dataclasses.dataclass
class SpecResult:
    """One speculative rollout: B branches × F frames from one start state.

    ``rings``/``states`` have a leading branch axis on every leaf;
    ``checksums[B, F, 2]`` is the per-branch stream of saved-frame two-lane
    (lo/hi 64-bit) checksums;
    ``branch_bits`` is the input tensor that produced it (kept for
    :func:`match_branch`); ``start_frame`` labels the first saved frame.
    """

    rings: SnapshotRing
    states: WorldState
    checksums: jnp.ndarray
    branch_bits: Any
    start_frame: int
    num_frames: int


class SpeculativeExecutor(Instrumented):
    """Jit-compiled B-branch × F-frame rollout bound to one schedule + shapes.

    With a mesh, the branch axis is laid out over the mesh's ``branch`` axis
    (data-parallel: zero cross-device traffic during the rollout; XLA inserts
    one gather at :meth:`commit`). Without a mesh everything runs on the
    default device.
    """

    def __init__(
        self,
        schedule: Schedule,
        num_branches: int,
        max_frames: int,
        mesh=None,
        branch_axis: str = "branch",
        entity_axis: Optional[str] = None,
        state_template: Optional[WorldState] = None,
        tracer=None,
    ):
        """With ``mesh`` alone, the branch axis is data-parallel across all
        devices. Adding ``entity_axis`` (+ a ``state_template`` for leaf
        structure) also splits the world's entity/capacity axis over that
        mesh axis — the model-parallel analog for entity-coupled systems
        (boids all-pairs forces): annotate, and GSPMD inserts the
        gathers/reductions over ICI.
        """
        self.schedule = schedule
        self.num_branches = int(num_branches)
        self.max_frames = int(max_frames)
        self.mesh = mesh
        self.branch_axis = branch_axis
        self.entity_axis = entity_axis
        self._set_sinks(tracer=tracer)

        run = functools.partial(self._run_impl, schedule)
        commit = self._commit_impl
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            from bevy_ggrs_tpu.parallel.sharding import (
                branch_pspec,
                replicated,
                world_and_ring_shardings,
            )

            spec_b = branch_pspec(mesh, branch_axis)
            rep = replicated(mesh)
            if entity_axis is not None:
                if state_template is None:
                    raise ValueError(
                        "entity_axis sharding needs a state_template"
                    )
                state_in, _ = world_and_ring_shardings(
                    state_template, mesh, entity_axis
                )
                states_out, rings_out = world_and_ring_shardings(
                    state_template, mesh, entity_axis, prefix=(branch_axis,)
                )
                self._run = jax.jit(
                    run,
                    in_shardings=(state_in, rep, spec_b, rep),
                    out_shardings=(rings_out, states_out, spec_b),
                )
                # Let GSPMD pick commit's output layout (entity stays split).
                self._commit = jax.jit(commit)
            else:
                # state, frame, bits, status replicated in; branch-stacked out.
                self._run = jax.jit(
                    run,
                    in_shardings=(rep, rep, spec_b, rep),
                    out_shardings=(spec_b, spec_b, spec_b),
                )
                self._commit = jax.jit(commit, out_shardings=rep)
        else:
            self._run = jax.jit(run)
            self._commit = jax.jit(commit)

    @staticmethod
    def _run_impl(schedule, state, start_frame, branch_bits, status):
        """All-branch rollout. Each branch: (save, advance) × F from the
        same state — identical semantics to F serial
        SaveGameState/AdvanceFrame request pairs per branch, its ring in
        step order and its rows in their own shapes (``rollout.py``
        ``rollout_branches`` without a form)."""
        return rollout_branches(
            schedule, state, start_frame, branch_bits, status
        )

    @staticmethod
    def _commit_impl(tree, branch):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, branch, 0, keepdims=False),
            tree,
        )

    # ------------------------------------------------------------------

    def run(
        self,
        state: WorldState,
        start_frame: int,
        branch_bits,
        status=None,
    ) -> SpecResult:
        """Roll all branches forward from ``state`` at ``start_frame``.

        ``branch_bits[B, F, P, …]`` (see :func:`enumerate_branches`);
        ``status[F, P]`` defaults to all-PREDICTED (speculative frames are by
        definition unconfirmed).
        """
        branch_bits = jnp.asarray(branch_bits)
        b, f = branch_bits.shape[0], branch_bits.shape[1]
        if b != self.num_branches or f != self.max_frames:
            raise ValueError(
                f"branch_bits [{b}, {f}, …] != configured "
                f"[{self.num_branches}, {self.max_frames}, …]"
            )
        num_players = branch_bits.shape[2]
        if status is None:
            status = jnp.full((f, num_players), PREDICTED, dtype=jnp.int32)
        with self.span("spec_branch_dispatch", branches=b, frames=f):
            rings, states, checksums = self._run(
                state, jnp.asarray(start_frame, jnp.int32), branch_bits,
                jnp.asarray(status, jnp.int32),
            )
        return SpecResult(
            rings=rings,
            states=states,
            checksums=checksums,
            branch_bits=branch_bits,
            start_frame=int(start_frame),
            num_frames=f,
        )

    def commit(self, result: SpecResult, branch: int):
        """Gather branch ``branch``'s (ring, state) — the confirmed-branch
        select + scatter-back (survey §2.3). One collective gather when the
        branch axis is sharded."""
        with self.span("spec_branch_commit"):
            branch = jnp.asarray(branch, jnp.int32)
            ring = self._commit(result.rings, branch)
            state = self._commit(result.states, branch)
            return ring, state


def merge_rings(main: SnapshotRing, spec: SnapshotRing) -> SnapshotRing:
    """Overlay the frames ``spec`` saved (a committed speculative ring: its
    rows stand in step order, ``state.py`` ``ring_of_steps``) onto the
    session's persistent ring: each saved row (``frames >= 0``) goes where
    its label says, row ``frame % depth``; untouched rows keep ``main``'s
    history. Rings must share depth."""
    if main.depth != spec.depth:
        raise ValueError(f"ring depth mismatch: {main.depth} != {spec.depth}")
    # hit[r, t]: row ``t`` of ``spec`` holds the frame that belongs in row
    # ``r`` of ``main``.
    hit = (spec.frames >= 0) & (
        jnp.remainder(spec.frames, main.depth)
        == jnp.arange(main.depth, dtype=jnp.int32)[:, None]
    )
    take, src = hit.any(axis=1), hit.argmax(axis=1)

    def sel(s, m):
        mask = take.reshape((-1,) + (1,) * (s.ndim - 1))
        return jnp.where(mask, s[src], m)

    return SnapshotRing(
        states=jax.tree_util.tree_map(sel, spec.states, main.states),
        frames=sel(spec.frames, main.frames),
        checksums=sel(spec.checksums, main.checksums),
    )
