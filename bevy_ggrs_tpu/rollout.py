"""Fused rollback/resimulation rollouts.

The reference executes a misprediction-recovery burst serially on the host:
``handle_requests`` walks ``[LoadGameState(F_c), SaveGameState(F_c),
AdvanceFrame(i_c), …, SaveGameState(F_now), AdvanceFrame(i_now)]`` one request
at a time, each save a deep reflective clone and each advance a full schedule
run (``/root/reference/src/ggrs_stage.rs:259-306``) — up to ``max_prediction``
(12) restore+resimulate cycles inside one render frame.

Here the whole burst is ONE device call: a loop over the frame axis of a
padded input tensor, with the snapshot ring save folded into each step and
per-frame checksums streamed out. The host only receives the checksums (the
session's desync/synctest signal — reference hands ggrs exactly that,
``ggrs_stage.rs:282-283``); ring and world state never leave HBM.

Bursts are padded to a fixed ``max_frames`` with a validity mask so every
burst length hits the same compiled executable (static shapes — no
per-depth recompiles). Invalid steps are identity: no state advance, no ring
write, checksum reported as 0. Still one executable, and the padding costs
nothing on the device: a burst whose caller hands over its live prefix
(:func:`live_steps`: one past the last step any mask sets) loops that
TRACED number of steps, not ``max_frames``; the count is an operand, not a
shape. A speculative rollout has no masks and no ring to write into: every
one of its steps is live, its trip count is its shape, and it is a scan
whose rows leave as ``ys`` (:func:`rollout_steps`).

A rollout of a whole TREE has a third shape where a step costs much more
than moving its row (:func:`share_width`): still that scan over the frames,
its ``ys`` every branch's row in step order, but a level of it loops over
the tree's CLASSES and not over its branches. The default tree makes every
branch a copy of a base up to the frame where one player changes one
control, so until that frame a branch's states, rows and checksums are the
base's bit for bit: 55 of 8 x 8 (branch, frame) prefixes are distinct with
both players free, 421 of 128 x 8. A level steps one world a distinct
prefix (:func:`prefix_classes`, from ``branch_bits`` alone), a static width
at a time inside a loop whose trip count is an operand like a burst's, and
hands every branch its class's result (:func:`_rollout_shared`). The rows,
states and checksums are the plain ``vmap``'s; a tree with more shared
structure costs less instead of the same.

The save-before-advance ordering and the "save is labeled with the current
frame" invariant (``ggrs_stage.rs:277``'s ``assert_eq!(self.frame, frame)``)
are preserved: step ``t`` saves frame ``start_frame + t`` then advances with
that frame's inputs.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bevy_ggrs_tpu.schedule import PlayerInputs, Schedule
from bevy_ggrs_tpu.state import (
    FLAT_ROW_BYTES, IN_PLACE_ROW_BYTES, ONCE, SHAPED, STEPS, SnapshotRing,
    WorldState, active_checksum, in_place_writes, large_row, ring_load,
    ring_of_steps, ring_row_lowerings, ring_row_read, ring_row_write,
    ring_rows_flat, ring_rows_shaped, ring_save, row_in_tiles, state_row,
    state_shaped,
)


def deepest_lane(
    n: jnp.ndarray, lane_axis: Optional[str] = None
) -> jnp.ndarray:
    """The trip count of a loop whose lane asks for ``n`` steps: ``n``
    itself, and under a ``vmap`` whose axis is named ``lane_axis`` the
    deepest lane's, ONE scalar for the dispatch: a count that differs per
    lane would batch the loop's predicate, and the loop would then run to
    the deepest lane all the same and select its whole carry, rings
    included, a step."""
    return n if lane_axis is None else jax.lax.pmax(n, lane_axis)


def live_steps(
    save_mask: jnp.ndarray,  # bool[max_frames]
    adv_mask: jnp.ndarray,  # bool[max_frames]
    lane_axis: Optional[str] = None,
) -> jnp.ndarray:
    """One past the last step at which either mask is set: what a burst has
    to run, every later step being padding (:func:`deepest_lane`'s under
    the slot ``vmap``)."""
    steps = jnp.arange(1, save_mask.shape[0] + 1, dtype=jnp.int32)
    n = jnp.max(jnp.where(save_mask | adv_mask, steps, 0))
    return deepest_lane(n, lane_axis)


def rollout_burst(
    schedule: Schedule,
    ring: SnapshotRing,
    state: WorldState,
    start_frame: jnp.ndarray,
    bits: jnp.ndarray,  # [max_frames, num_players, *input_shape]
    status: jnp.ndarray,  # int32[max_frames, num_players]
    save_mask: jnp.ndarray,  # bool[max_frames]
    adv_mask: jnp.ndarray,  # bool[max_frames]
    n_run: jnp.ndarray,  # int32[], from ``live_steps``
) -> Tuple[SnapshotRing, WorldState, jnp.ndarray]:
    """Execute up to ``max_frames`` (save?, advance?) steps as one fused loop.

    Step ``t``: if ``save_mask[t]``, save ``state`` as the current frame into
    the ring; if ``adv_mask[t]``, ``state = schedule(state, inputs[t])`` and
    the frame counter increments. Steps with both masks False are padding.
    Spectators advance without ever saving (`ggrs_stage.rs:195-211` never
    emits saves), hence the separate masks.

    ``n_run`` (:func:`live_steps` of the masks) makes the loop run that many
    steps and no more: the steps it leaves out are the identity, so every
    output is bit for bit what all ``max_frames`` steps give.

    Returns ``(ring, state, checksums[max_frames])`` with ``checksums[t]``
    the saved checksum at step ``t`` (0 where ``save_mask[t]`` is False).
    """
    start_frame = jnp.asarray(start_frame, dtype=jnp.int32)
    # Large rows ride the loop flat, as whole lane tiles where they are such
    # (``state.py`` ``FLAT_ROW_BYTES``, ``ring_rows_flat``).
    shaped, ring = ring, ring_rows_flat(ring)
    tiled = sum(
        row_in_tiles(x, 1) for x in jax.tree_util.tree_leaves(ring.states)
    )
    visits = in_place_writes[0]
    xs = (bits, status, save_mask, adv_mask)

    def step(t, loop):
        ring, state, frame, checksums = loop
        # Row ``t`` by ``state.py``'s row access: one dynamic slice under
        # the slot ``vmap`` too, since every lane is at the same step.
        b, s, sv, adv = jax.tree_util.tree_map(
            lambda x: ring_row_read(x, t), xs
        )
        ring, cs = ring_save(ring, state, frame, sv)  # no-op where not sv
        cs = jnp.where(sv, cs, jnp.uint32(0))
        advanced = schedule(state, PlayerInputs(bits=b, status=s))
        state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(adv, new, old), advanced, state
        )
        return (ring, state, frame + adv.astype(jnp.int32),
                ring_row_write(checksums, cs, t))

    ring, state, _, checksums = jax.lax.fori_loop(
        0, n_run, step,
        (ring, state, start_frame,
         jnp.zeros((save_mask.shape[0], 2), jnp.uint32)),
    )
    if in_place_writes[0] > visits:  # the saves' index was a lane's own
        ring_row_lowerings["in_place"] += tiled
    return ring_rows_shaped(ring, shaped), state, checksums


def rollout_steps(
    schedule: Schedule,
    state: WorldState,
    start_frame: jnp.ndarray,
    bits: jnp.ndarray,  # [frames, num_players, *input_shape]
    status: jnp.ndarray,  # int32[frames, num_players]
) -> Tuple[SnapshotRing, WorldState, jnp.ndarray]:
    """A speculative rollout of ONE branch: ``frames`` (save, advance)
    steps from ``state`` at ``start_frame``, every one live: the same
    states and checksums as that many serial ``SaveGameState`` /
    ``AdvanceFrame`` pairs. Returns ``(ring, state, checksums[frames])``
    with the ring in STEP order (``state.py`` ``ring_of_steps``: row ``t``
    is the state entering frame ``start_frame + t``) and AS WRITTEN: a
    large row flat (``state.py`` ``FLAT_ROW_BYTES``: the stacked rows are a
    loop-carried buffer like a burst's ring), a small one as it is.

    No ring is carried: the state entering a step and its checksum leave
    the loop at the loop's own counter, which every lane of every ``vmap``
    shares, so each row is written once, as one slice, whoever's frame it
    is, and nothing behind the loop writes it again here. Whoever wants
    the rows in their own shapes says so (:func:`rollout_branches` without
    a form; ``state.py`` ``state_shaped`` for one row)."""

    def body(state, xs):
        b, s = xs
        saved = (state_row(state), active_checksum(state))
        return schedule(state, PlayerInputs(bits=b, status=s)), saved

    final, (rows, checksums) = jax.lax.scan(body, state, (bits, status))
    return ring_of_steps(rows, start_frame, checksums), final, checksums


def branch_reached(schedule: Schedule, state: WorldState, inputs) -> list:
    """Which leaves of a state a branch's inputs reach over a rollout, a
    bool a leaf in tree order: jax's own finding, not the title's word.
    ONE abstract trace of the rollout under the branch ``vmap``, each
    final-state leaf (a scan carries a leaf batched or not as a whole: its
    rows and its end alike) passed through a ``custom_vmap`` identity whose
    rule is only ever called for a batched operand. ``state`` and
    ``inputs`` (one frame's ``[P, *input_shape]`` rows) give shapes and
    dtypes only."""
    reached = [False] * len(jax.tree_util.tree_leaves(state))

    def told(i):
        probe = jax.custom_batching.custom_vmap(lambda x: x)

        @probe.def_vmap
        def rule(axis_size, in_batched, x):
            reached[i] = True
            return x, True

        return probe

    def finals(state, branch_bits):
        def final_of(bits):
            final = rollout_steps(
                schedule, state, jnp.int32(0), bits,
                jnp.zeros(bits.shape[:2], jnp.int32),
            )[1]
            return [
                told(i)(x)
                for i, x in enumerate(jax.tree_util.tree_leaves(final))
            ]

        return jax.vmap(final_of)(branch_bits)

    counted = dict(ring_row_lowerings)  # this trace is nobody's program
    jax.eval_shape(
        finals,
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        ),
        jax.ShapeDtypeStruct((2, 2) + tuple(inputs.shape), inputs.dtype),
    )
    ring_row_lowerings.update(counted)
    return reached


def rollout_form(
    schedule: Schedule,
    state: WorldState,  # shapes and dtypes of one state
    inputs,  # shape and dtype of one frame's ``[P, *input_shape]`` rows
):
    """How a rollout of ``schedule`` from such a state is carried (a kind a
    state leaf, ``state.py`` ``SHAPED`` / ``STEPS`` / ``ONCE``; None where
    no row reaches ``FLAT_ROW_BYTES``: the shaped form throughout).

    Whether a leaf depends on the branch is :func:`branch_reached`'s
    finding. Without ``inputs`` the rollout cannot be traced ahead of the
    carry and every large leaf keeps its branch axis."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    large = [large_row(x) for x in leaves]
    if not any(large):
        return None
    batched = [True] * len(leaves)
    if inputs is not None:
        batched = branch_reached(schedule, state, inputs)
    return jax.tree_util.tree_unflatten(treedef, [
        SHAPED if not big else STEPS if dep else ONCE
        for big, dep in zip(large, batched)
    ])


def share_width(form, state: WorldState, num_branches: int) -> Optional[int]:
    """How many distinct input prefixes one iteration of a level's loop
    steps where a rollout carried as ``form`` from such a state steps each
    prefix of its tree once (:func:`_rollout_shared`); None where it steps
    every branch every frame (the plain ``vmap`` of the scan).

    Decided from shapes alone, here and nowhere else, beside
    :func:`rollout_form`, which decides the carried form from the same
    numbers. Sharing moves each world's row twice a level, so it pays
    where a step costs much more than moving its row: a state with a row
    of ``FLAT_ROW_BYTES`` or more steps a thousand entities and more a
    leaf; one with a row of ``IN_PLACE_ROW_BYTES`` or more is a state
    whose step IS the movement of its rows; one with neither (``form``
    None) steps a level of branches in the time of a few loop iterations
    (``PERF.md`` section 6, PR 58). The width is the tree's: 1 at 8
    branches, about an eighth of the branches above (16 at 128), a
    divisor of it."""
    if form is None:
        return None
    largest = max(
        int(np.prod(x.shape, dtype=np.int64)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(state)
    )
    if not FLAT_ROW_BYTES <= largest < IN_PLACE_ROW_BYTES:
        return None
    most = max(1, num_branches // 8)
    return max(w for w in range(1, most + 1) if num_branches % w == 0)


def prefix_classes(
    branch_bits: jnp.ndarray,  # [B, frames, num_players, *input_shape]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The classes of a tree's branches, level by level: branch ``b`` at
    level ``f`` belongs with the lowest ``b'`` whose bits equal its own on
    frames ``0..f`` (bit patterns: a ``-0.0`` is not a ``0.0``). Returns
    ``(order[frames, B], slot[frames, B], n[frames])``: a level has
    ``n[f]`` classes, ``order[f, k]`` is the representative of class ``k``
    (classes in the order of their representatives; 0 from ``n[f]`` on),
    ``slot[f, b]`` the class of branch ``b``. ``branch_tree.py``
    ``distinct_prefixes`` is the host's twin of the count."""
    B, F = branch_bits.shape[:2]
    if not jnp.issubdtype(branch_bits.dtype, jnp.integer):
        branch_bits = jax.lax.bitcast_convert_type(
            branch_bits, jnp.dtype(f"uint{8 * branch_bits.dtype.itemsize}")
        )
    bits = branch_bits.reshape(B, F, -1)
    differ = jnp.any(bits[:, None] != bits[None, :], axis=-1)  # [B, B, F]
    same = jnp.cumsum(differ.astype(jnp.int32), axis=-1) == 0
    first = jnp.argmax(same, axis=1).astype(jnp.int32)  # [B, F]
    ids = jnp.arange(B, dtype=jnp.int32)
    leads = first == ids[:, None]  # [B, F]: b is its class's lowest
    rank = jnp.cumsum(leads.astype(jnp.int32), axis=0) - 1
    order = jnp.sum(
        jnp.where(
            leads[None] & (rank[None] == ids[:, None, None]),
            ids[None, :, None], 0,
        ),
        axis=1,
    )  # [K, F]
    slot = jnp.take_along_axis(rank, first, axis=0)
    return order.T, slot.T, rank[-1] + 1


def _rows_at(rows: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """``rows[index]`` along the leading (branch or class) axis: a lane's
    own gather under the slot ``vmap``, of large rows flat (``state.py``
    ``FLAT_ROW_BYTES``: lane-dense whatever the row's own last axis; the
    way there and back moves no byte in the layout the loops carry)."""
    flat = jnp.take(jax.vmap(state_row)(rows), index, axis=0, mode="clip")
    return state_shaped(flat, rows[0], lead=1)


def _rollout_shared(
    schedule, state, start_frame, branch_bits, status, form, W, lane_axis
):
    """:func:`rollout_branches` in the carried form, a level stepping one
    world a class of :func:`prefix_classes`: the frame axis is the scan it
    is in :func:`rollout_steps` (its ``ys`` the rows every branch enters
    the frame with, in step order), and a level picks each class's
    representative row, steps the representatives ``W`` at a time inside
    a loop that runs as often as the classes ask for (the deepest lane's,
    under the slot ``vmap``: :func:`deepest_lane`) and hands every branch
    of a class the chunk stepped its class's result. A leaf no branch's
    inputs reach (:func:`branch_reached`) has no branch axis in the loop
    and is stepped once a level. Bit for bit the plain ``vmap``'s rows,
    states and checksums: a step is a function of its world and its
    inputs."""
    B, F = branch_bits.shape[:2]
    rows, treedef = jax.tree_util.tree_flatten(state)
    kinds = jax.tree_util.tree_leaves(form)
    reached = branch_reached(schedule, state, branch_bits[0, 0])
    if any(k == ONCE and r for k, r in zip(kinds, reached)):
        raise ValueError("a leaf carried without a branch axis has one")
    # (a leaf carried with a branch axis keeps it in the loop, reached or
    # not: a form made without the inputs' shape)
    dep = [r or k == STEPS for k, r in zip(kinds, reached)]
    order, slot, n = prefix_classes(branch_bits)
    trips = deepest_lane(-(-n // W), lane_axis)
    of = lambda rows, d: [r for r, dd in zip(rows, dep) if dd == d]
    every = lambda x: jnp.broadcast_to(x, (B,) + x.shape)

    def world_of(with_axis, without):
        a, b = iter(with_axis), iter(without)
        return treedef.unflatten([next(a) if d else next(b) for d in dep])

    def step(with_axis, without, bits, st):
        """The leaves one world leaves a frame with."""
        # The barriers keep the loop's slicing out of the step's fusions:
        # which products and sums a backend contracts follows its fusions,
        # and the rows have to be the serial step's bit for bit.
        world = jax.lax.optimization_barrier(world_of(with_axis, without))
        return jax.tree_util.tree_leaves(jax.lax.optimization_barrier(
            schedule(world, PlayerInputs(bits=bits, status=st))
        ))

    def level(x, xs):
        with_axis, without = x
        bits, st, order_f, slot_f, trips_f = xs
        heads = [_rows_at(r, order_f) for r in with_axis]
        head_bits = _rows_at(bits, order_f)

        def chunk(i, leaves):
            cut = lambda r: jax.lax.dynamic_slice_in_dim(r, i * W, W, 0)
            new = jax.vmap(lambda rows, b: step(rows, without, b, st))(
                [cut(r) for r in heads], cut(head_bits)
            )
            # Every branch of a class this chunk stepped takes its class's
            # result: one select over the level's rows where they lie. (A
            # write of the chunk at ``i`` into ``[B, *row]``, gathered to
            # the branches behind the loop, puts ``B`` in a tile's sublanes
            # under the slot ``vmap``: 14.6 us a leaf an iteration on the
            # chip against this pass's 2-9; ``PERF.md`` section 6, PR 58.)
            local = slot_f - i * W
            mine = (local >= 0) & (local < W)
            return [
                jnp.where(
                    mine.reshape((B,) + (1,) * (old.ndim - 1)),
                    r if W == 1 else _rows_at(r, jnp.clip(local, 0, W - 1)),
                    old,
                )
                for old, r in zip(leaves, of(new, True))
            ]

        # (every branch's class is stepped by some chunk, so what the loop
        # starts from is never read: the rows the level was entered with)
        stepped = jax.lax.fori_loop(0, trips_f, chunk, with_axis)
        if without:  # any branch's step says what these become
            once = of(
                step([r[0] for r in with_axis], without, bits[0], st), False
            )
        else:
            once = []
        # (the checksums every branch enters with: a pass over the level,
        # outside the loop, whose iterations are the many)
        enters = lambda rows: active_checksum(world_of(rows, without))
        cs = jax.vmap(enters)(with_axis) if with_axis else every(enters([]))
        return (
            (stepped, once),
            (
                [jax.vmap(state_row)(r) for r in with_axis],
                state_row(without), cs,
            ),
        )

    (ends, once_ends), (steps, once_steps, cs) = jax.lax.scan(
        level,
        ([every(r) for r in of(rows, True)], of(rows, False)),
        (jnp.moveaxis(branch_bits, 1, 0), status, order, slot, trips),
    )
    a, b = iter(zip(steps, ends)), iter(zip(once_steps, once_ends))
    ring_rows, finals = [], []
    for kind, d in zip(kinds, dep):
        r, end = next(a) if d else next(b)
        if kind == SHAPED:  # ``[B, frames, *row]``, as the plain vmap's
            r = jnp.moveaxis(r, 1, 0) if d else every(r)
        ring_rows.append(r)
        finals.append(end if d or kind == ONCE else every(end))
    ring_row_lowerings["step"] += len(rows)
    cs = jnp.moveaxis(cs, 1, 0)
    frames = jnp.asarray(start_frame, jnp.int32) + jnp.arange(
        F, dtype=jnp.int32
    )
    return (
        SnapshotRing(
            states=treedef.unflatten(ring_rows), frames=every(frames),
            checksums=cs,
        ),
        treedef.unflatten(finals),
        cs,
    )


def rollout_branches(
    schedule: Schedule,
    state: WorldState,
    start_frame: jnp.ndarray,
    branch_bits: jnp.ndarray,  # [B, frames, num_players, *input_shape]
    status: jnp.ndarray,  # int32[frames, num_players]
    form=None,  # :func:`rollout_form`'s answer for this schedule and state
    lane_axis: Optional[str] = None,
) -> Tuple[SnapshotRing, WorldState, jnp.ndarray]:
    """:func:`rollout_steps` of every branch of ``branch_bits`` from the
    same ``state``: ``(rings, states, checksums[B, frames])``.

    With ``form`` the rings and states come in the form the tick carries
    them between dispatches (``state.py``, "A ROLLOUT's branch ring is not
    a ring"): the branch ``vmap`` names each leaf's branch axis where the
    scan's batching rule already put it (a ``STEPS`` leaf ``[frames, B,
    n]``: the loop's ``ys`` buffer itself) or names none (a ``ONCE`` leaf
    ``[frames, n]``, its final state ``[*row]``: computed once by the loop,
    and not broadcast here), so no operation stands between the loop and
    the caller that writes a large leaf's bytes again. Where
    :func:`share_width` says so the same rows come from a scan whose
    level loops over its classes (:func:`_rollout_shared`; ``lane_axis``
    names the slot ``vmap``'s axis, as for a burst). Without ``form``,
    every leaf is ``[B, frames, *row]`` in its own shape (the form a mesh
    lays out, and every reader off the serving loop is handed)."""
    one = lambda bits: rollout_steps(schedule, state, start_frame, bits, status)
    if form is None:
        rings, states, checksums = jax.vmap(one)(branch_bits)
        shaped = state_shaped(rings.states, state, lead=2)
        return rings.replace(states=shaped), states, checksums
    kinds = jax.tree_util.tree_leaves(form)
    ring_row_lowerings["carried"] += sum(k != SHAPED for k in kinds)
    ring_row_lowerings["carried_once"] += sum(k == ONCE for k in kinds)
    width = share_width(form, state, branch_bits.shape[0])
    if width is not None:
        return _rollout_shared(
            schedule, state, start_frame, branch_bits, status, form, width,
            lane_axis,
        )
    at = {SHAPED: 0, STEPS: 1, ONCE: None}
    return jax.vmap(one, out_axes=(
        SnapshotRing(
            states=jax.tree_util.tree_map(at.get, form), frames=0,
            checksums=0,
        ),
        jax.tree_util.tree_map(lambda kind: None if kind == ONCE else 0, form),
        0,
    ))(branch_bits)


class RolloutExecutor:
    """Jit-compiled request-burst executor bound to one schedule + shapes.

    The session drivers translate their ``GGRSRequest`` lists (reference
    ``ggrs_stage.rs:259-269``) into at most one ``run()`` per
    ``advance_frame`` — the fusion that replaces the reference's serial
    request loop. Bursts always pad to ``max_frames`` so every call hits the
    same compiled executable.

    ``max_frames`` should be ``max_prediction + 2`` so the deepest possible
    rollback (load + full-window resimulate + the new frame) still fits one
    call.
    """

    def __init__(self, schedule: Schedule, max_frames: int, mesh=None,
                 entity_axis: str = "entity", state_template=None):
        """With ``mesh`` + ``state_template``, the world's entity/capacity
        axis is split over ``mesh``'s ``entity_axis`` for every call — the
        serial-path analog of the SpeculativeExecutor's entity sharding:
        world and ring stay distributed across chips for the whole session,
        GSPMD inserting collectives inside entity-coupled systems. Bitwise
        caveat: integer state and the checksum (a wrapping sum, exactly
        order-independent) match the unsharded layout; float reductions
        inside user systems may round differently per layout
        (docs/determinism.md)."""
        self.schedule = schedule
        self.max_frames = int(max_frames)
        run = functools.partial(self._run_impl, schedule)
        if mesh is not None:
            if state_template is None:
                raise ValueError("mesh sharding needs a state_template")
            from bevy_ggrs_tpu.parallel.sharding import (
                replicated,
                world_and_ring_shardings,
            )

            state_s, ring_s = world_and_ring_shardings(
                state_template, mesh, entity_axis
            )
            rep = replicated(mesh)
            self._fn = jax.jit(
                run,
                in_shardings=(ring_s, state_s, rep, rep, rep, rep, rep, rep,
                              rep),
                out_shardings=(ring_s, state_s, rep),
            )
        else:
            self._fn = jax.jit(run)

    @staticmethod
    def _run_impl(schedule, ring, state, do_load, load_frame, start_frame,
                  bits, status, save_mask, adv_mask):
        loaded = ring_load(ring, load_frame)
        state = jax.tree_util.tree_map(
            lambda l, s: jnp.where(do_load, l, s), loaded, state
        )
        frame0 = jnp.where(do_load, jnp.asarray(load_frame, jnp.int32),
                           jnp.asarray(start_frame, jnp.int32))
        return rollout_burst(schedule, ring, state, frame0, bits, status,
                             save_mask, adv_mask,
                             n_run=live_steps(save_mask, adv_mask))

    def run(
        self,
        ring: SnapshotRing,
        state: WorldState,
        start_frame: int,
        bits,
        status,
        n_frames: int,
        load_frame: Optional[int] = None,
        save_mask=None,
        adv_mask=None,
    ) -> Tuple[SnapshotRing, WorldState, jnp.ndarray]:
        """Pad a host-assembled burst to ``max_frames`` and dispatch it.

        ``bits``/``status`` are host arrays of shape ``[n_frames, players,
        …]``; ``load_frame=None`` means no rollback (plain steps from
        ``start_frame``). ``save_mask``/``adv_mask`` default to all-True over
        the first ``n_frames`` steps (the standard (save, advance) pairing).
        """
        import numpy as np

        if n_frames > self.max_frames:
            raise ValueError(
                f"burst of {n_frames} frames exceeds max_frames={self.max_frames}"
            )
        bits = np.asarray(bits)
        status = np.asarray(status)
        pad = self.max_frames - n_frames
        if pad:
            bits = np.concatenate(
                [bits, np.zeros((pad,) + bits.shape[1:], bits.dtype)], axis=0
            )
            status = np.concatenate(
                [status, np.zeros((pad,) + status.shape[1:], status.dtype)], axis=0
            )
        valid = np.arange(self.max_frames) < n_frames
        save_mask = valid if save_mask is None else (
            np.concatenate([np.asarray(save_mask, bool),
                            np.zeros(pad, bool)]) & valid
        )
        adv_mask = valid if adv_mask is None else (
            np.concatenate([np.asarray(adv_mask, bool),
                            np.zeros(pad, bool)]) & valid
        )
        do_load = load_frame is not None
        ring, state, checksums = self._fn(
            ring,
            state,
            jnp.asarray(do_load),
            jnp.asarray(load_frame if do_load else 0, jnp.int32),
            jnp.asarray(start_frame, jnp.int32),
            jnp.asarray(bits),
            jnp.asarray(status, jnp.int32),
            jnp.asarray(save_mask),
            jnp.asarray(adv_mask),
        )
        return ring, state, checksums


def advance_n(
    schedule: Schedule,
    state: WorldState,
    bits: jnp.ndarray,
    status: Optional[jnp.ndarray] = None,
) -> WorldState:
    """Plain N-frame advance (no ring, no checksums): ``lax.scan`` of the
    schedule over the leading frame axis of ``bits``. The building block the
    speculative engine vmaps over branches."""
    if status is None:
        status = jnp.zeros(bits.shape[:2], dtype=jnp.int32)

    def body(state, xs):
        b, s = xs
        return schedule(state, PlayerInputs(bits=b, status=s)), None

    return jax.lax.scan(body, state, (bits, status))[0]
