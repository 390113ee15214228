"""The two lowerings of one ring operation (``state.py`` ``ring_row_write`` /
``ring_row_read``): a dynamic slice where the index is one scalar, a select
over the small static axis where a ``vmap`` batches the index (the slot axis
of ``serve/batch.py``).

Structure: the lowered batched tick holds no ``scatter`` and no ``gather`` of
its own; the singleton keeps ``dynamic_update_slice``. Values: the select
form equals the dynamic form bit for bit, lane by lane, on random bit
patterns (NaN payloads, ``-0.0``, ``inf`` included) with a different index
in every lane.

And the fourth (PR 53): a batched write into a ring whose large rows stand
as whole lane tiles is one copy a writing lane (``ops/ring_write.py``,
interpreted here), held to the same dynamic form by the same value tests,
alone and as a loop's carry, beside the rows that must stay on the select
(``bool``, a row that does not tile, a small row).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import state as state_mod
from bevy_ggrs_tpu.fused import (
    FusedTickExecutor,
    TickInts,
    absorb_branch_frames,
)
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.rollout import rollout_steps
from bevy_ggrs_tpu.schedule import PREDICTED, Schedule
from bevy_ggrs_tpu.serve.batch import BatchedTickExecutor
from bevy_ggrs_tpu.state import (
    IN_PLACE_ROW_BYTES,
    SELECT_ROWS,
    HostWorld,
    TypeRegistry,
    in_place_writes,
    ring_init,
    ring_load,
    ring_row_read,
    ring_row_write,
    ring_rows_flat,
    ring_rows_shaped,
    ring_save,
    ring_step_load,
    row_in_tiles,
)

P = 2
LANES = 4
DEPTH = 5  # main ring: max_prediction 4
SPEC = 3  # speculative ring depth = spec frames
BRANCHES = 4
BURST = 6
LONG = SELECT_ROWS + 6  # an axis read by the one-hot pass (a wide rollout)

# ---------------------------------------------------------------------------
# A title with no gather of its own (box_game looks its input up by handle)
# ---------------------------------------------------------------------------


def _drift_system(state, inputs):
    push = jnp.sum(inputs.bits.astype(jnp.float32))
    pos = state.components["pos"]
    return state.replace(
        components={"pos": pos * jnp.float32(0.5) + push},
        resources={"tick": state.resources["tick"] + 1},
    )


def plain_schedule():
    return Schedule([_drift_system])


def plain_world():
    reg = TypeRegistry()
    reg.register_component("pos", shape=(3,))
    reg.register_resource("tick", jnp.int32(0))
    world = HostWorld(reg, 4)
    for i in range(3):
        world.spawn({"pos": [i, -i, 0.5]}, rollback_id=i)
    return world.commit()


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def random_like(rng, tree):
    """The same pytree filled with random BITS: floats get every pattern a
    uint32 can hold (NaNs with payloads, infinities, denormals, -0.0)."""

    def leaf(x):
        x = np.asarray(x)
        if x.dtype == np.bool_:
            return jnp.asarray(rng.integers(0, 2, size=x.shape).astype(bool))
        raw = rng.integers(0, 256, size=x.shape + (x.dtype.itemsize,),
                           dtype=np.uint8)
        return jnp.asarray(raw.view(x.dtype).reshape(x.shape))

    return jax.tree_util.tree_map(leaf, tree)


def bits_of(tree):
    def leaf(x):
        x = np.asarray(x)
        if x.dtype == np.bool_:
            return x
        return x.view(np.dtype(f"uint{x.dtype.itemsize * 8}"))

    return [leaf(x) for x in jax.tree_util.tree_leaves(tree)]


def assert_bits_equal(a, b):
    la, lb = bits_of(a), bits_of(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


def stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def lane(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def per_lane(fn, *args):
    """``fn`` once per lane with scalar indices — the dynamic form — and the
    results stacked."""
    n = jax.tree_util.tree_leaves(args)[0].shape[0]
    return stack([jax.jit(fn)(*lane(args, i)) for i in range(n)])


def lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def count(text, op):
    return len(re.findall(r"stablehlo\." + op + r"\b", text))


def random_ring(rng, state, depth):
    return random_like(rng, ring_init(state, depth))


def i32(xs):
    return jnp.asarray(xs, jnp.int32)


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def tick_args(ex, state, lanes, burst, branches, spec_frames, depth=DEPTH):
    """Zero arguments of the executor's packed tick (the carry of
    ``_tick_impl``'s four trees, the int32 vector, ``bits``,
    ``branch_bits``) with a leading ``[lanes]`` axis (``lanes=0``: the
    singleton's)."""
    lead = (lanes,) if lanes else ()
    bc = lambda tree, *more: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, lead + more + a.shape), tree
    )
    carry = ex.pack(
        bc(ring_init(state, depth)), bc(state),
        bc(ring_init(state, spec_frames), branches), bc(state, branches),
    )
    return (
        carry,
        np.zeros(lead + (TickInts.STATUS + burst * P,), np.int32),
        np.zeros(lead + (burst, P), np.uint8),
        np.zeros(lead + (branches, spec_frames, P), np.uint8),
    )


def batched_text(schedule, state):
    ex = BatchedTickExecutor(schedule, LANES, BURST, BRANCHES, SPEC)
    return ex._fn.lower(
        *tick_args(ex, state, LANES, BURST, BRANCHES, SPEC)
    ).as_text()


def singleton_text(schedule, state, **kw):
    ex = FusedTickExecutor(schedule, BURST, BRANCHES, SPEC, **kw)
    return ex._fn.lower(
        *tick_args(ex, state, 0, BURST, BRANCHES, SPEC)
    ).as_text()


def absorb_text(ex, state):
    carry = tick_args(ex, state, 0, BURST, BRANCHES, SPEC)[0]
    return ex._absorb.lower(
        carry, np.zeros(TickInts.ABSORB, np.int32)
    ).as_text()


def test_batched_tick_holds_no_gather_or_scatter():
    text = batched_text(plain_schedule(), plain_world())
    assert count(text, "scatter") == 0
    assert count(text, "gather") == 0
    assert count(text, "while") == 3  # absorb, burst, rollout: nothing nested


def test_batched_box_game_keeps_only_the_titles_gathers():
    state = box_game.make_world(P).commit()
    batched = batched_text(box_game.make_schedule(), state)
    single = singleton_text(box_game.make_schedule(), state)
    assert count(batched, "scatter") == 0
    # inputs.bits[safe_handle] in move_cube_system, once a schedule call site
    assert count(batched, "gather") == count(single, "gather") > 0


def test_singleton_tick_keeps_dynamic_slices():
    text = singleton_text(plain_schedule(), plain_world())
    assert count(text, "dynamic_update_slice") > 0
    assert count(text, "dynamic_slice") > 0
    assert count(text, "scatter") == 0
    assert count(text, "gather") == 0


def test_singleton_absorb_program_keeps_dynamic_slices():
    state = plain_world()
    ex = FusedTickExecutor(plain_schedule(), BURST, BRANCHES, SPEC)
    text = absorb_text(ex, state)
    assert count(text, "dynamic_update_slice") > 0
    assert count(text, "scatter") == 0 and count(text, "gather") == 0


def test_session_axis_mode_compiles_the_batched_form():
    """GGRS_SESSION_AXIS promises the batched executor's executable: the
    same select form, so the singleton suites prove it bitwise."""
    state = plain_world()
    text = singleton_text(plain_schedule(), state, session_axis=LANES)
    assert count(text, "scatter") == 0
    assert count(text, "gather") == 0
    assert count(text, "while") == 3
    ex = FusedTickExecutor(
        plain_schedule(), BURST, BRANCHES, SPEC, session_axis=LANES
    )
    absorb = absorb_text(ex, state)
    assert count(absorb, "scatter") == 0 and count(absorb, "gather") == 0


def test_lane_uniform_index_under_vmap_stays_dynamic():
    """The rollout's vmap over branches: one frame for all of them."""
    stack_ = jnp.zeros((BRANCHES, DEPTH, 4, 3))
    rows = jnp.ones((BRANCHES, 4, 3))
    write = lambda s, r, i: jax.vmap(ring_row_write, (0, 0, None))(s, r, i)
    read = lambda s, i: jax.vmap(ring_row_read, (0, None))(s, i)
    w = lowered(write, stack_, rows, jnp.int32(2))
    r = lowered(read, stack_, jnp.int32(2))
    assert count(w, "dynamic_update_slice") == 1 and count(w, "scatter") == 0
    assert count(r, "dynamic_slice") == 1 and count(r, "gather") == 0


def test_per_lane_index_under_vmap_is_a_select():
    stack_ = jnp.zeros((LANES, DEPTH, 4, 3))
    rows = jnp.ones((LANES, 4, 3))
    idx = i32([0, 1, 2, 3])
    w = lowered(jax.vmap(ring_row_write), stack_, rows, idx)
    r = lowered(jax.vmap(ring_row_read), stack_, idx)
    for text in (w, r):
        assert count(text, "scatter") == 0 and count(text, "gather") == 0
        assert count(text, "dynamic_update_slice") == 0
        assert count(text, "dynamic_slice") == 0
        assert count(text, "select") > 0


def test_per_lane_index_over_a_long_axis_is_one_dense_pass():
    """Past ``SELECT_ROWS`` rows (a slot's matched branch out of
    ``[B, ...]``) the read is a one-hot mask OR-ed over the axis: one select
    and one reduce whatever the axis is long, not a select a row, and not
    the gather jax would make of a batched index."""
    idx = i32([0, 1, LONG - 1, 3])
    for stack_, axes in ((jnp.zeros((LANES, LONG, 4, 3)), (0, 0)),
                         (jnp.zeros((LONG, 4, 3)), (None, 0))):
        text = lowered(jax.vmap(ring_row_read, axes), stack_, idx)
        assert count(text, "gather") == 0 and count(text, "scatter") == 0
        assert count(text, "dynamic_slice") == 0
        assert count(text, "reduce") == 1 and count(text, "or") == 1
        # _clamp's two and the mask's one.
        assert count(text, "select") <= 3
    short = lowered(jax.vmap(ring_row_read),
                    jnp.zeros((LANES, SELECT_ROWS, 4, 3)), idx)
    assert count(short, "reduce") == 0
    assert len(re.findall(r"call @_where", short)) >= SELECT_ROWS - 1


# ---------------------------------------------------------------------------
# Values: select form == dynamic form, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [
    jnp.float32, jnp.int32, jnp.uint32, jnp.uint8, jnp.bool_,
])
@pytest.mark.parametrize("stack_batched", [True, False])
def test_long_axis_read_matches_per_lane(dtype, stack_batched):
    """The one-hot pass against the dynamic form, every bit pattern, with
    indices out of range on both sides (a dynamic slice counts a negative
    index from the end and clamps), the stack batched or shared."""
    rng = np.random.default_rng(LONG + stack_batched)
    shape = ((LANES,) if stack_batched else ()) + (LONG, 3, 2)
    stack_ = random_like(rng, jnp.zeros(shape, dtype))
    if dtype == jnp.float32:     # -0.0, NaNs with payloads, infinities
        stack_ = stack_.at[..., :3, :, :].set(
            jnp.asarray(SPECIAL[:6].reshape(3, 2)))
    idx = i32([LONG - 1, -2, 3 * LONG, -7 * LONG])
    axes = (0 if stack_batched else None, 0)
    got = jax.jit(jax.vmap(ring_row_read, axes))(stack_, idx)
    want = stack([
        jax.jit(ring_row_read)(lane(stack_, i) if stack_batched else stack_,
                               idx[i])
        for i in range(LANES)])
    assert_bits_equal(got, want)


# kind: (row shape, dtype, whether a batched write copies it in place). A row
# is copied where it is ``IN_PLACE_ROW_BYTES`` or more, whole ``(8, 128)``
# tiles and of a 32-bit type; everything else keeps the select.
ROWS = {
    "small_f32": ((4, 3), jnp.float32, False),
    "tiles_f32": ((64, 128), jnp.float32, True),
    "tiles_i32": ((72, 128), jnp.int32, True),
    "bool": ((256, 128), jnp.bool_, False),  # a DMA takes no bool
    "hundred": ((100,), jnp.float32, False),
    "ragged": ((65, 128), jnp.float32, False),  # 32.5 KB, not whole tiles
    "few_tiles": ((8, 128), jnp.float32, False),  # whole tiles, 4 KB
}
assert 64 * 128 * 4 == IN_PLACE_ROW_BYTES
MASKS = {
    "unmasked": None,
    "some": [True, False, True, False],
    "nobody": [False] * LANES,
}


def random_rows(rng, kind, *lead):
    """Random bits in a ``[*lead, *row]`` array of ``ROWS[kind]``; a float
    one starts with the special patterns."""
    shape, dtype, _ = ROWS[kind]
    x = random_like(rng, jnp.zeros(lead + shape, dtype))
    if dtype == jnp.float32:
        flat = np.array(x).reshape(-1)
        flat[:SPECIAL.size] = SPECIAL
        x = jnp.asarray(flat.reshape(x.shape))
    return x


@pytest.mark.parametrize("indices", [
    [0, 1, 2, 3], [4, 4, 0, 0], [3, 0, 4, 1],
    [-1, 5, 9, -7],  # out of range: a dynamic slice clamps, so must a select
])
@pytest.mark.parametrize("masked", sorted(MASKS))
@pytest.mark.parametrize("kind", sorted(ROWS))
def test_row_write_and_read_match_per_lane(indices, masked, kind):
    rng = np.random.default_rng(sum(indices) + 17 * len(masked) + len(kind))
    stack_ = random_rows(rng, kind, LANES, DEPTH)
    rows = random_rows(rng, kind, LANES)
    idx = i32(indices)
    args = (stack_, rows, idx)
    if MASKS[masked] is not None:
        args += (jnp.asarray(MASKS[masked]),)
    visits = in_place_writes[0]
    got = jax.jit(jax.vmap(ring_row_write))(*args)
    assert (in_place_writes[0] > visits) == ROWS[kind][2]
    assert row_in_tiles(stack_, 2) == ROWS[kind][2]
    assert_bits_equal(got, per_lane(ring_row_write, *args))
    assert_bits_equal(
        jax.jit(jax.vmap(ring_row_read))(stack_, idx),
        per_lane(ring_row_read, stack_, idx),
    )


@pytest.mark.parametrize("steps", [0, 3, 6])
@pytest.mark.parametrize("kind", ["tiles_f32", "tiles_i32", "bool", "ragged"])
def test_row_write_as_a_loops_carry_matches_per_lane(steps, kind):
    """What a burst does: a loop with a traced trip count whose carry is
    the ring, every lane saving at its own slot in the steps its own mask
    sets (a padding lane copies nothing)."""
    rng = np.random.default_rng(steps + len(kind))
    stack_ = random_rows(rng, kind, LANES, DEPTH)
    rows = random_rows(rng, kind, LANES, 6)
    base = i32([0, 7, -3, 1 << 20])
    saves = jnp.asarray(rng.integers(0, 2, size=(LANES, 6)).astype(bool))
    saves = saves.at[2].set(False)  # a lane that only pads

    def burst(stack_, rows, base, saves, n):
        def step(t, s):
            return ring_row_write(
                s, ring_row_read(rows, t), jnp.remainder(base + t, DEPTH),
                ring_row_read(saves, t))

        return jax.lax.fori_loop(0, n, step, stack_)

    got = jax.jit(jax.vmap(burst, (0, 0, 0, 0, None)))(
        stack_, rows, base, saves, jnp.int32(steps))
    want = stack([
        jax.jit(burst)(*lane((stack_, rows, base, saves), i), jnp.int32(steps))
        for i in range(LANES)])
    assert_bits_equal(got, want)
    if steps == 0:
        assert_bits_equal(got, stack_)


def test_nested_vmap_lanes_over_branches():
    """The served rollout: the index is per slot, shared by a slot's
    branches (inner vmap unbatched, outer vmap batched)."""
    rng = np.random.default_rng(5)
    stack_ = random_like(
        rng, jnp.zeros((LANES, BRANCHES, DEPTH, 4), jnp.float32)
    )
    rows = random_like(rng, jnp.zeros((LANES, BRANCHES, 4), jnp.float32))
    idx = i32([4, 0, 2, 1])
    valid = jnp.asarray([True, True, False, True])
    inner_w = jax.vmap(ring_row_write, (0, 0, None, None))
    inner_r = jax.vmap(ring_row_read, (0, None))
    text = lowered(jax.vmap(inner_w), stack_, rows, idx, valid)
    assert count(text, "scatter") == 0 and count(text, "gather") == 0
    assert_bits_equal(
        jax.jit(jax.vmap(inner_w))(stack_, rows, idx, valid),
        per_lane(inner_w, stack_, rows, idx, valid),
    )
    assert_bits_equal(
        jax.jit(jax.vmap(inner_r))(stack_, idx),
        per_lane(inner_r, stack_, idx),
    )


@pytest.mark.parametrize("frames", [
    [0, 1, 2, 3],
    [4, 5, 9, 10],  # frame % depth wraps: rows 4, 0, 4, 0
    [DEPTH * 1000 - 1, DEPTH * 1000, 7, 2 ** 30 + 3],
])
def test_ring_save_and_load_match_per_lane(frames):
    rng = np.random.default_rng(frames[1])
    state = plain_world()
    rings = stack([random_ring(rng, state, DEPTH) for _ in range(LANES)])
    states = stack([random_like(rng, state) for _ in range(LANES)])
    f = i32(frames)
    valid = jnp.asarray([True, True, False, True])

    got = jax.jit(jax.vmap(ring_save))(rings, states, f)
    assert_bits_equal(got, per_lane(ring_save, rings, states, f))
    got = jax.jit(jax.vmap(ring_save))(rings, states, f, valid)
    assert_bits_equal(got, per_lane(ring_save, rings, states, f, valid))
    assert_bits_equal(
        jax.jit(jax.vmap(ring_load))(rings, f), per_lane(ring_load, rings, f)
    )
    text = lowered(jax.vmap(ring_save), rings, states, f, valid)
    assert count(text, "scatter") == 0 and count(text, "gather") == 0


ROWS_OF_WORLD = IN_PLACE_ROW_BYTES // 4  # int32[8192]: 32 KB


def tiled_world():
    """Large leaves of every kind a burst meets: ``[.., 2]`` float32 and
    int32 rows that are whole lane tiles, a large ``bool`` row, and a large
    row that does not tile (a resource of 1,100 floats)."""
    reg = TypeRegistry()
    reg.register_component("pos", shape=(2,))
    reg.register_component("tag", shape=(), dtype=jnp.int32)
    reg.register_resource("odd", np.zeros((100, 11), np.float32))
    reg.register_resource("tick", jnp.int32(0))
    world = HostWorld(reg, ROWS_OF_WORLD)  # bool[8192]: a large row
    for i in range(5):
        world.spawn({"pos": [i, -i], "tag": i}, rollback_id=i)
    return world.commit()


def parents_write(monkeypatch):
    """From here on no row counts as lane tiles: a burst carries its large
    rows flat and a per-lane write into them is the select, which is the
    parent's program."""
    monkeypatch.setattr(state_mod, "_row_tiles", lambda x, lead: None)


@pytest.mark.parametrize("frames", [
    [0, 1, 2, 3],
    [4, 5, 9, 10],  # frame % depth wraps: rows 4, 0, 4, 0
    [DEPTH * 1000 - 1, DEPTH * 1000, 7, 2 ** 30 + 3],
])
@pytest.mark.parametrize("masked", sorted(MASKS))
def test_save_into_a_bursts_ring_matches_per_lane(frames, masked, monkeypatch):
    """``ring_save`` into the form a burst carries (large rows flat, as lane
    tiles where they are such) against the dynamic form on the shaped ring,
    and against the parent's form of the same."""
    rng = np.random.default_rng(frames[1] + len(masked))
    state = tiled_world()
    rings = stack([random_ring(rng, state, DEPTH) for _ in range(LANES)])
    states = stack([random_like(rng, state) for _ in range(LANES)])
    args = (rings, states, i32(frames))
    if MASKS[masked] is not None:
        args += (jnp.asarray(MASKS[masked]),)

    def through_burst_form(ring, *a):
        carried = ring_rows_flat(ring)
        put, cs = ring_save(carried, *a)
        return ring_rows_shaped(put, ring), cs

    carried = ring_rows_flat(lane(rings, 0)).states
    assert carried.components["pos"].shape == (DEPTH, 128, 128)
    assert carried.components["tag"].shape == (DEPTH, 64, 128)
    assert carried.alive.shape == (DEPTH, ROWS_OF_WORLD)
    assert carried.resources["odd"].shape == (DEPTH, 1100)
    visits = in_place_writes[0]
    got = jax.jit(jax.vmap(through_burst_form))(*args)
    # pos, tag and rollback_id: the whole-tile leaves, and nobody else
    assert in_place_writes[0] - visits == 3
    assert_bits_equal(got, per_lane(ring_save, *args))
    parents_write(monkeypatch)
    assert ring_rows_flat(lane(rings, 0)).states.components[
        "pos"].shape == (DEPTH, 2 * ROWS_OF_WORLD)
    assert_bits_equal(got, jax.jit(jax.vmap(through_burst_form))(*args))
    assert in_place_writes[0] - visits == 3


@pytest.mark.parametrize("plan", ["nobody", "some"])
def test_served_particles_tick_returns_the_parents_bits(plan, monkeypatch):
    """The batched tick of the churning title: its bursts save four leaves
    by the in-place copy, and every bit it returns is what the parent's
    select form returns."""
    from bevy_ggrs_tpu.models import particles
    from tests.test_step_order_rollout import INPUTS, PLANS, _two_ticks

    # the cell's own 9,216 rows: ttl and rollback_id are 36 KB
    schedule = particles.make_schedule(7)
    state = particles.make_world(P, match_seed=7).commit()
    make = lambda: BatchedTickExecutor(    # noqa: E731
        schedule, LANES, BURST, BRANCHES, SPEC, inputs=INPUTS)
    ex = make()
    got = _two_ticks(ex, state, LANES, PLANS[plan], seed=53)
    traced = ex.traced_ring_rows()
    # position, velocity, ttl, rollback_id; the four bool leaves keep the
    # select (and, one axis as they are, count as shaped)
    assert traced["in_place"] == 4 and traced["flat"] == 4
    parents_write(monkeypatch)
    parent = make()
    want = _two_ticks(parent, state, LANES, PLANS[plan], seed=53)
    traced = parent.traced_ring_rows()
    assert "in_place" not in traced and traced["flat"] == 2
    assert_bits_equal(got[0], want[0])
    assert_bits_equal(got[1], want[1])


def _absorb(ring, spec_ring, spec_state, first, n, anchor, total):
    return absorb_branch_frames(
        ring, spec_ring, spec_state, first, n, anchor, total,
        max_steps=BURST, n_run=n,
    )


# (first_frame, n_frames, anchor, total_spec) per lane
ABSORB_CASES = {
    "n_frames_0": ([7, 8, 9, 10], [0, 0, 0, 0], [7, 8, 9, 10], [3, 3, 3, 3]),
    "n_frames_1": ([7, 8, 9, 10], [1, 1, 1, 1], [7, 8, 9, 10], [3, 3, 3, 3]),
    "n_frames_burst": (
        [4, 13, 22, 31], [BURST] * 4, [4, 13, 22, 31], [BURST] * 4,
    ),
    "mixed_depths": ([7, 9, 3, 40], [0, 1, 3, 2], [7, 8, 3, 39], [3, 3, 3, 3]),
    # first + n == anchor + total: the state comes from the rollout's end,
    # one lane; the lanes beside it end one frame inside the ring and one
    # past its end.
    "ends_at_spec_end": (
        [5, 5, 5, 6], [3, 2, 3, 2], [5, 5, 4, 5], [3, 3, 3, 3],
    ),
    "late_first_frame": ([9, 14, 4, 5], [1, 2, 1, 3], [7, 12, 3, 5], [3, 3, 3, 3]),
}


@pytest.mark.parametrize("case", sorted(ABSORB_CASES))
def test_absorb_matches_per_lane(case):
    first, n, anchor, total = (i32(x) for x in ABSORB_CASES[case])
    rng = np.random.default_rng(len(case))
    state = plain_world()
    rings = stack([random_ring(rng, state, DEPTH) for _ in range(LANES)])
    spec_rings = stack([random_ring(rng, state, SPEC) for _ in range(LANES)])
    spec_states = stack([random_like(rng, state) for _ in range(LANES)])
    args = (rings, spec_rings, spec_states, first, n, anchor, total)
    got = jax.jit(jax.vmap(_absorb))(*args)
    assert_bits_equal(got, per_lane(_absorb, *args))
    text = lowered(jax.vmap(_absorb), *args)
    assert count(text, "scatter") == 0 and count(text, "gather") == 0


@pytest.mark.parametrize("branches", [
    [0, 0, 0, 0],
    [BRANCHES - 1] * LANES,
    [0, BRANCHES - 1, 1, 2],
])
def test_absorb_program_picks_the_matched_branch(branches):
    """``_absorb_impl``: the branch pick, then the absorb, per-lane index
    in both."""
    rng = np.random.default_rng(branches[1])
    state = plain_world()
    rings = stack([random_ring(rng, state, DEPTH) for _ in range(LANES)])
    one = lambda: stack(
        [random_ring(rng, state, SPEC) for _ in range(BRANCHES)]
    )
    prev_rings = stack([one() for _ in range(LANES)])
    prev_states = stack([
        stack([random_like(rng, state) for _ in range(BRANCHES)])
        for _ in range(LANES)
    ])
    fn = functools.partial(FusedTickExecutor._absorb_impl, BURST)
    args = (
        rings, prev_rings, prev_states, i32(branches),
        i32([6, 7, 8, 9]), i32([2, 3, 0, 1]), i32([6, 7, 8, 9]),
        i32([3, 3, 3, 3]),
    )
    assert_bits_equal(jax.jit(jax.vmap(fn))(*args), per_lane(fn, *args))


SPECIAL = np.array(
    [0x80000000,  # -0.0
     0x7FC12345,  # quiet NaN with a payload
     0xFFA00001,  # negative signalling NaN with a payload
     0x7F800000,  # +inf
     0xFF800000,  # -inf
     0x00000001,  # smallest denormal
     0x3F800000, 0x00000000, 0x807FFFFF],
    np.uint32,
).view(np.float32)


def test_special_floats_survive_save_load_absorb_bit_for_bit():
    """An arithmetic one-hot (multiply and sum) would turn -0.0 into 0.0 and
    spread NaN over the row; a select moves bits."""
    state = plain_world()
    pos = np.resize(SPECIAL, state.components["pos"].shape)
    states = stack([
        state.replace(components={"pos": jnp.asarray(np.roll(pos, i))})
        for i in range(LANES)
    ])
    want = bits_of(states)
    frames = i32([4, 5, 11, 2])  # rows 4, 0, 1, 2 of the main ring below
    empty = stack([ring_init(state, DEPTH)] * LANES)

    # a rollout from each lane's frame saves the state that entered it (row
    # 0 of a branch ring, whatever ``frame % SPEC`` is); load it back
    rollout = lambda st, f: rollout_steps(  # noqa: E731
        plain_schedule(), st, f, jnp.zeros((SPEC, P), jnp.uint8),
        jnp.zeros((SPEC, P), jnp.int32))[0]
    spec_rings = jax.jit(jax.vmap(rollout))(states, frames)
    loaded, _ = jax.jit(jax.vmap(ring_step_load))(spec_rings, frames, frames)
    for x, y in zip(bits_of(loaded), want):
        np.testing.assert_array_equal(x, y)

    # absorb that one frame into the main ring, load it from there
    poison = jax.tree_util.tree_map(jnp.zeros_like, states)
    main, _, _ = jax.jit(jax.vmap(_absorb))(
        empty, spec_rings, poison, frames, i32([1] * LANES), frames,
        i32([SPEC] * LANES),
    )
    assert np.array_equal(
        np.asarray(main.frames)[np.arange(LANES), np.asarray(frames) % DEPTH],
        np.asarray(frames),
    )
    for x, y in zip(bits_of(jax.jit(jax.vmap(ring_load))(main, frames)), want):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_whole_tick_vmapped_matches_per_lane(seed):
    """The fused tick itself on random rings with every phase live and
    different frames, depths and branches in every lane."""
    rng = np.random.default_rng(seed)
    sched = box_game.make_schedule()
    state = box_game.make_world(P).commit()
    tick = functools.partial(FusedTickExecutor._tick_impl, sched, BURST)
    rings = stack([random_ring(rng, state, DEPTH) for _ in range(LANES)])
    states = stack([state] * LANES)
    prev_rings = stack([
        stack([random_ring(rng, state, SPEC) for _ in range(BRANCHES)])
        for _ in range(LANES)
    ])
    prev_states = stack([stack([state] * BRANCHES)] * LANES)
    base = rng.integers(5, 1000, size=LANES)
    absorb_n = i32([0, 1, 2, 3])
    n_burst = np.array([1, 4, BURST, 0])
    masks = jnp.asarray(np.arange(BURST)[None] < n_burst[:, None])
    args = (
        rings, states, prev_rings, prev_states,
        i32(rng.integers(0, BRANCHES, size=LANES)),
        i32(base), absorb_n, i32(base - np.array([0, 1, 0, 0])),
        i32([SPEC] * LANES),
        jnp.asarray([False, True, False, True]), i32(base - 2),
        i32(base) + absorb_n,
        jnp.asarray(rng.integers(0, 16, size=(LANES, BURST, P)), jnp.uint8),
        jnp.zeros((LANES, BURST, P), jnp.int32), masks, masks,
        jnp.asarray([True, False, True, True]), i32(base - 1),
        jnp.asarray(
            rng.integers(0, 16, size=(LANES, BRANCHES, SPEC, P)), jnp.uint8
        ),
    )
    status = jnp.full((SPEC, P), PREDICTED, jnp.int32)
    got = jax.jit(jax.vmap(tick, in_axes=(0,) * 19 + (None,)))(*args, status)
    want = stack([
        jax.jit(tick)(*lane(args, i), status) for i in range(LANES)
    ])
    assert_bits_equal(got, want)
