"""A title's per-entity input lookup (``PlayerInputs.for_handles``, PR 38).

- The helper is bit for bit ``bits[clip(handles, 0, P - 1)]`` for every
  handle, in range or out, for scalar and vector inputs, under no, one and
  two batch axes.
- The boids step traced under ``vmap`` (the client's rollout) and under
  ``vmap`` x ``vmap`` (the served ``[S]`` x ``[B]``) holds no ``gather``:
  the static property of the program that says the select form engaged,
  where indexing ``inputs.bits[handles]`` holds one.
- One boids step is bitwise what the indexed form gave, for the XLA force
  and the MXU kernel (interpreted here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.models import boids
from bevy_ggrs_tpu.ops.pairwise import FORCE_SCOPE
from bevy_ggrs_tpu.schedule import PlayerInputs, make_inputs
from tests.test_lane_uniform_ring import assert_bits_equal


def indexed(inputs: PlayerInputs, handles):
    """What titles wrote before the helper, and what it must equal."""
    return inputs.bits[jnp.clip(handles, 0, inputs.num_players - 1)]


def batched(fn, lanes):
    for _ in lanes:
        fn = jax.vmap(fn)
    return fn


def stacked(tree, lead):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, lead + x.shape), tree)


def gathers(jaxpr):
    """The ``gather`` equations of a jaxpr and of every jaxpr inside it
    (scans, conditionals, jitted calls), outside the force path: what is
    traced under ``FORCE_SCOPE`` (the MXU kernel's prologue takes columns
    by constant indices, and its kernel body is its own) is the force's."""
    found = []
    for eqn in jaxpr.eqns:
        if FORCE_SCOPE in str(eqn.source_info.name_stack):
            continue
        if eqn.primitive.name == "gather":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += gathers(sub)
    return found


@pytest.mark.parametrize("lanes", [(), (3,), (3, 4)], ids=["flat", "B", "SxB"])
@pytest.mark.parametrize("shape", [(), (2,)], ids=["scalar", "vector"])
@pytest.mark.parametrize("P", [1, 2, 4, 8])
def test_for_handles_equals_clipped_index(P, shape, lanes):
    rng = np.random.default_rng(100 * P + len(shape) * 10 + len(lanes))
    bits = jnp.asarray(
        rng.integers(0, 256, size=lanes + (P,) + shape), jnp.uint8)
    edge = np.asarray([-1, 0, P - 1, P, P + 3], np.int32)
    handles = jnp.asarray(np.concatenate([
        np.broadcast_to(edge, lanes + edge.shape),
        rng.integers(-2, P + 4, size=lanes + (11,), dtype=np.int32),
    ], axis=-1))
    inputs = PlayerInputs(
        bits=bits, status=jnp.zeros(lanes + (P,), jnp.int32))
    got = batched(PlayerInputs.for_handles, lanes)(inputs, handles)
    want = batched(indexed, lanes)(inputs, handles)
    assert got.dtype == bits.dtype
    assert got.shape == lanes + (16,) + shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if not lanes:
        # The clip's meaning, spelled out on the edge handles.
        np.testing.assert_array_equal(
            np.asarray(got[:5]),
            np.asarray(bits)[[0, 0, P - 1, P - 1, P - 1]])


@pytest.mark.parametrize("lanes", [(4,), (3, 4)], ids=["B", "SxB"])
@pytest.mark.parametrize("kernel", ["xla", "mxu"])
def test_boids_step_holds_no_gather_under_batch_axes(kernel, lanes):
    state = stacked(boids.make_world(64, 2).commit(), lanes)
    inputs = stacked(make_inputs(np.asarray([5, 10], np.uint8)), lanes)
    step = batched(boids.make_schedule(kernel=kernel), lanes)
    assert gathers(jax.make_jaxpr(step)(state, inputs).jaxpr) == []
    # The walk does see one where a title indexes by handle.
    handles = state.components["leader_handle"]
    assert gathers(
        jax.make_jaxpr(batched(indexed, lanes))(inputs, handles).jaxpr)


@pytest.mark.parametrize("lanes", [(), (2, 3)], ids=["flat", "SxB"])
@pytest.mark.parametrize("kernel", ["xla", "mxu"])
def test_boids_step_is_bitwise_the_indexed_form(kernel, lanes, monkeypatch):
    n = 64
    rng = np.random.default_rng(38)
    state = stacked(boids.make_world(n, 2, seed=3).commit(), lanes)
    # Worlds that differ a lane, and handles out of range on both sides.
    state = state.replace(components={
        **state.components,
        "position": state.components["position"] + jnp.asarray(
            rng.normal(0, 0.05, lanes + (n, 2)), jnp.float32),
        "leader_handle": jnp.asarray(
            rng.integers(-1, 4, size=lanes + (n,), dtype=np.int32)),
    })
    inputs = PlayerInputs(
        bits=jnp.asarray(rng.integers(0, 16, size=lanes + (2,)), jnp.uint8),
        status=jnp.zeros(lanes + (2,), jnp.int32))
    step = lambda: jax.jit(  # noqa: E731
        batched(boids.make_schedule(kernel=kernel), lanes))(state, inputs)
    after = step()
    monkeypatch.setattr(PlayerInputs, "for_handles", indexed)
    before = step()
    moved = np.asarray(
        after.components["velocity"] != state.components["velocity"])
    assert moved.any()
    assert_bits_equal(after, before)
