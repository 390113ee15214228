"""Speculative branch engine: correctness vs. serial, sharding equivalence.

The north-star component (survey §2.3): B candidate input branches × F
frames as one vmapped rollout, branch axis sharded over the device mesh.
Every branch must be bit-identical to the serial single-branch execution of
the same inputs — speculation is an optimization, never a semantic change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.parallel.sharding import branch_mesh, shard_branch_axis
from bevy_ggrs_tpu.parallel.speculate import (
    SpeculativeExecutor,
    bitmask_sampler,
    enumerate_branches,
    match_branch,
    merge_rings,
)
from bevy_ggrs_tpu.rollout import RolloutExecutor, advance_n
from bevy_ggrs_tpu.state import ring_init

B, F, P = 8, 4, 2


def setup():
    schedule = box_game.make_schedule()
    state = box_game.make_world(P).commit()
    rng = np.random.RandomState(3)
    bits = jnp.asarray(rng.randint(0, 16, (B, F, P), dtype=np.uint8))
    return schedule, state, bits


class TestEnumerate:
    def test_branch0_is_repeat_last(self):
        key = jax.random.PRNGKey(0)
        last = jnp.asarray([5, 9], dtype=jnp.uint8)
        bits = enumerate_branches(key, last, 16, 6, sampler=bitmask_sampler())
        assert bits.shape == (16, 6, 2)
        np.testing.assert_array_equal(
            np.asarray(bits[0]), np.broadcast_to(np.array([5, 9]), (6, 2))
        )

    def test_branches_differ(self):
        key = jax.random.PRNGKey(1)
        last = jnp.zeros((2,), jnp.uint8)
        bits = np.asarray(
            enumerate_branches(key, last, 32, 8, sampler=bitmask_sampler())
        )
        assert len({b.tobytes() for b in bits}) > 16


class TestMatch:
    def test_exact_match(self):
        bits = np.zeros((4, 5, 2), np.uint8)
        bits[2, :, 0] = 7
        confirmed = bits[2, :3]
        branch, depth = match_branch(bits, confirmed)
        assert branch == 2 and depth == 3

    def test_partial_match_prefers_deepest(self):
        bits = np.zeros((3, 5, 2), np.uint8)
        bits[1, 0, 0] = 1  # branch 1 wrong at frame 0
        bits[2, 2, 0] = 9  # branch 2 wrong at frame 2
        confirmed = np.zeros((4, 2), np.uint8)
        branch, depth = match_branch(bits, confirmed)
        assert branch == 0 and depth == 4  # branch 0 fully agrees

    def test_no_confirmed_frames(self):
        bits = np.zeros((4, 5, 2), np.uint8)
        assert match_branch(bits, np.zeros((0, 2), np.uint8)) == (0, 0)


class TestSpeculativeExecutor:
    def test_matches_serial_rollout_bitwise(self):
        schedule, state, bits = setup()
        ex = SpeculativeExecutor(schedule, B, F)
        result = ex.run(state, 0, bits)
        serial = RolloutExecutor(schedule, F)
        for b in range(B):
            ring0 = ring_init(state, F)
            ring, end_state, checksums = serial.run(
                ring0, state, 0, np.asarray(bits[b]),
                np.zeros((F, P), np.int32), n_frames=F,
            )
            spec_t = np.asarray(result.states.components["translation"][b])
            ser_t = np.asarray(end_state.components["translation"])
            np.testing.assert_array_equal(spec_t, ser_t)
            np.testing.assert_array_equal(
                np.asarray(result.checksums[b]), np.asarray(checksums)
            )

    def test_commit_selects_branch(self):
        schedule, state, bits = setup()
        ex = SpeculativeExecutor(schedule, B, F)
        result = ex.run(state, 0, bits)
        ring, end_state = ex.commit(result, 3)
        np.testing.assert_array_equal(
            np.asarray(end_state.components["translation"]),
            np.asarray(result.states.components["translation"][3]),
        )
        assert int(end_state.resources["frame_count"]) == F
        np.testing.assert_array_equal(
            np.asarray(ring.frames), np.arange(F, dtype=np.int32)
        )

    @pytest.mark.parametrize("start", [0, F + 3])
    def test_merge_rings_overlays_saved_slots(self, start):
        """A branch ring's rows stand in step order; the merge puts each
        where its ``frames`` label says, ``frame % depth``."""
        schedule, state, bits = setup()
        ex = SpeculativeExecutor(schedule, B, F)
        result = ex.run(state, start, bits)
        ring, _ = ex.commit(result, 1)
        np.testing.assert_array_equal(
            np.asarray(ring.frames), start + np.arange(F))
        main = ring_init(state, F)
        merged = merge_rings(main, ring)
        at = (start + np.arange(F)) % F
        np.testing.assert_array_equal(
            np.asarray(merged.frames)[at], np.asarray(ring.frames))
        np.testing.assert_array_equal(
            np.asarray(merged.checksums)[at], np.asarray(ring.checksums))
        for m, r in zip(jax.tree_util.tree_leaves(merged.states),
                        jax.tree_util.tree_leaves(ring.states)):
            np.testing.assert_array_equal(np.asarray(m)[at], np.asarray(r))
        # rows the branch ring never saved keep the main ring's history
        unsaved = ring.replace(frames=ring.frames.at[0].set(-1))
        kept = merge_rings(main, unsaved)
        assert int(kept.frames[start % F]) == -1
        np.testing.assert_array_equal(
            np.asarray(kept.frames)[at[1:]], np.asarray(ring.frames)[1:])

    def test_speculation_covers_confirmed_path(self):
        """The whole point: when confirmed inputs match a branch, committing
        it equals having simulated serially with those inputs."""
        schedule, state, bits = setup()
        ex = SpeculativeExecutor(schedule, B, F)
        result = ex.run(state, 0, bits)
        confirmed = np.asarray(bits)[5]  # pretend branch 5 was reality
        branch, depth = match_branch(np.asarray(bits), confirmed)
        assert depth == F
        _, end_state = ex.commit(result, branch)
        truth = advance_n(schedule, state, jnp.asarray(confirmed))
        np.testing.assert_array_equal(
            np.asarray(end_state.components["translation"]),
            np.asarray(truth.components["translation"]),
        )


class TestSharded:
    def test_sharded_equals_unsharded(self):
        schedule, state, _ = setup()
        n_dev = len(jax.devices())
        if n_dev < 2:
            pytest.skip("sharding test needs >1 device "
                        "(GGRS_TEST_TPU run on one chip)")
        mesh = branch_mesh()
        bb = 2 * n_dev
        rng = np.random.RandomState(11)
        bits = jnp.asarray(rng.randint(0, 16, (bb, F, P), dtype=np.uint8))

        plain = SpeculativeExecutor(schedule, bb, F)
        res_plain = plain.run(state, 0, bits)

        sharded = SpeculativeExecutor(schedule, bb, F, mesh=mesh)
        res_shard = sharded.run(state, 0, shard_branch_axis(bits, mesh))

        np.testing.assert_array_equal(
            np.asarray(res_plain.states.components["translation"]),
            np.asarray(res_shard.states.components["translation"]),
        )
        np.testing.assert_array_equal(
            np.asarray(res_plain.checksums), np.asarray(res_shard.checksums)
        )

    def test_sharded_commit_gathers(self):
        schedule, state, _ = setup()
        if len(jax.devices()) < 2:
            pytest.skip("sharding test needs >1 device")
        mesh = branch_mesh()
        bb = 16
        rng = np.random.RandomState(12)
        bits = jnp.asarray(rng.randint(0, 16, (bb, F, P), dtype=np.uint8))
        ex = SpeculativeExecutor(schedule, bb, F, mesh=mesh)
        result = ex.run(state, 0, shard_branch_axis(bits, mesh))
        ring, end_state = ex.commit(result, 13)
        truth = advance_n(schedule, state, bits[13])
        np.testing.assert_array_equal(
            np.asarray(end_state.components["translation"]),
            np.asarray(truth.components["translation"]),
        )


class TestGraftEntry:
    def test_entry_compiles_and_runs(self):
        import importlib, sys

        sys.path.insert(0, "/root/repo")
        mod = importlib.import_module("__graft_entry__")
        fn, args = mod.entry()
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)

    def test_dryrun_multichip(self):
        import importlib, sys

        sys.path.insert(0, "/root/repo")
        mod = importlib.import_module("__graft_entry__")
        mod.dryrun_multichip(8)
