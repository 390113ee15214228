"""The MXU force kernel compiled for a described v5e, at the shapes the
benchmark's cells run (no chip: the TPU's compiler is installed here and
compiles for a chip that is described and not attached); and the served
particles tick at a small ``[S] x [B] x F``, for what its outputs are made
of (PR 51: a rollout's rows leave the program as its loop wrote them).

Interpret mode cannot see what Mosaic refuses: a window of a lane-major
operand that is not tile-aligned (a 64-row strip of ``trpx`` was refused
here while every interpreted test passed, PR 49), more scoped memory than a
kernel may use, a relayout it does not implement. A compile that passes is
not a chip run and says nothing about results or times.

The topology is described inside a fixture (only the worker that runs this
file loads the TPU's library; ``/opt/skills/guides/on-chip-measurement``,
section 2) and every compile happens in this process. Keep such tests in
this one file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bevy_ggrs_tpu import fused
from bevy_ggrs_tpu.models import boids, particles
from bevy_ggrs_tpu.ops import checksum as checksum_ops
from bevy_ggrs_tpu.ops import pairwise
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.state import ONCE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (batch axes, rows the call owns, boids): boids256.* ([S] x [B] under the
# slot and branch vmaps), boids1k.wan ([B = 128]), the far end's single
# world, two column steps, a shard's rows (R < N), a boid count that pads.
SHAPES = {
    "served_64x8x1024": ((64, 8), 1024, 1024),
    "client_128x1024": ((128,), 1024, 1024),
    "one_world_1024": ((), 1024, 1024),
    "two_column_steps_2048": ((2,), 2048, 2048),
    "a_shards_rows_256_of_1024": ((8,), 256, 1024),
    "pads_1000": ((2,), 1000, 1000),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_mxu_force_kernel_compiles_for_a_v5e(shape, one_chip, monkeypatch):
    monkeypatch.setattr(pairwise, "pallas_interpret", lambda: False)
    batch, rows, n = SHAPES[shape]

    def force(rp, rv, p, v, ra, a):
        return pairwise.pairwise_force_rows_mxu2(
            rp, rv, p, v, ra, a, **boids._kernel_params())

    for _ in batch:
        force = jax.vmap(force)

    def arg(*tail):
        return jax.ShapeDtypeStruct(
            (*batch, *tail), jnp.float32, sharding=one_chip)

    compiled = jax.jit(force).lower(
        arg(rows, 2), arg(rows, 2), arg(n, 2), arg(n, 2), arg(rows), arg(n)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The trace finds the kernel by the scope's last part (FORCE_SCOPE).
    assert "pairwise_force" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _served_particles_tick(one_chip):
    """The ``[2] x [8] x 8`` batched tick of ``particles`` at 4,096 rows,
    compiled; the kinds its carry gives the state's leaves."""
    S, B, F = 2, 8, 8
    core = BatchedSessionCore(
        particles.make_schedule(7), particles.make_world(2, 4096, 7).commit(),
        8, 2, particles.INPUT_SPEC, num_slots=S, num_branches=B,
        spec_frames=F)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (core._carry,) + tuple(core._host_args()))
    return core._exec._fn.lower(*args).compile(), core._exec.packed.form


def _output_definitions(text):
    """The defining instruction of every output of the entry computation."""
    entry = text[text.index("ENTRY"):]
    defined = dict(re.findall(r"(?m)^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", entry))
    root = re.search(r"(?m)^\s*ROOT [^\n]*tuple\(([^\n]*)", entry).group(1)
    return [defined[name] for name in re.findall(r"%([\w.\-]+)", root)]


def test_served_particles_tick_writes_no_branch_invariant_row_twice(
        one_chip, monkeypatch):
    """A leaf no branch's inputs reach leaves the compiled tick once, not
    broadcast over the branches: the outputs shrink by those copies, and no
    output of a ring leaf's size is a broadcast. The parent's form (every
    leaf ``[S, B, F, *row]``) beside it, so that the test sees what it
    says."""
    monkeypatch.setattr(checksum_ops, "pallas_interpret", lambda: False)
    S, B, F, rows = 2, 8, 8, 4096
    change, form = _served_particles_tick(one_chip)
    monkeypatch.setattr(fused, "rollout_form", lambda *a: None)
    parent, _ = _served_particles_tick(one_chip)

    state = particles.make_world(2, rows, 7).commit()
    once = [x for kind, x in zip(jax.tree_util.tree_leaves(form),
                                 jax.tree_util.tree_leaves(state))
            if kind == ONCE]
    assert len(once) == 7
    copies = sum(x.nbytes for x in once) * S * (B - 1) * (F + 1)
    saved = (parent.memory_analysis().output_size_in_bytes
             - change.memory_analysis().output_size_in_bytes)
    assert saved >= 0.95 * copies

    def broadcasts(compiled, outputs_only):
        """Instructions that broadcast to a tensor of a whole branch-ring
        leaf's size (of one carried once, among the outputs)."""
        text = compiled.as_text()
        leaf = S * F * rows * (1 if outputs_only else B)
        lines = _output_definitions(text) if outputs_only else re.findall(
            r"(?m)^\s*%?[\w.\-]+ = (.*)$", text)
        found = []
        for line in lines:
            shape = re.match(r"\w+\[([\d,]*)\][^ ]* broadcast\(", line)
            if shape and np.prod(
                    [int(d) for d in shape.group(1).split(",") if d]) >= leaf:
                found.append(line)
        return found

    # At this size the parent packs most of the leaves it broadcast (they
    # feed a concatenation, not an output): it has them all the same.
    assert len(broadcasts(parent, False)) == len(once)
    assert not broadcasts(change, False)
    assert not broadcasts(change, True)
