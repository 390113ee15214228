"""The MXU force kernel compiled for a described v5e, at the shapes the
benchmark's cells run (no chip: the TPU's compiler is installed here and
compiles for a chip that is described and not attached); and the served
particles tick at a small ``[S] x [B] x F``, for what its outputs are made
of (PR 51: a rollout's rows leave the program as its loop wrote them) and
for how its bursts save a large row (PR 53: one Mosaic copy a lane, no
select of a whole ring leaf), beside the box_game tick that must not move.

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

from bevy_ggrs_tpu import fused, rollout
from bevy_ggrs_tpu.models import boids, box_game, particles
from bevy_ggrs_tpu.ops import checksum as checksum_ops
from bevy_ggrs_tpu.ops import pairwise
from bevy_ggrs_tpu.ops import ring_write
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.state import ONCE
from tests.test_device_phases import _computation
from tests.test_lane_uniform_ring import parents_write


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


def _served_tick(one_chip, schedule, state, input_spec):
    """The ``[2] x [8] x 8`` batched tick of a title, compiled; the kinds
    its carry gives the state's leaves."""
    S, B, F = 2, 8, 8
    core = BatchedSessionCore(
        schedule, state, 8, 2, input_spec, num_slots=S, num_branches=B,
        spec_frames=F)
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (core._carry,) + tuple(core._host_args()))
    return core._exec._fn.lower(*args).compile(), core._exec.packed.form


def _served_particles_tick(one_chip, rows=4096):
    """:func:`_served_tick` of ``particles`` at ``rows`` rows a match."""
    return _served_tick(
        one_chip, particles.make_schedule(7),
        particles.make_world(2, rows, 7).commit(), particles.INPUT_SPEC)


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels of a served tick compiled, as on the chip (the rule
    reads the default backend, which is the CPU here)."""
    for ops in (checksum_ops, pairwise, ring_write):
        monkeypatch.setattr(ops, "pallas_interpret", lambda: False)


def _output_definitions(text):
    """The defining instruction of every output of the entry computation."""
    entry = text[text.index("ENTRY"):]
    defined = dict(re.findall(r"(?m)^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", entry))
    root = re.search(r"(?m)^\s*ROOT [^\n]*tuple\(([^\n]*)", entry).group(1)
    return [defined[name] for name in re.findall(r"%([\w.\-]+)", root)]


def test_served_particles_tick_writes_no_branch_invariant_row_twice(
        one_chip, mosaic, monkeypatch):
    """A leaf no branch's inputs reach leaves the compiled tick once, not
    broadcast over the branches: the outputs shrink by those copies, and no
    output of a ring leaf's size is a broadcast. The parent's form (every
    leaf ``[S, B, F, *row]``) beside it, so that the test sees what it
    says."""
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


def _loop_with(text, scope):
    """The text of the one loop body of ``text`` that holds an operation
    traced under ``scope``."""
    names = set(re.findall(r"body=%([\w.\-]+)", text))
    bodies = [c for c in text.split("\n\n") if scope in c
              and c.lstrip()[1:].split(" ", 1)[0] in names]
    assert len(bodies) == 1
    return bodies[0]


def test_served_particles_burst_saves_a_large_row_by_one_copy_a_lane(
        one_chip, mosaic, monkeypatch):
    """Inside the burst's loop the four 32-bit leaves are written by the
    Mosaic kernel, under the scope the trace reads it by; no operation
    there selects over a whole f32 / s32 ring leaf any more (the ``bool``
    leaves' select stays), and the program needs no more scratch, within a
    quarter at this small size, than the parent's form, which is compiled beside it so that the
    test sees what it says."""
    S, depth, rows = 2, 9, particles.CAPACITY  # ttl's row: 36 KB
    change, _ = _served_particles_tick(one_chip, rows)
    parents_write(monkeypatch)
    parent, _ = _served_particles_tick(one_chip, rows)

    ring_leaf = re.compile(
        r"= \(?(?:f32|s32)\[%d,%d,(?:%d|%d|%d,128|%d,128)\]\S* [^\n]*"
        r"(?:select|fusion)\(" % (S, depth, rows, 2 * rows, rows // 128,
                                  2 * rows // 128))

    def burst(compiled):
        body = _loop_with(compiled.as_text(), "ggrs/burst)/while/body")
        calls = [
            line for line in body.splitlines() if "tpu_custom_call" in line
            and "ggrs/ring_write" in line]
        selects = [
            line for line in body.splitlines()
            if "ggrs/ring_write" in line and ring_leaf.search(line)]
        return calls, selects

    calls, selects = burst(change)
    assert len(calls) == 4 and not selects
    for line in calls:
        assert re.search(
            r'op_name="[^"]*ggrs/burst\)/while/body/ggrs/ring_write/', line)
        assert "output_to_operand_aliasing={{}: (3, {})}" in line
        # ring and output stay in HBM: parked in VMEM ("S(1)") the whole
        # ring would be copied in and out around every call
        assert not re.match(r"\s*%\S+ = \S*S\(1\)", line)
    calls, selects = burst(parent)
    assert not calls and selects
    # (8.5 MB beside 7.7 under two slots, where the rings fit the chip's
    # VMEM either way; 49.6 MB beside 96.7 under the cell's 64)
    assert (change.memory_analysis().temp_size_in_bytes
            <= 1.25 * parent.memory_analysis().temp_size_in_bytes)


def test_served_box_game_tick_is_the_parents(one_chip, mosaic, monkeypatch):
    """No box_game row is large: its compiled tick holds no kernel of this
    package's ring writes and is, operation for operation, the program of
    the parent's form."""
    tick = lambda: _served_tick(    # noqa: E731
        one_chip, box_game.make_schedule(), box_game.make_world(2).commit(),
        box_game.INPUT_SPEC)[0]
    change = tick()
    assert "ring_write/pallas_call" not in change.as_text()
    parents_write(monkeypatch)
    assert _computation(tick().as_text()) == _computation(change.as_text())


def test_served_boids_tick_steps_a_class_of_the_tree_a_kernel_call(
        one_chip, mosaic, monkeypatch):
    """The flock's rollout shares its steps (``rollout.py`` ``share_width``:
    8 KB leaves): in the compiled ``[2] x [8] x 8`` tick the force kernel
    stands twice, the burst's and the one of the level's loop, called for
    one world a lane where the parent's form calls it for all eight
    branches; the step that says what the leaves no input reaches become
    keeps no kernel (its force feeds nothing)."""
    tick = lambda: _served_tick(    # noqa: E731
        one_chip, boids.make_schedule(kernel="mxu"),
        boids.make_world(1024, 2).commit(), boids.INPUT_SPEC)[0].as_text()
    kernel = re.compile(
        r"%pairwise_force[\w.]* = \(f32\[([\d,]+)\][^\n]*custom-call\(")
    worlds = lambda text: sorted(    # noqa: E731
        int(np.prod([int(d) for d in m.split(",")])) // 1024
        for m in kernel.findall(text))
    change = tick()
    assert worlds(change) == [2, 2]
    rolls = _loop_with(
        change, "ggrs/rollout)/while/body/closed_call/while/body")
    assert len(kernel.findall(rolls)) == 1
    monkeypatch.setattr(rollout, "share_width", lambda *a: None)
    assert worlds(tick()) == [2, 16]
