"""The MXU force kernel compiled for a described v5e, at the shapes the
benchmark's cells run (no chip: the TPU's compiler is installed here and
compiles for a chip that is described and not attached).

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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bevy_ggrs_tpu.models import boids
from bevy_ggrs_tpu.ops import pairwise


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
