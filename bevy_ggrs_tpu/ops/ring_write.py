"""Pallas kernel for a per-lane ring row write that moves one row a lane.

``state.py`` ``ring_row_write`` under a ``vmap`` that batches the index
(every hosted match has its own frame counter) is, in XLA, a select over the
whole ``[lanes, depth, ...]`` leaf: right for a row of a few hundred bytes,
and ``2 x depth + 1`` times the bytes that change for a large one. This is
the same write as data movement and nothing else: ring and rows stay in
HBM, the ring is aliased to the output, and a lane whose flag is set gets
ONE asynchronous copy ``rows[lane] -> ring[lane, slot[lane]]``; a lane whose
flag is clear (a padding step of a burst) gets none. All copies are started,
then all awaited: the DMA engines run them side by side.

A DMA addresses whole tiles, so a row is ``[r, 128]`` with ``r`` a multiple
of 8 and a 32-bit dtype (:func:`tiles`; Mosaic refuses a ``[depth, n]``
ring, where the depth lies in a tile's sublanes, and ``bool``). Bits are
only moved: the result is bit for bit ``jnp.where(hot, row, ring)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bevy_ggrs_tpu.ops.interpret import pallas_interpret

LANES = 128  # a tile's minor axis
SUBLANES = 8  # a 32-bit tile's second-minor axis


def tiles(n: int, dtype) -> tuple | None:
    """``[r, 128]`` for a row of ``n`` elements of ``dtype`` that is whole
    ``(8, 128)`` tiles of a 32-bit type; None for any other."""
    if jnp.dtype(dtype).itemsize != 4 or n == 0 or n % (SUBLANES * LANES):
        return None
    return (n // LANES, LANES)


def _copy_rows_kernel(slot_ref, flag_ref, rows_ref, ring_ref, out_ref, sem):
    del ring_ref  # aliased to ``out_ref``: what is not copied over stays

    def copy(lane):
        return pltpu.make_async_copy(
            rows_ref.at[lane], out_ref.at[lane, slot_ref[lane]], sem
        )

    def start(lane, carry):
        pl.when(flag_ref[lane] != 0)(lambda: copy(lane).start())
        return carry

    def wait(lane, carry):
        pl.when(flag_ref[lane] != 0)(lambda: copy(lane).wait())
        return carry

    lanes = rows_ref.shape[0]
    jax.lax.fori_loop(0, lanes, start, 0)
    jax.lax.fori_loop(0, lanes, wait, 0)


def write_rows_in_place(
    ring: jnp.ndarray,  # [lanes, depth, r, 128]
    rows: jnp.ndarray,  # [lanes, r, 128]
    slot: jnp.ndarray,  # int32[lanes], in [0, depth)
    flag: jnp.ndarray,  # int32[lanes], nonzero: this lane writes
) -> jnp.ndarray:
    """``ring`` with ``ring[lane, slot[lane]] = rows[lane]`` for every lane
    whose ``flag`` is set."""
    lanes, _, r, minor = ring.shape
    assert tiles(r * minor, ring.dtype) == (r, minor), ring.shape
    assert rows.shape == (lanes, r, minor) and rows.dtype == ring.dtype
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)  # no block, no copy
    return pl.pallas_call(
        _copy_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[where_it_lies, where_it_lies], out_specs=where_it_lies,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        # The output, and with it the aliased ring, is pinned to HBM: left
        # to itself the compiler parks a ring that fits in VMEM for the
        # call, a copy of the whole ring in and out around every step.
        out_shape=pltpu.HBM(ring.shape, ring.dtype),
        input_output_aliases={3: 0},  # slot, flag, rows, RING -> out
        interpret=pallas_interpret(),
    )(slot.astype(jnp.int32), flag.astype(jnp.int32), rows, ring)
