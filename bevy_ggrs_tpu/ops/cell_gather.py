"""Pallas cell-gather kernel: per-cell slot×candidate interaction on TPU.

The grid-mode counterpart of :mod:`ops.pairwise`. The XLA grid path
materializes [C, K, M] pair-term intermediates in HBM; this kernel streams
the candidate axis through VMEM in ``col_chunk`` slices, keeps the
``n_terms`` running sums in VMEM scratch (one [cell_block, K] accumulator
per term, the idiom of ``ops/pairwise._force_kernel_mxu2``), and applies
``PairKernel.combine`` on-chip in the last column step — HBM traffic is
the gathered operands plus [C, K] outputs, never the pair cube.

Block layout: grid = (C / cell_block, M_padded / chunk); each step loads
``cell_block`` cells' row arrays ([cell_block, K]) and candidate arrays
([cell_block, chunk]) and unrolls a Python loop over the cells — every
in-kernel op is 2D ([K, chunk] pair blocks from a [K, 1] × [1, chunk]
broadcast, the in-register transpose trick of ``ops.pairwise._tcol``),
which is the shape family Mosaic handles best. Padding (K to the sublane
multiple, M to the chunk multiple) carries active=0, so the PairKernel
masking contract zeroes it; padded K columns are sliced off on return.

Numerics: accumulation order over candidates is identical to the XLA grid
path's ``jnp.sum`` over a [.., .., M] axis only up to reassociation — like
the dense kernels, grid-Pallas vs grid-XLA is allclose, not bitwise; each
impl is bitwise-reproducible with itself per platform+shape. Off-TPU the
kernel runs in interpret mode (``ops.interpret``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bevy_ggrs_tpu.ops.interpret import pallas_interpret


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def cell_slot_forces_pallas(kernel, rowvals, colvals, *, cell_block: int = 8,
                            col_chunk: int = 512):
    """Per-cell interaction outputs, tuple of ``out_dim`` [C, K] arrays.

    ``rowvals``/``colvals`` map ``kernel.row_names``/``col_names`` to
    gathered [C, K] / [C, M] f32 arrays (``neighbor.slot_forces`` builds
    them). ``kernel`` is a :class:`~bevy_ggrs_tpu.ops.neighbor.PairKernel`.
    """
    row_arrays = [rowvals[n].astype(jnp.float32) for n in kernel.row_names]
    col_arrays = [colvals[n].astype(jnp.float32) for n in kernel.col_names]
    c, k = row_arrays[0].shape
    m = col_arrays[0].shape[1]
    cb = min(cell_block, c)
    if c % cb:
        raise ValueError(f"num_cells {c} not divisible by cell_block {cb}")
    kp = _round_up(k, 8)
    chunk = _round_up(m, 128) if m <= col_chunk else col_chunk
    if chunk % 128:
        raise ValueError(f"col_chunk {chunk} must be a multiple of 128")
    mp = _round_up(m, chunk)
    if kp != k:
        row_arrays = [jnp.pad(a, ((0, 0), (0, kp - k))) for a in row_arrays]
    if mp != m:
        col_arrays = [jnp.pad(a, ((0, 0), (0, mp - m))) for a in col_arrays]

    n_row, n_col = len(row_arrays), len(col_arrays)
    n_out, n_terms = kernel.out_dim, kernel.n_terms
    row_names, col_names = kernel.row_names, kernel.col_names

    def body(*refs):
        row_refs = refs[:n_row]
        col_refs = refs[n_row:n_row + n_col]
        out_refs = refs[n_row + n_col:n_row + n_col + n_out]
        accs = refs[n_row + n_col + n_out:]
        cj = pl.program_id(1)

        @pl.when(cj == 0)
        def _reset():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        for i in range(cb):
            # [K, 1] row operands against this chunk's [1, chunk] cols.
            row = {
                name: jnp.transpose(ref[i:i + 1, :], (1, 0))
                for name, ref in zip(row_names, row_refs)
            }
            col = {
                name: ref[i:i + 1, :]
                for name, ref in zip(col_names, col_refs)
            }
            dx = row["px"] - col["px"]
            dy = row["py"] - col["py"]
            d2 = dx * dx + dy * dy
            terms = kernel.accumulate(dx, dy, d2, row, col)
            for term, acc in zip(terms, accs):
                part = jnp.sum(term, axis=1, keepdims=True)  # [K, 1]
                acc[i:i + 1, :] += jnp.transpose(part, (1, 0))

        @pl.when(cj == pl.num_programs(1) - 1)
        def _combine():
            for i in range(cb):
                sums = tuple(acc[i:i + 1, :] for acc in accs)
                row = {
                    name: ref[i:i + 1, :]
                    for name, ref in zip(row_names, row_refs)
                }
                outs = kernel.combine(sums, row)
                for out, ref in zip(outs, out_refs):
                    ref[i:i + 1, :] = out.astype(jnp.float32)

    row_spec = pl.BlockSpec((cb, kp), lambda ci, cj: (ci, 0))
    col_spec = pl.BlockSpec((cb, chunk), lambda ci, cj: (ci, cj))
    outs = pl.pallas_call(
        body,
        grid=(c // cb, mp // chunk),
        in_specs=[row_spec] * n_row + [col_spec] * n_col,
        out_specs=[row_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((c, kp), jnp.float32)] * n_out,
        scratch_shapes=[pltpu.VMEM((cb, kp), jnp.float32)] * n_terms,
        interpret=pallas_interpret(),
    )(*row_arrays, *col_arrays)
    if n_out == 1:
        outs = (outs,) if not isinstance(outs, (list, tuple)) else outs
    return tuple(o[:, :k] for o in outs)
