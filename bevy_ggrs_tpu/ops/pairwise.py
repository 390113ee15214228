"""Blocked Pallas kernels for all-pairs flocking forces (boids hot op).

The XLA path (:func:`bevy_ggrs_tpu.models.boids.pairwise_force_rows`)
materializes [R, N]-shaped neighbor masks and broadcast diffs; at the
BASELINE.md config-4 scale (1k+ boids × branches × frames) those
intermediates round-trip HBM. The kernels here tile rows × columns through
VMEM: each (row-block, col-block) step computes the block's pairwise
interactions entirely on-chip and folds them into per-row neighborhood sums
(neighbor count, separation x/y, velocity sum x/y, position sum x/y) held
in VMEM scratch; the final column step applies the mean/weight combine and
writes the force — one HBM read per input element, one write per output.

The column-block accumulation order is fixed (sequential grid), so results
are deterministic per platform+shape — the property SyncTest checks — but
float association differs from the XLA path, so the two are allclose, not
bitwise equal: a session must use one path consistently, same as the
reference's "all peers must share an architecture" float caveat
(``/root/reference/examples/README.md:13-18``).

Two kernels: the MXU kernel the boids configurations run
(:func:`pairwise_force_rows_mxu2`: the per-row sums as skinny matmuls, the
masks on the VPU) and its symmetry-halved triangle form for N >= 4,096. (A
third, which reduced the sums on the VPU as well, read 15.6 ms a 128 x 8
tick at N = 1,024 against this one's 6.4 and went in PR 57.) What the chip
read of them, by PR, shape and date, is in each one's docstring; the sizes
below are chosen by what the vector unit's 64 registers and four ALU slots
take, not by what fits VMEM (a [512, 1024] float32 block is 512 registers,
and VMEM holds dozens of them).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bevy_ggrs_tpu.obs.trace import TRACE_PREFIX, device_scope
from bevy_ggrs_tpu.ops.interpret import pallas_interpret

# Every dense force path runs under this device scope (``obs/trace.py``
# ``device_scope``; here and the XLA path of models/boids.py), so an
# operation's metadata says it is the flocking force, and the scope's last
# part names the Mosaic call in the trace: ``pairwise_force.N``.
FORCE = "pairwise_force"
FORCE_SCOPE = TRACE_PREFIX + FORCE


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# MXU variant: per-row sums as mask-matrix matmuls
# ---------------------------------------------------------------------------


# Rows of the separation feature stack: hi and lo of (active, px, py), and a
# third term of px and py (see _lane_feats).
SEP_ROWS = 8


def _tcol(row: jnp.ndarray) -> jnp.ndarray:
    """[1, R] lane-major -> [R, 1] sublane-major, inside the kernel.

    Every array upstream of the kernel is lane-major in the entity axis
    (XLA lays [B, N]-shaped state that way for the elementwise physics),
    but the pair matrix needs its row coordinate on SUBLANES. Round 3
    passed the kernel pre-transposed [R, 1] operands and let XLA relayout
    them: the profiler showed those copies cost ~1.2 ms of the 6.9 ms
    config-4 rollout (~1.1 us per branch-frame, per operand — fixed cost,
    not bandwidth), and only ~0.19 ms at 4k x 8b — the entire measured
    1k-vs-4k gap at equal pair counts (round-3 verdict weak #1). A
    Mosaic-native in-register transpose of the [1, R_BLK] block is far
    cheaper than either the XLA relayout or an MXU transpose-by-ones-dot
    (measured: K=1 dots at HIGHEST precision are latency-bound)."""
    return jnp.transpose(row, (1, 0))


def _pair_masks(rpx, rpy, cpx, cpy, *, neighbor_radius, separation_radius,
                w_cap=None):
    """Shared mask block of both MXU kernels: pair distances -> the bf16
    neighbor mask and the hi/lo-split separation weight matrix, and with
    ``w_cap`` the float32 ``(dx, dy, w)`` beside a weight matrix capped at it
    (see :func:`_close_pair_sums`; ``None`` without).

    ``d2`` and the membership compares stay f32 (borderline pairs classify
    identically on every path); ``rsqrt(d2)`` needs no epsilon clamp
    because pairs with ``d2 < 1e-10`` are outside ``nb``, so an inf can
    never be selected into ``w``; the neighbor mask is a direct predicate
    cast (exact 1.0/0.0 in bf16)."""
    dx = rpx - cpx  # [R_BLK, C_BLK]
    dy = rpy - cpy
    d2 = dx * dx + dy * dy
    nb = (d2 < jnp.float32(neighbor_radius) ** 2) & (
        d2 >= jnp.float32(1e-10)  # excludes self-pairs
    )
    neigh = nb.astype(jnp.bfloat16)
    inv_d = jax.lax.rsqrt(d2)
    w = jnp.where(
        nb & (d2 < jnp.float32(separation_radius) ** 2), inv_d,
        jnp.float32(0.0),
    )
    uncapped = None
    if w_cap is not None:
        uncapped = (dx, dy, w)
        w = jnp.minimum(w, jnp.float32(w_cap))
    w_hi = w.astype(jnp.bfloat16)
    w_lo = (w - w_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return neigh, w_hi, w_lo, uncapped


# The separation sum's matmul form, rpx * sum(w) - sum(w * cpx), cancels two
# float32 numbers of size |p| * w: a pair at distance d costs the force about
# 2e-8 * |p| / d (measured: 2e-5 at d = 1e-3 for |p| = 8, 3e-4 at 1e-4),
# where differencing first costs nothing. So the matmuls carry each weight up
# to CLOSE_W = 1 / (5e-3) only, and what a closer pair's weight has above it
# goes through the differenced form on the VPU, in the 128-row strips that
# hold such a pair. That is rare: in the plain reference's replay of the
# served cell's matches (tools/force_paths.py --count-only, 4 matches x 140
# frames from the common spawn) 1.3 % of force evaluations and 0.27 % of
# strips hold one, and none after the flock has spread (PERF.md section 6,
# PR 49; "about half of the block steps", PR 31, was a guess from the
# branch's cost, which was its live ranges and not its being taken).
CLOSE_W = 200.0

# Rows of a pair block whose masks are built, contracted and forgotten at a
# time: the lane tile of the lane-major row operands and of the accumulators'
# row axis (a narrower window of either is not tile-aligned) and the MXU's
# tile. Nobody's setting: the tests pass other heights to show that a force
# does not depend on it.
STRIP_ROWS = 128


def _close_pair_sums(dx, dy, w, col_active):
    """``sum_j max(w - CLOSE_W, 0) * (dx, dy)`` per row over the active
    columns, lane-major ``[2, R]``: the part of the separation sum the
    capped matmuls leave out, summed as differences. (The matmuls drop an
    inactive column through its zero features; here it takes the mask.)"""
    over = jnp.maximum(w - jnp.float32(CLOSE_W), jnp.float32(0.0)) * col_active
    sums = jnp.concatenate(
        [jnp.sum(dx * over, axis=1, keepdims=True),
         jnp.sum(dy * over, axis=1, keepdims=True)], axis=1
    )  # [R, 2]
    return jnp.transpose(sums, (1, 0))


def _acc_sums(acc_n, acc_w, sl=None, cacc_n=None, cacc_w=None):
    """Hi+lo accumulator sums read as REF SLICES: materializing the whole
    [10, R] scratch ref first (``acc_n[...]``) and slicing the value was
    measured ~0.25 us/grid-step slower — Mosaic loads the full register
    block instead of the eight rows actually used. Optionally folds in
    the triangle kernel's full-width col-side accumulators at ``sl``."""
    def row(ref, cref, i):
        r = ref[i:i + 1, :]
        return r if cref is None else r + cref[i:i + 1, sl]

    n = row(acc_n, cacc_n, 0) + row(acc_n, cacc_n, 5)
    spx = row(acc_n, cacc_n, 1) + row(acc_n, cacc_n, 6)
    spy = row(acc_n, cacc_n, 2) + row(acc_n, cacc_n, 7)
    svx = row(acc_n, cacc_n, 3) + row(acc_n, cacc_n, 8)
    svy = row(acc_n, cacc_n, 4) + row(acc_n, cacc_n, 9)
    sw = row(acc_w, cacc_w, 0) + row(acc_w, cacc_w, 3)
    swx = row(acc_w, cacc_w, 1) + row(acc_w, cacc_w, 4) + row(acc_w, cacc_w, 6)
    swy = row(acc_w, cacc_w, 2) + row(acc_w, cacc_w, 5) + row(acc_w, cacc_w, 7)
    return n, spx, spy, svx, svy, sw, swx, swy


def _combine_forces(sums, trpx, trpy, trvx, trvy, tra, *,
                    w_separation, w_alignment, w_cohesion, close=None):
    """Shared combine of both MXU kernels: the hi+lo accumulator sums
    (from :func:`_acc_sums`) -> the [1, R] force components, on lanes.
    ``close`` is the ``[2, R]`` accumulator of :func:`_close_pair_sums`."""
    one = jnp.float32(1.0)
    n, spx, spy, svx, svy, sw, swx, swy = sums
    if close is not None:
        swx = swx - close[0:1, :]
        swy = swy - close[1:2, :]
    n_safe = jnp.maximum(n, one)
    has = (n > 0).astype(jnp.float32)
    fx = (
        jnp.float32(w_separation) * (trpx * sw - swx)
        + jnp.float32(w_alignment) * (svx / n_safe - trvx) * has
        + jnp.float32(w_cohesion) * (spx / n_safe - trpx) * has
    )
    fy = (
        jnp.float32(w_separation) * (trpy * sw - swy)
        + jnp.float32(w_alignment) * (svy / n_safe - trvy) * has
        + jnp.float32(w_cohesion) * (spy / n_safe - trpy) * has
    )
    return fx * tra, fy * tra


_DOT_T = functools.partial(
    # Feature-major contraction: F[k, C] · M[R, C] -> [k, R], both operands
    # contracting their lane axis.
    jax.lax.dot_general,
    dimension_numbers=(((1,), (1,)), ((), ())),
    preferred_element_type=jnp.float32,
)


def _lane_feats(px, py, vx, vy, act):
    """Shared host-side prologue: lane-major [1, N] coordinate arrays ->
    the bf16 feature stacks ``(feat_t[10, N], sep_t[SEP_ROWS, N])``.
    Activity multiplies into the features here, so inactive and padded
    columns vanish from every neighborhood sum at zero per-pair cost."""
    f32feat = jnp.concatenate(
        [act, act * px, act * py, act * vx, act * vy], axis=0
    )  # [5, N] f32, feature-major
    hi, lo, rest = _hi_lo(f32feat)
    feat_t = jnp.concatenate([hi, lo], axis=0)  # [10, N] bf16
    # The separation sum is a cancellation, rpx * sum(w) - sum(w * cpx): what
    # hi + lo drops of a position (2**-17 of a coordinate up to 8) comes out
    # times sum(w), 4e-5 of a force on the v5e at 1,024 boids (PERF.md
    # section 6, PR 31). A third bf16 term of the two position rows takes it
    # to float32's own rounding, inside the 8-sublane tile the six rows
    # already filled.
    lo2 = _hi_lo(rest[1:3])[0]
    sep_t = jnp.concatenate([hi[0:3], lo[0:3], lo2], axis=0)  # [8, N] bf16
    return feat_t, sep_t


def _force_kernel_mxu2(
    trpx, trpy, trvx, trvy, tra,  # row refs [1, R_BLK] f32 (lane-major)
    cpx, cpy,  # col refs [1, C_BLK] f32
    feat_t, sep_t,  # [10, C_BLK] / [8, C_BLK] bf16 feature blocks
    fx_out, fy_out,  # [1, R_BLK]
    acc_n, acc_w,  # VMEM scratch [10, R_BLK] / [8, R_BLK] f32
    acc_c,  # VMEM scratch [2, R_BLK] f32: close pairs' differenced sums
    wmax_s,  # VMEM scratch [R_BLK / strip, C_BLK] f32: a strip's largest weights
    close_s,  # SMEM scratch [R_BLK / strip] i32: does the strip hold a close pair
    *,
    neighbor_radius: float,
    separation_radius: float,
    w_separation: float,
    w_alignment: float,
    w_cohesion: float,
    strip: int,
):
    """The seven per-row neighborhood sums (neighbor count, separation
    x/y, velocity sum x/y, position sum x/y) as two skinny matmuls, so the
    MXU carries the reduction:

    - every neighborhood sum is ``Σ_j M_ij · f_j`` for a pair matrix ``M``
      (the 0/1 neighbor mask, or the separation weight ``close·1/d``) and
      a per-column feature ``f ∈ {1, px, py, vx, vy}``;
    - the separation sum over pair *differences* folds into column
      features via ``Σ_j w_ij·dx_ij = rpx_i·Σ_j w_ij − Σ_j w_ij·cpx_j``;
    - column activity multiplies into the features outside the kernel, so
      inactive and padded columns vanish from every sum at zero per-pair
      cost.

    Orientation is the whole ballgame: ``M[R,C] @ F[C,k]`` puts the tiny
    k≈10 on the 128-lane axis (92% of the MXU idle — measured SLOWER than
    reducing on the VPU); feature-major ``F[k, C] · M[R, C] -> [k, R]`` (both
    operands contract their lane axis) pads k to the 8-sublane tile
    instead, and ``M`` is the operand the MXU holds still (a transposed
    push of 1.5 registers a float32 register of pairs: the MXU's slots are
    7 % used). ALL row operands arrive lane-major [1, R_BLK]; the
    pair-matrix orientation is produced in-kernel by :func:`_tcol`, a
    strip at a time, on the otherwise idle transpose unit.

    **The pair block is walked in strips of ``strip`` rows** (PR 49;
    ``STRIP_ROWS`` = 128, one grid step a 1,024-row world). The kernel is
    bound by the vector ALUs, by the compiler's own listing at the served
    shape (``[64] x [8] x 1,024``, v5e, libtpu 0.0.34): a register of 1,024
    pairs takes 26 ALU operations (5 for ``d2``, 5 compares and mask
    ANDs, 10 for ``rsqrt`` with its Newton step and special cases, 2 for
    the select and the cap, 1 for the strip's largest weight, 3 for the
    hi / lo split) in 4 slots a bundle. Written over the whole [512, 1024]
    block (until PR 49) every one of those operations was an array of 512
    registers and Mosaic emits an operation at a time: 6 spilled stores a
    register of pairs, ALU slots 76 % full, 8.75 bundles a register, and
    three arrays (``dx``, ``dy``, the uncapped ``w``) alive past the
    matmuls for a branch taken once in a thousand steps. Strip after
    strip, in straight-line code, each strip's values die before the next
    begins: 7.25 bundles a register, ALU slots 90 % full. A strip is the
    lane tile of the row operands (a narrower window of ``trpx`` is not
    aligned); narrower strips joined before the matmul cost more than
    they save (a bfloat16 ``concatenate`` is a repacking: 9.5 bundles a
    register at 16 rows), and as a ``fori_loop`` nothing overlaps a turn
    (8.4). On the chip (v5e, 2026-10-02; my chip runs, PR 49; PERF.md
    section 6): a call over ``[64] x [8]`` worlds of 1,024 boids 3.85 ->
    3.12 ms (7.5 -> 6.1 us a world), the forces bit for bit the same;
    ``pairwise_kernel_ms.serve`` 30.27 -> 24.39 ms a served dispatch
    (``boids256.synctest``), ``pairwise_kernel_ms.client`` 3.48 -> 2.78 ms
    under ``[B = 128]`` (``boids1k.wan``). A grid step costs ~0.75 us
    beside its bundles (the same strips as two 512-row steps: 6.8 us a
    world), so a 1,024-row world is one step; where every world holds a
    close pair a call is 4.74 -> 4.44 ms.

    **Close pairs.** Each strip leaves its largest uncapped weight a
    column in ``wmax_s``; after the last strip one flag a strip (does it
    pass ``CLOSE_W``) goes to ``close_s`` in scalar memory, and only where
    a flag is set does a second loop visit the strips: a strip that holds
    such a pair builds its ``dx``, ``dy``, ``w`` again (the same
    operations on the same operands: the same bits) and adds
    :func:`_close_pair_sums` to its columns of ``acc_c``. A strip without
    one would have added zeros, so the forces do not depend on the strip
    height (``tests/test_ops.py``), nor a row's matmul sums on which strip
    it rode in (``R`` is not the contracted axis).

    Precision: the MXU multiplies bf16 and accumulates f32. The neighbor
    mask is 0/1 (exact in bf16); the weight matrix and the features are
    split hi/lo (``x = bf16(x) + bf16(x − bf16(x))``), recovering ~f32
    products at 2x the (cheap, skinny) matmul cost — without the split,
    separation error reaches percents through the ``rpx·Σw − Σw·cpx``
    cancellation (dropping only the weight's lo term was measured at
    1.5e-3 relative force error for ~0.4 ms — rejected, accuracy class
    kept). That cancellation is also why the positions of the separation
    stack carry a third term (:func:`_lane_feats`) and why a pair closer
    than ``1 / CLOSE_W`` leaves the matmul form (:func:`_close_pair_sums`):
    with both, one step stays within a few 1e-6 of a float32 NumPy
    reference whatever the flock (PR 31). ``d2`` and the membership masks
    are computed in f32 exactly like the XLA path's, so borderline
    pairs classify identically on both; only summation rounding
    differs (allclose, not bitwise: a session uses one path throughout).
    ``rsqrt(d2)`` is taken without an epsilon clamp: pairs with
    ``d2 < 1e-10`` are outside ``nb``, so an inf can never be selected
    into ``w`` — bitwise identical, one fewer [R, C] VPU op."""
    cj = pl.program_id(1)
    n_cols = pl.num_programs(1)
    n_strips = trpx.shape[1] // strip

    @pl.when(cj == 0)
    def _reset():
        acc_n[...] = jnp.zeros_like(acc_n)
        acc_w[...] = jnp.zeros_like(acc_w)
        acc_c[...] = jnp.zeros_like(acc_c)

    def strip_masks(s):
        rows = pl.ds(pl.multiple_of(s * strip, strip), strip)
        return rows, _pair_masks(
            _tcol(trpx[:, rows]), _tcol(trpy[:, rows]), cpx[...], cpy[...],
            neighbor_radius=neighbor_radius,
            separation_radius=separation_radius,
            w_cap=CLOSE_W,
        )

    # Straight-line code, a strip after a strip: as a fori_loop the same
    # body is 1,077 bundles a strip against 930 (nothing overlaps a turn).
    for s in range(n_strips):
        rows, (neigh, w_hi, w_lo, (_, _, w)) = strip_masks(s)
        acc_n[:, rows] += _DOT_T(feat_t[...], neigh)  # [10, strip]
        acc_w[:, rows] += _DOT_T(sep_t[...], w_hi) + _DOT_T(sep_t[...], w_lo)
        wmax_s[s:s + 1, :] = jnp.max(w, axis=0, keepdims=True)

    # Which strips hold a pair closer than 1 / CLOSE_W: scalars, all read
    # here in one go (a vector's way to a scalar is ~150 cycles: one after
    # the other inside the loop below they were 3 us of a taken step, and
    # taken from ``w`` inside the loop above they cost it 110 bundles a
    # strip).
    n_close = jnp.int32(0)
    for s in range(n_strips):
        close = (
            jnp.max(wmax_s[s:s + 1, :]) > jnp.float32(CLOSE_W)
        ).astype(jnp.int32)
        close_s[s] = close
        n_close += close

    @pl.when(n_close > 0)
    def _close_pairs():
        # hi of an activity of 1.0 / 0.0 is the activity itself.
        col_active = feat_t[0:1, :].astype(jnp.float32)

        def strip_close(s, carry):
            @pl.when(close_s[s] > 0)
            def _a_close_pair():
                rows, (_, _, _, (dx, dy, w)) = strip_masks(s)
                acc_c[:, rows] += _close_pair_sums(dx, dy, w, col_active)

            return carry

        jax.lax.fori_loop(0, n_strips, strip_close, 0)

    @pl.when(cj == n_cols - 1)
    def _combine():
        fx, fy = _combine_forces(
            _acc_sums(acc_n, acc_w),
            trpx[...], trpy[...], trvx[...], trvy[...], tra[...],
            w_separation=w_separation,
            w_alignment=w_alignment,
            w_cohesion=w_cohesion,
            close=acc_c[...],
        )
        fx_out[...] = fx
        fy_out[...] = fy


@functools.partial(
    jax.jit,
    static_argnames=(
        "neighbor_radius",
        "separation_radius",
        "w_separation",
        "w_alignment",
        "w_cohesion",
        "row_block",
        "col_block",
        "strip_rows",
    ),
)
@device_scope(FORCE)
def pairwise_force_rows_mxu2(
    row_pos: jnp.ndarray,  # [R, 2]
    row_vel: jnp.ndarray,  # [R, 2]
    all_pos: jnp.ndarray,  # [N, 2]
    all_vel: jnp.ndarray,  # [N, 2]
    row_active: jnp.ndarray,  # float[R]
    all_active: jnp.ndarray,  # float[N]
    *,
    neighbor_radius: float,
    separation_radius: float,
    w_separation: float,
    w_alignment: float,
    w_cohesion: float,
    row_block: int = 1024,
    col_block: int = 1024,
    strip_rows: int = STRIP_ROWS,
) -> jnp.ndarray:
    """Same contract as :func:`models.boids.pairwise_force_rows`
    (separation / alignment / cohesion force per row boid from all boids),
    tiled on-chip, reductions on the MXU in feature-major orientation (see
    :func:`_force_kernel_mxu2`).
    Rows pad to a multiple of ``STRIP_ROWS``; ``strip_rows`` is a multiple
    of it that divides the row block (or the whole block, the form before
    PR 49) and changes no bit of a force (``tests/test_ops.py``)."""
    R, N = row_pos.shape[0], all_pos.shape[0]
    r_blk = min(row_block, _round_up(R, STRIP_ROWS))
    c_blk = min(col_block, _round_up(N, 128))
    r_pad = _round_up(R, r_blk) - R
    n_pad = _round_up(N, c_blk) - N
    strip = min(strip_rows, r_blk)
    if r_blk % strip or strip % STRIP_ROWS:
        raise ValueError(
            f"strip_rows={strip_rows} does not tile a row block of {r_blk}"
        )

    def col(v, pad):
        return jnp.pad(v.astype(jnp.float32), (0, pad))

    # Every row operand is lane-major; the kernel transposes positions
    # itself (see _tcol: the XLA relayout this replaces was the whole
    # 1k-vs-4k config-4 gap).
    trows = [
        col(row_pos[:, 0], r_pad)[None, :],
        col(row_pos[:, 1], r_pad)[None, :],
        col(row_vel[:, 0], r_pad)[None, :],
        col(row_vel[:, 1], r_pad)[None, :],
        col(row_active, r_pad)[None, :],
    ]
    cols = [
        col(all_pos[:, 0], n_pad)[None, :],
        col(all_pos[:, 1], n_pad)[None, :],
    ]
    feat_t, sep_t = _lane_feats(
        cols[0], cols[1],
        col(all_vel[:, 0], n_pad)[None, :],
        col(all_vel[:, 1], n_pad)[None, :],
        col(all_active, n_pad)[None, :],
    )

    grid = ((R + r_pad) // r_blk, (N + n_pad) // c_blk)
    trow_spec = pl.BlockSpec((1, r_blk), lambda ri, cj: (0, ri))
    col_spec = pl.BlockSpec((1, c_blk), lambda ri, cj: (0, cj))
    feat_spec = pl.BlockSpec((10, c_blk), lambda ri, cj: (0, cj))
    sep_spec = pl.BlockSpec((SEP_ROWS, c_blk), lambda ri, cj: (0, cj))
    out_spec = pl.BlockSpec((1, r_blk), lambda ri, cj: (0, ri))
    kernel = functools.partial(
        _force_kernel_mxu2,
        neighbor_radius=neighbor_radius,
        separation_radius=separation_radius,
        w_separation=w_separation,
        w_alignment=w_alignment,
        w_cohesion=w_cohesion,
        strip=strip,
    )
    fx, fy = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[trow_spec] * 5 + [col_spec] * 2 + [feat_spec, sep_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, R + r_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, R + r_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((10, r_blk), jnp.float32),
            pltpu.VMEM((SEP_ROWS, r_blk), jnp.float32),
            pltpu.VMEM((2, r_blk), jnp.float32),
            pltpu.VMEM((r_blk // strip, c_blk), jnp.float32),
            pltpu.SMEM((r_blk // strip,), jnp.int32),
        ],
        interpret=pallas_interpret(),
    )(*trows, *cols, feat_t, sep_t)
    return jnp.concatenate([fx[0, :R, None], fy[0, :R, None]], axis=1)



def _hi_lo(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``x = hi + lo + rest``: two bf16 halves and what they leave (float32,
    exact, as the difference of a value and its own rounding is). The
    rounding to bf16 precision
    is a ``reduce_precision``, which XLA must keep: written as a convert
    round trip (``x.astype(bf16).astype(f32)``) the TPU compiler, allowed
    excess precision, folds it to ``x`` and ``lo`` comes out all zero —
    measured on the v5e (chip_smoke.py, PR 21) as force errors of 6e-2 to
    3e0 through the separation term's cancellation. Same values bit for
    bit wherever the round trip was honoured (the CPU)."""
    def rounded(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = rounded(x)
    lo = rounded(x - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16), x - hi - lo


# ---------------------------------------------------------------------------
# Triangle variant: symmetry-halved mask work for the square (all-vs-all) case
# ---------------------------------------------------------------------------


def _force_kernel_tri(
    trpx, trpy, trvx, trvy, tra,  # [1, B0] f32 row blocks (at ri)
    cpx, cpy,  # [1, B0] f32 col blocks (at cj)
    feat_c, sep_c,  # [10, B0] / [8, B0] bf16 features at cj
    feat_r, sep_r,  # [10, B0] / [8, B0] bf16 features at ri
    fx_out, fy_out,  # [1, B0] (at ri)
    acc_n, acc_w,  # row-side scratch [10, B0] / [8, B0] f32
    cacc_n, cacc_w,  # col-side scratch [10, NB] / [8, NB] f32 (full width)
    rp_s,  # [B0, 2] f32 transposed row-position cache
    *,
    neighbor_radius: float,
    separation_radius: float,
    w_separation: float,
    w_alignment: float,
    w_cohesion: float,
    b0: int,
):
    """Symmetry-exploiting version of :func:`_force_kernel_mxu2` for the
    square all-vs-all case (``rows is cols`` — the unsharded flock step).

    Both pair matrices are symmetric (``neigh`` trivially; ``w`` because
    distance and both radii are), so each off-diagonal block's masks — the
    VPU work that dominates this kernel (measured round 4: the MXU dots
    are near-free at k <= 32) — are computed ONCE and accumulated in both
    directions: row-side via the feature-major transposed contraction,
    col-side by contracting the block's ROW axis with the standard matmul
    orientation into full-width accumulators. Blocks with ``cj < ri`` are
    predicated off entirely. Mask work per frame drops from ``n²`` to
    ``n(n+1)/2`` blocks (n = N/B0): 56% at N=4096/B0=1024 — measured
    5.2 -> 4.25 ms on the 4k x 8b x 8f rollout — approaching 50% as N
    grows; at N=1024 the 2x2 block grid cannot amortize the col-side dots
    and the skipped-step overhead (measured 6.4 vs 5.9 ms), so
    :func:`flock_system_mxu`'s dispatch keeps the general kernel below
    4096 boids.

    Correctness of the staging: col-side contributions to column range k
    come only from blocks (ri < k, cj = k), all of which execute before
    row strip k's final column step (grid iterates cj-minor), where the
    combine reads ``acc + cacc[k]``. The diagonal block covers its range
    entirely row-side (every entity there is a row). Accumulation
    regroups float sums vs the general kernel — allclose, not bitwise;
    same per-session kernel-choice contract as every other path.

    Not here: the general kernel's differenced sums for close pairs
    (:func:`_close_pair_sums`). Both directions of a block would need
    them; a pair closer than 5e-3 keeps the matmul form's cancellation
    (about 2e-8 * |p| / d of a force) until a cell runs N >= 4096. Nor
    its strips (PR 49): this kernel still builds a whole [B0, B0] block's
    masks at once and caches the transposed rows in ``rp_s``. What the two
    share, unchanged by PR 49: :func:`_pair_masks` (called here without
    ``w_cap``), :func:`_acc_sums`, :func:`_combine_forces`,
    :func:`_lane_feats`, ``_DOT_T``."""
    ri = pl.program_id(0)
    cj = pl.program_id(1)
    n_cols = pl.num_programs(1)

    @pl.when((ri == 0) & (cj == 0))
    def _init_cacc():
        cacc_n[...] = jnp.zeros_like(cacc_n)
        cacc_w[...] = jnp.zeros_like(cacc_w)

    @pl.when(cj == ri)
    def _reset_row():
        acc_n[...] = jnp.zeros_like(acc_n)
        acc_w[...] = jnp.zeros_like(acc_w)
        rp_s[...] = jnp.concatenate(
            [_tcol(trpx[...]), _tcol(trpy[...])], axis=1
        )

    @pl.when(cj >= ri)
    def _compute():
        neigh, w_hi, w_lo, _ = _pair_masks(
            rp_s[:, 0:1], rp_s[:, 1:2], cpx[...], cpy[...],
            neighbor_radius=neighbor_radius,
            separation_radius=separation_radius,
        )
        acc_n[...] += _DOT_T(feat_c[...], neigh)
        acc_w[...] += _DOT_T(sep_c[...], w_hi) + _DOT_T(sep_c[...], w_lo)

        @pl.when(cj > ri)
        def _colside():
            dot_s = functools.partial(
                jax.lax.dot_general,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sl = pl.dslice(cj * b0, b0)
            cacc_n[:, sl] += dot_s(feat_r[...], neigh)
            cacc_w[:, sl] += dot_s(sep_r[...], w_hi) + dot_s(
                sep_r[...], w_lo
            )

    @pl.when(cj == n_cols - 1)
    def _combine():
        sl = pl.dslice(ri * b0, b0)
        fx, fy = _combine_forces(
            _acc_sums(acc_n, acc_w, sl, cacc_n, cacc_w),
            trpx[...], trpy[...], trvx[...], trvy[...], tra[...],
            w_separation=w_separation,
            w_alignment=w_alignment,
            w_cohesion=w_cohesion,
        )
        fx_out[...] = fx
        fy_out[...] = fy


@functools.partial(
    jax.jit,
    static_argnames=(
        "neighbor_radius",
        "separation_radius",
        "w_separation",
        "w_alignment",
        "w_cohesion",
        "block",
    ),
)
@device_scope(FORCE)
def pairwise_force_square_mxu_tri(
    pos: jnp.ndarray,  # [N, 2]
    vel: jnp.ndarray,  # [N, 2]
    active: jnp.ndarray,  # float[N]
    *,
    neighbor_radius: float,
    separation_radius: float,
    w_separation: float,
    w_alignment: float,
    w_cohesion: float,
    block: int = 1024,
) -> jnp.ndarray:
    """All-vs-all flocking force with symmetry-halved pair work (see
    :func:`_force_kernel_tri`). Square case only — every entity is both a
    row and a column, which is what makes the triangle reuse valid; the
    sharded row-subset contract keeps using
    :func:`pairwise_force_rows_mxu2`."""
    N = pos.shape[0]
    b0 = min(block, _round_up(N, 128))
    pad = _round_up(N, b0) - N
    NB = N + pad

    def col(v):
        return jnp.pad(v.astype(jnp.float32), (0, pad))

    trows = [
        col(pos[:, 0])[None, :],
        col(pos[:, 1])[None, :],
        col(vel[:, 0])[None, :],
        col(vel[:, 1])[None, :],
        col(active)[None, :],
    ]
    feat_t, sep_t = _lane_feats(
        trows[0], trows[1], trows[2], trows[3], trows[4]
    )

    n_blocks = NB // b0
    grid = (n_blocks, n_blocks)
    trow_spec = pl.BlockSpec((1, b0), lambda ri, cj: (0, ri))
    col_spec = pl.BlockSpec((1, b0), lambda ri, cj: (0, cj))
    feat_c_spec = pl.BlockSpec((10, b0), lambda ri, cj: (0, cj))
    sep_c_spec = pl.BlockSpec((SEP_ROWS, b0), lambda ri, cj: (0, cj))
    feat_r_spec = pl.BlockSpec((10, b0), lambda ri, cj: (0, ri))
    sep_r_spec = pl.BlockSpec((SEP_ROWS, b0), lambda ri, cj: (0, ri))
    out_spec = pl.BlockSpec((1, b0), lambda ri, cj: (0, ri))
    kernel = functools.partial(
        _force_kernel_tri,
        neighbor_radius=neighbor_radius,
        separation_radius=separation_radius,
        w_separation=w_separation,
        w_alignment=w_alignment,
        w_cohesion=w_cohesion,
        b0=b0,
    )
    fx, fy = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[trow_spec] * 5 + [col_spec] * 2
        + [feat_c_spec, sep_c_spec, feat_r_spec, sep_r_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, NB), jnp.float32),
            jax.ShapeDtypeStruct((1, NB), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((10, b0), jnp.float32),
            pltpu.VMEM((SEP_ROWS, b0), jnp.float32),
            pltpu.VMEM((10, NB), jnp.float32),
            pltpu.VMEM((SEP_ROWS, NB), jnp.float32),
            pltpu.VMEM((b0, 2), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(*trows, trows[0], trows[1], feat_t, sep_t, feat_t, sep_t)
    return jnp.concatenate([fx[0, :N, None], fy[0, :N, None]], axis=1)

