"""Deterministic spatial-binning neighbor grid: O(N·k) pairwise interaction.

Every force path in the tree before this module — XLA
(:func:`bevy_ggrs_tpu.models.boids.pairwise_force_rows`), VPU-Pallas and
MXU (:mod:`bevy_ggrs_tpu.ops.pairwise`) — is all-pairs O(N²), so the
single-chip entity ceiling (~20k boids against the 16 ms budget) is set by
the asymptote, not kernel tuning. This module bins entities into a
fixed-shape spatial grid and evaluates pair interactions over the 9-cell
neighborhood only, turning the per-frame pair count from N² into
N·(9K + S) — with every shape static, so the result composes unchanged
with ``vmap`` (speculative branches), ``lax.scan`` (frame bursts) and
``shard_map`` (entity sharding).

Binning (bitwise-reproducible — the determinism contract):

- cell id = ``(floor(y/s) mod G)·G + (floor(x/s) mod G)`` with s =
  ``cell_size`` ≥ the interaction radius and G = ``grid_dim`` ≥ 4. The mod
  wrap makes every position binnable without data-dependent bounds; two
  points that alias into neighboring buckets while physically distant are
  only ever FALSE candidates — the kernel's own d² < r² mask rejects them,
  so aliasing affects cost, never values. G ≥ 4 keeps the nine neighbor
  offsets distinct mod G (no cell is visited twice, no pair double-counts).
- entities are ordered by a STABLE argsort of their cell id (ties broken
  by entity index — the reproducible order), then ranked within their
  cell by ``searchsorted``. Rank < K claims slot ``(cell, rank)``; ranks
  ≥ K spill, in the same stable order, to a dense fallback row of
  capacity S shared by every cell.
- dead/absorbed entities (``active`` false) bin to the sentinel cell C
  and reach neither slots nor spill — they mask out exactly as in
  :mod:`ops.pairwise` (force contributions and outputs are 0).
- all structures are integer tensors built from exact float ops
  (floor/mod) and unique-index scatters: bitwise-reproducible per
  platform+shape, and bit-identical to the NumPy oracle in
  ``tests/test_neighbor.py``.

Completeness: any active entity q within ``radius`` of a slotted row r
satisfies |floor-coord delta| ≤ 1 per axis (s ≥ radius), so q's bucket is
one of r's nine neighbor buckets — q is seen via its slot, or via the
spill row (appended to every cell's candidate list), or it was DROPPED
because more than S entities overflowed their cells. Drops are
deterministic, counted (``n_dropped``) and only possible when
``n > cell_capacity + spill_capacity`` in some pathological clustering;
the default configs size S so the test/bench worlds never drop. Spilled
entities' own forces are computed by a dense [S, N] fallback pass, so a
spill degrades cost, not correctness.

Float caveat (same as the kernel family): grid-mode force sums accumulate
in candidate order, a different association than the dense paths — grid
and dense are allclose, not bitwise equal; a session picks one mode, and
within grid mode the serial, fused-speculative and entity-sharded
executables are bitwise-equal to each other (machine-checked by
attestation and ``tests/test_neighbor.py``). Interactions whose terms are
pure 0/1 indicators (projectile hit tests) are exactly representable, so
dense and grid agree bitwise there.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Grid mode pays a sort + gather overhead per frame; below this entity
# count the dense paths win outright (mode="auto" crossover).
GRID_AUTO_THRESHOLD = 2048

_VALID_MODES = ("dense", "grid", "auto")

# Session-level default installed by SessionBuilder.with_interaction_mode;
# consulted (below the GGRS_FORCE_MODE env override, above the by-N auto
# rule) whenever a schedule was built without an explicit mode.
_session_default_mode: Optional[str] = None


def set_default_interaction_mode(mode: Optional[str]) -> None:
    """Install the process-wide default ``interact`` mode (``None`` clears
    it). Trace-time setting: schedules compiled before the call keep the
    mode they resolved."""
    global _session_default_mode
    if mode is not None and mode not in _VALID_MODES:
        raise ValueError(f"mode must be one of {_VALID_MODES}, got {mode!r}")
    _session_default_mode = mode


def resolve_mode(mode: Optional[str], n: int) -> str:
    """Resolve a requested interaction mode to ``"dense"`` or ``"grid"``.

    Precedence: an explicit ``"dense"``/``"grid"`` argument always wins
    (parity tests pin modes and must not be flipped under them); the
    ``GGRS_FORCE_MODE`` env var overrides ``None``/``"auto"`` (the CI
    double-run flag, mirroring ``GGRS_NO_NATIVE=1``); then the
    SessionBuilder default; then ``"auto"`` picks grid at
    ``n >= GRID_AUTO_THRESHOLD`` while ``None`` keeps the legacy dense
    path. Resolution happens at TRACE time — env changes after a schedule
    compiled have no effect on it."""
    if mode not in _VALID_MODES and mode is not None:
        raise ValueError(f"mode must be one of {_VALID_MODES}, got {mode!r}")
    if mode in ("dense", "grid"):
        return mode
    env = os.environ.get("GGRS_FORCE_MODE", "").strip().lower()
    if env in ("dense", "grid"):
        return env
    if _session_default_mode in ("dense", "grid"):
        return _session_default_mode
    if mode == "auto" or _session_default_mode == "auto":
        return "grid" if n >= GRID_AUTO_THRESHOLD else "dense"
    return "dense"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class GridConfig:
    """Static shape parameters of the neighbor grid (all trace-time
    constants — the grid never has a data-dependent shape)."""

    cell_size: float      # s: cell edge, must be >= the interaction radius
    grid_dim: int         # G: cells per axis (>= 4), C = G*G buckets
    cell_capacity: int    # K: slots per cell; rank >= K spills
    spill_capacity: int   # S: dense fallback rows shared by all cells

    def __post_init__(self):
        if self.grid_dim < 4:
            raise ValueError("grid_dim must be >= 4 (nine neighbor offsets "
                             "must stay distinct mod G)")
        if self.cell_capacity < 1 or self.spill_capacity < 1:
            raise ValueError("cell_capacity and spill_capacity must be >= 1")

    @property
    def num_cells(self) -> int:
        return self.grid_dim * self.grid_dim

    @property
    def cols(self) -> int:
        """Candidate columns per cell: 9 neighbor buckets + the spill row."""
        return 9 * self.cell_capacity + self.spill_capacity

    @property
    def padded_cols(self) -> int:
        """``cols`` rounded up to the f32 lane width (sentinel-padded)."""
        return _round_up(self.cols, 128)


def default_grid_config(n: int, radius: float,
                        world_half: float) -> GridConfig:
    """Derive the grid for an ``n``-entity world of extent ±``world_half``.

    cell_size = radius (tightest 3x3 coverage); G covers the world span
    (clamped to [4, 64] — a wider world just aliases, costing candidates,
    never correctness); K targets 2x the uniform mean occupancy
    (clustering headroom before spill); S is sized so worlds with
    n <= K + S can never drop an entity, and caps at 512 so the [S, N]
    fallback pass stays cheap at scale."""
    span = 2.0 * float(world_half)
    g = min(max(_next_pow2(int(np.ceil(span / float(radius)))), 4), 64)
    mean_occ = max(1, int(np.ceil(n / float(g * g))))
    k = min(max(_round_up(2 * mean_occ, 8), 16), 512)
    s = max(64, min(n, 512))
    return GridConfig(cell_size=float(radius), grid_dim=g,
                      cell_capacity=k, spill_capacity=s)


@functools.lru_cache(maxsize=None)
def neighbor_table(grid_dim: int) -> np.ndarray:
    """[C, 9] int32: the nine neighbor buckets (incl. self) of every cell,
    mod-wrapped. Data-independent, so it folds into the executable as a
    constant — candidate gathering never depends on positions."""
    g = grid_dim
    cy, cx = np.divmod(np.arange(g * g, dtype=np.int64), g)
    offs = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]
    tbl = np.stack(
        [((cy + dy) % g) * g + ((cx + dx) % g) for dy, dx in offs], axis=1
    )
    return tbl.astype(np.int32)


class NeighborGrid(NamedTuple):
    """Binning result. ``slots``/``spill`` hold entity indices with N as
    the empty sentinel (scatters/gathers treat N as 'drop'/'inactive')."""

    slots: jnp.ndarray      # [C, K] int32, N = empty
    spill: jnp.ndarray      # [S] int32, N = empty
    cell_of: jnp.ndarray    # [N] int32 bucket id; C for inactive
    occupancy: jnp.ndarray  # [C] int32 true per-cell count (incl. overflow)
    n_spilled: jnp.ndarray  # [] int32 entities past K (spilled or dropped)
    n_dropped: jnp.ndarray  # [] int32 entities past K + S (lost)


def bin_entities(pos: jnp.ndarray, active: jnp.ndarray,
                 config: GridConfig) -> NeighborGrid:
    """Stable sort-based binning (see module docstring for the contract).

    All ops are vmap/scan/shard_map-compatible and every scatter writes
    unique indices ((cell, rank) and spill ranks are unique), so the
    result is order-deterministic, not merely value-deterministic."""
    n = pos.shape[0]
    g, c = config.grid_dim, config.num_cells
    k, s = config.cell_capacity, config.spill_capacity
    active_b = active.astype(bool)

    inv = jnp.float32(1.0 / config.cell_size)
    ix = jnp.floor(pos[:, 0].astype(jnp.float32) * inv).astype(jnp.int32) % g
    iy = jnp.floor(pos[:, 1].astype(jnp.float32) * inv).astype(jnp.int32) % g
    cell_of = jnp.where(active_b, iy * g + ix, jnp.int32(c))  # [N]

    # Stable order: by cell, ties by entity index — THE reproducible order.
    order = jnp.argsort(cell_of, stable=True)  # [N]
    sorted_cell = cell_of[order]
    run_start = jnp.searchsorted(sorted_cell, sorted_cell, side="left")
    rank = jnp.arange(n, dtype=jnp.int32) - run_start.astype(jnp.int32)

    in_cell = sorted_cell < c
    slotted = in_cell & (rank < k)
    slot_idx = jnp.where(slotted, sorted_cell * k + rank, jnp.int32(c * k))
    slots = (
        jnp.full((c * k,), n, jnp.int32)
        .at[slot_idx].set(order.astype(jnp.int32), mode="drop")
        .reshape(c, k)
    )

    over = in_cell & (rank >= k)
    spill_rank = jnp.cumsum(over.astype(jnp.int32)) - 1
    spill_idx = jnp.where(over, spill_rank, jnp.int32(s))
    spill = jnp.full((s,), n, jnp.int32).at[spill_idx].set(
        order.astype(jnp.int32), mode="drop"
    )

    cells = jnp.arange(c, dtype=cell_of.dtype)
    occupancy = (
        jnp.searchsorted(sorted_cell, cells + 1, side="left")
        - jnp.searchsorted(sorted_cell, cells, side="left")
    ).astype(jnp.int32)
    n_spilled = jnp.sum(over.astype(jnp.int32))
    n_dropped = jnp.maximum(n_spilled - s, 0)
    return NeighborGrid(slots, spill, cell_of, occupancy, n_spilled,
                        n_dropped)


# ---------------------------------------------------------------------------
# The model-facing pair-interaction API
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PairKernel:
    """A pairwise interaction, factored so one definition drives the dense
    path, the XLA grid path and the Pallas cell-gather kernel (the shapes
    differ per path; both callbacks must use only broadcastable
    elementwise jnp ops).

    ``accumulate(dx, dy, d2, row, col)`` returns ``n_terms`` per-pair
    arrays that are SUMMED over the candidate axis. Every term must
    already carry its masks (``row["active"] * col["active"]``, the
    d² < radius² membership, self-exclusion if needed): padded/sentinel
    candidates arrive with active=0 and garbage positions, and an
    unmasked term would leak them into the sums.

    ``combine(sums, row)`` turns the summed terms into ``out_dim`` output
    components; it must multiply by ``row["active"]`` so masked rows
    output exact zeros.

    ``row``/``col`` map ``"px"``/``"py"``/``"active"`` plus the declared
    feature names to broadcast-ready arrays. ``radius`` bounds the
    interaction support — grid cells must be at least this wide."""

    radius: float
    out_dim: int
    n_terms: int
    accumulate: Callable
    combine: Callable
    row_feats: Tuple[str, ...] = ()
    col_feats: Tuple[str, ...] = ()

    @property
    def row_names(self) -> Tuple[str, ...]:
        return ("px", "py", "active") + tuple(self.row_feats)

    @property
    def col_names(self) -> Tuple[str, ...]:
        return ("px", "py", "active") + tuple(self.col_feats)


def _entity_arrays(pos, active_f, feats) -> Dict[str, jnp.ndarray]:
    base = {
        "px": pos[:, 0].astype(jnp.float32),
        "py": pos[:, 1].astype(jnp.float32),
        "active": active_f,
    }
    for name, v in (feats or {}).items():
        base[name] = v.astype(jnp.float32)
    return base


def build_grid_tables(pos, active, config: GridConfig,
                      feats: Optional[Dict[str, jnp.ndarray]] = None):
    """Bin + assemble the static gather tables shared by every grid
    consumer (unsharded interact, the sharded per-shard path, the Pallas
    kernel): the binning result, the [C, padded_cols] candidate table
    (9 neighbor buckets' slots + the spill row, sentinel-padded), and the
    sentinel-padded per-entity arrays (row N = inactive zeros, so every
    sentinel gather lands on a masked entry)."""
    n = pos.shape[0]
    active_f = active.astype(jnp.float32)
    grid = bin_entities(pos, active, config)
    c, k, s = config.num_cells, config.cell_capacity, config.spill_capacity
    tbl = jnp.asarray(neighbor_table(config.grid_dim))  # [C, 9]
    cand = jnp.concatenate(
        [grid.slots[tbl].reshape(c, 9 * k),
         jnp.broadcast_to(grid.spill[None, :], (c, s))], axis=1
    )
    pad = config.padded_cols - config.cols
    if pad:
        cand = jnp.concatenate(
            [cand, jnp.full((c, pad), n, jnp.int32)], axis=1
        )
    # Sentinel-padded arrays built by SCATTER into fresh zeros, not
    # concatenate: under GSPMD auto-sharding (entity-sharded jit), gathers
    # from an operand that inherited the entity sharding were seen
    # miscompiled by the SPMD gather partitioner of the jaxlib this was
    # written against (out-of-shard indices clamp into local padding and
    # duplicate contributions — measured then, not re-measured since); a
    # scatter-built operand gathers correctly, and the bitwise
    # serial-vs-sharded tests in tests/test_neighbor.py pin this form. The
    # shard_map path doesn't care (per-shard arrays are local), but the
    # same tables serve plain-jit executables over sharded state.
    iota = jnp.arange(n, dtype=jnp.int32)
    padded = {
        name: jnp.zeros((n + 1,), v.dtype).at[iota].set(v)
        for name, v in _entity_arrays(pos, active_f, feats).items()
    }
    return grid, cand, padded


def slot_forces(kernel: PairKernel, slots, cand, padded,
                impl: str = "xla") -> jnp.ndarray:
    """[Cb, K, out_dim] interaction outputs for a block of cells
    (``slots``/``cand`` may be a contiguous cell slice — the entity-sharded
    path calls this per shard; the unsharded path with the full tables).
    Sentinel rows compute garbage that their active=0 mask zeroes and the
    slot scatter drops."""
    rowvals = {name: padded[name][slots] for name in kernel.row_names}
    colvals = {name: padded[name][cand] for name in kernel.col_names}
    if impl == "pallas":
        from bevy_ggrs_tpu.ops.cell_gather import cell_slot_forces_pallas

        outs = cell_slot_forces_pallas(kernel, rowvals, colvals)
    else:
        row = {k2: v[:, :, None] for k2, v in rowvals.items()}
        col = {k2: v[:, None, :] for k2, v in colvals.items()}
        dx = row["px"] - col["px"]
        dy = row["py"] - col["py"]
        d2 = dx * dx + dy * dy
        terms = kernel.accumulate(dx, dy, d2, row, col)
        sums = tuple(jnp.sum(t, axis=2) for t in terms)
        outs = kernel.combine(sums, rowvals)
    return jnp.stack(outs, axis=-1)


def spill_forces(kernel: PairKernel, spill, padded) -> jnp.ndarray:
    """[S, out_dim] dense fallback: spilled entities interact with EVERY
    entity (the complete candidate set), so overflow degrades cost — an
    [S, N] pass — never the interaction values."""
    rowvals = {name: padded[name][spill] for name in kernel.row_names}
    row = {k2: v[:, None] for k2, v in rowvals.items()}
    col = {name: padded[name][None, :] for name in kernel.col_names}
    dx = row["px"] - col["px"]
    dy = row["py"] - col["py"]
    d2 = dx * dx + dy * dy
    terms = kernel.accumulate(dx, dy, d2, row, col)
    sums = tuple(jnp.sum(t, axis=1) for t in terms)
    return jnp.stack(kernel.combine(sums, rowvals), axis=-1)


def scatter_forces(n: int, slots, spill, slot_f, spill_f) -> jnp.ndarray:
    """Scatter per-slot and per-spill outputs back to entity order.
    Slot/spill membership is disjoint and sentinel indices (N) drop, so
    both scatters write unique rows; untouched rows (inactive or dropped
    overflow) stay exactly 0."""
    out_dim = slot_f.shape[-1]
    out = jnp.zeros((n, out_dim), jnp.float32)
    out = out.at[slots.reshape(-1)].set(
        slot_f.reshape(-1, out_dim), mode="drop"
    )
    return out.at[spill].set(spill_f, mode="drop")


def _interact_dense(pos, active_f, kernel: PairKernel, feats) -> jnp.ndarray:
    arrays = _entity_arrays(pos, active_f, feats)
    rowvals = {name: arrays[name] for name in kernel.row_names}
    row = {k2: v[:, None] for k2, v in rowvals.items()}
    col = {name: arrays[name][None, :] for name in kernel.col_names}
    dx = row["px"] - col["px"]
    dy = row["py"] - col["py"]
    d2 = dx * dx + dy * dy
    terms = kernel.accumulate(dx, dy, d2, row, col)
    sums = tuple(jnp.sum(t, axis=1) for t in terms)
    return jnp.stack(kernel.combine(sums, rowvals), axis=-1)


def interact(pos, active, kernel: PairKernel,
             feats: Optional[Dict[str, jnp.ndarray]] = None, *,
             mode: Optional[str] = None, config: Optional[GridConfig] = None,
             impl: str = "xla", world_half: Optional[float] = None,
             return_grid: bool = False):
    """Evaluate a pairwise interaction over all entities: the model-facing
    entry point (``models/boids.py`` grid mode, ``models/projectiles.py``
    hit test).

    ``pos`` [N, 2], ``active`` [N] (bool or 0/1 float), ``feats`` maps
    feature names to [N] arrays. ``mode`` resolves via
    :func:`resolve_mode`; grid mode needs a :class:`GridConfig` (or
    ``world_half`` to derive one). ``impl="pallas"`` routes the per-cell
    compute through the Pallas cell-gather kernel (grid mode only).
    Returns [N, out_dim]; with ``return_grid=True``, a
    ``(forces, NeighborGrid | None)`` pair for stats/tests."""
    n = pos.shape[0]
    active_f = active.astype(jnp.float32)
    m = resolve_mode(mode, n)
    if m == "dense":
        out = _interact_dense(pos, active_f, kernel, feats)
        return (out, None) if return_grid else out
    if config is None:
        if world_half is None:
            raise ValueError("grid mode needs config= or world_half=")
        config = default_grid_config(n, kernel.radius, world_half)
    if config.cell_size < kernel.radius:
        raise ValueError(
            f"cell_size {config.cell_size} < interaction radius "
            f"{kernel.radius}: the 9-cell neighborhood would miss pairs"
        )
    grid, cand, padded = build_grid_tables(pos, active_f, config, feats)
    slot_f = slot_forces(kernel, grid.slots, cand, padded, impl=impl)
    spill_f = spill_forces(kernel, grid.spill, padded)
    out = scatter_forces(n, grid.slots, grid.spill, slot_f, spill_f)
    return (out, grid) if return_grid else out


def grid_stats(pos, active, config: GridConfig) -> dict:
    """Host-side occupancy/spill summary of one binning (bench columns and
    the CI failure artifact): occupancy percentiles, slot utilization, and
    the spill/drop counters that say whether K and S were big enough."""
    grid = bin_entities(jnp.asarray(pos), jnp.asarray(active), config)
    occ = np.asarray(grid.occupancy)
    n = int(np.asarray(active).astype(bool).sum())
    spilled = int(np.asarray(grid.n_spilled))
    return {
        "grid_dim": config.grid_dim,
        "cell_capacity": config.cell_capacity,
        "spill_capacity": config.spill_capacity,
        "padded_cols": config.padded_cols,
        "occupancy_mean": round(float(occ.mean()), 2),
        "occupancy_p99": int(np.percentile(occ, 99)),
        "occupancy_max": int(occ.max()),
        "slot_utilization": round(
            (n - spilled) / float(config.num_cells * config.cell_capacity), 4
        ),
        "spilled": spilled,
        "spill_rate": round(spilled / n, 6) if n else 0.0,
        "dropped": int(np.asarray(grid.n_dropped)),
    }
