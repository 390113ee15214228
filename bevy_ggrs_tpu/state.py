"""Rollback world state as a device-resident SoA pytree.

TPU-native replacement for the reference's reflection-based snapshot engine
(``/root/reference/src/world_snapshot.rs``). Where the reference deep-clones
every registered component of every ``Rollback``-tagged entity into a
``WorldSnapshot { entities: Vec<RollbackEntity>, resources, checksum }``
(``world_snapshot.rs:51-56``), we keep the registered slice of the world as a
structure-of-arrays pytree permanently resident in HBM:

- ``components[name]``: ``[capacity, *shape]`` array per registered type
- ``present[name]``:    ``bool[capacity]`` — does this entity have the
  component? (parity with per-entity insert/remove component semantics,
  ``world_snapshot.rs:154-184``)
- ``alive``:            ``bool[capacity]`` — entity exists
- ``rollback_id``:      ``int32[capacity]`` — the stable identity that
  survives despawn/respawn across rollbacks (reference ``src/lib.rs:40-55``)
- ``resources[name]``:  arbitrary array pytrees (reference
  ``src/reflect_resource.rs``)

"Save" is then a single indexed write into a stacked ring
(:class:`SnapshotRing`, reference ring at ``src/ggrs_stage.rs:89,286``),
"load" a gather, and the reference's entity create/destroy reconciliation on
restore (``world_snapshot.rs:135-235``) is subsumed by restoring the
alive/present masks — no per-entity spawn/despawn walk.

The checksum mirrors the reference's order-insensitive wrapping sum of
per-component hashes (``world_snapshot.rs:72-75,123-125``) as a vectorized
integer reduction: a murmur3-style mix of each live slot's component words,
wrapping-summed over slots (order-insensitive), plus resource hashes. Integer
ops only, so it is bit-reproducible under XLA on a given platform.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

# The rollback-id space is split so host-minted and device-minted ids can
# never collide: host allocators (``RollbackIdProvider``, ``spawn``) own
# ``0 .. DEVICE_ID_BASE-1``; device-resident allocators (in-step spawns, see
# ``models/projectiles.py``) mint upward from ``DEVICE_ID_BASE``.
DEVICE_ID_BASE = 1 << 20


def _scope(name: str):
    """``obs/trace.py`` ``device_scope``, found when a program is traced:
    ``obs`` reads this module while it is imported (forensics), so the
    name cannot be bound here at import."""
    from bevy_ggrs_tpu.obs.trace import device_scope

    return device_scope(name)


# ---------------------------------------------------------------------------
# Type registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ComponentDef:
    """A registered rollback component type.

    Mirrors a ``register_rollback_component::<T>()`` registration
    (reference ``src/lib.rs:120-131``): the set of registered types is the
    gate deciding what crosses into the rollback domain.
    """

    name: str
    shape: Tuple[int, ...] = ()
    dtype: Any = jnp.float32
    default: Any = 0

    def prototype(self, capacity: int) -> jnp.ndarray:
        return jnp.full((capacity,) + tuple(self.shape), self.default, dtype=self.dtype)


@dataclasses.dataclass(frozen=True)
class ResourceDef:
    """A registered rollback resource (singleton) type.

    Mirrors ``register_rollback_resource::<T>()`` (reference
    ``src/lib.rs:134-146`` + ``src/reflect_resource.rs``). ``initial`` is an
    arbitrary pytree of arrays/scalars; its structure is the schema.
    """

    name: str
    initial: Any = None

    def prototype(self) -> Any:
        # jnp.array (copying): jnp.asarray can zero-copy a host buffer the
        # caller still owns — see HostWorld.commit.
        return jax.tree_util.tree_map(jnp.array, self.initial)


class TypeRegistry:
    """Collects the component/resource types that constitute rollback state.

    Only registered types are saved, restored, and checksummed — everything
    else in the user's program is untouched, exactly the boundary the
    reference draws with its plugin-private ``TypeRegistry``
    (``src/lib.rs:91,120-146``).
    """

    def __init__(self) -> None:
        self.components: Dict[str, ComponentDef] = {}
        self.resources: Dict[str, ResourceDef] = {}

    def register_component(
        self,
        name: str,
        shape: Tuple[int, ...] = (),
        dtype: Any = jnp.float32,
        default: Any = 0,
    ) -> "TypeRegistry":
        if name in self.components:
            raise ValueError(f"component {name!r} registered twice")
        self.components[name] = ComponentDef(name, tuple(shape), dtype, default)
        return self

    def register_resource(self, name: str, initial: Any) -> "TypeRegistry":
        if name in self.resources:
            raise ValueError(f"resource {name!r} registered twice")
        self.resources[name] = ResourceDef(name, initial)
        return self


# ---------------------------------------------------------------------------
# World state pytree
# ---------------------------------------------------------------------------


@struct.dataclass
class WorldState:
    """The registered slice of the world, as one SoA pytree.

    All leaves share a leading ``capacity`` axis except ``resources``.
    A free slot has ``alive=False`` and ``rollback_id=-1``.
    """

    alive: jnp.ndarray  # bool[capacity]
    rollback_id: jnp.ndarray  # int32[capacity]
    components: Dict[str, jnp.ndarray]  # name -> [capacity, *shape]
    present: Dict[str, jnp.ndarray]  # name -> bool[capacity]
    resources: Dict[str, Any]  # name -> pytree

    @property
    def capacity(self) -> int:
        return self.alive.shape[0]

    def num_alive(self) -> jnp.ndarray:
        return jnp.sum(self.alive.astype(jnp.int32))


def init_state(registry: TypeRegistry, capacity: int) -> WorldState:
    """An empty world with ``capacity`` entity slots."""
    return WorldState(
        alive=jnp.zeros((capacity,), dtype=jnp.bool_),
        rollback_id=jnp.full((capacity,), -1, dtype=jnp.int32),
        components={n: d.prototype(capacity) for n, d in registry.components.items()},
        present={n: jnp.zeros((capacity,), dtype=jnp.bool_) for n in registry.components},
        resources={n: d.prototype() for n, d in registry.resources.items()},
    )


# ---------------------------------------------------------------------------
# Host-side staging world
# ---------------------------------------------------------------------------


class HostWorld:
    """Mutable host-side staging area for building the initial world.

    Plays the role of the user's setup system spawning ``Rollback``-tagged
    entities (reference ``examples/box_game/box_game.rs:80-140``). Call
    :meth:`commit` to obtain the device-resident :class:`WorldState`.
    """

    def __init__(self, registry: TypeRegistry, capacity: int):
        self.registry = registry
        self.capacity = capacity
        self._alive = np.zeros((capacity,), dtype=bool)
        self._rollback_id = np.full((capacity,), -1, dtype=np.int32)
        self._components = {
            n: np.full((capacity,) + tuple(d.shape), d.default,
                       dtype=np.dtype(jnp.dtype(d.dtype).name))
            for n, d in registry.components.items()
        }
        self._present = {n: np.zeros((capacity,), dtype=bool) for n in registry.components}
        self._resources = {n: d.prototype() for n, d in registry.resources.items()}

    def spawn(self, components: Dict[str, Any], rollback_id: int) -> int:
        """Spawn an entity with the given components; returns its slot index.

        ``rollback_id`` must be unique among live entities — the reference
        asserts the same (``world_snapshot.rs:16``).
        """
        if rollback_id in self._rollback_id[self._alive]:
            raise ValueError(f"duplicate rollback_id {rollback_id}")
        for name in components:
            if name not in self._components:
                raise KeyError(f"component {name!r} not registered")
        free = np.flatnonzero(~self._alive)
        if free.size == 0:
            raise RuntimeError(f"world capacity {self.capacity} exhausted")
        slot = int(free[0])
        self._alive[slot] = True
        self._rollback_id[slot] = rollback_id
        for name, value in components.items():
            self._components[name][slot] = np.asarray(
                value, dtype=self._components[name].dtype
            )
            self._present[name][slot] = True
        return slot

    def despawn(self, slot: int) -> None:
        self._alive[slot] = False
        self._rollback_id[slot] = -1
        for name in self._present:
            self._present[name][slot] = False

    def set_resource(self, name: str, value: Any) -> None:
        if name not in self._resources:
            raise KeyError(f"resource {name!r} not registered")
        proto = self._resources[name]
        self._resources[name] = jax.tree_util.tree_map(
            lambda p, v: jnp.array(v, dtype=p.dtype), proto, value
        )

    def commit(self) -> WorldState:
        # jnp.array (copying), NOT jnp.asarray: on CPU the latter can
        # zero-copy the staging buffers, aliasing the "immutable" committed
        # state to this world — a later spawn/despawn would then silently
        # mutate already-committed snapshots (alignment-dependent, so it
        # bites intermittently).
        return WorldState(
            alive=jnp.array(self._alive),
            rollback_id=jnp.array(self._rollback_id),
            components={n: jnp.array(a) for n, a in self._components.items()},
            present={n: jnp.array(a) for n, a in self._present.items()},
            resources=jax.tree_util.tree_map(jnp.array, self._resources),
        )


def to_host(state: WorldState) -> Dict[str, Any]:
    """Device→host sync of a world state (the confirmed-branch scatter-back).

    Returns plain numpy arrays; this is the only place rendering/game code
    outside the rollback domain should read simulated state from.
    """
    return jax.tree_util.tree_map(np.asarray, dataclasses.asdict(state))


# ---------------------------------------------------------------------------
# Checksum
# ---------------------------------------------------------------------------

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_SEED = np.uint32(0x9747B28C)


def _rotl(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _to_u32_words(arr: jnp.ndarray) -> jnp.ndarray:
    """Flatten trailing dims of ``[cap, ...]`` to ``[cap, n_words]`` uint32."""
    cap = arr.shape[0]
    a = arr.reshape(cap, -1) if arr.ndim > 1 else arr.reshape(cap, 1)
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint32)
    nbits = a.dtype.itemsize * 8
    if nbits < 32:
        uint = jnp.dtype(f"uint{nbits}")
        return jax.lax.bitcast_convert_type(a, uint).astype(jnp.uint32)
    if nbits == 32:
        return jax.lax.bitcast_convert_type(a, jnp.uint32)
    # 64-bit dtypes only exist with jax x64 enabled; split into 2 words.
    w = jax.lax.bitcast_convert_type(a, jnp.uint32)  # [cap, n, 2]
    return w.reshape(cap, -1)


def _mix_one(h: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    k = w * _C1
    k = _rotl(k, 15) * _C2
    h = h ^ k
    return _rotl(h, 13) * np.uint32(5) + np.uint32(0xE6546B64)


_UNROLL_LIMIT = 64


def _mix_words(h: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """Murmur3-style streaming mix of ``words[cap, n]`` into ``h[cap]``
    (or ``h[2, cap]`` — the two-lane checksum state broadcasts over the
    leading axis).

    Small word counts unroll statically; large components (grids, big
    per-entity tensors) fall back to ``lax.scan`` over columns so trace size
    stays bounded.
    """
    n = words.shape[1]
    if n <= _UNROLL_LIMIT:
        for i in range(n):
            h = _mix_one(h, words[:, i])
        return h
    return jax.lax.scan(
        lambda hh, col: (_mix_one(hh, col), None), h, jnp.transpose(words)
    )[0]


def _fmix(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


# Seed separating the hi lane's mix stream from the lo lane's (golden-ratio
# word, the usual choice for independent hash streams).
#
# The exchanged checksum is 64 bits wide (the reference's saved-state cell
# carries u128 — ``ggrs_stage.rs:283``); on device (no uint64 without x64
# mode) it is carried as two uint32 lanes. Each lane is a FULL murmur stream
# over the same words from its own seed — NOT a re-finalization of the lo
# hash, which (being a bijection of it) would collide whenever the lo hash
# collides and leave single-slot divergence at 32-bit resistance. Both
# streams mix in the same word pass (one memory traversal, two VPU integer
# chains), so the cost is arithmetic only.
_HI_TWEAK = np.uint32(0x9E3779B9)


def _seed_rows(cap: int) -> jnp.ndarray:
    """[2, cap] per-lane murmur seeds (lane 0 = lo, lane 1 = hi).

    ``_mix_words``/``_mix_one`` broadcast over the leading lane axis
    unchanged: each mixed word column has shape [cap] against state [2, cap].
    """
    return jnp.stack([
        jnp.full((cap,), _SEED, dtype=jnp.uint32),
        jnp.full((cap,), _SEED ^ _HI_TWEAK, dtype=jnp.uint32),
    ])


def combine64(cs) -> int:
    """Host-side: fold a two-lane ``uint32[2]`` checksum into one Python int
    (the value sessions exchange and compare)."""
    a = np.asarray(cs, dtype=np.uint64).reshape(-1)
    return int(a[0] | (a[1] << np.uint64(32)))


def combine64_rows(cs) -> np.ndarray:
    """:func:`combine64` of every ``uint32[2]`` row of ``cs [..., 2]`` in
    one pass: ``uint64[...]`` (``.tolist()`` gives the Python ints)."""
    a = np.asarray(cs, dtype=np.uint64)
    return a[..., 0] | (a[..., 1] << np.uint64(32))


def checksum(state: WorldState) -> jnp.ndarray:
    """Order-insensitive 64-bit checksum of the rollback domain, as two
    uint32 lanes ``[lo, hi]``.

    Per-slot: a murmur-style hash over ``rollback_id`` and every
    present component's words (order-sensitive *within* a slot). Slot hashes
    are wrapping-summed over live slots, so the result is independent of slot
    order — matching the reference's wrapping ``checksum +=
    component.reflect_hash()`` (``world_snapshot.rs:72-75``). Resource hashes
    are mixed in the same way (``world_snapshot.rs:123-125``). The hi lane
    is an independent murmur stream over the same words (see ``_HI_TWEAK``),
    widening the exchanged value to 64 bits like the reference's u128-capable
    cell (``ggrs_stage.rs:283``).
    """
    cap = state.capacity
    h = _seed_rows(cap)  # [2, cap]: lo and hi lanes, mixed in one pass
    h = _mix_words(h, _to_u32_words(state.rollback_id))
    for name in sorted(state.components):
        words = _to_u32_words(state.components[name])
        # Mask non-present slots' words to a fixed sentinel so stale slot data
        # never affects the hash; mix the presence bit itself as well.
        pres = state.present[name][:, None]
        words = jnp.where(pres, words, jnp.uint32(0))
        h = _mix_words(h, state.present[name].astype(jnp.uint32).reshape(cap, 1))
        h = _mix_words(h, words)
    h = _fmix(h)
    lanes = jnp.sum(
        jnp.where(state.alive[None, :], h, jnp.uint32(0)), axis=1,
        dtype=jnp.uint32,
    )
    return lanes + _resources_checksum(state.resources)


def _resources_checksum(resources: Dict[str, Any]) -> jnp.ndarray:
    """Position-keyed resource hash, shared by the XLA and Pallas checksum
    paths. Returns the two-lane ``uint32[2]`` form (see :func:`checksum`):
    each lane is its own murmur stream from its own seed.

    Every word hashes INDEPENDENTLY — seeded by (resource name, word
    position) so transposing two words still changes the value — and the
    per-word hashes wrapping-sum, exactly the slot-hash construction. The
    round-3 implementation streamed all of a resource's words through one
    sequential murmur chain; that serial dependency lowered to a
    per-word ``lax.scan`` whose iteration overhead DOMINATED wide-resource
    models (measured: neural_bots with H=256 policy weights spent ~23 ms
    of a 26 ms rollout hashing ~3k words per saved frame — 8x the H=32
    rollout). Parallel hashing removes the serial chain; resource checksum
    VALUES change (any cross-version comparison is already undefined —
    peers must share a build, protocol VERSION gates the wire)."""
    total = jnp.zeros((2,), dtype=jnp.uint32)
    for name in sorted(resources):
        leaves = jax.tree_util.tree_leaves(resources[name])
        # Seed with the full name so same-length-named resources can't swap
        # values undetected.
        name_seed = 0
        for b in name.encode():
            name_seed = (name_seed * 31 + b) & 0xFFFFFFFF
        seeds = jnp.array(
            [_SEED ^ np.uint32(name_seed),
             (_SEED ^ _HI_TWEAK) ^ np.uint32(name_seed)],
            dtype=jnp.uint32,
        )
        # Per-resource constant term: a registered resource contributes
        # even when it has zero words, so peers disagreeing only in the
        # presence of an empty resource still desync-detect (the serial
        # chain had this property implicitly).
        total = total + _fmix(seeds)
        word_base = 0
        for leaf in leaves:
            words = _to_u32_words(jnp.atleast_1d(leaf).reshape(1, -1))[0]
            n = words.shape[0]
            # Positions continue across leaves so words cannot migrate
            # between a resource's leaves undetected.
            pos = (
                jnp.arange(word_base, word_base + n, dtype=jnp.uint32)
                * _HI_TWEAK
            )
            h = seeds[:, None] ^ pos[None, :]  # [2, n]
            h = _fmix(_mix_one(h, words[None, :]))
            total = total + jnp.sum(h, axis=1, dtype=jnp.uint32)
            word_base += n
    return total


def checksum_breakdown(state: WorldState) -> Dict[str, int]:
    """Per-part checksums for desync diagnosis.

    The session's desync detection (survey §5: checksum exchange) says THAT
    peers diverged; this says WHERE — which registered component or
    resource holds different bits. Each part is hashed independently
    (order-insensitive over live slots, like :func:`checksum`), so two
    peers can diff their breakdowns for the divergent frame and localize
    the first non-deterministic system. Host-side tool; not part of the
    per-frame hot path.
    """
    cap = state.capacity
    out: Dict[str, int] = {}

    def slot_sum(h):  # h [2, cap]
        h = _fmix(h)
        return combine64(jnp.sum(
            jnp.where(state.alive[None, :], h, jnp.uint32(0)), axis=1,
            dtype=jnp.uint32,
        ))

    h = _seed_rows(cap)
    out["rollback_id"] = slot_sum(_mix_words(h, _to_u32_words(state.rollback_id)))
    out["alive"] = slot_sum(
        _mix_words(h, state.alive.astype(jnp.uint32).reshape(cap, 1))
    )
    for name in sorted(state.components):
        words = _to_u32_words(state.components[name])
        pres = state.present[name]
        words = jnp.where(pres[:, None], words, jnp.uint32(0))
        hh = _mix_words(h, pres.astype(jnp.uint32).reshape(cap, 1))
        out[f"component/{name}"] = slot_sum(_mix_words(hh, words))
    for name in sorted(state.resources):
        out[f"resource/{name}"] = combine64(
            _resources_checksum({name: state.resources[name]})
        )
    return out


# Pluggable checksum implementation for ring_save. The Pallas kernel
# (bevy_ggrs_tpu.ops.checksum, bit-identical) installs itself here via
# set_checksum_impl; None means the XLA path above. Jitted callers bind the
# impl at trace time.
_checksum_impl: list = [None]


def set_checksum_impl(fn: Optional[Callable[[WorldState], jnp.ndarray]]) -> None:
    _checksum_impl[0] = fn


def active_checksum(state: WorldState) -> jnp.ndarray:
    fn = _checksum_impl[0]
    with _scope("checksum"):
        return fn(state) if fn is not None else checksum(state)


# ---------------------------------------------------------------------------
# Snapshot ring
# ---------------------------------------------------------------------------


@struct.dataclass
class SnapshotRing:
    """Device-resident ring of world states, indexed ``frame % depth``.

    Mirrors the reference's ``Vec<WorldSnapshot>`` sized to
    ``max_prediction()`` and indexed ``frame % len`` (``src/ggrs_stage.rs:89,
    169-173, 286, 294``) — but "save" is an indexed device write, not a deep
    reflective clone, and the whole ring stays in HBM. (A speculative
    rollout's branch ring has the same fields and another row order:
    :func:`ring_of_steps`.)
    """

    states: WorldState  # every leaf gains a leading [depth] axis
    frames: jnp.ndarray  # int32[depth], -1 = empty
    checksums: jnp.ndarray  # uint32[depth, 2] — [lo, hi] 64-bit lanes

    @property
    def depth(self) -> int:
        return self.frames.shape[0]


def ring_init(state: WorldState, depth: int) -> SnapshotRing:
    """A ring of ``depth`` copies of ``state`` with every slot marked empty."""
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (depth,) + x.shape), state
    )
    return SnapshotRing(
        states=stacked,
        frames=jnp.full((depth,), -1, dtype=jnp.int32),
        checksums=jnp.zeros((depth, 2), dtype=jnp.uint32),
    )


# One definition of "row ``index`` of a small static axis", two lowerings.
#
# With ONE index for the whole array (a singleton session, or the rollout's
# vmap over branches, which all share their session's frame) the access is a
# dynamic slice: the write is in place and touches one row, whatever the
# depth or the size of the world. Under a ``vmap`` that batches the INDEX
# (the slot axis of ``serve/batch.py``: every match has its own frame
# counter) jax would turn the same op into a ``scatter`` / ``gather``, which
# the TPU compiler expands into a loop of tiny slice ops over the lanes in
# every scan step (46.0 ms a dispatch of the served tick at S=64 x B=8,
# 0.84 ms with selects; PERF.md section 6, PR 25). There the access is a
# select over the axis instead: dense, elementwise, and exact (a select
# moves bits; no one-hot multiply, which would turn -0.0 into 0.0 and spread
# NaN). The choice is made where it can be seen: by the batching rule, from
# whether the index carries the batch axis. Nothing is configured.
#
# (A large row under a batched index is a third case, a copy a lane: "The
# fourth lowering", below.)
#
# A READ by select is a chain of one select a row of the axis, each an
# operation of the program: right for a ring (10 to 14 rows, read in every
# scan step), wrong for the one read a tick that picks a slot's matched
# branch out of ``[B, ...]`` once B is in the hundreds (B = 1,024 under 16
# slots: 20,000 selects over 20 leaves, 200 MB of code and six minutes of
# compiling; PERF.md section 6, PR 39). Past ``SELECT_ROWS`` rows a batched
# index reads through a one-hot mask OR-ed over the axis instead: one dense
# pass a leaf, as the write is. Not the gather jax would make of it: the
# compiler lays a ``[B, ...]`` rollout out with B in the lanes, and a gather
# of whole branches wants it transposed (3.9 GB of scratch at that shape).

SELECT_ROWS = 64


def _batched(x, is_batched: bool, axis_size: int):
    return x if is_batched else jnp.broadcast_to(x, (axis_size,) + x.shape)


def _clamp(index: jnp.ndarray, n: int) -> jnp.ndarray:
    # lax's dynamic index counts a negative one from the end and the slice
    # then clamps its start into range; the select form must pick the same
    # row for the same index.
    index = jnp.asarray(index, jnp.int32)
    return jnp.clip(jnp.where(index < 0, index + n, index), 0, n - 1)


@functools.lru_cache(maxsize=None)
def _row_write_at(axis: int):
    @jax.custom_batching.custom_vmap
    def write(stack, row, index, valid):
        new = jax.lax.dynamic_update_index_in_dim(
            stack, jnp.expand_dims(row, axis), index, axis
        )
        return new if valid is None else jnp.where(valid, new, stack)

    @write.def_vmap
    def write_vmap(axis_size, in_batched, stack, row, index, valid):
        stack_b, row_b, index_b, valid_b = in_batched
        stack = _batched(stack, stack_b, axis_size)
        row = _batched(row, row_b, axis_size)
        if not (index_b or valid_b):
            # Lane-uniform index: still one dynamic row write, one axis in.
            return _row_write_at(axis + 1)(stack, row, index, valid), True
        n = stack.shape[axis + 1]
        index = _batched(_clamp(index, n), index_b, axis_size)
        if axis == 0 and row_in_tiles(stack, 2):
            # A large row in whole lane tiles: one copy a writing lane.
            from bevy_ggrs_tpu.ops.ring_write import write_rows_in_place

            writes = (
                jnp.ones((axis_size,), jnp.int32) if valid is None
                else _batched(valid, valid_b, axis_size)
            )
            in_place_writes[0] += 1
            return write_rows_in_place(stack, row, index, writes), True
        hot = jnp.arange(n, dtype=jnp.int32) == index[:, None]  # [N, n]
        if valid is not None:  # the mask folds into the one-hot: one pass
            hot = hot & _batched(valid, valid_b, axis_size)[:, None]
        hot = hot.reshape((axis_size,) + (1,) * axis + (n,)
                          + (1,) * (stack.ndim - axis - 2))
        return jnp.where(hot, jnp.expand_dims(row, axis + 1), stack), True

    return write


def _row_read_one_hot(stack, index, at: int):
    """Row ``index[lane]`` of axis ``at`` of ``stack[lane, ...]`` in one
    dense pass: everything but the lane's row masked to zero bits, then OR-ed
    over the axis. It moves bits as a select does (no multiply, no sum)."""
    n = stack.shape[at]
    hot = jnp.arange(n, dtype=jnp.int32) == _clamp(index, n)[:, None]
    hot = hot.reshape(
        (stack.shape[0],) + (1,) * (at - 1) + (n,) + (1,) * (stack.ndim - at - 1)
    )
    if stack.dtype == jnp.bool_:
        return jnp.any(hot & stack, axis=at)
    word = jnp.dtype(f"uint{8 * stack.dtype.itemsize}")
    picked = jax.lax.reduce(
        jnp.where(hot, jax.lax.bitcast_convert_type(stack, word), word.type(0)),
        word.type(0), jax.lax.bitwise_or, (at,),
    )
    return jax.lax.bitcast_convert_type(picked, stack.dtype)


@functools.lru_cache(maxsize=None)
def _row_read_at(axis: int):
    @jax.custom_batching.custom_vmap
    def read(stack, index):
        return jax.lax.dynamic_index_in_dim(stack, index, axis, keepdims=False)

    @read.def_vmap
    def read_vmap(axis_size, in_batched, stack, index):
        stack_b, index_b = in_batched
        if not index_b:
            return _row_read_at(axis + 1)(stack, index), True
        at = axis + 1 if stack_b else axis
        n = stack.shape[at]
        if n > SELECT_ROWS:
            stack = _batched(stack, stack_b, axis_size)
            return _row_read_one_hot(stack, index, axis + 1), True
        row = lambda d: jax.lax.index_in_dim(stack, d, at, keepdims=False)
        out = _batched(row(0), stack_b, axis_size)
        index = _clamp(index, n).reshape((axis_size,) + (1,) * (out.ndim - 1))
        for d in range(1, n):
            out = jnp.where(index == d, row(d), out)
        return out, True

    return read


def _traced(*xs) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in xs)


def ring_row_write(
    stack: jnp.ndarray, row: jnp.ndarray, index: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``stack`` with row ``index`` of its leading axis replaced by ``row``
    (left as it is where the scalar ``valid`` is False)."""
    write = _row_write_at(0)
    if not _traced(index, valid):
        write = write.fun  # a concrete index cannot differ per lane
    with _scope("ring_write"):
        return write(stack, row, index, valid)


def ring_row_read(
    stack: jnp.ndarray, index: jnp.ndarray, axis: int = 0
) -> jnp.ndarray:
    """Row ``index`` of ``stack``'s axis ``axis``: a snapshot-ring row by
    ``frame % depth``, or the matched branch of a ``[B, ...]`` rollout (of
    the ``[F, B, n]`` rows a rollout carries as written: ``axis=1``)."""
    read = _row_read_at(axis)
    with _scope("ring_read"):
        return (read if _traced(index) else read.fun)(stack, index)


def ring_put(
    ring: SnapshotRing,
    state: WorldState,
    frame: jnp.ndarray,
    cs: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> SnapshotRing:
    """Write ``state`` with its checksum ``cs`` into ``frame``'s row (a
    no-op where ``valid`` is False). Either side may hold its large rows
    flat (``FLAT_ROW_BYTES``): a burst's ring (:func:`ring_rows_flat`)
    meets a shaped state, whose leaf is flattened the same way on its way
    in; the absorb's main ring meets a row of a rollout (:func:`state_row`,
    carried as the loop wrote it), which is shaped here, where a frame is
    absorbed, and nowhere before."""
    frame = jnp.asarray(frame, dtype=jnp.int32)
    slot = jnp.remainder(frame, ring.depth)

    def row_like(r, s):
        row = tuple(r.shape[1:])
        if s.shape == row:
            return s
        if s.ndim > 1 or row == _row_tiles(s, 0):
            return _row_tiled(s, 0)
        return _rows_shaped(s, row, 0)

    return SnapshotRing(
        states=jax.tree_util.tree_map(
            lambda r, s: ring_row_write(r, row_like(r, s), slot, valid),
            ring.states, state,
        ),
        frames=ring_row_write(ring.frames, frame, slot, valid),
        checksums=ring_row_write(ring.checksums, cs, slot, valid),
    )


def ring_save(
    ring: SnapshotRing, state: WorldState, frame: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> Tuple[SnapshotRing, jnp.ndarray]:
    """Save ``state`` as frame ``frame``; returns (ring, checksum). With
    ``valid`` (a traced bool) False the ring is left as it is.

    The checksum computed here is what the session hands to its saved-state
    cell for desync detection — the byte buffer never leaves the device,
    matching the reference's ``cell.save(frame, None, Some(checksum))``
    (``src/ggrs_stage.rs:282-283``).
    """
    cs = active_checksum(state)
    return ring_put(ring, state, frame, cs, valid), cs


# The third lowering: WHERE a burst keeps its ring while it writes it.
#
# A scan that carries a ring leaf ``[depth, N, k]`` with a narrow last axis
# (boids' ``position [1024, 2]``) carries it, on the TPU, in the default
# row-major tiling: ``k`` in the 128 lanes, 64 x the bytes for ``k`` = 2.
# Nothing while one row is written in place; but under the slot ``vmap`` the
# write is the select form above, which rewrites the WHOLE ring every scan
# step: at S=64 x B=8 x F=8 x 1,024 boids 2 x 2.1 GB padded where the data is
# 2 x 33 MB, 103 ms of a 194 ms dispatch, and 5.4 GB of scratch (my chip run,
# PR 37; ``PERF.md`` section 6). So for the length of a burst a row of
# ``FLAT_ROW_BYTES`` or more is kept FLAT, its largest axis last (the order
# the packed carry and the compiler's own parameter layouts use, so entering
# and leaving the form is a bitcast or one copy a burst): ``[depth, N * k]``
# is lane-dense in any tiling, and the select form then moves the data's
# bytes (1.3 % of the same dispatch). Chosen from the row's bytes alone: a
# row under one 4 KiB tile (every box_game leaf: 192 bytes at most) stays as
# it is and its programs with it. Bits are only moved.
FLAT_ROW_BYTES = 4 << 10

# The fourth lowering: a per-lane write of a LARGE row is a copy, not a pass.
#
# The select form reads and writes the whole ``[S, depth, n]`` leaf to
# change ``S`` rows of it, and does so for a lane whose step is padding to
# the deepest lane of its group too. Right for box_game's 192-byte rows (it
# replaced a scatter, above); at the churning title's 72 KB rows it was the
# largest single operation of the served tick, ``(2 x depth + 1)`` times
# the bytes that change, every burst step (1.46 ms of a 9.82 ms dispatch
# behind SyncTest sessions, 3.24 of 15.10 behind a network, where four
# lane-steps in five are padding; ledger, PR 52). So for the length of a
# burst a row of ``IN_PLACE_ROW_BYTES`` or more that is whole ``(8, 128)``
# tiles of a 32-bit type is carried as those tiles, ``[depth, n / 128,
# 128]`` (one row is then addressable by a DMA; in ``[depth, n]`` the depth
# lies in a tile's sublanes), and the batching rule of the write answers a
# batched index or mask over such a ring with ``ops/ring_write.py``: the
# ring stays where it lies in HBM, aliased to the output, one asynchronous
# copy a lane that writes and none for a lane that does not. On the chip, a
# burst step of that title's four such leaves under 64 lanes with the rings
# in HBM: 0.315 ms by select, 0.047 with every lane saving, 0.013 with 13,
# 0.008 with none; everything ``ring_write`` in the SyncTest dispatch 1.90
# -> 0.22 ms, the dispatch 9.82 -> 7.37 (my chip runs, PR 53; ``PERF.md``
# section 6). Shapes and dtypes alone decide. A ``bool`` row (a DMA takes
# none), a row that does not tile and every smaller row keep the flat form
# and the select. The constant is the crossover's: a ring of rows this
# size under 64 lanes (18 MB and more) is one the compiler leaves in HBM,
# where a select pays for every byte; the 1,024-boid title's 8 KB rows make
# 4.7 MB rings that it parks in VMEM for the whole loop, where select and
# copy both read under the timer's 3 us a step, and the kernel would pin
# them to HBM and add a copy a burst in and out for every one-axis leaf.
IN_PLACE_ROW_BYTES = 32 << 10

# How many ring leaves each form was traced with, process-wide (``kind``:
# "flat" / "shaped", a burst's ring by the form its loop carries it in;
# "step", a rollout's branch ring, whose rows leave the loop in step order:
# :func:`ring_of_steps`; "carried", those of them the tick carries as the
# loop wrote them, and "carried_once", those of these without a branch
# axis, in the ring and in the final state alike:
# :func:`branch_rows_carried`); ``serve/batch.py`` reports its executable's
# share as the labelled count ``ring_row_lowering``.
ring_row_lowerings: Dict[str, int] = {
    "flat": 0, "shaped": 0, "in_place": 0, "step": 0,
    "carried": 0, "carried_once": 0,
}
# How often the batching rule of a row write answered with the in-place
# copy: a loop's own batching rule visits its body more than once, so this
# counts visits, and ``rollout.py`` ``rollout_burst``, which knows its
# leaves, turns "visited at all" into the count above.
in_place_writes = [0]


def large_row(x, lead: int = 0) -> bool:
    """Whether ``x[*lead axes, *row]`` has a row of ``FLAT_ROW_BYTES`` or
    more (``x``: anything with a shape and a dtype)."""
    n = int(np.prod(x.shape[lead:], dtype=np.int64))
    return n * jnp.dtype(x.dtype).itemsize >= FLAT_ROW_BYTES


def _row_tiles(x, lead: int) -> Optional[Tuple[int, int]]:
    """``(n / 128, 128)`` where the row of ``x[*lead axes, *row]`` is
    ``IN_PLACE_ROW_BYTES`` or more and whole lane tiles of a type a DMA takes
    (``ops/ring_write.py`` ``tiles``); None for any other row."""
    from bevy_ggrs_tpu.ops.ring_write import tiles

    n = int(np.prod(x.shape[lead:], dtype=np.int64))
    if n * jnp.dtype(x.dtype).itemsize < IN_PLACE_ROW_BYTES:
        return None
    return tiles(n, x.dtype)


def row_in_tiles(x, lead: int) -> bool:
    """Whether the rows of ``x[*lead axes, *row]`` stand as whole lane
    tiles: a write into such a ring under a ``vmap`` that batches the index
    is one copy a lane (``_row_write_at``)."""
    return _row_tiles(x, lead) == tuple(x.shape[lead:])


def _row_flat(x: jnp.ndarray, lead: int) -> jnp.ndarray:
    """``x[*lead axes, *row]`` with a row of ``FLAT_ROW_BYTES`` or more and
    two axes or more flattened to ``[*lead axes, n]``, largest axis last;
    any other leaf as it is (a row that already is whole lane tiles among
    them: no form of it differs from its shape, :func:`_row_tiled`)."""
    shape = tuple(x.shape[lead:])
    n = int(np.prod(shape, dtype=np.int64))
    if len(shape) < 2 or not large_row(x, lead) or shape == _row_tiles(x, lead):
        return x
    perm = _lanes_last(shape)
    with _scope("row_layout"):
        if perm is not None:
            x = jnp.transpose(
                x, tuple(range(lead)) + tuple(lead + a for a in perm)
            )
        return x.reshape(x.shape[:lead] + (n,))


def _row_tiled(x: jnp.ndarray, lead: int) -> jnp.ndarray:
    """:func:`_row_flat`, and the flat row as whole lane tiles ``[*lead
    axes, n / 128, 128]`` where it is such (:func:`_row_tiles`): the form a
    batched write copies in place."""
    tiles = _row_tiles(x, lead)
    x = _row_flat(x, lead)
    if tiles is None or tuple(x.shape[lead:]) == tiles:
        return x
    with _scope("row_layout"):
        return x.reshape(x.shape[:lead] + tiles)


def ring_rows_flat(ring: SnapshotRing) -> SnapshotRing:
    """``ring`` with every large row flat (see ``FLAT_ROW_BYTES``), as whole
    lane tiles where it is such: the form a burst's loop carries.
    :func:`ring_put` writes a state into either form;
    :func:`ring_rows_shaped` is the way back."""
    states = jax.tree_util.tree_map(lambda x: _row_tiled(x, 1), ring.states)
    for flat, x in zip(jax.tree_util.tree_leaves(states),
                       jax.tree_util.tree_leaves(ring.states)):
        ring_row_lowerings["shaped" if flat is x else "flat"] += 1
    return ring.replace(states=states)


def _rows_shaped(
    x: jnp.ndarray, row: Tuple[int, ...], lead: int = 1
) -> jnp.ndarray:
    """The inverse of ``_row_flat(x, lead)``: ``x[*lead axes, ...]`` with
    rows of shape ``row``."""
    if x.shape[lead:] == row:
        return x
    perm = _lanes_last(row)
    with _scope("row_layout"):
        if perm is None:
            return x.reshape(x.shape[:lead] + row)
        x = x.reshape(x.shape[:lead] + tuple(row[a] for a in perm))
        return jnp.transpose(
            x, tuple(range(lead))
            + tuple(lead + int(a) for a in np.argsort(perm))
        )


def ring_rows_shaped(ring: SnapshotRing, like: SnapshotRing) -> SnapshotRing:
    """The inverse of :func:`ring_rows_flat`: ``ring``'s rows in the shapes
    ``like``'s have."""
    return ring.replace(
        states=jax.tree_util.tree_map(
            lambda x, ref: _rows_shaped(x, tuple(ref.shape[1:])),
            ring.states, like.states,
        )
    )


# A ROLLOUT's branch ring is not a ring. It is born empty, saved ``depth``
# times in a row and read by one reader (``fused.py``
# ``absorb_branch_frames``), so every row is written exactly once and at a
# step every lane shares; only the rotation ``start_frame % depth`` would
# differ from lane to lane. Written at ``frame % depth`` under the slot
# ``vmap`` each of the ``depth`` saves was the select form above over the
# WHOLE ``[S, B, depth, ...]`` leaf (7.3 ms of the churning title's 30.9 ms
# dispatch in one operation, at 80 % of the memory peak; ``PERF.md``
# section 6, PR 45). So its rows stand in STEP order: row ``t`` holds the
# state entering frame ``start_frame + t``, the loop hands each out at its
# own counter (:func:`state_row`, a scan's ``ys``: ``[depth, S, B, n]``, a
# step's rows one contiguous slice), and the reader, who knows the start
# frame, looks a frame up at ``frame - start_frame``. Not
# ``ring_row_write(stack, row, t)`` into a carried ``[S, B, depth, n]``
# buffer, lane-uniform as that index is: ``depth`` then lies in the
# sublanes of a tile and the one-row update is a strided write, 12.3 ms a
# dispatch for the same leaf (39.6 ms the dispatch; same section).
#
# And it is CARRIED as the loop wrote it. Shaped back after the loop
# (``[S, B, depth, *row]``, which is what a reader off the serving loop is
# still handed: :func:`branch_rows_shaped`) every byte of it was written
# three times a dispatch, the loop's ``ys``, a retiling and the copy that
# moved ``depth`` behind the slot and branch axes, and a leaf that no
# branch's inputs reach (seven of the churning title's eight: only its
# emitter reads an input) was computed once by the loop and then broadcast
# over the branches: 755 of the 1,057 MB a dispatch wrote were eight
# identical copies, 4.3 of its 13.5 ms (``PERF.md`` section 6, PR 51). So
# between dispatches a leaf whose row is ``FLAT_ROW_BYTES`` or more stays
# the ``ys`` buffer: step-major and flat, ``[depth, S, B, n]`` (kind
# ``STEPS``; ``[depth, B, n]`` for a singleton), the slot and branch
# ``vmap``s naming those axes where the scan's batching rule already put
# them, and WITHOUT a branch axis where jax's own trace of the rollout
# found none (kind ``ONCE``: ``[depth, S, n]``, its final state ``[S,
# *row]``; ``rollout.py`` ``rollout_form``). A smaller row keeps the
# shaped form (kind ``SHAPED``: every box_game leaf, so its programs are
# what they were), and so does every leaf of a mesh-sharded carry, whose
# layouts are written for it. Who shapes a row is who reads one: the
# absorb picks its lane's matched branch out of the carried rows
# (:func:`branch_rows_of`), reads a step of them flat
# (:func:`ring_step_load`) and shapes that one row on its way into the
# main ring (:func:`ring_put`).
SHAPED, STEPS, ONCE = "shaped", "steps", "once"


def state_row(state: WorldState) -> WorldState:
    """``state`` as one row of a ring that holds its large rows flat (see
    ``FLAT_ROW_BYTES``)."""
    return jax.tree_util.tree_map(lambda x: _row_flat(x, 0), state)


def state_shaped(
    row: WorldState, like: WorldState, lead: int = 0
) -> WorldState:
    """The inverse of :func:`state_row`: ``row``'s leaves (behind ``lead``
    axes of their own) in the shapes ``like``'s have."""
    return jax.tree_util.tree_map(
        lambda x, ref: _rows_shaped(x, tuple(ref.shape), lead), row, like
    )


def ring_of_steps(
    rows: WorldState,  # [depth] :func:`state_row`s, stacked in step order
    start_frame: jnp.ndarray,
    checksums: jnp.ndarray,  # uint32[depth, 2]
) -> SnapshotRing:
    """The branch ring of a rollout: row ``t`` is the state that entered
    frame ``start_frame + t``, with its checksum; ``frames`` says so. The
    rows stay as the loop wrote them, the large ones flat: nothing here
    touches a leaf's bytes."""
    depth = checksums.shape[0]
    ring_row_lowerings["step"] += len(jax.tree_util.tree_leaves(rows))
    return SnapshotRing(
        states=rows,
        frames=jnp.asarray(start_frame, jnp.int32)
        + jnp.arange(depth, dtype=jnp.int32),
        checksums=checksums,
    )


def _lead_index(lead: int, i: int) -> tuple:
    return (slice(None),) * lead + (i,)


def branch_rows_carried(
    rings: SnapshotRing, states: WorldState, form
) -> Tuple[SnapshotRing, WorldState]:
    """A rollout's ``[*lead, B, F, *row]`` rings and ``[*lead, B, *row]``
    final states in the form the tick carries them (``form``: a kind a
    state leaf, or None for the shaped form throughout): a ``STEPS`` leaf
    ``[F, *lead, B, n]``, a ``ONCE`` leaf ``[F, *lead, n]`` and its final
    state ``[*lead, *row]``, both from branch 0 (whoever packs trees of
    their own making gets no more of such a leaf back). The way in for
    trees built off the serving loop; the tick itself never calls it."""
    if form is None:
        return rings, states
    lead = rings.frames.ndim - 2

    def ring_leaf(kind, x):
        if kind == SHAPED:
            return x
        if kind == ONCE:
            x = x[_lead_index(lead, 0)]
        at = lead + (kind == STEPS)  # where the step axis stands
        return jnp.moveaxis(_row_flat(x, at + 1), at, 0)

    return (
        rings.replace(
            states=jax.tree_util.tree_map(ring_leaf, form, rings.states)
        ),
        jax.tree_util.tree_map(
            lambda kind, x: x[_lead_index(lead, 0)] if kind == ONCE else x,
            form, states,
        ),
    )


def branch_rows_shaped(
    rings: SnapshotRing, states: WorldState, form, like: WorldState,
    num_branches: int,
) -> Tuple[SnapshotRing, WorldState]:
    """The inverse of :func:`branch_rows_carried`, for readers off the
    serving loop: ``[*lead, B, F, *row]`` rings and ``[*lead, B, *row]``
    states with rows in the shapes ``like``'s leaves have, a ``ONCE`` leaf
    the same in every branch."""
    if form is None:
        return rings, states
    lead = rings.frames.ndim - 2

    def every_branch(x):
        return jnp.broadcast_to(
            jnp.expand_dims(x, lead),
            x.shape[:lead] + (num_branches,) + x.shape[lead:],
        )

    def ring_leaf(kind, x, ref):
        if kind == SHAPED:
            return x
        x = jnp.moveaxis(x, 0, lead + (kind == STEPS))
        if kind == ONCE:
            x = every_branch(x)
        return _rows_shaped(x, tuple(ref.shape), lead + 2)

    return (
        rings.replace(
            states=jax.tree_util.tree_map(
                ring_leaf, form, rings.states, like
            )
        ),
        jax.tree_util.tree_map(
            lambda kind, x: every_branch(x) if kind == ONCE else x,
            form, states,
        ),
    )


# vmap-of-cond moves a batched operand's axis to the front IN FRONT of the
# conditional: for a ``[depth, S, B, n]`` leaf a transposition of the whole
# ring, every dispatch, hit or no hit. An operand without the batch axis
# goes in as it is. So in front of the absorb's conditional the carried
# leaves of all lanes are gathered (``lax.all_gather`` over the slot
# ``vmap``'s axis at the position the lanes already stand in: under that
# ``vmap`` the identity), and inside it a lane takes its own rows back
# through the rule below, which reads the gathered buffer where it lies.


def branch_rows_gathered(rings: SnapshotRing, form, lane_axis: Optional[str]):
    """``rings`` with every carried leaf holding the rows of ALL lanes of
    the ``vmap`` over ``lane_axis`` (``[depth, S, ...]``): what
    :func:`branch_rows_of` reads under that ``vmap``."""
    if form is None or lane_axis is None:
        return rings
    return rings.replace(states=jax.tree_util.tree_map(
        lambda kind, x: x if kind == SHAPED
        else jax.lax.all_gather(x, lane_axis, axis=1),
        form, rings.states,
    ))


@functools.lru_cache(maxsize=None)
def _lane_rows(branched: bool):
    """``rows(whole, lane, branch)``: lane ``lane``'s ``[depth, n]`` rows
    out of ``whole[depth, S, B, n]``, its branch ``branch``'s (out of
    ``whole[depth, S, n]`` where not ``branched``). ``lane`` is the index
    along the axis being vmapped (``lax.axis_index``), so under that
    ``vmap`` lane ``s`` reads column ``s``: one select a branch over the
    buffer as it lies, no gather."""

    @jax.custom_batching.custom_vmap
    def rows(whole, lane, branch):
        mine = jax.lax.dynamic_index_in_dim(whole, lane, 1, keepdims=False)
        if not branched:
            return mine
        return jax.lax.dynamic_index_in_dim(mine, branch, 1, keepdims=False)

    @rows.def_vmap
    def rows_vmap(axis_size, in_batched, whole, lane, branch):
        whole_b, lane_b, branch_b = in_batched
        if whole_b or not lane_b or whole.shape[1] != axis_size:
            raise NotImplementedError(
                "a lane's rows are read from the gathered rows of the axis "
                "being vmapped, by that axis's index"
            )
        if not branched:
            return jnp.moveaxis(whole, 1, 0), True
        n = whole.shape[2]
        if n > SELECT_ROWS:
            return _row_read_one_hot(
                jnp.moveaxis(whole, 1, 0), _batched(branch, branch_b, axis_size), 2
            ), True
        branch = _batched(_clamp(branch, n), branch_b, axis_size)
        hit = branch.reshape((1, axis_size) + (1,) * (whole.ndim - 3))
        row = lambda d: jax.lax.index_in_dim(whole, d, 2, keepdims=False)
        picked = row(0)
        for d in range(1, n):
            picked = jnp.where(hit == d, row(d), picked)
        return jnp.moveaxis(picked, 1, 0), True

    return rows


def branch_rows_of(
    rings: SnapshotRing, states: WorldState, branch: jnp.ndarray, form,
    lane_axis: Optional[str] = None,
) -> Tuple[SnapshotRing, WorldState]:
    """Branch ``branch``'s ring and final state out of a rollout's, as the
    absorb reads them: a leaf with a branch axis picked along it, a leaf
    without one (``ONCE``) taken as it is; the carried rows stay flat,
    ``[depth, n]``. Under the slot ``vmap`` (``lane_axis``) the carried
    leaves are :func:`branch_rows_gathered`'s."""
    sel = lambda x: ring_row_read(x, branch)
    if form is None:
        return jax.tree_util.tree_map(sel, (rings, states))

    def ring_leaf(kind, x):
        if kind == SHAPED:
            return sel(x)
        if lane_axis is not None:
            with _scope("ring_read"):
                return _lane_rows(kind == STEPS)(
                    x, jax.lax.axis_index(lane_axis), branch
                )
        return ring_row_read(x, branch, axis=1) if kind == STEPS else x

    return (
        SnapshotRing(
            states=jax.tree_util.tree_map(ring_leaf, form, rings.states),
            frames=sel(rings.frames),
            checksums=sel(rings.checksums),
        ),
        jax.tree_util.tree_map(
            lambda kind, x: x if kind == ONCE else sel(x), form, states
        ),
    )


def ring_step_load(
    ring: SnapshotRing, frame: jnp.ndarray, start_frame: jnp.ndarray
) -> Tuple[WorldState, jnp.ndarray]:
    """``(state, checksum)`` a rollout from ``start_frame`` saved for
    ``frame`` (:func:`ring_of_steps`), the state as the ring holds it (a
    :func:`state_row`: large rows flat). The caller knows the frame to lie
    inside the rollout; one past its end reads the last row (the index
    clamps) and is selected away."""
    step = jnp.asarray(frame, jnp.int32) - jnp.asarray(start_frame, jnp.int32)
    read = lambda r: ring_row_read(r, step)
    return jax.tree_util.tree_map(read, ring.states), read(ring.checksums)


def ring_load(ring: SnapshotRing, frame: jnp.ndarray) -> WorldState:
    """Load the state saved for ``frame``. The caller must know it is live
    (the session protocol guarantees loads target frames within the
    prediction window, like the reference's ``frame % len`` indexing)."""
    slot = jnp.remainder(jnp.asarray(frame, dtype=jnp.int32), ring.depth)
    return jax.tree_util.tree_map(
        lambda r: ring_row_read(r, slot), ring.states
    )


# ---------------------------------------------------------------------------
# Packing codec: a pytree carried between jitted calls as a few flat arrays
# ---------------------------------------------------------------------------

# A jitted call pays the TPU runtime per BUFFER it is handed or allocates,
# not per byte: ~20 us each on the v5e machine, beside 0.15 ms a launch
# (tools/enqueue_probe.py; PERF.md section 6, PR 28: 99 buffers 2.2 ms, the
# same bytes in 30 buffers 0.97 ms). A packed leaf saves two buffers a tick
# (one in, one out: 40 us) and pays for its pack and its unpack copy, four
# passes over its bytes. At the chip's 819 GB/s the two break even at
# 40e-6 * 819e9 / 4 = 8.2 MB; the copies are relayouts of tiled leaves and run
# under the peak, so a leaf keeps a buffer of its own from half of that.
# box_game's largest carried leaf is 0.4 MB.
OWN_BUFFER_BYTES = 4 << 20


def _lanes_last(shape: Tuple[int, ...]) -> Optional[Tuple[int, ...]]:
    """The axis order a leaf is flattened in: its largest axis last and its
    second largest before it, the others as they stand (None: as it is).

    The TPU compiler lays a small-minor leaf out with its largest axis in
    the lanes and the next in the sublanes (the rollout's ``[B, F, cap, 3]``
    ring leaf as ``{0,2,3,1}``: branches in the lanes), so flattening it in
    row-major order is a transposition across lanes in both directions;
    in this order it is a copy of whole rows. Bits are only moved."""
    if len(shape) < 2:
        return None
    by_size = sorted(range(len(shape)), key=lambda a: (shape[a], a))
    last = [by_size[-2], by_size[-1]]
    perm = tuple(a for a in range(len(shape)) if a not in last) + tuple(last)
    return None if perm == tuple(range(len(shape))) else perm


class PackCodec:
    """``pack(tree)`` -> a tuple of arrays, one flat array per dtype holding
    every small leaf plus each large leaf as it is; ``unpack`` -> the tree.

    Built from a template pytree (shapes, dtypes). Both directions are
    reshapes, slices and concatenations of one dtype: bitwise, no
    conversion anywhere (bool stays bool). A leaf may carry leading batch
    axes in front of its template shape; they are kept, so a tree stacked
    over ``[S]`` packs to ``[S, n]`` arrays and a ``vmap`` of a packed
    function over axis 0 sees the unstacked form. A leaf whose bytes times
    ``copies`` (the batch it will be carried with) reach ``own_buffer_bytes``
    keeps a buffer of its own (see ``OWN_BUFFER_BYTES``); 0 packs nothing
    (one buffer a leaf: what a mesh-sharded layout needs). ``own_axes``
    (an entry a leaf, in tree order; None: the rule above) names the leaves
    that keep a buffer of their own whatever their size, with the position
    at which THEIR batch axes stand: a rollout's rows, carried as its loop
    wrote them, are ``[F, S, ...]`` (position 1), and a packed copy of such
    a leaf would write its bytes again. :attr:`axes` says, buffer by
    buffer, where a ``vmap`` over a packed function finds that axis.
    ``unpack`` takes NumPy arrays too (host-side reads of a packed
    result)."""

    def __init__(self, template, copies: int = 1,
                 own_buffer_bytes: int = OWN_BUFFER_BYTES, own_axes=None):
        leaves, self.treedef = jax.tree_util.tree_flatten(template)
        self.shapes = [tuple(x.shape) for x in leaves]
        self.dtypes = [jnp.dtype(x.dtype) for x in leaves]
        sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self._groups: Dict[str, int] = {}  # dtype name -> packed elements
        # Per leaf: (dtype name, offset, size), or None for its own buffer.
        self._plan: list = []
        # Per leaf: where its batch axes stand (0 unless ``own_axes`` says).
        self._at = [0 if a is None else int(a)
                    for a in own_axes or [None] * len(leaves)]
        own = [a is not None for a in own_axes or [None] * len(leaves)]
        for n, dt, mine in zip(sizes, self.dtypes, own):
            if mine or n * dt.itemsize * copies >= own_buffer_bytes:
                self._plan.append(None)
                continue
            off = self._groups.get(dt.name, 0)
            self._plan.append((dt.name, off, n))
            self._groups[dt.name] = off + n
        self._order = sorted(self._groups)
        self.num_buffers = len(self._order) + self._plan.count(None)
        self._perms = [_lanes_last(s) for s in self.shapes]
        self.axes = (0,) * len(self._order) + tuple(
            at for at, plan in zip(self._at, self._plan) if plan is None
        )

    def pack(self, tree) -> Tuple[jnp.ndarray, ...]:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"pack: tree {treedef} != template {self.treedef}")
        parts: Dict[str, list] = {name: [] for name in self._order}
        own = []
        for x, shape, dt, plan, perm, at in zip(
            leaves, self.shapes, self.dtypes, self._plan, self._perms,
            self._at,
        ):
            lead = x.ndim - len(shape)
            if (
                lead < 0 or x.dtype != dt
                or tuple(x.shape[:at] + x.shape[at + lead:]) != shape
            ):
                raise ValueError(
                    f"pack: leaf {x.dtype}{tuple(x.shape)} is not the "
                    f"template's {dt}{shape} with batch axes at {at}"
                )
            if plan is None:
                own.append(x)
                continue
            if perm is not None:
                x = x.transpose(
                    tuple(range(lead)) + tuple(lead + a for a in perm)
                )
            parts[plan[0]].append(x.reshape(x.shape[:lead] + (plan[2],)))
        return tuple(
            jnp.concatenate(parts[name], axis=-1) for name in self._order
        ) + tuple(own)

    def unpack(self, packed):
        if len(packed) != self.num_buffers:
            raise ValueError(
                f"unpack: {len(packed)} buffers, the codec has "
                f"{self.num_buffers}"
            )
        flat = dict(zip(self._order, packed))
        own = iter(packed[len(self._order):])
        leaves = []
        for shape, plan, perm in zip(self.shapes, self._plan, self._perms):
            if plan is None:
                leaves.append(next(own))
                continue
            name, off, n = plan
            x = flat[name][..., off:off + n]
            lead = x.ndim - 1
            if perm is None:
                leaves.append(x.reshape(x.shape[:-1] + shape))
                continue
            x = x.reshape(x.shape[:-1] + tuple(shape[a] for a in perm))
            inverse = sorted(range(len(perm)), key=perm.__getitem__)
            leaves.append(x.transpose(
                tuple(range(lead)) + tuple(lead + a for a in inverse)
            ))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def ring_frame_at(ring: SnapshotRing, frame: int) -> int:
    """Host-side: which frame currently occupies ``frame``'s slot."""
    return int(ring.frames[frame % ring.depth])
