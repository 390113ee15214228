"""Pallas kernel for the order-insensitive world checksum.

Computes bit-identically the same two-lane ``uint32[2]`` checksum as
:func:`bevy_ggrs_tpu.state.checksum` (the murmur3-style per-slot hash,
wrapping-summed over live slots into 64 bits as [lo, hi] lanes —
the vectorized form of the reference's ``checksum += component.reflect_hash()``
at ``/root/reference/src/world_snapshot.rs:72-75``), but as ONE kernel pass:

- XLA assembles the word matrix ``[W, capacity]`` (bitcasts + masking — pure
  layout work the compiler fuses into the producing ops);
- the kernel streams slot blocks through VMEM, runs the whole W-step hash
  chain per slot in registers, and accumulates the masked wrapping sum into
  SMEM — one HBM read per word, no per-component dispatch, no [cap]-sized
  intermediate written back.

Every op is integer, in the same order as the XLA path, so the two
implementations agree bitwise and peers may mix them freely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bevy_ggrs_tpu import state as state_lib
from bevy_ggrs_tpu.obs.trace import device_scope
from bevy_ggrs_tpu.ops.interpret import pallas_interpret
from bevy_ggrs_tpu.state import WorldState

# The bitwise contract with state.checksum is enforced by sharing the hash
# primitives, not copying them (both are plain jnp and lower inside kernels);
# same for the unroll threshold the two chains must agree on.
_SEED = state_lib._SEED
_HI_TWEAK = state_lib._HI_TWEAK
_mix_one = state_lib._mix_one
_fmix = state_lib._fmix
_UNROLL_LIMIT = state_lib._UNROLL_LIMIT

_LANE_BLOCK = 512


def _hash_kernel(words_ref, alive_ref, out_ref, *, n_words: int):
    """One slot block: chain-mix all ``n_words`` rows into both checksum
    lanes (lo/hi murmur streams from their own seeds — same word pass, two
    integer chains), fmix, masked-sum per lane.

    Each grid step writes its own partial sums (summed by XLA outside), so
    there is no cross-step carry — which keeps the kernel vmap-safe for the
    speculative branch axis.
    """
    blk = words_ref.shape[1]
    h = jnp.concatenate([
        jnp.full((1, blk), _SEED, dtype=jnp.uint32),
        jnp.full((1, blk), _SEED ^ _HI_TWEAK, dtype=jnp.uint32),
    ])  # [2, blk]; each mixed word row broadcasts over the lane axis
    if n_words <= _UNROLL_LIMIT:
        for i in range(n_words):
            h = _mix_one(h, words_ref[i : i + 1, :])
    else:
        h = jax.lax.fori_loop(
            0,
            n_words,
            lambda i, hh: _mix_one(hh, words_ref[pl.ds(i, 1), :]),
            h,
        )
    h = _fmix(h)
    h = jnp.where(alive_ref[0:1, :] != 0, h, jnp.uint32(0))
    # Mosaic has no unsigned reductions; a wrapping int32 sum is bit-identical.
    h_i32 = jax.lax.bitcast_convert_type(h, jnp.int32)
    out_ref[pl.program_id(0), 0] = jnp.sum(h_i32[0], dtype=jnp.int32)
    out_ref[pl.program_id(0), 1] = jnp.sum(h_i32[1], dtype=jnp.int32)


@jax.jit
def _entity_hash_sum(
    words_t: jnp.ndarray,  # uint32[W, capacity]
    alive_u32: jnp.ndarray,  # uint32[1, capacity]
) -> jnp.ndarray:
    n_words, cap = words_t.shape
    blk = min(_LANE_BLOCK, max(128, cap))
    pad = (-cap) % blk
    if pad:
        # Padded slots carry alive=0, so they contribute 0 to the sum no
        # matter what their (zero) words hash to.
        words_t = jnp.pad(words_t, ((0, 0), (0, pad)))
        alive_u32 = jnp.pad(alive_u32, ((0, 0), (0, pad)))
    n_blocks = words_t.shape[1] // blk
    partials = pl.pallas_call(
        functools.partial(_hash_kernel, n_words=n_words),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((n_words, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec(
            (n_blocks, 2), lambda i: (0, 0), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks, 2), jnp.int32),
        interpret=pallas_interpret(),
    )(words_t, alive_u32)
    return jnp.sum(
        jax.lax.bitcast_convert_type(partials, jnp.uint32), axis=0,
        dtype=jnp.uint32,
    )


def _word_matrix(state: WorldState) -> jnp.ndarray:
    """The ``[W, capacity]`` uint32 word stream, rows in the exact order the
    XLA path mixes them: rollback_id, then per sorted component its presence
    bit followed by its (presence-masked) words."""
    rows = [jnp.transpose(state_lib._to_u32_words(state.rollback_id))]
    for name in sorted(state.components):
        pres = state.present[name]
        words = state_lib._to_u32_words(state.components[name])
        words = jnp.where(pres[:, None], words, jnp.uint32(0))
        rows.append(pres.astype(jnp.uint32)[None, :])
        rows.append(jnp.transpose(words))
    return jnp.concatenate(rows, axis=0)


def checksum_pallas(state: WorldState) -> jnp.ndarray:
    """Drop-in, bit-identical replacement for :func:`state.checksum`."""
    with device_scope("checksum"):
        words_t = _word_matrix(state)
        alive = state.alive.astype(jnp.uint32)[None, :]
        total = _entity_hash_sum(words_t, alive)
        return total + state_lib._resources_checksum(state.resources)


def install_pallas_checksum(enable: bool = True) -> None:
    """Route :func:`state.ring_save`'s checksum through the Pallas kernel.

    Call before tracing (jitted callers bake the impl in at trace time).
    Both impls agree bitwise, so flipping this never desyncs a session.
    """
    state_lib.set_checksum_impl(checksum_pallas if enable else None)
