"""Entity births inside a jitted step: the free-row claim.

A title that spawns entities in its step (``models/projectiles.py``: a
projectile a firing player; ``models/particles.py``: a hundred particles a
frame) has ``K`` candidate births a frame, a static count, of which a mask
``wants`` says which happen. The claim rule, one for every title:

- the wanting births are RANKED in birth order (``rank[k]``: how many
  wanting births come before birth ``k``);
- they are matched rank for rank to the FREE rows (``~alive``) in ascending
  row order;
- a birth whose rank is not under the number of free rows finds none and
  is dropped (it fizzles); ``placed`` counts the births that found a row.

One lowering: every row reads its own ordinal among the free rows off the
free-rows prefix sum and, if that ordinal is under the number of wanting
births, takes the birth of that ordinal by a SELECT. Dense passes over the
capacity, nothing indexed: no ``scatter``, no ``searchsorted``, no order of
writes that could matter, and a cost that does not grow with the births.

Why not a row looked up a birth (``searchsorted``) and a scatter into it,
which touches K rows and not the capacity: under the served tick's
``[S] x [B]`` batch axes the scatter's operand layout poisons the step
around it. Measured at 100 births a frame, 9,216 rows, 512 lanes
(``particles.synctest``, ``chiprun_out/pr44_b/ops_scatter.out`` against
``ops_select.out``; PR 44): 93.76 ms a dispatch against 30.94. The scatter
fusions themselves are 9.76 ms of it; the other 53 ms are the
``{T(2,128)}`` layout the scatter forces on the state (the title's
integrate 23.58 ms where it is 0.09, and the ring's copies). That cost is
the layout's and not the serial loop's, so it does not fall with ``K``.

What a birth writes is given to :meth:`RowClaim.put` a leaf at a time,
either as one value for every birth, as a ``[K, ...]`` table by candidate
birth, or as a FUNCTION of the birth's ordinal (elementwise: it is called
with ``int32[capacity]`` row ordinals, entries of rows that take no birth
included, whose results are thrown away). Only the function keeps the claim
free of the births' count: a table costs a select a candidate birth.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Union

import jax.numpy as jnp

from bevy_ggrs_tpu.obs.trace import device_scope

Values = Union[jnp.ndarray, Callable[[jnp.ndarray], jnp.ndarray]]


def _lead(mask: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


@dataclasses.dataclass(frozen=True)
class RowClaim:
    """One frame's claim of free rows (see the module's docstring).

    ``rank``: ``int32[K]``, birth ``k``'s ordinal among the wanting births
    (meaningful where ``wants[k]``); ``placed``: ``int32[]``, the births that
    found a row.
    """

    wants: jnp.ndarray  # bool[K]
    rank: jnp.ndarray  # int32[K]
    placed: jnp.ndarray  # int32[]
    _ordinal: jnp.ndarray  # int32[capacity], row r's ordinal among the free
    _taken: jnp.ndarray  # bool[capacity], the rows that take a birth

    def put(self, leaf: jnp.ndarray, values: Values) -> jnp.ndarray:
        """``leaf[capacity, ...]`` with the claimed rows overwritten."""
        with device_scope("claim"):
            if callable(values):
                return jnp.where(
                    _lead(self._taken, leaf), values(self._ordinal), leaf
                )
            values = jnp.asarray(values, leaf.dtype)
            if values.ndim == leaf.ndim - 1:  # one value for every birth
                return jnp.where(_lead(self._taken, leaf), values, leaf)
            for k in range(self.wants.shape[0]):
                hit = (
                    self._taken & self.wants[k]
                    & (self._ordinal == self.rank[k])
                )
                leaf = jnp.where(_lead(hit, leaf), values[k], leaf)
            return leaf


def claim_rows(alive: jnp.ndarray, wants: jnp.ndarray) -> RowClaim:
    """Claim free rows of ``alive[capacity]`` for the births ``wants[K]``
    wants."""
    with device_scope("claim"):
        free = ~alive
        ordinal = jnp.cumsum(free.astype(jnp.int32)) - 1  # [cap]
        wanting = wants.astype(jnp.int32)
        taken = free & (ordinal < jnp.sum(wanting))
        return RowClaim(
            wants, jnp.cumsum(wanting) - 1,
            jnp.sum(taken.astype(jnp.int32)), ordinal, taken,
        )
