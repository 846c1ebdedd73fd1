"""Small-table row gathers that compile well on TPU.

``table[idx]`` with a million-row ``idx`` and a tiny table lowers to an
XLA gather that TPUs execute one element at a time (its cost on a
local chip: not measured). The boosting loop needs exactly
this shape in several places (leaf value -> row score contribution, the
reference's ScoreUpdater::AddScore walk, score_updater.hpp:58): a [n]
index vector into an [L <= a few hundred] table. ``gather_small``
replaces it with L sequential full-width selects — O(L * n / lanes)
vector work — while keeping exact dtype semantics
(values are moved bit-for-bit, never re-rounded).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gather_small"]


def gather_small(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` via a fori_loop of vector selects.

    Args:
      table: ``[L, ...]`` values (any dtype); L is static and small.
        Trailing dims (e.g. per-leaf coefficient rows) are supported.
      idx: ``[n]`` int indices into the table.
    Returns:
      ``[n, ...]`` array of ``table.dtype``.

    Out-of-range semantics DIVERGE from ``table[idx]`` under jit: XLA
    clamps indices to [0, L), so ``table[-1]`` returns ``table[0]``;
    this returns **0** for any out-of-range index. All current callers
    (score updates, valid scoring, linear-leaf eval) pass leaf ids that
    are in-range by construction; a caller introducing sentinel indices
    (e.g. -1 for an unrouted row) must mask them explicitly rather than
    rely on either behavior. Set ``LIGHTGBM_TPU_DEBUG_GATHER=1`` to
    assert in-range eagerly (host round-trip — debug only).
    """
    if os.environ.get("LIGHTGBM_TPU_DEBUG_GATHER") and not isinstance(
            idx, jax.core.Tracer):
        lo = int(jnp.min(idx))
        hi = int(jnp.max(idx))
        if lo < 0 or hi >= table.shape[0]:
            raise ValueError(
                f"gather_small: index range [{lo}, {hi}] outside "
                f"table [0, {table.shape[0]})")
    return _gather_small(table, idx)


@jax.jit
def _gather_small(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    L = table.shape[0]
    init = jnp.zeros(idx.shape + table.shape[1:], table.dtype)
    idx_b = idx.reshape(idx.shape + (1,) * (table.ndim - 1))

    def body(l, acc):
        return jnp.where(idx_b == l, table[l], acc)

    return lax.fori_loop(0, L, body, init)
