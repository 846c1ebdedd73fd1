"""The plain reference over a table that is spread over several chips.

``criteo-dp256-host4`` holds four ranks' rows, 7.1 GB of raw float32,
which one chip cannot take beside the reference's working set. This is
the same reference (``reference.py``: the same ``jax.numpy`` float32
functions, imported from there, nothing of the program), computed in
row blocks: block ``r`` is the rows ``[r n/D, (r+1) n/D)``, on device
``r``. Everything the check compares is additive over blocks: a node's
row counts (int32, exact) and its gradient and hessian sums, the
candidate thresholds' left sums, the log loss. Each block's float32
sums are added on the host in float64. The blocks' calls are enqueued
one after another and waited for together, so the chips work side by
side.

Only :func:`node_candidate_sums` is new arithmetic: ``reference.py``'s
``node_best_gain`` ends in a maximum, which is not additive, so this is
its first half (the sums), and :func:`best_gain` its second, on the
host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference as R


def block_bounds(n, k):
    """``k`` contiguous row blocks of ``n`` rows: ``[(lo, hi)]``."""
    edges = [(n * i) // k for i in range(k + 1)]
    return list(zip(edges[:-1], edges[1:]))


def table_to_devices(X, devices):
    """Per device the ``[F, rows]`` float32 block of its rows."""
    out = []
    for dev, (lo, hi) in zip(devices, block_bounds(X.shape[0],
                                                   len(devices))):
        with jax.default_device(dev):
            out.append(jax.device_put(R.table_to_device(X[lo:hi]), dev))
    return out


def rows_to_devices(v, devices, dtype=jnp.float32):
    """A per-row host vector as one committed block per device."""
    return [jax.device_put(np.asarray(v[lo:hi], dtype), dev)
            for dev, (lo, hi) in zip(devices,
                                     block_bounds(len(v), len(devices)))]


def each(fn, *per_block, **kwargs):
    """``fn`` over the blocks: all enqueued, then all returned."""
    return [fn(*args, **kwargs) for args in zip(*per_block)]


def add_up(parts, dtype=np.float64):
    """The blocks' partial sums, added on the host."""
    return sum(np.asarray(p, dtype) for p in parts)


@functools.partial(jax.jit, static_argnames=("block",))
def node_candidate_sums(X_T, cands, g, h, w, block=1 << 16):
    """Over the rows of one block with ``w`` = 1: per feature and
    candidate ``[F, k, 3]`` the count, gradient sum and hessian sum of
    the rows with ``x <= c``, and ``[3]`` the same three over all of
    them."""
    F, n = X_T.shape
    pad = (-n) % block
    g, h = g * w, h * w
    wp = jnp.pad(w, (0, pad)).reshape(-1, block)
    gp = jnp.pad(g, (0, pad)).reshape(-1, block)
    hp = jnp.pad(h, (0, pad)).reshape(-1, block)

    def one_feature(args):
        x, c = args
        xp = jnp.pad(x, (0, pad), constant_values=jnp.inf) \
            .reshape(-1, block)

        def blk(acc, xs):
            xb, wb, gb, hb = xs
            m = xb[None, :] <= c[:, None]
            add = jnp.stack([jnp.sum(jnp.where(m, wb[None, :], 0.0), axis=1),
                             jnp.sum(jnp.where(m, gb[None, :], 0.0), axis=1),
                             jnp.sum(jnp.where(m, hb[None, :], 0.0), axis=1)],
                            axis=1)
            return acc + add, None

        acc, _ = lax.scan(blk, jnp.zeros((c.shape[0], 3), jnp.float32),
                          (xp, wp, gp, hp))
        return acc

    return (lax.map(one_feature, (X_T, cands)),
            jnp.stack([jnp.sum(w), jnp.sum(g), jnp.sum(h)]))


def best_gain(left, total, min_data, min_hess, lam):
    """``reference.node_best_gain``'s second half on the added-up sums:
    the best ``GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)`` over every
    feature and candidate that leaves both sides their minimum."""
    cl, gl, hl = left[..., 0], left[..., 1], left[..., 2]
    N, G, H = total
    cr, gr, hr = N - cl, G - gl, H - hl
    ok = (cl >= min_data) & (cr >= min_data) \
        & (hl >= min_hess) & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) \
            - G * G / (H + lam)
    return float(np.max(np.where(ok, gain, -np.inf)))
