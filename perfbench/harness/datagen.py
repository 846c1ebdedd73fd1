"""The benchmark's data generator: a table of rows from ``--seed``.

One general generator reads the ``data`` group of a configuration file
(column kinds and the label's recipe) and the row count; the program
receives only the arrays. The arithmetic of the label is
``bench.make_higgs_like``'s (linear logit + noise > 0), plus pairwise
products so that trees grow unevenly; the draws are float32 from the
start (the original draws float64 and casts, which doubles host RSS).

The stream is defined per block of ``BLOCK_ROWS`` rows — block ``b`` of
seed ``s`` is ``numpy.random.default_rng([s, b])`` — so neither the
number of threads nor the order in which blocks are filled can change
the data, and a block never needs more than its own slab of memory.
The label's coefficients come from the configuration's ``coef_seed``,
not from ``--seed``: every seed draws different rows of the same
population, so every seed gives the trainer the same amount of work.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 18


def column_layout(spec):
    """``[(kind, first, last, params)]`` and the total column count."""
    out, at = [], 0
    for grp in spec["columns"]:
        if grp["kind"] not in ("count", "normal"):
            raise ValueError(f"unknown column kind {grp['kind']!r}")
        out.append((grp["kind"], at, at + int(grp["n"]), grp))
        at += int(grp["n"])
    return out, at


def label_coef(spec, n_features):
    lab = spec["label"]
    return np.random.default_rng(int(lab["coef_seed"])) \
        .standard_normal(n_features, dtype=np.float32)


def fill_block(spec, seed, block, X, y, coef=None):
    """Fill rows ``[block*BLOCK_ROWS, ...)`` of ``X`` / ``y`` in place."""
    layout, F = column_layout(spec)
    if coef is None:
        coef = label_coef(spec, F)
    lab = spec["label"]
    a = block * BLOCK_ROWS
    b = min(a + BLOCK_ROWS, X.shape[0])
    rng = np.random.default_rng([int(seed), int(block)])
    z = X[a:b]
    rng.standard_normal(out=z, dtype=np.float32)
    noise = rng.standard_normal(b - a, dtype=np.float32)
    logit = (z @ coef) * np.float32(lab["linear_scale"])
    logit += noise * np.float32(lab["noise_scale"])
    for i, j in lab.get("products", []):
        logit += np.float32(lab["product_scale"]) * z[:, i] * z[:, j]
    y[a:b] = logit > 0
    for kind, c0, c1, grp in layout:
        if kind == "count":
            # heavy-tailed non-negative integers with ties, as counts are
            z[:, c0:c1] = np.floor(np.exp(np.float32(grp["sigma"])
                                          * z[:, c0:c1]))


def make_table(spec, rows, seed, threads=8):
    """``(X [rows, F] float32 C-contiguous, y [rows] float32 in {0,1})``."""
    _, F = column_layout(spec)
    X = np.empty((rows, F), np.float32)
    y = np.empty((rows,), np.float32)
    coef = label_coef(spec, F)
    blocks = range((rows + BLOCK_ROWS - 1) // BLOCK_ROWS)
    if threads <= 1:
        for b in blocks:
            fill_block(spec, seed, b, X, y, coef)
    else:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            # list() re-raises a worker's exception here
            list(pool.map(lambda b: fill_block(spec, seed, b, X, y, coef),
                          blocks))
    return X, y
