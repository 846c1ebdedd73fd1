"""Tables for the ``train_eval`` cells: a train table and a held-out
one from ``--seed``, with query groups where the configuration has them.

The rows are ``datagen.py``'s: block ``b`` of seed ``s`` is
``default_rng([s, b])``, the columns its kinds, the latent its linear
term + noise + pairwise products. The validation table is drawn from the
blocks that follow the train table's, so the two share no row.

A configuration with a ``data.queries`` group is a ranking job. Its
query lengths and its label's coefficients come from seeds IN the
configuration (``length_seed``, ``coef_seed``), not from ``--seed``:
every seed draws other rows of the same population under the same
lengths, so every seed gives the trainer the same work. Lengths are
lognormal, clipped to ``[1, max_len]``, one query of exactly ``max_len``
in each table, the last queries trimmed so that the rows sum to the
stated count. The grade (0..4) is the latent plus a per-query offset,
cut at fixed multiples of the latent's standard deviation
(``grade_cuts_sd``). Without the group the label is ``datagen.py``'s
sign of the latent.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen

BLOCK_ROWS = datagen.BLOCK_ROWS


def query_lengths(rows, count, max_len, sigma, length_seed):
    """``[count]`` int64 lengths in ``[1, max_len]`` that sum to
    ``rows``, one of them exactly ``max_len``; from ``length_seed``
    alone."""
    rows, count, max_len = int(rows), int(count), int(max_len)
    if not count <= rows <= count * max_len:
        raise ValueError(f"{count} queries of 1..{max_len} rows cannot "
                         f"hold {rows} rows")
    rng = np.random.default_rng(int(length_seed))
    raw = rng.lognormal(0.0, float(sigma), count)
    longest = int(rng.integers(count - 1)) if count > 1 else 0
    # the last query is the one trimmed, so it is never the longest

    def lengths(scale):
        out = np.clip(np.rint(raw * scale), 1, max_len).astype(np.int64)
        out[longest] = max_len
        return out

    lo, hi = 0.0, float(max_len)
    for _ in range(60):             # the smallest scale that holds the rows
        mid = 0.5 * (lo + hi)
        lo, hi = (lo, mid) if lengths(mid).sum() >= rows else (mid, hi)
    out = lengths(hi)
    excess = int(out.sum() - rows)
    for q in range(count - 1, -1, -1):
        if excess <= 0:
            break
        if q != longest:
            take = min(excess, int(out[q]) - 1)
            out[q] -= take
            excess -= take
    if excess:
        raise ValueError("the query lengths cannot be trimmed to the rows")
    return out


def table_queries(spec, rows, which, published_rows):
    """Lengths of table ``which`` (``train`` / ``valid``) at ``rows``
    rows: the configuration's own at the published size; below it (the
    harness's CPU test) as many queries as keep the mean length, none
    longer than a third of the table."""
    q = spec["queries"][which]
    if rows == published_rows:
        count, max_len = int(q["count"]), int(spec["queries"]["max_len"])
    else:
        count = max(3, int(round(q["count"] * rows / published_rows)))
        max_len = max(1, min(int(spec["queries"]["max_len"]), rows // 3))
    return query_lengths(rows, count, max_len, spec["queries"]["sigma"],
                         q["length_seed"])


def latent_sd(spec, coef):
    lab = spec["label"]
    var = (float(lab["linear_scale"]) ** 2) * float(coef @ coef) \
        + float(lab["noise_scale"]) ** 2 \
        + len(lab.get("products", [])) * float(lab["product_scale"]) ** 2 \
        + float(lab.get("query_scale", 0.0)) ** 2
    return var ** 0.5


def _fill(spec, seed, block, X, latent, coef):
    """Rows of stream block ``block`` into ``X`` / ``latent`` (views of
    that block's rows): ``datagen.fill_block``'s arithmetic, the latent
    kept."""
    layout, _ = datagen.column_layout(spec)
    lab = spec["label"]
    rng = np.random.default_rng([int(seed), int(block)])
    rng.standard_normal(out=X, dtype=np.float32)
    noise = rng.standard_normal(X.shape[0], dtype=np.float32)
    latent[:] = (X @ coef) * np.float32(lab["linear_scale"])
    latent += noise * np.float32(lab["noise_scale"])
    for i, j in lab.get("products", []):
        latent += np.float32(lab["product_scale"]) * X[:, i] * X[:, j]
    for kind, c0, c1, grp in layout:
        if kind == "count":
            X[:, c0:c1] = np.floor(np.exp(np.float32(grp["sigma"])
                                          * X[:, c0:c1]))


def make_table(spec, rows, seed, first_block=0, threads=8):
    """``(X [rows, F] float32, latent [rows] float32)`` from the stream
    blocks ``first_block, first_block + 1, ...`` of ``seed``."""
    _, F = datagen.column_layout(spec)
    X = np.empty((rows, F), np.float32)
    latent = np.empty((rows,), np.float32)
    coef = datagen.label_coef(spec, F)
    blocks = range((rows + BLOCK_ROWS - 1) // BLOCK_ROWS)

    def one(b):
        a = b * BLOCK_ROWS
        _fill(spec, seed, first_block + b, X[a:a + BLOCK_ROWS],
              latent[a:a + BLOCK_ROWS], coef)

    if threads <= 1:
        for b in blocks:
            one(b)
    else:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            list(pool.map(one, blocks))     # list(): re-raises
    return X, latent


def grades(spec, latent, sizes, seed, which):
    """Relevance grades ``[rows]`` float32 in 0..len(cuts): the latent
    plus the query's offset, cut at ``grade_cuts_sd`` x the latent's
    standard deviation."""
    lab = spec["label"]
    coef = datagen.label_coef(spec, datagen.column_layout(spec)[1])
    rng = np.random.default_rng([int(seed), 0x0FF5E7,
                                 0 if which == "train" else 1])
    offset = rng.standard_normal(len(sizes), dtype=np.float32) \
        * np.float32(lab.get("query_scale", 0.0))
    cuts = np.asarray(lab["grade_cuts_sd"], np.float32) \
        * np.float32(latent_sd(spec, coef))
    return np.searchsorted(cuts, latent + np.repeat(offset, sizes),
                           side="right").astype(np.float32)


def make_tables(spec, rows, valid_rows, seed, published_rows, threads=8):
    """``{"train": (X, y, sizes), "valid": (X, y, sizes)}``; ``sizes`` is
    ``None`` where the configuration has no query groups."""
    first_valid = (rows + BLOCK_ROWS - 1) // BLOCK_ROWS
    out = {}
    for which, n, first, pub in (
            ("train", rows, 0, published_rows["train"]),
            ("valid", valid_rows, first_valid, published_rows["valid"])):
        X, latent = make_table(spec, n, seed, first, threads)
        if "queries" in spec:
            sizes = table_queries(spec, n, which, pub)
            y = grades(spec, latent, sizes, seed, which)
        else:
            sizes, y = None, (latent > 0).astype(np.float32)
        out[which] = (X, y, sizes)
    return out
