"""Tables that are mostly missing: a train table and a held-out one from
``--seed``, with NaN in station blocks (driver ``train_eval_missing``).

A production line: ``stations.count`` stations in ``stations.stages``
stages of alternative stations. A part (a row) passes through at most
one station of a stage and has measurements (finite values) only in the
adjacent columns of the stations it visited; every other cell is NaN.
The layout is drawn from seeds IN the configuration, never from
``--seed``: how many columns a station has (``min_columns`` to
``max_columns``, summing to the table's width), which share of the rows
visits it (log-uniform between ``min_share`` and ``max_share``, scaled
so that ``present_share`` of all cells are finite) and which stage it
belongs to (dealt so that no stage is visited by more than all rows),
which columns are coarse (``values.coarse_share`` of them hold
``levels_min`` to ``levels_max`` evenly spaced levels from -1 to 1, as
measurements read off a gauge do; the rest are continuous), and the
label's coefficients.

A finite value is ``clip(z / 3, -1, 1)`` of a standard normal ``z``,
rounded to its column's levels where the column is coarse. The label is
a thresholded latent::

    latent = linear_scale * sum_present(coef * value) + noise_scale * e
           + product_scale * sum(value_i * value_j)     # both present
           + sum(visit_coef_s * visited_s)              # the station, not
                                                        # a value of it
    y = latent > threshold

with ``threshold`` stated in the configuration (the latent's quantile at
the published share of positives over the file: ``calibrate_threshold``). The ``visited`` terms make "was this
station visited at all" informative, so that some node's best split is
missing against present and the default direction carries gain.

**Rows from ``--seed``.** Row batch ``b`` of table ``t`` (0 the train
table, 1 the held-out one) is ``numpy.random.default_rng([seed, t, b])``:
``block_rows`` rows of values, noise and visits. Every seed draws fresh
rows of the same line; the same seed gives the same tables whatever the
number of threads or the order of filling. The label's ``threshold`` is
the configuration's, so a seed's share of positives is the published
one to within the sample (0.56% to 0.61% of 1,000,000 rows).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

def layout(spec, n_features):
    """What no ``--seed`` changes: ``widths`` ``[S]`` columns a station,
    ``first`` ``[S]`` its first column, ``stage`` ``[S]`` its stage,
    ``share`` ``[S]`` the share of rows that visit it, ``column_station``
    ``[F]``, ``levels`` ``[F]`` float32 (half the number of steps of a
    coarse column, 0 where the column is continuous), ``coef`` ``[F]``."""
    st, F = spec["stations"], int(n_features)
    S, stages = int(st["count"]), int(st["stages"])
    lo, hi = int(st["min_columns"]), int(st["max_columns"])
    if S % stages or not S * lo <= F <= S * hi:
        raise ValueError(f"{S} stations of {lo}..{hi} columns in {stages} "
                         f"stages cannot hold {F} columns")
    rng = np.random.default_rng(int(st["layout_seed"]))
    widths = rng.integers(lo, hi + 1, S)
    while widths.sum() != F:            # walk to the width, inside the bounds
        step = 1 if widths.sum() < F else -1
        ok = np.flatnonzero((widths + step >= lo) & (widths + step <= hi))
        widths[rng.choice(ok)] += step
    first = np.concatenate([[0], np.cumsum(widths)[:-1]])
    s_lo, s_hi = float(st["min_share"]), float(st["max_share"])
    share = np.exp(rng.uniform(np.log(s_lo), np.log(s_hi), S))
    target = float(st["present_share"])
    for _ in range(200):                # the column-weighted mean, by scaling
        share = np.clip(share * target * F / (share @ widths), s_lo, s_hi)
    if abs(share @ widths / F - target) > 1e-3:
        raise ValueError("the stations' shares cannot reach present_share")
    # the alternatives of a stage: the most visited stations first, each
    # to the stage that is least visited so far and has room
    stage, per_stage = np.zeros(S, np.int64), np.zeros(stages)
    room = np.full(stages, S // stages)
    for s in np.argsort(-share, kind="stable"):
        g = int(np.argmin(np.where(room > 0, per_stage, np.inf)))
        stage[s], per_stage[g], room[g] = g, per_stage[g] + share[s], \
            room[g] - 1
    if per_stage.max() > 1.0:
        raise ValueError(f"a stage is visited by {per_stage.max():.2f} of "
                         "the rows; choose another layout_seed")
    val = spec["values"]
    vrng = np.random.default_rng(int(val["value_seed"]))
    coarse = vrng.random(F) < float(val["coarse_share"])
    n_levels = vrng.integers(int(val["levels_min"]),
                             int(val["levels_max"]) + 1, F)
    levels = np.where(coarse, (n_levels - 1) / 2.0, 0.0).astype(np.float32)
    coef = np.random.default_rng(int(spec["label"]["coef_seed"])) \
        .standard_normal(F, dtype=np.float32)
    return {"widths": widths, "first": first, "stage": stage,
            "share": share, "stages": stages,
            "column_station": np.repeat(np.arange(S), widths),
            "levels": levels, "coef": coef}


def _visits(lay, rng, rows):
    """``[rows, S]`` bool: the stations each row visited. One uniform a
    stage picks at most one of its stations."""
    S = len(lay["share"])
    out = np.zeros((rows, S), bool)
    u = rng.random((rows, lay["stages"]), dtype=np.float32)
    for g in range(lay["stages"]):
        at = 0.0
        for s in np.flatnonzero(lay["stage"] == g):
            out[:, s] = (u[:, g] >= at) & (u[:, g] < at + lay["share"][s])
            at += lay["share"][s]
    return out


def _fill(spec, lay, key, X, latent):
    """The batch of stream ``key`` (seed, table, batch) into ``X`` /
    ``latent`` (views of the rows it lands in)."""
    lab = spec["label"]
    rows = X.shape[0]
    rng = np.random.default_rng([int(k) for k in key])
    rng.standard_normal(out=X, dtype=np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    visited = _visits(lay, rng, rows)
    X *= np.float32(1.0 / 3.0)
    np.clip(X, -1.0, 1.0, out=X)
    coarse = lay["levels"] > 0
    half = np.where(coarse, lay["levels"], 1.0).astype(np.float32)
    np.copyto(X, np.rint((X + 1.0) * half) / half - 1.0,
              where=coarse[None, :])
    absent = ~visited[:, lay["column_station"]]
    np.copyto(X, np.float32(0.0), where=absent)     # what is not measured
    latent[:] = (X @ lay["coef"]) * np.float32(lab["linear_scale"])
    latent += noise * np.float32(lab["noise_scale"])
    for s, i, j in lab.get("products", []):
        a, b = lay["first"][s] + i, lay["first"][s] + j
        latent += np.float32(lab["product_scale"]) * X[:, a] * X[:, b]
    for s, c in lab.get("visited", []):
        latent += np.float32(c) * visited[:, s]
    np.copyto(X, np.float32(np.nan), where=absent)


def _run(jobs, threads):
    if threads <= 1:
        for job in jobs:
            job()
    else:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            for done in [pool.submit(job) for job in jobs]:
                done.result()               # re-raises


def make_table(spec, n_features, rows, seed, table=0, threads=8, lay=None):
    """``(X [rows, F] float32 with NaN, latent [rows] float32)``: the
    first ``rows`` rows of table ``table`` of ``seed``, every batch
    generated where it lands."""
    lay = lay or layout(spec, n_features)
    batch = int(spec["block_rows"])
    X = np.empty((rows, int(n_features)), np.float32)
    latent = np.empty((rows,), np.float32)
    _run([lambda a=a, b=b: _fill(spec, lay, (seed, table, b),
                                 X[a:a + batch], latent[a:a + batch])
          for b, a in enumerate(range(0, rows, batch))], threads)
    return X, latent


def calibrate_threshold(spec, n_features, rows):
    """The latent's quantile at ``1 - positive_share`` over ``rows`` rows
    of the stream of ``label.threshold_seed``, a batch at a time: how the
    configuration's ``threshold`` was found. Not used by a run."""
    lay, batch = layout(spec, n_features), int(spec["block_rows"])
    X = np.empty((batch, int(n_features)), np.float32)
    latent = np.empty((int(rows),), np.float32)
    for b, a in enumerate(range(0, int(rows), batch)):
        n = min(batch, int(rows) - a)
        _fill(spec, lay, (spec["label"]["threshold_seed"], 0, b), X[:n],
              latent[a:a + n])
    return float(np.quantile(latent,
                             1.0 - float(spec["label"]["positive_share"])))


def make_tables(spec, n_features, rows, valid_rows, seed, threads=8,
                positive_share=None):
    """``{"train": (X, y, None), "valid": (X, y, None)}``: the shape
    ``datagen_rank.make_tables`` gives, without query groups. Both tables
    from ``seed``; the label is cut at the configuration's ``threshold``;
    ``positive_share`` (the harness's CPU test alone) cuts it at that
    quantile of the train table's own latent instead."""
    lay = layout(spec, n_features)
    made = {which: make_table(spec, n_features, int(n), seed, table=t,
                              threads=threads, lay=lay)
            for t, (which, n) in enumerate((("train", rows),
                                            ("valid", valid_rows)))}
    thr = np.float32(spec["label"]["threshold"]) if positive_share is None \
        else np.quantile(made["train"][1], 1.0 - float(positive_share))
    return {w: (X, (latent > thr).astype(np.float32), None)
            for w, (X, latent) in made.items()}


SELFTEST_POSITIVE_SHARE = 0.1


def selftest(cfg, rows):
    """For the harness's CPU test of ``rows`` rows: ``(keywords for
    make_tables, the factor for a floor stated as a sum of hessians)``.
    At the configuration's 0.58% positives a table of a few thousand rows
    is nodes of negatives alone, whose gains are rounding noise and whose
    scores tie: nothing there to compare. Its tables take their label at
    the latent's own 0.9 quantile, and the floor shrinks with the table's
    hessian sum, so that a tree has as many leaves as the cell's (at most
    1 / 0.017)."""
    p, q = float(cfg["data"]["label"]["positive_share"]), \
        SELFTEST_POSITIVE_SHARE
    return {"positive_share": q}, \
        rows * q * (1 - q) / (int(cfg["num_data"]) * p * (1 - p))
