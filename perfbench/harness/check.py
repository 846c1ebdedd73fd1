"""The comparison that decides ``correct``.

What the timed job produced — its trees (``dump_model()``) and the score
vector it left on the device — is held against the plain reference
(``reference.py``) at the timed size, over all rows. The followed trees
are trees of the measured window: its first, its last and those evenly
between (``rounds_followed`` of them; all, where the window holds no
more). The numbers, each with a limit of its own
(``perfbench/limits/<cell>.json``; ``null`` there = read and printed,
not compared):

``leaf_count_mismatch``  leaves and internal nodes of the followed trees
    whose row count differs from the reference's routing (exact, 0);
``leaf_weight_gap`` / ``leaf_value_gap`` / ``split_gain_gap``
    widest gap between the program's number and the reference's, against
    the reference's number or the tree's median, whichever is larger;
``leaf_weight_median_gap`` / ``leaf_value_median_gap`` /
``split_gain_median_gap``  the same gaps at the tree's median leaf (or
    split), the widest over the followed trees: what the precision of
    the histogram's operands moves, and an inherited rounding of one
    large sum does not;
``root_split_shortfall`` / ``deep_split_shortfall``  how far the split at
    the root, and at a few deep nodes drawn from the seed, of a followed
    tree falls short of the reference's best split of the same rows on
    its own candidates;
``score_gap``  widest gap between the device's score after the window
    and the reference's routing of all rows through all its trees.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from . import reference as R

NUMBERS = ("leaf_count_mismatch", "leaf_weight_gap", "leaf_value_gap",
           "split_gain_gap", "leaf_weight_median_gap",
           "leaf_value_median_gap", "split_gain_median_gap",
           "root_split_shortfall", "deep_split_shortfall", "score_gap")
GAPS = (("leaf_weight", "leaf_weight"), ("leaf_value", "leaf_value"),
        ("split_gain", "gain"))


def _gaps(got, want):
    """``|got - want|`` against ``max(|want|, median |want|)``, each."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    floor = np.median(np.abs(want)) if want.size else 0.0
    den = np.maximum(np.abs(want), max(floor, 1e-300))
    gap = np.abs(got - want) / den
    # a number the program failed to produce is a failure, not a skip
    return np.where(np.isfinite(gap), gap, np.inf)


def _rel_gap(got, want):
    """The widest of ``_gaps``."""
    gap = _gaps(got, want)
    return float(np.max(gap)) if gap.size else 0.0


def _median_gap(got, want):
    gap = _gaps(got, want)
    return float(np.median(gap)) if gap.size else 0.0


def followed_trees(n_trees, warm, k):
    """Indices of ``k`` trees of the window (the trees after the ``warm``
    warm-up rounds): its first, its last, the others evenly between."""
    first = min(warm, n_trees - 1)
    return sorted({int(round(i))
                   for i in np.linspace(first, n_trees - 1, max(k, 1))})


def deep_nodes(tree, internal_count, seed, ti, k, min_rows, min_depth=2):
    """``k`` internal nodes of the tree, drawn from the seed among those
    ``min_depth`` or more below the root that hold ``min_rows`` or more."""
    ok = np.flatnonzero((R.node_depths(tree) >= min_depth)
                        & (internal_count >= min_rows))
    rng = np.random.default_rng([int(seed), 0xDEE9, int(ti)])
    return sorted(rng.choice(ok, size=min(k, len(ok)), replace=False))


def leaf_table(tree, counts, stats):
    """Per-leaf ``(count, G, H)`` from the per-internal-node sides."""
    I = tree["num_leaves"] - 1
    cnt = np.zeros(tree["num_leaves"], np.int64)
    G = np.zeros(tree["num_leaves"], np.float64)
    H = np.zeros(tree["num_leaves"], np.float64)
    for k in range(I):
        for side, child in enumerate((tree["left"][k], tree["right"][k])):
            if child >= I:
                cnt[child - I] = counts[k, side]
                G[child - I] = stats[k, 2 * side]
                H[child - I] = stats[k, 2 * side + 1]
    return cnt, G, H


def reference_tree(tree, counts, stats, lr, lam, bias):
    """What the reference says of one tree's numbers, from its routing."""
    counts = np.asarray(counts, np.int64)
    stats = np.asarray(stats, np.float64)
    cnt, G, H = leaf_table(tree, counts, stats)
    gl, hl, gr, hr = stats[:, 0], stats[:, 1], stats[:, 2], stats[:, 3]
    g, h = gl + gr, hl + hr
    with np.errstate(divide="ignore", invalid="ignore"):
        out = {
            "leaf_count": cnt,
            "internal_count": counts.sum(axis=1),
            "leaf_weight": H,
            "leaf_value": -lr * G / (H + lam) + bias,
            "gain": gl * gl / (hl + lam) + gr * gr / (hr + lam)
            - g * g / (h + lam),
        }
    return out


def tree_args(tree):
    return (jnp.asarray(tree["feature"]),
            jnp.asarray(R.threshold_f32(tree["threshold"])),
            jnp.asarray(tree["left"]), jnp.asarray(tree["right"]),
            jnp.asarray(tree["order"]))


def compare(model, prog_score, X, y, ref_cfg, lr, check_cfg, seed,
            operand_dtype, warm=0, log=None, control_dtype=None):
    """All the numbers of one run; ``model`` is ``dump_model()``'s dict,
    ``prog_score`` the program's ``[n]`` float32 score on the host,
    ``warm`` the rounds before the window.

    ``control_dtype`` (the control tools only) also puts the reference,
    computed with operands of that lower precision, in the program's
    place: the gaps of its leaf weights, values and gains against the
    reference's own are returned under ``"control"``."""
    log = log or (lambda *_: None)
    trees = [R.parse_tree(t) for t in model["tree_info"]]
    n = X.shape[0]
    lam = float(ref_cfg["lambda_l2"])
    follow = followed_trees(len(trees), warm,
                            int(check_cfg["rounds_followed"]))
    X_T = R.table_to_device(X)
    yd = jnp.asarray(y, jnp.float32)
    bias0 = R.init_score(y)
    score = jnp.full((n,), bias0, jnp.float32)
    cands = jnp.asarray(R.candidate_thresholds(
        X, seed, int(check_cfg["root_candidates"]),
        int(check_cfg["candidate_sample_rows"])))
    split_limits = (jnp.float32(ref_cfg["min_data_in_leaf"]),
                    jnp.float32(ref_cfg["min_sum_hessian_in_leaf"]),
                    jnp.float32(lam))
    deep_min_rows = n * float(check_cfg["deep_min_share"])
    log("reference: table on device")

    out = {name: 0.0 for name in NUMBERS}
    out["leaf_count_mismatch"] = 0
    out["root_split_shortfall"] = out["deep_split_shortfall"] = -np.inf
    control = {name: 0.0 for name in NUMBERS if name.endswith("_gap")
               and name != "score_gap"}

    def widen(into, got, want, bias):
        for key, field in GAPS:
            off = bias if field == "leaf_value" else 0.0
            a, b = got[field] - off, want[field] - off
            into[key + "_gap"] = max(into[key + "_gap"], _rel_gap(a, b))
            into[key + "_median_gap"] = max(into[key + "_median_gap"],
                                            _median_gap(a, b))

    for ti, tree in enumerate(trees):
        I = tree["num_leaves"] - 1
        if I == 0:
            score = score + jnp.float32(tree["leaf_value"][0]
                                        - (bias0 if ti == 0 else 0.0))
            continue
        followed = ti in follow
        bias = bias0 if ti == 0 else 0.0
        if followed:
            g, h = R.grad_hess(score, yd, operand_dtype=operand_dtype)
        else:
            g = h = jnp.zeros((1,), jnp.float32)
        at, counts, stats = R.route_tree(X_T, *tree_args(tree), g, h,
                                         with_stats=followed)
        if followed:
            ref = reference_tree(tree, counts, stats, lr, lam, bias)
            out["leaf_count_mismatch"] += int(
                np.sum(ref["leaf_count"] != tree["leaf_count"])
                + np.sum(ref["internal_count"] != tree["internal_count"]))
            widen(out, tree, ref, bias)
            ones = jnp.ones((n,), jnp.float32)
            for k in [0] + deep_nodes(tree, ref["internal_count"], seed, ti,
                                      int(check_cfg["deep_nodes"]),
                                      deep_min_rows):
                w = ones if k == 0 else R.rows_under(
                    at, jnp.asarray(R.subtree_leaves(tree, k)), I)
                best = float(jnp.max(R.node_best_gain(X_T, cands, g, h, w,
                                                      *split_limits)))
                key = "root_split_shortfall" if k == 0 \
                    else "deep_split_shortfall"
                out[key] = max(out[key], (best - float(ref["gain"][k]))
                               / max(best, 1e-300))
            values = ref["leaf_value"] - bias
            if control_dtype:
                gl, hl = R.grad_hess(score, yd, operand_dtype=control_dtype)
                _, c_l, s_l = R.route_tree(X_T, *tree_args(tree), gl, hl)
                widen(control, reference_tree(tree, c_l, s_l, lr, lam, bias),
                      ref, bias)
            log(f"reference: tree {ti} followed")
        else:
            values = tree["leaf_value"] - bias
        score = R.add_leaf_values(score, at,
                                  jnp.asarray(values, jnp.float32), I)
    prog_score = np.asarray(prog_score, np.float32).reshape(-1)
    losses = {"program": float(R.log_loss(jnp.asarray(prog_score), yd))
              if prog_score.shape == (n,) else None,
              "reference": float(R.log_loss(score, yd)),
              "at_start": float(R.log_loss(
                  jnp.full((n,), bias0, jnp.float32), yd))}
    ref_score = np.asarray(score)
    out["score_gap"] = np.inf if prog_score.shape != ref_score.shape \
        else _rel_gap(prog_score, ref_score)
    for key in ("root_split_shortfall", "deep_split_shortfall"):
        # no node to look at is a number not produced, which fails
        out[key] = float(out[key]) if np.isfinite(out[key]) else None
    out.update(trees=len(trees), followed=follow, log_loss=losses,
               control=control if control_dtype else None)
    return out


def judge(numbers, limits):
    """``(correct, {name: {"value", "limit"}})``; every number of
    ``NUMBERS`` needs an entry in the limits (``None``: not compared),
    and a missing or non-finite value of a compared number fails."""
    table, ok = {}, True
    for name in NUMBERS:
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        v, lim = numbers.get(name), limits[name]
        if lim is not None:
            lim = float(lim)
            ok = ok and bool(v is not None and np.isfinite(v) and v <= lim)
        table[name] = {"value": v, "limit": lim}
    return ok, table


def print_table(table, correct, file=sys.stderr):
    for name, row in table.items():
        print(f"check {name} value {row['value']!r} limit {row['limit']!r}",
              file=file)
    print(f"check correct {correct}", file=file)
