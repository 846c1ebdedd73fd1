"""The comparison that decides ``correct`` for a job whose tables have
missing values (driver ``train_eval_missing``, which the traffic file
points here), and the probe that ends a run on a program that cannot
run such a job: the twelve numbers of
``check_eval.py`` (``check.py``'s ten on the timed job's trees and train
score, ``valid_score_gap``, ``eval_metric_gap``), every row routed by
``reference_missing.py`` (a finite value by ``x <= threshold``, a NaN by
the node's ``default_left``), the root's and the deep nodes' best gain
searched with the NaN rows tried on either side (``split_shortfall``: a
node whose every gain is rounding noise does not count), and beside them:

``missing_direction_shortfall``  over every internal node of the followed
    trees whose ``missing_type`` is NaN and that at least one row reaches
    with a NaN in the node's column: the reference's gain with those rows
    on the OTHER side less its gain with them where the program's
    ``default_left`` put them, relative to the latter; the widest. A node
    whose other direction would break ``min_data_in_leaf`` /
    ``min_sum_hessian_in_leaf`` or empty a child does not count (that
    split was not on offer). Zero or below: no node had a better
    direction than the one recorded; a direction equal in gain is no
    fault. A tree grown with the NaN rows always sent one way, routed
    against its own record, or scored with the directions swapped reads
    above the limit here or in the numbers beside it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import check as C
from . import reference as R
from . import reference_missing as RM
from .check_eval import EVAL_NUMBERS, _widen, judge as _judge

NUMBERS = C.NUMBERS + EVAL_NUMBERS + ("missing_direction_shortfall",)


def split_shortfall(best, gain, sums):
    """How far a node's split (``gain``, by the reference's ``sums`` (GL,
    HL, GR, HR) of its two sides) is under the reference's best split of
    that node, relative to the best; but a gain is a difference of terms
    G * G / H, and the best is never taken for less than 1,024 float32
    roundings of those terms (2 ** -13 of their sum). With 0.58%
    positives most rows cannot be positive at all, so a tree ends by
    splitting nodes of tens of thousands of negatives at one score, where
    G / H is one constant and every split's gain is a rounding of zero:
    the chip read the program's split at 7.7e-09 and the reference's best
    at 3.05e-05 (ONE rounding of the terms' 294) on a node of 59,617 rows
    without a positive, a ratio of 0.9997 that says nothing, on two of
    the cell's first ten seeds. A node with a split to find (a gain of
    1 or more against terms of a few hundred) reads as it did."""
    gl, hl, gr, hr = (float(v) for v in sums)
    terms = gl * gl / max(hl, 1e-300) + gr * gr / max(hr, 1e-300)
    return (best - gain) / max(best, 2.0 ** -13 * terms, 1e-300)


def probe(lgb, params, log):
    """Whether the program can run a configuration whose tables have
    missing values (the driver calls this before the tables are made): on
    a tiny table whose label is "column 0 is NaN", one tree has to split
    on that column with ``missing_type`` NaN, send the NaN rows by the
    node's ``default_left``, and predict the raw NaN rows as it scored
    them in training. A program that bins a NaN as zero, drops the
    direction or routes prediction otherwise would train and report other
    trees than the configuration states, so the run ends here. The
    reference beside this is binary log loss under AUC: a configuration of
    another objective ends here too."""
    if (params["objective"], params["metric"]) != ("binary", "auc"):
        raise ValueError("this check holds a binary job under AUC; the "
                         f"configuration states {params['objective']!r} "
                         f"under {params['metric']!r}")
    rng = np.random.default_rng(35)
    X = rng.standard_normal((400, 3)).astype(np.float32)
    gone = rng.random(400) < 0.4
    X[gone, 0] = np.nan
    y = gone.astype(np.float32)
    p = dict(params, num_leaves=2, min_data_in_leaf=1, max_bin=15,
             min_sum_hessian_in_leaf=1e-3, metric="None")
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=1)
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    pred = np.asarray(bst.predict(X))
    said = {k: root.get(k) for k in ("split_feature", "missing_type",
                                     "default_left", "threshold")}
    log(f"missing-value probe: root {said}")
    apart = pred[gone].min() > pred[~gone].max()
    if root.get("split_feature") != 0 or root.get("missing_type") != "NaN" \
            or not apart:
        raise RuntimeError(
            "this program cannot run a configuration with missing values: "
            f"on a table whose label is 'column 0 is NaN' its tree's root "
            f"is {said} and its prediction "
            f"{'separates' if apart else 'does not separate'} the NaN rows")


def compare(model, prog, tables, ref_cfg, lr, check_cfg, seed,
            operand_dtype, warm=0, log=None, control_dtype=None):
    """All the numbers of one run. ``prog``: what the timed job left:
    ``score`` ``[n]`` and ``valid_score`` ``[nv]`` float32 on the host,
    ``evals`` ``{"auc": [value per round]}``."""
    log = log or (lambda *_: None)
    X, y, _ = tables["train"]
    Xv, yv, _ = tables["valid"]
    trees = [RM.parse_tree(t) for t in model["tree_info"]]
    n = X.shape[0]
    lam = float(ref_cfg["lambda_l2"])
    min_data = float(ref_cfg["min_data_in_leaf"])
    min_hess = float(ref_cfg["min_sum_hessian_in_leaf"])
    follow = C.followed_trees(len(trees), warm,
                              int(check_cfg["rounds_followed"]))
    X_T, Xv_T = R.table_to_device(X), R.table_to_device(Xv)
    yd, yvd = jnp.asarray(y, jnp.float32), jnp.asarray(yv, jnp.float32)
    bias0 = R.init_score(y)
    score = jnp.full((n,), bias0, jnp.float32)
    vscore = jnp.full((Xv.shape[0],), bias0, jnp.float32)
    mode = RM.missing_mode(ref_cfg)
    cands = jnp.asarray(RM.candidate_thresholds(
        X, seed, int(check_cfg["root_candidates"]),
        int(check_cfg["candidate_sample_rows"]), mode))
    split_limits = (jnp.float32(min_data), jnp.float32(min_hess),
                    jnp.float32(lam))
    deep_min_rows = n * float(check_cfg["deep_min_share"])
    block = min(1 << 16, 1 << max(int(n) - 1, 1).bit_length())
    log("reference: tables on device")

    none = jnp.zeros((1,), jnp.float32)    # where no sums are asked for
    out = {name: 0.0 for name in NUMBERS}
    out["leaf_count_mismatch"] = 0
    for key in ("root_split_shortfall", "deep_split_shortfall",
                "missing_direction_shortfall"):
        out[key] = -np.inf
    control = {name: 0.0 for name in C.NUMBERS if name.endswith("_gap")
               and name != "score_gap"}
    metrics = {"program": {}, "reference": {}}
    nodes = {"asked": 0, "on_missing": 0, "default_left": 0, "splits": 0}

    for ti, tree in enumerate(trees):
        I = tree["num_leaves"] - 1
        bias = bias0 if ti == 0 else 0.0
        if I == 0:
            step = jnp.float32(tree["leaf_value"][0] - bias)
            score, vscore = score + step, vscore + step
            continue
        followed = ti in follow
        g, h = R.grad_hess(score, yd, operand_dtype=operand_dtype) \
            if followed else (none, none)
        args = RM.tree_args(tree)
        at, counts, stats = RM.route_tree(X_T, *args, g, h,
                                          with_stats=followed)
        if followed:
            counts, stats = np.asarray(counts), np.asarray(stats)
            ref = C.reference_tree(tree, counts[:, :2], stats[:, :4], lr,
                                   lam, bias)
            out["leaf_count_mismatch"] += int(
                np.sum(ref["leaf_count"] != tree["leaf_count"])
                + np.sum(ref["internal_count"] != tree["internal_count"]))
            _widen(out, tree, ref, bias)
            short = RM.direction_shortfall(tree, counts, stats, min_data,
                                           min_hess, lam)
            out["missing_direction_shortfall"] = max(
                out["missing_direction_shortfall"], float(short.max()))
            nodes["asked"] += int(np.sum(short > -np.inf))
            nodes["splits"] += I
            nodes["on_missing"] += int(np.sum(tree["missing_type"] != 0))
            nodes["default_left"] += int(np.sum(
                (tree["missing_type"] != 0) & tree["default_left"]))
            ones = jnp.ones((n,), jnp.float32)
            for k in [0] + C.deep_nodes(tree, ref["internal_count"], seed,
                                        ti, int(check_cfg["deep_nodes"]),
                                        deep_min_rows):
                w = ones if k == 0 else R.rows_under(
                    at, jnp.asarray(R.subtree_leaves(tree, k)), I)
                best = float(jnp.max(RM.node_best_gain(
                    X_T, cands, g, h, w, *split_limits, block=block,
                    mode=mode)))
                key = "root_split_shortfall" if k == 0 \
                    else "deep_split_shortfall"
                out[key] = max(out[key], split_shortfall(
                    best, float(ref["gain"][k]), stats[k, :4]))
            values = ref["leaf_value"] - bias
            if control_dtype:
                gl, hl = R.grad_hess(score, yd, operand_dtype=control_dtype)
                _, c_l, s_l = RM.route_tree(X_T, *args, gl, hl)
                _widen(control, C.reference_tree(
                    tree, np.asarray(c_l)[:, :2], np.asarray(s_l)[:, :4],
                    lr, lam, bias), ref, bias)
        else:
            values = tree["leaf_value"] - bias
        values = jnp.asarray(values, jnp.float32)
        score = R.add_leaf_values(score, at, values, I)
        at_v, _, _ = RM.route_tree(Xv_T, *args, none, none, with_stats=False)
        vscore = R.add_leaf_values(vscore, at_v, values, I)
        if followed:
            want = [float(RM.auc(vscore, yvd))]
            got = [dict(enumerate(prog["evals"].get("auc", []))).get(ti)]
            metrics["reference"][ti], metrics["program"][ti] = want, got
            gap = np.inf if None in got else C._rel_gap(got, want)
            out["eval_metric_gap"] = max(out["eval_metric_gap"], gap)
            log(f"reference: tree {ti} followed ({I + 1} leaves, "
                f"{int(np.sum(short > -np.inf))} directions asked); auc "
                f"program {got} reference {want}")

    def against(got, want):
        got = np.asarray(got, np.float32).reshape(-1)
        want = np.asarray(want)
        return np.inf if got.shape != want.shape else C._rel_gap(got, want)

    out["score_gap"] = against(prog["score"], score)
    out["valid_score_gap"] = against(prog["valid_score"], vscore)
    for key in ("root_split_shortfall", "deep_split_shortfall",
                "missing_direction_shortfall"):
        # no node to look at is a number not produced, which fails
        out[key] = float(out[key]) if np.isfinite(out[key]) else None
    losses = {"program": float(R.log_loss(
        jnp.asarray(prog["score"], jnp.float32), yd))
        if np.shape(prog["score"]) == (n,) else None,
        "reference": float(R.log_loss(score, yd))}
    out.update(trees=len(trees), followed=follow, metrics=metrics,
               nodes=nodes, log_loss=losses,
               control=control if control_dtype else None)
    return out


def judge(numbers, limits):
    return _judge(numbers, limits, NUMBERS)
