"""The comparison that decides ``correct`` for a job with a validation
set (driver ``train_eval``): ``check.py``'s ten numbers on the timed
job's trees and train score, with the gradients of the configuration's
objective (binary log loss, or lambdarank from ``reference_rank.py``),
and beside them:

``valid_score_gap``  widest gap between the validation score the program
    left on the device and the reference's: every tree of the job routed
    over the raw validation table by ``x <= threshold``, the followed
    trees with the reference's own leaf values;
``eval_metric_gap``  widest gap, over the followed rounds and the
    metric's values (one for AUC, one per ``eval_at`` for NDCG), between
    what the program reported that round (``record_evaluation``) and the
    reference's metric on its own validation score after that tree;
``grad_gap`` / ``grad_median_gap``  (ranking only) the program's
    gradients and hessians after the window, taken at the program's own
    score, against the reference's lambdarank at the same score: the
    widest and the median row, against the reference's value or its
    median, whichever is larger. This one isolates the pairwise pass
    from the trees. Two documents whose scores all but tie can swap
    ranks between the two sides, which moves one pair's two rows and
    shifts the others' discounts by one rank: the widest row reads that,
    the median does not.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import check as C
from . import reference as R
from . import reference_rank as RR

EVAL_NUMBERS = ("valid_score_gap", "eval_metric_gap")
GRAD_NUMBERS = ("grad_gap", "grad_median_gap")


def numbers_of(ref_cfg):
    ranking = ref_cfg["objective"] == "lambdarank"
    return C.NUMBERS + EVAL_NUMBERS + (GRAD_NUMBERS if ranking else ())


class Objective:
    """The configuration's objective as the reference computes it:
    where the score starts, the gradients at a score, the metric's
    values on a table, and a loss for the log."""

    def __init__(self, ref_cfg, tables):
        self.kind = ref_cfg["objective"]
        if self.kind not in ("binary", "lambdarank"):
            raise ValueError(f"no reference for objective {self.kind!r}")
        self.cfg = ref_cfg
        self.layout = {}
        if self.kind == "lambdarank":
            self.layout = {w: RR.query_layout(t[2])
                           for w, t in tables.items()}
        y = tables["train"][1]
        self.bias0 = R.init_score(y) if self.kind == "binary" else 0.0
        self.metric_names = ["auc"] if self.kind == "binary" \
            else [f"ndcg@{k}" for k in ref_cfg["eval_at"]]

    def grad_hess(self, score, y, operand_dtype):
        if self.kind == "binary":
            return R.grad_hess(score, y, operand_dtype=operand_dtype)
        return RR.lambdarank_grad_hess(
            score, y, self.layout["train"], self.cfg["sigmoid"],
            self.cfg["lambdarank_truncation_level"],
            self.cfg["lambdarank_norm"], operand_dtype)

    def metric(self, score, y, which):
        if self.kind == "binary":
            return [float(RR.auc(score, y))]
        return RR.ndcg_at(score, y, self.layout[which], self.cfg["eval_at"])


def _widen(into, got, want, bias):
    for key, field in C.GAPS:
        off = bias if field == "leaf_value" else 0.0
        a, b = got[field] - off, want[field] - off
        into[key + "_gap"] = max(into[key + "_gap"], C._rel_gap(a, b))
        into[key + "_median_gap"] = max(into[key + "_median_gap"],
                                        C._median_gap(a, b))


def compare(model, prog, tables, ref_cfg, lr, check_cfg, seed,
            operand_dtype, warm=0, log=None, control_dtype=None):
    """All the numbers of one run. ``prog``: what the timed job left:
    ``score`` ``[n]`` and ``valid_score`` ``[nv]`` float32 on the host,
    ``evals`` ``{metric name: [value per round]}``, ``grad`` the
    program's ``(g, h)`` at ``score`` (ranking) or ``None``."""
    log = log or (lambda *_: None)
    X, y, _ = tables["train"]
    Xv, yv, _ = tables["valid"]
    obj = Objective(ref_cfg, tables)
    trees = [R.parse_tree(t) for t in model["tree_info"]]
    n = X.shape[0]
    lam = float(ref_cfg["lambda_l2"])
    follow = C.followed_trees(len(trees), warm,
                              int(check_cfg["rounds_followed"]))
    X_T, Xv_T = R.table_to_device(X), R.table_to_device(Xv)
    yd, yvd = jnp.asarray(y, jnp.float32), jnp.asarray(yv, jnp.float32)
    score = jnp.full((n,), obj.bias0, jnp.float32)
    vscore = jnp.full((Xv.shape[0],), obj.bias0, jnp.float32)
    cands = jnp.asarray(R.candidate_thresholds(
        X, seed, int(check_cfg["root_candidates"]),
        int(check_cfg["candidate_sample_rows"])))
    split_limits = (jnp.float32(ref_cfg["min_data_in_leaf"]),
                    jnp.float32(ref_cfg["min_sum_hessian_in_leaf"]),
                    jnp.float32(lam))
    deep_min_rows = n * float(check_cfg["deep_min_share"])
    log("reference: tables on device")

    none = jnp.zeros((1,), jnp.float32)    # where no sums are asked for
    out = {name: 0.0 for name in numbers_of(ref_cfg)}
    out["leaf_count_mismatch"] = 0
    out["root_split_shortfall"] = out["deep_split_shortfall"] = -np.inf
    control = {name: 0.0 for name in C.NUMBERS if name.endswith("_gap")
               and name != "score_gap"}
    metrics = {"program": {}, "reference": {}}

    for ti, tree in enumerate(trees):
        I = tree["num_leaves"] - 1
        bias = obj.bias0 if ti == 0 else 0.0
        if I == 0:
            step = jnp.float32(tree["leaf_value"][0] - bias)
            score, vscore = score + step, vscore + step
            continue
        followed = ti in follow
        g, h = obj.grad_hess(score, yd, operand_dtype) if followed \
            else (none, none)
        args = C.tree_args(tree)
        at, counts, stats = R.route_tree(X_T, *args, g, h,
                                         with_stats=followed)
        if followed:
            ref = C.reference_tree(tree, counts, stats, lr, lam, bias)
            out["leaf_count_mismatch"] += int(
                np.sum(ref["leaf_count"] != tree["leaf_count"])
                + np.sum(ref["internal_count"] != tree["internal_count"]))
            _widen(out, tree, ref, bias)
            ones = jnp.ones((n,), jnp.float32)
            for k in [0] + C.deep_nodes(tree, ref["internal_count"], seed,
                                        ti, int(check_cfg["deep_nodes"]),
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
                gl, hl = obj.grad_hess(score, yd, control_dtype)
                _, c_l, s_l = R.route_tree(X_T, *args, gl, hl)
                _widen(control,
                       C.reference_tree(tree, c_l, s_l, lr, lam, bias),
                       ref, bias)
        else:
            values = tree["leaf_value"] - bias
        values = jnp.asarray(values, jnp.float32)
        score = R.add_leaf_values(score, at, values, I)
        at_v, _, _ = R.route_tree(Xv_T, *args, none, none, with_stats=False)
        vscore = R.add_leaf_values(vscore, at_v, values, I)
        if followed:
            want = obj.metric(vscore, yvd, "valid")
            got = [dict(enumerate(prog["evals"].get(name, []))).get(ti)
                   for name in obj.metric_names]
            metrics["reference"][ti], metrics["program"][ti] = want, got
            gap = np.inf if None in got else C._rel_gap(got, want)
            out["eval_metric_gap"] = max(out["eval_metric_gap"], gap)
            log(f"reference: tree {ti} followed; {obj.metric_names} "
                f"program {got} reference {want}")

    def against(got, want):
        got = np.asarray(got, np.float32).reshape(-1)
        want = np.asarray(want)
        return np.inf if got.shape != want.shape else C._rel_gap(got, want)

    out["score_gap"] = against(prog["score"], score)
    out["valid_score_gap"] = against(prog["valid_score"], vscore)
    if obj.kind == "lambdarank":
        gaps = np.zeros((0,))
        if prog["grad"] is not None \
                and np.shape(prog["score"]) == (n,):
            ref_gh = obj.grad_hess(jnp.asarray(prog["score"], jnp.float32),
                                   yd, operand_dtype)
            gaps = np.concatenate([C._gaps(np.asarray(p).reshape(-1), r)
                                   for p, r in zip(prog["grad"], ref_gh)])
        out["grad_gap"] = float(np.max(gaps)) if gaps.size else np.inf
        out["grad_median_gap"] = float(np.median(gaps)) if gaps.size \
            else np.inf
    for key in ("root_split_shortfall", "deep_split_shortfall"):
        # no node to look at is a number not produced, which fails
        out[key] = float(out[key]) if np.isfinite(out[key]) else None
    # the loss, for the log and the control tools (``readings.py``)
    losses = None
    if obj.kind == "binary":
        losses = {"program": float(R.log_loss(
            jnp.asarray(prog["score"], jnp.float32), yd))
            if np.shape(prog["score"]) == (n,) else None,
            "reference": float(R.log_loss(score, yd))}
    out.update(trees=len(trees), followed=follow, metrics=metrics,
               log_loss=losses, control=control if control_dtype else None)
    return out


def judge(numbers, limits, names):
    """``check.judge`` over ``names``: every one needs an entry in the
    limits (``None``: not compared); a missing or non-finite value of a
    compared number fails."""
    table, ok = {}, True
    for name in names:
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        v, lim = numbers.get(name), limits[name]
        if lim is not None:
            lim = float(lim)
            ok = ok and bool(v is not None and np.isfinite(v) and v <= lim)
        table[name] = {"value": v, "limit": lim}
    return ok, table
