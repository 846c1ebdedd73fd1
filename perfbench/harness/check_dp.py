"""``check.compare`` for a table spread over several chips.

The same comparison (``check.py``: the same numbers, the same followed
trees and deep nodes, the same gaps, judged by the same ``judge``),
with the reference computed in row blocks, one a device
(``reference_dp.py``). What differs from ``check.compare`` is only
where a sum over all rows is taken: there in one reduction on one chip,
here a reduction a block, added on the host.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import check as C
from . import reference as R
from . import reference_dp as RD


def compare(model, prog_score, X, y, ref_cfg, lr, check_cfg, seed,
            operand_dtype, devices, warm=0, log=None, control_dtype=None):
    """``check.compare``'s numbers; ``devices`` are the chips the row
    blocks go to, in rank order."""
    log = log or (lambda *_: None)
    trees = [R.parse_tree(t) for t in model["tree_info"]]
    n = X.shape[0]
    lam = float(ref_cfg["lambda_l2"])
    follow = C.followed_trees(len(trees), warm,
                              int(check_cfg["rounds_followed"]))
    X_T = RD.table_to_devices(X, devices)
    yd = RD.rows_to_devices(y, devices)
    bias0 = R.init_score(y)
    score = [jnp.full_like(b, bias0) for b in yd]
    cands = jnp.asarray(R.candidate_thresholds(
        X, seed, int(check_cfg["root_candidates"]),
        int(check_cfg["candidate_sample_rows"])))
    split_limits = (float(ref_cfg["min_data_in_leaf"]),
                    float(ref_cfg["min_sum_hessian_in_leaf"]), lam)
    deep_min_rows = n * float(check_cfg["deep_min_share"])
    log(f"reference: table on {len(devices)} device(s)")

    out = {name: 0.0 for name in C.NUMBERS}
    out["leaf_count_mismatch"] = 0
    out["root_split_shortfall"] = out["deep_split_shortfall"] = -np.inf
    control = {name: 0.0 for name in C.NUMBERS if name.endswith("_gap")
               and name != "score_gap"}

    def widen(into, got, want, bias):
        for key, field in C.GAPS:
            off = bias if field == "leaf_value" else 0.0
            a, b = got[field] - off, want[field] - off
            into[key + "_gap"] = max(into[key + "_gap"], C._rel_gap(a, b))
            into[key + "_median_gap"] = max(into[key + "_median_gap"],
                                            C._median_gap(a, b))

    def routed(tree, g, h, with_stats):
        """Every block through the tree: the blocks' ``at`` and the
        added-up counts and sums."""
        args = C.tree_args(tree)
        parts = RD.each(lambda x, gb, hb: R.route_tree(
            x, *args, gb, hb, with_stats=with_stats), X_T, g, h)
        at = [p[0] for p in parts]
        if not with_stats:
            return at, None, None
        return (at, RD.add_up([p[1] for p in parts], np.int64),
                RD.add_up([p[2] for p in parts]))

    for ti, tree in enumerate(trees):
        I = tree["num_leaves"] - 1
        if I == 0:
            off = jnp.float32(tree["leaf_value"][0]
                              - (bias0 if ti == 0 else 0.0))
            score = [s + off for s in score]
            continue
        followed = ti in follow
        bias = bias0 if ti == 0 else 0.0
        if followed:
            gh = RD.each(lambda s, yb: R.grad_hess(
                s, yb, operand_dtype=operand_dtype), score, yd)
            g, h = [p[0] for p in gh], [p[1] for p in gh]
        else:
            g = h = [jnp.zeros((1,), jnp.float32)] * len(devices)
        at, counts, stats = routed(tree, g, h, followed)
        if followed:
            ref = C.reference_tree(tree, counts, stats, lr, lam, bias)
            out["leaf_count_mismatch"] += int(
                np.sum(ref["leaf_count"] != tree["leaf_count"])
                + np.sum(ref["internal_count"] != tree["internal_count"]))
            widen(out, tree, ref, bias)
            for k in [0] + C.deep_nodes(tree, ref["internal_count"], seed,
                                        ti, int(check_cfg["deep_nodes"]),
                                        deep_min_rows):
                under = jnp.asarray(R.subtree_leaves(tree, k))
                w = [jnp.ones_like(gb) if k == 0
                     else R.rows_under(a, under, I)
                     for a, gb in zip(at, g)]
                sums = RD.each(lambda x, gb, hb, wb: RD.node_candidate_sums(
                    x, cands, gb, hb, wb), X_T, g, h, w)
                best = RD.best_gain(RD.add_up([s[0] for s in sums]),
                                    RD.add_up([s[1] for s in sums]),
                                    *split_limits)
                key = "root_split_shortfall" if k == 0 \
                    else "deep_split_shortfall"
                out[key] = max(out[key], (best - float(ref["gain"][k]))
                               / max(best, 1e-300))
            values = ref["leaf_value"] - bias
            if control_dtype:
                ghl = RD.each(lambda s, yb: R.grad_hess(
                    s, yb, operand_dtype=control_dtype), score, yd)
                _, c_l, s_l = routed(tree, [p[0] for p in ghl],
                                     [p[1] for p in ghl], True)
                widen(control, C.reference_tree(tree, c_l, s_l, lr, lam,
                                                bias), ref, bias)
            log(f"reference: tree {ti} followed")
        else:
            values = tree["leaf_value"] - bias
        vals = jnp.asarray(values, jnp.float32)
        score = RD.each(lambda s, a: R.add_leaf_values(s, a, vals, I),
                        score, at)
    prog_score = np.asarray(prog_score, np.float32).reshape(-1)
    ref_score = np.concatenate([np.asarray(s) for s in score])
    sizes = np.asarray([s.shape[0] for s in score], np.float64)

    def loss(blocks):
        parts = RD.each(R.log_loss, blocks, yd)
        return float(np.dot(np.asarray(parts, np.float64), sizes) / n)

    same = prog_score.shape == ref_score.shape
    losses = {"program": loss(RD.rows_to_devices(prog_score, devices))
              if same else None,
              "reference": loss(score),
              "at_start": loss([jnp.full_like(s, bias0) for s in score])}
    out["score_gap"] = C._rel_gap(prog_score, ref_score) if same else np.inf
    for key in ("root_split_shortfall", "deep_split_shortfall"):
        out[key] = float(out[key]) if np.isfinite(out[key]) else None
    out.update(trees=len(trees), followed=follow, log_loss=losses,
               control=control if control_dtype else None)
    return out
