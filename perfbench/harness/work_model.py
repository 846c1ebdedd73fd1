"""The work a boosting round *needs*, from shapes and the trained trees.

It counts what the algorithm requires whatever implements it, so a PR
that changes the method cannot change the yardstick:

- histogram rows of a tree = all ``n`` rows at the root, plus, for every
  split, the rows of the smaller child (the larger child's histogram is
  the parent's minus the smaller's — the subtraction trick is the
  algorithm's);
- per histogram row and feature two additions (gradient, hessian) and
  one byte of bin read; per row 8 bytes of gradient and hessian;
- the whole round adds 24 bytes per row: gradients and hessians written,
  the score read and written, the label read, the row's leaf written.

The least time is the larger of operations over peak FLOP/s and bytes
over peak bytes/s; it can never exceed the time any implementation takes.
"""


def hist_rows(n, left_counts, right_counts):
    """Rows whose bins a tree's histograms must read."""
    return int(n) + sum(min(int(l), int(r))
                        for l, r in zip(left_counts, right_counts))


def tree_hist_rows(tree_json, n):
    """``hist_rows`` of one tree of ``Booster.dump_model()['tree_info']``,
    from its nodes' counts."""
    lefts, rights, stack = [], [], [tree_json["tree_structure"]]
    while stack:
        node = stack.pop()
        if "split_index" in node:
            kids = (node["left_child"], node["right_child"])
            left, right = (k["internal_count"] if "split_index" in k
                           else k["leaf_count"] for k in kids)
            lefts.append(left)
            rights.append(right)
            stack.extend(kids)
    return hist_rows(n, lefts, rights)


def round_work(n, rows, n_features):
    """One round: ``rows`` histogram rows of ``n_features`` bins, and the
    per-row pass over all ``n`` rows."""
    return {"ops": rows * n_features * 2,
            "bytes": rows * (n_features * 1 + 8) + n * 24}


def least_seconds(work, peaks):
    by_ops = work["ops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    return max(by_ops, by_bytes), ("flops" if by_ops >= by_bytes
                                   else "bytes")
