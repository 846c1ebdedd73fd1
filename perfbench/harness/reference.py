"""The plain reference of a boosting round, and what it is compared on.

This file imports nothing of the program and takes nothing that the
program has made except the thing being judged: the trees of the timed
job (``Booster.dump_model()``) and the score vector it left on the
device. From the raw table, the labels and the configuration's stated
arithmetic it recomputes, in straightforward ``jax.numpy`` float32:

- the objective's gradients and hessians at its *own* running score
  (binary log loss, ``sigmoid`` 1, ``boost_from_average``), in the
  operand type the configuration's ``precision`` states (float32), and
  summed in float32;
- for every node of each followed tree, by routing *all* rows through
  the tree on the raw values (``x <= threshold`` in double, as the
  model format defines it): the rows, the gradient sum and the hessian
  sum on each side. From them the leaf's count, weight and output
  ``-lr * G / (H + lambda_l2)`` and the split's gain
  ``GL^2/HL + GR^2/HR - G^2/H``;
- at the root of each followed tree, and at a few of its deep nodes
  drawn from the seed, the best gain over every feature on the
  reference's own candidate thresholds (quantiles of a sample of rows
  drawn from the seed) among the rows that reach the node, which the
  tree's own split there has to reach;
- the score after all the trees of the window, by routing all rows
  through every tree.

It runs on the chip the benchmark was started on, after the program's
state is freed, over the full table held feature-major on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PUT_ROWS = 1 << 20


# ---------------------------------------------------------------------
# the trees, from the model's public JSON
# ---------------------------------------------------------------------

def parse_tree(tree_json, shrinkage_check=None):
    """Arrays of one tree from ``dump_model()['tree_info'][i]``.

    Node ids: internal node ``k`` is ``k``, leaf ``j`` is
    ``num_internal + j``. ``order`` lists internal nodes parents first.
    """
    L = int(tree_json["num_leaves"])
    I = L - 1
    t = {
        "num_leaves": L,
        "feature": np.zeros(I, np.int32), "threshold": np.zeros(I, np.float64),
        "left": np.zeros(I, np.int32), "right": np.zeros(I, np.int32),
        "gain": np.zeros(I, np.float64),
        "internal_count": np.zeros(I, np.int64),
        "internal_weight": np.zeros(I, np.float64),
        "internal_value": np.zeros(I, np.float64),
        "leaf_value": np.zeros(L, np.float64),
        "leaf_weight": np.zeros(L, np.float64),
        "leaf_count": np.zeros(L, np.int64),
        "order": [],
    }
    if L == 1:
        leaf = tree_json["tree_structure"]
        t["leaf_value"][0] = leaf["leaf_value"]
        return t

    def node_id(n):
        return int(n["split_index"]) if "split_index" in n \
            else I + int(n["leaf_index"])

    stack = [tree_json["tree_structure"]]
    while stack:
        n = stack.pop()
        if "split_index" in n:
            k = int(n["split_index"])
            if n["decision_type"] != "<=" \
                    or str(n.get("missing_type", "None")) != "None":
                raise ValueError(
                    "this reference routes numerical '<=' splits without "
                    f"missing values only; node {k} has decision_type "
                    f"{n['decision_type']!r}, missing_type "
                    f"{n.get('missing_type')!r}")
            t["order"].append(k)
            t["feature"][k] = n["split_feature"]
            t["threshold"][k] = n["threshold"]
            t["gain"][k] = n["split_gain"]
            t["internal_count"][k] = n["internal_count"]
            t["internal_weight"][k] = n["internal_weight"]
            t["internal_value"][k] = n["internal_value"]
            t["left"][k] = node_id(n["left_child"])
            t["right"][k] = node_id(n["right_child"])
            stack.append(n["right_child"])
            stack.append(n["left_child"])
        else:
            j = int(n["leaf_index"])
            t["leaf_value"][j] = n["leaf_value"]
            t["leaf_weight"][j] = n["leaf_weight"]
            t["leaf_count"][j] = n["leaf_count"]
    t["order"] = np.asarray(t["order"], np.int32)
    return t


def threshold_f32(thr64):
    """The largest float32 that is ``<=`` each double threshold: for a
    float32 value ``x``, ``x <= thr`` in double holds exactly when
    ``x <= threshold_f32(thr)`` in float32."""
    t32 = thr64.astype(np.float32)
    over = t32.astype(np.float64) > thr64
    return np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32) \
        .astype(np.float32)


# ---------------------------------------------------------------------
# the table on the device, feature-major
# ---------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(buf, chunk, at):
    return lax.dynamic_update_slice(buf, chunk.T, (0, at))


def table_to_device(X):
    """``[F, n]`` float32 on the device from the host's ``[n, F]``."""
    n, F = X.shape
    buf = jnp.zeros((F, n), jnp.float32)
    for a in range(0, n, PUT_ROWS):
        buf = _put_rows(buf, jnp.asarray(X[a:a + PUT_ROWS]), a)
    return buf


# ---------------------------------------------------------------------
# the objective as the configuration states it
# ---------------------------------------------------------------------

def init_score(y):
    """``boost_from_average`` of the binary objective, sigmoid 1."""
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


@functools.partial(jax.jit, static_argnames="operand_dtype")
def grad_hess(score, y, operand_dtype="float32"):
    """Binary log loss at ``score`` (labels in {0,1}, sigmoid 1):
    ``response = -l / (1 + exp(l * score))`` with ``l`` = +-1,
    ``g = response``, ``h = |response| (1 - |response|)``; both rounded
    to ``operand_dtype`` and returned as float32."""
    lab = 2.0 * y - 1.0
    response = -lab / (1.0 + jnp.exp(lab * score))
    g = response
    a = jnp.abs(response)
    h = a * (1.0 - a)
    return _as_operand(g, operand_dtype), _as_operand(h, operand_dtype)


def _as_operand(v, dtype):
    if dtype == "float32":
        return v
    if dtype == "bfloat16":
        # not astype there and back: XLA may keep the excess precision
        return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"unknown operand dtype {dtype!r}")


@jax.jit
def log_loss(score, y):
    lab = 2.0 * y - 1.0
    return jnp.mean(jnp.logaddexp(0.0, -lab * score))


# ---------------------------------------------------------------------
# routing all rows through one tree
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="with_stats")
def route_tree(X_T, feature, thr32, left, right, order, g, h,
               with_stats=True):
    """Row -> node id after the tree, and per internal node the rows
    ``[cntL, cntR]`` (int32, exact) and the sums ``[GL, HL, GR, HR]``
    over the rows that reach it."""
    n = X_T.shape[1]
    I = feature.shape[0]

    def body(i, carry):
        at, counts, stats = carry
        k = order[i]
        x = lax.dynamic_index_in_dim(X_T, feature[k], 0, keepdims=False)
        here = at == k
        go_l = here & (x <= thr32[k])
        go_r = here & ~(x <= thr32[k])
        if with_stats:
            counts = counts.at[k].set(jnp.stack([
                jnp.sum(go_l, dtype=jnp.int32),
                jnp.sum(go_r, dtype=jnp.int32)]))
            stats = stats.at[k].set(jnp.stack([
                jnp.sum(jnp.where(go_l, g, 0.0)),
                jnp.sum(jnp.where(go_l, h, 0.0)),
                jnp.sum(jnp.where(go_r, g, 0.0)),
                jnp.sum(jnp.where(go_r, h, 0.0))]))
        at = jnp.where(go_l, left[k], jnp.where(go_r, right[k], at))
        return at, counts, stats

    at0 = jnp.zeros((n,), jnp.int32)
    counts0 = jnp.zeros((I, 2), jnp.int32)
    stats0 = jnp.zeros((I, 4), jnp.float32)
    return lax.fori_loop(0, I, body, (at0, counts0, stats0))


@jax.jit
def add_leaf_values(score, at, leaf_values, num_internal):
    return score + leaf_values[at - num_internal]


# ---------------------------------------------------------------------
# the best split at a node, on the reference's own candidates
# ---------------------------------------------------------------------

def candidate_thresholds(X, seed, k, sample_rows):
    """``[F, k]`` float32: per feature up to ``k`` distinct values of a
    row sample drawn from the seed, at evenly spaced ranks (``x <= c``
    is the split); unused slots hold ``+inf``."""
    n, F = X.shape
    rng = np.random.default_rng([int(seed), 0x5EED])
    rows = np.sort(rng.choice(n, size=min(sample_rows, n), replace=False))
    S = X[rows]
    out = np.full((F, k), np.inf, np.float32)
    q = (np.arange(1, k + 1) / (k + 1.0))
    for f in range(F):
        c = np.unique(np.quantile(S[:, f], q, method="lower"))
        out[f, :len(c)] = c
    return out


def subtree_leaves(tree, k):
    """``[num_leaves]`` bool: the leaves under internal node ``k``."""
    I = tree["num_leaves"] - 1
    under, stack = np.zeros(tree["num_leaves"], bool), [int(k)]
    while stack:
        node = stack.pop()
        if node >= I:
            under[node - I] = True
        else:
            stack += [int(tree["left"][node]), int(tree["right"][node])]
    return under


def node_depths(tree):
    """Depth of every internal node, the root at 0."""
    depth = np.zeros(max(tree["num_leaves"] - 1, 0), np.int32)
    for k in tree["order"]:          # parents first
        for child in (tree["left"][k], tree["right"][k]):
            if child < len(depth):
                depth[child] = depth[k] + 1
    return depth


@jax.jit
def rows_under(at, under, num_internal):
    """1.0 for the rows whose leaf (``at``, after the tree) is ``under``
    the node, else 0.0."""
    return under[at - num_internal].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block",))
def node_best_gain(X_T, cands, g, h, w, min_data, min_hess, lam,
                   block=1 << 16):
    """Best ``GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)`` over every
    feature and candidate, over the rows with ``w`` = 1 (all ones at the
    root); per feature ``[F]``."""
    F, n = X_T.shape
    pad = (-n) % block
    g, h = g * w, h * w
    wp = jnp.pad(w, (0, pad)).reshape(-1, block)
    gp = jnp.pad(g, (0, pad)).reshape(-1, block)
    hp = jnp.pad(h, (0, pad)).reshape(-1, block)
    N, G, H = jnp.sum(w), jnp.sum(g), jnp.sum(h)

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
        cl, gl, hl = acc[:, 0], acc[:, 1], acc[:, 2]
        cr, gr, hr = N - cl, G - gl, H - hl
        ok = (cl >= min_data) & (cr >= min_data) \
            & (hl >= min_hess) & (hr >= min_hess)
        gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) \
            - G * G / (H + lam)
        return jnp.max(jnp.where(ok, gain, -jnp.inf))

    return lax.map(one_feature, (X_T, cands))
