"""Host-side tree model + LightGBM-compatible text serialization.

Re-design of the reference Tree (/root/reference/include/LightGBM/tree.h,
src/io/tree.cpp) and the model text format
(src/boosting/gbdt_model_text.cpp:410 SaveModelToString / :421
LoadModelFromString). Trees are plain numpy arrays on the host; for batch
prediction a whole forest is stacked into a few device tensors
(ops/predict.py StackedTrees).

decision_type byte layout (tree.h kCategoricalMask/kDefaultLeftMask):
  bit0 = categorical split, bit1 = default_left, bits2-3 = missing_type
  (0 = none, 1 = zero, 2 = nan).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ..ops.binning import BinMapper, BinType, MissingType

__all__ = ["Tree", "tree_from_arrays"]

_MISSING_CODE = {MissingType.NONE: 0, MissingType.ZERO: 1, MissingType.NAN: 2}
_MISSING_NAME = {0: MissingType.NONE, 1: MissingType.ZERO, 2: MissingType.NAN}

CAT_MASK = 1
DEFAULT_LEFT_MASK = 2


@dataclasses.dataclass
class Tree:
    num_leaves: int
    split_feature: np.ndarray       # [L-1] i32
    split_gain: np.ndarray          # [L-1] f32
    threshold: np.ndarray           # [L-1] f64 (real-valued)
    threshold_bin: np.ndarray       # [L-1] i32 (bin-space; -1 if unknown)
    decision_type: np.ndarray       # [L-1] u8
    left_child: np.ndarray          # [L-1] i32
    right_child: np.ndarray         # [L-1] i32
    leaf_value: np.ndarray          # [L] f64
    leaf_weight: np.ndarray         # [L] f64
    leaf_count: np.ndarray          # [L] i64
    internal_value: np.ndarray      # [L-1] f64
    internal_weight: np.ndarray     # [L-1] f64
    internal_count: np.ndarray      # [L-1] i64
    shrinkage: float = 1.0
    # categorical splits: threshold_bin indexes into cat_threshold via
    # cat_boundaries (bitset spans), like tree.h cat_boundaries_
    num_cat: int = 0
    cat_boundaries: Optional[np.ndarray] = None
    cat_threshold: Optional[np.ndarray] = None
    # linear leaves (tree.h leaf_const_/leaf_coeff_/leaf_features_)
    is_linear: bool = False
    leaf_const: Optional[np.ndarray] = None      # [L] f64
    leaf_features: Optional[list] = None         # per-leaf real feature ids
    leaf_coeff: Optional[list] = None            # per-leaf coefficients

    @property
    def num_nodes(self) -> int:
        return max(self.num_leaves - 1, 0)

    def is_categorical_node(self, i: int) -> bool:
        return bool(self.decision_type[i] & CAT_MASK)

    def default_left(self, i: int) -> bool:
        return bool(self.decision_type[i] & DEFAULT_LEFT_MASK)

    def missing_type(self, i: int) -> int:
        return (int(self.decision_type[i]) >> 2) & 3

    def apply_shrinkage(self, rate: float) -> None:
        """Tree::Shrinkage (tree.h:188; scales linear leaves too,
        tree.h:192-206)."""
        self.leaf_value = self.leaf_value * rate
        self.internal_value = self.internal_value * rate
        self.shrinkage *= rate
        if self.is_linear and self.leaf_const is not None:
            self.leaf_const = self.leaf_const * rate
            self.leaf_coeff = [[c * rate for c in cs]
                               for cs in (self.leaf_coeff or [])]

    def num_leaves_actual(self) -> int:
        return self.num_leaves

    # -- single-row host predict (reference: tree.h:134) ------------------
    def predict_row(self, x: np.ndarray) -> float:
        leaf = self.predict_leaf_row(x)
        if self.is_linear and self.leaf_const is not None:
            out = float(self.leaf_const[leaf])
            feats = self.leaf_features[leaf] if self.leaf_features else []
            for f, c in zip(feats, self.leaf_coeff[leaf]):
                v = x[f]
                if np.isnan(v):
                    return float(self.leaf_value[leaf])
                out += c * v
            return out
        return float(self.leaf_value[leaf])

    def predict_leaf_row(self, x: np.ndarray) -> int:
        if self.num_leaves == 1:
            return 0
        node = 0
        while node >= 0:
            f = self.split_feature[node]
            v = x[f]
            if self.is_categorical_node(node):
                go_left = self._cat_decision(node, v)
            else:
                go_left = self._num_decision(node, v)
            node = self.left_child[node] if go_left else self.right_child[node]
        return ~node

    def _num_decision(self, node: int, v: float) -> bool:
        mt = self.missing_type(node)
        if np.isnan(v) and mt != 2:
            v = 0.0
        if mt == 2 and np.isnan(v):
            return self.default_left(node)
        if mt == 1 and (abs(v) <= 1e-35):
            return self.default_left(node)
        return v <= self.threshold[node]

    def _cat_decision(self, node: int, v: float) -> bool:
        # NaN routes right here but maps to bin 0 (the most frequent
        # category) during binned training/scoring — this asymmetry is
        # reference semantics, not a bug (tree.h:374-383 CategoricalDecision
        # vs bin.h:612 ValueToBin's `isnan -> return 0` for categoricals).
        if np.isnan(v) or v < 0:
            return False
        iv = int(v)
        cat_idx = int(self.threshold[node])
        lo = self.cat_boundaries[cat_idx]
        hi = self.cat_boundaries[cat_idx + 1]
        word = iv // 32
        if word >= hi - lo:
            return False
        return bool((int(self.cat_threshold[lo + word]) >> (iv % 32)) & 1)

    # -- text format ------------------------------------------------------
    def to_string(self, index: int) -> str:
        def fmt(arr, f):
            return " ".join(f % x for x in arr)

        L = self.num_leaves
        lines = [f"Tree={index}", f"num_leaves={L}",
                 f"num_cat={self.num_cat}"]
        if L > 1:
            lines += [
                "split_feature=" + fmt(self.split_feature, "%d"),
                "split_gain=" + fmt(self.split_gain, "%g"),
                "threshold=" + fmt(self.threshold, "%.17g"),
                "decision_type=" + fmt(self.decision_type, "%d"),
                "left_child=" + fmt(self.left_child, "%d"),
                "right_child=" + fmt(self.right_child, "%d"),
                "leaf_value=" + fmt(self.leaf_value, "%.17g"),
                "leaf_weight=" + fmt(self.leaf_weight, "%g"),
                "leaf_count=" + fmt(self.leaf_count, "%d"),
                "internal_value=" + fmt(self.internal_value, "%g"),
                "internal_weight=" + fmt(self.internal_weight, "%g"),
                "internal_count=" + fmt(self.internal_count, "%d"),
            ]
            if self.num_cat > 0:
                lines += [
                    "cat_boundaries=" + fmt(self.cat_boundaries, "%d"),
                    "cat_threshold=" + fmt(self.cat_threshold, "%d"),
                ]
        else:
            lines += ["leaf_value=" + fmt(self.leaf_value[:1], "%.17g")]
        lines += [f"is_linear={int(self.is_linear)}"]
        if self.is_linear and self.leaf_const is not None:
            L = self.num_leaves
            nf = [len(self.leaf_features[i]) if self.leaf_features else 0
                  for i in range(L)]
            lines += ["leaf_const=" + fmt(self.leaf_const[:L], "%.17g"),
                      "num_features=" + fmt(nf, "%d")]
            feat_toks, coef_toks = [], []
            for i in range(L):
                if nf[i]:
                    feat_toks += ["%d" % f for f in self.leaf_features[i]]
                    coef_toks += ["%.17g" % c for c in self.leaf_coeff[i]]
            lines += ["leaf_features=" + " ".join(feat_toks),
                      "leaf_coeff=" + " ".join(coef_toks)]
        lines += [f"shrinkage={self.shrinkage:g}"]
        return "\n".join(lines) + "\n\n"

    @classmethod
    def from_lines(cls, kv: Dict[str, str]) -> "Tree":
        L = int(kv["num_leaves"])
        num_cat = int(kv.get("num_cat", "0"))

        def arr(key, dtype, size, default=0):
            if key not in kv or size == 0:
                return np.full(size, default, dtype)
            vals = kv[key].split()
            return np.asarray(vals, dtype=dtype)

        n_nodes = max(L - 1, 0)
        t = cls(
            num_leaves=L,
            split_feature=arr("split_feature", np.int32, n_nodes),
            split_gain=arr("split_gain", np.float64, n_nodes),
            threshold=arr("threshold", np.float64, n_nodes),
            threshold_bin=np.full(n_nodes, -1, np.int32),
            decision_type=arr("decision_type", np.uint8, n_nodes),
            left_child=arr("left_child", np.int32, n_nodes),
            right_child=arr("right_child", np.int32, n_nodes),
            leaf_value=arr("leaf_value", np.float64, L),
            leaf_weight=arr("leaf_weight", np.float64, L),
            leaf_count=arr("leaf_count", np.int64, L),
            internal_value=arr("internal_value", np.float64, n_nodes),
            internal_weight=arr("internal_weight", np.float64, n_nodes),
            internal_count=arr("internal_count", np.int64, n_nodes),
            num_cat=num_cat,
            shrinkage=float(kv.get("shrinkage", "1")),
            is_linear=bool(int(kv.get("is_linear", "0"))),
        )
        # the batched predictor sweeps nodes in index order and relies
        # on internal children having LARGER indices than their parent
        # (ops/predict.py _traverse; Tree::Split numbering guarantees
        # this for every model LightGBM or this package writes) —
        # reject third-party model strings that violate it rather than
        # silently mispredicting
        for i in range(n_nodes):
            for c in (int(t.left_child[i]), int(t.right_child[i])):
                if 0 <= c <= i:
                    raise ValueError(
                        f"model tree node {i} has internal child {c} "
                        "<= its own index; node numbering must be "
                        "topological (parent before child)")
        if num_cat > 0:
            t.cat_boundaries = np.asarray(kv["cat_boundaries"].split(),
                                          np.int64)
            t.cat_threshold = np.asarray(kv["cat_threshold"].split(),
                                         np.uint32)
        if t.is_linear and "leaf_const" in kv:
            t.leaf_const = np.asarray(kv["leaf_const"].split(), np.float64)
            nf = np.asarray(kv.get("num_features", "").split() or [0] * L,
                            np.int64)
            feat_toks = kv.get("leaf_features", "").split()
            coef_toks = kv.get("leaf_coeff", "").split()
            t.leaf_features, t.leaf_coeff = [], []
            pos = 0
            for i in range(L):
                k = int(nf[i]) if i < len(nf) else 0
                t.leaf_features.append(
                    [int(v) for v in feat_toks[pos: pos + k]])
                t.leaf_coeff.append(
                    [float(v) for v in coef_toks[pos: pos + k]])
                pos += k
        return t


#: an int32 crosses in the f32 vector as two exact halves (the row
#: counts of a mesh pass float32's 2**24): value = hi * _LO + lo
_LO = 4096


@jax.jit
def pack_tree_device(t):
    """Everything except the categorical bitmask as ONE f32 vector: a
    tree crosses device->host in two transfers instead of one per
    field. An int32 field goes as two pieces (``>> 12``, ``& 4095``),
    each exact in float32 whatever the value."""
    import jax.numpy as jnp
    parts = []
    for f in t._fields:
        if f == "split_cat_mask":
            continue
        p = jnp.ravel(getattr(t, f))
        if p.dtype == jnp.int32:
            parts += [p >> 12, p & (_LO - 1)]
        else:
            parts.append(p)
    vec = jnp.concatenate([p.astype(jnp.float32) for p in parts])
    return vec, t.split_cat_mask


def unpack_tree_host(vec, cmask, proto):
    """Inverse of pack_tree_device; ``proto`` supplies shapes/dtypes."""
    vec = np.asarray(vec)
    fields = {}
    off = 0
    for f in proto._fields:
        if f == "split_cat_mask":
            continue
        arr = getattr(proto, f)
        sz = int(np.prod(arr.shape)) if arr.shape else 1
        if arr.dtype == np.int32:
            hi, lo = vec[off:off + sz], vec[off + sz:off + 2 * sz]
            piece = hi.astype(np.int32) * _LO + lo.astype(np.int32)
            off += 2 * sz
        else:
            piece = vec[off:off + sz].astype(arr.dtype)
            off += sz
        fields[f] = piece.reshape(arr.shape) if arr.shape else piece[0]
    fields["split_cat_mask"] = np.asarray(cmask)
    return type(proto)(**fields)


def _fetch_tree_host(dev_tree):
    """Device TreeArrays -> host TreeArrays in two transfers."""
    if isinstance(getattr(dev_tree, "split_feature", None), np.ndarray):
        return dev_tree
    vec, cmask = jax.device_get(pack_tree_device(dev_tree))
    return unpack_tree_host(vec, cmask, dev_tree)


def tree_from_arrays(dev_tree, mappers: Sequence[BinMapper],
                     used_features: Optional[np.ndarray] = None) -> Tree:
    """Convert device TreeArrays (ops/grow.py) to a host Tree, realizing
    bin-space thresholds as real values via the BinMappers."""
    # ONE device->host fetch for the whole tree: everything except the
    # categorical bitmask is packed into a single f32 vector on device
    # (int32 fields as two exact halves); per-field
    # np.asarray would pay a device round-trip per array (a dozen
    # pipeline stalls per boosting iteration)
    dev_tree = _fetch_tree_host(dev_tree)
    L = int(np.asarray(dev_tree.num_leaves))
    nn = max(L - 1, 0)
    inner_sf = np.asarray(dev_tree.split_feature)[:nn].astype(np.int32)
    if used_features is not None:
        sf = used_features[inner_sf].astype(np.int32)
    else:
        sf = inner_sf
    tb = np.asarray(dev_tree.threshold_bin)[:nn].astype(np.int32)
    dl = np.asarray(dev_tree.default_left)[:nn]
    is_cat_node = np.asarray(dev_tree.split_is_cat)[:nn]
    cat_masks = np.asarray(dev_tree.split_cat_mask)[:nn]
    thr = np.zeros(nn, np.float64)
    dtypes = np.zeros(nn, np.uint8)
    cat_boundaries = [0]
    cat_threshold: List[int] = []
    num_cat = 0
    for i in range(nn):
        # mappers are one-per-used-feature: index by the inner id
        m = mappers[inner_sf[i]]
        code = _MISSING_CODE[m.missing_type] << 2
        if m.bin_type == BinType.CATEGORICAL:
            # Realize the bin-membership mask from the split search as a
            # bitset over raw category values (tree.h SplitCategorical
            # layout: threshold = index into cat_boundaries_).
            if is_cat_node[i]:
                member = np.where(cat_masks[i][: len(m.bin_to_cat)])[0]
            else:  # legacy prefix split "bin <= t"
                member = np.arange(min(int(tb[i]) + 1, len(m.bin_to_cat)))
            cats = np.asarray(m.bin_to_cat, np.int64)[member]
            nwords = (int(cats.max()) // 32 + 1) if len(cats) else 1
            words = np.zeros(nwords, np.uint32)
            for c in cats:
                words[c // 32] |= np.uint32(1) << np.uint32(c % 32)
            thr[i] = float(num_cat)
            code |= CAT_MASK
            cat_threshold.extend(int(x) for x in words)
            cat_boundaries.append(len(cat_threshold))
            num_cat += 1
        else:
            thr[i] = m.bin_upper_bound(int(tb[i]))
            if dl[i]:
                code |= DEFAULT_LEFT_MASK
        dtypes[i] = code
    return Tree(
        num_cat=num_cat,
        cat_boundaries=np.asarray(cat_boundaries, np.int64)
        if num_cat else None,
        cat_threshold=np.asarray(cat_threshold, np.uint32)
        if num_cat else None,
        num_leaves=L,
        split_feature=sf,
        split_gain=np.asarray(dev_tree.split_gain)[:nn].astype(np.float64),
        threshold=thr,
        threshold_bin=tb,
        decision_type=dtypes,
        left_child=np.asarray(dev_tree.left_child)[:nn].astype(np.int32),
        right_child=np.asarray(dev_tree.right_child)[:nn].astype(np.int32),
        leaf_value=np.asarray(dev_tree.leaf_value)[:L].astype(np.float64),
        leaf_weight=np.asarray(dev_tree.leaf_weight)[:L].astype(np.float64),
        leaf_count=np.asarray(dev_tree.leaf_count)[:L].astype(np.int64),
        internal_value=np.asarray(
            dev_tree.internal_value)[:nn].astype(np.float64),
        internal_weight=np.asarray(
            dev_tree.internal_weight)[:nn].astype(np.float64),
        internal_count=np.asarray(
            dev_tree.internal_count)[:nn].astype(np.int64),
    )
