"""Faults planted underneath a ``train_eval_missing`` run, one context
manager each, and the hand tool that reads them (``control/readings.py``
with these added to its modes):

    python3 perfbench/control/faults_missing.py --workload <cell> \
        --seeds 11 --seconds 6 --modes sound,nan_always_right,\
nan_binned_as_zero,valid_directions_flipped,metric_on_train,\
train_score_stale

``nan_always_right``  the split search is never told which bin is the
    NaN bin, so it scans one direction only: the NaN rows sit right of
    every threshold and no node records ``default_left``. The trees are
    routed as recorded, so every count agrees; the gain a node would have
    won with its NaN rows on the left (``missing_direction_shortfall``)
    and the reference's best split at the root read it.
``nan_binned_as_zero``  the tables are binned under ``use_missing=false``:
    a NaN lands in the bin of 0.0, no column has a NaN bin and no node a
    direction. The trees route as recorded (a NaN reads as 0.0); the
    reference, which searches with the NaN rows on either side, finds the
    better splits.
``valid_directions_flipped``  the validation set is scored with every
    node's ``default_left`` inverted: the trees and the train score are
    sound, the held-out score and its metric are not.
``metric_on_train``  ``faults_eval.py``'s: the metric the engine reports
    for the validation set is computed on the train score and labels.
``train_score_stale``  the train score is put back as it stood before
    round ``at``: the tree of that round is in the model and in the
    validation score, and not in the score the next gradients are taken
    at. ``score_gap`` (every tree routed over the raw train table
    against the score left on the device) reads it; it is that number's
    fault, which none of the four above moves.

A planted fault has to be read by a NUMBER, so the tool runs the faults
with the check's probe and the driver's counter ranges off
(``no_probe``): on a sound driver ``nan_binned_as_zero`` ends the run
before the tables are made, which is the probe doing its work, and would
end it again at "0% of the cells missing". ``tests/perfbench`` drives a whole run
over each and sees ``correct`` come out false. Nothing here is used by a
benchmark run.
"""

import contextlib
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from control.faults import _patched  # noqa: E402
from control.faults_eval import metric_on_train  # noqa: E402


@contextlib.contextmanager
def _retraced(patch):
    """``patch`` around code that is traced: the grower is one jitted
    function for every job of the process, so what was traced without
    the fault (or with it) must not be run again."""
    import jax
    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        jax.clear_caches()


def nan_always_right():
    import jax.numpy as jnp
    from lightgbm_tpu.ops import grow
    inner = grow.find_best_split

    def find_best_split(hist, parent_g, parent_h, parent_cnt, feat_num_bins,
                        feat_nan_bin, *args, **kwargs):
        return inner(hist, parent_g, parent_h, parent_cnt, feat_num_bins,
                     jnp.full_like(feat_nan_bin, -1), *args, **kwargs)

    return _retraced(_patched(grow, "find_best_split", find_best_split))


def nan_binned_as_zero():
    import lightgbm_tpu as lgb
    inner = lgb.Dataset

    def dataset(data, label=None, params=None, **kwargs):
        return inner(data, label=label,
                     params=dict(params or {}, use_missing=False), **kwargs)

    return _patched(lgb, "Dataset", dataset)


def valid_directions_flipped():
    from lightgbm_tpu.models.gbdt import GBDTBooster as GBDT
    from lightgbm_tpu.models.tree import DEFAULT_LEFT_MASK
    inner = GBDT._predict_tree_binned_host

    def predict(self, tree, dataset):
        if dataset is not self.train_set and tree.num_leaves > 1:
            tree = copy.copy(tree)
            tree.decision_type = tree.decision_type ^ DEFAULT_LEFT_MASK
        return inner(self, tree, dataset)

    return _patched(GBDT, "_predict_tree_binned_host", predict)


def train_score_stale(at=3):
    from lightgbm_tpu.models.gbdt import GBDTBooster as GBDT
    inner = GBDT.train_one_iter

    def train_one_iter(self, *args, **kwargs):
        before = self.score if self.iter_ == at else None
        out = inner(self, *args, **kwargs)
        if before is not None:
            self.score = before
        return out

    return _patched(GBDT, "train_one_iter", train_one_iter)


FAULTS = {"nan_always_right": nan_always_right,
          "nan_binned_as_zero": nan_binned_as_zero,
          "valid_directions_flipped": valid_directions_flipped,
          "metric_on_train": metric_on_train,
          "train_score_stale": train_score_stale}


def main(argv=None, root=None):
    import run
    from control import faults, readings
    faults.FAULTS.update(FAULTS)
    inner = run.run_cell

    def run_cell(*args, **extra):
        # readings.py hands the sound run its control and the control run
        # its parameters; a run with neither has a fault planted
        if not {"control_dtype", "params_override"} & set(extra):
            extra["no_probe"] = True
        return inner(*args, **extra)

    with _patched(run, "run_cell", run_cell):
        return readings.main(argv, root)


if __name__ == "__main__":
    main()
