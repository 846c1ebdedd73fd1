"""Faults planted underneath a ``train_eval`` run, one context manager
each, and the hand tool that reads them (``control/readings.py`` with
these added to its modes):

    python3 perfbench/control/faults_eval.py --workload <cell> --seeds 11 \
        --seconds 6 --modes sound,valid_score_stale,metric_on_train

``valid_score_stale``  one tree of the window (round ``at``) is not added
    to the validation score: the trees and the train score are sound, the
    held-out score lacks a tree from then on.
``query_left_out``  (ranking) the rows of the longest query get no
    gradient and no hessian from an objective's second pass on: the
    pairwise pass leaves a query out (the first pass is sound, so that
    the driver's probe of a fresh objective does not end the run before
    a number can read the fault).
``metric_on_train``  the metric the engine reports for the validation
    set is computed on the train score and labels.

``tests/perfbench`` drives a whole run over each and sees ``correct``
come out false. Nothing here is used by a benchmark run.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from control.faults import _patched  # noqa: E402


def valid_score_stale(at=3):
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDTBooster as GBDT
    inner = GBDT._predict_tree_binned_host

    def predict(self, tree, dataset):
        out = inner(self, tree, dataset)
        if dataset is not self.train_set and self.iter_ == at:
            return jnp.zeros_like(out)
        return out

    return _patched(GBDT, "_predict_tree_binned_host", predict)


def query_left_out():
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ranking import LambdarankNDCG
    inner = LambdarankNDCG.grad_hess

    def grad_hess(self, score, label, weight):
        g, h = inner(self, score, label, weight)
        self._fault_passes = getattr(self, "_fault_passes", 0) + 1
        if self._fault_passes < 2:
            return g, h
        mask = np.asarray(self.q_mask)
        q = int(np.argmax(mask.sum(axis=1)))
        rows = jnp.asarray(np.asarray(self.q_idx)[q][mask[q]])
        return g.at[rows].set(0.0), h.at[rows].set(0.0)

    return _patched(LambdarankNDCG, "grad_hess", grad_hess)


def metric_on_train():
    from lightgbm_tpu.models.gbdt import GBDTBooster as GBDT
    inner = GBDT.eval_metrics

    def eval_metrics(self, metrics, data_idx):
        return inner(self, metrics, 0)

    return _patched(GBDT, "eval_metrics", eval_metrics)


FAULTS = {"valid_score_stale": valid_score_stale,
          "query_left_out": query_left_out,
          "metric_on_train": metric_on_train}


def main(argv=None, root=None):
    from control import faults, readings
    faults.FAULTS.update(FAULTS)
    return readings.main(argv, root)


if __name__ == "__main__":
    main()
