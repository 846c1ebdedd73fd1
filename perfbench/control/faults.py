"""Faults planted underneath the timed path, one context manager each.

``tests/perfbench`` drives a whole run over each and sees ``correct``
come out false; ``control/readings.py`` reads each on the chip at a
cell's own size. Nothing here is used by a benchmark run.

``state_unchanged``  every round returns the score it was given: the
    trees are still grown and kept, the state they should move is not.
``half_batch``  the program is handed the table with its second half of
    rows overwritten by the first half: it sees as many rows as the cell
    states (so the compiled program is the cell's own), but only half of
    the batch, each row counted twice — the mean taken over the rest.
``answer_altered``  one leaf value of the window's last tree, which is
    always a followed one, is changed by ``rel`` (a tenth: ten times what
    sound runs read) where the job hands its trees out
    (``Booster.dump_model``).
"""

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDTBooster as GBDT
    inner = GBDT.train_one_iter

    def train_one_iter(self, *args, **kwargs):
        # the step may donate the score it is given, so keep a copy
        kept = jnp.copy(self.score)
        done = inner(self, *args, **kwargs)
        self.score = kept
        return done

    return _patched(GBDT, "train_one_iter", train_one_iter)


def half_batch():
    import lightgbm_tpu as lgb
    inner = lgb.Dataset

    def dataset(data, label=None, **kwargs):
        half = data.shape[0] // 2
        seen = np.concatenate([data[:half], data[:data.shape[0] - half]])
        lab = np.concatenate([label[:half], label[:label.shape[0] - half]])
        return inner(seen, label=lab, **kwargs)

    return _patched(lgb, "Dataset", dataset)


def answer_altered(rel=0.1, tree=-1):
    import lightgbm_tpu as lgb
    inner = lgb.Booster.dump_model

    def dump_model(self, *args, **kwargs):
        model = inner(self, *args, **kwargs)
        leaves, stack = [], [model["tree_info"][tree]["tree_structure"]]
        while stack:
            node = stack.pop()
            if "leaf_value" in node:
                leaves.append(node)
            else:
                stack += [node["left_child"], node["right_child"]]
        # the leaf that says most, so that ``rel`` is what the gap reads
        max(leaves, key=lambda n: abs(n["leaf_value"]))["leaf_value"] \
            *= 1.0 + rel
        return model

    return _patched(lgb.Booster, "dump_model", dump_model)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
