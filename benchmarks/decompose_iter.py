"""Per-component decomposition of one 10.5M-row boosting iteration on
the REAL booster state (the bench's exact data/config): full
train_one_iter vs gradients / grow_tree / gather_small contrib /
score add / pack_tree_device in isolation. Run on TPU:
    python benchmarks/decompose_iter.py
(Needs ~25 min: 10.5M construct + first compiles.)"""
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time, numpy as np, jax, jax.numpy as jnp
import lightgbm_tpu as lgb
from lightgbm_tpu.ops.gather import gather_small
from lightgbm_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

N, F = 10_500_000, 28
rs = np.random.RandomState(0)
X = rs.randn(N, F).astype(np.float32)
coef = rs.randn(F).astype(np.float32)
y = ((X @ coef) > 0).astype(np.float64)
t0=time.perf_counter()
ds = lgb.Dataset(X.astype(np.float64), label=y, params={"max_bin": 255})
ds.construct()
print(f"construct: {time.perf_counter()-t0:.1f} s", flush=True)
del X
bst = lgb.Booster(params={"objective": "binary", "num_leaves": 255,
                          "max_bin": 255, "learning_rate": 0.1,
                          "verbosity": -1}, train_set=ds)
eng = bst._engine
t0=time.perf_counter()
eng.train_one_iter(); eng.score.block_until_ready()
print(f"warmup iter (incl compile): {time.perf_counter()-t0:.1f} s", flush=True)

t0 = time.perf_counter()
for _ in range(5):
    eng.train_one_iter()
eng.score.block_until_ready()
full = (time.perf_counter() - t0) / 5
print(f"full train_one_iter: {full*1e3:.1f} ms", flush=True)

grad, hess = eng._gradients(eng.score)
jax.block_until_ready((grad, hess))
t0 = time.perf_counter()
for _ in range(5):
    g, h = eng._gradients(eng.score)
jax.block_until_ready((g, h))
print(f"gradients: {(time.perf_counter()-t0)/5*1e3:.1f} ms", flush=True)

row_w = eng._row_weights(0, grad[0], hess[0])
fmask = eng._feature_mask()
args = (eng.bins_T, grad[0], hess[0], row_w, fmask,
        eng.feat_num_bins, eng.feat_nan_bin)
from lightgbm_tpu.ops.grow import grow_tree
out = grow_tree(eng.grow_cfg, *args)
jax.block_until_ready(out)
t0 = time.perf_counter()
for _ in range(3):
    dev_tree, row_leaf = grow_tree(eng.grow_cfg, *args)
jax.block_until_ready((dev_tree, row_leaf))
print(f"grow_tree: {(time.perf_counter()-t0)/3*1e3:.1f} ms", flush=True)

lv = dev_tree.leaf_value
c = gather_small(lv, row_leaf)
jax.block_until_ready(c)
t0 = time.perf_counter()
for _ in range(5):
    c = gather_small(lv, row_leaf)
jax.block_until_ready(c)
print(f"gather_small contrib: {(time.perf_counter()-t0)/5*1e3:.1f} ms", flush=True)

s = eng.score
s2 = s.at[0].add(c * 0.1)
jax.block_until_ready(s2)
t0 = time.perf_counter()
for _ in range(5):
    s2 = s.at[0].add(c * 0.1)
jax.block_until_ready(s2)
print(f"score add: {(time.perf_counter()-t0)/5*1e3:.1f} ms", flush=True)

from lightgbm_tpu.models.tree import pack_tree_device
v, m = pack_tree_device(dev_tree)
jax.block_until_ready((v, m))
t0 = time.perf_counter()
for _ in range(5):
    v, m = pack_tree_device(dev_tree)
jax.block_until_ready((v, m))
print(f"pack_tree_device: {(time.perf_counter()-t0)/5*1e3:.1f} ms", flush=True)

# bagging/_row_weights and feature mask
t0 = time.perf_counter()
for _ in range(5):
    rw = eng._row_weights(3, grad[0], hess[0])
jax.block_until_ready(rw)
print(f"row_weights: {(time.perf_counter()-t0)/5*1e3:.1f} ms", flush=True)
