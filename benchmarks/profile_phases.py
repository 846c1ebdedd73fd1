"""Per-phase wall-clock decomposition of one boosting iteration.

Times, at a Higgs-like shape (env BENCH_ROWS/BENCH_FEATURES/BENCH_LEAVES):
  - gradient computation (objective)
  - full grow_tree at num_leaves in {2, 8, 64, 255} (separates the
    root-histogram cost from per-split cost)
  - score update (predict_leaf_binned over the train rows)
  - micro: one MXU nibble histogram chunk, one pass-B variadic sort chunk

Run on TPU:  python benchmarks/profile_phases.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grow import GrowConfig, grow_tree
from lightgbm_tpu.ops.histogram import hist_from_rows
from lightgbm_tpu.ops.split import SplitParams, find_best_split
from lightgbm_tpu.ops.predict import predict_leaf_binned
from lightgbm_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

N = int(os.environ.get("BENCH_ROWS", 1_048_576))
F = int(os.environ.get("BENCH_FEATURES", 28))
L = int(os.environ.get("BENCH_LEAVES", 255))
B = 256
K = 16384

rs = np.random.RandomState(0)
bins_T = jnp.asarray(rs.randint(0, 255, size=(F, N), dtype=np.uint8))
grad = jnp.asarray(rs.randn(N).astype(np.float32))
hess = jnp.asarray(np.abs(rs.randn(N)).astype(np.float32) + 0.1)
row_w = jnp.ones((N,), jnp.float32)
fmask = jnp.ones((F,), bool)
fnb = jnp.full((F,), 255, jnp.int32)
fnan = jnp.full((F,), -1, jnp.int32)


def timeit(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps, out


def report(name, secs):
    print(f"{name:55s} {secs*1e3:10.2f} ms")


# ---- full tree at varying leaf counts ----
prev = None
for leaves in (2, 8, 64, L):
    cfg = GrowConfig(num_leaves=leaves, num_bins=B, split=SplitParams(),
                     hist_method="mxu", grower="compact", chunk=K)
    s, _ = timeit(grow_tree, cfg, bins_T, grad, hess, row_w, fmask,
                  fnb, fnan, reps=2)
    extra = ""
    if prev is not None:
        ds, dl = s - prev[0], leaves - prev[1]
        extra = f"   (+{ds/dl*1e3:.2f} ms/split marginal)"
    report(f"grow_tree num_leaves={leaves}", s)
    if extra:
        print(" " * 55 + extra)
    prev = (s, leaves)

# ---- micro: one histogram chunk (K rows) ----
rows_k = jnp.asarray(rs.randint(0, 255, size=(K, F), dtype=np.uint8))
pay_k = jnp.asarray(rs.randn(K, 2).astype(np.float32))
f_hist = jax.jit(lambda r, p: hist_from_rows(r, p, B, "mxu"))
s, _ = timeit(f_hist, rows_k, pay_k, reps=20, warmup=3)
report(f"hist_from_rows mxu chunk [{K}x{F}] -> [F,{B},2]", s)
tot_chunks = N // K
report(f"  x {tot_chunks} chunks (full-data pass equivalent)",
       s * tot_chunks)

# ---- micro: pass-B variadic sort of one chunk ----
key = jnp.asarray(rs.randint(0, 2 * K, size=(K,), dtype=np.int32))
cols = tuple(jnp.asarray(rs.randint(0, 2**31, size=(K,), dtype=np.int32))
             for _ in range(F // 4 + 3))


def f_sort(key, cols):
    return jax.lax.sort((key,) + cols, num_keys=1)


s, _ = timeit(jax.jit(f_sort), key, cols, reps=20, warmup=3)
report(f"pass-B variadic sort chunk [{K}] x {len(cols)+1} ops", s)

# ---- split search over all leaves' histograms ----
hist = jnp.asarray(rs.rand(F, B, 2).astype(np.float32))
f_split = jax.jit(lambda h: find_best_split(
    h, jnp.float32(1.0), jnp.float32(100.0), jnp.float32(N), fnb, fnan,
    fmask, SplitParams()))
s, _ = timeit(f_split, hist, reps=20, warmup=3)
report("find_best_split one leaf [F,B,2]", s)

# ---- score update: predict over all rows ----
sf = jnp.zeros((L - 1,), jnp.int32)
tb = jnp.full((L - 1,), 128, jnp.int32)
dlft = jnp.zeros((L - 1,), bool)
lc = -(jnp.arange(L - 1, dtype=jnp.int32) + 1)
rc = -(jnp.arange(L - 1, dtype=jnp.int32) + 2)
f_pred = jax.jit(lambda: predict_leaf_binned(sf, tb, dlft, lc, rc, fnan,
                                             bins_T))
s, _ = timeit(f_pred, reps=5, warmup=2)
report(f"predict_leaf_binned all {N} rows", s)

# ---- gradients ----
lbl = jnp.asarray((rs.rand(N) > 0.5).astype(np.float32))


def f_grad(score):
    p = jax.nn.sigmoid(score)
    return p - lbl, p * (1 - p)


s, _ = timeit(jax.jit(f_grad), jnp.zeros((N,), jnp.float32), reps=10)
report("binary grad/hess", s)
