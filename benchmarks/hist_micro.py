"""Microbenchmark of histogram-construction strategies on the TPU.

The GBDT hot loop is a (g, h, count) scatter-add over per-feature bins
(reference: dense_bin.hpp ConstructHistogramInner). TPUs have no scatter
hardware, so the right strategy is an empirical question. This measures:

  scan_scatter   - lax.scan over features, one .at[].add per feature
  flat_scatter   - ONE scatter of n*F updates into a flat [F*B*3] buffer
  onehot         - one-hot einsum riding the MXU
  segsum         - jax.ops.segment_sum with combined (f, bin) segment ids
  packed_scatter - quantized (g,h) packed into one int32 channel, flat scatter
  pallas         - hand-tiled VMEM-resident one-hot kernel
                   (lightgbm_tpu/ops/pallas_hist.py; the hist_method=
                   "pallas" production path). Its time against `onehot`
                   is the first half of the auto-flip gate; the binding
                   number is fused_iter_bench.py's pallas arm.

Run on the TPU:  python benchmarks/hist_micro.py
Env: HM_ROWS, HM_FEATURES, HM_BINS.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N = int(os.environ.get("HM_ROWS", 1_000_000))
F = int(os.environ.get("HM_FEATURES", 28))
B = int(os.environ.get("HM_BINS", 256))


def timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters


if __name__ == "__main__":
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()

    print(f"backend={jax.default_backend()} n={N} F={F} B={B}", flush=True)
    rs = np.random.RandomState(0)
    bins_T = jnp.asarray(rs.randint(0, B, size=(F, N)).astype(np.uint8))
    grad = jnp.asarray(rs.randn(N).astype(np.float32))
    hess = jnp.asarray(np.abs(rs.randn(N)).astype(np.float32))
    w = jnp.ones((N,), jnp.float32)

    @jax.jit
    def scan_scatter(bins_T, g, h, w):
        gh = jnp.stack([g * w, h * w, w], axis=-1)

        def body(carry, bins_f):
            hist = jnp.zeros((B, 3), jnp.float32).at[bins_f].add(
                gh, mode="drop")
            return carry, hist

        _, hists = lax.scan(body, None, bins_T)
        return hists

    @jax.jit
    def flat_scatter(bins_T, g, h, w):
        gh = jnp.stack([g * w, h * w, w], axis=-1)          # [n, 3]
        idx = (jnp.arange(F, dtype=jnp.int32)[:, None] * B
               + bins_T.astype(jnp.int32))                   # [F, n]
        flat = jnp.zeros((F * B, 3), jnp.float32)
        flat = flat.at[idx.reshape(-1)].add(
            jnp.tile(gh, (F, 1)), mode="drop")
        return flat.reshape(F, B, 3)

    @jax.jit
    def segsum(bins_T, g, h, w):
        gh = jnp.stack([g * w, h * w, w], axis=-1)
        idx = (jnp.arange(F, dtype=jnp.int32)[:, None] * B
               + bins_T.astype(jnp.int32)).reshape(-1)
        out = jax.ops.segment_sum(jnp.tile(gh, (F, 1)), idx,
                                  num_segments=F * B)
        return out.reshape(F, B, 3)

    @jax.jit
    def onehot(bins_T, g, h, w, block=32768):
        gh = jnp.stack([g * w, h * w, w], axis=-1)
        pad = (-N) % block
        if pad:
            bins_T = jnp.pad(bins_T, ((0, 0), (0, pad)))
            gh = jnp.pad(gh, ((0, pad), (0, 0)))
        nblk = bins_T.shape[1] // block
        bins_blk = bins_T.reshape(F, nblk, block).transpose(1, 0, 2)
        gh_blk = gh.reshape(nblk, block, 3)

        def body(acc, xs):
            b, ghb = xs
            oh = jax.nn.one_hot(b, B, dtype=jnp.bfloat16)
            acc = acc + jnp.einsum("frb,rc->fbc", oh,
                                   ghb.astype(jnp.bfloat16),
                                   preferred_element_type=jnp.float32)
            return acc, None

        init = jnp.zeros((F, B, 3), jnp.float32)
        hists, _ = lax.scan(body, init, (bins_blk, gh_blk))
        return hists

    @jax.jit
    def packed_scatter(bins_T, g, h, w):
        # int16 quantized (g,h) packed into one int32; count via a
        # separate int32 scatter of packed (1<<16 | 1)-style trick is
        # skipped - just g,h packed + count from per-leaf totals.
        gs = jnp.clip(g * w * 32767.0 / 4.0, -32767, 32767).astype(jnp.int32)
        hs = jnp.clip(h * w * 32767.0 / 4.0, 0, 65535).astype(jnp.int32)
        packed = (gs << 16) | hs
        idx = (jnp.arange(F, dtype=jnp.int32)[:, None] * B
               + bins_T.astype(jnp.int32))
        flat = jnp.zeros((F * B,), jnp.int32)
        flat = flat.at[idx.reshape(-1)].add(
            jnp.tile(packed, (F,)), mode="drop")
        return flat.reshape(F, B)

    arms = [("scan_scatter", scan_scatter),
            ("flat_scatter", flat_scatter),
            ("segsum", segsum),
            ("onehot", onehot),
            ("packed_scatter", packed_scatter)]

    from lightgbm_tpu.ops.pallas_hist import (hist_from_rows_pallas,
                                              pallas_available)
    if pallas_available():
        @jax.jit
        def pallas_arm(bins_T, g, h, w):
            gh = jnp.stack([g * w, h * w, w], axis=-1)
            return hist_from_rows_pallas(bins_T.T, gh, B)

        arms.append(("pallas", pallas_arm))
    else:
        print("pallas           SKIPPED (unavailable)", flush=True)

    results = {}
    for name, fn in arms:
        try:
            dt = timeit(fn, bins_T, grad, hess, w)
            gbs = (N * F * 1 + N * 12) / dt / 1e9
            results[name] = dt
            print(f"{name:16s} {dt*1e3:9.2f} ms   ({gbs:6.1f} GB/s eff)",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name:16s} FAILED: {type(e).__name__}: {e}", flush=True)
    if results:
        best = min(results, key=results.get)
        print(f"best: {best} ({results[best]*1e3:.2f} ms)", flush=True)
