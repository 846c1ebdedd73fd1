"""Device trace of one WIDE (Allstate-shaped, EFB-bundled) iteration.

Diagnostic for the per-split fixed cost at width (ROADMAP S1):
traces one train_one_iter at BENCH_ROWS x BENCH_FEATURES through the real
engine, parses the xplane directly and prints device-time by op
category, so the per-split fixed path can be attributed to actual
HLOs instead of suspicion.

Run on TPU:  python benchmarks/wide_trace.py
Env: BENCH_ROWS (131072), BENCH_FEATURES (4228), BENCH_LEAVES (255)
"""
import collections
import glob
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

N = int(os.environ.get("BENCH_ROWS", 131_072))
F = int(os.environ.get("BENCH_FEATURES", 4228))
L = int(os.environ.get("BENCH_LEAVES", 255))
TRACE_DIR = os.environ.get("TRACE_DIR", "/tmp/wide_trace")


def make_allstate_like(n, f, seed=0, per_group=128):
    rs = np.random.RandomState(seed)
    groups = f // per_group
    X = np.zeros((n, f), np.float32)
    signal = np.zeros(n, np.float32)
    vals = np.random.RandomState(12345).rand(
        groups, per_group).astype(np.float32) * 2
    rows = np.arange(n)
    for g in range(groups):
        pick = rs.randint(0, per_group, n)
        X[rows, g * per_group + pick] = vals[g, pick]
        signal += vals[g, pick]
    nanmask = rs.rand(n) < 0.1
    X[nanmask, 0] = np.nan
    y = (signal > np.median(signal)).astype(np.float32)
    return X, y.astype(np.float64)


def main():
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()

    X, y = make_allstate_like(N, F)
    t0 = time.time()
    ds = lgb.Dataset(X, label=y, params={"max_bin": 255})
    ds.construct()
    del X
    print(f"construct: {time.time() - t0:.1f} s", flush=True)

    bst = lgb.Booster(params={"objective": "binary", "num_leaves": L,
                              "max_bin": 255, "learning_rate": 0.1,
                              "verbosity": -1}, train_set=ds)
    eng = bst._engine
    if eng.bundle is not None:
        print(f"bundles: {len(eng.bundle.groups)} "
              f"(from {F} features)", flush=True)

    t0 = time.time()
    eng.train_one_iter()
    eng.score.block_until_ready()
    print(f"warmup (incl compile): {time.time() - t0:.1f} s", flush=True)
    t0 = time.time()
    eng.train_one_iter()
    eng.score.block_until_ready()
    steady = time.time() - t0
    print(f"steady: {steady * 1e3:.1f} ms/iter", flush=True)

    with jax.profiler.trace(TRACE_DIR):
        eng.train_one_iter()
        eng.score.block_until_ready()

    report(steady)


def report(steady):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = sorted(glob.glob(
        os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        print("no xplane written", flush=True)
        return
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())

    # device plane: op events with durations
    by_op = collections.Counter()
    n_ev = collections.Counter()
    total_ps = 0
    for plane in xs.planes:
        if "TPU" not in plane.name and "/device" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        for line in plane.lines:
            if "XLA Ops" not in line.name and "Steps" not in line.name \
                    and "XLA Modules" not in line.name:
                # keep only the op-level line when present
                pass
            for ev in line.events:
                name = ev_meta[ev.metadata_id].name
                if line.name.startswith("XLA Ops"):
                    by_op[name] += ev.duration_ps
                    n_ev[name] += 1
                    total_ps += ev.duration_ps

    # bucket by HLO category (fusion names carry the root op)
    def cat(name):
        m = re.match(r"%?([a-z-]+)", name)
        base = m.group(1) if m else name
        return base

    by_cat = collections.Counter()
    for name, ps in by_op.items():
        by_cat[cat(name)] += ps

    print(f"\ndevice total: {total_ps / 1e9:.1f} ms "
          f"(steady wall {steady * 1e3:.1f} ms)")
    print("\n-- by category --")
    for name, ps in by_cat.most_common(15):
        print(f"{name:40s} {ps / 1e9:9.1f} ms")
    print("\n-- top individual ops --")
    for name, ps in by_op.most_common(30):
        print(f"{name[:90]:90s} {ps / 1e9:9.2f} ms  x{n_ev[name]}")


if __name__ == "__main__":
    main()
