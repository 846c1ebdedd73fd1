"""Measure the fused-iteration fast path end-to-end at bench scale
(10.5M x 28, 255 leaves/bins) on the real chip, with three arms and two
FLIP gates:

- eager vs fused: wall per train_one_iter (fused gate forced off vs on).
- fused vs fused+pallas: the pallas-vs-mxu delta at THIS shape is the
  decision gate for flipping hist_method="auto" to pallas on TPU
  (docs/PALLAS.md).
- fused vs fused+scan: the multi-iteration scan window
  (Config.fused_scan_iters, docs/FUSED.md) traces SCAN_W iterations
  into one program; its gate decides flipping fused_scan_iters="auto"
  off 1. Each arm also prints a dispatch-gap decomposition: on-device
  program time (the boosting/fused_iter|fused_scan Timer phases) vs
  host driver time per iteration (wall minus device phases — dispatch,
  tree-pack fetch and Python driver, the ~15% of a Higgs iteration the
  scan exists to delete). The acceptance proxy off-chip: driver
  time/iter inside a window drops >= 5x vs the per-iteration fused
  arm; the on-chip verdict is wall it/s at this shape. NB: the CPU
  backend executes per-iteration programs synchronously inside the
  dispatch call, so off-chip the per-iteration arms' driver column is
  an UPPER bound (driver + compute); the scan arm's pop-driver number
  is exact on both backends (pure host work, no device traffic).

Run:  python benchmarks/fused_iter_bench.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDTBooster
from lightgbm_tpu.utils.compile_cache import configure_compile_cache
from lightgbm_tpu.utils.timer import Timer

configure_compile_cache()

N = int(os.environ.get("BENCH_FUSED_ROWS", "10500000"))  # smoke knob
F = 28
SCAN_W = int(os.environ.get("BENCH_SCAN_ITERS", "10"))
rs = np.random.RandomState(0)
X = rs.randn(N, F).astype(np.float32)
coef = rs.randn(F).astype(np.float32)
y = ((X @ coef) > 0).astype(np.float64)
t0 = time.perf_counter()
ds = lgb.Dataset(X, label=y, params={"max_bin": 255})
ds.construct()
print(f"construct: {time.perf_counter() - t0:.1f} s", flush=True)
del X

PARAMS = {"objective": "binary",
          "num_leaves": int(os.environ.get("BENCH_FUSED_LEAVES", "255")),
          "max_bin": 255, "learning_rate": 0.1, "verbosity": -1}

# Host-driver time = time spent INSIDE train_one_iter calls minus the
# in-call device-blocking phase (the scan's window-boundary batched
# fetch, timed under boosting/fused_scan). Per-iteration dispatches
# return async, so their in-call time IS the dispatch + Python driver
# overhead the scan deletes; the device wait then accrues at the final
# block_until_ready and lands in (wall - driver). The phase list is
# THE one the tracing plane's per-iteration host-gap derivation
# subtracts (obs/trace.py record_iteration_spans) — same source of
# truth, so the bench arms and the span attrs can never disagree.
from lightgbm_tpu.obs.trace import BLOCKING_PHASES as _BLOCKING_PHASES


def _phase_total(snap, labels):
    return sum(snap.get(lb, {}).get("total", 0.0) for lb in labels)


def run(tag, fused, iters=10, hist_method=None, scan=0):
    if not fused:
        orig = GBDTBooster._fused_ok
        GBDTBooster._fused_ok = lambda self: False
    try:
        params = dict(PARAMS)
        if hist_method:
            params["hist_method"] = hist_method
        if scan:
            params["fused_scan_iters"] = scan
        bst = lgb.Booster(params=params, train_set=ds)
        eng = bst._engine
        if scan:
            # direct train_one_iter driving (no engine loop): the
            # bench owns the cadence, so it grants the lookahead the
            # train() loop would have computed
            eng._scan_horizon = iters
        t0 = time.perf_counter()
        eng.train_one_iter()
        eng.score.block_until_ready()
        print(f"{tag}: warmup (incl compile) "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        if scan:
            # restart the window grid so the measured loop covers
            # whole windows (the warmup window is popped out first)
            while eng._scan_pend is not None:
                eng.train_one_iter()
            eng._scan_horizon = iters
        was_enabled = Timer.enabled()
        Timer.enable()
        base = Timer.snapshot()
        t_calls = 0.0
        t0 = time.perf_counter()
        for _ in range(iters):
            tc = time.perf_counter()
            eng.train_one_iter()
            t_calls += time.perf_counter() - tc
        eng.score.block_until_ready()
        wall = time.perf_counter() - t0
        snap = Timer.snapshot()
        Timer.enable(was_enabled)
        blocking = _phase_total(snap, _BLOCKING_PHASES) \
            - _phase_total(base, _BLOCKING_PHASES)
        dt = wall / iters
        driver = max(t_calls - blocking, 0.0) / iters
        print(f"{tag}: {dt * 1e3:.1f} ms/iter = {1 / dt:.3f} iters/sec "
              f"(vs_baseline {1 / dt / (500 / 130.094):.3f})", flush=True)
        print(f"{tag}: decomposition on-device+wait "
              f"{(wall / iters - driver) * 1e3:.2f} ms/iter, host "
              f"driver {driver * 1e3:.2f} ms/iter (inter-iteration "
              f"gap)", flush=True)
        # one machine-readable line per flip-gate arm: the span-
        # derived host-gap decomposition next to the wall number, so
        # the revive battery's greps AND the trace plane's host_gap_s
        # attrs reconcile against the same record
        print(json.dumps({
            "event": "bench_arm", "arm": tag, "iters": iters,
            "ms_per_iter": round(dt * 1e3, 3),
            "iters_per_sec": round(1 / dt, 4),
            "device_ms_per_iter": round((wall / iters - driver) * 1e3,
                                        3),
            "host_gap_ms_per_iter": round(driver * 1e3, 3),
            "blocking_phases": list(_BLOCKING_PHASES)}), flush=True)
        return dt, driver
    finally:
        if not fused:
            GBDTBooster._fused_ok = orig


eager, _ = run("eager", fused=False)
fused, fused_driver = run("fused", fused=True)
print(f"speedup: {eager / fused:.3f}x", flush=True)

scan, scan_driver = run(f"fused+scan{SCAN_W}", fused=True, iters=SCAN_W,
                        scan=SCAN_W)
gap_ratio = fused_driver / scan_driver if scan_driver > 0 else float("inf")
print(f"scan vs fused: {fused / scan:.3f}x wall, driver gap "
      f"{fused_driver * 1e3:.2f} -> {scan_driver * 1e3:.2f} ms/iter "
      f"({gap_ratio:.1f}x lower) — "
      f"{'FLIP fused_scan_iters auto to ' + str(SCAN_W) if scan < fused else 'keep per-iteration'} "
      "(record the verdict in docs/FUSED.md + PERF.md)",
      flush=True)

from lightgbm_tpu.ops.pallas_hist import pallas_available  # noqa: E402

if pallas_available():
    pallas, _ = run("fused+pallas", fused=True, hist_method="pallas")
    print(f"pallas vs mxu (fused): {fused / pallas:.3f}x — "
          f"{'FLIP auto to pallas' if pallas < fused else 'keep mxu'} "
          "(record the verdict in docs/PALLAS.md + PERF.md)",
          flush=True)
else:
    print("pallas arm SKIPPED (unavailable)", flush=True)
