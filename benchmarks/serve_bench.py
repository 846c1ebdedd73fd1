"""On-chip serve bench (ROADMAP 3d): rows/s + p99 through the REAL
serving stack — CompiledForest + MicroBatcher, in process, no sockets
— so the number measures model dispatch + micro-batching, not TCP.

Concurrent client threads submit fixed-size row blocks through
``MicroBatcher.submit`` for a fixed wall window; the bench reports
sustained rows/s, request latency percentiles and the batcher's own
coalescing stats as ONE JSON line on stdout (the bench.py
contract). A second traced window samples
requests through the tracing plane (obs/trace.py) and reports the
span-derived stage decomposition — queue wait / batch window / device
dispatch — so an on-chip p99 regression localizes to a stage without
a separate profiling run.

Knobs: BENCH_SERVE_SECS (window, default 10), BENCH_SERVE_CLIENTS
(default 8), BENCH_SERVE_ROWS (rows/request, default 64),
BENCH_SERVE_TREES (default 200), BENCH_SERVE_WINDOW_MS (default 2).

Run:  python benchmarks/serve_bench.py
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.trace import drain_span_events
from lightgbm_tpu.serve.batcher import MicroBatcher
from lightgbm_tpu.serve.compile import compile_forest
from lightgbm_tpu.utils.compile_cache import configure_compile_cache

SECS = float(os.environ.get("BENCH_SERVE_SECS", "10"))
CLIENTS = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
ROWS = int(os.environ.get("BENCH_SERVE_ROWS", "64"))
TREES = int(os.environ.get("BENCH_SERVE_TREES", "200"))
WINDOW_MS = float(os.environ.get("BENCH_SERVE_WINDOW_MS", "2"))
F = 28


def _train_forest():
    rs = np.random.RandomState(0)
    X = rs.randn(20000, F).astype(np.float32)
    y = ((X @ rs.randn(F)) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "max_bin": 63, "verbosity": -1}, ds,
                    num_boost_round=TREES)
    return compile_forest(bst, max_batch_rows=4096)


def _client_loop(batcher, X, stop, lat, errs):
    while not stop.is_set():
        t0 = time.perf_counter()
        try:
            batcher.submit(X).result(timeout=30)
        except Exception:
            errs.append(1)
            continue
        lat.append(time.perf_counter() - t0)


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def main():
    configure_compile_cache()
    t0 = time.perf_counter()
    forest = _train_forest()
    forest.warmup()
    build_s = time.perf_counter() - t0
    batcher = MicroBatcher(forest, batch_window_ms=WINDOW_MS)
    X = np.random.RandomState(1).randn(ROWS, F).astype(np.float32)

    # measured window: CLIENTS threads, untraced (production shape)
    stop = threading.Event()
    lat, errs = [], []
    threads = [threading.Thread(target=_client_loop,
                                args=(batcher, X, stop, lat, errs),
                                daemon=True)
               for _ in range(CLIENTS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(SECS)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    wall = time.perf_counter() - t0

    # traced window: sample the stage decomposition through the span
    # plane itself (serve/queue_wait / batch_window / dispatch)
    drain_span_events()
    stages = {}
    n_traced = 64
    for _ in range(n_traced):
        fut = batcher.submit(X, trace={"trace_id": "b" * 16,
                                       "span_id": "c" * 16})
        t_sub = time.perf_counter()
        fut.result(timeout=30)
        done = time.perf_counter()
        times = getattr(fut, "trace_times", None)
        if times is None:
            continue
        t_submit, t_deq, t_disp, t_done = times
        for key, dur in (("queue_wait", t_deq - t_submit),
                         ("batch_window", t_disp - t_deq),
                         ("dispatch", t_done - t_disp),
                         ("reply", done - t_done)):
            stages.setdefault(key, []).append(dur)
        del t_sub
    drain_span_events()

    stats = batcher.stats()
    batcher.close()
    lat.sort()
    rec = {
        "metric": "serve_rows_per_sec",
        "value": round(len(lat) * ROWS / wall, 1) if lat else None,
        "unit": "rows/s",
        "requests_per_sec": round(len(lat) / wall, 1),
        "clients": CLIENTS, "rows_per_request": ROWS,
        "window_ms": WINDOW_MS, "trees": TREES,
        "latency_ms": {
            "p50": round((_pct(lat, 0.50) or 0) * 1e3, 3),
            "p95": round((_pct(lat, 0.95) or 0) * 1e3, 3),
            "p99": round((_pct(lat, 0.99) or 0) * 1e3, 3),
            "max": round((lat[-1] if lat else 0) * 1e3, 3)},
        "errors": len(errs),
        "batcher": {k: stats.get(k) for k in
                    ("batches_total", "requests_total", "shed_total",
                     "p50_ms", "p99_ms") if k in stats},
        "stage_ms_mean": {
            k: round(sum(v) / len(v) * 1e3, 3)
            for k, v in sorted(stages.items()) if v},
        "build_s": round(build_s, 1),
    }
    print(json.dumps(rec), flush=True)
    return 0 if lat and not errs else 1


if __name__ == "__main__":
    sys.exit(main())
