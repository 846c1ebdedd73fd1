"""Benchmark: boosting iterations/sec + held-out AUC on a Higgs-shaped
synthetic dataset.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...} and exits 0. One
process, no fallback: any failure is a traceback and a non-zero exit,
and without ``BENCH_PLATFORM=cpu`` given explicitly (the tier-1 smoke)
a run that finds no TPU is an error.

Baseline (BASELINE.md): reference LightGBM trains Higgs-10M (10.5M x 28,
255 bins, 255 leaves) at 500 iters / 130.094 s = 3.843 iters/sec on a
28-thread 2x E5-2670v2 (docs/Experiments.rst:111-123). ``vs_baseline`` is
our iters/sec divided by that number, linearly rescaled to the 10.5M-row
workload when BENCH_ROWS is smaller (histogram work is O(rows); the
rescale factor is 1 at the full shape).

Accuracy: ``auc`` is the held-out AUC after BENCH_AUC_ITERS boosting
rounds, and ``auc_ref`` is the reference implementation's AUC trained on
the byte-identical dataset/params (measured once with an oracle build of
/root/reference at v4.6.0.99, 50 rounds, lr 0.1, 255 leaves/bins; the
synthetic task is separable so both sit near 0.97 — parity, not the
absolute Higgs 0.8457, is the check).
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_ITERS_PER_SEC = 500.0 / 130.094
HIGGS_ROWS = 10_500_000

# BENCH_PRESET=allstate: the wide-sparse EFB path (4228 one-hot-ish
# features w/ NaN, docs/Experiments.rst:121 Allstate shape; reference
# trains 13.2M rows in 148.231 s / 500 iters = 3.373 iters/sec). The
# full 13.2M x 4228 float32 matrix is ~223 GB — beyond host RAM — so
# the eager preset defaults to 2M rows; BENCH_STREAMING=1 (or a
# --streaming argv flag) instead ingests through the chunked two-pass
# pipeline (lightgbm_tpu/data/, docs/DATA.md), where peak host RSS is
# the BINNED matrix plus one generator chunk — the full-scale
# 13.2M-row shape becomes constructible on an ordinary host.
# Default preset: the REAL Higgs shape — measured, not extrapolated.
PRESET = os.environ.get("BENCH_PRESET", "higgs")
_ALLSTATE = PRESET == "allstate"
_STREAMING = (os.environ.get("BENCH_STREAMING", "") == "1"
              or "--streaming" in sys.argv)
# BENCH_SERVE=1 / --serve: after the training legs, benchmark the
# production inference path (lightgbm_tpu/serve/, docs/SERVING.md) —
# compiled shape-bucketed predict vs the eager Booster.predict CPU
# baseline over a mix of ad-hoc batch sizes; rows/sec, p50/p99 request
# latency and the recompile count after warmup ride along in a
# "serve" block of the one JSON line.
_SERVE = (os.environ.get("BENCH_SERVE", "") == "1"
          or "--serve" in sys.argv)
SERVE_REPEAT = int(os.environ.get("BENCH_SERVE_REPEAT", 3))
# rows per ingest chunk in streaming mode (the peak-RSS knob)
INGEST_CHUNK = int(os.environ.get("BENCH_INGEST_CHUNK", 262_144))
ALLSTATE_ROWS = 13_184_290
ALLSTATE_BASELINE_ITERS_PER_SEC = 500.0 / 148.231
N_ROWS = int(os.environ.get(
    "BENCH_ROWS", 2_097_152 if _ALLSTATE else HIGGS_ROWS))
N_FEATURES = int(os.environ.get("BENCH_FEATURES",
                                4228 if _ALLSTATE else 28))
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_BINS", 255))
WARMUP = int(os.environ.get("BENCH_WARMUP", 1))
ITERS = int(os.environ.get("BENCH_ITERS", 5))
AUC_ITERS = int(os.environ.get("BENCH_AUC_ITERS", 50))
N_VALID = int(os.environ.get("BENCH_VALID", 524_288))

# oracle (reference build, v4.6.0.99) held-out AUC on the identical
# seed-0 dataset, 50 rounds: measured via /tmp oracle runs of
# /root/reference with the same make_higgs_like generator
ORACLE_AUC = {1_048_576: 0.967940, 10_500_000: 0.967607}


def make_higgs_like(n, f, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f).astype(np.float32)
    coef = rs.randn(f).astype(np.float32)
    logits = X @ coef * 0.5 + 0.5 * rs.randn(n).astype(np.float32)
    y = (logits > 0).astype(np.float32)
    # float32 on purpose: binning casts per-column to float64 itself
    # (ops/binning.py), and a whole-matrix float64 copy doubles peak
    # host RSS for nothing
    return X, y.astype(np.float64)


def higgs_chunks(n, f, seed=0, chunk_rows=None):
    """Chunked Higgs-shaped generator for --streaming mode. Each chunk
    is drawn from a per-chunk RandomState (seeded by start row), so
    pass 1 and pass 2 of the ingest pipeline see identical data
    without the generator ever holding more than one chunk. NOTE: the
    row stream differs from make_higgs_like's single-stream layout, so
    streaming runs carry no ``auc_ref`` oracle."""
    chunk_rows = chunk_rows or INGEST_CHUNK
    coef = np.random.RandomState(987).randn(f).astype(np.float32)
    start = 0
    while start < n:
        c = min(chunk_rows, n - start)
        rs = np.random.RandomState(
            (seed * 1_000_003 + start) % (2 ** 31 - 1))
        X = rs.randn(c, f).astype(np.float32)
        logits = X @ coef * 0.5 + 0.5 * rs.randn(c).astype(np.float32)
        yield X, (logits > 0).astype(np.float64)
        start += c


def allstate_chunks(n, f, seed=0, per_group=128, chunk_rows=None):
    """Chunked Allstate-shaped generator: wide sparse one-hot blocks +
    NaN (the shape EFB exists for), emitted ``chunk_rows`` rows at a
    time so no [n, f] matrix is ever held. Values per position come
    from a FIXED stream (seed 12345) so train (seed=0) and valid
    (seed=1) sample the same underlying task; per-chunk RandomStates
    keyed on the start row make the stream re-iterable for the
    two-pass ingest. Labels threshold the signal at its expectation
    (``groups``; vals ~ U(0,2)) instead of the global median, which a
    chunked generator cannot know."""
    chunk_rows = chunk_rows or INGEST_CHUNK
    groups = f // per_group
    vals = np.random.RandomState(12345).rand(
        groups, per_group).astype(np.float32) * 2
    thresh = np.float32(groups)  # E[signal] = groups * E[U(0,2)]
    start = 0
    while start < n:
        c = min(chunk_rows, n - start)
        rs = np.random.RandomState(
            (seed * 1_000_003 + start) % (2 ** 31 - 1))
        X = np.zeros((c, f), np.float32)
        signal = np.zeros(c, np.float32)
        rows = np.arange(c)
        for g in range(groups):
            pick = rs.randint(0, per_group, c)
            X[rows, g * per_group + pick] = vals[g, pick]
            signal += vals[g, pick]
        X[rs.rand(c) < 0.1, 0] = np.nan
        yield X, (signal > thresh).astype(np.float64)
        start += c


def make_allstate_like(n, f, seed=0, per_group=128):
    """Eager wrapper over :func:`allstate_chunks`: fills ONE
    preallocated [n, f] float32 matrix chunk by chunk (transient
    overhead = one chunk, no float64 copy anywhere — the old
    whole-matrix construction loop plus label astype is gone,
    ADVICE.md medium). Peak host RSS across main() is
    (BENCH_ROWS + BENCH_VALID) * BENCH_FEATURES * 4 bytes; the
    --streaming mode drops even that by never materializing X."""
    X = np.empty((n, f), np.float32)
    y = np.empty(n, np.float64)
    row = 0
    for Xc, yc in allstate_chunks(n, f, seed=seed, per_group=per_group):
        X[row:row + len(yc)] = Xc
        y[row:row + len(yc)] = yc
        row += len(yc)
    return X, y


def _serve_bench(bst, lgb_obs, n_features):
    """The serving leg: compiled shape-bucketed prediction vs the
    eager ``Booster.predict`` baseline, over a mix of ad-hoc batch
    sizes (the daemon's actual workload shape).

    Both sides are measured steady-state: the eager baseline gets one
    untimed pass to populate its per-shape jit caches (so the compiled
    win measures the re-stack + bucketing advantage, not first-call
    compiles), and the compiled side is warmed through its power-of-two
    buckets — after which its recompile counter must stay flat (the
    TPL003 serving invariant; reported for the record)."""
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(99)
    sizes = [1, 3, 17, 33, 100, 257, 512, 777, 1024, 2000]
    reqs = [rs.randn(s, n_features).astype(np.float32) for s in sizes]
    rows = sum(sizes) * SERVE_REPEAT

    eager = lgb.Booster(model_str=bst.model_to_string())
    for X in reqs:
        eager.predict(X)                      # untimed warm pass
    t0 = time.time()
    for _ in range(SERVE_REPEAT):
        for X in reqs:
            eager.predict(X)
    dt_eager = time.time() - t0

    cf = bst.compile(max_batch_rows=4096)
    cf.warmup()
    watch = lgb_obs.RecompileWatcher()
    lat = []
    t0 = time.time()
    for _ in range(SERVE_REPEAT):
        for X in reqs:
            t = time.perf_counter()
            bst.predict(X)                    # routed through cf
            lat.append(time.perf_counter() - t)
    dt_compiled = time.time() - t0
    lat_ms = np.asarray(lat) * 1e3
    return {
        "batch_sizes": sizes,
        "repeat": SERVE_REPEAT,
        "rows_per_sec_compiled": round(rows / dt_compiled, 1),
        "rows_per_sec_eager": round(rows / dt_eager, 1),
        "speedup_vs_eager": round(dt_eager / dt_compiled, 3),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "recompiles_after_warmup": watch.delta(),
    }


def _peak_rss_bytes():
    """Linux ru_maxrss is KiB; the one number the streaming-ingest
    memory claim is checked against."""
    import resource
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


def auc(y, p):
    o = np.argsort(p)
    r = np.empty(len(p))
    r[o] = np.arange(1, len(p) + 1)
    npos = y.sum()
    nneg = len(y) - npos
    return (r[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)


def main():
    import jax
    plat = os.environ.get("BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and plat != "cpu":
        raise RuntimeError(
            f"bench.py measures the TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). BENCH_PLATFORM=cpu "
            "runs the tiny CPU smoke explicitly.")
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs as lgb_obs
    from lightgbm_tpu.utils.timer import Timer as _PhaseTimer

    # stdout belongs to the ONE JSON result line (driver contract,
    # tests/test_bench_contract.py). The package logger defaults to
    # stdout, and e.g. the native fastparse build-failure warning would
    # land there — route all library logging to stderr for the run.
    import logging
    _blog = logging.getLogger("lightgbm_tpu_bench")
    if not _blog.handlers:
        h = logging.StreamHandler(sys.stderr)
        h.setFormatter(logging.Formatter("%(message)s"))
        _blog.addHandler(h)
        _blog.setLevel(logging.INFO)
    lgb.register_logger(_blog)

    # run telemetry rides along in the one JSON line: phase wall times
    # (host-side Timer, ~µs/phase against ~100ms iterations), jit
    # recompile count and HBM gauges — the numbers the perf ROADMAP
    # items report against (docs/OBSERVABILITY.md)
    _PhaseTimer.enable()
    recompile_watch = lgb_obs.RecompileWatcher()

    valid_chunks = None
    Xv = yv = None
    if _STREAMING:
        # chunked two-pass ingestion (lightgbm_tpu/data/): the dense
        # float train matrix never exists; the valid set is predicted
        # chunk-by-chunk below, so it is never materialized either
        from lightgbm_tpu.data import GeneratorChunkSource
        gen = allstate_chunks if _ALLSTATE else higgs_chunks

        def train_chunks():
            return gen(N_ROWS, N_FEATURES, seed=0,
                       chunk_rows=INGEST_CHUNK)

        def valid_chunks():
            return gen(N_VALID, N_FEATURES, seed=1,
                       chunk_rows=INGEST_CHUNK)

        src = GeneratorChunkSource(train_chunks, num_rows=N_ROWS,
                                   num_features=N_FEATURES)
        ds = lgb.Dataset(src, params={"max_bin": MAX_BIN,
                                      "ingest_chunk_rows": INGEST_CHUNK})
        ds.construct()
    elif _ALLSTATE:
        # train/valid generated separately so peak host RSS is
        # (N_ROWS + N_VALID)·f·4 bytes — the slice-copy pattern below
        # would transiently hold ~2.6x that (X + Xtr + Xv), ~89 GB at
        # the default preset
        Xtr, ytr = make_allstate_like(N_ROWS, N_FEATURES, seed=0)
        Xv, yv = make_allstate_like(N_VALID, N_FEATURES, seed=1)
        ds = lgb.Dataset(Xtr, label=ytr, params={"max_bin": MAX_BIN})
        ds.construct()
        del Xtr
    else:
        # single generation + split: this exact layout is what
        # ORACLE_AUC was measured against — don't change it
        X, y = make_higgs_like(N_ROWS + N_VALID, N_FEATURES)
        # slice-copies so `del X` actually frees the big base array
        Xv, yv = X[N_ROWS:].copy(), y[N_ROWS:].copy()
        Xtr, ytr = X[:N_ROWS].copy(), y[:N_ROWS]
        del X
        ds = lgb.Dataset(Xtr, label=ytr, params={"max_bin": MAX_BIN})
        ds.construct()
        del Xtr

    bst = lgb.Booster(
        params={
            "objective": "binary",
            "num_leaves": NUM_LEAVES,
            "max_bin": MAX_BIN,
            "learning_rate": 0.1,
            "verbosity": -1,
            # BENCH_RESIDENCY=device: lay the binned rows directly
            # into their mesh slices and free the host copy
            # (parallel/placement.py, docs/SHARDING.md); the
            # host_binned_bytes fields below measure the claim
            "shard_residency": os.environ.get("BENCH_RESIDENCY",
                                              "auto"),
            # BENCH_SPLIT_SEARCH=sharded: reduce-scatter split search
            "split_search": os.environ.get("BENCH_SPLIT_SEARCH",
                                           "gathered"),
        },
        train_set=ds)

    for _ in range(WARMUP):
        bst._engine.train_one_iter()
    bst._engine.score.block_until_ready()

    t0 = time.time()
    for _ in range(ITERS):
        bst._engine.train_one_iter()
    bst._engine.score.block_until_ready()
    dt = time.time() - t0

    # accuracy leg: continue to AUC_ITERS rounds, then held-out AUC
    result_auc = None
    trained = WARMUP + ITERS
    if AUC_ITERS > trained:
        for _ in range(AUC_ITERS - trained):
            bst._engine.train_one_iter()
        if _STREAMING:
            # valid set predicted chunk-by-chunk: only predictions and
            # labels (8 bytes/row each) are ever held, never the rows
            preds, labels = [], []
            for Xc, yc in valid_chunks():
                preds.append(bst.predict(Xc))
                labels.append(yc)
            result_auc = float(auc(np.concatenate(labels),
                                   np.concatenate(preds)))
        else:
            result_auc = float(auc(yv, bst.predict(Xv)))

    iters_per_sec = ITERS / dt
    # linear rescale to the preset's full row count (histogram work is
    # O(rows); the factor is 1 at the default shape, so normally this
    # is a direct measurement)
    full_rows = ALLSTATE_ROWS if _ALLSTATE else HIGGS_ROWS
    base = ALLSTATE_BASELINE_ITERS_PER_SEC if _ALLSTATE \
        else BASELINE_ITERS_PER_SEC
    iters_per_sec_full = iters_per_sec * (N_ROWS / full_rows)
    scale_note = "" if N_ROWS == full_rows \
        else f" (rescaled to {full_rows} rows)"
    shape_name = "Allstate-shaped" if _ALLSTATE else "Higgs-shaped"
    result = {
        "metric": f"boosting iters/sec, {shape_name} "
                  f"{N_ROWS}x{N_FEATURES}"
                  f"{scale_note}, {NUM_LEAVES} leaves, "
                  f"{MAX_BIN} bins, backend={jax.default_backend()}"
                  + (", streaming-ingest" if _STREAMING else ""),
        "value": round(iters_per_sec_full, 4),
        "unit": "iters/sec",
        "vs_baseline": round(iters_per_sec_full / base, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": cache_dir,
        "peak_rss_bytes": _peak_rss_bytes(),
    }
    if _STREAMING:
        result["ingest"] = dict(ds._ingest_stats)
    # per-host resident binned bytes, measured AFTER construct+train:
    # the ingest stats record the shard's footprint at construct time;
    # this is what is still host-resident now — 0 under
    # shard_residency=device (the host copy was freed after the mesh
    # upload), so the "no host holds the global binned matrix" claim
    # is a measured number, not an assertion
    result["shard_residency"] = getattr(bst._engine, "_residency",
                                        "host")
    # the engine-kept gauge, not ds._bins: an EFB run under device
    # residency frees the Dataset copy but keeps the bundled host
    # matrix resident, and the gauge tracks THAT (gbdt.py publishes it
    # in every residency branch)
    from lightgbm_tpu.obs.registry import registry
    result["host_binned_bytes"] = int(
        registry.gauge("host_binned_bytes").value)
    if bst._engine.bundle is not None:
        b = bst._engine.bundle
        result["efb_bundles"] = len(b.groups)
        result["hbm_bin_bytes"] = int(bst._engine.bins_T.size
                                      * bst._engine.bins_T.dtype.itemsize)
    phases = _PhaseTimer.snapshot()
    top_phases = sorted(phases.items(), key=lambda kv: -kv[1]["total"])[:8]
    result["telemetry"] = {
        "recompiles": recompile_watch.delta(),
        "phases": {label: {"total": round(v["total"], 4),
                           "count": int(v["count"])}
                   for label, v in top_phases},
        "hbm": lgb_obs.device_memory_stats(),
    }
    # in-band XLA cost attribution (obs/cost.py; docs/ROOFLINE.md):
    # every first compile per signature recorded flops/bytes and the
    # cost-model-optimal ms at the device peaks, so each bench run
    # carries its own roofline denominators
    from lightgbm_tpu.obs.cost import drain_compile_events
    result["telemetry"]["xla_cost"] = [
        {k: ev.get(k) for k in ("entry", "flops",
                                "bytes_accessed", "wall_ms",
                                "optimal_ms", "device_kind")}
        for ev in drain_compile_events()]
    if _SERVE:
        result["serve"] = _serve_bench(bst, lgb_obs, N_FEATURES)
    if result_auc is not None:
        result["auc"] = round(result_auc, 6)
        # the oracle was measured against the exact eager single-stream
        # layout; streaming draws a different (per-chunk-seeded) stream
        oracle_config = (not _STREAMING and N_FEATURES == 28
                         and NUM_LEAVES == 255
                         and MAX_BIN == 255 and N_VALID == 524_288
                         and AUC_ITERS == 50)
        if oracle_config and N_ROWS in ORACLE_AUC:
            result["auc_ref"] = ORACLE_AUC[N_ROWS]
    print(json.dumps(result))


if __name__ == "__main__":
    main()
