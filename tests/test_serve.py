"""Production inference serving (lightgbm_tpu/serve/, docs/SERVING.md).

Layers under test:

1. Forest compiler (serve/compile.py): compiled-vs-eager prediction
   equivalence across every tree type (numeric, categorical,
   linear-tree, multiclass raw scores), power-of-two bucketing, the
   recompile-counter-flat-after-warmup contract (TPL003's serving
   invariant), and the donated hot-swap upload.
2. Micro-batcher (serve/batcher.py): request coalescing, concurrent
   submits, backpressure, hot swap with zero dropped in-flight
   requests.
3. Daemon (serve/daemon.py): the JSON-lines protocol as a pure
   function (fast), the jax-free CLI parse contract (subprocess, like
   `lint`), serve telemetry summarization + the stats CLI row, and —
   `slow`-marked because they spin real sockets/worlds — the live
   socket server, watch-dir hot swap, a launch-supervised replica
   chaos kill, and the bench.py --serve acceptance record.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs import RecompileWatcher  # noqa: E402
from lightgbm_tpu.serve.batcher import (  # noqa: E402
    MicroBatcher, QueueFullError)
from lightgbm_tpu.serve.compile import (  # noqa: E402
    bucket_rows, compile_forest)

from tests._mp_utils import REPO_DIR, free_port, kill_group  # noqa: E402
from tests.conftest import make_synthetic_binary  # noqa: E402

RS = np.random.RandomState(31)


def _train(params, X, y, rounds=5, **ds_kwargs):
    ds = lgb.Dataset(X, label=y,
                     params={"verbosity": -1,
                             **ds_kwargs.pop("ds_params", {})},
                     **ds_kwargs)
    return lgb.train({"verbosity": -1, **params}, ds,
                     num_boost_round=rounds)


@pytest.fixture(scope="module")
def binary_model():
    X, y = make_synthetic_binary(n=600, f=8, seed=3)
    return _train({"objective": "binary", "num_leaves": 15}, X, y), X


@pytest.fixture(scope="module")
def multiclass_model():
    X, _ = make_synthetic_binary(n=500, f=6, seed=5)
    y = (np.abs(X[:, 0]) + X[:, 1] > 0.6).astype(int) \
        + (X[:, 2] > 0.5).astype(int)
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 7}, X, y.astype(np.float64), rounds=4)
    return bst, X


@pytest.fixture(scope="module")
def categorical_model():
    n = 500
    Xn = RS.randn(n, 3)
    cat = RS.randint(0, 6, n).astype(np.float64)
    X = np.column_stack([Xn, cat])
    y = ((Xn[:, 0] > 0) ^ (cat >= 3)).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1},
                     categorical_feature=[3])
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, num_boost_round=5)
    return bst, X


@pytest.fixture(scope="module")
def linear_model():
    X, _ = make_synthetic_binary(n=500, f=5, seed=11)
    y = X @ RS.randn(5) + 0.05 * RS.randn(500)
    bst = _train({"objective": "regression", "num_leaves": 7,
                  "linear_tree": True}, X, y)
    return bst, X


def _fresh(bst):
    """An uncompiled clone: the eager baseline path."""
    return lgb.Booster(model_str=bst.model_to_string())


# ---------------------------------------------------------------------
# 1. forest compiler
# ---------------------------------------------------------------------

def test_bucket_rows():
    assert bucket_rows(1) == 16
    assert bucket_rows(16) == 16
    assert bucket_rows(17) == 32
    assert bucket_rows(1000) == 1024
    assert bucket_rows(10 ** 9, max_bucket=4096) == 4096
    assert bucket_rows(5, min_bucket=1, max_bucket=8) == 8
    with pytest.raises(ValueError):
        bucket_rows(0)


@pytest.mark.parametrize("fixture,raw", [
    ("binary_model", False), ("binary_model", True),
    ("multiclass_model", False), ("multiclass_model", True),
    ("categorical_model", False), ("linear_model", False),
])
def test_compiled_matches_eager(fixture, raw, request):
    """Equivalence across tree types: the compiled bucketed program
    and the eager library path answer identically (same f32 ops, same
    order) for ad-hoc batch sizes, including padded ones."""
    bst, X = request.getfixturevalue(fixture)
    eager = _fresh(bst)
    cf = compile_forest(bst, max_batch_rows=256)
    for n in (1, 7, 33, 123):
        Xq = X[:n]
        want = eager.predict(Xq, raw_score=raw)
        got = cf.predict(Xq, raw_score=raw)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-9,
                                   err_msg=f"{fixture} n={n} raw={raw}")


def test_booster_predict_routes_through_compiled(binary_model):
    bst, X = binary_model
    eager_pred = _fresh(bst).predict(X[:50])
    cf = bst.compile(max_batch_rows=256)
    assert bst._compiled_forest is cf
    np.testing.assert_allclose(bst.predict(X[:50]), eager_pred,
                               rtol=0, atol=1e-9)
    # chunking: a request larger than max_batch_rows splits cleanly
    np.testing.assert_allclose(bst.predict(X[:300]),
                               _fresh(bst).predict(X[:300]),
                               rtol=0, atol=1e-9)


def test_recompile_counter_flat_after_warmup(binary_model):
    """THE serving contract: after bucket warmup, 10 varied batch
    sizes cause ZERO recompiles of any registered jit entry point."""
    bst, X = binary_model
    cf = bst.compile(max_batch_rows=1024)
    cf.warmup()
    watch = RecompileWatcher()
    for n in (1, 3, 17, 100, 255, 256, 257, 512, 700, 1000):
        Xq = RS.randn(n, X.shape[1])
        bst.predict(Xq)          # routed through the compiled forest
        cf.predict_raw(Xq.astype(np.float32))
    assert watch.delta() == 0, (
        "a batch size recompiled after warmup — the shape-bucket "
        "invariant is broken")


def test_compiled_bypassed_when_booster_grows():
    """Training past a compilation silently bypasses it: predict must
    answer from ALL trees via the eager path, never from the stale
    compiled forest."""
    X, y = make_synthetic_binary(n=400, f=6, seed=9)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=3)
    cf = bst.compile()
    before = bst.predict(X[:20])
    np.testing.assert_allclose(before, _fresh(bst).predict(X[:20]),
                               rtol=0, atol=1e-9)
    bst.update()                       # grow one more tree in place
    assert bst.num_trees() == 4
    assert not cf.matches(0, bst.num_trees(), bst.num_trees())
    # explicit full range: must answer from all 4 trees via the eager
    # fallback, never from the stale 3-tree compilation
    full = bst.predict(X[:20], num_iteration=4)
    np.testing.assert_allclose(
        full, _fresh(bst).predict(X[:20], num_iteration=4),
        rtol=0, atol=1e-9)
    assert not np.allclose(full, before), \
        "the extra tree changed nothing — bypass not actually proven"


def test_compile_respects_num_iteration(binary_model):
    bst, X = binary_model
    cf = compile_forest(bst, num_iteration=2)
    want = _fresh(bst).predict(X[:40], num_iteration=2)
    np.testing.assert_allclose(cf.predict(X[:40]), want,
                               rtol=0, atol=1e-9)
    # and the routed path only engages for a matching range
    bst.compile(num_iteration=2)
    np.testing.assert_allclose(
        bst.predict(X[:40], num_iteration=2), want, rtol=0, atol=1e-9)
    np.testing.assert_allclose(
        bst.predict(X[:40]), _fresh(bst).predict(X[:40]),
        rtol=0, atol=1e-9)


def test_feature_count_mismatch_raises(binary_model):
    bst, X = binary_model
    cf = compile_forest(bst)
    with pytest.raises(lgb.LightGBMError, match="number of features"):
        cf.predict_raw(np.zeros((4, X.shape[1] + 2), np.float32))


def test_hot_swap_donated_upload(binary_model):
    """compile_forest(reuse=...) adopts the old forest's buffers when
    layouts match and must answer with the NEW model either way."""
    bst, X = binary_model
    cf_a = compile_forest(bst, max_batch_rows=256)
    a_pred = cf_a.predict(X[:30])
    # same shape config -> same stacked layout -> donated upload
    y2 = (X[:, 1] > 0).astype(np.float64)
    bst_b = _train({"objective": "binary", "num_leaves": 15}, X, y2)
    cf_b = compile_forest(bst_b, max_batch_rows=256, reuse=cf_a)
    assert cf_a._stacked is None, "donated forest must be dead"
    np.testing.assert_allclose(cf_b.predict(X[:30]),
                               _fresh(bst_b).predict(X[:30]),
                               rtol=0, atol=1e-9)
    assert not np.allclose(cf_b.predict(X[:30]), a_pred)
    # different layout (more leaves) -> plain transfer, same contract
    bst_c = _train({"objective": "binary", "num_leaves": 31}, X, y2,
                   rounds=7)
    cf_c = compile_forest(bst_c, max_batch_rows=256, reuse=cf_b)
    np.testing.assert_allclose(cf_c.predict(X[:30]),
                               _fresh(bst_c).predict(X[:30]),
                               rtol=0, atol=1e-9)


def test_dead_forest_raises_and_booster_falls_back(binary_model):
    """A forest whose buffers a newer compilation took over must raise
    on direct use — and a booster still caching it must fall back to
    the eager path, never serve donated garbage or silent zeros."""
    bst, X = binary_model
    want = _fresh(bst).predict(X[:10])
    cf_old = bst.compile(max_batch_rows=256)
    y2 = (X[:, 1] > 0).astype(np.float64)
    bst_b = _train({"objective": "binary", "num_leaves": 15}, X, y2)
    compile_forest(bst_b, max_batch_rows=256, reuse=cf_old)
    assert cf_old._dead
    with pytest.raises(RuntimeError, match="donated"):
        cf_old.predict_raw(X[:4].astype(np.float32))
    assert not cf_old.matches(cf_old.lo, cf_old.hi, cf_old.total_trees)
    np.testing.assert_allclose(bst.predict(X[:10]), want,
                               rtol=0, atol=1e-9)


def test_zero_row_predict(binary_model):
    bst, X = binary_model
    cf = compile_forest(bst, max_batch_rows=256)
    out = cf.predict_raw(np.empty((0, X.shape[1]), np.float32))
    assert out.shape == (0, 1)
    bst.compile(max_batch_rows=256)
    assert bst.predict(np.empty((0, X.shape[1]))).shape == (0,)


# ---------------------------------------------------------------------
# 2. micro-batcher
# ---------------------------------------------------------------------

def test_batcher_resolves_concurrent_requests(binary_model):
    bst, X = binary_model
    cf = compile_forest(bst, max_batch_rows=256)
    cf.warmup()
    mb = MicroBatcher(cf, batch_window_ms=2.0, max_batch_rows=256)
    try:
        sizes = [1, 5, 9, 17, 3, 40]
        futs = {}
        for i, n in enumerate(sizes):
            futs[i] = (mb.submit(X[i: i + n]), X[i: i + n])
        for i, (fut, Xq) in futs.items():
            got = fut.result(timeout=30)
            np.testing.assert_allclose(
                got, cf.predict_raw(Xq), rtol=0, atol=1e-9)
        st = mb.stats()
        assert st["requests_total"] == len(sizes)
        assert st["rows_total"] == sum(sizes)
        assert st["queue_depth_rows"] == 0
        assert st["p50_ms"] is not None
    finally:
        mb.close()


def test_batcher_backpressure(binary_model):
    bst, X = binary_model
    cf = compile_forest(bst, max_batch_rows=256)

    class _Slow:
        n_features = cf.n_features

        def __init__(self):
            self.release = threading.Event()

        def predict_raw(self, Xq):
            self.release.wait(30)
            return cf.predict_raw(Xq)

    slow = _Slow()
    # budget 32: the in-flight batch (8 rows, still pending until it
    # finishes) + one queued 16-row request fit; the next 16 do not
    mb = MicroBatcher(slow, batch_window_ms=0.0, max_batch_rows=8,
                      queue_max_rows=32)
    try:
        first = mb.submit(X[:8])      # occupies the worker
        time.sleep(0.05)
        second = mb.submit(X[:16])    # queued within budget
        with pytest.raises(QueueFullError):
            mb.submit(X[:16])
        assert mb.stats()["rejected_total"] == 1
        slow.release.set()
        first.result(timeout=30)
        second.result(timeout=30)
    finally:
        slow.release.set()
        mb.close()


def test_batcher_feature_mismatch(binary_model):
    bst, _ = binary_model
    cf = compile_forest(bst)
    mb = MicroBatcher(cf)
    try:
        with pytest.raises(ValueError, match="features"):
            mb.submit(np.zeros((2, cf.n_features + 1), np.float32))
    finally:
        mb.close()


def test_hot_swap_zero_dropped_requests(binary_model):
    """Requests in flight across a swap ALL resolve; post-swap answers
    come from the new model."""
    bst, X = binary_model
    cf_a = compile_forest(bst, max_batch_rows=256)
    cf_a.warmup(64)
    y2 = (X[:, 1] > 0).astype(np.float64)
    bst_b = _train({"objective": "binary", "num_leaves": 15}, X, y2)
    cf_b = compile_forest(bst_b, max_batch_rows=256)
    cf_b.warmup(64)
    a_ref = cf_a.predict_raw(X[:4])
    b_ref = cf_b.predict_raw(X[:4])
    assert not np.allclose(a_ref, b_ref)

    mb = MicroBatcher(cf_a, batch_window_ms=0.5, max_batch_rows=64)
    results = []
    res_lock = threading.Lock()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                fut = mb.submit(X[:4])
            except QueueFullError:
                continue
            out = fut.result(timeout=30)
            with res_lock:
                results.append(out)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        mb.swap(cf_b)
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        mb.close()
    assert results, "hammer threads produced nothing"
    matched = 0
    for out in results:
        is_a = np.allclose(out, a_ref, atol=1e-9)
        is_b = np.allclose(out, b_ref, atol=1e-9)
        assert is_a or is_b, "a request resolved to NEITHER model"
        matched += is_b
    assert matched, "no request ever answered from the swapped model"
    # the tail of the stream must be the new model
    np.testing.assert_allclose(results[-1], b_ref, rtol=0, atol=1e-9)
    assert mb.stats()["swaps_total"] == 1


# ---------------------------------------------------------------------
# 3. daemon protocol (pure-function fast tests)
# ---------------------------------------------------------------------

def _make_state(bst, tmp_path=None, telemetry=None):
    from lightgbm_tpu.serve.daemon import ServeState
    cf = compile_forest(bst, max_batch_rows=256)
    cf.warmup(64)
    mb = MicroBatcher(cf, batch_window_ms=0.5, max_batch_rows=256)
    state = ServeState(mb, cf.model_id, "test-model",
                       telemetry_path=telemetry)
    return state, cf


def test_handle_request_protocol(binary_model):
    from lightgbm_tpu.serve.daemon import handle_request
    bst, X = binary_model
    state, cf = _make_state(bst)
    try:
        r = handle_request({"cmd": "ping"}, state)
        assert r["ok"] and r["model"] == cf.model_id
        assert r["pid"] == os.getpid()

        r = handle_request({"rows": X[:3].tolist()}, state)
        np.testing.assert_allclose(r["predictions"],
                                   _fresh(bst).predict(X[:3]),
                                   rtol=0, atol=1e-9)
        assert r["n"] == 3 and r["model"] == cf.model_id

        r = handle_request({"features": X[0].tolist()}, state)
        assert len(r["predictions"]) == 1

        r = handle_request({"rows": X[:3].tolist(), "raw": True},
                           state)
        np.testing.assert_allclose(
            r["predictions"],
            _fresh(bst).predict(X[:3], raw_score=True),
            rtol=0, atol=1e-9)

        st = handle_request({"cmd": "stats"}, state)
        assert st["ok"] and st["requests_total"] >= 3
        assert "qps" in st and "hbm" in st and "recompiles" in st

        assert "error" in handle_request({"cmd": "nope"}, state)
        assert "error" in handle_request({"rows": "zzz"}, state)
        assert "error" in handle_request({"rows": []}, state)
        assert "error" in handle_request(["not", "a", "dict"], state)
        assert "error" in handle_request({}, state)

        r = handle_request({"cmd": "shutdown"}, state)
        assert r["shutting_down"] and state.shutdown_event.is_set()
    finally:
        state.close()


def test_handle_request_overload_maps_to_error(binary_model):
    from lightgbm_tpu.serve.daemon import handle_request
    bst, X = binary_model
    state, _ = _make_state(bst)
    try:
        def full(_rows, trace=None):
            raise QueueFullError("serve queue full: test")
        state.batcher.submit = full
        r = handle_request({"rows": X[:2].tolist()}, state)
        assert r.get("overloaded") and "error" in r
    finally:
        state.close()


def test_watcher_poll_swaps_and_survives_corrupt_model(
        binary_model, tmp_path):
    from lightgbm_tpu.serve.daemon import _Watcher
    bst, X = binary_model
    state, cf = _make_state(bst)
    try:
        model_a = str(tmp_path / "a.txt")
        bst.save_model(model_a)
        from lightgbm_tpu.serve.daemon import _artifact_key
        watcher = _Watcher(
            state, str(tmp_path), 0.1,
            dict(num_iteration=-1, min_bucket=16, max_batch_rows=256),
            _artifact_key(model_a), 64)
        assert watcher.poll_once() is False     # nothing new

        y2 = (X[:, 1] > 0).astype(np.float64)
        bst_b = _train({"objective": "binary", "num_leaves": 15},
                       X, y2)
        time.sleep(0.05)
        bst_b.save_model(str(tmp_path / "b.txt"))
        os.utime(str(tmp_path / "b.txt"),
                 (time.time() + 2, time.time() + 2))
        assert watcher.poll_once() is True
        assert state.model_id() == \
            compile_forest(bst_b).model_id
        fut = state.batcher.submit(X[:4].astype(np.float32))
        np.testing.assert_allclose(
            fut.result(timeout=30),
            _fresh(bst_b).predict(X[:4], raw_score=True)[:, None],
            rtol=0, atol=1e-9)

        # corrupt artifact: swap fails, old model keeps serving
        with open(tmp_path / "c.txt", "w") as fh:
            fh.write("this is not a model\n")
        os.utime(str(tmp_path / "c.txt"),
                 (time.time() + 4, time.time() + 4))
        before = state.model_id()
        assert watcher.poll_once() is False
        assert state.model_id() == before
        assert state.stats()["swap_failures"] == 1
    finally:
        state.close()


def test_serve_telemetry_and_stats_cli(binary_model, tmp_path):
    from lightgbm_tpu.obs import render_stats_table, summarize_events
    bst, X = binary_model
    telem = str(tmp_path / "serve.jsonl")
    state, cf = _make_state(bst, telemetry=telem)
    try:
        from lightgbm_tpu.serve.daemon import handle_request
        handle_request({"rows": X[:5].tolist()}, state)
        state.emit_serve_event()
        handle_request({"rows": X[:2].tolist()}, state)
        state.emit_serve_event()
    finally:
        state.close()
    summ = summarize_events(telem)
    assert summ["iterations"] == 0
    assert summ["serve_events"] == 2
    assert summ["serve"]["requests_total"] == 2
    assert summ["serve"]["rows_total"] == 7
    assert summ["serve"]["model"] == cf.model_id
    table = render_stats_table(summ)
    assert "serve" in table and cf.model_id in table
    # the stats CLI accepts a serve-only stream (no iteration events)
    from lightgbm_tpu.cli import main as cli_main
    assert cli_main(["stats", telem]) == 0
    assert cli_main(["stats", str(tmp_path / "missing.jsonl")]) == 1


def test_serve_cli_is_jax_free_until_model_load(tmp_path):
    """`python -m lightgbm_tpu serve --help` and bad-path errors must
    not import jax (the lint/launch contract, subprocess-proved)."""
    code = (
        "import sys\n"
        "from lightgbm_tpu.serve.daemon import main\n"
        "rc = main(['--help'])\n"
        "assert rc == 0, rc\n"
        "rc = main(['/nonexistent/model.txt'])\n"
        "assert rc == 1, rc\n"
        "assert 'jax' not in sys.modules, 'serve CLI imported jax!'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_DIR,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")
    assert "usage: python -m lightgbm_tpu serve" in proc.stdout


# ---------------------------------------------------------------------
# 4. live socket / supervised-replica tests (slow: real sockets)
# ---------------------------------------------------------------------

def _rpc(fh, obj):
    fh.write(json.dumps(obj) + "\n")
    fh.flush()
    line = fh.readline()
    assert line, "daemon closed the connection unexpectedly"
    return json.loads(line)


def _read_ready(proc, tries=200):
    """Skim the daemon's stdout for the serve_ready JSON line (library
    log lines may precede it)."""
    for _ in range(tries):
        line = proc.stdout.readline()
        if not line:
            raise AssertionError("daemon exited before serve_ready")
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if obj.get("event") == "serve_ready":
            return obj
    raise AssertionError("no serve_ready line in daemon output")


def _connect(port, timeout=60.0):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            s = socket.create_connection(("127.0.0.1", port),
                                         timeout=10)
            return s, s.makefile("rw")
        except OSError as e:
            last = e
            time.sleep(0.2)
    raise AssertionError(f"could not connect to daemon on :{port}: "
                         f"{last}")


@pytest.mark.slow
def test_daemon_socket_end_to_end(binary_model, tmp_path):
    bst, X = binary_model
    model = str(tmp_path / "model.txt")
    bst.save_model(model)
    telem = str(tmp_path / "serve.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve", model,
         "--port", "0", "--watch-dir", str(tmp_path),
         "--telemetry", telem, "--stats-interval", "0.5",
         "--watch-interval", "0.2", "--warmup-rows", "64",
         "--max-batch-rows", "256"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO_DIR, start_new_session=True)
    try:
        ready = _read_ready(proc)
        s, fh = _connect(ready["port"])
        try:
            r = _rpc(fh, {"rows": X[:5].tolist()})
            np.testing.assert_allclose(r["predictions"],
                                       _fresh(bst).predict(X[:5]),
                                       rtol=0, atol=1e-9)
            assert _rpc(fh, {"cmd": "ping"})["ok"]

            # hot swap through the watch dir (atomic save_model)
            y2 = (X[:, 1] > 0).astype(np.float64)
            bst_b = _train({"objective": "binary", "num_leaves": 15},
                           X, y2)
            time.sleep(0.2)
            bst_b.save_model(str(tmp_path / "model_v2.txt"))
            os.utime(str(tmp_path / "model_v2.txt"),
                     (time.time() + 2, time.time() + 2))
            want_b = _fresh(bst_b).predict(X[:5])
            deadline = time.time() + 60
            swapped = False
            while time.time() < deadline and not swapped:
                r = _rpc(fh, {"rows": X[:5].tolist()})
                swapped = np.allclose(r["predictions"], want_b,
                                      atol=1e-9)
                if not swapped:
                    time.sleep(0.2)
            assert swapped, "daemon never hot-swapped to model_v2"

            st = _rpc(fh, {"cmd": "stats"})
            assert st["swaps_total"] == 1
            r = _rpc(fh, {"cmd": "shutdown"})
            assert r["shutting_down"]
        finally:
            s.close()
        assert proc.wait(timeout=60) == 0
        with open(telem) as fhh:
            events = [json.loads(ln) for ln in fhh if ln.strip()]
        assert any(e.get("event") == "serve" and e.get("swaps_total")
                   for e in events)
    finally:
        if proc.poll() is None:
            kill_group(proc)


@pytest.mark.slow
def test_replica_kill_under_launch_recovers(binary_model, tmp_path):
    """Chaos: two serve replicas under the elastic supervisor; SIGKILL
    one -> the supervisor restarts the world -> both ports answer
    again (docs/SERVING.md multi-replica operation)."""
    bst, X = binary_model
    model = str(tmp_path / "model.txt")
    bst.save_model(model)
    base = free_port()
    sup = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "launch", "2",
         "--max-restarts", "2", "--grace", "1",
         "--log-dir", str(tmp_path), "--",
         sys.executable, "-m", "lightgbm_tpu", "serve", model,
         "--port", str(base), "--warmup-rows", "64",
         "--max-batch-rows", "256"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=REPO_DIR, start_new_session=True)
    want = _fresh(bst).predict(X[:3])
    try:
        pids = {}
        for rank in (0, 1):
            s, fh = _connect(base + rank, timeout=120)
            r = _rpc(fh, {"cmd": "ping"})
            pids[rank] = r["pid"]
            r = _rpc(fh, {"rows": X[:3].tolist()})
            np.testing.assert_allclose(r["predictions"], want,
                                       rtol=0, atol=1e-9)
            s.close()

        os.kill(pids[1], signal.SIGKILL)      # chaos: kill replica 1

        # the supervisor tears the world down and relaunches; the old
        # connections die, fresh ones must eventually answer with NEW
        # pids on the same ports
        deadline = time.time() + 180
        new_pid = None
        while time.time() < deadline:
            try:
                s, fh = _connect(base + 1, timeout=20)
                r = _rpc(fh, {"cmd": "ping"})
                if r.get("pid") not in (None, pids[1]):
                    new_pid = r["pid"]
                    r = _rpc(fh, {"rows": X[:3].tolist()})
                    np.testing.assert_allclose(
                        r["predictions"], want, rtol=0, atol=1e-9)
                    s.close()
                    break
                s.close()
            except (AssertionError, OSError, ValueError):
                pass
            time.sleep(0.5)
        assert new_pid is not None, (
            "replica 1 never came back under the supervisor")
        # replica 0 was also restarted and serves
        s, fh = _connect(base, timeout=120)
        r = _rpc(fh, {"rows": X[:3].tolist()})
        np.testing.assert_allclose(r["predictions"], want,
                                   rtol=0, atol=1e-9)
        s.close()
    finally:
        kill_group(sup)
        try:
            sup.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


@pytest.mark.slow
def test_bench_serve_mode_contract(tmp_path):
    """Acceptance: bench.py --serve emits the serve block with
    compiled rows/sec >= the eager baseline and p50/p99 present."""
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "BENCH_PLATFORM": "cpu", "BENCH_ROWS": "4000",
           "BENCH_VALID": "1000", "BENCH_ITERS": "2",
           "BENCH_AUC_ITERS": "5", "BENCH_LEAVES": "15",
           "BENCH_BINS": "31", "BENCH_SERVE": "1",
           "BENCH_DEADLINE": "700"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_DIR, "bench.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    serve = rec["serve"]
    assert serve["recompiles_after_warmup"] == 0
    assert serve["p50_ms"] > 0 and serve["p99_ms"] >= serve["p50_ms"]
    assert serve["rows_per_sec_compiled"] >= \
        serve["rows_per_sec_eager"], serve
