"""Continuous train -> publish -> serve lifecycle (ISSUE 13,
docs/PIPELINE.md).

Layers under test:

1. Atomic publisher (resilience/publisher.py): manifest-first
   publication, torn-artifact detection, jittered retry/backoff with
   the publish_torn chaos kind, newest-validated lookup.
2. Warm start: Booster.refit parity with the reference
   FitByExistingTree contract (structures unchanged, leaf values
   re-derived, shifted labels move eval the right direction, fused
   and eager trained forests), the refit-side non-finite guard
   (refit_nan chaos x all three policies), and init_model continued
   training on FRESH data through the PR-7 chunk sources — including
   checkpoint resume finishing at init + num_boost_round.
3. Load shedding (serve/batcher.py SheddingError): queue-depth and
   latency-budget sheds, the daemon's typed {"shed": true} reply.
4. Watch-dir poller resilience: a torn/partial artifact is skipped
   with a swap_failure fault event and RETRIED next poll.
5. Supervisor: RestartBudget sliding window + backoff, one-shot
   serve_kill stripping, and (slow) per-replica fleet restart,
   daemon graceful shutdown, and the full chaos pipeline e2e.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.resilience.elastic import (  # noqa: E402
    RestartBudget, strip_one_shot_faults, supervise)
from lightgbm_tpu.resilience.publisher import (  # noqa: E402
    PublishError, latest_manifest, load_manifest, manifest_path,
    publish_model, validate_artifact)

from tests._mp_utils import REPO_DIR, free_port, kill_group  # noqa: E402
from tests.conftest import make_synthetic_binary  # noqa: E402


def _logloss(p, y):
    p = np.clip(np.asarray(p), 1e-9, 1 - 1e-9)
    y = np.asarray(y)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


def _train(params, X, y, rounds=5, **kwargs):
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    return lgb.train({"verbosity": -1, **params}, ds,
                     num_boost_round=rounds, **kwargs)


@pytest.fixture(scope="module")
def binary_model():
    X, y = make_synthetic_binary(n=900, f=8)
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y)
    return bst, X, y


# ---------------------------------------------------------------------
# 1. atomic publisher
# ---------------------------------------------------------------------

def test_publish_roundtrip_and_validation(binary_model, tmp_path):
    bst, X, y = binary_model
    manifest = publish_model(bst, str(tmp_path), "model_g0000.txt",
                             metadata={"generation": 0,
                                       "train_auc": 0.9})
    target = str(tmp_path / "model_g0000.txt")
    assert os.path.exists(target)
    assert os.path.exists(manifest_path(target))
    assert manifest["generation"] == 0
    # the published bytes validate and round-trip to a live model
    assert validate_artifact(target)["sha256"] == manifest["sha256"]
    reloaded = lgb.Booster(model_file=target)
    np.testing.assert_allclose(reloaded.predict(X[:16]),
                               bst.predict(X[:16]), atol=1e-9)
    # newest-validated lookup
    got = latest_manifest(str(tmp_path))
    assert got is not None and got[0] == target
    assert got[1]["sha256"] == manifest["sha256"]


def test_torn_artifact_fails_validation(binary_model, tmp_path):
    bst, _, _ = binary_model
    publish_model(bst, str(tmp_path), "m.txt")
    target = str(tmp_path / "m.txt")
    data = open(target, "rb").read()
    # tear it the way a dying non-atomic writer would: partial prefix
    with open(target, "wb") as fh:
        fh.write(data[: len(data) // 2])
    with pytest.raises(PublishError, match="torn or partial"):
        validate_artifact(target)
    # latest_manifest skips the torn one instead of serving it
    assert latest_manifest(str(tmp_path)) is None
    # unmanaged artifacts (no sidecar) stay legacy: None, no raise
    plain = str(tmp_path / "plain.txt")
    with open(plain, "w") as fh:
        fh.write("hand-dropped model\n")
    assert validate_artifact(plain) is None
    assert load_manifest(plain) is None


def test_publish_torn_chaos_retries_to_success(binary_model, tmp_path,
                                               monkeypatch):
    """publish_torn@G: the first attempt leaves a torn artifact and
    fails; the jittered-backoff retry republishes atomically and the
    final artifact validates."""
    bst, _, _ = binary_model
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT", "publish_torn@2")
    sleeps = []
    manifest = publish_model(bst, str(tmp_path), "model_g0002.txt",
                             fault_iteration=2, backoff_base_sec=0.01,
                             _sleep=sleeps.append)
    assert len(sleeps) == 1 and sleeps[0] > 0
    target = str(tmp_path / "model_g0002.txt")
    assert validate_artifact(target)["sha256"] == manifest["sha256"]
    from lightgbm_tpu.resilience.faults import FAULT_EVENTS
    assert any(e["kind"] == "publish_torn" for e in FAULT_EVENTS)


def test_publish_exhausted_retries_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT",
                       "publish_torn@1,publish_torn@1,publish_torn@1")
    with pytest.raises(PublishError, match="failed after 3 attempt"):
        publish_model("not really a model", str(tmp_path), "m.txt",
                      retries=2, fault_iteration=1,
                      backoff_base_sec=0.001, _sleep=lambda _: None)


def test_fault_plan_new_kinds(monkeypatch):
    from lightgbm_tpu.resilience.faults import FaultPlan
    plan = FaultPlan("publish_torn@1,serve_kill@5,refit_nan@3")
    assert plan.active
    assert plan.iters("serve_kill") == (5,)
    assert plan.take("refit_nan", 3) and not plan.take("refit_nan", 3)
    # serve_kill gates on LIGHTGBM_TPU_RANK (replica id), NOT
    # jax.process_index(): a non-selected replica never dies
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "1")
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_RANK", "0")
    plan.maybe_serve_kill(5)          # would SIGKILL us if mis-gated
    assert plan.iters("serve_kill") == (5,)
    # unknown kinds still rejected
    with pytest.raises(ValueError):
        FaultPlan("tea_break@4")


def test_one_shot_strip_includes_serve_kill():
    spec = "serve_kill@25,nan_grad@3,rank_kill@8"
    assert strip_one_shot_faults(spec) == "nan_grad@3"


# ---------------------------------------------------------------------
# 2. warm start: refit parity + init_model incremental data
# ---------------------------------------------------------------------

def _tree_structure(bst):
    return [(list(t.split_feature[: t.num_leaves - 1]),
             [round(float(v), 12)
              for v in t.threshold[: t.num_leaves - 1]])
            for t in bst._models]


@pytest.mark.parametrize("mode", ["fused", "eager"])
def test_refit_reference_contract(mode):
    """FitByExistingTree: tree structures unchanged, leaf values
    re-derived from fresh gradients in boosting order; shifted labels
    move eval the right direction. Both the fused-path and the
    eager-path (valid-set-bearing) trained forests refit."""
    X, y = make_synthetic_binary(n=900, f=8)
    kwargs = {}
    if mode == "eager":
        Xv, yv = make_synthetic_binary(n=200, f=8, seed=11)
        kwargs["valid_sets"] = [lgb.Dataset(Xv, label=yv,
                                            params={"verbosity": -1})]
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y,
                 rounds=6, **kwargs)
    if mode == "eager":
        assert bst._engine._fused_fn is None
    flipped = 1.0 - y
    refitted = bst.refit(X, flipped, decay_rate=0.0)
    # structures byte-for-byte, leaf values re-derived
    assert _tree_structure(refitted) == _tree_structure(bst)
    assert any(
        not np.allclose(a.leaf_value, b.leaf_value)
        for a, b in zip(refitted._models, bst._models))
    # eval moves toward the new labels, and the original is untouched
    assert _logloss(refitted.predict(X), flipped) \
        < _logloss(bst.predict(X), flipped)
    # decay blends: decay=1.0 keeps the old leaf values exactly
    kept = bst.refit(X, flipped, decay_rate=1.0)
    for a, b in zip(kept._models, bst._models):
        np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                   rtol=0, atol=0)


def test_refit_nan_guard_policies(monkeypatch):
    X, y = make_synthetic_binary(n=600, f=6)
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT", "refit_nan@1")

    def train_with(policy):
        monkeypatch.delenv("LIGHTGBM_TPU_FAULT_INJECT", raising=False)
        bst = _train({"objective": "binary", "num_leaves": 7,
                      "nonfinite_policy": policy}, X, y, rounds=4)
        monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT", "refit_nan@1")
        return bst

    bst = train_with("raise")
    with pytest.raises(lgb.LightGBMError, match="tree 1"):
        bst.refit(X, y, decay_rate=0.0)

    bst = train_with("skip_tree")
    refitted = bst.refit(X, y, decay_rate=0.0)
    # the poisoned tree keeps its OLD leaf values; the others refit
    np.testing.assert_allclose(refitted._models[1].leaf_value,
                               bst._models[1].leaf_value,
                               rtol=0, atol=0)
    assert any(e["kind"] == "refit_nan" and e["action"] == "skip_tree"
               for e in refitted._refit_fault_log)
    assert all(np.all(np.isfinite(t.leaf_value))
               for t in refitted._models)

    bst = train_with("clamp")
    refitted = bst.refit(X, y, decay_rate=0.0)
    assert all(np.all(np.isfinite(t.leaf_value))
               for t in refitted._models)


def test_init_model_booster_matches_file_on_fresh_data(tmp_path):
    """Continued training on FRESH data must be identical whether
    init_model is an in-memory Booster or its saved file: the
    in-memory path used to keep stale threshold_bin indices from the
    OLD dataset's bin space (silent mis-binning); both now go through
    the model-text round trip."""
    X0, y0 = make_synthetic_binary(n=700, f=8, seed=3)
    X1, y1 = make_synthetic_binary(n=800, f=8, seed=4)
    X1 = X1 * 1.7 + 0.3          # different bin boundaries on purpose
    params = {"objective": "binary", "num_leaves": 15,
              "verbosity": -1}
    base = _train(params, X0, y0, rounds=4)
    path = str(tmp_path / "base.txt")
    base.save_model(path)
    cont_mem = lgb.train(params, lgb.Dataset(X1, label=y1), 4,
                         init_model=base)
    cont_file = lgb.train(params, lgb.Dataset(X1, label=y1), 4,
                          init_model=path)
    assert cont_mem.model_to_string() == cont_file.model_to_string()
    assert cont_mem.num_trees() == 8


def test_init_model_streamed_chunk_source():
    """The incremental-data path rides the PR-7 chunk sources: fresh
    generation data arrives as a streamed generator source and
    continued training appends to the published forest, identical to
    the eager continuation."""
    from lightgbm_tpu.data.sources import GeneratorChunkSource
    X0, y0 = make_synthetic_binary(n=700, f=8, seed=5)
    X1, y1 = make_synthetic_binary(n=900, f=8, seed=6)
    params = {"objective": "binary", "num_leaves": 15,
              "verbosity": -1}
    base = _train(params, X0, y0, rounds=3)

    def factory():
        for lo in range(0, len(y1), 256):
            yield X1[lo:lo + 256], y1[lo:lo + 256]

    src = GeneratorChunkSource(factory, num_rows=len(y1),
                               num_features=8)
    streamed = lgb.train(
        {**params, "ingest_chunk_rows": 256},
        lgb.Dataset(src, params={"verbosity": -1,
                                 "ingest_chunk_rows": 256}),
        4, init_model=base)
    # same ingest_chunk_rows param so the model headers match too (an
    # in-memory ndarray input stays eager regardless, docs/DATA.md)
    eager = lgb.train({**params, "ingest_chunk_rows": 256},
                      lgb.Dataset(X1, label=y1), 4, init_model=base)
    assert streamed.model_to_string() == eager.model_to_string()
    assert streamed.num_trees() == 7


def test_resume_of_continued_training_reaches_init_plus_rounds(
        tmp_path):
    """The relaunch-same-command contract: a snapshot written during
    init_model continued training records the init offset, so resume
    with the identical arguments finishes at init + num_boost_round —
    byte-identical to the uninterrupted run (previously it stopped
    short at max(resumed, num_boost_round))."""
    X, y = make_synthetic_binary(n=700, f=8, seed=9)
    params = {"objective": "binary", "num_leaves": 15,
              "verbosity": -1}
    base = _train(params, X, y, rounds=4)
    ck = str(tmp_path / "ck")
    full = lgb.train(params, lgb.Dataset(X, label=y), 6,
                     init_model=base,
                     callbacks=[lgb.checkpoint(ck, every_n_iters=3,
                                               keep=10)])
    assert full.num_trees() == 10
    # keep only the mid-run snapshot (engine iteration 6 = 4 init + 2)
    import glob
    snaps = sorted(glob.glob(os.path.join(ck, "ckpt_*.npz")))
    assert snaps, "no snapshots written"
    keep = snaps[0]
    for s in snaps[1:]:
        os.unlink(s)
    resumed = lgb.train(params, lgb.Dataset(X, label=y), 6,
                        init_model=base, resume_from=ck)
    assert resumed.num_trees() == 10, (
        f"resume stopped at {resumed.num_trees()} trees "
        f"(snapshot {os.path.basename(keep)})")
    assert resumed.model_to_string() == full.model_to_string()


# ---------------------------------------------------------------------
# 3. load shedding
# ---------------------------------------------------------------------

class _GatedForest:
    """Fake forest whose predict blocks until released."""
    n_features = 4

    def __init__(self):
        import threading
        self.release = threading.Event()
        self.calls = 0

    def predict_raw(self, X):
        self.calls += 1
        assert self.release.wait(timeout=30)
        return np.zeros((X.shape[0], 1), np.float32)


def test_batcher_sheds_oldest_on_queue_depth():
    from lightgbm_tpu.serve.batcher import MicroBatcher, SheddingError
    forest = _GatedForest()
    mb = MicroBatcher(forest, batch_window_ms=0.0, max_batch_rows=4,
                      queue_max_rows=4096, shed_queue_rows=8)
    try:
        X = np.zeros((4, 4), np.float32)
        first = mb.submit(X)          # dequeued, blocks on the device
        time.sleep(0.2)
        backlog = [mb.submit(X) for _ in range(5)]   # 20 rows pending
        forest.release.set()
        # oldest backlog entries shed until <= 8 rows pending; the
        # newest survive and serve
        outcomes = []
        for fut in backlog:
            try:
                fut.result(timeout=30)
                outcomes.append("ok")
            except SheddingError:
                outcomes.append("shed")
        assert first.result(timeout=30).shape == (4, 1)
        assert outcomes.count("shed") >= 2, outcomes
        assert outcomes[-1] == "ok", (
            f"newest request must survive a queue-depth shed: "
            f"{outcomes}")
        # sheds are FIFO: no served request is older than a shed one
        assert outcomes == sorted(outcomes,
                                  key=lambda o: o == "ok"), outcomes
        st = mb.stats()
        assert st["shed_total"] == outcomes.count("shed")
        assert st["shed_rows"] == 4 * outcomes.count("shed")
        assert st["queue_depth_rows"] == 0
    finally:
        forest.release.set()
        mb.close()


def test_batcher_sheds_blown_latency_budget():
    from lightgbm_tpu.serve.batcher import MicroBatcher, SheddingError
    forest = _GatedForest()
    mb = MicroBatcher(forest, batch_window_ms=0.0, max_batch_rows=4,
                      queue_max_rows=4096, shed_p99_ms=50.0)
    try:
        X = np.zeros((2, 4), np.float32)
        first = mb.submit(X)          # occupies the device
        time.sleep(0.1)
        stale = mb.submit(X)          # will wait > 50 ms
        time.sleep(0.2)
        forest.release.set()
        assert first.result(timeout=30) is not None
        with pytest.raises(SheddingError, match="latency budget"):
            stale.result(timeout=30)
        # a fresh request after the stall serves normally
        assert mb.submit(X).result(timeout=30).shape == (2, 1)
    finally:
        forest.release.set()
        mb.close()


def test_daemon_maps_shed_to_typed_reply(binary_model):
    from lightgbm_tpu.serve.batcher import SheddingError
    from lightgbm_tpu.serve.compile import compile_forest
    from lightgbm_tpu.serve.daemon import ServeState, handle_request
    from lightgbm_tpu.serve.batcher import MicroBatcher
    bst, X, _ = binary_model
    cf = compile_forest(bst, max_batch_rows=256)
    mb = MicroBatcher(cf, batch_window_ms=0.5, max_batch_rows=256)
    state = ServeState(mb, cf.model_id, "test-model")
    try:
        class _ShedFut:
            @staticmethod
            def result():
                raise SheddingError("request shed under load: test")
        state.batcher.submit = lambda rows, trace=None: _ShedFut()
        r = handle_request({"rows": X[:2].tolist()}, state)
        assert r.get("shed") and r.get("overloaded") and "error" in r
        assert state.stats()["shed_replies"] == 1
    finally:
        state.close()


def test_shed_config_validation():
    from lightgbm_tpu.config import Config
    with pytest.raises(ValueError, match="shed"):
        Config.from_params({"serve_shed_queue_rows": 200000,
                            "serve_queue_rows": 131072})
    cfg = Config.from_params({"serve_shed_queue_rows": 1000})
    assert cfg.serve_shed_queue_rows == 1000


# ---------------------------------------------------------------------
# 4. watch-dir poller resilience (torn artifacts retried)
# ---------------------------------------------------------------------

def test_watcher_retries_torn_artifact_until_republished(
        binary_model, tmp_path):
    """The torn-write regression: a torn managed artifact is skipped
    with a swap_failure fault event and RETRIED next poll — once the
    publisher's atomic retry lands, the very next poll swaps. The old
    permanently-skipped behavior would have ignored the repaired
    bytes when the retry preserved mtime-size coincidence, and a
    mid-write file would have been missed forever."""
    from lightgbm_tpu.resilience.faults import FAULT_EVENTS, drain_events
    from lightgbm_tpu.serve.batcher import MicroBatcher
    from lightgbm_tpu.serve.compile import compile_forest
    from lightgbm_tpu.serve.daemon import (ServeState, _artifact_key,
                                           _Watcher)
    bst, X, y = binary_model
    model_a = str(tmp_path / "a.txt")
    bst.save_model(model_a)
    cf = compile_forest(bst, max_batch_rows=256)
    mb = MicroBatcher(cf, batch_window_ms=0.5, max_batch_rows=256)
    state = ServeState(mb, cf.model_id, model_a)
    drain_events(FAULT_EVENTS)
    try:
        watcher = _Watcher(
            state, str(tmp_path), 0.1,
            dict(num_iteration=-1, min_bucket=16, max_batch_rows=256),
            _artifact_key(model_a), 64)
        # a NEW model published torn: manifest first, then a partial
        # model write (the publisher crashed between its two steps)
        bst_b = _train({"objective": "binary", "num_leaves": 15},
                       X, (X[:, 1] > 0).astype(np.float64))
        text = bst_b.model_to_string()
        target = str(tmp_path / "b.txt")
        publish_model(bst_b, str(tmp_path), "b.txt")
        with open(target, "w") as fh:
            fh.write(text[: len(text) // 3])
        os.utime(target, (time.time() + 2, time.time() + 2))

        assert watcher.poll_once() is False
        assert state.stats()["swap_failures"] == 1
        events = drain_events(FAULT_EVENTS)
        assert any(e["kind"] == "swap_failure" for e in events)
        # STILL torn next poll: retried (counter moves), not poisoned
        assert watcher.poll_once() is False
        assert state.stats()["swap_failures"] == 2
        # fault event fires once per observed key, not per poll
        assert not any(e["kind"] == "swap_failure"
                       for e in drain_events(FAULT_EVENTS))

        # the publisher's atomic retry lands -> next poll swaps and
        # reports the validated manifest
        manifest = publish_model(bst_b, str(tmp_path), "b.txt")
        os.utime(target, (time.time() + 4, time.time() + 4))
        assert watcher.poll_once() is True
        st = state.stats()
        assert st["model"] == compile_forest(bst_b).model_id
        assert st["manifest"]["sha256"] == manifest["sha256"]
    finally:
        state.close()


# ---------------------------------------------------------------------
# 5. supervisor: budget, backoff, routing, CLI
# ---------------------------------------------------------------------

def test_restart_budget_sliding_window():
    clock = [0.0]
    budget = RestartBudget(max_restarts=10, max_per_window=2,
                           window_sec=60.0, _now=lambda: clock[0])
    assert budget.admit() is None
    assert budget.admit() is None
    refusal = budget.admit()
    assert refusal is not None and "sliding window" in refusal
    clock[0] = 61.0               # the window slides: both entries age out
    assert budget.admit() is None
    assert budget.total == 3


def test_restart_budget_total_cap_and_backoff():
    import random
    budget = RestartBudget(max_restarts=2, _rng=random.Random(5))
    assert budget.admit() is None
    assert budget.admit() is None
    assert "total restart budget" in budget.admit()
    # jittered exponential shape: within [0.5, 1.5) x base x 2^(n-1),
    # capped at 15 s
    for consecutive, base in ((1, 0.5), (2, 1.0), (3, 2.0)):
        d = budget.backoff(consecutive)
        assert base * 0.5 <= d < base * 1.5, (consecutive, d)
    assert budget.backoff(20) < 15.0 * 1.5


def test_supervise_respects_sliding_window(tmp_path):
    """A crash-looping world stops at the window cap, well before the
    total budget."""
    rc = supervise(
        1, [sys.executable, "-c", "raise SystemExit(7)"],
        max_restarts=50, log_dir=str(tmp_path), grace=0.5,
        max_restarts_per_window=2, restart_window_sec=3600.0)
    assert rc == 7
    # generations 0..2 ran (2 admitted restarts), no more
    logs = sorted(os.listdir(tmp_path))
    assert logs == ["elastic_g0_rank0.log", "elastic_g1_rank0.log",
                    "elastic_g2_rank0.log"], logs


def test_split_faults_routing():
    from lightgbm_tpu.pipeline import _split_faults
    train, serve = _split_faults(
        "serve_kill@25, rank_kill@8,publish_torn@1,refit_nan@2")
    assert serve == "serve_kill@25"
    assert train == "rank_kill@8,publish_torn@1,refit_nan@2"
    assert _split_faults("") == ("", "")


def test_pipeline_cli_is_jax_free(tmp_path):
    """`python -m lightgbm_tpu pipeline --help` must not import jax
    (the lint/launch/serve contract, subprocess-proved)."""
    code = (
        "import sys\n"
        "from lightgbm_tpu.pipeline import main\n"
        "rc = main(['--help'])\n"
        "assert rc == 0, rc\n"
        "rc = main([])\n"
        "assert rc == 2, rc\n"
        "assert 'jax' not in sys.modules, 'pipeline CLI imported jax!'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_DIR,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")
    assert "usage: python -m lightgbm_tpu pipeline" in proc.stdout


def test_summarize_events_publish_and_stats_row(tmp_path):
    from lightgbm_tpu.obs import render_stats_table, summarize_events
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"event": "publish", "file": "m0.txt",
                             "generation": 0, "sha256": "a" * 64,
                             "train_auc": 0.91}) + "\n")
        fh.write(json.dumps({"event": "publish", "file": "m1.txt",
                             "generation": 1, "sha256": "b" * 64,
                             "train_auc": 0.93}) + "\n")
        fh.write(json.dumps({"event": "client", "attempts": 5,
                             "ok": 5}) + "\n")
    summ = summarize_events(path)
    assert summ["publishes"] == 2
    assert summ["publish"]["file"] == "m1.txt"
    table = render_stats_table(summ)
    assert "publish" in table and "m1.txt" in table
    from lightgbm_tpu.cli import main as cli_main
    assert cli_main(["stats", path]) == 0


# ---------------------------------------------------------------------
# 6. slow: graceful shutdown, per-replica fleet restart, chaos e2e
# ---------------------------------------------------------------------

def _read_ready(proc, tries=400):
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
    raise AssertionError("no serve_ready line")


def _connect(port, timeout=120.0):
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
    raise AssertionError(f"could not connect on :{port}: {last}")


def _rpc(fh, obj):
    fh.write(json.dumps(obj) + "\n")
    fh.flush()
    line = fh.readline()
    assert line, "daemon closed the connection unexpectedly"
    return json.loads(line)


@pytest.mark.slow
def test_daemon_sigterm_graceful_drain(binary_model, tmp_path):
    """SIGTERM = graceful shutdown: the in-flight request's reply
    still arrives, the daemon exits 0, and the final serve event is
    written — a supervised restart never drops an accepted request."""
    bst, X, _ = binary_model
    model = str(tmp_path / "model.txt")
    bst.save_model(model)
    telem = str(tmp_path / "serve.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve", model,
         "--port", "0", "--telemetry", telem, "--warmup-rows", "64",
         # a long batching window parks the ACCEPTED request in the
         # worker's coalesce loop, so SIGTERM provably lands while it
         # is in flight (close() short-circuits the window: the STOP
         # marker ends the wait and the batch still runs)
         "--window-ms", "2000",
         "--max-batch-rows", "256", "--grace", "20"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=REPO_DIR, start_new_session=True)
    try:
        ready = _read_ready(proc)
        s, fh = _connect(ready["port"])
        try:
            # a ping first: the connection must be APPLICATION-accepted
            # (out of the TCP backlog) for the drain contract to cover
            # it — a connection still in the backlog at shutdown is
            # reset, which clients see as a retryable connect error;
            # likewise a request still in the socket buffer is not yet
            # ACCEPTED, so give the handler a beat to submit it
            assert _rpc(fh, {"cmd": "ping"})["ok"]
            fh.write(json.dumps({"rows": X[:64].tolist()}) + "\n")
            fh.flush()
            time.sleep(0.3)          # handler reads + submits; batch
            #                          now parked in the 2 s window
            os.kill(proc.pid, signal.SIGTERM)      # mid-request
            line = fh.readline()
            assert line, "reply dropped by the graceful shutdown"
            reply = json.loads(line)
            assert "predictions" in reply and reply["n"] == 64
        finally:
            s.close()
        assert proc.wait(timeout=60) == 0
        with open(telem) as fhh:
            events = [json.loads(ln) for ln in fhh if ln.strip()]
        assert any(e.get("event") == "serve" for e in events)
    finally:
        if proc.poll() is None:
            kill_group(proc)


@pytest.mark.slow
def test_fleet_mode_restarts_only_the_dead_replica(binary_model,
                                                   tmp_path):
    """launch --health-port: SIGKILL one replica -> only IT restarts
    (the survivor's pid is unchanged and it keeps serving), unlike the
    world-restart training shape."""
    bst, X, _ = binary_model
    model = str(tmp_path / "model.txt")
    bst.save_model(model)
    base = free_port()
    sup = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "launch", "2",
         "--max-restarts", "3", "--grace", "1",
         "--health-port", str(base), "--health-interval", "0.5",
         "--health-grace", "300",   # exit-code supervision drives this
         "--log-dir", str(tmp_path / "logs"), "--",
         sys.executable, "-m", "lightgbm_tpu", "serve", model,
         "--port", str(base), "--warmup-rows", "64",
         "--max-batch-rows", "256"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=REPO_DIR, start_new_session=True)
    want = bst.predict(X[:3])
    try:
        pids = {}
        for rank in (0, 1):
            s, fh = _connect(base + rank, timeout=180)
            pids[rank] = _rpc(fh, {"cmd": "ping"})["pid"]
            s.close()

        os.kill(pids[1], signal.SIGKILL)

        deadline = time.time() + 180
        new_pid = None
        while time.time() < deadline:
            try:
                s, fh = _connect(base + 1, timeout=10)
                r = _rpc(fh, {"cmd": "ping"})
                if r.get("pid") not in (None, pids[1]):
                    new_pid = r["pid"]
                    s.close()
                    break
                s.close()
            except (AssertionError, OSError, ValueError):
                pass
            time.sleep(0.5)
        assert new_pid is not None, "replica 1 never came back"
        # replica 0 was NOT restarted: same pid, still serving
        s, fh = _connect(base, timeout=30)
        r = _rpc(fh, {"cmd": "ping"})
        assert r["pid"] == pids[0], (
            f"fleet mode must not restart the healthy replica "
            f"(pid {pids[0]} -> {r['pid']})")
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
@pytest.mark.timeout(900)
def test_pipeline_chaos_end_to_end(tmp_path):
    """The ISSUE 13 acceptance run: 3 generations under two-sided
    chaos — a training rank_kill mid-generation-1, a torn publish of
    generation 1, and a serve replica SIGKILL — and the loop still
    converges: every generation published and manifest-validated, the
    final served model IS the last publication, no accepted request
    was silently dropped, and client-observed service gaps stay
    within the restart grace budget."""
    workdir = str(tmp_path / "pipe")
    env = {k: v for k, v in os.environ.items()
           if k not in ("LIGHTGBM_TPU_FAULT_INJECT",
                        "LIGHTGBM_TPU_CHECKPOINT",
                        "LIGHTGBM_TPU_TELEMETRY")}
    env["PYTHONPATH"] = REPO_DIR
    # rounds=5: gen0 runs engine iterations 0-4, gen1 warm-starts at 5
    # -> rank_kill@7 fires ONLY in generation 1; publish_torn@1 tears
    # generation 1's publish (2 s backoff so the watcher provably
    # observes the torn artifact); serve_kill@12 kills the replica at
    # its 12th accepted request
    env["LIGHTGBM_TPU_FAULT_INJECT"] = \
        "rank_kill@7,publish_torn@1,serve_kill@12"
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "pipeline",
         "--workdir", workdir, "--generations", "3",
         "--rounds", "5", "--rows", "900", "--features", "8",
         "--request-rate", "15", "--request-rows", "4",
         "--health-interval", "0.5", "--health-grace", "25",
         "--swap-timeout", "240", "--grace", "10",
         "--param", "publish_backoff_sec=2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO_DIR, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=800)
    except subprocess.TimeoutExpired:
        kill_group(proc)
        out, _ = proc.communicate(timeout=30)
        pytest.fail(f"pipeline hung; partial output:\n{out[-4000:]}")
    assert proc.returncode == 0, f"pipeline failed:\n{out[-6000:]}"
    summary = None
    for line in out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if obj.get("event") == "pipeline_summary":
            summary = obj
    assert summary is not None, out[-4000:]
    assert summary["failures"] == []
    assert summary["generations_published"] == 3
    assert summary["swaps_confirmed"] == 2

    # final served model id == the last successfully published retrain
    fleet = summary["fleet"]
    assert fleet and all(st is not None for st in fleet)
    for st in fleet:
        assert st["manifest_sha256"] == \
            summary["last_published_sha256"]
        assert st["model_source"].endswith("model_g0002.txt")

    # no accepted request silently dropped; the replica kill was
    # client-visible as connection errors, not hangs
    client = summary["client"]
    assert client["timeout"] == 0, client
    assert client["ok"] > 0
    assert client["conn"] >= 1, (
        f"serve_kill@12 should surface as connection errors: {client}")
    # QPS/p99 continuity: the longest gap between successful replies
    # stays within the (generous) replica-restart budget
    assert client["max_ok_gap_s"] < 60.0, client

    # the torn publish was observed and refused by the watcher...
    serve_jsonl = os.path.join(workdir, "telemetry", "serve.jsonl")
    fault_kinds = set()
    with open(serve_jsonl) as fh:
        for ln in fh:
            if not ln.strip():
                continue
            ev = json.loads(ln)
            if ev.get("event") == "fault":
                fault_kinds.add(ev.get("kind"))
    assert "swap_failure" in fault_kinds, fault_kinds

    # ...and the publisher retried through it (fault event in the
    # generation-1 training telemetry)
    train1 = os.path.join(workdir, "telemetry", "train_g0001.jsonl")
    kinds1 = set()
    publishes = 0
    with open(train1) as fh:
        for ln in fh:
            if not ln.strip():
                continue
            ev = json.loads(ln)
            if ev.get("event") == "fault":
                kinds1.add(ev.get("kind"))
            if ev.get("event") == "publish":
                publishes += 1
    assert publishes == 1
    # the training rank_kill relaunched generation 1 under the
    # supervisor (a generation-1 elastic log exists) and the run
    # still published
    relaunch_log = os.path.join(workdir, "logs", "train_g0001",
                                "elastic_g1_rank0.log")
    assert os.path.exists(relaunch_log), sorted(
        os.listdir(os.path.join(workdir, "logs", "train_g0001")))
    # the serve replica was relaunched by the fleet supervisor
    fleet_logs = sorted(os.listdir(
        os.path.join(workdir, "logs", "fleet")))
    assert "elastic_g1_rank0.log" in fleet_logs, fleet_logs

    # --- tracing plane (ISSUE 16 acceptance): the same chaos run's
    # telemetry merges into a clock-corrected trace with a full
    # train -> publish -> swap -> serve critical path, despite the
    # SIGKILLed replica's truncated stream
    telem_dir = os.path.join(workdir, "telemetry")
    tr = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "trace", telem_dir],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=REPO_DIR)
    assert tr.returncode == 0, (
        f"trace CLI failed:\n{tr.stdout}\n{tr.stderr[-3000:]}")
    assert "critical path" in tr.stdout, tr.stdout
    with open(os.path.join(telem_dir, "trace.json")) as fh:
        doc = json.load(fh)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert xs, "empty Perfetto export from the chaos run"
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    span_names = {e["name"] for e in xs}
    for expected in ("train/iteration", "publish/model",
                     "swap/apply", "serve/request"):
        assert expected in span_names, sorted(span_names)

    from lightgbm_tpu.obs.trace import (correct_clock_skew,
                                        critical_paths, load_spans)
    spans = load_spans(telem_dir)
    offsets = correct_clock_skew(spans)
    assert len(offsets) >= 3  # trainer(s), replica, supervisor
    paths = critical_paths(spans)
    complete = [p for p in paths if p["complete"]]
    assert complete, [
        {"gen": p["generation"],
         "steps": [s["name"] for s in p["steps"]]} for p in paths]
    for p in complete:
        assert all(s["dur_s"] >= 0 for s in p["steps"]), p["steps"]
        t0s = [s["t0"] for s in p["steps"]]
        assert t0s == sorted(t0s), p["steps"]
        assert 0 < p["total_s"] < 600, p
        names = [s["name"] for s in p["steps"]]
        assert names[-1].startswith("serve/request"), names
