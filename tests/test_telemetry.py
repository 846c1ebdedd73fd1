"""The run-telemetry subsystem (lightgbm_tpu/obs/): JSONL event schema,
recompile counting, disabled-is-free, registry semantics, and the cv()
composition — docs/OBSERVABILITY.md is the contract under test."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback as cbm
from lightgbm_tpu import obs
from lightgbm_tpu.obs import (ITERATION_EVENT_KEYS, MetricsRegistry,
                              RecompileWatcher, device_memory_stats,
                              register_jit, summarize_events)
from lightgbm_tpu.utils.timer import Timer
from tests.conftest import make_synthetic_binary


def _small_train(tmp_path, callbacks=None, rounds=5, valid=True,
                 params=None):
    X, y = make_synthetic_binary(n=800, f=8)
    ds = lgb.Dataset(X[:600], label=y[:600])
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "min_data_in_leaf": 5}
    p.update(params or {})
    valid_sets = None
    if valid:
        vs = lgb.Dataset(X[600:], label=y[600:], reference=ds)
        valid_sets = [vs]
    return lgb.train(p, ds, num_boost_round=rounds,
                     valid_sets=valid_sets, callbacks=callbacks)


# ---------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("iters").inc()
    reg.counter("iters").inc(2)
    reg.gauge("hbm", device="0").set(100)
    reg.gauge("hbm", device="0").set(50)
    reg.histogram("phase_seconds", phase="grow").observe(0.5)
    reg.histogram("phase_seconds", phase="grow").observe(1.5)
    snap = reg.snapshot()
    assert snap["iters"]["series"][0]["value"] == 3
    g = snap["hbm"]["series"][0]
    assert g["labels"] == {"device": "0"}
    assert g["value"] == 50 and g["max"] == 100
    h = snap["phase_seconds"]["series"][0]
    assert h["count"] == 2 and h["total"] == 2.0 and h["mean"] == 1.0
    assert h["min"] == 0.5 and h["max"] == 1.5


def test_registry_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_registry_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def hammer():
        for _ in range(500):
            reg.counter("n").inc()
            reg.histogram("h", phase="p").observe(1.0)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["n"]["series"][0]["value"] == 4000
    assert snap["h"]["series"][0]["count"] == 4000


# ---------------------------------------------------------------------
# recompile tracking
# ---------------------------------------------------------------------

def test_recompile_counter_increments_once_on_shape_change():
    fn = register_jit("test/shape_change",
                      jax.jit(lambda x: (x * 2).sum()))
    watch = RecompileWatcher()
    fn(jnp.ones((8,)))
    assert watch.delta() == 1          # first shape: one compile
    fn(jnp.ones((8,)))
    assert watch.delta() == 0          # cache hit: no compile
    fn(jnp.ones((9,)))
    assert watch.delta() == 1          # shape change: exactly one
    assert watch.total == 2


def test_register_jit_passthrough_for_plain_callables():
    def plain(x):
        return x

    assert register_jit("test/plain", plain) is plain


def test_watcher_counts_replacement_as_new_compiles():
    fn1 = register_jit("test/replaced", jax.jit(lambda x: x + 1))
    watch = RecompileWatcher()
    fn1(jnp.ones(3))
    assert watch.delta() == 1
    # rebuild (reset_parameter / per-fold pattern): new function, its
    # compiles must count even though the old cache size "disappears"
    fn2 = register_jit("test/replaced", jax.jit(lambda x: x + 2))
    fn2(jnp.ones(3))
    assert watch.delta() == 1


def test_device_memory_stats_keys():
    stats = device_memory_stats()
    assert set(stats) == {"bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit"}
    for v in stats.values():
        assert v is None or isinstance(v, int)


# ---------------------------------------------------------------------
# the JSONL event stream
# ---------------------------------------------------------------------

def test_jsonl_schema_one_valid_event_per_iteration(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rounds = 5
    # num_leaves unique to this test: a guaranteed grower cache miss at
    # iteration 0 regardless of what compiled earlier in the process
    _small_train(tmp_path, callbacks=[cbm.telemetry(path)],
                 rounds=rounds, params={"num_leaves": 11})
    lines = [ln for ln in open(path).read().splitlines() if ln]
    all_events = [json.loads(ln) for ln in lines]
    # the guaranteed cache miss records its XLA cost attribution
    # (obs/cost.py) ahead of iteration 0's line; iteration events stay
    # strictly one per round
    compiles = [ev for ev in all_events if ev["event"] == "compile"]
    assert compiles, "the iteration-0 cache miss must record a " \
                     "compile event"
    assert all(ev["entry"] for ev in compiles)
    iter_lines = [json.dumps(ev) for ev in all_events
                  if ev["event"] == "iteration"]
    assert len(iter_lines) == rounds
    for i, line in enumerate(iter_lines):
        ev = json.loads(line)
        for key in ITERATION_EVENT_KEYS:
            assert key in ev, f"missing {key!r} in event {i}"
        assert ev["event"] == "iteration"
        assert ev["iteration"] == i
        assert ev["phases"], "phase table must not be empty"
        for label, v in ev["phases"].items():
            assert v["count"] >= 0 and v["total"] >= 0.0, (label, v)
        assert ev["recompiles"]["delta"] >= 0
        assert ev["recompiles"]["total"] >= ev["recompiles"]["delta"]
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            assert key in ev["hbm"]
        assert ev["tree"]["leaves"] is not None
        assert ev["tree"]["leaves"] >= 1
        assert ev["tree"]["split_gain_sum"] >= 0.0
        assert ev["eval"], "valid set present -> eval results required"
    # first iteration compiles the grower; later cache hits
    first = json.loads(iter_lines[0])
    assert first["recompiles"]["delta"] >= 1


def test_process_fault_log_pollution_is_isolated_a():
    """First half of the order-independence regression (the
    test_distributed_resilience -> test_jsonl_schema flake, ISSUE 11):
    leave stray events in the PROCESS-LEVEL fault log exactly like the
    in-process chaos tests do and rely on the conftest autouse fixture
    to drain them after this test."""
    from lightgbm_tpu.resilience.faults import record_fault_event
    record_fault_event("collective_timeout", iteration=12,
                       action="raise", detail="synthetic leak (test)")
    record_fault_event("init_retry", action="retry",
                       detail="synthetic leak (test)")


def test_process_fault_log_pollution_is_isolated_b(tmp_path):
    """Second half: the previous test's leaked process-level fault
    events must NOT appear in this run's JSONL stream — without the
    conftest isolation fixture the recorder drains them here and the
    one-event-per-iteration schema breaks (reproduced at b344f30 with
    test_distributed_resilience running first)."""
    from lightgbm_tpu.resilience.faults import FAULT_EVENTS
    assert not FAULT_EVENTS, (
        "process-level fault log leaked across tests — the conftest "
        "_isolate_process_fault_log fixture is gone or broken")
    path = str(tmp_path / "isolated.jsonl")
    rounds = 3
    _small_train(tmp_path, callbacks=[cbm.telemetry(path)],
                 rounds=rounds, valid=False)
    lines = [ln for ln in open(path).read().splitlines() if ln]
    events = [json.loads(ln) for ln in lines]
    # compile events and spans are this RUN's own cost attribution and
    # tracing, not leakage; fault events here would be the cross-test
    # pollution
    assert [e["event"] for e in events
            if e["event"] not in ("compile", "span")] \
        == ["iteration"] * rounds


def test_telemetry_records_fused_path_tree_stats(tmp_path):
    """No valid sets -> the fused/deferred path; tree stats must still
    be read (via the pending async copies, without flushing them)."""
    path = str(tmp_path / "fused.jsonl")
    bst = _small_train(tmp_path, callbacks=[cbm.telemetry(path)],
                       rounds=4, valid=False)
    events = [json.loads(ln) for ln in open(path).read().splitlines()
              if ln]
    events = [ev for ev in events if ev["event"] == "iteration"]
    assert len(events) == 4
    assert all(ev["tree"]["leaves"] >= 1 for ev in events)
    # the deferred queue must still materialize the full model
    assert bst.num_trees() == 4


MISSING_VALUE_COUNTERS = (
    "bin_cells", "bin_cells_missing", "tree_leaf_count", "tree_splits", "tree_splits_on_missing",
    "tree_splits_default_left")


@pytest.mark.parametrize("valid", [True, False])
def test_missing_value_counters_are_declared_and_run_beside_telemetry(
        tmp_path, valid):
    """ISSUE 35: the six host counters are families of obs/schemas.py
    (no labels), counted whether or not a recorder runs, on the eager
    path (tree/fetch) and the fused one (the deferred trees'
    materialisation); the recorder's histogram ``tree_leaves`` keeps its
    kind beside the counter ``tree_leaf_count``."""
    from lightgbm_tpu.obs import schemas
    from lightgbm_tpu.obs.registry import registry
    for name in MISSING_VALUE_COUNTERS:
        assert schemas.METRICS[name]["kind"] == "counter"
        assert schemas.METRICS[name]["labels"] == ()
    assert schemas.METRICS["tree_leaves"]["kind"] == "histogram"
    before = {k: registry.counter(k).snapshot()
              for k in MISSING_VALUE_COUNTERS}
    path = str(tmp_path / "run.jsonl")
    bst = _small_train(tmp_path, callbacks=[cbm.telemetry(path)], rounds=3,
                       valid=valid)
    leaves = sum(t["num_leaves"] for t in bst.dump_model()["tree_info"])
    got = {k: registry.counter(k).snapshot() - before[k]
           for k in MISSING_VALUE_COUNTERS}
    assert got["tree_leaf_count"] == leaves
    assert got["tree_splits"] == leaves - 3
    assert got["bin_cells"] == (800 if valid else 600) * 8
    # a dense table: no NaN bin, no cell in one, no node with a direction
    assert got["bin_cells_missing"] == 0
    assert got["tree_splits_on_missing"] == 0
    assert got["tree_splits_default_left"] == 0


def test_disabled_recorder_writes_nothing(tmp_path):
    path = str(tmp_path / "never.jsonl")
    was_enabled = Timer.enabled()
    _small_train(tmp_path, callbacks=None, rounds=3)
    assert not os.path.exists(path)
    assert Timer.enabled() == was_enabled


def test_timer_state_restored_after_telemetry(tmp_path):
    path = str(tmp_path / "run.jsonl")
    assert not Timer.enabled()
    _small_train(tmp_path, callbacks=[cbm.telemetry(path)], rounds=2)
    assert not Timer.enabled()


def test_env_var_activates_telemetry(tmp_path, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_TELEMETRY", path)
    _small_train(tmp_path, rounds=3)
    events = [json.loads(ln) for ln in open(path).read().splitlines()
              if ln]
    events = [ev for ev in events if ev["event"] == "iteration"]
    assert len(events) == 3


def test_cv_composes_with_telemetry(tmp_path):
    path = str(tmp_path / "cv.jsonl")
    X, y = make_synthetic_binary(n=600, f=6)
    ds = lgb.Dataset(X, label=y)
    res = lgb.cv({"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "min_data_in_leaf": 5},
                 ds, num_boost_round=4, nfold=3,
                 callbacks=[cbm.telemetry(path)])
    assert any(k.endswith("-mean") for k in res)
    events = [json.loads(ln) for ln in open(path).read().splitlines()
              if ln]
    events = [ev for ev in events if ev["event"] == "iteration"]
    assert len(events) == 4          # one event per cv iteration
    # tree stats aggregate across the fold engines: 3 folds x 1 tree
    assert all(ev["tree"]["trees"] == 3 for ev in events)
    assert all(ev["eval"] for ev in events)


def test_early_stopping_still_closes_recorder(tmp_path):
    path = str(tmp_path / "es.jsonl")
    X, y = make_synthetic_binary(n=800, f=8)
    ds = lgb.Dataset(X[:600], label=y[:600])
    vs = lgb.Dataset(X[600:], label=y[600:], reference=ds)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "min_data_in_leaf": 5},
              ds, num_boost_round=50, valid_sets=[vs],
              callbacks=[cbm.early_stopping(2, verbose=False),
                         cbm.telemetry(path)])
    assert not Timer.enabled()       # finish() ran despite the unwind
    assert os.path.exists(path)


# ---------------------------------------------------------------------
# stats summarizer + CLI
# ---------------------------------------------------------------------

def test_stats_summary_and_cli(tmp_path, capsys):
    path = str(tmp_path / "run.jsonl")
    _small_train(tmp_path, callbacks=[cbm.telemetry(path)], rounds=4)
    summary = summarize_events(path)
    assert summary["iterations"] == 4
    assert summary["recompiles"] >= 0  # 0 when the grower is cache-warm
    assert summary["total_leaves"] >= 4
    assert "tree_learner/grow" in summary["phases"]
    assert summary["last_eval"]

    from lightgbm_tpu.cli import main
    assert main(["stats", path]) == 0
    out = capsys.readouterr().out
    assert "iterations" in out
    assert "tree_learner/grow" in out


def test_stats_cli_missing_file(capsys):
    from lightgbm_tpu.cli import main
    assert main(["stats", "/nonexistent/nope.jsonl"]) == 1


def test_verbosity_param_silences_info(capsys):
    """Satellite regression: verbosity=-1 must silence [Info] lines for
    the call and restore the prior level afterwards."""
    from lightgbm_tpu.utils.log import get_verbosity
    prev = get_verbosity()
    X, y = make_synthetic_binary(n=400, f=6)
    ds = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 4, "verbosity": -1,
               "min_data_in_leaf": 5}, ds, num_boost_round=2,
              valid_sets=[ds])
    out = capsys.readouterr().out
    assert "[Info]" not in out
    assert get_verbosity() == prev


def test_fault_event_drain_is_atomic_under_concurrent_appends():
    """Regression for the lost-event race: the recorder used to drain
    fault logs with a bare ``list(log), []`` swap, so an event appended
    between the copy and the clear (a watchdog abort on another thread,
    a concurrent trainer) vanished. ``faults.drain_events`` swaps under
    the same lock ``append_fault_event`` takes — every event must land
    in exactly one drain."""
    import threading

    from lightgbm_tpu.resilience import faults

    # isolate from any events other tests left behind
    faults.drain_events(faults.FAULT_EVENTS)
    n_threads, per_thread = 4, 100  # 400 < the 512 cap: nothing ages out
    start = threading.Barrier(n_threads + 1)

    def writer(tid):
        start.wait()
        for i in range(per_thread):
            faults.record_fault_event(
                "test_race", iteration=i, action="noop",
                detail=f"t{tid}/{i}")

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    drained = []
    start.wait()
    while any(t.is_alive() for t in threads):
        drained.extend(faults.drain_events(faults.FAULT_EVENTS))
    for t in threads:
        t.join()
    drained.extend(faults.drain_events(faults.FAULT_EVENTS))
    mine = [ev for ev in drained if ev["kind"] == "test_race"]
    assert len(mine) == n_threads * per_thread, (
        f"lost {n_threads * per_thread - len(mine)} fault events "
        "across concurrent drains")
    assert len({ev["detail"] for ev in mine}) == n_threads * per_thread
    assert not faults.FAULT_EVENTS


def test_event_key_lists_are_the_schema_registry():
    """Satellite of the contract-lint PR: exactly one declaration per
    event. The recorder's ITERATION_EVENT_KEYS and the fault machinery
    are derived views of obs/schemas.py, never parallel lists."""
    from lightgbm_tpu.obs import schemas
    from lightgbm_tpu.resilience import elastic, faults
    assert ITERATION_EVENT_KEYS == \
        tuple(schemas.EVENTS["iteration"]["required"])
    assert faults._KNOWN_KINDS == schemas.injectable_fault_kinds()
    assert elastic._ONE_SHOT_KINDS == schemas.one_shot_fault_kinds()
    # the one-shot strip list is a subset classification of the
    # injectable kinds, not an independent registry
    assert set(elastic._ONE_SHOT_KINDS) <= set(faults._KNOWN_KINDS)
    # every declared event carries "event" itself as a required key
    for name, spec in schemas.EVENTS.items():
        assert "event" in spec["required"], name


def test_summarize_events_rejects_undeclared_event(tmp_path):
    """Ride-along bugfix: an undeclared event name is a corrupt or
    foreign-version stream -> named error, not a silent skip (and
    never a KeyError)."""
    from lightgbm_tpu.obs import UnknownEventError
    path = tmp_path / "bad.jsonl"
    path.write_text(
        json.dumps({"event": "fault", "kind": "nonfinite",
                    "iteration": 0, "action": "skip_tree",
                    "detail": "x", "time": 1.0}) + "\n"
        + json.dumps({"event": "iterration", "iteration": 0}) + "\n")
    with pytest.raises(UnknownEventError) as exc:
        summarize_events(str(path))
    assert exc.value.event_name == "iterration"
    assert "iterration" in str(exc.value)


def test_summarize_events_undeclared_tolerates_truncated_tail(tmp_path):
    """The truncated-final-line tolerance survives the undeclared-name
    check: a SIGKILL mid-write still yields the stream's summary."""
    good = json.dumps({"event": "fault", "kind": "nonfinite",
                       "iteration": 0, "action": "skip_tree",
                       "detail": "x", "time": 1.0})
    path = tmp_path / "cut.jsonl"
    path.write_text(good + "\n" + '{"event": "iterr')  # torn tail
    summary = summarize_events(str(path))
    assert summary["faults"] == {"nonfinite": 1}
