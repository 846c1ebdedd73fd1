"""The readers that read the program itself: ``program_span`` (the
program's own host spans) and ``program_registry`` (its counters), run
through a temporary copy of the benchmark (``bench_copy``) as the
harness runs them: a per-layer entry, its metric file, its reader file.

The unit cases plant spans in the program's real buffer (the readers
run in the driver's process and read it directly; ``tests/conftest.py``
empties it after every test); the last cases read one traced run of the
test-size cell in a child process.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench_copy  # noqa: E402

NEW = ("setup.construct_find_bins_s", "setup.construct_bin_rows_s",
       "setup.train_init_s", "setup.round0_trace_lower_s",
       "setup.round0_backend_s", "setup.round0_cost_capture_s",
       "setup.compile_cache_misses", "train.host_dispatch_ms_per_round")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = tmp_path_factory.mktemp("bench_copy_readers")
    bench_copy.make_copy(str(dst))
    return str(dst)


@pytest.fixture(scope="module")
def man(copy):
    sys.path.insert(0, os.path.join(copy, "perfbench"))
    try:
        from harness.manifest import Manifest
    finally:
        sys.path.pop(0)
    return Manifest(copy)


def metric(man, name):
    found = [m for m in man.doc["per_layer"] if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


def plant(name, start, dur, trace_id, parent=None):
    from lightgbm_tpu.obs import trace
    return trace.record_span(name, start, start + dur, trace_id=trace_id,
                             parent_id=parent)


def plant_job(rounds, trace_id="a" * 16, dispatch=(0.001, 0.002, 0.0005)):
    """A job's spans as the program records them in a traced run."""
    job = plant("train/job", 100.0, 50.0, trace_id)
    plant("train/init", 100.0, 1.25, trace_id, job)
    comp = plant("compile/gbdt/fused_iter", 102.0, 6.0, trace_id, job)
    plant("compile/trace", 102.0, 1.5, trace_id, comp)
    plant("compile/lower", 103.5, 0.5, trace_id, comp)
    plant("compile/backend", 104.0, 2.0, trace_id, comp)
    plant("compile/cost_capture", 106.5, 1.5, trace_id, comp)
    for r in range(rounds):
        rnd = plant("train/round", 110.0 + 4 * r, 4.0, trace_id, job)
        for name, dur in zip(("boosting/bagging", "boosting/fused_iter",
                              "tree/defer"), dispatch):
            plant(name, 110.0 + 4 * r, dur, trace_id, rnd)
    return job


def test_the_new_metrics_are_entries_and_files_only(man):
    for name in NEW:
        m = metric(man, name)
        assert m["workloads"][0] == "criteo256.train"
        assert m["source"] in ("program_span", "program_counter")
        desc = json.load(open(os.path.join(
            REPO, "perfbench", "metrics", name + ".json")))
        assert desc["name"] == name
        assert desc["reader"] in ("program_span", "program_registry")


def test_program_span_sums_construct_parts_under_the_last_construct(man):
    old = plant("dataset/construct", 1.0, 9.0, "b" * 16)
    plant("dataset/construct/find_bins", 1.0, 7.0, "b" * 16, old)
    root = plant("dataset/construct", 20.0, 9.8, "c" * 16)
    plant("dataset/construct/load", 20.0, 0.1, "c" * 16, root)
    plant("dataset/construct/find_bins", 20.1, 6.5, "c" * 16, root)
    plant("dataset/construct/bin_rows", 26.6, 3.2, "c" * 16, root)
    obs = {"host": {}}
    assert man.read_metric(metric(man, "setup.construct_find_bins_s"),
                           obs) == pytest.approx(6.5)
    assert man.read_metric(metric(man, "setup.construct_bin_rows_s"),
                           obs) == pytest.approx(3.2)


def test_program_span_reads_the_last_jobs_trace(man):
    plant_job(3, trace_id="d" * 16)
    from lightgbm_tpu.obs import trace
    later = plant("train/job", 500.0, 9.0, "e" * 16)
    plant("train/init", 500.0, 0.75, "e" * 16, later)
    obs = {"host": {"traced_rounds": 3}}
    assert man.read_metric(metric(man, "setup.train_init_s"), obs) \
        == pytest.approx(0.75)
    # the later job compiled nothing and traced no round: not a guess
    assert man.read_metric(metric(man, "setup.round0_backend_s"),
                           obs) is None
    assert man.read_metric(metric(man, "train.host_dispatch_ms_per_round"),
                           obs) is None
    trace.drain_span_events()
    plant_job(3)
    assert man.read_metric(metric(man, "setup.round0_trace_lower_s"),
                           obs) == pytest.approx(2.0)
    assert man.read_metric(metric(man, "setup.round0_backend_s"),
                           obs) == pytest.approx(2.0)
    assert man.read_metric(metric(man, "setup.round0_cost_capture_s"),
                           obs) == pytest.approx(1.5)
    assert man.read_metric(metric(man, "train.host_dispatch_ms_per_round"),
                           obs) == pytest.approx(3.5)


@pytest.mark.parametrize("case", ["no_spans", "rounds_differ",
                                  "no_traced_rounds", "a_part_missing"])
def test_program_span_reads_nothing_rather_than_guess(man, case):
    obs = {"host": {"traced_rounds": 3}}
    if case == "rounds_differ":
        plant_job(5)            # the whole job was traced, not 3 rounds
    elif case == "no_traced_rounds":
        plant_job(3)
        obs = {"host": {}}
    elif case == "a_part_missing":
        job = plant("train/job", 1.0, 9.0, "f" * 16)
        for r in range(3):
            plant("boosting/fused_iter", 2.0 + r, 0.002, "f" * 16, job)
    for name in ("train.host_dispatch_ms_per_round",):
        assert man.read_metric(metric(man, name), obs) is None
    if case == "no_spans":
        for name in NEW[:6]:
            assert man.read_metric(metric(man, name), obs) is None


def test_program_registry_reads_a_declared_counter(man, monkeypatch):
    import importlib
    from lightgbm_tpu.obs import schemas
    # the module: ``lightgbm_tpu.obs.registry`` the attribute is the
    # process-global instance
    reg_mod = importlib.import_module("lightgbm_tpu.obs.registry")
    m = metric(man, "setup.compile_cache_misses")
    fresh = reg_mod.MetricsRegistry()
    monkeypatch.setattr(reg_mod, "registry", fresh)
    assert man.read_metric(m, {}) == 0          # declared, never bumped
    fresh.counter("compile_cache_misses").inc(2)
    assert man.read_metric(m, {}) == 2
    # a program that does not declare the counter (this PR's parent)
    pruned = {k: v for k, v in schemas.METRICS.items()
              if k != "compile_cache_misses"}
    monkeypatch.setattr(schemas, "METRICS", pruned)
    assert man.read_metric(m, {}) is None


@pytest.fixture(scope="module")
def traced(copy, tmp_path_factory):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR":
               str(tmp_path_factory.mktemp("xla_cache_readers"))}
    return subprocess.run(
        [sys.executable, os.path.join(copy, "perfbench", "run.py"),
         "--workload", "tiny.train", "--seed", str(2 ** 31 + 11),
         "--seconds", "0.2", "--trace", "1", "--cpu-selftest-rows", "6000"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=900)


def test_a_traced_run_reports_every_new_metric(traced):
    assert traced.returncode == 0, traced.stderr[-4000:]
    line = json.loads(traced.stdout.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert all(isinstance(got[k], (int, float)) for k in NEW)


def test_the_span_account_agrees_with_the_clock_around_it(traced):
    line = json.loads(traced.stdout.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    parts = got["setup.construct_find_bins_s"] \
        + got["setup.construct_bin_rows_s"]
    assert 0 < parts <= got["setup.construct_s"]
    # round 0 holds the trace, the lowering, the backend compile and
    # the cost capture; the clock around it (first_round_s) holds them
    inside = got["setup.round0_trace_lower_s"] \
        + got["setup.round0_backend_s"] \
        + got["setup.round0_cost_capture_s"]
    assert 0 < inside <= got["setup.first_round_s"]
    assert got["setup.train_init_s"] > 0
    assert got["setup.compile_cache_misses"] >= 0
    # the enqueue of a round's program, not the round
    assert 0 < got["train.host_dispatch_ms_per_round"] \
        < got["train.round_max_ms"]
