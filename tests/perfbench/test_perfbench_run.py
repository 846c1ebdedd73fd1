"""Whole runs of the harness at test size on the CPU, in a temporary
copy of the benchmark that only adds files and entries (``bench_copy``):
the result line, the refusal without a TPU, a new driver kind with its
metric and reader, the control, and the planted faults.

The runs are made once, in two child processes (JAX is theirs alone);
the tests read what they printed.
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

ROWS = 6000
FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def failing(row):
    return {k for k, v in row["check"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]}


def child(cmd, cwd, cache, timeout=900):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": cache,
           # the driver sets it for its own use; a run takes no notice
           "BENCH_RUN": "7"}
    return subprocess.run([sys.executable] + cmd, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = tmp_path_factory.mktemp("bench_copy")
    before = {}
    for base, _, files in os.walk(os.path.join(REPO, "perfbench")):
        for f in files:
            if "__pycache__" not in base:
                p = os.path.join(base, f)
                with open(p, "rb") as fh:
                    before[os.path.relpath(p, REPO)] = fh.read()
    bench_copy.make_copy(str(dst))
    return str(dst), before, str(tmp_path_factory.mktemp("xla_cache"))


@pytest.fixture(scope="module")
def readings(copy):
    """One process: the sound run, the control, each fault."""
    dst, _, cache = copy
    r = child([os.path.join(dst, "perfbench", "control", "readings.py"),
               "--workload", "tiny.train", "--seeds", str(2 ** 31 + 5),
               "--seconds", "0.2", "--cpu-selftest-rows", str(ROWS),
               "--modes", ",".join(("sound", "control") + FAULTS)],
              dst, cache)
    assert r.returncode == 0, r.stderr[-4000:]
    rows = [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]
    return {row["mode"]: row for row in rows}


@pytest.fixture(scope="module")
def traced(copy):
    dst, _, cache = copy
    return child([os.path.join(dst, "perfbench", "run.py"),
                  "--workload", "tiny.train", "--seed", "3", "--seconds",
                  "0.2", "--trace", "1", "--cpu-selftest-rows", str(ROWS)],
                 dst, cache)


def test_the_copy_adds_files_and_edits_none(copy):
    dst, before, _ = copy
    for rel, body in before.items():
        with open(os.path.join(dst, rel), "rb") as fh:
            assert fh.read() == body, rel
    added = {os.path.relpath(os.path.join(b, f), dst)
             for b, _, fs in os.walk(os.path.join(dst, "perfbench"))
             for f in fs if "__pycache__" not in b} - set(before)
    assert {"perfbench/configs/tiny-rank.json",
            "perfbench/traffic/echo_plain.json",
            "perfbench/drivers/echo_rounds.py",
            "perfbench/metrics/echo.answer.json",
            "perfbench/readers/echo_times.py"} <= added


def test_a_new_driver_kind_metric_and_reader_run_as_files(copy):
    dst, _, cache = copy
    r = child([os.path.join(dst, "perfbench", "run.py"), "--workload",
               "tiny.echo", "--seed", "1", "--seconds", "1", "--trace", "1",
               "--cpu-selftest-rows", "1"], dst, cache)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 7
    # its own metric is read; the training cells' readers find nothing
    assert line["metrics"] == {"echo.answer": {"value": 42, "unit": "count"}}


def test_without_a_tpu_the_run_fails_and_prints_no_result(copy):
    dst, _, cache = copy
    r = child([os.path.join(dst, "perfbench", "run.py"), "--workload",
               "tiny.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
              dst, cache)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "the benchmark needs a TPU" in r.stderr


def test_with_only_the_benchmarks_files_the_run_fails(copy):
    """``BENCHMARK.json`` and ``paths`` alone, the program absent."""
    dst, _, cache = copy
    env_less = subprocess.run(
        [sys.executable, os.path.join(dst, "perfbench", "run.py"),
         "--workload", "tiny.train", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--cpu-selftest-rows", "100"], cwd=dst,
        env={"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert env_less.returncode != 0 and env_less.stdout.strip() == ""
    assert "lightgbm_tpu" in env_less.stderr


def test_an_unknown_cell_is_an_error(copy):
    dst, _, cache = copy
    r = child([os.path.join(dst, "perfbench", "run.py"), "--workload",
               "nope.train", "--seed", "1", "--seconds", "1"], dst, cache)
    assert r.returncode != 0 and "no workload 'nope.train'" in r.stderr


def test_last_line_holds_the_contracts_keys(traced):
    assert traced.returncode == 0, traced.stderr[-4000:]
    out = traced.stdout.strip().splitlines()
    line = json.loads(out[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["kind"] == "cpu" and line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))


def test_traced_run_reports_host_metrics_and_no_device_number(traced):
    line = json.loads(traced.stdout.strip().splitlines()[-1])
    got = set(line["metrics"])
    assert {"setup.datagen_s", "setup.construct_s", "setup.first_round_s",
            "train.compiles_in_window", "train.round_max_ms"} <= got
    assert line["metrics"]["train.compiles_in_window"]["value"] == 0
    # a CPU run has no device plane: every trace metric stays silent
    assert not got & {"grow.sort_ms_per_round",
                      "train.round_mfu",
                      "train.device_idle_pct", "train.dispatches_per_round"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_each_number_is_printed_beside_its_limit(traced):
    line = json.loads(traced.stdout.strip().splitlines()[-1])
    tail = traced.stderr.strip().splitlines()[-(len(line["check"]) + 1):]
    assert tail[-1] == "check correct True"
    for (name, row), text in zip(line["check"].items(), tail):
        assert text == (f"check {name} value {row['value']!r} "
                        f"limit {row['limit']!r}")
        assert row["limit"] is None or row["value"] <= row["limit"]


def test_sound_run_is_correct_and_reports_end_to_end(readings):
    row = readings["sound"]
    assert row["correct"] is True, row
    assert set(row["metrics"]) == {"setup_s", "train.ms_per_round",
                                   "train.peak_hbm_gib"}
    assert row["check"]["leaf_count_mismatch"]["value"] == 0
    assert row["metrics"]["setup_s"]["value"] > 0
    assert row["metrics"]["train.ms_per_round"]["value"] > 0


def test_reference_at_lower_precision_reads_above_the_program(readings):
    """The control of the reference put in the program's place, one
    precision down (bfloat16 operands for the CPU's float32): at the
    median leaf it reads over its limit, and three times the sound run's
    own gap or more."""
    row = readings["sound"]
    for name in ("leaf_weight_median_gap", "leaf_value_median_gap",
                 "split_gain_median_gap"):
        low, sound = row["control"][name], row["check"][name]
        assert low > sound["limit"] and low >= 3 * sound["value"], (name, row)


def test_the_programs_own_lower_precision_is_not_correct(readings):
    row = readings["control"]
    assert row["correct"] is False, row
    if "check" in row:
        assert failing(row), row


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_not_correct(readings, fault):
    row = readings[fault]
    assert row["correct"] is False, row
    assert "crashed" not in row, row
    expect = {"state_unchanged": "score_gap",
              "half_batch": "leaf_count_mismatch",
              "answer_altered": "leaf_value_gap"}[fault]
    assert expect in failing(row), row
