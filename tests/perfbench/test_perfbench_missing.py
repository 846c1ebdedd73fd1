"""The cell ``bosch968.train_eval`` (ISSUE 35) on the CPU at test size: the
generator of tables with NaN in station blocks, whole runs through
``run.py --cpu-selftest-rows`` on the real tree, the control and the four
planted faults of ``control/faults_missing.py``, the reference's
direction number on hand-made sums, the driver's probe, the manifest and
the new metrics' readers.

Whole runs are made once each, in child processes (JAX is theirs alone);
the tests read what they printed.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from harness import datagen_missing  # noqa: E402
from harness.manifest import Manifest, load_json  # noqa: E402

CELL, CONFIG = "bosch968.train_eval", "bosch-1m-968-missing"
ROWS = 4000
FAULTS = {"nan_always_right": {"root_split_shortfall",
                               "deep_split_shortfall"},
          "nan_binned_as_zero": {"root_split_shortfall",
                                 "deep_split_shortfall",
                                 "missing_direction_shortfall"},
          "valid_directions_flipped": {"valid_score_gap", "eval_metric_gap"},
          "metric_on_train": {"eval_metric_gap"},
          "train_score_stale": {"score_gap"}}
NEW_METRICS = ("grow.ms_per_round", "grow.leaves_per_round",
               "grow.missing_split_pct", "grow.default_left_pct",
               "construct.missing_cell_pct")


def child(cmd, cache, timeout=1800):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": cache, "BENCH_RUN": "7"}
    return subprocess.run([sys.executable] + cmd, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def failing(check):
    return {k for k, v in check.items() if v["limit"] is not None
            and not (v["value"] is not None and v["value"] <= v["limit"])}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return str(tmp_path_factory.mktemp("xla_cache"))


@pytest.fixture(scope="module")
def lines(cache):
    """One untraced and one traced run of the cell."""
    out = {}
    for trace in (0, 1):
        r = child([os.path.join(REPO, "perfbench", "run.py"),
                   "--workload", CELL, "--seed", str(2 ** 31 + 35),
                   "--seconds", "0.1", "--trace", str(trace),
                   "--cpu-selftest-rows", str(ROWS)], cache)
        assert r.returncode == 0, r.stderr[-4000:]
        out[trace] = json.loads(r.stdout.splitlines()[-1])
    return out


@pytest.fixture(scope="module")
def faults(cache):
    name = f"test_missing_{os.getpid()}.jsonl"
    r = child([os.path.join(REPO, "perfbench", "control",
                            "faults_missing.py"),
               "--workload", CELL, "--seeds", str(2 ** 31 + 36),
               "--seconds", "0.1", "--cpu-selftest-rows", str(ROWS),
               "--modes", ",".join(["sound"] + list(FAULTS)),
               "--out", name], cache)
    path = os.path.join(REPO, "chiprun_out", name)
    if os.path.exists(path):
        os.remove(path)
    assert r.returncode == 0, r.stderr[-4000:]
    return {row["mode"]: row
            for row in (json.loads(x) for x in r.stdout.splitlines()
                        if x.startswith("{"))}


# -- the generator ---------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return Manifest().config({"config": CONFIG})


def test_the_layout_is_the_configuration_s_and_no_seed_s(cfg):
    lay = datagen_missing.layout(cfg["data"], cfg["num_features"])
    again = datagen_missing.layout(cfg["data"], cfg["num_features"])
    for key in ("widths", "share", "stage", "levels", "coef"):
        assert np.array_equal(lay[key], again[key])
    w, share = lay["widths"], lay["share"]
    assert len(w) == 52 and w.sum() == 968 and 8 <= w.min() <= w.max() <= 40
    assert np.array_equal(np.bincount(lay["stage"]), np.full(13, 4))
    assert np.bincount(lay["stage"], weights=share).max() <= 1.0
    # a column is 55% to 97% missing, the table 81%
    assert 0.03 <= share.min() and share.max() <= 0.45
    assert abs(share @ w / 968 - 0.19) < 1e-3
    assert 0.70 < (lay["levels"] > 0).mean() < 0.80
    for s, i, j in cfg["data"]["label"]["products"]:
        assert max(i, j) < w[s]


def test_the_tables_are_the_same_whatever_the_threads(cfg):
    rows, held = 20000, 3675
    one = datagen_missing.make_tables(cfg["data"], 968, rows, held, 9,
                                      threads=1)
    many = datagen_missing.make_tables(cfg["data"], 968, rows, held, 9,
                                       threads=4)
    for which in ("train", "valid"):
        for a, b in zip(one[which][:2], many[which][:2]):
            assert np.array_equal(a, b, equal_nan=True)
        assert one[which][2] is None
    X, y, _ = one["train"]
    assert X.dtype == np.float32 and X.shape == (rows, 968)
    assert one["valid"][0].shape == (held, 968)
    gone = np.isnan(X)
    assert 0.80 <= gone.mean() <= 0.82
    per_column = gone.mean(axis=0)
    assert 0.5 < per_column.min() and per_column.max() < 0.98
    # a row has a station whole or not at all
    lay = datagen_missing.layout(cfg["data"], 968)
    for s in (0, 17, 51):
        block = gone[:, lay["first"][s]:lay["first"][s] + lay["widths"][s]]
        assert np.array_equal(block.all(axis=1), block.any(axis=1))
    finite = X[~gone]
    assert finite.min() >= -1.0 and finite.max() <= 1.0
    assert set(np.unique(y)) == {0.0, 1.0} and 0.003 < y.mean() < 0.009
    # the harness's CPU test cuts the label at a quantile of its own
    tenth = datagen_missing.make_tables(cfg["data"], 968, rows, held, 9,
                                        threads=2, positive_share=0.1)
    assert abs(tenth["train"][1].mean() - 0.1) < 0.002
    assert np.array_equal(tenth["train"][0], X, equal_nan=True)


def test_a_seed_draws_the_rows_and_not_the_line(cfg):
    """Rows come from ``--seed`` (values, noise, visits; both tables), the
    line they pass through from the configuration: two seeds share no row
    and read the same missing share a column."""
    rows, held = 20000, 3675

    weigh = np.random.default_rng(0).random(968)

    def keys(X):        # a row's finite values name it
        return np.nansum(X * weigh, axis=1)

    a = datagen_missing.make_tables(cfg["data"], 968, rows, held, 9,
                                    threads=4)
    b = datagen_missing.make_tables(cfg["data"], 968, rows, held,
                                    2 ** 31 + 10, threads=4)
    for which in ("train", "valid"):
        assert np.intersect1d(keys(a[which][0]), keys(b[which][0])).size < 5
        gone_a, gone_b = (np.isnan(t[which][0]).mean(axis=0) for t in (a, b))
        assert np.abs(gone_a - gone_b).max() < 0.03
    assert np.intersect1d(keys(a["train"][0]), keys(a["valid"][0])).size < 5
    # a table's whole batches do not depend on how many follow
    more, _ = datagen_missing.make_table(cfg["data"], 968, 8192, 9,
                                         threads=2)
    assert np.array_equal(more, a["train"][0][:8192], equal_nan=True)
    assert cfg["data"]["block_rows"] == 4096
    # the threshold is the configuration's: the published share of
    # positives to within the sample
    assert datagen_missing.calibrate_threshold(
        dict(cfg["data"], block_rows=4096), 968, 3 * 4096) \
        == pytest.approx(cfg["data"]["label"]["threshold"], abs=0.35)


def test_the_label_knows_whether_a_station_was_visited(cfg):
    X, lat = datagen_missing.make_table(cfg["data"], 968, 40000, 3,
                                        threads=2)
    lay = datagen_missing.layout(cfg["data"], 968)
    for s, coef in cfg["data"]["label"]["visited"]:
        there = ~np.isnan(X[:, lay["first"][s]])
        gap = lat[there].mean() - lat[~there].mean()
        assert abs(gap - coef) < 0.15 * abs(coef), (s, gap)
        # a visited minority that fails MORE (rework stations): the child
        # of fewer rows carries more hessian than its sibling once learned
        assert coef > 0 and there.mean() < 0.5


# -- whole runs ------------------------------------------------------------

def test_the_cell_runs_end_to_end_on_the_cpu(lines):
    line = lines[0]
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3               # the traffic's min_rounds
    assert set(line["metrics"]) == {"setup_s", "train.ms_per_round",
                                    "train.peak_hbm_gib"}
    assert "missing_direction_shortfall" in line["check"]
    assert line["check"]["leaf_count_mismatch"]["value"] == 0
    assert not failing(line["check"])


def test_a_traced_run_reads_the_program_s_counters(lines):
    got = lines[1]["metrics"]
    assert 80 <= got["construct.missing_cell_pct"]["value"] <= 82
    assert 1 < got["grow.leaves_per_round"]["value"] <= 255
    assert got["grow.missing_split_pct"]["value"] == 100.0
    assert 0 < got["grow.default_left_pct"]["value"] < 100
    assert got["eval.ms_per_round"]["value"] > 0
    assert got["train.compiles_in_window"]["value"] == 0
    assert got["setup.construct_find_bins_s"]["value"] > 0
    # device numbers come from a chip's trace alone
    assert not {"grow.ms_per_round", "grow.sort_ms_per_round",
                "train.round_mfu", "train.device_idle_pct"} & set(got)


def test_the_control_fails_a_number(faults):
    """The reference with its gradients rounded to bfloat16, put in the
    program's place, against the self-test's limits."""
    row = faults["sound"]
    assert row["correct"] is True, failing(row["check"])
    limits = load_json(os.path.join(REPO, "perfbench", "limits",
                                    CELL + ".json"))["cpu_selftest"]
    over = {k for k, v in row["control"].items()
            if limits[k] is not None and v > limits[k]}
    assert {"leaf_weight_median_gap", "leaf_value_median_gap",
            "split_gain_median_gap"} <= over


@pytest.mark.parametrize("mode", list(FAULTS))
def test_a_planted_fault_fails_a_number(faults, mode):
    row = faults[mode]
    assert "crashed" not in row, row
    assert row["correct"] is False
    bad = failing(row["check"])
    assert bad & FAULTS[mode], (mode, bad)
    sound = faults["sound"]["check"]
    # well over what the sound run reads, or a number not produced (at
    # this size a deep node holds a few hundred rows and a sound run reads
    # 0.03 there; the chip's readings, 28x and more, are in the limits file)
    assert any(row["check"][k]["value"] is None
               or row["check"][k]["value"] >= 5 * max(sound[k]["value"], 0.0)
               for k in bad & FAULTS[mode])


# -- the direction number on hand-made sums -------------------------------

def test_the_direction_number_reads_a_worse_direction_and_no_tie():
    from harness import reference_missing as RM
    tree = {"default_left": np.array([False, True, False, False]),
            "missing_type": np.array([2, 2, 2, 0])}
    # node 0: NaN rows (10, G 8, H 2) right; left would have been better
    # node 1: NaN rows left, which is the better side
    # node 2: the other side would leave the right child empty
    # node 3: no missing type, whatever the sums say
    counts = np.array([[50, 60, 10], [40, 30, 20], [30, 10, 10],
                       [20, 20, 5]])
    stats = np.array([[-10.0, 12.0, 14.0, 15.0, 8.0, 2.0],
                      [9.0, 10.0, -6.0, 7.0, 5.0, 5.0],
                      [3.0, 8.0, 2.0, 2.5, 2.0, 2.5],
                      [1.0, 5.0, -1.0, 5.0, 3.0, 1.0]])
    short = RM.direction_shortfall(tree, counts, stats, 1.0, 1e-3, 0.0)

    def gain(gl, hl, gr, hr):
        return gl * gl / hl + gr * gr / hr - (gl + gr) ** 2 / (hl + hr)

    here, other = gain(-10, 12, 14, 15), gain(-2, 14, 6, 13)
    assert other < here and np.isclose(short[0], (other - here) / here)
    here, other = gain(9, 10, -6, 7), gain(4, 5, -1, 12)
    assert np.isclose(short[1], (other - here) / here) and short[1] < 0
    assert short[2] == -np.inf and short[3] == -np.inf
    # the same node with its rows recorded on the worse side reads > 0
    flipped = dict(tree, default_left=np.array([False, False, False, False]))
    moved = stats.copy()
    moved[1] = [4.0, 5.0, -1.0, 12.0, 5.0, 5.0]
    moved_counts = counts.copy()
    moved_counts[1] = [20, 50, 20]
    again = RM.direction_shortfall(flipped, moved_counts, moved, 1.0, 1e-3,
                                   0.0)
    assert again[1] > 0.1
    # min_sum_hessian_in_leaf on the other side: not on offer
    assert RM.direction_shortfall(tree, counts, stats, 1.0, 14.5,
                                  0.0)[0] == -np.inf


def test_a_node_of_rounding_noise_is_no_shortfall():
    """A deep node of negatives at one score: every gain there is a
    float32 rounding of the terms G * G / H, the program's split and the
    reference's best among them (the chip's reading of such a node)."""
    from harness import check_missing
    noise = [125.73, 125.1, 167.36, 166.53]     # GL, HL, GR, HR: 294.6
    assert check_missing.split_shortfall(3.0517578125e-05, 7.665e-09,
                                         noise) < 1e-3
    # a node with a split to find reads as before: relative to the best
    real = [-92.55, 186.06, 36.79, 831.64]      # terms 47.7, gain 44.6
    assert check_missing.split_shortfall(44.6, 22.3, real) \
        == pytest.approx(0.5)
    assert check_missing.split_shortfall(2.056, 1.028,
                                         [88.44, 106.91, 67.55, 107.02]) \
        == pytest.approx(0.5)
    # and a fault that leaves a real gain on the table still reads ~1
    assert check_missing.split_shortfall(40.0, 0.01, real) > 0.99


def test_the_reference_routes_a_nan_as_the_source_s_decision_does():
    import jax.numpy as jnp
    from harness import reference_missing as RM
    x = jnp.asarray([np.nan, 0.0, -0.5, 0.7, 1e-36], jnp.float32)
    thr = jnp.float32(-0.1)
    for mt, dl, want in (
            (2, True, [True, False, True, False, False]),
            (2, False, [False, False, True, False, False]),
            (0, True, [False, False, True, False, False]),    # NaN -> 0.0
            (1, True, [True, True, True, False, True]),
            (1, False, [False, False, True, False, False])):
        got = RM.goes_left(x, thr, jnp.asarray(dl), jnp.int32(mt))
        assert list(np.asarray(got)) == want, (mt, dl)
    # +inf, the threshold of "every finite value left"
    got = RM.goes_left(x, jnp.float32(np.inf), jnp.asarray(False),
                       jnp.int32(2))
    assert list(np.asarray(got)) == [False, True, True, True, True]


# -- the check's probe -----------------------------------------------------

def test_the_probe_ends_a_run_that_bins_a_nan_as_zero(cfg):
    import lightgbm_tpu as lgb
    from control import faults_missing
    from harness import check_missing
    check_missing.probe(lgb, cfg["params"], lambda m: None)
    with faults_missing.nan_binned_as_zero():
        with pytest.raises(RuntimeError, match="cannot run a configuration "
                                               "with missing values"):
            check_missing.probe(lgb, cfg["params"], lambda m: None)
    with pytest.raises(ValueError, match="binary job under AUC"):
        check_missing.probe(lgb, dict(cfg["params"], metric="binary_logloss"),
                            lambda m: None)


# -- the manifest and the readers -----------------------------------------

def test_the_manifest_resolves_the_cell():
    man = Manifest()
    c = man.cell(CELL)
    assert (c["config"], c["traffic"], c["chips"]) \
        == (CONFIG, "train_eval_missing", 1)
    cfg, traffic, limits = man.config(c), man.traffic(c), man.limits(c)
    assert traffic["driver"] == "train_eval_missing"
    assert hasattr(man.driver(traffic), "run")
    # the parameters of train_eval.json, letter for letter
    plain = load_json(os.path.join(man.bench_dir, "traffic",
                                   "train_eval.json"))
    for key in ("warmup_rounds", "min_rounds", "max_rounds", "trace_rounds",
                "early_stopping_rounds", "check", "expect"):
        assert traffic[key] == plain[key]
    assert "unstated" not in traffic
    # the driver is told its generator and its check by name
    import importlib
    for role, needs in (("generator", ("make_tables", "selftest")),
                        ("check", ("compare", "judge", "probe"))):
        mod = importlib.import_module("harness." + traffic["harness"][role])
        assert all(hasattr(mod, n) for n in needs), role
    assert set(traffic["observe"]["counter_ratios"]) == {
        "leaves_per_round", "missing_split_pct", "default_left_pct",
        "missing_cell_pct"}
    assert cfg["expect"]["counter_ranges"]["missing_cell_pct"] == [80, 82]
    assert cfg["params"]["max_bin"] == 63 and cfg["num_features"] == 968
    assert (cfg["num_data"], cfg["valid_rows"]) == (1000000, 183747)
    from harness import check_missing
    for group in ("limits", "cpu_selftest"):
        assert set(check_missing.NUMBERS) <= set(limits[group])
    assert all(limits["limits"][k] is not None
               for k in check_missing.NUMBERS)
    entry = [e for e in man.doc["configs"] if e["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_iterations"]
    assert len(entry["source"]) <= 200 and entry["source"] == cfg["source"]
    per_layer = {m["name"] for m in man.metrics(c, "per_layer")}
    assert set(NEW_METRICS) <= per_layer
    assert {"train.round_mfu", "train.device_idle_pct", "eval.ms_per_round",
            "grow.sort_ms_per_round"} <= per_layer
    assert not {"rank.grad_ms_per_round", "comm.allreduce_ms_per_round",
                "train.host_dispatch_ms_per_round"} & per_layer
    for m in man.metrics(c, "per_layer"):
        desc = load_json(os.path.join(man.bench_dir, "metrics",
                                      m["name"] + ".json"))
        assert os.path.exists(os.path.join(man.bench_dir, "readers",
                                           desc["reader"] + ".py"))


def test_every_workloads_list_names_cells_that_exist():
    doc = Manifest().doc
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["end_to_end"] + doc["per_layer"]:
        listed = m.get("workloads", [])
        assert set(listed) <= cells and len(set(listed)) == len(listed), m
    for name in NEW_METRICS:
        entry = [m for m in doc["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL]


def test_the_new_metrics_read_a_recorded_observation():
    man = Manifest()
    obs = {"host": {"traced_rounds": 3},
           "counters": {"leaves_per_round": 41.5, "missing_split_pct": 100.0,
                        "default_left_pct": 27.5, "missing_cell_pct": 81.0},
           "programs": {"grow_ms_per_round": 187.25}, "trace": None,
           "work": None}
    want = {"grow.ms_per_round": 187.25, "grow.leaves_per_round": 41.5,
            "grow.missing_split_pct": 100.0, "grow.default_left_pct": 27.5,
            "construct.missing_cell_pct": 81.0}
    assert set(want) == set(NEW_METRICS)
    for name, value in want.items():
        assert man.read_metric({"name": name}, obs) == value
    # another driver's observations, or a program without the counters
    # (the parent of the PR that added them): nothing, and no raise
    bare = {"host": {}, "counters": {}, "programs": None, "trace": None}
    for name in NEW_METRICS:
        assert man.read_metric({"name": name}, bare) is None


def test_the_driver_counts_what_moved_since_its_probe():
    """The probe's tiny table and tree are not the job's: the driver
    reads the program's counters as deltas, and a ratio's terms as the
    traffic file signs them (leaves less splits is the number of trees)."""
    from lightgbm_tpu.obs.registry import MetricsRegistry
    man = Manifest()
    traffic = man.traffic(man.cell(CELL))
    driver = man.driver(traffic)
    names = ["tree_leaf_count", "tree_splits", "tree_splits_on_missing",
             "tree_splits_default_left"]
    reg = MetricsRegistry()
    assert driver._counted(reg, names) == dict.fromkeys(names, 0)
    reg.counter("tree_leaf_count").inc(2)
    reg.counter("tree_splits").inc(1)
    base = driver._counted(reg, names)
    for name, v in (("tree_leaf_count", 90), ("tree_splits", 88),
                    ("tree_splits_on_missing", 80),
                    ("tree_splits_default_left", 20)):
        reg.counter(name).inc(v)
    got = {k: v - base[k] for k, v in driver._counted(reg, names).items()}
    assert (got["tree_leaf_count"], got["tree_splits"]) == (90, 88)
    r = traffic["observe"]["counter_ratios"]["leaves_per_round"]
    assert driver._signed_sum(got, r["den"]) == 2
    assert driver._signed_sum(got, r["num"]) / 2 == 45.0


# -- files added, none edited ---------------------------------------------

PARENT = "d0cb4c7f166a1dbfd3d32e82b90ae4da063f1410"      # PR 34


def _git(*args):
    r = subprocess.run(["git"] + list(args), cwd=REPO, capture_output=True,
                       text=True)
    return r.stdout if r.returncode == 0 else None


def test_the_benchmark_gained_this_cell_and_nothing_else_changed():
    if _git("cat-file", "-e", PARENT + "^{commit}") is None:
        pytest.skip("the parent commit is not in reach")
    changed = _git("diff", "--name-status", PARENT, "--", "perfbench",
                   "tests/perfbench")
    assert changed is not None
    edited = [x for x in changed.splitlines() if x and x[0] != "A"]
    assert not edited, edited
    before = json.loads(_git("show", PARENT + ":BENCHMARK.json"))
    after = load_json(os.path.join(REPO, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(before[group], after[group]):
            old, new = dict(old), dict(new)
            was, now = old.pop("workloads", []), new.pop("workloads", [])
            assert old == new and now[:len(was)] == was
            assert set(now[len(was):]) <= {CELL}
    added = {g: [e["name"] for e in after[g][len(before[g]):]]
             for g in ("configs", "workloads", "end_to_end", "per_layer")}
    # this PR's entries come first of what was added since
    assert added["configs"][:1] == [CONFIG]
    assert added["workloads"][:1] == [CELL]
    assert added["end_to_end"] == []
    assert added["per_layer"][:5] == list(NEW_METRICS)
