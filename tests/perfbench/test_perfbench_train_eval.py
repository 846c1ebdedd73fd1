"""The ``train_eval`` cells (ISSUE 33) on the CPU at test size: whole
runs of both through ``run.py --cpu-selftest-rows`` on the real tree, the
planted faults of ``control/faults_eval.py``, the ranking generator, the
ranking work model, the manifest, and the add-only promise of the
benchmark's files.

Whole runs are made once each, in child processes (JAX is theirs
alone); the tests read what they printed.
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

from harness import datagen_rank, work_model_rank  # noqa: E402
from harness.manifest import Manifest, load_json  # noqa: E402

CELLS = ("mslr30k.train_eval", "criteo256.train_eval")
ROWS = {"mslr30k.train_eval": 2000, "criteo256.train_eval": 6000}
FAULTS = {"mslr30k.train_eval": ("valid_score_stale", "query_left_out",
                                 "metric_on_train"),
          "criteo256.train_eval": ("valid_score_stale", "metric_on_train")}
NEW_NUMBERS = {"mslr30k.train_eval": {"valid_score_gap", "eval_metric_gap",
                                      "grad_gap", "grad_median_gap"},
               "criteo256.train_eval": {"valid_score_gap",
                                        "eval_metric_gap"}}
PARENT = "43a2e52977bdb863e0b69ef529f7fed9b4aaed47"     # PR 32


def child(cmd, cache, timeout=1200):
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
    """One traced and one untraced run of each cell."""
    out = {}
    for cell in CELLS:
        for trace in (0, 1):
            r = child([os.path.join(REPO, "perfbench", "run.py"),
                       "--workload", cell, "--seed", str(2 ** 31 + 33),
                       "--seconds", "0.1", "--trace", str(trace),
                       "--cpu-selftest-rows", str(ROWS[cell])], cache)
            assert r.returncode == 0, r.stderr[-4000:]
            out[cell, trace] = json.loads(r.stdout.splitlines()[-1])
    return out


@pytest.fixture(scope="module")
def faults(cache, tmp_path_factory):
    out = {}
    for cell in CELLS:
        name = f"test_train_eval_{os.getpid()}.jsonl"
        r = child([os.path.join(REPO, "perfbench", "control",
                                "faults_eval.py"),
                   "--workload", cell, "--seeds", str(2 ** 31 + 34),
                   "--seconds", "0.1", "--cpu-selftest-rows",
                   str(ROWS[cell]), "--modes", ",".join(FAULTS[cell]),
                   "--out", name], cache)
        path = os.path.join(REPO, "chiprun_out", name)
        if os.path.exists(path):
            os.remove(path)
        assert r.returncode == 0, r.stderr[-4000:]
        for row in (json.loads(x) for x in r.stdout.splitlines()
                    if x.startswith("{")):
            out[cell, row["mode"]] = row
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_runs_end_to_end_on_the_cpu(lines, cell):
    line = lines[cell, 0]
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3               # the traffic's min_rounds
    assert set(line["metrics"]) == {"setup_s", "train.ms_per_round",
                                    "train.peak_hbm_gib"}
    assert NEW_NUMBERS[cell] <= set(line["check"])
    assert not failing(line["check"])


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_the_program_s_spans_and_counters(lines, cell):
    got = lines[cell, 1]["metrics"]
    assert got["eval.ms_per_round"]["value"] > 0
    assert got["train.compiles_in_window"]["value"] == 0
    assert got["setup.construct_find_bins_s"]["value"] > 0
    ranked = cell == "mslr30k.train_eval"
    assert ("rank.pair_fill_pct" in got) == ranked
    if ranked:
        assert 0 < got["rank.pair_fill_pct"]["value"] < 100
    # device numbers come from a chip's trace alone
    assert not {"rank.grad_ms_per_round", "rank.grad_roofline",
                "train.round_mfu", "train.device_idle_pct"} & set(got)


@pytest.mark.parametrize("cell,mode", [(c, m) for c in CELLS
                                       for m in FAULTS[c]])
def test_a_planted_fault_fails_a_number(faults, cell, mode):
    row = faults[cell, mode]
    assert "crashed" not in row, row
    assert row["correct"] is False
    bad = failing(row["check"])
    want = {"valid_score_stale": "valid_score_gap",
            "query_left_out": "grad_gap",
            "metric_on_train": "eval_metric_gap"}[mode]
    assert want in bad, (mode, bad)


# -- the ranking generator ---------------------------------------------

@pytest.fixture(scope="module")
def spec():
    return Manifest().config({"config": "mslr-web30k-lambdarank"})


def test_the_published_tables_have_the_published_queries(spec):
    q = spec["data"]["queries"]
    for which, rows in (("train", spec["num_data"]),
                        ("valid", spec["valid_rows"])):
        sizes = datagen_rank.table_queries(spec["data"], rows, which, rows)
        assert len(sizes) == q[which]["count"] and sizes.sum() == rows
        assert sizes.min() >= 1 and sizes.max() == q["max_len"] == 1251
        assert np.sum(sizes == 1251) >= 1
        assert 100 < sizes.mean() < 140
    assert (spec["num_data"], spec["valid_rows"]) == (2270296, 753611)
    assert (q["train"]["count"], q["valid"]["count"]) == (18919, 6306)


def test_the_tables_are_the_same_whatever_the_threads_and_lengths_whatever_the_seed(spec):
    pub = {"train": spec["num_data"], "valid": spec["valid_rows"]}
    rows = datagen_rank.BLOCK_ROWS + 1000       # two blocks
    one = datagen_rank.make_tables(spec["data"], rows, 3000, 9, pub,
                                   threads=1)
    many = datagen_rank.make_tables(spec["data"], rows, 3000, 9, pub,
                                    threads=4)
    other = datagen_rank.make_tables(spec["data"], rows, 3000, 10, pub,
                                     threads=4)
    for which in ("train", "valid"):
        for a, b in zip(one[which], many[which]):
            assert np.array_equal(a, b)
        assert np.array_equal(one[which][2], other[which][2])   # lengths
        assert not np.array_equal(one[which][0], other[which][0])
        X, y, sizes = one[which]
        assert X.shape[1] == 137 and sizes.sum() == len(y) == X.shape[0]
        assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    # the held-out rows come from the blocks after the train table's
    assert not np.array_equal(one["train"][0][:3000], one["valid"][0])
    share = np.bincount(one["train"][1].astype(int), minlength=5) / rows
    assert np.all(np.abs(share - [0.52, 0.32, 0.13, 0.02, 0.01]) < 0.03)


def test_a_configuration_without_queries_gets_the_binary_label():
    from harness import datagen
    cfg = Manifest().config({"config": "criteo-dp256-rank"})
    got = datagen_rank.make_tables(cfg["data"], 5000, 500, 4,
                                   {"train": 5000, "valid": 500}, threads=2)
    X, y = datagen.make_table(cfg["data"], 5000, 4, threads=2)
    assert np.array_equal(got["train"][0], X)       # the one-chip cell's
    assert np.array_equal(got["train"][1], y) and got["train"][2] is None


# -- the ranking work model ---------------------------------------------

def test_the_work_model_counts_the_pairs_of_a_hand_made_list():
    sizes = [4, 1, 3, 5]
    grades = [2, 1, 1, 0,   3,   1, 1, 1,   0, 1, 2, 3, 4]
    # query of 4: 5 pairs of unequal grades; truncation 1 visits 3 pairs
    # query of 1 and the all-equal query of 3: none
    # query of 5, all distinct: 10 pairs; truncation 1 visits 4, 2 visits 7
    assert work_model_rank.loop_pairs(sizes, grades, 1) == 3 + 4
    assert work_model_rank.loop_pairs(sizes, grades, 2) == 5 + 7
    assert work_model_rank.loop_pairs(sizes, grades, 30) == 5 + 10
    work = work_model_rank.gradient_work(sizes, grades, 30)
    assert work["pairs"] == 15 and work["bytes"] == 13 * 16
    assert work["ops"] == 15 * work_model_rank.OPS_PER_PAIR \
        + 4 * 2 + 0 + 3 * 2 + 5 * 3
    with pytest.raises(ValueError):
        work_model_rank.loop_pairs([2, 2], [0, 1, 2], 30)


def test_the_program_s_counter_counts_as_the_work_model_does():
    from lightgbm_tpu.ranking import source_loop_pairs
    rs = np.random.RandomState(2)
    sizes = rs.randint(1, 60, 40)
    grades = rs.randint(0, 5, sizes.sum())
    split = np.split(grades, np.cumsum(sizes)[:-1])
    for trunc in (1, 5, 30, 100):
        assert source_loop_pairs(sizes, split, trunc) \
            == work_model_rank.loop_pairs(sizes, grades, trunc)


# -- the manifest ----------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_resolves_the_cell(cell):
    man = Manifest()
    c = man.cell(cell)
    assert (c["traffic"], c["chips"]) == ("train_eval", 1)
    cfg, traffic, limits = man.config(c), man.traffic(c), man.limits(c)
    assert traffic["driver"] == "train_eval" and traffic["min_rounds"] == 3
    assert hasattr(man.driver(traffic), "run")
    names = NEW_NUMBERS[cell]
    assert names <= set(limits["limits"]) \
        and names <= set(limits["cpu_selftest"])
    per_layer = {m["name"] for m in man.metrics(c, "per_layer")}
    assert {"eval.ms_per_round", "train.round_mfu", "train.device_idle_pct",
            "train.dispatches_per_round"} <= per_layer
    assert "train.host_dispatch_ms_per_round" not in per_layer
    assert ({"rank.grad_ms_per_round", "rank.pair_fill_pct",
             "rank.grad_roofline"} <= per_layer) \
        == (cfg["params"]["objective"] == "lambdarank")
    for m in man.metrics(c, "per_layer"):
        desc = load_json(os.path.join(man.bench_dir, "metrics",
                                      m["name"] + ".json"))
        assert os.path.exists(os.path.join(man.bench_dir, "readers",
                                           desc["reader"] + ".py"))


def test_the_new_readers_read_nothing_from_another_driver_s_observations():
    man = Manifest()
    obs = {"host": {"traced_rounds": 3}, "counters": {}, "trace": None,
           "work": None}
    for name in ("rank.grad_ms_per_round", "rank.grad_roofline"):
        assert man.read_metric({"name": name}, obs) is None


def test_the_driver_s_probe_ends_a_run_on_another_lambdarank(monkeypatch):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ranking import LambdarankNDCG
    man = Manifest()
    cell = man.cell("mslr30k.train_eval")
    cfg, driver = man.config(cell), man.driver(man.traffic(cell))
    ref_cfg = dict(cfg["reference"], objective="lambdarank")
    driver._ranking_probe(lgb, cfg["params"], ref_cfg, lambda m: None)
    inner = LambdarankNDCG.grad_hess
    monkeypatch.setattr(
        LambdarankNDCG, "grad_hess",
        lambda self, *a: tuple(0.5 * v for v in inner(self, *a)))
    with pytest.raises(RuntimeError, match="cannot run a lambdarank"):
        driver._ranking_probe(lgb, cfg["params"], ref_cfg, lambda m: None)


def test_device_time_by_program_and_by_scope_from_a_hand_built_trace():
    man = Manifest()
    driver = man.driver(man.traffic(man.cell("mslr30k.train_eval")))
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.0, 1.0),       # in grads
           ("%while.2 = (s32[]) while(...)", 2.0, 2.0),        # in grow
           ("%sort.3 = (f32[8]) sort(...)", 2.5, 1.0),         # nested
           ("%fusion.1 = f32[8] fusion(...)", 5.0, 0.5)]       # in metric
    mods = [("jit_grads(1)", 0.0, 1.0), ("jit_grow(2)", 2.0, 2.0),
            ("jit_argsort(3)", 5.0, 0.5), ("jit_grads(1)", 9.0, 1.0)]
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                           "XLA Modules": mods}},
             "host": []}
    by_module, by_scope = driver._program_times(
        trace, (0.0, 6.0), lambda n: n.split("(", 1)[0],
        {"jit_grow": {"while.2": "boost/grow",
                      "sort.3": "grow/partition/key_sort"},
         "jit_grads": {"fusion.1": "boost/gradients/lambdarank"},
         "jit_absent": {"x": "y"}})
    assert by_module == {"jit_grads": {"s": 1.0, "n": 1},
                         "jit_grow": {"s": 2.0, "n": 1},
                         "jit_argsort": {"s": 0.5, "n": 1}}
    # fusion.1 of the metric's program is not the gradient's fusion.1
    assert by_scope == {
        "jit_grads": {"boost/gradients/lambdarank": 1.0},
        "jit_grow": {"boost/grow": 1.0, "grow/partition/key_sort": 1.0}}


# -- files added, none edited -----------------------------------------------

def _git(*args):
    r = subprocess.run(["git"] + list(args), cwd=REPO, capture_output=True,
                       text=True)
    return r.stdout if r.returncode == 0 else None


def test_the_benchmark_s_files_were_added_and_none_edited():
    if _git("cat-file", "-e", PARENT + "^{commit}") is None:
        pytest.skip("the parent commit is not in reach")
    changed = _git("diff", "--name-status", PARENT, "--", "perfbench",
                   "tests/perfbench")
    untracked = _git("ls-files", "--others", "--exclude-standard", "--",
                     "perfbench", "tests/perfbench")
    assert changed is not None and untracked is not None
    edited = [x for x in changed.splitlines() if x and x[0] != "A"]
    assert not edited, edited
    before = json.loads(_git("show", PARENT + ":BENCHMARK.json"))
    after = load_json(os.path.join(REPO, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(after[group]) >= len(before[group])
        for old, new in zip(before[group], after[group]):
            old, new = dict(old), dict(new)
            was, now = old.pop("workloads", []), new.pop("workloads", [])
            assert old == new and now[:len(was)] == was
    added = {g: [e["name"] for e in after[g][len(before[g]):]]
             for g in ("configs", "workloads", "per_layer")}
    # this PR's entries come first of what was added since
    assert added["configs"][:1] == ["mslr-web30k-lambdarank"]
    assert added["workloads"][:2] == list(CELLS)
    assert added["per_layer"][:4] == [
        "rank.grad_ms_per_round", "rank.pair_fill_pct", "rank.grad_roofline",
        "eval.ms_per_round"]
