"""The four-chip cell's own benchmark files, at test size on the CPU's
virtual devices: the driver ``train_rounds_dp`` with its readers through
whole runs in child processes (a sound traced run; the control and the
left-out-rank fault), and in this process the pieces it adds to the
harness: the reference in row blocks against the reference whole, the
four-rank learner against the serial one under the same check, the
collectives' trace reduction, the interconnect's byte function.

The cell is ``tiny4.train``: ``bench_copy``'s copy with one more
configuration (``criteo-dp256-host4`` at test size) and cell, files
added and none edited, as the PR that brought the real cell did.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "perfbench"))

import bench_copy  # noqa: E402
from test_perfbench_run import child, failing  # noqa: E402

ROWS, LEAVES, CHIPS = 6000, 15, 4
NEW = ("comm.allreduce_ms_per_round", "comm.reductions_per_round",
       "comm.wire_bytes_per_round", "comm.hist_allreduce_roofline",
       "train.rank_skew_pct")


def add_tiny4(dst):
    bench = os.path.join(dst, "perfbench")
    with open(os.path.join(bench, "configs",
                           "criteo-dp256-host4.json")) as fh:
        config = json.load(fh)
    config["num_data"] = ROWS
    config["params"] = dict(config["params"], num_leaves=LEAVES,
                            min_data_in_leaf=20)
    config["control_params"] = {"use_quantized_grad": True}
    bench_copy.write_json(os.path.join(bench, "configs", "tiny-host4.json"),
                          config)
    with open(os.path.join(bench, "limits", "tiny.train.json")) as fh:
        limits = json.load(fh)
    bench_copy.write_json(os.path.join(bench, "limits", "tiny4.train.json"),
                          limits)
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "tiny-host4", "source": "tests/perfbench (four ranks)",
        "file": "perfbench/configs/tiny-host4.json",
        "reduced": ["num_data", "num_leaves"], "why": "test size"})
    doc["workloads"].append({
        "name": "tiny4.train", "config": "tiny-host4",
        "traffic": "train_plain_dp", "chips": CHIPS, "why": "test size"})
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "criteo256x4.train" in metric.get("workloads", ()):
            metric["workloads"] = metric["workloads"] + ["tiny4.train"]
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)


@pytest.fixture(scope="module")
def copy4(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("bench_copy4"))
    bench_copy.make_copy(dst)
    add_tiny4(dst)
    return dst, str(tmp_path_factory.mktemp("xla_cache4"))


def child4(cmd, copy4):
    dst, cache = copy4
    return child(["-c", "import os, runpy, sys; "
                  "os.environ['XLA_FLAGS'] = "
                  f"'--xla_force_host_platform_device_count={CHIPS}'; "
                  "sys.argv = sys.argv[1:]; "
                  "runpy.run_path(sys.argv[0], run_name='__main__')"]
                 + cmd, dst, cache)


@pytest.fixture(scope="module")
def traced4(copy4):
    dst, _ = copy4
    return child4([os.path.join(dst, "perfbench", "run.py"), "--workload",
                   "tiny4.train", "--seed", str(2 ** 31 + 29), "--seconds",
                   "0.2", "--trace", "1", "--cpu-selftest-rows", str(ROWS)],
                  copy4)


@pytest.fixture(scope="module")
def readings4(copy4):
    """One process a mode: the fault is compiled into the program."""
    dst, _ = copy4
    rows = {}
    for mode in ("sound", "rank_left_out"):
        r = child4([os.path.join(dst, "perfbench", "control",
                                 "faults_dp.py"),
                    "--workload", "tiny4.train", "--seeds", "11",
                    "--seconds", "0.2", "--cpu-selftest-rows", str(ROWS),
                    "--modes", mode], copy4)
        assert r.returncode == 0, r.stderr[-4000:]
        rows[mode] = [json.loads(line) for line in r.stdout.splitlines()
                      if line.startswith("{")][-1]
    return rows


def test_the_real_cell_is_declared_with_its_files():
    from harness.manifest import Manifest
    man = Manifest(REPO)
    cell = man.cell("criteo256x4.train")
    assert cell["chips"] == 4 and cell["traffic"] == "train_plain_dp"
    config, one = man.config(cell), man.config(man.cell("criteo256.train"))
    assert config["num_data"] == 4 * one["num_data"] == 26_562_500
    for same in ("published", "precision", "reference", "data",
                 "num_features"):
        assert config[same] == one[same], same
    assert {k: v for k, v in config["params"].items()
            if k not in ("tree_learner", "num_devices")} == one["params"]
    assert man.traffic(cell)["check"] == \
        man.traffic(man.cell("criteo256.train"))["check"]
    assert set(man.limits(cell)["limits"]) == set(
        man.limits(man.cell("criteo256.train"))["limits"])
    names = {m["name"] for m in man.metrics(cell, "per_layer")}
    assert set(NEW) <= names
    assert {m["name"] for m in man.metrics(cell, "end_to_end")} == {
        "setup_s", "train.ms_per_round", "train.peak_hbm_gib"}
    one_names = {m["name"] for m in man.metrics(
        man.cell("criteo256.train"), "per_layer")}
    assert not set(NEW) & one_names


def test_traced_run_of_four_ranks_prints_the_contracts_line(traced4):
    assert traced4.returncode == 0, traced4.stderr[-4000:]
    line = json.loads(traced4.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == CHIPS
    assert line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert {"setup.datagen_s", "setup.construct_s", "setup.first_round_s",
            "train.compiles_in_window", "setup.train_init_s"} <= set(got)
    assert got["train.compiles_in_window"]["value"] == 0
    # the counters are the program's, so the CPU reads them too: a root
    # reduction a tree and one a split, of the local [F, B, 2] float32
    assert got["comm.reductions_per_round"]["value"] == LEAVES
    per = got["comm.wire_bytes_per_round"]["value"] / LEAVES
    assert per % (67 * 2 * 4) == 0 and 64 <= per / (67 * 2 * 4) <= 256
    # a CPU run has no device plane: every trace metric stays silent
    assert not set(got) & {"comm.allreduce_ms_per_round",
                           "comm.hist_allreduce_roofline",
                           "train.rank_skew_pct", "train.round_mfu"}


def test_sound_run_reports_end_to_end_and_the_control_reads_above(readings4):
    row = readings4["sound"]
    assert row["correct"] is True, row
    assert set(row["metrics"]) == {"setup_s", "train.ms_per_round",
                                   "train.peak_hbm_gib"}
    for name in ("leaf_weight_median_gap", "leaf_value_median_gap",
                 "split_gain_median_gap"):
        low, sound = row["control"][name], row["check"][name]
        assert low > sound["limit"] and low >= 3 * sound["value"], (name, row)


def test_a_rank_left_out_of_the_reduction_is_not_correct(readings4):
    row = readings4["rank_left_out"]
    assert row["correct"] is False and "crashed" not in row, row
    assert "leaf_weight_gap" in failing(row), row
    # every row is still routed and counted: the fault is in the sums
    assert row["check"]["leaf_weight_gap"]["value"] > 0.1


# -- the harness's new pieces, in this process -------------------------

@pytest.fixture(scope="module")
def small():
    """A table, the serial learner's model and the four-rank learner's."""
    import jax
    import lightgbm_tpu as lgb
    from harness import datagen
    from harness.manifest import Manifest
    man = Manifest(REPO)
    cell = man.cell("criteo256x4.train")
    cfg, traffic = man.config(cell), man.traffic(cell)
    X, y = datagen.make_table(cfg["data"], 3000, 29, threads=2)
    params = dict(cfg["params"], num_leaves=LEAVES, min_data_in_leaf=20)
    serial = {k: v for k, v in params.items()
              if k not in ("tree_learner", "num_devices")}
    out = {}
    for name, p in (("serial", serial), ("ranks", params)):
        ds = lgb.Dataset(X, label=y, params={"max_bin": p["max_bin"]})
        bst = lgb.train(p, ds, 4)
        out[name] = (bst.dump_model(), np.asarray(bst._engine.score)[0])
    limits = man.limits(cell)["cpu_selftest"]
    return X, y, cfg, traffic, out, limits, jax.devices()[:CHIPS]


def test_four_ranks_trees_are_the_serial_learners_and_pass_the_check(small):
    from harness import check, check_dp
    X, y, cfg, traffic, out, limits, devices = small

    strip = ("leaf_value", "leaf_weight", "internal_value",
             "internal_weight", "split_gain")

    def structure(node):
        return {k: (structure(v) if isinstance(v, dict) else v)
                for k, v in node.items() if k not in strip}

    (m_s, _), (m_r, score_r) = out["serial"], out["ranks"]
    assert [structure(t["tree_structure"]) for t in m_r["tree_info"]] == \
        [structure(t["tree_structure"]) for t in m_s["tree_info"]]
    numbers = check_dp.compare(m_r, score_r, X, y, cfg["reference"], 0.1,
                               traffic["check"], 29, "float32", devices,
                               warm=1)
    correct, table = check.judge(numbers, limits)
    assert correct, table


def test_reference_in_blocks_is_the_reference_whole(small):
    """The ten numbers by ``check_dp`` over four row blocks against
    ``check``'s over the whole table: the counts to the row, the rest
    to float32 summation order."""
    from harness import check, check_dp
    X, y, cfg, traffic, out, _, devices = small
    model, score = out["serial"]
    args = (model, score, X, y, cfg["reference"], 0.1, traffic["check"], 29,
            "float32")
    whole = check.compare(*args, warm=1, control_dtype="bfloat16")
    blocks = check_dp.compare(*args, devices, warm=1,
                              control_dtype="bfloat16")
    assert blocks["leaf_count_mismatch"] == whole["leaf_count_mismatch"] == 0
    assert blocks["followed"] == whole["followed"]
    for name in check.NUMBERS[1:]:
        assert blocks[name] == pytest.approx(whole[name], rel=0.2, abs=2e-6), \
            name
    for name, v in whole["control"].items():
        assert blocks["control"][name] == pytest.approx(v, rel=0.1), name
    for key, v in whole["log_loss"].items():
        assert blocks["log_loss"][key] == pytest.approx(v, rel=1e-6)


def test_blocks_are_contiguous_equal_shares_one_a_device(small):
    from harness import reference_dp as RD
    X, _, _, _, _, _, devices = small
    assert RD.block_bounds(10, 4) == [(0, 2), (2, 5), (5, 7), (7, 10)]
    assert RD.block_bounds(26_562_500, 4)[1] == (6_640_625, 13_281_250)
    blocks = RD.table_to_devices(X[:1001], devices)
    assert [b.shape for b in blocks] == [(67, 250), (67, 250), (67, 250),
                                         (67, 251)]
    assert [list(b.devices())[0] for b in blocks] == list(devices)
    np.testing.assert_array_equal(np.asarray(blocks[3]), X[750:1001].T)


def test_candidate_sums_added_over_blocks_give_the_references_best_gain(
        small):
    import jax.numpy as jnp
    from harness import reference as R, reference_dp as RD
    X, y, _, _, _, _, devices = small
    rs = np.random.default_rng(3)
    g = rs.standard_normal(len(y)).astype(np.float32)
    h = rs.random(len(y)).astype(np.float32)
    w = (rs.random(len(y)) < 0.7).astype(np.float32)
    cands = jnp.asarray(R.candidate_thresholds(X, 5, 16, 2000))
    want = float(jnp.max(R.node_best_gain(
        R.table_to_device(X), cands, jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(w), jnp.float32(20), jnp.float32(1e-3),
        jnp.float32(0.0))))
    sums = RD.each(lambda x, gb, hb, wb: RD.node_candidate_sums(
        x, cands, gb, hb, wb), RD.table_to_devices(X, devices),
        RD.rows_to_devices(g, devices), RD.rows_to_devices(h, devices),
        RD.rows_to_devices(w, devices))
    got = RD.best_gain(RD.add_up([s[0] for s in sums]),
                       RD.add_up([s[1] for s in sums]), 20.0, 1e-3, 0.0)
    assert got == pytest.approx(want, rel=1e-4)


def hand_trace():
    hist = ("%psum.84 = f32[67,256,2]{1,0,2:T(8,128)S(1)} all-reduce("
            "%get-tuple-element.4912), channel_id=1")
    sums = ("%all-reduce.26 = (s32[]{:T(128)}, s32[]{:T(128)}) all-reduce("
            "%get-tuple-element.4906, %sub.385), channel_id=1")
    user = "%fusion.9 = f32[67,256,2] fusion(%psum.84, %all-reduce.26)"

    def plane(shift, wait):
        return {"XLA Ops": [
            ("%while.1 = (...) while(%tuple.3)", 1.0 + shift, 1.0),
            (hist, 1.1 + shift, 0.010 + wait), (sums, 1.2 + shift, 0.002),
            (user, 1.3 + shift, 0.2),
            (hist, 5.0, 0.5)],                       # outside the window
            "XLA Modules": [("jit_fn", 1.0 + shift, 1.0)]}

    return {"devices": {"/device:TPU:0": plane(0.0, 0.0),
                        "/device:TPU:1": plane(0.05, 0.004),
                        "/device:TPU:9": {"XLA Ops": []}},
            "host": [("perfbench_round", 0.9, 1.3)]}


def test_collectives_are_read_by_opcode_and_meant_over_the_planes():
    from harness import trace_collectives as TC
    assert TC.allreduce_kind("%psum.84 = f32[2] all-reduce(%x)") == ""
    assert TC.allreduce_kind("%all-reduce-done.2") == "-done"
    assert TC.allreduce_kind(
        "%ar.1 = f32[2] all-reduce-start(%x), channel_id=1") == "-start"
    assert TC.allreduce_kind(
        "%fusion.9 = f32[2] fusion(%psum.84, %all-reduce.26)") is None
    got = TC.reduce(hand_trace(), "perfbench_round")
    assert [p["plane"] for p in got["planes"]] == ["/device:TPU:0",
                                                   "/device:TPU:1"]
    assert got["allreduce_ops"] == 2
    assert got["allreduce_s"] == pytest.approx((0.012 + 0.016) / 2)
    # both planes are busy for their while's second: no skew to see
    assert got["busy_skew_pct"] == pytest.approx(0.0, abs=1e-9)
    assert TC.reduce({"devices": {}, "host": []}, "x") is None


def test_ring_bytes_and_the_interconnects_rate():
    from harness import ici
    assert ici.ring_allreduce_bytes(136_680, 4) == 1.5 * 136_680
    assert ici.ring_allreduce_bytes(100, 1) == 0
    rate = ici.lookup("TPU v5 lite")
    assert rate["bytes_per_s"] == 1600e9 / 8 and "source" in rate
    assert ici.least_seconds(200e9, 2, rate) == pytest.approx(1.0)
    with pytest.raises(KeyError, match="no ICI rate"):
        ici.lookup("TPU v9")


def dp_driver():
    from harness.manifest import Manifest
    man = Manifest(REPO)
    cell = man.cell("criteo256x4.train")
    return man, cell, man.driver(man.traffic(cell))


@pytest.mark.parametrize("dtype, name, exact", [
    (None, "int32", 2 ** 31 - 1),          # the program as it is
    ("float32", "float32", 2 ** 24),       # the counts before int32
    ("missing", None, None)])              # a program that cannot be asked
def test_the_driver_asks_the_programs_tree_how_many_rows_it_counts(
        monkeypatch, dtype, name, exact):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import grow
    init = grow._init_tree
    if dtype == "missing":
        monkeypatch.delattr(grow, "_init_tree")
    elif dtype:
        monkeypatch.setattr(grow, "_init_tree", lambda *a: init(*a)._replace(
            leaf_count=jnp.zeros((a[0],), dtype)))
    assert dp_driver()[2].tree_row_counts(jax) == (name, exact)


def test_a_program_with_float32_counts_fails_before_the_table_is_made(
        monkeypatch):
    """The real cell's 26,562,500 rows against a tree that counts
    exactly to 2**24: no table, no training, a message."""
    import jax
    from harness import datagen
    man, cell, driver = dp_driver()
    monkeypatch.setattr(driver, "tree_row_counts",
                        lambda _: ("float32", 2 ** 24))
    monkeypatch.setattr(datagen, "make_table", lambda *a, **k: pytest.fail(
        "the table was made"))
    ctx = {"log": lambda msg: None, "cell": cell, "config": man.config(cell),
           "traffic": man.traffic(cell), "t_start": 0.0, "trace": False,
           "devices": jax.devices()[:CHIPS], "selftest_rows": 0, "seed": 1}
    with pytest.raises(RuntimeError, match="cannot run configuration "
                       "'criteo-dp256-host4'.*float32.*16,777,216.*"
                       "26,562,500"):
        driver.run(ctx)
    assert man.config(cell)["expect"]["tree_row_counts"] == "int32"
