"""The work a round needs, on a three-leaf tree by hand; the peaks
table; the observation reader. No JAX."""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import peaks, work_model  # noqa: E402


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_three_leaf_tree_by_hand():
    # 1000 rows; the root splits 700 | 300, the 700 split 600 | 100:
    # histograms over 1000 (root) + 300 + 100 rows, the larger child's
    # by subtraction
    rows = work_model.hist_rows(1000, [700, 600], [300, 100])
    assert rows == 1400
    whole = work_model.round_work(1000, rows, 67)
    assert whole == {"ops": 1400 * 67 * 2,
                     "bytes": 1400 * (67 + 8) + 24000}


def test_hist_rows_of_a_dumped_tree():
    tree = {"tree_structure": {
        "split_index": 0, "internal_count": 1000,
        "left_child": {"split_index": 1, "internal_count": 700,
                       "left_child": {"leaf_index": 0, "leaf_count": 600},
                       "right_child": {"leaf_index": 2, "leaf_count": 100}},
        "right_child": {"leaf_index": 1, "leaf_count": 300}}}
    assert work_model.tree_hist_rows(tree, 1000) == 1400
    assert work_model.tree_hist_rows(
        {"tree_structure": {"leaf_value": 0.0}}, 1000) == 1000


def test_least_seconds_says_which_bound():
    chip = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work_model.least_seconds({"ops": 1000, "bytes": 50}, chip) \
        == (10.0, "flops")
    assert work_model.least_seconds({"ops": 100, "bytes": 50}, chip) \
        == (5.0, "bytes")


def test_v5e_row_and_unknown_kind_raises():
    chip = peaks.lookup("TPU v5 lite")
    assert chip["flops_per_s"] == 197e12 and chip["bytes_per_s"] == 819e9
    for kind in ("TPU v5", "tpu v5 lite", "cpu", ""):
        with pytest.raises(KeyError, match="no peaks for device_kind"):
            peaks.lookup(kind)


def test_roofline_share_from_work_and_time():
    chip = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    obs = {"trace": {"window_s": 40.0},
           "work": {"round": {"ops": 1000, "bytes": 200}, "peaks": chip}}
    assert reader("roofline_pct").read(obs, {}) == 50.0


def test_observation_reader_walks_a_dotted_path():
    r = reader("observation")
    obs = {"host": {"construct_s": 1.5, "none": None},
           "counters": {"compiles_in_window": 0}}
    assert r.read(obs, {"path": "host.construct_s"}) == 1.5
    # a count of nought is a reading; an absent one is silence
    assert r.read(obs, {"path": "counters.compiles_in_window"}) == 0
    assert r.read(obs, {"path": "host.none"}) is None
    assert r.read(obs, {"path": "host.missing.deeper"}) is None
