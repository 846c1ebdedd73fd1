"""A temporary copy of the benchmark with a cell of test size added.

The copy takes ``BENCHMARK.json`` and ``perfbench/`` as they are and only
*adds*: a configuration, a traffic mix, a driver kind, a per-layer metric
with its reader, limits, and the entries that name them. No file of the
original is edited, which is what a later PR has to be able to do.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ECHO_DRIVER = '''"""Driver kind ``echo_rounds``: no program, counts to the
traffic's ``rounds``; stands for a later PR's new driver kind."""


def run(ctx):
    rounds = int(ctx["traffic"]["rounds"])
    table = {"echo_gap": {"value": 0.0,
                          "limit": ctx["limits"]["echo_gap"]}}
    return {"correct": True, "check": table, "attempted": rounds,
            "failed": 0, "memory_peak_bytes": None,
            "end_to_end": {"setup_s": 0.5, "train.ms_per_round": 1.0,
                           "train.peak_hbm_gib": 0.25},
            "observations": {"host": {}, "echo": {"rounds": rounds}}}
'''

ECHO_READER = '''"""Reads the echo driver's count; nothing to read elsewhere."""


def read(obs, args):
    echo = obs.get("echo")
    return None if not echo else echo["rounds"] * args["times"]
'''


def write_json(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "x") as fh:      # "x": never over a file that is there
        json.dump(doc, fh, indent=1)


def make_copy(dst, rows_leaves=(6000, 15)):
    """``dst``: an empty directory; returns the new cells' names."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(dst, "perfbench")
    with open(os.path.join(bench, "configs", "criteo-dp256-rank.json")) as fh:
        config = json.load(fh)
    rows, leaves = rows_leaves
    config["num_data"] = rows
    config["params"] = dict(config["params"], num_leaves=leaves,
                            min_data_in_leaf=20)
    # on the CPU the histogram is an exact float32 scatter whatever
    # hist_precision says, so the test's control is the program's other
    # lower-precision path
    config["control_params"] = {"use_quantized_grad": True}
    write_json(os.path.join(bench, "configs", "tiny-rank.json"), config)
    write_json(os.path.join(bench, "traffic", "echo_plain.json"),
               {"driver": "echo_rounds", "rounds": 7})
    with open(os.path.join(bench, "drivers", "echo_rounds.py"), "x") as fh:
        fh.write(ECHO_DRIVER)
    with open(os.path.join(bench, "readers", "echo_times.py"), "x") as fh:
        fh.write(ECHO_READER)
    write_json(os.path.join(bench, "metrics", "echo.answer.json"),
               {"name": "echo.answer", "reader": "echo_times",
                "args": {"times": 6}})
    with open(os.path.join(bench, "limits", "criteo256.train.json")) as fh:
        limits = json.load(fh)
    # float32 against float32 on a few thousand rows reads ~5e-4
    limits["cpu_selftest"].update(leaf_weight_gap=0.003, leaf_value_gap=0.003,
                                  split_gain_gap=0.003, score_gap=0.003)
    write_json(os.path.join(bench, "limits", "tiny.train.json"), limits)
    write_json(os.path.join(bench, "limits", "tiny.echo.json"),
               {"limits": {"echo_gap": 0.0},
                "cpu_selftest": {"echo_gap": 0.0}})
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "tiny-rank", "source": "tests/perfbench",
        "file": "perfbench/configs/tiny-rank.json",
        "reduced": ["num_data", "num_leaves"], "why": "test size"})
    doc["workloads"] += [
        {"name": "tiny.train", "config": "tiny-rank",
         "traffic": "train_plain", "chips": 1, "why": "test size"},
        {"name": "tiny.echo", "config": "tiny-rank",
         "traffic": "echo_plain", "chips": 1, "why": "a new driver kind"}]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        # a new cell joins the metrics it reports: one more name in a list
        if "workloads" in metric:
            metric["workloads"] = metric["workloads"] + ["tiny.train"] \
                + (["tiny.echo"] if metric in doc["end_to_end"] else [])
    doc["per_layer"].append({
        "name": "echo.answer", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "train.ms_per_round", "workloads": ["tiny.echo"]})
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1)
    return "tiny.train", "tiny.echo"
