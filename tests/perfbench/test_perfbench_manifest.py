"""``BENCHMARK.json`` against the contract's limits, and the files its
names lead to. Pure JSON and paths: no JAX."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "head_dim", "expansion", "experts_per_tok", "num_features",
               "num_leaves", "max_bin")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def one_line(text, most=200):
    return isinstance(text, str) and 1 <= len(text) <= most \
        and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(doc["command"]) <= 32
    assert all(one_line(w) for w in doc["command"])
    assert 1 <= len(doc["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in doc["paths"])


def test_command_names_only_files_under_paths(doc):
    for word in doc["command"][1:]:
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in doc["paths"]), word


def test_run_seconds_fits_a_full_check_of_24_cells(doc):
    rs = doc["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text_use_the_allowed_characters(doc):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          group, entry["name"]))
    metric_names = [n for m, _, n in names if m]
    assert len(metric_names) == len(set(metric_names))
    for group in ("configs", "workloads"):
        own = [n for _, g, n in names if g == group]
        assert len(own) == len(set(own))
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"])
    for c in doc["configs"]:
        assert one_line(c["why"]) and one_line(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_entries_hold_just_the_contracts_keys(doc):
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"])


def test_configs_are_files_under_paths_and_cut_no_width(doc):
    used = {w["config"] for w in doc["workloads"]}
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))
    for c in doc["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert PATH.match(c["file"])
        with open(os.path.join(REPO, c["file"])) as fh:
            body = json.load(fh)
        for key in c["reduced"]:
            assert not any(w in key for w in WIDTH_WORDS) \
                and not key.endswith(("_dim", "_rank")), key
            # a reduced key states what it was cut from
            assert key in body["published"], key
        # the widths are the source's own
        assert body["num_features"] == body["published"]["num_features"]
        assert body["params"]["num_leaves"] == body["published"]["num_leaves"]


def test_cells_are_unique_pairs_on_one_or_four_chips(doc):
    cells = doc["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in doc["configs"]}
    assert all(w["config"] in configs for w in cells)


def test_end_to_end_metrics_have_bounds_and_a_setup_time(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def reports(doc, metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    assert 1 <= len(doc["per_layer"]) <= 128
    for m in doc["per_layer"]:
        assert "bound" not in m
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            assert reports(doc, moved, cell), (m["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer(doc):
    for w in doc["workloads"]:
        e2e = [m["name"] for m in doc["end_to_end"]
               if reports(doc, m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(doc, m, w["name"]) for m in doc["per_layer"])


def test_rooflines_come_with_the_whole_steps_mfu(doc):
    for m in doc["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._\-]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in doc["per_layer"]), m["name"]


def test_every_file_a_cell_names_exists(doc):
    bench = os.path.join(REPO, "perfbench")
    for w in doc["workloads"]:
        traffic = os.path.join(bench, "traffic", w["traffic"] + ".json")
        with open(traffic) as fh:
            kind = json.load(fh)["driver"]
        assert os.path.isfile(os.path.join(bench, "drivers", kind + ".py"))
        with open(os.path.join(bench, "limits", w["name"] + ".json")) as fh:
            limits = json.load(fh)
        assert limits["limits"].keys() == limits["cpu_selftest"].keys()
    for m in doc["per_layer"]:
        with open(os.path.join(bench, "metrics", m["name"] + ".json")) as fh:
            desc = json.load(fh)
        assert desc["name"] == m["name"]
        assert os.path.isfile(os.path.join(bench, "readers",
                                           desc["reader"] + ".py"))


def test_paths_hold_files_named_from_a_names_characters(doc):
    for p in doc["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert PATH.match(rel), rel
