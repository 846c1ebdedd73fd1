"""The reader ``program_scope`` (ISSUE 37): the grower's device time by
the program's own scopes, and per row the program says it streamed, read
through the manifest as the harness reads it (a per-layer entry, its
metric file, its reader file) from observations built by hand; and
``rank.row_fill_pct`` through the ratio reader that was there.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from harness.manifest import Manifest  # noqa: E402

GROWER = "jit_grow_tree_impl"
CELLS = ["mslr30k.train_eval", "criteo256.train_eval", "bosch968.train_eval"]
MS = ("grow.hist_ms_per_round", "grow.partition_ms_per_round",
      "grow.split_scan_ms_per_round", "grow.fixed_ms_per_round",
      "grow.once_a_tree_ms_per_round", "grow.unscoped_ms_per_round")
PER_ROW = ("grow.hist_ns_per_row", "grow.partition_ns_per_row")
# one traced round of the grower as the driver hands it over, ms
SCOPES = {"grow/hist/build": 800.0, "grow/hist/subtract": 0.5,
          "grow/partition/gather": 200.0, "grow/partition/route": 8.0,
          "grow/partition/key_sort": 7.5, "grow/partition/payload": 4.0,
          "grow/split_scan": 11.0, "grow/fixed": 2.5, "grow/setup": 20.0,
          "grow/row_leaf": 12.0}
WANT = {"grow.hist_ms_per_round": 800.5,
        "grow.partition_ms_per_round": 219.5,
        "grow.split_scan_ms_per_round": 11.0,
        "grow.fixed_ms_per_round": 2.5,
        "grow.once_a_tree_ms_per_round": 32.0,
        "grow.unscoped_ms_per_round": 0.0}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO)


def metric(man, name):
    (found,) = [m for m in man.doc["per_layer"] if m["name"] == name]
    return found


def observed(scopes=SCOPES, rounds=3):
    return {"host": {"traced_rounds": rounds},
            "programs": {"scope_ms_per_round": {
                GROWER: dict(scopes), "jit__lambdarank_grads": {
                    "boost/gradients/lambdarank": 63.8}}}}


def plant_job(rows, trace_id="a" * 16):
    """A traced eager job's ``tree/fetch`` spans, one a round, stamped
    with ``rows[i]`` (``None``: a span without attrs, the parent's)."""
    from lightgbm_tpu.obs import trace
    job = trace.record_span("train/job", 100.0, 150.0, trace_id=trace_id)
    for i, attrs in enumerate(rows):
        trace.record_span("tree/fetch", 110.0 + i, 110.01 + i,
                          trace_id=trace_id, parent_id=job, attrs=attrs)


@pytest.mark.parametrize("name", MS + PER_ROW + ("rank.row_fill_pct",))
def test_the_new_metrics_are_entries_and_files_only(man, name):
    m = metric(man, name)
    assert m["moves"] == "train.ms_per_round" and "bound" not in m
    assert m["workloads"] == (["mslr30k.train_eval"]
                              if name.startswith("rank.") else CELLS)
    with open(os.path.join(REPO, "perfbench", "metrics",
                           name + ".json")) as fh:
        desc = json.load(fh)
    assert desc["name"] == name
    assert desc["reader"] == ("program_registry_ratio"
                              if name.startswith("rank.")
                              else "program_scope")
    if name in MS + PER_ROW:
        assert desc["args"]["program"] == GROWER
        assert m["layer"].startswith("grower")


@pytest.mark.parametrize("name", MS)
def test_program_scope_sums_the_named_scopes_of_the_one_program(man, name):
    assert man.read_metric(metric(man, name), observed()) \
        == pytest.approx(WANT[name])


def test_the_six_close_the_growers_account(man):
    """Every scope a one-chip grower can carry is in exactly one of the
    six, so they sum to the program's time by scope."""
    scopes = dict(SCOPES, **{"(unscoped)": 3.25})
    total = sum(man.read_metric(metric(man, n), observed(scopes))
                for n in MS)
    assert total == pytest.approx(sum(scopes.values()))


@pytest.mark.parametrize("case,want", [
    ("untraced", None), ("no_programs", None), ("another_program", None),
    ("parents_table", None), ("one_of_two_scopes", 12.0),
    ("unscoped_left", 34.8)])
def test_program_scope_reads_nothing_rather_than_guess(man, case, want):
    """``None`` where there is nothing to read; a scope of the list that
    the compiler left no op of counts 0 beside one that is there."""
    name = "grow.once_a_tree_ms_per_round"
    obs = observed()
    if case == "untraced":
        obs = {"host": {}, "programs": None}
    elif case == "no_programs":
        obs["programs"] = {"ms_per_round": {GROWER: 1090.0}}
    elif case == "another_program":
        del obs["programs"]["scope_ms_per_round"][GROWER]
    elif case == "parents_table":   # an executable from before the scopes
        obs = observed({k: v for k, v in SCOPES.items()
                        if k not in ("grow/setup", "grow/row_leaf")})
    elif case == "one_of_two_scopes":
        obs = observed({k: v for k, v in SCOPES.items()
                        if k != "grow/setup"})
    elif case == "unscoped_left":
        name = "grow.unscoped_ms_per_round"
        obs = observed(dict(SCOPES, **{"(unscoped)": 34.8}))
    got = man.read_metric(metric(man, name), obs)
    assert got is None if want is None else got == pytest.approx(want)


def test_program_scope_per_row_divides_by_what_the_spans_say(man):
    plant_job([{"hist_rows": 2_400_000, "partition_rows": 8_000_000},
               {"hist_rows": 2_600_000, "partition_rows": 9_000_000},
               {"hist_rows": 2_500_000, "partition_rows": 10_000_000}])
    obs = observed()
    # 800.5 ms a round over 2.5M rows a round, in ns a row
    assert man.read_metric(metric(man, "grow.hist_ns_per_row"), obs) \
        == pytest.approx(800.5e6 / 2.5e6)
    assert man.read_metric(metric(man, "grow.partition_ns_per_row"), obs) \
        == pytest.approx(219.5e6 / 9e6)


@pytest.mark.parametrize("case", ["no_spans", "no_attrs", "one_bare_span",
                                  "rounds_differ", "no_rows",
                                  "no_traced_rounds", "no_scopes"])
def test_program_scope_per_row_reads_nothing_rather_than_guess(man, case):
    stamped = {"hist_rows": 2_400_000, "partition_rows": 8_000_000}
    obs = observed()
    if case == "no_attrs":          # the parent of the PR that stamped them
        plant_job([None, None, None])
    elif case == "one_bare_span":
        plant_job([stamped, {"rows": 45}, stamped])
    elif case == "rounds_differ":   # the whole job was traced, not 3 rounds
        plant_job([stamped] * 5)
    elif case == "no_rows":
        plant_job([dict(stamped, hist_rows=0, partition_rows=0)] * 3)
    elif case == "no_traced_rounds":
        plant_job([stamped] * 3)
        obs["host"] = {}
    elif case == "no_scopes":
        plant_job([stamped] * 3)
        obs["programs"] = None
    for name in PER_ROW:
        assert man.read_metric(metric(man, name), obs) is None


def test_rank_row_fill_is_rows_over_row_slots(man, monkeypatch):
    import importlib
    from lightgbm_tpu.obs import schemas
    reg_mod = importlib.import_module("lightgbm_tpu.obs.registry")
    fresh = reg_mod.MetricsRegistry()
    monkeypatch.setattr(reg_mod, "registry", fresh)
    m = metric(man, "rank.row_fill_pct")
    assert man.read_metric(m, {}) is None       # no pass yet
    for _ in range(2):
        fresh.counter("rank_rows").inc(2_270_296)
        fresh.counter("rank_row_slots").inc(3_516_614)
    assert man.read_metric(m, {}) == pytest.approx(64.5591, abs=1e-3)
    # a program that does not declare the counter (this PR's parent)
    monkeypatch.setattr(schemas, "METRICS", {
        k: v for k, v in schemas.METRICS.items() if k != "rank_rows"})
    assert man.read_metric(m, {}) is None
