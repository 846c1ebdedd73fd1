"""The reduction from a trace to device busy time, idle gaps, op classes
and programs per round, on a trace built by hand, and the readers that
take their metrics from it. No JAX."""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import trace_reduce as T  # noqa: E402

SPAN = "perfbench_round"
PLANE = "/device:TPU:0"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WHILE = "%while.7 = (s32[], u32[64]) while(%tuple.3), body=%b"
SORT = "%sort.12 = (s32[16], s32[16]) sort(%key, %iota), dimensions={0}"
FUSION = "%fusion.44 = u32[16,18] fusion(%pad, %sort.12), kind=kCustom"
COPY = "%copy.3 = u32[16,18] copy(%fusion.44)"


def hand_trace():
    """Three rounds of 1 s each from t=10; in each one program runs
    from +0.1 to +0.9: a ``while`` of 0.8 s that holds a sort (0.3 s),
    a fusion that reads the sort (0.2 s, with a nested copy of 0.05 s)
    and 0.3 s of its own; then 0.2 s of nothing while the host fetches.
    Ops are named by their HLO line, as the TPU's trace names them."""
    ops, mods, host = [], [], []
    for r in range(3):
        t = 10.0 + r
        host.append((SPAN, t, 1.0))
        host.append(("fetch_tree", t + 0.9, 0.1))
        mods.append(("jit_fused_iter(123)", t + 0.1, 0.8))
        ops += [(WHILE, t + 0.1, 0.8), (SORT, t + 0.15, 0.3),
                (FUSION, t + 0.5, 0.2), (COPY, t + 0.55, 0.05)]
    # an op before the first round, outside the traced interval
    ops.append(("%fusion.1 = f32[8] fusion(%p)", 9.0, 0.5))
    mods.append(("jit_other(5)", 9.0, 0.5))
    return {"devices": {PLANE: {T.OPS_LINE: ops, T.MODULES_LINE: mods}},
            "host": host}


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(hand_trace(), span_name=SPAN)


def test_union_not_sum():
    total, merged = T.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.75)])
    assert total == pytest.approx(4.0)
    assert merged == [[0, 3], [5, 6]]


def test_busy_is_the_union_of_nested_ops_inside_the_spans(reduced):
    assert reduced["window_s"] == pytest.approx(3.0)
    # nested ops are not counted twice, the op before the window not at all
    assert reduced["busy_s"] == pytest.approx(2.4)
    assert reduced["spans"] == 3 and reduced["devices"] == 1


def test_self_time_goes_to_the_innermost_op_and_its_class(reduced):
    cats = reduced["category_s"]
    assert cats["sort"] == pytest.approx(0.9)
    # the fusion that reads %sort.12 is no sort: the head names the op
    assert set(cats) == {"sort", "other"}
    assert cats["other"] == pytest.approx(2.4 - 0.9)
    top = dict(reduced["device_ops"])
    assert top[WHILE] == pytest.approx(0.9)
    # the fusion's 0.2 s less the copy nested in it
    assert top[FUSION] == pytest.approx(0.45)
    assert top[COPY] == pytest.approx(0.15)


def test_programs_per_round_counts_module_events_in_the_window(reduced):
    assert reduced["module_executions"] == 3
    assert reduced["module_names"] == ["jit_fused_iter(123)"]
    obs = {"trace": reduced, "host": {"traced_rounds": 3}}
    assert reader("trace_dispatches_per_round").read(obs, {}) == 1.0


def test_idle_gaps_are_named_by_what_the_host_did(reduced):
    gaps = reduced["idle_gaps"]
    # between two programs: 0.1 s after one and 0.1 s before the next
    assert gaps[0][1] == pytest.approx(0.2)
    assert gaps[0][0] == SPAN + "/fetch_tree"
    assert sum(g for _, g in gaps) == pytest.approx(0.6)
    obs = {"trace": reduced, "host": {"traced_rounds": 3}}
    assert reader("device_idle_pct").read(obs, {}) == pytest.approx(20.0)


def test_op_head_and_names_kept_short():
    assert T.op_head(SORT) == "sort.12" and T.classify(SORT) == "sort"
    assert T.classify(FUSION) == "other"
    long = "%fusion.9 = f32[4] fusion(" + "%p, " * 100 + ")"
    r = T.reduce({"devices": {PLANE: {T.OPS_LINE: [(long, 0.0, 1.0)],
                                      T.MODULES_LINE: []}}, "host": []})
    assert len(r["device_ops"][0][0]) == T.OP_NAME_CHARS


def test_class_time_per_round(reduced):
    obs = {"trace": reduced, "host": {"traced_rounds": 3}}
    r = reader("trace_class_ms_per_round")
    assert r.read(obs, {"class": "sort"}) == pytest.approx(300.0)
    assert r.read(obs, {"class": "matmul"}) is None


@pytest.mark.parametrize("name,args", [
    ("trace_class_ms_per_round", {"class": "sort"}),
    ("trace_dispatches_per_round", {}),
    ("device_idle_pct", {}),
    ("roofline_pct", {}),
    ("observation", {"path": "host.startup_s"}),
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, args):
    obs = {"trace": None, "work": None, "host": {}}
    assert reader(name).read(obs, args) is None


def test_a_class_that_never_ran_is_silent_not_zero(reduced):
    only_other = T.reduce({"devices": {PLANE: {
        T.OPS_LINE: [("%add.1 = f32[] add(%a, %b)", 0.0, 1.0)],
        T.MODULES_LINE: []}},
        "host": []})
    obs = {"trace": only_other, "host": {"traced_rounds": 1},
           "work": None}
    assert reader("trace_class_ms_per_round").read(
        obs, {"class": "sort"}) is None
    assert reader("trace_dispatches_per_round").read(obs, {}) is None


def test_no_device_plane_gives_no_trace():
    assert T.reduce({"devices": {}, "host": [(SPAN, 0.0, 1.0)]},
                    span_name=SPAN) is None


def test_devices_are_averaged():
    one = [("%add.1 = f32[] add(%a, %b)", 0.0, 1.0)]
    two = [("%add.1 = f32[] add(%a, %b)", 0.0, 0.5)]
    r = T.reduce({"devices": {
        "/device:TPU:0": {T.OPS_LINE: one, T.MODULES_LINE: []},
        "/device:TPU:1": {T.OPS_LINE: two, T.MODULES_LINE: []}},
        "host": []}, window=(0.0, 1.0))
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(0.75)
