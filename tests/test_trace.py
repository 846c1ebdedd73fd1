"""Distributed tracing plane (ISSUE 16, obs/trace.py,
docs/OBSERVABILITY.md "Tracing").

Layers under test:

1. Span recorder: record/drain contract, buffer cap + drop counter,
   the ``{"event": "span"}`` schema, context propagation (explicit,
   env-inherited, and the ``span()`` context manager).
2. Per-iteration derivation: ``record_iteration_spans`` turns one
   telemetry iteration event into a ``train/iteration`` parent plus
   sequential ``phase/*`` children, with the fused-scan host-gap
   decomposition on scan iterations.
3. The ``python -m lightgbm_tpu trace`` CLI: stream merging across
   ``.rankN``/``.fleet`` suffixes, truncated-final-line tolerance vs
   mid-file corruption, cross-process clock-skew correction against
   synthetic skewed streams, Chrome trace-event (Perfetto) export
   schema, named critical-path reconstruction, and the jax-free
   subprocess proof.
4. Propagation through the serve protocol: a request's ``trace``
   field becomes a ``serve/request`` parent with queue-wait /
   batch-window / dispatch / reply children.
5. Env-driven device captures (utils/timer.py EnvCapture):
   ``LIGHTGBM_TPU_TRACE_TO`` whole-run and ``LIGHTGBM_TPU_XPROF``
   iteration-window wiring, plus ``timed()`` staying a shared no-op
   outside any capture.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tests._mp_utils import REPO_DIR  # noqa: E402

from lightgbm_tpu.obs import trace as T  # noqa: E402


# ---------------------------------------------------------------------
# helpers: fabricate span dicts / streams with controlled clocks
# ---------------------------------------------------------------------

def _span(name, mono, dur, *, wall_offset=1_000_000.0, proc="pidX",
          trace_id="t" * 16, span_id=None, parent_id=None, attrs=None):
    """A raw span event whose wall clock is ``mono + wall_offset`` —
    i.e. a process whose monotonic origin sits ``wall_offset`` seconds
    before the shared wall clock."""
    return {"event": "span", "name": name, "trace_id": trace_id,
            "span_id": span_id or T.new_span_id(),
            "parent_id": parent_id, "wall": mono + wall_offset,
            "mono": mono, "dur": dur, "proc": proc,
            "attrs": attrs or {}}


def _write_stream(path, events, *, truncate_tail=None):
    with open(path, "w", encoding="utf-8") as fh:
        for ev in events:
            fh.write(json.dumps(ev) + "\n")
        if truncate_tail is not None:
            fh.write(truncate_tail)  # no newline: mid-write crash


# ---------------------------------------------------------------------
# 1. span recorder basics
# ---------------------------------------------------------------------

def test_record_span_schema_and_drain():
    sid = T.record_span("unit/one", 1.0, 2.0,
                        trace_id="a" * 16, attrs={"k": 1})
    pending = T.span_events_snapshot()
    assert len(pending) == 1
    ev = pending[0]
    assert tuple(ev.keys()) == T.SPAN_EVENT_KEYS
    assert ev["event"] == "span"
    assert ev["span_id"] == sid
    assert ev["trace_id"] == "a" * 16
    assert ev["dur"] == pytest.approx(1.0)
    assert ev["attrs"] == {"k": 1}
    assert ev["proc"].startswith("pid")
    # wall/mono are a paired anchor at span start
    assert ev["mono"] == 1.0
    assert ev["wall"] > 0
    drained = T.drain_span_events()
    assert [e["span_id"] for e in drained] == [sid]
    assert T.drain_span_events() == []
    assert T.span_events_snapshot() == []


def test_buffer_cap_drops_then_drain_resets(monkeypatch):
    monkeypatch.setattr(T, "_SPANS_CAP", 8)
    for i in range(12):
        T.record_span("unit/cap", 0.0, 0.1, attrs={"i": i})
    assert len(T.span_events_snapshot()) == 8
    assert T._spans_dropped == 4
    assert len(T.drain_span_events()) == 8
    assert T._spans_dropped == 0
    # a fresh append after the drain lands again
    T.record_span("unit/after", 0.0, 0.1)
    assert len(T.drain_span_events()) == 1


def test_span_contextmanager_inherits_current_context():
    T.set_current_trace("b" * 16, "c" * 16)
    with T.span("unit/child") as h:
        assert h.trace_id == "b" * 16
        assert h.parent_id == "c" * 16
        h.attrs["extra"] = True
    (ev,) = T.drain_span_events()
    assert ev["trace_id"] == "b" * 16
    assert ev["parent_id"] == "c" * 16
    assert ev["attrs"] == {"extra": True}
    assert ev["dur"] >= 0.0


def test_span_contextmanager_roots_fresh_trace_without_context():
    T.set_current_trace(None)
    with T.span("unit/root"):
        pass
    (ev,) = T.drain_span_events()
    assert len(ev["trace_id"]) == 16
    assert ev["parent_id"] is None


def test_context_inherited_from_env(monkeypatch):
    monkeypatch.setenv(T.TRACE_CTX_ENV,
                       T.format_context("d" * 16, "e" * 16))
    monkeypatch.setattr(T, "_current", False)  # force re-parse
    ctx = T.current_context()
    assert ctx == {"trace_id": "d" * 16, "span_id": "e" * 16}


def test_context_env_malformed_is_absent(monkeypatch):
    monkeypatch.setenv(T.TRACE_CTX_ENV, "not-a-context")
    monkeypatch.setattr(T, "_current", False)
    assert T.current_context() is None


# ---------------------------------------------------------------------
# 2. per-iteration span derivation
# ---------------------------------------------------------------------

def _timed_nest():
    """train/job > train/round > (boosting/bagging, boosting/fused_iter
    > tree/defer), each a real ``timed`` section with a little work."""
    import time
    from lightgbm_tpu.utils.timer import timed
    with timed("train/job", job=True, trace_root=True):
        with timed("train/round"):
            with timed("boosting/bagging"):
                time.sleep(0.002)
            time.sleep(0.001)
            with timed("boosting/fused_iter"):
                with timed("tree/defer"):
                    time.sleep(0.002)


def test_timed_spans_real_parenting_and_real_starts():
    """Nested ``timed`` sections are real spans: parent = the enclosing
    section, one trace id for the job, true starts and ends (a child
    lies inside its parent, siblings in the order they ran, the gap
    between them kept) — nothing is laid out after the fact."""
    from lightgbm_tpu.utils.timer import Timer
    Timer.enable()
    try:
        _timed_nest()
    finally:
        Timer.enable(False)
    by = {e["name"]: e for e in T.drain_span_events()}
    assert set(by) == {"train/job", "train/round", "boosting/bagging",
                       "boosting/fused_iter", "tree/defer"}
    assert len({e["trace_id"] for e in by.values()}) == 1
    assert by["train/job"]["parent_id"] is None
    for child, parent in (("train/round", "train/job"),
                          ("boosting/bagging", "train/round"),
                          ("boosting/fused_iter", "train/round"),
                          ("tree/defer", "boosting/fused_iter")):
        c, p_ = by[child], by[parent]
        assert c["parent_id"] == p_["span_id"], child
        assert p_["mono"] <= c["mono"]
        assert c["mono"] + c["dur"] <= p_["mono"] + p_["dur"] + 1e-9
    bag, fused = by["boosting/bagging"], by["boosting/fused_iter"]
    assert bag["dur"] >= 0.002 and by["tree/defer"]["dur"] >= 0.002
    # the millisecond slept between the siblings is still between them
    assert fused["mono"] >= bag["mono"] + bag["dur"] + 0.001
    assert not any(n.startswith("phase/") for n in by)


def test_record_iteration_spans_adopts_real_children_and_scan_host_gap():
    """The recorder's ``train/iteration`` adopts the real spans of its
    interval (here recorded at top level, as ``record_iteration`` called
    outside ``train()`` sees them) and reads the scan host gap from the
    real blocking span, not from accumulator deltas."""
    import time
    from lightgbm_tpu.utils.timer import Timer, timed
    T.set_current_trace("f" * 16, "9" * 16)
    Timer.enable()
    try:
        t0 = time.perf_counter()
        with timed(T.FUSED_SCAN_PHASE):
            time.sleep(0.004)
        with timed("tree/defer"):
            pass
        time.sleep(0.003)
        t1 = time.perf_counter()
    finally:
        Timer.enable(False)
    T.record_iteration_spans({"iteration": 7, "scan": {"window": 8},
                              "phases": {"ignored": {"total": 9.0,
                                                     "count": 1}}},
                             t0, t1)
    evs = T.drain_span_events()
    parent = [e for e in evs if e["name"] == "train/iteration"][0]
    assert parent["trace_id"] == "f" * 16
    assert parent["parent_id"] == "9" * 16
    assert parent["attrs"]["iteration"] == 7
    assert parent["attrs"]["scan"] == {"window": 8}
    kids = [e for e in evs if e["parent_id"] == parent["span_id"]]
    assert sorted(k["name"] for k in kids) == [T.FUSED_SCAN_PHASE,
                                               "tree/defer"]
    scan = [k for k in kids if k["name"] == T.FUSED_SCAN_PHASE][0]
    assert t0 <= scan["mono"] and scan["dur"] >= 0.004
    # wall minus the REAL blocking span (the 9 s in `phases` is not read)
    assert parent["attrs"]["host_gap_s"] == pytest.approx(
        (t1 - t0) - scan["dur"], abs=1e-5)
    assert parent["attrs"]["host_gap_s"] >= 0.003
    # not a scan iteration: no gap attribute
    T.record_iteration_spans({"iteration": 8}, t1, t1 + 0.01)
    plain = T.drain_span_events()[0]
    assert "host_gap_s" not in plain["attrs"]


def test_fused_scan_phase_is_single_source_of_truth():
    # gbdt.py times its window dispatch under this exact label; the
    # host-gap derivation subtracts it — both import from trace.py
    from lightgbm_tpu.obs.trace import BLOCKING_PHASES, FUSED_SCAN_PHASE
    assert FUSED_SCAN_PHASE == "boosting/fused_scan"
    assert FUSED_SCAN_PHASE in BLOCKING_PHASES
    src = open(os.path.join(
        REPO_DIR, "lightgbm_tpu", "models", "gbdt.py")).read()
    assert "timed(FUSED_SCAN_PHASE)" in src


# ---------------------------------------------------------------------
# 3. trace CLI: loading, skew correction, export, critical paths
# ---------------------------------------------------------------------

def test_load_spans_walks_fleet_suffixes_and_tolerates_tail(tmp_path):
    _write_stream(tmp_path / "run.jsonl",
                  [_span("a", 1.0, 0.1),
                   {"event": "iteration", "iteration": 0}],
                  truncate_tail='{"event": "span", "name": "cut')
    _write_stream(tmp_path / "run.jsonl.rank1", [_span("b", 2.0, 0.1)])
    _write_stream(tmp_path / "run.jsonl.fleet", [_span("c", 3.0, 0.1)])
    (tmp_path / "notes.txt").write_text("not telemetry\n")
    sub = tmp_path / "serve"
    sub.mkdir()
    _write_stream(sub / "replica.jsonl", [_span("d", 4.0, 0.1)])
    spans = T.load_spans(str(tmp_path))
    got = sorted((s["name"], s["_stream"]) for s in spans)
    assert got == [("a", "run.jsonl"), ("b", "run.jsonl.rank1"),
                   ("c", "run.jsonl.fleet"),
                   ("d", os.path.join("serve", "replica.jsonl"))]


def test_load_spans_mid_file_garbage_raises(tmp_path):
    with open(tmp_path / "bad.jsonl", "w") as fh:
        fh.write("{ corrupt not json }\n")
        fh.write(json.dumps(_span("x", 1.0, 0.1)) + "\n")
    with pytest.raises(ValueError, match="malformed telemetry"):
        T.load_spans(str(tmp_path))


def test_clock_skew_correction_synthetic_streams(tmp_path):
    # trainer's monotonic origin is 1e6 s behind wall; the serve
    # replica restarted recently, its origin only 500 s behind — raw
    # mono values are wildly incomparable (publish mono 2000 vs swap
    # mono 7.0) but the corrected timeline must order them properly
    _write_stream(tmp_path / "train.jsonl", [
        _span("publish/model", 2000.0, 0.05,
              wall_offset=1_000_000.0, proc="pid1")])
    _write_stream(tmp_path / "serve.jsonl", [
        _span("swap/apply", 7.0, 0.02,
              wall_offset=1_001_993.25, proc="pid2")])
    spans = T.load_spans(str(tmp_path))
    offsets = T.correct_clock_skew(spans)
    assert len(offsets) == 2
    pub = next(s for s in spans if s["name"] == "publish/model")
    swap = next(s for s in spans if s["name"] == "swap/apply")
    # publish ends wall 1_002_000.05; swap starts wall 1_002_000.25
    gap = swap["t0"] - pub["t1"]
    assert gap == pytest.approx(0.2, abs=1e-6)
    assert swap["t1"] > swap["t0"] > pub["t1"] > pub["t0"]


def test_clock_skew_median_rejects_ntp_step():
    # one span's wall clock stepped 30 s mid-run; the median offset
    # must stick with the majority, not split the difference
    spans = [_span(f"s{i}", 10.0 + i, 0.01, wall_offset=100.0,
                   proc="p")
             for i in range(5)]
    spans.append(_span("stepped", 20.0, 0.01, wall_offset=130.0,
                       proc="p"))
    for s in spans:
        s["_stream"] = "x.jsonl"
    offsets = T.correct_clock_skew(spans)
    assert offsets[("x.jsonl", "p")] == pytest.approx(100.0)


def test_chrome_trace_schema(tmp_path):
    spans = [_span("train/iteration", 1.0, 0.1, proc="p1"),
             _span("serve/request", 2.0, 0.05, proc="p2")]
    spans[0]["_stream"] = "a.jsonl"
    spans[1]["_stream"] = "b.jsonl"
    T.correct_clock_skew(spans)
    doc = T.chrome_trace(spans)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(metas) == 2 and len(xs) == 2
    assert all(m["name"] == "process_name" for m in metas)
    assert {m["pid"] for m in metas} == {1, 2}
    assert min(e["ts"] for e in xs) == 0.0  # viewer opens at t=0
    for e in xs:
        assert e["dur"] > 0 and e["ts"] >= 0  # microseconds
        assert e["cat"] in ("train", "serve")
        assert "trace_id" in e["args"] and "span_id" in e["args"]
    assert T.chrome_trace([]) == {"traceEvents": [],
                                  "displayTimeUnit": "ms"}


def _lifecycle_streams(tmp_path, *, serve_wall_offset=2_000.0):
    """Synthetic 3-process lifecycle: trainer (iterations + publish),
    serve replica (swap steps), client (request riding its OWN
    trace, joined by model id)."""
    tid = "11" * 8
    pub_sid = "22" * 8
    _write_stream(tmp_path / "train.jsonl", [
        _span("train/iteration", 100.0, 0.1, trace_id=tid,
              proc="pid10", attrs={"iteration": 4}),
        _span("train/iteration", 100.2, 0.1, trace_id=tid,
              proc="pid10", attrs={"iteration": 5}),
        _span("publish/model", 100.4, 0.05, trace_id=tid,
              span_id=pub_sid, proc="pid10",
              attrs={"generation": 2, "file": "m2.txt"})])
    swap = [("swap/validate", 0.50), ("swap/load", 0.56),
            ("swap/stage", 0.62), ("swap/apply", 0.68)]
    _write_stream(tmp_path / "serve.jsonl", [
        _span(name, 7.0 + dt, 0.04, trace_id=tid, parent_id=pub_sid,
              wall_offset=1_000_093.4 + serve_wall_offset, proc="pid20",
              attrs={"model": "gen2"} if name == "swap/apply" else None)
        for name, dt in swap])
    _write_stream(tmp_path / "client.jsonl", [
        _span("serve/request", 8.1, 0.01, trace_id="33" * 8,
              wall_offset=1_000_093.4 + serve_wall_offset, proc="pid20",
              attrs={"model": "gen2", "rows": 4})])
    return tid


def test_critical_path_reconstruction(tmp_path):
    tid = _lifecycle_streams(tmp_path)
    spans = T.load_spans(str(tmp_path))
    T.correct_clock_skew(spans)
    (path,) = T.critical_paths(spans)
    assert path["trace_id"] == tid
    assert path["generation"] == 2
    assert path["model"] == "gen2"
    assert path["complete"] is True
    names = [s["name"] for s in path["steps"] if not s["gap"]]
    assert names == ["train/iteration #5", "publish/model",
                     "swap/validate", "swap/load", "swap/stage",
                     "swap/apply", "serve/request (model gen2)"]
    # every step and the total carry POSITIVE clock-corrected times
    assert all(s["dur_s"] >= 0 for s in path["steps"])
    assert path["total_s"] > 0
    # steps are monotone on the corrected timeline
    t0s = [s["t0"] for s in path["steps"]]
    assert t0s == sorted(t0s)
    text = T.render_critical_paths([path])
    assert "critical path" in text and "generation 2" in text
    assert "INCOMPLETE" not in text


def test_critical_path_incomplete_without_serve(tmp_path):
    _write_stream(tmp_path / "train.jsonl", [
        _span("train/iteration", 1.0, 0.1, attrs={"iteration": 0}),
        _span("publish/model", 1.2, 0.05, attrs={"generation": 0})])
    spans = T.load_spans(str(tmp_path))
    T.correct_clock_skew(spans)
    (path,) = T.critical_paths(spans)
    assert path["complete"] is False
    assert "INCOMPLETE" in T.render_critical_paths([path])


def test_trace_cli_end_to_end(tmp_path, capsys):
    _lifecycle_streams(tmp_path)
    assert T.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "Perfetto" in out
    assert "clock-skew correction" in out
    assert "critical path" in out
    doc = json.load(open(tmp_path / "trace.json"))
    assert doc["traceEvents"]
    # --out redirects the export
    alt = tmp_path / "alt.json"
    assert T.main([str(tmp_path), "--out", str(alt)]) == 0
    assert json.load(open(alt))["traceEvents"]


def test_trace_cli_error_paths(tmp_path, capsys):
    assert T.main(["--help"]) == 0
    assert "usage: python -m lightgbm_tpu trace" in \
        capsys.readouterr().out
    assert T.main([]) == 1
    assert T.main([str(tmp_path / "missing")]) == 1
    empty = tmp_path / "empty"
    empty.mkdir()
    assert T.main([str(empty)]) == 1  # no spans
    assert T.main([str(tmp_path), "--out"]) == 1  # dangling flag


def test_trace_cli_is_jax_free(tmp_path):
    """`python -m lightgbm_tpu trace` must never import jax — it
    post-processes JSONL where no backend may initialize."""
    d = tmp_path / "telem"
    d.mkdir()
    _write_stream(d / "t.jsonl",
                  [_span("train/iteration", 1.0, 0.1,
                         attrs={"iteration": 0})])
    code = (
        "import sys\n"
        "from lightgbm_tpu.obs.trace import main\n"
        f"rc = main([{str(d)!r}])\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'trace CLI imported jax!'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_DIR,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")


# ---------------------------------------------------------------------
# 4. propagation through the serve protocol + publisher manifest
# ---------------------------------------------------------------------

class _DummyForest:
    n_features = 3
    model_id = "dummy-1"

    def predict_raw(self, X):
        return np.zeros((X.shape[0], 1), np.float32)

    def finalize(self, raw, raw_score=False):
        return raw[:, 0]


def test_serve_protocol_span_propagation():
    from lightgbm_tpu.serve.batcher import MicroBatcher
    from lightgbm_tpu.serve.daemon import ServeState, handle_request
    b = MicroBatcher(_DummyForest(), batch_window_ms=0.5)
    state = ServeState(b, "dummy-1", "mem")
    try:
        # untraced request: zero span cost
        r = handle_request({"rows": [[1, 2, 3]]}, state)
        assert "predictions" in r
        assert T.drain_span_events() == []
        # traced request: serve/request parent + the 4 stage children
        r = handle_request({"rows": [[1, 2, 3], [4, 5, 6]],
                            "trace": {"trace_id": "a1" * 8,
                                      "span_id": "b2" * 8}}, state)
        assert "predictions" in r
        evs = T.drain_span_events()
        assert [e["name"] for e in evs] == [
            "serve/request", "serve/queue_wait", "serve/batch_window",
            "serve/dispatch", "serve/reply"]
        parent = evs[0]
        assert parent["trace_id"] == "a1" * 8
        assert parent["parent_id"] == "b2" * 8
        assert parent["attrs"] == {"model": "dummy-1", "rows": 2}
        assert all(e["parent_id"] == parent["span_id"]
                   and e["trace_id"] == "a1" * 8 for e in evs[1:])
        assert all(e["dur"] >= 0 for e in evs)
        # a malformed trace field is ignored, not fatal
        r = handle_request({"rows": [[1, 2, 3]], "trace": "bogus"},
                           state)
        assert "predictions" in r
        assert T.drain_span_events() == []
    finally:
        b.close()
        state.close()


def test_publisher_stamps_trace_context_into_manifest(tmp_path):
    from lightgbm_tpu.resilience.publisher import publish_model
    T.set_current_trace("77" * 8, "88" * 8)
    manifest = publish_model("tree\nend of trees\n", str(tmp_path),
                             "m0.txt", metadata={"generation": 0})
    assert manifest["trace"]["trace_id"] == "77" * 8
    evs = T.drain_span_events()
    (pub,) = [e for e in evs if e["name"] == "publish/model"]
    assert pub["trace_id"] == "77" * 8
    assert pub["span_id"] == manifest["trace"]["span_id"]
    assert pub["parent_id"] == "88" * 8
    assert pub["attrs"]["generation"] == 0
    assert pub["attrs"]["attempts"] == 1
    # a manifest published OUTSIDE any trace still self-identifies
    T.set_current_trace(None)
    manifest = publish_model("tree\nend of trees\n", str(tmp_path),
                             "m1.txt")
    assert len(manifest["trace"]["trace_id"]) == 16
    T.drain_span_events()


def test_summarize_events_counts_spans(tmp_path):
    from lightgbm_tpu.obs import render_stats_table, summarize_events
    path = str(tmp_path / "t.jsonl")
    _write_stream(path, [_span("a", 1.0, 0.1), _span("b", 2.0, 0.1)])
    summ = summarize_events(path)
    assert summ["spans"] == 2
    assert "trace spans" in render_stats_table(summ)


# ---------------------------------------------------------------------
# 5. env-driven device captures (LIGHTGBM_TPU_TRACE_TO / _XPROF)
# ---------------------------------------------------------------------

class _FakeTracer:
    """Records enter/exit pairs in place of jax.profiler captures."""

    def __init__(self):
        self.log = []

    def __call__(self, log_dir):
        tracer = self

        class _CM:
            def __enter__(self):
                tracer.log.append(("enter", log_dir))
                return self

            def __exit__(self, *exc):
                tracer.log.append(("exit", log_dir))
                return False

        return _CM()


def test_parse_xprof_spec():
    from lightgbm_tpu.utils.timer import parse_xprof_spec
    assert parse_xprof_spec("/tmp/x:iters=3-7") == ("/tmp/x", 3, 7)
    assert parse_xprof_spec("/tmp/x:iters=4") == ("/tmp/x", 4, 4)
    # windows-ish dirs with colons survive the rsplit
    assert parse_xprof_spec("a:b:iters=0-1") == ("a:b", 0, 1)
    for bad in ("/tmp/x", "/tmp/x:iters=a-b", ":iters=1-2",
                "/tmp/x:iters=5-2", "/tmp/x:iters=-1"):
        with pytest.raises(ValueError):
            parse_xprof_spec(bad)


def test_env_capture_from_env():
    from lightgbm_tpu.utils.timer import EnvCapture
    assert EnvCapture.from_env({}) is None
    cap = EnvCapture.from_env({"LIGHTGBM_TPU_TRACE_TO": "/tmp/t"})
    assert cap._trace_dir == "/tmp/t" and cap._xprof is None
    cap = EnvCapture.from_env(
        {"LIGHTGBM_TPU_XPROF": "/tmp/x:iters=2-3"})
    assert cap._xprof == ("/tmp/x", 2, 3)
    with pytest.raises(ValueError):
        EnvCapture.from_env({"LIGHTGBM_TPU_XPROF": "nope"})


def test_env_capture_whole_run_and_window():
    from lightgbm_tpu.utils.timer import EnvCapture
    fake = _FakeTracer()
    cap = EnvCapture(trace_dir="whole", xprof=("win", 2, 3),
                     _tracer=fake)
    cap.before_iteration(0)
    assert fake.log == [("enter", "whole")]  # window not armed yet
    cap.after_iteration(0)
    cap.before_iteration(2)
    assert ("enter", "win") in fake.log
    cap.after_iteration(2)       # i < last: window stays open
    assert ("exit", "win") not in fake.log
    cap.before_iteration(3)
    cap.after_iteration(3)       # i == last: window closes, disarms
    assert fake.log.count(("exit", "win")) == 1
    cap.before_iteration(4)      # never re-armed
    assert fake.log.count(("enter", "win")) == 1
    cap.close()
    assert fake.log[-1] == ("exit", "whole")
    cap.close()                  # idempotent
    assert fake.log.count(("exit", "whole")) == 1


def test_env_capture_close_finalizes_open_window():
    from lightgbm_tpu.utils.timer import EnvCapture
    fake = _FakeTracer()
    cap = EnvCapture(xprof=("win", 0, 100), _tracer=fake)
    cap.before_iteration(0)
    cap.after_iteration(0)       # window still open (last=100)
    cap.close()                  # exception-path finalization
    assert fake.log == [("enter", "win"), ("exit", "win")]


def test_timed_is_shared_noop_outside_any_capture():
    from lightgbm_tpu.utils import timer as tm
    assert not tm.Timer._enabled
    assert tm.timed("anything") is tm._NULL


@pytest.mark.slow
def test_timed_annotates_only_while_capture_live(tmp_path):
    """The TRACE_TO satellite: inside a live trace_to capture the
    SAME timed() call switches from the shared no-op to the
    TraceAnnotation-emitting path; after the capture it reverts."""
    from lightgbm_tpu.utils import timer as tm
    assert tm.timed("x") is tm._NULL
    with tm.trace_to(str(tmp_path / "prof")):
        cm = tm.timed("x")
        assert cm is not tm._NULL
        with cm:
            pass
    assert tm.timed("x") is tm._NULL
    # the capture actually materialized profile artifacts
    assert any((tmp_path / "prof").rglob("*"))


def test_span_keys_are_the_schema_registry():
    """Satellite of the contract-lint PR: SPAN_EVENT_KEYS is a derived
    view of the single-source schema registry (obs/schemas.py)."""
    from lightgbm_tpu.obs import schemas
    assert T.SPAN_EVENT_KEYS == \
        tuple(schemas.EVENTS["span"]["required"])


# ---------------------------------------------------------------------
# 5. one span model: job-level spans, compile stages, the device trace
#    read by layer (`trace --xplane`)
# ---------------------------------------------------------------------

def _tiny_train(rounds=3):
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(3)
    X = rs.randn(900, 6)
    y = (X[:, 0] - X[:, 2] > 0).astype(float)
    return lgb.train({"objective": "binary", "num_leaves": 7,
                      "max_bin": 31, "verbose": -1},
                     lgb.Dataset(X, label=y), rounds)


JOB_SPANS = {"train/job", "train/init", "train/build_step",
             "dataset/construct",
             "dataset/construct/load", "dataset/construct/find_bins",
             "dataset/construct/bin_rows", "compile/gbdt/fused_iter",
             "compile/trace", "compile/lower", "compile/backend",
             "compile/cost_capture"}
ROUND_SPANS = {"train/round", "train/update", "callbacks/before",
               "callbacks/after",
               "boosting/drain", "boosting/bagging", "boosting/fused_iter",
               "tree/defer", "engine/eval"}


def test_job_level_spans_with_telemetry_off_and_no_round_span():
    """Telemetry off, no capture, no Timer: the job-level spans are
    there (a few dozen appends a job, with real parents and one trace
    id) and not one per-round span was recorded."""
    from lightgbm_tpu.utils.timer import Timer
    assert not Timer.enabled()
    T.drain_span_events()
    bst = _tiny_train()
    evs = T.drain_span_events()
    names = {e["name"] for e in evs}
    assert JOB_SPANS <= names, JOB_SPANS - names
    assert not names & ROUND_SPANS
    assert len(evs) < 40
    by = {e["name"]: e for e in evs}
    ids = {e["span_id"]: e["name"] for e in evs}
    assert len({e["trace_id"] for e in evs}) == 1
    assert by["train/job"]["parent_id"] is None
    assert ids[by["train/init"]["parent_id"]] == "train/job"
    assert ids[by["train/build_step"]["parent_id"]] == "train/job"
    assert ids[by["dataset/construct"]["parent_id"]] == "train/init"
    for part in ("load", "find_bins", "bin_rows"):
        assert ids[by[f"dataset/construct/{part}"]["parent_id"]] \
            == "dataset/construct"
    # with no per-round span open, the compile hangs under the job
    comp = by["compile/gbdt/fused_iter"]
    assert ids[comp["parent_id"]] == "train/job"
    for stage in ("trace", "lower", "backend", "cost_capture"):
        st = by[f"compile/{stage}"]
        assert st["parent_id"] == comp["span_id"]
        assert comp["mono"] - 1e-6 <= st["mono"]
        assert st["mono"] + st["dur"] <= comp["mono"] + comp["dur"] + 1e-6
    assert by["compile/backend"]["attrs"]["cache"] in ("hit", "miss",
                                                       "uncached")
    assert by["train/init"]["dur"] < by["train/job"]["dur"]
    assert bst.num_trees() == 3
    # a second job roots its own trace
    _tiny_train(1)
    again = [e for e in T.drain_span_events() if e["name"] == "train/job"]
    assert again[0]["trace_id"] != by["train/job"]["trace_id"]


@pytest.mark.parametrize("params,cols,want", [
    # 6 columns pack to 2 words: the payload-carrying variadic sort
    ({}, 6, {"partition": "sort", "payload": "f32", "sort_operands": 5}),
    # 48 columns pack to 12 words; with the float32 pair that is past
    # _SORT_SINGLE_MAX: a (key, iota, g, h) sort + one row gather of the
    # words, the pair held planar
    ({}, 48, {"partition": "wide", "payload": "f32-planar",
              "sort_operands": 4}),
    ({"use_quantized_grad": True}, 48,
     {"partition": "wide", "payload": "int8", "sort_operands": 3}),
    # the masked grower partitions nothing and says nothing
    ({"grower": "masked"}, 6, {}),
])
def test_build_step_span_names_partition_and_payload(params, cols, want):
    """``train/build_step`` carries what the grower resolved when the
    step was traced: how a chunk is partitioned and the payload's form
    (a per-compile fact, so a job-level attribute)."""
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(5)
    X = rs.randn(900, cols)
    y = (X[:, 0] - X[:, 2] > 0).astype(float)
    T.drain_span_events()
    lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 31,
               "verbose": -1, **params}, lgb.Dataset(X, label=y), 1)
    span, = [e for e in T.drain_span_events()
             if e["name"] == "train/build_step"]
    assert span["attrs"] == want


def test_round_spans_recorded_while_timer_live_and_adopted(tmp_path):
    """With the telemetry recorder on, every round's spans are real and
    the recorder's train/iteration adopts them."""
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(4)
    X = rs.randn(900, 6)
    y = (X[:, 0] > 0).astype(float)
    path = str(tmp_path / "t.jsonl")
    lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 31,
               "verbose": -1}, lgb.Dataset(X, label=y), 3,
              callbacks=[lgb.telemetry(path)])
    evs = [json.loads(ln) for ln in open(path) if ln.strip()]
    spans = [e for e in evs if e["event"] == "span"]
    names = [s["name"] for s in spans]
    assert names.count("train/iteration") == 3
    assert names.count("train/round") == 3
    assert names.count("boosting/fused_iter") == 3
    assert not any(n.startswith("phase/") for n in names)
    its = {s["span_id"]: s for s in spans if s["name"] == "train/iteration"}
    job = [s for s in spans if s["name"] == "train/job"][0]
    assert all(s["parent_id"] == job["span_id"] for s in its.values())
    # the iteration adopts what hung under the open train/round
    # (train/update, callbacks/*); their own children stay theirs
    upd = {s["span_id"]: s for s in spans if s["name"] == "train/update"}
    assert len(upd) == 3
    assert all(s["parent_id"] in its for s in upd.values())
    fused = [s for s in spans if s["name"] == "boosting/fused_iter"]
    assert all(s["parent_id"] in upd for s in fused)
    for s in fused:
        it = its[upd[s["parent_id"]]["parent_id"]]
        assert it["mono"] <= s["mono"] <= it["mono"] + it["dur"]


def test_compile_cache_counters_follow_jax_monitoring():
    """The persistent cache's hit / miss events feed the two registry
    counters, whoever compiled; a stage event outside any tracked entry
    is nobody's."""
    from lightgbm_tpu.obs import cost
    from lightgbm_tpu.obs.registry import registry
    cost.install_compile_listeners()

    def value(name):
        fam = registry.snapshot().get(name)
        return fam["series"][0]["value"] if fam else 0

    h0, m0 = value("compile_cache_hits"), value("compile_cache_misses")
    from jax import monitoring
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5)
    assert value("compile_cache_hits") == h0 + 1
    assert value("compile_cache_misses") == m0 + 2
    assert not [e for e in T.span_events_snapshot()
                if e["name"].startswith("compile/")]


def _hand_built_capture():
    """One device, ops in ms: a `while` [0, 10) holding a sort [1, 3)
    and a fusion [4, 9), which holds nothing; a relayout copy [10, 13);
    a gap; then an unscoped op [20, 21). Host: train/round over it all,
    boosting/fused_iter [0, 14), callbacks/after [14, 22) holding a
    runtime event in the gap."""
    ms = 1e-3
    ops = [("%while.1 = (s32[]) while(%t), body=%b", 0.0, 10 * ms),
           ("%sort.5 = (u32[8]) sort(%k, %i)", 1 * ms, 2 * ms),
           ("%fusion.211 = f32[17,64] fusion(%a)", 4 * ms, 5 * ms),
           ("%copy.1297 = f32[8,2]{0,1} copy(%g)", 10 * ms, 3 * ms),
           ("%neg.3 = f32[8] negate(%x)", 20 * ms, 1 * ms)]
    host = [("train/round", -1 * ms, 24 * ms),
            ("boosting/fused_iter", -0.5 * ms, 14.5 * ms),
            ("callbacks/after", 14 * ms, 8 * ms),
            ("PjRtFuture::Await", 14.5 * ms, 5 * ms),
            ("perfbench_round", -0.2 * ms, 23 * ms)]
    table = {"while.1": "grow/fixed", "sort.5": "grow/partition/key_sort",
             "fusion.211": "grow/hist/build",
             "copy.1297": "grow/partition/payload"}
    return {"devices": {"/device:TPU:0": ops}, "host": host}, {None: table}


def test_xplane_by_scope_nesting_union_and_gap_attribution():
    from lightgbm_tpu.obs import xplane
    capture, table = _hand_built_capture()
    rep = xplane.report(capture, table)
    dev = rep["devices"][0]
    sc = dev["by_scope"]
    # the while does not count its body twice: 10 - 2 - 5 = 3 ms of its own
    assert sc["grow/fixed"] == pytest.approx(3e-3)
    assert sc["grow/partition/key_sort"] == pytest.approx(2e-3)
    assert sc["grow/hist/build"] == pytest.approx(5e-3)
    assert sc["grow/partition/payload"] == pytest.approx(3e-3)
    assert sc[xplane.UNSCOPED] == pytest.approx(1e-3)
    # busy = the union of [0, 13) and [20, 21), not the sum of durations
    assert dev["busy_s"] == pytest.approx(14e-3)
    assert dev["self_s"] == pytest.approx(14e-3)
    assert dev["window_s"] == pytest.approx(21e-3)
    assert dev["scoped_share"] == pytest.approx(13 / 14)
    # one gap over 1 ms, put down to the innermost PROGRAM span covering
    # its middle, the runtime's own event beside it; never the driver's
    (gap,) = dev["idle_gaps"]
    assert gap["gap_s"] == pytest.approx(7e-3)
    assert gap["span"] == "callbacks/after"
    assert gap["host"] == "PjRtFuture::Await"
    text = xplane.render_report(rep)
    assert "grow/partition/payload" in text and "(unscoped)" in text
    assert "in callbacks/after > PjRtFuture::Await" in text
    # without a table every op is unscoped, and the report says so
    bare = xplane.report(capture, None)
    assert bare["devices"][0]["scoped_share"] == 0.0
    assert "no op -> scope table" in xplane.render_report(bare)


def _two_program_capture():
    """Two programs whose ops share names (the compiler numbers each
    program's from the same stock): the grower [0, 10) ms and the
    gradient [12, 16) ms each run a ``fusion.202`` and a ``copy.7``;
    ``neg.3`` runs between them, in no program. Host: the round, the
    engine's ``metric/eval`` and ``valid/score_update`` inside a callback
    span, each over one idle gap."""
    ms = 1e-3
    ops = [("%fusion.202 = s32[2270296] fusion(%a)", 0.0, 6 * ms),
           ("%copy.7 = f32[8] copy(%b)", 6 * ms, 4 * ms),
           ("%neg.3 = f32[8] negate(%x)", 10 * ms, 0.5 * ms),
           ("%fusion.202 = f32[1774,128] fusion(%c)", 12 * ms, 3 * ms),
           ("%copy.7 = f32[8] copy(%d)", 15 * ms, 1 * ms),
           ("%fusion.9 = f32[8] fusion(%e)", 20 * ms, 1 * ms)]
    modules = [("jit_grow_tree_impl(123)", 0.0, 10 * ms),
               ("jit__lambdarank_grads(456)", 12 * ms, 4 * ms),
               ("jit__ndcg_at(789)", 20 * ms, 1 * ms)]
    host = [("train/round", -1 * ms, 23 * ms),
            ("callbacks/after", 10 * ms, 11 * ms),
            ("valid/score_update", 10.4 * ms, 1.7 * ms),
            ("metric/eval", 15.9 * ms, 5 * ms),
            ("PjRtFuture::Await", 16.5 * ms, 3 * ms)]
    tables = {"jit_grow_tree_impl": {"fusion.202": "grow/row_leaf",
                                     "copy.7": "grow/setup"},
              "jit__lambdarank_grads": {
                  "fusion.202": "boost/gradients/lambdarank"}}
    return {"devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": modules}, "host": host}, tables


@pytest.mark.parametrize("form", ["by_program", "flat", "file"])
def test_xplane_lays_each_programs_table_over_its_own_ops(form, tmp_path):
    """One op name in two programs: with the tables by program each
    ``fusion.202`` goes to its own program's scope and the gradient's
    ``copy.7``, which its table does not hold, stays unscoped; one table
    under no program (a file from before the overlay, as
    ``load_op_scopes`` folds it) names both alike."""
    from lightgbm_tpu.obs import xplane
    capture, tables = _two_program_capture()
    if form == "flat":
        tables = {None: {**tables["jit__lambdarank_grads"],
                         **tables["jit_grow_tree_impl"]}}
    elif form == "file":
        path = tmp_path / "op_scopes.json"
        path.write_text(json.dumps({
            "ops/grow_tree": {"ops": tables["jit_grow_tree_impl"],
                              "derived": [], "missing": [],
                              "module": "jit_grow_tree_impl"},
            "ranking/lambdarank_grads": {
                "ops": tables["jit__lambdarank_grads"], "derived": [],
                "module": "jit__lambdarank_grads"}}))
        tables = xplane.load_op_scopes(str(path))
        assert set(tables) == {"jit_grow_tree_impl",
                               "jit__lambdarank_grads"}
    dev = xplane.report(capture, tables)["devices"][0]
    sc, progs = dev["by_scope"], dev["by_program"]
    assert list(progs) == ["jit_grow_tree_impl", "jit__lambdarank_grads",
                           "jit__ndcg_at"]
    assert progs["jit_grow_tree_impl"]["runs"] == 1
    assert progs["jit_grow_tree_impl"]["self_s"] == pytest.approx(10e-3)
    assert progs["jit_grow_tree_impl"]["by_scope"] == pytest.approx(
        {"grow/row_leaf": 6e-3, "grow/setup": 4e-3})
    if form == "flat":
        assert sc == pytest.approx({"grow/row_leaf": 9e-3,
                                    "grow/setup": 5e-3,
                                    xplane.UNSCOPED: 1.5e-3})
    else:
        assert sc == pytest.approx({
            "grow/row_leaf": 6e-3, "grow/setup": 4e-3,
            "boost/gradients/lambdarank": 3e-3, xplane.UNSCOPED: 2.5e-3})
        assert progs["jit__lambdarank_grads"]["by_scope"] == pytest.approx(
            {"boost/gradients/lambdarank": 3e-3, xplane.UNSCOPED: 1e-3})
    ops, mods = (capture[k]["/device:TPU:0"] for k in ("devices", "modules"))
    rows = xplane.op_times(ops, tables, mods)
    assert sum(r[2] for r in rows) == pytest.approx(sum(sc.values()))
    assert (None, "neg.3", pytest.approx(0.5e-3), xplane.UNSCOPED) in rows
    text = xplane.render_report({"devices": [dev], "has_table": True})
    assert "program jit_grow_tree_impl: 1 runs" in text


@pytest.mark.parametrize("span,at_ms", [("valid/score_update", 10.5),
                                        ("metric/eval", 16.0)])
def test_a_gap_under_the_eval_paths_spans_is_put_down_to_them(span, at_ms):
    """``valid/*``, ``metric/*`` (and ``compile/*``) are program spans:
    a gap under one goes to it, not to the callback span around it."""
    from lightgbm_tpu.obs import xplane
    capture, tables = _two_program_capture()
    gaps = xplane.report(capture, tables)["devices"][0]["idle_gaps"]
    (gap,) = [g for g in gaps if g["at_s"] == pytest.approx(at_ms * 1e-3)]
    assert gap["span"] == span
    assert {"valid", "metric", "compile"} <= set(xplane.HOST_SPAN_ROOTS)


def test_trace_cli_xplane_reads_an_eager_capture_program_by_program(
        tmp_path, monkeypatch, capsys):
    """``trace <dir> --xplane <trace-dir>`` on an eager ranking capture:
    the tables ``write_op_scopes`` left beside it go each over its own
    program, and a gap under the evaluation path's spans is theirs."""
    from lightgbm_tpu.obs import xplane
    capture, tables = _two_program_capture()
    tdir = tmp_path / "prof"
    tdir.mkdir()
    (tdir / "op_scopes.json").write_text(json.dumps({
        "ops/grow_tree": {"ops": tables["jit_grow_tree_impl"],
                          "derived": [], "missing": [],
                          "module": "jit_grow_tree_impl"},
        "ranking/lambdarank_grads": {
            "ops": tables["jit__lambdarank_grads"], "derived": [],
            "missing": [], "module": "jit__lambdarank_grads"}}))
    monkeypatch.setattr(xplane, "find_xplane", lambda d: str(tdir / "x"))
    monkeypatch.setattr(xplane, "load", lambda path: capture)
    assert T.main([str(tmp_path), "--xplane", str(tdir)]) == 0
    out = capsys.readouterr().out
    grower = out[out.index("program jit_grow_tree_impl"):
                 out.index("program jit__lambdarank_grads")]
    assert "grow/row_leaf" in grower and "lambdarank" not in grower
    assert "boost/gradients/lambdarank" in out
    assert "in valid/score_update" in out and "in metric/eval" in out


def test_trace_cli_xplane_reads_the_table_beside_the_capture(
        tmp_path, monkeypatch, capsys):
    from lightgbm_tpu.obs import xplane
    capture, tables = _hand_built_capture()
    tdir = tmp_path / "prof"
    tdir.mkdir()
    (tdir / "op_scopes.json").write_text(json.dumps(
        {"gbdt/fused_iter": {"ops": tables[None],
                             "derived": ["copy.1297"]}}))
    monkeypatch.setattr(xplane, "find_xplane", lambda d: str(tdir / "x"))
    monkeypatch.setattr(xplane, "load", lambda path: capture)
    spans = tmp_path / "telemetry"
    spans.mkdir()
    # no span stream: with --xplane that is no error
    assert T.main([str(spans), "--xplane", str(tdir)]) == 0
    out = capsys.readouterr().out
    assert "92.86% of self time under a named scope" in out
    assert "grow/hist/build" in out and "idle" in out
    assert T.main([str(spans)]) == 1
    assert T.main([str(spans), "--xplane"]) == 1
    assert T.main([str(spans), "--xplane", str(tdir), "--scopes",
                   str(tmp_path / "nope.json")]) == 1
