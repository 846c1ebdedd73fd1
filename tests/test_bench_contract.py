"""The contract of bench.py: one process, one parseable JSON line on
stdout that names the device it ran on, exit 0 — and on any failure a
traceback and a non-zero exit with no result, never a record that
could be read as a measurement."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, timeout):
    # fixed minimal env: an ambient BENCH_* leak would silently change
    # what runs — same env-poisoning class the RSS test scrubs for
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root")}
    env.update(env_extra)
    return subprocess.run([sys.executable, BENCH], env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


def test_bench_success_emits_one_json_line(tmp_path):
    r = _run({"BENCH_PLATFORM": "cpu", "BENCH_ROWS": "4000",
              "BENCH_VALID": "1000", "BENCH_ITERS": "2",
              "BENCH_AUC_ITERS": "3", "BENCH_LEAVES": "7",
              "BENCH_BINS": "15",
              "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
             timeout=900)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert rec["value"] is not None and rec["value"] > 0
    assert "error" not in rec and "last_measured" not in rec
    # every result names the device it ran on
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["device_count"] >= 1
    assert rec["compile_cache_dir"] == str(tmp_path)
    # the embedded run-telemetry block (docs/OBSERVABILITY.md): phase
    # wall times, jit recompile count, HBM gauges (nulls on CPU)
    telem = rec["telemetry"]
    assert isinstance(telem["recompiles"], int) and \
        telem["recompiles"] >= 1  # at least the grow compile
    assert telem["phases"], telem
    for label, v in telem["phases"].items():
        assert v["total"] >= 0 and v["count"] >= 1, (label, v)
    assert "bytes_in_use" in telem["hbm"]


def _assert_loud_failure(r):
    assert r.returncode != 0
    assert "Traceback" in r.stderr
    for ln in r.stdout.splitlines():
        assert not ln.lstrip().startswith("{"), r.stdout
    assert "value" not in r.stdout and "last_measured" not in r.stdout


def test_bogus_platform_fails_loudly():
    """A backend that cannot initialize is a traceback and a non-zero
    exit — no probe loop, no supervisor, no failure record."""
    _assert_loud_failure(_run({"BENCH_PLATFORM": "bogus_backend",
                               "BENCH_ROWS": "4000"}, timeout=200))


def test_no_tpu_without_explicit_cpu_is_an_error():
    """JAX held to the CPU by the environment is not the explicit
    BENCH_PLATFORM=cpu smoke: bench.py must refuse to time the host
    and call it the benchmark."""
    r = _run({"JAX_PLATFORMS": "cpu", "BENCH_ROWS": "4000"},
             timeout=200)
    _assert_loud_failure(r)
    assert "measures the TPU" in r.stderr


# ---------------------------------------------------------------------
# tracing plane cost contract (ISSUE 16): always-on must mean free
# ---------------------------------------------------------------------

def test_tracing_off_iteration_path_is_structurally_free():
    """With no capture live, the fused iteration's timed() sections
    must still resolve to the SHARED no-op context — the tracing
    plane adds zero objects and zero clock reads to the hot loop.
    This is the structural half of the <=1%-overhead bench contract
    (the timing half below bounds the only per-iteration addition)."""
    from lightgbm_tpu.utils import timer as tm
    from lightgbm_tpu.utils.timer import EnvCapture
    assert not tm.Timer._enabled
    assert tm.timed("boosting/fused_scan") is tm._NULL
    # and the engine's env-capture hook is skipped entirely: no knob
    # set -> no object, the loop never takes the per-iteration calls
    assert EnvCapture.from_env({}) is None


def test_traced_round_spans_within_overhead_budget():
    """What tracing adds to one round when it is ON: the round's real
    spans (``train/round`` and its seven ``timed`` children, each a
    clock pair, an annotation and a locked append) plus the recorder's
    ``record_iteration_spans`` adopting them. Budget: <=1% of the
    seed's ~130 ms/iter fused iteration = 1.3 ms; assert a generous
    half of that per round so a regression (per-row spans, clock
    storms, a scan over an undrained buffer) fails loudly while CI
    jitter does not."""
    import time as _time

    from lightgbm_tpu.obs.trace import (drain_span_events,
                                        record_iteration_spans,
                                        set_current_trace)
    from lightgbm_tpu.utils.timer import Timer, timed
    labels = ("callbacks/before", "boosting/drain", "boosting/bagging",
              "boosting/fused_scan", "tree/defer", "engine/eval",
              "callbacks/after")

    def one_round(i):
        t0 = _time.perf_counter()
        with timed("train/round"):
            for label in labels:
                with timed(label):
                    pass
        record_iteration_spans({"iteration": i, "scan": {"window": 8}},
                               t0, _time.perf_counter())
        return drain_span_events()      # the recorder drains each round

    set_current_trace(None)
    Timer.enable()
    try:
        evs = one_round(0)              # warm the path
        assert len(evs) == len(labels) + 2
        n = 50
        t0 = _time.perf_counter()
        for i in range(n):
            one_round(i)
        per_round = (_time.perf_counter() - t0) / n
    finally:
        Timer.enable(False)
        Timer.reset()
        set_current_trace(None)
    assert per_round < 0.65e-3, (
        f"a traced round's spans cost {per_round * 1e3:.3f} ms — over "
        "the 1% tracing-overhead budget (1.3 ms) headroom")


def test_span_event_schema_is_documented():
    """{"event": "span"} is part of the telemetry JSONL contract:
    every key of SPAN_EVENT_KEYS appears in docs/OBSERVABILITY.md
    (same documentation gate the iteration/compile events meet)."""
    from lightgbm_tpu.obs.trace import SPAN_EVENT_KEYS
    assert SPAN_EVENT_KEYS[0] == "event"
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md"),
               encoding="utf-8").read()
    assert '"event": "span"' in doc
    for key in SPAN_EVENT_KEYS:
        assert f"`{key}`" in doc, (
            f"span schema key {key!r} undocumented in "
            "docs/OBSERVABILITY.md")
