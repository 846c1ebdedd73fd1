"""Fleet metrics plane (ISSUE 15; docs/OBSERVABILITY.md).

Layers under test:

1. OpenMetrics render/parse (obs/export.py): golden-parse of every
   rendered byte through the strict line grammar (no client library),
   name sanitization, label escaping, the kind mappings
   (counter ``_total``, gauge + ``_max``, histogram -> summary).
2. The /metrics HTTP endpoint: live scrape, content type, scrape
   counter, 404s — plus the subprocess proof that the whole export
   path is jax-free (supervisors serve it without pinning a backend).
3. XLA cost attribution (obs/cost.py): one ``{"event": "compile"}``
   record with flops+bytes per first compile per signature, none on
   cache hits, registry families fed; the jit_tracker
   rebuild-then-count regression (dead entries retire).
4. The serve daemon's ``{"cmd": "metrics"}`` protocol verb.
5. ``lightgbm_tpu stats <dir> [--fleet]``: per-file provenance and
   the merged fleet view, with the single-file path byte-compatible.
6. `slow`: a live 2-replica serve fleet under ``launch --health-port
   --metrics-port --scrape-interval`` plus an in-process trainer
   endpoint — scraped end-to-end, through a replica SIGKILL, with the
   supervisor's restarts label bumped (the ISSUE 15 acceptance run).
"""

from __future__ import annotations

import gc
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs.export import (  # noqa: E402
    CONTENT_TYPE, MetricsHTTPServer, parse_openmetrics,
    render_openmetrics)
from lightgbm_tpu.obs.registry import MetricsRegistry  # noqa: E402

from tests._mp_utils import REPO_DIR, free_port, kill_group  # noqa: E402
from tests.conftest import make_synthetic_binary  # noqa: E402


# ---------------------------------------------------------------------
# 1. render / parse
# ---------------------------------------------------------------------

def _populated_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("iterations").inc(7)
    reg.counter("comm_bytes", mode="data", wire="int8").inc(4096)
    reg.gauge("hbm_bytes_in_use").set(1000)
    reg.gauge("hbm_bytes_in_use").set(800)       # max stays 1000
    reg.histogram("phase_seconds", phase="tree_learner/grow") \
        .observe(0.5)
    reg.histogram("phase_seconds", phase="tree_learner/grow") \
        .observe(0.7)
    return reg


def test_render_golden_parses_and_round_trips():
    text = render_openmetrics(_populated_registry().snapshot())
    assert text.endswith("# EOF\n")
    samples = parse_openmetrics(text)      # strict grammar: any bad
    # line raises, so a full parse IS the golden check
    assert samples["lightgbm_tpu_iterations_total"][()] == 7.0
    key = (("mode", "data"), ("wire", "int8"))
    assert samples["lightgbm_tpu_comm_bytes_total"][key] == 4096.0
    assert samples["lightgbm_tpu_hbm_bytes_in_use"][()] == 800.0
    assert samples["lightgbm_tpu_hbm_bytes_in_use_max"][()] == 1000.0
    pkey = (("phase", "tree_learner/grow"),)
    assert samples["lightgbm_tpu_phase_seconds_count"][pkey] == 2.0
    assert samples["lightgbm_tpu_phase_seconds_sum"][pkey] \
        == pytest.approx(1.2)
    assert samples["lightgbm_tpu_phase_seconds_min"][pkey] == 0.5
    assert samples["lightgbm_tpu_phase_seconds_max"][pkey] == 0.7


def test_render_sanitizes_names_and_escapes_labels():
    reg = MetricsRegistry()
    reg.counter("weird/name-with.dots", path='a"b\\c\nd').inc()
    text = render_openmetrics(reg.snapshot())
    samples = parse_openmetrics(text)
    name = "lightgbm_tpu_weird_name_with_dots_total"
    assert name in samples
    (labels, value), = samples[name].items()
    assert value == 1.0
    assert labels == (("path", 'a"b\\c\nd'),)   # escape round-trip


@pytest.mark.parametrize("value", [
    'a"b\\c\nd',
    "C:\\new_model",      # literal backslash followed by 'n': chained
    "\\n",                # str.replace unescaping corrupts these two
    "\\", "\n", 'tricky\\"quote', "\\\\n"])
def test_label_escape_round_trip_is_exact(value):
    reg = MetricsRegistry()
    reg.gauge("g", v=value).set(1.0)
    samples = parse_openmetrics(render_openmetrics(reg.snapshot()))
    (labels, _), = samples["lightgbm_tpu_g"].items()
    assert labels == (("v", value),)


def test_parser_rejects_malformed_text():
    with pytest.raises(ValueError):
        parse_openmetrics("lightgbm_tpu_x_total 1\n")   # missing EOF
    with pytest.raises(ValueError):
        parse_openmetrics("not a metric line\n# EOF\n")
    with pytest.raises(ValueError):
        parse_openmetrics('x{bad labels} 1\n# EOF\n')
    with pytest.raises(ValueError):
        parse_openmetrics("# HELP x about\n# EOF\n")  # HELP not in
    # the strict subset this exporter emits
    with pytest.raises(ValueError):
        parse_openmetrics("# EOF\nx 1\n")       # content after EOF


def test_none_valued_gauges_are_skipped():
    reg = MetricsRegistry()
    reg.gauge("maybe").set(None)
    samples = parse_openmetrics(render_openmetrics(reg.snapshot()))
    assert "lightgbm_tpu_maybe" not in samples


# ---------------------------------------------------------------------
# 2. the /metrics endpoint
# ---------------------------------------------------------------------

def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.headers["Content-Type"], \
            resp.read().decode("utf-8")


def test_http_endpoint_serves_and_counts_scrapes():
    reg = _populated_registry()
    extra_calls = []

    def extra():
        extra_calls.append(1)
        return {"custom_gauge": {
            "kind": "gauge",
            "series": [{"labels": {"k": "v"}, "value": 3.5}]}}

    srv = MetricsHTTPServer(0, registry=reg, extra_families=extra)
    try:
        ctype, body = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert ctype == CONTENT_TYPE
        samples = parse_openmetrics(body)
        assert samples["lightgbm_tpu_iterations_total"][()] == 7.0
        assert samples["lightgbm_tpu_custom_gauge"][(("k", "v"),)] \
            == 3.5
        assert samples["lightgbm_tpu_metrics_scrapes_total"][()] == 1.0
        assert extra_calls
        _, body2 = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert parse_openmetrics(body2)[
            "lightgbm_tpu_metrics_scrapes_total"][()] == 2.0
        assert srv.scrape_count() == 2
        with pytest.raises(urllib.error.HTTPError):
            _get(f"http://127.0.0.1:{srv.port}/other")
    finally:
        srv.close()


def test_metrics_endpoint_is_jax_free():
    """The whole export path — registry, render, HTTP endpoint, strict
    parser — must work where no backend can initialize: the launch and
    pipeline supervisors serve /metrics without ever importing jax
    (the ISSUE 15 jax-free battery case)."""
    code = (
        "import sys, urllib.request\n"
        "from lightgbm_tpu.obs.registry import registry\n"
        "from lightgbm_tpu.obs.export import (MetricsHTTPServer,\n"
        "    parse_openmetrics, CONTENT_TYPE)\n"
        "registry.counter('iterations').inc(3)\n"
        "registry.gauge('fleet_replica_qps', rank=0).set(12.5)\n"
        "srv = MetricsHTTPServer(0)\n"
        "url = f'http://127.0.0.1:{srv.port}/metrics'\n"
        "with urllib.request.urlopen(url, timeout=10) as r:\n"
        "    assert r.headers['Content-Type'] == CONTENT_TYPE\n"
        "    body = r.read().decode('utf-8')\n"
        "s = parse_openmetrics(body)\n"
        "assert s['lightgbm_tpu_iterations_total'][()] == 3.0\n"
        "assert s['lightgbm_tpu_fleet_replica_qps']"
        "[(('rank', '0'),)] == 12.5\n"
        "srv.close()\n"
        "assert 'jax' not in sys.modules, "
        "'the metrics endpoint imported jax!'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_DIR,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")


# ---------------------------------------------------------------------
# 3. XLA cost attribution + jit_tracker retirement
# ---------------------------------------------------------------------

def test_cost_tracked_emits_one_compile_event_per_signature():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs import register_jit
    from lightgbm_tpu.obs.cost import CostTracked, drain_compile_events
    from lightgbm_tpu.obs.registry import registry

    drain_compile_events()
    name = "test/cost_entry"
    fn = register_jit(name, jax.jit(lambda x: (x * 2.0).sum()))
    assert isinstance(fn, CostTracked)
    # re-registering the same wrapper (or its wrapped fn) is a no-op
    assert register_jit(name, fn) is fn

    fn(jnp.ones((8,), jnp.float32))
    events = [e for e in drain_compile_events() if e["entry"] == name]
    assert len(events) == 1
    ev = events[0]
    assert ev["event"] == "compile"
    assert ev["flops"] is not None and ev["flops"] > 0
    assert ev["bytes_accessed"] is not None \
        and ev["bytes_accessed"] > 0
    assert ev["wall_ms"] > 0
    assert "float32[8]" in ev["signature"]

    # same signature again: a cache hit, no event
    fn(jnp.ones((8,), jnp.float32))
    assert not [e for e in drain_compile_events()
                if e["entry"] == name]

    # a new signature compiles again: one more event
    fn(jnp.ones((16,), jnp.float32))
    events = [e for e in drain_compile_events() if e["entry"] == name]
    assert len(events) == 1
    assert "float32[16]" in events[0]["signature"]

    # the registry families carried both compiles
    assert registry.counter("xla_compiles", entry=name) \
        .snapshot() == 2.0
    assert registry.gauge("xla_flops", entry=name) \
        .snapshot()["value"] > 0


def test_cost_wrapper_proxies_the_jit_surface():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs import register_jit

    fn = register_jit("test/proxy_entry", jax.jit(lambda x: x + 1))
    fn(jnp.ones((4,)))
    assert int(fn._cache_size()) == 1         # proxied attr
    lowered = fn.lower(jnp.ones((4,)))        # proxied AOT surface
    assert lowered.cost_analysis() is not None


def test_jit_rebuild_retires_dead_entries():
    """The stale-entry regression (ISSUE 15 satellite): rebuilding an
    entry point under the same name must not leave the collected
    function's last cache size in jit_cache_sizes()/total_recompiles()
    forever."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs import (jit_cache_sizes, register_jit,
                                  total_recompiles)

    name = "test/rebuild_entry"
    fn = register_jit(name, jax.jit(lambda x: x + 1.0))
    fn(jnp.ones((4,)))
    sizes = jit_cache_sizes()
    keys = [k for k in sizes if k[0] == name]
    assert len(keys) == 1 and sizes[keys[0]] == 1
    before = total_recompiles()

    # the OOM-ladder / _scan_fns reset shape: drop the old function,
    # rebuild, re-register under the same name
    fn = None
    gc.collect()
    fn = register_jit(name, jax.jit(lambda x: x + 2.0))
    fn(jnp.ones((4,)))
    sizes = jit_cache_sizes()
    keys = [k for k in sizes if k[0] == name]
    assert len(keys) == 1, (
        f"dead entry not retired: {sorted(sizes)}")
    assert sizes[keys[0]] == 1
    # the dead function's cache no longer inflates the total
    assert total_recompiles() <= before
    fn = None
    gc.collect()


def test_compile_events_ride_the_telemetry_stream(tmp_path):
    """End-to-end through the recorder: a training run's JSONL stream
    carries {"event": "compile"} records with flops+bytes, and the
    stats table renders the xla cost section."""
    from lightgbm_tpu.obs import render_stats_table, summarize_events

    X, y = make_synthetic_binary(n=400, f=6, seed=9)
    path = str(tmp_path / "run.jsonl")
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1}, ds, num_boost_round=3,
              callbacks=[lgb.callback.telemetry(path)])
    with open(path, encoding="utf-8") as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    compiles = [e for e in events if e.get("event") == "compile"]
    assert compiles, "no compile events in the stream"
    fused = [e for e in compiles if e["entry"] == "gbdt/fused_iter"]
    assert fused and fused[0]["flops"] is not None \
        and fused[0]["bytes_accessed"] is not None
    summary = summarize_events(path)
    assert "gbdt/fused_iter" in summary["compiles"]
    table = render_stats_table(summary)
    assert "xla cost attribution" in table
    assert "gbdt/fused_iter" in table


# ---------------------------------------------------------------------
# 4. the serve daemon's metrics verb
# ---------------------------------------------------------------------

class _FakeBatcher:
    def stats(self):
        return {"queue_depth_rows": 2, "requests_total": 5,
                "rows_total": 40, "batches_total": 3,
                "swaps_total": 0, "rejected_total": 0,
                "shed_total": 1, "shed_rows": 4,
                "p50_ms": 1.25, "p99_ms": 9.5}

    def close(self, timeout=None):
        pass


def test_serve_metrics_verb_returns_openmetrics_text():
    from lightgbm_tpu.serve.daemon import ServeState, handle_request

    state = ServeState(_FakeBatcher(), "abcd1234", "model.txt",
                       registry=MetricsRegistry())
    try:
        state.stats()                  # primes the cached rate window
        reply = handle_request({"cmd": "metrics"}, state)
        assert reply.get("ok"), reply
        assert reply["content_type"] == CONTENT_TYPE
        samples = parse_openmetrics(reply["metrics"])
        assert samples["lightgbm_tpu_serve_requests_total"][()] == 5.0
        assert samples["lightgbm_tpu_serve_shed_total"][()] == 1.0
        assert samples["lightgbm_tpu_serve_p99_ms"][()] == 9.5
        assert samples["lightgbm_tpu_serve_qps"][()] is not None
        # model id AND publication sha ride the labels (docs/
        # OBSERVABILITY.md; resilience/elastic.py reads both); no
        # manifest here, so the sha is empty
        mkey = (("model", "abcd1234"), ("sha", ""))
        assert samples["lightgbm_tpu_serve_model_info"] == {mkey: 1.0}
    finally:
        state.close()


# ---------------------------------------------------------------------
# 5. stats over a directory + the merged fleet view
# ---------------------------------------------------------------------

def _write_jsonl(path, events):
    with open(path, "w", encoding="utf-8") as fh:
        for ev in events:
            fh.write(json.dumps(ev) + "\n")


def _fake_iteration(i):
    return {"event": "iteration", "iteration": i, "wall_time": i + 1.0,
            "phases": {"tree_learner/grow": {"total": 0.1,
                                             "count": 1}},
            "recompiles": {"delta": 1 if i == 0 else 0, "total": 1},
            "hbm": {}, "tree": {"trees": 1, "leaves": 7,
                                "split_gain_sum": 2.0},
            "eval": {}, "comm": None, "scan": None}


def _fake_serve(requests):
    return {"event": "serve", "requests_total": requests,
            "rows_total": requests * 4, "batches_total": 3,
            "queue_depth_rows": 0, "qps": 11.0, "rows_per_sec": 44.0,
            "p50_ms": 1.0, "p99_ms": 8.0, "swaps_total": 1,
            "swap_failures": 0, "rejected_total": 0, "shed_total": 2,
            "recompiles": {"delta": 0, "total": 4},
            "hbm": {}, "model": "m1", "model_source": "x.txt",
            "uptime_s": 9.0}


def test_stats_directory_provenance_and_fleet_view(tmp_path, capsys):
    from lightgbm_tpu.cli import _task_stats

    train = [_fake_iteration(i) for i in range(3)]
    train.insert(0, {"event": "compile", "entry": "gbdt/fused_iter",
                     "flops": 1e9, "bytes_accessed": 2e9,
                     "wall_ms": 120.0, "compiles": 1,
                     "optimal_ms": 3.0, "device_kind": "fake-tpu",
                     "time": 1.0})
    _write_jsonl(tmp_path / "train.jsonl", train)
    _write_jsonl(tmp_path / "serve.jsonl", [_fake_serve(10)])
    _write_jsonl(tmp_path / "serve.jsonl.rank1", [_fake_serve(6)])
    _write_jsonl(tmp_path / "serve.jsonl.fleet", [
        {"event": "fleet", "shape": "replicas",
         "replicas": [{"rank": 0, "alive": True, "restarts": 0},
                      {"rank": 1, "alive": True, "restarts": 2}],
         "restarts_total": 2, "time": 2.0}])

    # per-file provenance
    rc = _task_stats([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    for rel in ("train.jsonl", "serve.jsonl", "serve.jsonl.rank1",
                "serve.jsonl.fleet"):
        assert f"== {rel} ==" in out, out
    assert "xla cost attribution (fake-tpu)" in out

    # merged fleet view sums the replicas and keeps the restarts
    rc = _task_stats([str(tmp_path), "--fleet"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet (merged view)" in out
    assert "2 replica(s), 16 req" in out
    assert "restarts 2" in out

    # the single-file path is unchanged by the directory feature
    rc = _task_stats([str(tmp_path / "train.jsonl")])
    single = capsys.readouterr().out
    assert rc == 0
    assert "== " not in single
    assert "iterations           : 3" in single


def test_stats_directory_without_events_fails(tmp_path, capsys):
    from lightgbm_tpu.cli import _task_stats
    _write_jsonl(tmp_path / "empty.jsonl", [])
    assert _task_stats([str(tmp_path)]) == 1


# ---------------------------------------------------------------------
# 6. live fleet scrape (slow: real sockets, subprocess fleet)
# ---------------------------------------------------------------------

def _rpc_once(port, obj, timeout=10.0):
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall((json.dumps(obj) + "\n").encode("utf-8"))
        return json.loads(s.makefile("r").readline())


def _wait_ping(port, deadline):
    while time.time() < deadline:
        try:
            if _rpc_once(port, {"cmd": "ping"}).get("ok"):
                return True
        except (OSError, ValueError):
            pass
        time.sleep(0.3)
    return False


def _scrape(port):
    _, body = _get(f"http://127.0.0.1:{port}/metrics")
    return parse_openmetrics(body)


@pytest.mark.slow
def test_live_fleet_scrape_and_restart_accounting(tmp_path):
    """The ISSUE 15 acceptance run: an in-process trainer endpoint
    plus a 2-replica serve fleet under `launch --health-port
    --metrics-port --scrape-interval`, scraped live end-to-end —
    OpenMetrics-parseable text carrying serve QPS/p99/shed, compile
    totals and publish counters — then a replica SIGKILL, after which
    the replica serves again and the supervisor's fleet records carry
    the bumped restarts label."""
    # ---- trainer side (in-process): train, publish, scrape ----------
    from lightgbm_tpu.obs.export import ensure_metrics_server
    from lightgbm_tpu.resilience.publisher import publish_model

    X, y = make_synthetic_binary(n=500, f=8, seed=21)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, num_boost_round=4,
                    callbacks=[lgb.callback.telemetry(
                        str(tmp_path / "telemetry" / "train.jsonl"))])
    publish_dir = str(tmp_path / "publish")
    os.makedirs(publish_dir, exist_ok=True)
    publish_model(bst, publish_dir, "model_g0000.txt",
                  metadata={"generation": 0})
    trainer_srv = ensure_metrics_server(0)
    assert trainer_srv is not None
    samples = _scrape(trainer_srv.port)
    assert samples["lightgbm_tpu_iterations_total"][()] >= 4.0
    assert "lightgbm_tpu_jit_recompiles_total" in samples
    assert any(name.startswith("lightgbm_tpu_xla_compiles_total")
               for name in samples), sorted(samples)[:20]
    assert samples["lightgbm_tpu_publish_total"][()] >= 1.0

    # ---- serve fleet (subprocess): 2 replicas + supervisor ----------
    model = str(tmp_path / "model.txt")
    bst.save_model(model)
    base = free_port()
    metrics_base = free_port()
    env = dict(os.environ)
    env["LIGHTGBM_TPU_TELEMETRY"] = str(
        tmp_path / "telemetry" / "serve.jsonl")
    sup = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "launch", "2",
         "--max-restarts", "3", "--grace", "1",
         "--health-port", str(base),
         "--health-interval", "1", "--health-grace", "300",
         "--metrics-port", str(metrics_base),
         "--scrape-interval", "0.5",
         "--log-dir", str(tmp_path / "logs"), "--",
         sys.executable, "-m", "lightgbm_tpu", "serve", model,
         "--port", str(base), "--warmup-rows", "64",
         "--max-batch-rows", "256", "--stats-interval", "0.5"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=REPO_DIR, env=env, start_new_session=True)
    try:
        deadline = time.time() + 180
        assert _wait_ping(base, deadline), "replica 0 never served"
        assert _wait_ping(base + 1, deadline), "replica 1 never served"
        pids = {r: _rpc_once(base + r, {"cmd": "ping"})["pid"]
                for r in (0, 1)}
        for r in (0, 1):                       # traffic for the rates
            for _ in range(3):
                reply = _rpc_once(base + r,
                                  {"rows": X[:4].tolist()})
                assert "predictions" in reply, reply
        time.sleep(1.5)                        # one stats cadence

        # replica endpoints: launch exported metrics_base+1, the
        # daemon added its rank
        for r in (0, 1):
            samples = _scrape(metrics_base + 1 + r)
            assert samples["lightgbm_tpu_serve_requests_total"][()] \
                >= 3.0
            assert "lightgbm_tpu_serve_shed_total" in samples
            assert "lightgbm_tpu_serve_p99_ms" in samples
            assert "lightgbm_tpu_serve_qps" in samples
            assert any(n.startswith("lightgbm_tpu_xla_compiles")
                       for n in samples)
        # the protocol verb serves the same text
        reply = _rpc_once(base, {"cmd": "metrics"})
        assert reply.get("ok"), reply
        assert parse_openmetrics(reply["metrics"])[
            "lightgbm_tpu_serve_requests_total"][()] >= 3.0

        # supervisor endpoint: per-replica fleet gauges
        samples = _scrape(metrics_base)
        up = samples.get("lightgbm_tpu_fleet_replica_up", {})
        assert up.get((("rank", "0"),)) == 1.0, samples.keys()
        assert up.get((("rank", "1"),)) == 1.0

        # ---- chaos: SIGKILL replica 1; fleet mode restarts it -------
        os.kill(pids[1], signal.SIGKILL)
        deadline = time.time() + 180
        new_pid = None
        while time.time() < deadline:
            try:
                got = _rpc_once(base + 1, {"cmd": "ping"})
                if got.get("pid") not in (None, pids[1]):
                    new_pid = got["pid"]
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        assert new_pid is not None, "replica 1 never came back"
        # its endpoint answers again (fresh process, fresh counters)
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                samples = _scrape(metrics_base + 2)
                break
            except OSError:
                time.sleep(0.5)
        # the supervisor's restarts label carries the history the
        # replica's own counters lost with the process
        deadline = time.time() + 60
        restarts = 0.0
        while time.time() < deadline and restarts < 1.0:
            samples = _scrape(metrics_base)
            restarts = samples.get(
                "lightgbm_tpu_fleet_replica_restarts", {}).get(
                (("rank", "1"),), 0.0)
            time.sleep(0.5)
        assert restarts >= 1.0, "restart never surfaced in /metrics"

        # graceful shutdown so the fleet file flushes
        for r in (0, 1):
            try:
                _rpc_once(base + r, {"cmd": "shutdown"})
            except (OSError, ValueError):
                pass
        sup.wait(timeout=60)
    finally:
        if sup.poll() is None:
            kill_group(sup)
            try:
                sup.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass

    # ---- the fleet telemetry + merged stats view --------------------
    fleet_file = str(tmp_path / "telemetry" / "serve.jsonl.fleet")
    assert os.path.exists(fleet_file), os.listdir(
        str(tmp_path / "telemetry"))
    with open(fleet_file, encoding="utf-8") as fh:
        fleet_events = [json.loads(line) for line in fh
                        if line.strip()]
    assert fleet_events
    assert fleet_events[-1]["event"] == "fleet"
    assert fleet_events[-1]["restarts_total"] >= 1
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "stats",
         str(tmp_path / "telemetry"), "--fleet"],
        capture_output=True, text=True, cwd=REPO_DIR, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "fleet (merged view)" in proc.stdout
    assert "restarts" in proc.stdout
