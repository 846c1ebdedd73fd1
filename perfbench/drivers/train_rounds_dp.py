"""Driver ``train_rounds_dp``: ``train_rounds`` for a job that spans the
cell's chips (``tree_learner=data`` over a mesh of them).

The clock, the window, the trace and the failures are ``train_rounds``'s
(this file is that one with the differences below; it cannot import the
other's one function piecewise). What differs, because one process now
drives several chips:

- the memory peak is the FULLEST chip's ``peak_bytes_in_use +
  peak_bytes_reserved``, and every chip's ``memory_stats()`` goes into
  the observations (``devices``);
- the work model is handed ONE chip's share of the round's work (the
  whole table's, over the chips) against one chip's peaks, so
  ``train.round_mfu`` stays a share of what the chips together can do;
- the reference is computed in row blocks spread over the chips
  (``harness/check_dp.py``): the raw table does not fit one;
- the configuration's ``expect`` also states the learner, the mesh, the
  histogram wire, and the partition and payload the grower resolves; a
  RESOLVED mismatch fails the run (a program that does not report one
  of them, as the parent of the PR that added the report, is not held
  to it);
- ``expect`` states, too, the type the program's trees record row counts
  in (``tree_row_counts``), and that is asked of the program BEFORE the
  table is made: one host holds more rows than float32 counts exactly
  (2**24), so a program whose trees keep float32 counts cannot run this
  configuration (its model holds counts a row off, which the check's
  exact ``leaf_count_mismatch`` refuses) and fails here, at once, with
  that message instead of a result;
- the collectives: the program's counters ``hist_reductions`` and
  ``hist_wire_bytes`` over all the rounds trained, the trace's
  ``all-reduce`` time and the chips' busy spread
  (``harness/trace_collectives.py``), and the all-reduce's share of the
  interconnect's rate (``harness/ici.py``) go into the observations
  (``comm``); each is ``None`` where there is nothing to read.


Set-up is everything before the window opens: the table from the seed,
``Dataset.construct()``, the compile cache, and the traffic's warm-up
rounds (round 0 compiles or loads the cache). The clock is a callback
of the one ``lgb.train`` call: it blocks on the score at each round's
end (``chip_smoke.py _round_clock``'s pattern). The window opens at the
end of the last warm-up round and closes at the end of the first round
that ends ``--seconds`` later, by ``EarlyStopException``; the rate is the
whole window over all its rounds. A traced run traces
``trace_rounds`` consecutive rounds inside the window, each under a
host ``TraceAnnotation``.

The run fails loudly, with no result, on: a compile inside the window,
a fault event, a resolved ``hist_method``, ``hist_precision`` or
iteration entry other than the configuration's, no ``peak_bytes_in_use``
on a TPU.

``correct`` is decided after the window has closed, the memory peak has
been read and the program's state is freed: ``harness/check.py``.
"""

import gc
import os
import resource
import shutil
import time

import numpy as np

SPAN = "perfbench_round"


def tree_row_counts(jax):
    """The dtype the program's trees record row counts in, and the most
    rows that dtype counts exactly; ``(None, None)`` where the program's
    tree cannot be asked (such a program is not held to it)."""
    try:
        import jax.numpy as jnp
        from lightgbm_tpu.ops import grow as prog_grow
        proto = jax.eval_shape(lambda: prog_grow._init_tree(2, 2, jnp.float32))
        dtype = np.dtype(proto.leaf_count.dtype)
    except Exception:                       # noqa: BLE001 - another program
        return None, None
    exact = int(np.iinfo(dtype).max) if dtype.kind in "iu" \
        else 2 ** (np.finfo(dtype).nmant + 1)
    return dtype.name, exact


def run(ctx):
    import jax
    from harness import (check, check_dp, datagen, ici, trace_collectives,
                         trace_reduce, work_model)
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs as prog_obs
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.obs.registry import registry

    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    # process start to here: Python, JAX and the program imported, the
    # TPU runtime up (run.py's device gate)
    startup_s = time.perf_counter() - ctx["t_start"]
    log(f"start-up {startup_s:.1f}s (runtime up after "
        f"{ctx.get('runtime_up_s', float('nan')):.1f}s)")
    devices = list(ctx["devices"])
    on_tpu = devices[0].platform == "tpu"
    rows = ctx["selftest_rows"] or int(cfg["num_data"])
    params = dict(cfg["params"])
    params.update(ctx.get("params_override", {}))
    warm = int(traffic["warmup_rounds"])
    n_trace = int(traffic["trace_rounds"]) if ctx["trace"] else 0
    host = {"compile_cache_dir": cache_dir, "startup_s": startup_s}
    as_stated = not ctx.get("params_override")   # the control's is another

    counts, exact = tree_row_counts(jax)
    if counts is not None and rows > exact:
        raise RuntimeError(
            f"this program cannot run configuration "
            f"{ctx['cell']['config']!r}: its trees record row counts in "
            f"{counts}, exact to {exact:,} rows, and the job has {rows:,} "
            f"(the configuration states "
            f"{cfg['expect'].get('tree_row_counts')})")

    t0 = time.perf_counter()
    X, y = datagen.make_table(cfg["data"], rows, ctx["seed"],
                              threads=min(12, os.cpu_count() or 1))
    host["datagen_s"] = time.perf_counter() - t0
    log(f"table {X.shape} from seed {ctx['seed']} in "
        f"{host['datagen_s']:.1f}s")

    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params={"max_bin": params["max_bin"]})
    ds.construct()
    host["construct_s"] = time.perf_counter() - t0
    log(f"construct {host['construct_s']:.1f}s")

    trace_dir = os.path.join(ctx["out_dir"], "trace", ctx["cell"]["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    clock = {"starts": [], "ends": [], "open": None, "watch": None,
             "span": None, "tracing": False, "traced": [], "close": None}

    def before(env):
        clock["starts"].append(time.perf_counter())
        i = env.iteration
        if n_trace and i == warm and not clock["tracing"]:
            os.makedirs(trace_dir, exist_ok=True)
            # the driver's own spans are TraceAnnotations (host tracer);
            # Python's function-call tracer is off: at a quarter of a
            # thousand splits a round it would be most of the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            clock["tracing"] = True
        if clock["tracing"]:
            clock["span"] = jax.profiler.TraceAnnotation(SPAN)
            clock["span"].__enter__()
    before.before_iteration = True

    def after(env):
        jax.block_until_ready(env.model._engine.score)
        now = time.perf_counter()
        if clock["span"] is not None:
            clock["span"].__exit__(None, None, None)
            clock["span"] = None
            clock["traced"].append(env.iteration)
            if len(clock["traced"]) == n_trace:
                jax.profiler.stop_trace()
                clock["tracing"] = False
        clock["ends"].append(now)
        if env.iteration == warm - 1:
            clock["open"] = now
            clock["watch"] = prog_obs.RecompileWatcher()
        elif clock["open"] is not None \
                and len(clock["traced"]) == n_trace \
                and now - clock["open"] >= ctx["seconds"]:
            clock["close"] = now
            raise EarlyStopException(env.iteration, [])

    try:
        bst = lgb.train(params, ds, num_boost_round=int(traffic["max_rounds"]),
                        callbacks=[before, after])
    finally:
        if clock["tracing"]:
            jax.profiler.stop_trace()
    if clock["close"] is None:
        raise RuntimeError("training ended before the window closed: "
                           f"{len(clock['ends'])} rounds")
    eng = bst._engine
    ends = clock["ends"]
    window_rounds = len(ends) - warm
    window_s = clock["close"] - clock["open"]
    round_ms = [(b - a) * 1e3
                for a, b in zip(clock["starts"][warm:], ends[warm:])]
    host["first_round_s"] = ends[0] - clock["starts"][0]
    host["setup_s"] = clock["open"] - ctx["t_start"]
    host["window_s"], host["window_rounds"] = window_s, window_rounds
    host["round_ms"] = round_ms
    host["round_max_ms"] = max(round_ms)
    log(f"window {window_s:.2f}s, {window_rounds} rounds, set-up "
        f"{host['setup_s']:.1f}s (first round {host['first_round_s']:.1f}s)")

    # -- what must hold for the run to be a run of this cell -----------
    compiles = clock["watch"].delta()
    snap = registry.snapshot()
    faults = {s["labels"].get("kind", "?"): int(s["value"])
              for s in snap.get("fault_events", {}).get("series", [])}
    compiled = {s["labels"].get("entry", "?"): int(s["value"])
                for s in snap.get("xla_compiles", {}).get("series", [])}
    expect = cfg["expect"]
    problems = []
    if compiles:
        problems.append(f"{compiles} program(s) compiled inside the window")
    if faults or eng.fault_log:
        problems.append(f"fault events: {faults} {eng.fault_log}")
    # what the grower resolved in its trace: the engine's copy, else
    # (a program that keeps none under a mesh) the grower's own record
    # of its last trace, which in this process was this job's
    plan = getattr(eng, "_grow_plan", None)
    if not plan:
        from lightgbm_tpu.ops import grow as prog_grow
        plan = dict(getattr(prog_grow, "last_plan", None) or {})
    mesh = getattr(eng, "mesh", None)
    resolved = {
        "hist_method": eng.grow_cfg.hist_method,
        "hist_precision": eng.grow_cfg.hist_precision,
        "tree_learner": "serial" if mesh is None
        else eng.grow_cfg.parallel_mode,
        "mesh_devices": None if mesh is None else int(mesh.devices.size),
        "hist_comm": eng.grow_cfg.hist_comm,
        "partition": plan.get("partition"),
        "payload": plan.get("payload"),
        "tree_row_counts": counts,
    }
    # the learner and the mesh hold wherever the cell runs; what the
    # grower resolves from the backend (method, precision, layout) only
    # on the chip
    everywhere = ("tree_learner", "mesh_devices", "hist_comm")
    for key, got in resolved.items():
        if as_stated and (on_tpu or key in everywhere) \
                and got is not None and got != expect[key]:
            problems.append(f"{key} resolved to {got!r}, the "
                            f"configuration states {expect[key]!r}")
    if compiled.get(expect["iteration_entry"], 0) < 1:
        problems.append(f"{expect['iteration_entry']!r} never compiled: "
                        f"{compiled}")
    # per chip, as train_rounds reads its one: what the process holds
    # plus the round's scratch, which the TPU runtime counts apart
    mems = [dev.memory_stats() or {} for dev in devices]
    peaks = [None if m.get("peak_bytes_in_use") is None
             else m["peak_bytes_in_use"] + m.get("peak_bytes_reserved", 0)
             for m in mems]
    if on_tpu and None in peaks:
        problems.append("memory_stats() gave no peak_bytes_in_use on "
                        f"{peaks.count(None)} of {len(devices)} chips")
    peak = None if None in peaks else max(peaks)
    if problems:
        raise RuntimeError("not a run of this cell: " + "; ".join(problems))
    for dev, m in zip(devices, mems):
        log(f"device {dev.id} memory_stats {m}")
    rounds_trained = len(clock["ends"])
    comm = {"devices": len(devices), "resolved": resolved}
    wire = {s["labels"].get("wire", "?"): s["value"]
            for s in snap.get("hist_wire_bytes", {}).get("series", [])}
    reductions = sum(s["value"] for s in
                     snap.get("hist_reductions", {}).get("series", []))
    if reductions:
        comm["reductions_per_round"] = reductions / rounds_trained
        comm["wire_bytes_per_round"] = sum(wire.values()) / rounds_trained
        comm["wire_bytes_by_wire"] = wire

    # -- the product, then the program's state is freed -----------------
    prog_score = np.asarray(eng.score)[0]
    model = bst.dump_model()
    n_features = X.shape[1]
    del eng, bst, ds
    gc.collect()
    log(f"program freed; device holds "
        f"{sum(a.nbytes for a in jax.live_arrays())} bytes")

    observations = {"host": host,
                    "counters": {"compiles_in_window": compiles},
                    "devices": [{"id": dev.id, "peak_bytes": p,
                                 "memory_stats": m}
                                for dev, p, m in zip(devices, peaks, mems)],
                    "comm": comm, "trace": None, "work": None}
    if n_trace:
        loaded = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        tr = trace_reduce.reduce(loaded, span_name=SPAN)
        coll = trace_collectives.reduce(loaded, SPAN)
        del loaded
        if ctx.get("keep_trace"):       # control/describe_trace.py
            host["trace_dir"] = trace_dir
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
        traced = clock["traced"]
        host["traced_rounds"] = len(traced)
        observations["trace"] = tr
        whole = {"ops": 0, "bytes": 0}
        for ti in traced:
            w = work_model.round_work(
                rows, work_model.tree_hist_rows(model["tree_info"][ti], rows),
                n_features)
            whole = {k: whole[k] + w[k] for k in whole}
        # one chip's share of the rounds' work, against one chip's peaks
        share = {k: v / len(devices) for k, v in whole.items()}
        observations["work"] = {"round": share, "peaks": ctx["peaks"]}
        if coll:
            comm["trace"] = coll
            comm["rank_skew_pct"] = coll["busy_skew_pct"]
            if coll["allreduce_ops"]:
                comm["allreduce_ms_per_round"] = \
                    coll["allreduce_s"] * 1e3 / len(traced)
            if comm.get("wire_bytes_per_round") and coll["allreduce_s"] \
                    and on_tpu:
                least = ici.least_seconds(
                    comm["wire_bytes_per_round"] * len(traced),
                    len(devices), ici.lookup(devices[0].device_kind))
                comm["hist_allreduce_roofline"] = \
                    100.0 * least / coll["allreduce_s"]
        log(f"trace reduced: {tr and {k: tr[k] for k in ('window_s', 'busy_s', 'module_executions', 'category_s')}}")

    operand = "float32" if not on_tpu \
        else cfg["precision"]["histogram_operands"]
    t0 = time.perf_counter()
    numbers = check_dp.compare(model, prog_score, X, y, cfg["reference"],
                               float(params["learning_rate"]),
                               traffic["check"], ctx["seed"], operand,
                               devices, warm=warm, log=log,
                               control_dtype=ctx.get("control_dtype"))
    correct, table = check.judge(numbers, ctx["limits"])
    host["check_s"] = time.perf_counter() - t0
    host["max_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    log(f"reference and comparison {host['check_s']:.1f}s over "
        f"{numbers['trees']} trees; log loss {numbers['log_loss']}; "
        f"host max RSS "
        f"{host['max_rss_bytes'] / 2 ** 30:.1f} GiB")
    return {
        "correct": correct, "check": table,
        "attempted": window_rounds, "failed": 0,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "setup_s": host["setup_s"],
            "train.ms_per_round": window_s * 1e3 / window_rounds,
            "train.peak_hbm_gib": None if peak is None else peak / 2 ** 30,
        },
        "observations": observations, "numbers": numbers,
    }
