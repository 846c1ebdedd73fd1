"""Driver ``train_eval_missing``: ``train_eval``'s job on tables that a
harness module NAMED BY THE TRAFFIC FILE makes and checks.

``train_eval``'s contract (one ``lgb.train`` call with a validation set,
its metric evaluated every round, timed round by round; set-up is
everything before the window opens; early stopping armed and never
firing inside a window). ``drivers/train_eval.py`` imports its generator
and its check by name, so a table it cannot make needed a driver; this
one imports them by the names the traffic gives, so the next table shape
needs a traffic file and harness modules, and no third driver:

``harness.generator``  module of ``perfbench/harness`` with
    ``make_tables(data, n_features, rows, valid_rows, seed, threads=,
    **selftest)`` returning ``{"train": (X, y, sizes), "valid": ...}``
    and ``selftest(cfg, rows)`` returning ``(keywords for make_tables,
    the factor for a floor stated as a sum over rows)`` for the
    harness's CPU test. Here ``datagen_missing``: float32 tables with
    NaN in station blocks, handed to ``lgb.Dataset`` as they are
    (nothing imputed, nothing pre-binned).
``harness.check``  module with ``compare(...)`` and ``judge(numbers,
    limits)`` as ``check_eval`` has them, and optionally ``probe(lgb,
    params, log)``, which ends the run before the tables are made on a
    program that cannot run the configuration. Here ``check_missing``
    (``reference_missing.py`` routes a NaN by the node's ``default_left``
    and searches a split with the NaN rows on either side;
    ``missing_direction_shortfall`` beside the twelve numbers).
``observe.counter_ratios``  ``{observation: {"num": [...], "den": [...],
    "scale": x}}`` over the program's host counters as they moved since
    the probe (a name with ``-`` in front is subtracted): what the
    ``observation`` reader of a per-layer metric finds under
    ``counters``. A program that declares none of them (the parent of
    the PR that added them) gives nothing, and the metric is left out.

The configuration's ``expect`` is held as in ``train_eval`` (method,
precision, partition, payload, objective, iteration entry), with the
width of the bin matrix the grower streams (``grower_columns``) and, under
``counter_ranges``, the interval an observed counter ratio has to lie in
(the table as the program saw it: 80 to 82% of the cells missing, every
split on a column that has a NaN bin). The run fails loudly, with no
result, on: a compile inside the window, a fault event, anything resolved
other than expected, early stopping inside the window, no
``peak_bytes_in_use`` on a TPU, and the check's probe.

``correct`` is decided after the window has closed, the memory peak has
been read and the program's state is freed. Before it is freed a traced
run takes what the per-layer metrics read: the device time a round of
each program (the trace's module line), of the grower's program by its
registered entry, and of its ops by the program's own scopes.
"""

import gc
import importlib
import os
import resource
import shutil
import time

import numpy as np

SPAN = "perfbench_round"

def _counted(registry, names):
    """The program's host counters ``names`` as they stand (0 where the
    program declares none)."""
    snap = registry.snapshot()
    return {name: sum(row.get("value") or 0
                      for row in snap.get(name, {}).get("series", []))
            for name in names}


def _signed_sum(counted, names):
    return sum(-counted[n[1:]] if n.startswith("-") else counted[n]
               for n in names)


def _partition_rows(tree_json):
    """Rows a tree's splits moved: the sum of its internal nodes' counts."""
    total, stack = 0, [tree_json["tree_structure"]]
    while stack:
        node = stack.pop()
        if "split_index" in node:
            total += int(node["internal_count"])
            stack += [node["left_child"], node["right_child"]]
    return total


def _program_times(trace, window, module_of, scope_tables):
    """Device seconds inside ``window`` from a loaded trace (device 0):
    per program (the module line's events by name), and per scope for
    the programs of ``scope_tables`` (``{module name: {op: scope}}``:
    self time of the ops that ran inside that program's executions).
    ``module_of`` maps a module event's name to the program's name."""
    from harness import trace_reduce
    w0, w1 = window
    plane = sorted(trace["devices"])[0]
    lines = trace["devices"][plane]
    mods = [(module_of(n), s, s + d)
            for n, s, d in lines.get(trace_reduce.MODULES_LINE, [])
            if s + d > w0 and s < w1]
    by_module = {}
    for name, a, b in mods:
        got = by_module.setdefault(name, {"s": 0.0, "n": 0})
        got["s"] += min(b, w1) - max(a, w0)
        got["n"] += 1
    by_scope = {}
    for prog, table in scope_tables.items():
        spans = sorted((a, b) for name, a, b in mods if name == prog)
        if not spans or not table:
            continue
        starts = np.asarray([a for a, _ in spans])
        ends = np.asarray([b for _, b in spans])
        ops = []
        for n, s, d in lines.get(trace_reduce.OPS_LINE, []):
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and s < ends[i]:
                ops.append((n, s, d))
        acc = by_scope.setdefault(prog, {})
        for n, self_s in trace_reduce.self_times(ops):
            sc = table.get(trace_reduce.op_head(n), "(unscoped)")
            acc[sc] = acc.get(sc, 0.0) + self_s
    return by_module, by_scope


def run(ctx):
    import jax
    from harness import trace_reduce, work_model
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs as prog_obs
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.obs.jit_tracker import live_entries
    from lightgbm_tpu.obs.registry import registry

    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    generator, check = (
        importlib.import_module("harness." + traffic["harness"][role])
        for role in ("generator", "check"))
    startup_s = time.perf_counter() - ctx["t_start"]
    log(f"start-up {startup_s:.1f}s (runtime up after "
        f"{ctx.get('runtime_up_s', float('nan')):.1f}s)")
    on_tpu = ctx["devices"][0].platform == "tpu"
    params = dict(cfg["params"])
    params.update(ctx.get("params_override", {}))
    ref_cfg = dict(cfg["reference"], objective=params["objective"])
    pub = {"train": int(cfg["num_data"]), "valid": int(cfg["valid_rows"])}
    rows = ctx["selftest_rows"] or pub["train"]
    valid_rows = max(2, pub["valid"] * rows // pub["train"])
    selftest = {}
    if ctx["selftest_rows"]:
        # the harness's CPU test: a floor stated as a sum over rows
        # shrinks with the table, by the factor its generator gives
        selftest, scale = generator.selftest(cfg, rows)
        for group in (params, ref_cfg):
            group["min_sum_hessian_in_leaf"] *= scale
    warm = int(traffic["warmup_rounds"])
    min_rounds = int(traffic["min_rounds"])
    n_trace = int(traffic["trace_rounds"]) if ctx["trace"] else 0
    host = {"compile_cache_dir": cache_dir, "startup_s": startup_s}
    if hasattr(check, "probe") and not ctx.get("no_probe"):
        check.probe(lgb, params, log)   # (the control tools plant faults)
    ratios = traffic.get("observe", {}).get("counter_ratios", {})
    counters = sorted({n.lstrip("-") for r in ratios.values()
                       for n in r["num"] + r["den"]})
    # the probe's table and tree are not the job's
    counted0 = _counted(registry, counters)

    t0 = time.perf_counter()
    tables = generator.make_tables(
        cfg["data"], int(cfg["num_features"]), rows, valid_rows, ctx["seed"],
        threads=min(12, os.cpu_count() or 1), **selftest)
    (X, y, sizes), (Xv, yv, vsizes) = tables["train"], tables["valid"]
    host["datagen_s"] = time.perf_counter() - t0
    log(f"tables {X.shape} + {Xv.shape} from seed {ctx['seed']} in "
        f"{host['datagen_s']:.1f}s; label means {float(y.mean()):.4%} + "
        f"{float(yv.mean()):.4%}")

    t0 = time.perf_counter()
    train = lgb.Dataset(X, label=y, group=sizes,
                        params={"max_bin": params["max_bin"]})
    valid = lgb.Dataset(Xv, label=yv, group=vsizes, reference=train)
    valid.construct()       # and, through the reference, the train set
    host["construct_s"] = time.perf_counter() - t0
    log(f"construct {host['construct_s']:.1f}s")

    trace_dir = os.path.join(ctx["out_dir"], "trace", ctx["cell"]["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    clock = {"starts": [], "ends": [], "open": None, "watch": None,
             "span": None, "tracing": False, "traced": [], "close": None}
    evals = {}

    def before(env):
        clock["starts"].append(time.perf_counter())
        if n_trace and env.iteration == warm and not clock["tracing"]:
            os.makedirs(trace_dir, exist_ok=True)
            # as train_rounds: the host tracer for the spans, Python's
            # function-call tracer off
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
        eng = env.model._engine
        jax.block_until_ready((eng.score, eng.valid_sets[0].score))
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
                and len(clock["ends"]) - warm >= min_rounds \
                and now - clock["open"] >= ctx["seconds"]:
            clock["close"] = now
            raise EarlyStopException(env.iteration, [])
    after.order = 1000      # after early stopping and the record: last

    try:
        bst = lgb.train(
            params, train, num_boost_round=int(traffic["max_rounds"]),
            valid_sets=[valid], valid_names=["valid"],
            callbacks=[lgb.early_stopping(
                int(traffic["early_stopping_rounds"]), verbose=False),
                lgb.record_evaluation(evals), before, after])
    finally:
        if clock["tracing"]:
            jax.profiler.stop_trace()
    if clock["close"] is None:
        raise RuntimeError(
            "training ended before the window closed (early stopping, or "
            f"no leaf left to split): {len(clock['ends'])} rounds")
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
        f"{host['setup_s']:.1f}s (first round {host['first_round_s']:.1f}s); "
        f"rounds, ms: {[round(v, 1) for v in round_ms]}")

    # -- what must hold for the run to be a run of this cell -----------
    compiles = clock["watch"].delta()
    snap = registry.snapshot()

    def family(name, label):
        return {s["labels"].get(label, "?"): int(s["value"])
                for s in snap.get(name, {}).get("series", [])}

    faults, compiled = family("fault_events", "kind"), \
        family("xla_compiles", "entry")
    expect = dict(cfg["expect"], **traffic.get("expect", {}))
    problems = []
    if compiles:
        problems.append(f"{compiles} program(s) compiled inside the window")
    if faults or eng.fault_log:
        problems.append(f"fault events: {faults} {eng.fault_log}")
    plan = getattr(eng, "_grow_plan", None)
    if not plan:        # a program that keeps none on this path
        from lightgbm_tpu.ops import grow as prog_grow
        plan = dict(getattr(prog_grow, "last_plan", None) or {})
    resolved = {"hist_method": eng.grow_cfg.hist_method,
                "hist_precision": eng.grow_cfg.hist_precision,
                "partition": plan.get("partition"),
                "payload": plan.get("payload"),
                "objective": getattr(eng.objective, "name", None),
                # models/gbdt.py: the one training matrix that reaches
                # the device is the bundled one where EFB bundled
                "grower_columns": int(
                    eng.bundle.bins_bundled.shape[1]
                    if getattr(eng, "bundle", None) is not None else eng.F)}
    counted = {k: v - counted0[k]
               for k, v in _counted(registry, counters).items()}
    observed = {}
    for name, r in ratios.items():
        den = _signed_sum(counted, r["den"])
        if den > 0:     # a program without the counters: nothing to read
            observed[name] = float(r.get("scale", 1.0)) \
                * _signed_sum(counted, r["num"]) / den
    as_stated = not ctx.get("params_override")   # the control's is another
    for name, (lo, hi) in expect.get("counter_ranges", {}).items():
        # (the control tools plant faults that a NUMBER has to read: with
        # the probe off these do not end the run either)
        if as_stated and not ctx.get("no_probe") and name in observed \
                and not lo <= observed[name] <= hi:
            problems.append(f"{name} reads {observed[name]:.4g}, expected "
                            f"{lo} to {hi}")
    for key, got in resolved.items():
        # what the grower resolves from the backend holds on the chip
        # only; the objective wherever the cell runs
        if key in expect and as_stated \
                and (on_tpu or key in ("objective", "grower_columns")) \
                and got != expect[key]:
            problems.append(f"{key} resolved to {got!r}, expected "
                            f"{expect[key]!r}")
    if compiled.get(expect["iteration_entry"], 0) < 1:
        problems.append(f"{expect['iteration_entry']!r} never compiled: "
                        f"{compiled}")
    mem = ctx["devices"][0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    if on_tpu and peak is None:
        problems.append("memory_stats() gave no peak_bytes_in_use")
    elif peak is not None:
        # as train_rounds: a program's temporaries are counted apart
        peak += mem.get("peak_bytes_reserved", 0)
    if problems:
        raise RuntimeError("not a run of this cell: " + "; ".join(problems))
    log(f"device memory_stats {mem}")

    # -- what the per-layer metrics read, while the engine lives -------
    observations = {"host": host,
                    "counters": dict(observed, compiles_in_window=compiles),
                    "trace": None, "work": None, "programs": None}
    model = bst.dump_model()
    n_features = X.shape[1]
    if n_trace:
        loaded = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        tr = trace_reduce.reduce(loaded, span_name=SPAN)
        if ctx.get("keep_trace"):
            host["trace_dir"] = trace_dir
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
        traced = clock["traced"]
        host["traced_rounds"] = len(traced)
        observations["trace"] = tr
        grow_entry, grow_module, tables_by_module = \
            expect["iteration_entry"], None, {}
        live = live_entries(grow_entry)
        if live:
            grow_module = "jit_" + live[-1].unwrapped.__name__
            tables_by_module[grow_module] = prog_obs.op_scopes(grow_entry)
        spans = sorted((s, s + d) for n, s, d in loaded["host"] if n == SPAN)
        if tr and spans:
            by_module, by_scope = _program_times(
                loaded, (spans[0][0], spans[-1][1]),
                lambda n: n.split("(", 1)[0], tables_by_module)
            per = 1e3 / len(traced)
            progs = {"ms_per_round": {k: v["s"] * per
                                      for k, v in by_module.items()},
                     "runs_per_round": {k: v["n"] / len(traced)
                                        for k, v in by_module.items()},
                     "scope_ms_per_round": {
                         p: {sc: s * per for sc, s in t.items()}
                         for p, t in by_scope.items()}}
            grow = by_module.get(grow_module)
            if grow:
                progs["grow_ms_per_round"] = grow["s"] * per
            observations["programs"] = progs
            log(f"programs, ms a round: "
                f"{ {k: round(v, 3) for k, v in sorted(progs['ms_per_round'].items(), key=lambda kv: -kv[1])[:12]} }")
            log(f"scopes, ms a round: {progs['scope_ms_per_round']}")
        del loaded
        whole = {"ops": 0, "bytes": 0}
        for ti in traced:
            w = work_model.round_work(
                rows, work_model.tree_hist_rows(model["tree_info"][ti], rows),
                n_features)
            whole = {k: whole[k] + w[k] for k in whole}
        observations["work"] = {"round": whole, "peaks": ctx["peaks"]}
        log(f"trace reduced: {tr and {k: tr[k] for k in ('window_s', 'busy_s', 'module_executions', 'category_s')}}")

    # -- the product, then the program's state is freed -----------------
    prog = {"score": np.asarray(eng.score)[0],
            "valid_score": np.asarray(eng.valid_sets[0].score)[0],
            "evals": dict(evals.get("valid", {}))}
    leaves = [t["num_leaves"] for t in model["tree_info"]]
    host["leaves_per_tree"] = [min(leaves), int(np.median(leaves)),
                               max(leaves)]
    # what a round's time follows: the rows its histograms read (the root
    # and every split's smaller child) and the rows its splits partition
    # (every split's parent)
    host["window_hist_rows"] = [
        work_model.tree_hist_rows(t, rows) for t in model["tree_info"][warm:]]
    host["window_partition_rows"] = [
        _partition_rows(t) for t in model["tree_info"][warm:]]
    log(f"window rounds: leaves {leaves[warm:]}; histogram rows "
        f"{host['window_hist_rows']}; partitioned rows "
        f"{host['window_partition_rows']}")
    del eng, bst, train, valid
    gc.collect()
    log(f"program freed; device holds "
        f"{sum(a.nbytes for a in jax.live_arrays())} bytes; leaves a tree "
        f"(min, median, max) {host['leaves_per_tree']}")

    operand = "float32" if not on_tpu \
        else cfg["precision"]["histogram_operands"]
    t0 = time.perf_counter()
    numbers = check.compare(
        model, prog, tables, ref_cfg, float(params["learning_rate"]),
        traffic["check"], ctx["seed"], operand, warm=warm, log=log,
        control_dtype=ctx.get("control_dtype"))
    correct, table = check.judge(numbers, ctx["limits"])
    host["check_s"] = time.perf_counter() - t0
    host["max_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    log(f"reference and comparison {host['check_s']:.1f}s over "
        f"{numbers['trees']} trees; nodes of the followed trees "
        f"{numbers.get('nodes')}; metric {numbers['metrics']}; host max "
        f"RSS {host['max_rss_bytes'] / 2 ** 30:.1f} GiB")
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
