"""Driver ``train_eval``: one ``lgb.train`` call with a validation set,
its metric evaluated every round, timed round by round.

``train_rounds``' contract with a second table. Set-up is everything
before the window opens: both tables from the seed, the validation
``Dataset(reference=train)`` constructed (which constructs the train set
through the reference chain), the compile cache, the warm-up rounds. The
job is ``lgb.train(params, train, num_boost_round=<large>,
valid_sets=[valid], callbacks=[early_stopping, record_evaluation,
clock])``: the engine scores the validation rows and evaluates the
metric every round, on the path a user's job takes, and the clock's
callback runs after that, so a round's time holds both. The window opens
at the end of the last warm-up round and closes at the end of the first
round that ends ``--seconds`` later and is at least the traffic's
``min_rounds``-th. Early stopping is armed and must not fire inside a
window.

What differs between the cells comes from the configuration: objective,
query groups (``data.queries``), metric, the reference's gradient. A
configuration written for a driver without a validation set states
neither a fold nor a metric: the traffic's ``unstated`` gives both.

The run fails loudly, with no result, on: a compile inside the window,
a fault event, a resolved method, precision, partition, payload,
objective or iteration entry other than expected, early stopping inside
the window, no ``peak_bytes_in_use`` on a TPU; and, before the tables are
made, on a program whose lambdarank gradient is not the source's (it
cannot run a ranking configuration: see ``_ranking_probe``).

``correct`` is decided after the window has closed, the memory peak has
been read and the program's state is freed: ``harness/check_eval.py``.
Before it is freed a traced run takes what the per-layer metrics read:
the device time of each program of the round (the trace's module line),
of the ranking gradient's program by its registered entry, and of the
grower's ops by the program's own scopes.
"""

import gc
import os
import resource
import shutil
import time

import numpy as np

SPAN = "perfbench_round"
RANK_ENTRY = "ranking/lambdarank_grads"


def _ranking_probe(lgb, params, ref_cfg, log):
    """Whether the program's lambdarank is the configuration's: its
    gradients on three tiny queries against the plain reference's. A
    program that departs from the source under ``lambdarank_norm``
    (before PR 33: no division of a pair's delta by the score distance,
    half the lambda sum) would train other trees than the configuration
    states, so it cannot run the configuration, and the run ends here. A
    program that cannot be asked is not held to it."""
    import jax.numpy as jnp
    from harness import reference_rank
    try:
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.ranking import LambdarankNDCG
    except ImportError:
        return
    rng = np.random.default_rng(33)
    sizes = [5, 9, 2]
    label = rng.integers(0, 5, sum(sizes)).astype(np.float32)
    score = jnp.asarray(rng.standard_normal(sum(sizes)), jnp.float32)
    ds = lgb.Dataset(rng.standard_normal((sum(sizes), 2)), label=label,
                     group=sizes)
    ds.construct()
    obj = LambdarankNDCG(Config.from_params(params))
    obj.set_dataset(ds)
    got = obj.grad_hess(score, jnp.asarray(label), None)
    want = reference_rank.lambdarank_grad_hess(
        score, jnp.asarray(label), reference_rank.query_layout(sizes),
        ref_cfg["sigmoid"], ref_cfg["lambdarank_truncation_level"],
        ref_cfg["lambdarank_norm"])
    gap = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
              for a, b in zip(got, want))
    log(f"lambdarank probe: widest gap {gap:.3g}")
    if not gap < 1e-3:
        raise RuntimeError(
            "this program cannot run a lambdarank configuration: its "
            "gradients on three tiny queries differ from the source's "
            f"(rank_objective.hpp) by {gap:.3g} of the largest")


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
    from harness import (check_eval, datagen_rank, trace_reduce, work_model,
                         work_model_rank)
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs as prog_obs
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.obs.jit_tracker import live_entries
    from lightgbm_tpu.obs.registry import registry

    log, cfg, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    startup_s = time.perf_counter() - ctx["t_start"]
    log(f"start-up {startup_s:.1f}s (runtime up after "
        f"{ctx.get('runtime_up_s', float('nan')):.1f}s)")
    on_tpu = ctx["devices"][0].platform == "tpu"
    unstated = traffic["unstated"]
    params = dict(cfg["params"])
    params.setdefault("metric", unstated["metric"])
    params.update(ctx.get("params_override", {}))
    ranking = params["objective"] == "lambdarank"
    ref_cfg = dict(cfg["reference"], objective=params["objective"])
    pub = {"train": int(cfg["num_data"]),
           "valid": int(cfg.get("valid_rows")
                        or cfg["num_data"] * unstated["valid_share"])}
    rows = ctx["selftest_rows"] or pub["train"]
    valid_rows = max(2, pub["valid"] * rows // pub["train"])
    if ctx["selftest_rows"]:
        # the harness's CPU test: a floor stated as a sum over rows
        # shrinks with the table, or no leaf of a tiny table could split
        for group in (params, ref_cfg):
            group["min_sum_hessian_in_leaf"] = \
                group.get("min_sum_hessian_in_leaf", 1e-3) * rows / pub["train"]
    warm = int(traffic["warmup_rounds"])
    min_rounds = int(traffic["min_rounds"])
    n_trace = int(traffic["trace_rounds"]) if ctx["trace"] else 0
    host = {"compile_cache_dir": cache_dir, "startup_s": startup_s}
    if ranking:
        _ranking_probe(lgb, params, ref_cfg, log)

    t0 = time.perf_counter()
    tables = datagen_rank.make_tables(
        cfg["data"], rows, valid_rows, ctx["seed"], pub,
        threads=min(12, os.cpu_count() or 1))
    (X, y, sizes), (Xv, yv, vsizes) = tables["train"], tables["valid"]
    host["datagen_s"] = time.perf_counter() - t0
    log(f"tables {X.shape} + {Xv.shape} from seed {ctx['seed']} in "
        f"{host['datagen_s']:.1f}s"
        + (f"; {len(sizes)} + {len(vsizes)} queries, longest "
           f"{sizes.max()} / {vsizes.max()}" if ranking else ""))

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
                "objective": getattr(eng.objective, "name", None)}
    as_stated = not ctx.get("params_override")   # the control's is another
    for key, got in resolved.items():
        # what the grower resolves from the backend holds on the chip
        # only; the objective wherever the cell runs
        if key in expect and as_stated and (on_tpu or key == "objective") \
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
                    "counters": {"compiles_in_window": compiles},
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
        names, tables_by_module = {}, {}
        for entry in (RANK_ENTRY, expect["iteration_entry"]):
            live = live_entries(entry)
            if live:
                names[entry] = "jit_" + live[-1].unwrapped.__name__
                tables_by_module[names[entry]] = prog_obs.op_scopes(entry)
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
            grad = by_module.get(names.get(RANK_ENTRY))
            if grad:
                progs["rank_grad_s"] = grad["s"]
                progs["rank_grad_ms_per_round"] = grad["s"] * per
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
        work = {"round": whole, "peaks": ctx["peaks"]}
        if ranking:
            grad_work = work_model_rank.gradient_work(
                sizes, y, ref_cfg["lambdarank_truncation_level"])
            work["rank_grad"] = {k: grad_work[k] * len(traced)
                                 for k in ("ops", "bytes")}
            work["round"] = {k: whole[k] + work["rank_grad"][k]
                             for k in whole}
            host["rank_pairs_per_round"] = grad_work["pairs"]
        observations["work"] = work
        log(f"trace reduced: {tr and {k: tr[k] for k in ('window_s', 'busy_s', 'module_executions', 'category_s')}}")

    # -- the product, then the program's state is freed -----------------
    prog = {"score": np.asarray(eng.score)[0],
            "valid_score": np.asarray(eng.valid_sets[0].score)[0],
            "evals": dict(evals.get("valid", {})), "grad": None}
    if ranking:
        # one more pass of the program's own gradient, at its own score
        g, h = eng._gradients(eng.score)
        prog["grad"] = (np.asarray(g)[0], np.asarray(h)[0])
        del g, h
    leaves = [t["num_leaves"] for t in model["tree_info"]]
    host["leaves_per_tree"] = [min(leaves), int(np.median(leaves)),
                               max(leaves)]
    del eng, bst, train, valid
    gc.collect()
    log(f"program freed; device holds "
        f"{sum(a.nbytes for a in jax.live_arrays())} bytes; leaves a tree "
        f"(min, median, max) {host['leaves_per_tree']}")

    operand = "float32" if not on_tpu \
        else cfg["precision"]["histogram_operands"]
    t0 = time.perf_counter()
    numbers = check_eval.compare(
        model, prog, tables, ref_cfg, float(params["learning_rate"]),
        traffic["check"], ctx["seed"], operand, warm=warm, log=log,
        control_dtype=ctx.get("control_dtype"))
    correct, table = check_eval.judge(numbers, ctx["limits"],
                                      check_eval.numbers_of(ref_cfg))
    host["check_s"] = time.perf_counter() - t0
    host["max_rss_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    log(f"reference and comparison {host['check_s']:.1f}s over "
        f"{numbers['trees']} trees; metric {numbers['metrics']}; host max "
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
