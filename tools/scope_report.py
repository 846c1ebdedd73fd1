"""One traced run of a benchmark cell, read by layer (operator's tool).

    chiprun --chips 1 -- python tools/scope_report.py \
        --workload criteo256.train --seed 7 --out chiprun_out/scopes.json

Runs the cell through the benchmark's own ``run_cell`` with the trace
kept, holds the program's jitted entries alive past the driver's
``del``, then asks the program for the op -> scope table of every
scoped entry that ran (``obs.op_scopes`` over ``SCOPED_ENTRIES``) and
lays each over the ops of ITS program's executions in the device trace
(``obs.xplane``): device self time by scope and by program, the share
directly scoped and derived, the heaviest ops of ``--entry``'s program
with their scopes (a null scope: an op no scope or derivation rule
reaches), the scopes each table is ``missing`` (a stale compile-cache
entry), each idle gap over 1 ms with the program span covering it, the
persistent cache's hits and misses, and the job's host spans. Prints
the benchmark's result line, then one JSON object; ``--out`` also
writes it. Not part of the benchmark: it reads the program, the
benchmark does not change.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="criteo256.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--entry", default="gbdt/fused_iter")
    ap.add_argument("--cpu-selftest-rows", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import run as bench_run
    from harness.manifest import Manifest
    from lightgbm_tpu.models import gbdt
    from lightgbm_tpu.obs import scopes, trace, xplane
    from lightgbm_tpu.obs.registry import registry

    kept = []                   # the entries, past the driver's `del`
    register = gbdt.register_jit

    def keeping(name, fn, **kw):
        out = register(name, fn, **kw)
        kept.append(out)
        return out

    gbdt.register_jit = keeping
    man = Manifest(ROOT)
    line, out = bench_run.run_cell(
        man, args.workload, args.seed, args.seconds, 1,
        args.cpu_selftest_rows, t_start=T_START, keep_trace=True)
    print(json.dumps(line), flush=True)

    t0 = time.perf_counter()
    by_entry = scopes.tables_of_run()
    table_s = time.perf_counter() - t0
    tables = {t.module: t for t in by_entry.values() if t}
    table = by_entry.get(args.entry)
    host = out["observations"]["host"]
    doc = {"workload": args.workload, "seed": args.seed,
           "entry": args.entry, "op_scopes_s": table_s,
           "op_scopes": {
               entry: t and {"module": t.module, "ops": len(t),
                             "derived": len(t.derived),
                             "missing": list(t.missing)}
               for entry, t in by_entry.items()},
           "compile_cache_dir": host.get("compile_cache_dir"),
           "metrics": {k: v["value"] for k, v in line["metrics"].items()},
           "counters": {
               name: sum(r.get("value") or 0 for r in fam["series"])
               for name, fam in registry.snapshot().items()
               if name.startswith("compile_cache_")},
           "spans": [{k: s[k] for k in ("name", "mono", "dur", "attrs")}
                     | {"parent": s["parent_id"], "id": s["span_id"]}
                     for s in trace.span_events_snapshot()]}
    tdir = host.get("trace_dir")
    if tdir:
        capture = xplane.load(xplane.find_xplane(tdir))
        rep = xplane.report(capture, tables)
        for dev in rep["devices"]:
            ops = capture["devices"][dev["plane"]]
            rows = xplane.op_times(
                ops, tables, capture["modules"].get(dev["plane"], []))
            dev["derived_s"] = sum(
                s for prog, op, s, _ in rows
                if prog in tables and op in tables[prog].derived)
            # the heaviest ops of --entry's program (of every program
            # where it has no table): [program, op, self_s, scope or
            # null, derived?]
            dev["top_ops"] = [
                [prog, op, s, None if sc == xplane.UNSCOPED else sc,
                 prog in tables and op in tables[prog].derived]
                for prog, op, s, sc in sorted(rows, key=lambda r: -r[2])
                if not table or prog == table.module][:40]
            # what the host was doing across each gap: every host event
            # that overlaps it, outermost first
            t_first = min(s for _, s, _ in ops)
            for gap in dev["idle_gaps"]:
                a = t_first + gap["at_s"]
                b = a + gap["gap_s"]
                over = [(n, s - a, d) for n, s, d in capture["host"]
                        if s < b and s + d > a]
                gap["host_events"] = [
                    [n[:60], round(s * 1e3, 4), round(d * 1e3, 4)]
                    for n, s, d in sorted(over, key=lambda e: -e[2])[:40]]
            # the two edges of the driver's window, which the benchmark
            # counts as gaps too: its first span's start to the first
            # op, the last op's end to its last span's end
            rounds = sorted((s, s + d) for n, s, d in capture["host"]
                            if n == "perfbench_round")
            if rounds:
                t_last = max(s + d for _, s, d in ops)
                dev["window_edges"] = [
                    {"edge": edge, "gap_s": b - a, "host_events": [
                        [n[:60], round((s - a) * 1e3, 4), round(d * 1e3, 4)]
                        for n, s, d in sorted(
                            ((n, s, d) for n, s, d in capture["host"]
                             if s <= 0.5 * (a + b) <= s + d),
                            key=lambda e: e[2])[:8]]}
                    for edge, a, b in (("head", rounds[0][0], t_first),
                                       ("tail", t_last, rounds[-1][1]))]
        doc["report"] = rep
        print(xplane.render_report(rep), file=sys.stderr, flush=True)
    print(json.dumps(doc), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh)


if __name__ == "__main__":
    main()
