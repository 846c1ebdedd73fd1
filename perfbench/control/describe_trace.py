"""Hand tool: run one traced cell, keep its trace, and write down what
the trace looks like (planes, lines, the heaviest event names with
their stats keys) to ``chiprun_out/trace_description.json``.

    python3 perfbench/control/describe_trace.py --workload <cell> --seed <n> --seconds <s>

Not part of a benchmark run. It exists so that ``trace_reduce.py``'s
constants (plane prefix, line names, op classes) can be checked against
a real trace after a JAX upgrade.
"""

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def describe(path, top=40):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            tot, cnt, keys = {}, {}, {}
            n = 0
            for ev in line.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns * 1e-9
                cnt[ev.name] = cnt.get(ev.name, 0) + 1
                if ev.name not in keys:
                    keys[ev.name] = {str(k): str(v)[:120]
                                     for k, v in ev.stats}
            heavy = sorted(tot, key=lambda k: -tot[k])[:top]
            lines.append({"line": line.name, "events": n,
                          "heaviest": [{"name": k, "seconds": tot[k],
                                        "count": cnt[k], "stats": keys[k]}
                                       for k in heavy]})
        planes.append({"plane": plane.name, "lines": lines})
    return planes


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import run
    from harness import check, trace_reduce
    from harness.manifest import Manifest
    man = Manifest()
    line, out = run.run_cell(man, args.workload, args.seed, args.seconds, 1,
                             keep_trace=True)
    check.print_table(line["check"], line["correct"])
    print(json.dumps(line), flush=True)
    kept = out["observations"]["host"]["trace_dir"]
    xplane = trace_reduce.find_xplane(kept)
    out_dir = os.path.join(man.root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "trace_description.json"), "w") as fh:
        json.dump({"xplane_bytes": os.path.getsize(xplane),
                   "planes": describe(xplane)}, fh, indent=1)
    shutil.rmtree(kept, ignore_errors=True)


if __name__ == "__main__":
    main()
