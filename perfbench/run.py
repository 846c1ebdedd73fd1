"""The benchmark's entry point: one cell, one run, one result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: resolves the cell from ``BENCHMARK.json`` (see
``harness/manifest.py``), makes the inputs from ``--seed``, sets up and
warms the program, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object
as the last line of standard output. Without a TPU it fails and prints
no result; ``--cpu-selftest-rows N`` is the harness's own test switch:
it runs the same code on the CPU at ``N`` rows and never yields a device
number (``platform`` says ``cpu``).
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-selftest-rows", type=int, default=0)
    return ap.parse_args(argv)


def log(msg):
    print(f"perfbench [{time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def device_gate(cell, selftest):
    """The process's first contact with JAX: the chips the cell asks
    for, or the explicit self-test."""
    import jax
    if selftest:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if not selftest and devs[0].platform != "tpu":
        raise RuntimeError(f"the benchmark needs a TPU; JAX found "
                           f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < int(cell["chips"]):
        raise RuntimeError(f"cell {cell['name']} asks for {cell['chips']} "
                           f"chip(s); JAX sees {len(devs)}")
    return jax, devs


def run_cell(man, name, seed, seconds, trace, selftest_rows=0, t_start=None,
             **extra):
    """One run of one cell: ``(result line as a dict, driver's output)``.
    ``t_start`` is where set-up is counted from (default: now); ``extra``
    reaches the driver's context, for the control tools."""
    from harness import peaks
    cell = man.cell(name)
    config, traffic = man.config(cell), man.traffic(cell)
    limits = man.limits(cell)
    selftest = selftest_rows > 0
    jax, devs = device_gate(cell, selftest)
    runtime_up = time.perf_counter()
    chip = None if selftest else peaks.lookup(devs[0].device_kind)
    ctx = {
        "cell": cell, "config": config, "traffic": traffic,
        "limits": limits["cpu_selftest"] if selftest else limits["limits"],
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "selftest_rows": selftest_rows,
        "t_start": time.perf_counter() if t_start is None else t_start,
        "devices": devs[:int(cell["chips"])], "peaks": chip,
        "out_dir": os.path.join(man.root, ".perfbench_out"), "log": log,
    }
    ctx["runtime_up_s"] = runtime_up - ctx["t_start"]
    ctx.update(extra)
    out = man.driver(traffic).run(ctx)
    obs = out["observations"]
    metrics = {}
    if trace:
        for m in man.metrics(cell, "per_layer"):
            v = man.read_metric(m, obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in man.metrics(cell, "end_to_end"):
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    tr = obs.get("trace")
    if trace and tr:
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["check"] = out["check"]
    return line, out


def main(argv=None, root=None):
    args = parse_args(argv)
    from harness import check
    from harness.manifest import Manifest
    man = Manifest(root) if root else Manifest()
    line, _ = run_cell(man, args.workload, args.seed, args.seconds,
                       args.trace, args.cpu_selftest_rows, t_start=T_START)
    check.print_table(line["check"], line["correct"])
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
