"""Hand tool: the readings that a cell's limits are set from.

    python3 perfbench/control/readings.py --workload <cell> --seeds 11,12,13 \
        --seconds 6 [--modes sound,control,half_batch,...] [--out name.jsonl]

One process, one seed after another (set-up is long, so the program's
seeds and the control's are read together). Per seed and mode it makes
one whole run of the cell through ``run.run_cell`` and appends one JSON
line with every number compared to ``chiprun_out/<out>``:

``sound``    the program as the configuration states it; the line also
    carries the control: the reference put in the program's place with
    its operands one precision down (``control``: bfloat16 for the
    float32 the configurations state);
``control``  the program's own lower-precision path switched on (the
    configuration's ``control_params``);
``state_unchanged`` / ``half_batch`` / ``answer_altered``  the faults of
    ``control/faults.py`` planted underneath the run.

Not part of a benchmark run; ``--cpu-selftest-rows`` as in ``run.py``.
"""

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

LOWER = {"float32": "bfloat16"}


def main(argv=None, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--modes", default="sound")
    ap.add_argument("--out", default="readings.jsonl")
    ap.add_argument("--cpu-selftest-rows", type=int, default=0)
    args = ap.parse_args(argv)
    import run
    from control import faults
    from harness.manifest import Manifest
    man = Manifest(root) if root else Manifest()
    config = man.config(man.cell(args.workload))
    out_dir = os.path.join(man.root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for mode in args.modes.split(","):
            extra, plant = {}, contextlib.nullcontext()
            if mode == "sound":
                # the CPU's histogram is float32 whatever the config says
                operand = "float32" if args.cpu_selftest_rows \
                    else config["precision"]["histogram_operands"]
                extra["control_dtype"] = LOWER[operand]
            elif mode == "control":
                extra["params_override"] = config["control_params"]
            else:
                plant = faults.FAULTS[mode]()
            t0 = time.perf_counter()
            row = {"workload": args.workload, "seed": seed, "mode": mode}
            try:
                with plant:
                    line, out = run.run_cell(
                        man, args.workload, seed, args.seconds, 0,
                        args.cpu_selftest_rows, **extra)
                row.update(correct=line["correct"], check=line["check"],
                           control=out["numbers"].get("control"),
                           trees=out["numbers"]["trees"],
                           log_loss=out["numbers"]["log_loss"],
                           metrics=line["metrics"])
            except Exception as e:      # a control that crashes has failed
                row.update(correct=False, crashed=repr(e)[:500])
            row["took_s"] = time.perf_counter() - t0
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, args.out), "a") as fh:
                fh.write(json.dumps(row) + "\n")
    return rows


if __name__ == "__main__":
    main()
