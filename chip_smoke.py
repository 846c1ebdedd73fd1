"""On-chip smoke: the main path once, end to end, on one TPU chip.

    python chip_smoke.py               # train leg, then serve leg
    python chip_smoke.py --devices 4   # train leg, tree_learner=data, 4 chips
    python chip_smoke.py --kernels     # compile the Pallas kernels (TPU only)
    python chip_smoke.py --cpu-selftest --rows 20000   # tier-1 test only

*Train leg* (a child process): Higgs-shaped data from a seed at the
full width of the job (10,500,000 x 28, 255 leaves, 255 bins,
``objective=binary``), ``lgb.Dataset(...).construct()``, ``lgb.train``
for 1 compiling + 5 steady rounds with default params otherwise,
``predict`` on 524,288 held-out rows, ``save_model`` ->
``Booster(model_file=)`` -> exact predict parity, held-out AUC above a
pinned floor. *Serve leg*: ``python -m lightgbm_tpu serve <model>`` on
the model just saved, JSON-lines requests of 1 / 64 / 1000 rows plus
``stats`` and ``shutdown``; replies equal the train leg's
``Booster.predict`` to 1e-6.

A chip belongs to one process at a time, so this parent never imports
JAX and runs the trainer and the daemon as sequential children.

This is not a benchmark: the timings in the result line are
informational. It exists to fail loudly. Any of these is a traceback
and a non-zero exit with no result line: no TPU (the only way onto the
CPU is ``--cpu-selftest``), a leg that raises, a compile after the
warm-up round, a resolved ``hist_method`` other than ``mxu``, any fault
event (the OOM ladder firing turns the run into a different program),
the native binning library not loading, no peaks row for the chip's
``device_kind``, a prediction mismatch, AUC under the floor.

A passing run ends with two lines on stdout, each one JSON object: the
report of what ran (platform, versions, timings, compiled entries,
parity results, the serve block), and last the verdict the driver
reads, exactly ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with the device as JAX reported it to the train leg.
"""

import argparse
import hashlib
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

HIGGS_ROWS = 10_500_000
N_FEATURES = 28
N_VALID = 524_288
NUM_LEAVES = 255
MAX_BIN = 255
ROUNDS = 6                   # 1 that compiles + 5 steady
# held-out AUC after ROUNDS rounds at the full shape: the first passing
# chip run gave 0.878235, bit-identical over three runs (PR 21; the
# 50-round oracle is 0.9676)
AUC_FLOOR = 0.87
SELFTEST_AUC_FLOOR = 0.75    # tiny-row CPU self-test: sanity only
SERVE_SIZES = (1, 64, 1000)
SERVE_TOL = 1e-6
SERVE_READY_TIMEOUT_S = 600
CHILD_TIMEOUT_S = 1100       # under the 1200 s the whole script may take

REPO = os.path.dirname(os.path.abspath(__file__))


def _fail(failures, what):
    print(f"chip_smoke: FAILED CHECK: {what}", file=sys.stderr)
    failures.append(what)


def _raise_if(failures):
    if failures:
        raise RuntimeError(f"{len(failures)} check(s) failed: "
                           + "; ".join(failures))


def _platform_gate(args):
    """The process's first JAX contact: TPU, or the explicit self-test."""
    import jax
    if args.cpu_selftest:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if not args.cpu_selftest and devs[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < args.devices:
        raise RuntimeError(
            f"--devices {args.devices} but JAX sees {len(devs)} "
            "device(s); refusing to shrink the mesh")
    return jax, devs


def _versions(jax):
    import importlib.metadata as md
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


# ---------------------------------------------------------------------
# train leg (child process; holds the chip until it exits)
# ---------------------------------------------------------------------

def _round_clock(jax, obs):
    """before/after-iteration callbacks for ``lgb.train``: wall time per
    round ending in ``block_until_ready`` on the score, the resolved
    hist_method at every round, and a RecompileWatcher armed once the
    warm-up round is done."""
    rec = {"starts": [], "ends": [], "hist_methods": [], "watch": None}

    def before(env):
        rec["hist_methods"].append(env.model._engine.grow_cfg.hist_method)
        rec["starts"].append(time.perf_counter())
    before.before_iteration = True

    def after(env):
        jax.block_until_ready(env.model._engine.score)
        rec["ends"].append(time.perf_counter())
        if rec["watch"] is None:
            rec["watch"] = obs.RecompileWatcher()

    return rec, before, after


def _sharding_report(jax, eng, n_devices, failures):
    """Four-chip findings: where bins / score / gradients live."""
    grad, _ = eng._gradients(eng.score)
    arrays = {"bins": eng.bins_T, "score": eng.score, "grad": grad}
    rep = {"shard_residency": eng._residency,
           "mesh_devices": int(eng.mesh.devices.size)
           if eng.mesh is not None else 0}
    for name, arr in arrays.items():
        shards = arr.addressable_shards
        rep[name] = {
            "sharding": str(arr.sharding),
            "global_shape": list(arr.shape),
            "devices": len({s.device.id for s in shards}),
            "shard_shapes": sorted({tuple(s.data.shape) for s in shards}),
        }
        if rep[name]["devices"] != n_devices \
                or arr.sharding.is_fully_replicated:
            _fail(failures, f"{name} is not sharded over {n_devices} "
                            f"devices: {rep[name]}")
    if rep["mesh_devices"] != n_devices:
        _fail(failures, f"mesh has {rep['mesh_devices']} devices, "
                        f"wanted {n_devices}")
    stats = [d.memory_stats() for d in jax.devices()[:n_devices]]
    if all(s for s in stats):
        used = [int(s["bytes_in_use"]) for s in stats]
        rep["bytes_in_use_per_device"] = used
        rep["peak_bytes_in_use_per_device"] = [
            int(s["peak_bytes_in_use"]) for s in stats]
        # device 0 also holds the engine's unsharded [n] f32 vectors
        # (the label: +42 MB at Higgs width, 192 vs 149 MB — PR 21);
        # rows left on one device would make it 4x the others
        if max(used) > 1.5 * min(used):
            _fail(failures, f"per-device bytes_in_use are not balanced "
                            f"(max > 1.5 x min): {used}")
    else:
        rep["bytes_in_use_per_device"] = None   # CPU: no allocator stats
    return rep


def train_leg(args):
    import numpy as np
    jax, devs = _platform_gate(args)
    on_tpu = devs[0].platform == "tpu"

    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.registry import registry
    from lightgbm_tpu.utils import native

    from bench import auc, make_higgs_like

    failures = []
    rows, n_valid = args.rows, min(N_VALID, max(1000, args.rows // 4))
    X, y = make_higgs_like(rows + n_valid, N_FEATURES)
    Xv, yv = X[rows:].copy(), y[rows:].copy()
    Xtr, ytr = X[:rows].copy(), y[:rows]
    del X

    t0 = time.perf_counter()
    ds = lgb.Dataset(Xtr, label=ytr, params={"max_bin": MAX_BIN})
    ds.construct()
    construct_s = time.perf_counter() - t0
    del Xtr
    native_loaded = native._load() is not None
    if not native_loaded:
        _fail(failures, "native binning library did not load "
                        "(construct fell back to numpy)")

    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "max_bin": MAX_BIN}
    if args.devices > 1:
        params["tree_learner"] = "data"
        if args.cpu_selftest:
            # shard_residency=auto means "device" on an accelerator mesh
            # and "host" on CPU virtual devices; the self-test asks for
            # the branch the chips take
            params["shard_residency"] = "device"
    clock, before, after = _round_clock(jax, obs)
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=ROUNDS,
                    callbacks=[before, after])
    train_s = time.perf_counter() - t0
    recompiles_after_warmup = clock["watch"].delta()
    eng = bst._engine

    want_hist = "mxu" if on_tpu else "scatter"
    hist_methods = sorted(set(clock["hist_methods"])
                          | {eng.grow_cfg.hist_method})
    if hist_methods != [want_hist]:
        _fail(failures, f"hist_method resolved to {hist_methods}, "
                        f"expected only {want_hist!r}")
    if recompiles_after_warmup != 0:
        _fail(failures, f"{recompiles_after_warmup} program(s) compiled "
                        "after the warm-up round")
    snap = registry.snapshot()
    fault_events = {s["labels"].get("kind", "?"): int(s["value"])
                    for s in snap.get("fault_events", {}).get("series", [])}
    if fault_events or eng.fault_log:
        _fail(failures, f"fault events recorded: {fault_events} "
                        f"{eng.fault_log}")
    compiled = {s["labels"].get("entry", "?"): int(s["value"])
                for s in snap.get("xla_compiles", {}).get("series", [])}
    want_entry = "parallel/dp_grow" if args.devices > 1 \
        else "gbdt/fused_iter"
    if compiled.get(want_entry, 0) < 1:
        _fail(failures, f"the expected iteration program {want_entry!r} "
                        f"never compiled: {compiled}")
    kind, peak_flops, peak_bw = obs.device_peaks()
    if on_tpu and (peak_flops is None or peak_bw is None):
        _fail(failures, f"obs.cost.device_peaks() has no row for "
                        f"device_kind {kind!r}")

    sharding = None
    if args.devices > 1:
        sharding = _sharding_report(jax, eng, args.devices, failures)
        if on_tpu and sharding["shard_residency"] != "device":
            _fail(failures, "shard_residency=auto did not take the "
                            f"device branch: {sharding['shard_residency']}")

    pred = bst.predict(Xv)
    if pred.shape != (n_valid,) or not np.all(np.isfinite(pred)) \
            or pred.min() < 0.0 or pred.max() > 1.0:
        _fail(failures, f"predict: shape {pred.shape}, finite "
                        f"{bool(np.all(np.isfinite(pred)))}, range "
                        f"[{pred.min()}, {pred.max()}]")
    held_out_auc = float(auc(yv, pred))
    floor = SELFTEST_AUC_FLOOR if args.cpu_selftest else AUC_FLOOR
    if not held_out_auc >= floor:
        _fail(failures, f"held-out AUC {held_out_auc:.6f} under the "
                        f"floor {floor}")

    model_path = os.path.join(args.workdir, "model.txt")
    bst.save_model(model_path)
    reloaded = lgb.Booster(model_file=model_path)
    parity_exact = bool(np.array_equal(pred, reloaded.predict(Xv)))
    if not parity_exact:
        _fail(failures, "save_model -> Booster(model_file=) predict "
                        "is not exactly equal")

    # what the serve leg must reproduce: distinct held-out slices and
    # Booster.predict's answers for them
    fixtures, start = {}, 0
    for size in SERVE_SIZES:
        fixtures[f"rows_{size}"] = Xv[start:start + size]
        fixtures[f"pred_{size}"] = pred[start:start + size]
        start += size
    np.savez(os.path.join(args.workdir, "serve_fixtures.npz"), **fixtures)

    with open(model_path, "rb") as fh:
        model_sha = hashlib.sha256(fh.read()).hexdigest()
    ends = clock["ends"]
    mem = obs.device_memory_stats()
    if on_tpu and mem["peak_bytes_in_use"] is None:
        _fail(failures, "device.memory_stats() reported no "
                        "peak_bytes_in_use on a TPU")
    result = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "devices_used": args.devices,
        **_versions(jax),
        "compile_cache_dir": cache_dir,
        "rows": rows, "features": N_FEATURES, "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN, "objective": "binary", "rounds": ROUNDS,
        "tree_learner": params.get("tree_learner", "serial"),
        "held_out_rows": n_valid,
        "construct_s": round(construct_s, 3),
        "train_s": round(train_s, 3),
        "first_round_s": round(ends[0] - clock["starts"][0], 3),
        "ms_per_round_informational": round(
            (ends[-1] - ends[0]) / (len(ends) - 1) * 1e3, 1),
        "hist_method": eng.grow_cfg.hist_method,
        "compiled_entries": compiled,
        "recompiles_after_warmup": recompiles_after_warmup,
        "fault_events": fault_events,
        "native_loaded": native_loaded,
        "device_peaks": {"device_kind": kind, "flops": peak_flops,
                         "bytes_per_s": peak_bw},
        "trees": len(eng.models),
        "leaves_per_tree": [int(t.num_leaves) for t in eng.models],
        "auc": round(held_out_auc, 6), "auc_floor": floor,
        "save_load_parity_exact": parity_exact,
        "model_sha256": model_sha,
        "peak_bytes_in_use": mem["peak_bytes_in_use"],
        "sharding": sharding,
    }
    print("chip_smoke train leg: " + json.dumps(result), file=sys.stderr)
    _raise_if(failures)
    with open(os.path.join(args.workdir, "train.json"), "w") as fh:
        json.dump(result, fh)


# ---------------------------------------------------------------------
# kernels mode (child process, TPU only): do the Pallas kernels compile?
# ---------------------------------------------------------------------

def kernels_leg(args):
    import numpy as np
    jax, devs = _platform_gate(args)
    import jax.numpy as jnp

    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    from lightgbm_tpu.ops.histogram import hist_from_rows
    from lightgbm_tpu.ops.pallas_hist import hist_from_rows_pallas_jit

    rs = np.random.RandomState(0)
    outcomes = {}

    def hist_case(name, s, f, b, dtype):
        rows = jnp.asarray(rs.randint(0, b, (s, f)).astype(dtype))
        # bf16-exact payload: the kernel's single MXU pass rounds f32
        # operands to bf16, so parity with scatter is exact up to f32
        # summation order only for values bf16 holds exactly
        pay = jnp.asarray(rs.randint(-64, 64, (s, 2)).astype(np.float32)
                          / 16.0)
        got = hist_from_rows_pallas_jit(rows, pay, num_bins=b,
                                        interpret=False)
        want = hist_from_rows(rows, pay, b, method="scatter")
        err = float(jnp.max(jnp.abs(got - want)))
        if got.shape != (f, b, 2) or not err <= 1e-3:
            raise AssertionError(
                f"pallas histogram != scatter: shape {got.shape}, "
                f"max abs err {err}")
        outcomes[name] = {"ok": True, "max_abs_err": err}

    cases = [
        ("pallas_hist[16384x28,u8,B=255]",
         lambda n: hist_case(n, 16384, 28, 255, np.uint8)),
        ("pallas_hist[4096x8,u16,B=2040]",
         lambda n: hist_case(n, 4096, 8, 2040, np.uint16)),
    ]
    # every kernel's outcome is wanted from the one chip call, so a
    # refusal is recorded with the compiler's message and the next
    # kernel still runs; any refusal fails the run below
    for name, run in cases:
        try:
            run(name)
        except Exception as e:      # noqa: BLE001 - recorded, re-raised
            import traceback
            traceback.print_exc()
            outcomes[name] = {"ok": False,
                              "error": f"{type(e).__name__}: {e}"[:4000]}
    result = {"platform": devs[0].platform,
              "device_kind": devs[0].device_kind,
              "device_count": len(devs), **_versions(jax),
              "kernels": outcomes}
    print("chip_smoke kernels: " + json.dumps(result), file=sys.stderr)
    bad = [n for n, o in outcomes.items() if not o["ok"]]
    if bad:
        raise RuntimeError(f"kernel(s) refused or wrong: {bad}")
    with open(os.path.join(args.workdir, "kernels.json"), "w") as fh:
        json.dump(result, fh)


# ---------------------------------------------------------------------
# serve leg (run by the jax-free parent against a daemon child)
# ---------------------------------------------------------------------

def _wait_ready(proc):
    """The daemon's ``serve_ready`` line, or its death (a daemon still
    silent at the deadline is killed, which ends the read)."""
    killer = threading.Timer(SERVE_READY_TIMEOUT_S, proc.kill)
    killer.start()
    try:
        for line in proc.stdout:
            sys.stderr.write("serve| " + line)
            if line.startswith("{") and '"serve_ready"' in line:
                return json.loads(line)
    finally:
        killer.cancel()
    raise RuntimeError("serve daemon never printed serve_ready "
                       f"(exit code {proc.wait()})")


def serve_leg(workdir, child_env, train):
    import numpy as np
    fx = np.load(os.path.join(workdir, "serve_fixtures.npz"))
    telemetry = os.path.join(workdir, "serve.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve",
         os.path.join(workdir, "model.txt"), "--port", "0",
         "--telemetry", telemetry],
        cwd=REPO, env=child_env, stdout=subprocess.PIPE, text=True)
    failures = []
    try:
        ready = _wait_ready(proc)
        with socket.create_connection(("127.0.0.1", ready["port"]),
                                      timeout=120) as sock:
            rfile = sock.makefile("r")

            def ask(obj):
                sock.sendall((json.dumps(obj) + "\n").encode())
                return json.loads(rfile.readline())

            max_err = 0.0
            for size in SERVE_SIZES:
                reply = ask({"rows": fx[f"rows_{size}"].tolist()})
                if "predictions" not in reply:
                    raise RuntimeError(f"serve request of {size} rows "
                                       f"failed: {reply}")
                got = np.asarray(reply["predictions"], np.float64)
                want = fx[f"pred_{size}"]
                err = float(np.max(np.abs(got - want))) \
                    if got.shape == want.shape else float("inf")
                max_err = max(max_err, err)
                if not err <= SERVE_TOL:
                    _fail(failures, f"serve reply for {size} rows "
                                    f"differs from Booster.predict by "
                                    f"{err}")
            stats = ask({"cmd": "stats"})
            ask({"cmd": "shutdown"})
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        _fail(failures, f"serve daemon exited with code {rc}")
    # the daemon arms its watcher after warm-up; ``total`` (unlike
    # ``delta``) is not consumed by the daemon's own stats loop
    if stats["recompiles"]["total"] != 0:
        _fail(failures, f"serve compiled {stats['recompiles']['total']} "
                        "program(s) after warm-up")
    with open(telemetry) as fh:
        events = [json.loads(ln) for ln in fh if ln.strip()]
    kinds = sorted({e.get("device_kind") for e in events
                    if e.get("event") == "compile"}, key=str)
    if kinds != [train["device_kind"]]:
        _fail(failures, f"serve compiled on device_kind {kinds}, the "
                        f"trainer ran on {train['device_kind']!r}")
    if train["platform"] == "tpu" and stats["hbm"]["bytes_in_use"] is None:
        _fail(failures, "serve stats report no HBM bytes_in_use: the "
                        "daemon is not on the TPU")
    faults = [e for e in events if e.get("event") == "fault"]
    if faults:
        _fail(failures, f"serve fault events: {faults}")
    _raise_if(failures)
    return {"replies_checked": len(SERVE_SIZES),
            "request_rows": list(SERVE_SIZES),
            "max_abs_err": max_err, "tolerance": SERVE_TOL,
            "requests_total": stats["requests_total"],
            "recompiles_after_warmup": stats["recompiles"]["total"],
            "buckets": ready["buckets"],
            "device_kind": kinds[0],
            "hbm_bytes_in_use": stats["hbm"]["bytes_in_use"]}


# ---------------------------------------------------------------------
# the jax-free parent
# ---------------------------------------------------------------------

def _run_child(leg, args, workdir, env):
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg,
           "--workdir", workdir, "--rows", str(args.rows),
           "--devices", str(args.devices)]
    if args.cpu_selftest:
        cmd.append("--cpu-selftest")
    subprocess.run(cmd, cwd=REPO, env=env, check=True,
                   timeout=CHILD_TIMEOUT_S)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-selftest", action="store_true",
                    help="tiny CPU run of the same legs (tier-1 test)")
    ap.add_argument("--rows", type=int, default=HIGGS_ROWS,
                    help="training rows; only --cpu-selftest may cut them")
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4: tree_learner=data over four chips, train "
                         "leg only")
    ap.add_argument("--kernels", action="store_true",
                    help="compile the Pallas kernels on the chip")
    ap.add_argument("--leg", choices=("train", "kernels"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rows != HIGGS_ROWS and not args.cpu_selftest:
        ap.error("--rows is the job's width and is only cut by "
                 "--cpu-selftest")
    if args.kernels and args.cpu_selftest:
        ap.error("--kernels compiles for the TPU; there is no CPU form")

    if args.leg:                        # child: the only JAX importers
        {"train": train_leg, "kernels": kernels_leg}[args.leg](args)
        return

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [REPO, env.get("PYTHONPATH")]))
    if args.cpu_selftest:
        env["JAX_PLATFORMS"] = "cpu"
        if args.devices > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={args.devices}").strip()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.kernels:
            _run_child("kernels", args, workdir, env)
            with open(os.path.join(workdir, "kernels.json")) as fh:
                result = json.load(fh)
        else:
            _run_child("train", args, workdir, env)
            with open(os.path.join(workdir, "train.json")) as fh:
                result = json.load(fh)
            if args.devices == 1:
                result["serve"] = serve_leg(workdir, env, result)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(result))
    print(json.dumps({"ok": True,
                      "device": {"platform": result["platform"],
                                 "kind": result["device_kind"],
                                 "count": result["device_count"]}}),
          flush=True)


if __name__ == "__main__":
    main()
