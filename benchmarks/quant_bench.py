"""Quantized-training benches, two arms:

1. (default) Quantized-GRADIENT training (use_quantized_grad: int8
   stochastic rounding, exact int32 MXU histograms — the reference's
   gradient_discretizer.hpp feature) at bench scale on the real chip,
   fused path. Secondary metric: the primary bench stays the
   reference's own (non-quantized) Higgs config. Run:
       python benchmarks/quant_bench.py

2. (--comms) Quantized histogram ALLREDUCE (parallel/comms.py,
   hist_comm): time f32 vs int16 vs int8 reductions of the
   Allstate-wide [F=4228, B=255, 2] histogram on 8 devices and print
   a flip/keep verdict in the fused_iter_bench.py format — the gate
   for letting hist_comm="auto" resolve to int8 instead of int16.
   On the chip the int modes run the real int-wire exchange
   (all_to_all + all_gather); on CPU hosts the shared-scale psum
   transport is timed instead (and the wire saving is a model — see
   docs/COLLECTIVES.md). Run:
       python benchmarks/quant_bench.py --comms
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time

import numpy as np


def main_comms() -> None:
    # a CPU host still measures an 8-rank world (virtual devices; the
    # flag only affects the host platform, so a TPU backend ignores it)
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel import comms
    from jax import shard_map
    from lightgbm_tpu.parallel.mesh import make_mesh

    F, B, reps = 4228, 255, 8
    ndev = min(8, len(jax.devices()))
    mesh = make_mesh(ndev)
    axis = mesh.axis_names[0]
    rs = np.random.RandomState(0)
    # per-device histogram shards (one [F, B, 2] local hist each)
    hists = jnp.asarray(rs.randn(ndev, F, B, 2).astype(np.float32))
    print(f"comms arm: [F={F}, B={B}, 2] histogram allreduce, "
          f"world={ndev}, backend={jax.default_backend()}, "
          f"{reps} chained reductions/measure", flush=True)

    times = {}
    for mode in ("f32", "int16", "int8"):
        def step(h):
            h = h[0]
            ef = jnp.zeros_like(h)
            out = jnp.zeros_like(h)
            # chain reps reductions so dispatch overhead amortizes and
            # the EF carry is exercised like the grower's loop
            for _ in range(reps):
                y, ef = comms.hist_allreduce(h + out * 1e-9, axis,
                                             mode, ef)
                out = y
            return out[None]

        fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis), check_vma=False))
        fn(hists).block_until_ready()          # compile
        t0 = time.perf_counter()
        n_meas = 3
        for _ in range(n_meas):
            fn(hists).block_until_ready()
        dt = (time.perf_counter() - t0) / (n_meas * reps)
        times[mode] = dt
        bytes_model = comms.payload_bytes("data", F, B, mode)
        print(f"hist_comm={mode:5s}: {dt * 1e3:8.2f} ms/allreduce "
              f"(modeled wire {bytes_model / 2 ** 20:.2f} MiB)",
              flush=True)

    # the pending decision this arm gates (resolve_hist_comm): does
    # auto resolve to int8 instead of int16 past the quantize
    # threshold? int8 must beat BOTH int16 and f32 to flip; otherwise
    # the verdict names which of the current rules stands.
    if times["int8"] < times["int16"] and times["int8"] < times["f32"]:
        verdict = "FLIP hist_comm auto to int8"
    elif times["int16"] < times["f32"]:
        verdict = "keep auto->int16 rule (int8 not winning)"
    else:
        verdict = "keep f32 (quantized wire not winning on this backend)"
    print(f"int8 vs int16: {times['int16'] / times['int8']:.3f}x, "
          f"int8 vs f32 allreduce: {times['f32'] / times['int8']:.3f}x "
          f"— {verdict} "
          "(record the verdict in docs/COLLECTIVES.md + PERF.md)",
          flush=True)


def main_quant() -> None:
    import lightgbm_tpu as lgb

    N, F = 10_500_000, 28
    rs = np.random.RandomState(0)
    X = rs.randn(N, F).astype(np.float32)
    coef = rs.randn(F).astype(np.float32)
    y = ((X @ coef) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 255})
    ds.construct()
    del X

    for quant in (False, True):
        bst = lgb.Booster(params={"objective": "binary",
                                  "num_leaves": 255,
                                  "max_bin": 255, "learning_rate": 0.1,
                                  "verbosity": -1,
                                  "use_quantized_grad": quant},
                          train_set=ds)
        eng = bst._engine
        t0 = time.perf_counter()
        eng.train_one_iter()
        eng.score.block_until_ready()
        wu = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(5):
            eng.train_one_iter()
        eng.score.block_until_ready()
        dt = (time.perf_counter() - t0) / 5
        print(f"quantized={quant}: {dt * 1e3:.1f} ms/iter "
              f"({1 / dt:.3f} it/s, vs_baseline "
              f"{1 / dt / (500 / 130.094):.3f}, warmup {wu:.0f}s)",
              flush=True)


if __name__ == "__main__":
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    if "--comms" in sys.argv:
        main_comms()
    else:
        main_quant()
