"""REAL multi-process SPMD training: two OS processes, each with two
virtual CPU devices, form one 4-device mesh over the JAX distributed
runtime (the reference's socket/MPI Network::Init + distributed
learners, _test_distributed.py:54 pattern) and must train the
IDENTICAL model a single process trains on the same 4-device mesh.

This is the full multi-host path: coordinator wiring
(parallel/distributed.py), bin-mapper sync + per-process row shards
(parallel/spmd.py), and global-array assembly for the shard_map
learner (models/gbdt.py). The data-parallel learner dispatches jitted
collectives across processes, which the installed jaxlib 0.9.0 runs on
the CPU backend over gloo; the host-transport chaos tests
(test_distributed_resilience.py) cover the rest of the distributed
surface.
"""

import os
import subprocess

import numpy as np
import pytest

import lightgbm_tpu as lgb
from _mp_utils import (TESTS_DIR, drain_all, free_port, spawn_worker,
                       worker_base_env)

pytestmark = pytest.mark.mp


@pytest.mark.timeout(600)
def test_two_process_data_parallel_matches_single_process(tmp_path):
    port = free_port()
    env = worker_base_env()
    procs = [
        spawn_worker([os.path.join(TESTS_DIR, "spmd_worker.py"),
                      str(rank), str(port), str(tmp_path)], env)
        for rank in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            drain_all(procs, "SPMD workers timed out after 540 s "
                             "(stuck collective?)")
        outs.append(out.decode())
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} DONE" in out

    # single-process oracle: same data, same 4-device mesh, and bin
    # boundaries from process 0's shard (what sync_bin_mappers
    # broadcast in the workers)
    rs = np.random.RandomState(0)
    n, f = 2000, 6
    X = rs.randn(n, f)
    y = ((X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2]
          + 0.1 * rs.randn(n)) > 0).astype(float)
    ref = lgb.Dataset(X[: n // 2], label=y[: n // 2],
                      params={"verbosity": -1})
    ref.construct()
    full = lgb.Dataset(X, label=y, reference=ref)
    single = lgb.train({"objective": "binary", "num_leaves": 15,
                        "min_data_in_leaf": 5, "tree_learner": "data",
                        "num_devices": 4, "verbosity": -1}, full,
                       num_boost_round=5)
    mp_model = lgb.Booster(
        model_file=str(tmp_path / "model_mp.txt"))
    ps = single.predict(X[:300])
    pm = mp_model.predict(X[:300])
    np.testing.assert_allclose(ps, pm, rtol=1e-5, atol=1e-7)
