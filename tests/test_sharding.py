"""Device-resident sharded training (ISSUE 10; docs/SHARDING.md):
``shard_residency=device`` NamedSharding dataset placement
(parallel/placement.py) + ``split_search=sharded`` reduce-scatter
split search (parallel/comms.py, ops/grow.py).

The invariants under test:

- the reduce-scatter chunk is BIT-IDENTICAL to the matching slice of
  the full allreduce at f32 wire — which is what makes sharded-search
  training byte-identical to the gathered baseline (proved for all
  three data-parallel growers);
- device residency frees the host binned matrix after the mesh upload
  (and says so clearly when a host consumer asks later), without
  changing a single tree byte;
- checkpoint save/restore crosses residency modes byte-identically,
  and a device-resident snapshot carries per-shard fingerprints;
- the post-reduction payload model shows the ~D cut the subsystem
  sells (the measured twin lives in __graft_entry__.dryrun_multichip);
- unequal per-rank shards fail with an error naming ranks and counts,
  not an opaque allgather shape error (2-proc kv world);
- host peak RSS under device residency sits ~one binned matrix below
  the gathered path (VmHWM-gated like test_two_round.py).
"""

import json
import os
import re
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import LightGBMError
from lightgbm_tpu.parallel import comms, placement
from lightgbm_tpu.parallel.mesh import make_mesh

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-device mesh")

GROWERS = ("compact", "masked", "level")


def _data(n=500, f=11, seed=3):
    """f=11 over 4 devices: uneven Fl=3 chunks with scatter padding."""
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    y = ((X[:, 0] + 0.5 * X[:, 1] ** 2 - 0.3 * X[:, 2]
          + 0.1 * rs.randn(n)) > 0.2).astype(np.float64)
    return X, y


def _params(extra=None):
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "tree_learner": "data", "num_devices": 4, "seed": 7,
         "deterministic": True, "verbosity": -1}
    if extra:
        p.update(extra)
    return p


def _train(X, y, extra=None, rounds=5, **kw):
    p = _params(extra)
    ds = lgb.Dataset(X, label=y, params=p)
    return lgb.train(p, ds, num_boost_round=rounds, **kw), ds


def _strip_params(model_str):
    """Model text minus the recorded-params block (shard_residency /
    split_search legitimately differ between the runs under
    comparison; the TREES must not)."""
    return re.sub(r"parameters:.*?end of parameters", "", model_str,
                  flags=re.S)


# ---------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------

def test_config_validation():
    from lightgbm_tpu.config import Config
    assert Config.from_params({}).shard_residency == "auto"
    assert Config.from_params({}).split_search == "gathered"
    with pytest.raises(ValueError, match="shard_residency"):
        Config.from_params({"shard_residency": "hbm"})
    with pytest.raises(ValueError, match="split_search"):
        Config.from_params({"split_search": "scattered"})


# ---------------------------------------------------------------------
# the reduce-scatter primitive
# ---------------------------------------------------------------------

@needs_mesh
def test_f32_reduce_scatter_chunk_is_psum_slice_bitwise():
    """The foundation of the byte-identity claim: each device's
    psum_scatter chunk must equal the matching slice of the full psum
    BIT-FOR-BIT, so a sharded search scores exactly the numbers the
    gathered search scores."""
    mesh = make_mesh(8)
    axis = mesh.axis_names[0]
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16, 9, 2).astype(np.float32) * 3.0

    def body(xl):
        return comms.hist_reduce_scatter(xl[0], axis, "f32")[None]

    chunks = np.asarray(jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False))(jnp.asarray(x)))
    ref = x.sum(axis=0)                       # [16, 9, 2]
    got = chunks.reshape(16, 9, 2)            # 8 ranks x 2-row chunks
    assert np.array_equal(got, ref)


@needs_mesh
@pytest.mark.parametrize("mode", ["int16", "int8"])
def test_int_reduce_scatter_close_and_ef_resumes(mode):
    """The quantized wire loses bits by design; the chunk must stay
    close to the exact reduction and the error-feedback residual must
    shrink a follow-up reduction's error (telescoping like the
    allreduce's)."""
    mesh = make_mesh(8)
    axis = mesh.axis_names[0]
    rs = np.random.RandomState(1)
    x = rs.randn(8, 16, 9, 2).astype(np.float32) * 5.0

    def body(xl):
        ef0 = jnp.zeros_like(xl[0])
        c1, ef1 = comms.hist_reduce_scatter(xl[0], axis, mode, ef0)
        c2, _ = comms.hist_reduce_scatter(xl[0], axis, mode, ef1)
        return c1[None], c2[None]

    c1, c2 = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(axis),
        out_specs=(P(axis), P(axis)), check_vma=False))(jnp.asarray(x))
    ref = x.sum(axis=0)
    got1 = np.asarray(c1).reshape(16, 9, 2)
    got2 = np.asarray(c2).reshape(16, 9, 2)
    scale = np.abs(ref).max()
    tol = scale * (0.02 if mode == "int8" else 0.002)
    assert np.abs(got1 - ref).max() < tol
    # second round re-sends the first round's residual: its error must
    # not exceed the cold one (error feedback, not error compounding)
    assert np.abs(got2 - ref).max() <= np.abs(got1 - ref).max() + tol


# ---------------------------------------------------------------------
# payload model (the modeled twin of dryrun_multichip's measured arm)
# ---------------------------------------------------------------------

def test_post_reduction_payload_model_shows_the_d_cut():
    F, B, D = 4228, 255, 8
    full = comms.post_reduction_bytes("data", F, B, D, "gathered")
    shard = comms.post_reduction_bytes("data", F, B, D, "sharded")
    assert full == F * B * 2 * 4              # the full [F, B, 2] hist
    chunk = -(-F // D) * B * 2 * 4
    assert shard == chunk + D * comms.splitinfo_elems(B) * 4
    assert full >= 7.5 * shard                # ~D cut at the wide shape
    # gathered == the existing payload model (no behavior change)
    assert comms.post_reduction_elems("data", F, B, D, "gathered") \
        == comms.payload_elems("data", F, B)
    # non-data modes are untouched by the knob
    for m in ("feature", "voting"):
        assert comms.post_reduction_bytes(m, F, B, D, "sharded") \
            == comms.payload_bytes(m, F, B)
    # int wire shrinks the chunk but never the f32 SplitInfo records
    shard8 = comms.post_reduction_bytes("data", F, B, D, "sharded",
                                        "int8")
    assert D * comms.splitinfo_elems(B) * 4 < shard8 < shard


# ---------------------------------------------------------------------
# sharded split search: byte-identical training (all 3 growers)
# ---------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("grower", GROWERS)
def test_sharded_search_byte_identical(grower):
    X, y = _data()
    base, _ = _train(X, y, {"grower": grower})
    shard, _ = _train(X, y, {"grower": grower,
                             "split_search": "sharded"})
    assert _strip_params(shard.model_to_string()) \
        == _strip_params(base.model_to_string())


@needs_mesh
def test_device_residency_byte_identical_and_frees_host():
    X, y = _data()
    base, _ = _train(X, y)
    dev, ds = _train(X, y, {"shard_residency": "device",
                            "split_search": "sharded"})
    assert _strip_params(dev.model_to_string()) \
        == _strip_params(base.model_to_string())
    # the host binned matrix is gone, and says so clearly
    assert ds._bins is None
    with pytest.raises(LightGBMError, match="freed after device"):
        ds.host_bins()
    from lightgbm_tpu.obs.registry import registry
    assert registry.gauge("host_binned_bytes").value == 0.0
    # prediction re-bins fresh input through the mappers — no host
    # binned matrix required
    p = dev.predict(X[:50])
    q = base.predict(X[:50])
    np.testing.assert_array_equal(p, q)
    # the training matrix is actually sharded over the mesh
    bins_T = dev._engine.bins_T
    assert len(bins_T.sharding.device_set) == 4


@needs_mesh
def test_sharded_efb_falls_back_to_gathered():
    """EFB-bundled matrices keep the gathered search (with a warning),
    and the model matches the bundled gathered baseline exactly."""
    rs = np.random.RandomState(5)
    n, groups, per = 600, 4, 6                # one-hot blocks bundle
    cols, signal = [], np.zeros(n)
    for g in range(groups):
        pick = rs.randint(0, per, n)
        block = np.zeros((n, per))
        vals = rs.rand(per) * 2
        block[np.arange(n), pick] = vals[pick]
        cols.append(block)
        signal += vals[pick]
    X = np.hstack(cols + [rs.randn(n, 2)])
    y = (signal + 0.5 * X[:, -1] > np.median(signal)).astype(float)
    extra = {"enable_bundle": True, "num_leaves": 7}
    base, _ = _train(X, y, extra, rounds=3)
    shard, _ = _train(X, y, dict(extra, split_search="sharded"),
                      rounds=3)
    assert base._engine.bundle is not None    # EFB really engaged
    assert _strip_params(shard.model_to_string()) \
        == _strip_params(base.model_to_string())
    assert shard._engine.grow_cfg.split_search == "gathered"


# ---------------------------------------------------------------------
# checkpoint: resume across residency modes, per-shard fingerprints
# ---------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("write_res,resume_res",
                         [("device", "host"), ("host", "device")])
def test_checkpoint_resume_across_residency(write_res, resume_res,
                                            tmp_path):
    X, y = _data(n=400)
    full, _ = _train(X, y, rounds=8)
    _train(X, y, {"shard_residency": write_res,
                  "split_search": "sharded"}, rounds=4,
           callbacks=[lgb.checkpoint(str(tmp_path), every_n_iters=4)])
    resumed, _ = _train(X, y, {"shard_residency": resume_res},
                        rounds=8, resume_from=str(tmp_path))
    assert _strip_params(resumed.model_to_string()) \
        == _strip_params(full.model_to_string())


@needs_mesh
def test_device_snapshot_carries_shard_fingerprints(tmp_path):
    from lightgbm_tpu.resilience.checkpoint import write_snapshot
    X, y = _data(n=400)
    dev, _ = _train(X, y, {"shard_residency": "device"}, rounds=2)
    path = write_snapshot(str(tmp_path), dev)
    with np.load(path) as z:
        state = json.loads(bytes(z["state_json"]).decode())
        score = z["score"]
    fps = state["score_shard_fingerprints"]
    assert fps is not None and len(fps) == 4   # one per device shard
    assert len({f["sha256"] for f in fps}) >= 1
    # the snapshot stores the ASSEMBLED host matrix (resume works
    # across residency modes), matching fetch_global exactly
    np.testing.assert_array_equal(
        score, np.asarray(placement.fetch_global(dev._engine.score),
                          np.float32))


# ---------------------------------------------------------------------
# placement unit surface
# ---------------------------------------------------------------------

@needs_mesh
def test_place_rows_roundtrip_and_padding():
    mesh = make_mesh(8)
    rs = np.random.RandomState(2)
    host = rs.randint(0, 255, size=(5, 20), dtype=np.uint8)  # rows ax 1
    placed = placement.place_rows(mesh, host, row_axis=1, pad=4)
    assert placed.shape == (5, 24)
    back = np.asarray(placement.fetch_global(placed))
    np.testing.assert_array_equal(back[:, :20], host)
    assert not back[:, 20:].any()             # zero row padding
    fps = placement.shard_fingerprints(placed)
    assert len(fps) == 8
    # fingerprints are an identity: re-placing the same rows agrees
    fps2 = placement.shard_fingerprints(
        placement.place_rows(mesh, host, row_axis=1, pad=4))
    assert fps == fps2


def test_place_rows_requires_divisible_rows():
    mesh = make_mesh(4)
    with pytest.raises(ValueError, match="divisible"):
        placement.ShardPlan(mesh, 10)


def test_place_refuses_rows_outside_this_ranks_slices():
    """Multi-controller misalignment: a held row outside this rank's
    own device windows would be silently zero-filled by another rank's
    pad — place() must refuse BEFORE any upload (fake pod topology:
    this process owns the HIGH shards but holds rows [5, 10) of 12,
    and 10 is not on a rows_per_shard=3 boundary)."""
    class _Dev:
        def __init__(self, p):
            self.process_index = p

    class _Mesh:
        devices = np.array([_Dev(1), _Dev(1), _Dev(0), _Dev(0)])
        axis_names = ("data",)

    plan = placement.ShardPlan(_Mesh, 12)     # windows of 3 rows each
    with pytest.raises(ValueError, match="whole number of device"):
        plan.place(np.zeros((5, 4), np.uint8), row_axis=0,
                   local_offset=5, exclusive_rows=True)


def test_fetch_global_ships_shards_not_full_buffers(monkeypatch):
    """The multi-controller checkpoint gather must ship only this
    rank's shard data + index bounds through the host transport, never
    full-array-shaped buffers — and still reassemble exactly."""
    from lightgbm_tpu.parallel import hostsync

    full = np.arange(32, dtype=np.float32).reshape(4, 8)

    class _Shard:
        def __init__(self, index, data):
            self.index, self.data = index, data

    class _Arr:
        is_fully_addressable = False
        shape, dtype = full.shape, full.dtype
        addressable_shards = [_Shard((slice(0, 2), slice(0, 8)),
                                     full[0:2])]

    theirs_data = full[2:4][None]                       # [S=1, 2, 8]
    theirs_idx = np.asarray([[[2, 4], [0, 8]]], np.int64)
    sent = []

    def fake_allgather(a, tag):
        sent.append((tag, a.nbytes))
        other = theirs_idx if tag.endswith("_idx") else theirs_data
        return np.stack([a, other.reshape(a.shape)])

    monkeypatch.setattr(hostsync, "host_allgather", fake_allgather)
    out = placement.fetch_global(_Arr())
    np.testing.assert_array_equal(out, full)
    data_bytes = max(b for t, b in sent if not t.endswith("_idx"))
    assert data_bytes == full[0:2].nbytes      # half, not P x full

    # a missing cover must raise, not zero-fill
    def hole_allgather(a, tag):
        return a[None]                         # only this rank's half
    monkeypatch.setattr(hostsync, "host_allgather", hole_allgather)
    with pytest.raises(RuntimeError, match="tile"):
        placement.fetch_global(_Arr())


# ---------------------------------------------------------------------
# 2-process kv worlds (the multi-controller surface)
# ---------------------------------------------------------------------

def _spawn_world(tmp_path, mode):
    from _mp_utils import drain_all, free_port, spawn_worker, \
        worker_base_env
    port = free_port()
    worker = os.path.join(TESTS_DIR, "sharding_worker.py")
    procs = [
        spawn_worker([worker, str(tmp_path), mode], worker_base_env({
            "LIGHTGBM_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "LIGHTGBM_TPU_NUM_PROCS": "2",
            "LIGHTGBM_TPU_RANK": str(rank),
            "LIGHTGBM_TPU_COLLECTIVE_TIMEOUT": "60",
        }))
        for rank in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=360)
        except subprocess.TimeoutExpired:
            drain_all(procs, f"sharding {mode} workers timed out")
        outs.append(out.decode(errors="replace"))
    return procs, outs


@pytest.mark.mp
@pytest.mark.slow
@pytest.mark.timeout(420)
def test_two_process_kv_device_sharded_byte_identical(tmp_path):
    """The acceptance world: 2 CPU processes over the kv transport,
    device residency + sharded search, all three growers —
    byte-identical trees to the gathered baseline."""
    procs, outs = _spawn_world(tmp_path, "equiv")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} DONE" in out
    with open(tmp_path / "models.json") as fh:
        models = json.load(fh)
    for grower in GROWERS:
        assert _strip_params(models[f"{grower}/sharded"]) \
            == _strip_params(models[f"{grower}/gathered"]), grower


@pytest.mark.mp
@pytest.mark.timeout(300)
def test_two_process_unequal_rows_named_error(tmp_path):
    """Unequal per-rank shard row counts must raise a LightGBMError
    naming the ranks and row counts BEFORE the bulk allgather (the old
    failure mode was an opaque shape error, spmd.py)."""
    procs, outs = _spawn_world(tmp_path, "unequal_rows")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} UNEQUAL_ROWS_OK" in out


@pytest.mark.mp
@pytest.mark.timeout(300)
def test_two_process_unequal_metadata_named_error(tmp_path):
    """A rank carrying `weight` while another does not must be named
    before the metadata allgathers deadlock/misalign."""
    procs, outs = _spawn_world(tmp_path, "unequal_meta")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} UNEQUAL_META_OK" in out


# ---------------------------------------------------------------------
# host peak RSS (VmHWM-gated like test_two_round.py — gVisor /proc
# has no VmHWM line)
# ---------------------------------------------------------------------

def _proc_has_vmhwm() -> bool:
    try:
        with open("/proc/self/status") as fh:
            return any(line.startswith("VmHWM:") for line in fh)
    except OSError:
        return False


def _run_mem_worker(mode):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/root"),
           "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    out = subprocess.run(
        [sys.executable, os.path.join(TESTS_DIR,
                                      "sharding_mem_worker.py"), mode],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.timeout(1800)
@pytest.mark.skipif(sys.platform != "linux" or not _proc_has_vmhwm(),
                    reason="peak measurement needs VmHWM in "
                           "/proc/self/status")
def test_device_residency_host_peak_below_gathered():
    """Construct+train lifetime peak RSS under shard_residency=device
    must sit below the gathered path's by a meaningful fraction of the
    binned matrix (the host copy both paths build, which only the
    device path frees before the training buffers grow on top)."""
    dev = _run_mem_worker("device")
    host = _run_mem_worker("host")
    assert dev["host_binned_bytes"] == 0, dev
    assert host["host_binned_bytes"] > 0, host
    saved_mb = (host["vmhwm_kb"] - dev["vmhwm_kb"]) / 1024
    assert saved_mb > 0.4 * host["bins_mb"], (host, dev)


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_mem_worker_reports_zero_resident_bytes_under_device():
    """VmHWM-free fallback of the residency claim, runnable in this
    container: after construct+train the device-residency worker holds
    ZERO host binned bytes while the host one holds the full matrix."""
    dev = _run_mem_worker("device")
    assert dev["host_binned_bytes"] == 0, dev
    host = _run_mem_worker("host")
    assert host["host_binned_bytes"] >= host["bins_mb"] * 2 ** 20 * 0.99, \
        host
