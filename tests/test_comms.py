"""Quantized histogram collectives + payload-adaptive parallelism
(lightgbm_tpu/parallel/comms.py, ISSUE 9; docs/COLLECTIVES.md).

Covers the four invariants the subsystem sells:
- the quantized allreduce is REPLICATED (byte-identical on all ranks)
  and close to the exact f32 reduction;
- error feedback keeps ACCUMULATED error bounded across many
  reductions (many trees' worth), instead of compounding;
- the dtype-aware payload model matches both the known MULTICHIP_r04
  expectations and the lowered StableHLO, and the int8 wire really is
  int8 on the exchange path;
- tree_learner=auto picks data-parallel at the narrow Higgs shape,
  voting at the wide Allstate shape, feature at replicable sizes.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import comms
from lightgbm_tpu.parallel.mesh import make_mesh, shard_rows

from conftest import make_synthetic_binary

needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-device mesh")

F, B = 13, 9  # deliberately unaligned with the 256-element block


def _mesh():
    return make_mesh(8)


def _per_rank(fn, *arrays):
    """Run ``fn`` under shard_map returning every rank's result
    stacked on axis 0 (so tests can assert cross-rank byte-equality,
    which the usual replicated out_spec would hide)."""
    mesh = _mesh()
    axis = mesh.axis_names[0]
    sharded = shard_map(lambda *a: fn(axis, *a), mesh=mesh,
                        in_specs=tuple(P(axis) for _ in arrays),
                        out_specs=P(axis), check_vma=False)
    return np.asarray(jax.jit(sharded)(*arrays))


# ---------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("mode", ["int16", "int8"])
@pytest.mark.parametrize("strategy", ["psum", "exchange"])
def test_quantized_allreduce_rank_identical_and_close(mode, strategy):
    rs = np.random.RandomState(0)
    x = rs.randn(8, F, B, 2).astype(np.float32) * 5.0

    def body(axis, xl):
        return comms.hist_allreduce(xl[0], axis, mode,
                                    strategy=strategy)[None]

    out = _per_rank(body, jnp.asarray(x))
    ref = x.sum(axis=0)
    for r in range(1, 8):
        assert np.array_equal(out[r], out[0]), (
            f"rank {r} diverged from rank 0 — split decisions would "
            "deadlock the mesh")
    tol = 2e-4 if mode == "int16" else 2e-2
    assert np.max(np.abs(out[0] - ref)) / np.max(np.abs(ref)) < tol


@needs_mesh
def test_f32_mode_is_exact_psum():
    rs = np.random.RandomState(1)
    x = rs.randn(8, F, B, 2).astype(np.float32)

    def body(axis, xl):
        return comms.hist_allreduce(xl[0], axis, "f32")[None]

    out = _per_rank(body, jnp.asarray(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-6,
                               atol=1e-5)


@needs_mesh
def test_int_histograms_fall_back_to_exact_psum():
    """Quantized-gradient training reduces exact int32 histograms —
    the comms layer must never quantize them."""
    rs = np.random.RandomState(2)
    x = rs.randint(-1000, 1000, size=(8, F, B, 2)).astype(np.int32)

    def body(axis, xl):
        return comms.hist_allreduce(xl[0], axis, "int8")[None]

    out = _per_rank(body, jnp.asarray(x))
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out[0], x.sum(axis=0))


@needs_mesh
@pytest.mark.parametrize("mode", ["int8", "int16"])
@pytest.mark.parametrize("strategy", ["psum", "exchange"])
def test_error_feedback_bounds_accumulated_error(mode, strategy):
    """EF telescope: across 10 trees' worth of sequential reductions
    (num_leaves-1 = 6 splits/tree -> 60 rounds) the CUMULATIVE
    dequantization error stays bounded by ~one quantization step,
    where the feedback-free chain compounds. Covers BOTH transports —
    the exchange arm executes the phase-2 requantization-error fold
    into the owner's chunk (comms._allreduce_exchange), not just the
    shared-scale psum path CPU training defaults to."""
    rounds = 60
    rs = np.random.RandomState(3)
    hists = rs.randn(8, rounds, F, B, 2).astype(np.float32)

    def run(use_ef):
        def body(axis, h_seq):
            def step(ef, h):
                if use_ef:
                    y, ef = comms.hist_allreduce(h, axis, mode,
                                                 error_feedback=ef,
                                                 strategy=strategy)
                else:
                    y = comms.hist_allreduce(h, axis, mode,
                                             strategy=strategy)
                return ef, y

            _, ys = lax.scan(step, jnp.zeros((F, B, 2), jnp.float32),
                             h_seq[0])
            return ys[None]

        ys = _per_rank(body, jnp.asarray(hists))[0]
        true = hists.sum(axis=0)
        return np.abs(np.cumsum(ys - true, axis=0)).max(axis=(1, 2, 3))

    err_ef = run(True)
    err_no = run(False)
    # bounded: the running total never exceeds a small multiple of one
    # round's quantization error, and beats the feedback-free chain
    assert err_ef.max() < 0.5 * err_no.max(), (err_ef.max(),
                                               err_no.max())
    assert err_ef[-1] < 3.0 * err_ef[: rounds // 6].max(), (
        "accumulated error kept growing across trees", err_ef)


@needs_mesh
def test_exchange_wire_really_is_int8(monkeypatch):
    """On the exchange strategy the largest collective operand is the
    packed int8 payload — ~4x fewer bytes than the f32 psum it
    replaces (scale sideband included in the measurement)."""
    monkeypatch.setenv("LIGHTGBM_TPU_COMM_EXCHANGE", "1")
    mesh = _mesh()
    axis = mesh.axis_names[0]
    # wide enough that the D*BLOCK padding is negligible next to the
    # payload (the ratio at tiny shapes measures padding, not wire)
    x = jnp.zeros((8, 256, 255, 2), jnp.float32)

    def trace(mode):
        def body(xl):
            return comms.hist_allreduce(xl[0], axis, mode)[None]

        return comms.collective_payloads(
            shard_map(body, mesh=mesh, in_specs=P(axis),
                      out_specs=P(axis), check_vma=False), x)

    max_f32 = max(r["bytes"] for r in trace("f32"))
    recs8 = trace("int8")
    max_i8 = max(r["bytes"] for r in recs8)
    assert any(r["itemsize"] == 1 for r in recs8), recs8
    assert max_f32 / max_i8 > 3.8, (max_f32, max_i8)


# ---------------------------------------------------------------------
# payload model + cost model (the dryrun accounting seed)
# ---------------------------------------------------------------------

def test_payload_model_matches_r04_expectations():
    """MULTICHIP_r04's measured ordering at F=64, B=16, top_k=3:
    full-hist 2048 >> voting 384 >> feature 32 elems."""
    assert comms.payload_elems("data", 64, 16) == 2048
    assert comms.payload_elems("voting", 64, 16, top_k=3) == 384
    assert comms.payload_elems("feature", 64, 16) == 32


@needs_mesh
def test_jaxpr_accounting_reproduces_r04_shape():
    """The dtype-aware walk over the lowered data-parallel grower
    reproduces the model: max collective == the full [F, B, 2] f32
    histogram, in elems AND bytes."""
    from lightgbm_tpu.ops.grow import GrowConfig, grow_tree_impl
    from lightgbm_tpu.ops.split import SplitParams

    fw, bw = 64, 16
    mesh = _mesh()
    axis = mesh.axis_names[0]
    cfg = GrowConfig(num_leaves=7, num_bins=bw,
                     split=SplitParams(min_data_in_leaf=1.0),
                     hist_method="scatter", axis_name=axis)
    n = 64 * 8

    def fn(bins_T, grad, hess, w, fm, fnb, fnan):
        return grow_tree_impl(cfg, bins_T, grad, hess, w, fm, fnb,
                              fnan)

    sh = shard_map(fn, mesh=mesh,
                   in_specs=(P(None, axis), P(axis), P(axis), P(axis),
                             P(), P(), P()),
                   out_specs=(P(), P(axis)), check_vma=False)
    recs = comms.collective_payloads(
        sh, jnp.zeros((fw, n), jnp.uint8), jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.ones((fw,), jnp.bool_), jnp.full((fw,), bw, jnp.int32),
        jnp.full((fw,), -1, jnp.int32))
    assert max(r["elems"] for r in recs) == \
        comms.payload_elems("data", fw, bw) == 2048
    assert max(r["bytes"] for r in recs) == \
        comms.payload_bytes("data", fw, bw, "f32") == 8192


def test_wire_bytes_reduction_at_allstate_shape():
    elems = comms.payload_elems("data", 4228, 255)
    f32b = elems * comms.WIRE_ITEMSIZE["f32"]
    i8b = elems * comms.WIRE_ITEMSIZE["int8"]
    assert f32b / i8b >= 4.0
    assert f32b > 8 * 2 ** 20  # the 8.6 MB per-level reduction


def test_choose_parallel_mode_decision_table():
    # the ISSUE 9 acceptance shapes
    assert comms.choose_parallel_mode(28, 255, 10_500_000, 8) == "data"
    assert comms.choose_parallel_mode(4228, 255, 13_200_000, 8) == \
        "voting"
    # small data replicates -> feature
    assert comms.choose_parallel_mode(4228, 255, 4000, 8) == "feature"
    # voting can't elect fewer features than exist
    assert comms.choose_parallel_mode(30, 255, 10_500_000, 8,
                                      top_k=20) == "data"
    # one device: nothing to shard
    assert comms.choose_parallel_mode(4228, 255, 13_200_000, 1) == \
        "data"
    # a cheaper wire can keep a mid-width shape on exact data-parallel
    assert comms.choose_parallel_mode(900, 255, 10 ** 7, 8,
                                      "f32") == "voting"
    assert comms.choose_parallel_mode(900, 255, 10 ** 7, 8,
                                      "int8") == "data"


def test_resolve_hist_comm_auto():
    assert comms.resolve_hist_comm("auto", 28, 255) == "f32"
    assert comms.resolve_hist_comm("auto", 4228, 255) == "int16"
    assert comms.resolve_hist_comm("int8", 28, 255) == "int8"
    # auto resolves against the ACTIVE mode's payload: voting moves
    # the small elected buffer, so it stays exact f32 at a width
    # where data-parallel would quantize
    assert comms.resolve_hist_comm("auto", 4228, 255,
                                   parallel_mode="voting") == "f32"
    assert comms.resolve_hist_comm("auto", 4228, 255,
                                   parallel_mode="feature") == "f32"


# ---------------------------------------------------------------------
# training end-to-end on the 8-device world
# ---------------------------------------------------------------------

def _train(X, y, rounds=5, callbacks=None, **extra):
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds,
                     callbacks=callbacks or [])


@needs_mesh
def test_int16_training_matches_f32_within_eval_tolerance():
    X, y = make_synthetic_binary(n=4000, f=11, seed=7)
    p_f32 = _train(X, y, tree_learner="data").predict(X[:500])
    b = _train(X, y, tree_learner="data", hist_comm="int16")
    assert b._engine.grow_cfg.hist_comm == "int16"
    p_i16 = b.predict(X[:500])
    assert np.max(np.abs(p_i16 - p_f32)) < 1e-3


@needs_mesh
def test_int8_training_runs_and_is_deterministic():
    X, y = make_synthetic_binary(n=4000, f=11, seed=9)
    b1 = _train(X, y, rounds=3, tree_learner="data", hist_comm="int8")
    b2 = _train(X, y, rounds=3, tree_learner="data", hist_comm="int8")
    assert b1.model_to_string() == b2.model_to_string()
    # still learns: better than the 0.5 coin flip
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, b1.predict(X)) > 0.8


@needs_mesh
@pytest.mark.parametrize("grower", ["compact", "masked", "level"])
def test_grower_output_rank_identical_under_int8(grower):
    """The acceptance invariant: every rank's TREE is byte-equal under
    quantized comms (the grower's out_spec normally hides this —
    return each rank's copy explicitly). All three growers thread
    their own EF carry (rolling [F,B,2] for compact/masked, per-leaf
    [L,F,B,2] slots for level) — each must stay replicated."""
    from lightgbm_tpu.ops.grow import GrowConfig, grow_tree_impl
    from lightgbm_tpu.ops.split import SplitParams

    n, f, mb = 64 * 8, 6, 15
    rs = np.random.RandomState(11)
    bins = rs.randint(0, mb, size=(f, n)).astype(np.uint8)
    yv = (bins.astype(np.float32).T @ rs.randn(f).astype(np.float32)
          > 0).astype(np.float32)
    mesh = _mesh()
    axis = mesh.axis_names[0]
    cfg = GrowConfig(num_leaves=7, num_bins=mb,
                     split=SplitParams(min_data_in_leaf=1.0,
                                       min_sum_hessian_in_leaf=1e-6),
                     hist_method="scatter", axis_name=axis,
                     hist_comm="int8", grower=grower)

    def fn(bins_T, grad, hess, w, fm, fnb, fnan):
        tree, _ = grow_tree_impl(cfg, bins_T, grad, hess, w, fm, fnb,
                                 fnan)
        return (tree.num_leaves[None], tree.leaf_value[None],
                tree.split_feature[None], tree.threshold_bin[None])

    sh = jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P(), P(),
                  P()),
        out_specs=(P(axis),) * 4, check_vma=False))
    nl, lv, sf, tb = sh(
        jnp.asarray(bins), jnp.asarray(0.5 - yv),
        jnp.full((n,), 0.25, jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.ones((f,), jnp.bool_), jnp.full((f,), mb, jnp.int32),
        jnp.full((f,), -1, jnp.int32))
    for arr in (np.asarray(nl), np.asarray(lv), np.asarray(sf),
                np.asarray(tb)):
        for r in range(1, 8):
            assert np.array_equal(arr[r], arr[0]), "rank divergence"
    assert int(np.asarray(nl)[0]) == 7


@needs_mesh
def test_auto_tree_learner_engine_wiring():
    """tree_learner=auto at a replicable size resolves to the cost
    model's choice and the engine records it."""
    X, y = make_synthetic_binary(n=2000, f=9, seed=5)
    b = _train(X, y, rounds=2, tree_learner="auto")
    eng = b._engine
    assert eng.mesh is not None
    expected = comms.choose_parallel_mode(
        int(eng.bins_T.shape[0]), eng.grow_cfg.num_bins, eng.n,
        int(eng.mesh.devices.size), "f32", eng.grow_cfg.voting_top_k)
    assert eng.grow_cfg.parallel_mode == expected == "feature"


@needs_mesh
@pytest.mark.parametrize("grower", ["level", "masked"])
def test_auto_tree_learner_demotes_to_data_for_noncompact_grower(grower):
    """auto must never hand the level/masked growers a mode they don't
    implement: at this replicable size the cost model says feature,
    but level raises on anything but data-parallel and masked would
    psum D identical replicated histograms (D-times-inflated counts).
    Both demote to data and still train."""
    X, y = make_synthetic_binary(n=2000, f=9, seed=5)
    b = _train(X, y, rounds=2, tree_learner="auto", grower=grower)
    eng = b._engine
    assert eng.mesh is not None
    assert eng.grow_cfg.parallel_mode == "data"
    assert np.isfinite(b.predict(X[:100])).all()


@needs_mesh
def test_telemetry_comm_fields(tmp_path):
    import lightgbm_tpu.callback as cbm
    from lightgbm_tpu.obs.recorder import summarize_events

    path = str(tmp_path / "comm.jsonl")
    X, y = make_synthetic_binary(n=2000, f=9, seed=6)
    _train(X, y, rounds=2, tree_learner="data", hist_comm="int16",
           callbacks=[cbm.telemetry(path)])
    events = [json.loads(ln) for ln in open(path).read().splitlines()]
    iters = [e for e in events if e.get("event") == "iteration"]
    assert len(iters) == 2
    for ev in iters:
        comm = ev["comm"]
        assert comm["hist_comm"] == "int16"
        assert comm["parallel_mode"] == "data"
        assert comm["world"] == 8
        assert comm["payload_bytes"] > 0
    summary = summarize_events(path)
    assert summary["comm_bytes"] == sum(
        e["comm"]["payload_bytes"] for e in iters)


def test_serial_training_has_null_comm(tmp_path):
    import lightgbm_tpu.callback as cbm

    path = str(tmp_path / "serial.jsonl")
    X, y = make_synthetic_binary(n=600, f=5, seed=8)
    _train(X, y, rounds=1, callbacks=[cbm.telemetry(path)])
    events = [json.loads(ln) for ln in open(path).read().splitlines()
              if ln]
    # compile events (obs/cost.py) legally precede the iteration line
    ev = next(e for e in events if e["event"] == "iteration")
    assert "comm" in ev and ev["comm"] is None
