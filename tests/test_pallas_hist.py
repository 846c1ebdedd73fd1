"""Interpret-mode parity suite for the Pallas histogram kernel.

``hist_method="pallas"`` (ops/pallas_hist.py) runs the SAME kernel on
CPU under ``pallas_call(..., interpret=True)`` that a TPU runs
natively; these tests prove it numerically equal to the mxu and
scatter paths — bit-exact for int8-quantized payloads, within the mxu
path's documented float tolerance otherwise — across bin widths
(u8/u16), payload dtypes, and padded/non-multiple shapes, plus the
selection / fallback logic (``auto``, the kill switch, the OOM
degradation ladder rung) and whole-tree growth parity.
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import resolve_hist_method
from lightgbm_tpu.ops.histogram import (build_histogram, hist_from_rows,
                                        hist_from_rows_int)
from lightgbm_tpu.ops.pallas_hist import (INT_BLOCK, hist_from_rows_pallas,
                                          pallas_available)

# the float bar: the mxu path's own multi-pass tolerance class
# (tests/test_grower_equivalence.py::test_hist_mxu_matches_scatter) —
# both pallas and mxu accumulate in f32 on CPU, differing from the
# scatter path only in summation order
FLOAT_TOL = dict(atol=2e-3, rtol=1e-4)


def _ref_hist(rows, pay, B):
    F = rows.shape[1]
    out = np.zeros((F, B, pay.shape[1]), np.float64)
    for f in range(F):
        np.add.at(out[f], rows[:, f], pay.astype(np.float64))
    return out


def test_pallas_importable_here():
    """Tier-1 runs the kernel under the interpreter: the environment
    must expose pallas (if this ever fails, the parity suite below is
    silently vacuous — fail loudly instead)."""
    assert pallas_available()


@pytest.mark.parametrize("S,F,B", [
    (5000, 11, 67),      # nothing aligned: F % FPACK != 0, B % 128 != 0
    (512, 8, 128),       # everything exactly tile-aligned
    (130, 1, 2),         # single feature, tiny row count, 2 bins
    (4097, 9, 255),      # one row past a tile, odd feature count
])
def test_float_parity_u8(S, F, B):
    rs = np.random.RandomState(3)
    rows = rs.randint(0, B, (S, F)).astype(np.uint8)
    pay = np.stack([rs.randn(S), rs.rand(S)], axis=1).astype(np.float32)
    got = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="pallas"))
    ref = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="scatter"))
    assert got.shape == (F, B, 2)
    np.testing.assert_allclose(got, ref, **FLOAT_TOL)
    mxu = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="mxu"))
    np.testing.assert_allclose(got, mxu, **FLOAT_TOL)
    np.testing.assert_allclose(got, _ref_hist(rows, pay, B), **FLOAT_TOL)


def test_float_parity_u16_wide_bins():
    """u16 bin columns with B > 256 (the bundled/EFB bin-position
    regime)."""
    rs = np.random.RandomState(4)
    S, F, B = 3000, 5, 300
    rows = rs.randint(0, B, (S, F)).astype(np.uint16)
    pay = np.stack([rs.randn(S), rs.rand(S)], axis=1).astype(np.float32)
    got = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="pallas"))
    ref = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="scatter"))
    np.testing.assert_allclose(got, ref, **FLOAT_TOL)


def test_wide_bins_shrink_row_tile():
    """B in the thousands (bundled EFB bin positions): the row tile
    shrinks so one feature's VMEM one-hot block stays bounded; results
    must be unchanged."""
    from lightgbm_tpu.ops.pallas_hist import _row_tile
    assert _row_tile(256) == 1024 and _row_tile(2048) == 512
    # the budget holds at every padded width down to the 128-lane
    # floor of the row tile (reached at bp = 8192)
    for bp in (128, 256, 1024, 4096, 8192):
        rt = _row_tile(bp)
        assert rt * bp * 4 <= 4 * 2 ** 20, (bp, rt)
        assert rt >= 128 and rt & (rt - 1) == 0
    assert _row_tile(131072) == 128
    rs = np.random.RandomState(13)
    S, F, B = 900, 3, 1500
    rows = rs.randint(0, B, (S, F)).astype(np.uint16)
    pay = np.stack([rs.randn(S), rs.rand(S)], axis=1).astype(np.float32)
    got = np.asarray(hist_from_rows(jnp.asarray(rows), jnp.asarray(pay),
                                    B, method="pallas"))
    np.testing.assert_allclose(got, _ref_hist(rows, pay, B), **FLOAT_TOL)


def test_int8_payload_bit_exact():
    """Quantized path: int8 (g, h) payloads must accumulate to the
    EXACT int32 histogram (subtraction-safety depends on it)."""
    rs = np.random.RandomState(5)
    S, F, B = 7001, 6, 255
    rows = rs.randint(0, B, (S, F)).astype(np.uint8)
    pay = rs.randint(-127, 128, (S, 2)).astype(np.int8)
    got = np.asarray(hist_from_rows_int(jnp.asarray(rows),
                                        jnp.asarray(pay), B,
                                        method="pallas"))
    assert got.dtype == np.int32
    mxu = np.asarray(hist_from_rows_int(jnp.asarray(rows),
                                        jnp.asarray(pay), B,
                                        method="mxu"))
    assert np.array_equal(got, mxu)
    ref = _ref_hist(rows, pay, B).astype(np.int64)
    assert np.array_equal(got.astype(np.int64), ref)


def test_int8_blocked_accumulation_exact():
    """Row counts past INT_BLOCK exercise the per-super-block int32
    conversion (f32 accumulation alone would lose integer exactness
    past 2^24)."""
    rs = np.random.RandomState(6)
    S, F, B = INT_BLOCK + 9000, 2, 16
    rows = rs.randint(0, B, (S, F)).astype(np.uint8)
    pay = np.full((S, 2), 127, np.int8)  # worst case magnitudes
    got = np.asarray(hist_from_rows_pallas(jnp.asarray(rows),
                                           jnp.asarray(pay), B,
                                           int_exact=True))
    ref = _ref_hist(rows, pay, B).astype(np.int64)
    assert np.array_equal(got.astype(np.int64), ref)


def test_sibling_subtraction_consistency():
    """The histogram-subtraction trick the growers rely on: for any
    row split, hist(parent) - hist(child) must equal hist(sibling) —
    bit-exact in the quantized path, within float tolerance otherwise
    (the compact/level growers recover every big sibling this way)."""
    rs = np.random.RandomState(7)
    S, F, B = 6000, 9, 63
    rows = rs.randint(0, B, (S, F)).astype(np.uint8)
    left = rs.rand(S) < 0.37
    # float payload
    pay = np.stack([rs.randn(S), rs.rand(S)], axis=1).astype(np.float32)
    h_all = hist_from_rows(jnp.asarray(rows), jnp.asarray(pay), B,
                           method="pallas")
    h_left = hist_from_rows(jnp.asarray(rows),
                            jnp.asarray(pay * left[:, None]), B,
                            method="pallas")
    sib = np.asarray(h_all - h_left)
    ref = np.asarray(hist_from_rows(
        jnp.asarray(rows), jnp.asarray(pay * ~left[:, None]), B,
        method="pallas"))
    np.testing.assert_allclose(sib, ref, atol=5e-3, rtol=1e-4)
    # int8 payload: exactly
    payi = rs.randint(-127, 128, (S, 2)).astype(np.int8)
    hi_all = hist_from_rows_int(jnp.asarray(rows), jnp.asarray(payi), B,
                                method="pallas")
    hi_left = hist_from_rows_int(
        jnp.asarray(rows), jnp.asarray(payi * left[:, None]), B,
        method="pallas")
    hi_right = hist_from_rows_int(
        jnp.asarray(rows), jnp.asarray(payi * ~left[:, None]), B,
        method="pallas")
    assert np.array_equal(np.asarray(hi_all - hi_left),
                          np.asarray(hi_right))


def test_build_histogram_mask_and_weights():
    """The grower-facing entry: leaf mask + bagging weights fold into
    the payload identically across methods."""
    rs = np.random.RandomState(8)
    F, n, B = 7, 4000, 31
    bins_T = jnp.asarray(rs.randint(0, B, (F, n)).astype(np.uint8))
    g = jnp.asarray(rs.randn(n).astype(np.float32))
    h = jnp.asarray(rs.rand(n).astype(np.float32))
    w = jnp.asarray((rs.rand(n) > 0.3).astype(np.float32) * 1.7)
    mask = jnp.asarray(rs.rand(n) > 0.5)
    a = build_histogram(bins_T, g, h, w, mask, B, "scatter")
    b = build_histogram(bins_T, g, h, w, mask, B, "pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **FLOAT_TOL)


# ---------------------------------------------------------------------
# whole-tree parity (the kernel inside the jitted growers)
# ---------------------------------------------------------------------

def _grow_args(n=5000, F=7, B=31, seed=0):
    from lightgbm_tpu.ops.grow import GrowConfig  # noqa: F401
    rs = np.random.RandomState(seed)
    bins = jnp.asarray(rs.randint(0, B, (F, n)).astype(np.uint8))
    g = jnp.asarray(rs.randn(n).astype(np.float32))
    h = jnp.asarray((np.abs(rs.randn(n)) + 0.1).astype(np.float32))
    w = jnp.ones((n,), jnp.float32)
    return (bins, g, h, w, jnp.ones((F,), bool),
            jnp.full((F,), B, jnp.int32), jnp.full((F,), -1, jnp.int32))


@pytest.mark.parametrize("quant", [False, True])
def test_compact_grower_tree_parity(quant):
    """grow_tree(hist_method=pallas) builds the identical tree to the
    scatter and mxu paths — structure exactly, float-search thresholds
    included (ties would diverge loudly here)."""
    from lightgbm_tpu.ops.grow import GrowConfig, grow_tree
    import jax

    args = _grow_args()
    trees = {}
    for m in ("scatter", "mxu", "pallas"):
        cfg = GrowConfig(num_leaves=15, num_bins=31, hist_method=m,
                         chunk=1024, quantized=quant)
        extra = {}
        if quant:
            extra = dict(quant_key=jax.random.PRNGKey(0))
        trees[m] = grow_tree(cfg, *args, **extra)
    tS, rlS = trees["scatter"]
    for m in ("mxu", "pallas"):
        t, rl = trees[m]
        assert int(t.num_leaves) == int(tS.num_leaves)
        assert np.array_equal(np.asarray(t.split_feature),
                              np.asarray(tS.split_feature)), m
        assert np.array_equal(np.asarray(t.threshold_bin),
                              np.asarray(tS.threshold_bin)), m
        assert np.array_equal(np.asarray(rl), np.asarray(rlS)), m
        np.testing.assert_allclose(np.asarray(t.leaf_value),
                                   np.asarray(tS.leaf_value),
                                   rtol=1e-4, atol=1e-5)


def test_engine_end_to_end_pallas_matches_scatter():
    """Full lgb.train through the fused step with hist_method=pallas:
    same trees as the scatter run (structure exact)."""
    rs = np.random.RandomState(9)
    X = rs.randn(2500, 8).astype(np.float32)
    y = ((X @ rs.randn(8)) > 0).astype(np.float64)
    models = {}
    for m in ("scatter", "pallas"):
        ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
        models[m] = lgb.train(
            {"objective": "binary", "num_leaves": 12, "max_bin": 63,
             "hist_method": m, "verbosity": -1}, ds, num_boost_round=4)
    a, b = models["scatter"], models["pallas"]
    assert b._engine.grow_cfg.hist_method == "pallas"
    for ta, tb in zip(a._models, b._models):
        assert ta.num_leaves == tb.num_leaves
        nn = ta.num_nodes
        assert np.array_equal(ta.split_feature[:nn],
                              tb.split_feature[:nn])
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------
# selection + fallback
# ---------------------------------------------------------------------

def test_resolve_hist_method_matrix(monkeypatch):
    assert resolve_hist_method("auto", "cpu", True) == "scatter"
    assert resolve_hist_method("auto", "tpu", True) == "mxu"
    assert resolve_hist_method("mxu", "cpu", True) == "mxu"
    assert resolve_hist_method("scatter", "tpu", True) == "scatter"
    assert resolve_hist_method("pallas", "tpu", True) == "pallas"
    # the auto -> pallas flip is gated on the measured bench win;
    # LIGHTGBM_TPU_AUTO_PALLAS=1 is the flip switch
    monkeypatch.setenv("LIGHTGBM_TPU_AUTO_PALLAS", "1")
    assert resolve_hist_method("auto", "tpu", True) == "pallas"
    assert resolve_hist_method("auto", "cpu", True) == "scatter"
    # unavailable pallas: auto falls back, an explicit request that
    # cannot be honoured raises
    assert resolve_hist_method("auto", "tpu", False) == "mxu"
    for backend in ("tpu", "cpu"):
        with pytest.raises(RuntimeError, match="hist_method='pallas'"):
            resolve_hist_method("pallas", backend, False)


def test_kill_switch_disables_pallas(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_PALLAS", "1")
    assert not pallas_available()
    with pytest.raises(RuntimeError, match="DISABLE_PALLAS"):
        resolve_hist_method("pallas", "cpu")
    monkeypatch.delenv("LIGHTGBM_TPU_DISABLE_PALLAS")
    assert pallas_available()


def test_config_accepts_and_validates():
    from lightgbm_tpu.config import Config
    assert Config(hist_method="pallas").hist_method == "pallas"
    with pytest.raises(ValueError, match="hist_method"):
        Config(hist_method="vmem")


def test_precision_knob_warns_on_pallas(monkeypatch):
    """hist_precision multi-pass emulation is mxu-only: selecting
    pallas with a non-default precision must say so, not silently
    ignore the knob."""
    import lightgbm_tpu.utils.log as log_mod
    seen = []
    monkeypatch.setattr(log_mod, "log_warning",
                        lambda msg: seen.append(msg))
    rs = np.random.RandomState(14)
    X = rs.randn(600, 5).astype(np.float32)
    y = ((X @ rs.randn(5)) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
    bst = lgb.train({"objective": "binary", "num_leaves": 8,
                     "max_bin": 31, "hist_method": "pallas",
                     "hist_precision": "highest", "verbosity": -1},
                    ds, num_boost_round=2)
    assert any("hist_precision" in m for m in seen), seen
    assert bst._engine.grow_cfg.hist_method == "pallas"


def test_oom_ladder_steps_pallas_to_mxu(tmp_path, monkeypatch):
    """The degradation ladder's new first rung: an injected
    RESOURCE_EXHAUSTED on a pallas run sheds to mxu (then the existing
    mxu -> scatter -> pool rungs apply), recorded as a fault event."""
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT", "oom@1")
    rs = np.random.RandomState(10)
    X = rs.randn(1200, 6).astype(np.float32)
    y = ((X @ rs.randn(6)) > 0).astype(np.float64)
    tpath = str(tmp_path / "t.jsonl")
    ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
    bst = lgb.train({"objective": "binary", "num_leaves": 8,
                     "max_bin": 31, "hist_method": "pallas",
                     "verbosity": -1}, ds, num_boost_round=4,
                    callbacks=[lgb.telemetry(tpath)])
    assert bst.current_iteration() == 4
    assert bst._engine.grow_cfg.hist_method == "mxu"
    events = [json.loads(l) for l in open(tpath) if l.strip()]
    oom = [e for e in events if e["event"] == "fault"
           and e["kind"] == "oom"]
    assert oom and "pallas -> mxu" in oom[0]["action"]
