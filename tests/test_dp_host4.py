"""One host of a data-parallel job: four ranks over four (virtual) chips.

What ``criteo-dp256-host4`` (perfbench) rests on, at test size on the
CPU: the four-rank learner grows the serial learner's trees; the ranks'
local histograms are the shares of the whole table's, and the one-rank
cut (``criteo-dp256-rank``) is exactly one of them; row state is placed
row-sharded once at init; the reductions are scoped and counted; an
allocation failure under a mesh stops after one rung.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.registry import registry

RANKS = 4
P = {"objective": "binary", "num_leaves": 15, "verbose": -1,
     "min_data_in_leaf": 20, "learning_rate": 0.1}
DP = dict(P, tree_learner="data", num_devices=RANKS)


@pytest.fixture(scope="module")
def table():
    rs = np.random.RandomState(29)
    n = 4000                            # 1,000 rows a rank
    X = rs.randn(n, 10).astype(np.float32)
    X[:, :3] = np.floor(np.exp(1.2 * X[:, :3]))      # count-like, ties
    y = ((X[:, 3:] @ rs.randn(7) + X[:, 4] * X[:, 5]
          + 0.5 * rs.randn(n)) > 0).astype(np.float32)
    return X, y


def structure(bst):
    """Every tree's splits and counts, the values left out."""
    out = []
    for t in bst.dump_model()["tree_info"]:
        nodes, stack = [], [t["tree_structure"]]
        while stack:
            nd = stack.pop()
            if "split_index" in nd:
                nodes.append((nd["split_feature"], nd["threshold"],
                              nd["internal_count"]))
                stack += [nd["right_child"], nd["left_child"]]
            else:
                nodes.append(("leaf", nd["leaf_count"]))
        out.append(nodes)
    return out


def counter(name):
    fam = registry.snapshot().get(name)
    return sum(s["value"] for s in fam["series"]) if fam else 0


@pytest.fixture(scope="module")
def jobs(table):
    X, y = table
    serial = lgb.train(P, lgb.Dataset(X, label=y), 4)
    before = counter("hist_reductions"), counter("hist_wire_bytes")
    host = lgb.train(DP, lgb.Dataset(X, label=y), 4)
    placed = lgb.train(dict(DP, shard_residency="device"),
                       lgb.Dataset(X, label=y), 4)
    after = counter("hist_reductions"), counter("hist_wire_bytes")
    return serial, host, placed, (after[0] - before[0],
                                  after[1] - before[1])


@pytest.mark.parametrize("which", ["host", "placed"])
def test_four_ranks_grow_the_serial_learners_trees(table, jobs, which):
    X, _ = table
    serial, host, placed, _ = jobs
    dp = {"host": host, "placed": placed}[which]
    assert structure(dp) == structure(serial)
    np.testing.assert_allclose(dp.predict(X), serial.predict(X),
                               rtol=0, atol=2e-6)


def test_placed_and_unplaced_row_state_agree_bit_for_bit(table, jobs):
    X, _ = table
    _, host, placed, _ = jobs
    assert np.array_equal(host.predict(X), placed.predict(X))


def test_row_state_is_placed_row_sharded_once_at_init(jobs):
    _, host, placed, _ = jobs
    eng = placed._engine
    assert eng._rows_placed and not host._engine._rows_placed
    axis = eng.mesh.axis_names[0]
    for arr in (eng.label, eng._row_w_ones):
        assert arr.sharding.spec == jax.sharding.PartitionSpec(axis)
        assert len(arr.sharding.device_set) == RANKS
    assert eng.score.sharding.spec == jax.sharding.PartitionSpec(None, axis)
    # the no-bagging weights are the placed vector itself, every round
    assert eng._row_weights(3, None, None) is eng._row_w_ones
    g, h = eng._gradients(eng.score)
    assert g.sharding.spec == jax.sharding.PartitionSpec(None, axis)


def test_place_rows_is_a_job_span():
    from lightgbm_tpu.obs.trace import span_events_snapshot
    rs = np.random.RandomState(1)
    X = rs.randn(800, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    lgb.train(dict(DP, num_leaves=4, shard_residency="device"),
              lgb.Dataset(X, label=y), 1)
    spans = [s for s in span_events_snapshot()
             if s["name"] == "train/place_rows"]
    assert len(spans) == 1
    assert spans[0]["attrs"] == {"rows": 800, "devices": RANKS}
    lgb.train(dict(P, num_leaves=4), lgb.Dataset(X, label=y), 1)
    assert len([s for s in span_events_snapshot()
                if s["name"] == "train/place_rows"]) == 1   # no mesh: none


def test_reductions_are_counted_from_the_traced_sites(jobs):
    """Two jobs of 4 rounds: a root reduction a tree and one a split,
    each of the local ``[F, B, 2]`` float32 histogram."""
    _, host, placed, (reductions, wire_bytes) = jobs
    leaves = [t["num_leaves"] for t in host.dump_model()["tree_info"]]
    assert reductions == 2 * sum(leaves)
    sites = placed._engine._reduction_sites
    assert [(per, wire) for per, wire, _ in sites] == [("tree", "f32"),
                                                       ("split", "f32")]
    F, B = 10, placed._engine.grow_cfg.num_bins
    assert {b for _, _, b in sites} == {F * B * 2 * 4}
    assert wire_bytes == reductions * F * B * 2 * 4


def test_serial_jobs_count_no_reductions(table):
    X, y = table
    before = counter("hist_reductions")
    lgb.train(P, lgb.Dataset(X, label=y), 2)
    assert counter("hist_reductions") == before


def test_the_grow_program_carries_both_collective_scopes(jobs):
    from lightgbm_tpu import obs
    table = obs.op_scopes("parallel/dp_grow")
    assert table is not None
    scopes = set(table.values())
    # (nothing is left to the bare ``boost/grow`` around the grower since
    # its once-a-tree work has scopes of its own)
    assert {"grow/hist/allreduce", "grow/sums/allreduce", "grow/setup",
            "grow/row_leaf", "grow/hist/build", "grow/split_scan"} <= scopes


def test_ranks_histograms_are_the_shares_of_the_whole(table, jobs):
    """The guide's share-to-whole test: at the root and at the first
    split's left child, the four ranks' local histograms add up to the
    whole table's, and the one-rank cut's histogram (a serial job on
    rank r's rows) is exactly rank r's term."""
    from lightgbm_tpu.ops.histogram import build_histogram
    X, y = table
    serial = jobs[0]
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    bins_T = jnp.asarray(ds.host_bins().T)
    n = X.shape[0]
    B = serial._engine.grow_cfg.num_bins
    rs = np.random.RandomState(5)
    g = jnp.asarray(rs.randn(n).astype(np.float32))
    h = jnp.asarray(rs.rand(n).astype(np.float32))
    ones = jnp.ones((n,), jnp.float32)
    root = serial.dump_model()["tree_info"][0]["tree_structure"]
    left = jnp.asarray(X[:, root["split_feature"]] <= root["threshold"])
    per = n // RANKS
    for mask in (jnp.ones((n,), bool), left):
        whole = np.asarray(build_histogram(bins_T, g, h, ones, mask, B),
                           np.float64)
        terms = []
        for r in range(RANKS):
            sl = slice(r * per, (r + 1) * per)
            term = build_histogram(bins_T[:, sl], g[sl], h[sl], ones[sl],
                                   mask[sl], B)
            # the one-rank cut: the same rows as a table of their own
            alone = build_histogram(jnp.asarray(ds.host_bins()[sl].T),
                                    g[sl], h[sl], jnp.ones((per,)),
                                    mask[sl], B)
            assert np.array_equal(np.asarray(term), np.asarray(alone))
            terms.append(np.asarray(term, np.float64))
        # float32 sums of a few thousand signed gradients: 1e-7 of sum |g|
        np.testing.assert_allclose(sum(terms), whole, rtol=1e-5, atol=5e-4)
        assert not np.allclose(sum(terms[:-1]), whole, rtol=1e-3)


def test_one_rank_alone_trains_on_exactly_its_share(table):
    """With the init score fixed (no ``boost_from_average``), the root
    of the one-rank job on rank r's rows holds rank r's term of the
    four-rank root: the counts add up exactly, the hessian sums to
    float32 rounding."""
    X, y = table
    fixed = dict(P, boost_from_average=False)
    per = X.shape[0] // RANKS
    whole = lgb.train(dict(fixed, tree_learner="data", num_devices=RANKS),
                      lgb.Dataset(X, label=y), 1)
    w_root = whole.dump_model()["tree_info"][0]["tree_structure"]
    counts, weights = [], []
    for r in range(RANKS):
        sl = slice(r * per, (r + 1) * per)
        alone = lgb.train(fixed, lgb.Dataset(X[sl], label=y[sl]), 1)
        root = alone.dump_model()["tree_info"][0]["tree_structure"]
        counts.append(root["internal_count"])
        weights.append(root["internal_weight"])
    assert counts == [per] * RANKS and sum(counts) == w_root["internal_count"]
    np.testing.assert_allclose(sum(weights), w_root["internal_weight"],
                               rtol=1e-6)


@pytest.mark.parametrize("ooms, raises", [(1, False), (2, True)])
def test_under_a_mesh_the_oom_ladder_takes_one_rung(table, monkeypatch,
                                                    ooms, raises):
    X, y = table
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT",
                       ",".join(["oom@1"] * ooms))
    ds = lgb.Dataset(X[:1200], label=y[:1200])
    params = dict(DP, num_leaves=4)
    if raises:
        with pytest.raises(lgb.basic.LightGBMError,
                           match="under a mesh \\(one rung\\)"):
            lgb.train(params, ds, 3)
    else:
        bst = lgb.train(params, ds, 3)
        oom = [f for f in bst._engine.fault_log if f["kind"] == "oom"]
        assert len(oom) == 1 and bst.num_trees() == 3


def test_without_a_mesh_the_ladder_keeps_its_rungs(table, monkeypatch):
    X, y = table
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_INJECT", "oom@1,oom@1")
    # 15 leaves: the CPU's ladder is the pool's halvings, 15 -> 7 -> 3
    bst = lgb.train(P, lgb.Dataset(X[:1200], label=y[:1200]), 3)
    assert len([f for f in bst._engine.fault_log
                if f["kind"] == "oom"]) == 2
