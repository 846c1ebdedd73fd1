"""Device scopes (obs/scopes.py): the round's ops named by layer, the
op -> scope table read back from the program, and the proof that the
names are metadata only. CPU, small jobs."""

import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import scopes
from lightgbm_tpu.obs.scopes import (DEVICE_SCOPES, op_scopes,
                                     scope_of_op_name,
                                     scopes_from_hlo_text)

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "verbose": -1}


def _data(n=1500, f=8, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    return X, (X[:, 0] + X[:, 1] > 0).astype(float)


def _fused_step_args(eng):
    if eng._row_w_ones is None:
        eng._row_w_ones = jnp.ones((eng.n,), jnp.float32)
    return (eng.score, jnp.asarray(0, jnp.int32),
            jnp.asarray(0.1, jnp.float32), eng._row_w_ones,
            jnp.ones((eng.F,), jnp.bool_), eng.bins_T, eng.feat_num_bins,
            eng.feat_nan_bin, eng.label, eng.weight, eng.monotone,
            eng.feat_is_cat, eng.interaction_groups, eng.forced,
            eng._bundle_dev)


def _lower_fused_step():
    X, y = _data()
    bst = lgb.Booster(PARAMS, lgb.Dataset(X, label=y))
    eng = bst._engine
    fn = eng._get_fused_fn()
    return getattr(fn, "unwrapped", fn).lower(*_fused_step_args(eng))


def _lower_grow_tree():
    """``ops/grow_tree`` as the eager iteration calls it (a validation
    set, a ranking objective): the grower alone, its own program."""
    import functools
    from lightgbm_tpu.ops.grow import grow_tree_impl
    X, y = _data()
    eng = lgb.Booster(PARAMS, lgb.Dataset(X, label=y))._engine
    ones = jnp.ones((eng.n,), jnp.float32)
    # a jit of its own: the registered one keeps its first trace
    return jax.jit(functools.partial(grow_tree_impl, eng.grow_cfg)).lower(
        eng.bins_T, ones, ones, ones,
        jnp.ones((eng.F,), jnp.bool_), eng.feat_num_bins, eng.feat_nan_bin,
        eng.monotone, eng.feat_is_cat, None, eng.interaction_groups,
        eng.forced, None, None, eng._bundle_dev)


LOWER = {"gbdt/fused_iter": _lower_fused_step,
         "ops/grow_tree": _lower_grow_tree}


_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")


def _but_for_metadata(hlo_text):
    """An optimized module's text without what ``metadata`` feeds: each
    instruction's ``metadata={...}`` and the header's source tables
    (FileNames ... StackFrames) that ``stack_frame_id`` indexes."""
    lines, skipping = [], False
    for line in hlo_text.splitlines():
        if line.startswith(("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames")):
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        if not skipping:
            lines.append(_METADATA.sub("", line))
    return "\n".join(lines)


@pytest.fixture
def no_persistent_cache():
    """Another test of the same worker may have placed a persistent
    compile cache; its key strips debug info, so the second of two
    compiles that differ only in metadata would be handed the first's
    executable, metadata and all — the very hazard ``op_scopes`` guards
    against, and not what this test compares."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("entry", sorted(LOWER))
def test_scopes_are_metadata_only(entry, monkeypatch, no_persistent_cache):
    """The lowered program with debug info stripped, and the optimized
    HLO but for its ``metadata={...}``, are the same text with the scopes
    on as with ``jax.named_scope`` patched to a no-op: the scopes (the
    once-a-tree ``grow/setup`` and ``grow/row_leaf`` among them) cannot
    move a number (nor the persistent cache's key, which strips debug
    info the same way)."""
    scoped = LOWER[entry]()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = LOWER[entry]()
    monkeypatch.undo()
    with_dbg = scoped.as_text(debug_info=True)
    assert all(sc in with_dbg for sc in (
        "grow/partition/payload", "grow/setup", "grow/row_leaf"))
    assert ("boost/gradients" in with_dbg) == (entry == "gbdt/fused_iter")
    assert "grow/partition" not in bare.as_text(debug_info=True) \
        and "grow/setup" not in bare.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()
    s_opt = scoped.compile().as_text()
    b_opt = bare.compile().as_text()
    assert "grow/hist/build" in s_opt and "grow/hist/build" not in b_opt
    assert _but_for_metadata(s_opt) == _but_for_metadata(b_opt)
    assert "fusion" in _but_for_metadata(s_opt)


def test_op_scopes_maps_every_scoped_op_of_the_step_that_ran():
    X, y = _data()
    # the booster is kept: an entry lives as long as its engine
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), 2)
    table = op_scopes("gbdt/fused_iter")
    assert table is not None and bst.num_trees() == 2
    assert set(table.values()) <= set(DEVICE_SCOPES)
    # every phase of the iteration and every layer of the grower shows;
    # nothing of the grower is left to the bare ``boost/grow`` around it
    assert {"boost/gradients", "boost/score_update",
            "boost/tree_pack", "grow/partition/key_sort",
            "grow/partition/payload", "grow/hist/build",
            "grow/hist/subtract", "grow/split_scan", "grow/fixed",
            "grow/setup", "grow/row_leaf"} <= set(table.values())
    assert "boost/grow" not in table.values()
    assert table.missing == () and table.module.startswith("jit_")
    # against the executable's own text: each instruction whose op_name
    # lies under a declared scope is in the table under that scope
    from lightgbm_tpu.obs.jit_tracker import live_entries
    fn = [f for f in live_entries("gbdt/fused_iter")
          if f.last_avals is not None][-1]
    args, kwargs = fn.last_avals
    text = fn.unwrapped.lower(*args, **kwargs).compile().as_text()
    seen = 0
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line)
        om = re.search(r'op_name="([^"]*)"', line)
        if not m or not om:
            continue
        found, ok = scope_of_op_name(om.group(1))
        assert ok, om.group(1)
        if found is not None:
            assert table[m.group(1)] == found, line[:120]
            assert m.group(1) not in table.derived
            seen += 1
    assert seen > 100
    assert all(op in table for op in table.derived)
    assert op_scopes("no/such_entry") is None


def test_scope_of_op_name_innermost_and_undeclared():
    assert scope_of_op_name("jit(step)/boost/grow/grow/fixed/while/body/"
                            "grow/partition/key_sort/sort") \
        == ("grow/partition/key_sort", True)
    assert scope_of_op_name("jit(step)/boost/grow/jit(f)/add") \
        == ("boost/grow", True)
    assert scope_of_op_name("jit(step)/jit(main)/mul") == (None, True)
    # a renamed or foreign scope under a declared root: not declared
    assert scope_of_op_name("jit(step)/grow/partition/old_name/sort")[1] \
        is False
    assert scope_of_op_name("jit(step)/boost/grow/grow/hist/gone/dot")[1] \
        is False
    with pytest.raises(ValueError):
        scopes.scope("grow/not_declared")


HLO = '''HloModule jit_step

%fused_computation.1 (p0: f32[8,2]) -> f32[8,2] {
  %p0 = f32[8,2]{1,0} parameter(0)
  ROOT %mul.1 = f32[8,2]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/grow/fixed/while/body/grow/hist/build/mul"}
}

%body (arg: (s32[], f32[8,2])) -> (s32[], f32[8,2]) {
  %arg = (s32[], f32[8,2]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %pay = f32[8,2]{1,0} get-tuple-element(%arg), index=1
  %dus.1 = f32[8,2]{1,0} dynamic-update-slice(%pay, %pay, %i, %i), metadata={op_name="jit(step)/grow/fixed/while/body/grow/partition/payload/dynamic_update_slice"}
  %next = s32[] add(%i, %i)
  ROOT %tuple.1 = (s32[], f32[8,2]{1,0}) tuple(%next, %dus.1)
}

%cond (arg.1: (s32[], f32[8,2])) -> pred[] {
  %arg.1 = (s32[], f32[8,2]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt = pred[] compare(%i.1, %i.1), direction=LT
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b), metadata={op_name="reduce_window_sum"}
}

ENTRY %main (x: f32[8,2]) -> f32[8,2] {
  %x = f32[8,2]{1,0:T(8,128)} parameter(0)
  %zero = s32[] constant(0)
  %copy-start.1 = (f32[8,2]{1,0}, f32[8,2]{1,0}, u32[]) copy-start(%x)
  %copy-done.1 = f32[8,2]{1,0} copy-done(%copy-start.1)
  %bcast.1 = s32[] broadcast(%zero), dimensions={}
  %pad.1 = f32[8,2]{1,0} pad(%copy-done.1, %bcast.1), padding=0_0x0_0, metadata={op_name="jit(step)/grow/setup/pad"}
  %init = (s32[], f32[8,2]{1,0}) tuple(%bcast.1, %pad.1)
  %while.1 = (s32[], f32[8,2]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/grow/fixed/while"}
  %gte.1 = f32[8,2]{1,0:T(8,128)} get-tuple-element(%while.1), index=1, metadata={op_name="jit(step)/grow/fixed/while"}
  %copy.1 = f32[8,2]{0,1:T(2,128)} copy(%gte.1)
  %fusion.1 = f32[8,2]{1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  %rw.1 = f32[8,2]{1,0} reduce-window(%fusion.1, %zero), window={size=1x2}, to_apply=%region_add
  %custom-call.1 = f32[4]{0} custom-call(), custom_call_target="AllocateBuffer"
  %cmp.1 = pred[8,2]{1,0} compare(%rw.1, %rw.1), direction=LT, metadata={op_name="jit(step)/shard_map/compare.7"}
  %sel.1 = f32[8,2]{1,0} select(%cmp.1, %rw.1, %rw.1), metadata={op_name="jit(step)/shard_map/jit(_where)/select_n"}
  ROOT %neg = f32[8,2]{1,0} negate(%rw.1), metadata={op_name="jit(step)/neg"}
}
'''


def test_ops_the_compiler_left_without_metadata_get_a_derived_scope():
    """A layout copy, a fusion whose root kept the name, a loop body's
    bare op: each takes the scope of what produced its value, of its
    fused root, of its loop — and is listed as derived."""
    direct = scopes_from_hlo_text(HLO, derive=False)
    assert dict(direct) == {"mul.1": "grow/hist/build",
                            "dus.1": "grow/partition/payload",
                            "while.1": "grow/fixed", "gte.1": "grow/fixed",
                            "pad.1": "grow/setup"}
    table = scopes_from_hlo_text(HLO)
    assert table.module == "jit_step" and table.missing == ()
    # the copy relays the value the loop's payload write produced: it is
    # looked up through the tuple element, not given the loop's name
    assert table["copy.1"] == "grow/partition/payload"
    assert table["fusion.1"] == "grow/hist/build"
    assert table["next"] == "grow/fixed" and table["lt"] == "grow/fixed"
    assert {"copy.1", "fusion.1", "next", "lt"} <= table.derived
    assert "dus.1" not in table.derived


def test_the_entry_computations_bare_ops_go_by_what_they_read_or_feed():
    """What nothing calls has no loop to take a scope from. A scan the
    tracer named without its scope path (``reduce_window_sum``) goes with
    the value it reads; a parameter's prefetch and a constant's broadcast,
    which read nothing scoped, with what consumes them (through the tuple
    that only bundles them); an op that reads and feeds nothing scoped
    stays out of the table, as parameters and constants do. And so does
    an op the tracer DID name, outside every ``with scope`` (``neg``; the
    ``select_n`` of a ``jnp.where`` inside a ``shard_map`` body): work
    nobody named is not its neighbour's. What the compiler inlined from
    such a body nameless (``jit(step)/shard_map/compare.7``: the call's
    name before the instruction's) is the compiler's."""
    table = scopes_from_hlo_text(HLO)
    assert table["rw.1"] == "grow/hist/build"       # reads fusion.1
    assert table["cmp.1"] == "grow/hist/build"      # reads rw.1
    assert "neg" not in table                       # jit(step)/neg
    assert "sel.1" not in table                     # .../jit(_where)/select_n
    assert table["copy-done.1"] == "grow/setup"     # feeds pad.1
    assert table["copy-start.1"] == "grow/setup"    # feeds copy-done.1
    assert table["bcast.1"] == "grow/setup"         # feeds pad.1 and the tuple
    assert {"rw.1", "cmp.1", "copy-done.1", "copy-start.1",
            "bcast.1"} <= table.derived
    assert "custom-call.1" not in table
    assert "x" not in table and "zero" not in table


def test_a_collective_inside_setup_is_the_small_reductions_not_setups():
    """Innermost wins: the quantized arm's per-tree scales are a ``pmax``
    over the mesh traced inside ``grow/setup``; it is one of the grower's
    small reductions (``grow/sums/allreduce``), as the doc says every one
    is. The lowering's name locations, a 4-device mesh, nothing compiled."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import lightgbm_tpu.ops.grow as growmod
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import make_dp_grow_fn
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    cfg = growmod.GrowConfig(
        num_leaves=7, num_bins=16, split=SplitParams(min_data_in_leaf=2.0),
        grower="compact", hist_method="scatter", track_rows=False,
        parallel_mode="data", quantized=True, stochastic=False)
    F, n = 5, 4 * 256

    def sds(shape, dt, *spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(*spec)))

    lowered = make_dp_grow_fn(cfg, mesh).trace(
        sds((F, n), jnp.uint8, None, "data"), sds((n,), jnp.float32, "data"),
        sds((n,), jnp.float32, "data"), sds((n,), jnp.float32, "data"),
        sds((F,), jnp.bool_), sds((F,), jnp.int32),
        sds((F,), jnp.int32)).lower()
    paths = {p for p in scopes._LOC_NAME_RE.findall(
        lowered.as_text(debug_info=True)) if p.endswith("/pmax")}
    assert paths and all("grow/setup" in p for p in paths)
    assert {scope_of_op_name(p) for p in paths} \
        == {("grow/sums/allreduce", True)}


LOWERING = '''module @jit_step {
  func.func public @main(%arg0: tensor<8x2xf32> loc("x")) {
    return loc(#loc9)
  } loc(#loc)
}
#loc1 = loc("/root/grow/setup/file.py":4:12)
#loc2 = loc("step"(#loc1))
#loc3 = loc("jit(step)/grow/setup/pad"(#loc2))
#loc4 = loc("jit(step)/grow/fixed/while/body/grow/hist/build/mul"(#loc2))
#loc5 = loc("jit(step)/grow/fixed/while/body/grow/partition/payload/dynamic_update_slice"(#loc2))
#loc6 = loc("jit(step)/grow/row_leaf/while/body/select_n"(#loc2))
#loc7 = loc("jit(step)/grow/row_leaf/jit(_where)/select_n"(#loc2))
'''


@pytest.mark.parametrize("case", ["stale", "sound", "not_asked"])
def test_a_scope_the_source_opens_and_the_executable_lacks_is_missing(
        case, monkeypatch):
    """The persistent cache answers with the WRITER's metadata: an
    executable that merely lacks a scope added since it was written
    passes both refusals, so the table lists such scopes (from the
    current lowering's name locations, never its file paths), with one
    log line, and stays a table."""
    logged = []     # not the stream: another test's verbosity is global
    monkeypatch.setattr("lightgbm_tpu.utils.log.log_warning", logged.append)
    text = HLO
    if case == "sound":     # the executable has an op of the new scope too
        text = HLO.replace('op_name="jit(step)/neg"',
                           'op_name="jit(step)/grow/row_leaf/neg"')
    table = scopes_from_hlo_text(
        text, lowering=None if case == "not_asked" else LOWERING)
    assert table is not None and table["pad.1"] == "grow/setup"
    assert table.missing == (("grow/row_leaf",) if case == "stale" else ())
    assert ("grow/row_leaf and no op of the executable" in "".join(logged)) \
        == (case == "stale")


def test_a_foreign_or_bare_executable_is_refused_not_guessed():
    """What a warm compile cache hands back: the writer's metadata. No
    scope at all (an entry from before the scopes), or one the list no
    longer declares (after a rename): no table."""
    bare = re.sub(r", metadata=\{[^}]*\}", "", HLO)
    assert scopes_from_hlo_text(bare) is None
    renamed = HLO.replace("grow/partition/payload", "grow/partition/pay_v0")
    assert scopes_from_hlo_text(renamed) is None
