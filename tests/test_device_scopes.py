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


def test_scopes_are_metadata_only(monkeypatch, no_persistent_cache):
    """The lowered ``gbdt/fused_iter`` with debug info stripped, and the
    optimized HLO but for its ``metadata={...}``, are the same text with
    the scopes on as with ``jax.named_scope`` patched to a no-op: the
    scopes cannot move a number (nor the persistent cache's key, which
    strips debug info the same way)."""
    scoped = _lower_fused_step()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_fused_step()
    monkeypatch.undo()
    with_dbg = scoped.as_text(debug_info=True)
    assert "grow/partition/payload" in with_dbg \
        and "boost/gradients" in with_dbg
    assert "grow/partition" not in bare.as_text(debug_info=True)
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
    # every phase of the iteration and every layer of the grower shows
    assert {"boost/gradients", "boost/grow", "boost/score_update",
            "boost/tree_pack", "grow/partition/key_sort",
            "grow/partition/payload", "grow/hist/build",
            "grow/hist/subtract", "grow/split_scan",
            "grow/fixed"} <= set(table.values())
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

ENTRY %main (x: f32[8,2]) -> f32[8,2] {
  %x = f32[8,2]{1,0:T(8,128)} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,2]{1,0}) tuple(%zero, %x)
  %while.1 = (s32[], f32[8,2]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/grow/fixed/while"}
  %gte.1 = f32[8,2]{1,0:T(8,128)} get-tuple-element(%while.1), index=1, metadata={op_name="jit(step)/grow/fixed/while"}
  %copy.1 = f32[8,2]{0,1:T(2,128)} copy(%gte.1)
  %fusion.1 = f32[8,2]{1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
  ROOT %neg = f32[8,2]{1,0} negate(%fusion.1)
}
'''


def test_ops_the_compiler_left_without_metadata_get_a_derived_scope():
    """A layout copy, a fusion whose root kept the name, a loop body's
    bare op: each takes the scope of what produced its value, of its
    fused root, of its loop — and is listed as derived."""
    direct = scopes_from_hlo_text(HLO, derive=False)
    assert dict(direct) == {"mul.1": "grow/hist/build",
                            "dus.1": "grow/partition/payload",
                            "while.1": "grow/fixed", "gte.1": "grow/fixed"}
    table = scopes_from_hlo_text(HLO)
    # the copy relays the value the loop's payload write produced: it is
    # looked up through the tuple element, not given the loop's name
    assert table["copy.1"] == "grow/partition/payload"
    assert table["fusion.1"] == "grow/hist/build"
    assert table["next"] == "grow/fixed" and table["lt"] == "grow/fixed"
    assert "neg" not in table and "x" not in table
    assert {"copy.1", "fusion.1", "next", "lt"} <= table.derived
    assert "dus.1" not in table.derived


def test_a_foreign_or_bare_executable_is_refused_not_guessed():
    """What a warm compile cache hands back: the writer's metadata. No
    scope at all (an entry from before the scopes), or one the list no
    longer declares (after a rename): no table."""
    bare = re.sub(r", metadata=\{[^}]*\}", "", HLO)
    assert scopes_from_hlo_text(bare) is None
    renamed = HLO.replace("grow/partition/payload", "grow/partition/pay_v0")
    assert scopes_from_hlo_text(renamed) is None
