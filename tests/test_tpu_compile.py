"""Compiles for a DESCRIBED TPU: what the chip's compiler makes of the
hot path, checked without a chip.

libtpu is installed in the sandbox and compiles for a ``v5e:2x2``
topology that is described, not attached. Nothing runs, so nothing
here is a time; the tests read the optimized HLO for what a chip run
would pay for. The topology is described inside a module fixture (only
one process may hold libtpu, and every xdist worker imports every test
file), the tests skip where it cannot be, and every such test lives in
THIS file so that one worker holds the library.
"""

import contextlib
import functools
import math
import re

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and compiles again): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", keep)
        cc.reset_cache()


F, ROWS = 67, 200_000          # the benchmark's width; rows a chip
# what the grower resolves there: the chunk's sort carries key, iota, g, h
WIDE_F32_PLAN = {"partition": "wide", "payload": "f32-planar",
                 "sort_operands": 4}


def grow_cfg(**over):
    import lightgbm_tpu.ops.grow as growmod
    from lightgbm_tpu.ops.split import SplitParams
    return growmod.GrowConfig(
        num_leaves=255, num_bins=256,
        split=SplitParams(min_data_in_leaf=20.0), grower="compact",
        hist_method="mxu", hist_precision="high", track_rows=False,
        **over)


def grower_args(n, rows, rep, cols):
    """The grower's seven arguments as shapes: the bin matrix sharded
    ``cols``, the three per-row vectors ``rows``, the rest ``rep``."""
    def sds(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (sds((F, n), jnp.uint8, cols), sds((n,), jnp.float32, rows),
            sds((n,), jnp.float32, rows), sds((n,), jnp.float32, rows),
            sds((F,), jnp.bool_, rep), sds((F,), jnp.int32, rep),
            sds((F,), jnp.int32, rep))


def assert_planar_payload_and_no_whole_copy(hlo, cfg, n):
    """No f32 copy as large as the payload, the planar buffer under one
    layout, the 2-D form gone (``n``: rows a chip)."""
    rows2 = 2 * (n + 2 * cfg.chunk)                    # 2(n+2K)
    copies = re.findall(r"= (f32\[([\d,]+)\]\S*) copy\(", hlo)
    assert copies, "the optimized HLO names no f32 copy at all: the " \
        "pattern has rotted, not the program improved"
    whole = [shape for shape, dims in copies
             if math.prod(map(int, dims.split(","))) >= rows2]
    assert not whole, whole
    # S(n) names the memory space a small buffer was placed in, not a
    # layout (at 200,000 rows the 3.7 MB buffer can sit in S(1))
    layouts = {re.sub(r"S\(\d+\)", "", lay) for lay in re.findall(
        r"f32\[%d\](\{[^}]*\})" % (2 * rows2), hlo)}
    assert len(layouts) == 1, layouts
    assert not re.search(r"f32\[%d,2\]" % rows2, hlo)


def compile_grower(cfg, jitted, args):
    """``(compiled, what the grower resolved while tracing, cfg)`` of a
    jitted grower compiled for the described chip(s) ``args`` sit on."""
    import lightgbm_tpu.ops.grow as growmod
    with no_compile_cache():
        growmod.last_plan.clear()
        compiled = jitted.trace(*args) \
            .lower(lowering_platforms=("tpu",)).compile()
        return compiled, dict(growmod.last_plan), cfg


@pytest.fixture(scope="module")
def meshless(one_chip):
    """The compact grower compiled for one described chip.
    Module-scoped: the mesh-less tests read it and the mesh test
    measures against it."""
    import lightgbm_tpu.ops.grow as growmod
    cfg = grow_cfg()
    return compile_grower(
        cfg, jax.jit(functools.partial(growmod.grow_tree_impl, cfg)),
        grower_args(ROWS, one_chip, one_chip, one_chip))


@pytest.fixture(scope="module")
def four_rank(topo):
    """``parallel/dp_grow`` compiled for the described four-chip host at
    ``criteo256x4.train``'s width, 200,000 rows a chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.parallel.data_parallel import make_dp_grow_fn
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    cfg = grow_cfg(parallel_mode="data", hist_comm="f32")
    return compile_grower(cfg, make_dp_grow_fn(cfg, mesh), grower_args(
        mesh.devices.size * ROWS, NamedSharding(mesh, P("data")),
        NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "data"))))


def test_wide_f32_payload_has_one_layout_and_no_whole_buffer_copy(meshless):
    """The benchmark cell's grower at its width (67 columns, 255 leaves,
    256 bins, mxu / high) and 200,000 rows. The float32 (g, h) payload
    of the wide partition is one 1-D planar buffer, which has a single
    possible layout: as a 2-D ``[2(n+2K), 2]`` carry the partition loop
    and the child-histogram loop each chose their own, and the compiler
    put a copy of the WHOLE buffer between them, once a split (58% of
    the round on the v5e, ledger PR 26). No copy in the program may be
    that large, and the buffer must appear under one layout."""
    compiled, plan, cfg = meshless
    assert plan == WIDE_F32_PLAN
    assert_planar_payload_and_no_whole_copy(compiled.as_text(), cfg, ROWS)


def test_four_rank_grower_keeps_the_layout_and_reduces_twice_a_split(
        meshless, four_rank):
    """``parallel/dp_grow`` for the described four-chip host at
    ``criteo256x4.train``'s width, 200,000 rows a chip: under
    ``shard_map`` the grower resolves what the mesh-less one does (wide
    partition, planar payload under one layout, no payload-sized copy),
    its scratch a chip is the mesh-less grower's (the collectives add a
    histogram's worth), and a split costs two all-reduces: the child
    counts (two integers in one) and the smaller child's histogram; the
    root costs two a tree (its float sums ride its histogram's, its
    integer row count goes alone). At the cell's own 6,640,625 rows a
    chip the same compile gave temp 10,299,808,768 B a chip against
    10,299,786,752 mesh-less (sandbox compile, PR 29)."""
    one = meshless[0]
    compiled, plan, cfg = four_rank
    assert plan == WIDE_F32_PLAN
    hlo = compiled.as_text()
    assert_planar_payload_and_no_whole_copy(hlo, cfg, ROWS)
    reduces = [(m.group(1), m.group(2)) for m in (
        re.search(r" = (.*?) all-reduce(?:-start)?\(.*op_name=\"([^\"]*)\"",
                  line) for line in hlo.splitlines()) if m]
    in_loop = [shape for shape, op in reduces if "while/body" in op]
    assert len(reduces) == 4 and len(in_loop) == 2, reduces
    hist = [shape for shape in in_loop
            if shape.startswith("f32[%d,%d,2]" % (F, cfg.num_bins))]
    assert len(hist) == 1, in_loop
    assert all("grow/hist/allreduce" in op or "grow/sums/allreduce" in op
               for _, op in reduces), reduces
    temp, temp1 = (c.memory_analysis().temp_size_in_bytes
                   for c in (compiled, one))
    assert abs(temp - temp1) <= 0.05 * temp1, (temp, temp1)


def partition_ops(hlo):
    """``(result shape, opcode, op_name)`` of every instruction the
    chunk partition traced: those whose ``op_name`` carries
    ``grow/partition/``, inside fused computations too."""
    return [m.groups() for m in (
        re.search(r" = \(?(\w+\[[\d,]*\]).*? ([\w-]+)\(.*"
                  r"op_name=\"([^\"]*grow/partition/[^\"]*)\"", line)
        for line in hlo.splitlines()) if m]


@pytest.mark.parametrize("which", ["meshless", "four_rank"])
def test_wide_partition_moves_a_chunks_rows_once(which, request):
    """The wide partition's ``while`` body as the chip's compiler leaves
    it, mesh-less and four-rank alike: ONE row gather a chunk and it is
    of the ``[K, NW]`` packed words alone, by the sorted permutation
    itself (no rotated copy of it: the rights are placed by the write's
    offset); the float32 pair rides the chunk's ONE sort, which carries
    four operands ``(key, iota, g, h)`` (PR 32: no ``[K, NW + 2]`` row
    anywhere, no concatenate to it, no ``f32[K]`` column sliced out of a
    2-D block, whose minor dimension is padded to 128 lanes); no
    bounds-fill ``select`` behind the gather (``perm`` is promised in
    bounds); at most two re-tiles between the flat ``u32[K * NW]`` and
    the 2-D ``u32[K, NW]`` (the slice on the way in, the gathered block
    on the way out; five before PR 30, when a round spent 47% of its
    time in these ops). Every pattern is first shown to match something,
    so that a renamed op fails here."""
    compiled, plan, cfg = request.getfixturevalue(which)
    assert plan == WIDE_F32_PLAN
    hlo = compiled.as_text()
    K, NW = cfg.chunk, -(-F // 4)
    words, flat = f"u32[{K},{NW}]", f"u32[{K * NW}]"
    ops = partition_ops(hlo)
    # the gather instruction itself sits in a fused computation whose
    # metadata may drop the scope: count it by shape over the program,
    # and its fusion by scope
    gathers = re.findall(r" = (\w+\[[\d,]*\])\S* gather\(", hlo)
    assert len(gathers) > 1, "the gather pattern finds no other " \
        "gather of the program: it has rotted"
    assert [g for g in gathers if g.startswith(f"u32[{K},")] == [words], \
        gathers
    assert [shape for shape, _, name in ops
            if name.endswith("/gather")
            and "grow/partition/gather" in name].count(words) >= 1, ops
    # nothing as wide as the words + the pair anywhere, in any type
    assert f"[{K},{NW}]" in hlo and f"[{K},{NW + 2}]" not in hlo
    # the chunk's sort: one, keyed on its first operand, the pair behind
    # key and iota
    # (an operand's type carries its layout, parentheses and all)
    sorts = [re.findall(r"(\w+)\[(\d+)\]", m) for m in re.findall(
        r" = \((.*?)\) sort\(", hlo)]
    assert sorts, "the sort pattern finds no sort: it has rotted"
    assert [[dt for dt, _ in s] for s in sorts if s[0][1] == str(K)] \
        == [["s32", "s32", "f32", "f32"]], sorts
    key_sort = [op for _, op, name in ops
                if "grow/partition/key_sort" in name]
    assert "sort" in key_sort, key_sort
    assert not {"dynamic-slice", "pad", "concatenate"} & set(key_sort), \
        key_sort                      # rot(perm, s_r) compiled to these
    # the payload's columns: 1-D in, 1-D out, never through a 2-D block
    payload = [(shape, op) for shape, op, name in ops
               if "grow/partition/payload" in name]
    assert (f"f32[{K}]", "dynamic-slice") in payload, payload
    assert not [shape for shape, _ in payload
                if re.fullmatch(r"\w+\[%d,\d+\]" % K, shape)], payload
    assert not [op for _, op in payload
                if op in ("slice", "concatenate", "bitcast-convert")], payload
    selects = [shape for shape, op, _ in ops if op == "select"]
    assert flat in selects, selects   # the two masked word writes
    assert words not in selects and f"pred[{K},{NW}]" not in hlo, selects
    # (the gather's own fused computation ends in a reshape to its
    # result's shape, under the gather's name: not an op of the body)
    retiles = [shape for shape, op, name in ops
               if op in ("reshape", "copy") and shape in (words, flat)
               and not name.endswith("/gather")]
    assert 1 <= len(retiles) <= 2, retiles


@pytest.mark.parametrize("which", ["meshless", "four_rank"])
def test_every_op_the_chip_would_time_has_a_scope(which, request):
    """The grower's account closes in the program the chip's compiler
    makes (ISSUE 37): outside the fused computations and reducers, whose
    instructions a trace never shows, every instruction that does anything
    is in the op -> scope table, directly or derived, once-a-tree work
    under ``grow/setup`` and ``grow/row_leaf``; what is left bare
    (parameters, constants, tuples and their elements, bitcasts) runs
    nothing. The derivation's neighbour rules take only the compiler's
    own ops, never one the tracer named outside every ``with scope``: so
    this fails the day the grower traces work under no scope, and until
    then ``(unscoped)`` in a trace of this program is a stale table."""
    from lightgbm_tpu.obs import scopes
    compiled, _, _ = request.getfixturevalue(which)
    hlo = compiled.as_text()
    table = scopes.scopes_from_hlo_text(hlo)
    assert table is not None and table.module
    assert {"grow/setup", "grow/row_leaf", "grow/fixed", "grow/hist/build",
            "grow/partition/gather"} <= set(table.values())
    insts, _, _ = scopes._parse_hlo(hlo)
    # fused computations, reducers, comparators: run inside their caller
    inner = {c for inst in insts.values() if inst[1] != "while"
             for c in inst[3]}
    free = {"parameter", "constant", "tuple", "get-tuple-element",
            "bitcast"}
    timed = {name for name, inst in insts.items()
             if inst[0] not in inner and inst[1] not in free}
    assert len(timed) > 500 and {"while", "fusion", "copy"} <= {
        insts[name][1] for name in timed}
    assert sorted(timed - set(table)) == []


def test_the_lambdarank_pass_is_shaped_by_its_length_classes(one_chip):
    """The ranking gradient as the chip's compiler leaves it (PR 36), on
    the length mix of the benchmark's ranking table (18,919 lognormal
    queries, mean ~120, one of 1,251): every length class's blocks are in
    the ONE program; a row's gradient is READ from its one slot (two
    element gathers, ``f32[rows]``, nothing else gathered by element and
    nothing scattered); the ranks are a count over the pairwise compare
    (no sort); no ``[queries, longest]`` array and no pair tensor of the
    longest's width for more queries than the widest class holds; and
    the program's temporaries are a few blocks' worth, not the padded
    table's. The row gather's pattern is shown to match first."""
    import numpy as np
    from lightgbm_tpu import ranking
    rng = np.random.default_rng(30331)
    sizes = np.clip(np.rint(rng.lognormal(0.0, 0.55, 18919) * 103.3),
                    1, 1251).astype(np.int64)
    sizes[7] = 1251
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(qb[-1])
    lay = ranking._rank_layout(qb, np.ones(n), 30)
    dims = [c[2].shape for c in lay.classes]
    assert [w for _, _, w in dims][::len(dims) - 1] == [128, 1251], dims
    assert n <= lay.row_slots < 2 * n and lay.pair_slots < 1.2e9

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    fn = getattr(ranking._lambdarank_grads, "unwrapped",
                 ranking._lambdarank_grads)
    with no_compile_cache():
        compiled = fn.trace(
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
            jax.tree_util.tree_map(sds, lay.classes), sds(lay.slot_of_row),
            None, jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
            trunc=30, norm=True).lower(
                lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    gathers = re.findall(r" = (\w+\[[\d,]*\])\S* gather\(", hlo)
    assert gathers.count(f"f32[{n}]") == 2, gathers
    # a query's score is one contiguous slice, never an element gather
    assert all(g == f"f32[{n}]" or not g.endswith(f"[{lay.row_slots}]")
               for g in gathers), gathers
    assert not re.search(r" (scatter|sort)\(", hlo)
    assert f"[{len(sizes)},1251]" not in hlo
    widest_blk = dims[-1][1]
    lead = {int(m) for m in re.findall(r"\[(\d+),1251,1251\]", hlo)}
    assert lead <= {1, widest_blk}, lead
    for _, blk, w in dims:
        assert f"[{blk},{w}]" in hlo, (blk, w)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * (1 << 25), mem
