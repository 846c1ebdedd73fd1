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


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and compiles again): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", keep)
    cc.reset_cache()


def test_wide_f32_payload_has_one_layout_and_no_whole_buffer_copy(
        one_chip, no_compile_cache):
    """The benchmark cell's grower at its width (67 columns, 255 leaves,
    256 bins, mxu / high) and 200,000 rows. The float32 (g, h) payload
    of the wide partition is one 1-D planar buffer, which has a single
    possible layout: as a 2-D ``[2(n+2K), 2]`` carry the partition loop
    and the child-histogram loop each chose their own, and the compiler
    put a copy of the WHOLE buffer between them, once a split (58% of
    the round on the v5e, ledger PR 26). No copy in the program may be
    that large, and the buffer must appear under one layout."""
    import lightgbm_tpu.ops.grow as growmod
    from lightgbm_tpu.ops.split import SplitParams
    F, n = 67, 200_000
    cfg = growmod.GrowConfig(
        num_leaves=255, num_bins=256,
        split=SplitParams(min_data_in_leaf=20.0), grower="compact",
        hist_method="mxu", hist_precision="high", track_rows=False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    growmod.last_plan.clear()
    compiled = jax.jit(functools.partial(growmod.grow_tree_impl, cfg)) \
        .trace(sds((F, n), jnp.uint8), sds((n,), jnp.float32),
               sds((n,), jnp.float32), sds((n,), jnp.float32),
               sds((F,), jnp.bool_), sds((F,), jnp.int32),
               sds((F,), jnp.int32)) \
        .lower(lowering_platforms=("tpu",)).compile()
    assert growmod.last_plan == {"partition": "wide",
                                 "payload": "f32-planar"}
    hlo = compiled.as_text()
    rows2 = 2 * (n + 2 * cfg.chunk)                    # 2(n+2K)
    copies = re.findall(r"= (f32\[([\d,]+)\]\S*) copy\(", hlo)
    assert copies, "the optimized HLO names no f32 copy at all: the " \
        "pattern has rotted, not the program improved"
    whole = [shape for shape, dims in copies
             if math.prod(map(int, dims.split(","))) >= rows2]
    assert not whole, whole
    layouts = set(re.findall(r"f32\[%d\](\{[^}]*\})" % (2 * rows2), hlo))
    assert len(layouts) == 1, layouts
    # and the 2-D form is gone from the program altogether
    assert not re.search(r"f32\[%d,2\]" % rows2, hlo)
