"""Compiles of the main path for a TPU v5e that is described, not attached.

Each test compiles one program at a published Table 2 size with the chip's
own compiler (Mosaic for the Pallas kernel), which refuses what interpret
mode cannot see: blocks that break the (8, 128) rule, kernels that need
more VMEM than a kernel may use, programs that do not fit in HBM. Nothing
runs. The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import bucketing, get_instance
from repro.core import kernels_math as km
from repro.core.pb import _pb_impl
from repro.distributed import stkde_dist
from repro.kernels import default_tile
from repro.kernels.stkde_tile import CHUNK, stkde_tiles_pallas

HBM_BYTES = 16e9          # one v5e chip
POLLEN_PANELS = 4608      # PollenUS_Hr-Lb's 512-slot panels, step-rounded


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_scatter_pb_compiles_at_pollen_size(one_chip):
    inst = get_instance("PollenUS_Hr-Lb")
    pts = jax.ShapeDtypeStruct((inst.n, 3), jnp.float32, sharding=one_chip)
    compiled = _pb_impl.lower(pts, inst.domain(), "sym", km.DEFAULT_KS,
                              km.DEFAULT_KT, 1 << 22, None).compile()
    _fits(compiled)


def test_tile_kernel_compiles_at_pollen_size(one_chip):
    inst = get_instance("PollenUS_Hr-Lb")
    dom = inst.domain()
    tile = default_tile(dom)
    ntiles = bucketing.num_tiles(dom, tile)
    assert ntiles == (21, 10, 6)
    lanes = jax.ShapeDtypeStruct((3, POLLEN_PANELS * CHUNK), jnp.float32,
                                 sharding=one_chip)
    tile_of = jax.ShapeDtypeStruct((POLLEN_PANELS,), jnp.int32,
                                   sharding=one_chip)
    compiled = stkde_tiles_pallas.lower(lanes, tile_of, dom, tile,
                                        inst.n).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("strategy", ["pd", "dd"])
def test_mesh_strategy_compiles_on_2x2(topo, strategy):
    inst = get_instance("Flu_Mr-Hb")
    dom, pts = inst.domain(), inst.points()
    axes = ("data", "model")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), axes)
    local = (-(-dom.Gx // 2), -(-dom.Gy // 2), dom.Gt)
    bucket = {"pd": bucketing.bucket_points_home,
              "dd": bucketing.bucket_points_overlap}[strategy]
    cap = bucket(pts, dom, local).cap
    build = {"pd": stkde_dist.build_pd, "dd": stkde_dist.build_dd}[strategy]
    shard = NamedSharding(mesh, P(*axes))
    args = (jax.ShapeDtypeStruct((2, 2, cap, 3), jnp.float32, sharding=shard),
            jax.ShapeDtypeStruct((2, 2, cap), jnp.float32, sharding=shard))
    compiled = build(dom, mesh, axes, len(pts)).lower(*args).compile()
    _fits(compiled)
    if strategy == "pd":
        assert "collective-permute" in compiled.as_text()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placed_from_outside(from_env, tmp_path, monkeypatch):
    """The entry points' cache lives where JAX_COMPILATION_CACHE_DIR says,
    else at a fixed ``.jax_cache/`` in the checkout; compiles land there."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro import compile_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.ROOT_CACHE)
        assert compile_cache.ROOT_CACHE.parent.joinpath("src").is_dir()
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        if from_env:
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            compilation_cache.reset_cache()
            jax.jit(lambda x: x * 3.0 + 1.0).lower(
                jnp.ones((7,), jnp.float32)).compile()
            assert any(tmp_path.iterdir())
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
