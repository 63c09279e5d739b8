"""Pallas tile-kernel sweep tests: kernel (interpret mode) vs pure-jnp oracle
vs the independent scatter formulation (core.pb). Compiles for the chip are
in test_chip_compile.py."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Domain, pb, clustered_events, bucketing
from repro.core import kernels_math as km
from repro.kernels import stkde_tiled
from repro.kernels.ref import stkde_tiles_ref
from repro.kernels.stkde_tile import stkde_tiles_pallas


def _make(dom, n, seed):
    return clustered_events(n, dom, seed=seed)


# ----------------------------------------------------------- shape sweeps
TILE_CASES = [
    # (grid, hs, ht, tile)
    ((33, 25, 17), 3.0, 2.0, (8, 8, 8)),
    ((32, 32, 16), 4.0, 1.0, (16, 16, 8)),
    ((64, 48, 12), 6.0, 3.0, (32, 16, 4)),
    ((17, 19, 23), 2.0, 2.0, (8, 8, 16)),  # ragged: tiles overhang the grid
    ((40, 40, 8), 5.0, 1.0, (40, 40, 8)),  # single tile
]


@pytest.mark.parametrize("grid,hs,ht,tile", TILE_CASES)
def test_kernel_vs_scatter_sweep(grid, hs, ht, tile):
    dom = Domain(
        gx=float(grid[0]), gy=float(grid[1]), gt=float(grid[2]),
        sres=1.0, tres=1.0, hs=hs, ht=ht,
    )
    pts = _make(dom, 400, seed=hash(grid) % 1000)
    want = np.asarray(pb(pts, dom))
    got = np.asarray(stkde_tiled(pts, dom, tile=tile, mode="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("chunk", [8, 64, 256])
def test_kernel_chunk_sizes(chunk):
    dom = Domain(gx=32, gy=32, gt=16, sres=1.0, tres=1.0, hs=3.0, ht=2.0)
    pts = _make(dom, 600, seed=11)
    want = np.asarray(stkde_tiled(pts, dom, use_ref=True))
    got = np.asarray(stkde_tiled(pts, dom, chunk=chunk, mode="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)


def test_kernel_nonunit_resolution_and_origin():
    dom = Domain(
        gx=20.0, gy=15.0, gt=30.0, sres=0.6, tres=2.2, hs=2.0, ht=4.0,
        ox=-7.0, oy=3.0, ot=100.0,
    )
    rng = np.random.default_rng(4)
    pts = np.stack(
        [
            -7.0 + rng.random(300) * 20.0,
            3.0 + rng.random(300) * 15.0,
            100.0 + rng.random(300) * 30.0,
        ],
        axis=1,
    ).astype(np.float32)
    want = np.asarray(pb(pts, dom))
    got = np.asarray(stkde_tiled(pts, dom, mode="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)


def test_kernel_paper_verbatim_kernel_funcs():
    dom = Domain(gx=24, gy=24, gt=12, sres=1.0, tres=1.0, hs=3.0, ht=2.0)
    pts = _make(dom, 200, seed=13)
    kw = dict(ks=km.ks_paper_verbatim, kt=km.kt_paper_verbatim)
    want = np.asarray(pb(pts, dom, variant="sym", **kw))
    got = np.asarray(stkde_tiled(pts, dom, mode="interpret", **kw))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(1, 300),
    hs=st.floats(1.0, 5.0),
    ht=st.floats(1.0, 3.0),
    seed=st.integers(0, 99),
)
def test_property_kernel_equals_scatter(n, hs, ht, seed):
    dom = Domain(gx=26, gy=22, gt=18, sres=1.0, tres=1.0, hs=hs, ht=ht)
    pts = _make(dom, n, seed=seed)
    want = np.asarray(pb(pts, dom))
    got = np.asarray(stkde_tiled(pts, dom, mode="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_empty_tiles_are_zero():
    """Points concentrated in one corner leave far tiles exactly zero."""
    dom = Domain(gx=64, gy=64, gt=16, sres=1.0, tres=1.0, hs=2.0, ht=1.0)
    pts = np.full((50, 3), 3.0, dtype=np.float32)
    grid = np.asarray(stkde_tiled(pts, dom, mode="interpret"))
    assert grid[10:, 10:, :].sum() == 0.0
    assert grid[:8, :8, :8].sum() > 0


def test_dtype_is_f32_accumulation():
    dom = Domain(gx=16, gy=16, gt=8, sres=1.0, tres=1.0, hs=2.0, ht=1.0)
    pts = _make(dom, 100, seed=17)
    out = stkde_tiled(pts, dom, mode="interpret")
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("mode", ["compiled", "auto"])
def test_kernel_interprets_only_on_request(mode):
    """Off the TPU, any mode but "interpret" raises instead of silently
    falling back to the interpreter."""
    dom = Domain(gx=16, gy=16, gt=8, sres=1.0, tres=1.0, hs=2.0, ht=1.0)
    pts = _make(dom, 50, seed=19)
    with pytest.raises(ValueError, match="interpret"):
        stkde_tiled(pts, dom, mode=mode)
