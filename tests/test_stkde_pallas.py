"""Pallas tile-kernel sweep tests: kernel (interpret mode) vs pure-jnp oracle
vs the independent scatter formulation (core.pb). Compiles for the chip are
in test_chip_compile.py."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Domain, pb, clustered_events, bucketing
from repro.core import kernels_math as km
from repro.kernels import stkde_tiled, tiled_inputs
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


@pytest.mark.parametrize("chunk", [8, 64, 128, 512])
def test_kernel_chunk_sizes(chunk):
    """Every panel size gives the oracle's grid and the scatter's."""
    dom = Domain(gx=32, gy=32, gt=16, sres=1.0, tres=1.0, hs=3.0, ht=2.0)
    pts = _make(dom, 600, seed=11)
    lanes, tile_of, tile = tiled_inputs(pts, dom, chunk=chunk)
    want = stkde_tiles_ref(jnp.asarray(lanes), jnp.asarray(tile_of), dom,
                           tile, len(pts))
    want = np.asarray(want)[: dom.Gx, : dom.Gy, : dom.Gt]
    got = np.asarray(stkde_tiled(pts, dom, chunk=chunk, mode="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(got, np.asarray(pb(pts, dom)),
                               rtol=1e-5, atol=1e-8)


# ---------------------------------------------------------- packed layout
def _packed(pts, dom, tile, chunk):
    """The kernel's padded grid and the oracle's from one packing."""
    lanes, tile_of, tile = tiled_inputs(pts, dom, tile, chunk)
    got = stkde_tiles_pallas(jnp.asarray(lanes), jnp.asarray(tile_of), dom,
                             tile, len(pts), mode="interpret")
    ref = stkde_tiles_ref(jnp.asarray(lanes), jnp.asarray(tile_of), dom,
                          tile, len(pts))
    return lanes, tile_of, np.asarray(got), np.asarray(ref)


def _panels_per_tile(pts, dom, tile, chunk):
    counts = bucketing.bucket_points_overlap(pts, dom, tile).counts
    return counts.reshape(-1), np.maximum(1, -(-counts.reshape(-1) // chunk))


def test_packed_empty_tiles_get_one_parked_panel_and_are_zero():
    """Tiles no cylinder reaches still get a panel, and come out exactly
    zero."""
    dom = Domain(gx=64, gy=64, gt=16, sres=1.0, tres=1.0, hs=2.0, ht=1.0)
    tile = (8, 8, 8)
    rng = np.random.default_rng(3)
    pts = (2.0 + rng.random((80, 3)) * 10.0).astype(np.float32)
    counts, _ = _panels_per_tile(pts, dom, tile, 128)
    assert (counts == 0).sum() > 100
    lanes, tile_of, got, ref = _packed(pts, dom, tile, 128)
    assert set(tile_of.tolist()) == set(range(counts.size))
    g = got[: dom.Gx, : dom.Gy, : dom.Gt]
    assert (g[24:, :, :] == 0.0).all() and (g[:, 24:, :] == 0.0).all()
    assert g.sum() > 0
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(g, np.asarray(pb(pts, dom)),
                               rtol=1e-5, atol=1e-8)


def test_hot_tile_spans_many_panels_beside_partial_ones():
    """One tile's copies run over many panels; its neighbours hold one
    partial panel each; all of them accumulate the scatter's grid."""
    dom = Domain(gx=32, gy=32, gt=8, sres=1.0, tres=1.0, hs=2.0, ht=1.0)
    tile = (8, 8, 8)
    rng = np.random.default_rng(5)
    hot = 11.0 + rng.random((1500, 3)) * np.array([2.0, 2.0, 6.0])
    cold = rng.random((40, 3)) * np.array([32.0, 32.0, 8.0])
    pts = np.concatenate([hot, cold]).astype(np.float32)
    counts, per_tile = _panels_per_tile(pts, dom, tile, 128)
    assert per_tile.max() >= 12
    assert ((counts > 0) & (counts < 128)).sum() >= 4
    lanes, tile_of, got, ref = _packed(pts, dom, tile, 128)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(got[: dom.Gx, : dom.Gy, : dom.Gt],
                               np.asarray(pb(pts, dom)), rtol=1e-5, atol=1e-8)


def test_panel_count_rounding_shares_a_shape_and_changes_nothing():
    """Event sets of near-equal load pack into one panel count, and the
    parked panels the rounding adds leave the grid bit for bit."""
    dom = Domain(gx=40, gy=32, gt=16, sres=1.0, tres=1.0, hs=3.0, ht=2.0)
    tile, chunk = (8, 8, 8), 128
    shapes = set()
    for seed in (21, 22):
        pts = _make(dom, 500, seed=seed)
        lanes, tile_of, got, _ = _packed(pts, dom, tile, chunk)
        shapes.add(lanes.shape)
        _, per_tile = _panels_per_tile(pts, dom, tile, chunk)
        raw = int(per_tile.sum())
        assert len(tile_of) == bucketing.round_up(raw, bucketing.PANEL_STEP)
        assert len(tile_of) > raw
        assert (lanes[:, raw * chunk:] == bucketing.PARK).all()
        assert (tile_of[raw:] == per_tile.size - 1).all()
        bare = stkde_tiles_pallas(
            jnp.asarray(lanes[:, : raw * chunk]), jnp.asarray(tile_of[:raw]),
            dom, tile, len(pts), mode="interpret")
        np.testing.assert_array_equal(got, np.asarray(bare))
    assert len(shapes) == 1


@pytest.mark.parametrize("grid,hs,ht,tile", TILE_CASES)
def test_panel_table_is_contiguous_per_tile(grid, hs, ht, tile):
    """Each tile owns one run of panels, in tile order, as many as its
    load needs (at least one); the run holds its dense bucket's copies in
    the bucket's order, then parked slots."""
    dom = Domain(gx=float(grid[0]), gy=float(grid[1]), gt=float(grid[2]),
                 sres=1.0, tres=1.0, hs=hs, ht=ht)
    pts = _make(dom, 400, seed=sum(grid))
    chunk = 16
    lanes, tile_of = bucketing.bucket_points_panels(pts, dom, tile, chunk)
    dense = bucketing.bucket_points_overlap(pts, dom, tile)
    counts = dense.counts.reshape(-1)
    per_tile = np.maximum(1, -(-counts // chunk))
    assert tile_of.dtype == np.int32 and lanes.dtype == np.float32
    assert lanes.shape == (3, len(tile_of) * chunk)
    assert (np.diff(tile_of) >= 0).all()
    runs = np.bincount(tile_of, minlength=counts.size)
    np.testing.assert_array_equal(runs[:-1], per_tile[:-1])
    assert runs[-1] >= per_tile[-1]
    first = np.concatenate([[0], np.cumsum(runs)[:-1]]) * chunk
    flat = dense.points.reshape(counts.size, dense.cap, 3)
    for f, c in enumerate(counts):
        run = lanes[:, first[f]: first[f] + runs[f] * chunk]
        np.testing.assert_array_equal(run[:, :c].T, flat[f, :c])
        assert (run[:, c:] == bucketing.PARK).all()


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
