"""Core STKDE algorithm tests: equivalence, properties, geometry."""
import math

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    Domain,
    vb,
    vb_dec,
    pb,
    clustered_events,
    bucketing,
    kernels_math as km,
)
from repro.core.geometry import from_points


def small_domain(hs=3.0, ht=2.0):
    return Domain(gx=24.0, gy=18.0, gt=14.0, sres=1.0, tres=1.0, hs=hs, ht=ht)


# --------------------------------------------------------------- equivalence
class TestEquivalence:
    def test_all_variants_match_vb(self):
        dom = small_domain()
        pts = clustered_events(300, dom, seed=0)
        gold = np.asarray(vb(jnp.asarray(pts), dom))
        for variant in ("pb", "disk", "bar", "sym"):
            got = np.asarray(pb(pts, dom, variant=variant))
            np.testing.assert_allclose(got, gold, rtol=1e-5, atol=1e-8)

    def test_vb_dec_matches_vb(self):
        dom = small_domain()
        pts = clustered_events(300, dom, seed=1)
        np.testing.assert_allclose(
            np.asarray(vb_dec(pts, dom)),
            np.asarray(vb(jnp.asarray(pts), dom)),
            rtol=1e-5,
            atol=1e-8,
        )

    @settings(max_examples=12, deadline=None)
    @given(
        hs=st.floats(0.6, 4.5),
        ht=st.floats(0.6, 3.5),
        sres=st.floats(0.5, 1.5),
        tres=st.floats(0.5, 1.5),
        n=st.integers(5, 120),
        seed=st.integers(0, 10_000),
    )
    def test_property_pb_equals_vb(self, hs, ht, sres, tres, n, seed):
        dom = Domain(
            gx=16.0, gy=12.0, gt=10.0, sres=sres, tres=tres, hs=hs, ht=ht
        )
        pts = clustered_events(n, dom, seed=seed)
        gold = np.asarray(vb(jnp.asarray(pts), dom))
        got = np.asarray(pb(pts, dom))
        np.testing.assert_allclose(got, gold, rtol=1e-4, atol=1e-7)

    def test_paper_verbatim_kernels_also_equivalent(self):
        dom = small_domain()
        pts = clustered_events(100, dom, seed=2)
        kw = dict(ks=km.ks_paper_verbatim, kt=km.kt_paper_verbatim)
        gold = np.asarray(vb(jnp.asarray(pts), dom, **kw))
        got = np.asarray(pb(pts, dom, variant="sym", **kw))
        np.testing.assert_allclose(got, gold, rtol=1e-5, atol=1e-8)


# ---------------------------------------------------------------- properties
class TestProperties:
    def test_mass(self):
        """Total mass ~ 2/3 for interior points (kernel integral; DESIGN §6)."""
        dom = Domain(
            gx=40.0, gy=40.0, gt=40.0, sres=0.25, tres=0.25, hs=4.0, ht=4.0
        )
        rng = np.random.default_rng(0)
        pts = (10 + 20 * rng.random((50, 3))).astype(np.float32)  # interior
        grid = np.asarray(pb(pts, dom))
        mass = grid.sum() * dom.sres**2 * dom.tres
        assert abs(mass - 2.0 / 3.0) < 0.02, mass

    def test_nonnegative_and_finite(self):
        dom = small_domain()
        pts = clustered_events(500, dom, seed=3)
        grid = np.asarray(pb(pts, dom))
        assert np.isfinite(grid).all()
        assert (grid >= 0).all()

    def test_translation_invariance(self):
        """Shifting points and origin by whole voxels shifts the grid."""
        dom = small_domain()
        pts = clustered_events(80, dom, seed=4)
        g0 = np.asarray(pb(pts, dom))
        import dataclasses

        dom2 = dataclasses.replace(dom, ox=dom.ox + 5.0)  # +5 voxels in x
        g1 = np.asarray(pb(pts + np.array([5.0, 0, 0], np.float32), dom2))
        np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-8)

    def test_single_point_peak_location(self):
        dom = small_domain()
        pts = np.array([[12.5, 9.5, 7.5]], dtype=np.float32)
        grid = np.asarray(pb(pts, dom))
        assert np.unravel_index(grid.argmax(), grid.shape) == (12, 9, 7)

    def test_boundary_points_no_crash_no_nan(self):
        dom = small_domain()
        pts = np.array(
            [[0.01, 0.01, 0.01], [23.9, 17.9, 13.9], [0.0, 17.99, 7.0]],
            dtype=np.float32,
        )
        for variant in ("pb", "sym"):
            grid = np.asarray(pb(pts, dom, variant=variant))
            assert np.isfinite(grid).all()
            # boundary points lose part of their cylinder -> less mass
            assert grid.sum() > 0

    def test_superposition(self):
        """Density is a sum over points (linearity in the point set)."""
        dom = small_domain()
        pts = clustered_events(40, dom, seed=5)
        g_all = np.asarray(pb(pts, dom)) * len(pts)
        g_sum = sum(
            np.asarray(pb(pts[i : i + 1], dom)) for i in range(len(pts))
        )
        np.testing.assert_allclose(g_all, g_sum, rtol=1e-4, atol=1e-7)


# ------------------------------------------------------------------ geometry
class TestGeometry:
    def test_grid_shape_ceil(self):
        dom = Domain(gx=10.1, gy=8.0, gt=3.5, sres=1.0, tres=1.0, hs=2, ht=1)
        assert dom.grid_shape == (11, 8, 4)
        assert dom.Hs == 2 and dom.Ht == 1

    def test_from_points_contains_all(self):
        rng = np.random.default_rng(1)
        pts = rng.normal(100, 30, size=(200, 3)).astype(np.float32)
        dom = from_points(pts, sres=2.0, tres=3.0, hs=5.0, ht=6.0)
        vox = np.asarray(dom.point_voxels(jnp.asarray(pts)))
        assert (vox >= 0).all()
        assert (vox < np.array(dom.grid_shape)).all()

    @settings(max_examples=20, deadline=None)
    @given(
        sres=st.floats(0.3, 3.0),
        hs=st.floats(0.5, 6.0),
    )
    def test_voxel_bandwidth_covers_kernel_support(self, sres, hs):
        """Hs*sres >= hs: the voxel cylinder bbox covers the true support."""
        dom = Domain(gx=10, gy=10, gt=10, sres=sres, tres=1.0, hs=hs, ht=1.0)
        assert dom.Hs * sres >= hs - 1e-6


# ----------------------------------------------------------------- bucketing
def _dense_overlap_before(pts, dom, tile, cap):
    """Overlap bucketing as it was written before its copy enumeration was
    shared with the panel layout: every (point, tile offset) as an
    (n, S, 3) index array, masked, into ``_densify``."""
    pts = np.asarray(pts, dtype=np.float32)
    n = len(pts)
    nt = bucketing.num_tiles(dom, tile)
    vox = bucketing._point_voxels_np(pts, dom)
    H = np.array([dom.Hs, dom.Hs, dom.Ht])
    B, NT = np.array(tile), np.array(nt)
    lo = np.clip((vox - H) // B, 0, NT - 1)
    hi = np.clip((vox + H) // B, 0, NT - 1)
    span = hi - lo + 1
    smax = span.max(axis=0)
    offs = np.stack(np.meshgrid(*(np.arange(m) for m in smax),
                                indexing="ij"), axis=-1).reshape(-1, 3)
    tids = lo[:, None, :] + offs[None, :, :]
    ok = (offs[None, :, :] < span[:, None, :]).all(axis=-1)
    flat = (tids[..., 0] * nt[1] + tids[..., 1]) * nt[2] + tids[..., 2]
    sel = ok.reshape(-1)
    ids = flat.reshape(-1)[sel]
    pts_rep = np.broadcast_to(pts[:, None, :], tids.shape).reshape(-1, 3)[sel]
    return bucketing._densify(ids, pts_rep, nt, cap, n, tile, "overlap")


class TestBucketing:
    def test_home_counts_sum_to_n(self):
        dom = small_domain()
        pts = clustered_events(500, dom, seed=6)
        b = bucketing.bucket_points_home(pts, dom, (8, 8, 8))
        assert b.counts.sum() == 500
        assert b.valid.sum() == 500

    def test_overlap_superset_of_home(self):
        dom = small_domain()
        pts = clustered_events(200, dom, seed=7)
        bh = bucketing.bucket_points_home(pts, dom, (8, 8, 8))
        bo = bucketing.bucket_points_overlap(pts, dom, (8, 8, 8))
        assert bo.counts.sum() >= bh.counts.sum()
        assert bo.replication_factor >= 1.0

    def test_overlap_covers_every_affected_tile(self):
        """A point's kernel support never leaks outside its overlap tiles."""
        dom = small_domain(hs=3.0, ht=2.0)
        pts = np.array([[11.7, 8.2, 6.9]], dtype=np.float32)
        tile = (8, 8, 4)
        b = bucketing.bucket_points_overlap(pts, dom, tile)
        g_full = np.asarray(pb(pts, dom))
        covered = np.zeros(dom.grid_shape, dtype=bool)
        ntx, nty, ntt = b.ntiles
        for i in range(ntx):
            for j in range(nty):
                for k in range(ntt):
                    if b.counts[i, j, k]:
                        covered[
                            i * tile[0] : (i + 1) * tile[0],
                            j * tile[1] : (j + 1) * tile[1],
                            k * tile[2] : (k + 1) * tile[2],
                        ] = True
        assert (g_full[~covered] == 0).all()

    @pytest.mark.parametrize("grid,hs,ht,tile,cap", [
        ((24, 18, 14), 3.0, 2.0, (8, 8, 8), None),
        ((17, 19, 23), 2.0, 2.0, (8, 8, 16), None),   # tiles overhang
        ((40, 40, 8), 5.0, 1.0, (40, 40, 8), 1024),   # one tile, given cap
        ((48, 40, 20), 4.0, 3.0, (24, 20, 20), None),  # a 2x2 DD layout
    ])
    def test_overlap_buckets_unchanged_by_the_shared_enumeration(
            self, grid, hs, ht, tile, cap):
        """The mesh strategies' dense overlap buckets are bit-identical to
        those of the enumeration before the panel layout shared it."""
        dom = Domain(gx=float(grid[0]), gy=float(grid[1]),
                     gt=float(grid[2]), sres=1.0, tres=1.0, hs=hs, ht=ht)
        pts = clustered_events(700, dom, seed=sum(grid))
        got = bucketing.bucket_points_overlap(pts, dom, tile, cap=cap)
        want = _dense_overlap_before(pts, dom, tile, cap)
        for field in ("points", "valid", "counts"):
            a, b = getattr(got, field), getattr(want, field)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
        assert got.cap == want.cap
        assert got.replication_factor == want.replication_factor

    def test_capacity_overflow_raises(self):
        dom = small_domain()
        pts = clustered_events(100, dom, seed=8)
        with pytest.raises(ValueError):
            bucketing.bucket_points_home(pts, dom, (8, 8, 8), cap=1)
