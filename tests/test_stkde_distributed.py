"""Multi-device STKDE strategy tests (subprocess with 8 fake host devices).

Every strategy must agree with the single-device PB-SYM reference to fp32
scatter-vs-reduction tolerance, across mesh shapes and bandwidths.
"""
import textwrap

import pytest

from util_subproc import run_with_devices

COMMON = textwrap.dedent(
    """
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core import Domain, pb, clustered_events
    from repro.distributed.stkde_dist import (
        stkde_dr, stkde_dd, stkde_pd, stkde_pd_xt, stkde_dd_lpt,
        stkde_hybrid)

    def check(got, want, tag, tol=5e-7):
        d = np.abs(np.asarray(got) - want).max()
        assert d < tol, f"{tag}: maxdiff {d}"
        print(tag, "ok", d)
    """
)


def test_all_strategies_match_reference():
    code = COMMON + textwrap.dedent(
        """
        dom = Domain(gx=48., gy=40., gt=20., sres=1., tres=1., hs=3., ht=2.)
        pts = clustered_events(1500, dom, seed=5)
        want = np.asarray(pb(pts, dom))
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        check(stkde_dr(pts, dom, mesh), want, "dr")
        check(stkde_dd(pts, dom, mesh), want, "dd")
        check(stkde_pd(pts, dom, mesh), want, "pd")
        check(stkde_pd_xt(pts, dom, mesh), want, "pd_xt")
        check(stkde_dd_lpt(pts, dom, mesh), want, "dd_lpt")
        mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                              axis_types=(AxisType.Auto,)*3)
        check(stkde_hybrid(pts, dom, mesh3), want, "hybrid")
        from repro.distributed.stkde_dist import stkde_pd_xyt
        check(stkde_pd_xyt(pts, dom, mesh3), want, "pd_xyt")

        # prepared points arrive on the mesh already split: each device
        # holds its own shard, not the whole set
        from repro.distributed.stkde_dist import (
            prepare_dr, prepare_dd, prepare_pd)
        w2 = ("data", "model")
        full = prepare_dr(pts, dom, mesh, w2)
        assert {s.data.shape for s in full.addressable_shards} == {
            (full.shape[0] // 8, 3)}
        for prep in (prepare_dd, prepare_pd):
            for arr in prep(pts, dom, mesh, w2):
                shards = arr.addressable_shards
                assert len({s.device for s in shards}) == 8
                assert {s.data.shape[:2] for s in shards} == {(1, 1)}
        """
    )
    run_with_devices(code, 8)


def test_mesh_shape_sweep():
    code = COMMON + textwrap.dedent(
        """
        dom = Domain(gx=40., gy=36., gt=10., sres=1., tres=1., hs=2., ht=1.)
        pts = clustered_events(700, dom, seed=9)
        want = np.asarray(pb(pts, dom))
        for shape in [(1, 8), (8, 1), (2, 4)]:
            mesh = jax.make_mesh(shape, ("data", "model"),
                                 axis_types=(AxisType.Auto,)*2)
            check(stkde_dd(pts, dom, mesh), want, f"dd{shape}")
            check(stkde_pd(pts, dom, mesh), want, f"pd{shape}")
            check(stkde_pd_xt(pts, dom, mesh), want, f"pd_xt{shape}")
        """
    )
    run_with_devices(code, 8)


def test_nondivisible_grid_padding():
    """Grid dims not divisible by the device grid exercise the pad/slice."""
    code = COMMON + textwrap.dedent(
        """
        dom = Domain(gx=45., gy=34., gt=13., sres=1., tres=1., hs=2., ht=2.)
        pts = clustered_events(600, dom, seed=3)
        want = np.asarray(pb(pts, dom))
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        check(stkde_dd(pts, dom, mesh), want, "dd-pad")
        check(stkde_pd(pts, dom, mesh), want, "pd-pad")
        check(stkde_dd_lpt(pts, dom, mesh), want, "dd_lpt-pad")
        """
    )
    run_with_devices(code, 8)


def test_pd_rejects_too_small_subdomains():
    code = COMMON + textwrap.dedent(
        """
        dom = Domain(gx=16., gy=16., gt=8., sres=1., tres=1., hs=8., ht=2.)
        pts = clustered_events(100, dom, seed=1)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        try:
            stkde_pd(pts, dom, mesh)
        except ValueError as e:
            assert "bandwidth" in str(e)
            print("raised ok")
        else:
            raise AssertionError("expected ValueError")
        """
    )
    run_with_devices(code, 8)


def test_heavy_clustering_with_lpt():
    """All mass in one corner: worst case for block DD, fine for LPT."""
    code = COMMON + textwrap.dedent(
        """
        dom = Domain(gx=64., gy=64., gt=8., sres=1., tres=1., hs=3., ht=1.)
        rng = np.random.default_rng(0)
        pts = (rng.normal(8, 2.0, size=(2000, 3))
                 .clip(0.1, 60).astype(np.float32))
        pts[:, 2] = rng.uniform(0, 7.9, 2000)
        want = np.asarray(pb(pts, dom))
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        check(stkde_dd_lpt(pts, dom, mesh, tile=(16, 16, 8)), want, "lpt")
        check(stkde_dr(pts, dom, mesh), want, "dr")
        """
    )
    run_with_devices(code, 8)


def test_nocomm_builds_identical_on_single_device():
    """PD built with collectives=False is numerically identical to the
    full build on a 1-device mesh (empty ppermute perms contribute zeros,
    size-1 psum is the identity)."""
    code = COMMON + textwrap.dedent(
        """
        from repro.distributed.stkde_dist import (
            prepare_pd, build_pd, prepare_hybrid)

        dom = Domain(gx=48., gy=48., gt=16., sres=1., tres=1., hs=3., ht=2.)
        pts = clustered_events(1500, dom, seed=7)
        n = len(pts)
        mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,)*3)
        w2 = ("data", "model")

        args = prepare_pd(pts, dom, mesh, w2)
        full = np.asarray(build_pd(dom, mesh, w2, n)(*args))
        noc = np.asarray(build_pd(dom, mesh, w2, n,
                                  collectives=False)(*args))
        np.testing.assert_array_equal(full, noc)
        print("pd ok")

        args = prepare_hybrid(pts, dom, mesh, w2, rep_axis="pod")
        full = np.asarray(build_pd(dom, mesh, w2, n,
                                   rep_axis="pod")(*args))
        noc = np.asarray(build_pd(dom, mesh, w2, n, rep_axis="pod",
                                  collectives=False)(*args))
        assert noc.shape == (1,) + full.shape
        np.testing.assert_array_equal(full, noc[0])
        print("hybrid ok")
        """
    )
    run_with_devices(code, 1)


def test_nocomm_builds_differ_only_by_halo_terms_8dev():
    """On a real 2x2x2 mesh PD built with collectives=False differs from
    the full build only in the halo bands / rep-psum: subdomain interiors
    more than one bandwidth from a cut boundary are bitwise identical,
    and the boundary bands do differ (comm moves real mass)."""
    code = COMMON + textwrap.dedent(
        """
        from repro.distributed.stkde_dist import (
            prepare_pd, build_pd, prepare_hybrid)

        dom = Domain(gx=48., gy=48., gt=16., sres=1., tres=1., hs=3., ht=2.)
        pts = clustered_events(1500, dom, seed=7)
        n = len(pts)
        Hs = dom.Hs
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,)*3)
        w2 = ("data", "model")

        def split(tag, full, noc, interior):
            assert full.shape == noc.shape, (tag, full.shape, noc.shape)
            assert (full != noc).any(), tag + ": no halo mass moved"
            np.testing.assert_array_equal(
                full[interior], noc[interior], err_msg=tag)
            print(tag, "ok")

        # pd over the (2, 2) worker grid: 24x24 blocks, Hs-wide x/y halos
        args = prepare_pd(pts, dom, mesh, w2)
        full = np.asarray(build_pd(dom, mesh, w2, n)(*args))
        noc = np.asarray(build_pd(dom, mesh, w2, n,
                                  collectives=False)(*args))
        ix = np.s_[:, :, Hs:-Hs, Hs:-Hs, :]
        split("pd", full, noc, ix)

        # hybrid: nocomm is rep-stacked; away from halo bands the full
        # build is exactly the rep-sum of the unfolded partials
        args = prepare_hybrid(pts, dom, mesh, w2, rep_axis="pod")
        full = np.asarray(build_pd(dom, mesh, w2, n,
                                   rep_axis="pod")(*args))
        noc = np.asarray(build_pd(dom, mesh, w2, n, rep_axis="pod",
                                  collectives=False)(*args))
        assert noc.shape == (2,) + full.shape
        asm = noc.sum(axis=0)
        assert (full != asm).any(), "hybrid: no halo mass moved"
        ix = np.s_[:, :, Hs:-Hs, Hs:-Hs, :]
        np.testing.assert_allclose(
            full[ix], asm[ix], rtol=1e-6, atol=1e-8, err_msg="hybrid")
        print("hybrid ok")
        """
    )
    run_with_devices(code, 8)


def test_auto_api_on_mesh():
    code = COMMON + textwrap.dedent(
        """
        from repro.core.api import stkde
        dom = Domain(gx=48., gy=32., gt=16., sres=1., tres=1., hs=3., ht=2.)
        pts = clustered_events(900, dom, seed=2)
        want = np.asarray(pb(pts, dom))
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        check(stkde(pts, dom, mesh=mesh, strategy="auto"), want, "auto")
        """
    )
    run_with_devices(code, 8)
