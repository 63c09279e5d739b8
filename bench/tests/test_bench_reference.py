"""The copied VB reference against the program on a tiny CPU instance,
and the control's reading against the limit."""
import numpy as np
import pytest

from bench import reference, traffic
from bench.tests.tiny import TINY, TINY_X4

BUILD = {"seed_draws": "events"}
LIMIT = 1e-4


def brute_force(points, cfg, voxels):
    """Algorithm 1 with every event against every voxel."""
    p = points.astype(np.float64)
    hs, ht = reference.bandwidths(cfg)
    out = []
    for xc, yc, tc in reference.voxel_centers(cfg, voxels):
        u, v, w = (xc - p[:, 0]) / hs, (yc - p[:, 1]) / hs, (tc - p[:, 2]) / ht
        r2 = u * u + v * v
        ks = np.where(r2 < 1, 2 / np.pi * (1 - r2) ** 2, 0)
        kt = np.where(np.abs(w) < 1, 0.75 * (1 - w * w), 0)
        out.append((ks * kt).sum())
    return np.array(out) / (len(p) * hs * hs * ht)


def program_grid(points, cfg):
    from repro.core.api import stkde
    from bench.harness import make_domain

    return np.asarray(stkde(points, make_domain(cfg)))


@pytest.mark.parametrize("seed", [3, 2**35 + 1])
def test_reference_matches_brute_force_and_program(seed):
    pts = traffic.events(TINY, BUILD, seed)
    grid = program_grid(pts, TINY)
    peak = np.unravel_index(int(grid.argmax()), grid.shape)
    vox = reference.sample_voxels(TINY, pts, peak, seed)
    want = reference.vb_reference(pts, TINY, vox)
    np.testing.assert_allclose(want, brute_force(pts, TINY, vox),
                               rtol=1e-12, atol=1e-18)
    # every voxel of the grid, not only the sampled ones
    g = np.stack(np.meshgrid(*map(np.arange, TINY["grid"]), indexing="ij"),
                 -1).reshape(-1, 3)
    full = reference.vb_reference(pts, TINY, g).reshape(TINY["grid"])
    assert reference.max_err_rel(grid.reshape(-1), full.reshape(-1)) < LIMIT
    assert reference.max_err_rel(grid[tuple(vox.T)], want) < LIMIT


def test_control_in_bfloat16_fails_the_limit():
    for seed in (5, 6, 7):
        pts = traffic.events(TINY, BUILD, seed)
        grid = program_grid(pts, TINY)
        vox = reference.sample_voxels(
            TINY, pts, np.unravel_index(int(grid.argmax()), grid.shape), seed)
        want = reference.vb_reference(pts, TINY, vox)
        assert reference.max_err_rel(grid[tuple(vox.T)], want) < LIMIT
        assert reference.max_err_rel(
            reference.vb_control(pts, TINY, vox), want) > 3 * LIMIT


def test_samples_cover_peak_faces_seams_and_blocks():
    pts = traffic.events(TINY_X4, BUILD, 1)
    vox = reference.sample_voxels(TINY_X4, pts, (5, 6, 7), 1)
    g = np.array(TINY_X4["grid"])
    assert (vox == [5, 6, 7]).all(axis=1).any()
    assert np.all((vox >= 0) & (vox < g))
    for axis in range(3):
        assert (vox[:, axis] == 0).any() and (vox[:, axis] == g[axis] - 1).any()
    for axis, s in reference.seams(TINY_X4):
        assert (vox[:, axis] == s - 1).sum() >= 32
        assert (vox[:, axis] == s).sum() >= 32
    for lo, hi in reference.blocks(TINY_X4):
        assert np.all((vox >= lo) & (vox < hi), axis=1).sum() >= 32
    assert reference.seams(TINY) == []


def test_max_err_rel_reads_nonfinite_as_infinite():
    assert reference.max_err_rel(np.array([np.nan, 1.0]),
                                 np.array([1.0, 1.0])) == np.inf
