"""The plain reference that decides ``correct``, and its control.

``vb_reference`` is the paper's Algorithm 1 (VB) in float64 NumPy, a copy
of the one in ``chip_smoke.py`` with a column index added so that a voxel
tests only the events within one bandwidth of it (the kernel is zero
beyond). It imports nothing of the program and takes nothing it made.

``vb_control`` is the same sum with the kernel values and the sum in
bfloat16, the step below the float32 that the configurations state. A
comparison that does not fail it cannot tell a float32 grid from a
bfloat16 one.

``sample_voxels`` says where to look; ``max_err_rel`` is the number
compared: the largest gap between the program's grid and the reference at
the sampled voxels, as a share of the reference's largest value there.
"""
from __future__ import annotations

import math

import numpy as np


def bandwidths(cfg: dict):
    return cfg["Hs"] * cfg["sres"], cfg["Ht"] * cfg["tres"]


def voxel_centers(cfg: dict, voxels: np.ndarray) -> np.ndarray:
    res = np.array([cfg["sres"], cfg["sres"], cfg["tres"]])
    return (np.asarray(voxels, np.float64) + 0.5) * res


def vb_reference(points: np.ndarray, cfg: dict,
                 voxels: np.ndarray) -> np.ndarray:
    """Density at ``voxels`` (m, 3) by Algorithm 1, in float64."""
    p = np.asarray(points, np.float64)
    hs, ht = bandwidths(cfg)
    # columns of hs x hs: every event within hs of a voxel lies in the
    # voxel's column or one of its eight neighbours
    cx = np.floor(p[:, 0] / hs).astype(np.int64)
    cy = np.floor(p[:, 1] / hs).astype(np.int64)
    stride = int(cy.max()) + 3
    key = (cx + 1) * stride + (cy + 1)
    order = np.argsort(key, kind="stable")
    key, ps = key[order], p[order]
    out = np.empty(len(voxels))
    for i, (xc, yc, tc) in enumerate(voxel_centers(cfg, voxels)):
        bx, by = math.floor(xc / hs) + 1, math.floor(yc / hs) + 1
        rows = []
        for gx in (bx - 1, bx, bx + 1):
            lo = np.searchsorted(key, gx * stride + by - 1, "left")
            hi = np.searchsorted(key, gx * stride + by + 1, "right")
            rows.append(ps[lo:hi])
        q = np.concatenate(rows)
        u = (xc - q[:, 0]) / hs
        v = (yc - q[:, 1]) / hs
        w = (tc - q[:, 2]) / ht
        r2 = u * u + v * v
        ks = np.where(r2 < 1.0, 2.0 / np.pi * (1.0 - r2) ** 2, 0.0)
        kt = np.where(np.abs(w) < 1.0, 0.75 * (1.0 - w * w), 0.0)
        out[i] = (ks * kt).sum()
    return out / (len(p) * hs * hs * ht)


def vb_control(points: np.ndarray, cfg: dict, voxels: np.ndarray,
               block: int = 64) -> np.ndarray:
    """Algorithm 1 with the kernel values, their products and the sum in
    bfloat16 (distances stay float32), on JAX's default device."""
    import jax
    import jax.numpy as jnp

    hs, ht = bandwidths(cfg)
    norm = jnp.bfloat16(1.0 / (len(points) * hs * hs * ht))
    p = jnp.asarray(points, jnp.float32)
    c = voxel_centers(cfg, voxels).astype(np.float32)
    m = len(c)
    c = np.concatenate([c, np.zeros((-m % block, 3), np.float32)])

    @jax.jit
    def one(cb):
        u = (cb[:, None, 0] - p[None, :, 0]) / hs
        v = (cb[:, None, 1] - p[None, :, 1]) / hs
        w = (cb[:, None, 2] - p[None, :, 2]) / ht
        r2 = u * u + v * v
        ks = jnp.where(r2 < 1.0, (2.0 / np.pi) * (1.0 - r2) ** 2,
                       0.0).astype(jnp.bfloat16)
        kt = jnp.where(jnp.abs(w) < 1.0, 0.75 * (1.0 - w * w),
                       0.0).astype(jnp.bfloat16)
        return jnp.sum(ks * kt * norm, axis=1, dtype=jnp.bfloat16)

    out = [np.asarray(one(c[i:i + block]), np.float64)
           for i in range(0, len(c), block)]
    return np.concatenate(out)[:m]


def max_err_rel(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over the sampled voxels, as a share of the
    reference's largest value there. A non-finite value reads infinite."""
    got = np.asarray(got, np.float64)
    scale = float(np.max(np.abs(want)))
    if not np.all(np.isfinite(got)) or scale == 0.0:
        return math.inf
    return float(np.max(np.abs(got - want))) / scale


def _reach(cfg: dict) -> np.ndarray:
    return np.array([cfg["Hs"], cfg["Hs"], cfg["Ht"]])


def seams(cfg: dict):
    """(axis, index) of every voxel plane where one device's block of the
    grid ends and the next begins; none on one chip."""
    mesh = cfg.get("mesh")
    if not mesh:
        return []
    out = []
    for axis, parts in enumerate(mesh["shape"][:2]):
        size = math.ceil(cfg["grid"][axis] / parts)
        out += [(axis, k * size) for k in range(1, parts)]
    return out


def blocks(cfg: dict):
    """(lo, hi) voxel corners of each device's block of the grid."""
    g = np.array(cfg["grid"])
    mesh = cfg.get("mesh")
    if not mesh:
        return [(np.zeros(3, int), g)]
    a, b = mesh["shape"][:2]
    sx, sy = math.ceil(g[0] / a), math.ceil(g[1] / b)
    return [(np.array([i * sx, j * sy, 0]),
             np.minimum(g, [(i + 1) * sx, (j + 1) * sy, g[2]]))
            for i in range(a) for j in range(b)]


def sample_voxels(cfg: dict, points: np.ndarray, peak, seed: int,
                  per_part: int = 64) -> np.ndarray:
    """Voxels to compare, drawn from ``seed``: the densest voxel (``peak``,
    the argmax of the grid under test) and a box of one bandwidth around
    it; voxels near random events; the grid's six faces; both sides of
    every seam between devices' blocks; each device's block; and uniform
    voxels. Where events are near, samples sit near events, so that they
    read density and not empty space."""
    rng = np.random.default_rng([seed, 7])
    g = np.array(cfg["grid"])
    reach = _reach(cfg)
    res = np.array([cfg["sres"], cfg["sres"], cfg["tres"]])
    home = np.minimum(np.floor(np.asarray(points) / res).astype(int), g - 1)

    def jitter(base, k):
        off = rng.integers(-reach, reach + 1, size=(k, 3))
        return np.clip(base + off, 0, g - 1)

    def near_events(mask, k):
        idx = np.flatnonzero(mask)
        if len(idx) == 0:
            return None
        return jitter(home[rng.choice(idx, size=k)], k)

    peak = np.asarray(peak, int)
    parts = [peak[None], jitter(peak[None], 4 * per_part - 1),
             jitter(home[rng.integers(0, len(home), 4 * per_part)],
                    4 * per_part),
             rng.integers(0, g, size=(2 * per_part, 3))]
    for axis in range(3):
        for face in (0, g[axis] - 1):
            near = np.abs(home[:, axis] - face) <= reach[axis]
            v = near_events(near, per_part // 2)
            if v is None:
                v = rng.integers(0, g, size=(per_part // 2, 3))
            v[:, axis] = face
            parts.append(v)
    for axis, s in seams(cfg):
        near = np.abs(home[:, axis] - s) <= reach[axis]
        for side in (s - 1, s):
            v = near_events(near, per_part)
            if v is None:
                v = rng.integers(0, g, size=(per_part, 3))
            v[:, axis] = side
            parts.append(v)
    if len(blocks(cfg)) > 1:
        for lo, hi in blocks(cfg):
            inside = np.all((home >= lo) & (home < hi), axis=1)
            v = near_events(inside, per_part)
            if v is None:
                v = rng.integers(lo, hi, size=(per_part, 3))
            parts.append(np.clip(v, lo, hi - 1))
    return np.concatenate(parts).astype(np.int64)
