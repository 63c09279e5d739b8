"""The one generator of the benchmark's inputs.

Events are a clustered space-time point process (a copy of the program's
``repro.core.datasets.clustered_events``) with one change: the geography,
that is the cluster centres and their Zipf sizes, comes from the
configuration's own seed. A real data set's cities do not move between
queries; with the geography fixed, every seed gives the same number of
events per cluster and the same work.

What ``--seed`` draws is the mix's ``seed_draws``: ``"events"``, the events
inside the geography; or ``"order"``, the order of one event set drawn from
the configuration's seed. The second is for a path whose compiled shapes
follow the data (bucket capacities), where new events would mean a new
compile in every run.

A traffic mix is a data file under ``bench/traffic/`` that this module
reads; no mix needs code of its own. Every mix is one caller in a closed
loop over the configuration's whole event set; the harness drives it.
"""
from __future__ import annotations

import numpy as np


def events(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """The configuration's ``(n, 3)`` float32 events ``[x, y, t]`` for one
    run."""
    if traffic.get("seed_draws") not in ("events", "order"):
        raise ValueError(f"traffic seed_draws {traffic.get('seed_draws')!r}"
                         " is not 'events' or 'order'")
    n = cfg["n"]
    geo = cfg["geography"]
    if traffic["seed_draws"] == "events":
        return clustered_events(n, cfg, geo["seed"], seed, geo["clusters"],
                                geo["cluster_frac"])
    pts = clustered_events(n, cfg, geo["seed"], geo["seed"],
                           geo["clusters"], geo["cluster_frac"])
    return pts[np.random.default_rng(seed).permutation(n)]


def box(cfg: dict):
    """(lo, span) of the domain box in domain units; the origin is 0."""
    gx, gy, gt = cfg["grid"]
    span = np.array([gx * cfg["sres"], gy * cfg["sres"], gt * cfg["tres"]])
    return np.zeros(3), span


def geography(n: int, cfg: dict, geo_seed: int, n_clusters: int,
              cluster_frac: float):
    """(centres, sizes): where the clusters lie and how many events each
    holds (Zipf weights), fixed by ``geo_seed``."""
    lo, span = box(cfg)
    geo = np.random.default_rng(geo_seed)
    centers = lo + geo.random((n_clusters, 3)) * span
    w = 1.0 / np.arange(1, n_clusters + 1)
    w /= w.sum()
    return centers, geo.multinomial(int(n * cluster_frac), w)


def clustered_events(n: int, cfg: dict, geo_seed: int, seed: int,
                     n_clusters: int, cluster_frac: float) -> np.ndarray:
    lo, span = box(cfg)
    centers, sizes = geography(n, cfg, geo_seed, n_clusters, cluster_frac)
    n_c = int(sizes.sum())
    sigma_s = max(span[0], span[1]) / 40.0
    sigma_t = span[2] / 30.0

    rng = np.random.default_rng(seed)
    parts = []
    for c, s in zip(centers, sizes):
        if s == 0:
            continue
        p = np.empty((s, 3))
        p[:, 0] = rng.normal(c[0], sigma_s, s)
        p[:, 1] = rng.normal(c[1], sigma_s, s)
        # seasonal: the cluster's time plus a bounded harmonic and noise
        p[:, 2] = c[2] + sigma_t * np.sin(rng.normal(0, 1.2, s)) + rng.normal(
            0, sigma_t / 3, s)
        parts.append(p)
    n_bg = n - n_c
    if n_bg:
        parts.append(lo + rng.random((n_bg, 3)) * span)
    pts = np.concatenate(parts, axis=0)[:n]
    hi = lo + span * (1 - 1e-3)
    return np.clip(pts, lo, hi).astype(np.float32)
