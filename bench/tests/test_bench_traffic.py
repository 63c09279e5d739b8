"""The generator: fixed geography, events from the seed."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
BUILD = json.loads((BENCH / "traffic" / "build.json").read_text())


@pytest.mark.parametrize("config", ["pollenus_hr_lb", "flu_mr_hb_x4"])
def test_geography_fixed_events_from_seed(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    geo = cfg["geography"]
    centers, sizes = traffic.geography(cfg["n"], cfg, geo["seed"],
                                       geo["clusters"], geo["cluster_frac"])
    a = traffic.events(cfg, BUILD, 11)
    b = traffic.events(cfg, BUILD, 2**40 + 3)
    assert a.shape == b.shape == (cfg["n"], 3)
    assert a.dtype == np.float32
    assert not np.array_equal(a, b)
    assert np.array_equal(a, traffic.events(cfg, BUILD, 11))
    # the largest cluster's events sit around the same centre for both
    # seeds: the seed moves events, not the geography
    k = sizes[0]
    sigma = max(cfg["grid"][:2]) / 40.0
    for ev in (a, b):
        mean = ev[:k, :2].mean(axis=0)
        assert np.all(np.abs(mean - centers[0, :2]) < 4 * sigma / np.sqrt(k)
                      + 1.0)
    lo, span = traffic.box(cfg)
    assert np.all(a >= lo) and np.all(a < lo + span)


def test_same_events_in_an_order_from_the_seed():
    cfg = json.loads((BENCH / "configs" / "flu_mr_hb_x4.json").read_text())
    same = json.loads((BENCH / "traffic" / "build_same_events.json")
                      .read_text())
    a = traffic.events(cfg, same, 5)
    b = traffic.events(cfg, same, 2**33 + 9)
    assert not np.array_equal(a, b)
    key = lambda p: p[np.lexsort(p.T)]
    assert np.array_equal(key(a), key(b))


def test_traffic_beyond_the_generator_is_refused():
    cfg = json.loads((BENCH / "configs" / "pollenus_hr_lb.json").read_text())
    with pytest.raises(ValueError):
        traffic.events(cfg, dict(BUILD, seed_draws="geography"), 1)
    with pytest.raises(ValueError):
        traffic.events(cfg, {"about": "no seed_draws"}, 1)
