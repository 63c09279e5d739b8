"""The single-device path: ``plan.choose_single`` prices the XLA scatter
against the Pallas tile kernel from the call's shape, and ``stkde()`` takes
its answer unless ``use_tiled_kernel`` forces a path."""
import math

import jax
import numpy as np
import pytest

from repro import kernels, obs
from repro.core import INSTANCES, Domain, clustered_events, plan
from repro.core.api import stkde
from repro.obs import trace

# The faster path of each instance's second call, both paths forced, on one
# TPU v5e (PERF.md §6).
FASTER_ON_V5E = {
    "PollenUS_Hr-Lb": "tiled",
    "PollenUS_Lr-Lb": "pb",
    "Flu_Mr-Hb": "pb",
    "Flu_Mr-Lb": "pb",
    "Flu_Lr-Hb": "pb",
    "Flu_Lr-Lb": "pb",
    "Dengue_Lr-Lb": "pb",
    "Dengue_Lr-Hb": "tiled",
    "Dengue_Hr-Lb": "pb",
    "Dengue_Hr-Hb": "tiled",
    "Dengue_Hr-VHb": "tiled",
}

DOM = Domain(gx=30., gy=26., gt=12., sres=1., tres=1., hs=3., ht=2.)


@pytest.fixture
def on_tpu(monkeypatch):
    """The planner as it runs on a v5e: the TPU backend and its model."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(plan, "default_hw", lambda: plan.V5E)


@pytest.fixture
def interpret_kernel(monkeypatch):
    """``stkde()`` asks for the compiled kernel; on the CPU run its body
    under the Pallas interpreter instead."""
    compiled = kernels.stkde_tiled
    monkeypatch.setattr(kernels, "stkde_tiled", lambda *a, **kw: compiled(
        *a, **{**kw, "mode": "interpret"}))


def _root():
    (root,) = trace.get_tracer().spans("stkde")
    return root


@pytest.mark.parametrize("name", sorted(FASTER_ON_V5E))
def test_v5e_prices_pick_the_faster_path(on_tpu, name):
    inst = INSTANCES[name]
    path, prices = plan.choose_single(inst.domain(), inst.n)
    assert path == FASTER_ON_V5E[name]
    assert set(prices) == {"pb", "tiled"}
    assert all(0 < s < math.inf for s in prices.values())


@pytest.mark.parametrize("name", sorted(FASTER_ON_V5E))
@pytest.mark.parametrize("hw", ("default", "v5e"))
def test_off_the_tpu_always_the_scatter(name, hw):
    inst = INSTANCES[name]
    path, prices = plan.choose_single(
        inst.domain(), inst.n, plan.V5E if hw == "v5e" else None)
    assert path == "pb"
    if hw == "default":    # the CPU's model has no compiled tile kernel
        assert prices["tiled"] == math.inf and prices["pb"] > 0


def test_prices_follow_the_work():
    def prices(dom, n):
        return plan.choose_single(dom, n, plan.V5E)[1]

    base = prices(DOM, 1000)
    more = prices(DOM, 2000)
    assert more["pb"] == pytest.approx(2 * base["pb"])
    assert more["tiled"] > base["tiled"]
    wide = prices(
        Domain(gx=30., gy=26., gt=12., sres=1., tres=1., hs=6., ht=2.), 1000)
    # 13^2 against 7^2 in the disk: the scatter pays per cylinder update
    assert wide["pb"] == pytest.approx(base["pb"] * 13 ** 2 / 7 ** 2)


def test_default_build_on_the_cpu_takes_the_scatter():
    pts = clustered_events(400, DOM, seed=2)
    before = obs.counter("stkde.path.pb").value
    trace.reset()
    grid = stkde(pts, DOM)
    root = _root()
    assert root.attrs["path"] == "pb"
    assert root.attrs["priced_pb_s"] > 0
    assert root.attrs["priced_tiled_s"] == math.inf
    assert obs.counter("stkde.path.pb").value == before + 1
    (pl,) = trace.get_tracer().spans("stkde.api.plan")
    assert pl.parent_id == root.span_id
    assert trace.get_tracer().spans("stkde.pb")
    forced = stkde(pts, DOM, use_tiled_kernel=False)
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(forced))


def test_forced_scatter_skips_the_planner():
    pts = clustered_events(300, DOM, seed=4)
    before = obs.counter("stkde.path.pb").value
    trace.reset()
    stkde(pts, DOM, use_tiled_kernel=False)
    root = _root()
    assert root.attrs["path"] == "pb"
    assert "priced_pb_s" not in root.attrs
    assert not trace.get_tracer().spans("stkde.api.plan")
    assert obs.counter("stkde.path.pb").value == before + 1


def test_forced_tile_kernel(interpret_kernel):
    pts = clustered_events(300, DOM, seed=4)
    want = np.asarray(stkde(pts, DOM, use_tiled_kernel=False))
    before = obs.counter("stkde.path.tiled").value
    trace.reset()
    got = np.asarray(stkde(pts, DOM, use_tiled_kernel=True))
    root = _root()
    assert root.attrs["path"] == "tiled"
    assert not trace.get_tracer().spans("stkde.api.plan")
    assert trace.get_tracer().spans("stkde.tiled.dispatch")
    assert obs.counter("stkde.path.tiled").value == before + 1
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * want.max())


def test_default_build_takes_the_kernel_where_it_prices_cheaper(
        on_tpu, interpret_kernel):
    # wide cylinders on a one-tile grid: each point is one copy for the
    # kernel and 17^2 * 7 cylinder updates for the scatter
    dom = Domain(gx=16., gy=16., gt=8., sres=1., tres=1., hs=8., ht=3.)
    pts = clustered_events(2000, dom, seed=6)
    path, prices = plan.choose_single(dom, len(pts))
    assert path == "tiled" and prices["tiled"] < prices["pb"]
    want = np.asarray(stkde(pts, dom, use_tiled_kernel=False))
    before = obs.counter("stkde.path.tiled").value
    trace.reset()
    got = np.asarray(stkde(pts, dom))
    root = _root()
    assert root.attrs["path"] == "tiled"
    assert root.attrs["priced_tiled_s"] == prices["tiled"]
    assert root.attrs["priced_pb_s"] == prices["pb"]
    assert obs.counter("stkde.path.tiled").value == before + 1
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * want.max())
