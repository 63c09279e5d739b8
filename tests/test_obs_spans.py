"""The span tree of one ``stkde()`` build: a root ``stkde``, the API phases,
and every strategy's ``bucket`` / ``dispatch`` / ``reassemble`` phases."""
import importlib.util
import json
import pathlib
import textwrap
import types

import numpy as np
import pytest

from repro.core import Domain, clustered_events
from repro.core.api import stkde
from repro.obs import trace
from util_subproc import run_with_devices

STRATEGIES = ("dr", "dd", "pd", "pd_xt", "pd_xyt", "dd_lpt", "hybrid")
NO_REASSEMBLY = {"pb", "dr"}   # each returns the whole grid as it is

TREES = textwrap.dedent(
    """
    import json
    import jax
    from jax.sharding import AxisType
    from repro.core import Domain, clustered_events
    from repro.core.api import stkde
    from repro.obs import trace

    dom = Domain(gx=48., gy=40., gt=20., sres=1., tres=1., hs=3., ht=2.)
    pts = clustered_events(1500, dom, seed=5)
    mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(AxisType.Auto,) * 3)
    calls = {"pb": dict(), "pb_forced": dict(use_tiled_kernel=False),
             "auto": dict(mesh=mesh2)}
    for s in ("dr", "dd", "pd", "pd_xt", "dd_lpt"):
        calls[s] = dict(mesh=mesh2, strategy=s)
    for s in ("hybrid", "pd_xyt"):
        calls[s] = dict(mesh=mesh3, strategy=s)
    trees = {}
    for case, kw in calls.items():
        trace.reset()
        stkde(pts, dom, fallback=False, **kw)
        trees[case] = [
            dict(name=s.name, id=s.span_id, parent=s.parent_id,
                 start=s.start_ns, attrs=s.attrs)
            for s in trace.get_tracer().spans()]
    print(json.dumps(trees))
    """
)


@pytest.fixture(scope="module")
def trees():
    out = run_with_devices(TREES, 8)
    return json.loads(out.strip().splitlines()[-1])


def _ancestors(by_id, sp):
    out = []
    while sp["parent"] is not None:
        sp = by_id[sp["parent"]]
        out.append(sp["id"])
    return out


def _children(spans, parent_id):
    return [s["name"] for s in sorted(spans, key=lambda s: s["start"])
            if s["parent"] == parent_id]


@pytest.mark.parametrize("case", ("pb", "pb_forced", "auto") + STRATEGIES)
def test_build_span_tree(trees, case):
    spans = trees[case]
    by_id = {s["id"]: s for s in spans}
    (root,) = [s for s in spans if s["parent"] is None]
    assert root["name"] == "stkde"
    s = root["attrs"]["path"]
    assert s == {"pb_forced": "pb"}.get(case, case) or (
        case == "auto" and s in STRATEGIES)
    assert root["attrs"]["n"] == 1500
    assert root["attrs"]["voxels"] == 48 * 40 * 20

    # every span reaches the root by parent_id
    for sp in spans:
        assert sp is root or _ancestors(by_id, sp)[-1] == root["id"]

    # a planner runs where the caller left the choice to the program
    plan = ["stkde.api.plan"] if case in ("pb", "auto") else []
    assert _children(spans, root["id"]) == (
        ["stkde.api.validate"] + plan
        + [f"stkde.{s}", "stkde.api.wait", "stkde.api.check_finite"])
    (strat,) = [sp for sp in spans if sp["name"] == f"stkde.{s}"]
    phases = [f"stkde.{s}.bucket", f"stkde.{s}.dispatch"]
    if s not in NO_REASSEMBLY:
        phases.append(f"stkde.{s}.reassemble")
    assert _children(spans, strat["id"]) == phases

    # the points' transfer happens inside the bucketing phase
    (bucket,) = [sp for sp in spans if sp["name"] == f"stkde.{s}.bucket"]
    transfers = [sp for sp in spans if sp["name"] == "transfer.to_device"]
    assert transfers
    for t in transfers:
        assert t["attrs"]["bytes"] > 0
        assert bucket["id"] in _ancestors(by_id, t)
    if case == "auto":
        (pl,) = [sp for sp in spans if sp["name"] == "stkde.api.plan"]
        assert "bucketing.home" in _children(spans, pl["id"])
    # the single-device planner prices both paths from the call's shape
    # alone: no bucketing under its span, and its prices on the root
    priced = {"priced_pb_s", "priced_tiled_s"}
    if case == "pb":
        (pl,) = [sp for sp in spans if sp["name"] == "stkde.api.plan"]
        assert _children(spans, pl["id"]) == []
        assert priced <= set(root["attrs"])
        assert root["attrs"]["priced_pb_s"] > 0
    else:
        assert not priced & set(root["attrs"])
    (check,) = [sp for sp in spans if sp["name"] == "stkde.api.check_finite"]
    assert check["attrs"]["bytes"] == 48 * 40 * 20 * 4
    # a first call compiles inside its dispatch phase (pb_forced repeats
    # the pb call, which compiled already)
    (disp,) = [sp for sp in spans if sp["name"] == f"stkde.{s}.dispatch"]
    if case != "pb_forced":
        assert disp["attrs"]["compiles"] >= 1
        assert disp["attrs"]["compile_s"] > 0


def test_dispatch_counts_compiles_of_the_first_call_only():
    # a domain no other test uses, so the first call is a fresh compile
    dom = Domain(gx=21., gy=19., gt=7., sres=1., tres=1., hs=2., ht=1.)
    pts = clustered_events(300, dom, seed=11)
    tr = trace.get_tracer()

    def dispatch_attrs():
        (sp,) = tr.spans("stkde.pb.dispatch")
        return sp.attrs

    first = stkde(pts, dom)
    attrs = dispatch_attrs()
    assert attrs["compiles"] >= 1 and attrs["compile_s"] > 0
    trace.reset()
    again = stkde(pts, dom)
    assert "compiles" not in dispatch_attrs()
    assert "compile_s" not in dispatch_attrs()
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))


def test_rejected_input_closes_the_tree_at_validation():
    import dataclasses

    from repro.resilience.errors import ReproValidationError

    dom = Domain(gx=20., gy=18., gt=8., sres=1., tres=1., hs=2., ht=1.)
    pts = clustered_events(50, dom, seed=1)
    with pytest.raises(ReproValidationError):
        stkde(pts, dataclasses.replace(dom, sres=0.0))
    spans = trace.get_tracer().spans()
    assert sorted(s.name for s in spans) == ["stkde", "stkde.api.validate"]
    (root,) = [s for s in spans if s.parent_id is None]
    assert root.name == "stkde" and root.attrs == {}


def test_tiled_kernel_opens_the_same_phases():
    dom = Domain(gx=24., gy=20., gt=10., sres=1., tres=1., hs=2., ht=1.)
    pts = clustered_events(200, dom, seed=3)
    from repro.kernels import default_tile, stkde_tiled

    stkde_tiled(pts, dom, mode="interpret")
    spans = trace.get_tracer().spans()
    (root,) = [s for s in spans if s.name == "stkde.tiled"]
    kids = sorted((s for s in spans if s.parent_id == root.span_id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == [
        "stkde.tiled.bucket", "stkde.tiled.dispatch",
        "stkde.tiled.reassemble"]
    (t,) = trace.get_tracer().spans("transfer.to_device")
    assert t.parent_id == kids[0].span_id and t.attrs["bytes"] > 0

    # the overlap bucketing names the panel layout: ``cap`` is the slots
    # laid out per tile, so the benchmark's fill reads copies over the
    # slots the kernel computes on
    from repro.core import bucketing
    from repro.kernels.stkde_tile import CHUNK

    (ov,) = trace.get_tracer().spans("bucketing.overlap")
    assert ov.parent_id == kids[0].span_id
    tile = default_tile(dom)
    counts = bucketing.bucket_points_overlap(pts, dom, tile).counts
    raw = int(np.maximum(1, -(-counts // CHUNK)).sum())
    panels = bucketing.round_up(raw, bucketing.PANEL_STEP)
    assert ov.attrs["panels"] == panels
    assert ov.attrs["tiles"] == "x".join(map(str, counts.shape))
    assert ov.attrs["cap"] == panels * CHUNK / counts.size
    assert ov.attrs["n"] == 200
    assert ov.attrs["replication"] == round(counts.sum() / 200, 3)
    rec = types.SimpleNamespace(spans=[ov])
    assert _bucket_fill()(rec) == pytest.approx(
        100 * counts.sum() / (panels * CHUNK), rel=1e-3)


def _bucket_fill():
    """The benchmark's ``bucket_fill`` reader (bench/metrics/)."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "metrics" / "bucket_fill.py")
    spec = importlib.util.spec_from_file_location("bucket_fill", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
