"""Observability layer: spans, Chrome export, metrics."""
import json

import numpy as np
import pytest

from repro.obs import metrics, trace


# ------------------------------------------------------------------ trace
def test_span_nesting_and_attrs():
    tr = trace.Tracer()
    with tr.span("outer", a=1) as outer:
        with tr.span("inner") as inner:
            inner.set(found=3)
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner"}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].attrs == {"a": 1}
    assert spans["inner"].attrs == {"found": 3}
    # inner closed first and fits inside outer
    assert spans["inner"].duration_ns <= spans["outer"].duration_ns
    assert spans["inner"].start_ns >= spans["outer"].start_ns


def test_retained_spans_stop_at_the_cap_and_count_the_dropped():
    tr = trace.Tracer()
    for i in range(trace.MAX_SPANS + 10):
        with tr.span(f"s{i}"):
            pass
    kept = tr.spans()
    assert len(kept) == trace.MAX_SPANS == 65_536 and tr.dropped == 10
    assert kept[0].name == "s10" and kept[-1].name == f"s{trace.MAX_SPANS + 9}"
    assert len(tr.to_chrome_trace()["traceEvents"]) == trace.MAX_SPANS
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_global_span_helper_records():
    with trace.span("unit.test", k="v") as sp:
        pass
    assert sp.duration_s >= 0
    assert trace.get_tracer().spans("unit.test")


def test_chrome_trace_schema(tmp_path):
    tr = trace.Tracer()
    with tr.span("phase", n=7, arr=np.arange(2)):
        pass
    doc = tr.to_chrome_trace()
    # must round-trip through json (numpy attrs coerced to strings)
    doc2 = json.loads(json.dumps(doc))
    assert doc2["displayTimeUnit"] == "ms"
    (ev,) = doc2["traceEvents"]
    assert ev["ph"] == "X"
    assert ev["name"] == "phase"
    for key in ("ts", "dur", "pid", "tid", "args"):
        assert key in ev
    assert ev["dur"] >= 0
    assert ev["args"]["n"] == 7
    p = tmp_path / "trace.json"
    tr.save(str(p))
    assert json.loads(p.read_text())["traceEvents"]


def test_ingest_foreign_events():
    tr = trace.Tracer()
    tr.ingest([{"name": "child", "ph": "X", "ts": 1.0, "dur": 2.0,
                "pid": 0, "tid": 0, "args": {}}], pid=42)
    evs = tr.to_chrome_trace()["traceEvents"]
    assert evs[0]["pid"] == 42


# ---------------------------------------------------------------- metrics
def test_counter_and_gauge():
    metrics.counter("t.c").inc()
    metrics.counter("t.c").inc(2)
    metrics.gauge("t.g").set(1.5)
    d = metrics.export()
    assert d["counters"]["t.c"] == 3
    assert d["gauges"]["t.g"] == 1.5


def test_histogram_percentiles():
    h = metrics.Histogram()
    for v in range(1, 101):          # 1..100
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    # log buckets give ~4% relative resolution
    assert abs(s["p50"] - 50) / 50 < 0.10
    assert abs(s["p95"] - 95) / 95 < 0.10
    assert abs(s["p99"] - 99) / 99 < 0.10
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_nonpositive_and_empty():
    h = metrics.Histogram()
    assert np.isnan(h.percentile(0.5))
    h.observe(0.0)
    h.observe(-1.0)
    assert h.count == 2
    assert h.percentile(0.5) == -1.0  # underflow bucket reports min


def test_registry_merge_cross_process_shape():
    r = metrics.Registry()
    h = r.histogram("x_s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    r.counter("n").inc(5)
    r2 = metrics.Registry()
    r2.merge(json.loads(json.dumps(r.to_dict())))
    d = r2.to_dict()
    assert d["counters"]["n"] == 5
    assert d["histograms"]["x_s"]["count"] == 3
    assert d["histograms"]["x_s"]["min"] == pytest.approx(0.1)


def test_registry_reset_between_tests_a():
    # with the autouse fixture, this name must not exist yet
    assert "leak.probe" not in metrics.get_registry().names()
    metrics.counter("leak.probe").inc()


def test_registry_reset_between_tests_b():
    # ordering with _a doesn't matter: neither test may see the other's state
    assert "leak.probe" not in metrics.get_registry().names()
    metrics.counter("leak.probe").inc()
