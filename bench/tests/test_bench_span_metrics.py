"""The readers of ``api_s``, ``dispatch_s`` and ``finite_check_s``: seconds
per build in their spans, a span nested in another of its set counted
once, and nothing where the program opens no such span."""
import pathlib

import pytest

from bench import harness

REPO = pathlib.Path(__file__).resolve().parents[2]

# (metric, a span it reads, another it reads that can nest in the first)
CASES = [
    ("api_s", "stkde.api.plan", "stkde.api.validate"),
    ("dispatch_s", "stkde.hybrid.dispatch", "stkde.pd.dispatch"),
    ("finite_check_s", "stkde.api.check_finite", "stkde.api.check_finite"),
]


def span(name, sid, parent, secs):
    return harness.ProgramSpan(name=name, start_s=0.0, duration_s=secs,
                               span_id=sid, parent_id=parent, attrs={})


def record(spans, builds=2):
    return harness.Record(cfg={}, chips=1, builds=builds, trace=None,
                          spans=spans, jax_events=[], peaks=None, flops=0,
                          bytes=0)


@pytest.mark.parametrize("metric,outer,inner", CASES)
def test_reader_sums_per_build_and_counts_nesting_once(metric, outer, inner):
    read = harness.load_metric(REPO, metric)
    spans = [
        span("stkde", 1, None, 10.0),            # build 1
        span(outer, 2, 1, 1.0),
        span(inner, 3, 2, 0.5),                  # inside outer: not again
        span("stkde.dd.bucket", 4, 1, 0.7),      # another layer's span
        span("stkde", 5, None, 10.0),            # build 2
        span(inner, 6, 5, 0.6),
    ]
    assert read(record(spans)) == pytest.approx((1.0 + 0.6) / 2)
    assert read(record(spans, builds=4)) == pytest.approx((1.0 + 0.6) / 4)


@pytest.mark.parametrize("metric", [c[0] for c in CASES])
def test_reader_reads_nothing_without_its_spans(metric):
    read = harness.load_metric(REPO, metric)
    # a program without these spans, as the commit before them
    others = [span("bucketing.overlap", 1, None, 0.2),
              span("stkde.dd", 2, None, 1.0)]
    assert read(record(others)) is None
    assert read(record([])) is None
    assert read(record([span(CASES[0][1], 1, None, 1.0)], builds=0)) is None


def test_readers_find_the_spans_of_a_real_build():
    from repro.core import Domain, clustered_events
    from repro.core.api import stkde
    from repro.obs import trace

    dom = Domain(gx=20., gy=18., gt=8., sres=1., tres=1., hs=2., ht=1.)
    stkde(clustered_events(200, dom, seed=4), dom)
    spans = [harness.ProgramSpan(s.name, s.start_ns / 1e9, s.duration_s,
                                 s.span_id, s.parent_id, dict(s.attrs))
             for s in trace.get_tracer().spans()]
    for metric, _, _ in CASES:
        value = harness.load_metric(REPO, metric)(record(spans, builds=1))
        assert value is not None and value > 0, metric


def overlap(sid, parent, n, tiles, cap, replication):
    s = span("bucketing.overlap", sid, parent, 0.1)
    s.attrs.update(n=n, tiles=tiles, cap=cap, replication=replication)
    return s


def test_bucket_fill_sums_copies_over_padded_slots():
    read = harness.load_metric(REPO, "bucket_fill")
    spans = [
        span("stkde", 1, None, 1.0),
        span("stkde.tiled.bucket", 2, 1, 0.5),
        overlap(3, 2, n=1000, tiles="2x3x1", cap=400, replication=1.5),
        span("bucketing.home", 4, 1, 0.1),       # home buckets: not read
        span("stkde", 5, None, 1.0),
        overlap(6, 5, n=1000, tiles="2x2x1", cap=500, replication=1.2),
    ]
    # (1500 + 1200) copies in (6 * 400 + 4 * 500) slots
    assert read(record(spans)) == pytest.approx(100 * 2700 / 4400)
    assert read(record(spans[3:5])) is None
    # a span closed before its attributes were set is not read
    assert read(record([span("bucketing.overlap", 1, None, 0.1)])) is None


def test_bucket_fill_and_bucket_s_read_a_real_overlap_bucketing():
    from repro.core import Domain, bucketing, clustered_events
    from repro.obs import trace

    dom = Domain(gx=40., gy=36., gt=24., sres=1., tres=1., hs=3., ht=2.)
    pts = clustered_events(2000, dom, seed=5)
    trace.reset()
    with trace.span("stkde.tiled.bucket"):
        b = bucketing.bucket_points_overlap(pts, dom, (8, 8, 8))
    spans = [harness.ProgramSpan(s.name, s.start_ns / 1e9, s.duration_s,
                                 s.span_id, s.parent_id, dict(s.attrs))
             for s in trace.get_tracer().spans()]
    want = 100 * b.counts.sum() / (b.counts.size * b.cap)
    fill = harness.load_metric(REPO, "bucket_fill")(record(spans, builds=1))
    assert 0 < fill < 100
    assert fill == pytest.approx(want, rel=1e-3)
    # the overlap span nests in the strategy's bucket span: counted once
    outer = [s for s in spans if s.name == "stkde.tiled.bucket"][0]
    bucket_s = harness.load_metric(REPO, "bucket_s")
    assert bucket_s(record(spans, builds=2)) == pytest.approx(
        outer.duration_s / 2)
