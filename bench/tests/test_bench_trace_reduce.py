"""The trace reduction on synthetic event lists."""
import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps_and_touching():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert tr.total([(0, 1), (0.5, 2), (5, 6)]) == pytest.approx(3.0)


def test_gaps_cover_the_rest_of_the_window():
    busy = [(1, 2), (1.5, 3), (5, 6), (9, 12)]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (3, 5), (6, 9)]
    idle = sum(b - a for a, b in tr.gaps(busy, 0, 10))
    assert idle + tr.total(tr.clip(busy, 0, 10)) == pytest.approx(10)


def test_self_times_subtract_nested_ops():
    ev = [("while", 0, 10), ("fusion", 1, 3), ("sort", 4, 8),
          ("inner", 5, 6), ("copy", 12, 13)]
    st = tr.self_times(ev)
    assert st == pytest.approx({"while": 4, "fusion": 2, "sort": 3,
                                "inner": 1, "copy": 1})
    assert sum(st.values()) == pytest.approx(tr.total(
        [(a, b) for _, a, b in ev]))


def test_gap_attribution_takes_the_innermost_host_span():
    spans = [("bench.build", 0, 10), ("stkde.dd", 1, 6),
             ("stkde.dd.bucket", 1, 3)]
    got = tr.attribute_gaps([(1.5, 2.5), (4, 5), (7, 9), (11, 12)], spans)
    assert got == pytest.approx({"stkde.dd.bucket": 1, "stkde.dd": 1,
                                 "bench.build": 2, "(no span)": 1})
    # a gap across several spans is split between them
    got = tr.attribute_gaps([(2, 8)], spans)
    assert got == pytest.approx({"stkde.dd.bucket": 1, "stkde.dd": 3,
                                 "bench.build": 2})


def test_names_of_ops_and_programs():
    text = ("%all-gather.3 = f32[4,8]{1,0} all-gather(f32[1,8]{1,0} %p), "
            "replica_groups={{0,1,2,3}}")
    assert tr.op_name(text) == "all-gather.3"
    assert tr.opcode(text) == "all-gather"
    assert tr.is_collective(text)
    assert tr.is_collective("%collective-permute-start.1 = (f32[2]) "
                            "collective-permute-start(f32[2] %x)")
    assert not tr.is_collective("%fusion.2 = f32[8] fusion(f32[8] %a), "
                                "kind=kLoop, calls=%all-gather-like")
    assert tr.program_name("jit__pb_impl(5883991455540174240)") == "_pb_impl"
    assert tr.is_layout_program("jit_reshape(12)")
    assert not tr.is_layout_program("jit_f(151)")


def _device():
    progs = [("jit_f(1)", 0.0, 4.0), ("jit_reshape(2)", 5.0, 6.0),
             ("jit_f(1)", 8.0, 9.0)]
    ops = [("%while.1 = f32[] while(f32[] %a)", 0.0, 3.0),
           ("%scatter.1 = f32[9] scatter(f32[9] %g)", 0.5, 2.5),
           ("%all-reduce.1 = f32[9] all-reduce(f32[9] %g)", 3.0, 4.0),
           ("%copy.1 = f32[9] copy(f32[9] %g)", 5.0, 6.0),
           ("%fusion.1 = f32[9] fusion(f32[9] %g)", 8.0, 9.0)]
    aops = [("%all-gather-start.1 = (f32[9]) all-gather-start(f32[2] %x)",
             5.5, 7.0)]
    return tr.Device("/device:TPU:0", ops, aops, progs)


def test_device_busy_density_and_collectives():
    d = _device()
    assert d.busy_s() == pytest.approx(6.0)
    assert d.density_s() == pytest.approx(5.0)     # not the reshape program
    assert d.collective_s() == pytest.approx(2.5)  # 3-4 and 5.5-7
    own = d.op_self_s()
    assert own["f/while.1"] == pytest.approx(1.0)
    assert own["reshape/copy.1"] == pytest.approx(1.0)


def test_trace_idle_share_breakdown_and_gaps():
    t = tr.Trace([_device(), _device()],
                 [("bench.build", 0, 5), ("bench.build", 5, 10),
                  ("stkde.dd.bucket", 6.0, 8.0)], 0.0, 10.0)
    assert t.window_s == 10.0
    assert t.busy_s() == pytest.approx(6.0)
    assert 1 - t.busy_s() / t.window_s == pytest.approx(0.4)
    ops = dict(t.device_ops())
    assert sum(ops.values()) == pytest.approx(6.0)
    assert t.idle_gaps() == [["bench.build", pytest.approx(2.0)],
                             ["stkde.dd.bucket", pytest.approx(2.0)]]
