"""The work count against the two instances' hand-computed numbers."""
import pytest

from bench import work

V5E = work.peaks_for("TPU v5 lite")


def test_pollenus_hr_lb_work_and_roofline():
    n, grid, Hs, Ht = 588189, (651, 301, 84), 10, 3
    # 441 disk values x 10 + 7 bar values x 5 + 441 x 7 multiply-adds x 2
    assert work.point_flops(Hs, Ht) == 441 * 10 + 7 * 5 + 441 * 7 * 2
    assert work.build_flops(n, Hs, Ht) == pytest.approx(6.246e9, rel=1e-3)
    assert work.build_bytes(n, grid) == pytest.approx(72.9e6, rel=1e-3)
    t, bound = work.least_time(work.build_flops(n, Hs, Ht),
                               work.build_bytes(n, grid), V5E, 1)
    assert bound == "memory"
    assert t == pytest.approx(89e-6, rel=0.01)
    assert work.build_flops(n, Hs, Ht) / V5E.flops == pytest.approx(
        32e-6, rel=0.01)


def test_flu_mr_hb_on_four_chips_is_bound_by_bytes():
    n, grid, Hs, Ht = 31478, (233, 615, 1985), 4, 7
    assert work.build_bytes(n, grid) == pytest.approx(1.138e9, rel=1e-3)
    t, bound = work.least_time(work.build_flops(n, Hs, Ht),
                               work.build_bytes(n, grid), V5E, 4)
    assert bound == "memory"
    assert t == pytest.approx(0.347e-3, rel=0.01)


def test_flu_mr_hb_on_one_chip_is_bound_by_bytes():
    n, grid, Hs, Ht = 31478, (233, 615, 1985), 4, 7
    t, bound = work.least_time(work.build_flops(n, Hs, Ht),
                               work.build_bytes(n, grid), V5E, 1)
    assert bound == "memory"
    assert t == pytest.approx(1.389e-3, rel=0.01)


def test_pollenus_hr_lb_on_four_chips_is_a_quarter_of_one():
    n, grid, Hs, Ht = 588189, (651, 301, 84), 10, 3
    t, bound = work.least_time(work.build_flops(n, Hs, Ht),
                               work.build_bytes(n, grid), V5E, 4)
    assert bound == "memory"
    assert t == pytest.approx(89e-6 / 4, rel=0.01)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        work.peaks_for("TPU v99")
