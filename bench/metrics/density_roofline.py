"""Density compute's share of its roofline, in %: the least time the
cell's chips need for a build's useful work (``bench/work.py``: PB-SYM's
operations, the float32 grid written once and the points read once, at
the chip's peaks) over the density compute's device seconds per build on
the busiest chip (layer: density compute)."""
from bench import work


def read(rec):
    if rec.trace is None or rec.builds == 0 or rec.peaks is None:
        return None
    busiest = max(d.density_s() for d in rec.trace.devices) / rec.builds
    if busiest <= 0:
        return None
    t_min, _ = work.least_time(rec.flops, rec.bytes, rec.peaks, rec.chips)
    return 100.0 * t_min / busiest
