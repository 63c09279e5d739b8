"""Device seconds of density compute per build, on the busiest chip
(layer: density compute). Density compute is every op that runs in a
program other than a pure data-movement one (``trace_reduce``'s
``LAYOUT_PROGRAMS``): the scatter of ``core/pb.py``, a tile kernel, or a
mesh strategy's local compute."""


def read(rec):
    if rec.trace is None or rec.builds == 0:
        return None
    busiest = max(d.density_s() for d in rec.trace.devices)
    return busiest / rec.builds if busiest > 0 else None
