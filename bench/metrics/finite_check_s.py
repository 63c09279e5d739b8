"""Host seconds per build in ``stkde.api.check_finite``: the grid's copy
to the host and the NumPy scan for NaN and Inf (layer: output check)."""
from bench.spans import seconds_per_build


def read(rec):
    return seconds_per_build(rec, r"stkde\.api\.check_finite")
