"""Host seconds per build in a strategy's ``stkde.<s>.dispatch`` span:
building its ``jax.jit`` and calling it, so the retrace, lowering, cache
fetch and enqueue (layer: compile / dispatch)."""
from bench.spans import seconds_per_build


def read(rec):
    return seconds_per_build(rec, r"stkde\.[a-z_]+\.dispatch")
