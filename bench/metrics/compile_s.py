"""Host seconds per build spent tracing, lowering and compiling programs
inside the window, from ``jax.monitoring`` (layer: compile). The backend
compile event includes a persistent-cache retrieval, so a program served
from the cache counts what fetching it cost."""
from bench.harness import COMPILE_EVENTS


def read(rec):
    if rec.builds == 0:
        return None
    return sum(s for e, s in rec.jax_events if e in COMPILE_EVENTS) / (
        rec.builds)
