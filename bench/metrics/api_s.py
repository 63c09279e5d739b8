"""Host seconds per build in the API layer ahead of the strategy: input
validation (``stkde.api.validate``) and the strategy choice with its load
count (``stkde.api.plan``) (layer: API, validation, strategy choice)."""
from bench.spans import seconds_per_build


def read(rec):
    return seconds_per_build(rec, r"stkde\.api\.(validate|plan)")
