"""Host seconds per build in the program's point bucketing (layer: host
bucketing): its ``stkde.<strategy>.bucket`` spans and the ``bucketing.*``
spans that open outside them, such as the planner's load count, each
counted once."""
from bench.spans import seconds_per_build


def read(rec):
    return seconds_per_build(
        rec, r"stkde\.[a-z_]+\.bucket|bucketing\.(home|overlap)")
