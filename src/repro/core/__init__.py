"""Core STKDE: the paper's contribution as composable JAX modules."""
from .geometry import Domain, from_points
from . import kernels_math
from .vb import vb, vb_dec
from .pb import pb, pb_sym, VARIANTS
from . import bucketing
from .datasets import (
    STKDEInstance,
    INSTANCES,
    get_instance,
    clustered_events,
)
# keep last: api pulls in resilience, which imports core.geometry above
from .api import ChunkedResult, stkde, stkde_chunked

__all__ = [
    "ChunkedResult",
    "stkde",
    "stkde_chunked",
    "Domain",
    "from_points",
    "kernels_math",
    "vb",
    "vb_dec",
    "pb",
    "pb_sym",
    "VARIANTS",
    "bucketing",
    "STKDEInstance",
    "INSTANCES",
    "get_instance",
    "clustered_events",
]
