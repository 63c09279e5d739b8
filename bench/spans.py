"""Host seconds per build in a set of the program's spans, for the readers
of ``bench/metrics/`` whose source is ``program_span``."""
from __future__ import annotations

import re
from typing import Optional


def seconds_per_build(rec, pattern: str) -> Optional[float]:
    """Seconds per build in the spans whose name matches ``pattern``, a
    span nested in another of the set counted once; None when the window
    holds no such span."""
    name = re.compile(pattern)
    by_id = {s.span_id: s for s in rec.spans}
    ours = {s.span_id for s in rec.spans if name.fullmatch(s.name)}
    if rec.builds == 0 or not ours:
        return None

    def outermost(s):
        p = s.parent_id
        while p is not None:
            if p in ours:
                return False
            p = by_id[p].parent_id if p in by_id else None
        return True

    return sum(s.duration_s for s in rec.spans
               if s.span_id in ours and outermost(s)) / rec.builds
