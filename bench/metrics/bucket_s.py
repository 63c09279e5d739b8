"""Host seconds per build in the program's point bucketing (layer: host
bucketing): its ``stkde.<strategy>.bucket`` spans and the ``bucketing.*``
spans that open outside them, such as the planner's load count, each
counted once."""
import re

_BUCKET = re.compile(r"stkde\.[a-z_]+\.bucket|bucketing\.(home|overlap)")


def read(rec):
    if rec.builds == 0:
        return None
    by_id = {s.span_id: s for s in rec.spans}
    ours = {s.span_id for s in rec.spans if _BUCKET.fullmatch(s.name)}

    def outermost(s):
        p = s.parent_id
        while p is not None:
            if p in ours:
                return False
            p = by_id[p].parent_id if p in by_id else None
        return True

    total = sum(s.duration_s for s in rec.spans
                if s.span_id in ours and outermost(s))
    return total / rec.builds if ours else None
