"""Share of the padded bucket slots that hold a point copy, in %, over the
window's overlap bucketing (layer: host bucketing). Each
``bucketing.overlap`` span carries the events bucketed (``n``), the copies
per event (``replication``), the tile grid (``tiles``, "AxBxC") and the
slots per tile (``cap``); the fill is the copies over tiles times
``cap``, summed over the spans (the program rounds ``replication`` to
three decimals, so copies are read to within n / 2000). The padding is
what the host lays out and the device then computes on, so a low fill
moves ``build_s``."""
import math


def read(rec):
    copies = slots = 0.0
    for s in rec.spans:
        if s.name != "bucketing.overlap" or "cap" not in s.attrs:
            continue
        a = s.attrs
        copies += a["n"] * a["replication"]
        slots += math.prod(int(t) for t in str(a["tiles"]).split("x")) * (
            a["cap"])
    return 100.0 * copies / slots if slots > 0 else None
