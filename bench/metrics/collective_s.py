"""Device seconds per build in which a collective (all-reduce, all-gather,
all-to-all, collective-permute, reduce-scatter) was in flight, on the
busiest chip (layer: collectives)."""


def read(rec):
    if rec.trace is None or rec.builds == 0:
        return None
    return max(d.collective_s() for d in rec.trace.devices) / rec.builds
